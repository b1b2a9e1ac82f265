"""Tiny-shape debug driver for _solve_wave_block_impl vs the classic
compact kernel: synthetic compact tables, fast compiles. Meant for the
CPU backend: run with JAX_PLATFORMS=cpu."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

import numpy as np
import jax.numpy as jnp

from nomad_tpu.solver.binpack import (
    _solve_wave_block_impl, _solve_wave_compact_impl)

B, K = 8, 4
P = int(sys.argv[1]) if len(sys.argv) > 1 else 12
C = P + B
rng = np.random.default_rng(int(sys.argv[2]) if len(sys.argv) > 2 else 0)

# columns: c, used_cpu, used_mem, cpu_cap, mem_cap, placed, aff, pos
n_fit = int(sys.argv[3]) if len(sys.argv) > 3 else C
compact = np.zeros((C, 8), dtype=np.float32)
compact[:, 7] = -1.0
caps = rng.integers(1, 5, size=n_fit)
cpu_cap = rng.choice([2000.0, 4000.0, 8000.0], size=n_fit)
ask = 500.0
compact[:n_fit, 0] = np.minimum(caps, (cpu_cap // ask))
compact[:n_fit, 1] = rng.integers(0, 2, size=n_fit) * 500.0
compact[:n_fit, 2] = rng.integers(0, 2, size=n_fit) * 256.0
compact[:n_fit, 3] = cpu_cap
compact[:n_fit, 4] = cpu_cap * 2
compact[:n_fit, 5] = rng.integers(0, 3, size=n_fit).astype(np.float32)
compact[:n_fit, 6] = rng.choice([0.0, 0.0, 0.5, -0.25], size=n_fit)
compact[:n_fit, 7] = np.arange(n_fit, dtype=np.float32)
compact[:n_fit, 0] = np.maximum(compact[:n_fit, 0], 1)

scal_f = np.array([ask, 256.0, float(P)], dtype=np.float32)
L = int(sys.argv[4]) if len(sys.argv) > 4 else 5
n_active = P
scal_i = np.array([L, n_active], dtype=np.int32)
pen = np.full(P, -1, dtype=np.int32)

classic = jax.jit(lambda *a: _solve_wave_compact_impl(
    *a, sp=None, spread_alg=False, dtype_name="float32", B=B))
block = jax.jit(lambda *a: _solve_wave_block_impl(
    *a, spread_alg=False, dtype_name="float32", B=B, K=K))

c0 = [np.asarray(x) for x in classic(compact, scal_f, scal_i, pen)]
print("classic done", flush=True)
c1 = [np.asarray(x) for x in block(compact, scal_f, scal_i, pen)]
print("block done", flush=True)
names = ("chosen", "scores", "ny")
ok = True
for nm, a, b in zip(names, c0, c1):
    n = int((a != b).sum())
    if n:
        ok = False
        bad = np.nonzero(a != b)[0][:8]
        print(f"{nm}: {n} mismatches at {bad}")
        print("  classic", a[bad])
        print("  block  ", b[bad])
print("PARITY OK" if ok else "PARITY FAIL")
print("chosen classic", c0[0][:16])
print("chosen block  ", c1[0][:16])
