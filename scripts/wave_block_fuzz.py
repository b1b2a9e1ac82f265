"""Randomized equivalence fuzz: _solve_wave_block_impl vs the classic
compact kernel over synthetic compact tables. One process, few shapes
(compile reuse), many seeds. Meant for the CPU backend: run with
JAX_PLATFORMS=cpu."""
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import jax

import numpy as np
import jax.numpy as jnp
from functools import partial

from nomad_tpu.solver.binpack import (
    _solve_wave_block_impl, _solve_wave_compact_impl)

N_SEEDS = int(sys.argv[1]) if len(sys.argv) > 1 else 50
FAILS = 0


def make_case(rng, C, B):
    compact = np.zeros((C, 8), dtype=np.float32)
    compact[:, 7] = -1.0
    n_fit = rng.integers(0, C + 1)
    if n_fit:
        caps = rng.integers(1, 9, size=n_fit).astype(np.float32)
        cpu_cap = rng.choice([2000.0, 4000.0, 8000.0], size=n_fit)
        ask = float(rng.choice([250.0, 500.0, 1000.0]))
        c = np.minimum(caps, np.maximum(cpu_cap // ask, 1.0))
        compact[:n_fit, 0] = c
        compact[:n_fit, 1] = rng.integers(0, 3, size=n_fit) * ask
        compact[:n_fit, 2] = rng.integers(0, 3, size=n_fit) * 128.0
        compact[:n_fit, 3] = cpu_cap
        compact[:n_fit, 4] = cpu_cap * 2
        compact[:n_fit, 5] = rng.choice(
            [0.0, 0.0, 0.0, 1.0, 2.0, 50.0], size=n_fit)
        compact[:n_fit, 6] = rng.choice(
            [0.0, 0.0, 0.5, -0.25, 1.0, -1.0], size=n_fit)
        compact[:n_fit, 7] = rng.permutation(C)[:n_fit].astype(np.float32)
    else:
        ask = 500.0
    # occasionally crush scores negative via huge prior collisions and a
    # tiny count so the skip/fallback machinery engages
    count = float(rng.choice([1.0, 4.0, 30.0, 2000.0]))
    scal_f = np.array([ask, 128.0, count], dtype=np.float32)
    return compact, scal_f


for (C_P, B, K, L) in ((40, 8, 4, 5), (160, 32, 32, 14),
                       (96, 32, 8, 3), (360, 128, 32, 100)):
    P = C_P - B
    classic = jax.jit(partial(_solve_wave_compact_impl, sp=None,
                              spread_alg=False, dtype_name="float32",
                              B=B))
    block = jax.jit(partial(_solve_wave_block_impl, spread_alg=False,
                            dtype_name="float32", B=B, K=K))
    shape_fail = 0
    for seed in range(N_SEEDS):
        rng = np.random.default_rng(seed * 7919 + C_P)
        compact, scal_f = make_case(rng, C_P, B)
        n_active = int(rng.integers(1, P + 1))
        scal_i = np.array([L, n_active], dtype=np.int32)
        pen = np.full(P, -1, dtype=np.int32)
        c0 = [np.asarray(x) for x in classic(compact, scal_f, scal_i, pen)]
        c1 = [np.asarray(x) for x in block(compact, scal_f, scal_i, pen)]
        bad = [int((a != b).sum()) for a, b in zip(c0, c1)]
        if any(bad):
            FAILS += 1
            shape_fail += 1
            if shape_fail <= 2:
                print(f"FAIL shape=(P={P},B={B},K={K},L={L}) seed={seed} "
                      f"n_active={n_active} mism={bad}")
                names = ("chosen", "scores", "ny")
                for nm, a, b in zip(names, c0, c1):
                    idx = np.nonzero(a != b)[0][:6]
                    if len(idx):
                        print(f"  {nm} idx={idx}\n    classic={a[idx]}"
                              f"\n    block  ={b[idx]}")
    print(f"shape (P={P},B={B},K={K},L={L}): "
          f"{N_SEEDS - shape_fail}/{N_SEEDS} seeds exact", flush=True)
print("TOTAL FAILS:", FAILS)
sys.exit(1 if FAILS else 0)
