"""Mesh-shape parity grid (ISSUE 19): every viable 8-device (evals,
nodes) grid must produce BIT-IDENTICAL results to the single-device
programs for BOTH production kernels -- the fused greedy dense solve
(solve_eval_batch via mesh_solve_fn) and the LPQ relaxation
(_lp_solve_body via mesh_lpq_fn).

The module runs under the sharding-discipline sanitizer AND the
dispatch-discipline sanitizer simultaneously (conftest
_SHARDCHECK_SUITES + _JITCHECK_SUITES, HLO audit ON), and each case
asserts the full zero-violation contract in-test: zero spec drift,
zero implicit transfers, zero collective-budget excess, zero per-shard
byte-parity breaks, plus zero retraces / host syncs.

Why a grid and not one shape: the greedy's cross-shard ops (max/
argmax window selection) are order-insensitive, so ANY grid must be
bit-exact; the LPQ's dual-ascent combine is an all-gather precisely so
that node- and lane-sharding stay bit-exact too -- a regression that
re-associates either reduction (e.g. swapping the gather for a psum)
flips placements only on SOME grids, which is what this sweep exists
to catch.
"""
import functools
import os
import sys

import jax
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

# Import the kernel modules at collection time, BEFORE the sanitizer
# fixtures enable jitcheck: module-level jits constructed pre-enable
# stay raw (jitcheck's documented gap, same state tier-1 runs the
# whole suite in).  The programs under test here -- the REGISTERED
# mesh factories' jits -- are constructed inside the test window and
# are fully tracked; without this, the inner per-lane jit re-tracing
# under a second outer trace context (ref program vs mesh program)
# reads as a steady-state retrace, which no production dispatch path
# ever performs.
import nomad_tpu.solver.binpack   # noqa: F401,E402
import nomad_tpu.solver.lpq       # noqa: F401,E402

# every factorization of 8 devices: pure eval-parallel, both mixed
# grids, and pure node-parallel
GRID = [(8, 1), (4, 2), (2, 4), (1, 8)]


def _zero_violations(sh_state, jit_state):
    """The four shardcheck violation classes + both jitcheck classes."""
    assert sh_state["spec_drift"] == [], sh_state["spec_drift"]
    assert sh_state["implicit_xfers"] == [], sh_state["implicit_xfers"]
    assert sh_state["collective_excess"] == [], \
        sh_state["collective_excess"]
    assert sh_state["shard_parity_reports"] == [], \
        sh_state["shard_parity_reports"]
    assert jit_state["retraces"] == [], jit_state["retraces"]
    assert jit_state["host_syncs"] == [], jit_state["host_syncs"]


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the virtual 8-device mesh")
@pytest.mark.parametrize("e_par,n_par", GRID)
def test_greedy_mesh_shape_parity(e_par, n_par):
    """Fused greedy dense solve: bit-parity vs single-device on every
    grid, through the REGISTERED factories (the exact callables
    production dispatches; the sanitizer wrappers only engage on the
    module-attribute route)."""
    from nomad_tpu import jitcheck, shardcheck
    from nomad_tpu.parallel import mesh as meshmod
    from nomad_tpu.solver import xferobs
    from nomad_tpu.solver.binpack import solve_eval_batch
    import __graft_entry__ as graft

    xferobs._reset_for_tests()
    E, P, N = 8, 16, 256
    rng = np.random.default_rng(100 + e_par)
    lanes = [graft._varied_inputs(rng, N, P) for _ in range(E)]
    stack = lambda idx: jax.tree.map(
        lambda *xs: np.stack(xs), *[l[idx] for l in lanes])
    const, init, batch = stack(0), stack(1), stack(2)

    ref = jax.jit(
        functools.partial(solve_eval_batch, spread_alg=False,
                          dtype_name="float32"))(
        *jax.device_put((const, init, batch), jax.devices()[0]))
    ref_chosen, ref_scores = np.asarray(ref[0]), np.asarray(ref[1])

    mesh = meshmod.make_mesh(8, eval_parallel=e_par)
    assert mesh.devices.shape == (e_par, n_par)
    with mesh:
        s_const, s_init, s_batch = meshmod.shard_solver_inputs(
            mesh, const, init, batch)
        fn = meshmod.mesh_solve_fn(mesh, False, "float32")
        chosen, scores, n_yielded = fn(s_const, s_init, s_batch)

    np.testing.assert_array_equal(np.asarray(chosen), ref_chosen)
    np.testing.assert_array_equal(np.asarray(scores), ref_scores)
    np.testing.assert_array_equal(np.asarray(n_yielded),
                                  np.asarray(ref[2]))
    assert (ref_chosen >= 0).any()   # a world that places nothing
    #                                  would prove nothing

    assert xferobs.shard_parity() == 0
    _zero_violations(shardcheck.state(), jitcheck.state())
    xferobs._reset_for_tests()


def test_mesh_kill_switch(monkeypatch):
    """``NOMAD_TPU_MESH=0`` is a true kill switch: every mesh factory
    refuses a mesh (``pick_mesh`` -> None), so dispatch takes the
    single-device program path.  The bit-for-bit dispatch parity under
    the off position is the multichip dryrun's kill-switch check; this
    pins the gate the dispatch stack consults."""
    from nomad_tpu.parallel import mesh as meshmod

    monkeypatch.setenv("NOMAD_TPU_MESH", "0")
    assert not meshmod.mesh_enabled()
    assert meshmod.pick_mesh(8, 256) is None

    monkeypatch.setenv("NOMAD_TPU_MESH", "1")
    assert meshmod.mesh_enabled()
    monkeypatch.delenv("NOMAD_TPU_MESH")
    assert meshmod.mesh_enabled()   # on is the default


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs the virtual 8-device mesh")
@pytest.mark.parametrize("e_par,n_par", GRID)
def test_lpq_mesh_shape_parity(e_par, n_par):
    """LPQ relaxation: bit-parity vs the single-device program on
    every grid. The lanes shard on 'evals' and the dual-ascent combine
    is an all-gather -- bytes move, sums never re-associate -- so the
    parity here is structural, not shape-dependent luck."""
    from nomad_tpu import jitcheck, shardcheck
    from nomad_tpu.parallel import mesh as meshmod
    from nomad_tpu.solver import xferobs
    from nomad_tpu.solver.lpq import _lp_program, lpq_steps

    xferobs._reset_for_tests()
    L, N, steps = 16, 256, lpq_steps()
    rng = np.random.default_rng(200 + e_par)
    V = rng.standard_normal((L, N)).astype(np.float32)
    feas = rng.uniform(size=(L, N)) > 0.3
    ask = np.abs(rng.standard_normal((L, 3))).astype(np.float32)
    pcount = rng.integers(1, 4, L).astype(np.float32)
    freeT = (np.abs(rng.standard_normal((N, 3))) * 4.0
             ).astype(np.float32)
    active = np.ones(L, dtype=bool)

    X_ref, mu_ref = _lp_program(L, N, steps)(
        V, feas, ask, pcount, freeT, active)
    X_ref, mu_ref = np.asarray(X_ref), np.asarray(mu_ref)
    assert np.isfinite(X_ref).all()

    mesh = meshmod.make_mesh(8, eval_parallel=e_par)
    assert mesh.devices.shape == (e_par, n_par)
    with mesh:
        s_in = meshmod.shard_lpq_inputs(
            mesh, V, feas, ask, pcount, freeT, active)
        X_m, mu_m = meshmod.mesh_lpq_fn(mesh, L, N, steps)(*s_in)

    np.testing.assert_array_equal(np.asarray(X_m), X_ref)
    np.testing.assert_array_equal(np.asarray(mu_m), mu_ref)

    assert xferobs.shard_parity() == 0
    _zero_violations(shardcheck.state(), jitcheck.state())
    xferobs._reset_for_tests()
