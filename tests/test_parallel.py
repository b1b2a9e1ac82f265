"""Multi-chip sharding tests on the virtual 8-device CPU mesh."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu.parallel import make_mesh, shard_solver_inputs
from nomad_tpu.solver.binpack import solve_eval_batch


def _inputs(E, N, P):
    import __graft_entry__ as ge
    const1, init1, batch1 = ge._example_inputs(n_nodes=N, n_place=P,
                                               dtype="float64")
    stack = lambda t: jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf, (E,) + leaf.shape), t)
    return stack(const1), stack(init1), stack(batch1)


def test_mesh_shapes():
    mesh = make_mesh(8)
    assert mesh.devices.size == 8
    assert mesh.axis_names == ("evals", "nodes")


def test_eval_batch_unsharded_matches_sharded():
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(8)
    n_par = mesh.devices.shape[1]
    E, N, P = mesh.devices.shape[0] * 2, 16 * n_par, 4
    const, init, batch = _inputs(E, N, P)

    plain = solve_eval_batch(const, init, batch, dtype_name="float64")
    with mesh:
        s_const, s_init, s_batch = shard_solver_inputs(mesh, const, init, batch)
        sharded = solve_eval_batch(s_const, s_init, s_batch,
                                   dtype_name="float64")
    np.testing.assert_array_equal(np.asarray(plain[0]),
                                  np.asarray(sharded[0]))
    np.testing.assert_allclose(np.asarray(plain[1]),
                               np.asarray(sharded[1]), rtol=0, atol=0)


def test_sharded_parity_at_padded_scale():
    """Sharded vs unsharded equality at a real padded fleet shape (4096
    nodes), the bucket the 4K-node BASELINE tiers use -- this is the CI
    stand-in for multi-chip hardware (VERDICT r2 next #4)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    mesh = make_mesh(8)
    E, N, P = mesh.devices.shape[0], 4096, 16
    const, init, batch = _inputs(E, N, P)
    plain = solve_eval_batch(const, init, batch, dtype_name="float64")
    with mesh:
        s_const, s_init, s_batch = shard_solver_inputs(mesh, const, init,
                                                       batch)
        sharded = solve_eval_batch(s_const, s_init, s_batch,
                                   dtype_name="float64")
    np.testing.assert_array_equal(np.asarray(plain[0]),
                                  np.asarray(sharded[0]))
    np.testing.assert_allclose(np.asarray(plain[1]),
                               np.asarray(sharded[1]), rtol=0, atol=0)


def test_batch_worker_mesh_branch_end_to_end(monkeypatch):
    """BatchWorker(use_mesh=True) over the virtual mesh: the fused batch
    must dispatch through solver/batch.py's mesh branch (asserted via the
    mesh_dispatches counter) and place every alloc correctly. The wave
    predicate is patched off -- eligible lanes would otherwise take the O(B)
    kernel, which deliberately skips mesh sharding (nothing N-heavy)."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import time as _time

    from nomad_tpu import mock
    from nomad_tpu.server import Server
    from nomad_tpu.server.telemetry import metrics
    from nomad_tpu.structs import SchedulerConfiguration

    from nomad_tpu.solver.service import PackedLane
    monkeypatch.setattr(PackedLane, "_wavefront_check",
                        lambda self: False)
    metrics.reset()
    server = Server(num_workers=4, heartbeat_ttl=30.0, eval_batching=True,
                    batch_width=4)
    server.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-binpack"))
    server.start()
    try:
        for i in range(8):
            n = mock.node()
            n.id = f"mesh-node-{i:04d}"
            n.compute_class()
            server.register_node(n)
        jobs = []
        for i in range(4):
            job = mock.job(id=f"mesh-job-{i}")
            job.task_groups[0].count = 3
            jobs.append(job)
        for job in jobs:
            server.register_job(job)

        def placed():
            return sum(
                1 for job in jobs
                for a in server.state.allocs_by_job(job.namespace, job.id)
                if a.desired_status == "run")

        deadline = _time.time() + 30
        while _time.time() < deadline and placed() < 12:
            _time.sleep(0.05)
        assert placed() == 12
        snap = metrics.snapshot()
        assert snap["counters"].get("nomad.solver.mesh_dispatches", 0) >= 1
    finally:
        server.shutdown()


def test_eval_batch_independence():
    # each eval in the batch sees ONLY its own usage (optimistic concurrency)
    E, N, P = 2, 32, 3
    const, init, batch = _inputs(E, N, P)
    # preload eval 1 with usage on node 0
    used = np.zeros((E, N))
    used[1, 0] = 3500.0
    init = init._replace(used_cpu=jnp.asarray(used))
    chosen, scores, n_yield, state = solve_eval_batch(
        const, init, batch, dtype_name="float64")
    got = np.asarray(chosen)
    # the preloaded usage on eval 1's node 0 must change its choices
    # relative to eval 0 -- if usage leaked across evals they'd be equal
    assert not np.array_equal(got[0], got[1]), got
    # eval 1 must not overflow node 0: its used_cpu was nearly full
    final_used = np.asarray(state.used_cpu)
    assert final_used[1, 0] <= 4000.0


def test_wavefront_batched_shards_over_eval_axis():
    """The fused wavefront dispatch data-parallels lanes across devices
    (no collectives -- each chip scans its lanes); sharded results must
    equal the per-lane solo solves."""
    if len(jax.devices()) < 8:
        pytest.skip("needs 8 virtual devices")
    import random

    import tests.test_wavefront as tw
    from nomad_tpu.solver.binpack import solve_lane_fused, solve_wavefront

    lanes = [tw._world(random.Random(1400 + k), n=48, p=16, limit=5)
             for k in range(8)]
    const = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                   *[l[0] for l in lanes])
    init = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                  *[l[1] for l in lanes])
    batch = jax.tree_util.tree_map(lambda *xs: np.stack(xs),
                                   *[l[2] for l in lanes])
    chosen_b, scores_b, ny_b = solve_lane_fused(
        const, init, batch, spread_alg=False, dtype_name="float64",
        batched=True, wave=True)
    for k, (c, i, b) in enumerate(lanes):
        c1, s1, y1 = solve_wavefront(c, i, b, dtype_name="float64")
        np.testing.assert_array_equal(chosen_b[k], np.asarray(c1))
        np.testing.assert_array_equal(ny_b[k], np.asarray(y1))
