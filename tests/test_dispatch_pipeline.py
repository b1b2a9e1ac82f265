"""Async dispatch pipeline + eval-axis padding semantics (ISSUE 2).

Tier-1 smoke for the pipelined SolveBarrier: tiny shapes on the CPU
backend, one pipelined round at three slots asserted bit-identical to
the same pipeline with one slot (a serial order) and to each lane's
solo dispatch, on every CI run. Plus the straggler
regression (a timeout racing a newer generation must re-check the
result cell under the condvar, never read it unset) and the
fuse-and-solve padding contracts: padded eval lanes (replicas of lane 0
with active=False) and padded placement steps place nothing and charge
nothing to the cross-lane fixpoint ledger.
"""
import threading

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import AllocPlaceResult
from nomad_tpu.solver import batch as batch_mod
from nomad_tpu.solver import guard
from nomad_tpu.solver.batch import (
    SolveBarrier, _cross_lane_fixpoint, _pad_placement_axis,
    fuse_and_solve)
from nomad_tpu.solver.service import TpuPlacementService, dispatch_lane
from nomad_tpu.structs import Plan


@pytest.fixture(autouse=True)
def clean_guard():
    guard._reset_for_tests()
    yield
    guard._reset_for_tests()


def build_world(n_nodes=16):
    h = Harness()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"pipe-node-{i:04d}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    return h, nodes


def pack_lane(h, nodes, i, count=4):
    job = mock.job(id=f"pipe-job-{i}")
    job.task_groups[0].count = count
    tg = job.task_groups[0]
    plan = Plan(eval_id=f"pipe-eval-{i:027d}", priority=50, job=job)
    ctx = EvalContext(h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(count)]
    svc = TpuPlacementService(ctx, job, batch_mode=False, spread_alg=False)
    lane = svc.pack(tg, places, nodes)
    assert lane is not None
    return lane


def run_barrier(lanes, depth):
    barrier = SolveBarrier(participants=len(lanes), depth=depth)
    out = {}

    def worker(i):
        out[i] = barrier.solve(lanes[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(lanes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
    assert sorted(out) == list(range(len(lanes)))
    return out


def test_pipelined_round_matches_serial_order():
    """The tier-1 gate for the async dispatch path: one pipelined round
    at three slots must produce bit-identical placements to both the
    one-slot pipeline (a serial order) and each lane's solo dispatch."""
    h, nodes = build_world()
    lanes = [pack_lane(h, nodes, i) for i in range(3)]
    solo = [dispatch_lane(lane) for lane in lanes]
    sync = run_barrier(lanes, depth=1)
    piped = run_barrier(lanes, depth=3)
    for i in range(3):
        assert (sync[i][0] == solo[i][0]).all()
        assert (piped[i][0] == solo[i][0]).all()
        assert np.allclose(np.asarray(piped[i][1], dtype=np.float64),
                           np.asarray(sync[i][1], dtype=np.float64))
        assert (piped[i][2] == sync[i][2]).all()


def test_barrier_has_one_dispatch_route():
    """Production passes no depth and gets the module's constant; a
    barrier of one slot is the same pipeline (its generation is staged
    on the intake thread and dispatched off the eval thread), not a
    second, synchronous route."""
    assert SolveBarrier(participants=1)._depth \
        == batch_mod.DISPATCH_DEPTH == 2
    assert batch_mod.pipeline_state()["depth"] == 2
    h, nodes = build_world()
    lanes = [pack_lane(h, nodes, 60 + i, count=2) for i in range(2)]
    ran_on = []
    orig = batch_mod.fuse_and_solve

    def spy(lanes, use_mesh=True, **kw):
        ran_on.append(threading.current_thread().name)
        return orig(lanes, use_mesh=use_mesh, **kw)

    batch_mod.fuse_and_solve = spy
    try:
        pipe = batch_mod._get_pipeline(1)
        staged0 = pipe.staged()
        run_barrier(lanes, depth=1)
        assert batch_mod._get_pipeline(1) is pipe
        assert pipe.staged() == staged0 + 1
    finally:
        batch_mod.fuse_and_solve = orig
    assert ran_on and not any(n.startswith("Thread-") or n == "MainThread"
                              for n in ran_on), ran_on


def test_pipeline_overlaps_generations():
    """Depth-2 pipeline really keeps two dispatches in flight: two
    single-participant barriers submitted back-to-back with a slow fuse
    must overlap rather than serialize."""
    import time as _time

    stamps = []
    orig = batch_mod.fuse_and_solve

    def slow_fuse(lanes, use_mesh=True, **kw):
        stamps.append(("start", _time.monotonic()))
        _time.sleep(0.3)
        stamps.append(("end", _time.monotonic()))
        return orig(lanes, use_mesh=use_mesh, **kw)

    h, nodes = build_world()
    lanes = [pack_lane(h, nodes, 10 + i, count=2) for i in range(2)]
    batch_mod.fuse_and_solve = slow_fuse
    try:
        barriers = [SolveBarrier(participants=1, depth=2)
                    for _ in range(2)]
        out = {}

        def worker(i):
            out[i] = barriers[i].solve(lanes[i])

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(2)]
        t0 = _time.monotonic()
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        wall = _time.monotonic() - t0
    finally:
        batch_mod.fuse_and_solve = orig
    assert sorted(out) == [0, 1]
    starts = sorted(t for k, t in stamps if k == "start")
    ends = sorted(t for k, t in stamps if k == "end")
    # second dispatch started before the first finished = overlap
    assert len(starts) == 2 and len(ends) == 2
    assert starts[1] < ends[0], (stamps, wall)


def test_straggler_timeout_racing_generation_never_reads_unset_cell():
    """Regression (satellite 2): with a dispatch in flight for a NEWER
    generation, a waiter's barrier timeout must re-check its cell under
    the condvar and keep waiting -- the old code broke out of the loop
    and KeyError'd on cell["result"] before the completion landed."""
    import time as _time

    h, nodes = build_world()
    lane_a = pack_lane(h, nodes, 20, count=2)
    lane_b = pack_lane(h, nodes, 21, count=2)
    solo_a = dispatch_lane(lane_a)

    orig = batch_mod.fuse_and_solve

    def slow_fuse(lanes, use_mesh=True, **kw):
        _time.sleep(0.8)            # in flight across >1 timeout window
        return orig(lanes, use_mesh=use_mesh, **kw)

    orig_timeout = batch_mod.BARRIER_TIMEOUT_S
    batch_mod.BARRIER_TIMEOUT_S = 0.2
    batch_mod.fuse_and_solve = slow_fuse
    try:
        # participants=2: A arrives, B never does -> A's timeout fires a
        # partial dispatch (gen 1, async). A's NEXT timeout lands while
        # gen 1 is still executing; the fixed loop keeps waiting.
        barrier = SolveBarrier(participants=2, depth=2)
        res = {}
        err = []

        def worker():
            try:
                res["a"] = barrier.solve(lane_a)
            except Exception as e:  # noqa: BLE001 -- the regression
                err.append(e)       # manifested as KeyError here

        t = threading.Thread(target=worker)
        t.start()
        t.join(30)
        assert not t.is_alive(), "waiter wedged"
        assert not err, err
        assert (res["a"][0] == solo_a[0]).all()
        del lane_b
    finally:
        batch_mod.fuse_and_solve = orig
        batch_mod.BARRIER_TIMEOUT_S = orig_timeout


def test_pad_placement_axis_semantics():
    """Padded placement steps must be inert: active=False, zero asks --
    and the 0-size ask_cores branch (the 'no core asks' static shape)
    must stay 0-size so the compiled signature is preserved."""
    h, nodes = build_world(n_nodes=8)
    lane = pack_lane(h, nodes, 30, count=3)
    b = lane.batch
    assert b.ask_cores.shape[0] == 0

    same = _pad_placement_axis(b, b.ask_cpu.shape[0])
    assert same is b                      # no-op keeps the object

    grown = _pad_placement_axis(b, 8)
    assert grown.ask_cpu.shape[0] == 8
    assert grown.active[:3].all() and not grown.active[3:].any()
    assert (grown.ask_cpu[3:] == 0).all()
    assert (grown.penalty_idx[3:] == -1).all()
    assert (grown.count[3:] == 1).all()   # anti-affinity denominator
    assert grown.ask_cores.shape[0] == 0  # 0-size branch preserved

    # non-empty core asks DO grow with the axis
    core_b = b._replace(ask_cores=np.full(3, 2, dtype=np.int32))
    grown2 = _pad_placement_axis(core_b, 8)
    assert grown2.ask_cores.shape[0] == 8
    assert (grown2.ask_cores[:3] == 2).all()
    assert (grown2.ask_cores[3:] == 0).all()


def _ledger_total_charges(lanes, results):
    """Sum of placements charged against a fresh fixpoint ledger."""
    ledger = {}
    _cross_lane_fixpoint(lanes, results, ledger)
    return ledger


def test_eval_axis_padding_lanes_are_inert(monkeypatch):
    """fuse_and_solve pins wave groups to the e_pad_hint bucket by
    replicating lane 0 into padding lanes with active masked False:
    results must stay bit-identical to each lane's solo dispatch (the
    padded lanes placed nothing) and the fixpoint ledger must carry
    charges for REAL lanes' placements only."""
    h, nodes = build_world()
    lanes = [pack_lane(h, nodes, 40 + i, count=3) for i in range(3)]
    assert lanes[0].wavefront_ok()
    solo = [dispatch_lane(lane) for lane in lanes]

    # e_pad_hint=8 forces e_pad (8) > e_real (3): 5 inert replicas ride
    # the dispatch (the wave-pinning path)
    results = fuse_and_solve(lanes, e_pad_hint=8)
    for res, ref in zip(results, solo):
        assert (res[0] == ref[0]).all()

    ledger = _ledger_total_charges(lanes, results)
    placed = sum(int((res[0] >= 0).sum()) for res in results)
    # every charged node traces to a real lane's placement; 3 identical
    # 500cpu lanes from one snapshot cannot charge more than their own
    # placement count
    assert placed > 0
    charged_nodes = set(ledger)
    real_nodes = {lanes[i].nodes[np.asarray(lanes[i].order)[pos]].id
                  for i, res in enumerate(results)
                  for pos in np.asarray(res[0]) if pos >= 0}
    assert charged_nodes <= real_nodes
    # and dense grouping takes the same padding contract: patch the
    # wave predicate off so the vmapped dense kernel sees the inert lanes
    from nomad_tpu.solver.service import PackedLane
    monkeypatch.setattr(PackedLane, "_wavefront_check",
                        lambda self: False)
    dense_lanes = [pack_lane(h, nodes, 50 + i, count=3)
                   for i in range(3)]
    assert not dense_lanes[0].wavefront_ok()
    dense_solo = [dispatch_lane(lane) for lane in dense_lanes]
    dense_res = fuse_and_solve(dense_lanes, e_pad_hint=0)
    for res, ref in zip(dense_res, dense_solo):
        assert (res[0] == ref[0]).all()


def test_program_factories_single_flight():
    """lru_cache does not single-flight: two pipelined generations
    racing ONE cold shape bucket used to both execute the factory,
    duplicating the XLA trace/compile and constructing two identical
    jits at one site -- the fresh-identical-closure pattern the
    jitcheck fixture (correctly) failed as a steady-state retrace the
    moment the overlap test raced a cold wave bucket. The factories
    now serialize invocations: every concurrent cold caller must get
    THE SAME program object."""
    import threading

    from nomad_tpu.solver.binpack import _wave_compact_program

    # a shape-bucket key no other test uses: genuinely cold
    key = ((7, 64, 9), (0, 7), False, "float32", True, 16, False)
    results = [None] * 8
    start = threading.Barrier(8)

    def racer(i):
        start.wait()
        results[i] = _wave_compact_program(*key)

    threads = [threading.Thread(target=racer, args=(i,), daemon=True)
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        while t.is_alive():
            t.join(timeout=5.0)
    assert all(r is results[0] for r in results), results
    # warm path: same object again, no rebuild
    assert _wave_compact_program(*key) is results[0]
