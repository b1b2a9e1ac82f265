"""Chaos suite: inject faults at every layer and assert the system
degrades the way the design promises.

The scenarios: the device wedging MID-ROUND, after init had succeeded,
plus the broker/raft failure classes: a mid-dispatch solver hang must cost one watchdog
deadline -- never the worker; the eval must complete via the host
oracle with parity-identical placements; the breaker must trip and then
auto-recover once the fault clears; a failed eval must be nacked and
requeued, never lost.

Fast variants run in tier-1 (`-m chaos` selects just these); soak
variants are additionally marked `slow`.
"""
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.benchkit import run_tier_placements
from nomad_tpu.faultinject import FaultRegistry, InjectedFault, faults
from nomad_tpu.server import Server
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.solver import guard

pytestmark = pytest.mark.chaos

N_NODES, COUNT, SEED = 12, 6, 7


@pytest.fixture(autouse=True)
def clean_slate():
    from nomad_tpu.server.tracing import tracer
    from nomad_tpu.solver import constcache
    guard._reset_for_tests()
    faults._reset_for_tests()
    constcache._reset_for_tests()
    tracer._reset_for_tests()
    metrics.reset()
    yield
    faults._reset_for_tests()
    guard._reset_for_tests()
    constcache._reset_for_tests()
    tracer._reset_for_tests()


def _host_placements():
    return run_tier_placements(3, N_NODES, COUNT, SEED, "binpack")


def _tpu_placements():
    return run_tier_placements(3, N_NODES, COUNT, SEED, "tpu-binpack")


def _recovery_in_process(monkeypatch):
    """Breaker recovery is a real probe dispatch on the device this
    process holds (the solver.probe fault point holds it open while a
    scenario needs that). A child could never open an attached device:
    fail the scenario if recovery tries to start one."""
    import subprocess

    def refuse(*a, **kw):
        raise AssertionError("breaker recovery started a child process")
    monkeypatch.setattr(subprocess, "Popen", refuse)


# ----------------------------------------------------------------------
# The acceptance scenario: mid-dispatch hang -> bounded fallback ->
# breaker trip -> auto-recovery once the fault clears.


def test_dispatch_hang_bounded_fallback_trip_and_autorecovery(
        monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_DISPATCH_TIMEOUT", "0.3")
    monkeypatch.setenv("NOMAD_TPU_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "0.05")
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF_MAX", "0.2")
    _recovery_in_process(monkeypatch)

    host = _host_placements()
    assert host, "world must place something"

    # wedge the device: every dispatch hangs until the fault is
    # disarmed; the probe point holds the breaker open meanwhile
    faults.arm("solver.dispatch", "hang")
    faults.arm("solver.probe", "error")

    t0 = time.time()
    degraded = _tpu_placements()
    wall = time.time() - t0

    # the worker never blocked past the deadline (one-ish timeouts of
    # 0.3s each, not the unbounded hang), and the eval COMPLETED with
    # the host oracle's exact placements
    assert wall < 5.0, f"eval blocked {wall:.1f}s despite 0.3s deadline"
    assert degraded == host, "host fallback must be parity-identical"

    st = guard.state()
    assert st["degraded"] is True
    assert st["breaker"]["state"] in ("open", "half_open")
    assert st["breaker"]["trips"] >= 1
    assert st["dispatch"]["timeout"] >= 1
    assert st["host_fallback_dispatches"] >= 1
    assert guard.dispatch_allowed() is False

    # the injected fault clears -> background probes pass -> the
    # breaker closes WITHOUT any operator action (round 5 required a
    # manual reprobe)
    faults.disarm_all()
    deadline = time.time() + 10.0
    while time.time() < deadline:
        if guard.breaker_state()["state"] == guard.BREAKER_CLOSED:
            break
        time.sleep(0.02)
    st = guard.state()
    assert st["breaker"]["state"] == guard.BREAKER_CLOSED
    assert st["breaker"]["recoveries"] >= 1
    assert st["degraded"] is False
    assert guard.dispatch_allowed() is True

    # and the recovered path schedules densely again, still at parity
    recovered = _tpu_placements()
    assert recovered == host


def test_dispatch_exception_falls_back_parity(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_BREAKER_THRESHOLD", "100")
    host = _host_placements()
    faults.arm("solver.dispatch", "error")
    degraded = _tpu_placements()
    assert degraded == host
    st = guard.state()
    assert st["dispatch"]["error"] >= 1
    assert st["host_fallback_dispatches"] >= 1
    # under threshold: no trip
    assert st["breaker"]["state"] == guard.BREAKER_CLOSED


def test_dispatch_latency_within_deadline_no_trip(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_DISPATCH_TIMEOUT", "30")
    host = _host_placements()
    faults.arm("solver.dispatch", "delay", delay_s=0.05)
    placed = _tpu_placements()
    assert placed == host
    st = guard.state()
    assert st["dispatch"]["ok"] >= 1
    assert st["dispatch"]["timeout"] == 0
    assert st["breaker"]["state"] == guard.BREAKER_CLOSED
    counters = metrics.snapshot()["counters"]
    assert counters.get("nomad.scheduler.placements_tpu", 0) > 0, \
        "dense path must have actually dispatched"


def test_breaker_open_routes_host_without_dispatching(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "30")
    _recovery_in_process(monkeypatch)
    host = _host_placements()
    metrics.reset()
    for _ in range(guard._breaker_threshold()):
        guard.record_dispatch_failure("timeout")
    assert guard.breaker_state()["state"] == guard.BREAKER_OPEN
    assert guard.dispatch_allowed() is False
    placed = _tpu_placements()
    assert placed == host
    counters = metrics.snapshot()["counters"]
    assert counters.get("nomad.scheduler.placements_tpu", 0) == 0
    assert counters.get(
        "nomad.solver.host_fallback_dispatches", 0) >= 1


# ----------------------------------------------------------------------
# Eval pipeline: injected failures must nack/requeue, never lose evals.


def _wait_placed(server, job_id, want, timeout=15.0):
    deadline = time.time() + timeout
    allocs = []
    while time.time() < deadline:
        allocs = [a for a in server.state.allocs_by_job(
            "default", job_id) if a.desired_status == "run"]
        if len(allocs) >= want:
            return allocs
        time.sleep(0.05)
    raise AssertionError(
        f"only {len(allocs)}/{want} allocs placed within {timeout}s")


def test_worker_invoke_fault_eval_not_lost():
    faults.arm("worker.invoke", "error", count=1)
    server = Server(num_workers=1, heartbeat_ttl=30.0)
    server.start()
    try:
        from nomad_tpu.client import SimClient
        client = SimClient(server, mock.node())
        client.start()
        job = mock.job(id="chaos-invoke")
        job.task_groups[0].count = 2
        server.register_job(job)
        # first delivery raises -> nack -> requeue -> second succeeds
        _wait_placed(server, "chaos-invoke", 2)
        assert faults.snapshot()["faults"] == [], \
            "count=1 fault must auto-disarm after firing"
        counters = metrics.snapshot()["counters"]
        assert counters.get("nomad.fault.injected.worker.invoke") == 1
    finally:
        server.shutdown()


def test_plan_apply_fault_eval_not_lost():
    faults.arm("plan.apply", "error", count=1)
    server = Server(num_workers=1, heartbeat_ttl=30.0)
    server.start()
    try:
        from nomad_tpu.client import SimClient
        client = SimClient(server, mock.node())
        client.start()
        job = mock.job(id="chaos-plan")
        job.task_groups[0].count = 2
        server.register_job(job)
        _wait_placed(server, "chaos-plan", 2)
    finally:
        server.shutdown()


def test_broker_dequeue_fault_worker_survives():
    # an erroring dequeue must not kill the worker thread (pre-round-6
    # the raise escaped Worker.run's try and silently halted scheduling)
    faults.arm("broker.dequeue", "error", count=2)
    server = Server(num_workers=1, heartbeat_ttl=30.0)
    server.start()
    try:
        from nomad_tpu.client import SimClient
        client = SimClient(server, mock.node())
        client.start()
        job = mock.job(id="chaos-dequeue")
        job.task_groups[0].count = 1
        server.register_job(job)
        _wait_placed(server, "chaos-dequeue", 1)
    finally:
        server.shutdown()


# ----------------------------------------------------------------------
# Transport + heartbeat injection points.


def test_rpc_drop_and_delay():
    from nomad_tpu.raft.transport import TcpTransport

    t = TcpTransport()
    t.register("echo", lambda m: {"ok": True, "x": m.get("x")})
    t.start()
    try:
        assert t.send(t.addr, {"type": "echo", "x": 1})["x"] == 1
        faults.arm("raft.rpc", "drop")
        with pytest.raises(ConnectionError):
            t.send(t.addr, {"type": "echo", "x": 2})
        faults.disarm("raft.rpc")
        faults.arm("raft.rpc", "delay", delay_s=0.1)
        t0 = time.time()
        assert t.send(t.addr, {"type": "echo", "x": 3})["x"] == 3
        assert time.time() - t0 >= 0.1
    finally:
        t.shutdown()


def test_heartbeat_stall_still_serves():
    server = Server(num_workers=0, heartbeat_ttl=30.0)
    server.start()
    try:
        node = mock.node()
        server.register_node(node)
        faults.arm("heartbeat", "delay", delay_s=0.1)
        t0 = time.time()
        ttl = server.heartbeat(node.id)
        assert ttl > 0
        assert time.time() - t0 >= 0.1
    finally:
        server.shutdown()


# ----------------------------------------------------------------------
# The framework itself + the HTTP arming surface.


def test_registry_env_arming(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_FAULT_INJECT",
                       "heartbeat=delay:0.01:2, raft.rpc=drop,"
                       "bogus entry,typo=nosuchaction")
    reg = FaultRegistry()
    snap = {f["point"]: f for f in reg.snapshot()["faults"]}
    assert snap["heartbeat"]["action"] == "delay"
    assert snap["heartbeat"]["count"] == 2
    assert snap["raft.rpc"]["action"] == "drop"
    assert "typo" not in snap          # bad entries must not abort boot
    reg.fire("heartbeat")
    reg.fire("heartbeat")              # count exhausts -> auto-disarm
    assert "heartbeat" not in {
        f["point"] for f in reg.snapshot()["faults"]}


def test_registry_error_and_count():
    reg = FaultRegistry()
    reg.arm("p", "error", count=2)
    with pytest.raises(InjectedFault):
        reg.fire("p")
    with pytest.raises(InjectedFault):
        reg.fire("p")
    reg.fire("p")                      # exhausted: no-op
    with pytest.raises(ValueError):
        reg.arm("p", "explode")
    assert reg.disarm("p") is False


def test_faults_http_endpoints_and_agent_self():
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.api.http import HttpServer

    server = Server(num_workers=0, heartbeat_ttl=30.0)
    server.start()
    http = HttpServer(server, port=0)
    http.start()
    try:
        api = ApiClient(f"http://127.0.0.1:{http.port}")
        snap = api.post("/v1/operator/faults",
                        {"point": "heartbeat", "action": "delay",
                         "delay_s": 0.01})
        assert snap["faults"][0]["point"] == "heartbeat"
        assert api.get("/v1/operator/faults")["faults"]
        snap = api.post("/v1/operator/faults",
                        {"point": "heartbeat", "disarm": True})
        assert snap["faults"] == []

        # breaker + degraded verdict ride /v1/agent/self
        st = api.get("/v1/agent/self")["stats"]["solver_guard"]
        assert "breaker" in st and "degraded" in st
        assert st["breaker"]["state"] == "closed"
    finally:
        http.shutdown()
        server.shutdown()


def test_guard_state_reports_breaker_degraded(monkeypatch):
    """What /v1/agent/self serves: ``degraded`` follows the breaker."""
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "30")
    _recovery_in_process(monkeypatch)
    assert guard.state()["degraded"] is False
    for _ in range(guard._breaker_threshold()):
        guard.record_dispatch_failure("timeout")
    st = guard.state()
    assert st["degraded"] is True
    assert st["breaker"]["state"] == guard.BREAKER_OPEN
    assert st["breaker"]["trips"] == 1
    guard.reset_breaker()
    st = guard.state()
    assert st["degraded"] is False
    assert st["breaker"]["state"] == guard.BREAKER_CLOSED


# ----------------------------------------------------------------------
# Pipelined dispatch under injected
# faults: every waiter gets exactly one result-or-fallback (no lost
# evals, no double-wake), and the const cache invalidates cleanly
# across a breaker trip/recovery cycle.


def test_pipelined_dispatch_fault_every_waiter_exactly_one_outcome(
        monkeypatch):
    """solver.dispatch armed with depth>1 in flight: several concurrent
    barrier generations fail, and each waiting eval thread must observe
    EXACTLY one outcome (DispatchFailed -> host fallback), never a lost
    wakeup, never two."""
    import threading

    from nomad_tpu.solver import batch as batch_mod
    from nomad_tpu.solver.batch import SolveBarrier

    monkeypatch.setenv("NOMAD_TPU_BREAKER_THRESHOLD", "100")
    # fake lanes/results: nothing for the fixpoint to read
    monkeypatch.setattr(batch_mod, "_cross_lane_fixpoint",
                        lambda lanes, results, ledger: None)

    class Lane:
        def __init__(self, tag):
            self.tag = tag

        def fuse_key(self):
            return ("chaos",)

    orig = batch_mod.fuse_and_solve

    def faulted_fuse(lanes, use_mesh=True, **kw):
        faults.fire("solver.dispatch")
        return [("ok", ln.tag) for ln in lanes]

    batch_mod.fuse_and_solve = faulted_fuse
    faults.arm("solver.dispatch", "error")
    outcomes = []
    outcomes_lock = threading.Lock()
    try:
        # 3 generations across 3 barriers, depth 3: all in flight at once
        barriers = [SolveBarrier(participants=2, depth=3)
                    for _ in range(3)]

        def worker(b, tag):
            try:
                res = barriers[b].solve(Lane(tag))
                with outcomes_lock:
                    outcomes.append(("result", tag, res))
            except guard.DispatchFailed:
                with outcomes_lock:
                    outcomes.append(("fallback", tag, None))
            except Exception as e:  # noqa: BLE001 -- the assertion
                with outcomes_lock:
                    outcomes.append(("unexpected", tag, e))

        threads = [threading.Thread(target=worker, args=(b, f"{b}-{k}"))
                   for b in range(3) for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(30)
        assert not any(t.is_alive() for t in threads), "waiter wedged"
        kinds = sorted(o[0] for o in outcomes)
        tags = sorted(o[1] for o in outcomes)
        # exactly one outcome per waiter, all fallbacks, none doubled
        assert kinds == ["fallback"] * 6, outcomes
        assert tags == sorted(f"{b}-{k}" for b in range(3)
                              for k in range(2))
    finally:
        batch_mod.fuse_and_solve = orig


def test_pack_cache_never_stale_across_table_write_mid_pipeline():
    """ISSUE 4 chaos: with the pipelined barrier and warm pack caches,
    a node-table write + alloc write landing BETWEEN generations must
    never let an eval solve against a stale usage base or stale fleet
    tables -- the post-write generation's placements must equal a
    control packed from emptied caches and solved from the same
    snapshot."""
    import threading

    import numpy as np

    from nomad_tpu.scheduler import Harness
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.batch import SolveBarrier
    from nomad_tpu.solver.service import TpuPlacementService
    from nomad_tpu.structs import Plan
    from nomad_tpu.tensor import pack as tpack

    tpack._reset_pack_caches_for_tests()
    h = Harness()
    nodes = []
    for i in range(8):
        n = mock.node()
        n.id = f"stale-node-{i:04d}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)

    def pack_round(tag, node_list):
        snap = h.state.snapshot()
        lanes = []
        for i in range(2):
            job = mock.job(id=f"stale-job-{tag}-{i}")
            job.task_groups[0].count = 3
            tg = job.task_groups[0]
            plan = Plan(eval_id=f"stale-eval-{tag}-{i:021d}"[-36:],
                        priority=50, job=job)
            ctx = EvalContext(snap, plan)
            places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                                       task_group=tg) for k in range(3)]
            svc = TpuPlacementService(ctx, job, batch_mode=False,
                                      spread_alg=False)
            lane = svc.pack(tg, places, node_list)
            assert lane is not None
            lanes.append(lane)
        return lanes

    def run_barrier(lanes):
        barrier = SolveBarrier(participants=len(lanes), depth=2)
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
        return [out[i] for i in range(len(lanes))]

    # generation 1: warms the matrix cache, spec memos, usage base and
    # the fused-stack arena
    run_barrier(pack_round("warm", nodes))

    # mid-pipeline world change: a new node (table write) AND a new
    # running alloc eating capacity on node 0
    extra = mock.node()
    extra.id = "stale-node-extra"
    extra.compute_class()
    h.state.upsert_node(extra)
    filler = mock.job(id="stale-filler")
    filler.task_groups[0].tasks[0].resources.cpu = 4000
    h.state.upsert_job(filler)
    a = mock.alloc_for(filler, nodes[0])
    a.client_status = "running"
    h.state.upsert_allocs([a])
    all_nodes = nodes + [extra]

    # generation 2 packs from the NEW snapshot with warm caches
    hot = run_barrier(pack_round("after", all_nodes))

    # control: identical evals, every pack cache and the arena emptied
    from nomad_tpu.solver import batch as batch_mod
    tpack._reset_pack_caches_for_tests()
    batch_mod.arena_clear("cold control")
    cold = run_barrier(pack_round("after", all_nodes))
    for a_res, b_res in zip(hot, cold):
        assert (np.asarray(a_res[0]) == np.asarray(b_res[0])).all(), \
            "eval solved against a stale pack cache"


def test_pack_caches_invalidate_across_breaker_trip_and_recovery(
        monkeypatch):
    """Fill the host pack caches + arena, trip the breaker, recover:
    both edges must drop them (nothing derived before the wedge
    survives past recovery), and packing works again after."""
    from nomad_tpu import mock as _mock
    from nomad_tpu.solver import batch as batch_mod
    from nomad_tpu.tensor import pack as tpack

    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "30")
    _recovery_in_process(monkeypatch)
    tpack._reset_pack_caches_for_tests()
    batch_mod.arena_clear("test baseline")

    nodes = []
    for i in range(4):
        n = _mock.node()
        n.id = f"trip-node-{i:04d}"
        n.compute_class()
        nodes.append(n)
    tpack.pack_nodes_cached(nodes, 5)
    ent, _ = batch_mod._ARENA.acquire(
        ("trip", 2, 32), {"t": [((2, 8), __import__("numpy")
                                 .dtype("float64"))]})
    batch_mod._ARENA.release(ent)
    assert len(tpack._NODE_MATRIX_CACHE) == 1
    assert batch_mod.arena_state()["entries"] == 1

    for _ in range(guard._breaker_threshold()):
        guard.record_dispatch_failure("timeout")
    assert guard.breaker_state()["state"] == guard.BREAKER_OPEN
    assert len(tpack._NODE_MATRIX_CACHE) == 0, \
        "trip must drop pack caches"
    assert batch_mod.arena_state()["entries"] == 0, \
        "trip must drop pooled arena buffers"
    assert tpack.pack_cache_stats()["invalidations"] >= 1

    # refill while open; the recovery edge re-baselines again
    tpack.pack_nodes_cached(nodes, 6)
    guard.reset_breaker()
    assert guard.breaker_state()["state"] == guard.BREAKER_CLOSED
    assert len(tpack._NODE_MATRIX_CACHE) == 0, \
        "recovery must re-baseline the pack caches"
    assert tpack.pack_cache_stats()["invalidations"] >= 2

    # and the cache works normally after the cycle
    m = tpack.pack_nodes_cached(nodes, 7)
    assert tpack.pack_nodes_cached(nodes, 7) is m


def test_const_cache_invalidates_across_breaker_trip_and_recovery(
        monkeypatch):
    """Fill the device-resident cache, trip the breaker, recover: the
    cache must drop its buffers on BOTH edges and work again after."""
    import numpy as np

    from nomad_tpu.solver import constcache

    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "30")
    _recovery_in_process(monkeypatch)

    table = np.full(4096, 3.0, dtype=np.float32)
    constcache.device_put_cached([table], version=1)
    assert constcache.stats()["entries"] == 1

    for _ in range(guard._breaker_threshold()):
        guard.record_dispatch_failure("timeout")
    assert guard.breaker_state()["state"] == guard.BREAKER_OPEN
    st = constcache.stats()
    assert st["entries"] == 0, "trip must drop resident buffers"
    assert st["invalidations"] >= 1

    # buffers uploaded while the breaker is open get dropped again on
    # the recovery edge (reprobe -> reset path closes the breaker)
    constcache.device_put_cached([table], version=2)
    guard.reset_breaker()
    assert guard.breaker_state()["state"] == guard.BREAKER_CLOSED
    st = constcache.stats()
    assert st["entries"] == 0, "recovery must re-baseline the cache"
    assert st["invalidations"] >= 2

    # and the cache works normally after the cycle
    _, s1 = constcache.device_put_cached([table], version=3)
    _, s2 = constcache.device_put_cached([table], version=3)
    assert s1 == table.nbytes and s2 == 0


# ----------------------------------------------------------------------
# Eval trace flight recorder under faults: every degraded eval must be
# retrievable end-to-end with its root cause, and trace memory must
# stay under the configured cap no matter how many evals degrade.


def test_degraded_eval_trace_retained_with_root_cause(monkeypatch):
    """Watchdog timeout -> host fallback: the eval's trace must survive
    tail-based retention even at sample rate 0, name the root cause,
    and carry the solve spans."""
    from nomad_tpu.server.tracing import tracer

    monkeypatch.setenv("NOMAD_TPU_TRACE_SAMPLE", "0")
    monkeypatch.setenv("NOMAD_TPU_TRACE_SLOW_MS", "999999")
    monkeypatch.setenv("NOMAD_TPU_DISPATCH_TIMEOUT", "0.3")
    monkeypatch.setenv("NOMAD_TPU_BREAKER_THRESHOLD", "100")

    host = _host_placements()
    tracer._reset_for_tests()          # drop the host run's traces
    faults.arm("solver.dispatch", "hang")
    degraded = _tpu_placements()
    faults.disarm_all()
    assert degraded == host

    traces = tracer.list_traces(degraded=True)
    assert traces, "degraded eval left no retained trace"
    tr = tracer.get(traces[0]["eval_id"])
    assert tr["degraded_reason"] in ("watchdog_timeout",
                                     "host_fallback")
    names = {s["name"] for s in tr["spans"]}
    assert "degraded" in names
    assert "solver.pack" in names or "solver.dispatch_solo" in names
    # healthy runs at sample 0 retain nothing
    tracer._reset_for_tests()
    _tpu_placements()
    assert tracer.stats()["retained"] == 0


def test_breaker_trip_stamps_inflight_traces(monkeypatch):
    from nomad_tpu.server.tracing import tracer

    monkeypatch.setenv("NOMAD_TPU_TRACE_SAMPLE", "0")
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "30")
    _recovery_in_process(monkeypatch)
    tracer.begin("inflight-1")
    for _ in range(guard._breaker_threshold()):
        guard.record_dispatch_failure("timeout")
    assert guard.breaker_state()["state"] == guard.BREAKER_OPEN
    tracer.end("inflight-1")
    tr = tracer.get("inflight-1")
    assert tr is not None, "trip must force retention of in-flight evals"
    assert tr["degraded_reason"] == "breaker_open"


def test_trace_memory_capped_under_fault_storm(monkeypatch):
    """200 degraded (always-keep) evals against a 16-trace / 64KB cap:
    the ring must hold the caps, keeping the newest."""
    from nomad_tpu.server.tracing import tracer

    monkeypatch.setenv("NOMAD_TPU_TRACE_CAP", "16")
    monkeypatch.setenv("NOMAD_TPU_TRACE_MB", "0.0625")   # 64 KB
    monkeypatch.setenv("NOMAD_TPU_TRACE_SAMPLE", "1.0")
    for i in range(200):
        ctx = tracer.begin(f"storm-{i}", lane="service")
        with tracer.activate(ctx):
            with tracer.span("solver.fuse_dispatch", generation=i):
                pass
            tracer.mark_degraded("host_fallback")
        tracer.end(f"storm-{i}")
    st = tracer.stats()
    assert st["retained"] <= 16
    assert st["retained_bytes"] <= 64 * 1024
    assert tracer.get("storm-199") is not None, "newest must survive"


# ----------------------------------------------------------------------
# Soak: repeated wedge/recover cycles stay parity-correct.


@pytest.mark.slow
def test_soak_wedge_recover_cycles(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_DISPATCH_TIMEOUT", "0.3")
    monkeypatch.setenv("NOMAD_TPU_BREAKER_THRESHOLD", "1")
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "0.05")
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF_MAX", "0.2")
    _recovery_in_process(monkeypatch)
    host = _host_placements()
    for cycle in range(3):
        faults.arm("solver.dispatch", "hang")
        faults.arm("solver.probe", "error")
        assert _tpu_placements() == host, f"cycle {cycle} degraded"
        faults.disarm_all()
        deadline = time.time() + 10.0
        while time.time() < deadline:
            if guard.breaker_state()["state"] == guard.BREAKER_CLOSED:
                break
            time.sleep(0.02)
        assert guard.breaker_state()["state"] == guard.BREAKER_CLOSED
        assert _tpu_placements() == host, f"cycle {cycle} recovered"
    assert guard.breaker_state()["recoveries"] >= 3
