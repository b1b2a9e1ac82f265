"""Production eval batching: many evals fused into one solver dispatch
(replaces the reference's one-eval-per-worker contract,
nomad/worker.go:397 + scheduler/scheduler.go:59-68, with the TPU-native
coalesced form -- SURVEY.md section 7 hard part 5)."""
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.client import SimClient
from nomad_tpu.server import Server
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.structs import (
    SchedulerConfiguration, EVAL_STATUS_BLOCKED, EVAL_STATUS_COMPLETE,
)


def wait_until(cond, timeout=15.0, interval=0.05, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timeout waiting for {msg}")


def make_server(n_nodes=6, width=4, cpu=4000, mem=8192):
    server = Server(num_workers=width, heartbeat_ttl=30.0,
                    eval_batching=True)
    cfg = SchedulerConfiguration(scheduler_algorithm="tpu-binpack")
    server.state.set_scheduler_config(cfg)
    server.start()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"batch-node-{i:04d}"
        n.node_resources.cpu.cpu_shares = cpu
        n.node_resources.memory.memory_mb = mem
        n.compute_class()
        nodes.append(n)
        server.register_node(n)
    return server, nodes


def committed_allocs(server, job):
    return [a for a in server.state.allocs_by_job(job.namespace, job.id)
            if a.desired_status == "run"]


def test_dequeue_batch_distinct_jobs():
    from nomad_tpu.server.broker import EvalBroker
    from nomad_tpu.structs import Evaluation, generate_uuid

    broker = EvalBroker()
    broker.set_enabled(True)
    evs = []
    for i in range(5):
        ev = Evaluation(id=generate_uuid(), namespace="default",
                        job_id=f"job-{i % 3}", priority=50, type="service",
                        triggered_by="job-register", status="pending")
        evs.append(ev)
        broker.enqueue(ev)
    batch = broker.dequeue_batch(["service"], max_k=10, timeout=0.5)
    jobs = {(ev.namespace, ev.job_id) for ev, _ in batch}
    # one in-flight eval per job: 3 distinct jobs -> 3 dequeued
    assert len(batch) == 3
    assert len(jobs) == 3
    for ev, token in batch:
        assert broker.ack(ev.id, token) is None


def test_batched_evals_fuse_into_one_dispatch():
    """K jobs registered together must place via a fused multi-lane
    dispatch (batch_lanes sample > 1), with every alloc correct.

    Deflake (ISSUE 15 satellite): the fuse-width assert depends on the
    evals actually RENDEZVOUSING in one broker dequeue -- but
    register_job enqueues each eval under its own broker lock
    acquisition, so on a 1-core host a polling batch worker could
    dequeue job 0 alone before jobs 1..3 existed and legally fuse a
    1-lane dispatch (~1/5 runs).  Enqueue all four evals ATOMICALLY
    (one enqueue_all, the same idiom the fixpoint test uses): any
    dequeue_batch now sees all four distinct jobs or none, which is
    the pipeline condition the `lanes >= 2` assert actually depends
    on, instead of a thread-timing race."""
    from nomad_tpu.structs import Evaluation, generate_uuid

    metrics.reset()
    server, nodes = make_server(n_nodes=8, width=4)
    try:
        jobs = []
        evs = []
        for i in range(4):
            job = mock.job(id=f"batch-job-{i}")
            job.task_groups[0].count = 3
            jobs.append(job)
            server.state.upsert_job(job)
            evs.append(Evaluation(
                id=generate_uuid(), namespace=job.namespace,
                priority=job.priority, type=job.type,
                triggered_by="job-register", job_id=job.id,
                status="pending"))
        server.state.upsert_evals(evs)
        server.broker.enqueue_all(evs)
        for job in jobs:
            wait_until(lambda j=job: len(committed_allocs(server, j)) == 3,
                       msg=f"{job.id} placed")
        snap = metrics.snapshot()
        # batch_lanes is a COUNT and now rides the unit-free gauge
        # registry (satellite fix: it used to render as milliseconds)
        lanes = snap["gauges"].get("nomad.solver.batch_lanes")
        assert lanes is not None, sorted(snap["gauges"])
        assert lanes["max"] >= 2.0, lanes   # >= 2 lanes fused at least once
        assert snap["counters"]["nomad.scheduler.placements_tpu"] == 12
        # node capacity respected: each node 4000 cpu, mock asks 500/alloc
        by_node = {}
        for job in jobs:
            for a in committed_allocs(server, job):
                by_node.setdefault(a.node_id, 0)
                by_node[a.node_id] += 1
        assert all(v <= 8 for v in by_node.values())
    finally:
        server.shutdown()


def test_batched_conflict_resolved_by_plan_applier():
    """Two evals in one batch racing for the same last capacity: the
    serialized applier commits one, the other retries/blocks -- optimistic
    concurrency preserved under fused dispatch."""
    metrics.reset()
    # one node with room for exactly ONE mock alloc (500 cpu, 256 mem)
    server, nodes = make_server(n_nodes=1, width=4, cpu=600, mem=400)
    try:
        j1 = mock.job(id="conflict-a")
        j1.task_groups[0].count = 1
        j2 = mock.job(id="conflict-b")
        j2.task_groups[0].count = 1
        server.register_job(j1)
        server.register_job(j2)

        def settled():
            a1 = committed_allocs(server, j1)
            a2 = committed_allocs(server, j2)
            if len(a1) + len(a2) != 1:
                return False
            loser = j2 if a1 else j1
            evs = server.state.evals_by_job(loser.namespace, loser.id)
            return any(e.status == EVAL_STATUS_BLOCKED for e in evs)

        wait_until(settled, msg="one winner one blocked")
        # never two allocs on the 600-cpu node
        all_allocs = (committed_allocs(server, j1)
                      + committed_allocs(server, j2))
        assert len(all_allocs) == 1
    finally:
        server.shutdown()


def test_multi_tg_eval_sequences_within_batch():
    """A 2-TG job inside a batch: TG2's lane must see TG1's placements
    (usage overlay), preserving within-eval sequential dependence."""
    metrics.reset()
    server, nodes = make_server(n_nodes=2, width=2, cpu=1100, mem=4096)
    try:
        job = mock.job(id="two-tg")
        tg1 = job.task_groups[0]
        tg1.count = 2
        import copy
        tg2 = copy.deepcopy(tg1)
        tg2.name = "second"
        tg2.count = 2
        job.task_groups.append(tg2)
        # each node fits two 500-cpu allocs (1100 cap): 4 allocs total
        # requires TG2 to see TG1's usage or it would over-commit
        server.register_job(job)
        wait_until(lambda: len(committed_allocs(server, job)) == 4,
                   msg="all 4 allocs placed")
        by_node = {}
        for a in committed_allocs(server, job):
            by_node.setdefault(a.node_id, 0)
            by_node[a.node_id] += 1
        assert sorted(by_node.values()) == [2, 2], by_node
    finally:
        server.shutdown()


def test_cross_lane_fixpoint_avoids_applier_retry():
    """Two evals in one batch whose best-fit choices collide on the same
    node, with spare capacity elsewhere: the barrier's conflict fixpoint
    must settle the loser onto the spare node BEFORE plan submission, so
    the applier commits both plans with zero rejections (no retry round
    trips through the broker).

    Deflake (ISSUE 15 satellite): the `fixpoint_conflicts >= 1` assert
    depends on both evals solving in ONE barrier generation -- the
    fuse-width condition.  On a cold process the first eval's packing
    path pays the jit warmup, so the 10s straggler valve could fire
    and dispatch the early arriver ALONE: each eval then picks its
    node sequentially, no conflict ever happens, and the assert loses
    to thread timing (the test failed deterministically when run
    standalone, and ~1/5 in-suite on the 1-core host).  Widening the
    straggler valve for the test makes the barrier actually await the
    rendezvous the assert depends on; the valve's own semantics have
    their own test below."""
    from nomad_tpu.solver import batch as batch_mod

    metrics.reset()
    # one TIGHT node (fits exactly one 500cpu/256mb mock alloc; best-fit
    # scores it highest for BOTH evals regardless of shuffle order) plus
    # one roomy spare: the fused batch must collide on the tight node
    server, nodes = make_server(n_nodes=1, width=4, cpu=600, mem=400)
    spare = mock.node()
    spare.id = "batch-node-spare"
    spare.node_resources.cpu.cpu_shares = 4000
    spare.node_resources.memory.memory_mb = 8192
    spare.compute_class()
    server.register_node(spare)
    orig_timeout = batch_mod.BARRIER_TIMEOUT_S
    batch_mod.BARRIER_TIMEOUT_S = 120.0
    try:
        from nomad_tpu.structs import Evaluation, generate_uuid

        j1 = mock.job(id="fixpoint-a")
        j1.task_groups[0].count = 1
        j2 = mock.job(id="fixpoint-b")
        j2.task_groups[0].count = 1
        # enqueue both evals ATOMICALLY (one broker lock acquisition) so a
        # polling batch worker cannot dequeue one before the other exists
        # -- register_job enqueues each eval separately, which makes the
        # same-batch rendezvous (the thing under test) timing-dependent
        evs = []
        for j in (j1, j2):
            server.state.upsert_job(j)
            ev = Evaluation(id=generate_uuid(), namespace=j.namespace,
                            priority=j.priority, type=j.type,
                            triggered_by="job-register", job_id=j.id,
                            status="pending")
            evs.append(ev)
        server.state.upsert_evals(evs)
        server.broker.enqueue_all(evs)
        wait_until(lambda: len(committed_allocs(server, j1)) == 1
                   and len(committed_allocs(server, j2)) == 1,
                   msg="both jobs placed")
        a1 = committed_allocs(server, j1)[0]
        a2 = committed_allocs(server, j2)[0]
        assert a1.node_id != a2.node_id
        # the point of the fixpoint: the applier never saw a conflict
        assert server.planner.plans_rejected == 0
        snap = metrics.snapshot()
        assert snap["counters"].get(
            "nomad.solver.fixpoint_conflicts", 0) >= 1, \
            sorted(snap["counters"])
    finally:
        batch_mod.BARRIER_TIMEOUT_S = orig_timeout
        server.shutdown()


def test_solve_barrier_dispatch_exception_fans_out():
    """A dispatch failure must re-raise in EVERY blocked participant
    (VERDICT r2 weak #5) as DispatchFailed (the deadline layer's
    verdict), so each eval independently degrades to the host oracle
    via make_solve_hook instead of nacking."""
    import threading

    from nomad_tpu.solver import batch as batch_mod
    from nomad_tpu.solver import guard
    from nomad_tpu.solver.batch import SolveBarrier

    class BoomLane:
        def fuse_key(self):
            return ("boom",)

    guard._reset_for_tests()
    orig = batch_mod.fuse_and_solve
    batch_mod.fuse_and_solve = lambda lanes, use_mesh=True, **kw: (
        (_ for _ in ()).throw(RuntimeError("device exploded")))
    try:
        barrier = SolveBarrier(participants=3)
        errors = []

        def worker():
            try:
                barrier.solve(BoomLane())
            except guard.DispatchFailed as e:
                errors.append((e.kind, str(e.__cause__)))

        threads = [threading.Thread(target=worker) for _ in range(2)]
        for t in threads:
            t.start()
        barrier.done()      # third participant finished without solving
        for t in threads:
            t.join(10)
        assert errors == [("error", "device exploded")] * 2
        # the failure also counted toward the dispatch breaker
        assert guard.breaker_state()["consecutive_failures"] == 1
    finally:
        batch_mod.fuse_and_solve = orig
        guard._reset_for_tests()


def test_solve_barrier_straggler_timeout_dispatches_without_it():
    """If a participant neither arrives nor finishes within the timeout
    window, the waiting lanes dispatch anyway instead of wedging."""
    import threading
    import time as _time

    from nomad_tpu.solver import batch as batch_mod
    from nomad_tpu.solver.batch import SolveBarrier

    class Lane:
        def __init__(self, tag):
            self.tag = tag

        def fuse_key(self):
            return ("t",)

    dispatched = []
    orig_fuse = batch_mod.fuse_and_solve
    batch_mod.fuse_and_solve = lambda lanes, use_mesh=True, **kw: (
        dispatched.append([ln.tag for ln in lanes])
        or [("ok", ln.tag) for ln in lanes])
    orig_timeout = batch_mod.BARRIER_TIMEOUT_S
    batch_mod.BARRIER_TIMEOUT_S = 0.3
    orig_fix = batch_mod._cross_lane_fixpoint    # fake lanes/results
    batch_mod._cross_lane_fixpoint = lambda lanes, results, ledger: None
    try:
        # 3 participants; only 2 ever arrive -- the third is a straggler
        barrier = SolveBarrier(participants=3)
        results = {}

        def worker(tag):
            results[tag] = barrier.solve(Lane(tag))

        t0 = _time.time()
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in ("a", "b")]
        for t in threads:
            t.start()
        for t in threads:
            t.join(10)
        assert _time.time() - t0 < 5.0
        assert sorted(results) == ["a", "b"]
        assert results["a"] == ("ok", "a")
        assert dispatched and sorted(dispatched[0]) == ["a", "b"]
    finally:
        batch_mod.fuse_and_solve = orig_fuse
        batch_mod.BARRIER_TIMEOUT_S = orig_timeout
        batch_mod._cross_lane_fixpoint = orig_fix
