"""Crash-safe N-worker control plane (ISSUE 16, ROADMAP 2a): the
supervised scheduler pool (death/wedge detection, escalating-backoff
restarts, NOMAD_TPU_WORKER_SUPERVISE=0 kill switch), broker lease
exactly-once redelivery under worker crashes (incl. the replacement
racing the nack-timeout sweep), the stale-lease fence on plan
submission, poison-eval quarantine dead letters, cross-worker
group-commit serialization, and the whole-pool chaos drill built on
the ``worker.crash`` fault point.
"""
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.client import SimClient
from nomad_tpu.faultinject import faults
from nomad_tpu.server import Server
from nomad_tpu.server import worker as worker_mod
from nomad_tpu.server.broker import EvalBroker
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.server.worker import StaleEvalToken, WorkerPlanner
from nomad_tpu.structs import ALLOC_CLIENT_RUNNING, Plan

pytestmark = pytest.mark.chaos


def wait_until(cond, timeout=15.0, interval=0.02, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timeout waiting for {msg}")


def _counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def _fast_supervisor(monkeypatch, stall="1.0"):
    monkeypatch.setenv("NOMAD_TPU_WORKER_STALL_S", stall)
    monkeypatch.setenv("NOMAD_TPU_WORKER_CHECK_S", "0.05")
    monkeypatch.setenv("NOMAD_TPU_WORKER_RESTART_BASE_S", "0.05")
    monkeypatch.setenv("NOMAD_TPU_WORKER_RESTART_MAX_S", "0.3")


class _WedgedStandIn(threading.Thread):
    """A worker-shaped thread that is alive but makes no progress:
    ``last_progress`` frozen in the past, loop parked on an event.
    Planted into a pool slot to exercise the supervisor's stall
    detector without arming a global hang fault."""

    def __init__(self):
        super().__init__(daemon=True, name="wedged-standin")
        self.last_progress = time.monotonic() - 3600.0
        self.evals_processed = 0
        self.stop_called = False
        self._ev = threading.Event()

    def stop(self):
        self.stop_called = True
        self._ev.set()

    def run(self):
        self._ev.wait(60.0)


def _stop_worker(w, deadline_s=10.0):
    # joined in a loop: under the schedcheck controlled scheduler a
    # single timed join can return before the thread is observed dead
    w.stop()
    deadline = time.time() + deadline_s
    while w.is_alive() and time.time() < deadline:
        w.join(timeout=0.2)
    assert not w.is_alive()


def _running(server, job):
    return [a for a in server.state.allocs_by_job(job.namespace, job.id)
            if a.client_status == ALLOC_CLIENT_RUNNING
            and a.desired_status == "run"]


def _live_names(server, job):
    return sorted(a.name
                  for a in server.state.allocs_by_job(job.namespace,
                                                      job.id)
                  if not a.terminal_status())


def _slots(job, count):
    return sorted(f"{job.id}.{job.task_groups[0].name}[{i}]"
                  for i in range(count))


# ----------------------------------------------------------------------
# Supervisor: death detection + restart


def test_supervisor_restarts_dead_worker(monkeypatch):
    """An armed worker.crash kills one worker thread mid-eval; the
    supervisor detects the death and respawns the slot, and the
    orphaned eval redelivers through the nack timeout to a surviving
    worker -- placed exactly once."""
    _fast_supervisor(monkeypatch, stall="30")
    server = Server(num_workers=2, eval_batching=False,
                    heartbeat_ttl=60.0)
    server.broker.nack_timeout = 0.4
    server.start()
    clients = []
    try:
        for i in range(2):
            n = mock.node()
            n.id = f"wp-death-node-{i:04d}"
            c = SimClient(server, n)
            c.start()
            clients.append(c)
        wait_until(lambda: len(server.state.nodes()) == 2,
                   msg="nodes registered")

        faults.arm("worker.crash", "error", count=1)
        job = mock.job(id="wp-death-svc")
        job.task_groups[0].count = 2
        job.task_groups[0].tasks[0].config = {}
        server.register_job(job)

        wait_until(lambda: server.supervisor.deaths_detected >= 1,
                   msg="death detected")
        wait_until(lambda: server.supervisor.restarts_total >= 1
                   and len(server.workers) == 2
                   and all(w.is_alive() for w in server.workers),
                   msg="slot respawned")
        wait_until(lambda: len(_running(server, job)) == 2,
                   msg="2 running after crash")
        # exactly once despite the orphaned lease's redelivery
        assert _live_names(server, job) == _slots(job, 2)
    finally:
        faults.disarm_all()
        for c in clients:
            c.stop()
        server.shutdown()


def test_supervisor_restarts_wedged_worker(monkeypatch):
    """A worker thread that is alive but making no progress past
    NOMAD_TPU_WORKER_STALL_S is declared wedged: the supervisor stops
    it, abandons the thread, and respawns the slot."""
    _fast_supervisor(monkeypatch, stall="0.3")
    server = Server(num_workers=2, eval_batching=False,
                    heartbeat_ttl=60.0)
    server.start()
    standin = _WedgedStandIn()
    try:
        with server._leader_lock:
            _stop_worker(server.workers[0])
            standin.start()
            server.workers[0] = standin
        wait_until(lambda: server.supervisor.wedges_detected >= 1,
                   msg="wedge detected")
        wait_until(lambda: server.workers[0] is not standin
                   and server.workers[0].is_alive(),
                   msg="wedged slot respawned")
        assert standin.stop_called
        assert server.supervisor.restarts_total >= 1
    finally:
        standin.stop()
        server.shutdown()


def test_supervisor_backoff_escalates_and_caps(monkeypatch):
    """Consecutive restarts of one slot escalate the respawn hold
    min(base * 2**(n-1), max) -- the NodeFlapTracker shape -- so a
    crash-looping slot cannot burn CPU respawning."""
    monkeypatch.setenv("NOMAD_TPU_WORKER_RESTART_BASE_S", "0.1")
    monkeypatch.setenv("NOMAD_TPU_WORKER_RESTART_MAX_S", "0.35")
    server = Server(num_workers=1, eval_batching=False)
    sup = server.supervisor
    now = 100.0
    holds = []
    for _ in range(5):
        sup._schedule_restart_locked(0, now)
        holds.append(round(sup._pending[0] - now, 6))
    assert holds == [0.1, 0.2, 0.35, 0.35, 0.35]


def test_supervise_killswitch_is_true_noop(monkeypatch):
    """NOMAD_TPU_WORKER_SUPERVISE=0: no watcher thread exists, a dead
    worker stays dead (pre-supervision pool), and scheduling parity is
    preserved -- the surviving worker still places everything exactly
    once via nack-timeout redelivery."""
    monkeypatch.setenv("NOMAD_TPU_WORKER_SUPERVISE", "0")
    _fast_supervisor(monkeypatch, stall="0.3")
    server = Server(num_workers=2, eval_batching=False,
                    heartbeat_ttl=60.0)
    server.broker.nack_timeout = 0.4
    server.start()
    clients = []
    try:
        assert server.supervisor.enabled is False
        assert server.supervisor._thread is None
        assert not [t for t in threading.enumerate()
                    if t.name.startswith("worker-supervisor")]

        n = mock.node()
        n.id = "wp-ks-node-0000"
        c = SimClient(server, n)
        c.start()
        clients.append(c)
        wait_until(lambda: len(server.state.nodes()) == 1,
                   msg="node registered")

        faults.arm("worker.crash", "error", count=1)
        job = mock.job(id="wp-ks-svc")
        job.task_groups[0].count = 2
        job.task_groups[0].tasks[0].config = {}
        server.register_job(job)

        wait_until(lambda: any(not w.is_alive()
                               for w in server.workers),
                   msg="one worker dead")
        wait_until(lambda: len(_running(server, job)) == 2,
                   timeout=20.0, msg="2 running on surviving worker")
        # no watcher thread exists (asserted above), so nothing could
        # have restarted the slot during the whole placement window
        assert server.supervisor.restarts_total == 0
        assert server.supervisor.deaths_detected == 0
        assert sum(1 for w in server.workers if w.is_alive()) == 1
        assert _live_names(server, job) == _slots(job, 2)
    finally:
        faults.disarm_all()
        for c in clients:
            c.stop()
        server.shutdown()


# ----------------------------------------------------------------------
# Broker lease: exactly-once redelivery + stale-lease fence


def test_lease_redelivery_replacement_races_nack_sweep():
    """A crashed worker's lease expires; the replacement's dequeue
    races the nack-timeout sweep.  The eval redelivers EXACTLY once
    (one fresh lease, per-token uniqueness): the dead worker's token
    goes stale, the replacement's token is the outstanding one, and a
    stale ack bounces while the fresh ack lands."""
    b = EvalBroker(nack_timeout=0.05)
    b.set_enabled(True)
    try:
        ev = mock.evaluation(job_id="wp-lease-job")
        ev.id = "wp-lease-eval-0001"
        b.enqueue(ev)
        got, tok1 = b.dequeue(["service"], timeout=2.0)
        assert got is not None and got.id == ev.id
        lease_deadline = b._unack[ev.id][2]
        wait_until(lambda: time.time() > lease_deadline,
                   msg="lease lapsed")
        # the replacement worker's dequeue runs the expiry sweep and
        # takes the redelivery; widen the window so the SECOND lease
        # cannot itself lapse mid-assert
        b.nack_timeout = 30.0
        got2, tok2 = b.dequeue(["service"], timeout=2.0)
        assert got2 is not None and got2.id == ev.id
        assert tok2 != tok1
        # exactly once: no third delivery while the fresh lease holds
        none, _ = b.dequeue(["service"], timeout=0.2)
        assert none is None
        assert b.token_outstanding(ev.id, tok1) is False
        assert b.token_outstanding(ev.id, tok2) is True
        assert b.ack(ev.id, tok1) is not None       # stale ack bounces
        assert b.ack(ev.id, tok2) is None           # fresh ack lands
    finally:
        b.shutdown()


def test_stale_lease_fence_rejects_zombie_plan():
    """A wedged-then-woken worker submitting on a lapsed lease must
    die at the fence (StaleEvalToken + nomad.plan.stale_token_rejected)
    BEFORE the plan reaches the applier -- redelivery owns the eval."""
    b = EvalBroker(nack_timeout=0.05)
    b.set_enabled(True)
    try:
        ev = mock.evaluation(job_id="wp-fence-job")
        ev.id = "wp-fence-eval-0001"
        b.enqueue(ev)
        got, tok1 = b.dequeue(["service"], timeout=2.0)
        assert got is not None
        lease_deadline = b._unack[ev.id][2]
        wait_until(lambda: time.time() > lease_deadline,
                   msg="lease lapsed")
        b.nack_timeout = 30.0
        got2, tok2 = b.dequeue(["service"], timeout=2.0)
        assert got2 is not None and tok2 != tok1

        class _Shim:    # the fence consults only server.broker
            pass
        shim = _Shim()
        shim.broker = b
        zombie = WorkerPlanner(shim, tok1, eval_id=ev.id,
                               worker_name="zombie-worker")
        before = _counter("nomad.plan.stale_token_rejected")
        with pytest.raises(StaleEvalToken):
            zombie.submit_plan(Plan(eval_id=ev.id, job=mock.job()))
        assert _counter("nomad.plan.stale_token_rejected") == before + 1
        # the live delivery is untouched by the rejected zombie
        assert b.token_outstanding(ev.id, tok2) is True
        assert b.ack(ev.id, tok2) is None
    finally:
        b.shutdown()


# ----------------------------------------------------------------------
# Poison-eval quarantine


def _burn_cycles(b, ev_id, until, deadline_s=15.0):
    """Dequeue+nack the eval until ``until()`` holds (each
    delivery-limit exhaustion is one poison strike; the delayed
    watcher re-admits the failed queue between cycles)."""
    deadline = time.time() + deadline_s
    while time.time() < deadline and not until():
        got, tok = b.dequeue(["service"], timeout=0.25)
        if got is not None:
            assert got.id == ev_id
            b.nack(got.id, tok)
    return until()


def test_poison_eval_quarantined_then_released(monkeypatch):
    """An eval that exhausts its delivery limit NOMAD_TPU_POISON_AFTER
    times dead-letters: out of every queue, never auto-retried, listed
    in quarantine_state, and re-admitted with a clean slate only by
    operator release."""
    monkeypatch.setenv("NOMAD_TPU_POISON_AFTER", "2")
    b = EvalBroker(nack_timeout=0.05, delivery_limit=2)
    b.set_enabled(True)
    try:
        ev = mock.evaluation(job_id="wp-poison-job")
        ev.id = "wp-poison-eval-001"
        b.enqueue(ev)
        assert _burn_cycles(
            b, ev.id, lambda: b.quarantine_state()["total"] == 1), \
            "poison eval never quarantined"
        qs = b.quarantine_state()
        assert [e["id"] for e in qs["evals"]] == [ev.id]
        assert qs["evals"][0]["strikes"] == 2
        assert qs["evals"][0]["job_id"] == "wp-poison-job"
        assert b.stats()["total_quarantined"] == 1

        # dead-lettered means GONE from the queues: a re-enqueue of the
        # same eval is ignored and nothing dequeues
        b.enqueue(ev)
        got, _ = b.dequeue(["service"], timeout=0.3)
        assert got is None

        released = b.release_quarantined(ev.id)
        assert released == [ev.id]
        assert b.quarantine_state()["total"] == 0
        got, tok = b.dequeue(["service"], timeout=2.0)
        assert got is not None and got.id == ev.id
        assert b.ack(ev.id, tok) is None    # clean slate: ack works
        assert not b._poison_strikes
    finally:
        b.shutdown()


def test_poison_after_zero_disables_quarantine(monkeypatch):
    """NOMAD_TPU_POISON_AFTER=0 restores today's infinite retry: the
    eval keeps cycling through the failed queue, never dead-lettered."""
    monkeypatch.setenv("NOMAD_TPU_POISON_AFTER", "0")
    b = EvalBroker(nack_timeout=0.05, delivery_limit=2)
    b.set_enabled(True)
    try:
        ev = mock.evaluation(job_id="wp-nopoison-job")
        ev.id = "wp-nopoison-eval-01"
        b.enqueue(ev)
        strikes = lambda: b._poison_strikes.get(ev.id, 0)  # noqa: E731
        assert _burn_cycles(b, ev.id, lambda: strikes() >= 3), \
            "eval stopped cycling"
        assert b.quarantine_state()["total"] == 0
        # still retryable: it comes around again
        got, tok = b.dequeue(["service"], timeout=2.0)
        assert got is not None and got.id == ev.id
        assert b.ack(ev.id, tok) is None
    finally:
        b.shutdown()


# ----------------------------------------------------------------------
# Cross-worker group commit


def test_cross_worker_conflict_serialized(monkeypatch):
    """Node-overlapping plans from DIFFERENT pool workers serialize
    deterministically in queue order, counted in
    nomad.plan.cross_worker_serialized (same-submitter overlaps keep
    the old batch_conflict counter); both still commit exactly once."""
    from nomad_tpu.server.plan_apply import Planner
    from nomad_tpu.state import StateStore
    from nomad_tpu.structs import (
        AllocatedResources, AllocatedSharedResources,
        AllocatedTaskResources, Allocation,
    )
    monkeypatch.setenv("NOMAD_TPU_PLAN_BATCH_WINDOW_MS", "500")

    store = StateStore()
    nodes = []
    for i in range(4):
        n = mock.node()
        n.id = f"wp-xw-node-{i:04d}"
        n.compute_class()
        store.upsert_node(n)
        nodes.append(n)

    def plan_on(node_list, k):
        job = mock.job(id=f"wp-xw-job-{k}")
        plan = Plan(eval_id=f"wp-xw-eval-{k:012d}"[-36:], priority=50,
                    job=job)
        for j, node in enumerate(node_list):
            plan.append_alloc(Allocation(
                id=f"wp-xw-{k}-{j}-{'0' * 24}"[:36],
                name=f"{job.id}.web[0]", job_id=job.id, job=job,
                task_group="web", node_id=node.id,
                allocated_resources=AllocatedResources(
                    tasks={"web": AllocatedTaskResources(
                        cpu_shares=100, memory_mb=64)},
                    shared=AllocatedSharedResources(disk_mb=10))))
        return plan

    planner = Planner(store)
    try:
        before = _counter("nomad.plan.cross_worker_serialized")
        plans = [plan_on([nodes[0], nodes[1]], 0),   # worker A
                 plan_on([nodes[1], nodes[2]], 1),   # worker B: overlap
                 plan_on([nodes[3]], 2)]             # worker A: disjoint
        workers = ["pool-worker-a", "pool-worker-b", "pool-worker-a"]
        results = [None] * 3
        errors = [None] * 3
        planner.expect_plans(3)

        def run(i):
            try:
                results[i] = planner.apply(plans[i], worker=workers[i])
            except BaseException as e:  # noqa: BLE001
                errors[i] = e

        threads = [threading.Thread(target=run, args=(i,))
                   for i in range(3)]
        for i, t in enumerate(threads):
            t.start()
            deadline = time.time() + 5
            while time.time() < deadline:
                with planner._cv:
                    if planner._seq >= i + 1:
                        break
                time.sleep(0.001)
        for t in threads:
            t.join(20)
        assert not any(errors), errors
        ra, rb, rc = results
        assert not ra.rejected_nodes and not rb.rejected_nodes
        # worker B's overlapping plan fell out of A's group and
        # committed strictly after -- deterministic queue order
        assert ra.alloc_index < rb.alloc_index
        assert _counter("nomad.plan.cross_worker_serialized") > before
        # backoff escalation state resets once a group commits clean
        assert planner._conflict_streak == 0
        assert len(store.allocs()) == 5     # every alloc exactly once
    finally:
        planner.shutdown()


# ----------------------------------------------------------------------
# benchkit.run_worker_scaling, shrunk


def test_run_worker_scaling_smoke():
    """Shrunk benchkit.run_worker_scaling: both pool sizes place the
    whole workload at fold parity 0 and report a positive rate."""
    from nomad_tpu.benchkit import run_worker_scaling
    out = run_worker_scaling(pool_sizes=(1, 2), n_nodes=16, jobs=3,
                             per_eval=8, timeout_s=60.0)
    assert out["pool_sizes"] == [1, 2]
    assert out["truncated"] is False
    assert out["parity_mismatch"] == 0
    assert all(v > 0 for v in out["placements_per_sec"].values())
    assert set(out["placements_per_sec"]) == {1, 2}


# ----------------------------------------------------------------------
# Whole-pool chaos drill (worker.crash + wedge + poison, ISSUE 16 proof)


class _PoisonSched:
    """Scheduler wrapper that raises for one marked job's evals --
    every delivery nacks, driving the eval through delivery-limit
    exhaustion into quarantine while all other evals run normally."""

    def __init__(self, inner, poison_job_id):
        self._inner = inner
        self._poison = poison_job_id

    def process(self, ev):
        if ev.job_id == self._poison:
            raise RuntimeError("poison eval: scheduler always crashes")
        return self._inner.process(ev)


def test_worker_kill_chaos_drill(monkeypatch):
    """The ISSUE 16 proof drill: kill 25% of the pool mid-storm
    (worker.crash), wedge one worker past the stall threshold, and
    feed one poison eval.  Asserts: every placement exactly once
    (name-slot accounting, no double previous_allocation), fold parity
    0, the quarantine contains exactly the poison eval, and the
    supervisor healed the pool back to full strength."""
    _fast_supervisor(monkeypatch, stall="1.0")
    monkeypatch.setenv("NOMAD_TPU_POISON_AFTER", "2")
    poison_job_id = "wp-poison-svc"
    real_factory = worker_mod.new_scheduler
    monkeypatch.setattr(
        worker_mod, "new_scheduler",
        lambda name, snapshot, planner, **kw: _PoisonSched(
            real_factory(name, snapshot, planner, **kw),
            poison_job_id))

    server = Server(num_workers=4, eval_batching=False,
                    heartbeat_ttl=60.0)
    server.broker.nack_timeout = 0.4
    server.broker.delivery_limit = 2
    server.start()
    clients = []
    standin = _WedgedStandIn()
    try:
        for i in range(8):
            n = mock.node()
            n.id = f"wp-drill-node-{i:04d}"
            c = SimClient(server, n)
            c.start()
            clients.append(c)
        wait_until(lambda: len(server.state.nodes()) == 8,
                   msg="fleet registered")

        # storm: 12 placements through the healthy pool first
        storm = mock.job(id="wp-storm-svc")
        storm.task_groups[0].count = 12
        storm.task_groups[0].tasks[0].config = {}
        server.register_job(storm)
        wait_until(lambda: len(_running(server, storm)) == 12,
                   timeout=20.0, msg="12 running pre-chaos")

        # kill 25% of the 4-worker pool mid-traffic
        faults.arm("worker.crash", "error", count=1)
        churn = mock.job(id="wp-churn-svc")
        churn.task_groups[0].count = 6
        churn.task_groups[0].tasks[0].config = {}
        server.register_job(churn)
        wait_until(lambda: server.supervisor.deaths_detected >= 1,
                   msg="crash detected")

        # wedge one surviving worker (alive, zero progress)
        with server._leader_lock:
            alive = [i for i, w in enumerate(server.workers)
                     if w.is_alive() and not isinstance(
                         w, _WedgedStandIn)]
            slot = alive[0]
            _stop_worker(server.workers[slot])
            standin.start()
            server.workers[slot] = standin
        wait_until(lambda: server.supervisor.wedges_detected >= 1,
                   msg="wedge detected")

        # one poison eval: its scheduler raises on every delivery
        poison = mock.job(id=poison_job_id)
        poison.task_groups[0].count = 1
        server.register_job(poison)
        wait_until(
            lambda: server.broker.quarantine_state()["total"] >= 1,
            timeout=25.0, msg="poison eval quarantined")

        # pool self-heals to full strength and keeps scheduling
        wait_until(lambda: len(server.workers) == 4
                   and all(w.is_alive() for w in server.workers)
                   and not any(isinstance(w, _WedgedStandIn)
                               for w in server.workers),
                   timeout=20.0, msg="pool healed")
        wait_until(lambda: len(_running(server, churn)) == 6,
                   timeout=25.0, msg="6 running post-chaos")

        # quarantine contains EXACTLY the poison eval
        qs = server.broker.quarantine_state()
        assert qs["total"] == 1, qs
        assert qs["evals"][0]["job_id"] == poison_job_id

        # exactly-once placement despite crash + wedge + redelivery:
        # every name slot holds one live alloc, no lost alloc was
        # double-replaced
        assert _live_names(server, storm) == _slots(storm, 12)
        assert _live_names(server, churn) == _slots(churn, 6)
        for job in (storm, churn):
            allocs = server.state.allocs_by_job(job.namespace, job.id)
            by_prev = {}
            for a in allocs:
                if not a.terminal_status() and a.previous_allocation:
                    by_prev.setdefault(a.previous_allocation,
                                       []).append(a)
            assert all(len(v) <= 1 for v in by_prev.values()), by_prev

        # fold parity: the incremental memos agree with a full refold
        assert server.state.alloc_table.fold_parity_mismatch() == 0

        assert server.supervisor.restarts_total >= 2
        assert server.supervisor.deaths_detected >= 1
        assert server.supervisor.wedges_detected >= 1
    finally:
        faults.disarm_all()
        standin.stop()
        for c in clients:
            c.stop()
        server.shutdown()
