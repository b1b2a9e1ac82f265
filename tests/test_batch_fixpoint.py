"""The cross-lane fixpoint sees the other barrier's plans in flight
(ISSUE 34): what a barrier has handed to its evals is booked
(server/inflight.py), the server's other barrier charges it until the
commit's index says a lane's usage holds it, and every exit that commits
nothing releases it.

Two barriers over one store, tiny shapes, the CPU backend. The tight
node fits a stated number of the mock job's allocs (500 MHz, 256 MB)
and scores best for every eval, so both barriers' lanes choose it.
"""
import itertools
import sys
import threading
import time

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import AllocPlaceResult
from nomad_tpu.server.inflight import InflightBookings
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.solver import guard
from nomad_tpu.solver.batch import SolveBarrier
from nomad_tpu.solver.service import (
    PackedLane, TpuPlacementService, dispatch_lane)
from nomad_tpu.structs import Plan, PlanResult

NEED = (500.0, 256.0)
# node ids are a world's own: the pack caches key a node matrix by its
# ids and the node table's index, which two fresh stores share
_world = itertools.count()


@pytest.fixture(autouse=True)
def clean():
    guard._reset_for_tests()
    metrics.reset()
    yield
    guard._reset_for_tests()


def counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def deduction(reg, owner, node_id, usage_index):
    """What ``owner``'s fixpoint, starting now, would be charged on one
    node at one usage index."""
    view = reg.foreign(owner)
    return None if view is None else view.deduction(node_id, usage_index)


def build_world(tight_fits, spares=1):
    """One tight node that holds ``tight_fits`` allocs and no more, and
    roomy spares; the store's commits settle ``reg``'s bookings as a
    server's do."""
    h = Harness()
    reg = InflightBookings()
    h.state.plan_commit_hook = reg.settle
    nodes = []
    w = next(_world)
    for i in range(spares + 1):
        n = mock.node()
        n.id = f"fix{w}-node-tight" if i == 0 else f"fix{w}-node-spare-{i}"
        if i == 0:
            n.node_resources.cpu.cpu_shares = int(NEED[0] * tight_fits + 100)
            n.node_resources.memory.memory_mb = int(
                NEED[1] * tight_fits + 100)
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    return h, nodes, reg


def pack_lane(h, nodes, tag, count=1):
    job = mock.job(id=f"fix-job-{tag}")
    job.task_groups[0].count = count
    tg = job.task_groups[0]
    plan = Plan(eval_id=f"fix-eval-{tag:>027}", priority=50, job=job)
    ctx = EvalContext(h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(count)]
    svc = TpuPlacementService(ctx, job, batch_mode=False, spread_alg=False)
    lane = svc.pack(tg, places, nodes)
    assert lane is not None
    return lane


def solve(barrier, lanes):
    out = {}

    def worker(i):
        out[i] = barrier.solve(lanes[i])

    threads = [threading.Thread(target=worker, args=(i,))
               for i in range(len(lanes))]
    for t in threads:
        t.start()
    for t in threads:
        t.join(60)
        assert not t.is_alive()
    assert sorted(out) == list(range(len(lanes)))
    return [out[i] for i in range(len(lanes))]


def placed_on(lane, res):
    order = np.asarray(lane.order)
    return [lane.nodes[order[int(p)]].id for p in np.asarray(res[0])
            if p >= 0]


def commit(h, lane, res):
    """The lane's placements as a committed plan result; returns the
    commit's index."""
    result = PlanResult()
    by_id = {n.id: n for n in lane.nodes}
    for k, nid in enumerate(placed_on(lane, res)):
        a = mock.alloc_for(lane.service.job, by_id[nid], index=k)
        a.eval_id = lane.service.ctx.plan.eval_id
        result.node_allocation.setdefault(nid, []).append(a)
    return h.state.upsert_plan_results(result)


# ---------------------------------------------------------------------
# two barriers over one store


@pytest.mark.parametrize("tight_fits,moves", [(1, True), (2, False)],
                         ids=["booking_fills_node", "booking_leaves_room"])
def test_second_barrier_charges_delivered_uncommitted_placements(
        tight_fits, moves):
    """Barrier A's eval holds a placement on the tight node, delivered
    and not committed. Barrier B's lane, packed from the same committed
    state, moves off the node when A's booking fills it and stays when
    it does not."""
    h, nodes, reg = build_world(tight_fits)
    TIGHT = nodes[0].id
    lane_a, lane_b = pack_lane(h, nodes, "a"), pack_lane(h, nodes, "b")
    solo_b = dispatch_lane(lane_b)
    assert placed_on(lane_b, solo_b) == [TIGHT]

    a = SolveBarrier(participants=1, bookings=reg)
    res_a, = solve(a, [lane_a])
    assert placed_on(lane_a, res_a) == [TIGHT]
    assert reg.state()["unsettled_evals"] == 1
    assert counter("nomad.solver.fixpoint_cross_batch_conflicts") == 0

    b = SolveBarrier(participants=1, bookings=reg)
    res_b, = solve(b, [lane_b])
    if moves:
        assert placed_on(lane_b, res_b) != [TIGHT]
        assert len(placed_on(lane_b, res_b)) == 1
        assert counter("nomad.solver.fixpoint_cross_batch_conflicts") == 1
        assert counter("nomad.solver.fixpoint_conflicts") == 1
    else:
        assert (np.asarray(res_b[0]) == np.asarray(solo_b[0])).all()
        assert (np.asarray(res_b[1]) == np.asarray(solo_b[1])).all()
        assert counter("nomad.solver.fixpoint_cross_batch_conflicts") == 0
    assert reg.state()["unsettled_evals"] == 2
    for eval_id in (lane_a.service.ctx.plan.eval_id,
                    lane_b.service.ctx.plan.eval_id):
        reg.release(eval_id)
    assert reg.state()["unsettled_evals"] == 0
    a.retire()
    b.retire()
    assert reg.state() == {"unsettled_evals": 0, "settled_bookings": 0,
                           "booked_nodes": 0, "open_barriers": 0}


@pytest.mark.parametrize("packed", ["before_commit", "after_commit"])
def test_booking_is_charged_exactly_once_by_index(packed):
    """A's alloc on the tight node commits at index c. A lane of B
    folded below c lacks it: the booking, settled above the lane's
    index, is charged, and on a node that fits one the lane moves. A
    lane folded at or above c holds the alloc in its usage: the booking
    is not charged again, and on a node that fits two the lane takes
    the last slot as it would alone."""
    h, nodes, reg = build_world(
        tight_fits=1 if packed == "before_commit" else 2)
    TIGHT = nodes[0].id
    lane_a = pack_lane(h, nodes, "a")
    a = SolveBarrier(participants=1, bookings=reg)
    # B is open while A's plan commits, so the settled booking stays
    b = SolveBarrier(participants=1, bookings=reg)
    res_a, = solve(a, [lane_a])
    if packed == "before_commit":
        lane_b = pack_lane(h, nodes, "b")
    c = commit(h, lane_a, res_a)
    assert reg.state()["unsettled_evals"] == 0
    assert reg.state()["settled_bookings"] == 1
    if packed == "after_commit":
        lane_b = pack_lane(h, nodes, "b")
    assert (lane_b.usage_index >= c) == (packed == "after_commit")
    solo_b = dispatch_lane(lane_b)
    assert placed_on(lane_b, solo_b) == [TIGHT]
    # by index alone, and the same when asked again: a read moves nothing
    assert deduction(reg, b, TIGHT, c) is None
    for _ in range(2):
        assert deduction(reg, b, TIGHT, c - 1)[:2] == list(NEED)
    assert deduction(reg, a, TIGHT, c - 1) is None       # never its own

    res_b, = solve(b, [lane_b])
    if packed == "before_commit":
        assert placed_on(lane_b, res_b) == [nodes[1].id]
        assert counter("nomad.solver.fixpoint_cross_batch_conflicts") == 1
    else:
        for k in range(3):
            assert (np.asarray(res_b[k]) == np.asarray(solo_b[k])).all()
        assert counter("nomad.solver.fixpoint_cross_batch_conflicts") == 0
    a.retire()
    b.retire()
    assert reg.state()["booked_nodes"] == 0


@pytest.mark.parametrize("shape", ["lone_lane", "lone_barrier"])
def test_nothing_in_flight_solves_as_without_bookings(shape):
    """With no other barrier's booking a barrier gives, bit for bit,
    what one without bookings gives (the parent's): a lone lane its
    solo dispatch, several lanes the same fixpoint."""
    h, nodes, reg = build_world(tight_fits=2, spares=6)
    n = 1 if shape == "lone_lane" else 3
    lanes = [pack_lane(h, nodes, f"p{i}", count=3) for i in range(n)]
    plain = solve(SolveBarrier(participants=n), lanes)
    booked = solve(SolveBarrier(participants=n, bookings=reg), lanes)
    for got, want in zip(booked, plain):
        for k in range(3):
            assert (np.asarray(got[k]) == np.asarray(want[k])).all()
    if shape == "lone_lane":
        solo = dispatch_lane(lanes[0])
        assert (np.asarray(booked[0][0]) == np.asarray(solo[0])).all()
        assert counter("nomad.solver.fixpoint_dispatches") == 0
    assert counter("nomad.solver.fixpoint_cross_batch_conflicts") == 0
    # and it booked what it placed
    assert reg.state()["unsettled_evals"] == n


def test_consumer_only_lane_books_and_is_never_resolved(monkeypatch):
    """A lane the wave kernel cannot re-solve (a whole-axis lane) is
    charged like any other: its placement over the other barrier's
    booking stays in the plan for the applier, uncharged and unbooked;
    the rest of it is booked."""
    monkeypatch.setattr(PackedLane, "_wavefront_check", lambda self: False)
    h, nodes, reg = build_world(tight_fits=1)
    TIGHT = nodes[0].id
    lane_a = pack_lane(h, nodes, "a")
    lane_b = pack_lane(h, nodes, "b", count=2)
    assert not lane_b.wavefront_ok()
    solo_b = dispatch_lane(lane_b)
    assert placed_on(lane_b, solo_b)[0] == TIGHT

    a = SolveBarrier(participants=1, bookings=reg)
    solve(a, [lane_a])
    b = SolveBarrier(participants=1, bookings=reg)
    res_b, = solve(b, [lane_b])
    assert (np.asarray(res_b[0]) == np.asarray(solo_b[0])).all()
    assert counter("nomad.solver.fixpoint_unresolvable") == 1
    assert counter("nomad.solver.fixpoint_cross_batch_conflicts") == 1
    assert counter("nomad.solver.fixpoint_dispatches") == 0
    # A reads B's booking: the spare node's placement, not the tight one
    other = placed_on(lane_b, res_b)[1]
    assert deduction(reg, a, TIGHT, 0) is None
    assert deduction(reg, a, other, 0) == [NEED[0], NEED[1],
                                          deduction(reg, a, other, 0)[2], 0]


def test_no_ledger_entry_for_a_node_no_lane_was_charged_on():
    """A ledger entry is one lane's view of a node, kept for the lanes
    after it. The other barrier's bookings are laid over a re-solve's
    own usage and make no entry: one made from this lane's usage for a
    node it never chose would stand in for a later lane's newer usage
    with nothing in the node's history to explain the move it causes."""
    from nomad_tpu.server.inflight import ForeignView
    from nomad_tpu.solver.batch import _cross_lane_fixpoint

    h, nodes, _reg = build_world(tight_fits=2, spares=3)
    lane = pack_lane(h, nodes, "b")
    solo = dispatch_lane(lane)
    first, = placed_on(lane, solo)
    cap = next(n for n in nodes if n.id == first).node_resources
    fill = (float(cap.cpu.cpu_shares), float(cap.memory.memory_mb), 0.0, 0)

    def moved_to(extra):
        booked = {first: [(fill, None)]}
        booked.update({nid: [((0.0, 0.0, 0.0, 0), None)] for nid in extra})
        results, ledger = [tuple(np.array(x) for x in solo)], {}
        _cross_lane_fixpoint([lane], results, ledger, ForeignView(booked))
        return placed_on(lane, results[0]), ledger

    (second,), ledger = moved_to(())
    assert second != first and set(ledger) == {first, second}
    bystanders = [n.id for n in nodes if n.id not in (first, second)]
    (again,), ledger = moved_to(bystanders)
    assert again == second
    assert set(ledger) == {first, second}


# ---------------------------------------------------------------------
# every exit releases


def booked_pair():
    """A's eval booked on the tight node, which fits one, and on the
    spare; B open."""
    h, nodes, reg = build_world(tight_fits=1)
    TIGHT = nodes[0].id
    lane_a = pack_lane(h, nodes, "a", count=2)
    a = SolveBarrier(participants=1, bookings=reg)
    b = SolveBarrier(participants=1, bookings=reg)
    res_a, = solve(a, [lane_a])
    spots = placed_on(lane_a, res_a)
    assert spots[0] == TIGHT and spots[1] != TIGHT
    assert reg.state()["unsettled_evals"] == 1
    return h, reg, a, b, lane_a, res_a, spots


class _Broker:
    def __init__(self, outstanding=True):
        self.outstanding = outstanding
        self.nacked = []

    def token_outstanding(self, eval_id, token):
        return self.outstanding

    def ack(self, eval_id, token):
        return None

    def nack(self, eval_id, token):
        self.nacked.append(eval_id)


class _Server:
    """What WorkerPlanner and BatchWorker touch of a server."""
    logger = None

    def __init__(self, h, reg, apply):
        self.state, self.inflight = h.state, reg
        self.broker = _Broker()
        self.planner = type("P", (), {"apply": staticmethod(apply)})()

    def on_plan_result(self, plan, result):
        pass


def exit_refused_in_part(h, reg, a, lane_a, res_a, spots):
    """The applier commits the spare's placement and refuses the tight
    node's: the one takes the commit's index, the other goes at once."""
    result = PlanResult()
    alloc = mock.alloc_for(
        lane_a.service.job,
        next(n for n in lane_a.nodes if n.id == spots[1]))
    alloc.eval_id = lane_a.service.ctx.plan.eval_id
    result.node_allocation[spots[1]] = [alloc]
    result.rejected_nodes = [spots[0]]
    h.state.upsert_plan_results(result)
    return {spots[1]}


def exit_refused_whole(h, reg, a, lane_a, res_a, spots):
    from nomad_tpu.server.worker import WorkerPlanner
    plan = lane_a.service.ctx.plan
    server = _Server(h, reg, lambda plan, worker=None: PlanResult(
        rejected_nodes=list(spots)))
    WorkerPlanner(server, "tok", eval_id=plan.eval_id).submit_plan(plan)
    return set()


def exit_submit_raises(h, reg, a, lane_a, res_a, spots):
    from nomad_tpu.server.worker import WorkerPlanner
    plan = lane_a.service.ctx.plan

    def boom(plan, worker=None):
        raise RuntimeError("planner is shut down")
    server = _Server(h, reg, boom)
    with pytest.raises(RuntimeError):
        WorkerPlanner(server, "tok", eval_id=plan.eval_id).submit_plan(plan)
    return set()


def _run_one_raising(exc, h, reg, a, lane_a, monkeypatch):
    """BatchWorker._run_one around a scheduler that ends in ``exc``."""
    from nomad_tpu.server import worker as worker_mod
    from nomad_tpu.structs import Evaluation

    def invoke(server, ev, token, **kw):
        raise exc
    monkeypatch.setattr(worker_mod, "invoke_scheduler", invoke)
    server = _Server(h, reg, None)
    w = worker_mod.BatchWorker(server, 0, width=1)
    ev = Evaluation(id=lane_a.service.ctx.plan.eval_id, namespace="default",
                    job_id="fix-job-a", priority=50, type="service",
                    triggered_by="job-register", status="pending")
    done = []
    barrier = type("B", (), {"done": lambda self: done.append(1)})()
    w._run_one(ev, "tok", barrier, None)
    assert done == [1] and server.broker.nacked == [ev.id]
    return set()


def exit_stale_token(h, reg, a, lane_a, res_a, spots, monkeypatch):
    from nomad_tpu.server.worker import StaleEvalToken
    return _run_one_raising(StaleEvalToken("lease lapsed"), h, reg, a,
                            lane_a, monkeypatch)


def exit_nack(h, reg, a, lane_a, res_a, spots, monkeypatch):
    return _run_one_raising(RuntimeError("scheduler failed"), h, reg, a,
                            lane_a, monkeypatch)


def exit_dispatch_error(h, reg, a, lane_a, res_a, spots, monkeypatch):
    """A later generation's dispatch fails (the eval's second task
    group): the eval nacks, and what the first generation booked goes."""
    return _run_one_raising(guard.DispatchFailed("error", "boom"), h, reg,
                            a, lane_a, monkeypatch)


def exit_worker_death(h, reg, a, lane_a, res_a, spots):
    """The supervisor finds the batch worker's thread gone: its barrier
    retires with it."""
    from nomad_tpu.server.core import WorkerSupervisor
    worker = type("W", (), {"barrier": a, "name": "batch-worker-0",
                            "is_alive": lambda self: False})()
    active = threading.Event()
    active.set()
    server = type("S", (), {"workers": [worker], "_leader_active": active,
                            "_leader_lock": threading.Lock()})()
    sup = WorkerSupervisor(server)
    sup._check_once()
    assert sup.deaths_detected == 1
    return set()


def exit_leadership_lost(h, reg, a, lane_a, res_a, spots):
    from nomad_tpu.server import Server
    server = Server(num_workers=1, eval_batching=True)
    try:
        server.start()
        server.inflight = reg
        server.revoke_leadership()
    finally:
        server.shutdown()
    return set()


@pytest.mark.parametrize("leave", [
    exit_refused_in_part, exit_refused_whole, exit_submit_raises,
    exit_stale_token, exit_nack, exit_dispatch_error, exit_worker_death,
    exit_leadership_lost], ids=lambda f: f.__name__[5:])
def test_every_exit_releases_the_booking(leave, monkeypatch):
    """After each way out, `inflight_bookings` reads 0 and B's view of
    the nodes is as before the booking, but for what committed (which B,
    folded below the commit, still has to be charged)."""
    h, reg, a, b, lane_a, res_a, spots = booked_pair()
    args = (h, reg, a, lane_a, res_a, spots)
    if "monkeypatch" in leave.__code__.co_varnames[
            :leave.__code__.co_argcount]:
        args += (monkeypatch,)
    before = h.state.latest_index()
    committed = leave(*args)
    assert reg.state()["unsettled_evals"] == 0
    gauge = metrics.snapshot()["gauges"]["nomad.solver.inflight_bookings"]
    assert gauge["max"] == 1.0 and gauge["min"] == 0.0
    for nid in spots:
        got = deduction(reg, b, nid, before)
        if nid in committed:
            assert got[:2] == list(NEED)
            assert deduction(reg, b, nid, h.state.latest_index()) is None
        else:
            assert got is None
    a.retire()
    b.retire()
    assert reg.state()["booked_nodes"] == 0


def test_settled_booking_outlives_only_the_barriers_open_at_its_commit():
    h, nodes, reg = build_world(tight_fits=2)
    TIGHT = nodes[0].id
    lane_a = pack_lane(h, nodes, "a")
    a = SolveBarrier(participants=1, bookings=reg)
    early = SolveBarrier(participants=1, bookings=reg)
    res_a, = solve(a, [lane_a])
    c = commit(h, lane_a, res_a)
    late = SolveBarrier(participants=1, bookings=reg)
    assert deduction(reg, early, TIGHT, c - 1)[:2] == list(NEED)
    a.retire()
    assert reg.state()["settled_bookings"] == 1     # `early` may need it
    early.retire()
    assert reg.state()["settled_bookings"] == 0     # `late` folds above c
    assert deduction(reg, late, TIGHT, c - 1) is None
    # a retired barrier books nothing (an abandoned worker's late wake)
    reg.book(early, "fix-eval-zombie", {TIGHT: [1.0, 1.0, 1.0, 0]})
    assert reg.state()["booked_nodes"] == 0
    late.retire()


def test_registry_survives_concurrent_book_settle_release():
    """More threads than cores, a short switch interval: every eval's
    bookings end settled or released, none lost or left."""
    reg = InflightBookings()
    owners = [object() for _ in range(4)]
    for o in owners:
        reg.open_view(o)
    errors = []
    index = [0]
    index_lock = threading.Lock()

    def worker(w):
        try:
            for k in range(150):
                eval_id = f"stress-{w}-{k}"
                owner = owners[w % len(owners)]
                nodes = {f"n{(w + k + j) % 13}": [1.0, 1.0, 1.0, 0]
                         for j in range(3)}
                with reg.fixpoint_lock:
                    reg.book(owner, eval_id, nodes)
                for o in owners:
                    deduction(reg, o, f"n{k % 13}", k)
                if k % 3:
                    result = PlanResult()
                    alloc = type("A", (), {"eval_id": eval_id})()
                    result.node_allocation[next(iter(nodes))] = [alloc]
                    with index_lock:
                        index[0] += 1
                        reg.settle((result,), index[0])
                reg.release(eval_id)
        except Exception as e:  # noqa: BLE001 -- reported below
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(w,))
                   for w in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
            assert not t.is_alive()
    finally:
        sys.setswitchinterval(old)
    assert not errors
    assert reg.state()["unsettled_evals"] == 0
    for o in owners:
        reg.retire(o)
    assert reg.state() == {"unsettled_evals": 0, "settled_bookings": 0,
                           "booked_nodes": 0, "open_barriers": 0}
    assert not reg._count


# ---------------------------------------------------------------------
# the served path: two batch workers, one plan held in the applier's door


def test_served_second_worker_moves_off_the_first_workers_plan_in_flight():
    """Job a's plan is delivered and held before the applier; job b,
    taken by the other batch worker, must move off the node a's booking
    fills, so that both commit with no refusal and no second round."""
    from nomad_tpu.server import Server
    from nomad_tpu.structs import (
        Evaluation, SchedulerConfiguration, generate_uuid)

    server = Server(num_workers=2, heartbeat_ttl=30.0, eval_batching=True)
    server.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-binpack"))
    server.start()
    TIGHT = "fix-served-node-tight"
    for i in range(2):
        n = mock.node()
        n.id = TIGHT if i == 0 else f"fix-served-node-spare-{i}"
        if i == 0:
            n.node_resources.cpu.cpu_shares = 600
            n.node_resources.memory.memory_mb = 400
        n.compute_class()
        server.register_node(n)

    held, release = threading.Event(), threading.Event()
    real_apply = server.planner.apply

    def apply(plan, eval_updates=None, worker=None):
        if plan.job is not None and plan.job.id == "fix-served-a":
            held.set()
            assert release.wait(60)
        return real_apply(plan, eval_updates, worker=worker)
    server.planner.apply = apply

    def enqueue(job_id):
        j = mock.job(id=job_id)
        j.task_groups[0].count = 1
        server.state.upsert_job(j)
        ev = Evaluation(id=generate_uuid(), namespace=j.namespace,
                        priority=j.priority, type=j.type,
                        triggered_by="job-register", job_id=j.id,
                        status="pending")
        server.state.upsert_evals([ev])
        server.broker.enqueue(ev)
        return j

    def allocs(j):
        return [a for a in server.state.allocs_by_job(j.namespace, j.id)
                if a.desired_status == "run"]

    def wait_until(cond, msg):
        deadline = time.time() + 60
        while time.time() < deadline:
            if cond():
                return
            time.sleep(0.02)
        raise AssertionError(f"timeout waiting for {msg}")

    try:
        ja = enqueue("fix-served-a")
        assert held.wait(60)
        assert server.inflight.state()["unsettled_evals"] == 1
        jb = enqueue("fix-served-b")
        wait_until(lambda: len(allocs(jb)) == 1, "job b placed")
        release.set()
        wait_until(lambda: len(allocs(ja)) == 1, "job a placed")
        assert allocs(ja)[0].node_id == TIGHT
        assert allocs(jb)[0].node_id != TIGHT
        assert server.planner.plans_rejected == 0
        assert counter("nomad.solver.fixpoint_cross_batch_conflicts") == 1
        assert counter("nomad.scheduler.register_attempts") == 2
        wait_until(
            lambda: server.inflight.state()["unsettled_evals"] == 0
            and server.inflight.state()["open_barriers"] == 0,
            "the idle server holds no booking")
        assert server.inflight.state()["booked_nodes"] == 0
    finally:
        release.set()
        server.shutdown()
