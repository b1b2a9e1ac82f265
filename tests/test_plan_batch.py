"""Group-commit plan applier (ISSUE 5): disjoint-plan batching parity
vs the same plans submitted one at a time, conflict fallback
ordering, and the mid-batch chaos drills (per-plan staging fault +
whole-transaction split)."""
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.faultinject import InjectedFault, faults
from nomad_tpu.server.plan_apply import Planner
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    AllocatedResources, AllocatedSharedResources, AllocatedTaskResources,
    Allocation, Evaluation, Plan, generate_uuid,
    EVAL_STATUS_COMPLETE,
)


def make_world(n_nodes=8):
    store = StateStore()
    nodes = []
    for i in range(n_nodes):
        node = mock.node()
        node.id = f"pb-node-{i:04d}"
        node.compute_class()
        store.upsert_node(node)
        nodes.append(node)
    return store, nodes


def cpu_alloc(node, job, cpu=100, aid=None):
    return Allocation(
        id=aid or generate_uuid(), name=f"{job.id}.web[0]", job_id=job.id,
        job=job, task_group="web", node_id=node.id,
        allocated_resources=AllocatedResources(
            tasks={"web": AllocatedTaskResources(cpu_shares=cpu,
                                                 memory_mb=64)},
            shared=AllocatedSharedResources(disk_mb=10)))


def plan_on(nodes, k, priority=50, aid_prefix="pb"):
    """One plan placing one alloc on each of the given nodes, with
    DETERMINISTIC alloc ids so two worlds produce comparable state."""
    job = mock.job(id=f"pb-job-{k}")
    plan = Plan(eval_id=f"pb-eval-{k:016d}"[-36:], priority=priority,
                job=job)
    for j, node in enumerate(nodes):
        plan.append_alloc(cpu_alloc(
            node, job, aid=f"{aid_prefix}-{k}-{j}-{'0' * 20}"[:36]))
    return plan


def submit_group(planner, plans, evals=None):
    """Submit plans concurrently after a group hint, the way a fused
    barrier generation does. Returns (results, errors) by plan index.
    Thread starts are staggered on observed queue depth so the plans'
    seq order (and therefore drain order) matches list order -- the
    expect_plans window holds the dispatcher's drain meanwhile."""
    results = [None] * len(plans)
    errors = [None] * len(plans)
    planner.expect_plans(len(plans))

    def run(i):
        try:
            results[i] = planner.apply(
                plans[i], [evals[i]] if evals else None)
        except BaseException as e:  # noqa: BLE001
            errors[i] = e

    threads = [threading.Thread(target=run, args=(i,))
               for i in range(len(plans))]
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
    return results, errors


def world_state(store):
    """Comparable commit outcome: alloc id -> (node, desired/client
    status, modify == the committing index)."""
    out = {}
    for a in store.allocs():
        out[a.id] = (a.node_id, a.desired_status, a.client_status)
    return out


def run_world(batch, monkeypatch, n_plans=6, window_ms="500"):
    """``batch``: the plans arrive together behind an expect_plans
    hint; otherwise each is submitted and waited for before the next,
    a queue of one: the group of one through ``_commit_one``."""
    monkeypatch.setenv("NOMAD_TPU_PLAN_BATCH_WINDOW_MS", window_ms)
    store, nodes = make_world(n_nodes=2 * n_plans)
    planner = Planner(store)
    try:
        # pairwise-disjoint node sets: plan k touches nodes 2k, 2k+1
        plans = [plan_on(nodes[2 * k:2 * k + 2], k)
                 for k in range(n_plans)]
        evals = [Evaluation(id=p.eval_id, status=EVAL_STATUS_COMPLETE,
                            job_id=p.job.id) for p in plans]
        if batch:
            results, errors = submit_group(planner, plans, evals)
            assert not any(errors), errors
        else:
            results = [planner.apply(p, [e])
                       for p, e in zip(plans, evals)]
        return store, planner, plans, results
    finally:
        planner.shutdown()


def test_disjoint_batch_parity(monkeypatch):
    """The same disjoint-plan workload arriving as one group and one
    plan at a time must land identical allocs, eval updates and
    per-result index invariants."""
    store_b, planner_b, plans_b, res_b = run_world(True, monkeypatch)
    store_s, planner_s, plans_s, res_s = run_world(False, monkeypatch)

    assert world_state(store_b) == world_state(store_s)
    assert planner_b.plans_applied == planner_s.plans_applied == 6
    assert planner_b.plans_rejected == planner_s.plans_rejected == 0
    # the group really grouped (>= one multi-plan transaction); plans
    # arriving alone never touch the group path
    assert planner_b.batches_committed >= 1
    assert planner_s.batches_committed == 0
    # every commit stamped its result with the index the store landed
    # at, and every committed alloc's modify_index matches its plan's
    # commit index -- in BOTH modes
    for store, results in ((store_b, res_b), (store_s, res_s)):
        for r in results:
            assert r.alloc_index > 0
            for allocs in r.node_allocation.values():
                for a in allocs:
                    assert store.alloc_by_id(a.id).modify_index \
                        == r.alloc_index
    # eval updates rode the commits in both modes
    for store in (store_b, store_s):
        for k in range(6):
            ev = store.eval_by_id(f"pb-eval-{k:016d}"[-36:])
            assert ev is not None and ev.status == EVAL_STATUS_COMPLETE
    # serial mode: one index bump per plan (strictly increasing);
    # batch mode: grouped plans share bumps (fewer distinct indexes)
    assert len({r.alloc_index for r in res_s}) == 6
    assert len({r.alloc_index for r in res_b}) < 6


def test_batch_of_one_is_serial(monkeypatch):
    """With no concurrent arrivals the batch path degrades to exactly
    the serial applier: one plan, one commit, one index."""
    store, nodes = make_world(n_nodes=2)
    planner = Planner(store)
    try:
        r = planner.apply(plan_on(nodes, 0))
        assert not r.rejected_nodes and r.alloc_index > 0
        assert planner.plans_applied == 1
        assert planner.batches_committed == 0   # single-plan legacy path
    finally:
        planner.shutdown()


def test_conflict_falls_back_to_serial_order(monkeypatch):
    """A plan whose node set overlaps the group must not join it: it
    (and everything queued behind it) commits in a LATER transaction,
    after the group -- today's serial order."""
    monkeypatch.setenv("NOMAD_TPU_PLAN_BATCH_WINDOW_MS", "500")
    store, nodes = make_world(n_nodes=6)
    planner = Planner(store)
    try:
        before = _conflict_count()
        plan_a = plan_on([nodes[0], nodes[1]], 0)   # nodes 0,1
        plan_b = plan_on([nodes[1], nodes[2]], 1)   # overlaps A on 1
        plan_c = plan_on([nodes[3]], 2)             # disjoint from both
        # same priority: heap order == submission (seq) order. Stall the
        # dispatcher's drain so all three arrive before the first pop.
        results, errors = submit_group(planner, [plan_a, plan_b, plan_c])
        assert not any(errors), errors
        ra, rb, rc = results
        assert not ra.rejected_nodes
        assert not rb.rejected_nodes
        assert not rc.rejected_nodes
        # A committed strictly before B (B fell out of A's group)
        assert ra.alloc_index < rb.alloc_index
        # B and C were requeued together and are disjoint -> same group
        assert rb.alloc_index == rc.alloc_index
        assert _conflict_count() > before
        assert len(store.allocs()) == 5
    finally:
        planner.shutdown()


def _conflict_count():
    from nomad_tpu.server.telemetry import metrics
    return metrics.snapshot()["counters"].get(
        "nomad.plan.batch_conflict_serialized", 0)


def test_chaos_mid_batch_staging_fault(monkeypatch):
    """faultinject plan.commit mid-batch: the injected plan's waiter
    gets the fault, the batch splits around it, and every surviving
    plan commits exactly once."""
    monkeypatch.setenv("NOMAD_TPU_PLAN_BATCH_WINDOW_MS", "500")
    store, nodes = make_world(n_nodes=6)
    planner = Planner(store)
    faults.arm("plan.commit", "error", count=1)
    try:
        plans = [plan_on([nodes[2 * k], nodes[2 * k + 1]], k)
                 for k in range(3)]
        results, errors = submit_group(planner, plans)
        injected = [e for e in errors if isinstance(e, InjectedFault)]
        assert len(injected) == 1, (errors, results)
        survivors = [r for r in results if r is not None]
        assert len(survivors) == 2
        # exactly-once: every survivor's allocs landed, each exactly
        # once; the injected plan's allocs never landed
        seen = world_state(store)
        landed = 0
        for r, plan in zip(results, plans):
            for allocs in plan.node_allocation.values():
                for a in allocs:
                    if r is None:
                        assert a.id not in seen
                    else:
                        assert seen[a.id][0] == a.node_id
                        landed += 1
        assert landed == 4
        # the applier survives: a follow-up plan still commits
        r = planner.apply(plan_on([nodes[4]], 9))
        assert not r.rejected_nodes
    finally:
        faults.disarm_all()
        planner.shutdown()


class ExplodingBatchStore(StateStore):
    """Whole-transaction failure: the batched apply raises before any
    write, forcing the applier's split-to-serial fallback."""

    def __init__(self):
        super().__init__()
        self.explode = 0
        self.batch_calls = 0
        self.serial_calls = 0

    def apply_plan_results_batch(self, entries):
        self.batch_calls += 1
        if self.explode > 0:
            self.explode -= 1
            raise RuntimeError("simulated raft batch failure")
        return super().apply_plan_results_batch(entries)

    def upsert_plan_results(self, result, eval_updates=None):
        self.serial_calls += 1
        return super().upsert_plan_results(result, eval_updates)


def test_chaos_batch_transaction_split(monkeypatch):
    """A whole-batch transaction failure splits to serial: every plan
    still commits exactly once through the single-plan path."""
    monkeypatch.setenv("NOMAD_TPU_PLAN_BATCH_WINDOW_MS", "500")
    store = ExplodingBatchStore()
    nodes = []
    for i in range(6):
        node = mock.node()
        node.id = f"pb-node-{i:04d}"
        node.compute_class()
        store.upsert_node(node)
        nodes.append(node)
    store.explode = 1
    planner = Planner(store)
    try:
        plans = [plan_on([nodes[2 * k], nodes[2 * k + 1]], k)
                 for k in range(3)]
        results, errors = submit_group(planner, plans)
        assert not any(errors), errors
        assert store.batch_calls >= 1
        assert store.serial_calls == 3      # the split fallback
        seen = world_state(store)
        for plan in plans:
            for allocs in plan.node_allocation.values():
                for a in allocs:
                    assert a.id in seen
        assert len(store.allocs()) == 6     # exactly once each
        assert planner.plans_applied == 3
    finally:
        planner.shutdown()


def test_group_window_releases_without_arrivals(monkeypatch):
    """An over-counted expect_plans hint (evals that never submit) must
    only delay the drain by the bounded window, never wedge it."""
    monkeypatch.setenv("NOMAD_TPU_PLAN_BATCH_WINDOW_MS", "50")
    store, nodes = make_world(n_nodes=2)
    planner = Planner(store)
    try:
        planner.expect_plans(100)           # lies: only one plan comes
        t0 = time.monotonic()
        r = planner.apply(plan_on(nodes, 0))
        assert not r.rejected_nodes
        assert time.monotonic() - t0 < 5.0
    finally:
        planner.shutdown()
