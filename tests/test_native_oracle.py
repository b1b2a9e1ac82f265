"""Parity: compiled C++ host-baseline oracle (native/pack_kernels.cc
nt_solve_eval) vs the Python reference oracle (GenericStack.select loop).

The native kernel is the compiled-host baseline; these tests gate that
it reproduces the Python
oracle's placements exactly -- same shuffle, same log2 window, same skip
and tie-break semantics (reference: scheduler/rank.go:205, stack.go:82-95,
select.go, util.go:167).
"""
import pytest

from nomad_tpu import mock, native
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.native_oracle import PackedWorld, solve, supported
from nomad_tpu.scheduler.stack import GenericStack, SelectOptions
from nomad_tpu.structs import (
    AllocatedResources, AllocatedSharedResources, Allocation, Plan,
    SchedulerConfiguration, generate_uuid, SCHED_ALG_SPREAD,
)

EVAL_ID = "native-parity-eval-00000001"

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")


def build_world(n_nodes, hetero=True, ineligible_every=0):
    h = Harness()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"npo-node-{i:05d}"
        if hetero:
            n.node_resources.cpu.cpu_shares = (2000, 4000, 8000)[i % 3]
            n.node_resources.memory.memory_mb = (4096, 8192, 16384)[i % 3]
        if ineligible_every and i % ineligible_every == 0:
            del n.attributes["driver.mock"]
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    return h, nodes


def python_oracle(h, job, nodes, n_placements, cfg=None):
    plan = Plan(eval_id=EVAL_ID, priority=50, job=job)
    snap = h.state.snapshot()
    ctx = EvalContext(snap, plan)
    stack = GenericStack(False, ctx)
    if cfg is not None:
        stack.set_scheduler_configuration(cfg)
    stack.set_job(job)
    stack.set_nodes(list(nodes))
    tg = job.task_groups[0]
    placed = {}
    for i in range(n_placements):
        name = f"{job.id}.{tg.name}[{i}]"
        option = stack.select(tg, SelectOptions(alloc_name=name))
        if option is None:
            placed[i] = None
            continue
        alloc = Allocation(
            id=generate_uuid(), name=name, job_id=job.id, job=job,
            task_group=tg.name, node_id=option.node.id,
            allocated_resources=AllocatedResources(
                tasks=dict(option.task_resources),
                shared=AllocatedSharedResources(
                    disk_mb=tg.ephemeral_disk.size_mb)))
        plan.append_alloc(alloc)
        placed[i] = option.node.id
    return placed


def native_oracle(h, job, nodes, n_placements, spread=False):
    tg = job.task_groups[0]
    assert supported(tg)
    plan = Plan(eval_id=EVAL_ID, priority=50, job=job)
    snap = h.state.snapshot()
    ctx = EvalContext(snap, plan)
    world = PackedWorld(nodes, ctx, job, tg)
    return solve(world, EVAL_ID, snap.latest_index(),
                 n_placements, tg.count, spread_alg=spread)


def assert_parity(h, job, nodes, n_placements, cfg=None, spread=False):
    py = python_oracle(h, job, nodes, n_placements, cfg=cfg)
    nat = native_oracle(h, job, nodes, n_placements, spread=spread)
    assert nat is not None
    mismatches = [(i, py[i], nat[i]) for i in py if py[i] != nat.get(i)]
    assert not mismatches, f"first mismatches: {mismatches[:5]}"


def test_fresh_heterogeneous_fleet():
    h, nodes = build_world(240)
    job = mock.job(id="npo-job")
    job.task_groups[0].count = 60
    h.state.upsert_job(job)
    assert_parity(h, job, nodes, 60)


def test_partially_used_world_and_antiaffinity():
    h, nodes = build_world(120)
    job = mock.job(id="npo-job")
    job.task_groups[0].count = 8   # small desired => strong penalty
    other = mock.job(id="npo-other")
    h.state.upsert_job(job)
    allocs = []
    for i, n in enumerate(nodes):
        if i % 3 == 0:
            allocs.append(mock.alloc_for(other, n, index=i))
        if i % 7 == 0:
            allocs.append(mock.alloc_for(job, n, index=i))
    h.state.upsert_allocs(allocs)
    assert_parity(h, job, nodes, 40)


def test_ineligible_nodes_filtered():
    h, nodes = build_world(150, ineligible_every=4)
    job = mock.job(id="npo-job")
    job.task_groups[0].count = 30
    h.state.upsert_job(job)
    assert_parity(h, job, nodes, 30)


def test_exhaustion_yields_unplaced():
    h, nodes = build_world(8, hetero=False)
    job = mock.job(id="npo-job")
    job.task_groups[0].count = 200
    job.task_groups[0].tasks[0].resources.cpu = 1900
    h.state.upsert_job(job)
    py = python_oracle(h, job, nodes, 40)
    nat = native_oracle(h, job, nodes, 40)
    assert py == nat
    assert None in py.values()   # the fleet really was exhausted


def test_spread_algorithm():
    h, nodes = build_world(160)
    job = mock.job(id="npo-job")
    job.task_groups[0].count = 50
    h.state.upsert_job(job)
    cfg = SchedulerConfiguration(scheduler_algorithm=SCHED_ALG_SPREAD)
    assert_parity(h, job, nodes, 50, cfg=cfg, spread=True)


def test_bench_shape_smoke():
    """The 10,000-node shape, scaled down."""
    h, nodes = build_world(1000)
    job = mock.job(id="bench-job")
    job.task_groups[0].count = 300
    h.state.upsert_job(job)
    assert_parity(h, job, nodes, 300)
