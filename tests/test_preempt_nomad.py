"""The preemption path against Nomad 1.7's semantics as the benchmark's
plain reference writes them (perfbench/reference/preemption.py, with the
tier-5 world builders of perfbench/tests/conftest.py, both loaded by
path and read only), and the pieces around an eviction the comparison
cannot see alone: what the applier commits, what the scheduler counts,
what the HTTP API keeps, and which kernel a lane takes."""
import importlib.util
import itertools
import json
import os
import sys
import urllib.request

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.server.plan_apply import Planner
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.state import StateStore
from nomad_tpu.structs import (
    AllocatedDeviceResource, AllocatedResources, AllocatedSharedResources,
    AllocatedTaskResources, Allocation, Plan, generate_uuid,
)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")


def _load_world_builders():
    """perfbench/tests/conftest.py under a name of its own, with this
    suite's sys.path put back after (the benchmark's directory at the
    end, where its `reference` package resolves)."""
    path = list(sys.path)
    try:
        spec = importlib.util.spec_from_file_location(
            "perfbench_world_builders",
            os.path.join(BENCH, "tests", "conftest.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod
    finally:
        sys.path[:] = path + ([BENCH] if BENCH not in path else [])


WB = _load_world_builders()

# (nodes, count, seed, cpu_only, util, the fillers hold every GPU): a
# cpu world at 95% where Nomad evicts nothing, one at 100% where every
# placement evicts (the score before the evictions), and two whose
# fillers hold every GPU (PreemptForDevice)
WORLDS = {
    "cpu95": (200, 24, 12, True, 0.95, False),
    "cpu100": (200, 24, 12, True, 1.0, False),
    "gpu_a": (200, 12, 21, False, 0.95, True),
    "gpu_b": (300, 16, 22, False, 0.95, True),
}


@pytest.mark.parametrize("alg", ["binpack", "tpu-binpack"])
@pytest.mark.parametrize("world", sorted(WORLDS))
def test_the_plan_is_the_references(world, alg):
    """Every placement on the reference's node, evicting allocs of the
    reference's classes, at the reference's score: the host iterator
    stack and the device path alike."""
    n, count, seed, cpu_only, util, held = WORLDS[world]
    metrics.reset()
    built, plan, _state = WB.tier5_world(n, count, seed, cpu_only, alg=alg,
                                         util=util, gpu_holders=held)
    same, alike, gap, placed = WB.against_host(built, plan)
    assert (same, alike, placed) == (count, count, count)
    assert gap < 1e-9
    evicting = sum(1 for ids in plan[1].values() if ids)
    assert evicting == (0 if world == "cpu95" else count)
    counters = metrics.snapshot()["counters"]
    assert counters.get("nomad.solver.evicting_placements", 0) == evicting
    if alg == "tpu-binpack":
        assert counters.get("nomad.scheduler.placements_tpu", 0) == count
        assert counters.get("nomad.scheduler.placements_host_fallback",
                            0) == 0
        assert counters.get("nomad.solver.device_preempt_host_fallback",
                            0) == 0


def _pack(world, count=8):
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService
    (_fleet, base, job_json, eval_id, _index), state = world
    job = state.job_by_id("default", job_json["id"])
    tg = job.task_groups[0]
    ctx = EvalContext(state.snapshot(),
                      Plan(eval_id=eval_id, priority=job.priority, job=job))
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(count)]
    return TpuPlacementService(ctx, job, False, False, preempt=True).pack(
        tg, places, [state.node_by_id(n) for n in base])


@pytest.mark.parametrize("held,wave", [(True, True), (False, False)])
def test_a_lane_takes_the_windowed_kernel_only_where_nothing_fits(held,
                                                                  wave):
    """Where every GPU is held no node fits the ask without eviction,
    and the lane takes the windowed preemption kernel; where a free GPU
    is left somewhere, the whole-axis kernel runs selectNextOption's two
    selects."""
    built, _plan, state = WB.tier5_world(200, 8, 21, False, alg=None,
                                         gpu_holders=held)
    lane = _pack((built, state))
    assert lane is not None and lane.ptab is not None
    assert lane.wavefront_ok() is wave


def _world_with_filler(gpu=False):
    store = StateStore()
    node = mock.gpu_node(count=2) if gpu else mock.node()
    node.id = "pn-node-0001"
    node.compute_class()
    store.upsert_node(node)
    low = mock.job(priority=10)
    low.id = "pn-low"
    store.upsert_job(low)
    filler = mock.alloc_for(low, node)
    filler.client_status = "running"
    store.upsert_allocs([filler])
    return store, node, filler


def _evicting_plan(node, filler, cpu, eval_id="pn-eval-000000000001"):
    job = mock.job(priority=70)
    alloc = Allocation(
        id=generate_uuid(), name=f"{job.id}.web[0]", job_id=job.id, job=job,
        task_group="web", node_id=node.id,
        allocated_resources=AllocatedResources(
            tasks={"web": AllocatedTaskResources(cpu_shares=cpu,
                                                 memory_mb=64)},
            shared=AllocatedSharedResources(disk_mb=10)))
    plan = Plan(eval_id=eval_id, priority=70, job=job)
    plan.append_preempted_alloc(filler, alloc.id)
    plan.append_alloc(alloc)
    return plan, alloc


def test_a_refused_node_commits_none_of_its_evictions():
    store, node, filler = _world_with_filler()
    planner = Planner(store)
    try:
        # more than the node holds, even with the filler gone
        plan, _ = _evicting_plan(node, filler, cpu=10 ** 6)
        result = planner.apply(plan)
        assert node.id in result.rejected_nodes
        assert not result.node_preemptions
        assert store.alloc_by_id(filler.id).desired_status == "run"
    finally:
        planner.shutdown()


def test_an_alloc_evicted_once_keeps_the_first_eviction():
    """A second plan that evicts an alloc an earlier plan evicted drops
    that eviction (plan_apply.go evaluatePlanPlacements): the record
    keeps the first placement as what evicted it."""
    store, node, filler = _world_with_filler()
    planner = Planner(store)
    try:
        first, a1 = _evicting_plan(node, filler, cpu=100)
        r1 = planner.apply(first)
        assert r1.node_preemptions[node.id][0].id == filler.id
        second, _a2 = _evicting_plan(node, filler, cpu=100,
                                     eval_id="pn-eval-000000000002")
        r2 = planner.apply(second)
        assert not r2.rejected_nodes and not r2.node_preemptions
        stored = store.alloc_by_id(filler.id)
        assert stored.desired_status == "evict"
        assert stored.preempted_by_allocation == a1.id
    finally:
        planner.shutdown()


def test_the_applier_times_its_preemption_arithmetic():
    from nomad_tpu.server import tracing
    seen = []
    prev = tracing._SPAN_SINK
    tracing.set_span_sink(lambda name, ms: seen.append(name))
    store, node, filler = _world_with_filler()
    planner = Planner(store)
    try:
        planner.apply(_evicting_plan(node, filler, cpu=100)[0])
    finally:
        planner.shutdown()
        tracing.set_span_sink(prev)
    assert "plan.verify_preemptions" in seen


def test_an_evicted_alloc_leaves_the_schedulers_view_at_its_commit():
    """Its client still reports it running; the host stack's proposed
    allocs, the alloc table's scheduler view and the device path's
    usage fold no longer count it."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.solver.service import TpuPlacementService
    from nomad_tpu.tensor.pack import pack_nodes_cached
    store, node, filler = _world_with_filler()
    planner = Planner(store)
    try:
        planner.apply(_evicting_plan(node, filler, cpu=100)[0])
    finally:
        planner.shutdown()
    assert store.alloc_by_id(filler.id).client_status == "running"
    snap = store.snapshot()
    job = mock.job()
    ctx = EvalContext(snap, Plan(eval_id="pn-eval-000000000003",
                                 priority=50, job=job))
    proposed = {a.id for a in ctx.proposed_allocs(node.id)}
    assert filler.id not in proposed and len(proposed) == 1
    table = store.alloc_table
    assert table.live[table._row_of[filler.id]] == 0
    matrix = pack_nodes_cached([node], None)
    usage = TpuPlacementService(ctx, job, False, False)\
        ._pack_usage_incremental(matrix, [node], job.task_groups[0])
    assert usage.used_cpu[0] == 100


def _http(port, path, method="GET", body=None):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}", method=method,
        data=json.dumps(body).encode() if body is not None else None)
    with urllib.request.urlopen(req, timeout=10) as resp:
        return json.loads(resp.read() or b"{}")


@pytest.fixture
def agent():
    from nomad_tpu.api.devagent import start_agent
    server, http = start_agent(workers=0, port=0)
    yield server, http.port
    http.shutdown()
    server.shutdown()


def test_a_jobspec_keeps_its_device_asks_and_migrate_stanza(agent):
    server, port = agent
    group = {"name": "web", "count": 1, "migrate": {"max_parallel": 3},
             "tasks": [{"name": "web", "driver": "mock", "resources": {
                 "cpu": 100, "memory_mb": 64,
                 "devices": [{"name": "nvidia/gpu", "count": 2}]}}]}
    plain = dict(group, name="api")
    plain.pop("migrate")
    plain["tasks"] = [dict(plain["tasks"][0], name="api")]
    _http(port, "/v1/jobs", "PUT", {"job": {
        "id": "pn-gpu", "type": "service", "datacenters": ["dc1"],
        "task_groups": [group, plain]}})
    job = server.state.job_by_id("default", "pn-gpu")
    devs = job.task_groups[0].tasks[0].resources.devices
    assert [(d.name, d.count) for d in devs] == [("nvidia/gpu", 2)]
    assert job.task_groups[0].migrate.max_parallel == 3
    # a service group without the stanza takes Nomad's default
    assert job.task_groups[1].migrate.max_parallel == 1
    back = _http(port, "/v1/job/pn-gpu")
    assert back["task_groups"][0]["migrate"]["max_parallel"] == 3
    assert back["task_groups"][0]["tasks"][0]["resources"]["devices"][0][
        "count"] == 2


def test_a_batch_group_without_the_stanza_has_none(agent):
    server, port = agent
    _http(port, "/v1/jobs", "PUT", {"job": {
        "id": "pn-batch", "type": "batch", "datacenters": ["dc1"],
        "task_groups": [{"name": "b", "count": 1, "tasks": [{
            "name": "b", "driver": "mock",
            "resources": {"cpu": 100, "memory_mb": 64}}]}]}})
    assert server.state.job_by_id("default",
                                  "pn-batch").task_groups[0].migrate is None


def test_the_operator_post_keeps_preemption_config(agent):
    server, port = agent
    path = "/v1/operator/scheduler/configuration"
    _http(port, path, "POST", {
        "scheduler_algorithm": "tpu-binpack", "preemption_config": {
            "service_scheduler_enabled": True,
            "batch_scheduler_enabled": False,
            "system_scheduler_enabled": True}})
    got = server.state.scheduler_config().preemption_config
    assert got.service_scheduler_enabled and got.system_scheduler_enabled
    assert not got.batch_scheduler_enabled
    assert _http(port, path)["preemption_config"][
        "service_scheduler_enabled"] is True
    # a body without it takes the defaults, as before
    _http(port, path, "POST", {"scheduler_algorithm": "binpack"})
    assert not server.state.scheduler_config().preemption_config\
        .service_scheduler_enabled


def test_device_holders_are_given_back_where_a_placement_evicts_them():
    """The device allocator's replay at materialize frees the instances
    the placement's evictions held."""
    from nomad_tpu.scheduler.rank import DeviceAllocator
    from nomad_tpu.structs import DeviceRequest
    store, node, filler = _world_with_filler(gpu=True)
    dev = node.node_resources.devices[0]
    held = filler.copy_skip_job()
    held.allocated_resources.tasks["web"].devices = [AllocatedDeviceResource(
        vendor=dev.vendor, type=dev.type, name=dev.name,
        device_ids=list(dev.instance_ids))]
    allocator = DeviceAllocator(None, node)
    allocator.add_allocs([held])
    req = DeviceRequest(name="nvidia/gpu", count=1)
    assert allocator.assign_device(req)[0] is None
    allocator.release_allocs([held])
    assert allocator.assign_device(req)[0] is not None


def test_the_windowed_work_model_by_hand():
    """perfbench/work_models/wave_preempt.json at one small shape: 2
    lanes, 4 placements, a buffer of 3, 2 candidates, 5 groups, 1 device
    group: table 2*7*12 = 168, candidates 2*7*2*(7 + 1/4) = 203, counts
    10, scalars and penalties 2*13 = 26, results 2*4*(3 + 1/2) = 28 ->
    435 four-byte words."""
    spec = importlib.util.spec_from_file_location(
        "perfbench_readers", os.path.join(BENCH, "readers.py"))
    readers = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(readers)
    with open(os.path.join(BENCH, "work_models", "wave_preempt.json")) as f:
        model = json.load(f)
    shape = {"E": 2, "P": 4, "B": 3, "A": 2, "G": 5, "Gd": 1}
    assert readers.model_bytes(model, shape) == 4 * 435.0


def test_the_shared_candidates_are_the_walk_s():
    """A preempting eval packed from the snapshot's shared candidate
    rows gets the tables the per-node walk of its proposed allocs gives,
    and the shared rows caught up across a commit are the rows built
    anew at it."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.solver import service
    built, _plan, state = WB.tier5_world(200, 8, 21, False, alg=None,
                                         gpu_holders=True)
    fleet, base_ids, job_json, eval_id, _index = built
    job = state.job_by_id("default", job_json["id"])
    nodes = [state.node_by_id(n) for n in base_ids]
    lane = _pack((built, state))
    ctx = EvalContext(state.snapshot(),
                      Plan(eval_id=eval_id, priority=job.priority, job=job))
    svc = service.TpuPlacementService(ctx, job, False, False, preempt=True)
    walked = {n.id: ctx.proposed_allocs(n.id) for n in nodes}
    ptab, pinit, cands = svc._pack_preemption(
        job.task_groups[0], nodes, lane.order, lane.matrix.n_pad,
        np.float64, walked, lane.const.dev_aff.shape[1])
    for name in ptab._fields:
        if name != "grp":
            np.testing.assert_array_equal(
                np.asarray(getattr(lane.ptab, name)),
                np.asarray(getattr(ptab, name)), err_msg=name)
    # the same groups under other numbers
    pairs = set(zip(np.asarray(lane.ptab.grp).ravel().tolist(),
                    np.asarray(ptab.grp).ravel().tolist()))
    assert len({a for a, _ in pairs}) == len({b for _, b in pairs}) \
        == len(pairs)
    assert (-1, -1) in pairs and all((a < 0) == (b < 0) for a, b in pairs)
    assert np.asarray(lane.pinit.counts).shape == \
        np.asarray(pinit.counts).shape
    assert [[a.id for a in c] for c in lane.cand_allocs] == \
        [[a.id for a in c] for c in cands]
    # one commit later: an eviction and a placement on one node
    snap0 = state.snapshot()
    first = service._preempt_base(snap0, lane.matrix, nodes)
    node = nodes[0]
    victim = next(a for a in state.allocs_by_node(node.id)
                  if a.job is not None and a.job.priority < 60)
    plan, _ = _evicting_plan(node, victim, cpu=100)
    ev = victim.copy_skip_job()
    ev.desired_status = "evict"
    state.upsert_allocs([ev] + plan.node_allocation[node.id])
    snap1 = state.snapshot()
    caught = service._preempt_base(snap1, lane.matrix, nodes)
    fresh = service._PreemptBase.build(snap1, lane.matrix.node_ids, nodes,
                                       lane.matrix.n_pad)
    assert caught is not first
    for name in ("cpu", "mem", "disk", "prio", "maxp", "valid", "held"):
        np.testing.assert_array_equal(getattr(caught, name),
                                      getattr(fresh, name), err_msg=name)
    assert [[a.id for a in r] for r in caught.allocs] == \
        [[a.id for a in r] for r in fresh.allocs]


def test_hold_gpus_leaves_no_instance_free():
    import random

    from nomad_tpu import benchkit
    from nomad_tpu.scheduler import Harness
    rng = random.Random(3)
    saved, mock._counter = mock._counter, itertools.count()
    try:
        h = Harness()
        nodes = benchkit.make_fleet(rng, h, 40, gpus=True)
        benchkit.seed_utilization(rng, h, nodes, 0.95,
                                  priorities=(10, 20, 30, 40))
        benchkit.hold_gpus(rng, h, nodes)
    finally:
        mock._counter = saved
    for node in nodes:
        for group in node.node_resources.devices:
            held = [i for a in h.state.allocs_by_node(node.id)
                    for t in a.allocated_resources.tasks.values()
                    for d in t.devices for i in d.device_ids]
            assert sorted(held) == sorted(group.instance_ids)


def _evicted_priority_sets():
    """What a GPU service's eviction takes on a full node of the tier-5
    fleet: one holder (priority 10 or 20) and up to two cpu fillers of
    the four tiers."""
    return sorted({tuple(sorted((h,) + fs)) for h in (10, 20)
                   for k in range(3)
                   for fs in itertools.combinations_with_replacement(
                       (10, 20, 30, 40), k)})


@pytest.mark.parametrize("used", [(8500, 10240), (8500, 10752),
                                  (4500, 5120), (8500, 9728)])
def test_evicting_options_rank_in_float32_as_float64_ranks_them(used):
    """Two options on nodes of one state, one eviction set apart: the
    float32 select picks what the reference's float64 means pick, the
    earlier in scan order where they are equal. The means of sets a
    quarter of a net priority apart differ by ~4e-8, where a float32
    mean ties or flips them."""
    import jax
    import jax.numpy as jnp

    from nomad_tpu.solver import binpack
    from reference import placement as rp
    from reference import preemption as rpre

    class Evicted:
        def __init__(self, priority):
            self.priority = priority

    cap = {8500: (8000.0, 16384.0), 4500: (4000.0, 8192.0)}[used[0]]
    base64 = float(rp.binpack_score(cap[0], cap[1], *used, "float64"))
    free = [np.float32(1.0 - u / c) for u, c in zip(used, cap)]
    base32 = binpack._binpack_score(jnp.float32(free[0]),
                                    jnp.float32(free[1]), False)
    sets = _evicted_priority_sets()
    pairs = [(a, b) for a in sets for b in sets if a != b]
    nets = np.array([[rpre.net_priority([Evicted(p) for p in s])
                      for s in pair] for pair in pairs])
    mean64 = (base64 + np.vectorize(rpre.preemption_score)(nets)) / 2.0
    want = np.where(mean64[:, 1] > mean64[:, 0], 1, 0)
    hi, lo = binpack._evicting_mean(
        jnp.full(nets.shape, base32, jnp.float32),
        jnp.ones(nets.shape, jnp.float32), jnp.asarray(nets, jnp.float32))
    assert hi.dtype == jnp.float32
    fit = jnp.ones(nets.shape, bool)
    chosen, _score, _n = jax.vmap(
        lambda s, f, t: binpack._select_window(s, f, 2, jnp.float32, t))(
            hi, fit, lo)
    np.testing.assert_array_equal(np.asarray(chosen), want)


def test_the_float32_device_path_is_the_references(monkeypatch):
    """The device path in the chip's precision on a world whose
    evicting options come one quarter of a net priority apart: every
    placement on the reference's node, evicting the reference's classes
    (a float32 mean picked two of them the other way round)."""
    from nomad_tpu.solver import service

    init = service.TpuPlacementService.__init__

    def float32(self, *args, **kwargs):
        kwargs["dtype"] = "float32"
        init(self, *args, **kwargs)
    monkeypatch.setattr(service.TpuPlacementService, "__init__", float32)
    metrics.reset()
    built, plan, _state = WB.tier5_world(400, 32, 223, False,
                                         alg="tpu-binpack",
                                         gpu_holders=True)
    same, alike, gap, placed = WB.against_host(built, plan)
    assert (same, alike, placed) == (32, 32, 32)
    assert gap < 1e-6
    counters = metrics.snapshot()["counters"]
    assert counters.get("nomad.scheduler.placements_tpu", 0) == 32
    assert counters.get("nomad.solver.device_preempt_host_fallback", 0) == 0


def test_a_windowed_dispatch_warms_its_whole_axis_twin():
    """Where an eviction has left a free GPU beside room, a later lane
    fits without eviction and takes the whole-axis kernel over the same
    tables: its program is compiled when the windowed lanes of those
    shapes first dispatch, and every lane count meets that one program
    (a window's first such lane compiled it, ~20 s on the chip)."""
    from nomad_tpu.solver import batch as batch_mod
    from nomad_tpu.solver import guard
    built, _plan, state = WB.tier5_world(200, 8, 21, False, alg=None,
                                         gpu_holders=True)
    wave = _pack((built, state))
    assert wave.wavefront_ok()
    node_id = next(n for n in built[1]
                   if state.node_by_id(n).node_resources.devices)
    stopped = []
    for a in state.allocs_by_node(node_id):
        a = a.copy_skip_job()
        a.desired_status, a.client_status = "stop", "complete"
        stopped.append(a)
    state.upsert_allocs(stopped)
    dense = _pack((built, state))
    assert not dense.wavefront_ok()
    assert dense.fuse_key()[:-1] == wave.fuse_key()[:-1]
    guard.compile_stats()
    batch_mod.fuse_and_solve([wave], use_mesh=False, e_pad_hint=8)
    compiled = guard.compile_stats()["backend_compiles"]
    for lanes in ([dense], [dense] * 3):
        got = batch_mod.fuse_and_solve(lanes, use_mesh=False, e_pad_hint=8)
        assert all(int(np.sum(chosen >= 0)) == 8 for chosen, *_ in got)
    assert guard.compile_stats()["backend_compiles"] == compiled


def _device_tables_two_ways(state, job, nodes, dtype,
                            eval_id="dt-eval-000000000001"):
    """A preempting eval's device tables on a fresh plan: packed from
    the snapshot's shared base, and by the walk of every node's proposed
    allocs."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.util import shuffled_order
    from nomad_tpu.solver import service
    from nomad_tpu.tensor.pack import pack_nodes_cached
    snap = state.snapshot()
    ctx = EvalContext(snap, Plan(eval_id=eval_id, priority=job.priority,
                                 job=job))
    svc = service.TpuPlacementService(ctx, job, False, False, preempt=True)
    matrix = pack_nodes_cached(nodes, snap.node_table_index)
    order = shuffled_order(eval_id, snap.latest_index(), len(nodes))
    tg = job.task_groups[0]
    requests = [r for t in tg.tasks for r in t.resources.devices]
    args = (tg, requests, nodes, order, matrix.n_pad)
    base = service._preempt_base(snap, matrix, nodes)
    from_base = svc._pack_devices(*args, None, dtype, base, matrix)
    walked = {n.id: ctx.proposed_allocs(n.id) for n in nodes}
    walk = svc._pack_devices(*args, walked, dtype, None, matrix)
    return from_base, walk


def _tier5_device_world(world):
    n, count, seed, _cpu_only, util, held = WORLDS[world]
    built, _plan, state = WB.tier5_world(n, count, seed, False, alg=None,
                                         util=util, gpu_holders=held)
    job = state.job_by_id("default", built[2]["id"])
    return state, job, [state.node_by_id(n) for n in built[1]]


def _commit_gpu_moves(state, job, nodes):
    """On a few GPU nodes, a holder evicted and, on every other one of
    them, a placement of ``job`` holding one of the instances it gave
    back, all in one commit."""
    changed = []
    gpu_nodes = [n for n in nodes if n.node_resources.devices][:6]
    for k, node in enumerate(gpu_nodes):
        holder = next(a for a in state.allocs_by_node(node.id)
                      if any(t.devices for t in
                             a.allocated_resources.tasks.values()))
        ev = holder.copy_skip_job()
        ev.desired_status = "evict"
        changed.append(ev)
        if k % 2:
            dev = next(d for t in holder.allocated_resources.tasks.values()
                       for d in t.devices)
            placed = mock.alloc_for(job, node, index=k)
            placed.client_status = "running"
            next(iter(placed.allocated_resources.tasks.values())).devices = [
                AllocatedDeviceResource(vendor=dev.vendor, type=dev.type,
                                        name=dev.name,
                                        device_ids=dev.device_ids[:1])]
            changed.append(placed)
    state.upsert_allocs(changed)


def _two_group_world():
    """24 nodes with none, one or two device groups of four kinds (two
    of one model at different memory), their instances partly held by
    a priority-10 job; a priority-70 job asks one GPU of 10 GB or more
    with an affinity for the v100, and any one GPU."""
    from nomad_tpu.structs import Affinity, Constraint, DeviceRequest
    from nomad_tpu.structs.resources import NodeDeviceResource
    kinds = [(), (("1080ti", 11264, 2),), (("v100", 16384, 4),),
             (("t4", 8192, 2),), (("1080ti", 11264, 2), ("v100", 32768, 2)),
             (("t4", 8192, 4), ("v100", 16384, 4))]
    store = StateStore()
    low = mock.job(priority=10)
    low.id = "dg-low"
    store.upsert_job(low)
    nodes, allocs = [], []
    for i in range(24):
        node = mock.node()
        node.id = f"dg-node-{i:04d}"
        node.node_resources.devices = [NodeDeviceResource(
            vendor="nvidia", type="gpu", name=name,
            instance_ids=[f"{node.id}-{name}-{k}" for k in range(count)],
            attributes={"memory": mem})
            for name, mem, count in kinds[i % len(kinds)]]
        node.compute_class()
        store.upsert_node(node)
        nodes.append(node)
        for g_i, group in enumerate(node.node_resources.devices):
            take = group.instance_ids[:(i + g_i) % 3]
            if take:
                a = mock.alloc_for(low, node, index=len(allocs))
                a.client_status = "running"
                next(iter(a.allocated_resources.tasks.values())).devices = [
                    AllocatedDeviceResource(
                        vendor=group.vendor, type=group.type,
                        name=group.name, device_ids=take)]
                allocs.append(a)
    store.upsert_allocs(allocs)
    job = mock.job(priority=70)
    job.id = "dg-high"
    job.task_groups[0].tasks[0].resources.devices = [
        DeviceRequest(name="nvidia/gpu", count=1, constraints=[Constraint(
            l_target="${device.attr.memory}", operand=">=",
            r_target="10000")], affinities=[Affinity(
                l_target="${device.model}", r_target="v100", operand="=",
                weight=50)]),
        DeviceRequest(name="gpu", count=1)]
    store.upsert_job(job)
    return store, job, nodes


@pytest.mark.parametrize("dtype", [np.float64, np.float32])
@pytest.mark.parametrize("case", ["gpu_a", "gpu_b", "gpu_a_caught_up",
                                  "two_groups"])
def test_the_device_tables_from_the_base_are_the_walk_s(case, dtype,
                                                        monkeypatch):
    """A preempting eval packed from the snapshot's shared base takes its
    device tables from the base's holdings and one check per distinct
    device group: they are, bit for bit, the tables the walk of every
    node's proposed allocs gives, on a base built at the snapshot and on
    one caught up to it across a commit."""
    from nomad_tpu.solver import service
    from nomad_tpu.tensor.pack import pack_device_groups_cached
    if case == "two_groups":
        state, job, nodes = _two_group_world()
    else:
        state, job, nodes = _tier5_device_world(case.split("_caught")[0])
    if case.endswith("caught_up"):
        _device_tables_two_ways(state, job, nodes, dtype)
        _commit_gpu_moves(state, job, nodes)

        def rebuilt(*_a, **_k):
            raise AssertionError("the base was built anew, not caught up")
        monkeypatch.setattr(service._PreemptBase, "build", rebuilt)
    from_base, walk = _device_tables_two_ways(state, job, nodes, dtype)
    for name, got, want in zip(("aff", "counts", "sum_w", "free"),
                               from_base, walk):
        assert got.dtype == want.dtype and got.shape == want.shape, name
        assert np.array_equal(got, want), name
    free = walk[3]
    assert (free == -1).any() and (free >= 0).any()
    if case.endswith("caught_up"):
        assert (free > 0).any()
    if case == "two_groups":
        groups = pack_device_groups_cached(nodes, free.shape[2])
        assert len(groups.reps) == 4 and free.shape[1] == 2
        # the constraint keeps the t4 from the first ask, not the second
        assert (free[0] == -1).sum() > (free[1] == -1).sum()
        assert set(np.unique(walk[0][0])) == {0.0, 50.0}


def test_device_tables_from_base_counts_the_lanes_packed_from_a_base():
    """One count per preempting lane whose device tables came from the
    snapshot's base; none for a lane whose plan already holds an entry,
    packed through the walk of every node's proposed allocs."""
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService
    metrics.reset()
    built, _plan, state = WB.tier5_world(200, 8, 21, False, alg=None,
                                         gpu_holders=True)
    name = "nomad.solver.device_tables_from_base"
    assert _pack((built, state)) is not None
    assert metrics.snapshot()["counters"].get(name, 0) == 1
    job = state.job_by_id("default", built[2]["id"])
    tg = job.task_groups[0]
    nodes = [state.node_by_id(n) for n in built[1]]
    plan = Plan(eval_id=built[3], priority=job.priority, job=job)
    plan.append_alloc(mock.alloc_for(job, nodes[0], index=7))
    ctx = EvalContext(state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(7)]
    lane = TpuPlacementService(ctx, job, False, False, preempt=True).pack(
        tg, places, nodes)
    assert lane is not None and lane.ptab is not None
    assert metrics.snapshot()["counters"].get(name, 0) == 1
