"""The whole-axis path as it is served (a scan window too wide for the
wave kernels: a spread over racks on a job of 126 allocs or more), held
to the benchmark's plain reference placement by placement, and the
family of programs it runs from, which has to be closed: first attempts
and retries of every width at every lane count land in the one program
their first fused dispatch compiled.

Small and seeded: 300 nodes in 12 racks, a third of them part-filled,
jobs of 150 allocs spread on ${meta.rack} (limit 150, + 3 skips > 128).
"""
import importlib.util
import os
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import AllocPlaceResult
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.solver import batch as batch_mod
from nomad_tpu.solver import guard
from nomad_tpu.solver.service import TpuPlacementService
from nomad_tpu.structs import Plan
from nomad_tpu.structs.job import Spread
from nomad_tpu.tensor import pack as tpack

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
N_NODES, N_RACKS, COUNT, WORKERS = 300, 12, 150, 8
SCORE_TOLERANCE = 1e-4      # perfbench/limits/served_placements.json
REMAINING = (COUNT, 1, 31, 33, 149)
LANES = (1, 3, 8)


def _reference():
    spec = importlib.util.spec_from_file_location(
        "perfbench_reference_placement",
        os.path.join(ROOT, "perfbench", "reference", "placement.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _reference()


@pytest.fixture(autouse=True)
def clean_caches():
    tpack._reset_pack_caches_for_tests()
    batch_mod.arena_clear("test baseline")
    yield
    tpack._reset_pack_caches_for_tests()
    batch_mod.arena_clear("test teardown")


def build_world():
    rng = random.Random(20261003)
    h = Harness()
    nodes = []
    for i in range(N_NODES):
        n = mock.node()
        n.id = f"df-node-{i:04d}"
        n.meta["rack"] = f"rack-{i % N_RACKS:02d}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    filler = mock.job(id="df-filler")
    h.state.upsert_job(filler)
    held = []
    for n in rng.sample(nodes, N_NODES // 3):
        for k in range(rng.randint(1, 5)):
            a = mock.alloc_for(filler, n, index=len(held))
            a.client_status = "running"
            held.append(a)
    h.state.upsert_allocs(held)
    return h, nodes


def spread_job(job_id):
    job = mock.job(id=job_id)
    tg = job.task_groups[0]
    tg.count = COUNT
    tg.spreads = [Spread(attribute="${meta.rack}", weight=100)]
    return job


def pack_lane(h, nodes, job, eval_id, remaining):
    """The lane of an eval that still has `remaining` of the job's
    placements to make, packed against the store as it stands."""
    tg = job.task_groups[0]
    plan = Plan(eval_id=eval_id, priority=50, job=job)
    ctx = EvalContext(h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg)
              for k in range(COUNT - remaining, COUNT)]
    svc = TpuPlacementService(ctx, job, batch_mode=False, spread_alg=False)
    lane = svc.pack(tg, places, nodes)
    assert lane is not None and not lane.wavefront_ok()
    return lane, ctx.state.latest_index()


def solve(lanes, use_mesh=False):
    """As the barrier dispatches them. With ``use_mesh`` on conftest's
    eight virtual devices the group takes the (evals, nodes) mesh leg,
    which several chips take by default."""
    m0 = counter("nomad.solver.mesh_dispatches")
    res = batch_mod.fuse_and_solve(lanes, use_mesh=use_mesh,
                                   e_pad_hint=WORKERS)
    assert counter("nomad.solver.mesh_dispatches") == m0 + int(use_mesh)
    return res


def commit_partial(h, nodes, job, eval_id, remaining):
    """What a partial commit leaves: the first COUNT - remaining
    placements of the job's first attempt, in the store."""
    if remaining == COUNT:
        return
    lane, _ = pack_lane(h, nodes, job, eval_id, COUNT)
    chosen = solve([lane])[0][0]
    allocs = []
    for k in range(COUNT - remaining):
        a = mock.alloc_for(job, lane.nodes[lane.order[int(chosen[k])]],
                           index=k)
        a.client_status = "running"
        allocs.append(a)
    h.state.upsert_allocs(allocs)


def reference_sequence(h, nodes, job, eval_id, index, remaining):
    tg = job.task_groups[0]
    by_id = {n.id: n for n in nodes}
    usage, counts = {}, {}
    for a in h.state.allocs():
        cr = a.allocated_resources.comparable()
        u = usage.setdefault(a.node_id, [0.0, 0.0, 0.0, 0])
        u[0] += cr.cpu_shares
        u[1] += cr.memory_mb
        u[2] += cr.disk_mb
        if a.job_id == job.id and a.task_group == tg.name:
            u[3] += 1
            rack = by_id[a.node_id].meta["rack"]
            counts[rack] = counts.get(rack, 0) + 1

    def cap(node_id):
        nr, rr = by_id[node_id].node_resources, \
            by_id[node_id].reserved_resources
        return (float(nr.cpu.cpu_shares - rr.cpu_shares),
                float(nr.memory.memory_mb - rr.memory_mb),
                float(nr.disk.disk_mb - rr.disk_mb))
    ask = (float(sum(t.resources.cpu for t in tg.tasks)),
           float(sum(t.resources.memory_mb for t in tg.tasks)),
           float(tg.ephemeral_disk.size_mb))
    order = ref.shuffled([n.id for n in nodes], eval_id, index)
    return ref.place_sequence(
        order, lambda n: usage.get(n, [0.0, 0.0, 0.0, 0]), cap, ask,
        COUNT, remaining, ref.scan_limit(len(order), COUNT, True),
        (lambda n: by_id[n].meta["rack"], counts))


def counter(name):
    return metrics.snapshot()["counters"].get(name, 0)


def gauge_count(name):
    return metrics.snapshot()["gauges"].get(name, {"count": 0})["count"]


def evals_of(case, n_lanes):
    return [(spread_job(f"df-{case}-{i}"), f"df-eval-{case}-{i:021d}")
            for i in range(n_lanes)]


MESH = pytest.mark.parametrize("use_mesh", (False, True),
                               ids=("one-device", "mesh"))


@MESH
@pytest.mark.parametrize("n_lanes", LANES)
@pytest.mark.parametrize("remaining", REMAINING)
def test_whole_axis_lane_matches_reference(remaining, n_lanes, use_mesh):
    h, nodes = build_world()
    evals = evals_of(f"{remaining}-{n_lanes}", n_lanes)
    for job, eval_id in evals:
        h.state.upsert_job(job)
        commit_partial(h, nodes, job, eval_id, remaining)
    packed = [pack_lane(h, nodes, job, eval_id, remaining)
              for job, eval_id in evals]
    d0 = counter("nomad.solver.dense_dispatches")
    results = solve([lane for lane, _ in packed], use_mesh)
    assert counter("nomad.solver.dense_dispatches") == d0 + 1
    for (job, eval_id), (lane, index), res in zip(evals, packed, results):
        chosen, scores = res[0], res[1]
        assert chosen.shape == (remaining,)
        seq = reference_sequence(h, nodes, job, eval_id, index, remaining)
        for k, (node_id, score, _window) in enumerate(seq):
            assert node_id is not None
            assert lane.nodes[lane.order[int(chosen[k])]].id == node_id, \
                f"placement {k} of {eval_id}"
            assert abs(float(scores[k]) - score) <= SCORE_TOLERANCE


@pytest.fixture(scope="module")
def warm_family():
    """One solve on each leg at the lane bucket a served system uses
    (the barrier's width): what it compiled is all the family has."""
    tpack._reset_pack_caches_for_tests()
    h, nodes = build_world()
    evals = evals_of("warm", WORKERS)
    for job, _ in evals:
        h.state.upsert_job(job)
    for use_mesh in (False, True):
        solve([pack_lane(h, nodes, job, eval_id, COUNT)[0]
               for job, eval_id in evals], use_mesh)
    return h, nodes


@MESH
@pytest.mark.parametrize("n_lanes", LANES)
@pytest.mark.parametrize("remaining", REMAINING)
def test_family_is_closed(warm_family, remaining, n_lanes, use_mesh):
    """No width of retry and no lane count meets a program the first
    dispatch did not build."""
    h, nodes = warm_family
    lanes = [pack_lane(h, nodes, job, eval_id, remaining)[0]
             for job, eval_id in evals_of(f"c{remaining}-{n_lanes}",
                                          n_lanes)]
    before = guard.compile_stats()["backend_compiles"]
    built = counter("nomad.solver.dense_programs")
    launches = gauge_count("nomad.solver.dense_steps")
    res = solve(lanes, use_mesh)
    assert all((r[0] >= 0).all() for r in res)
    assert guard.compile_stats()["backend_compiles"] == before
    assert counter("nomad.solver.dense_programs") == built
    assert gauge_count("nomad.solver.dense_steps") == launches + 1


def test_a_launch_runs_each_lane_over_its_own_steps():
    """What the gauge the benchmark reads is made from: a lane's steps
    end at its last active placement, whatever its padding."""
    from nomad_tpu.solver.binpack import active_steps
    active = np.zeros((3, 256), dtype=bool)
    active[0, :31] = True
    active[1, :5] = True
    assert active_steps(active).tolist() == [31, 5, 0]
    assert int(active_steps(active.any(axis=1))) == 2
