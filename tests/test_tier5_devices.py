"""Tier-5 with GPU device reservations on the windowed kernels
(VERDICT r4 next-step 5; BASELINE tier 5: "priority tiers + GPU device
reservations").

Covers the three layers of the device extension:
  1. kernel: uniform device-ask lanes ride the non-preempt WAVEFRONT as
     a capacity dimension, bit-identical to the dense oracle;
  2. preempt kernels: one device ask evicts its holders on the device
     (PreemptForDevice); several asks stay with the host iterator;
  3. end-to-end: the tier-5 world WITH device reservations places via a
     windowed kernel at >= 600 nodes with placement AND eviction-set
     parity against the host oracle.
"""
import itertools
import random

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.benchkit import run_tier_placements
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import AllocPlaceResult
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.solver.service import TpuPlacementService
from nomad_tpu.structs import (
    DeviceRequest, NodeDeviceResource, Plan, SchedulerConfiguration,
)


def _gpu_world(rng, n_nodes, used_frac=0.0):
    h = Harness()
    h.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-binpack"))
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"gpu-node-{i:04d}"
        n.node_resources.cpu.cpu_shares = rng.choice([4000, 8000])
        if i % 2 == 0:
            n.node_resources.devices = [NodeDeviceResource(
                vendor="nvidia", type="gpu", name="v100",
                instance_ids=[f"{n.id}-g{k}"
                              for k in range(rng.choice([2, 4]))])]
        n.compute_class()
        h.state.upsert_node(n)
        nodes.append(n)
    return h, nodes


def _pack_lane(h, job, nodes, count, preempt=False):
    tg = job.task_groups[0]
    tg.count = count
    plan = Plan(eval_id=f"dev-eval-{random.getrandbits(60):015x}0",
                priority=job.priority, job=job)
    ctx = EvalContext(h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(count)]
    service = TpuPlacementService(ctx, job, batch_mode=False,
                                  spread_alg=False, preempt=preempt)
    return service.pack(tg, places, nodes)


@pytest.mark.parametrize("seed", range(3))
def test_device_lane_rides_wavefront_bit_parity(seed):
    """Non-preempt uniform GPU lane: wavefront vs dense, bit-identical
    chosen/scores at a shape where device capacity binds."""
    from nomad_tpu.solver.binpack import (
        solve_lane_wave, solve_placements)

    rng = random.Random(seed)
    mock._counter = itertools.count()
    h, nodes = _gpu_world(rng, 96)
    job = mock.job(id=f"gpu-job-{seed}")
    job.task_groups[0].tasks[0].resources.cpu = 100   # devices bind first
    job.task_groups[0].tasks[0].resources.devices = [
        DeviceRequest(name="nvidia/gpu", count=1)]
    h.state.upsert_job(job)
    lane = _pack_lane(h, job, nodes, 64)
    assert lane is not None
    assert lane.wavefront_ok(), "uniform GPU lane must be wave-eligible"

    wc, ws, wy = solve_lane_wave(lane.const, lane.init, lane.batch,
                                 spread_alg=False, dtype_name="float32")
    dc, ds, dy, _ = solve_placements(lane.const, lane.init, lane.batch,
                                     spread_alg=False,
                                     dtype_name="float32")
    assert (np.asarray(wc) == np.asarray(dc)).all()
    assert np.allclose(np.asarray(ws), np.asarray(ds))
    assert (np.asarray(wy) == np.asarray(dy)).all()
    # the GPU fleet is half the nodes with 2-4 instances: placements
    # must exhaust device capacity somewhere (else the test proves
    # nothing about the device dimension)
    gpu_total = sum(len(n.node_resources.devices[0].instance_ids)
                    for n in nodes if n.node_resources.devices)
    assert int((np.asarray(dc) >= 0).sum()) == min(64, gpu_total)


def test_device_affinity_lane_stays_dense():
    """A device ask WITH affinities has a live score component the wave
    kernel does not model: it must gate to dense."""
    from nomad_tpu.structs import Affinity

    rng = random.Random(0)
    mock._counter = itertools.count()
    h, nodes = _gpu_world(rng, 16)
    job = mock.job(id="gpu-aff-job")
    job.task_groups[0].tasks[0].resources.devices = [
        DeviceRequest(name="nvidia/gpu", count=1,
                      affinities=[Affinity(l_target="${device.model}",
                                           r_target="v100", operand="=",
                                           weight=50)])]
    h.state.upsert_job(job)
    lane = _pack_lane(h, job, nodes, 4)
    assert lane is not None
    assert not lane.wavefront_ok()


def test_tier5_with_devices_places_via_windowed_kernel():
    """The VERDICT done-criterion: tier-5 world WITH device reservations
    at >= 600 nodes, placement + eviction-set parity host vs tpu, and
    the tpu run actually dispatching the WINDOWED preempt kernel. The
    fillers hold every GPU: at 95% of cpu a GPU ask that found a free
    instance would place without eviction (selectNextOption)."""
    metrics.reset()
    host, host_ev = run_tier_placements(5, 600, 48, seed=11,
                                        alg="binpack",
                                        with_evictions=True,
                                        gpu_holders=True)
    tpu, tpu_ev = run_tier_placements(5, 600, 48, seed=11,
                                      alg="tpu-binpack",
                                      with_evictions=True,
                                      gpu_holders=True)
    assert host, "host placed nothing -- bad world"
    assert tpu == host
    assert tpu_ev == host_ev
    assert sum(1 for v in host_ev.values() if v) >= 10, (
        "tier-5 must exercise preemption")
    # placements must land on GPU-equipped nodes only
    for name, node_id in tpu.items():
        assert int(node_id.split("-")[-1]) % 2 == 0, (name, node_id)
    snap = metrics.snapshot()["counters"]
    assert snap.get("nomad.solver.wavefront_preempt_dispatches", 0) >= 1, (
        "tier-5 device lane did not ride the windowed preempt kernel: "
        f"{ {k: v for k, v in snap.items() if 'solver' in k} }")
    assert snap.get("nomad.scheduler.placements_host_fallback", 0) == 0


def test_preempt_device_lane_with_candidate_gpus_falls_back_to_host():
    """Candidates holding matching devices would be freed by eviction
    (PreemptForDevice territory). The kernels evict for one device ask;
    a task group asking for two, each assigned in turn against what the
    last evicted, is routed by pack() to the host iterator."""
    from nomad_tpu.structs import (
        AllocatedDeviceResource, PreemptionConfig)

    rng = random.Random(2)
    mock._counter = itertools.count()
    h, nodes = _gpu_world(rng, 12)
    cfg = SchedulerConfiguration(
        scheduler_algorithm="tpu-binpack",
        preemption_config=PreemptionConfig(
            service_scheduler_enabled=True))
    h.state.set_scheduler_config(cfg)
    # low-priority filler HOLDING a gpu on every gpu node
    for n in nodes:
        if not n.node_resources.devices:
            continue
        j = mock.job(priority=20)
        j.id = f"gpu-filler-{n.id}"
        h.state.upsert_job(j)
        a = mock.alloc_for(j, n)
        a.client_status = "running"
        tr = a.allocated_resources.tasks["web"]
        tr.devices.append(AllocatedDeviceResource(
            vendor="nvidia", type="gpu", name="v100",
            device_ids=[n.node_resources.devices[0].instance_ids[0]]))
        h.state.upsert_allocs([a])

    job = mock.job(id="gpu-preempt-job", priority=70)
    job.task_groups[0].tasks[0].resources.devices = [
        DeviceRequest(name="nvidia/gpu", count=1),
        DeviceRequest(name="nvidia/gpu/v100", count=1)]
    h.state.upsert_job(job)
    metrics.reset()
    lane = _pack_lane(h, job, nodes, 4, preempt=True)
    assert lane is None, "candidate-held GPUs must force host fallback"
    snap = metrics.snapshot()["counters"]
    assert snap.get("nomad.solver.device_preempt_host_fallback", 0) >= 1
