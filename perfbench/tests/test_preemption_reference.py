"""reference/preemption.py, written to Nomad 1.7, beside the program's
host iterator stack on seeded worlds of `nomad_tpu.benchkit` (BASELINE
tier 5: a fleet of three machine classes filled by priority 10-40
fillers, one priority-70 job; a cpu-only variant without its GPU ask;
and a variant whose fillers hold every GPU), each read the way check.py
reads the served one, as the API's JSON (`structs.codec.encode`):

- where both follow the same source function (PreemptForTaskGroup with
  filterSuperset, netPriority, preemptionScore), exactly;
- where the host departs from the source (PERF.md section 7, item 0),
  the host reads as the reference with those departures laid over it
  (`conftest.host_departures`), or as the reference itself once the
  program follows the source: recorded, not forced on the reference."""
import copy
import itertools
import random

import pytest
from conftest import against_host, device_pairs, preemptor_pairs, tier5_world

from reference import preemption as ref

# (nodes, count, seed, cpu_only): at least eight worlds, 200-1,000 nodes
WORLDS = [(200, 12, 11, False), (200, 24, 12, True), (300, 20, 13, False),
          (400, 16, 14, True), (500, 30, 15, False), (600, 25, 16, True),
          (800, 20, 17, False), (1000, 40, 18, True), (1000, 30, 19, False)]


@pytest.mark.parametrize("n,count,seed,cpu_only", WORLDS)
def test_the_reference_preempts_as_the_host_preemptor(n, count, seed,
                                                       cpu_only):
    """Every node the ask does not fit: the same classes evicted, the
    same preemption score."""
    world, _plan, state = tier5_world(n, count, seed, cpu_only, alg=None)
    pairs = preemptor_pairs(world, state)
    assert len(pairs) > n // 2
    for theirs, mine, s_theirs, s_mine in pairs:
        assert mine and mine == theirs
        assert abs(s_theirs - s_mine) < 1e-12


@pytest.mark.parametrize("util", [0.95, 1.0])
@pytest.mark.parametrize("n,count,seed,cpu_only", WORLDS)
def test_the_host_stack_departs_only_where_perf_md_says(n, count, seed,
                                                        cpu_only, util):
    """At 95% some node fits each ask and Nomad evicts nothing, where
    the host evicts; at 100% every placement evicts and only the score
    departs. Either way the host is the reference with the listed
    departures laid over it, or the reference."""
    world, plan, _state = tier5_world(n, count, seed, cpu_only, util=util)
    plain = against_host(world, plan)
    departed = against_host(world, plan, departures=True)
    assert plain[3] == count
    assert any(got[:2] == (count, count) and got[2] < 1e-9
               for got in (plain, departed)), (plain, departed)


# (nodes, count, seed): the fillers hold every GPU instance (`hold_gpus`)
# and the job asks for one, so every placement evicts for a device
DEVICE_WORLDS = [(200, 12, 21), (300, 16, 22), (400, 20, 23),
                 (600, 24, 24), (1000, 30, 25)]


@pytest.mark.parametrize("n,count,seed", DEVICE_WORLDS)
def test_device_preemption_beside_the_host_preemptor(n, count, seed):
    """Every node whose GPUs are all held: the host's
    Preemptor.preempt_for_device evicts what host_departures'
    PreemptForDevice does on every node, or what the reference's does on
    every node once the program follows the source; where the two
    differ, the host takes a holder of a higher priority or of more
    instances (PERF.md section 7, item 0 (j))."""
    world, _plan, _state = tier5_world(n, count, seed, False, alg=None,
                                       gpu_holders=True)
    pairs = device_pairs(world)
    assert len(pairs) == n // 2
    assert all(host and mine for _node, host, mine, _dep in pairs)
    assert all(host == dep for _node, host, _mine, dep in pairs) or all(
        host == mine for _node, host, mine, _dep in pairs)
    for _node, host, mine, _dep in pairs:
        if host != mine:
            assert min(c[0] for c in host) > min(c[0] for c in mine) or \
                sum(dict(c[2]).get("nvidia/gpu/v100", 0) for c in host) > \
                sum(dict(c[2]).get("nvidia/gpu/v100", 0) for c in mine)


@pytest.mark.parametrize("n,count,seed", DEVICE_WORLDS)
def test_a_device_ask_evicts_as_the_reference_with_the_departures(n, count,
                                                                   seed):
    """The host stack's whole plan: every placement evicts for its GPU,
    and sits where the reference with the host's departures laid over it
    puts it, evicting the same classes with the same score (or where the
    reference does)."""
    world, plan, _state = tier5_world(n, count, seed, False,
                                      gpu_holders=True)
    assert len(plan[1]) == count and all(plan[1].values())
    plain = against_host(world, plan)
    departed = against_host(world, plan, departures=True)
    assert any(got[:2] == (count, count) and got[2] < 1e-9
               for got in (plain, departed)), (plain, departed)


def test_the_final_history_replays_each_eval_as_its_live_state():
    """Twelve priority-70 evals in turn on one world, the host stack
    committing each plan: a job stopped (its client reporting it a step
    later) every third step, half of the evicted allocs reported complete
    after each. Each eval's reference plan from the history as it reads
    at the end, at the eval's own index (each stop's commit where the
    fleet stand-in saw it, `stop_index`; each eviction's at its evicting
    placement), is its plan from the world as it stood live."""
    from nomad_tpu import benchkit, mock
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.structs import codec
    from nomad_tpu.structs.config import (PreemptionConfig,
                                          SchedulerConfiguration)
    rng = random.Random(5)
    saved, mock._counter = mock._counter, itertools.count()
    try:
        h = Harness()
        cfg = SchedulerConfiguration(scheduler_algorithm="binpack")
        cfg.preemption_config = PreemptionConfig(
            service_scheduler_enabled=True)
        h.state.set_scheduler_config(cfg)
        nodes = benchkit.make_fleet(rng, h, 200, gpus=True)
        benchkit.seed_utilization(rng, h, nodes, 0.95,
                                  priorities=(10, 20, 30, 40))
        steps, stop_index, jobs, evicting = [], {}, [], 0
        for step in range(12):
            job = benchkit.tier_job(5, rng, 10)
            job.task_groups[0].tasks[0].resources.devices = []
            job.id = f"j{step}"
            h.state.upsert_job(job)
            ev = mock.evaluation(job_id=job.id, type=job.type,
                                 priority=job.priority)
            ev.id = f"ev-{step:010d}"
            index = h.state.latest_index()
            base = [n.id for n in h.state.ready_nodes_in_pool(job.node_pool)
                    if n.datacenter in job.datacenters]
            live = {n.id: (codec.encode(n), [codec.encode(a) for a in
                                             h.state.allocs_by_node(n.id)])
                    for n in nodes}
            steps.append((codec.encode(job), base, ev.id, index, ref.replay(
                codec.encode(job), "web", ref.shuffled(base, ev.id, index),
                index, live.__getitem__)))
            assert h.process(job.type, ev) is None
            writes = []
            if step % 3 == 1:
                for a in h.state.allocs_by_job("default", job.id):
                    writes.append(copy.copy(a))
                    writes[-1].desired_status = "stop"
            if step % 3 == 2:
                for a in h.state.allocs_by_job("default", jobs[-1]):
                    if a.desired_status == "stop":
                        stop_index[a.id] = a.modify_index
                        writes.append(copy.copy(a))
                        writes[-1].client_status = "complete"
            evictions = [a for n in nodes for a in h.state.allocs_by_node(n.id)
                         if a.desired_status == "evict"
                         and a.client_status == "running"]
            evicting += len(evictions)
            for a in evictions[:len(evictions) // 2]:
                writes.append(copy.copy(a))
                writes[-1].client_status = "complete"
            if writes:
                h.state.upsert_allocs(writes)
            jobs.append(job.id)
    finally:
        mock._counter = saved
    assert evicting > 0 and stop_index
    fleet = {n.id: (codec.encode(n), [
        dict(codec.encode(a), **({"stop_index": stop_index[a.id]}
                                 if a.id in stop_index else {}))
        for a in h.state.allocs_by_node(n.id)]) for n in nodes}
    for job, base, eval_id, index, then in steps:
        now = ref.replay(job, "web", ref.shuffled(base, eval_id, index),
                         index, fleet.__getitem__)
        assert [(s[0], s[1], sorted(s[3])) for s in now] == [
            (s[0], s[1], sorted(s[3])) for s in then]
