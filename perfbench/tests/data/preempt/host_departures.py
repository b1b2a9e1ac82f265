"""reference/preemption.py with four departures of the program's host
stack from Nomad 1.7 laid over it (PERF.md section 7, item 0): (a) one
select with eviction on (rank.py, generic.py: `evict` is always on); (b)
an evicting option scored on its usage after the evictions and dropped
where that does not fit (rank.py fit2); (c) a stopped or evicted alloc
counted until its client reports it (`host_node_allocs`); and (j)
PreemptForDevice as scheduler/preemption.py has it
(`host_preempt_for_device`). No cell uses
it: the rehearsals copy it into a tree's reference/ where they need the
program's own placements as the baseline, so that a wrong eviction shows
on the nodes where both place (tests/test_preempt_standin.py), and
tests/conftest.py lays it over the reference beside the host stack."""
import math

from reference import committed as cs
from reference import preemption

scan_limit, shuffled = preemption.scan_limit, preemption.shuffled
score, nomads_node_allocs = preemption.score, preemption.node_allocs


def host_node_allocs(on_node: list, index: int, job_id: str) -> list:
    """preemption.node_allocs as the program counts a node's allocs
    (scheduler/context.py proposed_allocs, the device path's fold of the
    alloc table's `live` column): a stopped or evicted one holds what it
    held until its client reports it terminal, and from its stop's or
    eviction's commit nothing evicts it (scheduler/preemption.py
    set_candidates)."""
    out = nomads_node_allocs(
        [dict(b, desired_status="run") for b in on_node], index, job_id)
    stopped = {b["id"] for b in on_node
               if (cs.stopped_at(b, on_node) or math.inf) <= index}
    for a in out:
        if a.id in stopped:
            a.priority = None
    return out


def host_preempt_for_device(groups: list, proposed: list, reserved: dict,
                            count: int, job_priority: int) -> list:
    """Preemptor.preempt_for_device: per matching device group, its
    eligible holders (`reserved` unread); free = the group's instances
    less what those holders hold, so an ineligible holder's instances
    count as free and a group they fill is passed; the holders by
    instances held, most first and in the node's order among equals,
    until they hold the ask's count less the free; the option whose
    distinct priorities sum least, the first among equals. Nomad's takes
    the holders by priority first (reference/preemption.py)."""
    best, best_net = [], math.inf
    for key, n in groups:
        holders = [a for a in proposed if a.devices.get(key)
                   and a.eligible(job_priority)]
        if not holders:
            continue
        needed = count - max(n - sum(a.devices[key] for a in holders), 0)
        if needed <= 0:
            continue
        covered, chosen = 0, []
        for a in sorted(holders, key=lambda a: -a.devices[key]):
            if covered >= needed:
                break
            covered += a.devices[key]
            chosen.append(a)
        net = sum({a.priority for a in chosen})
        if covered >= needed and net < best_net:
            best, best_net = chosen, net
    return best


def departed(option):
    """`option` (preemption.option) as the host stack has it."""
    def host_option(node, proposed, ask, asks_devices, priority,
                    evicted_of, evict):
        nomads = preemption.preempt_for_device
        preemption.preempt_for_device = host_preempt_for_device
        try:
            got = option(node, proposed, ask, asks_devices, priority,
                         evicted_of, True)
        finally:
            preemption.preempt_for_device = nomads
        if got is None or not got[1]:
            return got
        used, evicted, taken = got
        cap = cs.node_capacity(node)
        left = [a for a in proposed if a not in evicted]
        used = [sum(a.res[i] for a in left) + ask[i] for i in range(3)]
        if any(used[i] > cap[i] for i in range(3)):
            return None
        return used, evicted, taken
    return host_option


option, node_allocs = departed(preemption.option), host_node_allocs


def replay(job: dict, group: str, order: list, usage_index: int, fetch,
           dtype: str = "float64") -> list:
    """preemption.replay with the departures laid over it."""
    nomads = preemption.option, preemption.node_allocs
    preemption.option, preemption.node_allocs = option, node_allocs
    try:
        return preemption.replay(job, group, order, usage_index, fetch,
                                 dtype)
    finally:
        preemption.option, preemption.node_allocs = nomads
