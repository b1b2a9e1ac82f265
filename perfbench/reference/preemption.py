"""Plain reference for placements that may evict lower-priority allocs,
written to Nomad 1.7's scheduler and independent of the program: it
imports nothing of nomad_tpu and takes nothing the program computed but
the answers under test. Plain Python floats, float64.

It scans and scores as reference/placement.py does (shuffle, log2 scan
limit, up to three skipped non-positive scores, binpack, job
anti-affinity, even spread, the mean of the scores) and adds the
scheduler's preemption, each step after the function it follows:

- generic_sched.go selectNextOption: a placement is selected with
  eviction off; only where no node is an option does it select again
  with eviction on (preemption_config's flag for the job's scheduler,
  which this module is named for: manifest.py);
- state_store.go AllocsByNodeTerminal / context.go ProposedAllocs: a
  node's proposed allocs are those committed and not terminal, less what
  the plan evicts, plus what it places. A stopped or evicted alloc is
  server-terminal from its stop's or eviction's commit
  (`committed.stopped_at`), whatever its client reports later;
- rank.go BinPackIterator.Next with evict on: a device ask that no free
  matching instance meets asks PreemptForDevice and drops what it
  evicts from the proposed allocs; then, where the proposed allocs and
  the ask do not fit (AllocsFit), PreemptForTaskGroup over the proposed
  allocs; an option that neither clears is exhausted and does not count
  toward the limit. The binpack score is taken from AllocsFit's
  utilisation before the task group's evictions (over capacity, so the
  score clamps), and the option is kept whether or not the usage after
  them fits;
- preemption.go SetCandidates: every proposed alloc but this job's own;
  PreemptForTaskGroup: node_remaining = capacity - the candidates;
  filterAndGroupPreemptibleAllocs: allocs with a job and at least 10
  priority levels below this one, grouped by priority ascending; within
  a group, repeatedly the alloc nearest the ask still needed by
  basicResourceDistance (sqrt of the squared shortfalls over the ask, of
  memory, cpu and disk) plus scoreForTaskGroup's max-parallel penalty
  ((evicted of its group + 1 - max_parallel) * 50 once the plan has
  evicted max_parallel of that group), until node_remaining plus the
  chosen covers the ask; then filterSuperset: the chosen by distance to
  the whole ask, farthest first, kept until they cover it;
- preemption.go PreemptForDevice: per matching device group, its
  eligible holders by priority ascending until the instances they hold
  plus the group's free ones meet the ask; selectBestAllocs: in each
  such option the holders by instances held, most first, until they
  hold the ask's count; the option whose distinct priorities sum least;
- rank.go PreemptionScoringIterator: an evicting option appends
  preemptionScore(netPriority(evicted)) = 1 / (1 + exp(0.0048 *
  (netPriority - 2048))), netPriority = max priority + sum of priorities
  / max priority, after the other scores and before their mean.

The source leaves open the order among equal distances (Go's unstable
sort, the swap on removal) and among device groups (a map): here the
node's allocs as the API reads them back and the node's device groups
in their order; the comparison is blind to the first
(check.eviction_class). Where the program's host stack departs from
this, PERF.md section 7 lists it as a fault of the program.
"""
from __future__ import annotations

import math

from reference import committed as cs
from reference.placement import (  # noqa: F401 -- the module interface
    MAX_SKIP, SKIP_SCORE, _f, anti_affinity, binpack_score,
    even_spread_boost, scan_limit, shuffled)

PRIORITY_DELTA = 10          # preemption.go filterAndGroupPreemptibleAllocs
MAX_PARALLEL_PENALTY = 50.0  # preemption.go maxParallelPenalty
SCORE_RATE = 0.0048          # rank.go preemptionScore
SCORE_ORIGIN = 2048.0


class Alloc:
    """One proposed alloc on a node as the eval sees it. `priority` is
    None for an alloc without a job, which nothing evicts."""
    __slots__ = ("id", "group", "priority", "max_parallel", "res", "devices",
                 "own")

    def __init__(self, id, group, priority, max_parallel, res, devices, own):
        self.id, self.group, self.priority = id, group, priority
        self.max_parallel, self.res, self.devices = max_parallel, res, devices
        self.own = own

    def eligible(self, job_priority: int) -> bool:
        """filterAndGroupPreemptibleAllocs' test."""
        return (not self.own and self.priority is not None
                and job_priority - self.priority >= PRIORITY_DELTA)


def node_allocs(on_node: list, index: int, job_id: str) -> list:
    """The node's proposed allocs at state `index`: committed by then and
    not terminal by then (an evicted or stopped one from its commit, any
    other from its client's report), in the order the API reads them
    back."""
    out = []
    for b in on_node:
        if b["create_index"] > index:
            continue
        gone = [x for x in (cs.released_at(b, on_node),
                            cs.stopped_at(b, on_node)) if x is not None]
        if gone and min(gone) <= index:
            continue
        out.append(Alloc(
            b.get("id"), (b["job_id"], b["task_group"]),
            int(b["job"]["priority"]) if b.get("job") else None,
            cs.max_parallel(b), cs.alloc_resources(b), cs.devices_held(b),
            b["job_id"] == job_id))
    return out


def distance(ask: tuple, used: tuple) -> float:
    """preemption.go basicResourceDistance over (cpu, mem, disk)."""
    mem_c = cpu_c = disk_c = 0.0
    if ask[1] > 0:
        mem_c = (ask[1] - used[1]) / ask[1]
    if ask[0] > 0:
        cpu_c = (ask[0] - used[0]) / ask[0]
    if ask[2] > 0:
        disk_c = (ask[2] - used[2]) / ask[2]
    return math.sqrt(mem_c ** 2 + cpu_c ** 2 + disk_c ** 2)


def covers(have: list, ask: tuple) -> bool:
    return all(have[i] >= ask[i] for i in range(3))


def preempt_for_task_group(proposed: list, cap: tuple, ask: tuple,
                           job_priority: int, evicted_of: dict) -> list:
    """preemption.go PreemptForTaskGroup + filterSuperset: the allocs to
    evict from a node of capacity `cap` holding `proposed` for `ask`, []
    when no set clears it. `evicted_of`: group -> allocs of it the plan
    has evicted so far (SetPreemptions)."""
    cands = [a for a in proposed if not a.own]
    remaining = [cap[i] - sum(a.res[i] for a in cands) for i in range(3)]
    needed = list(ask)
    available = list(remaining)
    groups: dict = {}
    for a in cands:
        if a.eligible(job_priority):
            groups.setdefault(a.priority, []).append(a)
    best, met = [], False
    for _prio, group in sorted(groups.items()):
        group = list(group)
        while group and not met:
            best_d, best_i = math.inf, -1
            for i, a in enumerate(group):
                d = distance(tuple(needed), a.res)
                n = evicted_of.get(a.group, 0)
                if a.max_parallel > 0 and n >= a.max_parallel:
                    d += float(n + 1 - a.max_parallel) * MAX_PARALLEL_PENALTY
                if d < best_d:
                    best_d, best_i = d, i
            closest = group.pop(best_i)
            for i in range(3):
                available[i] += closest.res[i]
                needed[i] -= closest.res[i]
            met = covers(available, ask)
            best.append(closest)
        if met:
            break
    if not met:
        return []
    # filterSuperset: farthest from the whole ask first, kept until the
    # remainder covers it
    best = sorted(best, key=lambda a: distance(ask, a.res), reverse=True)
    available, out = list(remaining), []
    for a in best:
        for i in range(3):
            available[i] += a.res[i]
        out.append(a)
        if covers(available, ask):
            break
    return out


def device_groups(node: dict, request: str) -> list:
    """[(vendor/type/name, instances)] of the node's device groups that
    a request (`type`, `vendor/type` or `vendor/type/name`) names."""
    want = request.split("/")
    out = []
    for d in node["node_resources"].get("devices") or ():
        # <type>, <vendor>/<type> or <vendor>/<type>/<name>
        if want == [d["vendor"], d["type"], d["name"]][:len(want)] \
                if len(want) > 1 else want == [d["type"]]:
            out.append((f"{d['vendor']}/{d['type']}/{d['name']}",
                        len(d.get("instance_ids") or ())))
    return out


def free_instances(key: str, n: int, proposed: list, reserved: dict) -> int:
    """A device group's instances that no proposed alloc holds and no
    earlier ask of this placement took."""
    return n - sum(a.devices.get(key, 0) for a in proposed) \
        - reserved.get(key, 0)


def assign_devices(groups: list, proposed: list, reserved: dict, count: int):
    """device.go AssignDevice without affinities: a matching group with
    `count` free instances (the first), or None."""
    for key, n in groups:
        if free_instances(key, n, proposed, reserved) >= count:
            return key
    return None


def preempt_for_device(groups: list, proposed: list, reserved: dict,
                       count: int, job_priority: int) -> list:
    """preemption.go PreemptForDevice + selectBestAllocs: the holders of
    matching instances to evict, [] when no group's can free enough."""
    options = []
    for key, n in groups:
        holders = [a for a in proposed if a.devices.get(key)
                   and a.eligible(job_priority)]
        free = free_instances(key, n, proposed, reserved)
        chosen, held = [], 0
        for prio in sorted({a.priority for a in holders}):
            for a in holders:
                if a.priority == prio and held + free < count:
                    chosen.append(a)
                    held += a.devices[key]
        if chosen and held + free >= count:
            options.append((key, chosen))
    best, best_net = [], math.inf
    for key, chosen in options:
        taken, held, prios = [], 0, set()
        for a in sorted(chosen, key=lambda a: -a.devices[key]):
            if held >= count:
                break
            taken.append(a)
            held += a.devices[key]
            prios.add(a.priority)
        if sum(prios) < best_net:
            best, best_net = taken, sum(prios)
    return best


def net_priority(allocs: list) -> float:
    """rank.go netPriority."""
    top = max((float(a.priority) for a in allocs), default=0.0)
    if top == 0.0:
        return 0.0
    return top + float(sum(a.priority for a in allocs)) / top


def preemption_score(net: float) -> float:
    """rank.go preemptionScore."""
    return 1.0 / (1.0 + math.exp(SCORE_RATE * (net - SCORE_ORIGIN)))


def option(node: dict, proposed: list, ask: tuple, asks_devices: list,
           job_priority: int, evicted_of: dict, evict: bool):
    """rank.go BinPackIterator.Next for one node: None when the node is
    exhausted, else (utilisation AllocsFit reports, with the placement
    and before the task group's evictions; evicted allocs; the device
    group each device ask takes)."""
    cap = cs.node_capacity(node)
    evicted, taken, reserved = [], [], {}
    for name, count in asks_devices:
        groups = device_groups(node, name)
        key = assign_devices(groups, proposed, reserved, count)
        if key is None:
            if not evict:
                return None
            extra = preempt_for_device(groups, proposed, reserved, count,
                                       job_priority)
            if not extra:
                return None
            evicted += extra
            proposed = [a for a in proposed if a not in evicted]
            # the device allocator is built anew from what is left
            reserved = {}
            key = assign_devices(groups, proposed, reserved, count)
            if key is None:
                return None
        reserved[key] = reserved.get(key, 0) + count
        taken.append((key, count))
    used = [sum(a.res[i] for a in proposed) + ask[i] for i in range(3)]
    if not covers([cap[i] - used[i] for i in range(3)], (0.0, 0.0, 0.0)):
        if not evict:
            return None
        extra = preempt_for_task_group(proposed, cap, ask, job_priority,
                                       evicted_of)
        if not extra:
            return None
        evicted += extra
    return used, evicted, taken


def score(node: dict, proposed: list, used: list, evicted: list,
          group: tuple, count: int, counts: dict, attr,
          dtype: str = "float64") -> float:
    """The mean of one option's scores, in rank.go's order: binpack from
    `used`, job anti-affinity over the allocs of `group` among
    `proposed`, the even-spread boost where it is not zero (`counts`:
    this plan's placements per value of the spread attribute `attr`),
    the preemption score of `evicted`. `dtype` other than float64
    rounds each score and the mean to that precision."""
    cap = cs.node_capacity(node)
    scores = [float(binpack_score(cap[0], cap[1], used[0], used[1], dtype))]
    anti = anti_affinity(sum(1 for a in proposed if a.group == group), count,
                         dtype)
    if anti is not None:
        scores.append(float(anti))
    if attr is not None:
        boost = float(even_spread_boost(
            counts, node.get("meta", {}).get(attr), dtype))
        if boost != 0.0:
            scores.append(boost)
    if evicted:
        scores.append(preemption_score(net_priority(evicted)))
    if dtype == "float64":
        return sum(scores) / len(scores)
    rounding = _f(dtype)
    scores = [float(rounding(x)) for x in scores]
    return float(rounding(sum(scores) / len(scores)))


def group_constraints(job: dict, group: str) -> list:
    tg = cs.task_group(job, group)
    return list(job.get("constraints") or ()) + list(
        tg.get("constraints") or ())


def feasible(node: dict, constraints: list, asks_devices: list) -> bool:
    """The feasibility checkers the configurations exercise: constraints
    on ${meta.*}, ${attr.*} or ${node.class} with = or !=, and a device
    group of enough instances for each device ask (DeviceChecker: the
    instances, not the free ones)."""
    for c in constraints:
        target = c["l_target"]
        if target.startswith("${meta."):
            value = (node.get("meta") or {}).get(target[7:-1])
        elif target.startswith("${attr."):
            value = (node.get("attributes") or {}).get(target[7:-1])
        elif target == "${node.class}":
            value = node.get("node_class")
        else:
            raise NotImplementedError(f"constraint target {target!r}")
        op = c.get("operand", "=")
        if op in ("=", "==", "is"):
            ok = value is not None and str(value) == str(c["r_target"])
        elif op in ("!=", "not"):
            ok = value is None or str(value) != str(c["r_target"])
        else:
            raise NotImplementedError(f"constraint operand {op!r}")
        if not ok:
            return False
    return all(any(n >= count for _key, n in device_groups(node, name))
               for name, count in asks_devices)


def replay(job: dict, group: str, order: list, usage_index: int, fetch,
           dtype: str = "float64") -> list:
    """The reference's placements of one eval's group, [(node, score,
    window, evicted alloc ids)] in placement order, as placement.replay
    with preemption on for this job's scheduler. `dtype` other than
    float64 (the control) rounds every score it compares to that
    precision."""
    count, ask = cs.plan_ask(job, group)
    asks_devices = cs.device_asks(job, group)
    constraints = group_constraints(job, group)
    attr = cs.spread_attribute(job)
    priority = int(job.get("priority", 50))
    limit = scan_limit(len(order), count, attr is not None)
    state: dict = {}        # node id -> its proposed allocs
    counts: dict = {}       # the spread's counts of this job's allocs
    evicted_of: dict = {}   # group -> its allocs this plan has evicted
    infeasible: set = set()

    def select(evict: bool):
        """stack.go Select: the best of the first `limit` options."""
        seen, aside = [], []
        for node_id in order:
            if node_id in infeasible:
                continue
            node, on_node = fetch(node_id)
            if node_id not in state:
                if not feasible(node, constraints, asks_devices):
                    infeasible.add(node_id)
                    continue
                state[node_id] = node_allocs(on_node, usage_index, job["id"])
            got = option(node, state[node_id], ask, asks_devices, priority,
                         evicted_of, evict)
            if got is None:
                continue
            used, evicted, taken = got
            mean = score(node, state[node_id], used, evicted,
                         (job["id"], group), count, counts, attr, dtype)
            if mean <= SKIP_SCORE and len(aside) < MAX_SKIP:
                aside.append((node_id, mean, evicted, taken))
                continue
            seen.append((node_id, mean, evicted, taken))
            if len(seen) == limit:
                break
        seen += aside[:limit - len(seen)]
        if not seen:
            return None
        return max(seen, key=lambda o: o[1]), [o[0] for o in seen]

    out = []
    for _ in range(count):
        got = select(False) or select(True)
        if got is None:
            out.append((None, None, [], []))
            continue
        (node_id, mean, evicted, taken), window = got
        allocs = [a for a in state[node_id] if a not in evicted]
        allocs.append(Alloc(None, (job["id"], group), priority, 0, ask,
                            dict(taken), True))
        state[node_id] = allocs
        for a in evicted:
            evicted_of[a.group] = evicted_of.get(a.group, 0) + 1
        if attr is not None:
            value = fetch(node_id)[0].get("meta", {}).get(attr)
            counts[value] = counts.get(value, 0) + 1
        out.append((node_id, mean, window, [a.id for a in evicted]))
    return out
