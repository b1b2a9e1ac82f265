"""The comparison that decides `correct`: the answers the window
produced, read back over the API once it has closed, against the plain
reference the configuration names (`reference`, by default
reference/placement.py; reference/preemption.py for a deployment that
evicts).

Compared, each with its own limit (limits/<name>.json, set from readings
on the chip, PERF.md section 2):

- missing_allocs: placements asked for in the window that do not read
  back as committed, over every window job;
- not_device: dispatches or placements answered by anything but the
  device (guard timeouts, errors, host fallbacks, breaker trips,
  placements the host iterators made under a tpu algorithm);
- blocked_evals: evals blocked for want of capacity at the window's end;
- overcommitted_nodes: nodes the comparison read that ever held more
  than their capacity when their whole alloc history is replayed in
  index order;
- unreproduced_evals: sampled evals whose first plan the reference does
  not reproduce from any state the eval can have been solved against
  (`compare_plan`); those of which it replayed no state at all
  (`scan_reach` admitted none) the run reports beside it, as
  `unreplayed_evals`: the instrument never looked;
- choice_mismatches: over the sampled evals' first plans, placements
  that sit on another node than the reference chose in the same scan
  order from the same committed state, and that no other eval's
  placement on the reference's node, committed beside this plan,
  explains (`taken`: the cross-lane fixpoint moves a placement only off
  a node that lanes ahead of it had);
- score_gap_max: over the placements that sit where the reference put
  them, the widest distance between the score the program reported for
  the node and the reference's float64 score of it at the replayed
  state; a placement with no score reads infinity;
- eviction_mismatches: over the placements that sit where the reference
  put them, those whose evicted allocs (the allocs on the node whose
  preempted_by_allocation names the placement) are not the reference's,
  compared as multisets of `eviction_class`, and that no committed state
  of that one node between the eval's snapshot and its plan explains
  (`own_state_gap`: the device path reads each node's usage live).
"""
from __future__ import annotations

import bisect
import importlib
import json
import os
import re

from reference import committed as cs

HERE = os.path.dirname(os.path.abspath(__file__))
# a plan solved against a snapshot can commit this many raft indexes
# after a lane that was ahead of it in the same fused dispatch
INDEX_SLACK = 64
# the reference reproduces a plan when this share of its placements sit
# where the reference put them (a wrong index reproduces none)
REPRODUCED_SHARE = 0.5
# the most committed states one eval is replayed at, over all its
# snapshot indexes: set from the most that sound runs walk (PERF.md
# section 2); an eval that would walk further keeps its best comparison
WALK_STATES = 128
# the most node reads (one node a replay scans for one placement) that
# the replays of all of a run's sampled evals make together: a check's
# time grows with them. Set from the most that sound runs read (PERF.md
# section 2); once they are spent, the evals left are not replayed
WALK_READS = 12_000_000


def evicts(config: dict) -> bool:
    """Whether a deployment's scheduler evicts for its window's jobs:
    the `scheduler.preemption` flag of their scheduler (`job.type`)."""
    flags = (config.get("scheduler") or {}).get("preemption") or {}
    return flags.get((config.get("job") or {}).get("type")) is True


def reference_name(config: dict) -> str:
    """The reference module a configuration names, by default the one
    its preemption settings call for: `preemption` where the scheduler
    evicts, else `placement` (manifest.py refuses a file that names one
    against its settings)."""
    return config.get("reference",
                      "preemption" if evicts(config) else "placement")


def reference(name: str = "placement"):
    """The reference module perfbench/reference/<name>.py: `replay(job,
    group, order, usage_index, fetch, dtype)` -> [(node, score, window,
    evicted alloc ids)], `scan_limit` and `shuffled`; one that evicts
    also `node_allocs`, `option` and `score`, one node's answer and its
    score (`own_state_gap`)."""
    return importlib.import_module(f"reference.{name}")


ref = reference()


def name_index(a: dict) -> int:
    m = re.search(r"\[(\d+)\]$", a["name"])
    return int(m.group(1)) if m else 0


def eviction_class(a: dict) -> tuple:
    """What an evicted alloc is to the rest of the eval: its priority,
    what it held, and its group where a migrate stanza makes the count of
    that group's evictions part of the next choice. Allocs of one class
    are interchangeable (the source leaves the order among equal
    distances open: Go's unstable sort, the swap on removal), so evicted
    sets are compared as multisets of classes."""
    held = tuple(sorted(cs.devices_held(a).items()))
    group = (a["job_id"], a["task_group"]) if cs.max_parallel(a) else None
    return (cs.alloc_priority(a), cs.alloc_resources(a), held, group)


def evicted_classes(on_node: list, alloc_id: str) -> list:
    """Sorted classes of the allocs that `alloc_id`'s placement evicted:
    those on its node whose preempted_by_allocation names it."""
    return sorted(eviction_class(b) for b in on_node
                  if b.get("preempted_by_allocation") == alloc_id)


def taken(on_node: list, cap: tuple, before: list, ask: tuple,
          index: int, plan_index: int, eval_id: str):
    """What other evals did to this node after the state at `index` and
    no later than INDEX_SLACK past this plan. "filled": their placements
    committed there leave no room for `ask` beside what the node held
    (`before`, this eval's own earlier placements included): a lane
    ahead in the same fused dispatch has the node, and the fixpoint
    moves the placement. "touched": they committed there without filling
    it: what refuses a lane ahead its share of the node, whose charge
    the fixpoint's ledger still holds and no history shows. None: no
    other eval came near, and nothing of the program's moves a
    placement off such a node, full or not (a reference that evicts
    picks full nodes)."""
    used, touched = list(before[:3]), False
    for b in on_node:
        if (b["eval_id"] != eval_id
                and index < b["create_index"] <= plan_index + INDEX_SLACK):
            touched = True
            for i, x in enumerate(cs.alloc_resources(b)):
                used[i] += x
    if not touched:
        return None
    return "filled" if any(used[i] + ask[i] > cap[i] for i in range(3)) \
        else "touched"


def against(served: dict, seq: list, job: dict, group: str, eval_id: str,
            usage_index: int, plan_index: int, fetch,
            evicted: dict = None) -> dict:
    """`served` beside the reference's `seq`: `same` (placement numbers
    that sit where the reference put them), `gaps` (their score
    distances), `moved` ([filled, touched]: elsewhere, and `taken`
    explains it), `mismatches` (elsewhere, unexplained: [placement
    number, the reference's node, the served node, what the reference's
    node held, how many indexes past this plan other evals committed on
    it]), `eviction_mismatches` (in `same`, but `evicted[k]`, the sorted
    classes the program's placement evicted, are not the reference's:
    [placement number, node, the reference's, the program's])."""
    _count, ask = cs.plan_ask(job, group)
    same = [k for k in served if seq[k][0] == served[k][0]]
    gaps = [abs(served[k][1] - seq[k][1]) if served[k][1] is not None
            else float("inf") for k in same]
    eviction_mismatches = []
    for k in same:
        if seq[k][3] or (evicted or {}).get(k):
            by_id = {b.get("id"): b for b in fetch(seq[k][0])[1]}
            mine = sorted(eviction_class(by_id[i]) for i in seq[k][3])
            theirs = (evicted or {}).get(k, [])
            if mine != theirs:
                eviction_mismatches.append([k, seq[k][0], mine, theirs])
    mismatches, moved, own = [], {"filled": 0, "touched": 0}, {}
    for k, (r, _score, _window, _evicted) in enumerate(seq):
        if k in served and served[k][0] != r:
            if r is None:
                mismatches.append([k, r, served[k][0], None, []])
            else:
                node, on_node = fetch(r)
                before = cs.usage_at(on_node, usage_index, job["id"], group)
                for i in range(3):
                    before[i] += own.get(r, 0) * ask[i]
                why = taken(on_node, cs.node_capacity(node), before, ask,
                            usage_index, plan_index, eval_id)
                if why:
                    moved[why] += 1
                else:
                    mismatches.append([k, r, served[k][0], before, sorted(
                        {b["create_index"] - plan_index for b in on_node
                         if b["eval_id"] != eval_id
                         and b["create_index"] > usage_index})[:4]])
        if r is not None:
            own[r] = own.get(r, 0) + 1
    return {"same": same, "gaps": gaps, "moved": moved,
            "mismatches": mismatches,
            "eviction_mismatches": eviction_mismatches}


def scan_reach(n: int, limit: int):
    """(reach, share): a snapshot index is replayed when its shuffle of
    the `n` nodes puts `share` of the plan or more in its first `reach`
    positions, for an eval that looks at `limit` fitting nodes a
    placement. (None, 0.0), which every index passes, where no such rule
    can tell.

    Under the right index every placement is the best of the first
    `limit` nodes in scan order that the ask fits. With a share f of the
    fleet unable to take it those lie over limit / (1 - f) positions,
    and the plan over that span wherever the scores put it: three
    quarters of it inside 3 * limit at f = 3/4. 4 * limit leaves a third
    as much again for the nodes the eval itself fills and holds to f =
    0.81. Under a wrong index the plan's m nodes lie anywhere, and three
    quarters of them fall inside 4 * limit with the upper tail of
    Binomial(m, 4 * limit / n): at the sweep's far corner (1,200 of
    10,000) 8e-7 for 80 nodes and 2e-18 for 250. Past three fifths of
    the fleet 250 nodes pass three times in ten million and fewer more
    often, while the right index's three quarters may lie beyond: a
    window wider than 3/20 of the fleet is not filtered.

    A window of which four fit into an eighth of the fleet (the 14 nodes
    of a binpack eval, whose plan slides down the scan as it fills node
    after node) keeps the rule it was given first: half of the plan
    inside that eighth, 1e-8 for a wrong index and 40 nodes."""
    floor = max(8 * ref.scan_limit(n, 0, False), n // 8)
    if 4 * limit <= floor:
        return floor, 0.5
    return (4 * limit, 0.75) if 4 * limit <= 3 * n // 5 else (None, 0.0)


def share_in_reach(served: dict, order: list, reach) -> float:
    """The share of a plan's placements on the first `reach` nodes of a
    scan order (on any of them where `reach` is None)."""
    head = set(order[:reach])
    return sum(node in head for node, _s in served.values()) / len(served)


def own_states(on_node: list, index: int, plan_index: int) -> list:
    """The committed states of one node from an eval's snapshot `index`
    up to its plan's commit: the snapshot's, then each at which one of
    the node's allocs was created, stopped, released or reported
    terminal by its client."""
    return [index] + sorted(
        {x for b in on_node
         for x in (b["create_index"], cs.released_at(b, on_node),
                   cs.stopped_at(b, on_node), cs.reported_at(b))
         if x is not None and index < x < plan_index})


def own_state_gap(k: int, served: dict, ids: dict, theirs: list, job: dict,
                  group: str, index: int, plan_index: int, fetch, reference):
    """Placement `k` sits on the reference's node and evicted the classes
    `theirs` there, unlike the replay of the whole plan. Judged again at
    each committed state of its own node (`own_states`), with the plan's
    earlier placements on that node and what they evicted laid over it:
    the answer of `reference.option` for that one node, where fitting
    without eviction is the answer "nothing", and its score
    (`reference.score`). The device path reads each node's usage live,
    so a node it read may have had room at a state the replay of the
    whole plan did not pick (PERF.md section 2). Returns the narrowest
    distance between the program's score and the score of a state whose
    answer is `theirs` (None where no state's is), and each state with
    its answer ([state, the priorities evicted, [] for none; None where
    the node is exhausted]). `ids`: placement number -> alloc id."""
    node_id = served[k][0]
    node, on_node = fetch(node_id)
    by_id = {b["id"]: b for b in on_node}
    count, ask = cs.plan_ask(job, group)
    asks_devices = cs.device_asks(job, group)
    attr = cs.spread_attribute(job)
    earlier = {ids[j] for j in served if j < k}
    evicted_of: dict = {}   # group -> its allocs the plan evicted before k
    counts: dict = {}       # the spread's counts of the plan before k
    for j in served:
        if j < k:
            other, on_other = fetch(served[j][0])
            for b in on_other:
                if b.get("preempted_by_allocation") == ids[j]:
                    g = (b["job_id"], b["task_group"])
                    evicted_of[g] = evicted_of.get(g, 0) + 1
            if attr is not None:
                value = other.get("meta", {}).get(attr)
                counts[value] = counts.get(value, 0) + 1
    placed = [b for b in on_node if b["id"] in earlier]
    gone = {b["id"] for b in on_node
            if b.get("preempted_by_allocation") in earlier}
    best, answers = None, []
    for state in own_states(on_node, index, plan_index):
        proposed = [a for a in reference.node_allocs(on_node, state,
                                                     job["id"])
                    if a.id not in gone]
        proposed += reference.node_allocs(placed, plan_index, job["id"])
        got = reference.option(node, proposed, ask, asks_devices,
                               int(job.get("priority", 50)), evicted_of,
                               True)
        answers.append([state, None if got is None else sorted(
            a.priority for a in got[1])])
        if got is None or sorted(
                eviction_class(by_id[a.id]) for a in got[1]) != theirs:
            continue
        gap = float("inf") if served[k][1] is None else abs(
            served[k][1] - reference.score(
                node, proposed, got[0], got[1], (job["id"], group), count,
                counts, attr))
        best = gap if best is None else min(best, gap)
    return best, answers


def walk_budget() -> dict:
    """What the replays of one run's sampled evals may still read."""
    return {"reads": WALK_READS}


def compare_plan(served: dict, ids: dict, job: dict, group: str,
                 eval_id: str, plan_index: int, indexes, base_order: list,
                 fetch, reference=ref, evicted: dict = None,
                 budget: dict = None) -> dict:
    """One eval's first plan against the reference.

    `served`: placement number (the alloc name's index) -> (node id,
    score reported or None), for the allocs that plan committed at
    `plan_index`; `ids`: placement number -> alloc id; `indexes`: the
    state indexes the eval's snapshot can have had, likeliest first;
    `base_order`: the ready nodes in registration order; `reference`:
    the module that replays the eval; `evicted`: placement number -> the
    sorted classes of what it evicted (`evicted_classes`), for a
    deployment that evicts. An evicted set unlike the reference's is
    judged again at its own node's states (`own_state_gap`): where one
    state gives it, and the program's score lies no farther from that
    state's than from the replayed one's, the score gap is taken there;
    else the mismatch stays, listed with every state's answer. `budget`:
    the run's `walk_budget`, which every node a replay reads draws on (a
    fresh one where None).

    The eval's scan order is the shuffle its snapshot's index seeds; the
    usage it packs is the live alloc table's when it packs, a committed
    state no older than the snapshot and older than the plan. So for
    each snapshot index that puts the plan at the head of the scan, as
    far as this eval's own window reaches (`scan_reach`), and each later
    state at which a node the scan reached changed, the reference
    replays the eval and is compared with `served` (`against`); the pair
    with the fewest unexplained placements, then the fewest evicted sets
    unlike the reference's, stands. No more than WALK_STATES states are
    replayed in all, and none once the run's budget is spent; an eval
    that would need more keeps the best so far, which does not reproduce
    the plan, and says so (`walk_capped`).
    Returns that comparison with `reproduced` (REPRODUCED_SHARE of the
    plan sits where the reference put it), `index`, `usage_index`,
    `order`, `walked` (the states replayed) and `walk_capped`; `index`
    is None when no index was replayed at all."""
    budget = walk_budget() if budget is None else budget
    count, _ask = cs.plan_ask(job, group)
    reach, share = scan_reach(len(base_order), reference.scan_limit(
        len(base_order), count, cs.spread_attribute(job) is not None))
    best, walked, capped = None, 0, False
    events: dict = {}   # node id -> the indexes at which its allocs changed
    for index in indexes:
        order = reference.shuffled(base_order, eval_id, index)
        if share_in_reach(served, order, reach) < share:
            continue    # no node is read for a wrong index
        usage_index = index
        while usage_index is not None:
            if walked == WALK_STATES or budget["reads"] <= 0:
                capped = True
                break
            walked += 1
            seen: set = set()

            def fetch_seen(node_id, seen=seen):
                seen.add(node_id)
                budget["reads"] -= 1
                return fetch(node_id)
            seq = reference.replay(job, group, order, usage_index,
                                   fetch_seen)
            got = against(served, seq, job, group, eval_id, usage_index,
                          plan_index, fetch_seen, evicted)
            kept, gaps = [], dict(zip(got["same"], got["gaps"]))
            for m in got["eviction_mismatches"]:
                gap, answers = own_state_gap(
                    m[0], served, ids, m[3], job, group, index, plan_index,
                    fetch, reference)
                # the state that explains the evictions explains the score
                # no worse than the replayed one
                if gap is None or gap > gaps[m[0]]:
                    kept.append(m + [answers])
                else:
                    gaps[m[0]] = gap
            got.update(index=index, usage_index=usage_index, order=order,
                       gaps=[gaps[k] for k in got["same"]],
                       eviction_mismatches=kept, evictions_at_own_state=len(
                           got["eviction_mismatches"]) - len(kept))
            if best is None or rank(got) < rank(best):
                best = got
            if not got["mismatches"] and not got["eviction_mismatches"]:
                break
            # the next state at which a node this replay read changed
            for node_id in seen - events.keys():
                on_node = fetch(node_id)[1]
                events[node_id] = sorted(
                    {x for b in on_node
                     for x in (b["create_index"], cs.released_at(b, on_node))
                     if x is not None})
            usage_index = min(
                (x for node_id in seen for x in events[node_id][bisect.bisect(
                    events[node_id], usage_index):][:1] if x < plan_index),
                default=None)
        if capped or best is not None and not best["mismatches"] and \
                not best["eviction_mismatches"] and \
                len(best["same"]) >= REPRODUCED_SHARE * len(served):
            break
    if best is None:
        return {"reproduced": False, "index": None, "usage_index": None,
                "same": [], "gaps": [], "mismatches": [],
                "eviction_mismatches": [], "evictions_at_own_state": 0,
                "moved": {"filled": 0, "touched": 0},
                "walked": walked, "walk_capped": capped}
    best["reproduced"] = len(best["same"]) >= REPRODUCED_SHARE * len(served)
    best.update(walked=walked, walk_capped=capped)
    return best


def rank(got: dict) -> tuple:
    return (len(got["mismatches"]), len(got["eviction_mismatches"]),
            -len(got["same"]))


def load_limits(name: str) -> dict:
    with open(os.path.join(HERE, "limits", f"{name}.json")) as f:
        return json.load(f)["limits"]


def verdict(numbers: dict, limits: dict) -> tuple:
    """(correct, [[name, number, limit], ...]): every number has a
    limit and none is over it."""
    rows, ok = [], True
    for name in sorted(numbers):
        if name not in limits:
            raise KeyError(f"no limit for compared number {name!r}")
        value, limit = numbers[name], limits[name]
        rows.append([name, value, limit])
        if value is None or value > limit:
            ok = False
    return ok, rows
