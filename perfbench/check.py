"""The comparison that decides `correct`: the answers the window
produced, read back over the API once it has closed, against the plain
reference (reference/placement.py).

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
  state; a placement with no score reads infinity.
"""
from __future__ import annotations

import json
import os
import re

from reference import placement as ref

HERE = os.path.dirname(os.path.abspath(__file__))
# a plan solved against a snapshot can commit this many raft indexes
# after a lane that was ahead of it in the same fused dispatch
INDEX_SLACK = 64
# the reference reproduces a plan when this share of its placements sit
# where the reference put them (a wrong index reproduces none)
REPRODUCED_SHARE = 0.5


def alloc_resources(a: dict) -> tuple:
    res = a["allocated_resources"]
    cpu = sum(t["cpu_shares"] for t in res["tasks"].values())
    mem = sum(t["memory_mb"] for t in res["tasks"].values())
    return float(cpu), float(mem), float(res["shared"]["disk_mb"])


def node_capacity(n: dict) -> tuple:
    nr, rr = n["node_resources"], n.get("reserved_resources") or {}
    return (float(nr["cpu"]["cpu_shares"] - rr.get("cpu_shares", 0)),
            float(nr["memory"]["memory_mb"] - rr.get("memory_mb", 0)),
            float(nr["disk"]["disk_mb"] - rr.get("disk_mb", 0)))


def released_at(a: dict):
    """The index at which an alloc stopped counting against its node:
    its last write once the client has reported it terminal, never
    before (context.go ProposedAllocs)."""
    if a["client_status"] in ("complete", "failed", "lost"):
        return a["modify_index"]
    return None


def name_index(a: dict) -> int:
    m = re.search(r"\[(\d+)\]$", a["name"])
    return int(m.group(1)) if m else 0


def usage_at(on_node: list, index: int, job_id: str, group: str) -> list:
    """[cpu, mem, disk, allocs of this job's group] the node held in the
    state at `index`: every alloc committed by then that its client had
    not yet reported terminal."""
    u = [0.0, 0.0, 0.0, 0]
    for b in on_node:
        if b["create_index"] > index:
            continue
        r_b = released_at(b)
        if r_b is not None and r_b <= index:
            continue
        for i, x in enumerate(alloc_resources(b)):
            u[i] += x
        if b["job_id"] == job_id and b["task_group"] == group:
            u[3] += 1
    return u


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
    placement off such a node."""
    used, touched = list(before[:3]), False
    for b in on_node:
        if (b["eval_id"] != eval_id
                and index < b["create_index"] <= plan_index + INDEX_SLACK):
            touched = True
            for i, x in enumerate(alloc_resources(b)):
                used[i] += x
    if any(used[i] + ask[i] > cap[i] for i in range(3)):
        return "filled"
    return "touched" if touched else None


def spread_attribute(job: dict):
    """`${meta.rack}` -> `rack`; the one even spread the configurations
    use. None when the group has no spread."""
    for tg in job["task_groups"]:
        for s in tg.get("spreads") or []:
            m = re.match(r"^\$\{meta\.(.+)\}$", s["attribute"])
            if m and not s.get("spread_target"):
                return m.group(1)
    return None


def plan_ask(job: dict, group: str) -> tuple:
    """(count, (cpu, mem, disk) one placement asks for) of a job's group."""
    tg = next(t for t in job["task_groups"] if t["name"] == group)
    return int(tg["count"]), (
        float(sum(t["resources"]["cpu"] for t in tg["tasks"])),
        float(sum(t["resources"]["memory_mb"] for t in tg["tasks"])),
        float(tg["ephemeral_disk"]["size_mb"]))


def replay(job: dict, group: str, order: list, usage_index: int, fetch,
           dtype: str = "float64") -> list:
    """The reference's placements of one eval's group, [(node, score,
    window)] in placement order: the nodes scanned in `order`, each
    node's usage from its alloc history as committed at `usage_index`
    (`fetch(node id)` -> (node, every alloc it ever held), asked only
    for nodes the scan reaches)."""
    count, ask = plan_ask(job, group)
    attr = spread_attribute(job)
    spread = None
    if attr is not None:
        # a new job's first plan: none of its allocs stands yet
        spread = (lambda n: fetch(n)[0]["meta"].get(attr), {})
    return ref.place_sequence(
        order,
        lambda n: usage_at(fetch(n)[1], usage_index, job["id"], group),
        lambda n: node_capacity(fetch(n)[0]), ask, count, count,
        ref.scan_limit(len(order), count, attr is not None), spread, dtype)


def against(served: dict, seq: list, job: dict, group: str, eval_id: str,
            usage_index: int, plan_index: int, fetch) -> dict:
    """`served` beside the reference's `seq`: `same` (placement numbers
    that sit where the reference put them), `gaps` (their score
    distances), `moved` ([filled, touched]: elsewhere, and `taken`
    explains it), `mismatches` (elsewhere, unexplained: [placement
    number, the reference's node, the served node, what the reference's
    node held, how many indexes past this plan other evals committed on
    it])."""
    _count, ask = plan_ask(job, group)
    same = [k for k in served if seq[k][0] == served[k][0]]
    gaps = [abs(served[k][1] - seq[k][1]) if served[k][1] is not None
            else float("inf") for k in same]
    mismatches, moved, own = [], {"filled": 0, "touched": 0}, {}
    for k, (r, _score, _window) in enumerate(seq):
        if k in served and served[k][0] != r:
            if r is None:
                mismatches.append([k, r, served[k][0], None, []])
            else:
                node, on_node = fetch(r)
                before = usage_at(on_node, usage_index, job["id"], group)
                for i in range(3):
                    before[i] += own.get(r, 0) * ask[i]
                why = taken(on_node, node_capacity(node), before, ask,
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
            "mismatches": mismatches}


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


def compare_plan(served: dict, job: dict, group: str, eval_id: str,
                 plan_index: int, indexes, base_order: list, fetch) -> dict:
    """One eval's first plan against the reference.

    `served`: placement number (the alloc name's index) -> (node id,
    score reported or None), for the allocs that plan committed at
    `plan_index`; `indexes`: the state indexes the eval's snapshot can
    have had, likeliest first; `base_order`: the ready nodes in
    registration order.

    The eval's scan order is the shuffle its snapshot's index seeds; the
    usage it packs is the live alloc table's when it packs, a committed
    state no older than the snapshot and older than the plan. So for
    each snapshot index that puts the plan at the head of the scan, as
    far as this eval's own window reaches (`scan_reach`), and each later
    state at which a node the scan reached changed, the reference
    replays the eval and is compared with `served` (`against`); the pair
    with the fewest unexplained placements stands.
    Returns that comparison with `reproduced` (REPRODUCED_SHARE of the
    plan sits where the reference put it), `index`, `usage_index` and
    `order`; `index` is None when no index was replayed at all."""
    count, _ask = plan_ask(job, group)
    reach, share = scan_reach(len(base_order), ref.scan_limit(
        len(base_order), count, spread_attribute(job) is not None))
    best = None
    for index in indexes:
        order = ref.shuffled(base_order, eval_id, index)
        if share_in_reach(served, order, reach) < share:
            continue    # no node is read for a wrong index
        usage_index = index
        while usage_index is not None:
            seen: set = set()

            def fetch_seen(node_id, seen=seen):
                seen.add(node_id)
                return fetch(node_id)
            seq = replay(job, group, order, usage_index, fetch_seen)
            got = against(served, seq, job, group, eval_id, usage_index,
                          plan_index, fetch_seen)
            got.update(index=index, usage_index=usage_index, order=order)
            if best is None or (len(got["mismatches"]), -len(got["same"])) \
                    < (len(best["mismatches"]), -len(best["same"])):
                best = got
            if not got["mismatches"]:
                break
            # the next state at which a node this replay read changed
            events = {x for node_id in seen for b in fetch(node_id)[1]
                      for x in (b["create_index"], released_at(b))
                      if x is not None and usage_index < x < plan_index}
            usage_index = min(events, default=None)
        if best is not None and not best["mismatches"] and \
                len(best["same"]) >= REPRODUCED_SHARE * len(served):
            break
    if best is None:
        return {"reproduced": False, "index": None, "usage_index": None,
                "same": [], "gaps": [], "mismatches": [],
                "moved": {"filled": 0, "touched": 0}}
    best["reproduced"] = len(best["same"]) >= REPRODUCED_SHARE * len(served)
    return best


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
