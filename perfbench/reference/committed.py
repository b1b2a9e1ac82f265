"""The committed state as the API returns it, read the one way every
reference module and the comparison read it: what an alloc holds, what
a node offers, which allocs a node held at a state index, and what one
placement of a job's group asks for. Plain dicts in, numbers out;
nothing of the program.

When an alloc stops counting against its node (`released_at`):

- an evicted one at its eviction's commit, whatever its client reports
  later: an evict is server-terminal (Nomad 1.7 state_store.go
  AllocsByNodeTerminal leaves it out of ProposedAllocs, and plan_apply
  subtracts a plan's node_preemptions at the commit);
- any other once its client has reported it terminal (a stop as the
  accepted reference/placement.py counts it, as the program does; Nomad
  frees a stop at its commit too: PERF.md section 7).

The plan verifier's view (run.replay_overcommitted) frees a stop once
its commit is seen, and an eviction as above.
"""
from __future__ import annotations

import re

EVICT = "evict"


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


def released_at(a: dict, on_node: list):
    """The index at which an alloc stopped counting against its node:
    an evicted one's eviction commit (`evicted_at`), any other's last
    write once its client has reported it terminal; None while it
    counts."""
    if a["desired_status"] == EVICT:
        return evicted_at(a, on_node)
    return reported_at(a)


def reported_at(a: dict):
    """The index of its client's terminal report (its last write), None
    while its client has reported none."""
    if a["client_status"] in ("complete", "failed", "lost"):
        return a["modify_index"]
    return None


def evicted_at(a: dict, on_node: list):
    """The index at which an evicted alloc's eviction committed: that of
    the placement that evicted it, which sits on the same node (None for
    an alloc nothing evicted; its own last write if the evicting alloc is
    gone)."""
    if a["desired_status"] != EVICT:
        return None
    by = a.get("preempted_by_allocation")
    for b in on_node:
        if b.get("id") == by:
            return b["create_index"]
    return a["modify_index"]


def stopped_at(a: dict, on_node: list):
    """The index at which an alloc's stop or eviction committed, after
    which it is no candidate for eviction (a stop still counting until
    its client's report): an eviction's from `evicted_at`; a stop's as
    the fleet stand-in saw it before reporting (`stop_index`), else its
    last write; None for an alloc that is to run."""
    if a["desired_status"] == EVICT:
        return evicted_at(a, on_node)
    if a["desired_status"] != "run":
        return a.get("stop_index", a["modify_index"])
    return None


def usage_at(on_node: list, index: int, job_id: str, group: str) -> list:
    """[cpu, mem, disk, allocs of this job's group] the node held in the
    state at `index`: every alloc committed by then and not released by
    then (`released_at`)."""
    u = [0.0, 0.0, 0.0, 0]
    for b in on_node:
        if b["create_index"] > index:
            continue
        r_b = released_at(b, on_node)
        if r_b is not None and r_b <= index:
            continue
        for i, x in enumerate(alloc_resources(b)):
            u[i] += x
        if b["job_id"] == job_id and b["task_group"] == group:
            u[3] += 1
    return u


def spread_attribute(job: dict):
    """`${meta.rack}` -> `rack`; the one even spread the configurations
    use. None when the group has no spread."""
    for tg in job["task_groups"]:
        for s in tg.get("spreads") or []:
            m = re.match(r"^\$\{meta\.(.+)\}$", s["attribute"])
            if m and not s.get("spread_target"):
                return m.group(1)
    return None


def task_group(job: dict, group: str) -> dict:
    return next(t for t in job["task_groups"] if t["name"] == group)


def plan_ask(job: dict, group: str) -> tuple:
    """(count, (cpu, mem, disk) one placement asks for) of a job's group."""
    tg = task_group(job, group)
    return int(tg["count"]), (
        float(sum(t["resources"]["cpu"] for t in tg["tasks"])),
        float(sum(t["resources"]["memory_mb"] for t in tg["tasks"])),
        float(tg["ephemeral_disk"]["size_mb"]))


def device_asks(job: dict, group: str) -> list:
    """[(device name, count)] one placement of a job's group asks for."""
    return [(d["name"], int(d.get("count", 1)))
            for t in task_group(job, group)["tasks"]
            for d in t["resources"].get("devices") or ()]


def alloc_priority(a: dict) -> int:
    """Its job's priority; 50 for an alloc without a job, as the host
    stack reads one (rank.py net_priority)."""
    job = a.get("job")
    return int(job["priority"]) if job else 50


def max_parallel(a: dict) -> int:
    """The migrate stanza's max_parallel of the alloc's group, 0 where
    the group has none (preemption.go SetCandidates)."""
    for tg in (a.get("job") or {}).get("task_groups") or ():
        if tg["name"] == a["task_group"]:
            return int((tg.get("migrate") or {}).get("max_parallel", 0))
    return 0


def devices_held(a: dict) -> dict:
    """vendor/type/name -> device instances the alloc holds."""
    held: dict = {}
    for t in a["allocated_resources"]["tasks"].values():
        for d in t.get("devices") or ():
            key = f"{d['vendor']}/{d['type']}/{d['name']}"
            held[key] = held.get(key, 0) + len(d.get("device_ids") or ())
    return held
