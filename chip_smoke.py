#!/usr/bin/env python
"""chip_smoke: the quickest proof that nomad-tpu still starts on the chip.

Drives the served scheduling path once, the way a user does -- jobs over
the HTTP API -> broker -> batch workers -> pack -> fused device dispatch
-> plan verify -> commit -- on a 10,000-node fleet, then every program
the shipped algorithms can select, and checks by the repo's own means
that the DEVICE did the work: host-oracle parity on the placements, and
the guard's own counters showing no timeout, no error, no host fallback
and a closed breaker after every phase. It measures no speed; compile
seconds are reported as the set-up time they are.

One process, no children that touch JAX (the chip belongs to whoever
opened it first), no ``NOMAD_TPU_*`` variable set here (the defaults are
what is being brought up), no try/except around a phase: the first
failed check ends the run with a traceback and a non-zero exit. Refuses
to run at all unless JAX came up on a TPU -- JAX falls back to the CPU
without raising, so "it ran" alone would prove nothing.

    python chip_smoke.py [--seed N]

What each phase found goes out as one ``chip_smoke: report {...}`` line;
the last line of stdout is the verdict and nothing else: ``{"ok": true,
"device": {"platform": "tpu", "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import itertools
import json
import os
import random
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

FLEET_NODES = 10_000        # BASELINE tiers 3-4; top of the reference's
#                             own sweep (benchmarks_test.go:74-79)
SERVED_JOBS = 8             # one fused dispatch: 8 lanes
SERVED_PER_JOB = 2_000      # the headline lane width (P bucket 2048)
# capacity classes the repo's served-path worlds use (cpu MHz, mem MB)
NODE_CLASSES = ((2000, 4096), (4000, 8192), (8000, 16384))
ROUND_TIMEOUT_S = 600.0


class SmokeFailure(Exception):
    """A check did not hold."""


def require(cond, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def say(msg: str) -> None:
    print(f"chip_smoke: {msg}", flush=True)


# ----------------------------------------------------------------------
# What the guard and the metrics registry say the device did


def _counters() -> dict:
    from nomad_tpu.server.telemetry import metrics
    return dict(metrics.snapshot()["counters"])


class Window:
    """Counter and compile-clock deltas over one phase."""

    def __init__(self):
        from nomad_tpu.solver import guard
        self._c0 = _counters()
        self._k0 = guard.compile_stats()
        self._t0 = time.monotonic()

    def moved(self, name: str) -> int:
        return _counters().get(name, 0) - self._c0.get(name, 0)

    def close(self, *counter_names: str) -> dict:
        from nomad_tpu.solver import guard
        k1 = guard.compile_stats()
        return {
            "wall_s": round(time.monotonic() - self._t0, 3),
            # set-up time, not speed: trace + lower + XLA compile (or a
            # persistent-cache fetch) of every program this phase met
            # for the first time
            "compile_s": round(k1["seconds"] - self._k0["seconds"], 3),
            "programs_compiled": (k1["backend_compiles"]
                                  - self._k0["backend_compiles"]),
            "dispatch_ok": self.moved("nomad.solver.dispatch_ok"),
            "counters": {n: self.moved(n) for n in counter_names},
        }


def device_did_the_work(label: str, window: Window) -> dict:
    """The device-side verdict after a phase; raises unless the phase
    dispatched to the device and nothing stood in for it."""
    from nomad_tpu.solver import guard
    st = guard.state()
    # every one of these must read zero for the whole run: each is a
    # route by which something other than the device could have answered
    seen = {
        "dispatch.timeout": st["dispatch"]["timeout"],
        "dispatch.error": st["dispatch"]["error"],
        "host_fallback_dispatches": st["host_fallback_dispatches"],
        "backend_unavailable_total": st["backend_unavailable_total"],
        "breaker.trips": st["breaker"]["trips"],
    }
    for name, n in seen.items():
        require(n == 0, f"{label}: {name} = {n}, want 0")
    require(st["breaker"]["state"] == guard.BREAKER_CLOSED,
            f"{label}: breaker is {st['breaker']['state']}")
    require(not st["degraded"], f"{label}: guard reports degraded")
    require(window.moved("nomad.solver.dispatch_ok") > 0,
            f"{label}: no dispatch reached the device")
    fell = _counters().get("nomad.scheduler.placements_host_fallback", 0)
    require(fell == 0, f"{label}: {fell} placements took the host "
                       "iterator under a tpu algorithm")
    return seen


# ----------------------------------------------------------------------
# Phase: the native control plane, built here from what git holds


def phase_native() -> dict:
    """Compile native/pack_kernels.cc now, on this machine, and load
    that -- never a library that came with the tree. A failed build
    raises; it does not select the Python paths."""
    from nomad_tpu import native
    t0 = time.time()
    path = native.build()
    require(os.path.getmtime(path) >= t0 - 1.0,
            f"{path} was not written by this run")
    require(native.load() is not None, f"{path} built but did not load")
    return {"library": os.path.relpath(path, os.path.dirname(
        os.path.abspath(__file__))), "abi": native.ABI_VERSION,
        "build_s": round(time.time() - t0, 3)}


# ----------------------------------------------------------------------
# Phase: the served path at fleet size


def register_fleet(server, rng: random.Random, n_nodes: int,
                   prefix: str) -> None:
    """``n_nodes`` ready nodes in the three capacity classes, straight
    into the server (a SimClient is a thread per node; the long
    heartbeat TTL stands in for them)."""
    from nomad_tpu import mock
    classes = [NODE_CLASSES[i % 3] for i in range(n_nodes)]
    rng.shuffle(classes)
    for i, (cpu, mem) in enumerate(classes):
        n = mock.node()
        n.id = f"{prefix}-node-{i:06d}"
        n.node_resources.cpu.cpu_shares = cpu
        n.node_resources.memory.memory_mb = mem
        n.attributes["platform.rack"] = f"rack-{i % 25:03d}"
        n.compute_class()
        server.register_node(n)


def service_job(job_id: str, count: int, rng: random.Random,
                constraints=()) -> dict:
    """A JSON jobspec as a user would PUT it."""
    return {
        "id": job_id, "name": job_id, "type": "service",
        "datacenters": ["dc1"],
        "task_groups": [{
            "name": "web", "count": count,
            "constraints": list(constraints),
            "ephemeral_disk": {"size_mb": 150},
            "tasks": [{
                "name": "web", "driver": "mock",
                "config": {"run_for": "30s"},
                "resources": {"cpu": rng.choice([250, 500]),
                              "memory_mb": rng.choice([256, 512])},
            }],
        }],
    }


def submit_and_wait(api, jobs, algorithm: str, one_queue: bool = True,
                    timeout_s: float = ROUND_TIMEOUT_S) -> int:
    """PUT every job, then read /v1/job/<id>/allocations until each
    shows its whole count with desired_status run; returns allocs run.
    ``one_queue`` offers the jobs as ONE backlog -- pause the eval
    broker the way an operator does, PUT, resume -- so the workers meet
    them all at once and two runs fuse the same lanes into the same
    dispatches whatever the submitter's pace. Without it the workers
    race the submitter: batches split, plans collide and retry."""
    if one_queue:
        api.set_scheduler_config(scheduler_algorithm=algorithm,
                                 pause_eval_broker=True)
    for job in jobs:
        reply = api.request("PUT", "/v1/jobs", body={"job": job})
        require(reply.get("eval_id"), f"no eval for {job['id']}")
    if one_queue:
        api.set_scheduler_config(scheduler_algorithm=algorithm)
    want = {job["id"]: job["task_groups"][0]["count"] for job in jobs}
    run = {}
    deadline = time.monotonic() + timeout_s
    while want and time.monotonic() < deadline:
        for job_id, count in list(want.items()):
            # the cheap per-status counts first: a full alloc listing is
            # megabytes of JSON at this width, read once when it can pass
            summary = api.get(f"/v1/job/{job_id}/summary")["summary"]
            if sum(tg["starting"] + tg["running"]
                   for tg in summary.values()) < count:
                continue
            allocs = api.job_allocations(job_id)
            n_run = sum(1 for a in allocs if a["desired_status"] == "run")
            if n_run >= count:
                require(n_run == count,
                        f"{job_id}: {n_run} allocs run, asked {count}")
                require(len({a["id"] for a in allocs}) == len(allocs),
                        f"{job_id}: duplicate alloc ids")
                run[job_id] = n_run
                del want[job_id]
        if want:
            time.sleep(0.2)
    require(not want, f"jobs never fully placed in {timeout_s:.0f}s: "
                      f"{sorted(want)}")
    return sum(run.values())


def phase_served(seed: int, n_nodes: int = FLEET_NODES,
                 n_jobs: int = SERVED_JOBS,
                 per_job: int = SERVED_PER_JOB,
                 dense_per_job: int = 64) -> dict:
    """Server + HTTP API wired as the dev agent wires them, a fleet of
    ``n_nodes``, and four rounds of jobs over HTTP: a cold round, the
    same shape again on the part-filled fleet (warm programs), two
    distinct_property jobs, which only the dense kernel models (on
    several chips: the fused dispatch that shards over the (evals,
    nodes) mesh), and a round taken as it comes -- workers racing the
    submitter, so batches split, the cross-lane fixpoint re-solves
    conflicts, plans retry, and small table deltas meet the const-cache
    and delta-promote routes."""
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.api.devagent import start_agent

    rng = random.Random(seed)
    server, http = start_agent(workers=n_jobs, port=0,
                               algorithm="tpu-binpack",
                               eval_batching=True, heartbeat_ttl=3600.0)
    out: dict = {"nodes": n_nodes, "rounds": []}
    try:
        register_fleet(server, rng, n_nodes, "served")
        api = ApiClient(f"http://127.0.0.1:{http.port}", timeout=120.0)
        require(len(api.nodes()) == n_nodes, "fleet not visible over HTTP")
        for r in (1, 2):
            w = Window()
            jobs = [service_job(f"smoke-s{seed}-r{r}-{i}", per_job, rng)
                    for i in range(n_jobs)]
            n_run = submit_and_wait(api, jobs, "tpu-binpack")
            require(n_run == n_jobs * per_job, f"round {r}: {n_run} run")
            require(w.moved("nomad.scheduler.placements_tpu") >= n_run,
                    f"round {r}: the solver placed fewer than were run")
            verdict = device_did_the_work(f"served round {r}", w)
            rec = w.close("nomad.solver.wavefront_dispatches",
                          "nomad.scheduler.placements_tpu",
                          "nomad.plan.rejected_allocs")
            rec.update(round=r, allocs_run=n_run)
            out["rounds"].append(rec)
            say(f"served round {r}: {n_run} allocs run over HTTP, "
                f"{rec['programs_compiled']} programs compiled in "
                f"{rec['compile_s']}s (set-up), "
                f"{rec['dispatch_ok']} dispatches ok")
        require(out["rounds"][0]["counters"][
            "nomad.solver.wavefront_dispatches"] > 0,
            "served round 1 never took the wavefront kernel")
        # round two meets the same shapes on a part-filled fleet. It may
        # compile what it is the first to need -- the delta-scatter that
        # promotes the resident tables, a fixpoint re-solve bucket no
        # conflict had hit yet -- but a warm path that recompiled round
        # one's programs would show as many compiles again
        require(out["rounds"][1]["programs_compiled"]
                < out["rounds"][0]["programs_compiled"],
                f"round 2 compiled {out['rounds'][1]['programs_compiled']}"
                f" programs, round 1 {out['rounds'][0]['programs_compiled']}"
                ": the warm path is not warm")

        w = Window()
        dp = {"l_target": "${attr.platform.rack}", "r_target": "8",
              "operand": "distinct_property"}
        jobs = [service_job(f"smoke-s{seed}-dense-{i}", dense_per_job,
                            rng, constraints=[dp]) for i in range(2)]
        n_run = submit_and_wait(api, jobs, "tpu-binpack")
        device_did_the_work("served dense round", w)
        require(w.moved("nomad.solver.dense_dispatches") > 0,
                "distinct_property jobs never reached the dense kernel")
        rec = w.close("nomad.solver.dense_dispatches",
                      "nomad.solver.mesh_dispatches")
        rec.update(round="dense", allocs_run=n_run)
        out["rounds"].append(rec)
        say(f"served dense round: {n_run} allocs run, counters "
            f"{rec['counters']}, compile {rec['compile_s']}s (set-up)")

        w = Window()
        jobs = [service_job(f"smoke-s{seed}-paced-{i}", per_job // 4, rng)
                for i in range(n_jobs)]
        n_run = submit_and_wait(api, jobs, "tpu-binpack", one_queue=False)
        device_did_the_work("served round as it comes", w)
        rec = w.close("nomad.solver.wavefront_dispatches",
                      "nomad.solver.fixpoint_dispatches",
                      "nomad.solver.fixpoint_conflicts",
                      "nomad.solver.const_cache_hit",
                      "nomad.solver.delta_promotions",
                      "nomad.plan.rejected_allocs")
        rec.update(round="as it comes", allocs_run=n_run)
        out["rounds"].append(rec)
        say(f"served round as it comes: {n_run} allocs run, "
            f"{rec['dispatch_ok']} dispatches ok, counters "
            f"{rec['counters']}")

        c = _counters()
        out["native_verify_hits"] = c.get("nomad.native.verify_hits", 0)
        require(out["native_verify_hits"] > 0,
                "no plan was verified by the native kernel")
        require(c.get("nomad.native.verify_fallbacks", 0) == 0,
                "plan verify fell back to the Python path")
        out["allocs_run"] = sum(r["allocs_run"] for r in out["rounds"][:2])
        out["masks"] = verdict
        from nomad_tpu.solver import guard
        cc = guard.state()["const_cache"]
        out["const_cache"] = {k: cc[k] for k in (
            "hits", "misses", "delta_promotions", "delta_fallbacks")}
    finally:
        http.shutdown()
        server.shutdown()
    return out


# ----------------------------------------------------------------------
# Phase: right answers at that width


def phase_parity(seed: int, n_nodes: int = FLEET_NODES,
                 count: int = SERVED_PER_JOB) -> dict:
    """BASELINE tier 3 (C1M shape: cpu + mem + dynamic ports, class mix,
    constraints) as one eval on the host iterator stack and on the
    solver: every placement on the same node."""
    from nomad_tpu.benchkit import run_tier_placements
    host = run_tier_placements(3, n_nodes, count, seed, alg="binpack")
    w = Window()
    tpu = run_tier_placements(3, n_nodes, count, seed, alg="tpu-binpack")
    keys = set(host) | set(tpu)
    mismatch = sum(1 for k in keys if host.get(k) != tpu.get(k))
    require(len(host) == count, f"host oracle placed {len(host)}/{count}")
    require(mismatch == 0, f"tier-3 parity: {mismatch} of {len(keys)} "
                           "placements differ from the host oracle")
    device_did_the_work("tier-3 parity", w)
    rec = w.close("nomad.solver.wavefront_dispatches")
    rec.update(nodes=n_nodes, placements=len(tpu), mismatch=mismatch)
    return rec


# ----------------------------------------------------------------------
# Phase: every program the shipped algorithms can select


def _world(seed: int, n_nodes: int, utilization: float, preemption: bool):
    from nomad_tpu import mock
    from nomad_tpu.benchkit import make_fleet, seed_utilization
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.structs import PreemptionConfig, SchedulerConfiguration

    rng = random.Random(seed)
    mock._counter = itertools.count()
    h = Harness()
    h.state.set_scheduler_config(SchedulerConfiguration(
        scheduler_algorithm="tpu-binpack",
        preemption_config=PreemptionConfig(
            system_scheduler_enabled=False,
            service_scheduler_enabled=preemption,
            batch_scheduler_enabled=preemption)))
    nodes = make_fleet(rng, h, n_nodes, gpus=True)
    seed_utilization(rng, h, nodes, utilization,
                     priorities=(10, 20, 30, 40) if preemption else (50,))
    return h, rng


def _schedule(h, job, name: str) -> int:
    """One eval for ``job`` through the scheduler; returns how many
    allocs its plans placed."""
    from nomad_tpu import mock
    h.state.upsert_job(job)
    ev = mock.evaluation(job_id=job.id, type=job.type,
                         priority=job.priority)
    ev.id = f"smoke-eval-{name}-{job.id}"
    n_before = len(h.plans)
    err = h.process(job.type, ev)
    require(err is None, f"{name}: scheduler returned {err}")
    return sum(len(allocs) for plan in h.plans[n_before:]
               for allocs in plan.node_allocation.values())


def _program(h, name: str, job, counter: str, want: int) -> dict:
    w = Window()
    placed = _schedule(h, job, name)
    require(placed == want, f"{name}: placed {placed}, want {want}")
    device_did_the_work(name, w)
    require(w.moved(counter) > 0, f"{name}: {counter} did not move")
    rec = w.close(counter)
    rec.update(placements=placed)
    say(f"program {name}: {placed} placed, {counter} +"
        f"{rec['counters'][counter]}, compile {rec['compile_s']}s (set-up)")
    return rec


def phase_programs(seed: int, n_nodes: int = FLEET_NODES,
                   count: int = 100, preempt_count: int = 64) -> dict:
    """Each remaining kernel the scheduler can select, compiled and run
    once at fleet width through the scheduler: spread + affinity (the
    wide-window compact wave: the window is max(count, 100) and the
    wide buffer holds 128), distinct_property + scored device asks
    (dense), a system job, and on a 95%-utilised fleet with device
    reservations the windowed and the dense preemption search."""
    from nomad_tpu import mock
    from nomad_tpu.benchkit import tier_job
    from nomad_tpu.structs import (
        Affinity, Constraint, DeviceRequest, Spread)

    out = {}
    h, rng = _world(seed, n_nodes, 0.25, preemption=False)
    # first, while every node still has room: one alloc per node
    job = mock.system_job()
    job.id = f"smoke-system-{seed}"
    job.datacenters = ["dc1", "dc2"]
    job.task_groups[0].tasks[0].resources.cpu = 100
    job.task_groups[0].tasks[0].resources.memory_mb = 64
    out["system"] = _program(
        h, "system", job, "nomad.solver.dispatch_ok", n_nodes)

    job = tier_job(4, rng, count)
    job.id = f"smoke-tier4-{seed}"
    out["wave_compact_spread_affinity"] = _program(
        h, "tier4", job, "nomad.solver.wavefront_dispatches", count)

    job = tier_job(3, rng, preempt_count)
    job.id = f"smoke-dense-{seed}"
    job.constraints = list(job.constraints) + [Constraint(
        l_target="${attr.platform.rack}", r_target="8",
        operand="distinct_property")]
    job.task_groups[0].networks = []
    job.task_groups[0].tasks[0].resources.devices = [DeviceRequest(
        name="nvidia/gpu", count=1, affinities=[Affinity(
            l_target="${device.model}", r_target="v100", operand="=",
            weight=50)])]
    out["dense_distinct_property_devices"] = _program(
        h, "dense", job, "nomad.solver.dense_dispatches", preempt_count)

    h, rng = _world(seed + 1, n_nodes, 0.95, preemption=True)

    job = tier_job(5, rng, preempt_count)
    job.id = f"smoke-wave-preempt-{seed}"
    out["wave_preempt_devices"] = _program(
        h, "wave-preempt", job,
        "nomad.solver.wavefront_preempt_dispatches", preempt_count)
    job = tier_job(5, rng, preempt_count)
    job.id = f"smoke-dense-preempt-{seed}"
    job.task_groups[0].spreads = [
        Spread(attribute="${node.datacenter}", weight=50)]
    out["dense_preempt_devices"] = _program(
        h, "dense-preempt", job, "nomad.solver.dense_dispatches",
        preempt_count)
    return out


# ----------------------------------------------------------------------
# Phase: the whole-queue LP tier


def phase_lpq(seed: int, n_nodes: int = FLEET_NODES, n_jobs: int = 128,
              per_job: int = 8) -> dict:
    """A small tpu-lpq queue through the served path: the LPQ worker
    coalesces the pending evals into joint relaxations on the device,
    rounds, repairs and commits."""
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.api.devagent import start_agent

    rng = random.Random(seed + 2)
    server, http = start_agent(workers=SERVED_JOBS, port=0,
                               algorithm="tpu-lpq",
                               eval_batching=True, heartbeat_ttl=3600.0)
    try:
        register_fleet(server, rng, n_nodes, "lpq")
        api = ApiClient(f"http://127.0.0.1:{http.port}", timeout=120.0)
        w = Window()
        jobs = [service_job(f"smoke-s{seed}-lpq-{i}", per_job, rng)
                for i in range(n_jobs)]
        n_run = submit_and_wait(api, jobs, "tpu-lpq")
        device_did_the_work("lpq", w)
        require(w.moved("nomad.lpq.solves") > 0, "no LP relaxation ran")
        require(w.moved("nomad.lpq.placements") > 0,
                "the LP tier placed nothing")
        rec = w.close("nomad.lpq.solves", "nomad.lpq.placements",
                      "nomad.lpq.greedy_lanes", "nomad.lpq.repairs",
                      "nomad.lpq.failed", "nomad.lpq.mesh_dispatches")
        rec.update(allocs_run=n_run)
    finally:
        http.shutdown()
        server.shutdown()
    return rec


# ----------------------------------------------------------------------
# Phase: several chips


def phase_mesh(served: dict) -> dict:
    """Nothing to prove on one device. On several, the same run must
    have used them: the dense dispatch sharded over the (evals, nodes)
    mesh, the wave dispatch sharded over the eval axis, and bytes that
    really landed on every device -- code that has only met virtual
    devices may put everything on the first."""
    import jax

    from nomad_tpu.parallel.mesh import make_mesh
    from nomad_tpu.solver import xferobs

    devices = jax.devices()
    out = {"devices": len(devices)}
    if len(devices) == 1:
        return out
    c = _counters()
    ledger = xferobs.state()["per_shard"]
    labels = [f"d{d.id}" for d in devices]
    out.update({
        "default_grid": [int(x) for x in make_mesh().devices.shape],
        "mesh_dispatches": c.get("nomad.solver.mesh_dispatches", 0),
        "per_shard_bytes": {
            group: {d: rows.get(d, {}).get("actual_bytes", 0)
                    for d in labels}
            for group, rows in ledger.items()},
        # what the runtime itself says each device held at its fullest
        # (the CPU backend keeps no such statistics)
        "peak_bytes_in_use": {
            f"d{d.id}": d.memory_stats()["peak_bytes_in_use"]
            for d in devices if d.memory_stats() is not None},
    })
    require(out["mesh_dispatches"] > 0,
            "no dense dispatch sharded over the mesh")
    # the wave transports shard the fused eval axis (8 lanes over the
    # devices) and log it under their table tag
    wave = out["per_shard_bytes"].get("compact")
    require(wave is not None, "the wave dispatch never sharded its "
                              "eval axis over the devices")
    dense = next(r for r in served["rounds"] if r["round"] == "dense")
    require(dense["counters"]["nomad.solver.mesh_dispatches"] > 0,
            "the served dense round did not take the mesh")
    for group, rows in out["per_shard_bytes"].items():
        for d, n in rows.items():
            require(n > 0, f"ledger group {group}: device {d} "
                           "was shipped nothing")
    for d, n in out["peak_bytes_in_use"].items():
        require(n > 0, f"device {d} never held a byte")
    return out


# ----------------------------------------------------------------------
# Phase: an opened breaker closes again, in this process


def phase_breaker_drill(seed: int, n_nodes: int = 64) -> dict:
    """Runs last, after the zero-fallback verdict is in: inject three
    dispatch errors, watch the breaker open and the evals complete on
    the host oracle, then watch the recovery loop close it again with a
    probe dispatch on the device this process still holds -- a child
    could never have opened it."""
    from nomad_tpu import mock
    from nomad_tpu.faultinject import faults
    from nomad_tpu.solver import guard

    h, _rng = _world(seed + 3, n_nodes, 0.0, preemption=False)
    threshold = guard._breaker_threshold()
    before = guard.state()
    faults.arm("solver.dispatch", "error", count=threshold)
    try:
        for i in range(threshold):
            job = mock.job(id=f"smoke-drill-{seed}-{i}")
            job.task_groups[0].count = 4
            require(_schedule(h, job, "drill") == 4,
                    "the host oracle did not complete a degraded eval")
    finally:
        faults.disarm("solver.dispatch")
    tripped = guard.state()
    require(tripped["breaker"]["trips"] == before["breaker"]["trips"] + 1,
            "three injected dispatch errors did not open the breaker")
    t0 = time.monotonic()
    while (guard.breaker_state()["state"] != guard.BREAKER_CLOSED
           and time.monotonic() - t0 < 60.0):
        time.sleep(0.05)
    br = guard.breaker_state()
    require(br["state"] == guard.BREAKER_CLOSED,
            f"breaker still {br['state']} 60s after the fault cleared: "
            f"{br['last_probe']}")
    require(br["last_probe"]["report"]["dispatch"]["ok"],
            "breaker closed without a passing probe dispatch")
    job = mock.job(id=f"smoke-drill-{seed}-after")
    job.task_groups[0].count = 4
    w = Window()
    require(_schedule(h, job, "drill-after") == 4, "post-recovery eval")
    require(w.moved("nomad.solver.dispatch_ok") > 0,
            "dispatch did not return to the device after recovery")
    return {"injected_errors": threshold, "tripped": True,
            "closed_in_process": True,
            "closed_after_s": round(time.monotonic() - t0, 3),
            "probe_dispatch_ms": br["last_probe"]["report"]["dispatch"]["ms"],
            "recoveries": br["recoveries"]}


# ----------------------------------------------------------------------


def describe_device() -> dict:
    from importlib.metadata import PackageNotFoundError, version

    import jax
    import jaxlib
    devs = jax.devices()
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = None
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "jax": jax.__version__,
            "jaxlib": jaxlib.__version__, "libtpu": libtpu}


def verdict(dev: dict) -> dict:
    """The last line: exactly these keys, the device as JAX reports it."""
    return {"ok": True, "device": {"platform": dev["platform"],
                                   "kind": dev["kind"],
                                   "count": dev["count"]}}


def _cache_entries(cache_dir) -> int:
    return len(os.listdir(cache_dir)) if os.path.isdir(cache_dir) else 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="fleet and jobs are generated from it")
    args = ap.parse_args(argv)

    t_start = time.monotonic()
    dev = describe_device()
    say(f"platform={dev['platform']} device_kind={dev['kind']} "
        f"device_count={dev['count']} jax={dev['jax']} "
        f"jaxlib={dev['jaxlib']} libtpu={dev['libtpu']}")
    if dev["platform"] != "tpu":
        print(f"chip_smoke: JAX came up on {dev['platform']!r}, not a "
              "TPU; nothing here would prove the chip works",
              file=sys.stderr)
        return 2
    import jax
    require(not jax.config.jax_enable_x64,
            "x64 is on: the chip path runs float32 with x64 off")
    import nomad_tpu.solver  # noqa: F401 -- switches the cache on
    cache_dir = jax.config.jax_compilation_cache_dir
    cache_from = ("JAX_COMPILATION_CACHE_DIR"
                  if os.environ.get("JAX_COMPILATION_CACHE_DIR")
                  else "the checkout default")
    entries_before = _cache_entries(cache_dir)
    say(f"compile cache: {cache_dir} ({cache_from}), "
        f"{entries_before} entries")

    # the ids the server mints (eval ids seed the node shuffle) come from
    # the seed too, so two runs meet the same tie-breaks
    from nomad_tpu.structs.job import reseed_ids
    reseed_ids(args.seed)

    out = {"seed": args.seed, "device": verdict(dev)["device"],
           "versions": {k: dev[k] for k in ("jax", "jaxlib", "libtpu")}}
    out["native"] = phase_native()
    say(f"native: built {out['native']['library']} in "
        f"{out['native']['build_s']}s")
    out["served"] = phase_served(args.seed)
    out["parity_tier3"] = phase_parity(args.seed)
    say(f"tier-3 parity at {out['parity_tier3']['nodes']} x "
        f"{out['parity_tier3']['placements']}: mismatch "
        f"{out['parity_tier3']['mismatch']}")
    out["programs"] = phase_programs(args.seed)
    out["lpq"] = phase_lpq(args.seed)
    say(f"lpq: {out['lpq']['allocs_run']} allocs run, counters "
        f"{out['lpq']['counters']}")
    out["mesh"] = phase_mesh(out["served"])
    from nomad_tpu.solver import guard
    st = guard.state()
    out["guard"] = {
        "dispatch": {k: st["dispatch"][k]
                     for k in ("ok", "timeout", "error")},
        "host_fallback_dispatches": st["host_fallback_dispatches"],
        "backend_unavailable_total": st["backend_unavailable_total"],
        "breaker": {"state": st["breaker"]["state"],
                    "trips": st["breaker"]["trips"]},
        "device": st["device"],
    }
    out["breaker_drill"] = phase_breaker_drill(args.seed)
    say(f"breaker drill: closed in-process after "
        f"{out['breaker_drill']['closed_after_s']}s")
    out["compile_cache"] = {
        "dir": cache_dir, "placed_by": cache_from,
        "entries_before": entries_before,
        "entries_written": _cache_entries(cache_dir) - entries_before}
    out["wall_s"] = round(time.monotonic() - t_start, 1)
    say("report " + json.dumps(out))
    print(json.dumps(verdict(dev)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
