"""Tier-shaped worlds (BASELINE.md config tiers 1-5): fixtures, not a
benchmark.

What tier 1 and ``chip_smoke.py`` call: the fleet and job generators
(``make_fleet``, ``seed_utilization``, ``tier_job``), the parity and
placement runs over them (``run_tier_parity``, ``run_tier_placements``)
and the served-pipeline drives (``run_scale_northstar``,
``run_scale_churn``, ``run_worker_scaling``), the same world shapes at
any size (reference sweep analog:
scheduler/benchmarks/benchmarks_test.go:36-79). Speed is measured by
``perfbench/run.py`` alone (BENCHMARK.json, PERF.md).

Tiers (BASELINE.md "Targets"):
  1: 3-TG service job (web/api/worker) on a 5-node dev cluster
  2: batch allocs over uniform nodes, binpack vs spread algorithm
  3: C1M-replay shape -- cpu+mem+dynamic-port asks, node-class mix,
     kernel/class constraints
  4: C2M shape -- affinity + anti-affinity (implicit) + spread mixes
  5: preemption-heavy -- high utilization, priority tiers (see
     tests/test_preemption_tpu.py for the parity harness)
"""
from __future__ import annotations

import itertools
import os
import random
from typing import Dict, List, Optional, Tuple

from . import mock
from .structs import (
    Affinity, Constraint, DeviceRequest, NetworkResource,
    NodeDeviceResource, Port, PreemptionConfig, SchedulerConfiguration,
    Spread, SpreadTarget,
    ALLOC_CLIENT_RUNNING,
)

RACK_COUNT = 25   # reference sweep uses {10,25,50,75} racks


def export_chrome_trace(path: str) -> "str | None":
    """Write the flight recorder's retained eval traces as a
    chrome://tracing / Perfetto JSON artifact (the per-eval span view
    that explains WHERE a round's latency went). Returns the written
    path, or None when tracing is off or nothing was retained."""
    import json

    from .server.tracing import trace_enabled, tracer
    from .solver import xferobs

    if not trace_enabled():
        return None
    doc = tracer.chrome_trace()
    if not doc["traceEvents"]:
        # no retained eval spans -> no artifact (the counter tracks
        # annotate the span view; they are not a trace by themselves)
        return None
    # Perfetto counter tracks (ISSUE 13): shipped bytes / resident
    # bytes / in-flight depth per retained dispatch record, rendered as
    # counter lanes under the eval spans
    doc["traceEvents"].extend(xferobs.counter_events())
    try:
        with open(path, "w", encoding="utf-8") as f:
            json.dump(doc, f)
    except OSError:
        return None
    return path


def run_scale_northstar(target_allocs: int, n_nodes: int = 10000,
                        e_evals: int = 32, per_eval: int = 2000,
                        round_timeout_s: float = 300.0,
                        log=None) -> dict:
    """The north-star-scale shape: drive ``target_allocs`` LIVE
    allocations through the full production batched pipeline (Server +
    BatchWorker eval coalescing + SolveBarrier fused dispatch +
    group-commit plan applier) WITHOUT draining between rounds, so the
    state store, alloc table and applier carry the accumulated fleet the
    whole way -- the number the ROADMAP's north star is phrased in,
    measured instead of extrapolated.

    Scale hygiene baked in: the AllocTable is preallocated to the target
    (no doubling copies under the store lock), per-placement
    explainability stubs are pruned (NOMAD_TPU_LEAN_ALLOC_METRICS), and
    the peak RSS rides the returned dict so the memory ceiling is part
    of the artifact. The same code path shrinks to a tier-1 smoke at a
    few thousand allocs (tests/test_scale_northstar.py).

    Returns {"allocs", "wall_s", "placements_per_sec", "rss_mb",
    "rounds", "truncated"}."""
    import os
    import resource
    import time

    from . import mock
    from .server import Server
    from .structs import SchedulerConfiguration

    def say(msg):
        if log is not None:
            log(msg)

    allocs_per_node = max(1, (target_allocs + n_nodes - 1) // n_nodes)
    rounds = max(1, (target_allocs + e_evals * per_eval - 1)
                 // (e_evals * per_eval))
    prev_lean = os.environ.get("NOMAD_TPU_LEAN_ALLOC_METRICS")
    os.environ["NOMAD_TPU_LEAN_ALLOC_METRICS"] = "1"
    server = Server(num_workers=e_evals, heartbeat_ttl=3600.0,
                    eval_batching=True, batch_width=e_evals)
    server.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-binpack"))
    server.state.preallocate_allocs(
        int(target_allocs * 1.1) + e_evals * per_eval)
    server.start()
    placed_total = 0
    truncated = False
    try:
        # fleet provisioned so the target fits with ~40% headroom at
        # 10cpu/32mb/10disk per alloc (tiny asks: the point is the alloc
        # COUNT, not per-alloc weight)
        for i in range(n_nodes):
            n = mock.node()
            n.id = f"nstar-node-{i:06d}"
            n.node_resources.cpu.cpu_shares = int(allocs_per_node * 14)
            n.node_resources.memory.memory_mb = int(allocs_per_node * 45)
            n.node_resources.disk.disk_mb = int(allocs_per_node * 14)
            n.compute_class()
            server.register_node(n)
        say(f"northstar: fleet up ({n_nodes} nodes, "
            f"{rounds} rounds x {e_evals}x{per_eval})")

        t0 = time.perf_counter()
        for r in range(rounds):
            jobs = []
            for i in range(e_evals):
                job = mock.job(id=f"nstar-{r:03d}-{i:02d}")
                tg = job.task_groups[0]
                tg.count = per_eval
                tg.ephemeral_disk.size_mb = 10
                tg.tasks[0].resources.cpu = 10
                tg.tasks[0].resources.memory_mb = 32
                jobs.append(job)
            for job in jobs:
                server.register_job(job)
            want = e_evals * per_eval
            deadline = time.time() + round_timeout_s
            placed = 0
            while time.time() < deadline:
                approx = sum(
                    server.state.num_allocs_by_job(job.namespace, job.id)
                    for job in jobs)
                if approx >= want:
                    placed = sum(
                        1 for job in jobs
                        for a in server.state.allocs_by_job(
                            job.namespace, job.id)
                        if a.desired_status == "run")
                    if placed >= want:
                        break
                time.sleep(0.05)
            else:
                placed = sum(
                    1 for job in jobs
                    for a in server.state.allocs_by_job(job.namespace,
                                                        job.id)
                    if a.desired_status == "run")
            placed_total += placed
            if placed < want:
                truncated = True
                say(f"northstar: round {r} TRUNCATED "
                    f"({placed}/{want}); stopping at {placed_total}")
                break
            # scale hygiene: the round's placements are LIVE for the
            # rest of the run -- freeze them into the permanent GC
            # generation so full collections (which JAX hooks with a
            # per-collection callback) stop re-walking millions of
            # immortal allocs
            import gc
            gc.collect()
            gc.freeze()
            if (r + 1) % 4 == 0 or r + 1 == rounds:
                dt_so_far = time.perf_counter() - t0
                say(f"northstar: {placed_total} live allocs after "
                    f"round {r + 1}/{rounds} "
                    f"({placed_total / dt_so_far:.0f}/s)")
        wall = time.perf_counter() - t0
    finally:
        if prev_lean is None:
            os.environ.pop("NOMAD_TPU_LEAN_ALLOC_METRICS", None)
        else:
            os.environ["NOMAD_TPU_LEAN_ALLOC_METRICS"] = prev_lean
        server.shutdown()
    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    return {
        "allocs": placed_total,
        "wall_s": round(wall, 3),
        "placements_per_sec": round(placed_total / wall, 2) if wall
        else 0.0,
        "rss_mb": round(rss_mb, 1),
        "rounds": rounds,
        "truncated": truncated,
    }


def run_scale_churn(live_target: int, n_nodes: int = 10000,
                    e_evals: int = 32, per_eval: int = 2000,
                    rounds: int = 6, churn_jobs: int = 4,
                    flap_nodes: int = 2,
                    round_timeout_s: float = 300.0,
                    gc_watermark: Optional[int] = None,
                    log=None) -> dict:
    """Sustained-churn north star (ISSUE 6 / ROADMAP item 5): hold
    ~``live_target`` LIVE allocations while the pipeline absorbs
    continuous arrivals (new jobs), completions (deregister + client
    ack), and node flaps (down -> lost-alloc reschedule -> recovery
    through the flap damper) at steady state -- production traffic is
    churn, not a queue drained once. Every round ends with a GC pass
    under the terminal-alloc watermark plus table compaction, and a
    fold-parity check of the incremental delta memos against a full
    refold, so the run measures BOUNDED state, not accumulation.

    Reports p50/p99 submit->commit latency over the arrival jobs, RSS
    per round (growth across churn rounds is the leak signal; peak ru_
    maxrss alone can't show re-use), and ``parity_mismatch`` (must be
    0). The same code path shrinks to a tier-1 smoke
    (tests/test_scale_churn.py), mirroring test_scale_northstar's
    split."""
    import os
    import resource
    import time

    from . import mock
    from .server import Server
    from .structs import ALLOC_CLIENT_COMPLETE, SchedulerConfiguration

    def say(msg):
        if log is not None:
            log(msg)

    def rss_now_mb() -> float:
        try:
            with open("/proc/self/statm") as f:
                pages = int(f.read().split()[1])
            return pages * (resource.getpagesize() / 1048576.0)
        except (OSError, ValueError, IndexError):
            return (resource.getrusage(resource.RUSAGE_SELF).ru_maxrss
                    / 1024.0)

    allocs_per_node = max(1, (live_target + n_nodes - 1) // n_nodes)
    warmup_waves = max(1, (live_target + e_evals * per_eval - 1)
                       // (e_evals * per_eval))
    if gc_watermark is None:
        gc_watermark = max(1000, live_target // 4)
    prev_lean = os.environ.get("NOMAD_TPU_LEAN_ALLOC_METRICS")
    os.environ["NOMAD_TPU_LEAN_ALLOC_METRICS"] = "1"
    server = Server(num_workers=e_evals, heartbeat_ttl=3600.0,
                    eval_batching=True, batch_width=e_evals)
    server.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-binpack"))
    server.state.preallocate_allocs(
        int(live_target * 1.2) + e_evals * per_eval)
    server.start()
    truncated = False
    latencies_ms: list = []
    rss_rounds: list = []
    parity_mismatch = 0
    arrivals = completions = flaps = quarantine_deferrals = 0
    active_jobs: list = []      # insertion order = age order
    job_seq = 0

    def churn_job():
        nonlocal job_seq
        job = mock.job(id=f"churn-{job_seq:05d}")
        job_seq += 1
        tg = job.task_groups[0]
        tg.count = per_eval
        tg.ephemeral_disk.size_mb = 10
        tg.tasks[0].resources.cpu = 10
        tg.tasks[0].resources.memory_mb = 32
        return job

    def wait_placed(jobs, deadline):
        """Block until every job's allocs are placed; records per-job
        submit->commit latency. Returns False on timeout."""
        pending = {(j.namespace, j.id): t0 for j, t0 in jobs}
        while pending and time.time() < deadline:
            for key in list(pending):
                ns, jid = key
                if server.state.num_allocs_by_job(ns, jid) >= per_eval:
                    placed = sum(
                        1 for a in server.state.allocs_by_job(ns, jid)
                        if a.desired_status == "run")
                    if placed >= per_eval:
                        latencies_ms.append(
                            (time.perf_counter() - pending.pop(key))
                            * 1e3)
            if pending:
                time.sleep(0.02)
        return not pending

    try:
        # fleet with ~60% headroom: flapped nodes and in-flight
        # replacements need somewhere to land
        fleet_ids = []
        for i in range(n_nodes):
            n = mock.node()
            n.id = f"churn-node-{i:06d}"
            n.node_resources.cpu.cpu_shares = int(allocs_per_node * 16)
            n.node_resources.memory.memory_mb = int(allocs_per_node * 52)
            n.node_resources.disk.disk_mb = int(allocs_per_node * 16)
            n.compute_class()
            server.register_node(n)
            fleet_ids.append(n.id)
        say(f"churn: fleet up ({n_nodes} nodes); warming to "
            f"{live_target} live allocs")

        for w in range(warmup_waves):
            jobs = [churn_job() for _ in range(e_evals)]
            batch = []
            for job in jobs:
                t0 = time.perf_counter()
                server.register_job(job)
                batch.append((job, t0))
                active_jobs.append(job)
            if not wait_placed(batch, time.time() + round_timeout_s):
                truncated = True
                say(f"churn: warmup wave {w} TRUNCATED")
                break
        latencies_ms.clear()        # warmup is not steady state
        rss_rounds.append(round(rss_now_mb(), 1))
        # ISSUE-20 delta-stream leg: snapshot the version-chain and
        # transfer-ledger counters AFTER warmup so the reported
        # bytes-per-dispatch is the warm steady state (install-time
        # wholesale uploads are warmup, not the regime under test)
        from .solver import constcache as _cc
        from .solver import xferobs as _xo
        cc0 = _cc.stats()
        xo0 = _xo.state() if _xo.enabled() else {}

        flappy = fleet_ids[:flap_nodes]
        t_run0 = time.perf_counter()
        for r in range(rounds):
            if truncated:
                break
            # completions: the oldest jobs leave (deregister -> stop
            # eval), and their clients ack terminal
            leaving = active_jobs[:churn_jobs]
            active_jobs = active_jobs[churn_jobs:]
            for job in leaving:
                server.deregister_job(job.namespace, job.id)
                acks = []
                for a in server.state.allocs_by_job(job.namespace,
                                                    job.id):
                    upd = a.copy_skip_job()
                    upd.client_status = ALLOC_CLIENT_COMPLETE
                    upd.client_terminal_time = time.time()
                    acks.append(upd)
                server.update_allocs_from_client(acks)
                completions += len(acks)
            # flaps: the same nodes go down every round, so the flap
            # damper's escalating quarantine actually engages
            for nid in flappy:
                node = server.state.node_by_id(nid)
                if node is not None and node.ready():
                    server.update_node_status(nid, "down")
                    flaps += 1
            # arrivals replace the departed capacity
            batch = []
            for _ in range(churn_jobs):
                job = churn_job()
                t0 = time.perf_counter()
                server.register_job(job)
                batch.append((job, t0))
                active_jobs.append(job)
            arrivals += churn_jobs * per_eval
            if not wait_placed(batch, time.time() + round_timeout_s):
                truncated = True
                say(f"churn: round {r} TRUNCATED")
            # flapped nodes try to come back; quarantined ones are
            # deferred (they retry next round)
            for nid in flappy:
                node = server.state.node_by_id(nid)
                if node is not None and node.status == "down":
                    rem = server.flaps.quarantine_remaining(nid)
                    if rem > 0:
                        quarantine_deferrals += 1
                    server.heartbeat(nid)
            # bounded state: terminal sweep + watermark + compaction
            server.run_gc_once(threshold=0.0,
                               terminal_watermark=gc_watermark)
            parity_mismatch += \
                server.state.alloc_table.fold_parity_mismatch()
            rss_rounds.append(round(rss_now_mb(), 1))
            say(f"churn: round {r + 1}/{rounds} done "
                f"(rss {rss_rounds[-1]:.0f}MB, "
                f"parity_mismatch={parity_mismatch})")
        churn_wall = time.perf_counter() - t_run0
        # settle before reading: the final round's replacement
        # placements and stop-acks commit asynchronously, so an
        # immediate live count can race them a couple of allocs high
        # or low (the tier-1 smoke asserts EXACT target).  A bounded
        # poll until the count holds the target removes the race
        # without weakening the gate -- a genuinely accumulating or
        # leaking run never settles and still fails the assert.
        deadline = time.time() + 15.0
        while time.time() < deadline:
            live_now = sum(
                1 for j in active_jobs
                for a in server.state.allocs_by_job(j.namespace, j.id)
                if not a.terminal_status())
            if live_now == live_target:
                break
            time.sleep(0.05)
        cc1 = _cc.stats()
        xo1 = _xo.state() if _xo.enabled() else {}
        xfer_parity = abs(_xo.parity()) if _xo.enabled() else 0
    finally:
        if prev_lean is None:
            os.environ.pop("NOMAD_TPU_LEAN_ALLOC_METRICS", None)
        else:
            os.environ["NOMAD_TPU_LEAN_ALLOC_METRICS"] = prev_lean
        server.shutdown()

    live = sum(1 for j in active_jobs
               for a in server.state.allocs_by_job(j.namespace, j.id)
               if not a.terminal_status())
    terminal = sum(1 for a in server.state.allocs()
                   if a.terminal_status())
    lat = sorted(latencies_ms)

    def pct(p):
        if not lat:
            return 0.0
        return round(lat[min(len(lat) - 1, int(p * len(lat)))], 2)

    rss_mb = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 1024.0
    out = {
        "live_allocs": live,
        "terminal_allocs": terminal,
        "rounds": rounds,
        "churn_wall_s": round(churn_wall, 3),
        "arrivals": arrivals,
        "completions": completions,
        "flaps": flaps,
        "quarantine_deferrals": quarantine_deferrals,
        "submit_commit_p50_ms": pct(0.50),
        "submit_commit_p99_ms": pct(0.99),
        "rss_mb_rounds": rss_rounds,
        "rss_growth_mb": round(rss_rounds[-1] - rss_rounds[0], 1)
        if len(rss_rounds) >= 2 else 0.0,
        "rss_mb": round(rss_mb, 1),
        "gc_watermark": gc_watermark,
        "parity_mismatch": parity_mismatch,
        "truncated": truncated,
    }
    # ISSUE-20 delta-stream leg: warm steady-state deltas over the
    # churn rounds only (warmup installs subtracted out).  dispatches
    # come off the transfer ledger; with NOMAD_TPU_XFEROBS=0 the
    # per-dispatch normalization is structurally absent and reported 0.
    n_disp = (xo1.get("dispatches", 0) or 0) - \
             (xo0.get("dispatches", 0) or 0)
    d_bytes = cc1["delta_bytes_total"] - cc0["delta_bytes_total"]
    shipped = (xo1.get("shipped_bytes_total", 0) or 0) - \
              (xo0.get("shipped_bytes_total", 0) or 0)
    out.update({
        "delta_promotions": cc1["delta_promotions"]
        - cc0["delta_promotions"],
        "delta_reuses": cc1["delta_reuses"] - cc0["delta_reuses"],
        "delta_fallbacks": cc1["delta_fallbacks"]
        - cc0["delta_fallbacks"],
        "delta_bytes_per_dispatch": round(d_bytes / n_disp, 1)
        if n_disp else 0.0,
        "shipped_bytes_per_dispatch": round(shipped / n_disp, 1)
        if n_disp else 0.0,
        "xfer_ledger_parity": xfer_parity,
    })
    return out


def run_worker_scaling(pool_sizes=(1, 2, 4, 8), n_nodes: int = 2000,
                       jobs: int = 16, per_eval: int = 250,
                       timeout_s: float = 300.0, log=None) -> dict:
    """Crash-safe N-worker control plane scaling (ISSUE 16): the same
    end-to-end placement workload (``jobs`` jobs x ``per_eval`` allocs
    each) pushed through the supervised PLAIN worker pool at each size
    in ``pool_sizes``, reporting e2e placements/s per size at fold
    parity 0.  eval_batching stays OFF on purpose: the axis under test
    is scheduler-loop parallelism across N workers racing the
    group-commit applier (cross-worker serialization and all), not
    dispatch fusion -- the fused path has its own headline.  A size
    that cannot finish inside ``timeout_s`` marks the run truncated
    (never silently published as complete)."""
    import os
    import time as _time

    from . import mock
    from .server import Server

    def say(msg):
        if log is not None:
            log(msg)

    total = jobs * per_eval
    allocs_per_node = max(1, (total * 13 // 10 + n_nodes - 1)
                          // n_nodes)
    prev_lean = os.environ.get("NOMAD_TPU_LEAN_ALLOC_METRICS")
    os.environ["NOMAD_TPU_LEAN_ALLOC_METRICS"] = "1"
    pps: dict = {}
    walls: dict = {}
    parity_mismatch = 0
    truncated = False
    try:
        for size in pool_sizes:
            server = Server(num_workers=int(size), heartbeat_ttl=3600.0,
                            eval_batching=False)
            server.start()
            try:
                for i in range(n_nodes):
                    n = mock.node()
                    n.id = f"wscale-{size}-node-{i:06d}"
                    n.node_resources.cpu.cpu_shares = \
                        int(allocs_per_node * 16)
                    n.node_resources.memory.memory_mb = \
                        int(allocs_per_node * 52)
                    n.node_resources.disk.disk_mb = \
                        int(allocs_per_node * 16)
                    n.compute_class()
                    server.register_node(n)
                batch = []
                t0 = _time.perf_counter()
                for k in range(jobs):
                    job = mock.job(id=f"wscale-{size}-job-{k:04d}")
                    tg = job.task_groups[0]
                    tg.count = per_eval
                    tg.ephemeral_disk.size_mb = 10
                    tg.tasks[0].resources.cpu = 10
                    tg.tasks[0].resources.memory_mb = 32
                    server.register_job(job)
                    batch.append(job)
                deadline = _time.time() + timeout_s
                pending = {(j.namespace, j.id) for j in batch}
                while pending and _time.time() < deadline:
                    for key in list(pending):
                        ns, jid = key
                        placed = sum(
                            1 for a in server.state.allocs_by_job(ns,
                                                                  jid)
                            if a.desired_status == "run")
                        if placed >= per_eval:
                            pending.discard(key)
                    if pending:
                        _time.sleep(0.02)
                wall = _time.perf_counter() - t0
                if pending:
                    truncated = True
                    say(f"worker-scaling: pool={size} TRUNCATED "
                        f"({len(pending)}/{jobs} jobs unplaced after "
                        f"{timeout_s:.0f}s)")
                placed_total = total - len(pending) * per_eval
                walls[int(size)] = round(wall, 3)
                pps[int(size)] = round(placed_total / wall, 2) \
                    if wall > 0 else 0.0
                parity_mismatch += \
                    server.state.alloc_table.fold_parity_mismatch()
                say(f"worker-scaling: pool={size} -> "
                    f"{pps[int(size)]:.0f} placements/s "
                    f"({placed_total} placed in {wall:.2f}s, "
                    f"parity_mismatch={parity_mismatch})")
            finally:
                server.shutdown()
    finally:
        if prev_lean is None:
            os.environ.pop("NOMAD_TPU_LEAN_ALLOC_METRICS", None)
        else:
            os.environ["NOMAD_TPU_LEAN_ALLOC_METRICS"] = prev_lean
    base = pps.get(int(pool_sizes[0])) or 0.0
    best = max(pps.values()) if pps else 0.0
    return {
        "pool_sizes": [int(s) for s in pool_sizes],
        "placements_per_sec": pps,
        "wall_s": walls,
        "placed_per_size": total,
        "speedup_best_vs_1": round(best / base, 3) if base else 0.0,
        "parity_mismatch": parity_mismatch,
        "truncated": truncated,
    }


def make_fleet(rng: random.Random, h, n_nodes: int,
               racks: int = RACK_COUNT, gpus: bool = False) -> List:
    """Heterogeneous fleet: 3 machine classes, rack + datacenter spread
    attributes (the reference bench's rack axis). ``gpus`` equips every
    other node with an nvidia/gpu group of 2-4 instances (the BASELINE
    tier-5 'GPU device reservations' axis)."""
    nodes = []
    for i in range(n_nodes):
        node = mock.node()
        node.id = f"tier-node-{i:06d}"
        node.node_resources.cpu.cpu_shares = (4000, 8000, 16000)[i % 3]
        node.node_resources.memory.memory_mb = (8192, 16384, 32768)[i % 3]
        node.datacenter = f"dc{i % 2 + 1}"
        node.attributes["platform.rack"] = f"rack-{i % racks:03d}"
        if gpus and i % 2 == 0:
            n_inst = (2, 4)[i % 4 // 2]
            node.node_resources.devices = [NodeDeviceResource(
                vendor="nvidia", type="gpu", name="v100",
                instance_ids=[f"{node.id}-gpu-{k}"
                              for k in range(n_inst)])]
        node.compute_class()
        h.state.upsert_node(node)
        nodes.append(node)
    return nodes


def seed_utilization(rng: random.Random, h, nodes, frac: float,
                     priorities=(50,)) -> None:
    """Fill ~frac of each node's cpu with existing allocs."""
    for node in nodes:
        cap = node.node_resources.cpu.cpu_shares
        target = int(cap * frac)
        used = 0
        while used + 500 <= target:
            j = mock.job(priority=rng.choice(priorities))
            j.id = f"filler-{node.id}-{used}"
            j.task_groups[0].tasks[0].resources.cpu = 500
            j.task_groups[0].tasks[0].resources.memory_mb = rng.choice(
                [512, 1024])
            h.state.upsert_job(j)
            a = mock.alloc_for(j, node)
            a.client_status = ALLOC_CLIENT_RUNNING
            h.state.upsert_allocs([a])
            used += 500


def tier_job(tier: int, rng: random.Random, count: int):
    """The job each tier schedules."""
    job = mock.job(type="batch" if tier == 2 else "service")
    tg = job.task_groups[0]
    tg.count = count
    task = tg.tasks[0]
    task.resources.cpu = rng.choice([250, 500, 1000])
    task.resources.memory_mb = rng.choice([256, 512, 1024])

    if tier == 1:
        # BASELINE tier 1: 3-TG service job on a 5-node dev cluster --
        # the smallest end-to-end shape (web + api + worker, distinct
        # asks, one TG with dynamic ports)
        import copy as _copy
        tg.name = "web"
        tg.count = max(1, min(count, 3))
        tg.networks = [NetworkResource(dynamic_ports=[Port(label="http")])]
        for name, cnt, cpu, mem in (("api", 2, 500, 512),
                                    ("worker", 1, 1000, 1024)):
            tg2 = _copy.deepcopy(job.task_groups[0])
            tg2.name = name
            tg2.count = cnt
            tg2.networks = []
            tg2.tasks[0].resources.cpu = cpu
            tg2.tasks[0].resources.memory_mb = mem
            job.task_groups.append(tg2)
        return job

    if tier == 3:
        # C1M shape: ports + constraints (cpu+mem+port per BASELINE tier 3)
        tg.networks = [NetworkResource(
            dynamic_ports=[Port(label="http"), Port(label="rpc")])]
        job.constraints = [Constraint(l_target="${attr.kernel.name}",
                                      r_target="linux", operand="=")]
        tg.constraints = [Constraint(l_target="${attr.cpu.numcores}",
                                     r_target="2", operand=">=")]
    elif tier == 4:
        # C2M shape: affinity/anti-affinity/spread mixes
        job.affinities = [Affinity(l_target="${node.datacenter}",
                                   r_target="dc1", operand="=",
                                   weight=rng.choice([50, 100]))]
        tg.spreads = [Spread(attribute="${meta.platform.rack}", weight=50)]
    elif tier == 5:
        job.priority = 70
        task.resources.cpu = 1000
        # BASELINE tier 5: "priority tiers + GPU device reservations".
        # The GPU ask constrains placement to the equipped half of the
        # fleet; preemption pressure stays cpu (the filler jobs hold no
        # devices, so device availability never changes under eviction
        # and the windowed preempt kernel stays exact)
        task.resources.devices = [DeviceRequest(name="nvidia/gpu", count=1)]
    return job


def run_tier_placements(tier: int, n_nodes: int, count: int, seed: int,
                        alg: str, spread_variant: bool = False,
                        with_evictions: bool = False):
    """Build one world, schedule one tier-shaped eval with the given
    algorithm, return {alloc name -> node id} (plus, with_evictions,
    {alloc name -> sorted evicted alloc names})."""
    from .scheduler import Harness

    rng = random.Random(seed)
    mock._counter = itertools.count()
    h = Harness()
    cfg = SchedulerConfiguration(scheduler_algorithm=alg)
    if tier == 5:
        cfg.preemption_config = PreemptionConfig(
            service_scheduler_enabled=True, batch_scheduler_enabled=True)
    h.state.set_scheduler_config(cfg)
    nodes = make_fleet(rng, h, n_nodes, gpus=(tier == 5))
    if tier == 5:
        seed_utilization(rng, h, nodes, 0.95, priorities=(10, 20, 30, 40))
    elif tier in (3, 4):
        seed_utilization(rng, h, nodes, 0.25)

    job = tier_job(tier, rng, count)
    job.id = f"tier{tier}-job-{seed}"
    h.state.upsert_job(job)
    ev = mock.evaluation(job_id=job.id, type=job.type,
                         priority=job.priority)
    ev.id = f"tier{tier}-eval-{seed:08d}"
    err = h.process(job.type if job.type in ("service", "batch")
                    else "service", ev)
    assert err is None, err
    placed: Dict[str, str] = {}
    evicted: Dict[str, List[str]] = {}
    for plan in h.plans:
        pre_by_id: Dict[str, List[str]] = {}
        for node_id, allocs in plan.node_preemptions.items():
            for a in allocs:
                pre_by_id.setdefault(a.preempted_by_allocation,
                                     []).append(a.name)
        for node_id, allocs in plan.node_allocation.items():
            for a in allocs:
                if a.eval_id == ev.id:
                    placed[a.name] = node_id
                    evicted[a.name] = sorted(pre_by_id.get(a.id, []))
    if with_evictions:
        return placed, evicted
    return placed


def run_tier_parity(tier: int, n_nodes: int, count: int, seed: int,
                    spread_variant: bool = False
                    ) -> Tuple[Dict[str, str], Dict[str, str]]:
    """host-oracle vs tpu placements for one tier world; caller asserts
    equality."""
    host_alg = "spread" if spread_variant else "binpack"
    tpu_alg = "tpu-spread" if spread_variant else "tpu-binpack"
    host = run_tier_placements(tier, n_nodes, count, seed, host_alg,
                               spread_variant)
    tpu = run_tier_placements(tier, n_nodes, count, seed, tpu_alg,
                              spread_variant)
    return host, tpu
