#!/usr/bin/env python3
"""One run of one cell of BENCHMARK.json:

    python3 perfbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the submitter and never imports JAX; the system under
test runs in a child (server.py) that holds the chip. Set-up (native
build or load, fleet, base load through the served path, warm-up of the
cell's own programs, a lead-in of the cell's own traffic) ends where the
measured window starts; the comparison that decides `correct` runs after
the window has closed. The last line of stdout is the result.

Cells, configurations, traffic mixes and per-layer metrics are data:
BENCHMARK.json names them and this directory holds a file for each.
"""
from __future__ import annotations

import argparse
import json
import os
import random
import subprocess
import sys
import threading
import time
from concurrent.futures import ThreadPoolExecutor

T_PROCESS = time.monotonic()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, HERE)

import check  # noqa: E402
import fleet  # noqa: E402
import readers  # noqa: E402
from httpc import Api  # noqa: E402
from traffic import Generator  # noqa: E402


def log(msg: str) -> None:
    print(f"perfbench: {msg}", file=sys.stderr, flush=True)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_cell(name: str) -> dict:
    """Everything a cell is, found by name: its manifest entry, its
    configuration, its traffic mix (with the cell's own parameters laid
    over it) and the metrics that list it."""
    manifest = load_json(ROOT, "BENCHMARK.json")
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise SystemExit(f"perfbench: no workload {name!r} in "
                         f"BENCHMARK.json (has {sorted(cells)})")
    cell = dict(cells[name])
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(ROOT, cfg_entry["file"])
    mix = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    own = os.path.join(HERE, "workloads", f"{name}.json")
    if os.path.exists(own):
        mix.update(load_json(own).get("traffic", {}))

    def mine(metric: dict) -> bool:
        return name in metric.get("workloads", cells)
    return {"cell": cell, "config": config, "mix": mix,
            "all_end_to_end": manifest["end_to_end"],
            "end_to_end": [m for m in manifest["end_to_end"] if mine(m)],
            "per_layer": [m for m in manifest["per_layer"] if mine(m)]}


def scale_for_rehearsal(config: dict, mix: dict, scale: float) -> None:
    """A CPU rehearsal of the mechanics at a fraction of the fleet: nodes,
    job width, base load and rate shrink together, nothing else changes."""
    fleet, job = config["fleet"], config["job"]
    fleet["nodes"] = max(64, int(fleet["nodes"] * scale))
    fleet["racks"] = min(fleet["racks"], fleet["nodes"])
    job["count"] = max(2, int(job["count"] * scale))
    for rnd in config["scheduler"]["warm_rounds"]:
        rnd["count"] = max(2, int(rnd["count"] * scale))
    config["base_load_allocs"] = int(config["base_load_allocs"] * scale)
    if scale < 1:   # the hold is exercised, not sat through
        config["scheduler"]["window_phase"]["period_s"] = 5
    if "rate_per_s" in mix:
        mix["rate_per_s"] = min(float(mix["rate_per_s"]), 4.0)


def jobspec(template: dict, job_id: str) -> dict:
    """The JSON jobspec a user would PUT: the source's mock.Job() with
    the count set, and the spread stanza where the configuration has
    one."""
    task = template["task"]
    group = {
        "name": "web", "count": int(template["count"]),
        "ephemeral_disk": {"size_mb": int(template["ephemeral_disk_mb"])},
        "tasks": [{
            "name": "web", "driver": "mock",
            "config": {"run_for": "30s"},
            "resources": {"cpu": int(task["cpu_mhz"]),
                          "memory_mb": int(task["memory_mb"])},
        }],
    }
    if template.get("spread"):
        group["spreads"] = [dict(template["spread"])]
    return {"id": job_id, "name": job_id, "type": template["type"],
            "datacenters": ["dc1"], "task_groups": [group]}


class Server:
    """The child and the pipe to it."""

    def __init__(self, spec: dict):
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "server.py"),
             json.dumps(spec)],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            bufsize=1, cwd=ROOT)
        self._lock = threading.Lock()

    def read(self) -> dict:
        line = self.proc.stdout.readline()
        if not line:
            raise RuntimeError(
                f"the server process ended (exit {self.proc.wait()})")
        return json.loads(line)

    def ask(self, op: str, **kw) -> dict:
        with self._lock:
            self.proc.stdin.write(json.dumps({"op": op, **kw}) + "\n")
            self.proc.stdin.flush()
            return self.read()

    def tell(self, op: str, **kw) -> None:
        with self._lock:
            self.proc.stdin.write(json.dumps({"op": op, **kw}) + "\n")
            self.proc.stdin.flush()

    def close(self) -> None:
        if self.proc.poll() is None:
            try:
                self.tell("quit")
                self.proc.wait(timeout=60)
            except (OSError, subprocess.TimeoutExpired):
                self.proc.kill()
                self.proc.wait()


def run_many(gen: Generator, n_jobs: int, width: int) -> list:
    """`n_jobs` lifecycles, `width` at a time."""
    with ThreadPoolExecutor(width) as pool:
        return list(pool.map(lambda _: gen.lifecycle(time.monotonic()),
                             range(n_jobs)))


def fused_round(api: Api, gen: Generator, width: int, algorithm: str) -> list:
    """`width` jobs offered as one backlog -- pause the eval broker as an
    operator does, PUT, resume -- so the workers fuse them into one
    dispatch of that many lanes: warms the program of that shape."""
    path = "/v1/operator/scheduler/configuration"
    api.call("POST", path, {"scheduler_algorithm": algorithm,
                            "pause_eval_broker": True})
    with ThreadPoolExecutor(width) as pool:
        jobs = [pool.submit(gen.lifecycle, time.monotonic())
                for _ in range(width)]
        time.sleep(0.15)    # every PUT is in before the broker resumes
        api.call("POST", path, {"scheduler_algorithm": algorithm})
        return [j.result() for j in jobs]


def set_up(api: Api, server: Server, cell: dict, seed: int) -> dict:
    """Base load through the served path, then the fused rounds the
    configuration lists. Returns what it cost."""
    config, mix = cell["config"], cell["mix"]
    template = config["job"]
    count = int(template["count"])

    def make_job(job_id):
        return jobspec(template, job_id), count
    ack = lambda job_id: server.tell("ack", job=job_id)  # noqa: E731
    t0 = time.monotonic()
    base = Generator(api, {**mix, "stop_when_placed": False}, make_job,
                     ack, "base", seed)
    n_base = max(1, round(config["base_load_allocs"] / count))
    recs = run_many(base, n_base, int(config["scheduler"]["workers"]))
    bad = [r for r in recs if not r["ok"]]
    if bad:
        raise RuntimeError(f"base load: {len(bad)} of {n_base} jobs not "
                           f"placed: {bad[0]['error']}")
    t1 = time.monotonic()
    records = []
    for i, rnd in enumerate(config["scheduler"]["warm_rounds"]):
        width = dict(template, count=int(rnd["count"]))
        warm = Generator(
            api, mix, lambda jid, w=width: (jobspec(w, jid), w["count"]),
            ack, f"warm{i}", seed)
        recs = fused_round(api, warm, int(rnd["lanes"]),
                           config["scheduler"]["algorithm"])
        bad = [r for r in recs if not r["ok"]]
        if bad:
            raise RuntimeError(f"warm-up round {rnd}: {bad[0]['error']}")
        records += warm.snapshot()
    return {"base_jobs": n_base, "base_allocs": n_base * count,
            "base_s": t1 - t0, "warm_s": time.monotonic() - t1,
            "warm_records": records}


def committed_count(api: Api, rec: dict, deadline: float) -> int:
    """How many of a window job's allocs read back as committed. A
    stopped job reads complete once the stand-in has acknowledged it;
    the summary leaves out a stopped alloc whose client has not yet
    reported, so a short summary is re-read by alloc name."""
    while True:
        summary = api.get(f"/v1/job/{rec['id']}/summary")["summary"]
        got = sum(tg["complete"] + tg["starting"] + tg["running"]
                  for tg in summary.values())
        if got >= rec["count"] or time.monotonic() > deadline:
            break
        time.sleep(0.05)
    if got < rec["count"]:
        names = {a["name"]
                 for a in api.get(f"/v1/job/{rec['id']}/allocations")
                 if a["client_status"] not in ("failed", "lost")}
        log(f"read back {got} of {rec['count']} allocs of {rec['id']} in "
            f"its summary {summary}, {len(names)} by name "
            f"({rec['error'] or 'placed'})")
        got = len(names)
    return got


def replay_overcommitted(nodes_seen: dict, stop_floor: dict) -> int:
    """Nodes the comparison read that ever held more than their
    capacity. The plan verifier stops counting an alloc once its stop is
    committed (acknowledged by the client or not), which is no earlier
    than the index at which its submitter saw it placed and then stopped
    it."""
    over = 0
    for node, on_node in nodes_seen.values():
        events = []
        for b in on_node:
            cpu, mem, disk = check.alloc_resources(b)
            events.append((b["create_index"], 1, cpu, mem, disk))
            if (b["desired_status"] != "run"
                    or check.released_at(b) is not None):
                r_b = max(b["create_index"],
                          stop_floor.get(b["job_id"], b["modify_index"]))
                events.append((r_b, 0, -cpu, -mem, -disk))
        if check.ref.overcommitted(check.node_capacity(node), events):
            over += 1
    return over


def first_plan(live: list) -> tuple:
    """(index the eval's first plan committed at, {placement number:
    (node id, score the program reported for it)}) of a job's allocs."""
    plan_index = min(a["create_index"] for a in live)
    return plan_index, {
        check.name_index(a): (
            a["node_id"],
            a["metrics"]["scores"].get(f"{a['node_id']}.normalized-score"))
        for a in live if a["create_index"] == plan_index}


def read_back(api: Api, cell: dict, window_jobs: list, seed: int,
              snap_end: dict, stop_floor: dict, control: str) -> tuple:
    """The window's answers, read over the API after it closed, and the
    numbers `check` compares. `stop_floor`: job id -> the state index at
    which its submitter saw it placed (every job this run stopped).
    With `control`, the reference computed in that precision serves the
    sampled evals in the program's place and goes through the same
    comparison. Returns (numbers, the control's numbers or None, info)."""
    mix = cell["mix"]
    deadline = time.monotonic() + 60.0
    missing = sum(max(0, rec["count"] - committed_count(api, rec, deadline))
                  for rec in window_jobs)
    rng = random.Random(seed)
    placed = [r for r in window_jobs if r["ok"]]
    # the last job to finish, then the rest in an order drawn from the seed
    order = placed[-1:] + rng.sample(placed[:-1], len(placed[:-1]))
    base_order = list(fleet.racks(cell["config"]["fleet"], seed))
    nodes_seen: dict = {}

    def fetch(node_id):
        if node_id not in nodes_seen:
            nodes_seen[node_id] = (
                api.get(f"/v1/node/{node_id}"),
                api.get(f"/v1/node/{node_id}/allocations")["allocs"])
        return nodes_seen[node_id]
    def fresh():
        return {"evals": 0, "unreproduced": 0, "unreplayed": 0,
                "mismatches": [], "filled": 0, "touched": 0, "retried": 0,
                "gaps": [], "back": [], "ahead": []}
    tally, ctl = fresh(), fresh() if control else None
    for rec in order[:int(mix["check_jobs"])]:
        job = api.get(f"/v1/job/{rec['id']}")
        live = [a for a in api.get(f"/v1/job/{rec['id']}/allocations")
                if a["client_status"] not in ("failed", "lost")]
        missing += max(0, rec["count"] - len({a["name"] for a in live}))
        ev = api.get(f"/v1/evaluation/{rec['eval_id']}")
        plan_index, served = first_plan(live)
        group = live[0]["task_group"]
        # solved against a state no older than the eval and older than
        # its plan, likeliest the newest
        indexes = range(plan_index - 1, ev["create_index"] - 1, -1)
        got = check.compare_plan(served, job, group, rec["eval_id"],
                                 plan_index, indexes, base_order, fetch)
        add(tally, got, len(live) - len(served), plan_index)
        if control and got["reproduced"]:
            low = check.replay(job, group, got["order"],
                               got["usage_index"], fetch, control)
            add(ctl, check.compare_plan(
                {k: low[k][:2] for k in served}, job, group, rec["eval_id"],
                plan_index, [got["index"]], base_order, fetch), 0,
                plan_index)
    c = snap_end["counters"]
    not_device = (c.get("nomad.solver.dispatch_timeout", 0)
                  + c.get("nomad.solver.dispatch_error", 0)
                  + c.get("nomad.solver.host_fallback_dispatches", 0)
                  + c.get("nomad.solver.backend_unavailable", 0)
                  + c.get("nomad.scheduler.placements_host_fallback", 0)
                  + snap_end["breaker"]["trips"]
                  + (1 if snap_end["degraded"] else 0)
                  + (0 if c.get("nomad.solver.dispatch_ok", 0) > 0 else 1))
    shared = {
        "missing_allocs": missing,
        "not_device": not_device,
        "blocked_evals": snap_end["blocked_evals"],
        "overcommitted_nodes": replay_overcommitted(nodes_seen, stop_floor)}

    def numbers(t):
        return {**shared,
                "unreproduced_evals": t["unreproduced"],
                "choice_mismatches": len(t["mismatches"]),
                # no placement compared is not a pass
                "score_gap_max": max(t["gaps"]) if t["gaps"] else None}
    gaps = sorted(tally["gaps"])
    info = {"sampled_evals": tally["evals"],
            # unreproduced because no index was replayed at all: the
            # instrument never looked (check.scan_reach)
            "unreplayed_evals": tally["unreplayed"],
            "placements_compared": len(gaps) + len(tally["mismatches"])
            + tally["filled"] + tally["touched"],
            "moved_off_filled_node": tally["filled"],
            "moved_off_touched_node": tally["touched"],
            "mismatches_first": tally["mismatches"][:5],
            "retried_not_compared": tally["retried"],
            "plan_minus_snapshot_index": tally["back"],
            "usage_minus_snapshot_index": tally["ahead"],
            "score_gap_p50": readers.percentile(gaps, 50) if gaps else None,
            "nodes_read": len(nodes_seen)}
    if control:
        info["control_unreplayed_evals"] = ctl["unreplayed"]
    return numbers(tally), numbers(ctl) if control else None, info


def add(tally: dict, got: dict, retried: int, plan_index: int) -> None:
    tally["evals"] += 1
    tally["unreproduced"] += 0 if got["reproduced"] else 1
    tally["unreplayed"] += 1 if got["index"] is None else 0
    tally["mismatches"] += got["mismatches"]
    tally["filled"] += got["moved"]["filled"]
    tally["touched"] += got["moved"]["touched"]
    tally["retried"] += retried
    tally["gaps"] += got["gaps"]
    if got["reproduced"]:
        tally["back"].append(plan_index - got["index"])
        tally["ahead"].append(got["usage_index"] - got["index"])


def read_metrics(kind: str, metrics: list, run: dict) -> dict:
    """Each listed metric through the reader its file names; one that
    finds nothing to read is left out."""
    out = {}
    for m in metrics:
        spec = load_json(HERE, kind, f"{m['name']}.json")
        value = readers.READERS[spec["reader"]](spec.get("args", {}), run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def hold_for_phase(phase: dict, started: float, lead_in_s: float) -> float:
    """The server does periodic work on a clock that starts with it (the
    core GC job, every `period_s`), and a window that met it at another
    point each time set-up took a second more or less would measure the
    set-up. So the window opens when the server's age is `at_age_s`
    modulo the period: sleep until `lead_in_s` before the next such
    instant. Returns the seconds slept, which are no part of set-up."""
    period, at_age = float(phase["period_s"]), float(phase["at_age_s"])
    age_then = time.monotonic() - started + lead_in_s
    wait = (at_age - age_then) % period
    time.sleep(wait)
    return wait


def measure_window(server: Server, gen: Generator, seconds: float,
                   trace_s: float, held_s: float) -> dict:
    """The measured window: the server's counters and the allocs the
    submitters have seen run, at each edge; in a traced run the
    profiler's trace of `trace_s` seconds in its middle. `held_s`: what
    `hold_for_phase` slept, taken out of the set-up's seconds."""
    snap0 = server.ask("snapshot")
    t0, seen0 = time.monotonic(), gen.placed_seen
    edges = None
    if trace_s:
        trace_s = min(trace_s, seconds / 2)
        time.sleep((seconds - trace_s) / 2)
        edges = [server.ask("trace_start")]
        time.sleep(trace_s)
        edges.append(server.ask("trace_stop"))
    rest = t0 + seconds - time.monotonic()
    if rest > 0:
        time.sleep(rest)
    t1, seen1 = time.monotonic(), gen.placed_seen
    return {"snap0": snap0, "snap1": server.ask("snapshot"), "t0": t0,
            "t1": t1, "seen0": seen0, "seen1": seen1,
            "traced": edges, "held_s": held_s,
            "setup_s": t0 - T_PROCESS - held_s}


def diagnostics(win: dict, window_jobs: list) -> dict:
    """Beside the metrics, for whoever reads a run: how the wait moved
    through the window, what was still in flight at its close, what
    compiled inside it, how far the stand-in lagged."""
    t0, t1 = win["t0"], win["t1"]
    return {
        "window_s": t1 - t0, "jobs_in_window": len(window_jobs),
        "server_age_at_window_start_s": win["server_age_s"],
        "held_for_phase_s": win["held_s"],
        "commit_ms_p95": readers.percentile(sorted(
            r["commit_ms"] for r in window_jobs) or [0.0], 95),
        "p50_ms_by_fifth": [readers.percentile(sorted(
            r["commit_ms"] for r in window_jobs
            if k <= 5 * (r["due"] - t0) / (t1 - t0) < k + 1) or [0.0], 50)
            for k in range(5)],
        "unfinished_at_close": sum(
            1 for r in window_jobs
            if r["placed"] is None or r["placed"] > t1),
        # [seconds into the window, ms] of each full collection in it
        "gc_full_in_window": [
            [t - win["snap0"]["t"], ms] for t, ms in win["snap1"]["gc_full"]
            if t >= win["snap0"]["t"]],
        "gc_full_before_window": len(win["snap0"]["gc_full"]),
        "compiles_in_window": win["snap1"]["compile"]["backend_compiles"]
        - win["snap0"]["compile"]["backend_compiles"],
        "ack_lag_ms": win["snap1"]["ack_lag_ms"]}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse", type=float, default=0.0, metavar="SCALE",
                    help="CPU rehearsal of the mechanics at this fraction "
                         "of the fleet; measures nothing")
    ap.add_argument("--control", default="",
                    metavar="DTYPE",
                    help="the control: the reference computed in this lower "
                         "precision (bfloat16) serves the sampled evals in "
                         "the program's place and decides `correct`, which "
                         "has to come out false; for the readings a limit "
                         "is set from, never passed by a benchmark run")
    args = ap.parse_args(argv)

    cell = load_cell(args.workload)
    config, mix = cell["config"], cell["mix"]
    if args.rehearse:
        scale_for_rehearsal(config, mix, args.rehearse)
    limits = check.load_limits(config["limits"])
    seams = load_json(HERE, "seams.json")["seams"] if args.trace else []
    server = Server({
        "workload": args.workload, "seed": args.seed, "config": config,
        "trace": args.trace, "seams": seams,
        "chips": int(cell["cell"]["chips"]),
        "require_platform": "" if args.rehearse else "tpu"})
    try:
        hello = server.read()
        if "error" in hello:
            log(hello["error"])
            return 3
        device = hello["device"]
        if hello["x64"]:
            log("x64 is on: the configurations state float32, x64 off")
            return 3
        api = Api(hello["ready"])
        cost = set_up(api, server, cell, args.seed)
        log(f"set-up: fleet + {cost['base_allocs']} base allocs in "
            f"{cost['base_s']:.1f}s, warm-up {cost['warm_s']:.1f}s")

        template = config["job"]
        count = int(template["count"])
        gen = Generator(
            api, mix, lambda jid: (jobspec(template, jid), count),
            lambda jid: server.tell("ack", job=jid), args.workload,
            args.seed)
        lead_in = float(mix["lead_in_s"])
        held = hold_for_phase(config["scheduler"]["window_phase"],
                              hello["started"], lead_in)
        gen.start(time.monotonic(), [lead_in, args.seconds])
        time.sleep(lead_in)
        win = measure_window(server, gen, args.seconds,
                             float(mix["trace_s"]) if args.trace else 0.0,
                             held)
        win["server_age_s"] = win["t0"] - hello["started"]
        t0, t1 = win["t0"], win["t1"]
        drained = gen.stop(timeout_s=90.0)
        t_end = time.monotonic()
        records = gen.snapshot()
        if mix["loop"] == "open":
            # the schedule's second stretch, due from the instant the
            # lead-in ended: the window, but for the snapshot's few ms
            window_jobs = [r for r in records if r["stretch"] == 1]
        else:
            window_jobs = [r for r in records
                           if r["placed"] is None or r["placed"] >= t0]
        for r in window_jobs:
            if "sent" in r:
                r["late_ms"] = (r["sent"] - r["due"]) * 1e3
            # a job that never placed has waited until the run gave up
            r["commit_ms"] = ((r["placed"] if r["ok"] else t_end)
                              - r["due"]) * 1e3
        memory = server.ask("memory")["memory_peak_bytes"]
        snap_end = server.ask("snapshot")
        trace = None
        if args.trace:
            trace = server.ask(
                "trace_reduce", programs=mix["solve_programs"],
                labels=[s["label"] for s in seams])
        t_check = time.monotonic()
        stop_floor = {r["id"]: r["index_placed"]
                      for r in cost.pop("warm_records") + records
                      if r.get("index_placed") and "stopped" in r}
        numbers, control, info = read_back(
            api, cell, window_jobs, args.seed, snap_end, stop_floor,
            args.control)
        if not drained:
            numbers["missing_allocs"] += 1
        if control is not None:
            # the control stands in the program's place: its verdict is
            # the run's, and the program's own numbers go beside it
            info["program"] = numbers
            numbers = control
        ok, rows = check.verdict(numbers, limits)
        check_s = time.monotonic() - t_check
    finally:
        server.close()

    run = {"snap0": win["snap0"], "snap1": win["snap1"],
           "records": window_jobs, "trace": trace, "config": config,
           "device": device, "window": win}
    if args.trace:
        metrics = read_metrics("layer_metrics", cell["per_layer"], run)
    else:
        metrics = read_metrics("end_to_end", cell["end_to_end"], run)
    failed = sum(1 for r in window_jobs if not r["ok"])
    dev = {"platform": device["platform"], "kind": device["kind"],
           "count": device["count"], "memory_peak_bytes": memory}
    result = {"correct": bool(ok), "attempted": len(window_jobs),
              "failed": failed, "metrics": metrics, "device": dev}
    if trace is not None:
        dev["busy_s"] = trace["busy_s"]
        dev["window_s"] = trace["window_s"]
        result["breakdown"] = {"device_ops": trace["device_ops"],
                               "idle_gaps": trace["idle_gaps"]}
        result["programs"] = trace["programs"]
    result["info"] = {
        **info, **diagnostics(win, window_jobs), "check_s": check_s,
        "all_end_to_end": {k: v["value"] for k, v in read_metrics(
            "end_to_end", cell["all_end_to_end"], run).items()},
        "set_up": cost}
    result["compared"] = {name: {"value": v, "limit": lim}
                          for name, v, lim in rows}
    for name, v, lim in rows:
        log(f"compared {name} = {v} (limit {lim})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
