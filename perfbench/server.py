"""The system under test, in a process of its own: it holds the chip.

Started by run.py, which never imports JAX. Builds the configuration's
deployment through the program's public entry (api/devagent.start_agent,
server.register_node), then answers run.py over a pipe: one JSON object
a line on stdin, one a line back on the descriptor it was started with.
Everything else this process prints goes to stderr.

What it takes from the program: the agent, its telemetry registry, the
guard's state and compile clock, the span sink, the broker's stats. The
fleet stand-in (one thread acknowledging stops for every node agent)
lives here, as `client/agent.SimClient` does for a small fleet.
"""
from __future__ import annotations

import json
import os
import queue
import shutil
import sys
import threading
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _pipe():
    """Keep the real stdout for the protocol; send every other print of
    this process to stderr."""
    out = os.fdopen(os.dup(1), "w", buffering=1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    return out


def register_fleet(server, fleet: dict, seed: int) -> None:
    """`nodes` ready nodes of the one size the configuration states, in
    `racks` racks dealt from the seed (the source's mock.Node() with
    meta.rack set)."""
    from nomad_tpu import mock
    from fleet import racks
    size = fleet["node"]
    for node_id, rack in racks(fleet, seed).items():
        n = mock.node()
        n.id = node_id
        n.name = node_id
        n.node_resources.cpu.cpu_shares = int(size["cpu_mhz"])
        n.node_resources.memory.memory_mb = int(size["memory_mb"])
        n.node_resources.disk.disk_mb = int(size["disk_mb"])
        n.meta["rack"] = rack
        n.compute_class()
        server.register_node(n)


class FleetStandIn(threading.Thread):
    """Acknowledges stops as the node agents would: once every alloc of a
    stopped job reads desired_status stop, report them complete, which is
    what frees their capacity."""

    def __init__(self, server):
        super().__init__(name="fleet-stand-in", daemon=True)
        self.server = server
        self.jobs: "queue.Queue" = queue.Queue()
        self.acked = 0
        self.lag_ms_total = 0.0
        self.lag_n = 0
        self._halt = threading.Event()

    def run(self) -> None:
        later = []
        while not self._halt.is_set():
            try:
                later.append(self.jobs.get(timeout=0.02))
                while True:
                    later.append(self.jobs.get_nowait())
            except queue.Empty:
                pass
            later = [(job_id, t_in) for job_id, t_in in later
                     if not self._ack(job_id, t_in)]
            if later:
                time.sleep(0.02)

    def _ack(self, job_id: str, t_in: float) -> bool:
        allocs = self.server.state.allocs_by_job("default", job_id)
        if any(a.desired_status == "run" for a in allocs):
            return False        # the stop plan has not committed yet
        acks = []
        for a in allocs:
            if not a.client_terminal_status():
                upd = a.copy_skip_job()
                upd.client_status = "complete"
                upd.client_terminal_time = time.time()
                acks.append(upd)
        if acks:
            self.server.update_allocs_from_client(acks)
        self.acked += len(acks)
        self.lag_ms_total += (time.monotonic() - t_in) * 1e3
        self.lag_n += 1
        return True

    def halt(self) -> None:
        self._halt.set()


class GcPauses:
    """Every collection of the oldest generation, which stops every
    thread of the server for as long as it takes: when (seconds on this
    process's monotonic clock) and how long."""

    def __init__(self):
        import gc
        self.full: list = []
        self._t0 = 0.0
        gc.callbacks.append(self)

    def __call__(self, phase: str, info: dict) -> None:
        if info["generation"] < 2:
            return
        if phase == "start":
            self._t0 = time.monotonic()
        else:
            self.full.append([self._t0, (time.monotonic() - self._t0) * 1e3])


class SpanSums:
    """Every span the program records, by name: count and total ms. The
    sink the program had (the quality observatory's) keeps being called."""

    def __init__(self):
        from nomad_tpu.server import tracing
        self._lock = threading.Lock()
        self.sums: dict = {}
        self._prev = getattr(tracing, "_SPAN_SINK", None)
        tracing.set_span_sink(self)

    def __call__(self, name: str, dur_ms: float) -> None:
        with self._lock:
            row = self.sums.setdefault(name, [0, 0.0])
            row[0] += 1
            row[1] += dur_ms
        if self._prev is not None:
            self._prev(name, dur_ms)

    def snapshot(self) -> dict:
        with self._lock:
            return {k: list(v) for k, v in self.sums.items()}


def wrap_seams(seams: list) -> None:
    """Traced runs only: put a jax.profiler.TraceAnnotation around each
    listed callable so idle gaps can be named by what the host did. A
    seam the program no longer has is skipped and reported."""
    import importlib
    import jax
    for seam in seams:
        try:
            owner = importlib.import_module(seam["module"])
            for part in seam.get("owner", "").split("."):
                if part:
                    owner = getattr(owner, part)
            fn = getattr(owner, seam["attr"])
        except (ImportError, AttributeError) as e:
            print(f"perfbench.server: seam {seam} not found: {e}",
                  file=sys.stderr)
            continue

        def make(fn=fn, label=seam["label"]):
            def wrapped(*a, **kw):
                with jax.profiler.TraceAnnotation(label):
                    return fn(*a, **kw)
            wrapped.__wrapped__ = fn
            return wrapped
        setattr(owner, seam["attr"], make())


def snapshot(server, stand_in, spans, pauses) -> dict:
    from nomad_tpu.server.telemetry import metrics
    from nomad_tpu.solver import guard
    tel = metrics.snapshot()
    st = guard.state()

    def totals(series: dict, mean_key: str) -> dict:
        return {k: [v.get("count", 0), v.get(mean_key, 0.0) * v.get("count", 0)]
                for k, v in series.items()}
    return {
        "t": time.monotonic(),
        "gc_full": list(pauses.full),
        "counters": dict(tel["counters"]),
        # count and total only: the ring's percentiles outlive a window
        "timers": totals(tel["samples"], "mean_ms"),
        "gauges": totals(tel["gauges"], "mean"),
        "spans": spans.snapshot() if spans is not None else {},
        "compile": guard.compile_stats(),
        "breaker": {"state": st["breaker"]["state"],
                    "trips": st["breaker"]["trips"]},
        "degraded": bool(st["degraded"]),
        "blocked_evals": server.blocked_evals.stats()["total_blocked"],
        "broker": server.broker.stats(),
        "acked": stand_in.acked,
        "ack_lag_ms": [stand_in.lag_n, stand_in.lag_ms_total],
    }


def memory_peak_bytes() -> int:
    import jax
    peak = 0
    for d in jax.devices():
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def main() -> int:
    out = _pipe()
    spec = json.loads(sys.argv[1])
    sys.path.insert(0, ROOT)
    cache = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = cache

    def reply(obj: dict) -> None:
        out.write(json.dumps(obj) + "\n")
        out.flush()

    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if spec["require_platform"] and (
            device["platform"] != spec["require_platform"]
            or device["count"] < spec["chips"]):
        reply({"error": f"JAX came up on {device}, the cell asks for "
                        f"{spec['chips']} x {spec['require_platform']}"})
        return 3
    import nomad_tpu.solver  # noqa: F401 -- switches the compile cache on
    from nomad_tpu import native
    from nomad_tpu.api.devagent import start_agent
    from nomad_tpu.structs.job import reseed_ids

    native.build()
    if native.load() is None:
        reply({"error": "the native control-plane library did not load"})
        return 3
    reseed_ids(spec["seed"])
    cfg = spec["config"]
    sched = cfg["scheduler"]
    traced = bool(spec["trace"])
    if traced:
        wrap_seams(spec["seams"])
    if os.environ.get("PERFBENCH_PLANT"):
        # tests/test_faults.py breaks the program underneath a run here
        import runpy
        runpy.run_path(os.environ["PERFBENCH_PLANT"])
    t_started = time.monotonic()
    server, http = start_agent(
        workers=int(sched["workers"]), port=0,
        algorithm=sched["algorithm"],
        eval_batching=bool(sched["eval_batching"]),
        heartbeat_ttl=float(sched["heartbeat_ttl_s"]))
    stand_in = FleetStandIn(server)
    pauses = GcPauses()
    spans = SpanSums() if traced else None
    trace_dir = os.path.join(ROOT, ".perfbench_trace",
                             f"{spec['workload']}")
    try:
        register_fleet(server, cfg["fleet"], spec["seed"])
        stand_in.start()
        reply({"ready": http.port, "device": device, "started": t_started,
               "x64": bool(jax.config.jax_enable_x64),
               "cache_dir": cache})
        for line in sys.stdin:
            msg = json.loads(line)
            op = msg["op"]
            if op == "ack":
                stand_in.jobs.put((msg["job"], time.monotonic()))
            elif op == "snapshot":
                reply(snapshot(server, stand_in, spans, pauses))
            elif op == "memory":
                reply({"memory_peak_bytes": memory_peak_bytes()})
            elif op == "trace_start":
                shutil.rmtree(trace_dir, ignore_errors=True)
                opts = jax.profiler.ProfileOptions()
                opts.python_tracer_level = 0    # host spans: ours only
                jax.profiler.start_trace(trace_dir, profiler_options=opts)
                reply(snapshot(server, stand_in, spans, pauses))
            elif op == "trace_stop":
                jax.profiler.stop_trace()
                reply(snapshot(server, stand_in, spans, pauses))
            elif op == "trace_reduce":
                import tracered
                red = tracered.reduce_dir(trace_dir, msg["programs"],
                                          msg["labels"])
                shutil.rmtree(trace_dir, ignore_errors=True)
                reply(red)
            elif op == "quit":
                break
    finally:
        stand_in.halt()
        http.shutdown()
        server.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
