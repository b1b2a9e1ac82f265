"""Eval-scoped span flight recorder (server/tracing.py): tail-based
retention, hard memory caps, cross-thread context handoff through the
dispatch pipeline, the /v1/agent/trace surface, the operator waterfall
renderer, and the NOMAD_TPU_TRACE=0 kill-switch parity guarantee."""
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server.tracing import TraceCtx, tracer, trace_enabled

N_NODES, COUNT, SEED = 12, 6, 7


@pytest.fixture(autouse=True)
def clean_tracer(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE_SAMPLE", "1.0")
    tracer._reset_for_tests()
    yield
    tracer._reset_for_tests()


def _finish(trace_id, **kw):
    tracer.end(trace_id, **kw)


# ----------------------------------------------------------------------
# Recorder unit behavior


def test_begin_span_end_roundtrip():
    ctx = tracer.begin("ev-1", job="j1", lane="service")
    with tracer.activate(ctx):
        with tracer.span("stage.a", step=1):
            # nomadlint: waive=no-sleep-sync -- simulated work: the measured span duration is the subject
            time.sleep(0.01)
        with tracer.span("stage.b", ctx=ctx):
            pass
    _finish("ev-1")
    tr = tracer.get("ev-1")
    assert tr is not None
    assert tr["eval_id"] == "ev-1"
    assert tr["tags"]["job"] == "j1"
    names = [s["name"] for s in tr["spans"]]
    assert names == ["stage.a", "stage.b"]
    assert tr["spans"][0]["dur_ms"] >= 5.0
    assert tr["spans"][0]["tags"] == {"step": 1}


def test_tail_retention_healthy_sampled_out(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE_SAMPLE", "0")
    for i in range(20):
        tracer.begin(f"ok-{i}")
        _finish(f"ok-{i}")
    assert tracer.stats()["retained"] == 0
    assert tracer.stats()["dropped"] == 20


def test_tail_retention_degraded_always_kept(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE_SAMPLE", "0")
    ctx = tracer.begin("bad-1")
    tracer.mark_degraded("host_fallback", ctx=ctx)
    _finish("bad-1")
    ctx = tracer.begin("err-1")
    _finish("err-1", status="nacked", error="Boom: x")
    assert tracer.stats()["retained"] == 2
    tr = tracer.get("bad-1")
    assert tr["degraded"] and tr["degraded_reason"] == "host_fallback"
    # the degraded event span timestamps the root cause
    assert any(s["name"] == "degraded" for s in tr["spans"])
    assert tracer.get("err-1")["error"] == "Boom: x"


def test_tail_retention_slow_always_kept(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE_SAMPLE", "0")
    monkeypatch.setenv("NOMAD_TPU_TRACE_SLOW_MS", "5")
    ctx = tracer.begin("slow-1")
    tracer.record("stage", time.time() - 1.0, 1000.0, ctx=ctx)
    _finish("slow-1")
    assert tracer.get("slow-1") is not None


def test_memory_hard_caps(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE_CAP", "8")
    monkeypatch.setenv("NOMAD_TPU_TRACE_MAX_SPANS", "4")
    for i in range(50):
        ctx = tracer.begin(f"cap-{i}")
        for k in range(10):            # > MAX_SPANS: rest truncated
            tracer.event(f"s{k}", ctx=ctx)
        tracer.mark_degraded("host_fallback", ctx=ctx)  # always-keep
        _finish(f"cap-{i}")
    st = tracer.stats()
    assert st["retained"] <= 8, "trace-count cap violated"
    tr = tracer.get("cap-49")
    assert len(tr["spans"]) == 4
    assert tr["truncated_spans"] > 0


def test_byte_cap_evicts_oldest(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE_MB", "0.01")   # ~10KB
    for i in range(64):
        ctx = tracer.begin(f"byte-{i}")
        for k in range(8):
            tracer.event("stage.with.a.longish.name", ctx=ctx,
                         detail="x" * 64)
        tracer.mark_degraded("host_fallback", ctx=ctx)
        _finish(f"byte-{i}")
    st = tracer.stats()
    assert st["retained_bytes"] <= 0.01 * 1024 * 1024
    assert st["retained"] < 64
    assert tracer.get("byte-63") is not None, "newest must survive"


def test_kill_switch_no_ops(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    assert not trace_enabled()
    assert tracer.begin("off-1") is None
    with tracer.span("x") as sp:
        sp.tag(a=1)                    # must not raise
    tracer.mark_degraded("host_fallback")
    _finish("off-1")
    st = tracer.stats()
    assert st["active"] == 0 and st["retained"] == 0


def test_group_ctx_fans_out_to_every_member():
    a = tracer.begin("ga")
    b = tracer.begin("gb")
    g = tracer.group([a, b, None, a])
    assert isinstance(g, TraceCtx) and len(g.traces) == 2
    with tracer.span("fused", ctx=g, generation=3):
        pass
    _finish("ga")
    _finish("gb")
    for tid in ("ga", "gb"):
        spans = tracer.get(tid)["spans"]
        assert [s["name"] for s in spans] == ["fused"]
        assert spans[0]["tags"]["generation"] == 3


def test_explicit_handoff_across_threads():
    """The pipeline pattern: ctx captured on the eval thread, spans
    recorded from a different thread land in the right trace."""
    ctx = tracer.begin("xt-1")
    done = threading.Event()

    def pipeline_thread():
        with tracer.activate(ctx):
            with tracer.span("solver.fuse_dispatch", generation=1):
                pass
        done.set()

    threading.Thread(target=pipeline_thread, daemon=True).start()
    assert done.wait(5.0)
    _finish("xt-1")
    spans = tracer.get("xt-1")["spans"]
    assert [s["name"] for s in spans] == ["solver.fuse_dispatch"]


def test_abandoned_active_traces_bounded(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE_CAP", "4")
    for i in range(100):               # never end()ed
        tracer.begin(f"leak-{i}")
    assert tracer.stats()["active"] <= 16   # 4 * cap


def test_sampling_is_deterministic_not_rng(monkeypatch):
    import random
    monkeypatch.setenv("NOMAD_TPU_TRACE_SAMPLE", "0.5")
    random.seed(1234)
    before = random.getstate()
    for i in range(32):
        tracer.begin(f"det-{i}")
        _finish(f"det-{i}")
    assert random.getstate() == before, \
        "tracing must not touch global RNG state"
    kept1 = {t["eval_id"] for t in tracer.list_traces(limit=0)}
    tracer._reset_for_tests()
    for i in range(32):
        tracer.begin(f"det-{i}")
        _finish(f"det-{i}")
    kept2 = {t["eval_id"] for t in tracer.list_traces(limit=0)}
    assert kept1 == kept2, "same ids must sample identically"
    assert 0 < len(kept1) < 32


# ----------------------------------------------------------------------
# Chrome/Perfetto export + benchkit artifact hook


def test_chrome_trace_export(tmp_path):
    ctx = tracer.begin("ch-1")
    with tracer.span("stage.a", ctx=ctx):
        pass
    tracer.mark_degraded("watchdog_timeout", ctx=ctx)
    _finish("ch-1")
    doc = tracer.chrome_trace()
    metas = [e for e in doc["traceEvents"] if e["ph"] == "M"]
    xs = [e for e in doc["traceEvents"] if e["ph"] == "X"]
    assert metas and xs
    assert "degraded:watchdog_timeout" in metas[0]["args"]["name"]
    assert all(e["ts"] > 0 and e["dur"] >= 0 for e in xs)

    from nomad_tpu.benchkit import export_chrome_trace
    out = tmp_path / "BENCH_trace.json"
    assert export_chrome_trace(str(out)) == str(out)
    import json
    data = json.loads(out.read_text())
    assert data["traceEvents"]


def test_export_skips_when_disabled_or_empty(tmp_path, monkeypatch):
    from nomad_tpu.benchkit import export_chrome_trace
    assert export_chrome_trace(str(tmp_path / "e.json")) is None
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    assert export_chrome_trace(str(tmp_path / "e.json")) is None


# ----------------------------------------------------------------------
# End-to-end: broker -> worker -> scheduler -> plan apply, via a live
# server; then over the HTTP surface.


def _wait(cond, timeout=10.0, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(0.05)
    raise AssertionError(f"timeout waiting for {msg}")


def test_server_lifecycle_spans_end_to_end():
    from nomad_tpu.client import SimClient
    from nomad_tpu.server import Server

    server = Server(num_workers=2, heartbeat_ttl=5.0)
    server.start()
    try:
        c = SimClient(server, mock.node())
        c.start()
        _wait(lambda: len(server.state.nodes()) == 1, msg="node up")
        job = mock.job()
        job.task_groups[0].count = 2
        ev = server.register_job(job)
        _wait(lambda: len(server.state.allocs_by_job(
            job.namespace, job.id)) == 2, msg="allocs placed")
        _wait(lambda: tracer.get(ev.id) is not None
              and tracer.get(ev.id)["status"] == "complete",
              msg="trace retained")
        tr = tracer.get(ev.id)
        names = {s["name"] for s in tr["spans"]}
        for want in ("broker.wait", "worker.wait_for_index",
                     "worker.invoke", "plan.submit", "plan.evaluate",
                     "plan.commit"):
            assert want in names, (want, sorted(names))
        # cross-thread spans carry their recording thread for forensics
        threads = {s["thread"] for s in tr["spans"]}
        assert len(threads) > 1, threads
        c.stop()
    finally:
        server.shutdown()


def test_http_trace_surface():
    from nomad_tpu.api.client import ApiClient, ApiError
    from nomad_tpu.api.http import HttpServer
    from nomad_tpu.server import Server

    # fabricate retained traces directly -- the HTTP layer is under test
    ctx = tracer.begin("h-deg")
    tracer.mark_degraded("host_fallback", ctx=ctx)
    _finish("h-deg")
    ctx = tracer.begin("h-ok")
    with tracer.span("stage.a", ctx=ctx):
        # nomadlint: waive=no-sleep-sync -- simulated work: the measured span duration is the subject
        time.sleep(0.01)
    _finish("h-ok")

    server = Server(num_workers=0, heartbeat_ttl=30.0)
    server.start()
    http = HttpServer(server, port=0)
    http.start()
    try:
        api = ApiClient(f"http://127.0.0.1:{http.port}")
        reply = api.get("/v1/agent/trace")
        ids = {t["eval_id"] for t in reply["traces"]}
        assert {"h-deg", "h-ok"} <= ids
        assert reply["stats"]["retained"] >= 2

        reply = api.get("/v1/agent/trace", degraded="1")
        assert {t["eval_id"] for t in reply["traces"]} == {"h-deg"}
        assert reply["traces"][0]["degraded_reason"] == "host_fallback"

        reply = api.get("/v1/agent/trace", slowest="1")
        assert len(reply["traces"]) == 1

        tr = api.get("/v1/agent/trace/h-ok")
        assert [s["name"] for s in tr["spans"]] == ["stage.a"]

        doc = api.get("/v1/agent/trace", format="chrome")
        assert doc["traceEvents"]

        with pytest.raises(ApiError):
            api.get("/v1/agent/trace/nope")
        try:
            api.get("/v1/agent/trace/nope")
        except ApiError as e:
            assert e.status == 404
    finally:
        http.shutdown()
        server.shutdown()


# ----------------------------------------------------------------------
# Operator waterfall rendering


def test_waterfall_renderer():
    from nomad_tpu.cli import _render_trace_waterfall

    t0 = time.time()
    tr = {
        "eval_id": "wf-1", "status": "complete", "dur_ms": 120.0,
        "degraded": True, "degraded_reason": "watchdog_timeout",
        "tags": {"lane": "service"}, "truncated_spans": 0,
        "spans": [
            {"name": "broker.wait", "t0": t0, "dur_ms": 40.0,
             "tags": {"deliveries": 0}},
            {"name": "solver.fuse_dispatch", "t0": t0 + 0.05,
             "dur_ms": 60.0, "tags": {"generation": 2}},
            {"name": "plan.commit", "t0": t0 + 0.115, "dur_ms": 5.0},
        ],
    }
    out = _render_trace_waterfall(tr)
    assert "wf-1" in out
    assert "DEGRADED(watchdog_timeout)" in out
    for name in ("broker.wait", "solver.fuse_dispatch", "plan.commit"):
        assert name in out
    assert "generation=2" in out
    assert "▇" in out
    # later spans start further right than earlier ones
    lines = [ln for ln in out.splitlines() if "▇" in ln]
    assert lines[0].index("▇") < lines[-1].index("▇")


def test_waterfall_renderer_empty_trace():
    from nomad_tpu.cli import _render_trace_waterfall
    out = _render_trace_waterfall(
        {"eval_id": "e", "status": "complete", "dur_ms": 0.0,
         "degraded": False, "spans": []})
    assert "no spans" in out


# ----------------------------------------------------------------------
# Kill-switch parity: NOMAD_TPU_TRACE=0 must leave scheduling
# byte-identical (same worlds, same placements, zero recorder state).


def test_trace_off_scheduling_parity(monkeypatch):
    from nomad_tpu.benchkit import run_tier_placements

    on = run_tier_placements(3, N_NODES, COUNT, SEED, "tpu-binpack")
    tracer._reset_for_tests()
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    off = run_tier_placements(3, N_NODES, COUNT, SEED, "tpu-binpack")
    assert on == off, "tracing kill switch changed placements"
    st = tracer.stats()
    assert st["active"] == 0 and st["retained"] == 0


# ----------------------------------------------------------------------
# Pipelined dispatch (depth > 1): spans must survive crossing the
# pipeline's threads via the explicit ctx handoff in the barrier cells.


def test_pipelined_barrier_spans_reach_every_eval_trace(monkeypatch):
    from nomad_tpu.solver import batch as batch_mod
    from nomad_tpu.solver.batch import SolveBarrier

    # fake lanes/results: nothing for the fixpoint to read
    monkeypatch.setattr(batch_mod, "_cross_lane_fixpoint",
                        lambda lanes, results, ledger: None)

    class Lane:
        def fuse_key(self):
            return ("t",)

    orig = batch_mod.fuse_and_solve
    batch_mod.fuse_and_solve = lambda lanes, use_mesh=True, **kw: [
        ("ok",) for _ in lanes]
    try:
        barrier = SolveBarrier(participants=2, depth=3)
        errs = []

        def eval_thread(k):
            ctx = tracer.begin(f"pipe-{k}")
            try:
                with tracer.activate(ctx):
                    barrier.solve(Lane())
            except Exception as e:  # noqa: BLE001
                errs.append(e)
            finally:
                tracer.end(f"pipe-{k}")

        threads = [threading.Thread(target=eval_thread, args=(k,))
                   for k in range(2)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(20)
        assert not errs, errs
        for k in range(2):
            tr = tracer.get(f"pipe-{k}")
            assert tr is not None, f"pipe-{k} not retained"
            names = {s["name"] for s in tr["spans"]}
            assert "solver.fuse_dispatch" in names, (k, names)
            assert "solver.barrier" in names, (k, names)
            fuse = next(s for s in tr["spans"]
                        if s["name"] == "solver.fuse_dispatch")
            # recorded from the pipeline's in-flight thread, not the
            # eval thread -- the handoff is what's under test
            assert fuse["thread"].startswith("solver-dispatch"), fuse
            assert fuse["tags"]["lanes"] == 2
    finally:
        batch_mod.fuse_and_solve = orig
