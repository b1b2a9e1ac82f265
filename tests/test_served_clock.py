"""The clock inside the served path (ISSUE 25): the program's spans on
the profiler's timeline, the dispatch stage clock, the guard's handoff
timer, the store lock's account, the core GC job's span and the
interpreter heartbeat -- and the guard that keeps the benchmark's
per-layer metric files reading names the program still emits."""
import glob
import json
import os
import re
import sys
import threading
import time
import types

import pytest

from nomad_tpu import lockcheck, mock
from nomad_tpu.server import tracing
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.server.tracing import tracer
from nomad_tpu.solver import constcache, guard, stages, xferobs
from nomad_tpu.state import StateStore
from nomad_tpu.state import storelock

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STAGE_TIMERS = ("nomad.solver.dispatch_prep", "nomad.solver.dispatch_put",
                "nomad.solver.dispatch_launch",
                "nomad.solver.dispatch_fetch")


@pytest.fixture(autouse=True)
def clean():
    def reset():
        guard._reset_for_tests()
        constcache._reset_for_tests()
        xferobs._reset_for_tests()
        tracer._reset_for_tests()
        metrics.reset()
    reset()
    yield
    reset()


class Sink:
    """A span sink that keeps what it is fed; the one that was there
    comes back on exit."""

    def __enter__(self):
        self.seen = []
        self._prev = tracing._SPAN_SINK
        tracing.set_span_sink(
            lambda name, dur_ms: self.seen.append((name, dur_ms)))
        return self

    def __exit__(self, *exc):
        tracing.set_span_sink(self._prev)

    def names(self):
        return [n for n, _ in self.seen]


def build_world(n_nodes=24):
    from nomad_tpu.scheduler import Harness
    h = Harness()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"clock-node-{i:04d}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    return h, nodes


def pack_lane(h, nodes, i, count=4, snapshot=None):
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService
    from nomad_tpu.structs import Plan
    job = mock.job(id=f"clock-job-{i}")
    job.task_groups[0].count = count
    tg = job.task_groups[0]
    plan = Plan(eval_id=f"clock-eval-{i:026d}", priority=50, job=job)
    ctx = EvalContext(snapshot or h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(count)]
    lane = TpuPlacementService(ctx, job, batch_mode=False,
                               spread_alg=False).pack(tg, places, nodes)
    assert lane is not None
    return lane


def wait_until(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {msg}"
        time.sleep(0.01)


def parked(store):
    """A watcher waits in the store's watch registry."""
    return store._watch.parked() > 0


def timers():
    return metrics.snapshot()["samples"]


def counters():
    return metrics.snapshot()["counters"]


# ---------------------------------------------------------------------------
# one clock: a span is a host annotation in the profiler's trace


def host_events(trace_dir):
    from jax.profiler import ProfileData
    files = glob.glob(os.path.join(trace_dir, "plugins", "profile", "*",
                                   "*.xplane.pb"))
    assert files, "the profiler wrote no trace"
    names = {}
    for plane in ProfileData.from_file(files[-1]).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                names.setdefault(ev.name, []).append(
                    (int(ev.start_ns), int(ev.duration_ns)))
    return names


def test_span_is_a_host_annotation_of_its_own_name(tmp_path):
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        with tracer.span("clocktest.outer"):
            with tracer.span("clocktest.inner", lanes=3):
                threading.Event().wait(0.01)    # the span's work
    finally:
        jax.profiler.stop_trace()
    seen = host_events(str(tmp_path))
    assert "clocktest.outer" in seen and "clocktest.inner" in seen
    (o_start, o_dur), = seen["clocktest.outer"]
    (i_start, i_dur), = seen["clocktest.inner"]
    assert i_dur >= 10e6 * 0.9
    assert o_start <= i_start and i_start + i_dur <= o_start + o_dur


def test_no_annotation_when_the_tracer_is_off(monkeypatch):
    made = []
    monkeypatch.setattr(tracing, "_annotation_cls",
                        lambda: made.append(1) or None)
    with tracer.span("clocktest.on"):
        pass
    assert made == [1]
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    with Sink() as sink:
        with tracer.span("clocktest.off"):
            pass
    assert made == [1]
    assert sink.seen == []


def test_span_without_a_context_feeds_the_sink_and_stores_nothing():
    assert tracer.current() is None
    with Sink() as sink:
        with tracer.span("clocktest.nobody"):
            pass
    assert sink.names() == ["clocktest.nobody"]
    assert tracer.stats()["active"] == 0
    assert tracer.stats()["retained"] == 0


# ---------------------------------------------------------------------------
# dispatch stages


@pytest.mark.parametrize("transport", ["wave", "dense", "mesh"])
def test_stage_timers_add_up_to_the_dispatch_timer(transport, monkeypatch):
    """After a fused dispatch at toy size every stage timer has the
    dispatch timer's count, and the stage totals are the dispatch
    timer's total (the stages are contiguous on one clock)."""
    from nomad_tpu.solver.batch import fuse_and_solve
    if transport != "wave":
        from nomad_tpu.solver.service import PackedLane
        monkeypatch.setattr(PackedLane, "_wavefront_check",
                            lambda self: False)
    if transport == "dense":
        monkeypatch.setenv("NOMAD_TPU_MESH", "0")
    h, nodes = build_world()
    lanes = [pack_lane(h, nodes, i) for i in range(8)]
    assert lanes[0].wavefront_ok() == (transport == "wave")
    with Sink() as sink:
        for _ in range(2):          # the second is warm: no compile
            fuse_and_solve(lanes)
    c = counters()
    assert c.get("nomad.solver.mesh_dispatches", 0) == \
        (2 if transport == "mesh" else 0)
    t = timers()
    n = t["nomad.solver.dispatch"]["count"]
    assert n == 2
    total = 0.0
    for name in STAGE_TIMERS + ("nomad.solver.dispatch_unpack",):
        assert t[name]["count"] == n, name
        if name in STAGE_TIMERS:
            total += t[name]["mean_ms"] * n
    whole = t["nomad.solver.dispatch"]["mean_ms"] * n
    assert abs(total - whole) <= 0.05 * whole, (total, whole, t)
    assert t["nomad.solver.fuse"]["count"] == 2
    # each stage is a span too, inside the dispatch span
    got = sink.names()
    for span in ("solver.dispatch", "solver.dispatch_prep",
                 "solver.dispatch_put", "solver.dispatch_launch",
                 "solver.dispatch_fetch", "solver.dispatch_unpack",
                 "solver.fuse"):
        assert got.count(span) == 2, (span, got)


def test_stage_clock_is_off_outside_a_dispatch_and_with_the_tracer(
        monkeypatch):
    stages.mark("put")              # no clock on this thread: a no-op
    assert not set(STAGE_TIMERS) & set(timers())
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    with stages.clock():
        stages.mark("put")
    assert not set(STAGE_TIMERS) & set(timers())


def test_failed_dispatch_samples_no_stage():
    with pytest.raises(RuntimeError):
        with stages.clock():
            stages.mark("put")
            raise RuntimeError("the put failed")
    assert not set(STAGE_TIMERS) & set(timers())
    assert getattr(stages._TLS, "clock", None) is None


# ---------------------------------------------------------------------------
# guard handoff


def test_guard_handoff_once_a_dispatch():
    for _ in range(3):
        assert guard.run_dispatch(lambda: "placed", timeout_s=5.0) == "placed"
    s = timers()["nomad.solver.guard_handoff"]
    assert s["count"] == 3
    assert 0.0 <= s["max_ms"] < 5000.0


def test_guard_handoff_on_the_timeout_path():
    hang = threading.Event()
    try:
        with pytest.raises(guard.DispatchFailed):
            guard.run_dispatch(lambda: hang.wait(30), timeout_s=0.2)
    finally:
        hang.set()
    s = timers()["nomad.solver.guard_handoff"]
    assert s["count"] == 1
    # the runner started at once and never handed back: its side of the
    # handoff is not the 200 ms the caller waited
    assert s["max_ms"] < 150.0


# ---------------------------------------------------------------------------
# the store lock's account


def test_contending_threads_are_charged_by_role_and_holder(monkeypatch):
    store = StateStore()
    assert isinstance(store._lock, storelock.StoreLock)
    holding, release = threading.Event(), threading.Event()
    blocked = threading.Event()

    def clock():                    # first read: the waiter starts to block
        blocked.set()
        return time.perf_counter()
    monkeypatch.setattr(storelock, "perf_counter", clock)

    def snapshot():                 # the holder's note is its caller's name
        with store._lock:
            holding.set()
            release.wait(10)

    holder = threading.Thread(target=snapshot, name="http-holder",
                              daemon=True)
    # a getter that still takes the lock: it walks a live table
    waiter = threading.Thread(target=store.nodes,
                              name="batch-eval-deadbeef", daemon=True)
    holder.start()
    assert holding.wait(10)
    waiter.start()
    assert blocked.wait(10)
    threading.Event().wait(0.05)    # the holder's work
    release.set()
    holder.join(10)
    waiter.join(10)
    assert not holder.is_alive() and not waiter.is_alive()
    c = counters()
    assert c["nomad.state.lock_wait_us.worker"] >= 40_000
    assert c["nomad.state.lock_blocked_by_us.snapshot"] == \
        c["nomad.state.lock_wait_us.worker"]
    assert c["nomad.state.lock_contended"] == 1
    assert c["nomad.state.lock_acquires"] == 2
    assert not any(k.startswith("nomad.state.lock_wait_us.") and
                   not k.endswith(".worker") for k in c)


@pytest.mark.parametrize("thread_name,role", [
    ("batch-eval-0a1b2c3d", "worker"), ("dispatch-solver.batch", "worker"),
    ("solver-dispatch-inflight", "worker"),
    ("Thread-12 (process_request_thread)", "http"),
    ("plan-commit_0", "applier"), ("plan-dispatch", "applier"),
    ("core-gc", "core"), ("fleet-stand-in", "other"),
])
def test_thread_roles(thread_name, role):
    assert storelock.thread_role(thread_name) == role
    assert role in storelock._WAIT_SERIES


def test_uncontended_acquire_reads_no_clock(monkeypatch):
    reads = []

    def clock():
        reads.append(1)
        return time.monotonic()
    monkeypatch.setattr(storelock, "perf_counter", clock)
    store = StateStore()
    for _ in range(300):            # past one flush of the acquire count
        store.nodes()
        store.snapshot()
    store.upsert_node(mock.node())
    assert reads == []
    c = counters()
    assert c["nomad.state.lock_acquires"] >= 512
    assert "nomad.state.lock_contended" not in c


def test_reentrant_acquires_count_once_and_keep_the_outer_holder():
    store = StateStore()
    lock = store._lock

    def outer():
        with lock:
            def inner():
                with lock:
                    return lock._depth, lock._holder
            return inner(), lock._depth
    (depth_in, holder_in), depth_out = outer()
    assert (depth_in, depth_out, lock._depth) == (2, 1, 0)
    assert holder_in == "outer"
    assert lock._n == 1
    assert lock.acquire() and lock.acquire(blocking=False)
    lock.release()
    lock.release()
    assert lock._depth == 0 and lock._n == 2
    # free again: another thread gets it without waiting
    got = []
    t = threading.Thread(target=lambda: got.append(lock.acquire(False)),
                         daemon=True)
    t.start()
    t.join(10)
    assert got == [True]


def test_block_until_wakes_on_a_write_off_the_store_lock():
    """A watcher waits in the watch registry, on a lock of its own: it
    parks, wakes on a write and returns the store's index without one
    acquire of the store's lock, and a writer that holds the store's
    lock meanwhile does not stand in its way."""
    store = StateStore()
    store.upsert_node(mock.node())
    start = store.latest_index()
    acquires = store._lock._n
    out = []

    def watch():
        out.append(store.block_until(start, timeout=10.0))
    t = threading.Thread(target=watch, name="http-watcher", daemon=True)
    t.start()
    wait_until(lambda: parked(store), msg="the watcher to park")
    t0 = time.monotonic()
    store.upsert_node(mock.node())
    with store._lock:               # the watcher returns under a held lock
        t.join(10)
        assert not t.is_alive()
    assert out == [start + 1]
    assert time.monotonic() - t0 < 2.0
    assert store._lock._depth == 0
    # the write and this test's own `with`, nothing of the watcher's
    assert store._lock._n - acquires == 2
    assert store.block_until(start + 1, timeout=0.05) == start + 1
    assert not parked(store)
    c = counters()
    assert c["nomad.state.watch_waits"] == 2
    assert c["nomad.state.watch_wakes"] == 1
    assert "nomad.state.watch_wakes_spurious" not in c
    assert not any(k.startswith("nomad.state.lock_wait_us.") for k in c)


def test_raw_rlock_when_the_tracer_is_off(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    store = StateStore()
    assert type(store._lock) is type(threading.RLock())
    store.upsert_node(mock.node())
    assert store.block_until(0, timeout=0.05) == store.latest_index()
    assert not any(k.startswith("nomad.state.lock_") for k in counters())


def test_lockcheck_and_the_account_stack():
    """Under the lock-order sanitizer the store's lock is the account
    over lockcheck's wrapper over the RLock: both keep working, the
    sanitizer's witness sites still name the store's methods, and a
    watcher parked in the watch registry (whose lock the sanitizer
    instruments too) is not reported as holding a lock it waits on."""
    was = lockcheck.enabled()
    lockcheck.enable()
    try:
        store = StateStore()
        assert isinstance(store._lock, storelock.StoreLock)
        assert isinstance(store._lock._inner, lockcheck._LockWrapper)
        assert isinstance(store._watch._lock, lockcheck._LockWrapper)
        start = store.latest_index()
        seen = []
        real_record = lockcheck._record_acquire

        def record(w, bare, frame):
            if w is store._lock._inner:
                seen.append(frame.f_code.co_filename)
            return real_record(w, bare, frame)
        lockcheck._record_acquire = record
        try:
            t = threading.Thread(
                target=lambda: store.block_until(start, timeout=10.0),
                daemon=True)
            t.start()
            wait_until(lambda: parked(store), msg="the watcher to park")
            # past NOMAD_TPU_LOCKCHECK_WAIT_MS
            threading.Event().wait(0.25)
            store.upsert_node(mock.node())
            t.join(10)
            assert not t.is_alive()
        finally:
            lockcheck._record_acquire = real_record
        assert seen and not any(f.endswith("storelock.py") for f in seen)
        rep = lockcheck.state()
        mine = [r for kind in ("cycles", "held_across", "escaped")
                for r in rep.get(kind, ())
                if "storelock" in json.dumps(r, default=str)
                or "watch.py" in json.dumps(r, default=str)]
        assert mine == []
        # the one order there is: store, then watch
        assert any(w["from"].startswith("nomad_tpu/state/storelock.py")
                   and w["to"].startswith("nomad_tpu/state/watch.py")
                   for w in lockcheck._edge_wit.values())
        assert store._lock._depth == 0
    finally:
        if not was:
            lockcheck.disable()
            lockcheck._reset_for_tests()


# ---------------------------------------------------------------------------
# the core GC job, the heartbeat, the supervisor


@pytest.fixture
def server():
    from nomad_tpu.server import Server
    s = Server(num_workers=1)
    s.start()
    try:
        yield s
    finally:
        s.shutdown()


def test_core_gc_reaches_sink_timer_and_active_traces(server):
    ctx = tracer.begin("eval-in-flight-during-gc", job="j")
    assert ctx is not None
    with Sink() as sink:
        out = server.run_gc_once()
    assert "evals" in out
    assert "core.gc" in sink.names()
    assert timers()["nomad.core.gc"]["count"] == 1
    assert "nomad.core.gc_evals_scanned" in counters() or \
        not server.state.evals()
    spans = tracer.get("eval-in-flight-during-gc")["spans"]
    stalls = [s for s in spans if s["name"] == "core.gc"]
    assert len(stalls) == 1 and stalls[0]["tags"]["dur_ms"] >= 0.0
    tracer.end("eval-in-flight-during-gc")


def test_core_gc_counts_what_it_scans(server):
    for _ in range(4):
        server.register_node(mock.node())
    job = mock.job()
    job.task_groups[0].count = 2
    server.register_job(job)
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        evs = server.state.evals()
        if evs and all(e.terminal_status() for e in evs) and \
                len(server.state.allocs()) == 2:
            break
        time.sleep(0.05)
    evs = server.state.evals()
    assert evs and all(e.terminal_status() for e in evs)
    server.run_gc_once()
    c = counters()
    assert c["nomad.core.gc_evals_scanned"] == len(evs)
    # the placing eval's two allocs off the by-eval index, then the
    # whole table once for the sweep
    assert c["nomad.core.gc_allocs_scanned"] == 2 + 2
    # the eval's status update carried the index of its snapshot
    placed = [e for e in evs if e.triggered_by == "job-register"]
    assert placed and all(0 < e.snapshot_index <= e.modify_index
                          for e in placed)
    assert timers()["nomad.worker.invoke_register"]["count"] == len(placed)
    assert c["nomad.scheduler.register_evals"] == len(placed)
    assert c["nomad.scheduler.register_attempts"] >= len(placed)


def test_sched_lag_thread_starts_and_stops_with_the_server():
    from nomad_tpu.server import Server

    def lag_threads():
        return [t for t in threading.enumerate() if t.name == "sched-lag"]
    before = len(lag_threads())
    s = Server(num_workers=1)
    s.start()
    try:
        assert len(lag_threads()) == before + 1
        deadline = time.monotonic() + 5
        while time.monotonic() < deadline and \
                timers().get("nomad.runtime.sched_lag",
                             {"count": 0})["count"] < 3:
            time.sleep(0.05)
        assert timers()["nomad.runtime.sched_lag"]["count"] >= 3
    finally:
        s.shutdown()
    deadline = time.monotonic() + 5
    while time.monotonic() < deadline and len(lag_threads()) > before:
        time.sleep(0.05)
    assert len(lag_threads()) == before


def test_no_sched_lag_thread_when_the_tracer_is_off(monkeypatch):
    from nomad_tpu.server import Server
    monkeypatch.setenv("NOMAD_TPU_TRACE", "0")
    s = Server(num_workers=1)
    s.start()
    try:
        assert "sched-lag" not in [t.name for t in s._threads]
    finally:
        s.shutdown()


def test_supervisor_survives_a_half_imported_guard(server, monkeypatch):
    """sys.modules can hold nomad_tpu.solver.guard while another thread
    is still importing it: no last_compile_activity yet."""
    monkeypatch.setitem(sys.modules, "nomad_tpu.solver.guard",
                        types.ModuleType("nomad_tpu.solver.guard"))
    server.supervisor._check_once()


def test_pack_usage_ahead_is_sampled_by_the_live_fold():
    h, nodes = build_world()
    pack_lane(h, nodes, 0)
    g = metrics.snapshot()["gauges"]["nomad.solver.pack_usage_ahead"]
    assert g["count"] == 1 and g["max"] == 0.0
    snap = h.state.snapshot()
    # an alloc write after the snapshot: the fold is that much ahead
    h.state.upsert_allocs([mock.alloc_for(mock.job(), nodes[0])])
    pack_lane(h, nodes, 1, snapshot=snap)
    g = metrics.snapshot()["gauges"]["nomad.solver.pack_usage_ahead"]
    assert g["count"] == 2
    assert g["max"] == h.state.latest_index() - snap.index > 0


# ---------------------------------------------------------------------------
# the benchmark's per-layer metric files read names the program emits


def _names_read(spec: dict):
    args = spec.get("args", {})
    for key in ("timers", "spans", "counters", "per"):
        yield from args.get(key, ())
    for key in ("gauge", "counter", "per_timer", "lanes_gauge"):
        if isinstance(args.get(key), str):
            yield args[key]


def test_layer_metric_files_read_names_the_program_emits():
    """A rename in the program must not silently turn a per_layer
    column to null: every timer, span, counter or gauge a file under
    perfbench/layer_metrics/ reads is a string literal somewhere under
    nomad_tpu/."""
    literals = set()
    for path in glob.glob(os.path.join(ROOT, "nomad_tpu", "**", "*.py"),
                          recursive=True):
        with open(path, encoding="utf-8") as f:
            literals.update(re.findall(r'"([A-Za-z0-9_.]+)"', f.read()))
    files = glob.glob(os.path.join(ROOT, "perfbench", "layer_metrics",
                                   "*.json"))
    assert len(files) >= 50
    missing = {}
    for path in files:
        with open(path) as f:
            spec = json.load(f)
        for name in _names_read(spec):
            # an f-string site: nomad.worker.invoke_scheduler_{type}
            if name not in literals:
                missing.setdefault(os.path.basename(path), []).append(name)
    assert missing == {}
