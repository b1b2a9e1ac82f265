"""Accelerator backend guard: a wedged runtime (PJRT init or a dispatch
hanging) must degrade scheduling to the host oracle instead of stranding
worker threads at pending evals -- and every check and recovery runs
in-process, because the attached device belongs to this process alone."""
import subprocess
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.solver import guard
from nomad_tpu.structs import SchedulerConfiguration


@pytest.fixture(autouse=True)
def restore_guard():
    yield
    guard._reset_for_tests()


class _FakeDevice:
    platform = "tpu"
    device_kind = "fake v0"


def _fake_jax(devices_fn):
    """A stand-in ``jax`` module whose backend init is ``devices_fn``."""
    class FakeJax:
        devices = staticmethod(devices_fn)
    return FakeJax


@pytest.fixture
def no_children(monkeypatch):
    """The device belongs to one process: any child the guard started
    could not open it. Fail the test if one is spawned."""
    def refuse(*a, **kw):
        raise AssertionError("guard started a child process")
    monkeypatch.setattr(subprocess, "Popen", refuse)


def test_guard_times_out_on_hung_init(monkeypatch):
    guard._reset_for_tests()

    def hung():
        time.sleep(60)

    import sys
    monkeypatch.setitem(sys.modules, "jax", _fake_jax(hung))
    t0 = time.time()
    assert guard.backend_available(timeout_s=0.3) is False
    assert time.time() - t0 < 2.0
    # pinned for the process lifetime, no re-probe
    t0 = time.time()
    assert guard.backend_available(timeout_s=60.0) is False
    assert time.time() - t0 < 0.1


def test_scheduling_falls_back_to_host_when_backend_dead(monkeypatch):
    guard._reset_for_tests()
    guard._STATE.update(checked=True, ok=False)
    metrics.reset()
    server = Server(num_workers=1, heartbeat_ttl=30.0)
    server.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-binpack"))
    server.start()
    try:
        from nomad_tpu.client import SimClient
        client = SimClient(server, mock.node())
        client.start()
        job = mock.job(id="guard-job")
        job.task_groups[0].count = 2
        server.register_job(job)
        deadline = time.time() + 10
        while time.time() < deadline:
            allocs = [a for a in server.state.allocs_by_job(
                "default", "guard-job") if a.desired_status == "run"]
            if len(allocs) == 2:
                break
            time.sleep(0.05)
        assert len(allocs) == 2, "host fallback must still place"
        snap = metrics.snapshot()["counters"]
        assert snap.get("nomad.scheduler.placements_tpu", 0) == 0
    finally:
        server.shutdown()


def test_guard_passes_on_live_backend():
    guard._reset_for_tests()
    # the CPU backend in CI initializes instantly
    assert guard.backend_available(timeout_s=30.0) is True
    # ...and the guard says WHICH backend came up: JAX falls back to CPU
    # without raising, so "init returned" alone proves nothing
    assert guard.state()["device"] == {
        "platform": "cpu", "kind": "cpu", "count": 8}


def test_degrade_observe_reprobe_recover(monkeypatch, no_children):
    """The full operator loop: a hung init degrades the guard; the
    degradation is observable; a reprobe while it hangs neither blocks
    nor flips the guard; a reprobe after the init thread completes late
    RECOVERS the process without a restart, verified by a real dispatch
    on the device this process holds."""
    import sys

    import jax as real_jax

    guard._reset_for_tests()
    metrics.reset()
    release = threading.Event()

    def slow():
        release.wait(30)
        return [_FakeDevice()] * 8

    monkeypatch.setitem(sys.modules, "jax", _fake_jax(slow))
    # degrade: the probe times out while init hangs
    assert guard.backend_available(timeout_s=0.2) is False
    guard.note_host_fallback()
    guard.note_host_fallback()

    # observe: state reports the degradation and the fallback count
    st = guard.state()
    assert st["checked"] and not st["ok"]
    assert st["probe_timed_out"] is True
    assert st["host_fallback_dispatches"] == 2
    assert st["backend_unavailable_total"] == 1

    # init stays hung: a reprobe must NOT hang, must not dispatch into
    # the hung runtime, and names the one remedy left
    t0 = time.time()
    rep = guard.reprobe(timeout_s=1.0)
    assert time.time() - t0 < 1.0
    assert rep["recovered"] is False
    assert rep["init_hung"] is True
    assert rep["dispatch"] is None
    assert guard.state()["ok"] is False

    # the leaked init thread finishes late
    release.set()
    deadline = time.time() + 5
    while time.time() < deadline:
        if guard._PROBE["done"].is_set():
            break
        time.sleep(0.01)
    monkeypatch.setitem(sys.modules, "jax", real_jax)
    rep = guard.reprobe(timeout_s=30.0)
    assert rep["recovered"] is True
    assert rep["init_hung"] is False
    assert rep["dispatch"]["ok"] is True
    assert guard.backend_available() is True
    st = guard.state()
    assert st["ok"] and st["recovered_late"]
    assert st["recovered_total"] == 1
    assert st["device"] == {"platform": "tpu", "kind": "fake v0",
                            "count": 8}


def test_reprobe_before_first_check_runs_inprocess_probe():
    """reprobe() on a never-consulted guard takes the normal timed first
    probe (an unguarded first jax init is the hang the guard exists to
    prevent)."""
    guard._reset_for_tests()
    rep = guard.reprobe(timeout_s=30.0)
    assert rep["recovered"] is False
    assert rep["dispatch"] is None
    # CPU backend in CI initializes fine
    assert rep["first_probe_ok"] is True
    assert rep["state"]["checked"] is True and rep["state"]["ok"] is True
    assert guard.state()["last_reprobe"] is not None


def test_reprobe_late_recovery_resets_breaker(monkeypatch, no_children):
    """The leaked init thread finished with live devices after the first
    probe timed out; reprobe flips the guard, proves the device with a
    dispatch and resets the dispatch breaker -- all in this process."""
    guard._reset_for_tests()
    guard._STATE.update(probe_timed_out=True)
    with guard._LOCK:
        guard._set_flags_locked(True, False)
    done = threading.Event()
    done.set()
    guard._PROBE["done"] = done
    guard._PROBE["result"] = {"n": 4, "device": None}
    # a wedged round also tripped the breaker; recovery must clear it
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "30")
    for _ in range(guard._breaker_threshold()):
        guard.record_dispatch_failure("timeout")
    assert guard.breaker_state()["state"] == guard.BREAKER_OPEN

    rep = guard.reprobe(timeout_s=30.0)
    assert rep["recovered"] is True
    assert rep["dispatch"]["ok"] is True
    assert guard.backend_available() is True
    assert guard.breaker_state()["state"] == guard.BREAKER_CLOSED
    assert guard.state()["degraded"] is False


def test_reprobe_leaves_breaker_open_when_the_device_is_hung(monkeypatch):
    """Init is fine but the device no longer answers: the probe dispatch
    is abandoned at its deadline and the breaker stays open."""
    guard._reset_for_tests()
    assert guard.backend_available(timeout_s=30.0) is True
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "30")
    for _ in range(guard._breaker_threshold()):
        guard.record_dispatch_failure("timeout")
    hang = threading.Event()
    monkeypatch.setattr(guard, "_probe_program",
                        lambda: lambda x: hang.wait(60))
    t0 = time.time()
    rep = guard.reprobe(timeout_s=0.3)
    assert time.time() - t0 < 5.0
    assert rep["dispatch"]["timed_out"] is True
    assert rep["dispatch"]["ok"] is False
    assert guard.breaker_state()["state"] == guard.BREAKER_OPEN
    hang.set()


def test_probe_dispatch_checks_the_answer(monkeypatch):
    """A device that returns, but returns garbage, is not healthy."""
    assert guard._probe_dispatch(30.0)["ok"] is True
    monkeypatch.setattr(guard, "_probe_program", lambda: lambda x: x)
    rep = guard._probe_dispatch(30.0)
    assert rep["ok"] is False and rep["timed_out"] is False

    def boom(x):
        raise RuntimeError("device lost")
    monkeypatch.setattr(guard, "_probe_program", lambda: boom)
    rep = guard._probe_dispatch(30.0)
    assert rep["ok"] is False and "device lost" in rep["error"]


def test_open_breaker_closes_in_process(monkeypatch, no_children):
    """The acceptance drill at unit size: injected dispatch failures
    open the breaker, the background loop probes the device THIS process
    holds, and the breaker closes with no child and no operator."""
    from nomad_tpu.faultinject import faults

    guard._reset_for_tests()
    faults._reset_for_tests()
    assert guard.dispatch_allowed() is True
    monkeypatch.setenv("NOMAD_TPU_BREAKER_BACKOFF", "0.05")
    faults.arm("solver.dispatch", "error", count=3)
    try:
        for _ in range(3):
            with pytest.raises(guard.DispatchFailed):
                guard.run_dispatch(lambda: 1)
        assert guard.breaker_state()["trips"] == 1
        deadline = time.time() + 10
        while time.time() < deadline:
            if guard.breaker_state()["state"] == guard.BREAKER_CLOSED:
                break
            time.sleep(0.02)
        br = guard.breaker_state()
        assert br["state"] == guard.BREAKER_CLOSED
        assert br["recoveries"] == 1
        assert br["last_probe"]["report"]["dispatch"]["ok"] is True
        assert guard.run_dispatch(lambda: 7) == 7
    finally:
        faults._reset_for_tests()


# ----------------------------------------------------------------------
# Compile time is set-up, not a dead device


def _compile_stage(seconds):
    """What JAX does around a trace / lower / backend-compile stage:
    a scalar event at entry, a duration event at exit, on this thread."""
    import jax.monitoring as jm
    jm.record_scalar(guard._BACKEND_COMPILE, time.time())
    time.sleep(seconds)
    jm.record_event_duration_secs(guard._BACKEND_COMPILE, seconds)


def test_watchdog_does_not_charge_compile_time():
    """A cold shape bucket overruns the execution deadline many times
    over and still lands: no timeout, no breaker pressure."""
    metrics.reset()
    before = guard.compile_stats()

    def cold_dispatch():
        _compile_stage(0.9)
        return "placed"

    assert guard.run_dispatch(cold_dispatch, timeout_s=0.2) == "placed"
    st = guard.state()
    assert st["dispatch"]["timeout"] == 0 and st["dispatch"]["ok"] == 1
    assert st["breaker"]["consecutive_failures"] == 0
    after = guard.compile_stats()
    assert after["backend_compiles"] == before["backend_compiles"] + 1
    assert after["seconds"] - before["seconds"] >= 0.9
    assert after["in_progress"] == 0


def test_watchdog_still_bounds_execution_after_a_compile():
    metrics.reset()
    hang = threading.Event()

    def compile_then_hang():
        _compile_stage(0.3)
        hang.wait(60)

    t0 = time.time()
    with pytest.raises(guard.DispatchFailed) as ei:
        guard.run_dispatch(compile_then_hang, timeout_s=0.3)
    assert time.time() - t0 < 5.0
    assert ei.value.kind == "timeout" and "0.3s deadline" in str(ei.value)
    assert guard.state()["dispatch"]["timeout"] == 1
    hang.set()


def test_compile_stage_has_a_deadline_of_its_own(monkeypatch):
    monkeypatch.setattr(guard, "COMPILE_DEADLINE_S", 0.3)
    t0 = time.time()
    with pytest.raises(guard.DispatchFailed) as ei:
        guard.run_dispatch(lambda: _compile_stage(5.0), timeout_s=30.0)
    assert time.time() - t0 < 3.0
    assert "compile deadline" in str(ei.value)


def test_real_jit_compile_is_seen_and_excuses_a_waiting_worker():
    """Pins the jax.monitoring event names to the installed JAX: a real
    compile inside a dispatch must move the compile clock, and while it
    runs the worker supervisor's stall clock reads 'now'."""
    import jax
    import jax.numpy as jnp

    seen = []

    @jax.jit
    def fresh(x):
        seen.append(guard.last_compile_activity())    # runs while tracing
        return jnp.cumsum(x * 3 + 1)

    before = guard.compile_stats()
    t0 = time.monotonic()
    out = guard.run_dispatch(lambda: int(fresh(jnp.arange(8))[-1]))
    assert out == 92
    after = guard.compile_stats()
    assert after["backend_compiles"] > before["backend_compiles"]
    assert after["seconds"] > before["seconds"]
    assert seen and seen[0] >= t0
    assert guard.last_compile_activity() >= seen[0]


def test_worker_waiting_on_a_compile_is_not_wedged(monkeypatch):
    """The stall clock restarts at every compile-stage edge
    (guard.last_compile_activity): a worker whose dispatch sits in a
    cold XLA compile past NOMAD_TPU_WORKER_STALL_S is slow, not wedged.
    Once the compile is over and the stall window passes with no
    progress, it is wedged like any other."""
    import jax.monitoring as jm

    from nomad_tpu.solver import guard

    monkeypatch.setenv("NOMAD_TPU_WORKER_STALL_S", "0.3")
    monkeypatch.setenv("NOMAD_TPU_WORKER_CHECK_S", "0.05")
    server = Server(num_workers=2, eval_batching=False,
                    heartbeat_ttl=60.0)
    server.start()

    class Stalled(threading.Thread):
        """Worker-shaped: alive, no progress for an hour."""

        def __init__(self):
            super().__init__(daemon=True, name="stalled-standin")
            self.last_progress = time.monotonic() - 3600.0
            self.evals_processed = 0
            self._ev = threading.Event()

        def stop(self):
            self._ev.set()

        def run(self):
            self._ev.wait(60.0)

    standin = Stalled()
    compiling = threading.Event()
    release = threading.Event()

    def compile_stage():
        # what JAX does around a backend compile, on the compiling
        # thread: a scalar event at entry, a duration event at exit
        jm.record_scalar(guard._BACKEND_COMPILE, time.time())
        compiling.set()
        release.wait(30.0)
        jm.record_event_duration_secs(guard._BACKEND_COMPILE, 0.0)

    guard.compile_stats()               # listeners in
    t = threading.Thread(target=compile_stage, daemon=True,
                         name="compile-standin")
    t.start()
    try:
        assert compiling.wait(5.0)
        with server._leader_lock:
            server.workers[0].stop()
            standin.start()
            server.workers[0] = standin
        hold_until = time.time() + 1.0  # > 3 stall windows, mid-compile
        while time.time() < hold_until:
            assert server.supervisor.wedges_detected == 0
            assert server.workers[0] is standin
            time.sleep(0.02)
        release.set()
        t.join(timeout=5.0)
        deadline = time.time() + 10
        while (server.supervisor.wedges_detected < 1
               and time.time() < deadline):
            time.sleep(0.02)
        assert server.supervisor.wedges_detected >= 1, (
            "a stalled worker must still be caught once the compile "
            "is over")
    finally:
        release.set()
        standin.stop()
        server.shutdown()


def test_guard_state_in_agent_self_and_reprobe_endpoint():
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.api.http import HttpServer

    guard._reset_for_tests()
    guard._STATE.update(checked=True, ok=False, probe_timed_out=True)
    server = Server(num_workers=0, heartbeat_ttl=30.0)
    server.start()
    http = HttpServer(server, port=0)
    http.start()
    try:
        api = ApiClient(f"http://127.0.0.1:{http.port}")
        st = api.get("/v1/agent/self")["stats"]["solver_guard"]
        assert st["checked"] is True and st["ok"] is False

        rep = api.post("/v1/operator/solver/reprobe?timeout=1", {})
        assert rep["recovered"] is False
        assert rep["init_hung"] is True and rep["dispatch"] is None
        assert rep["state"]["ok"] is False
    finally:
        http.shutdown()
        server.shutdown()


def test_cli_operator_solver_status_and_reprobe(capsys):
    from nomad_tpu import cli
    from nomad_tpu.api.http import HttpServer

    guard._reset_for_tests()
    guard._STATE.update(checked=True, ok=False, probe_timed_out=True)
    server = Server(num_workers=0, heartbeat_ttl=30.0)
    server.start()
    http = HttpServer(server, port=0)
    http.start()
    try:
        base = f"http://127.0.0.1:{http.port}"
        assert cli.main(["-address", base, "operator", "solver",
                         "status"]) == 0
        out = capsys.readouterr().out
        assert "ok" in out and "= False" in out

        assert cli.main(["-address", base, "operator", "solver",
                         "reprobe"]) == 0
        out = capsys.readouterr().out
        assert "recovered" in out
        assert "restart the agent" in out   # init hung: the one remedy

        # a live guard: the reprobe proves the device with a dispatch
        guard._reset_for_tests()
        assert cli.main(["-address", base, "operator", "solver",
                         "reprobe"]) == 0       # first touch
        assert cli.main(["-address", base, "operator", "solver",
                         "reprobe"]) == 0
        out = capsys.readouterr().out
        assert "probe dispatch     = ok" in out
        assert cli.main(["-address", base, "operator", "solver",
                         "status"]) == 0
        assert "device.platform" in capsys.readouterr().out
    finally:
        http.shutdown()
        server.shutdown()
