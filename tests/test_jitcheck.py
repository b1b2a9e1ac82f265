"""Dispatch-discipline sanitizer tests (ISSUE 10 tentpole): the
kill-switch path must be a true no-op (jax entry points untouched, no
wrapper observable), enabled solves must be bit-for-bit identical to
disabled ones, and each detector -- steady-state retrace, hot-path
host sync, dtype drift, fingerprint-cache mutation, frozen-memo
invariant -- must fire on a seeded violation.  The sanitizer itself
runs over the dispatch-pipeline / lpq / solver-parity suites (plus the
multichip dryrun) via the conftest fixture; these tests pin its own
semantics.
"""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu import jitcheck, mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import AllocPlaceResult
from nomad_tpu.solver import batch as batch_mod
from nomad_tpu.solver.service import TpuPlacementService, dispatch_lane
from nomad_tpu.structs import Plan
from nomad_tpu.tensor import pack as tpack


@pytest.fixture(autouse=True)
def _clean_checker():
    """Every test leaves the real jax entry points restored and the
    checker state empty, pass or fail."""
    yield
    jitcheck.disable()
    jitcheck._reset_for_tests()
    tpack._reset_pack_caches_for_tests()
    batch_mod.arena_clear("jitcheck test teardown")


def _build_lane(i=0, n_nodes=8, count=4):
    h = Harness()
    nodes = []
    for k in range(n_nodes):
        n = mock.node()
        n.id = f"jc-node-{k:04d}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    job = mock.job(id=f"jc-job-{i}")
    job.task_groups[0].count = count
    tg = job.task_groups[0]
    plan = Plan(eval_id=f"jc-eval-{i:029d}", priority=50, job=job)
    ctx = EvalContext(h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(count)]
    svc = TpuPlacementService(ctx, job, batch_mode=False,
                              spread_alg=False)
    return svc.pack(tg, places, nodes)


# ----------------------------------------------------------------------
# kill switch + parity


def test_killswitch_is_inert(monkeypatch):
    """NOMAD_TPU_JITCHECK=0 (or unset) is a true no-op: jax.jit and
    the array conversion dunders are the originals and no wrapper is
    observable."""
    monkeypatch.setenv("NOMAD_TPU_JITCHECK", "0")
    jit_before = jax.jit
    get_before = jax.device_get
    jitcheck.maybe_install_from_env()
    assert not jitcheck.enabled()
    assert jax.jit is jit_before
    assert jax.device_get is get_before
    f = jax.jit(lambda x: x + 1)
    assert type(f).__name__ != "_JitWrapper"
    st = jitcheck.state()
    assert st["enabled"] is False and st["jits"] == 0


def test_env_knob_installs(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_JITCHECK", "1")
    jit_before = jax.jit
    jitcheck.maybe_install_from_env()
    assert jitcheck.enabled()
    f = jax.jit(lambda x: x + 1)
    assert type(f).__name__ == "_JitWrapper"
    jitcheck.disable()
    assert jax.jit is jit_before
    # wrappers created while enabled keep working, inert
    np.testing.assert_array_equal(np.asarray(f(jnp.ones(2))),
                                  np.asarray([2.0, 2.0]))


def test_enabled_solve_is_bitwise_identical():
    """The acceptance parity gate: the same fused solve with the
    sanitizer recording must return bit-for-bit what the raw path
    returns (wrappers only observe; they never touch values)."""
    lane_off = _build_lane(i=0)
    off = dispatch_lane(lane_off)
    jitcheck.enable()
    try:
        lane_on = _build_lane(i=0)
        on = dispatch_lane(lane_on)
        st = jitcheck.state()
    finally:
        jitcheck.disable()
    for a, b in zip(off, on):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    assert st["retraces"] == [] and st["host_syncs"] == []


# ----------------------------------------------------------------------
# steady-state retraces


def test_nested_jit_per_call_is_a_retrace():
    """THE bug class: a fresh @jax.jit closure per call defeats the
    compile cache -- same abstract signature traced every call. The
    report carries the witness signature pair and the count."""
    from nomad_tpu.server.telemetry import metrics
    metrics.reset()
    jitcheck.enable()

    def bad(x):
        g = jax.jit(lambda y: y + 1)
        return g(x)

    for _ in range(3):
        bad(jnp.ones(4))
    st = jitcheck.state()
    assert st["retrace_count"] == 1
    rep = st["retraces"][0]
    assert rep["count"] == 3
    assert rep["witness"]["new"] == rep["signature"]
    assert "test_jitcheck.py" in rep["site"]
    assert metrics.snapshot()["counters"].get(
        "nomad.jitcheck.retrace", 0) >= 1
    metrics.reset()


def test_lru_factory_holds_one_trace_per_bucket():
    """The satellite fix pattern: an lru_cache'd shape-bucket factory
    constructs each program once -- steady state holds exactly one
    trace per bucket and repeated calls hit the compile cache."""
    jitcheck.enable()

    @functools.lru_cache(maxsize=None)
    def program(n_pad, scale):
        return jax.jit(lambda x: x * scale)

    for _ in range(3):
        program(4, 2.0)(jnp.ones(4))
    for _ in range(3):
        program(8, 2.0)(jnp.ones(8))
    # a second STATIC variant at the same site with the same shapes
    # must not read as a retrace (distinct closure fingerprint)
    for _ in range(3):
        program(4, 3.0)(jnp.ones(4))
    st = jitcheck.state(sites=True)
    assert st["retrace_count"] == 0, st["retraces"]
    assert st["traces"] == 3
    site = [s for s in st["sites"] if "test_jitcheck" in s["site"]][0]
    assert site["steady"] is True and site["jits"] == 3


def test_real_fused_factory_steady_state(monkeypatch):
    """The hoisted binpack factories under the checker: dispatching
    the same lane shape twice compiles once; a second shape bucket
    adds exactly one trace and no retrace."""
    from nomad_tpu.solver import binpack
    # rebuild the bucket programs under the checker (entries built by
    # earlier tests pre-enable are raw -- the documented gap)
    binpack._make_fused_fn.cache_clear()
    binpack._wave_compact_program.cache_clear()
    binpack._wave_preempt_program.cache_clear()
    jitcheck.enable()
    dispatch_lane(_build_lane(i=1))
    st1 = jitcheck.state()
    assert st1["traces"] >= 1
    dispatch_lane(_build_lane(i=2))           # same shapes, warm
    st2 = jitcheck.state()
    assert st2["retrace_count"] == 0, st2["retraces"]
    assert st2["traces"] == st1["traces"]
    # a new placement bucket (p_pad 32 -> 64) is a fresh program: one
    # more trace, still no retrace
    dispatch_lane(_build_lane(i=3, count=40))
    st3 = jitcheck.state()
    assert st3["retrace_count"] == 0, st3["retraces"]
    assert st3["traces"] > st2["traces"]


# ----------------------------------------------------------------------
# hot-path host syncs


def test_hot_path_host_sync_detected_and_attributed():
    from nomad_tpu.solver import guard
    jitcheck.enable()

    def syncs():
        return float(jnp.float32(3.25))

    assert guard.run_dispatch(syncs, label="solver.test",
                              timeout_s=5.0) == 3.25
    st = jitcheck.state()
    assert st["host_sync_count"] == 1
    rep = st["host_syncs"][0]
    assert rep["kind"] == "__float__"
    assert rep["label"] == "solver.test"
    assert "test_jitcheck.py" in rep["site"]


def test_sanctioned_fetch_is_not_a_violation():
    from nomad_tpu.solver import guard
    jitcheck.enable()

    def fetches():
        out = jnp.ones(8) * 2
        with jitcheck.sanctioned_fetch():
            return jax.device_get(out)

    res = guard.run_dispatch(fetches, timeout_s=5.0)
    np.testing.assert_array_equal(res, np.full(8, 2.0))
    st = jitcheck.state()
    assert st["host_sync_count"] == 0
    assert st["sanctioned_fetches"] >= 1


def test_cold_sync_outside_dispatch_is_not_hot():
    jitcheck.enable()
    _ = float(jnp.float32(1.0))       # no dispatch region active
    assert jitcheck.state()["host_sync_count"] == 0


# ----------------------------------------------------------------------
# dtype drift


def test_x64_leak_flagged_when_forced(monkeypatch):
    from nomad_tpu.server.telemetry import metrics
    metrics.reset()
    monkeypatch.setenv("NOMAD_TPU_JITCHECK_X64", "1")
    jitcheck.enable()
    jax.device_put(np.ones(4, dtype=np.float64))
    st = jitcheck.state()
    assert st["x64_leak_count"] == 1
    assert st["dtype_drift"][0]["kind"] == "float64"
    assert metrics.snapshot()["counters"].get(
        "nomad.jitcheck.x64_leak", 0) >= 1
    metrics.reset()


def test_x64_auto_mode_respects_enabled_x64(monkeypatch):
    """conftest enables x64 for CPU parity: float64 there is the
    configured compute dtype, not a leak."""
    monkeypatch.setenv("NOMAD_TPU_JITCHECK_X64", "auto")
    jitcheck.enable()
    assert jax.config.jax_enable_x64
    jax.device_put(np.ones(4, dtype=np.float64))
    assert jitcheck.state()["x64_leak_count"] == 0


def test_weak_scalar_arg_reported():
    jitcheck.enable()
    f = jax.jit(lambda x: x * 2)
    f(2.5)                           # python float -> weak f32 tracer
    st = jitcheck.state()
    assert any(d["kind"] == "weak-scalar" for d in st["dtype_drift"])


# ----------------------------------------------------------------------
# fingerprint-cache mutation + frozen-memo invariant


def test_fingerprint_mutation_detected():
    from nomad_tpu.server.telemetry import metrics
    metrics.reset()
    jitcheck.enable()
    a = np.arange(16, dtype=np.float32)
    jitcheck.note_fingerprint(a)
    assert jitcheck.verify_caches() == 0
    a[3] = 99.0
    assert jitcheck.verify_caches() == 1
    st = jitcheck.state()
    assert any(m["kind"] == "content-mutation" for m in st["mutations"])
    assert metrics.snapshot()["counters"].get(
        "nomad.jitcheck.mutated_cache", 0) >= 1
    metrics.reset()


def test_constcache_sources_register_and_freeze(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_CONST_CACHE_MIN_BYTES", "1")
    from nomad_tpu.solver import constcache
    constcache._reset_for_tests()
    jitcheck.enable()
    src = np.arange(64, dtype=np.float32)
    bufs, _ = constcache.device_put_cached([src])
    assert not src.flags.writeable
    with pytest.raises(ValueError):
        src[0] = 1.0
    constcache._reset_for_tests()


def test_frozen_memo_mutation_raises():
    """Satellite regression gate: mutating an array that entered a
    pack memo raises instead of silently corrupting a shared
    snapshot view."""
    h = Harness()
    nodes = []
    for k in range(4):
        n = mock.node()
        n.id = f"jcf-node-{k:04d}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    matrix = tpack.pack_nodes_cached(nodes, 11)
    for arr in (matrix.cpu_cap, matrix.mem_cap, matrix.disk_cap,
                matrix.dyn_free, matrix.valid):
        assert not arr.flags.writeable
    with pytest.raises(ValueError):
        matrix.cpu_cap[0] = 1.0
    # uncached packs stay writable (nothing shares them)
    loose = tpack.pack_nodes(nodes)
    assert loose.cpu_cap.flags.writeable


def test_arena_pool_buffers_freeze_on_release():
    specs = {"t": [((4, 8), np.float32)]}
    ent, reused = batch_mod._ARENA.acquire(("jck", 4, 8), specs)
    arr = ent.trees["t"][0]
    arr[:] = 1.0                      # checked out: writable
    batch_mod._ARENA.release(ent)
    with pytest.raises(ValueError):
        arr[:] = 2.0                  # pooled: frozen
    ent2, reused2 = batch_mod._ARENA.acquire(("jck", 4, 8), specs)
    assert reused2 and ent2 is ent
    ent2.trees["t"][0][:] = 3.0       # re-acquired: thawed
    batch_mod._ARENA.release(ent2)


def test_usage_base_memo_is_frozen():
    lane = _build_lane(i=7)
    base_ent = getattr(lane.matrix, "_usage_base", None)
    if base_ent is not None:          # delta path on: memo attached
        base = base_ent[2]
        for k in ("used_cpu", "used_mem", "used_disk", "dyn_used"):
            assert not base[k].flags.writeable


# ----------------------------------------------------------------------
# surfaces


def test_agent_self_and_operator_cli_surface(capsys):
    """stats.jitcheck rides /v1/agent/self; `operator jitcheck`
    renders it and exits 1 when steady-state retraces exist."""
    from nomad_tpu import cli
    from nomad_tpu.api.client import ApiClient
    from nomad_tpu.api.http import HttpServer
    from nomad_tpu.server import Server

    server = Server(num_workers=0, heartbeat_ttl=30.0)
    server.start()
    http = HttpServer(server, port=0)
    http.start()
    base = f"http://127.0.0.1:{http.port}"
    try:
        st = ApiClient(base).get("/v1/agent/self")["stats"]["jitcheck"]
        assert st["enabled"] is False and st["retraces"] == []

        assert cli.main(["-address", base,
                         "operator", "jitcheck"]) == 0
        assert "enabled" in capsys.readouterr().out

        jitcheck.enable()

        def bad(x):
            g = jax.jit(lambda y: y - 1)
            return g(x)

        bad(jnp.ones(3))
        bad(jnp.ones(3))
        rc = cli.main(["-address", base,
                       "operator", "jitcheck", "--sites"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "RETRACE 0" in out and "test_jitcheck.py" in out
        assert "site " in out        # --sites table rendered
    finally:
        http.shutdown()
        server.shutdown()
