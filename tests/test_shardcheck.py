"""Sharding-discipline sanitizer gauntlet (ISSUE 15).

Structure mirrors the sibling sanitizer suites: the kill switch is a
TRUE no-op (module attrs raw, bitwise dispatch parity), every detector
is proven by a seeded violation producing a witness (forced
replication -> spec drift + per-shard byte parity, raw/host puts ->
implicit transfer, planted extra all-gather -> collective excess), and
the HTTP/CLI surfaces mirror the siblings exactly."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu import shardcheck
from nomad_tpu.parallel import mesh as meshmod
from nomad_tpu.solver import xferobs

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

needs_mesh = pytest.mark.skipif(
    jax.device_count() < 8, reason="needs the virtual 8-device mesh")


@pytest.fixture(autouse=True)
def _clean(monkeypatch):
    # the AOT HLO audit doubles a compile per program; individual
    # tests opt back in where the audit is the thing under test
    monkeypatch.setenv("NOMAD_TPU_SHARDCHECK_HLO", "0")
    yield
    shardcheck.disable()
    shardcheck._reset_for_tests()
    xferobs._reset_for_tests()


def _mesh_inputs(E=8, N=64, P=4, dtype="float32"):
    import __graft_entry__ as ge

    c1, i1, b1 = ge._example_inputs(n_nodes=N, n_place=P, dtype=dtype)
    stack = lambda t: jax.tree.map(
        lambda leaf: jnp.broadcast_to(leaf, (E,) + leaf.shape), t)
    return stack(c1), stack(i1), stack(b1)


def _sharded_call(mesh, const, init, batch, dtype="float32"):
    with mesh:
        sc, si, sb = meshmod.shard_solver_inputs(mesh, const, init,
                                                 batch)
        fn = meshmod.mesh_solve_fn(mesh, False, dtype)
        out = fn(sc, si, sb)
    return (np.asarray(out[0]), np.asarray(out[1]),
            np.asarray(out[2])), (sc, si, sb), fn


# ----------------------------------------------------------------------
# kill switch + parity


def test_kill_switch_is_a_true_noop():
    """Default off: the parallel/mesh.py entry points are the raw
    functions (no wrapper observable) and every shardcheck entry
    point is inert."""
    assert not shardcheck.enabled()
    assert "shardcheck" not in repr(meshmod.mesh_solve_fn)
    assert meshmod.shard_solver_inputs.__name__ == \
        "shard_solver_inputs"
    # inert entry points: no state recorded, nothing raises
    shardcheck.audit_group(None, "mesh_const", {}, where="input")
    assert shardcheck.audit_hlo(("f",), "a = all-gather(b)\n") == \
        {"all-gather": 1}
    st = shardcheck.state()
    assert st["enabled"] is False
    assert st["leaves_checked"] == 0
    assert st["baselines"] == {}


def test_env_knob_installs(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_SHARDCHECK", "1")
    shardcheck.maybe_install_from_env()
    assert shardcheck.enabled()
    assert "_patched" in meshmod.mesh_solve_fn.__name__
    shardcheck.disable()
    assert not shardcheck.enabled()
    assert meshmod.mesh_solve_fn.__name__ == "mesh_solve_fn"


@needs_mesh
def test_bitwise_parity_mesh_dispatch():
    """Enabled vs disabled mesh dispatch is bitwise identical: the
    wrapper only observes shardings, never the data."""
    mesh = meshmod.make_mesh(8)
    const, init, batch = _mesh_inputs()
    (off_c, off_s, off_y), _, _ = _sharded_call(mesh, const, init,
                                                batch)
    shardcheck.enable()
    (on_c, on_s, on_y), _, _ = _sharded_call(mesh, const, init, batch)
    st = shardcheck.state()
    assert st["wrapped_dispatches"] == 1
    assert (off_c == on_c).all()
    assert (off_s == on_s).all()
    assert (off_y == on_y).all()
    assert st["spec_drift_count"] == 0
    assert st["implicit_xfer_count"] == 0
    assert st["shard_parity_count"] == 0
    assert xferobs.shard_parity() == 0


@needs_mesh
def test_bitwise_parity_fused_coordinator_dispatch():
    """The real dispatch route (solver/batch.py fuse_and_solve with
    use_mesh=True) under the checker: same results as the unchecked
    run, wrapped dispatches counted, zero violations on the clean
    tree."""
    from nomad_tpu.solver.batch import fuse_and_solve

    class _Lane:
        def __init__(self, c, i, b):
            self.const, self.init, self.batch = c, i, b
            self.ptab = self.pinit = None
            self.dtype_name = "float32"
            self.spread_alg = False

        def fuse_key(self):
            return ("shardcheck-test", self.const.cpu_cap.shape[0],
                    self.batch.ask_cpu.shape[0])

        def wavefront_ok(self):
            return False

    import __graft_entry__ as ge

    rng = np.random.default_rng(7)
    lanes = [ge._varied_inputs(rng, 512, 4) for _ in range(4)]
    mk = lambda: [_Lane(*ln) for ln in lanes]
    off = fuse_and_solve(mk(), use_mesh=True)
    shardcheck.enable()
    on = fuse_and_solve(mk(), use_mesh=True)
    st = shardcheck.state()
    shardcheck.disable()
    assert st["wrapped_dispatches"] >= 1, st
    assert st["sanctioned_puts"] >= 1
    assert st["spec_drift"] == []
    assert st["implicit_xfers"] == []
    assert st["shard_parity_reports"] == []
    for (c0, s0, y0), (c1, s1, y1) in zip(off, on):
        assert (np.asarray(c0) == np.asarray(c1)).all()
        assert (np.asarray(s0) == np.asarray(s1)).all()
        assert (np.asarray(y0) == np.asarray(y1)).all()


# ----------------------------------------------------------------------
# seeded violations, one per detector


@needs_mesh
def test_forced_replication_is_spec_drift_with_amplification():
    """Detector (a): a fleet table declared sharded but actually
    replicated -- every const leaf flagged with the N x-memory
    amplification bytes in the witness, and the telemetry counter
    fires."""
    from jax.sharding import NamedSharding
    from nomad_tpu.server.telemetry import metrics

    metrics.reset()
    mesh = meshmod.make_mesh(8)
    const, init, batch = _mesh_inputs()
    shardcheck.enable()
    with mesh:
        sc, si, sb = meshmod.shard_solver_inputs(mesh, const, init,
                                                 batch)
        # forced replication: re-put the const tree fully replicated
        # (this device_put is the seeded VIOLATION under test; tests/
        # are outside the no-implicit-put lint scope by design)
        repl = jax.tree.map(
            lambda leaf: jax.device_put(leaf, NamedSharding(
                mesh, meshmod.output_partition_specs(leaf))),
            sc)
        fn = meshmod.mesh_solve_fn(mesh, False, "float32")
        fn(repl, si, sb)
    st = shardcheck.state()
    assert st["spec_drift_count"] > 0
    by_field = {r["field"]: r for r in st["spec_drift"]}
    cpu = by_field["cpu_cap"]
    assert cpu["kind"] == "spec-mismatch"
    assert cpu["declared"] == str(("evals", "nodes"))
    assert cpu["actual"] == "()"
    # (8,64) float32 = 2048 bytes over 8 shards: each of 8 devices
    # holds 2048 instead of 256 -- 14336 wasted bytes fleet-wide
    assert cpu["amplification_bytes"] == 8 * (2048 - 256)
    assert "stack" in cpu and cpu["stack"]
    snap = metrics.snapshot()
    assert snap["counters"]["nomad.shardcheck.spec_drift"] >= 1
    # detector (d) sees the same corruption as a per-shard byte
    # parity break in the ledger rows
    assert xferobs.shard_parity() > 0
    assert st["shard_parity_count"] > 0
    pr = st["shard_parity_reports"][0]
    assert pr["actual_per_device"] > pr["declared_per_device"]


@needs_mesh
def test_host_and_raw_put_arrays_are_implicit_transfers():
    """Detector (b): host np.ndarrays and raw-put (single-device)
    arrays entering the mesh callable -- XLA would upload/reshard
    silently; both flagged with bytes + witness."""
    mesh = meshmod.make_mesh(8)
    const, init, batch = _mesh_inputs()
    shardcheck.enable()
    with mesh:
        sc, si, sb = meshmod.shard_solver_inputs(mesh, const, init,
                                                 batch)
        fn = meshmod.mesh_solve_fn(mesh, False, "float32")
        # host numpy batch: never routed through shard_solver_inputs;
        # XLA uploads it silently and the dispatch SUCCEEDS -- exactly
        # why a sanitizer has to flag it
        np_batch = jax.tree.map(np.asarray, batch)
        fn(sc, si, np_batch)
        # uncommitted single-device arrays (a plain jnp build that
        # never went through a sanctioned put): silently resharded,
        # dispatch succeeds, flagged
        fn(sc, init, sb)
        # raw device_put COMMITTED to one device (the classic bypass
        # of the sanctioned transports): jax itself refuses to mix
        # committed placements -- the witness is recorded before the
        # dispatch dies, so the report names the leaf, not just the
        # jax traceback
        raw_init = jax.tree.map(
            lambda leaf: jax.device_put(leaf, jax.devices()[0]), init)
        with pytest.raises(ValueError):
            fn(sc, raw_init, sb)
    st = shardcheck.state()
    kinds = {r["kind"] for r in st["implicit_xfers"]}
    assert "host-array" in kinds, kinds
    assert "SingleDeviceSharding" in kinds, kinds
    host = next(r for r in st["implicit_xfers"]
                if r["kind"] == "host-array")
    assert host["group"] == "mesh_batch"
    assert host["bytes"] > 0 and host["stack"]
    assert st["implicit_xfer_count"] >= 2
    # no false drift reports: the correctly-sharded groups stay clean
    assert all(r["group"] != "mesh_const" for r in st["spec_drift"])


def test_planted_extra_all_gather_is_collective_excess():
    """Detector (c): the first program of a family records the
    sanctioned baseline; a later program with an extra steady-state
    all-gather exceeds it, with the HLO instruction lines as
    witness."""
    shardcheck.enable()
    fam = ("mesh", ("evals", "nodes"), False, "float32")
    base = ("  %r = f32[8] all-reduce(%x), to_apply=%sum\n"
            "  %g = f32[8,64] all-gather(%y), dimensions={1}\n")
    counts = shardcheck.audit_hlo(fam, base, program="baseline")
    assert counts == {"all-reduce": 1, "all-gather": 1}
    st = shardcheck.state()
    assert st["baselines_recorded"] == 1
    assert st["collective_excess_count"] == 0
    # same budget again: async start/done forms count once
    shardcheck.audit_hlo(fam, (
        "  %r = f32[8] all-reduce-start(%x)\n"
        "  %rd = f32[8] all-reduce-done(%r)\n"
        "  %g = f32[8,64] all-gather(%y)\n"), program="steady")
    assert shardcheck.state()["collective_excess_count"] == 0
    # the plant: one extra all-gather over the recorded budget
    shardcheck.audit_hlo(fam, base + (
        "  %g2 = f32[8,64] all-gather(%z), dimensions={1}\n"),
        program="planted")
    st = shardcheck.state()
    assert st["collective_excess_count"] == 1
    r = st["collective_excess"][0]
    assert r["excess"] == {"all-gather": "2 > baseline 1"}
    assert r["program"] == "planted"
    assert any("all-gather" in ln for ln in r["witness_instructions"])
    # a different family records its own baseline, no cross-talk
    shardcheck.audit_hlo(("other",), base + base)
    assert shardcheck.state()["collective_excess_count"] == 1


@needs_mesh
def test_ledger_mismatch_rows_ride_xferobs():
    """Detector (d): the per-shard rows land in the transfer ledger
    under the mesh_* tags and reconcile to zero on a clean dispatch;
    a seeded declared/actual mismatch shows up in shard_parity() and
    the per-shard table."""
    mesh = meshmod.make_mesh(8)
    const, init, batch = _mesh_inputs()
    shardcheck.enable()
    _sharded_call(mesh, const, init, batch)
    snap = xferobs.state()
    assert set(snap["per_shard"]) == {"mesh_const", "mesh_init",
                                      "mesh_batch"}
    rows = snap["per_shard"]["mesh_const"]
    assert len(rows) == 8
    assert all(r["declared_bytes"] == r["actual_bytes"]
               for r in rows.values())
    assert snap["shard_parity_bytes"] == 0
    # seeded ledger mismatch: a transport claims 100 declared bytes
    # the device does not actually hold
    xferobs.note_shard_bytes("mesh_const", "d3", 100, 0)
    assert xferobs.shard_parity() == 100
    assert xferobs.state()["shard_parity_bytes"] == 100


# ----------------------------------------------------------------------
# compile audit (offline)


@needs_mesh
def test_compile_audit_inventories_programs():
    """compile_audit compiles every registered program for the
    8-device mesh with NO server -- both greedy spread variants, the
    LPQ kernel (ISSUE 19) and the delta-scatter program (ISSUE 20) --
    and returns the collective + cost + per-shard-budget inventory."""
    inv = shardcheck.compile_audit(n_devices=8, nodes=64, place=4)
    assert inv["mesh"] == [4, 2]
    assert len(inv["programs"]) == 4
    for p in inv["programs"]:
        assert "audit_error" not in p, p
        if p["program"].startswith("mesh_delta_scatter"):
            continue
        # the cross-shard reduction (select/argmax for greedy, the
        # dual-ascent gather for LPQ) must be visible
        assert p["collectives"], p
    lpq = [p for p in inv["programs"]
           if p["program"].startswith("mesh_lpq")]
    assert len(lpq) == 1
    # the ISSUE-20 delta scatter: replicated (coords, vals) in, each
    # shard keeps the updates landing in its slice -- its sanctioned
    # collective budget is ZERO, so any future regression inserting an
    # all-gather into the promote path trips collective_excess
    ds = [p for p in inv["programs"]
          if p["program"].startswith("mesh_delta_scatter")]
    assert len(ds) == 1
    assert ds[0]["collectives"] == {}
    assert ds[0]["delta_payload_bytes_per_shard"] > 0
    # the LPQ combine is an all-gather by design (a psum would
    # re-associate the load sum and break bit-parity)
    assert lpq[0]["collectives"].get("all-gather")
    assert "all-reduce" not in lpq[0]["collectives"]
    budget = inv["per_shard_budget"]
    # node-sharded const tables: per-shard strictly below total
    assert budget["mesh_const"]["declared_per_shard_bytes"] < \
        budget["mesh_const"]["total_bytes"]
    assert budget["mesh_batch"]["declared_per_shard_bytes"] * 8 <= \
        budget["mesh_batch"]["total_bytes"] * 2
    assert "lpq_in" in budget


def test_compile_audit_refuses_without_devices():
    inv = shardcheck.compile_audit(n_devices=64)
    assert "error" in inv


# ----------------------------------------------------------------------
# HLO audit wired into the wrapped dispatch


@needs_mesh
def test_program_audit_records_baseline_on_dispatch(monkeypatch):
    """With the HLO knob on, a wrapped dispatch AOT-compiles its
    program once, records the family baseline and the per-program
    inventory -- and a second dispatch of the same program does not
    re-audit."""
    monkeypatch.setenv("NOMAD_TPU_SHARDCHECK_HLO", "1")
    mesh = meshmod.make_mesh(8)
    const, init, batch = _mesh_inputs(N=32)
    shardcheck.enable()
    _sharded_call(mesh, const, init, batch)
    st = shardcheck.state(programs=True)
    assert st["programs_audited"] == 1
    assert st["baselines_recorded"] == 1
    assert st["audit_errors"] == 0
    assert len(st["programs"]) == 1
    assert st["programs"][0]["collectives"], st["programs"]
    _sharded_call(mesh, const, init, batch)
    st = shardcheck.state()
    assert st["programs_audited"] == 1
    assert st["collective_excess_count"] == 0


# ----------------------------------------------------------------------
# surfaces


@needs_mesh
def test_agent_self_and_operator_cli_surface(capsys):
    """stats.shardcheck rides /v1/agent/self; `operator shardcheck`
    renders it and exits 1 on spec drift, and `operator sanitizers`
    carries the fifth row."""
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
        st = ApiClient(base).get(
            "/v1/agent/self")["stats"]["shardcheck"]
        assert st["enabled"] is False and st["spec_drift"] == []

        assert cli.main(["-address", base,
                         "operator", "shardcheck"]) == 0
        assert "enabled" in capsys.readouterr().out
        assert cli.main(["-address", base,
                         "operator", "sanitizers"]) == 0
        out = capsys.readouterr().out
        assert "shardcheck" in out and "spec_drift" in out

        # seed a drift, the CLI must exit 1 and print the witness
        from jax.sharding import NamedSharding

        shardcheck.enable()
        mesh = meshmod.make_mesh(8)
        const, init, batch = _mesh_inputs(N=32)
        with mesh:
            sc, si, sb = meshmod.shard_solver_inputs(
                mesh, const, init, batch)
            repl = jax.tree.map(
                lambda leaf: jax.device_put(leaf, NamedSharding(
                    mesh, meshmod.output_partition_specs(leaf))),
                sc)
            meshmod.mesh_solve_fn(mesh, False, "float32")(repl, si, sb)
        rc = cli.main(["-address", base,
                       "operator", "shardcheck", "--stacks"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "SPEC DRIFT 0" in out and "spec-mismatch" in out
        rc = cli.main(["-address", base, "operator", "sanitizers"])
        out = capsys.readouterr().out
        assert rc == 1 and "FAIL" in out
    finally:
        http.shutdown()
        server.shutdown()


@needs_mesh
def test_cli_compile_audit_local(capsys):
    """`operator shardcheck --compile-audit` runs locally (no agent)
    and prints the per-group budgets + per-program collectives."""
    from nomad_tpu import cli

    rc = cli.main(["operator", "shardcheck", "--compile-audit",
                   "--nodes", "64"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "mesh" in out
    assert "mesh_const" in out
    assert "program: mesh_solve(spread_alg=False" in out
    assert "all-" in out      # some collective inventoried


def test_state_count_fields():
    """shardcheck.state() carries the zero-tolerance counts that
    /v1/agent/self and ``operator shardcheck`` serve."""
    counts = ("spec_drift_count", "implicit_xfer_count",
              "collective_excess_count")
    st = shardcheck.state()
    assert st["enabled"] is False
    assert [st[k] for k in counts] == [0] * 3
    shardcheck.enable()
    shardcheck.audit_hlo(("f",), "a = all-reduce(b)\n")
    shardcheck.audit_hlo(("f",), "a = all-reduce(b)\n"
                                 "c = all-reduce(d)\n")
    st = shardcheck.state()
    assert st["enabled"] is True
    assert st["collective_excess_count"] == 1
