"""MVCC snapshot-isolation sanitizer tests (ISSUE 11 tentpole): the
kill-switch path must be a true no-op (AllocTable/StateStore methods
untouched, no wrapper observable), enabled runs must be bit-for-bit
identical to disabled ones on a real dispatch + plan-commit cycle, and
each of the five detectors -- torn snapshot read, aliasing write,
delta-journal gap, write-skew witness, stale version-keyed memo --
must fire on a seeded violation.  The sanitizer itself runs over the
plan-batch / pack-delta / churn-storm / lpq suites via the conftest
fixture; these tests pin its own semantics.
"""
import numpy as np
import pytest

from nomad_tpu import mock, statecheck
from nomad_tpu.state.alloc_table import AllocTable
from nomad_tpu.state.store import StateStore
from nomad_tpu.structs import PlanResult


@pytest.fixture(autouse=True)
def _clean_checker():
    """Every test leaves the real store/table methods restored and the
    checker state empty, pass or fail."""
    yield
    statecheck.disable()
    statecheck._reset_for_tests()


def _world(n_nodes=2, job_id="sc-job"):
    s = StateStore()
    nodes = []
    for k in range(n_nodes):
        n = mock.node()
        n.id = f"sc-node-{k:04d}"
        n.compute_class()
        s.upsert_node(n)
        nodes.append(n)
    job = mock.job(id=job_id)
    return s, nodes, job


# ----------------------------------------------------------------------
# kill switch + parity


def test_killswitch_is_inert(monkeypatch):
    """NOMAD_TPU_STATECHECK=0 (or unset) is a true no-op: the class
    methods are the raw functions and no wrapper is observable."""
    monkeypatch.setenv("NOMAD_TPU_STATECHECK", "0")
    statecheck.maybe_install_from_env()
    assert not statecheck.enabled()
    for name in ("pack", "fold_verify", "count_placed", "usage_by_node",
                 "upsert", "upsert_many", "remove", "register_node",
                 "compact", "_fold_verify_all"):
        assert not getattr(getattr(AllocTable, name),
                           "_statecheck_wrapped", False), name
    assert StateStore._bump.__qualname__.startswith("StateStore.")
    assert StateStore.apply_plan_results_batch.__qualname__.startswith(
        "StateStore.")
    st = statecheck.state()
    assert st["enabled"] is False and st["reads"] == 0
    # the scope context managers are inert no-ops too
    with statecheck.eval_scope(None):
        with statecheck.strict_scope("off"):
            pass
    assert statecheck.state()["scopes"] == 0


def test_env_knob_installs(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_STATECHECK", "1")
    statecheck.maybe_install_from_env()
    assert statecheck.enabled()
    assert getattr(AllocTable.upsert, "_statecheck_wrapped", False)
    # and disable restores the raw methods for everyone after us
    statecheck.disable()
    assert not getattr(AllocTable.upsert, "_statecheck_wrapped", False)


def _dispatch_and_commit(i=0):
    """A real dispatch + plan-commit cycle: solve one lane on the fused
    TPU path, then commit the resulting placements through the store's
    batch path. Returns (scores, node ids, store index)."""
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService, \
        dispatch_lane
    from nomad_tpu.structs import Plan
    from nomad_tpu.tensor import pack as tpack

    tpack._reset_pack_caches_for_tests()
    h = Harness()
    nodes = []
    for k in range(8):
        n = mock.node()
        n.id = f"par-node-{k:04d}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    job = mock.job(id=f"par-job-{i}")
    job.task_groups[0].count = 4
    tg = job.task_groups[0]
    plan = Plan(eval_id=f"par-eval-{i:029d}", priority=50, job=job)
    ctx = EvalContext(h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(4)]
    svc = TpuPlacementService(ctx, job, batch_mode=False,
                              spread_alg=False)
    lane = svc.pack(tg, places, nodes)
    solved = dispatch_lane(lane)
    allocs = [mock.alloc_for(job, nodes[k % len(nodes)], index=k)
              for k in range(4)]
    result = PlanResult(node_allocation={
        a.node_id: [a] for a in allocs[:1]})
    idx, outcomes = h.state.apply_plan_results_batch([(result, None)])
    assert outcomes == [None]
    return ([np.asarray(x) for x in solved],
            [n.id for n in nodes], idx)


def test_enabled_cycle_is_bitwise_identical():
    """The acceptance parity gate: the same dispatch + plan-commit
    cycle with the sanitizer recording returns bit-for-bit what the
    raw path returns (wrappers only observe; they never touch
    values)."""
    off_solved, off_nodes, off_idx = _dispatch_and_commit(i=0)
    statecheck.enable()
    try:
        on_solved, on_nodes, on_idx = _dispatch_and_commit(i=0)
        st = statecheck.state()
    finally:
        statecheck.disable()
    assert off_nodes == on_nodes and off_idx == on_idx
    for a, b in zip(off_solved, on_solved):
        np.testing.assert_array_equal(a, b)
    assert st["torn_reads"] == [] and st["aliasing_writes"] == []
    assert st["reads"] > 0 and st["mutations"] > 0


# ----------------------------------------------------------------------
# (a) torn snapshot reads


def test_intra_read_tear_detected(monkeypatch):
    """A mutation landing DURING one instrumented read (a writer racing
    a lockless reader) is a torn read with a witness stack."""
    from nomad_tpu import native

    statecheck.enable()
    s, nodes, job = _world()
    s.upsert_allocs([mock.alloc_for(job, nodes[0])])
    extra = mock.alloc_for(job, nodes[1], index=7)
    real_count = native.count_placed

    def racing_count(*a, **k):
        s.alloc_table.upsert(extra)     # the racing writer
        return real_count(*a, **k)

    monkeypatch.setattr(native, "count_placed", racing_count)
    t = s.alloc_table
    n_pad = 4
    slots = np.full(n_pad, -1, dtype=np.int32)
    slots[0] = t.node_slot_of(nodes[0].id)
    t.count_placed(n_pad, slots, job.namespace, job.id,
                   job.task_groups[0].name)
    st = statecheck.state()
    assert st["torn_read_count"] == 1
    rep = st["torn_reads"][0]
    assert rep["kind"] == "intra-read-tear"
    assert rep["op"] == "count_placed"
    assert rep["versions"][1] > rep["versions"][0]
    assert "test_statecheck.py" in rep["stack"]


def test_strict_scope_tear_detected():
    """Two table versions observed inside one strict (verify) scope:
    the applier judged a plan against two different states."""
    from nomad_tpu.server.telemetry import metrics
    metrics.reset()
    statecheck.enable()
    s, nodes, job = _world()
    s.upsert_allocs([mock.alloc_for(job, nodes[0])])
    with statecheck.strict_scope("test.verify"):
        with s._lock:
            s.alloc_table.fold_verify([nodes[0].id])
        s.upsert_allocs([mock.alloc_for(job, nodes[1], index=1)])
        with s._lock:
            s.alloc_table.fold_verify([nodes[0].id])
    st = statecheck.state()
    assert any(r["kind"] == "scope-tear" for r in st["torn_reads"]), \
        st["torn_reads"]
    assert metrics.snapshot()["counters"].get(
        "nomad.statecheck.torn_read", 0) >= 1
    metrics.reset()


def test_eval_scope_drift_is_report_only():
    """The SAME interleaving inside a non-strict eval scope is the
    documented optimistic-read design (the applier re-verifies): it is
    recorded as drift, never as a torn read."""
    statecheck.enable()
    s, nodes, job = _world()
    s.upsert_allocs([mock.alloc_for(job, nodes[0])])
    snap = s.snapshot()
    with statecheck.eval_scope(snap):
        with s._lock:
            s.alloc_table.fold_verify([nodes[0].id])
        s.upsert_allocs([mock.alloc_for(job, nodes[1], index=1)])
        with s._lock:
            s.alloc_table.fold_verify([nodes[0].id])
    st = statecheck.state()
    assert st["torn_read_count"] == 0
    assert st["drift_count"] >= 1
    assert st["drifts"][0]["scope"] == "eval"


# ----------------------------------------------------------------------
# (b) aliasing writes


def test_direct_row_write_detected():
    """A direct column write bypassing the instrumented mutators (the
    runtime twin of nomadlint's no-direct-table-write): row bytes
    changed under an unchanged table version."""
    statecheck.enable()
    s, nodes, job = _world()
    a = mock.alloc_for(job, nodes[0])
    s.upsert_allocs([a])
    t = s.alloc_table
    row = t._row_of[a.id]
    t.cpu[row] += 123.0             # nobody bumped version
    assert statecheck.verify_state() >= 1
    st = statecheck.state()
    assert any(r["kind"] == "row-mutated"
               for r in st["aliasing_writes"]), st["aliasing_writes"]


def test_version_blind_mutation_detected(monkeypatch):
    """A mutator that forgets to bump ``version`` silently invalidates
    every version-keyed cache; simulate one by stubbing the real
    upsert under the wrapper."""
    statecheck.enable()
    s, nodes, job = _world()
    monkeypatch.setitem(statecheck._REAL, "table.upsert",
                        lambda self, alloc: None)
    s.alloc_table.upsert(mock.alloc_for(job, nodes[0]))
    st = statecheck.state()
    assert any(r["kind"] == "version-blind-mutation"
               for r in st["aliasing_writes"]), st["aliasing_writes"]


def test_published_array_thaw_and_mutation_detected():
    """Published memo arrays (what tensor/pack freezes) must stay
    writeable=False and content-stable; thawing + rewriting one is
    caught by the rotating re-fingerprint."""
    from nomad_tpu.server.telemetry import metrics
    metrics.reset()
    statecheck.enable()
    arr = np.arange(16, dtype=np.float64)
    arr.setflags(write=False)
    statecheck.note_published(arr)
    assert statecheck.state()["aliasing_write_count"] == 0
    arr.setflags(write=True)
    arr[0] = 99.0
    assert statecheck.verify_state() >= 1
    st = statecheck.state()
    kinds = {r["kind"] for r in st["aliasing_writes"]}
    assert kinds & {"published-thawed", "published-mutated"}, kinds
    assert metrics.snapshot()["counters"].get(
        "nomad.statecheck.aliasing_write", 0) >= 1
    metrics.reset()


def test_unfrozen_publish_detected():
    """Publishing a still-writeable array is itself a violation (the
    writeable=False guard on snapshot-exposed ndarrays)."""
    statecheck.enable()
    statecheck.note_published(np.zeros(8))
    st = statecheck.state()
    assert any(r["kind"] == "published-writeable"
               for r in st["aliasing_writes"])


def test_fold_view_mutation_detected():
    """_fold_verify_all hands out views of the live fold columns; a
    consumer writing into them corrupts the store's resident fold."""
    statecheck.enable()
    s, nodes, job = _world()
    s.upsert_allocs([mock.alloc_for(job, nodes[0])])
    with s._lock:
        vc, vm, vd, vs = s.alloc_table._fold_verify_all()
    vc[0] += 7.0                    # consumer writes into the view
    assert statecheck.verify_state() >= 1
    st = statecheck.state()
    assert any(r["kind"] == "fold-view-mutated"
               for r in st["aliasing_writes"]), st["aliasing_writes"]


def test_pack_freeze_registers_published_arrays():
    """The tensor/pack freeze path routes every frozen memo payload
    into the published-array registry while the checker records."""
    from nomad_tpu.tensor import pack as tpack

    statecheck.enable()
    s, nodes, job = _world(n_nodes=4)
    snap = s.snapshot()
    tpack._reset_pack_caches_for_tests()
    tpack.pack_nodes_cached(snap.ready_nodes_in_pool(),
                            snap.node_table_index)
    st = statecheck.state()
    assert st["published_arrays"] > 0
    assert st["aliasing_write_count"] == 0
    tpack._reset_pack_caches_for_tests()


# ----------------------------------------------------------------------
# (c) delta-journal coverage gaps


def test_journal_gap_detected_and_mark_uncoverable():
    """A delta-less allocs bump outside mark_uncoverable reports (with
    a stack); inside the scope it is an explicit, silent gap."""
    statecheck.enable()
    s, _nodes, _job = _world()
    with s._lock:
        s._bump("allocs")           # silent gap: reported
    st = statecheck.state()
    assert st["journal_gap_count"] == 1
    assert "test_statecheck.py" in st["journal_gaps"][0]["site"]
    with statecheck.mark_uncoverable("test wholesale write"):
        with s._lock:
            s._bump("allocs")       # explicit gap: quiet
    st = statecheck.state()
    assert st["journal_gap_count"] == 1
    assert st["uncoverable_marked"] == 1


def test_snapshot_restore_is_an_explicit_gap():
    """The raft snapshot restore marks itself uncoverable -- the one
    designed wholesale writer stays quiet."""
    from nomad_tpu.raft.fsm import dump_state

    statecheck.enable()
    s, nodes, job = _world()
    s.upsert_allocs([mock.alloc_for(job, nodes[0])])
    blob = dump_state(s)
    s.restore_from_snapshot(blob)
    st = statecheck.state()
    assert st["journal_gap_count"] == 0, st["journal_gaps"]
    assert st["uncoverable_marked"] == 1


# ----------------------------------------------------------------------
# (d) write-skew witnesses


def test_write_skew_witness_on_overlapping_batch():
    """Two plan results touching the same node inside ONE batch commit
    skipped the applier's conflict path -- the exact hazard N workers
    multiply."""
    from nomad_tpu.server.telemetry import metrics
    metrics.reset()
    statecheck.enable()
    s, nodes, job = _world()
    a1 = mock.alloc_for(job, nodes[0])
    a1.eval_id = "e" * 30 + "1"
    a2 = mock.alloc_for(job, nodes[0], index=1)
    a2.eval_id = "e" * 30 + "2"
    r1 = PlanResult(node_allocation={nodes[0].id: [a1]})
    r2 = PlanResult(node_allocation={nodes[0].id: [a2]})
    s.apply_plan_results_batch([(r1, None), (r2, None)])
    st = statecheck.state()
    assert st["write_skew_count"] == 1
    rep = st["write_skews"][0]
    assert rep["node"] == nodes[0].id
    assert set(rep["plans"]) == {a1.eval_id, a2.eval_id}
    assert metrics.snapshot()["counters"].get(
        "nomad.statecheck.write_skew", 0) >= 1
    metrics.reset()


def test_disjoint_batch_is_clean():
    statecheck.enable()
    s, nodes, job = _world()
    a1 = mock.alloc_for(job, nodes[0])
    a2 = mock.alloc_for(job, nodes[1], index=1)
    r1 = PlanResult(node_allocation={nodes[0].id: [a1]})
    r2 = PlanResult(node_allocation={nodes[1].id: [a2]})
    s.apply_plan_results_batch([(r1, None), (r2, None)])
    assert statecheck.state()["write_skew_count"] == 0


# ----------------------------------------------------------------------
# (e) stale version-keyed memos


def test_stale_matrix_cache_entry_swept():
    """A _NODE_MATRIX_CACHE entry tagged older than the latest
    node-table write should have been dropped by the invalidation
    hook; a survivor is a stale memo."""
    from nomad_tpu.tensor import pack as tpack

    statecheck.enable()
    s, nodes, _job = _world()
    latest = s.table_index("nodes")
    assert latest > 0
    # simulate an entry the invalidation hook failed to drop
    with tpack._NODE_MATRIX_LOCK:
        tpack._NODE_MATRIX_CACHE[(latest - 1, ("ghost",))] = object()
    try:
        assert statecheck.verify_state() >= 1
        st = statecheck.state()
        assert any(r["kind"] == "node_matrix"
                   for r in st["stale_memos"]), st["stale_memos"]
    finally:
        tpack._reset_pack_caches_for_tests()


def test_memo_served_version_mismatch():
    """The usage-base/fold-cache hit hooks assert the served entry's
    version token matches the snapshot's."""
    statecheck.enable()
    statecheck.note_memo_served("usage_base", 3, 5)
    st = statecheck.state()
    assert st["stale_memo_count"] == 1
    rep = st["stale_memos"][0]
    assert rep["entry_version"] == 3 and rep["live_version"] == 5
    # matching tokens are the designed hit: quiet
    statecheck.note_memo_served("usage_base", 5, 5)
    assert statecheck.state()["stale_memo_count"] == 1


# ----------------------------------------------------------------------
# scopes + surfaces


def test_worker_scope_attributes_to_trace_span():
    """eval_scope picks up the enclosing PR-3 trace span ids so a
    finding names the eval that tore."""
    from nomad_tpu.server.tracing import tracer

    statecheck.enable()
    s, nodes, job = _world()
    s.upsert_allocs([mock.alloc_for(job, nodes[0])])
    eid = "scope-eval-" + "0" * 20
    ctx = tracer.begin(eid, job=job.id)
    with tracer.activate(ctx):
        with statecheck.strict_scope("test.verify"):
            with s._lock:
                s.alloc_table.fold_verify([nodes[0].id])
            s.upsert_allocs([mock.alloc_for(job, nodes[1], index=1)])
            with s._lock:
                s.alloc_table.fold_verify([nodes[0].id])
    tracer.end(eid, status="complete")
    st = statecheck.state()
    tears = [r for r in st["torn_reads"] if r["kind"] == "scope-tear"]
    assert tears and eid in tears[0]["evals"]


def test_agent_self_and_operator_cli_surface(capsys):
    """stats.statecheck rides /v1/agent/self; `operator statecheck`
    renders it and exits 1 when torn reads or aliasing writes exist,
    and `operator sanitizers` aggregates all three checkers."""
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
            "/v1/agent/self")["stats"]["statecheck"]
        assert st["enabled"] is False and st["torn_reads"] == []

        assert cli.main(["-address", base,
                         "operator", "statecheck"]) == 0
        assert "enabled" in capsys.readouterr().out
        assert cli.main(["-address", base,
                         "operator", "sanitizers"]) == 0
        out = capsys.readouterr().out
        assert "lockcheck" in out and "jitcheck" in out \
            and "statecheck" in out

        statecheck.enable()
        s = server.state
        n = mock.node()
        s.upsert_node(n)
        job = mock.job(id="cli-sc-job")
        s.upsert_allocs([mock.alloc_for(job, n)])
        with statecheck.strict_scope("cli.verify"):
            with s._lock:
                s.alloc_table.fold_verify([n.id])
            s.upsert_allocs([mock.alloc_for(job, n, index=1)])
            with s._lock:
                s.alloc_table.fold_verify([n.id])
        rc = cli.main(["-address", base,
                       "operator", "statecheck", "--stacks"])
        out = capsys.readouterr().out
        assert rc == 1
        assert "TORN READ 0" in out and "scope-tear" in out
        rc = cli.main(["-address", base, "operator", "sanitizers"])
        out = capsys.readouterr().out
        assert rc == 1 and "FAIL" in out
    finally:
        http.shutdown()
        server.shutdown()


def test_state_count_fields():
    """statecheck.state() carries the zero-tolerance counts that
    /v1/agent/self and ``operator statecheck`` serve."""
    counts = ("torn_read_count", "aliasing_write_count",
              "journal_gap_count", "write_skew_count", "stale_memo_count")
    st = statecheck.state()
    assert st["enabled"] is False
    assert [st[k] for k in counts] == [0] * 5
    statecheck.enable()
    statecheck.note_memo_served("usage_base", 1, 2)
    st = statecheck.state()
    assert st["enabled"] is True
    assert st["stale_memo_count"] == 1
