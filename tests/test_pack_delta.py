"""Incremental memo deltas (ISSUE 6): the alloc table's verify/usage
folds are maintained in place by every write instead of refolding per
table version, plans carry their delta context through
StateStore._bump into one shared cache notification, and the solver's
usage-base memo catches a stale base up by applying journaled deltas.
Every maintained column is parity-gated, bit for bit, against the
from-scratch fold it was built from (``AllocTable._fold_inc_build``).
"""
import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.state import StateStore
from nomad_tpu.state.alloc_table import AllocTable
from nomad_tpu.tensor import pack as tpack


@pytest.fixture(autouse=True)
def clean_caches():
    tpack._reset_pack_caches_for_tests()
    yield
    tpack._reset_pack_caches_for_tests()


def build_store(n_nodes=8):
    store = StateStore()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"pd-node-{i:04d}"
        n.compute_class()
        store.upsert_node(n)
        nodes.append(n)
    return store, nodes


def churn_ops(store, nodes, seed=7, n_jobs=6, per_job=12):
    """A deterministic mixed write load: placements (batch + scalar),
    client-terminal transitions, and deletions."""
    import random
    rng = random.Random(seed)
    all_allocs = []
    for j in range(n_jobs):
        job = mock.job(id=f"pd-job-{j}")
        store.upsert_job(job)
        allocs = []
        for k in range(per_job):
            a = mock.alloc_for(job, nodes[rng.randrange(len(nodes))])
            a.client_status = "running"
            allocs.append(a)
        if j % 2:
            store.upsert_allocs(allocs)          # batch path
        else:
            for a in allocs:                     # scalar path
                store.upsert_allocs([a])
        all_allocs.extend(allocs)
    # a third complete, a sixth is deleted outright
    done = [a for i, a in enumerate(all_allocs) if i % 3 == 0]
    for a in done:
        upd = a.copy_skip_job()
        upd.client_status = "complete"
        store.update_allocs_from_client([upd])
    store.delete_allocs([a.id for i, a in enumerate(all_allocs)
                         if i % 6 == 1])
    return all_allocs


def snapshot_folds(store, node_ids):
    t = store.alloc_table
    uc, um, ud, spec, found = t.fold_verify(node_ids)
    slots = np.fromiter((t.node_slot_of(i) for i in node_ids),
                        dtype=np.int32, count=len(node_ids))
    packed = t.pack(len(node_ids), slots, with_ports=False)
    return (uc, um, ud, spec, found, packed["used_cpu"],
            packed["used_mem"], packed["used_disk"], packed["dyn_used"])


# ----------------------------------------------------------------------
# Incremental fold vs full refold (parity gate)


def test_incremental_fold_parity_after_mixed_churn():
    store, nodes = build_store()
    # force the incremental fold alive BEFORE the churn, so every write
    # path below exercises the delta adjustments
    store.alloc_table._fold_inc_get()
    churn_ops(store, nodes)
    assert store.alloc_table.fold_parity_mismatch() == 0


def test_incremental_fold_parity_with_special_allocs():
    """Port-carrying allocs set the special flag; the count-based vspec
    column must stay reversible through add/remove cycles (a boolean OR
    could never clear back out incrementally)."""
    from nomad_tpu.structs.resources import AllocatedPortMapping

    store, nodes = build_store(4)
    store.alloc_table._fold_inc_get()
    job = mock.job(id="pd-ports")
    store.upsert_job(job)
    allocs = []
    for k in range(6):
        a = mock.alloc_for(job, nodes[k % 4])
        a.client_status = "running"
        a.allocated_resources.shared.ports = [
            AllocatedPortMapping(label="http", value=21000 + k)]
        allocs.append(a)
    store.upsert_allocs(allocs)
    node_ids = [n.id for n in nodes]
    _, _, _, spec_before, _ = store.alloc_table.fold_verify(node_ids)
    assert spec_before.any()
    store.delete_allocs([a.id for a in allocs])
    uc, um, ud, spec, found = store.alloc_table.fold_verify(node_ids)
    assert not spec.any()
    assert uc.sum() == 0 and um.sum() == 0 and ud.sum() == 0
    assert store.alloc_table.fold_parity_mismatch() == 0


def _upsert_more(store, nodes, allocs):
    job = mock.job(id="pd-late")
    store.upsert_job(job)
    late = [mock.alloc_for(job, nodes[k % len(nodes)]) for k in range(9)]
    store.upsert_allocs(late)
    for a in allocs[2::5]:                  # rows overwritten in place
        upd = a.copy_skip_job()
        upd.client_status = "failed"
        store.update_allocs_from_client([upd])


def _remove_more(store, nodes, allocs):
    t = store.alloc_table
    store.delete_allocs([a.id for a in allocs[3::4] if a.id in t._row_of])


def _compact(store, nodes, allocs):
    assert store.alloc_table.free_rows > 0
    store.alloc_table.compact()


@pytest.mark.parametrize("write", [_upsert_more, _remove_more, _compact],
                         ids=["upsert", "remove", "compact"])
def test_maintained_folds_equal_the_from_scratch_fold(write):
    """Columns kept alive through a write load equal, bit for bit, the
    fold a table that never held them builds from scratch at its first
    read: the same fold and pack trees either way."""
    store_a, nodes_a = build_store()
    store_a.alloc_table._fold_inc_get()     # alive before any write
    write(store_a, nodes_a, churn_ops(store_a, nodes_a))
    assert store_a.alloc_table._fold_inc is not None or write is _compact
    maintained = snapshot_folds(store_a, [n.id for n in nodes_a])

    store_b, nodes_b = build_store()
    write(store_b, nodes_b, churn_ops(store_b, nodes_b))
    assert store_b.alloc_table._fold_inc is None    # built at the read
    scratch = snapshot_folds(store_b, [n.id for n in nodes_b])
    for got, want in zip(maintained, scratch):
        np.testing.assert_array_equal(got, want)
    assert store_a.alloc_table.fold_parity_mismatch() == 0


def test_node_slot_growth_keeps_fold_aligned():
    """Registering nodes past the slot capacity grows the incremental
    arrays; usage folded before and after must stay slot-aligned."""
    store, nodes = build_store(2)
    t = store.alloc_table
    t._fold_inc_get()
    job = mock.job(id="pd-grow")
    store.upsert_job(job)
    a = mock.alloc_for(job, nodes[0])
    a.client_status = "running"
    store.upsert_allocs([a])
    # force a slot-capacity doubling
    for i in range(t._node_cap + 4):
        n = mock.node()
        n.id = f"pd-extra-{i:05d}"
        n.compute_class()
        store.upsert_node(n)
    assert t.fold_parity_mismatch() == 0


# ----------------------------------------------------------------------
# Compaction (bounded state)


def test_compact_preserves_rows_and_folds():
    store, nodes = build_store()
    t = store.alloc_table
    t._fold_inc_get()
    allocs = churn_ops(store, nodes)
    survivors = [a.id for a in allocs if a.id in t._row_of]
    before = snapshot_folds(store, [n.id for n in nodes])
    rows_before, free_before = t.n_rows, t.free_rows
    assert free_before > 0          # churn_ops deleted a sixth
    stats = t.compact()
    assert stats["rows_after"] == rows_before - free_before
    assert t.free_rows == 0
    assert sorted(t._row_of) == sorted(survivors)
    after = snapshot_folds(store, [n.id for n in nodes])
    for got, want in zip(after, before):
        np.testing.assert_array_equal(got, want)
    assert t.fold_parity_mismatch() == 0


def test_compact_shrinks_capacity():
    t = AllocTable(initial_capacity=1024)
    t.preallocate(16384)
    assert t._cap >= 16384
    stats = t.compact()
    assert stats["cap_after"] == 1024 and t._cap == 1024


def test_store_compact_watermark_gates():
    """compact_alloc_table only pays the copy past BOTH thresholds."""
    store, nodes = build_store(2)
    job = mock.job(id="pd-wm")
    store.upsert_job(job)
    allocs = []
    for k in range(20):
        a = mock.alloc_for(job, nodes[k % 2])
        allocs.append(a)
    store.upsert_allocs(allocs)
    store.delete_allocs([a.id for a in allocs[:10]])
    assert store.compact_alloc_table() is None          # < min_free
    assert store.compact_alloc_table(min_free=4) is not None
    assert store.alloc_table.free_rows == 0


# ----------------------------------------------------------------------
# Delta-aware _bump notification + journal (satellite)


def test_bump_passes_plan_delta_to_shared_hook(monkeypatch):
    """The cache-invalidation hooks must receive the write's delta
    context (old/new alloc pairs), not just 'something changed'."""
    seen = []

    def spy(tables, index, delta=None):
        seen.append((tuple(tables), index, delta))

    monkeypatch.setattr(tpack, "note_table_write", spy)
    store, nodes = build_store(2)
    job = mock.job(id="pd-hook")
    store.upsert_job(job)
    a = mock.alloc_for(job, nodes[0])
    store.upsert_allocs([a])
    alloc_writes = [s for s in seen if "allocs" in s[0]]
    assert alloc_writes
    tables, index, delta = alloc_writes[-1]
    assert delta and delta[0][0] is None and delta[0][1].id == a.id
    # node writes flow through the SAME notification shape
    assert any("nodes" in s[0] for s in seen)


def test_alloc_delta_journal_coverage_and_upto():
    store, nodes = build_store(2)
    job = mock.job(id="pd-journal")
    store.upsert_job(job)
    a = mock.alloc_for(job, nodes[0])
    idx0 = store.latest_index()
    store.upsert_allocs([a])
    idx1 = store.latest_index()
    upd = a.copy_skip_job()
    upd.client_status = "complete"
    store.update_allocs_from_client([upd])
    idx2 = store.latest_index()

    covered, pairs = store.alloc_deltas_since(idx0)
    assert covered and len(pairs) == 2
    assert pairs[0][0] is None and pairs[0][1].id == a.id
    assert pairs[1][0].id == a.id and \
        pairs[1][1].client_status == "complete"
    # upto excludes the later write
    covered, pairs = store.alloc_deltas_since(idx0, upto=idx1)
    assert covered and len(pairs) == 1
    # a span older than the bounded journal is not covered
    for k in range(200):
        b = mock.alloc_for(job, nodes[k % 2])
        store.upsert_allocs([b])
    covered, _ = store.alloc_deltas_since(idx0)
    assert not covered


def test_usage_base_catches_up_via_journal():
    """Across two snapshots of one store, the matrix-attached usage base
    must advance by applying journaled deltas (usage_base_delta_hits)
    and match a cold refold exactly."""
    from nomad_tpu.tensor.pack import fold_usage_base

    from tests.test_pack_cache import build_world, make_service

    h, nodes = build_world(8, with_allocs=4)
    svc, tg, places = make_service(h, nodes, 0)
    matrix = tpack.pack_nodes_cached(
        nodes, h.state.snapshot().node_table_index)
    u1 = svc._pack_usage_incremental(matrix, nodes, tg)
    base0 = tpack.pack_cache_stats()

    # churn between snapshots: one more alloc lands
    j = mock.job(id="pd-ub-churn")
    h.state.upsert_job(j)
    extra = mock.alloc_for(j, nodes[0])
    extra.client_status = "running"
    h.state.upsert_allocs([extra])

    svc2, tg2, _ = make_service(h, nodes, 1)
    u2 = svc2._pack_usage_incremental(matrix, nodes, tg2)
    stats = tpack.pack_cache_stats()
    assert stats["usage_base_delta_hits"] == \
        base0["usage_base_delta_hits"] + 1

    snap = h.state.snapshot()
    cold = fold_usage_base(
        matrix, nodes,
        lambda nid: [x for x in snap.allocs_by_node(nid)
                     if not x.client_terminal_status()])
    np.testing.assert_array_equal(u2.used_cpu, cold["used_cpu"])
    np.testing.assert_array_equal(u2.used_mem, cold["used_mem"])
    np.testing.assert_array_equal(u2.used_disk, cold["used_disk"])


# ----------------------------------------------------------------------
# Delta-journal capacity knob + overflow accounting (ISSUE 8 satellite)


def test_delta_journal_capacity_knob(monkeypatch):
    """NOMAD_TPU_DELTA_JOURNAL sizes the alloc-delta journal: a span
    that overflows the default 128 entries stays coverable under a
    larger bound (an LP batch's plan group is one entry, but serial
    write fan-out is many)."""
    monkeypatch.setenv("NOMAD_TPU_DELTA_JOURNAL", "512")
    store, nodes = build_store(2)
    job = mock.job(id="pd-knob")
    store.upsert_job(job)
    idx0 = store.latest_index()
    for k in range(300):
        a = mock.alloc_for(job, nodes[k % 2])
        store.upsert_allocs([a])
    covered, pairs = store.alloc_deltas_since(idx0)
    assert covered and len(pairs) == 300
    # the default bound would have wrapped at 128
    assert store._alloc_deltas.maxlen == 512


def test_delta_journal_overflow_counter(monkeypatch):
    """An overflow-forced wholesale rebuild (journal wrapped past the
    consumer's base index) counts into
    nomad.state.delta_journal_overflow; an uncoverable-but-not-wrapped
    span (delta-less write) does not."""
    from nomad_tpu.server.telemetry import metrics

    monkeypatch.setenv("NOMAD_TPU_DELTA_JOURNAL", "16")
    metrics.reset()
    store, nodes = build_store(2)
    job = mock.job(id="pd-overflow")
    store.upsert_job(job)
    idx0 = store.latest_index()
    for k in range(40):                 # wraps the 16-entry journal
        a = mock.alloc_for(job, nodes[k % 2])
        store.upsert_allocs([a])
    covered, _ = store.alloc_deltas_since(idx0)
    assert not covered
    snap = metrics.snapshot()
    assert snap["counters"].get(
        "nomad.state.delta_journal_overflow", 0) == 1

    # a covered read does not bump the counter
    idx1 = store.latest_index()
    a = mock.alloc_for(job, nodes[0])
    store.upsert_allocs([a])
    covered, pairs = store.alloc_deltas_since(idx1)
    assert covered and len(pairs) == 1
    snap = metrics.snapshot()
    assert snap["counters"].get(
        "nomad.state.delta_journal_overflow", 0) == 1


def test_journal_overflow_under_concurrent_readers_never_tears():
    """ISSUE 11 satellite: ``alloc_deltas_since`` racing ``upsert_many``
    writers must return a COVERABLE range or an explicit gap
    (covered=False), never a partially-applied delta set.  Writers
    commit fixed-size batches whose pairs share a per-batch job id;
    a torn read would surface as a batch appearing with only part of
    its pairs.  The journal is shrunk so readers race real overflow,
    not just the happy path."""
    import threading

    store, nodes = build_store(4)
    base_job = mock.job(id="pd-race")
    store.upsert_job(base_job)
    BATCH = 7
    ROUNDS = 60
    stop = threading.Event()
    problems = []

    def writer():
        for r in range(ROUNDS):
            job = mock.job(id=f"pd-race-{r}")
            allocs = [mock.alloc_for(job, nodes[k % len(nodes)],
                                     index=k) for k in range(BATCH)]
            store.upsert_allocs(allocs)
        stop.set()

    def reader():
        last = store.latest_index()
        while True:
            upto = store.table_index("allocs")
            covered, pairs = store.alloc_deltas_since(last, upto=upto)
            if covered:
                # every write's batch must arrive WHOLE: count pairs
                # per batch job id -- a partial batch is a torn set
                per_batch = {}
                for old, new in pairs:
                    a = new if new is not None else old
                    per_batch.setdefault(a.job_id, 0)
                    per_batch[a.job_id] += 1
                for jid, count in per_batch.items():
                    if jid.startswith("pd-race-") and count != BATCH:
                        problems.append(
                            f"partial batch {jid}: {count}/{BATCH}")
                last = upto
            else:
                # explicit gap (overflow or delta-less write): the
                # reader refolds by resetting its base -- legitimate,
                # never wrong data
                last = store.table_index("allocs")
            if stop.is_set():
                # one final drain after the writer finished
                upto = store.table_index("allocs")
                covered, pairs = store.alloc_deltas_since(last,
                                                          upto=upto)
                break

    # shrink the journal so overflow actually happens mid-race
    import os
    old = os.environ.get("NOMAD_TPU_DELTA_JOURNAL")
    os.environ["NOMAD_TPU_DELTA_JOURNAL"] = "16"
    try:
        from collections import deque
        with store._lock:
            store._alloc_deltas = deque(store._alloc_deltas, maxlen=16)
        threads = [threading.Thread(target=writer),
                   threading.Thread(target=reader),
                   threading.Thread(target=reader)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=60)
        assert not any(t.is_alive() for t in threads)
    finally:
        if old is None:
            os.environ.pop("NOMAD_TPU_DELTA_JOURNAL", None)
        else:
            os.environ["NOMAD_TPU_DELTA_JOURNAL"] = old
    assert problems == [], problems
