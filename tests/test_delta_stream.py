"""Delta streaming (ISSUE 20): journal-edge and byte-parity nets for
the device-resident version chain (solver/constcache.py chain_apply +
device_put_cached delta_src route).

The correctness contract under test: the scatter path can be SKIPPED
(wholesale fallback) but never WRONG -- every outcome's device buffer
must equal the wholesale upload bit for bit; journal overflow, delta-
less writes and snapshot restores force counted fallbacks; and a
promoted buffer, read back from the device, is the host's array, on the
real pipelined dispatch path too.
"""
import numpy as np
import pytest

import jax

from nomad_tpu import mock
from nomad_tpu.solver import constcache


@pytest.fixture(autouse=True)
def _clean_cache(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    constcache._reset_for_tests()
    yield
    constcache._reset_for_tests()


def table(seed=0, shape=(8, 256)):
    """A chain-eligible table: >= NOMAD_TPU_CONST_CACHE_MIN_BYTES."""
    rng = np.random.default_rng(seed)
    a = rng.standard_normal(shape).astype(np.float32)
    assert a.nbytes >= constcache._min_bytes()
    return a


class FakeStore:
    """Programmable journal: (covered, pairs) per call."""

    def __init__(self, covered=True, pairs=()):
        self.covered = covered
        self.pairs = list(pairs)
        self.calls = []

    def alloc_deltas_since(self, index, upto=None):
        self.calls.append((index, upto))
        return self.covered, list(self.pairs)


def put_chain(arrs, store, token, tags=None):
    return constcache.device_put_cached(
        [np.array(a) for a in arrs],      # fresh, writable transports
        version=token, cacheable=[False] * len(arrs),
        tags=tags or ["compact"] * len(arrs),
        delta_src=(store, token))


# ----------------------------------------------------------------------
# host diff + padding primitives


def test_bitwise_diff_is_bytewise_not_value_equality():
    """-0.0 vs +0.0 compare EQUAL and NaN never equals itself under
    ``!=`` -- the bitwise diff must see both, or the kill switch's
    bit-for-bit promise breaks on sign flips and NaN payloads."""
    old = np.array([0.0, 1.0, np.nan, 2.0], dtype=np.float32)
    new = old.copy()
    assert constcache._bitwise_changed(old, new).size == 0
    new[0] = -0.0                         # value-equal, bit-different
    new[2] = np.float32(np.nan)           # same bits: NOT a change
    changed = constcache._bitwise_changed(old, new)
    assert changed.tolist() == [0]
    # a NaN with a different payload IS a change
    new2 = old.copy()
    new2.view(np.uint32)[2] ^= 1
    assert constcache._bitwise_changed(old, new2).tolist() == [2]


def test_pad_updates_pow2_bucket_min8_duplicates_slot0():
    idx = np.array([3, 17, 42], dtype=np.int64)
    vals = np.array([1.0, 2.0, 3.0], dtype=np.float32)
    idx_p, vals_p, bucket = constcache._pad_updates(idx, vals)
    assert bucket == 8 and idx_p.size == 8 and vals_p.size == 8
    assert idx_p.dtype == np.int32
    # padding repeats slot 0 (duplicate writes of the SAME value are
    # deterministic), so the padded scatter is bitwise the unpadded one
    assert set(idx_p[3:].tolist()) == {3}
    assert set(vals_p[3:].tolist()) == {1.0}
    idx9 = np.arange(9)
    _, _, b9 = constcache._pad_updates(
        idx9, np.ones(9, dtype=np.float32))
    assert b9 == 16


# ----------------------------------------------------------------------
# chain outcomes: install -> reuse -> promote, each bitwise-verified


def test_install_reuse_promote_sequence_bitwise_exact():
    store = FakeStore(covered=True)
    a = table(seed=1)

    bufs, shipped = put_chain([a], store, token=10)
    assert shipped == a.nbytes            # install: wholesale, not a
    st = constcache.stats()               # fallback
    assert st["chain_entries"] == 1 and st["delta_fallbacks"] == 0

    bufs, shipped = put_chain([a], store, token=11)
    assert shipped == 0                   # bitwise identical: reuse
    assert constcache.stats()["delta_reuses"] == 1
    np.testing.assert_array_equal(np.asarray(bufs[0]), a)

    b = a.copy()
    b[0, 3] = -0.0
    b[5, 100] = np.float32(7.25)
    bufs, shipped = put_chain([b], store, token=12)
    st = constcache.stats()
    assert st["delta_promotions"] == 1 and st["delta_fallbacks"] == 0
    assert 0 < shipped < b.nbytes // 4    # KB-scale delta, not a table
    got = np.asarray(bufs[0])
    wholesale = np.asarray(jax.device_put(b))
    assert got.dtype == wholesale.dtype and got.shape == wholesale.shape
    assert (got.view(np.uint8) == wholesale.view(np.uint8)).all()
    # the chain row advanced base -> token with one applied delta
    row = [r for r in constcache.residency()
           if r["id"].startswith("chain:")][0]
    assert row["version"] == 12 and row["deltas_applied"] == 1


def test_uncovered_span_is_counted_gap_fallback_never_wrong():
    store = FakeStore(covered=True)
    a = table(seed=2)
    put_chain([a], store, token=1)
    store.covered = False                 # journal cannot vouch
    b = a.copy()
    b[2, 2] += 1.0
    bufs, shipped = put_chain([b], store, token=2)
    st = constcache.stats()
    assert st["delta_fallbacks"] == 1
    assert st["delta_gap_fallbacks"] == 1
    assert shipped == b.nbytes            # wholesale re-upload
    np.testing.assert_array_equal(np.asarray(bufs[0]), b)
    # the slot re-installed at the new token: a covered next
    # generation deltas against IT, not the stale base
    store.covered = True
    c = b.copy()
    c[0, 0] += 1.0
    bufs, _ = put_chain([c], store, token=3)
    assert constcache.stats()["delta_promotions"] == 1
    np.testing.assert_array_equal(np.asarray(bufs[0]), c)


def test_oversized_diff_is_counted_size_fallback(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_DELTA_MAX_FRAC", "0.25")
    store = FakeStore(covered=True)
    a = table(seed=3)
    put_chain([a], store, token=1)
    b = a + 1.0                           # every element changed
    bufs, shipped = put_chain([b], store, token=2)
    st = constcache.stats()
    assert st["delta_size_fallbacks"] == 1
    assert st["delta_bytes_total"] == 0   # nothing shipped as delta
    assert shipped == b.nbytes
    np.testing.assert_array_equal(np.asarray(bufs[0]), b)


def test_exception_from_journal_is_a_gap_not_a_crash():
    class Exploding(FakeStore):
        def alloc_deltas_since(self, index, upto=None):
            raise RuntimeError("journal on fire")

    store = Exploding()
    a = table(seed=4)
    put_chain([a], store, token=1)
    bufs, _ = put_chain([a], store, token=2)
    assert constcache.stats()["delta_gap_fallbacks"] == 1
    np.testing.assert_array_equal(np.asarray(bufs[0]), a)


# ----------------------------------------------------------------------
# real-journal edges: overflow, delta-less writes, snapshot restore


def _world(n_nodes=2):
    from nomad_tpu.state.store import StateStore

    s = StateStore()
    nodes = []
    for k in range(n_nodes):
        n = mock.node()
        n.id = f"ds-node-{k:04d}"
        n.compute_class()
        s.upsert_node(n)
        nodes.append(n)
    return s, nodes, mock.job(id="ds-job")


def test_journal_overflow_forces_counted_wholesale(monkeypatch):
    """More alloc writes than the journal ring holds between two
    sightings of a slot: the span is unrecoverable, the chain must
    fall back wholesale (counted) and still be bitwise right."""
    monkeypatch.setenv("NOMAD_TPU_DELTA_JOURNAL", "8")
    store, nodes, job = _world()
    store.upsert_job(job)
    a = table(seed=5)
    put_chain([a], store, token=store.latest_index())
    for i in range(12):                   # > ring capacity
        al = mock.alloc_for(job, nodes[i % 2])
        store.upsert_allocs([al])
    b = a.copy()
    b[1, 1] += 1.0
    bufs, shipped = put_chain([b], store, token=store.latest_index())
    st = constcache.stats()
    assert st["delta_gap_fallbacks"] == 1 and st["delta_promotions"] == 0
    assert shipped == b.nbytes
    np.testing.assert_array_equal(np.asarray(bufs[0]), b)


def test_covered_span_on_real_store_promotes(monkeypatch):
    """The positive control for the overflow test: few writes inside
    the ring -> covered span -> promote, bitwise-exact."""
    monkeypatch.setenv("NOMAD_TPU_DELTA_JOURNAL", "64")
    store, nodes, job = _world()
    store.upsert_job(job)
    a = table(seed=6)
    put_chain([a], store, token=store.latest_index())
    for _ in range(3):
        store.upsert_allocs([mock.alloc_for(job, nodes[0])])
    b = a.copy()
    b[4, 40] = 9.5
    bufs, _ = put_chain([b], store, token=store.latest_index())
    st = constcache.stats()
    assert st["delta_promotions"] == 1 and st["delta_fallbacks"] == 0
    assert st["delta_touched_nodes_last"] >= 1   # journal scoping fed
    np.testing.assert_array_equal(np.asarray(bufs[0]), b)


def test_snapshot_restore_is_a_gap(monkeypatch):
    """restore_from_snapshot replaces alloc state wholesale behind a
    delta-less journal entry (an EXPLICIT mark_uncoverable gap) -- the
    chain must refuse to delta across it."""
    from nomad_tpu.raft.fsm import dump_state

    store, nodes, job = _world()
    store.upsert_job(job)
    store.upsert_allocs([mock.alloc_for(job, nodes[0])])
    a = table(seed=7)
    put_chain([a], store, token=store.latest_index())
    store.restore_from_snapshot(dump_state(store))
    b = a.copy()
    b[0, 1] += 2.0
    bufs, shipped = put_chain([b], store, token=store.latest_index())
    st = constcache.stats()
    assert st["delta_gap_fallbacks"] == 1 and st["delta_promotions"] == 0
    assert shipped == b.nbytes
    np.testing.assert_array_equal(np.asarray(bufs[0]), b)


# ----------------------------------------------------------------------
# a promoted buffer equals the host's array, read back from the device


@pytest.mark.parametrize("chained", [True, False],
                         ids=["delta_src", "no_delta_src"])
def test_device_buffers_equal_the_host_arrays(chained):
    """Every generation's device buffer, read back, is the host's array
    bit for bit (-0.0 and inf included) whether it was installed,
    promoted by a scatter, or -- for a caller that names no
    ``delta_src``, the only way left to ask for no chain -- shipped
    whole through the content path, which builds NO chain state."""
    gens = [table(seed=8)]
    g = gens[0].copy()
    g[3, 33] = -0.0
    gens.append(g)
    g2 = g.copy()
    g2[7, 200] = np.float32(np.inf)
    gens.append(g2)

    store = FakeStore(covered=True)
    for t, a in enumerate(gens):
        if chained:
            bufs, shipped = put_chain([a], store, token=t + 1)
        else:
            bufs, shipped = constcache.device_put_cached(
                [np.array(a)], version=t + 1, cacheable=[False],
                tags=["compact"])
            assert shipped == a.nbytes    # every generation re-ships
        got = np.asarray(jax.device_get(bufs[0]))
        assert got.dtype == a.dtype and got.shape == a.shape
        assert (got.view(np.uint8) == a.view(np.uint8)).all()
    st = constcache.stats()
    if chained:
        assert st["delta_promotions"] == 2 and st["delta_fallbacks"] == 0
    else:
        assert st["chain_entries"] == 0 and store.calls == []
        assert st["delta_promotions"] == 0 and st["delta_reuses"] == 0


def test_chain_on_real_pipelined_dispatch_stays_consistent(monkeypatch):
    """Delta streaming ON through the real pipelined path: fold parity
    and ledger parity hold, and every resident chain buffer equals its
    frozen host shadow bit for bit after the run (the zero-tolerance
    byte-parity net over whatever mix of reuse/promote/fallback the
    schedule produced)."""
    monkeypatch.setenv("NOMAD_TPU_FLAP_THRESHOLD", "2")
    monkeypatch.setenv("NOMAD_TPU_FLAP_BASE_S", "0.3")
    monkeypatch.setenv("NOMAD_TPU_FLAP_MAX_S", "0.6")
    from nomad_tpu.benchkit import run_scale_churn

    out = run_scale_churn(240, n_nodes=20, e_evals=2, per_eval=40,
                          rounds=3, churn_jobs=1, flap_nodes=1,
                          round_timeout_s=120.0)
    assert out["truncated"] is False
    assert out["parity_mismatch"] == 0
    assert out["xfer_ledger_parity"] == 0
    with constcache._LOCK:
        entries = list(constcache._CHAIN.values())
    assert entries, "the pipelined dispatch must populate the chain"
    for ce in entries:
        got = np.asarray(jax.device_get(ce.buf))
        host = np.asarray(ce.host)
        assert got.dtype == host.dtype and got.shape == host.shape
        assert (got.view(np.uint8).reshape(-1)
                == host.view(np.uint8).reshape(-1)).all()


# ----------------------------------------------------------------------
# sanitizer net: promoted entries are clean memos, not aliases


def test_statecheck_clean_on_promoted_entries():
    """With the snapshot-isolation sanitizer armed, a promote-heavy
    sequence must record ZERO stale memos and ZERO aliasing writes:
    chain entries serve AT the dispatch token, and their shadows are
    frozen before publication."""
    from nomad_tpu import statecheck

    statecheck.enable()
    try:
        store, nodes, job = _world()
        store.upsert_job(job)
        a = table(seed=9)
        put_chain([a], store, token=store.latest_index())
        for gen in range(3):
            store.upsert_allocs([mock.alloc_for(job, nodes[0])])
            b = a.copy()
            b[gen, gen] = float(gen + 1)
            put_chain([b], store, token=store.latest_index())
            a = b
        st = constcache.stats()
        assert st["delta_promotions"] >= 1
        sc = statecheck.state()
        assert sc["stale_memo_count"] == 0, sc["stale_memos"]
        assert sc["aliasing_write_count"] == 0, sc["aliasing_writes"]
        assert sc["memo_serves"] >= 1      # the gate actually looked
    finally:
        statecheck.disable()
        statecheck._reset_for_tests()


def test_promoted_shadow_is_frozen():
    """The host shadow entering the chain is a frozen promise about
    the resident buffer; writing through it must raise."""
    store = FakeStore(covered=True)
    a = table(seed=10)
    put_chain([a], store, token=1)
    with constcache._LOCK:
        ce = next(iter(constcache._CHAIN.values()))
    with pytest.raises(ValueError):
        ce.host[0, 0] = 123.0
