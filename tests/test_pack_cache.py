"""Snapshot-scoped pack caches + in-place fused-stack arena (ISSUE 4).

Covers: the node-matrix cache's true-LRU recency (a hit must refresh
move-to-end order), pack_nodes_cached keying (key_hint vs computed key,
filtered-subset isolation, table-bump invalidation), the
feasibility/spread/affinity memos and the incremental usage base (all
parity-gated, bit for bit on the packed trees, against a pack from a
fresh NodeMatrix whose memo is empty), and the tier-1 warm-path
regression guard: two identical fused dispatches where the second must
reuse arena buffers (zero fresh large host allocations) and place
identically to a freshly built stack.
"""
import threading

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.scheduler import Harness
from nomad_tpu.scheduler.context import EvalContext
from nomad_tpu.scheduler.reconcile import AllocPlaceResult
from nomad_tpu.solver import batch as batch_mod
from nomad_tpu.solver.service import TpuPlacementService, dispatch_lane
from nomad_tpu.structs import Affinity, Plan, Spread
from nomad_tpu.tensor import pack as tpack


@pytest.fixture(autouse=True)
def clean_caches():
    tpack._reset_pack_caches_for_tests()
    batch_mod.arena_clear("test baseline")
    yield
    tpack._reset_pack_caches_for_tests()
    batch_mod.arena_clear("test teardown")


def build_world(n_nodes=16, with_allocs=0):
    h = Harness()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"pc-node-{i:04d}"
        n.datacenter = f"dc{i % 2 + 1}"
        n.meta["rack"] = f"rack-{i % 3}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    for k in range(with_allocs):
        j = mock.job(id=f"pc-filler-{k}")
        h.state.upsert_job(j)
        a = mock.alloc_for(j, nodes[k % n_nodes])
        a.client_status = "running"
        h.state.upsert_allocs([a])
    return h, nodes


def make_service(h, nodes, i, count=4, snap=None, kind="binpack"):
    job = mock.job(id=f"pc-job-{i}")
    job.task_groups[0].count = count
    tg = job.task_groups[0]
    if kind == "spread":
        tg.spreads = [Spread(attribute="${meta.rack}", weight=50)]
    elif kind == "affinity":
        job.affinities = [Affinity(l_target="${node.datacenter}",
                                   r_target="dc1", operand="=",
                                   weight=50)]
    plan = Plan(eval_id=f"pc-eval-{i:029d}", priority=50, job=job)
    ctx = EvalContext(snap or h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(count)]
    svc = TpuPlacementService(ctx, job, batch_mode=False, spread_alg=False)
    return svc, tg, places


# ----------------------------------------------------------------------
# Satellite: node-matrix cache is true LRU (hit refreshes recency)


def test_node_matrix_cache_lru_hit_refreshes_recency():
    """8 jobs filtering different node subsets must not thrash the
    hottest entry: after a hit on the oldest entry, inserting one more
    entry evicts the LEAST-recently-USED key, not the oldest-inserted."""
    h, nodes = build_world(4)
    cap = tpack._NODE_MATRIX_CACHE_MAX
    mats = [tpack.pack_nodes_cached(nodes, 100, key_hint=("subset", k))
            for k in range(cap)]
    # touch the oldest-inserted entry: identity hit refreshes recency
    assert tpack.pack_nodes_cached(
        nodes, 100, key_hint=("subset", 0)) is mats[0]
    # one more insert evicts ("subset", 1) -- the true LRU victim
    tpack.pack_nodes_cached(nodes, 100, key_hint=("subset", "new"))
    assert tpack.pack_nodes_cached(
        nodes, 100, key_hint=("subset", 0)) is mats[0]
    assert tpack.pack_nodes_cached(
        nodes, 100, key_hint=("subset", 1)) is not mats[1]


# ----------------------------------------------------------------------
# Satellite: pack_nodes_cached keying contracts


def test_pack_nodes_cached_key_hint_matches_computed_key():
    h, nodes = build_world(6)
    ids = tuple(n.id for n in nodes)
    m_hint = tpack.pack_nodes_cached(nodes, 7, key_hint=ids)
    m_computed = tpack.pack_nodes_cached(nodes, 7)
    assert m_hint is m_computed
    assert m_hint.n_real == len(nodes)
    np.testing.assert_array_equal(
        m_hint.cpu_cap, tpack.pack_nodes(nodes).cpu_cap)


def test_pack_nodes_cached_filtered_subsets_never_share():
    """Two jobs filtering different node subsets at the SAME table
    version must get distinct matrices."""
    h, nodes = build_world(6)
    m_a = tpack.pack_nodes_cached(nodes[:4], 7)
    m_b = tpack.pack_nodes_cached(nodes[1:5], 7)
    assert m_a is not m_b
    assert m_a.node_ids != m_b.node_ids


def test_pack_nodes_cached_table_bump_invalidates():
    h, nodes = build_world(6)
    m_old = tpack.pack_nodes_cached(nodes, 7)
    # same subset, newer table version: fresh matrix
    m_new = tpack.pack_nodes_cached(nodes, 8)
    assert m_old is not m_new
    # the write hook drops stale-version entries entirely
    tpack.note_node_table_write(8)
    assert all(k[0] >= 8 for k in tpack._NODE_MATRIX_CACHE)
    assert tpack.pack_nodes_cached(nodes, 7) is not m_old


def test_store_write_reaches_pack_cache_hook():
    """A real node-table write must drop stale matrices through the
    state/store.py _bump wiring (same path as the const cache)."""
    h, nodes = build_world(4)
    svc, tg, places = make_service(h, nodes, 0)
    lane = svc.pack(tg, places, nodes)
    assert lane is not None
    assert len(tpack._NODE_MATRIX_CACHE) >= 1
    old_keys = set(tpack._NODE_MATRIX_CACHE)
    extra = mock.node()
    extra.id = "pc-node-extra"
    extra.compute_class()
    h.state.upsert_node(extra)
    assert not (set(tpack._NODE_MATRIX_CACHE) & old_keys)


# ----------------------------------------------------------------------
# Spec memos: hits share one frozen array; parity with the uncached path


def test_feasibility_memo_hits_and_freezes(monkeypatch):
    h, nodes = build_world(8)
    snap = h.state.snapshot()
    svc1, tg1, places1 = make_service(h, nodes, 1, snap=snap)
    svc2, tg2, places2 = make_service(h, nodes, 2, snap=snap)
    m = tpack.pack_nodes_cached(nodes, snap.node_table_index)
    f1 = tpack.pack_feasibility_cached(svc1.ctx, None, tg1, nodes,
                                       m.n_pad, places1[0].name, m)
    f2 = tpack.pack_feasibility_cached(svc2.ctx, None, tg2, nodes,
                                       m.n_pad, places2[0].name, m)
    assert f1 is f2                       # same constraint fingerprint
    assert not f1.flags.writeable         # shared => frozen
    fresh = tpack.pack_feasibility(svc1.ctx, None, tg1, nodes, m.n_pad,
                                   alloc_name=places1[0].name, matrix=m)
    np.testing.assert_array_equal(f1, fresh)
    # a different constraint set must not share the entry
    from nomad_tpu.structs import Constraint
    tg2.constraints = [Constraint(l_target="${attr.kernel.name}",
                                  r_target="plan9", operand="=")]
    f3 = tpack.pack_feasibility_cached(svc2.ctx, None, tg2, nodes,
                                       m.n_pad, places2[0].name, m)
    assert f3 is not f1
    assert not f3[:len(nodes)].any()


@pytest.mark.parametrize("kind", ["binpack", "spread", "affinity"])
def test_memoised_pack_equals_pack_from_fresh_matrix(kind):
    """A lane packed from warm memos equals, bit for bit, the lane a
    fresh NodeMatrix (empty memo) packs: every packed tree equal,
    placements identical."""
    h, nodes = build_world(12, with_allocs=6)
    snap = h.state.snapshot()

    def pack_one():
        svc, tg, places = make_service(h, nodes, 3, snap=snap, kind=kind)
        return svc.pack(tg, places, nodes)

    lane_off = pack_one()           # fresh matrix: every memo built
    hits0 = tpack.pack_cache_stats()["hits"]
    lane_on = pack_one()            # same eval id => same shuffle
    assert tpack.pack_cache_stats()["hits"] > hits0
    tpack._reset_pack_caches_for_tests()
    lane_off2 = pack_one()          # and a second fresh matrix agrees
    assert lane_on is not None and lane_off is not None
    assert lane_on.const.spread_vidx.shape[0] == (kind == "spread")
    assert bool(np.asarray(lane_on.const.has_affinity)) \
        == (kind == "affinity")
    for tree in ("const", "init", "batch"):
        for x, y in zip(getattr(lane_off, tree), getattr(lane_off2, tree)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
    for tree in ("const", "init", "batch"):
        a, b = getattr(lane_on, tree), getattr(lane_off, tree)
        for f, (x, y) in zip(a._fields, zip(a, b)):
            np.testing.assert_array_equal(
                np.asarray(x), np.asarray(y), err_msg=f"{tree}.{f}")
    on = dispatch_lane(lane_on)
    off = dispatch_lane(lane_off)
    assert (np.asarray(on[0]) == np.asarray(off[0])).all()


def test_incremental_usage_matches_plain_fold_with_plan_deltas():
    """The snapshot base + plan-delta overlay must equal pack_usage's
    per-eval proposed-alloc fold, including stops, placements and port
    accounting."""
    from nomad_tpu.structs import (
        AllocatedPortMapping, ALLOC_DESIRED_STOP)

    h, nodes = build_world(8, with_allocs=5)
    # give one stored alloc ports so the bitmap path is exercised
    j = mock.job(id="pc-ports")
    h.state.upsert_job(j)
    a_ports = mock.alloc_for(j, nodes[2])
    a_ports.client_status = "running"
    a_ports.allocated_resources.shared.ports = [
        AllocatedPortMapping(label="http", value=20123,
                             host_ip="10.0.0.2")]
    h.state.upsert_allocs([a_ports])

    snap = h.state.snapshot()
    svc, tg, places = make_service(h, nodes, 4, snap=snap)
    # plan deltas: stop one stored alloc, place one new
    stored = [a for a in snap.allocs()
              if not a.client_terminal_status()][0]
    import copy
    stop = copy.copy(stored)
    stop.desired_status = ALLOC_DESIRED_STOP
    svc.ctx.plan.node_update.setdefault(stored.node_id, []).append(stop)
    new_job = mock.job(id="pc-placed")
    placed_alloc = mock.alloc_for(new_job, nodes[5])
    svc.ctx.plan.node_allocation.setdefault(
        nodes[5].id, []).append(placed_alloc)

    matrix = tpack.pack_nodes_cached(nodes, snap.node_table_index)
    inc = svc._pack_usage_incremental(matrix, nodes, tg)
    # port-carrying bases are refolded per eval (the 80MB-bitmap trade
    # _pack_usage_from_table's fold cache makes): no memo hit expected
    before = tpack.pack_cache_stats()
    inc2 = svc._pack_usage_incremental(matrix, nodes, tg)
    after = tpack.pack_cache_stats()
    assert after["usage_base_hits"] == before["usage_base_hits"]
    assert after["usage_base_misses"] == before["usage_base_misses"] + 1

    from nomad_tpu.tensor import pack_usage
    proposed = {n.id: svc.ctx.proposed_allocs(n.id) for n in nodes}
    plain = pack_usage(matrix, proposed, svc.job.id, tg.name,
                       svc.job.namespace, nodes)
    for f in ("used_cpu", "used_mem", "used_disk", "placed_jobtg",
              "placed_job", "dyn_used"):
        np.testing.assert_array_equal(
            getattr(inc, f), getattr(plain, f), err_msg=f)
        np.testing.assert_array_equal(
            getattr(inc2, f), getattr(plain, f), err_msg=f)
    if plain.port_bitmap is None:
        assert inc.port_bitmap is None
    else:
        np.testing.assert_array_equal(inc.port_bitmap, plain.port_bitmap)


def test_incremental_usage_base_memoized_per_snapshot():
    """Port-free bases ARE memoized: the second eval of one snapshot
    hits the base, and a store write (new snapshot) refolds."""
    h, nodes = build_world(8, with_allocs=4)
    snap = h.state.snapshot()
    matrix = tpack.pack_nodes_cached(nodes, snap.node_table_index)
    svc1, tg1, _ = make_service(h, nodes, 50, snap=snap)
    svc1._pack_usage_incremental(matrix, nodes, tg1)
    before = tpack.pack_cache_stats()["usage_base_hits"]
    svc2, tg2, _ = make_service(h, nodes, 51, snap=snap)
    u2 = svc2._pack_usage_incremental(matrix, nodes, tg2)
    assert tpack.pack_cache_stats()["usage_base_hits"] == before + 1

    from nomad_tpu.tensor import pack_usage
    proposed = {n.id: svc2.ctx.proposed_allocs(n.id) for n in nodes}
    plain = pack_usage(matrix, proposed, svc2.job.id, tg2.name,
                       svc2.job.namespace, nodes)
    for f in ("used_cpu", "used_mem", "used_disk", "placed_jobtg",
              "placed_job", "dyn_used"):
        np.testing.assert_array_equal(
            getattr(u2, f), getattr(plain, f), err_msg=f)

    # a write mints a new snapshot: the fresh base must see the new
    # alloc even while the old matrix stays cached
    j = mock.job(id="pc-late")
    h.state.upsert_job(j)
    a = mock.alloc_for(j, nodes[0])
    a.client_status = "running"
    h.state.upsert_allocs([a])
    snap2 = h.state.snapshot()
    svc3, tg3, _ = make_service(h, nodes, 52, snap=snap2)
    m2 = tpack.pack_nodes_cached(nodes, snap2.node_table_index)
    u3 = svc3._pack_usage_incremental(m2, nodes, tg3)
    cr = a.allocated_resources.comparable()
    assert u3.used_cpu[0] == u2.used_cpu[0] + cr.cpu_shares


# ----------------------------------------------------------------------
# Tier-1 warm-path regression guard: arena reuse + kill-switch parity


def test_warm_fused_dispatch_reuses_arena_and_matches_fresh_stack():
    """Two identical fused dispatches: the second must be served from
    the arena pool (entry reuse, zero fresh large host allocations) and
    place identically to a freshly built stack of freshly packed lanes."""
    from nomad_tpu.solver.batch import fuse_and_solve

    h, nodes = build_world(16)

    def pack_lanes(lo):
        snap = h.state.snapshot()
        lanes = []
        for i in range(3):
            svc, tg, places = make_service(h, nodes, lo + i, snap=snap)
            lane = svc.pack(tg, places, nodes)
            assert lane is not None
            lanes.append(lane)
        return lanes

    lanes = pack_lanes(10)
    s0 = batch_mod.arena_state()
    first = fuse_and_solve(lanes)
    s1 = batch_mod.arena_state()
    assert s1["allocs"] >= s0["allocs"] + 1
    second = fuse_and_solve(lanes)
    s2 = batch_mod.arena_state()
    # warm generation: pool served it -- no fresh buffer allocation
    assert s2["reuses"] >= s1["reuses"] + 1
    assert s2["allocs"] == s1["allocs"], "warm path allocated buffers"
    for a, b in zip(first, second):
        assert (a[0] == b[0]).all()
        assert (a[2] == b[2]).all()

    # empty pool + empty memos: same lanes, fresh buffers + a pack
    # from a fresh matrix, same placements
    batch_mod.arena_clear("fresh reference")
    tpack._reset_pack_caches_for_tests()
    off_lanes = pack_lanes(10)      # same eval ids => same shuffle
    off = fuse_and_solve(off_lanes)
    assert batch_mod.arena_state()["allocs"] == s2["allocs"] + 1
    for a, b in zip(first, off):
        assert (a[0] == b[0]).all()


def test_arena_padding_rows_skipped_but_masked_inert():
    """With e_pad > e_real, a reused entry skips the padding-row fill
    (pad_fills_skipped climbs) yet results stay identical to each
    lane's solo dispatch -- stale rows are valid lanes masked inactive."""
    from nomad_tpu.solver.batch import fuse_and_solve

    h, nodes = build_world(16)
    snap = h.state.snapshot()
    lanes = []
    for i in range(3):
        svc, tg, places = make_service(h, nodes, 20 + i, snap=snap)
        lanes.append(svc.pack(tg, places, nodes))
    solo = [dispatch_lane(lane) for lane in lanes]
    res1 = fuse_and_solve(lanes, e_pad_hint=8)     # cold: pads filled
    s1 = batch_mod.arena_state()
    res2 = fuse_and_solve(lanes, e_pad_hint=8)     # warm: pads skipped
    s2 = batch_mod.arena_state()
    assert s2["pad_fills_skipped"] >= s1["pad_fills_skipped"] + 1
    for res, ref in zip(res1, solo):
        assert (res[0] == ref[0]).all()
    for res, ref in zip(res2, solo):
        assert (res[0] == ref[0]).all()
    # shrinking e_real on a reused entry: rows beyond the new e_real
    # held REAL lanes last generation; active masking keeps them inert
    sub = lanes[:2]
    res3 = fuse_and_solve(sub, e_pad_hint=8)
    for res, ref in zip(res3, solo[:2]):
        assert (res[0] == ref[0]).all()


def test_arena_bounds(monkeypatch):
    from nomad_tpu.solver.batch import _ARENA

    specs = {"t": [((4, 8), np.dtype(np.float64))]}
    e1, r1 = _ARENA.acquire(("k1", 4, 8), specs)
    assert not r1
    _ARENA.release(e1)
    e2, r2 = _ARENA.acquire(("k1", 4, 8), specs)
    assert r2 and e2 is e1
    # shape mismatch under the same key never reuses
    e3, r3 = _ARENA.acquire(("k1", 4, 8),
                            {"t": [((4, 16), np.dtype(np.float64))]})
    assert not r3
    _ARENA.release(e2)
    _ARENA.release(e3)
    # entry bound evicts oldest free entries
    monkeypatch.setenv("NOMAD_TPU_PACK_ARENA_ENTRIES", "1")
    held = [_ARENA.acquire((f"k{i}", 1, 1),
                           {"t": [((2, 2), np.dtype(np.float64))]})[0]
            for i in range(3)]
    for ent in held:
        _ARENA.release(ent)
    assert batch_mod.arena_state()["entries"] <= 1


def test_pipeline_staged_prepare_overlaps_and_matches_sync():
    """Barrier rounds route through the prepare stage (arena fill on
    the intake thread): staged_total climbs and results stay
    bit-identical to each lane's solo dispatch."""
    from nomad_tpu.solver.batch import SolveBarrier, pipeline_state

    h, nodes = build_world(16)
    snap = h.state.snapshot()
    lanes = []
    for i in range(3):
        svc, tg, places = make_service(h, nodes, 30 + i, snap=snap)
        lanes.append(svc.pack(tg, places, nodes))
    solo = [dispatch_lane(lane) for lane in lanes]

    def run_barrier(depth):
        barrier = SolveBarrier(participants=len(lanes), depth=depth)
        out = {}

        def worker(i):
            out[i] = barrier.solve(lanes[i])

        threads = [threading.Thread(target=worker, args=(i,))
                   for i in range(len(lanes))]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert sorted(out) == list(range(len(lanes)))
        return out

    staged0 = pipeline_state()["staged_total"]
    piped = run_barrier(depth=2)
    assert pipeline_state()["staged_total"] >= staged0 + 1
    for i in range(len(lanes)):
        assert (piped[i][0] == solo[i][0]).all()


def test_pack_telemetry_emitted():
    """service.pack must time itself into nomad.solver.pack_ms and
    count cache hits/misses; guard.state() must surface the pack layer."""
    from nomad_tpu.server.telemetry import metrics
    from nomad_tpu.solver import guard

    metrics.reset()
    h, nodes = build_world(8)
    snap = h.state.snapshot()
    for i in (40, 41):
        svc, tg, places = make_service(h, nodes, i, snap=snap)
        assert svc.pack(tg, places, nodes) is not None
    snap_m = metrics.snapshot()
    assert snap_m["samples"]["nomad.solver.pack_ms"]["count"] == 2
    assert snap_m["counters"].get("nomad.solver.pack_cache_miss", 0) >= 1
    assert snap_m["counters"].get("nomad.solver.pack_cache_hit", 0) >= 1
    st = guard.state()
    assert st["pack_cache"]["hits"] + st["pack_cache"]["matrix_hits"] >= 1
    assert "reuses" in st["pack_arena"]
    assert st["pack"]["cache_hit"] >= 1
