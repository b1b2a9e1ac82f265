"""Device-resident const cache (solver/constcache.py, ISSUE 2): content
addressing, LRU/byte bounds, version-tagged invalidation on node-table
writes, and the dispatch-bytes accounting."""
import numpy as np
import pytest

from nomad_tpu.server.telemetry import metrics
from nomad_tpu.solver import constcache


@pytest.fixture(autouse=True)
def clean_cache(monkeypatch):
    constcache._reset_for_tests()
    metrics.reset()
    yield
    constcache._reset_for_tests()


def arr(fill, n=4096, dtype=np.float32):
    return np.full(n, fill, dtype=dtype)


def test_hit_miss_and_byte_accounting():
    a, b = arr(1.0), arr(2.0)
    bufs1, shipped1 = constcache.device_put_cached([a, b], version=7)
    assert shipped1 == a.nbytes + b.nbytes
    # same content -> both hit, zero bytes on the wire
    bufs2, shipped2 = constcache.device_put_cached(
        [arr(1.0), arr(2.0)], version=7)
    assert shipped2 == 0
    st = constcache.stats()
    assert st["hits"] == 2 and st["misses"] == 2
    assert st["bytes_saved_total"] == a.nbytes + b.nbytes
    assert st["resident_bytes"] == a.nbytes + b.nbytes
    # pinned buffers are REUSED, not re-uploaded
    assert bufs2[0] is bufs1[0] and bufs2[1] is bufs1[1]
    # results are faithful
    assert (np.asarray(bufs2[0]) == a).all()
    # dispatch-bytes metrics recorded per call
    snap = metrics.snapshot()
    assert snap["counters"]["nomad.solver.dispatch_bytes_total"] == \
        shipped1
    assert snap["gauges"]["nomad.solver.dispatch_bytes"]["count"] == 2


def test_small_arrays_ship_fresh():
    """Delta-sized arrays (below the min-bytes threshold) always ship:
    they ARE the streaming traffic, and caching them would churn the
    LRU."""
    small = np.arange(8, dtype=np.int32)
    _, s1 = constcache.device_put_cached([small])
    _, s2 = constcache.device_put_cached([small])
    assert s1 == s2 == small.nbytes
    assert constcache.stats()["entries"] == 0


def test_cacheable_mask_excludes_delta_buffers():
    a, b = arr(3.0), arr(4.0)
    constcache.device_put_cached([a, b], cacheable=[True, False])
    st = constcache.stats()
    assert st["entries"] == 1
    _, shipped = constcache.device_put_cached(
        [a, b], cacheable=[True, False])
    assert shipped == b.nbytes          # only the delta re-ships


def test_lru_bound(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_CONST_CACHE_ENTRIES", "2")
    for i in range(4):
        constcache.device_put_cached([arr(float(i))])
    st = constcache.stats()
    assert st["entries"] == 2
    assert st["evictions"] == 2
    # the most recent entries survive
    _, shipped = constcache.device_put_cached([arr(3.0)])
    assert shipped == 0


def test_node_table_write_drops_stale_versions():
    constcache.device_put_cached([arr(1.0)], version=5)
    constcache.device_put_cached([arr(2.0)], version=9)
    constcache.note_node_table_write(9)
    st = constcache.stats()
    assert st["entries"] == 1           # version-5 entry dropped
    assert st["invalidations"] == 1
    # the surviving entry still hits
    _, shipped = constcache.device_put_cached([arr(2.0)], version=9)
    assert shipped == 0


def test_state_store_write_invalidates_through_the_hook():
    """A real node-table write must reach the cache (state/store.py
    _bump wiring)."""
    from nomad_tpu import mock
    from nomad_tpu.state.store import StateStore

    store = StateStore()
    n = mock.node()
    n.compute_class()
    idx = store.upsert_node(n)
    constcache.device_put_cached([arr(1.0)], version=idx)
    n2 = mock.node()
    n2.compute_class()
    store.upsert_node(n2)
    assert constcache.stats()["entries"] == 0


def test_invalidate_all():
    constcache.device_put_cached([arr(1.0)], version=1)
    constcache.invalidate_all("test")
    st = constcache.stats()
    assert st["entries"] == 0 and st["resident_bytes"] == 0
    assert st["invalidations"] == 1


def test_fused_dispatch_ships_fewer_bytes_warm():
    """Integration: the second identical fused dispatch must ship at
    least 2x fewer bytes (const tables resident) with bit-identical
    results; a node-table write then forces a re-upload."""
    from nomad_tpu import mock
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService, dispatch_lane
    from nomad_tpu.structs import Plan

    h = Harness()
    nodes = []
    for i in range(24):
        n = mock.node()
        n.id = f"cc-node-{i:04d}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    job = mock.job(id="cc-job")
    job.task_groups[0].count = 6
    tg = job.task_groups[0]
    plan = Plan(eval_id="cc-eval-000000000000000000000000001",
                priority=50, job=job)
    ctx = EvalContext(h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(6)]
    svc = TpuPlacementService(ctx, job, batch_mode=False, spread_alg=False)
    lane = svc.pack(tg, places, nodes)
    assert lane is not None

    def bytes_total():
        return metrics.snapshot()["counters"].get(
            "nomad.solver.dispatch_bytes_total", 0)

    b0 = bytes_total()
    cold = dispatch_lane(lane)
    cold_bytes = bytes_total() - b0
    b0 = bytes_total()
    warm = dispatch_lane(lane)
    warm_bytes = bytes_total() - b0
    assert (np.asarray(cold[0]) == np.asarray(warm[0])).all()
    assert cold_bytes > 0
    assert warm_bytes * 2 <= cold_bytes, (cold_bytes, warm_bytes)

    # node-table write -> stale fleet tables dropped -> full re-upload
    extra = mock.node()
    extra.id = "cc-node-extra"
    extra.compute_class()
    h.state.upsert_node(extra)
    assert constcache.stats()["resident_bytes"] == 0
