"""The wave lane's native precompute (native.wave_compact, through
binpack.wave_lane_compact) against its numpy oracle
(binpack.wavefront_compact_host): all five outputs equal, bit for bit,
on seeded lanes in both precisions; the fixpoint's patched re-solve
against the copy-then-patch view it replaced; and the numpy stand-in
where the library cannot load, counted."""
import jax
import numpy as np
import pytest

from nomad_tpu import native
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.solver import binpack
from nomad_tpu.solver.binpack import (
    WAVE_B, NodeConst, NodeState, PlacementBatch, WavePatch,
    wave_lane_compact, wavefront_compact_host)

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="needs the native library")


def _lane(seed, dtype, *, n=600, p=40, spreads=0, affinity=False,
          distinct=None, n_dyn=0, static=False, devices=False,
          fit_share=0.85, ask=(500.0, 256.0, 300.0), inert=False):
    """A uniform-ask lane whose usage sits at, just under and just over
    the multiples of the ask, so every fit correction is exercised."""
    rng = np.random.default_rng(seed)
    dt = np.dtype(dtype)
    cpu_cap = rng.choice([2000.0, 4000.0, 3999.9, 8000.0], n).astype(dt)
    mem_cap = rng.choice([4096.0, 8192.0, 8191.7], n).astype(dt)
    disk_cap = np.full(n, 90 * 1024.0, dtype=dt)

    def usage(cap, a):
        if a <= 0:
            return rng.uniform(0, 1000, n).astype(dt)
        k = rng.integers(0, 8, n)
        u = (cap - (k * dt.type(a)).astype(dt)).astype(dt)
        u = np.where(rng.random(n) < 0.1, u + dt.type(37.5), u)
        # a few units in the last place either way: the float quotient
        # then misses by one, and the corrections decide
        steps = rng.integers(-3, 4, n)
        for s in (1, 2, 3):
            u = np.where(steps >= s, np.nextafter(u, dt.type(np.inf)), u)
            u = np.where(steps <= -s, np.nextafter(u, dt.type(-np.inf)), u)
        return np.maximum(u, 0).astype(dt)
    used_cpu = usage(cpu_cap, ask[0])
    used_mem = usage(mem_cap, ask[1])
    used_disk = usage(disk_cap, ask[2])
    feasible = rng.random(n) < fit_share
    placed = rng.integers(0, 3, n).astype(np.int32)
    placed_job = (placed + rng.integers(0, 2, n)).astype(np.int32)
    aff = (rng.choice([-1.0, -0.5, 0.0, 0.25, 1.0], n).astype(dt)
           if affinity else np.zeros(n, dtype=dt))
    V = 5
    vidx = rng.integers(-1, V, (spreads, n)).astype(np.int32)
    extra = {}
    init_extra = {}
    if devices:
        R, G = 1, 2
        extra = dict(dev_aff=np.zeros((R, G, n), dtype=np.float32),
                     dev_count=np.array([1], dtype=np.int32))
        init_extra = dict(dev_free=rng.integers(-1, 4, (R, G, n))
                          .astype(np.int32))
    const = NodeConst(
        cpu_cap=cpu_cap, mem_cap=mem_cap, disk_cap=disk_cap,
        feasible=feasible, affinity=aff,
        has_affinity=np.asarray(affinity),
        distinct_hosts=np.asarray(distinct is not None),
        distinct_job_level=np.asarray(distinct == "job"),
        spread_vidx=vidx,
        spread_desired=np.full((spreads, V), -1.0, dtype=dt),
        spread_has_targets=np.zeros(spreads, dtype=bool),
        spread_weights=np.full(spreads, 50.0, dtype=dt),
        spread_sum_weights=np.asarray(50.0 * spreads, dtype=dt),
        n_spreads=np.asarray(spreads, dtype=np.int32), **extra)
    init = NodeState(
        used_cpu=used_cpu, used_mem=used_mem, used_disk=used_disk,
        placed=placed, placed_job=placed_job,
        static_free=(rng.random(n) < 0.6) if static
        else np.ones(n, dtype=bool),
        dyn_avail=rng.integers(-3, 30, n).astype(np.int32),
        spread_counts=rng.integers(0, 3, (spreads, V)).astype(np.int32),
        **init_extra)
    batch = PlacementBatch(
        ask_cpu=np.full(p, ask[0], dtype=dt),
        ask_mem=np.full(p, ask[1], dtype=dt),
        ask_disk=np.full(p, ask[2], dtype=dt),
        n_dyn_ports=np.full(p, n_dyn, dtype=np.int32),
        has_static=np.full(p, static, dtype=bool),
        limit=np.full(p, 8, dtype=np.int32),
        count=np.full(p, p, dtype=np.int32),
        penalty_idx=rng.choice([-1, -1, -1, 3], p).astype(np.int32),
        active=np.zeros(p, dtype=bool) if inert else np.ones(p, dtype=bool))
    return const, init, batch


def _assert_same(got, want):
    for g, w in zip(got[:4], want[:4]):
        assert g.dtype == w.dtype and g.shape == w.shape
        assert np.array_equal(g, w)
    for g, w in zip(got[4], want[4]):
        assert np.asarray(g).dtype == np.asarray(w).dtype
        assert np.array_equal(g, w)


CASES = {
    "plain": {},
    "spreads": dict(spreads=2),
    "affinity": dict(affinity=True),
    "distinct_group": dict(distinct="group"),
    "distinct_job": dict(distinct="job"),
    "dynamic_ports": dict(n_dyn=3),
    "static_ports": dict(static=True),
    "devices": dict(devices=True),
    "mostly_infeasible": dict(fit_share=0.02),
    "ask_of_zero": dict(ask=(0.0, 256.0, 0.0)),
    "fractional_ask": dict(ask=(333.3, 100.01, 0.5)),
    "inert": dict(inert=True),
    "everything": dict(spreads=3, affinity=True, distinct="job", n_dyn=2,
                       static=True),
}


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("case", sorted(CASES))
@pytest.mark.parametrize("seed", [0, 1, 2147483711])
def test_native_precompute_matches_numpy(case, dtype, seed):
    const, init, batch = _lane(seed, dtype, **CASES[case])
    for p_pad, B in ((None, WAVE_B), (64, WAVE_B), (64, 128)):
        want = wavefront_compact_host(const, init, batch, dtype,
                                      p_pad=p_pad, B=B)
        got = wave_lane_compact(const, init, batch, dtype, p_pad=p_pad,
                                B=B)
        _assert_same(got, want)


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("short", [-1, 0, 1], ids=["one_short", "exactly_c",
                                                  "one_over"])
def test_the_walk_stops_at_the_last_row_it_needs(dtype, short):
    """Fewer fitting nodes than rows, exactly as many, one more: the
    table is the numpy one, and the walk reads up to the C-th fitting
    node and no further."""
    const, init, batch = _lane(7, dtype, n=400, p=8)
    C = 8 + WAVE_B
    fit = np.nonzero(wavefront_compact_host(
        const, init, batch, dtype)[0][:, 7] >= 0)[0]
    assert fit.size == C            # the lane has more than enough room
    # keep C + short of the nodes with room
    c_all = np.zeros(400, dtype=bool)
    full = wavefront_compact_host(const, init, batch, dtype, p_pad=400)[0]
    pos = full[full[:, 7] >= 0, 7].astype(int)
    c_all[pos[:C + short]] = True
    const = const._replace(feasible=const.feasible & c_all)
    metrics.reset()
    want = wavefront_compact_host(const, init, batch, dtype)
    got = wave_lane_compact(const, init, batch, dtype)
    _assert_same(got, want)
    counters = metrics.snapshot()["counters"]
    scanned = counters["nomad.solver.wave_precompute_nodes_scanned"]
    last = pos[min(C, C + short) - 1]
    assert scanned == (400 if short < 0 else last + 1)
    assert counters["nomad.solver.wave_precompute_nodes_total"] == 400
    assert counters["nomad.solver.wave_precomputes"] == 1


@pytest.mark.parametrize("dtype", ["float32", "float64"])
@pytest.mark.parametrize("seed", [3, 4])
def test_a_patch_is_the_init_it_stands_for(dtype, seed):
    """Usage, ports and placed counts overridden at some positions read
    as the same overrides written into copies of the arrays."""
    const, init, batch = _lane(seed, dtype, spreads=1, distinct="group",
                               n_dyn=2)
    rng = np.random.default_rng(seed)
    pos = np.sort(rng.choice(600, 90, replace=False)).astype(np.int64)
    patch = WavePatch(
        pos=pos,
        used_cpu=(init.used_cpu[pos] + rng.choice([0, 500, 4000], 90))
        .astype(init.used_cpu.dtype),
        used_mem=init.used_mem[pos].copy(),
        used_disk=init.used_disk[pos].copy(),
        dyn_avail=(init.dyn_avail[pos] - 2).astype(np.int32),
        placed=(init.placed[pos] + 1).astype(np.int32),
        placed_job=(init.placed_job[pos] + 1).astype(np.int32))
    want = wavefront_compact_host(const, patch.applied_to(init), batch,
                                  dtype, p_pad=64)
    got = wave_lane_compact(const, init, batch, dtype, p_pad=64,
                            patch=patch)
    _assert_same(got, want)


@pytest.mark.parametrize("patched", [False, True])
def test_without_the_library_numpy_answers_and_is_counted(monkeypatch,
                                                          patched):
    const, init, batch = _lane(5, "float32", spreads=1)
    patch = None
    if patched:
        pos = np.array([2, 9, 40], dtype=np.int64)
        patch = WavePatch(pos, init.used_cpu[pos] + 1000,
                          init.used_mem[pos], init.used_disk[pos],
                          init.dyn_avail[pos], init.placed[pos] + 1,
                          init.placed_job[pos])
    want = wave_lane_compact(const, init, batch, "float32", patch=patch)
    metrics.reset()
    monkeypatch.setattr(binpack.native, "load", lambda: None)
    got = wave_lane_compact(const, init, batch, "float32", patch=patch)
    _assert_same(got, want)
    counters = metrics.snapshot()["counters"]
    assert counters["nomad.solver.wave_precompute_fallbacks"] == 1
    assert "nomad.solver.wave_precomputes" not in counters


# ---------------------------------------------------------------------
# the fixpoint's re-solve: a patch against the copies it replaced


def _copied_init(lane, pos_of, ledger, free_of, foreign, accepted_own):
    """The re-solve's view of ``lane`` as the fixpoint built it before
    the patch: copies of the lane's usage with the ledger, the other
    barrier's bookings and the lane's own placements written in."""
    const, init = lane.const, lane.init
    used_cpu = np.array(init.used_cpu, copy=True)
    used_mem = np.array(init.used_mem, copy=True)
    used_disk = np.array(init.used_disk, copy=True)
    dyn_avail = np.array(init.dyn_avail, copy=True)
    for nid, f in ledger.items():
        p = pos_of(nid)
        if p is None:
            continue
        f = free_of(nid, f)
        used_cpu[p] = float(const.cpu_cap[p]) - f[0]
        used_mem[p] = float(const.mem_cap[p]) - f[1]
        used_disk[p] = float(const.disk_cap[p]) - f[2]
        dyn_avail[p] = f[3]
    if foreign is not None:
        u = lane.usage_index
        for nid, d in foreign.deductions(
                float("inf") if u is None else u).items():
            p = None if nid in ledger else pos_of(nid)
            if p is not None:
                used_cpu[p] += d[0]
                used_mem[p] += d[1]
                used_disk[p] += d[2]
                dyn_avail[p] -= d[3]
    placed = np.array(init.placed, copy=True)
    placed_job = np.array(init.placed_job, copy=True)
    spread_counts = np.array(init.spread_counts, copy=True)
    S = spread_counts.shape[0] if spread_counts.ndim else 0
    for pos in accepted_own:
        placed[pos] += 1
        placed_job[pos] += 1
        for s in range(S):
            v = int(const.spread_vidx[s, pos])
            if v >= 0:
                spread_counts[s, v] += 1
    return init._replace(
        used_cpu=used_cpu, used_mem=used_mem, used_disk=used_disk,
        dyn_avail=dyn_avail, placed=placed, placed_job=placed_job,
        spread_counts=spread_counts)


def _fixpoint_worlds():
    """Worlds of tests/test_batch_fixpoint.py: a tight node and spares,
    lanes of one barrier that pile onto it, and the other barrier's
    bookings delivered and not committed."""
    from nomad_tpu.server.inflight import ForeignView
    from nomad_tpu.solver.service import dispatch_lane
    from tests.test_batch_fixpoint import build_world, pack_lane

    worlds = {}
    h, nodes, _reg = build_world(tight_fits=2, spares=3)
    lane = pack_lane(h, nodes, "solo")
    solo = dispatch_lane(lane)
    first = lane.nodes[int(np.asarray(lane.order)[int(solo[0][0])])].id
    cap = next(n for n in nodes if n.id == first).node_resources
    fill = (float(cap.cpu.cpu_shares), float(cap.memory.memory_mb), 0.0, 0)
    booked = {first: [(fill, None)]}
    booked.update({n.id: [((250.0, 128.0, 0.0, 0), None)]
                   for n in nodes if n.id != first})
    worlds["foreign_fills_the_first_choice"] = (
        [lane], [solo], ForeignView(booked))

    h, nodes, _reg = build_world(tight_fits=1, spares=8)
    lanes = [pack_lane(h, nodes, f"pile{i}", count=3) for i in range(4)]
    results = [dispatch_lane(x) for x in lanes]
    worlds["own_lanes_only"] = (lanes, results, None)
    spares = nodes[1:5]
    worlds["own_lanes_and_foreign"] = (lanes, results, ForeignView({
        n.id: [((float(n.node_resources.cpu.cpu_shares) - 600.0, 512.0,
                 0.0, 0), None)] for n in spares}))
    return worlds


@pytest.mark.parametrize("world", ["foreign_fills_the_first_choice",
                                   "own_lanes_and_foreign",
                                   "own_lanes_only"])
def test_patched_resolve_is_the_copied_one(world, monkeypatch):
    from nomad_tpu.solver import batch as batch_mod
    from nomad_tpu.solver.binpack import solve_lane_fused

    lanes, results, foreign = _fixpoint_worlds()[world]
    seen = []
    real = batch_mod._ledger_patch

    def compare(lane, pos_of, ledger, free_of, foreign, accepted_own):
        patch, counts = real(lane, pos_of, ledger, free_of, foreign,
                             accepted_own)
        copied = _copied_init(lane, pos_of, ledger, free_of, foreign,
                              accepted_own)
        patched = patch.applied_to(lane.init._replace(spread_counts=counts))
        for name in NodeState._fields:
            a, b = getattr(patched, name), getattr(copied, name)
            assert np.asarray(a).dtype == np.asarray(b).dtype, name
            assert np.array_equal(a, b), name
        sub = jax.tree_util.tree_map(
            lambda a: np.asarray(a)[:1], lane.batch)
        kw = dict(spread_alg=lane.spread_alg, dtype_name=lane.dtype_name,
                  wave=True)
        got = solve_lane_fused(lane.const, lane.init._replace(
            spread_counts=counts), sub, patch=patch, **kw)
        want = solve_lane_fused(lane.const, copied, sub, **kw)
        for g, w in zip(got, want):
            assert np.array_equal(g, w)
        seen.append(len(patch.pos))
        return patch, counts

    monkeypatch.setattr(batch_mod, "_ledger_patch", compare)
    results = [tuple(np.array(x) for x in r) for r in results]
    batch_mod._cross_lane_fixpoint(lanes, results, {}, foreign)
    assert seen, "the world re-solved nothing"


@pytest.mark.parametrize("short", ["node_array", "spread_table", "patch"])
def test_arrays_of_other_lengths_are_refused(short):
    const, init, batch = _lane(6, "float32", spreads=1)
    patch = WavePatch(np.array([1, 2], dtype=np.int64),
                      *[np.asarray(getattr(init, f))[[1, 2]]
                        for f in WavePatch._fields[1:]])
    if short == "node_array":
        init = init._replace(placed=init.placed[:-1])
    elif short == "spread_table":
        const = const._replace(spread_vidx=const.spread_vidx[:, :-1])
    else:
        patch = patch._replace(used_mem=patch.used_mem[:1])
    with pytest.raises(ValueError):
        wave_lane_compact(const, init, batch, "float32", patch=patch)
