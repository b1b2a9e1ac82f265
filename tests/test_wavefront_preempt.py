"""Windowed-preemption wavefront kernel parity vs the dense preempt
kernel (solve_lane_wave_preempt vs solve_placements_preempt).

The dense kernel is itself parity-gated against the host oracle
(tests/test_preemption_tpu.py places AND evicts identically), so dense
equality here closes the chain: wave == dense == host. The windowed
kernel takes a lane where no node fits its ask without eviction (the
service routes the rest to the dense kernel), so every world here starts
so: each node holds at least 3,001 of its 4,000 MHz, beyond its
candidates where they hold less (the job's own or other allocs no
eviction takes). Worlds sweep the dimensions the window design must
preserve: priority tiers (ascending group gating), max_parallel
penalties (group counts in the carry), distinct_hosts, affinity columns,
reschedule penalties, multi-copy saturation (the deferred zombie shift,
and slots that fit without eviction once the lane evicted there), and
inert padding lanes in the batched form."""
import random

import numpy as np
import pytest

from nomad_tpu.solver.binpack import (
    NodeConst, NodeState, PlacementBatch, PreemptState, PreemptTables,
    solve_lane_wave_preempt, solve_placements_preempt,
    wavefront_preempt_pristine,
)


def _world(rng, n=40, p=16, a=6, limit=5, fill=0.9, distinct=False,
           affinity=False, maxp=0, n_groups=8, pen_frac=0.0):
    """Random preempt world: n nodes, a candidate slots each, high fill
    from low-priority candidates so placements regularly need eviction."""
    dt = np.float64
    cpu_cap = np.full(n, 4000.0, dtype=dt)
    mem_cap = np.full(n, 8192.0, dtype=dt)
    disk_cap = np.full(n, 102400.0, dtype=dt)
    feasible = np.ones(n, dtype=bool)
    for i in range(n):
        if rng.random() < 0.1:
            feasible[i] = False

    ccpu = np.zeros((n, a), dtype=dt)
    cmem = np.zeros((n, a), dtype=dt)
    cdisk = np.zeros((n, a), dtype=dt)
    cprio = np.zeros((n, a), dtype=np.int32)
    cmaxp = np.zeros((n, a), dtype=np.int32)
    cgrp = np.full((n, a), -1, dtype=np.int32)
    cvalid = np.zeros((n, a), dtype=bool)
    used = np.zeros(n, dtype=dt)
    used_m = np.zeros(n, dtype=dt)
    for i in range(n):
        budget = fill * 4000.0
        k = 0
        while k < a and used[i] + 700 <= budget:
            c = rng.choice([500.0, 700.0, 900.0])
            if used[i] + c > budget:
                break
            ccpu[i, k] = c
            cmem[i, k] = rng.choice([512.0, 1024.0])
            cdisk[i, k] = 150.0
            cprio[i, k] = rng.choice([10, 20, 30, 40, 80])
            cmaxp[i, k] = maxp if rng.random() < 0.5 else 0
            cgrp[i, k] = rng.randrange(n_groups)
            cvalid[i, k] = True
            used[i] += c
            used_m[i] += cmem[i, k]
            k += 1
    used = np.maximum(used, 3001.0)

    aff = np.zeros(n, dtype=dt)
    if affinity:
        for i in range(n):
            if rng.random() < 0.3:
                aff[i] = rng.choice([-0.5, 0.25, 0.5])

    const = NodeConst(
        cpu_cap=cpu_cap, mem_cap=mem_cap, disk_cap=disk_cap,
        feasible=feasible, affinity=aff,
        has_affinity=np.bool_(affinity),
        distinct_hosts=np.bool_(distinct),
        distinct_job_level=np.bool_(False),
        spread_vidx=np.zeros((0, n), dtype=np.int32),
        spread_desired=np.zeros((0, 0), dtype=dt),
        spread_has_targets=np.zeros(0, dtype=bool),
        spread_weights=np.zeros(0, dtype=dt),
        spread_sum_weights=dt(0.0),
        n_spreads=np.int32(0))
    init = NodeState(
        used_cpu=used, used_mem=used_m,
        used_disk=np.full(n, 600.0, dtype=dt),
        placed=np.zeros(n, dtype=np.int32),
        placed_job=np.zeros(n, dtype=np.int32),
        static_free=np.ones(n, dtype=bool),
        dyn_avail=np.full(n, 12001, dtype=np.int32),
        spread_counts=np.zeros((0, 0), dtype=np.int32))
    pen = np.full(p, -1, dtype=np.int32)
    if pen_frac:
        for k in range(p):
            if rng.random() < pen_frac:
                pen[k] = rng.randrange(n)
    batch = PlacementBatch(
        ask_cpu=np.full(p, 1000.0, dtype=dt),
        ask_mem=np.full(p, 256.0, dtype=dt),
        ask_disk=np.full(p, 150.0, dtype=dt),
        n_dyn_ports=np.zeros(p, dtype=np.int32),
        has_static=np.zeros(p, dtype=bool),
        limit=np.full(p, limit, dtype=np.int32),
        count=np.full(p, p, dtype=np.int32),
        penalty_idx=pen,
        active=np.ones(p, dtype=bool))
    ptab = PreemptTables(
        cpu=ccpu, mem=cmem, disk=cdisk, prio=cprio, maxp=cmaxp, grp=cgrp,
        dyn_ports=np.zeros((n, a), dtype=np.int32),
        static_rel=np.zeros((n, a), dtype=bool),
        valid=cvalid, job_prio=np.int32(70))
    pinit = PreemptState(
        evicted=np.zeros((n, a), dtype=bool),
        counts=np.zeros(n_groups, dtype=np.int32))
    return const, init, batch, ptab, pinit


def _compare(const, init, batch, ptab, pinit):
    fit0, _ = wavefront_preempt_pristine(const, init, batch, ptab,
                                         "float64")
    assert not fit0.any()
    cd, sd, yd, evd, _ = solve_placements_preempt(
        const, init, batch, ptab, pinit, spread_alg=False,
        dtype_name="float64")
    cw, sw, yw, evw = solve_lane_wave_preempt(
        const, init, batch, ptab, pinit, spread_alg=False,
        dtype_name="float64")
    np.testing.assert_array_equal(cw, np.asarray(cd))
    np.testing.assert_array_equal(yw, np.asarray(yd))
    np.testing.assert_array_equal(evw, np.asarray(evd))
    sel = cw >= 0
    np.testing.assert_allclose(sw[sel], np.asarray(sd)[sel], rtol=1e-12)
    return cw, evw


@pytest.mark.parametrize("seed", range(6))
def test_preempt_wave_parity_random(seed):
    rng = random.Random(3000 + seed)
    c, ev = _compare(*_world(rng, n=40, p=16, limit=5))
    assert (c >= 0).any()


@pytest.mark.parametrize("seed", range(3))
def test_preempt_wave_parity_max_parallel(seed):
    """max_parallel penalties reorder the greedy picks via the global
    group counts riding the carry."""
    rng = random.Random(3100 + seed)
    _compare(*_world(rng, n=30, p=20, a=8, limit=4, maxp=1, n_groups=3))


def test_preempt_wave_parity_distinct_hosts():
    rng = random.Random(3200)
    c, ev = _compare(*_world(rng, n=50, p=20, limit=5, distinct=True))
    chosen = c[c >= 0]
    assert len(set(chosen.tolist())) == len(chosen)


def test_preempt_wave_parity_affinity_and_penalty():
    rng = random.Random(3300)
    _compare(*_world(rng, n=40, p=16, limit=5, affinity=True,
                     pen_frac=0.3))


def test_preempt_wave_parity_saturation():
    """Few nodes, many placements: windows churn through saturation and
    the deferred zombie shift repeatedly."""
    rng = random.Random(3400)
    c, ev = _compare(*_world(rng, n=10, p=24, limit=3, fill=0.85))
    # churn guarantee: more placements than nodes forces repeat choices,
    # exercising saturation/zombie shifts
    assert len(set(c[c >= 0].tolist())) < (c >= 0).sum()

def test_preempt_wave_batched_with_inert_padding():
    """The fuse path pads the eval axis; padding lanes are inert replicas
    and must place nothing while real lanes stay exact."""
    import jax
    real = [_world(random.Random(3500 + k), n=24, p=12, limit=4)
            for k in range(3)]
    pad = real[0]
    pad = (pad[0], pad[1],
           pad[2]._replace(active=np.zeros_like(np.asarray(pad[2].active))),
           pad[3], pad[4])
    lanes = real + [pad] * 5
    stack = lambda idx: jax.tree_util.tree_map(  # noqa: E731
        lambda *xs: np.stack(xs), *[l[idx] for l in lanes])
    const, init, batch = stack(0), stack(1), stack(2)
    ptab, pinit = stack(3), stack(4)
    cb, sb, yb, evb = solve_lane_wave_preempt(
        const, init, batch, ptab, pinit, spread_alg=False,
        dtype_name="float64", batched=True)
    for k, lw in enumerate(real):
        cd, sd, yd, evd, _ = solve_placements_preempt(
            *lw, spread_alg=False, dtype_name="float64")
        np.testing.assert_array_equal(cb[k], np.asarray(cd))
        np.testing.assert_array_equal(evb[k], np.asarray(evd))
    assert (cb[len(real):] == -1).all()
