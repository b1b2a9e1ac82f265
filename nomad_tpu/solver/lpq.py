"""Whole-queue LP-relaxation solver tier (``tpu-lpq``, ISSUE 8).

The greedy tier solves each eval's lane independently: placement quality
is order-dependent (whoever dequeues first grabs the best-fit nodes) and
every eval pays its own share of dispatch overhead.  This module is the
second scheduler tier the ROADMAP's open item 3 calls for, shaped after
CvxCluster-style granular allocation and differentiable combinatorial
scheduling: the coalesced pending queue is relaxed into ONE dense
lane x node matrix program solved on-device, then rounded back to
integral placements with a host-side feasibility repair pass.

Pipeline per batch (LpqBarrier generation):

  1. **Coalesce** -- the LPQ BatchWorker drains up to
     ``NOMAD_TPU_LPQ_BATCH`` compatible pending evals from the broker
     (``EvalBroker.dequeue_lpq``); each eval's GenericScheduler runs
     unchanged on its own thread and submits its PackedLane here
     (``make_lpq_hook``), exactly like the greedy SolveBarrier.
  2. **Assemble** -- LP-eligible lanes sharing one node universe (same
     version-keyed NodeMatrix, i.e. the PR-4 pack memos) are mapped back
     to canonical node order and stacked into a dense (L, N) value
     matrix V (the host oracle's BestFit-v3 + anti-affinity score),
     per-lane feasibility/fit masks, uniform asks, and the fleet's free
     capacity vector.  Preemption is folded in as NEGATIVE VALUE terms:
     a node that only fits after evicting lower-priority allocs stays
     feasible, priced down by the normalized eviction need.
  3. **Solve** -- a jitted projected-gradient / softmax-annealing loop
     (``_lp_program``): primal X = temperature-annealed softmax over the
     price-adjusted values, dual prices mu ascend on per-node
     cpu/mem/disk overload.  One device dispatch amortizes over every
     placement in the batch.
  4. **Round + repair (host)** -- per-lane placement counts from X by
     largest remainder, then a sequential repair pass charges every
     placement against a shared free-capacity ledger: a placement whose
     rounded node no longer fits is *evicted back to the greedy tier* --
     re-placed by the greedy rule (host score minus LP congestion
     prices) on a node with verified capacity, counted in
     ``nomad.lpq.repairs`` -- never silently committed.  Placements
     landing on eviction-priced nodes run the HOST preemption oracle
     (scheduler/preemption.py Preemptor -- the semantics ground truth)
     to pick the actual eviction set.
  5. **Quality + audit** -- the rounded solution is compared against a
     greedy replay of the same queue (fragmentation index + packing
     efficiency, the PR-7 scoreboard formulas) into
     ``nomad.lpq.quality_delta`` / ``nomad.lpq.frag_delta``, and solved
     lanes flow through the PR-7 shadow audit with ``lpq=True`` (score
     drift still gates; decision divergence from the greedy oracle is
     expected and counted separately in ``nomad.quality.lpq_divergence``).

Results flow through the existing materialize -> plan applier path;
lanes the LP does not model (ports, devices, cores, spreads,
distinct-*, penalties) are solved by the greedy fused dispatch within
the same barrier generation, so behavior stays complete.

Kill switch ``NOMAD_TPU_LPQ=0`` (or any non-lpq scheduler algorithm)
restores the greedy tier bit-for-bit: the LPQ worker branch, broker
coalescer and this module are never entered.

Knobs:
  NOMAD_TPU_LPQ            kill switch (default on when tpu-lpq selected)
  NOMAD_TPU_LPQ_BATCH      max evals coalesced per batch (128)
  NOMAD_TPU_LPQ_STEPS      annealing/dual-ascent iterations (48)
  NOMAD_TPU_LPQ_GATHER_MS  broker gather window for a fuller batch (20)
  NOMAD_TPU_LPQ_COMPARE    0: skip the greedy-replay quality comparison
"""
from __future__ import annotations

import functools
import os
import threading
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..server.telemetry import metrics
from ..server.tracing import tracer
from .service import PackedLane

# Safety valve mirroring solver/batch.py: a straggler eval thread must
# not wedge every blocked participant.
LPQ_BARRIER_TIMEOUT_S = 10.0

# Pad the lane axis to these buckets so XLA compiles one LP program per
# bucket, not one per batch size.
_L_BUCKETS = (8, 16, 32, 64, 128, 256)

# Negative-value weight for preemption: how hard an eviction-needing
# node is priced down per unit of normalized eviction need.
_PREEMPT_VALUE_PENALTY = 0.5


def lpq_enabled() -> bool:
    """NOMAD_TPU_LPQ=0 is the kill switch: the greedy tier runs
    bit-for-bit even when the scheduler algorithm selects tpu-lpq."""
    return os.environ.get("NOMAD_TPU_LPQ", "1") != "0"


def lpq_batch_width() -> int:
    try:
        return max(1, int(os.environ.get("NOMAD_TPU_LPQ_BATCH", "128")))
    except ValueError:
        return 128


def lpq_steps() -> int:
    try:
        return max(4, int(os.environ.get("NOMAD_TPU_LPQ_STEPS", "48")))
    except ValueError:
        return 48


def lpq_gather_s() -> float:
    try:
        return max(0.0, float(os.environ.get(
            "NOMAD_TPU_LPQ_GATHER_MS", "20")) / 1e3)
    except ValueError:
        return 0.02


def lpq_compare_enabled() -> bool:
    return os.environ.get("NOMAD_TPU_LPQ_COMPARE", "1") != "0"


def lpq_active(state) -> bool:
    """Is the LP queue tier selected AND alive?  False routes everything
    through the greedy tier (the prior path bit-for-bit)."""
    if not lpq_enabled():
        return False
    if not hasattr(state, "scheduler_config"):
        return False
    cfg = state.scheduler_config()
    if cfg is None:
        return False
    from ..structs import SCHED_ALG_TPU_LPQ
    return cfg.scheduler_algorithm == SCHED_ALG_TPU_LPQ


# ---------------------------------------------------------------------------
# stats
# ---------------------------------------------------------------------------

_STATS_LOCK = threading.Lock()
_STATS = {
    "solves": 0, "lanes_total": 0, "placements": 0, "repairs": 0,
    "failed": 0, "preempt_evictions": 0, "greedy_lanes": 0,
    "quality_delta": None, "frag_delta": None,
}


def _stat(name: str, n=1) -> None:
    with _STATS_LOCK:
        _STATS[name] += n


def _stat_set(name: str, v) -> None:
    with _STATS_LOCK:
        _STATS[name] = v


def lpq_stats() -> dict:
    """Snapshot of the LP tier's counters."""
    with _STATS_LOCK:
        out = dict(_STATS)
    solves = out["solves"]
    out["evals_per_solve"] = (out["lanes_total"] / solves) if solves else 0.0
    out["repair_rate"] = (out["repairs"] / out["placements"]
                          if out["placements"] else 0.0)
    return out


def _reset_for_tests() -> None:
    with _STATS_LOCK:
        for k in _STATS:
            _STATS[k] = None if k in ("quality_delta", "frag_delta") else 0


# ---------------------------------------------------------------------------
# eligibility
# ---------------------------------------------------------------------------

def lp_lane_eligible(lane: PackedLane) -> bool:
    """Does the joint LP model everything this lane asks for?  Mirrors
    quality._lane_simple (pure cpu/mem/disk binpack + anti-affinity)
    but ADDITIONALLY admits preemption lanes -- eviction rides the LP as
    negative-value terms and the rounded eviction sets come from the
    host oracle.  Everything else (ports, devices, cores, spreads,
    distinct-*, reschedule penalties) solves on the greedy fused path
    within the same barrier generation."""
    c, b = lane.const, lane.batch
    return (c.spread_vidx.shape[0] == 0
            and c.dp_vidx.shape[0] == 0
            and c.dev_aff.shape[0] == 0
            and c.mhz_per_core.shape[0] == 0
            and not bool(c.has_affinity)
            and not bool(c.distinct_hosts)
            and b.ask_cores.shape[0] == 0
            and int(np.asarray(b.n_dyn_ports)[0]) == 0
            and not bool(np.asarray(b.has_static)[0])
            and bool((np.asarray(b.penalty_idx) < 0).all())
            and bool(np.asarray(b.active).all()))


# ---------------------------------------------------------------------------
# the on-device relaxation
# ---------------------------------------------------------------------------

def _l_bucket(n: int) -> int:
    for b in _L_BUCKETS:
        if n <= b:
            return b
    return int(2 ** np.ceil(np.log2(n)))


def _lp_solve_body(N: int, steps: int, gather=None):
    """The pure projected-gradient / softmax-annealing solve, shared by
    the single-device program (``_lp_program``) and the mesh program
    (parallel/mesh.py ``mesh_lpq_fn``).

    Variables: X (L, N), each lane's relaxed placement distribution over
    nodes (rows of one lane are exchangeable -- uniform asks -- so the
    alloc x node program collapses to lane x node with per-lane
    multiplicity ``pcount``).  Dual prices mu (N, 3) ascend on
    cpu/mem/disk overload; the primal follows the price-adjusted values
    through a falling softmax temperature (anneal -> argmax).

    ``gather`` is the mesh hook: applied to the load-einsum operand so
    the sharded lane axis is all-gathered (replicated) BEFORE the
    reduction over lanes.  The einsum then runs whole on every device
    -- identical kernel, identical f32 summation order -- which is what
    keeps mesh output bit-for-bit equal to single-device (a psum over
    lane shards re-associates the sum, and the anneal amplifies that
    ulp noise into placement flips).  None (single-device) is the
    identity: the traced math is unchanged."""
    import jax
    import jax.numpy as jnp

    t_hi, t_lo, eta = 0.25, 0.02, 0.5
    if gather is None:
        gather = lambda x: x  # noqa: E731 -- identity, single-device

    def solve(V, feas, ask, pcount, free, active):
        # V/feas (L, N); ask (L, 3); pcount/active (L,); free (N, 3)
        cap = jnp.maximum(free, 1.0)
        any_f = feas.any(axis=1, keepdims=True)
        live = any_f & active[:, None]

        def X_at(mu, temp):
            price = jnp.einsum("lr,nr->ln", ask, mu)
            logits = jnp.where(feas, (V - price) / temp, -jnp.inf)
            X = jax.nn.softmax(jnp.where(any_f, logits, 0.0), axis=1)
            return jnp.where(live, X, 0.0)

        def body(mu, t):
            frac = t.astype(jnp.float32) / max(steps - 1, 1)
            temp = t_hi * (t_lo / t_hi) ** frac
            X = X_at(mu, temp)
            load = jnp.einsum("ln,lr->nr",
                              gather(X * pcount[:, None]), ask)
            mu = jnp.clip(mu + eta * (load - free) / cap, 0.0, None)
            return mu, None

        mu0 = jnp.zeros((N, 3), dtype=jnp.float32)
        mu, _ = jax.lax.scan(body, mu0, jnp.arange(steps))
        return X_at(mu, t_lo), mu

    return solve


@functools.lru_cache(maxsize=16)
def _lp_program(L_pad: int, N: int, steps: int):
    """Jitted single-device LP relaxation (see _lp_solve_body)."""
    import jax

    return jax.jit(_lp_solve_body(N, steps))


# ---------------------------------------------------------------------------
# host-side assembly, rounding, repair
# ---------------------------------------------------------------------------

class _LaneView:
    """One LP-eligible lane mapped back to canonical (NodeMatrix) node
    order, with everything rounding/repair/scoring needs."""

    __slots__ = ("lane", "inv", "feas", "feas_fit", "used", "placed",
                 "placed0", "ask", "count", "P", "relief", "relief_ok",
                 "V", "n_yield")

    def __init__(self, lane: PackedLane):
        self.lane = lane
        c, s, b = lane.const, lane.init, lane.batch
        n_pad = np.asarray(c.cpu_cap).shape[0]
        n = len(lane.order)
        perm = np.concatenate([np.asarray(lane.order, dtype=np.int64),
                               np.arange(n, n_pad, dtype=np.int64)])
        inv = np.empty(n_pad, dtype=np.int64)
        inv[perm] = np.arange(n_pad)
        self.inv = inv                      # canonical j -> shuffled pos

        def canon(arr, dtype=np.float64):
            return np.asarray(arr)[inv].astype(dtype)

        self.feas = np.asarray(c.feasible)[inv]
        self.used = np.stack([canon(s.used_cpu), canon(s.used_mem),
                              canon(s.used_disk)])          # (3, N)
        self.placed = canon(s.placed, np.int64)
        # pre-repair snapshot: the score replay (and the PR-7 audit's
        # follow re-score) must carry from the INITIAL counts; the
        # repair pass mutates self.placed as it commits
        self.placed0 = self.placed.copy()
        self.ask = np.asarray([float(np.asarray(b.ask_cpu)[0]),
                               float(np.asarray(b.ask_mem)[0]),
                               float(np.asarray(b.ask_disk)[0])])
        self.count = max(float(np.asarray(b.count)[0]), 1.0)
        self.P = int(np.asarray(b.ask_cpu).shape[0])
        self.relief = None
        self.relief_ok = None
        if lane.ptab is not None:
            pt = lane.ptab
            elig = (np.asarray(pt.valid)
                    & (int(np.asarray(pt.job_prio))
                       - np.asarray(pt.prio) >= 10))
            self.relief = np.stack([
                (np.asarray(pt.cpu) * elig).sum(axis=1)[inv],
                (np.asarray(pt.mem) * elig).sum(axis=1)[inv],
                (np.asarray(pt.disk) * elig).sum(axis=1)[inv],
            ]).astype(np.float64)                           # (3, N)


def _lane_values(view: _LaneView, cap: np.ndarray, spread_alg: bool
                 ) -> None:
    """Fill view.V / view.feas_fit: the host oracle's initial score per
    node (binpack BestFit-v3 + job anti-affinity -- the same formula
    quality._replay_lane pins) with preemption folded in as a negative
    value term on nodes that only fit after eviction."""
    from .binpack import BINPACK_MAX

    ask = view.ask
    new = view.used + ask[:, None]                          # (3, N)
    free_frac_cpu = 1.0 - new[0] / np.maximum(cap[0], 1e-9)
    free_frac_mem = 1.0 - new[1] / np.maximum(cap[1], 1e-9)
    total = np.power(10.0, free_frac_cpu) + np.power(10.0, free_frac_mem)
    raw = (total - 2.0) if spread_alg else (20.0 - total)
    binpack = np.clip(raw, 0.0, BINPACK_MAX) / BINPACK_MAX
    coll = view.placed > 0
    anti = np.where(coll, -(view.placed + 1.0) / view.count, 0.0)
    V = (binpack + anti) / (1.0 + coll.astype(np.float64))

    fit_alone = view.feas & (new <= cap).all(axis=0)
    if view.relief is None:
        view.feas_fit = fit_alone
    else:
        with_relief = view.feas & \
            (new <= cap + view.relief).all(axis=0)
        view.relief_ok = with_relief & ~fit_alone
        view.feas_fit = fit_alone | with_relief
        # negative-value preemption term: normalized eviction need
        need = np.clip(new - cap, 0.0, None) / np.maximum(
            ask[:, None], 1e-9)
        V = V - _PREEMPT_VALUE_PENALTY * np.where(
            view.relief_ok, need.sum(axis=0), 0.0)
    view.V = np.where(view.feas_fit, V, -1e9)
    view.n_yield = int(view.feas_fit.sum())


def _score_follow(view: _LaneView, chosen_canon: np.ndarray,
                  cap: np.ndarray, spread_alg: bool) -> np.ndarray:
    """Host scores for the solved sequence: the oracle formula with the
    lane-local sequential carry -- float-identical to what the PR-7
    shadow audit's follow replay recomputes, so LP-solved lanes audit
    with ~zero score drift."""
    from .binpack import BINPACK_MAX

    used = view.used.copy()
    placed = view.placed0.astype(np.float64).copy()
    ask = view.ask
    out = np.zeros(len(chosen_canon), dtype=np.float64)
    for p, b in enumerate(chosen_canon):
        if b < 0:
            continue
        new_cpu = used[0, b] + ask[0]
        new_mem = used[1, b] + ask[1]
        fc = 1.0 - new_cpu / max(cap[0, b], 1e-9)
        fm = 1.0 - new_mem / max(cap[1, b], 1e-9)
        total = np.power(10.0, fc) + np.power(10.0, fm)
        raw = (total - 2.0) if spread_alg else (20.0 - total)
        binpack = min(max(raw, 0.0), BINPACK_MAX) / BINPACK_MAX
        if placed[b] > 0:
            out[p] = (binpack - (placed[b] + 1.0) / view.count) / 2.0
        else:
            out[p] = binpack
        used[:, b] += ask
        placed[b] += 1
    return out


def _frag_and_pack(cap_cpu, cap_mem, used_cpu, used_mem
                   ) -> Tuple[float, float]:
    """The PR-7 quality-scoreboard formulas (server/quality.py report):
    capacity-weighted fragmentation index + packing efficiency over
    occupied nodes, computed for a hypothetical usage vector."""
    with np.errstate(divide="ignore", invalid="ignore"):
        util_cpu = np.clip(np.where(cap_cpu > 0,
                                    used_cpu / np.maximum(cap_cpu, 1e-9),
                                    0.0), 0.0, 1.0)
        util_mem = np.clip(np.where(cap_mem > 0,
                                    used_mem / np.maximum(cap_mem, 1e-9),
                                    0.0), 0.0, 1.0)
    free_cpu, free_mem = 1.0 - util_cpu, 1.0 - util_mem
    usable = np.minimum(free_cpu, free_mem)
    free_any = np.maximum(free_cpu, free_mem)
    w = (np.where(cap_cpu.sum() > 0,
                  cap_cpu / max(cap_cpu.sum(), 1e-9), 0.0)
         + np.where(cap_mem.sum() > 0,
                    cap_mem / max(cap_mem.sum(), 1e-9), 0.0)) / 2.0
    denom = float((free_any * w).sum())
    frag = 1.0 - float((usable * w).sum()) / denom if denom > 1e-12 \
        else 0.0
    occ = (used_cpu > 0) | (used_mem > 0)
    if occ.any():
        pack = (float(used_cpu[occ].sum()
                      / max(cap_cpu[occ].sum(), 1e-9))
                + float(used_mem[occ].sum()
                        / max(cap_mem[occ].sum(), 1e-9))) / 2.0
    else:
        pack = 0.0
    return frag, pack


def _try_preempt(view: _LaneView, b: int, free: np.ndarray,
                 evicted_ids: set, evicted_so_far: List) -> Optional[List]:
    """Run the HOST preemption oracle (scheduler/preemption.py -- the
    semantics ground truth the LP's negative-value terms approximate) on
    canonical node b; returns the eviction set when the ask verifiably
    fits afterward, else None."""
    from ..scheduler.preemption import Preemptor
    from ..structs import (
        AllocatedResources, AllocatedSharedResources, AllocatedTaskResources,
    )

    lane = view.lane
    if lane.cand_allocs is None:
        return None
    pos = int(view.inv[b])
    A = np.asarray(lane.ptab.valid).shape[1]
    cands = [a for a in lane.cand_allocs[pos][:A]
             if a.id not in evicted_ids]
    if not cands:
        return None
    svc = lane.service
    tg = lane.tg
    ask_res = AllocatedResources(
        tasks={t.name: AllocatedTaskResources(
            cpu_shares=t.resources.cpu, memory_mb=t.resources.memory_mb)
            for t in tg.tasks},
        shared=AllocatedSharedResources(disk_mb=tg.ephemeral_disk.size_mb))
    preemptor = Preemptor(svc.job.priority, svc.ctx,
                          (svc.job.namespace, svc.job.id))
    preemptor.set_node(lane.nodes[b])
    preemptor.set_preemptions(evicted_so_far)
    preemptor.set_candidates(cands)
    evicted = preemptor.preempt_for_task_group(ask_res)
    if not evicted:
        return None
    freed = np.zeros(3)
    for a in evicted:
        cr = a.allocated_resources.comparable()
        freed += (cr.cpu_shares, cr.memory_mb, cr.disk_mb)
    # verify against the SHARED ledger (other lanes may have landed here
    # this batch -- the oracle only saw this lane's candidates)
    if not (view.ask <= free[:, b] + freed + 1e-9).all():
        return None
    return evicted


def solve_queue(lanes: List[PackedLane], ledger: Dict[str, list]
                ) -> List[tuple]:
    """Solve one barrier generation: the LP-eligible lanes (sharing one
    version-keyed NodeMatrix) through the joint relaxation, everything
    else through the greedy fused dispatch.  Returns per-lane result
    tuples in input order (chosen, scores, n_yielded[, evict_rows]),
    all in the lane's own shuffled coordinates."""
    from .batch import _cross_lane_fixpoint, fuse_and_solve

    results: List = [None] * len(lanes)

    # group LP-eligible lanes by node universe: pack_nodes_cached dedups
    # the NodeMatrix by (table version, node-id tuple), so matrix
    # identity IS node-universe identity; the largest group solves
    # jointly, stragglers ride the greedy path
    groups: Dict[int, List[int]] = {}
    for i, lane in enumerate(lanes):
        m = getattr(lane, "matrix", None)
        if m is not None and lp_lane_eligible(lane):
            groups.setdefault(id(m), []).append(i)
    lp_idx: List[int] = max(groups.values(), key=len) if groups else []

    if lp_idx:
        t0 = time.perf_counter()
        lp_results = _solve_lp_group([lanes[i] for i in lp_idx], ledger)
        dt_ms = (time.perf_counter() - t0) * 1e3
        metrics.sample_ms("nomad.lpq.solve_ms", dt_ms)
        metrics.incr("nomad.lpq.solves")
        metrics.sample("nomad.lpq.lanes_per_solve", float(len(lp_idx)))
        _stat("solves")
        _stat("lanes_total", len(lp_idx))
        for i, res in zip(lp_idx, lp_results):
            results[i] = res

    greedy_idx = [i for i in range(len(lanes)) if results[i] is None]
    if greedy_idx:
        sub = [lanes[i] for i in greedy_idx]
        sub_res = fuse_and_solve(sub)
        # charge greedy placements against the same capacity ledger the
        # LP committed into, resolving residual conflicts for wave lanes
        _cross_lane_fixpoint(sub, sub_res, ledger)
        metrics.incr("nomad.lpq.greedy_lanes", len(sub))
        _stat("greedy_lanes", len(sub))
        for i, res in zip(greedy_idx, sub_res):
            results[i] = res
    return results


def _solve_lp_group(lanes: List[PackedLane], ledger: Dict[str, list]
                    ) -> List[tuple]:
    matrix = lanes[0].matrix
    spread_alg = bool(lanes[0].spread_alg)
    views = [_LaneView(lane) for lane in lanes]
    n_pad = views[0].used.shape[1]

    cap = np.stack([np.asarray(matrix.cpu_cap, dtype=np.float64),
                    np.asarray(matrix.mem_cap, dtype=np.float64),
                    np.asarray(matrix.disk_cap, dtype=np.float64)])
    for v in views:
        _lane_values(v, cap, spread_alg)

    # shared free capacity: conservative elementwise max of lane usage
    # (lanes differ only by their own plan deltas), overridden by the
    # cross-generation ledger where earlier commits already charged it
    used_max = np.maximum.reduce([v.used for v in views])
    free = np.clip(cap - used_max, 0.0, None)               # (3, N)
    pos_of = matrix.__dict__.get("_pos_index")
    if pos_of is None:
        pos_of = {nid: i for i, nid in enumerate(matrix.node_ids)}
        matrix._pos_index = pos_of
    for nid, f in ledger.items():
        b = pos_of.get(nid)
        if b is not None:
            free[0, b] = min(free[0, b], f[0])
            free[1, b] = min(free[1, b], f[1])
            free[2, b] = min(free[2, b], f[2])

    # -- device solve ---------------------------------------------------
    L = len(views)
    L_pad = _l_bucket(L)
    V = np.full((L_pad, n_pad), -1e9, dtype=np.float32)
    feas = np.zeros((L_pad, n_pad), dtype=bool)
    ask = np.zeros((L_pad, 3), dtype=np.float32)
    pcount = np.zeros(L_pad, dtype=np.float32)
    active = np.zeros(L_pad, dtype=bool)
    for li, v in enumerate(views):
        V[li] = v.V
        feas[li] = v.feas_fit
        ask[li] = v.ask
        pcount[li] = v.P
        active[li] = True
    import jax

    steps = lpq_steps()
    mesh = None
    if jax.device_count() > 1:
        # pick_mesh is the NOMAD_TPU_MESH chokepoint: knob off (or no
        # usable grid) -> None -> the single-device program bit-for-bit
        from ..parallel.mesh import pick_mesh
        mesh = pick_mesh(L_pad, n_pad)
    if mesh is not None:
        from .. import jitcheck
        from ..parallel.mesh import mesh_lpq_fn, shard_lpq_inputs
        from . import xferobs
        metrics.incr("nomad.lpq.mesh_dispatches")
        with mesh:
            s_in = shard_lpq_inputs(mesh, V, feas, ask, pcount,
                                    free.T.astype(np.float32), active)
            program = mesh_lpq_fn(mesh, L_pad, n_pad, steps)
            X_dev, mu_dev = program(*s_in)
        with jitcheck.sanctioned_fetch("lpq"):
            # the mesh route's one bulk fetch: gather + host copy
            X = np.asarray(X_dev, dtype=np.float64)[:L]
            mu = np.asarray(mu_dev, dtype=np.float64)       # (N, 3)
        xferobs.note_fetch(
            int(X_dev.nbytes) + int(mu_dev.nbytes), "lpq")
    else:
        program = _lp_program(L_pad, n_pad, steps)
        X, mu = program(V, feas, ask, pcount,
                        free.T.astype(np.float32), active)
        X = np.asarray(X, dtype=np.float64)[:L]
        mu = np.asarray(mu, dtype=np.float64)               # (N, 3)

    # -- round: per-lane integral counts by largest remainder -----------
    assigned: List[np.ndarray] = []
    for li, v in enumerate(views):
        x = np.where(v.feas_fit, X[li], 0.0)
        tot = x.sum()
        if tot <= 0:
            assigned.append(np.full(v.P, -1, dtype=np.int64))
            continue
        x = x / tot
        counts = np.floor(x * v.P).astype(np.int64)
        deficit = v.P - int(counts.sum())
        if deficit > 0:
            frac = x * v.P - counts
            frac[~v.feas_fit] = -1.0
            for b in np.argsort(-frac)[:deficit]:
                counts[b] += 1
        # expand to one node index per placement, best-X nodes first
        order = np.argsort(-x)
        chosen = np.repeat(order, counts[order])[:v.P]
        if chosen.shape[0] < v.P:
            chosen = np.concatenate([
                chosen, np.full(v.P - chosen.shape[0], -1, np.int64)])
        assigned.append(chosen)

    # -- repair: charge every placement against the shared ledger -------
    free_r = free.copy()
    evicted_ids: set = set()
    evicted_so_far: List = []
    chosen_out = [np.full(v.P, -1, dtype=np.int64) for v in views]
    evict_out = [
        (np.zeros((v.P, np.asarray(v.lane.ptab.valid).shape[1]),
                  dtype=bool) if v.lane.ptab is not None else None)
        for v in views]
    n_repair = n_fail = n_evict = 0

    def commit(v, li, p, b, evicted=None):
        nonlocal n_evict
        free_r[:, b] -= v.ask
        if evicted:
            freed = np.zeros(3)
            pos = int(v.inv[b])
            cands = v.lane.cand_allocs[pos]
            for a in evicted:
                cr = a.allocated_resources.comparable()
                freed += (cr.cpu_shares, cr.memory_mb, cr.disk_mb)
                evicted_ids.add(a.id)
                evicted_so_far.append(a)
                for a_i, cand in enumerate(cands):
                    if cand.id == a.id:
                        evict_out[li][p, a_i] = True
                        break
            free_r[:, b] += freed
            n_evict += len(evicted)
        v.placed[b] += 1
        chosen_out[li][p] = b

    for li, v in enumerate(views):
        for p in range(v.P):
            b = int(assigned[li][p])
            if b >= 0 and (v.ask <= free_r[:, b] + 1e-9).all():
                commit(v, li, p, b)
                continue
            if (b >= 0 and v.relief_ok is not None and v.relief_ok[b]):
                evicted = _try_preempt(v, b, free_r, evicted_ids,
                                       evicted_so_far)
                if evicted:
                    commit(v, li, p, b, evicted)
                    continue
            # rounded node infeasible at commit time: evict the
            # placement back to the GREEDY rule -- best host score minus
            # LP congestion price, over verified remaining capacity
            n_repair += 1
            fits = v.feas_fit & (free_r + 1e-9 >= v.ask[:, None]).all(
                axis=0)
            if fits.any():
                price = mu @ v.ask                          # (N,)
                score = np.where(fits, v.V - price, -np.inf)
                commit(v, li, p, int(np.argmax(score)))
                continue
            if v.relief_ok is not None:
                relievable = np.flatnonzero(v.relief_ok)
                placed_ok = False
                for b2 in relievable[np.argsort(-v.V[relievable])][:8]:
                    evicted = _try_preempt(v, int(b2), free_r,
                                           evicted_ids, evicted_so_far)
                    if evicted:
                        commit(v, li, p, int(b2), evicted)
                        placed_ok = True
                        break
                if placed_ok:
                    continue
            n_fail += 1     # nothing fits anywhere: the greedy tier
            #                 would fail this placement too -> blocked

    # publish the committed capacity into the cross-generation ledger
    touched = np.flatnonzero(
        (free_r != free).any(axis=0))
    for b in touched:
        nid = matrix.node_ids[b] if b < len(matrix.node_ids) else None
        if nid is None:
            continue
        f = ledger.get(nid)
        if f is None:
            ledger[nid] = [free_r[0, b], free_r[1, b], free_r[2, b], 0]
        else:
            f[0], f[1], f[2] = free_r[0, b], free_r[1, b], free_r[2, b]

    n_placed = sum(int((c >= 0).sum()) for c in chosen_out)
    metrics.incr("nomad.lpq.placements", max(n_placed, 0))
    if n_repair:
        metrics.incr("nomad.lpq.repairs", n_repair)
    if n_fail:
        metrics.incr("nomad.lpq.failed", n_fail)
    if n_evict:
        metrics.incr("nomad.lpq.preempt_evictions", n_evict)
    _stat("placements", n_placed)
    _stat("repairs", n_repair)
    _stat("failed", n_fail)
    _stat("preempt_evictions", n_evict)

    # -- batch-level quality: LP vs a greedy replay of the same queue ---
    if lpq_compare_enabled():
        try:
            _compare_quality(views, cap, free, chosen_out, spread_alg)
        except Exception:  # noqa: BLE001 -- comparison is advisory
            pass

    # -- per-lane outputs in shuffled coordinates -----------------------
    out: List[tuple] = []
    for li, v in enumerate(views):
        scores = _score_follow(v, chosen_out[li], cap, spread_alg)
        chosen_shuf = np.where(chosen_out[li] >= 0,
                               v.inv[np.clip(chosen_out[li], 0, None)],
                               -1).astype(np.int64)
        n_yielded = np.full(v.P, max(v.n_yield, 1), dtype=np.int64)
        if evict_out[li] is not None:
            out.append((chosen_shuf, scores, n_yielded, evict_out[li]))
        else:
            out.append((chosen_shuf, scores, n_yielded))
    return out


def _compare_quality(views, cap, free0, chosen_out, spread_alg: bool
                     ) -> None:
    """Fragmentation + packing efficiency of the LP solution vs a
    greedy replay of the same queue from the same starting state
    (the greedy tier's decision rule: per-placement max host score over
    fitting nodes, sequential carry) -- the PR-7 scoreboard formulas
    applied to both hypothetical usage vectors."""
    from .binpack import BINPACK_MAX

    used0 = cap - free0
    # LP usage
    used_lp = used0.copy()
    for li, v in enumerate(views):
        for b in chosen_out[li]:
            if b >= 0:
                used_lp[:, int(b)] += v.ask
    # greedy replay usage
    used_g = used0.copy()
    for v in views:
        placed = v.placed0.astype(np.float64).copy()
        for _ in range(v.P):
            new = used_g + v.ask[:, None]
            fits = v.feas & (new <= cap).all(axis=0)
            if not fits.any():
                continue
            fc = 1.0 - new[0] / np.maximum(cap[0], 1e-9)
            fm = 1.0 - new[1] / np.maximum(cap[1], 1e-9)
            total = np.power(10.0, fc) + np.power(10.0, fm)
            raw = (total - 2.0) if spread_alg else (20.0 - total)
            binpack = np.clip(raw, 0.0, BINPACK_MAX) / BINPACK_MAX
            coll = placed > 0
            anti = np.where(coll, -(placed + 1.0) / v.count, 0.0)
            score = np.where(fits, (binpack + anti) / (1.0 + coll),
                             -np.inf)
            b = int(np.argmax(score))
            used_g[:, b] += v.ask
            placed[b] += 1

    valid = cap[0] > 0
    frag_lp, pack_lp = _frag_and_pack(
        cap[0][valid], cap[1][valid], used_lp[0][valid], used_lp[1][valid])
    frag_g, pack_g = _frag_and_pack(
        cap[0][valid], cap[1][valid], used_g[0][valid], used_g[1][valid])
    q_delta = pack_lp - pack_g          # higher = LP packs tighter
    f_delta = frag_lp - frag_g          # lower = LP fragments less
    metrics.sample("nomad.lpq.quality_delta", q_delta)
    metrics.sample("nomad.lpq.frag_delta", f_delta)
    _stat_set("quality_delta", round(q_delta, 6))
    _stat_set("frag_delta", round(f_delta, 6))


# ---------------------------------------------------------------------------
# the rendezvous barrier + scheduler hook
# ---------------------------------------------------------------------------

class LpqBarrier:
    """Rendezvous point for one LPQ batch of eval threads: same contract
    as solver/batch.py SolveBarrier (solve() blocks, done() on exit, the
    last arriver dispatches), but the dispatch is the whole-queue LP
    solve instead of the per-lane greedy fuse.  Multi-TG evals
    rendezvous once per TG (generations), sharing a free-capacity
    ledger so later generations see earlier commitments."""

    def __init__(self, participants: int, plan_group_hint=None):
        self._cv = threading.Condition()
        self._participants = participants
        self._finished = 0
        self._waiting: List[Tuple[PackedLane, dict]] = []
        self._generation = 0
        self._plan_group_hint = plan_group_hint
        self._ledger: Dict[str, list] = {}

    def done(self) -> None:
        with self._cv:
            self._finished += 1
            if self._ready_locked():
                self._dispatch_locked()

    def solve(self, lane: PackedLane):
        # explicit trace handoff, same as SolveBarrier: the dispatching
        # thread records the fused spans into every waiter's trace
        cell: dict = {"trace_ctx": tracer.current()}
        t_arrive = time.time()
        with self._cv:
            self._waiting.append((lane, cell))
            if self._ready_locked():
                self._dispatch_locked()
            while "result" not in cell and "error" not in cell:
                gen = self._generation
                if not self._cv.wait(timeout=LPQ_BARRIER_TIMEOUT_S):
                    # straggler safety valve (same as SolveBarrier): if
                    # our lane is still queued, dispatch what we have
                    if (self._generation == gen
                            and any(c is cell for _, c in self._waiting)):
                        self._dispatch_locked()
            if "error" in cell:
                tracer.record("solver.barrier", t_arrive,
                              (time.time() - t_arrive) * 1e3,
                              outcome="error", tier="lpq")
                raise cell["error"]
            tracer.record("solver.barrier", t_arrive,
                          (time.time() - t_arrive) * 1e3, outcome="ok",
                          tier="lpq")
            return cell["result"]

    def _ready_locked(self) -> bool:
        return (self._waiting
                and len(self._waiting) + self._finished
                >= self._participants)

    def _dispatch_locked(self) -> None:
        batch = self._waiting
        self._waiting = []
        self._generation += 1
        gen = self._generation
        lanes = [lane for lane, _ in batch]
        gctx = tracer.group([c.get("trace_ctx") for _, c in batch])
        try:
            from .guard import run_dispatch
            with tracer.activate(gctx), \
                    tracer.span("solver.lpq_dispatch", ctx=gctx,
                                generation=gen, lanes=len(lanes)):
                results = run_dispatch(
                    lambda: solve_queue(lanes, self._ledger),
                    label="solver.lpq")
            for (lane, cell), res in zip(batch, results):
                cell["result"] = res
        except Exception as e:  # noqa: BLE001 -- waiters must not strand
            for _, cell in batch:
                cell["error"] = e
        finally:
            hint = self._plan_group_hint
            if hint is not None and batch:
                try:
                    hint(len(batch))
                except Exception:  # noqa: BLE001 -- advisory only
                    pass
            self._cv.notify_all()


def make_lpq_hook(barrier: LpqBarrier):
    """The solve hook the LPQ tier's GenericSchedulers call instead of
    service.solve(): pack on the calling thread, solve the whole queue
    at the barrier, materialize on the calling thread.  A failed
    dispatch degrades THIS eval to the host oracle (return None)."""
    def hook(service, tg, places, nodes, penalties):
        from ..server.quality import observatory as _quality
        from .guard import DispatchFailed, note_host_fallback

        with tracer.span("solver.pack", tg=tg.name, places=len(places)):
            lane = service.pack(tg, places, nodes, penalties)
        if lane is None:
            return None          # not solver-eligible -> host fallback
        try:
            res = barrier.solve(lane)
        except DispatchFailed:
            note_host_fallback()
            return None
        # PR-7 shadow audit: LP decisions are EXPECTED to diverge from
        # the greedy oracle (that is the tier's point); the lpq flag
        # keeps score-drift gating while counting divergence separately
        _quality.maybe_capture_audit(lane, res[0], res[1],
                                     lpq=lp_lane_eligible(lane))
        with tracer.span("solver.materialize", tg=tg.name):
            return service.materialize(lane, *res)
    return hook
