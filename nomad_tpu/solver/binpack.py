"""The TPU solver core: the scheduler's inner loop as a kernel family.

This is the north star (BASELINE.json): the per-candidate work of
BinPackIterator.Next (reference: scheduler/rank.go:205) -- fit check,
BestFit-v3 scoring, anti-affinity/penalty/affinity/spread scoring, and the
LimitIterator/MaxScoreIterator selection semantics (select.go, stack.go:82)
-- with the within-eval sequential dependence (earlier placements consume
resources, context.go:176 ProposedAllocs) carried through a lax.scan.
Three kernels share those semantics, picked by lane shape:

  - **wavefront** (solve_lane_wave; the production fast path): uniform-ask
    lanes admit a closed-form per-node placement capacity, so the scan
    carries only a B-slot buffer of the front-of-order fit nodes -- O(B)
    per step, a compact (P+B, 8+S) table as the only transfer, spread
    counts in the carry, penalties in the scan xs.
  - **dense** (solve_placements[_preempt]): every node rescored per step;
    handles the node-coupling features the wavefront gates out
    (distinct_property, devices, cores, dense preemption search).
  - **system** (solve_system): one INDEPENDENT fit+score per node, no
    window at all (scheduler_system.go semantics).

Selection parity: the reference scans a shuffled, log2-limited window with
up-to-3 low-score skips and picks the max score (first-seen wins ties).
Every kernel reproduces that exactly (see _select_window and the
wavefront's in-buffer emulation); the oracle suites gate all of them.

All arrays are in SHUFFLED ORDER (nomad_tpu/scheduler/util.py
shuffled_order); callers map chosen indexes back to node ids.
"""
from __future__ import annotations

import functools
import threading
from typing import NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from .. import jitcheck
from . import stages


def _single_flight(fn):
    """Serialize invocations of a program factory: functools.lru_cache
    does NOT single-flight, so two pipelined generations hitting one
    COLD shape bucket concurrently would both execute the factory --
    a duplicated multi-second XLA trace/compile of the same program,
    and exactly the fresh-identical-closure-per-call pattern jitcheck
    flags as a steady-state retrace (found by the dispatch-pipeline
    overlap test racing a cold wave bucket).  Warm lookups pay one
    uncontended lock acquire per dispatch."""
    lock = threading.Lock()

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with lock:
            return fn(*args, **kwargs)
    # the lru wrapper's cache management stays reachable (tests and
    # the jitcheck gauntlet rebuild buckets via cache_clear); not a
    # store-derived memo, so version-keyed-memo has nothing to key
    for attr in ("cache_clear", "cache_info"):
        setattr(wrapped, attr, getattr(fn, attr))
    return wrapped

MAX_SKIP = 3               # select.go maxSkip
SKIP_THRESHOLD = 0.0       # select.go skipScoreThreshold
BINPACK_MAX = 18.0


def _dense_unroll() -> int:
    """Dense-scan unroll: 4 on TPU (amortizes per-step loop overhead in
    the O(N)-per-step kernels), 1 elsewhere (the body is large; unrolling
    multiplies compile time on CPU test/virtual-mesh runs)."""
    import jax as _jax
    return 4 if _jax.default_backend() == "tpu" else 1

_EMPTY_I2 = np.zeros((0, 0), dtype=np.int32)
_EMPTY_I1 = np.zeros(0, dtype=np.int32)
_EMPTY_B1 = np.zeros(0, dtype=bool)
_EMPTY_F1 = np.zeros(0, dtype=np.float32)
_EMPTY_F3 = np.zeros((0, 0, 0), dtype=np.float32)
_EMPTY_I3 = np.zeros((0, 0, 0), dtype=np.int32)


class PlacementBatch(NamedTuple):
    """Per-placement (scan-step) inputs, each shaped (P,)."""

    ask_cpu: jnp.ndarray
    ask_mem: jnp.ndarray
    ask_disk: jnp.ndarray
    n_dyn_ports: jnp.ndarray    # int32 dynamic ports asked
    has_static: jnp.ndarray     # bool: TG asks static ports
    limit: jnp.ndarray          # int32 scan-window limit for this placement
    count: jnp.ndarray          # int32 TG desired count (anti-affinity denom)
    penalty_idx: jnp.ndarray    # int32 node index to penalize, -1 = none
    active: jnp.ndarray         # bool: real placement vs padding
    # reserved-core ask (rank.go:481-524): effective cpu becomes
    # ask_cpu + ask_cores * mhz_per_core[node]; zeros when no core asks
    ask_cores: jnp.ndarray = _EMPTY_I1


class NodeState(NamedTuple):
    """Scan carry: mutable usage along the node axis, shaped (N,)."""

    used_cpu: jnp.ndarray
    used_mem: jnp.ndarray
    used_disk: jnp.ndarray
    placed: jnp.ndarray         # int32: this job+TG alloc count per node
    placed_job: jnp.ndarray     # int32: this job's alloc count (any TG)
    static_free: jnp.ndarray    # bool: TG's static ports still free
    dyn_avail: jnp.ndarray      # int32: free dynamic-range ports
    spread_counts: jnp.ndarray  # (S, V) int32
    dp_counts: jnp.ndarray = _EMPTY_I2     # (Dp, Vd) int32 allocs per value
    dev_free: jnp.ndarray = _EMPTY_I3      # (R, Gd, N) int32 free
                                           # instances; -1 = no match
    cores_free: jnp.ndarray = _EMPTY_I1    # (N,) int32 free reservable
                                           # cores; 0-size when no core ask


class NodeConst(NamedTuple):
    """Static per-eval node arrays, shaped (N,) (+ spread/distinct/device
    tables; the trailing fields default to 0-size axes, statically skipped
    at trace time)."""

    cpu_cap: jnp.ndarray
    mem_cap: jnp.ndarray
    disk_cap: jnp.ndarray
    feasible: jnp.ndarray       # bool: constraint/driver/etc feasibility
    affinity: jnp.ndarray       # float: normalized affinity score per node
    has_affinity: jnp.ndarray   # bool scalar
    distinct_hosts: jnp.ndarray  # bool scalar: distinct_hosts applies
    distinct_job_level: jnp.ndarray  # bool scalar: it is a JOB-level
                                     # constraint (blocks any of the job's
                                     # allocs, feasible.go:507)
    # spreads
    spread_vidx: jnp.ndarray    # (S, N) int32 value index per node, -1 missing
    spread_desired: jnp.ndarray  # (S, V) float; -1 = no target for value
    spread_has_targets: jnp.ndarray  # (S,) bool
    spread_weights: jnp.ndarray      # (S,) float
    spread_sum_weights: jnp.ndarray  # float scalar
    n_spreads: jnp.ndarray      # int32 scalar (0 = no spreads)
    # distinct_property (feasible.go:661, propertyset.go): per constraint
    # d, value index per node (-1 = attr missing -> infeasible) + limit
    dp_vidx: jnp.ndarray = _EMPTY_I2       # (Dp, N) int32
    dp_limit: jnp.ndarray = _EMPTY_I1       # (Dp,) int32
    dp_tg_scope: jnp.ndarray = _EMPTY_B1   # (Dp,) bool (info only)
    # devices (feasible.go:1270, scheduler/device.go): per TG device
    # request r and matching node device-group g
    dev_aff: jnp.ndarray = _EMPTY_F3       # (R, Gd, N) affinity score
    dev_count: jnp.ndarray = _EMPTY_I1     # (R,) int32 asked count
    dev_sum_weight: jnp.ndarray = np.float32(0.0)  # scalar sum |weights|
    # cores (rank.go:340-344): per-node MHz per reservable core; 0-size
    # when the lane carries no core asks (statically skipped at trace time)
    mhz_per_core: jnp.ndarray = _EMPTY_F1  # (N,) float


def _binpack_score(free_cpu, free_mem, spread_alg: bool):
    """BestFit v3 / worst-fit, normalized to [0,1]
    (reference: structs/funcs.go:236,263; rank.go:571 fitness/18)."""
    total = jnp.power(10.0, free_cpu) + jnp.power(10.0, free_mem)
    raw = jnp.where(spread_alg, total - 2.0, 20.0 - total)
    return jnp.clip(raw, 0.0, BINPACK_MAX) / BINPACK_MAX


def _spread_value_rows(state: NodeState, const: NodeConst, dtype):
    """Vectorized SpreadIterator.Next + evenSpreadScoreBoost (reference:
    spread.go:128-270) along the value axis: the (S, V) boost of a node
    that holds each value. A node's boost depends on its value alone."""
    def value_row(desired, has_targets, weight, counts):
        used = counts + 1                     # include this placement
        weight_frac = weight / jnp.maximum(const.spread_sum_weights, 1e-9)

        # -- target path (reference: spread.go:171-200)
        no_target = desired < 0.0
        boost_t = jnp.where(
            no_target, -1.0,
            jnp.where(desired == 0.0, -1.0,
                      (desired - used.astype(dtype))
                      / jnp.maximum(desired, 1e-9) * weight_frac))

        # -- even-spread path (reference: spread.go:216-270)
        present = counts > 0
        any_present = jnp.any(present)
        big = jnp.iinfo(jnp.int32).max
        min_c = jnp.min(jnp.where(present, counts, big))
        max_c = jnp.max(jnp.where(present, counts, 0))
        min_f = min_c.astype(dtype)
        max_f = max_c.astype(dtype)
        cur_f = counts.astype(dtype)
        even = jnp.where(
            counts != min_c,
            jnp.where(min_c == 0, -1.0, (min_f - cur_f) / jnp.maximum(min_f, 1e-9)),
            jnp.where(min_c == max_c, -1.0,
                      (max_f - min_f) / jnp.maximum(min_f, 1e-9)))
        boost_e = jnp.where(any_present, even, 0.0)
        return jnp.where(has_targets, boost_t, boost_e).astype(dtype)

    return jax.vmap(value_row)(
        const.spread_desired, const.spread_has_targets,
        const.spread_weights, state.spread_counts)


# The widest value axis (V, a static shape) that _spread_score lays over
# the node axis by compare-and-select; wider, it gathers the row. One
# step of the whole-axis scan, one lane, v5e, N 16,384, S 1 (us; PR 31):
#   V            72    75   100   128   300   512  1,024 2,048 4,096 8,192 16,384
#   select     63.7  64.1  64.7  64.3  67.2  70.5  78.4  92.9  122   181   299
#   one gather  190 (188-190 at every V: ~118 us of it the gather)
#   the tables gathered, then the arithmetic a node: 220 (1.25 gathers a step)
# The select is one fused compare-select-reduce, 0.9 ps a (value, node)
# pair, and writes no (V, N) words; the gather is 7 ns a node whatever
# V. They meet near V 8,500; 4,096 is the widest reading at which the
# select wins. The value axis is padded to the sublane tile (8): unpadded,
# V 75 and 100 read 74 us and V 300 105.
SPREAD_SELECT_V = 4096


def _spread_score(state: NodeState, const: NodeConst, dtype):
    """(N,) total spread boost: the value rows laid over the node axis.
    Up to SPREAD_SELECT_V values a node's index is compared with every
    value's and the one that matches added up (x + 0.0 is x, so the
    bits are a gather's); wider, the row is gathered. Batched gathers
    are the TPU's slow path (see _spread_boosts, the wave kernel's)."""
    S, N = const.spread_vidx.shape
    if S == 0:
        return jnp.zeros(N, dtype=dtype)

    def over_nodes(vidx, row):
        # vidx: (N,) value index, -1 = the node lacks the attribute
        if row.shape[0] <= SPREAD_SELECT_V:
            row = jnp.pad(row, (0, -row.shape[0] % 8))  # held by no node
            hit = (vidx[None, :]
                   == jnp.arange(row.shape[0], dtype=vidx.dtype)[:, None])
            per_node = jnp.sum(jnp.where(hit, row[:, None], 0.0), axis=0)
        else:
            per_node = row[jnp.maximum(vidx, 0)]
        return jnp.where(vidx < 0, -1.0, per_node)

    rows = _spread_value_rows(state, const, dtype)
    return jnp.sum(jax.vmap(over_nodes)(const.spread_vidx, rows), axis=0)


def _select_window(score, fit, limit, dtype):
    """Dense emulation of LimitIterator + MaxScoreIterator over nodes laid
    out in shuffled order (reference: select.go:38-77, stack.go:82).

    Yield set = first min(L, C) counted options (C = feasible minus the
    first <=3 low-score skips) plus skipped options as fallback when the
    source ran dry; winner = max score, earliest yield wins ties.
    Returns (chosen_index, chosen_score, n_yielded); chosen = -1 if none.
    """
    n = score.shape[0]
    low = fit & (score <= SKIP_THRESHOLD)
    skip_rank = jnp.cumsum(low.astype(jnp.int32))        # 1-based among low
    skipped = low & (skip_rank <= MAX_SKIP)
    counted = fit & ~skipped
    cpos = jnp.cumsum(counted.astype(jnp.int32))         # 1-based
    total_counted = cpos[-1] if n > 0 else jnp.int32(0)
    window = counted & (cpos <= limit)
    # fallback: yield skipped (in skip order) for the deficit
    deficit = jnp.maximum(0, limit - jnp.minimum(total_counted, limit))
    srank = jnp.cumsum(skipped.astype(jnp.int32))
    fallback = skipped & (srank <= deficit)
    yielded = window | fallback
    # yield order: counted first (cpos), then skipped (limit + srank)
    order = jnp.where(window, cpos, limit + srank)
    neg_inf = jnp.array(-jnp.inf, dtype=dtype)
    eff_score = jnp.where(yielded, score, neg_inf)
    best_score = jnp.max(eff_score)
    is_best = yielded & (eff_score == best_score)
    big = jnp.iinfo(jnp.int32).max
    best_order = jnp.min(jnp.where(is_best, order, big))
    chosen = jnp.argmax(is_best & (order == best_order))
    any_yield = jnp.any(yielded)
    chosen = jnp.where(any_yield, chosen, -1)
    return chosen, jnp.where(any_yield, best_score, neg_inf), \
        jnp.sum(yielded.astype(jnp.int32))


class PreemptTables(NamedTuple):
    """Per-eval candidate-eviction tables for dense preemption
    (reference: scheduler/preemption.go PreemptForTaskGroup :201-271,
    filterAndGroupPreemptibleAllocs :666, basicResourceDistance :611,
    filterSuperset :705). Candidate axis A = padded max allocs/node; rows
    are in the SAME order as ctx.proposed_allocs so float-tie argmins break
    identically to the host's first-strictly-smaller scan."""

    cpu: jnp.ndarray         # (N, A) comparable usage per candidate
    mem: jnp.ndarray         # (N, A)
    disk: jnp.ndarray        # (N, A)
    prio: jnp.ndarray        # (N, A) int32 job priority
    maxp: jnp.ndarray        # (N, A) int32 migrate.max_parallel
    grp: jnp.ndarray         # (N, A) int32 index into counts, -1 none
    dyn_ports: jnp.ndarray   # (N, A) int32 dynamic-range ports held
    static_rel: jnp.ndarray  # (N, A) bool holds an asked static port
    valid: jnp.ndarray       # (N, A) bool eligible candidate
    job_prio: jnp.ndarray    # () int32 scheduling job's priority


class PreemptState(NamedTuple):
    """Preemption scan carry: which candidates this eval already evicted,
    and per-(job,tg) eviction counts feeding the max_parallel penalty
    (reference: preemption.go scoreForTaskGroup / currentPreemptions)."""

    evicted: jnp.ndarray     # (N, A) bool
    counts: jnp.ndarray      # (G,) int32


MAX_PARALLEL_PENALTY = 50.0  # preemption.go:16
PREEMPT_SCORE_RATE = 0.0048  # rank.go preemptionScore
PREEMPT_SCORE_ORIGIN = 2048.0


def _distance(need_c, need_m, need_d, used_c, used_m, used_d):
    """basicResourceDistance (preemption.go:611): component is 0 when the
    corresponding ask dimension is <= 0."""
    dc = jnp.where(need_c > 0, (need_c - used_c) / jnp.maximum(need_c, 1e-9),
                   0.0)
    dm = jnp.where(need_m > 0, (need_m - used_m) / jnp.maximum(need_m, 1e-9),
                   0.0)
    dd = jnp.where(need_d > 0, (need_d - used_d) / jnp.maximum(need_d, 1e-9),
                   0.0)
    return jnp.sqrt(dc * dc + dm * dm + dd * dd)


def _preempt_search(state: NodeState, pstate: PreemptState,
                    ptab: PreemptTables, const: NodeConst,
                    ask_cpu, ask_mem, ask_disk, dtype,
                    lo: int, hi: Optional[int]):
    """Vectorized PreemptForTaskGroup over node positions [lo:hi).

    Per node: greedily pick eligible candidates (ascending priority group,
    then minimal distance+penalty) until the freed+free resources superset
    the ask, then filterSuperset. Returns per-node (met, evict_mask (n,A),
    freed_cpu/mem/disk, net_prio) for the slice."""
    sl = slice(lo, hi)
    used_c = ptab.cpu[sl].astype(dtype)
    used_m = ptab.mem[sl].astype(dtype)
    used_d = ptab.disk[sl].astype(dtype)
    valid_now = ptab.valid[sl] & ~pstate.evicted[sl]
    eligible = valid_now & (ptab.job_prio - ptab.prio[sl] >= 10)
    return _preempt_search_core(
        used_c, used_m, used_d, ptab.prio[sl], ptab.maxp[sl], ptab.grp[sl],
        valid_now, eligible, const.cpu_cap[sl], const.mem_cap[sl],
        const.disk_cap[sl], pstate.counts, ask_cpu, ask_mem, ask_disk,
        dtype)


def _preempt_search_core(used_c, used_m, used_d, prio, maxp, grp,
                         valid_now, eligible, cpu_cap, mem_cap, disk_cap,
                         counts, ask_cpu, ask_mem, ask_disk, dtype,
                         static_iters: bool = False):
    """The search itself over raw (n, A) candidate arrays -- shared by the
    dense per-node form (_preempt_search) and the windowed wavefront form
    (the slot buffer passes its B carried slots). ``static_iters`` runs
    the greedy as a fixed-length A-step scan instead of a while_loop:
    identical results (the body no-ops once a node is met), but
    straight-line compilable -- inside another scan a dynamic-trip-count
    loop of tiny (B, A) ops is pure dispatch latency."""
    n, A = used_c.shape

    # The host Preemptor's nodeRemaining subtracts only the CANDIDATE
    # allocs (own-job and terminal allocs are filtered before the
    # subtraction, preemption.go setCandidates) -- NOT the full carried
    # usage. An eviction set that "covers" the ask by this accounting can
    # still fail the authoritative AllocsFit re-check (rank.go:541), which
    # the caller models as the fit2 clamp.
    avail_c0 = cpu_cap - jnp.sum(jnp.where(valid_now, used_c, 0.0), axis=1)
    avail_m0 = mem_cap - jnp.sum(jnp.where(valid_now, used_m, 0.0), axis=1)
    avail_d0 = disk_cap - jnp.sum(jnp.where(valid_now, used_d, 0.0), axis=1)

    # max_parallel penalty from preemptions committed earlier in this eval
    n_pre = jnp.where(grp >= 0, counts[jnp.maximum(grp, 0)], 0)
    penalty = jnp.where((maxp > 0) & (n_pre >= maxp),
                        ((n_pre + 1 - maxp).astype(dtype)
                         * MAX_PARALLEL_PENALTY), 0.0)

    big_i = jnp.iinfo(jnp.int32).max
    inf = jnp.array(jnp.inf, dtype=dtype)

    def cond(carry):
        picked, av_c, av_m, av_d, _, _, _ = carry
        # allMet starts False in the host loop: the first pick is
        # unconditional even when available already covers the ask
        met = ((av_c >= ask_cpu) & (av_m >= ask_mem) & (av_d >= ask_disk)
               & jnp.any(picked, axis=1))
        cand = eligible & ~picked
        return jnp.any(~met & jnp.any(cand, axis=1))

    def body(carry):
        picked, av_c, av_m, av_d, ne_c, ne_m, ne_d = carry
        met = ((av_c >= ask_cpu) & (av_m >= ask_mem) & (av_d >= ask_disk)
               & jnp.any(picked, axis=1))
        cand = eligible & ~picked
        # ascending priority-group gating (preemption.go:666): only the
        # lowest remaining priority is pickable this round
        cur_prio = jnp.min(jnp.where(cand, prio, big_i), axis=1)
        in_group = cand & (prio == cur_prio[:, None])
        dist = _distance(ne_c[:, None], ne_m[:, None], ne_d[:, None],
                         used_c, used_m, used_d) + penalty
        key = jnp.where(in_group, dist, inf)
        pick = jnp.argmin(key, axis=1)          # first-min ties = host order
        do = ~met & jnp.any(in_group, axis=1)
        onehot = (jnp.arange(A)[None, :] == pick[:, None]) & do[:, None]
        pc = jnp.sum(jnp.where(onehot, used_c, 0.0), axis=1)
        pm = jnp.sum(jnp.where(onehot, used_m, 0.0), axis=1)
        pd = jnp.sum(jnp.where(onehot, used_d, 0.0), axis=1)
        return (picked | onehot, av_c + pc, av_m + pm, av_d + pd,
                ne_c - pc, ne_m - pm, ne_d - pd)

    init = (jnp.zeros((n, A), dtype=bool), avail_c0, avail_m0, avail_d0,
            jnp.full(n, ask_cpu, dtype=dtype),
            jnp.full(n, ask_mem, dtype=dtype),
            jnp.full(n, ask_disk, dtype=dtype))
    if static_iters:
        def scan_body(carry, _):
            return body(carry), None
        out_carry, _ = jax.lax.scan(scan_body, init, None, length=A,
                                    unroll=min(A, 8))
        picked, av_c, av_m, av_d, _, _, _ = out_carry
    else:
        picked, av_c, av_m, av_d, _, _, _ = jax.lax.while_loop(
            cond, body, init)
    met = ((av_c >= ask_cpu) & (av_m >= ask_mem) & (av_d >= ask_disk)
           & jnp.any(picked, axis=1))

    # filterSuperset (preemption.go:705): re-add picked in DESCENDING
    # distance-to-original-ask order until the ask is covered again.
    d0 = _distance(ask_cpu, ask_mem, ask_disk, used_c, used_m, used_d)
    sort_key = jnp.where(picked, -d0, inf)       # ascending(-d) = desc(d)
    order = jnp.argsort(sort_key, axis=1, stable=True)
    oc = jnp.take_along_axis(jnp.where(picked, used_c, 0.0), order, axis=1)
    om = jnp.take_along_axis(jnp.where(picked, used_m, 0.0), order, axis=1)
    od = jnp.take_along_axis(jnp.where(picked, used_d, 0.0), order, axis=1)
    cum_c = avail_c0[:, None] + jnp.cumsum(oc, axis=1)
    cum_m = avail_m0[:, None] + jnp.cumsum(om, axis=1)
    cum_d = avail_d0[:, None] + jnp.cumsum(od, axis=1)
    met_at = ((cum_c >= ask_cpu) & (cum_m >= ask_mem) & (cum_d >= ask_disk))
    # first position (in sorted order) where cumulative covers the ask;
    # keep sorted positions 0..first_met inclusive
    first_met = jnp.argmax(met_at, axis=1)
    keep_sorted = (jnp.arange(A)[None, :] <= first_met[:, None])
    in_picked_sorted = jnp.take_along_axis(picked, order, axis=1)
    keep_sorted = keep_sorted & in_picked_sorted
    evict = jnp.zeros_like(picked)
    evict = jax.vmap(lambda e, o, k: e.at[o].set(k))(evict, order,
                                                     keep_sorted)

    freed_c = jnp.sum(jnp.where(evict, used_c, 0.0), axis=1)
    freed_m = jnp.sum(jnp.where(evict, used_m, 0.0), axis=1)
    freed_d = jnp.sum(jnp.where(evict, used_d, 0.0), axis=1)

    # netPriority (rank.go): max prio + sum/max over the evicted set
    prio_f = prio.astype(dtype)
    mx = jnp.max(jnp.where(evict, prio_f, 0.0), axis=1)
    sm = jnp.sum(jnp.where(evict, prio_f, 0.0), axis=1)
    net_prio = jnp.where(mx > 0, mx + sm / jnp.maximum(mx, 1e-9), 0.0)
    return met, evict, freed_c, freed_m, freed_d, net_prio


# The selection window only ever yields the first `limit` (<= ~14 for 10K
# nodes) counted options in shuffled order, plus up to MAX_SKIP skips. So
# whenever the first FAST_T shuffled positions contain >= limit counted
# options, the outcome is fully determined by those FAST_T nodes -- the
# common case on healthy fleets. The scan step then runs O(FAST_T) work
# instead of O(N), falling back to the full pass via lax.cond otherwise.
FAST_T = 1024


def _scoring_parts(state: NodeState, const: NodeConst, b, dtype,
                   spread_alg: bool, lo: int, hi: Optional[int]):
    """Shared per-node fit + scoring over positions [lo:hi): returns
    (fit, final, feas_nonres, other_sum, nscores, new_cpu, new_mem)."""
    (ask_cpu, ask_mem, ask_disk, n_dyn, has_static, limit, count,
     penalty_idx, active, ask_cores) = b
    sl = slice(lo, hi)
    cpu_cap = const.cpu_cap[sl]
    mem_cap = const.mem_cap[sl]
    n = cpu_cap.shape[0]

    # reserved cores (rank.go:481-524): core-asking tasks' cpu becomes
    # mhz_per_core * cores on the candidate node, so the effective cpu
    # ask is node-dependent; count-exact core availability gates fit
    has_cores = const.mhz_per_core.shape[0] > 0
    eff_cpu = (ask_cpu + ask_cores.astype(dtype) * const.mhz_per_core[sl]
               if has_cores else ask_cpu)
    new_cpu = state.used_cpu[sl] + eff_cpu
    new_mem = state.used_mem[sl] + ask_mem
    new_disk = state.used_disk[sl] + ask_disk

    distinct_count = jnp.where(const.distinct_job_level,
                               state.placed_job[sl], state.placed[sl])
    # non-resource feasibility (constraints/ports/distinct) -- the part a
    # successful preemption cannot rescue
    feas_nonres = (const.feasible[sl]
                   & (state.dyn_avail[sl] >= n_dyn)
                   & (state.static_free[sl] | ~has_static)
                   & (~const.distinct_hosts | (distinct_count == 0)))

    # distinct_property (feasible.go:661): attr must resolve and the
    # job/tg's alloc count at this node's value must be under the limit
    Dp = const.dp_vidx.shape[0]
    if Dp > 0:
        vidx_d = const.dp_vidx[:, sl]
        safe_d = jnp.maximum(vidx_d, 0)
        cnt_d = jnp.take_along_axis(state.dp_counts, safe_d, axis=1)
        feas_nonres &= jnp.all(
            (vidx_d >= 0) & (cnt_d < const.dp_limit[:, None]), axis=0)

    # devices (feasible.go:1270 + device.go): every request needs a
    # matching group with enough free instances; affinity score of the
    # best group per request contributes one normalized score component
    R = const.dev_aff.shape[0]
    dev_score = None
    if R > 0:
        free_g = state.dev_free[:, :, sl]
        ok_g = free_g >= const.dev_count[:, None, None]
        feas_nonres &= jnp.all(jnp.any(ok_g, axis=1), axis=0)
        neg_inf = jnp.array(-jnp.inf, dtype=dtype)
        aff_g = jnp.where(ok_g, const.dev_aff[:, :, sl].astype(dtype),
                          neg_inf)
        best_aff = jnp.max(aff_g, axis=1)                   # (R, n)
        sum_aff = jnp.sum(jnp.where(jnp.any(ok_g, axis=1), best_aff, 0.0),
                          axis=0)
        dev_present = const.dev_sum_weight > 0
        dev_score = jnp.where(
            dev_present,
            sum_aff / jnp.maximum(const.dev_sum_weight, 1e-9), 0.0)
    if has_cores:
        feas_nonres &= state.cores_free[sl] >= ask_cores
    fit = (feas_nonres
           & (new_cpu <= cpu_cap)
           & (new_mem <= mem_cap)
           & (new_disk <= const.disk_cap[sl]))

    free_cpu = 1.0 - new_cpu / jnp.maximum(cpu_cap, 1e-9)
    free_mem = 1.0 - new_mem / jnp.maximum(mem_cap, 1e-9)
    binpack = _binpack_score(free_cpu, free_mem, spread_alg)

    collisions = state.placed[sl]
    anti = jnp.where(
        collisions > 0,
        -(collisions.astype(dtype) + 1.0) / jnp.maximum(
            count.astype(dtype), 1.0),
        0.0)
    idx = jnp.arange(lo, lo + n)
    is_penalty = idx == penalty_idx
    resched = jnp.where(is_penalty, -1.0, 0.0)
    aff = jnp.where(const.has_affinity, const.affinity[sl], 0.0)
    aff_present = aff != 0.0
    sliced_const = const._replace(spread_vidx=const.spread_vidx[:, sl])
    spread_total = _spread_score(state, sliced_const, dtype)
    spread_present = spread_total != 0.0

    nscores = (1
               + (collisions > 0).astype(dtype)
               + is_penalty.astype(dtype)
               + aff_present.astype(dtype)
               + spread_present.astype(dtype))
    other_sum = anti + resched + aff + spread_total
    if dev_score is not None:
        dev_present_f = (const.dev_sum_weight > 0).astype(dtype)
        nscores = nscores + dev_present_f
        other_sum = other_sum + dev_score
    final = (binpack + other_sum) / nscores
    return (fit, final, feas_nonres, other_sum, nscores, new_cpu, new_mem,
            new_disk)


def _window_outputs(final, fit, limit, dtype, lo):
    chosen, cscore, n_yield = _select_window(final, fit, limit, dtype)
    low = fit & (final <= SKIP_THRESHOLD)
    skip_rank = jnp.cumsum(low.astype(jnp.int32))
    skipped = low & (skip_rank <= MAX_SKIP)
    counted_total = jnp.sum((fit & ~skipped).astype(jnp.int32))
    chosen = jnp.where(chosen >= 0, chosen + lo, -1)
    return chosen, cscore, n_yield, counted_total


def _score_and_select(state: NodeState, const: NodeConst, b, dtype,
                      spread_alg: bool, lo: int, hi: Optional[int]):
    """One Stack.Select over node positions [lo:hi) (static slice).
    Returns (chosen global index, score, n_yield, counted_in_slice)."""
    limit = b[5]
    fit, final = _scoring_parts(state, const, b, dtype, spread_alg,
                                lo, hi)[:2]
    return _window_outputs(final, fit, limit, dtype, lo)


def _score_and_select_preempt(state: NodeState, pstate: PreemptState,
                              ptab: PreemptTables, const: NodeConst, b,
                              dtype, spread_alg: bool,
                              lo: int, hi: Optional[int]):
    """Stack.Select with eviction enabled (BinPackIterator evict=True,
    rank.go:545-565): nodes that fail the resource fit but have a
    successful preemption search are yielded with the post-eviction
    binpack score plus the preemption penalty (rank.go:851 logistic on
    netPriority), exactly like the host chain. Returns the plain window
    outputs plus the chosen node's eviction row and freed resources."""
    (ask_cpu, ask_mem, ask_disk, n_dyn, has_static, limit, count,
     penalty_idx, active, ask_cores) = b
    sl = slice(lo, hi)
    (fit, final, feas_nonres, other_sum, nscores, new_cpu, new_mem,
     new_disk) = _scoring_parts(state, const, b, dtype, spread_alg, lo, hi)

    met, evict, freed_c, freed_m, freed_d, net_prio = _preempt_search(
        state, pstate, ptab, const, ask_cpu, ask_mem, ask_disk, dtype,
        lo, hi)

    # fit2: the authoritative re-check after eviction (rank.go:541 ->
    # preemption insufficient under FULL usage -> node exhausted). The
    # search's candidates-only accounting can overstate availability when
    # this eval already placed on the node.
    fit2 = ((new_cpu - freed_c <= const.cpu_cap[sl])
            & (new_mem - freed_m <= const.mem_cap[sl])
            & (new_disk - freed_d <= const.disk_cap[sl]))
    fit_p = feas_nonres & ~fit & met & fit2
    free_cpu_p = 1.0 - (new_cpu - freed_c) / jnp.maximum(
        const.cpu_cap[sl], 1e-9)
    free_mem_p = 1.0 - (new_mem - freed_m) / jnp.maximum(
        const.mem_cap[sl], 1e-9)
    binpack_p = _binpack_score(free_cpu_p, free_mem_p, spread_alg)
    pscore = 1.0 / (1.0 + jnp.exp(
        PREEMPT_SCORE_RATE * (net_prio - PREEMPT_SCORE_ORIGIN)))
    final_p = (binpack_p + other_sum + pscore) / (nscores + 1.0)

    fit_c = fit | fit_p
    final_c = jnp.where(fit_p, final_p, final)
    chosen, cscore, n_yield, counted = _window_outputs(
        final_c, fit_c, limit, dtype, lo)

    # Gather the chosen node's eviction info (slice-local index)
    local = jnp.clip(chosen - lo, 0, evict.shape[0] - 1)
    was_preempt = (chosen >= 0) & fit_p[local]
    evict_row = jnp.where(was_preempt, evict[local],
                          jnp.zeros_like(evict[0]))
    freed = jnp.where(
        was_preempt,
        jnp.stack([freed_c[local], freed_m[local], freed_d[local]]),
        jnp.zeros(3, dtype=dtype))
    return chosen, cscore, n_yield, counted, evict_row, freed


def _commit_tables(state: NodeState, new_state: NodeState,
                   const: NodeConst, do, safe) -> NodeState:
    """Shared per-step commit of the spread / distinct_property / device
    carry tables for the winning node."""
    sel_vidx = const.spread_vidx[:, safe]               # (S,)
    S, V = state.spread_counts.shape
    if S > 0:
        upd = ((jnp.arange(V)[None, :] == jnp.maximum(sel_vidx, 0)[:, None])
               & (sel_vidx >= 0)[:, None] & do)
        new_state = new_state._replace(
            spread_counts=state.spread_counts + upd.astype(jnp.int32))

    Dp = const.dp_vidx.shape[0]
    if Dp > 0:
        dvidx = const.dp_vidx[:, safe]                  # (Dp,)
        Vd = state.dp_counts.shape[1]
        upd = ((jnp.arange(Vd)[None, :] == jnp.maximum(dvidx, 0)[:, None])
               & (dvidx >= 0)[:, None] & do)
        new_state = new_state._replace(
            dp_counts=state.dp_counts + upd.astype(jnp.int32))

    R = const.dev_aff.shape[0]
    if R > 0:
        Gd = state.dev_free.shape[1]
        free_c = state.dev_free[:, :, safe]             # (R, Gd)
        ok_gc = free_c >= const.dev_count[:, None]
        neg_inf = jnp.array(-jnp.inf, dtype=const.dev_aff.dtype)
        aff_c = jnp.where(ok_gc, const.dev_aff[:, :, safe], neg_inf)
        g_star = jnp.argmax(aff_c, axis=1)              # (R,) first-max
        oh = (jnp.arange(Gd)[None, :] == g_star[:, None])
        dec = (oh & do) * const.dev_count[:, None]
        new_state = new_state._replace(
            dev_free=state.dev_free.at[:, :, safe].add(
                -dec.astype(jnp.int32)))
    return new_state


def active_steps(active):
    """Scan steps a placement axis needs: one past its last active
    placement, along the last axis (numpy or jnp)."""
    xp = jnp if isinstance(active, jax.Array) else np
    p = active.shape[-1]
    return xp.max(xp.where(active, xp.arange(1, p + 1, dtype=np.int32), 0),
                  axis=-1)


def _unplaced(shapes, lead: tuple = ()):
    """(chosen, score, n_yielded) of steps that never ran."""
    return tuple(jnp.full(lead + y.shape, v, dtype=y.dtype)
                 for v, y in zip((-1, -jnp.inf, 0), shapes))


def _solve_placements_impl(const: NodeConst, init: NodeState,
                           batch: PlacementBatch, spread_alg: bool = False,
                           dtype_name: str = "float32", n_steps=None):
    """Place a batch of allocations sequentially via lax.scan.

    Each step reproduces one Stack.Select call (stack.go:128): score every
    node against current usage, select within the limited window, commit the
    winner's resources into the carry. Returns (chosen (P,), scores (P,),
    n_yielded (P,), final NodeState).

    The trip count is an operand, not a shape: the scan runs in blocks
    of the unroll width over the first ``n_steps`` placements only (by
    default up to the last active one) and leaves the rest of the axis
    at its fill (chosen -1, score -inf), so one program serves every
    lane width up to P and a lane pays for its own steps, not for its
    padding. Batched callers pass ``n_steps`` as one scalar for all
    their lanes: a trip count that rides the vmapped axis would turn
    the loop's carry into a select per block.
    """
    dtype = jnp.dtype(dtype_name)
    n_total = const.cpu_cap.shape[0]
    use_fast = n_total > 2 * FAST_T
    has_cores = const.mhz_per_core.shape[0] > 0

    def step(state: NodeState, b):
        (ask_cpu, ask_mem, ask_disk, n_dyn, has_static, limit, count,
         penalty_idx, active, ask_cores) = b

        if use_fast:
            # fast path: the window resolved within the first FAST_T
            # shuffled positions -- valid iff they contain >= limit
            # counted options (then the full-pass window is identical)
            f_chosen, f_score, f_yield, f_counted = _score_and_select(
                state, const, b, dtype, spread_alg, 0, FAST_T)

            def full(_):
                c, s, y, _cnt = _score_and_select(
                    state, const, b, dtype, spread_alg, 0, None)
                return c, s, y

            def fast(_):
                return f_chosen, f_score, f_yield

            chosen, cscore, n_yield = jax.lax.cond(
                f_counted >= limit, fast, full, operand=None)
        else:
            chosen, cscore, n_yield, _ = _score_and_select(
                state, const, b, dtype, spread_alg, 0, None)

        do = active & (chosen >= 0)
        safe = jnp.maximum(chosen, 0)
        # O(1) scatter updates: only the winner's usage changes
        add_f = do.astype(dtype)
        add_i = do.astype(jnp.int32)
        eff_cpu = (ask_cpu + ask_cores.astype(dtype)
                   * const.mhz_per_core[safe] if has_cores else ask_cpu)
        new_state = state._replace(
            used_cpu=state.used_cpu.at[safe].add(add_f * eff_cpu),
            used_mem=state.used_mem.at[safe].add(add_f * ask_mem),
            used_disk=state.used_disk.at[safe].add(add_f * ask_disk),
            placed=state.placed.at[safe].add(add_i),
            placed_job=state.placed_job.at[safe].add(add_i),
            static_free=state.static_free.at[safe].set(
                state.static_free[safe] & ~(do & has_static)),
            dyn_avail=state.dyn_avail.at[safe].add(-add_i * n_dyn),
        )
        if has_cores:
            new_state = new_state._replace(
                cores_free=state.cores_free.at[safe].add(
                    -add_i * ask_cores))
        new_state = _commit_tables(state, new_state, const, do, safe)
        chosen_out = jnp.where(do, chosen, -1)
        return new_state, (chosen_out, cscore, n_yield)

    ask_cores_xs = (batch.ask_cores if batch.ask_cores.shape[0]
                    else jnp.zeros_like(batch.count))
    xs = (batch.ask_cpu, batch.ask_mem, batch.ask_disk, batch.n_dyn_ports,
          batch.has_static, batch.limit, batch.count, batch.penalty_idx,
          batch.active, ask_cores_xs)
    unroll = _dense_unroll()
    if n_steps is None:
        n_steps = active_steps(batch.active)
    p = batch.ask_cpu.shape[0]
    block = unroll if p % unroll == 0 else 1

    def run_block(k, carry):
        state, outs = carry
        lo = k * block
        state, ys = jax.lax.scan(
            step, state,
            tuple(jax.lax.dynamic_slice_in_dim(x, lo, block) for x in xs),
            unroll=block)
        return state, tuple(
            jax.lax.dynamic_update_slice_in_dim(o, y, lo, 0)
            for o, y in zip(outs, ys))

    _, ys = jax.eval_shape(step, init, tuple(x[0] for x in xs))
    final_state, (chosen, scores, n_yielded) = jax.lax.fori_loop(
        0, (n_steps + block - 1) // block, run_block,
        (init, _unplaced(ys, (p,))))
    return chosen, scores, n_yielded, final_state


solve_placements = functools.partial(
    jax.jit, static_argnames=("spread_alg", "dtype_name"))(
        _solve_placements_impl)


def _solve_placements_preempt_impl(const: NodeConst, init: NodeState,
                                   batch: PlacementBatch,
                                   ptab: PreemptTables,
                                   pinit: PreemptState,
                                   spread_alg: bool = False,
                                   dtype_name: str = "float32"):
    """solve_placements with dense preemption: each scan step runs the
    eviction-enabled select; committing a preempting winner releases the
    evicted candidates' resources and ports into the carry and bumps the
    per-(job,tg) eviction counts (the reference's plan.NodePreemptions +
    currentPreemptions bookkeeping, generic_sched.go:924 + preemption.go).

    Extra outputs: evict_rows (P, A) bool -- candidate rows evicted by each
    placement on its chosen node."""
    dtype = jnp.dtype(dtype_name)
    n_total = const.cpu_cap.shape[0]
    use_fast = n_total > 2 * FAST_T
    G = pinit.counts.shape[0]
    A = ptab.cpu.shape[1]

    def step(carry, b):
        state, pstate = carry
        (ask_cpu, ask_mem, ask_disk, n_dyn, has_static, limit, count,
         penalty_idx, active, ask_cores) = b

        if use_fast:
            f = _score_and_select_preempt(
                state, pstate, ptab, const, b, dtype, spread_alg,
                0, FAST_T)

            def full(_):
                return _score_and_select_preempt(
                    state, pstate, ptab, const, b, dtype, spread_alg,
                    0, None)

            def fast(_):
                return f

            chosen, cscore, n_yield, _cnt, evict_row, freed = jax.lax.cond(
                f[3] >= limit, fast, full, operand=None)
        else:
            chosen, cscore, n_yield, _cnt, evict_row, freed = \
                _score_and_select_preempt(
                    state, pstate, ptab, const, b, dtype, spread_alg,
                    0, None)

        do = active & (chosen >= 0)
        safe = jnp.maximum(chosen, 0)
        add_f = do.astype(dtype)
        add_i = do.astype(jnp.int32)
        evict_row = evict_row & do

        # release evicted usage + ports, then charge the placement
        dyn_back = jnp.sum(
            jnp.where(evict_row, ptab.dyn_ports[safe], 0)).astype(jnp.int32)
        static_back = jnp.any(evict_row & ptab.static_rel[safe])
        new_state = state._replace(
            used_cpu=state.used_cpu.at[safe].add(
                add_f * ask_cpu - freed[0]),
            used_mem=state.used_mem.at[safe].add(
                add_f * ask_mem - freed[1]),
            used_disk=state.used_disk.at[safe].add(
                add_f * ask_disk - freed[2]),
            placed=state.placed.at[safe].add(add_i),
            placed_job=state.placed_job.at[safe].add(add_i),
            static_free=state.static_free.at[safe].set(
                (state.static_free[safe] | static_back)
                & ~(do & has_static)),
            dyn_avail=state.dyn_avail.at[safe].add(
                dyn_back - add_i * n_dyn),
        )
        new_state = _commit_tables(state, new_state, const, do, safe)

        grp_row = ptab.grp[safe]                      # (A,)
        grp_hot = ((jnp.arange(G, dtype=jnp.int32)[None, :]
                    == jnp.maximum(grp_row, 0)[:, None])
                   & (grp_row >= 0)[:, None] & evict_row[:, None])
        new_counts = (pstate.counts
                      + jnp.sum(grp_hot, axis=0)).astype(jnp.int32)
        new_pstate = PreemptState(
            evicted=pstate.evicted.at[safe].set(
                pstate.evicted[safe] | evict_row),
            counts=new_counts)
        chosen_out = jnp.where(do, chosen, -1)
        return (new_state, new_pstate), (chosen_out, cscore, n_yield,
                                         evict_row)

    ask_cores_xs = (batch.ask_cores if batch.ask_cores.shape[0]
                    else jnp.zeros_like(batch.count))
    (final_state, final_pstate), (chosen, scores, n_yielded, evict_rows) = \
        jax.lax.scan(
            step, (init, pinit),
            (batch.ask_cpu, batch.ask_mem, batch.ask_disk,
             batch.n_dyn_ports, batch.has_static, batch.limit, batch.count,
             batch.penalty_idx, batch.active, ask_cores_xs),
            unroll=_dense_unroll())
    return chosen, scores, n_yielded, evict_rows, final_state


solve_placements_preempt = functools.partial(
    jax.jit, static_argnames=("spread_alg", "dtype_name"))(
        _solve_placements_preempt_impl)


def solve_eval_batch_preempt(const, init, batch, ptab, pinit,
                             spread_alg: bool = False,
                             dtype_name: str = "float32"):
    """Batched-eval form of solve_placements_preempt (leading (E, ...)
    axis), mirroring solve_eval_batch."""
    inner = functools.partial(solve_placements_preempt,
                              spread_alg=spread_alg, dtype_name=dtype_name)
    return jax.vmap(inner)(const, init, batch, ptab, pinit)


def solve_eval_batch(const: NodeConst, init: NodeState, batch: PlacementBatch,
                     spread_alg: bool = False,
                     dtype_name: str = "float32"):
    """Solve E independent evaluations in one dispatch: every leaf carries a
    leading eval axis (E, ...). This is the TPU-native form of the
    reference's optimistic concurrency (SURVEY.md section 2.6: N scheduler
    workers scheduling concurrently against snapshots, serialized only at
    plan apply) -- evals don't see each other's placements; the plan
    applier resolves conflicts exactly as nomad/plan_apply.go does.

    The eval axis is the data-parallel axis for multi-chip sharding; the
    node axis shards as the model axis (see parallel/mesh.py).
    """
    def inner(c, i, b, n_steps):
        return solve_placements(c, i, b, spread_alg=spread_alg,
                                dtype_name=dtype_name, n_steps=n_steps)
    # one trip count for the dispatch, its widest lane's: the eval axis
    # is a vector (and, on a mesh, a device) axis here, so a narrower
    # lane's further steps are inert, not skipped
    return jax.vmap(inner, in_axes=(0, 0, 0, None))(
        const, init, batch, jnp.max(active_steps(batch.active)))


# ---------------------------------------------------------------------------
# Fused transport: one host->device transfer per dispatch.
#
# A lane's NamedTuples flatten to ~30-45 small leaves; transferring each
# separately pays one host<->device round trip apiece, where the compiled
# 2000-step scan itself runs in well under a millisecond. Here leaves
# are grouped by (dtype, shape), stacked into a handful of buffers, moved
# in ONE jax.device_put, and re-sliced INSIDE the jit (free -- XLA fuses
# the slices away). Outputs are stacked in-jit and fetched once.

# group-class -> transfer-ledger tree-group name (solver/xferobs.py):
# position i is the i-th tree handed to _fuse_trees
_FUSE_TREE_NAMES = ("const", "init", "batch", "ptab", "pinit")


def _fuse_trees(trees):
    """Flatten trees and group non-empty leaves by (tree-index, dtype,
    shape). Returns (stacked buffers, per-leaf meta, treedef, group
    keys). The tree-index marker (0 = the NodeConst tree; 1.. = the
    mutable init/batch/preempt trees) keeps fleet-constant leaves in
    their OWN stacked buffers even when a usage leaf shares dtype+shape
    (cpu_cap vs used_cpu): the device-resident const cache can then pin
    the const buffers across dispatches while the delta buffers ship
    fresh every time.  Keying by the full tree index (not just the
    const/delta class) additionally keeps init, batch and the
    preemption port tables in separate buffers, so the transfer ledger
    (solver/xferobs.py) can decompose every dispatch's bytes by tree
    group; same bytes either way, one stacked buffer more or less per
    shape bucket."""
    metas = []
    groups: dict = {}
    per_tree = [jax.tree_util.tree_flatten(t) for t in trees]
    treedef = jax.tree_util.tree_structure(tuple(trees))
    for ti, (leaves, _) in enumerate(per_tree):
        for leaf in leaves:
            arr = np.asarray(leaf)
            if arr.size == 0:
                metas.append(("zero", arr.shape, arr.dtype.str))
                continue
            key = (ti, arr.dtype.str, arr.shape)
            rows = groups.setdefault(key, [])
            metas.append(("buf", key, len(rows)))
            rows.append(arr)
    group_keys = tuple(groups.keys())
    stacked = [np.stack(groups[k]) for k in group_keys]
    return stacked, tuple(metas), treedef, group_keys


@_single_flight
@functools.lru_cache(maxsize=None)
def _make_fused_fn(metas, treedef, group_keys, spread_alg: bool,
                   dtype_name: str, preempt: bool, batched: bool):
    """Per-shape-bucket factory for the fused-transport program. The
    lru_cache IS the dispatch discipline: one jitted callable per
    bucket signature, constructed exactly once, so steady state holds
    exactly one trace per bucket (jitcheck's retrace gate; the old
    module dict kept the same keys but hid the `@jax.jit` behind a
    bare call site)."""
    from ..server.telemetry import metrics
    metrics.incr("nomad.solver.dense_programs")
    gpos = {k: i for i, k in enumerate(group_keys)}

    def rebuild(buffers):
        leaves = []
        for m in metas:
            if m[0] == "zero":
                leaves.append(jnp.zeros(m[1], dtype=np.dtype(m[2])))
            else:
                leaves.append(buffers[gpos[m[1]]][m[2]])
        return jax.tree_util.tree_unflatten(treedef, leaves)

    if preempt:
        inner = functools.partial(_solve_placements_preempt_impl,
                                  spread_alg=spread_alg,
                                  dtype_name=dtype_name)
        if batched:
            inner = jax.vmap(inner)

        @jax.jit
        def fn(*buffers):
            const, init, batch, ptab, pinit = rebuild(buffers)
            chosen, scores, n_yielded, evict_rows, _ = inner(
                const, init, batch, ptab, pinit)
            out = jnp.stack([chosen.astype(scores.dtype), scores,
                             n_yielded.astype(scores.dtype)])
            return out, evict_rows
        return fn

    def one(const, init, batch, n_steps=None):
        return _solve_placements_impl(
            const, init, batch, spread_alg=spread_alg,
            dtype_name=dtype_name, n_steps=n_steps)[:3]

    def _solve_lanes_in_turn(const, init, batch):
        """The lanes of a dispatch one after the other, each over its
        own active steps, up to the last lane that has any: a padded
        lane and a padded step cost nothing, and a retry of 40
        placements beside a first attempt of 1,200 pays for 40. One
        program serves every lane count and width up to its buffers'
        (E, P). A step is a chain of some hundred small scans and
        reductions over the node axis, bound by their latency (v5e,
        N 16,384, S 1, V 75, PR 31): 64 us alone, as a batch of one or
        as plain (N,) vectors; 64 us each of 8 here; 24 us each of 8 as
        a vector axis under vmap (193 us for the eight). So the loop
        loses 2.7x where eight lanes are equally wide and wins where
        they differ, which is what a drained queue sends (`spread-drain`:
        a first attempt beside retries of tens, 1,330 steps a launch, 85
        ms in turn against 232 ms for the vector's 1,200 steps). A
        gather in the step is linear in the lanes and levels the three
        (SPREAD_SELECT_V). Lanes of like width as one vector: ROADMAP
        A4."""
        steps = active_steps(batch.active)
        trees = (const, init, batch)
        row_of_one = jax.vmap(one, in_axes=(0, 0, 0, None))

        def lane(e, outs):
            ys = row_of_one(*jax.tree_util.tree_map(
                lambda a: jax.lax.dynamic_slice_in_dim(a, e, 1, 0), trees),
                steps[e])
            return tuple(jax.lax.dynamic_update_slice_in_dim(o, y, e, 0)
                         for o, y in zip(outs, ys))
        return jax.lax.fori_loop(
            0, active_steps(steps > 0), lane,
            _unplaced(jax.eval_shape(row_of_one, *trees, steps[0])))

    @jax.jit
    def fn(*buffers):
        const, init, batch = rebuild(buffers)
        if batched:
            chosen, scores, n_yielded = _solve_lanes_in_turn(
                const, init, batch)
        else:
            chosen, scores, n_yielded = one(const, init, batch)
        return jnp.stack([chosen.astype(scores.dtype), scores,
                          n_yielded.astype(scores.dtype)])
    return fn


def solve_lane_fused(const, init, batch, ptab=None, pinit=None, *,
                     spread_alg: bool, dtype_name: str,
                     batched: bool = False, wave: bool = False,
                     cache_version=None, delta_src=None):
    """Solve with minimal transfers: returns host-side numpy
    (chosen int64, scores, n_yielded int64[, evict_rows]). When ``batched``
    every leaf carries a leading eval axis and outputs do too. ``wave``
    routes through the wavefront path (caller must have checked
    eligibility): host-side O(N) precompute + compact-table device scan
    (solve_lane_wave). Stacking chosen/n_yielded through the score dtype
    is exact: node indexes and yield counts are < 2^24. ``cache_version``
    tags const-tree buffers in the device-resident cache with the
    packing snapshot's node_table_index (solver/constcache.py);
    ``delta_src`` is that snapshot's (store, index) pair for the
    ISSUE-20 version chain -- journal-covered generations ship only
    their diff and scatter it into the resident buffers on device. The
    wave transports ride it; the whole-axis branch below ships whole."""
    if wave and ptab is None:
        return solve_lane_wave(const, init, batch, spread_alg=spread_alg,
                               dtype_name=dtype_name, batched=batched,
                               cache_version=cache_version,
                               delta_src=delta_src)
    if wave and ptab is not None:
        return solve_lane_wave_preempt(
            const, init, batch, ptab, pinit, spread_alg=spread_alg,
            dtype_name=dtype_name, batched=batched,
            cache_version=cache_version, delta_src=delta_src)
    trees = ((const, init, batch) if ptab is None
             else (const, init, batch, ptab, pinit))
    stacked, metas, treedef, group_keys = _fuse_trees(trees)
    fn = _make_fused_fn(metas, treedef, group_keys, spread_alg,
                        dtype_name, ptab is not None, batched)
    from . import xferobs
    from .constcache import device_put_cached
    # only const-tree buffers (tree index 0) are pinned: init/batch
    # deltas change every dispatch and would churn the LRU. Tags name
    # each stacked buffer's tree group for the transfer ledger; the
    # stacked buffers are _fuse_trees' fresh np.stack outputs, so the
    # content cache may retain them as frozen shadows without copying.
    # No version chain (delta_src) for any caller of this branch: a
    # whole-axis lane's tables lie in its own eval's scan order, so
    # between dispatches they change wholesale (where they do not, a
    # fleet of one node size, the content cache already holds them),
    # and the chain's scatter programs, one an update-count bucket and
    # buffer shape, are a second family of compiles that a drained
    # window keeps meeting (PERF.md section 6, PR 30).
    stages.mark("put")
    buffers, _ = device_put_cached(
        stacked, version=cache_version,
        cacheable=[k[0] == 0 for k in group_keys],
        tags=[_FUSE_TREE_NAMES[k[0]] for k in group_keys])
    stages.mark("launch")
    out = fn(*buffers)
    stages.mark("fetch")
    # the 3-way output axis is leading in both forms: (3, P) or (3, E, P)
    if ptab is not None:
        with jitcheck.sanctioned_fetch("fused_preempt"):
            # the ONE designed bulk fetch of the fused transport
            combined, evict_rows = jax.device_get(out)
        xferobs.note_fetch(
            xferobs.tree_nbytes((combined, evict_rows)), "fused_preempt")
        return (combined[0].astype(np.int64), combined[1],
                combined[2].astype(np.int64), np.asarray(evict_rows))
    with jitcheck.sanctioned_fetch("fused"):
        combined = jax.device_get(out)
    xferobs.note_fetch(xferobs.tree_nbytes(combined), "fused")
    return (combined[0].astype(np.int64), combined[1],
            combined[2].astype(np.int64))


# ---------------------------------------------------------------------------
# Wavefront kernel: O(B)-per-step selection for uniform-ask lanes.
#
# Every placement in a lane is the SAME TaskGroup ask (service.pack fills the
# (P,) ask arrays with one value), so a node's whole score/feasibility
# trajectory is a closed form of how many copies it already took:
#   new_cpu(j) = used0 + (j+1)*ask          (bit-exact vs the scan's
#                                            accumulation for integer-valued
#                                            floats -- cpu/mem/disk are ints)
#   capacity c = max m with used0 + m*ask <= cap (per resource, ports,
#                distinct_hosts), computed ONCE per node.
# The selection window (select.go LimitIterator + MaxScoreIterator) only
# ever examines the first limit+MAX_SKIP FIT nodes in shuffled order, so the
# scan carries just a B-slot buffer of those front nodes (position, copies
# taken j, capacity c, score inputs) instead of rescoring all N nodes:
# per-step work drops from O(N) to O(B), the chosen slot's j increments, and
# a saturated slot (j == c) is shifted out and refilled from a precomputed
# fit-order list. Steps are ~100x cheaper than the dense pass; parity with
# the host oracle is enforced by the same gating suites (test_solver_parity,
# test_parity_scale) because eligible lanes route here in production.
#
# Eligibility (checked host-side, service.PackedLane.wavefront_ok): no
# distinct_property / devices / cores / preemption, uniform asks over the
# active prefix, and limit + MAX_SKIP within a buffer variant (WAVE_B for
# log2 windows, WAVE_B_WIDE for spread/affinity windows). Spreads ride the
# compact kernel's carry as (S, V) counts; reschedule penalties ride the
# scan xs. The in-kernel variant below (_solve_wavefront_impl) stays
# S == 0-only and is the test reference; production routes through
# solve_lane_wave (host precompute + compact (C, 8+S) table).

WAVE_B = 32
# wide-window variant for spread/affinity lanes (the host stack forces
# limit = max(count, 100) when either is present, stack.go:176-185)
WAVE_B_WIDE = 128


class _WaveSpread(NamedTuple):
    """Spread tables the compact wavefront carries: per-spread value
    counts (the ONLY cross-placement coupling spreads add) plus the
    static scoring tables."""

    counts: jnp.ndarray       # (S, V) int32
    desired: jnp.ndarray      # (S, V)
    has_targets: jnp.ndarray  # (S,) bool
    weights: jnp.ndarray      # (S,)
    sum_weights: jnp.ndarray  # ()


# Placement-axis padding for wavefront dispatch shapes: pow2 with a floor,
# so production lanes of many sizes land on FEW compiled variants (inert
# padded steps cost ~a microsecond each; an extra XLA compile costs
# seconds).
WAVE_P_BUCKETS_MIN = 32


def _wave_p_bucket(p: int) -> int:
    b = WAVE_P_BUCKETS_MIN
    while b < p:
        b *= 2
    return b


def _wave_unroll() -> int:
    """Scan unroll: 8 on TPU (amortizes per-step loop overhead), 1
    elsewhere (unrolling multiplies the compiled body; CPU/virtual-mesh
    runs are compile-time-bound, not step-overhead-bound)."""
    import jax as _jax
    return 8 if _jax.default_backend() == "tpu" else 1


def _wave_refill_shift(compact, cursor, w, j2, slot, gate, arangeB,
                       arangeC):
    """Shared winner shift/refill for the compact and run-block wave
    kernels: shift slots above ``w`` left, append the ``cursor`` row of
    ``compact``, advance the cursor -- all gated on ``gate``. The two
    kernels' bit-parity contract depends on this being ONE
    implementation (tests/test_wave_block.py)."""
    C = compact.shape[0]
    B = arangeB.shape[0]
    # refill row by a one-hot masked reduce (safe under vmap on TPU;
    # a vmapped scalar-index slice lowers to a gather)
    oh_c = arangeC == jnp.clip(cursor, 0, C - 1)
    entry_row = jnp.sum(jnp.where(oh_c[:, None], compact, 0.0), axis=0)
    take_next = arangeB >= w
    is_last = arangeB == B - 1
    j_sh = jnp.where(is_last, 0,
                     jnp.where(take_next, jnp.roll(j2, -1), j2))
    slot_sh = jnp.where(
        is_last[:, None], entry_row[None, :],
        jnp.where(take_next[:, None], jnp.roll(slot, -1, axis=0), slot))
    j3 = jnp.where(gate, j_sh, j2)
    slot2 = jnp.where(gate, slot_sh, slot)
    cursor2 = cursor + gate.astype(jnp.int32)
    return j3, slot2, cursor2


def _slotmat_cols(c, init: NodeState, const: NodeConst, aff_node, dtype):
    """(N, 7) per-node row: [c, used_cpu0, used_mem0, cpu_cap, mem_cap,
    placed0, affinity]. c/placed are < 2^24 so the float cast is exact."""
    return jnp.stack([
        c.astype(dtype), init.used_cpu.astype(dtype),
        init.used_mem.astype(dtype), const.cpu_cap.astype(dtype),
        const.mem_cap.astype(dtype), init.placed.astype(dtype),
        aff_node.astype(dtype)], axis=1)


def _solve_wavefront_impl(const: NodeConst, init: NodeState,
                          batch: PlacementBatch, spread_alg: bool = False,
                          dtype_name: str = "float32"):
    """Uniform-ask lane solve; returns (chosen (P,) i32, scores (P,),
    n_yielded (P,) i32), identical to _solve_placements_impl's first three
    outputs on eligible lanes."""
    dtype = jnp.dtype(dtype_name)
    N = const.cpu_cap.shape[0]
    P = batch.ask_cpu.shape[0]
    B = WAVE_B

    # Lane scalars from row 0 (uniform over the active prefix; padding rows
    # are inert and their outputs are sliced off by the caller).
    ask_cpu = batch.ask_cpu[0]
    ask_mem = batch.ask_mem[0]
    ask_disk = batch.ask_disk[0]
    n_dyn = batch.n_dyn_ports[0]
    has_static = batch.has_static[0]
    L = batch.limit[0]
    count = batch.count[0]
    n_active = jnp.sum(batch.active.astype(jnp.int32))

    BIG_I = jnp.int32(2 ** 30)

    def cap_dim(used0, cap, ask):
        # c = max m >= 0 with used0 + m*ask <= cap, using the SAME float
        # predicate as scoring (float division then +-2 correction).
        q = jnp.floor((cap - used0) / jnp.maximum(ask, 1e-9)).astype(
            jnp.int32)

        def fits(m):
            return used0 + m.astype(dtype) * ask <= cap

        q = jnp.where(fits(q), q, q - 1)
        q = jnp.where(fits(q), q, q - 1)
        q = jnp.maximum(q, 0)
        q = jnp.where(fits(q + 1), q + 1, q)
        q = jnp.where(fits(q + 1), q + 1, q)
        q = jnp.where(fits(q), q, 0)       # used0 alone already over cap
        return jnp.where(ask > 0, q, BIG_I)

    c = jnp.minimum(cap_dim(init.used_cpu, const.cpu_cap, ask_cpu),
                    cap_dim(init.used_mem, const.mem_cap, ask_mem))
    c = jnp.minimum(c, cap_dim(init.used_disk, const.disk_cap, ask_disk))
    c = jnp.minimum(c, jnp.where(n_dyn > 0,
                                 init.dyn_avail // jnp.maximum(n_dyn, 1),
                                 BIG_I))
    c = jnp.where(has_static,
                  jnp.minimum(c, jnp.where(init.static_free, 1, 0)), c)
    distinct0 = jnp.where(const.distinct_job_level, init.placed_job,
                          init.placed)
    c = jnp.where(const.distinct_hosts,
                  jnp.minimum(c, jnp.where(distinct0 > 0, 0, 1)), c)
    c = jnp.where(const.feasible, c, 0)
    c = jnp.clip(c, 0, P)

    aff_node = jnp.where(const.has_affinity, const.affinity,
                         jnp.zeros_like(const.affinity))

    # fit_order[k] = shuffled position of the k-th fit node; N = sentinel.
    # Length covers both the node count and the compact prefix P+B (P can
    # exceed N on tiny fleets).
    L_fo = max(N, P) + B
    tak = c > 0
    kpos = jnp.cumsum(tak.astype(jnp.int32)) - 1
    scatter_idx = jnp.where(tak, kpos, L_fo)         # OOB -> dropped
    fit_order = jnp.full(L_fo, N, dtype=jnp.int32).at[scatter_idx].set(
        jnp.arange(N, dtype=jnp.int32), mode="drop")

    nodemat = _slotmat_cols(c, init, const, aff_node, dtype)

    # Only the first P+B fit nodes can ever enter the buffer (one pull per
    # saturation, at most one saturation per placement), so gather their
    # rows ONCE into a compact table: per-step refills then index (P+B, 7)
    # instead of the full (N, 7) -- the big-table gather inside the scan is
    # what dominated at larger fused widths.
    C = P + B
    compact_pos = fit_order[:C]                        # (C,) node positions
    safe_cp = jnp.clip(compact_pos, 0, N - 1)
    compact = nodemat[safe_cp]                         # (C, 7) one gather
    compact = compact.at[:, 0].set(
        jnp.where(compact_pos < N, compact[:, 0], 0.0))

    pos0 = compact_pos[:B]
    slot0 = compact[:B]
    j0 = jnp.zeros(B, dtype=jnp.int32)
    cursor0 = jnp.int32(B)

    arangeB = jnp.arange(B, dtype=jnp.int32)
    arangeC = jnp.arange(C, dtype=jnp.int32)
    neg_inf = jnp.array(-jnp.inf, dtype=dtype)
    big = jnp.iinfo(jnp.int32).max

    def step(carry, xs):
        i, pen_i = xs
        pos, j, slot, cursor = carry
        cs = slot[:, 0]
        fit = (pos < N) & (j.astype(dtype) < cs)
        jp1 = (j + 1).astype(dtype)
        new_cpu = slot[:, 1] + jp1 * ask_cpu
        new_mem = slot[:, 2] + jp1 * ask_mem
        free_cpu = 1.0 - new_cpu / jnp.maximum(slot[:, 3], 1e-9)
        free_mem = 1.0 - new_mem / jnp.maximum(slot[:, 4], 1e-9)
        binpack = _binpack_score(free_cpu, free_mem, spread_alg)
        coll = slot[:, 5] + j.astype(dtype)
        anti = jnp.where(
            coll > 0, -(coll + 1.0) / jnp.maximum(count.astype(dtype), 1.0),
            0.0)
        # per-placement reschedule penalty: the previous alloc's node
        # scores -1 for THIS placement only (rank.go penalty iterator)
        is_pen = (pen_i >= 0) & (pos == pen_i)
        resched = jnp.where(is_pen, -1.0, 0.0)
        affs = slot[:, 6]
        aff_present = affs != 0.0
        nscores = (1.0 + (coll > 0).astype(dtype)
                   + is_pen.astype(dtype) + aff_present.astype(dtype))
        other = (anti + resched) + affs
        final = (binpack + other) / nscores

        low = fit & (final <= SKIP_THRESHOLD)
        skip_rank = jnp.cumsum(low.astype(jnp.int32))
        skipped = low & (skip_rank <= MAX_SKIP)
        counted = fit & ~skipped
        cpos = jnp.cumsum(counted.astype(jnp.int32))
        total_counted = cpos[-1]
        window = counted & (cpos <= L)
        deficit = jnp.maximum(0, L - jnp.minimum(total_counted, L))
        srank = jnp.cumsum(skipped.astype(jnp.int32))
        fallback = skipped & (srank <= deficit)
        yielded = window | fallback
        order = jnp.where(window, cpos, L + srank)
        eff = jnp.where(yielded, final, neg_inf)
        best = jnp.max(eff)
        is_best = yielded & (eff == best)
        border = jnp.min(jnp.where(is_best, order, big))
        w = jnp.argmax(is_best & (order == border))
        any_yield = jnp.any(yielded)
        do = (i < n_active) & any_yield
        # NOTE: the step body is deliberately gather/scatter-free beyond
        # the one-hot selects below -- per-lane dynamic indexing inside the
        # scan turns into batched gather/scatter under vmap, which costs
        # ~usec per op on TPU and dominated the fused-eval dispatch.
        oh_w = arangeB == w
        chosen = jnp.where(
            do, jnp.sum(jnp.where(oh_w, pos, 0), dtype=jnp.int32), -1)
        score_out = jnp.where(any_yield, best, neg_inf)
        ny = jnp.sum(yielded.astype(jnp.int32))

        # commit: the chosen slot takes one more copy; shift it out + refill
        # from the fit order once saturated (at most one per step)
        do_i = do.astype(jnp.int32)
        j2 = j + oh_w.astype(jnp.int32) * do_i
        jw = jnp.sum(jnp.where(oh_w, j2, 0), dtype=jnp.int32)
        csw = jnp.sum(jnp.where(oh_w, cs, 0.0))
        sat = do & (jw.astype(dtype) >= csw)
        ccur = jnp.clip(cursor, 0, C - 1)
        oh_c = arangeC == ccur
        entry = jnp.sum(jnp.where(oh_c, compact_pos, 0), dtype=jnp.int32)
        entry_row = jnp.sum(jnp.where(oh_c[:, None], compact, 0.0), axis=0)
        # shift-left at w (static roll + masks), refill the last slot
        take_next = arangeB >= w
        is_last = arangeB == B - 1
        pos_sh = jnp.where(is_last, entry,
                           jnp.where(take_next, jnp.roll(pos, -1), pos))
        j_sh = jnp.where(is_last, 0,
                         jnp.where(take_next, jnp.roll(j2, -1), j2))
        slot_sh = jnp.where(
            is_last[:, None], entry_row[None, :],
            jnp.where(take_next[:, None], jnp.roll(slot, -1, axis=0), slot))
        pos2 = jnp.where(sat, pos_sh, pos)
        j3 = jnp.where(sat, j_sh, j2)
        slot2 = jnp.where(sat, slot_sh, slot)
        cursor2 = cursor + sat.astype(jnp.int32)
        return (pos2, j3, slot2, cursor2), (chosen, score_out, ny)

    _, (chosen, scores, n_yielded) = jax.lax.scan(
        step, (pos0, j0, slot0, cursor0),
        (jnp.arange(P, dtype=jnp.int32),
         batch.penalty_idx.astype(jnp.int32)), unroll=_wave_unroll())
    return chosen.astype(jnp.int32), scores, n_yielded


solve_wavefront = functools.partial(
    jax.jit, static_argnames=("spread_alg", "dtype_name"))(
        _solve_wavefront_impl)


def _solve_system_impl(const: NodeConst, init: NodeState,
                       batch: PlacementBatch, spread_alg: bool = False,
                       dtype_name: str = "float32"):
    """System-job dense solve: one INDEPENDENT fit+score per node, all at
    once (reference: scheduler_system.go runs one Stack.Select per node
    with that node as the only candidate). SystemStack has no limit
    window, no distinct-hosts iterator and no affinity/spread/
    anti-affinity scoring (stack.go:201 SystemStack chain), so the score
    is the normalized binpack fitness alone. Returns (fit (N,) bool,
    score (N,)) in shuffled order."""
    dtype = jnp.dtype(dtype_name)
    ask_cpu = batch.ask_cpu[0]
    ask_mem = batch.ask_mem[0]
    ask_disk = batch.ask_disk[0]
    n_dyn = batch.n_dyn_ports[0]
    has_static = batch.has_static[0]
    has_cores = const.mhz_per_core.shape[0] > 0
    if has_cores:
        ask_cores = batch.ask_cores[0]
        eff_cpu = ask_cpu + ask_cores.astype(dtype) * const.mhz_per_core
    else:
        eff_cpu = ask_cpu
    new_cpu = init.used_cpu + eff_cpu
    new_mem = init.used_mem + ask_mem
    new_disk = init.used_disk + ask_disk
    feas = (const.feasible
            & (init.dyn_avail >= n_dyn)
            & (init.static_free | ~has_static))
    if has_cores:
        feas &= init.cores_free >= ask_cores
    fit = (feas
           & (new_cpu <= const.cpu_cap)
           & (new_mem <= const.mem_cap)
           & (new_disk <= const.disk_cap))
    free_cpu = 1.0 - new_cpu / jnp.maximum(const.cpu_cap, 1e-9)
    free_mem = 1.0 - new_mem / jnp.maximum(const.mem_cap, 1e-9)
    score = _binpack_score(free_cpu, free_mem, spread_alg)
    return fit, score


solve_system = functools.partial(
    jax.jit, static_argnames=("spread_alg", "dtype_name"))(
        _solve_system_impl)


# -- compact wavefront: host-side O(N) precompute, device-side scan --------
#
# The wavefront scan only ever reads the first C = P + B fit-order rows, so
# the O(N) precompute (capacity fold + fit-order compress + row gather) runs
# on the HOST in numpy and only the compact (C, 8) table crosses the
# host->device boundary: ~65KB/lane instead of ~0.5MB of N-sized tables.
# It cuts per-dispatch link bytes and HBM traffic E-fold in fused batches.
# The float predicates here MUST mirror _solve_wavefront_impl / the dense
# kernel op-for-op (IEEE ops agree between numpy and XLA) so placements
# stay bit-identical.

def wavefront_buffer_size(limit: int) -> Optional[int]:
    """Static slot-buffer size for a lane's scan window: small for log2
    windows, wide for the limit>=100 spread/affinity windows; None when
    the window outgrows every variant (dense kernel territory)."""
    if limit + MAX_SKIP <= WAVE_B:
        return WAVE_B
    if limit + MAX_SKIP <= WAVE_B_WIDE:
        return WAVE_B_WIDE
    return None


# shared with service._wave_devices_ok's eligibility bound: a lane passes
# the wave gate ONLY if the capacity replay provably terminates within
# this many steps, so wavefront_compact_host can assert the replay
# succeeded rather than silently skipping the device clamp
WAVE_DEVICE_CAP_STEPS = 1024


def _wave_device_capacity(const, init,
                          cap_steps: int = WAVE_DEVICE_CAP_STEPS
                          ) -> Optional[np.ndarray]:
    """Per-node placement capacity in the DEVICE dimension for a uniform
    lane: numpy replay of the dense kernel's per-step commit (feasible if
    every request has a group with free >= count; the first-max-affinity
    eligible group is drained, _commit_carry_tables). Capacity is the
    number of placements until device-infeasible. Returns None when the
    simulation can't bound (a request with count <= 0 would never drain).

    Eligibility for the wave path additionally requires
    dev_sum_weight == 0 (no device affinities): with zero weight the
    dense kernel's device score component vanishes, so capacity is the
    ONLY device effect and the wave scoring stays bit-identical.
    """
    R = int(np.asarray(const.dev_aff).shape[0])
    if R == 0:
        return None
    dev_cnt = np.asarray(const.dev_count, dtype=np.int64)
    if (dev_cnt <= 0).any():
        return None
    free = np.asarray(init.dev_free, dtype=np.int64).copy()  # (R, Gd, N)
    aff = np.asarray(const.dev_aff, dtype=np.float64)
    N = free.shape[2]
    c_dev = np.zeros(N, dtype=np.int64)
    alive = np.ones(N, dtype=bool)
    rr = np.arange(R)
    nn = np.arange(N)
    for _ in range(cap_steps):
        ok_g = free >= dev_cnt[:, None, None]            # (R, Gd, N)
        feas = ok_g.any(axis=1).all(axis=0) & alive      # (N,)
        if not feas.any():
            break
        # first-max affinity among eligible groups, exactly the dense
        # argmax (ties -> lowest group index)
        aff_m = np.where(ok_g, aff, -np.inf)
        g_star = aff_m.argmax(axis=1)                    # (R, N)
        dec = np.zeros_like(free)
        dec[rr[:, None], g_star, nn[None, :]] = dev_cnt[:, None]
        free -= np.where(feas[None, None, :], dec, 0)
        c_dev += feas
        alive = feas
    else:
        return None             # capacity unbounded within cap_steps
    return c_dev


def wavefront_compact_host(const, init, batch, dtype_name: str,
                           p_pad: Optional[int] = None,
                           B: int = WAVE_B):
    """Numpy precompute for ONE lane: returns (compact (C, 8+S),
    scal_f (3,), scal_i (2,), pen (P,), spread tables). Columns: c,
    used_cpu, used_mem, cpu_cap, mem_cap, placed, affinity,
    pos(sentinel -1), then one spread value-index column per spread.
    ``p_pad`` grows the output axis (C = p_pad + B) so many lane sizes
    share one compiled variant; the padded steps are inert (beyond
    n_active) and callers slice outputs."""
    dt = np.dtype(dtype_name)
    P = int(np.asarray(batch.ask_cpu).shape[0])
    P_out = max(P, p_pad or 0)
    N = int(np.asarray(const.cpu_cap).shape[0])
    ask_cpu = np.asarray(batch.ask_cpu, dtype=dt)[0]
    ask_mem = np.asarray(batch.ask_mem, dtype=dt)[0]
    ask_disk = np.asarray(batch.ask_disk, dtype=dt)[0]
    n_dyn = int(np.asarray(batch.n_dyn_ports)[0])
    has_static = bool(np.asarray(batch.has_static)[0])
    count = np.asarray(batch.count, dtype=dt)[0]
    L = int(np.asarray(batch.limit)[0])
    n_active = int(np.asarray(batch.active).sum())

    BIG = np.int64(2 ** 30)
    cpu_cap = np.asarray(const.cpu_cap, dtype=dt)
    mem_cap = np.asarray(const.mem_cap, dtype=dt)
    disk_cap = np.asarray(const.disk_cap, dtype=dt)
    used_cpu = np.asarray(init.used_cpu, dtype=dt)
    used_mem = np.asarray(init.used_mem, dtype=dt)
    used_disk = np.asarray(init.used_disk, dtype=dt)

    def cap_dim(used0, cap, ask):
        with np.errstate(divide="ignore", invalid="ignore",
                         over="ignore"):
            q = np.floor((cap - used0) / np.maximum(ask, dt.type(1e-9)))
        q = np.where(np.isfinite(q), q, 0).astype(np.int64)

        def fits(m):
            return used0 + m.astype(dt) * ask <= cap

        q = np.where(fits(q), q, q - 1)
        q = np.where(fits(q), q, q - 1)
        q = np.maximum(q, 0)
        q = np.where(fits(q + 1), q + 1, q)
        q = np.where(fits(q + 1), q + 1, q)
        q = np.where(fits(q), q, 0)
        return np.where(ask > 0, q, BIG)

    c = np.minimum(cap_dim(used_cpu, cpu_cap, ask_cpu),
                   cap_dim(used_mem, mem_cap, ask_mem))
    c = np.minimum(c, cap_dim(used_disk, disk_cap, ask_disk))
    if n_dyn > 0:
        c = np.minimum(c, np.asarray(init.dyn_avail, dtype=np.int64)
                       // n_dyn)
    if has_static:
        c = np.minimum(c, np.where(np.asarray(init.static_free), 1, 0))
    if bool(np.asarray(const.distinct_hosts)):
        distinct0 = (np.asarray(init.placed_job)
                     if bool(np.asarray(const.distinct_job_level))
                     else np.asarray(init.placed))
        c = np.minimum(c, np.where(distinct0 > 0, 0, 1))
    if np.asarray(const.dev_aff).shape[0]:
        c_dev = _wave_device_capacity(const, init)
        # wavefront_ok admits device lanes only when the replay bound
        # holds, so a None here is an eligibility bug, not a fallback
        assert c_dev is not None, "unbounded device capacity replay"
        # uniform device asks fold into the closed-form capacity; the
        # score is unaffected (wavefront_ok gates on zero device
        # affinity weight, where the dense device score component is 0)
        c = np.minimum(c, c_dev)
    c = np.where(np.asarray(const.feasible), c, 0)
    c = np.clip(c, 0, P)

    aff = (np.asarray(const.affinity, dtype=dt)
           if bool(np.asarray(const.has_affinity))
           else np.zeros(N, dtype=dt))

    S = int(np.asarray(const.spread_vidx).shape[0])
    fit_pos = np.nonzero(c > 0)[0][:P_out + B]
    C = P_out + B
    compact = np.zeros((C, 8 + S), dtype=dt)
    compact[:, 7] = -1.0
    if S:
        compact[:, 8:] = -1.0           # missing spread attr sentinel
    k = fit_pos.shape[0]
    compact[:k, 0] = c[fit_pos]
    compact[:k, 1] = used_cpu[fit_pos]
    compact[:k, 2] = used_mem[fit_pos]
    compact[:k, 3] = cpu_cap[fit_pos]
    compact[:k, 4] = mem_cap[fit_pos]
    compact[:k, 5] = np.asarray(init.placed)[fit_pos].astype(dt)
    compact[:k, 6] = aff[fit_pos]
    compact[:k, 7] = fit_pos.astype(dt)
    if S:
        compact[:k, 8:] = np.asarray(
            const.spread_vidx)[:, fit_pos].T.astype(dt)
    scal_f = np.array([ask_cpu, ask_mem, count], dtype=dt)
    scal_i = np.array([L, n_active], dtype=np.int32)
    pen = np.full(P_out, -1, dtype=np.int32)
    pen[:P] = np.asarray(batch.penalty_idx, dtype=np.int32)
    sp = _WaveSpread(
        counts=np.asarray(init.spread_counts, dtype=np.int32),
        desired=np.asarray(const.spread_desired, dtype=dt),
        has_targets=np.asarray(const.spread_has_targets, dtype=bool),
        weights=np.asarray(const.spread_weights, dtype=dt),
        sum_weights=np.asarray(const.spread_sum_weights, dtype=dt))
    return compact, scal_f, scal_i, pen, sp


def _solve_wave_compact_impl(compact, scal_f, scal_i, pen, sp=None,
                             spread_alg: bool = False,
                             dtype_name: str = "float32",
                             B: int = WAVE_B):
    """Device-side scan over a host-precomputed compact table; identical
    outputs to the dense kernel on eligible lanes (P = C - B). ``sp``
    carries spread tables when the lane has spreads (the wide-window
    variant; spreads couple placements only through per-value counts,
    which ride the carry)."""
    dtype = jnp.dtype(dtype_name)
    C = compact.shape[0]
    P = C - B
    S = sp.counts.shape[0] if sp is not None else 0
    ask_cpu = scal_f[0]
    ask_mem = scal_f[1]
    count = scal_f[2]
    L = scal_i[0]
    n_active = scal_i[1]

    slot0 = compact[:B]
    j0 = jnp.zeros(B, dtype=jnp.int32)
    cursor0 = jnp.int32(B)
    arangeB = jnp.arange(B, dtype=jnp.int32)
    arangeC = jnp.arange(C, dtype=jnp.int32)
    neg_inf = jnp.array(-jnp.inf, dtype=dtype)
    big = jnp.iinfo(jnp.int32).max
    if S:
        V = sp.counts.shape[1]
        arangeV = jnp.arange(V, dtype=jnp.int32)
        weight_fracs = sp.weights / jnp.maximum(sp.sum_weights, 1e-9)

    def _spread_boosts(slot, counts):
        """(S, B) per-slot spread boost, mirroring the whole-axis
        kernel's _spread_value_rows op for op; slot value indexes live
        in columns 8.. as exact int floats. The tables are laid over
        the slots by compare-and-select, not gathered: a batched
        gather is the TPU's slow path, 7 ns an element on a v5e
        (measured where it cost most: SPREAD_SELECT_V, _spread_score)."""
        def one_spread(vidx_f, desired, has_targets, weight_frac, cnts):
            missing = vidx_f < 0
            safe = jnp.maximum(vidx_f, 0.0).astype(jnp.int32)
            oh_v = arangeV[None, :] == safe[:, None]          # (B, V)
            current_i = jnp.sum(jnp.where(oh_v, cnts[None, :], 0),
                                axis=1)
            used = current_i + 1
            des = jnp.sum(jnp.where(oh_v, desired[None, :], 0.0), axis=1)
            no_target = des < 0.0
            boost_t = jnp.where(
                no_target, -1.0,
                jnp.where(des == 0.0, -1.0,
                          (des - used.astype(dtype))
                          / jnp.maximum(des, 1e-9) * weight_frac))
            present = cnts > 0
            any_present = jnp.any(present)
            big_i = jnp.iinfo(jnp.int32).max
            min_c = jnp.min(jnp.where(present, cnts, big_i))
            max_c = jnp.max(jnp.where(present, cnts, 0))
            min_f = min_c.astype(dtype)
            max_f = max_c.astype(dtype)
            cur_f = current_i.astype(dtype)
            even = jnp.where(
                current_i != min_c,
                jnp.where(min_c == 0, -1.0,
                          (min_f - cur_f) / jnp.maximum(min_f, 1e-9)),
                jnp.where(min_c == max_c, -1.0,
                          (max_f - min_f) / jnp.maximum(min_f, 1e-9)))
            boost_e = jnp.where(any_present, even, 0.0)
            per_node = jnp.where(has_targets, boost_t, boost_e)
            return jnp.where(missing, -1.0, per_node).astype(dtype)

        return jax.vmap(one_spread)(
            jnp.moveaxis(slot[:, 8:], 1, 0), sp.desired, sp.has_targets,
            weight_fracs, counts)

    def step(carry, xs):
        i, pen_i = xs
        if S:
            j, slot, cursor, counts = carry
        else:
            j, slot, cursor = carry
        cs = slot[:, 0]
        fit = j.astype(dtype) < cs            # sentinel rows: c = 0
        jp1 = (j + 1).astype(dtype)
        new_cpu = slot[:, 1] + jp1 * ask_cpu
        new_mem = slot[:, 2] + jp1 * ask_mem
        free_cpu = 1.0 - new_cpu / jnp.maximum(slot[:, 3], 1e-9)
        free_mem = 1.0 - new_mem / jnp.maximum(slot[:, 4], 1e-9)
        binpack = _binpack_score(free_cpu, free_mem, spread_alg)
        coll = slot[:, 5] + j.astype(dtype)
        anti = jnp.where(
            coll > 0, -(coll + 1.0) / jnp.maximum(count, 1.0), 0.0)
        # per-placement reschedule penalty via the pos column (exact int
        # floats), matching the dense kernel's is_penalty term
        is_pen = (pen_i >= 0) & (slot[:, 7] == pen_i.astype(dtype))
        resched = jnp.where(is_pen, -1.0, 0.0)
        affs = slot[:, 6]
        if S:
            spread_total = jnp.sum(_spread_boosts(slot, counts), axis=0)
        else:
            spread_total = jnp.zeros(B, dtype=dtype)
        spread_present = spread_total != 0.0
        nscores = (1.0 + (coll > 0).astype(dtype)
                   + is_pen.astype(dtype) + (affs != 0.0).astype(dtype)
                   + spread_present.astype(dtype))
        final = (binpack
                 + (((anti + resched) + affs) + spread_total)) / nscores

        low = fit & (final <= SKIP_THRESHOLD)
        skip_rank = jnp.cumsum(low.astype(jnp.int32))
        skipped = low & (skip_rank <= MAX_SKIP)
        counted = fit & ~skipped
        cpos = jnp.cumsum(counted.astype(jnp.int32))
        total_counted = cpos[-1]
        window = counted & (cpos <= L)
        deficit = jnp.maximum(0, L - jnp.minimum(total_counted, L))
        srank = jnp.cumsum(skipped.astype(jnp.int32))
        fallback = skipped & (srank <= deficit)
        yielded = window | fallback
        order = jnp.where(window, cpos, L + srank)
        eff = jnp.where(yielded, final, neg_inf)
        best = jnp.max(eff)
        is_best = yielded & (eff == best)
        border = jnp.min(jnp.where(is_best, order, big))
        w = jnp.argmax(is_best & (order == border))
        any_yield = jnp.any(yielded)
        do = (i < n_active) & any_yield
        oh_w = arangeB == w
        chosen = jnp.where(
            do,
            jnp.sum(jnp.where(oh_w, slot[:, 7], 0.0)).astype(jnp.int32),
            -1)
        score_out = jnp.where(any_yield, best, neg_inf)
        ny = jnp.sum(yielded.astype(jnp.int32))

        do_i = do.astype(jnp.int32)
        j2 = j + oh_w.astype(jnp.int32) * do_i
        jw = jnp.sum(jnp.where(oh_w, j2, 0), dtype=jnp.int32)
        csw = jnp.sum(jnp.where(oh_w, cs, 0.0))
        sat = do & (jw.astype(dtype) >= csw)
        j3, slot2, cursor2 = _wave_refill_shift(
            compact, cursor, w, j2, slot, sat, arangeB, arangeC)
        if S:
            # winner's value index per spread -> bump its count
            vw = jnp.sum(jnp.where(oh_w[:, None], slot[:, 8:], 0.0),
                         axis=0)                              # (S,)
            safe_vw = jnp.maximum(vw, 0.0).astype(jnp.int32)
            upd = ((arangeV[None, :] == safe_vw[:, None])
                   & (vw >= 0)[:, None] & do)
            counts2 = counts + upd.astype(jnp.int32)
            return ((j3, slot2, cursor2, counts2),
                    (chosen, score_out, ny))
        return (j3, slot2, cursor2), (chosen, score_out, ny)

    carry0 = ((j0, slot0, cursor0, sp.counts.astype(jnp.int32)) if S
              else (j0, slot0, cursor0))
    _, (chosen, scores, n_yielded) = jax.lax.scan(
        step, carry0,
        (jnp.arange(P, dtype=jnp.int32), pen.astype(jnp.int32)),
        unroll=_wave_unroll())
    return chosen, scores, n_yielded


# ---------------------------------------------------------------------------
# Run-block wavefront: the compact kernel's semantics in ~P/7 chain
# steps instead of P.
#
# On-chip profiling (scripts/wave_step_bisect.py) showed the per-step
# cost of the compact scan is dependency-chain LATENCY -- a handful of
# sequentially dependent vector ops -- not arithmetic width; the chip
# pays it P times because the scan commits one placement per step. The
# shortcut is the FROZEN-OPPONENT structure of the greedy select
# (rank.go:205 BinPackIterator + select.go MaxScoreIterator): scores
# couple placements only through the winner's own per-node count j, so
# while one slot keeps winning, every other slot's head score is
# frozen. One chain step can therefore commit a winner's whole RUN:
# pick the argmax head (first-seen-in-order tie rule), then compute in
# closed form how many consecutive picks q it takes before
#   - its stream value loses to the frozen runner-up head (strictly
#     below, or tied with a runner-up of earlier window order),
#   - it saturates its closed-form capacity c (committed, then the
#     classic shift/refill runs and the block ends -- refills change
#     window composition),
#   - its value crosses the skip threshold in either direction (the
#     low/skip sets, select.go maxSkip, are recomputed at the next
#     block start), or
#   - the eval's n_active placements are exhausted,
# and emit all q picks (scores are the winner's precomputed stream
# values) in one dynamic-update-slice. BestFit streams mostly RISE with
# usage (fuller nodes score higher), so winners run until saturation
# and runs are long: the headline lane shape (10K nodes, 2000
# placements) has 272 winner runs averaging 7.4 picks
# (scripts/wave_event_stats.py). No assumption on stream shape is
# needed -- a run ends exactly when the per-step argmax would change.
#
# Equivalence argument (induction on committed picks): at a block start
# the head state (fit/low/skip/window/fallback/order/deficit) is
# recomputed exactly as the per-placement kernel's step does, so the
# argmax-with-tie-rule winner is the classic step's winner. While the
# winner runs, opponents' heads and every selection set are unchanged
# (fit changes only at the winner's saturation, low/skip sets only at
# threshold crossings -- both end the block), so the q-th pick of the
# run faces the same frozen comparison the classic kernel would
# compute, and the run-length conditions stop precisely at the first
# pick where the classic winner would differ. Outputs are
# bit-identical: emitted scores are the same elementwise expressions
# (broadcast over (B, K) instead of (B,)), and n_yielded is frozen
# between events by the same argument.
#
# Eligibility (enforced by solve_lane_wave): no spread tables (S == 0;
# spread boosts couple scores across slots through shared value
# counts) and no active reschedule penalties (penalties couple the
# score to the absolute placement index).

WAVE_K = 32            # run-block width: max picks committed per step
WAVE_INNER = 64        # run decisions per outer buffer-commit round


def _wave_block_shape() -> tuple:
    """(K, INNER) defaults by backend: measured on CPU, (16, 32) runs
    ~20% faster than the TPU-tuned (32, 64) (smaller matrices stay
    cache-resident; the CPU pays per-element, not per-chain-step). TPU
    keeps the tuned shape -- chain-step count dominates there."""
    import jax as _jax
    if _jax.default_backend() == "tpu":
        return WAVE_K, WAVE_INNER
    return 16, 32


def _solve_wave_block_impl(compact, scal_f, scal_i, pen,
                           spread_alg: bool = False,
                           dtype_name: str = "float32",
                           B: int = WAVE_B, K: int = WAVE_K,
                           INNER: int = WAVE_INNER):
    """Run-block wavefront solve over a host-precomputed compact table;
    bit-identical outputs to _solve_wave_compact_impl on eligible lanes
    (see block comment above). ``pen`` is accepted for call-signature
    parity and must be penalty-free (callers gate)."""
    del pen                     # gated: no active reschedule penalties
    dtype = jnp.dtype(dtype_name)
    C = compact.shape[0]
    P = C - B
    ask_cpu = scal_f[0]
    ask_mem = scal_f[1]
    count = scal_f[2]
    L = scal_i[0]
    n_active = scal_i[1]
    arangeB = jnp.arange(B, dtype=jnp.int32)
    arangeK = jnp.arange(K, dtype=jnp.int32)
    arangeC = jnp.arange(C, dtype=jnp.int32)
    arangePK = jnp.arange(P + K, dtype=jnp.int32)
    neg_inf = jnp.array(-jnp.inf, dtype=dtype)
    big = jnp.iinfo(jnp.int32).max

    def head_state(j, slot):
        """The classic step's per-slot head computation at the current
        (j, slot) -- (B,)-wide only; the winner's forward stream is
        rebuilt from scalars in block_step. All expressions mirror
        _solve_wave_compact_impl op for op so scores are bit-identical.
        The three selection cumsums collapse to one stacked cumsum via
        cumsum(skipped) == min(cumsum(low), MAX_SKIP) (the skip budget
        takes exactly the first MAX_SKIP lows) and cumsum(counted) ==
        cumsum(fit) - cumsum(skipped) (skipped is a subset of fit)."""
        cs = slot[:, 0]
        fit0 = j.astype(dtype) < cs
        jp1 = (j + 1).astype(dtype)
        new_cpu = slot[:, 1] + jp1 * ask_cpu
        new_mem = slot[:, 2] + jp1 * ask_mem
        free_cpu = 1.0 - new_cpu / jnp.maximum(slot[:, 3], 1e-9)
        free_mem = 1.0 - new_mem / jnp.maximum(slot[:, 4], 1e-9)
        binpack = _binpack_score(free_cpu, free_mem, spread_alg)
        coll = slot[:, 5] + j.astype(dtype)
        anti = jnp.where(
            coll > 0, -(coll + 1.0) / jnp.maximum(count, 1.0), 0.0)
        affs = slot[:, 6]
        nsc = (1.0 + (coll > 0).astype(dtype)
               + (affs != 0.0).astype(dtype))
        f0 = (binpack + (anti + affs)) / nsc
        low = fit0 & (f0 <= SKIP_THRESHOLD)
        cs2 = jnp.cumsum(
            jnp.stack([low, fit0]).astype(jnp.int32), axis=1)
        skip_rank = cs2[0]
        srank = jnp.minimum(skip_rank, MAX_SKIP)
        skipped = low & (skip_rank <= MAX_SKIP)
        cpos = cs2[1] - srank
        counted = fit0 & ~skipped
        window = counted & (cpos <= L)
        deficit = jnp.maximum(0, L - jnp.minimum(cpos[-1], L))
        fallback = skipped & (srank <= deficit)
        yielded = window | fallback
        order = jnp.where(window, cpos, L + srank)
        ny = jnp.sum(yielded.astype(jnp.int32), dtype=jnp.int32)
        any_yield = jnp.any(yielded)
        return f0, low, yielded, order, ny, any_yield

    def block_step(carry, _):
        """One greedy run decision over the SMALL solver state. Emitted
        records (winner pos, run length, start offset, ny, the winner's
        K score values) are lax.scan ys -- kept OUT of the carry so the
        vmapped loop's per-iteration masking touches only ~B*9 floats,
        not the (P+K,) output buffers."""
        j, slot, cursor, p, done = carry
        f0, low, yielded, order, ny, any_yield = head_state(j, slot)

        # classic winner: max head, ties to the earliest window order.
        # The candidate set must be masked to YIELDED slots (the compact
        # kernel's `is_best = yielded & (eff == best)` rule): if every
        # yielded head is exactly -inf, best == neg_inf also matches
        # non-yielded slots, and one with a smaller order value would
        # steal the win (ADVICE low #1).
        effH = jnp.where(yielded, f0, neg_inf)
        best = jnp.max(effH)
        w = jnp.argmin(jnp.where(yielded & (effH == best), order, big))
        oh_w = arangeB == w

        # winner scalars in ONE masked reduce (all integer-valued
        # columns are < 2^24: exact in the score dtype)
        svals = jnp.sum(jnp.where(
            oh_w[:, None],
            jnp.concatenate(
                [slot[:, :8],
                 jnp.stack([j.astype(dtype), order.astype(dtype),
                            low.astype(dtype)], axis=1)], axis=1),
            0.0), axis=0)
        cs_w, ucpu_w, umem_w = svals[0], svals[1], svals[2]
        ccap_w, mcap_w, placed_w = svals[3], svals[4], svals[5]
        aff_w, pos_w = svals[6], svals[7]
        j_wf, order_wf, low_wf = svals[8], svals[9], svals[10]
        low_w = low_wf != 0.0
        eff_o = jnp.where(oh_w, neg_inf, effH)
        rub = jnp.max(eff_o)
        rub_ord = jnp.min(jnp.where(eff_o == rub, order, big))

        # winner's forward stream from scalars: vals[q] = score of its
        # (j_w + q + 1)-th placement, the same elementwise expressions
        # as head_state broadcast over q (exact-int float arithmetic)
        jq = j_wf + arangeK.astype(dtype)
        validw = jq < cs_w
        jp1q = jq + 1.0
        fcq = 1.0 - (ucpu_w + jp1q * ask_cpu) / jnp.maximum(ccap_w, 1e-9)
        fmq = 1.0 - (umem_w + jp1q * ask_mem) / jnp.maximum(mcap_w, 1e-9)
        bpq = _binpack_score(fcq, fmq, spread_alg)
        collq = placed_w + jq
        antiq = jnp.where(
            collq > 0, -(collq + 1.0) / jnp.maximum(count, 1.0), 0.0)
        nscq = (1.0 + (collq > 0).astype(dtype)
                + jnp.where(aff_w != 0.0, 1.0, 0.0))
        vals = (bpq + (antiq + aff_w)) / nscq

        # run length: picks until the winner loses, transitions through
        # the skip threshold, runs out of capacity, or exhausts the eval
        q = arangeK
        win_q = ((vals > rub)
                 | ((vals == rub) & (order_wf < rub_ord.astype(dtype)))
                 | (q == 0))
        cross = jnp.where(low_w, vals > SKIP_THRESHOLD,
                          vals <= SKIP_THRESHOLD) & (q > 0)
        stop_q = (~validw) | (~win_q) | cross | (q >= n_active - p)
        tlim = jnp.min(jnp.where(stop_q, q, K))
        # saturation: the q_sat-th pick fills the slot (j_w + q_sat + 1
        # == c_w); commit it, then shift/refill. c/j < 2^24: exact
        # floats.
        q_sat = (cs_w - 1.0 - j_wf).astype(jnp.int32)
        has_sat = (q_sat < K) & (q_sat < tlim)
        t = jnp.where(has_sat, q_sat + 1, tlim)
        # t >= 1 whenever active: q=0 is valid (the winner is yielded,
        # hence fit), wins by construction, and cannot be a threshold
        # crossing
        active = any_yield & ~done & (p < n_active)
        t = jnp.where(active, t, 0)
        has_sat = has_sat & active

        j2 = j + oh_w.astype(jnp.int32) * t

        # classic shift/refill, gated on the saturation event
        j3, slot2, cursor2 = _wave_refill_shift(
            compact, cursor, w, j2, slot, has_sat, arangeB, arangeC)
        done2 = done | ~any_yield
        # invalid stream positions store 0.0 (not -inf): the outer
        # expansion reads them through a one-hot matmul, and
        # 0 * -inf would poison the row sums with NaN; positions
        # beyond the run length are never selected anyway
        rec = (pos_w, t, p, ny, jnp.where(validw, vals, 0.0))
        return (j3, slot2, cursor2, p + t, done2), rec

    def outer_body(carry):
        """INNER run decisions via lax.scan (small carry), then ONE
        vectorized expansion of the records into the output buffers --
        the buffers ride only this outer loop, whose trip count is
        ~P / (INNER * mean-run) instead of the block count."""
        j, slot, cursor, p, done, ch_buf, sc_buf, ny_buf = carry
        p_begin = p
        (j2, slot2, cursor2, p2, done2), recs = jax.lax.scan(
            block_step, (j, slot, cursor, p, done), None, length=INNER)
        pos_r, t_r, p0_r, ny_r, vals_r = recs

        # expansion: position s belongs to the LAST block whose start
        # offset is <= s (starts are non-decreasing; finished-lane
        # records have t=0 and start=p2 > s for any committed s). All
        # record lookups go through one-hot MATMULS, not gathers --
        # batched gathers hit TPU slow paths, one (P+K, INNER) matmul
        # rides the MXU. Record scalars are exact small ints in the
        # score dtype.
        s = arangePK
        leq = (p0_r[None, :] <= s[:, None])            # (P+K, INNER)
        nxt = jnp.concatenate(
            [leq[:, 1:], jnp.zeros((P + K, 1), dtype=bool)], axis=1)
        blk_oh = (leq & ~nxt).astype(dtype)            # one-hot of blk
        recmat = jnp.stack(
            [pos_r, t_r.astype(dtype), p0_r.astype(dtype),
             ny_r.astype(dtype)], axis=1)              # (INNER, 4)
        # HIGHEST precision: TPU matmuls default to bf16 passes,
        # which would round the exact-int node positions; with one-hot
        # rows (single nonzero term) full-f32 passes are exact
        rs = jnp.matmul(blk_oh, recmat,
                        precision=jax.lax.Precision.HIGHEST)

        q_s = s.astype(dtype) - rs[:, 2]
        covered = ((s >= p_begin) & (s < p2)
                   & (q_s >= 0) & (q_s < rs[:, 1]))
        rowvals = jnp.matmul(blk_oh, vals_r,
                             precision=jax.lax.Precision.HIGHEST)
        q_oh = (arangeK[None, :].astype(dtype)
                == jnp.clip(q_s, 0, K - 1)[:, None])
        sc_s = jnp.sum(jnp.where(q_oh, rowvals, 0.0), axis=1)
        ch_buf = jnp.where(covered, rs[:, 0].astype(jnp.int32), ch_buf)
        sc_buf = jnp.where(covered, sc_s, sc_buf)
        ny_buf = jnp.where(covered, rs[:, 3].astype(jnp.int32), ny_buf)
        return (j2, slot2, cursor2, p2, done2, ch_buf, sc_buf, ny_buf)

    slot0 = compact[:B]
    j0 = jnp.zeros(B, dtype=jnp.int32)
    carry0 = (j0, slot0, jnp.int32(B), jnp.int32(0),
              jnp.array(False),
              jnp.full(P + K, -1, dtype=jnp.int32),
              jnp.full(P + K, -jnp.inf, dtype=dtype),
              jnp.zeros(P + K, dtype=jnp.int32))

    def cond(carry):
        _, _, _, p, done, _, _, _ = carry
        return (p < n_active) & ~done

    (j_f, slot_f, _, p_end, _, ch_buf, sc_buf,
     ny_buf) = jax.lax.while_loop(cond, outer_body, carry0)

    # beyond-active / stuck tail: the classic scan keeps emitting
    # (chosen=-1, best-head score, n_yielded) from its frozen state for
    # every remaining step; broadcast the same from the final state
    f0_f, _, yielded_f, _, ny_f, any_yield_f = head_state(j_f, slot_f)
    effH_f = jnp.where(yielded_f, f0_f, neg_inf)
    best_f = jnp.max(effH_f)
    fill_mask = arangePK >= p_end
    sc_fill = jnp.where(any_yield_f, best_f, neg_inf)
    ch_buf = jnp.where(fill_mask, -1, ch_buf)
    sc_buf = jnp.where(fill_mask, sc_fill, sc_buf)
    ny_buf = jnp.where(fill_mask, ny_f, ny_buf)
    return ch_buf[:P], sc_buf[:P], ny_buf[:P]


# ---------------------------------------------------------------------------
# Wavefront preemption: the windowed kernel family extended to the
# eviction-enabled select (VERDICT r3 next-step 3).
#
# The dense preempt path re-runs the greedy eviction search over ALL N
# nodes' (N, A) candidate tables per placement step -- the tier-5 lanes
# where the dense scan was slowest. But the selection window only ever
# examines the first limit+MAX_SKIP OPTION nodes in shuffled order, where
# an option is plain-fit OR eviction-met (rank.go:545-565); so the scan
# can carry a B-slot buffer of front option nodes -- each slot holding its
# (A,) candidate columns and accumulated eviction mask -- and run the
# search over (B, A) instead of (N, A): ~N/B (=300x at 10K nodes) less
# per-step work, sharing _preempt_search_core with the dense kernel.
#
# Window-membership correctness: a node OUTSIDE the window has never been
# chosen, so its state is pristine and its option-status is static ->
# precomputable on the host (the refill list). Option-status is monotone
# non-increasing (picks and evictions only consume), so a shifted-out
# slot can never become an option again; eviction-met is coverage-based
# and therefore independent of the max_parallel penalty ordering, so
# global count changes can't resurrect a node either. Slots shift out
# when the chosen node exhausts BOTH plain fit and eviction potential;
# refills enter pristine from the precomputed list.
#
# Eligibility (wavefront_preempt_ok): preempt lanes already exclude
# networks/devices/cores (service.tg_solver_eligible preempt=True), so
# the kernel models cpu/mem/disk + distinct_hosts + affinity + penalties;
# spreads stay dense.

# slot columns for the preempt wavefront (compactP, (C, _WPC_NCOLS))
_WPC_FEAS = 0
_WPC_UC, _WPC_UM, _WPC_UD = 1, 2, 3
_WPC_CC, _WPC_CM, _WPC_CD = 4, 5, 6
_WPC_PLACED, _WPC_PLACED_JOB = 7, 8
_WPC_AFF, _WPC_POS = 9, 10
_WPC_CDEV = 11          # device-dimension placement capacity (2^24 =
_WPC_NCOLS = 12         # unbounded; exact in float32)
_WPC_DEV_UNBOUNDED = float(2 ** 24)


def _numpy_preempt_pristine(ccpu, cmem, cdisk, cprio, cmaxp, cgrp, cvalid,
                            counts, cpu_cap, mem_cap, disk_cap, job_prio,
                            ask_cpu, ask_mem, ask_disk):
    """Exact host-side transcription of _preempt_search_core at pristine
    state (no prior evictions), vectorized over all N nodes in numpy.
    Returns (met (N,), freed (3, N)) using the greedy + filterSuperset
    eviction set -- the same values the device search would produce.
    All arithmetic runs in the candidate arrays' dtype: a float64 host
    pass against a float32 device search could flip near-tie argmins and
    admit nodes the in-step search can't yield (window-starving zombies)
    or drop real options."""
    dt = ccpu.dtype
    ask_cpu = dt.type(ask_cpu)
    ask_mem = dt.type(ask_mem)
    ask_disk = dt.type(ask_disk)
    N, A = ccpu.shape
    elig = cvalid & (job_prio - cprio >= 10)
    avail_c0 = (cpu_cap - np.sum(np.where(cvalid, ccpu, 0.0), axis=1,
                                 dtype=dt)).astype(dt)
    avail_m0 = (mem_cap - np.sum(np.where(cvalid, cmem, 0.0), axis=1,
                                 dtype=dt)).astype(dt)
    avail_d0 = (disk_cap - np.sum(np.where(cvalid, cdisk, 0.0), axis=1,
                                  dtype=dt)).astype(dt)
    n_pre = np.where(cgrp >= 0, counts[np.maximum(cgrp, 0)], 0)
    penalty = np.where((cmaxp > 0) & (n_pre >= cmaxp),
                       (n_pre + 1 - cmaxp) * dt.type(MAX_PARALLEL_PENALTY),
                       dt.type(0.0)).astype(dt)

    def dist(ne_c, ne_m, ne_d):
        eps = dt.type(1e-9)
        zero = dt.type(0.0)
        dc = np.where(ne_c > 0, (ne_c - ccpu) / np.maximum(ne_c, eps), zero)
        dm = np.where(ne_m > 0, (ne_m - cmem) / np.maximum(ne_m, eps), zero)
        dd = np.where(ne_d > 0, (ne_d - cdisk) / np.maximum(ne_d, eps),
                      zero)
        return np.sqrt(dc * dc + dm * dm + dd * dd).astype(dt)

    picked = np.zeros((N, A), dtype=bool)
    av_c, av_m, av_d = avail_c0.copy(), avail_m0.copy(), avail_d0.copy()
    ne_c = np.full(N, ask_cpu, dtype=dt)
    ne_m = np.full(N, ask_mem, dtype=dt)
    ne_d = np.full(N, ask_disk, dtype=dt)
    # must fit cprio's dtype: a wider sentinel silently WRAPS under
    # NEP-50 value-based casting (int64 max as int32 == -1, which then
    # wins every np.min and empties the pick group)
    big_i = np.iinfo(np.int32).max
    for _ in range(A):
        met = ((av_c >= ask_cpu) & (av_m >= ask_mem) & (av_d >= ask_disk)
               & picked.any(axis=1))
        cand = elig & ~picked
        if not np.any(~met & cand.any(axis=1)):
            break
        cur_prio = np.min(np.where(cand, cprio, big_i), axis=1)
        in_group = cand & (cprio == cur_prio[:, None])
        key = np.where(in_group,
                       dist(ne_c[:, None], ne_m[:, None], ne_d[:, None])
                       + penalty, np.inf)
        pick = np.argmin(key, axis=1)
        do = ~met & in_group.any(axis=1)
        onehot = (np.arange(A)[None, :] == pick[:, None]) & do[:, None]
        pc = np.sum(np.where(onehot, ccpu, 0.0), axis=1)
        pm = np.sum(np.where(onehot, cmem, 0.0), axis=1)
        pd = np.sum(np.where(onehot, cdisk, 0.0), axis=1)
        picked |= onehot
        av_c += pc; av_m += pm; av_d += pd            # noqa: E702
        ne_c -= pc; ne_m -= pm; ne_d -= pd            # noqa: E702
    met = ((av_c >= ask_cpu) & (av_m >= ask_mem) & (av_d >= ask_disk)
           & picked.any(axis=1))

    # filterSuperset: re-add picked in descending distance-to-ask order
    d0 = dist(np.full(N, ask_cpu)[:, None], np.full(N, ask_mem)[:, None],
              np.full(N, ask_disk)[:, None])
    sort_key = np.where(picked, -d0, np.inf)
    order = np.argsort(sort_key, axis=1, kind="stable")
    oc = np.take_along_axis(np.where(picked, ccpu, 0.0), order, axis=1)
    om = np.take_along_axis(np.where(picked, cmem, 0.0), order, axis=1)
    od = np.take_along_axis(np.where(picked, cdisk, 0.0), order, axis=1)
    cum_c = avail_c0[:, None] + np.cumsum(oc, axis=1)
    cum_m = avail_m0[:, None] + np.cumsum(om, axis=1)
    cum_d = avail_d0[:, None] + np.cumsum(od, axis=1)
    met_at = ((cum_c >= ask_cpu) & (cum_m >= ask_mem)
              & (cum_d >= ask_disk))
    first_met = np.argmax(met_at, axis=1)
    keep_sorted = (np.arange(A)[None, :] <= first_met[:, None])
    keep_sorted &= np.take_along_axis(picked, order, axis=1)
    evict = np.zeros_like(picked)
    np.put_along_axis(evict, order, keep_sorted, axis=1)
    freed = np.stack([np.sum(np.where(evict, t, 0.0), axis=1)
                      for t in (ccpu, cmem, cdisk)])
    return met, freed


def wavefront_preempt_compact_host(const, init, batch, ptab, pinit,
                                   dtype_name: str,
                                   p_pad: Optional[int] = None,
                                   B: int = WAVE_B):
    """Host precompute for ONE preempt lane: the pristine option
    predicate + refill-ordered compact node columns and candidate tables.
    Returns (compactP (C, _WPC_NCOLS), cand dict of (C, A) arrays, scal_f (4,),
    scal_i (4,), pen (P,), counts0 (G,))."""
    dt = np.dtype(dtype_name)
    P = int(np.asarray(batch.ask_cpu).shape[0])
    P_out = max(P, p_pad or 0)
    N = int(np.asarray(const.cpu_cap).shape[0])
    A = int(np.asarray(ptab.cpu).shape[1])
    ask_cpu = float(np.asarray(batch.ask_cpu, dtype=dt)[0])
    ask_mem = float(np.asarray(batch.ask_mem, dtype=dt)[0])
    ask_disk = float(np.asarray(batch.ask_disk, dtype=dt)[0])
    count = float(np.asarray(batch.count, dtype=dt)[0])
    L = int(np.asarray(batch.limit)[0])
    n_active = int(np.asarray(batch.active).sum())
    job_prio = int(np.asarray(ptab.job_prio))

    cpu_cap = np.asarray(const.cpu_cap, dtype=dt)
    mem_cap = np.asarray(const.mem_cap, dtype=dt)
    disk_cap = np.asarray(const.disk_cap, dtype=dt)
    used_c = np.asarray(init.used_cpu, dtype=dt)
    used_m = np.asarray(init.used_mem, dtype=dt)
    used_d = np.asarray(init.used_disk, dtype=dt)
    feas = np.asarray(const.feasible, dtype=bool)
    placed0 = np.asarray(init.placed)
    placed_job0 = np.asarray(init.placed_job)
    distinct = bool(np.asarray(const.distinct_hosts))
    job_level = bool(np.asarray(const.distinct_job_level))
    distinct_flag = (2 if distinct and job_level
                     else (1 if distinct else 0))

    dcount0 = placed_job0 if job_level else placed0
    feas_nonres0 = feas if not distinct else (feas & (dcount0 == 0))
    # device-dimension capacity (uniform ask, zero affinity weight --
    # wavefront_ok gates): a node with no eligible group (or drained by
    # earlier placements, tracked via j in the kernel) is NOT an option,
    # not even via eviction -- eviction never frees matching devices
    # (pack() rejects lanes whose evictable candidates hold them), so a
    # failed device assign skips the node exactly like rank.go:443's
    # PreemptForDevice returning nil
    if np.asarray(const.dev_aff).shape[0]:
        c_dev = _wave_device_capacity(const, init)
        assert c_dev is not None, "unbounded device capacity replay"
        dev_ok0 = c_dev >= 1
    else:
        c_dev = None
        dev_ok0 = np.ones(N, dtype=bool)
    fit0 = (feas_nonres0 & dev_ok0
            & (used_c + ask_cpu <= cpu_cap)
            & (used_m + ask_mem <= mem_cap)
            & (used_d + ask_disk <= disk_cap))

    cvalid = np.asarray(ptab.valid, dtype=bool)               # (N, A)
    cprio = np.asarray(ptab.prio)
    ccpu = np.asarray(ptab.cpu, dtype=dt)
    cmem = np.asarray(ptab.mem, dtype=dt)
    cdisk = np.asarray(ptab.disk, dtype=dt)
    cmaxp = np.asarray(ptab.maxp)
    cgrp = np.asarray(ptab.grp)
    counts_np = np.asarray(pinit.counts, dtype=np.int64)
    # pristine eviction outcome, computed EXACTLY (numpy transcription of
    # _preempt_search_core's greedy + filterSuperset + the fit2 clamp): a
    # conservative coverage bound here admits nodes the in-step search
    # can never actually yield, and B such zombies starve the window
    met0, freed0 = _numpy_preempt_pristine(
        ccpu, cmem, cdisk, cprio, cmaxp, cgrp, cvalid, counts_np,
        cpu_cap, mem_cap, disk_cap, job_prio,
        ask_cpu, ask_mem, ask_disk)
    fit2g0 = ((used_c + ask_cpu - freed0[0] <= cpu_cap)
              & (used_m + ask_mem - freed0[1] <= mem_cap)
              & (used_d + ask_disk - freed0[2] <= disk_cap))
    option0 = fit0 | (feas_nonres0 & dev_ok0 & ~fit0 & met0 & fit2g0)

    fit_pos = np.nonzero(option0)[0][:P_out + B]
    C = P_out + B
    compact = np.zeros((C, _WPC_NCOLS), dtype=dt)
    compact[:, _WPC_POS] = -1.0
    k = fit_pos.shape[0]
    compact[:k, _WPC_FEAS] = feas[fit_pos].astype(dt)
    compact[:k, _WPC_UC] = used_c[fit_pos]
    compact[:k, _WPC_UM] = used_m[fit_pos]
    compact[:k, _WPC_UD] = used_d[fit_pos]
    compact[:k, _WPC_CC] = cpu_cap[fit_pos]
    compact[:k, _WPC_CM] = mem_cap[fit_pos]
    compact[:k, _WPC_CD] = disk_cap[fit_pos]
    compact[:k, _WPC_PLACED] = placed0[fit_pos].astype(dt)
    compact[:k, _WPC_PLACED_JOB] = placed_job0[fit_pos].astype(dt)
    aff = (np.asarray(const.affinity, dtype=dt)
           if bool(np.asarray(const.has_affinity))
           else np.zeros(N, dtype=dt))
    compact[:k, _WPC_AFF] = aff[fit_pos]
    compact[:k, _WPC_POS] = fit_pos.astype(dt)
    if c_dev is not None:
        compact[:k, _WPC_CDEV] = np.minimum(
            c_dev[fit_pos], P_out + 1).astype(dt)
    else:
        compact[:, _WPC_CDEV] = dt.type(_WPC_DEV_UNBOUNDED)

    def take(arr, fill):
        out = np.full((C, A), fill, dtype=arr.dtype)
        out[:k] = arr[fit_pos]
        return out

    cand = {
        "cpu": take(ccpu, dt.type(0)),
        "mem": take(cmem, dt.type(0)),
        "disk": take(cdisk, dt.type(0)),
        "prio": take(cprio.astype(np.int32), np.int32(0)),
        "maxp": take(np.asarray(ptab.maxp, dtype=np.int32), np.int32(0)),
        "grp": take(np.asarray(ptab.grp, dtype=np.int32), np.int32(-1)),
        "valid": take(cvalid, False),
    }
    scal_f = np.array([ask_cpu, ask_mem, ask_disk, count], dtype=dt)
    scal_i = np.array([L, n_active, job_prio, distinct_flag],
                      dtype=np.int32)
    pen = np.full(P_out, -1, dtype=np.int32)
    pen[:P] = np.asarray(batch.penalty_idx, dtype=np.int32)
    counts0 = np.asarray(pinit.counts, dtype=np.int32)
    return compact, cand, scal_f, scal_i, pen, counts0


def _solve_wave_preempt_impl(compact, cand, scal_f, scal_i, pen, counts0,
                             B: int = WAVE_B, spread_alg: bool = False,
                             dtype_name: str = "float32"):
    """Device scan for the windowed preemption select. Returns
    (chosen (P,), scores (P,), n_yielded (P,), evict_rows (P, A))."""
    dtype = jnp.dtype(dtype_name)
    C = compact.shape[0]
    A = cand["cpu"].shape[1]
    P = C - B
    G = counts0.shape[0]
    ask_cpu = scal_f[0]
    ask_mem = scal_f[1]
    ask_disk = scal_f[2]
    count = scal_f[3]
    L = scal_i[0]
    n_active = scal_i[1]
    job_prio = scal_i[2]
    distinct_flag = scal_i[3]

    slot0 = compact[:B]
    cand0 = {k: v[:B] for k, v in cand.items()}
    j0 = jnp.zeros(B, dtype=jnp.int32)
    evict0 = jnp.zeros((B, A), dtype=bool)
    cursor0 = jnp.int32(B)
    arangeB = jnp.arange(B, dtype=jnp.int32)
    arangeC = jnp.arange(C, dtype=jnp.int32)
    neg_inf = jnp.array(-jnp.inf, dtype=dtype)
    big = jnp.iinfo(jnp.int32).max

    def option_state(slot, cd, j, evicted, counts):
        """Per-slot fit/preempt status + scores against current state."""
        jf = j.astype(dtype)
        freed_prev_c = jnp.sum(jnp.where(evicted, cd["cpu"], 0.0), axis=1)
        freed_prev_m = jnp.sum(jnp.where(evicted, cd["mem"], 0.0), axis=1)
        freed_prev_d = jnp.sum(jnp.where(evicted, cd["disk"], 0.0), axis=1)
        used_now_c = slot[:, _WPC_UC] + jf * ask_cpu - freed_prev_c
        used_now_m = slot[:, _WPC_UM] + jf * ask_mem - freed_prev_m
        used_now_d = slot[:, _WPC_UD] + jf * ask_disk - freed_prev_d
        new_c = used_now_c + ask_cpu
        new_m = used_now_m + ask_mem
        new_d = used_now_d + ask_disk

        dcount = jnp.where(distinct_flag == 2,
                           slot[:, _WPC_PLACED_JOB] + jf,
                           slot[:, _WPC_PLACED] + jf)
        # device capacity countdown: each landed placement (j) consumed
        # one unit; a drained node stops being an option entirely (no
        # eviction can free matching devices -- pack() gates on that)
        dev_ok = slot[:, _WPC_CDEV] - jf >= 1.0
        feas_nonres = ((slot[:, _WPC_FEAS] > 0.5) & dev_ok
                       & ((distinct_flag == 0) | (dcount == 0.0)))
        fit = (feas_nonres
               & (new_c <= slot[:, _WPC_CC])
               & (new_m <= slot[:, _WPC_CM])
               & (new_d <= slot[:, _WPC_CD]))

        valid_now = cd["valid"] & ~evicted
        eligible = valid_now & (job_prio - cd["prio"] >= 10)
        # static-length greedy on TPU (a dynamic-trip-count loop of tiny
        # (B, A) ops inside a scan step is per-iteration sync latency);
        # early-exit while_loop on CPU (the search usually needs only a
        # few picks, and full-A straight-line code costs more than the
        # saved dispatches there)
        import jax as _jax
        met, evict, freed_c, freed_m, freed_d, net_prio = \
            _preempt_search_core(
                cd["cpu"], cd["mem"], cd["disk"], cd["prio"], cd["maxp"],
                cd["grp"], valid_now, eligible, slot[:, _WPC_CC],
                slot[:, _WPC_CM], slot[:, _WPC_CD], counts,
                ask_cpu, ask_mem, ask_disk, dtype,
                static_iters=_jax.default_backend() == "tpu")
        fit2 = ((new_c - freed_c <= slot[:, _WPC_CC])
                & (new_m - freed_m <= slot[:, _WPC_CM])
                & (new_d - freed_d <= slot[:, _WPC_CD]))
        fit_p = feas_nonres & ~fit & met & fit2

        # scoring (mirrors _score_and_select_preempt on the slot axis)
        free_cpu = 1.0 - new_c / jnp.maximum(slot[:, _WPC_CC], 1e-9)
        free_mem = 1.0 - new_m / jnp.maximum(slot[:, _WPC_CM], 1e-9)
        binpack = _binpack_score(free_cpu, free_mem, spread_alg)
        free_cpu_p = 1.0 - (new_c - freed_c) / jnp.maximum(
            slot[:, _WPC_CC], 1e-9)
        free_mem_p = 1.0 - (new_m - freed_m) / jnp.maximum(
            slot[:, _WPC_CM], 1e-9)
        binpack_p = _binpack_score(free_cpu_p, free_mem_p, spread_alg)
        pscore = 1.0 / (1.0 + jnp.exp(
            PREEMPT_SCORE_RATE * (net_prio - PREEMPT_SCORE_ORIGIN)))
        return (fit, fit_p, binpack, binpack_p, pscore, evict,
                freed_c, freed_m, freed_d)

    def step(carry, xs):
        i, pen_i = xs
        j, slot, cd, evicted, cursor, counts, pending = carry

        (fit, fit_p, binpack, binpack_p, pscore, evict,
         freed_c, freed_m, freed_d) = option_state(
            slot, cd, j, evicted, counts)

        coll = slot[:, _WPC_PLACED] + j.astype(dtype)
        anti = jnp.where(
            coll > 0, -(coll + 1.0) / jnp.maximum(count, 1.0), 0.0)
        is_pen = (pen_i >= 0) & (slot[:, _WPC_POS] == pen_i.astype(dtype))
        resched = jnp.where(is_pen, -1.0, 0.0)
        affs = slot[:, _WPC_AFF]
        nscores = (1.0 + (coll > 0).astype(dtype)
                   + is_pen.astype(dtype) + (affs != 0.0).astype(dtype))
        other = anti + resched + affs
        final_plain = (binpack + other) / nscores
        final_pre = (binpack_p + other + pscore) / (nscores + 1.0)
        fit_c = fit | fit_p
        final = jnp.where(fit_p, final_pre, final_plain)

        low = fit_c & (final <= SKIP_THRESHOLD)
        skip_rank = jnp.cumsum(low.astype(jnp.int32))
        skipped = low & (skip_rank <= MAX_SKIP)
        counted = fit_c & ~skipped
        cpos = jnp.cumsum(counted.astype(jnp.int32))
        total_counted = cpos[-1]
        window = counted & (cpos <= L)
        deficit = jnp.maximum(0, L - jnp.minimum(total_counted, L))
        srank = jnp.cumsum(skipped.astype(jnp.int32))
        fallback = skipped & (srank <= deficit)
        yielded = window | fallback
        order = jnp.where(window, cpos, L + srank)
        eff = jnp.where(yielded, final, neg_inf)
        best = jnp.max(eff)
        is_best = yielded & (eff == best)
        border = jnp.min(jnp.where(is_best, order, big))
        w = jnp.argmax(is_best & (order == border))
        any_yield = jnp.any(yielded)
        do = (i < n_active) & any_yield
        oh_w = arangeB == w
        chosen = jnp.where(
            do,
            jnp.sum(jnp.where(oh_w, slot[:, _WPC_POS], 0.0))
            .astype(jnp.int32), -1)
        score_out = jnp.where(any_yield, best, neg_inf)
        ny = jnp.sum(yielded.astype(jnp.int32))

        # commit: the winner takes one copy; a preempting winner applies
        # its eviction row and bumps the per-group counts
        was_pre = jnp.any(oh_w & fit_p) & do
        evict_w = evict & oh_w[:, None] & was_pre
        evict_row_out = jnp.any(evict_w, axis=0)                # (A,)
        do_i = do.astype(jnp.int32)
        j2 = j + oh_w.astype(jnp.int32) * do_i
        evicted2 = evicted | evict_w
        grp_hot = ((jnp.arange(G, dtype=jnp.int32)[None, None, :]
                    == jnp.maximum(cd["grp"], 0)[:, :, None])
                   & (cd["grp"] >= 0)[:, :, None]
                   & evict_w[:, :, None])
        counts2 = counts + jnp.sum(grp_hot, axis=(0, 1)).astype(jnp.int32)

        # shift-out, DEFERRED one step: this step's search already gives
        # every slot's exact option status, and a committed winner's state
        # only changes at its commit -- so the PREVIOUS winner ("pending")
        # is a zombie iff it is not an option NOW. Deferring avoids a
        # second in-step search; at most one zombie occupies the buffer
        # for one step (never counted -- fit_c is False -- so the window
        # semantics are unaffected while B >= L + MAX_SKIP + 1). Entries
        # are exact options by the host's pristine predicate, so zombies
        # only ever arise from winners.
        z = jnp.maximum(pending, 0)
        oh_z = arangeB == z
        zomb = (pending >= 0) & ~jnp.any(oh_z & fit_c)
        oh_c = arangeC == jnp.clip(cursor, 0, C - 1)
        entry_row = jnp.sum(jnp.where(oh_c[:, None], compact, 0.0),
                            axis=0)
        entry_cd = {
            kk: jnp.sum(jnp.where(oh_c[:, None], vv,
                                  jnp.zeros((), dtype=vv.dtype)),
                        axis=0).astype(vv.dtype)
            for kk, vv in cand.items()}
        take_next = arangeB >= z
        is_last = arangeB == B - 1

        def shift1(cur, entry):
            return jnp.where(
                is_last.reshape((B,) + (1,) * (cur.ndim - 1)),
                entry[None], jnp.where(
                    take_next.reshape((B,) + (1,) * (cur.ndim - 1)),
                    jnp.roll(cur, -1, axis=0), cur))

        j_sh = shift1(j2, jnp.zeros((), dtype=jnp.int32))
        slot_sh = shift1(slot, entry_row)
        cd_sh = {kk: shift1(vv, entry_cd[kk]) for kk, vv in cd.items()}
        ev_sh = shift1(evicted2, jnp.zeros(A, dtype=bool))
        j3 = jnp.where(zomb, j_sh, j2)
        slot2 = jnp.where(zomb, slot_sh, slot)
        cd2 = {kk: jnp.where(zomb, cd_sh[kk], vv)
               for kk, vv in cd.items()}
        ev3 = jnp.where(zomb, ev_sh, evicted2)
        cursor2 = cursor + zomb.astype(jnp.int32)
        # next step's pending = this step's winner, index adjusted for the
        # zombie roll (w can never equal z: zombies are never yielded)
        w_adj = jnp.where(zomb & (w > z), w - 1, w)
        pending2 = jnp.where(do, w_adj.astype(jnp.int32), -1)
        return ((j3, slot2, cd2, ev3, cursor2, counts2, pending2),
                (chosen, score_out, ny, evict_row_out))

    carry0 = (j0, slot0, cand0, evict0, cursor0,
              counts0.astype(jnp.int32), jnp.int32(-1))
    _, (chosen, scores, n_yielded, evict_rows) = jax.lax.scan(
        step, carry0,
        (jnp.arange(P, dtype=jnp.int32), pen.astype(jnp.int32)),
        unroll=1)
    return chosen, scores, n_yielded, evict_rows


@_single_flight
@functools.lru_cache(maxsize=None)
def _wave_preempt_program(cm_shape, cd_shape, c0_shape,
                          spread_alg: bool, dtype_name: str,
                          batched: bool, B: int):
    """Per-shape-bucket factory for the windowed-preemption compact
    program. The shape keys don't feed the program body -- they pin one
    jitted callable per bucket so every callable's compile cache holds
    exactly one trace in steady state (jitcheck retrace discipline,
    same keys the old module dict used)."""
    inner = functools.partial(_solve_wave_preempt_impl, B=B,
                              spread_alg=spread_alg,
                              dtype_name=dtype_name)
    if batched:
        inner = jax.vmap(inner)

    @jax.jit
    def fn(cm, cd, sf, si, pn, c0):
        chosen, scores, ny, ev = inner(cm, cd, sf, si, pn, c0)
        return jnp.stack([chosen.astype(scores.dtype), scores,
                          ny.astype(scores.dtype)]), ev
    return fn


def solve_lane_wave_preempt(const, init, batch, ptab, pinit, *,
                            spread_alg: bool, dtype_name: str,
                            batched: bool = False, cache_version=None,
                            delta_src=None):
    """Windowed-preemption solve with host precompute + compact transfer;
    returns host numpy (chosen int64, scores, n_yielded int64,
    evict_rows (P, A) bool), shaped like solve_lane_fused's preempt
    outputs. Callers gate on wavefront_preempt_ok."""
    S_dim = np.asarray(const.spread_vidx).shape[1 if batched else 0]
    if S_dim:
        raise ValueError(
            "wave-preempt kernel carries no spread columns; spread lanes "
            "must stay dense (callers gate on wavefront_ok)")
    if batched:
        E = np.asarray(batch.ask_cpu).shape[0]
        P = int(np.asarray(batch.ask_cpu).shape[1])
        L = int(np.asarray(batch.limit)[0][0])
    else:
        P = int(np.asarray(batch.ask_cpu).shape[0])
        L = int(np.asarray(batch.limit)[0])
    B = wavefront_buffer_size(L)
    if B is None:
        raise ValueError(f"lane limit {L} exceeds every wavefront buffer "
                         "width (caller must gate on wavefront_preempt_ok)")
    p_pad = _wave_p_bucket(P)
    if batched:
        active_rows = np.asarray(batch.active).any(axis=1)

        def pack_one(e):
            pick = lambda a: jax.tree_util.tree_map(  # noqa: E731
                lambda x, e=e: x[e], a)
            return wavefront_preempt_compact_host(
                pick(const), pick(init), pick(batch), pick(ptab),
                pick(pinit), dtype_name, p_pad=p_pad, B=B)

        inert = None
        packs = []
        for e in range(E):
            if not active_rows[e]:
                if inert is None:
                    inert = pack_one(e)
                packs.append(inert)
            else:
                packs.append(pack_one(e))
        compact = np.stack([p[0] for p in packs])
        cand = {k: np.stack([p[1][k] for p in packs])
                for k in packs[0][1]}
        scal_f = np.stack([p[2] for p in packs])
        scal_i = np.stack([p[3] for p in packs])
        pen = np.stack([p[4] for p in packs])
        counts0 = np.stack([p[5] for p in packs])
    else:
        compact, cand, scal_f, scal_i, pen, counts0 = \
            wavefront_preempt_compact_host(const, init, batch, ptab, pinit,
                                           dtype_name, p_pad=p_pad, B=B)

    fn = _wave_preempt_program(compact.shape, cand["cpu"].shape,
                               counts0.shape, spread_alg, dtype_name,
                               batched, B)
    stages.mark("put")
    cm, cd, sf, si, pn, c0 = _put_eval_sharded(
        batched, compact.shape[0],
        (compact, cand, scal_f, scal_i, pen, counts0),
        cache_version=cache_version, tag="compact_preempt",
        delta_src=delta_src)
    stages.mark("launch")
    out = fn(cm, cd, sf, si, pn, c0)
    stages.mark("fetch")
    with jitcheck.sanctioned_fetch("wave_preempt"):
        combined, ev = jax.device_get(out)
    from . import xferobs
    xferobs.note_fetch(xferobs.tree_nbytes((combined, ev)),
                       "wave_preempt")
    combined = combined[..., :P]
    ev = ev[..., :P, :]
    return (combined[0].astype(np.int64), combined[1],
            combined[2].astype(np.int64), np.asarray(ev))


def _put_eval_sharded(batched: bool, e_dim: int, trees,
                      cache_version=None, tag: str = "compact",
                      delta_src=None):
    """Device-put a tuple of (possibly nested) arrays, sharding the
    leading eval axis across ALL attached devices when it divides the
    device count (and NOMAD_TPU_MESH is not 0 -- the same master
    switch as the dense/LPQ mesh routes, so rollback to single-device
    is one knob). The fused eval axis is embarrassingly data-parallel:
    each chip runs its lanes' scans independently (no collectives;
    outputs gather on fetch). Shared by the wave and wave-preempt
    dispatch paths so their sharding gates can't diverge.

    The single-device path routes through the device-resident const
    cache (solver/constcache.py): compact tables that repeat across
    barrier generations of one snapshot ship once and stay pinned,
    keyed by content and tagged with ``cache_version`` (the packing
    snapshot's node_table_index). The sharded path ships fresh -- the
    cache stores unsharded buffers -- but still reports its bytes so
    ``nomad.solver.dispatch_bytes`` means one thing everywhere.
    ``tag`` is the transfer ledger's tree-group attribution for these
    tables (the wave transports ship merged compact tables that can't
    decompose into const/init/batch)."""
    from ..parallel.mesh import mesh_enabled
    from .constcache import device_put_cached

    if not (batched and mesh_enabled() and jax.device_count() > 1
            and e_dim % jax.device_count() == 0):
        leaves, treedef = jax.tree_util.tree_flatten(trees)
        # the compact tables are the wave packers' fresh np.stack
        # outputs, so the ISSUE-20 version chain can retain them as
        # frozen shadows and scatter only the changed elements into
        # the resident buffers (delta_src = the packing snapshot's
        # (store, index); eval-axis sharded puts below stay wholesale)
        buffers, _ = device_put_cached(leaves, version=cache_version,
                                       tags=[tag] * len(leaves),
                                       delta_src=delta_src)
        return jax.tree_util.tree_unflatten(treedef, buffers)
    # sharded route: the mesh factory, the PartitionSpec and the
    # NamedSharding put all live in parallel/mesh.py (the sharding-spec
    # registry; nomadlint's mesh-factory / no-implicit-put rules pin
    # the discipline)
    from ..parallel.mesh import shard_eval_axis
    return shard_eval_axis(trees, tag=tag)


@_single_flight
@functools.lru_cache(maxsize=None)
def _wave_compact_program(cm_shape, sp_shape, spread_alg: bool,
                          dtype_name: str, batched: bool, B: int,
                          use_block: bool):
    """Per-shape-bucket factory for the wavefront compact/block
    programs (the no-callsite-jit discipline: one jitted callable per
    bucket, constructed once behind this lru_cache). The two jit
    bodies differ statically: the block-merge kernel takes no spread
    tables (callers gate sp to zero-size)."""
    impl = (_solve_wave_block_impl if use_block
            else _solve_wave_compact_impl)
    inner = functools.partial(impl, spread_alg=spread_alg,
                              dtype_name=dtype_name, B=B)
    if use_block:
        k_blk, inner_blk = _wave_block_shape()
        inner = functools.partial(inner, K=k_blk, INNER=inner_blk)
    if batched:
        inner = jax.vmap(inner)

    if use_block:
        @jax.jit
        def fn(cm, sf, si, pn, spx):
            chosen, scores, ny = inner(cm, sf, si, pn)
            return jnp.stack([chosen.astype(scores.dtype), scores,
                              ny.astype(scores.dtype)])
    else:
        @jax.jit
        def fn(cm, sf, si, pn, spx):
            chosen, scores, ny = inner(cm, sf, si, pn, spx)
            return jnp.stack([chosen.astype(scores.dtype), scores,
                              ny.astype(scores.dtype)])
    return fn


def solve_lane_wave(const, init, batch, *, spread_alg: bool,
                    dtype_name: str, batched: bool = False,
                    cache_version=None, delta_src=None):
    """Wavefront solve with host precompute + compact transfer; returns
    host numpy (chosen int64, scores, n_yielded int64), shaped like
    solve_lane_fused's non-preempt outputs. The slot-buffer width B is
    picked from the lane's limit (WAVE_B for log2 windows, WAVE_B_WIDE
    for spread/affinity windows); callers guarantee it fits."""
    if batched:
        E = np.asarray(batch.ask_cpu).shape[0]
        P = int(np.asarray(batch.ask_cpu).shape[1])
        L = int(np.asarray(batch.limit)[0][0])
        B = wavefront_buffer_size(L)
        if B is None:
            raise ValueError(f"lane limit {L} exceeds every wavefront "
                             "buffer width (caller must gate on "
                             "wavefront_ok)")
        p_pad = _wave_p_bucket(P)
        # Deliberately a PER-LANE loop, not an (E, N) vectorized pass: a
        # batched numpy pack was built and measured 2x SLOWER at the
        # headline shape (60ms vs 32ms for 32 lanes x 10K nodes) -- the
        # per-lane arrays (~80KB) stay cache-resident while (E, N)
        # temporaries (~26MB apiece) thrash, and the fit-prefix
        # extraction needs a stable argsort batched vs a cheap nonzero
        # per lane. Inert padding lanes (active all-False, replicas of
        # lane 0 from the fuse path's E-bucket pinning) place nothing;
        # one precompute serves them all instead of E-e_real redundant
        # O(N) host folds.
        active_rows = np.asarray(batch.active).any(axis=1)

        def pack_one(e):
            return wavefront_compact_host(
                jax.tree_util.tree_map(lambda a: a[e], const),
                jax.tree_util.tree_map(lambda a: a[e], init),
                jax.tree_util.tree_map(lambda a: a[e], batch),
                dtype_name, p_pad=p_pad, B=B)

        inert_pack = None
        lanes = []
        for e in range(E):
            if not active_rows[e]:
                if inert_pack is None:
                    inert_pack = pack_one(e)
                lanes.append(inert_pack)
            else:
                lanes.append(pack_one(e))
        compact = np.stack([l[0] for l in lanes])
        scal_f = np.stack([l[1] for l in lanes])
        scal_i = np.stack([l[2] for l in lanes])
        pen = np.stack([l[3] for l in lanes])
        sp = jax.tree_util.tree_map(
            lambda *xs: np.stack(xs), *[l[4] for l in lanes])
    else:
        P = int(np.asarray(batch.ask_cpu).shape[0])
        L = int(np.asarray(batch.limit)[0])
        B = wavefront_buffer_size(L)
        if B is None:
            raise ValueError(f"lane limit {L} exceeds every wavefront "
                             "buffer width (caller must gate on "
                             "wavefront_ok)")
        p_pad = _wave_p_bucket(P)
        compact, scal_f, scal_i, pen, sp = wavefront_compact_host(
            const, init, batch, dtype_name, p_pad=p_pad, B=B)

    # zero-size spread tables flow through uniformly: the kernel skips
    # spread work statically when S == 0. Lanes with no spreads and no
    # active reschedule penalties take the block-merge kernel (one chain
    # step per window event, ~10x fewer sequential steps -- see the
    # block comment at _solve_wave_block_impl); others take the
    # per-placement compact scan.
    use_block = (sp.counts.shape[-2] == 0
                 and bool((np.asarray(pen) < 0).all()))
    fn = _wave_compact_program(compact.shape, sp.counts.shape,
                               spread_alg, dtype_name, batched, B,
                               use_block)
    stages.mark("put")
    cm, sf, si, pn, spd = _put_eval_sharded(
        batched, compact.shape[0], (compact, scal_f, scal_i, pen, sp),
        cache_version=cache_version, delta_src=delta_src)
    stages.mark("launch")
    out = fn(cm, sf, si, pn, spd)
    stages.mark("fetch")
    with jitcheck.sanctioned_fetch("wave"):
        combined = jax.device_get(out)
    from . import xferobs
    xferobs.note_fetch(xferobs.tree_nbytes(combined), "wave")
    # slice padded placement steps back off (outputs are [..., :p_pad])
    combined = combined[..., :P]
    return (combined[0].astype(np.int64), combined[1],
            combined[2].astype(np.int64))


def make_node_const(matrix, feasible: np.ndarray, affinity,
                    distinct_hosts: bool, spread_info, order: np.ndarray,
                    dtype=np.float32,
                    distinct_job_level: bool = False) -> NodeConst:
    """Assemble NodeConst in shuffled order (order[i] = original index of the
    node at shuffled position i)."""
    n_pad = matrix.n_pad
    perm = np.asarray(order, dtype=np.int64)
    cpu = matrix.cpu_cap[perm].astype(dtype)
    mem = matrix.mem_cap[perm].astype(dtype)
    disk = matrix.disk_cap[perm].astype(dtype)
    feas = (feasible & matrix.valid)[perm]
    aff = (affinity[perm].astype(dtype) if affinity is not None
           else np.zeros(n_pad, dtype=dtype))
    if spread_info is not None:
        vidx = spread_info.value_index[:, perm]
        desired = spread_info.desired.astype(dtype)
        has_t = spread_info.has_targets
        weights = spread_info.weights.astype(dtype)
        sum_w = np.asarray(spread_info.sum_weights, dtype=dtype)
        n_s = spread_info.n_spreads
    else:
        vidx = np.zeros((0, n_pad), dtype=np.int32)
        desired = np.zeros((0, 1), dtype=dtype)
        has_t = np.zeros(0, dtype=bool)
        weights = np.zeros(0, dtype=dtype)
        sum_w = np.asarray(0.0, dtype=dtype)
        n_s = 0
    # numpy-backed on purpose: lanes from many evals are np.stack'ed into
    # one (E, ...) batch before any device transfer (solver/batch.py)
    return NodeConst(
        cpu_cap=cpu, mem_cap=mem,
        disk_cap=disk, feasible=np.asarray(feas),
        affinity=aff,
        has_affinity=np.asarray(affinity is not None),
        distinct_hosts=np.asarray(bool(distinct_hosts)),
        distinct_job_level=np.asarray(bool(distinct_job_level)),
        spread_vidx=np.asarray(vidx), spread_desired=np.asarray(desired),
        spread_has_targets=np.asarray(has_t),
        spread_weights=np.asarray(weights),
        spread_sum_weights=np.asarray(sum_w),
        n_spreads=np.asarray(n_s, dtype=np.int32))


def make_node_state(usage, matrix, static_ports_free: np.ndarray,
                    order: np.ndarray, n_spreads: int, n_values: int,
                    spread_counts=None, dtype=np.float32) -> NodeState:
    perm = np.asarray(order, dtype=np.int64)
    counts = (spread_counts if spread_counts is not None
              else np.zeros((n_spreads, max(n_values, 1)), dtype=np.int32))
    return NodeState(
        used_cpu=usage.used_cpu[perm].astype(dtype),
        used_mem=usage.used_mem[perm].astype(dtype),
        used_disk=usage.used_disk[perm].astype(dtype),
        placed=np.asarray(usage.placed_jobtg[perm], dtype=np.int32),
        placed_job=np.asarray(usage.placed_job[perm], dtype=np.int32),
        static_free=np.asarray(static_ports_free[perm]),
        dyn_avail=(matrix.dyn_free - usage.dyn_used)[perm].astype(np.int32),
        spread_counts=np.asarray(counts))
