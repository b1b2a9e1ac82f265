"""Tensorization: structs <-> dense arrays for the TPU solver.

This is the marshalling layer the north star calls for (BASELINE.json:
"nomad/structs Allocation/Node are marshalled into packed int32 tensors"):
node capacities, proposed usage, port bitmaps, spread-attribute value
indexes and feasibility masks become fixed-shape numpy arrays that
nomad_tpu/solver/binpack.py consumes on TPU.

Shapes are padded to bucket sizes so XLA compiles once per bucket, not once
per fleet size (SURVEY.md section 7 hard part 6: bucket-and-pad).
"""
from __future__ import annotations

from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..structs.resources import (
    DEFAULT_MAX_DYNAMIC_PORT, DEFAULT_MIN_DYNAMIC_PORT,
)

PORT_WORDS = 2048          # 65536 ports / 32 bits
DEFAULT_NODE_BUCKETS = (64, 256, 1024, 4096, 16384, 65536)


def bucket_size(n: int, buckets=DEFAULT_NODE_BUCKETS) -> int:
    for b in buckets:
        if n <= b:
            return b
    return int(2 ** np.ceil(np.log2(max(n, 1))))


@dataclass
class NodeMatrix:
    """Static per-eval node-axis tensors (padded to n_pad).

    Columns mirror what BinPackIterator reads per node
    (reference: scheduler/rank.go:205-571).
    """

    n_real: int
    n_pad: int
    node_ids: List[str]
    cpu_cap: np.ndarray        # (n_pad,) float64 -- capacity minus reserved
    mem_cap: np.ndarray
    disk_cap: np.ndarray
    # (n_pad, PORT_WORDS) uint32 agent-reserved ports; None when no node
    # reserves ports (the common case -- the 10K-node bitmap is 80MB, so it
    # is only materialized when port state actually exists)
    port_bitmap: Optional[np.ndarray]
    dyn_free: np.ndarray       # (n_pad,) int32 free ports in dynamic range
    valid: np.ndarray          # (n_pad,) bool -- real node vs padding
    # computed-class coding for vectorized feasibility: codes (n_pad,)
    # int32 (-1 = padding or class never computed), class_reps[i] = the
    # node index representing code i
    class_codes: Optional[np.ndarray] = None
    class_reps: Optional[List[int]] = None


def pack_nodes(nodes, n_pad: Optional[int] = None) -> NodeMatrix:
    n = len(nodes)
    if n_pad is None:
        n_pad = bucket_size(n)
    cpu = np.zeros(n_pad, dtype=np.float64)
    mem = np.zeros(n_pad, dtype=np.float64)
    disk = np.zeros(n_pad, dtype=np.float64)
    ports: Optional[np.ndarray] = None
    dyn_free = np.zeros(n_pad, dtype=np.int32)
    valid = np.zeros(n_pad, dtype=bool)
    ids = []
    codes = np.full(n_pad, -1, dtype=np.int32)
    code_of: Dict[str, int] = {}
    reps: List[int] = []
    for i, node in enumerate(nodes):
        ids.append(node.id)
        cls = node.computed_class
        if cls:
            code = code_of.get(cls)
            if code is None:
                code = len(reps)
                code_of[cls] = code
                reps.append(i)
            codes[i] = code
        nr, rr = node.node_resources, node.reserved_resources
        cpu[i] = nr.cpu.cpu_shares - rr.cpu_shares
        mem[i] = nr.memory.memory_mb - rr.memory_mb
        disk[i] = nr.disk.disk_mb - rr.disk_mb
        lo, hi = nr.min_dynamic_port, nr.max_dynamic_port
        dyn_free[i] = max(0, hi - lo + 1)
        for p in rr.reserved_ports:
            if 0 <= p < 65536:
                if ports is None:
                    ports = np.zeros((n_pad, PORT_WORDS), dtype=np.uint32)
                ports[i, p >> 5] |= np.uint32(1 << (p & 31))
                if lo <= p <= hi:
                    dyn_free[i] -= 1
        valid[i] = True
    return NodeMatrix(n_real=n, n_pad=n_pad, node_ids=ids, cpu_cap=cpu,
                      mem_cap=mem, disk_cap=disk, port_bitmap=ports,
                      dyn_free=dyn_free, valid=valid, class_codes=codes,
                      class_reps=reps)


# pack_nodes is ~20ms at 10K nodes but its inputs only change when the
# node table does; cache per (node-table version, node-id tuple). The id
# tuple guards against different filtered subsets (datacenter/pool
# eligibility differs per job) sharing a table version. Concurrent eval
# workers hit this, hence the lock. True LRU: a hit refreshes recency
# (move_to_end), so 8+ jobs filtering different node subsets can no
# longer thrash the hottest entry out in insertion order.
import threading as _threading
from collections import OrderedDict as _OrderedDict

_NODE_MATRIX_CACHE: "_OrderedDict[tuple, NodeMatrix]" = _OrderedDict()
_NODE_MATRIX_CACHE_MAX = 8
_NODE_MATRIX_LOCK = _threading.Lock()

# ---------------------------------------------------------------------------
# Snapshot-scoped pack caches (perf: kill the host-side packing tax).
#
# Between consecutive evals the node table is usually unchanged and only
# proposed-alloc usage deltas move (the CvxCluster observation applied to
# the eval stream, PAPERS.md): everything derived purely from (node-table
# version, job/TG spec) is memoized ON the version-keyed NodeMatrix --
# feasibility masks, spread tables, affinity columns -- and the
# job-independent usage fold is memoized per snapshot (service.py keeps
# the base + overlays each eval's own plan deltas). Invalidation rides the
# existing hooks: a node-table write mints a new matrix key (state/store
# _bump also drops stale-version matrices here), and the dispatch
# breaker's trip/recovery edges clear everything (solver/guard.py).

_PACK_STATS = {
    "hits": 0,              # feasibility/spread/affinity memo hits
    "misses": 0,
    "matrix_hits": 0,       # node-matrix cache
    "matrix_misses": 0,
    "usage_base_hits": 0,   # per-snapshot usage-base fold (service.py)
    "usage_base_misses": 0,
    # stale base advanced by applying journaled alloc deltas instead of
    # refolding (service.py _catch_up_usage_base; counts as a hit in the
    # per-eval window)
    "usage_base_delta_hits": 0,
    "invalidations": 0,
}
_PACK_STATS_LOCK = _threading.Lock()

# per-matrix memo bound: one matrix serves every job shape of one fleet
# version; a pathological spec churn clears rather than grows unbounded
_MATRIX_MEMO_MAX = 64


# per-thread hit/miss window: service.pack attributes cache outcomes to
# ONE eval's pack call; reading deltas off the global counters would
# double-count under concurrent eval threads
_PACK_TLS = _threading.local()


def _stat_incr(name: str, n: int = 1) -> None:
    with _PACK_STATS_LOCK:
        _PACK_STATS[name] += n
    bucket = ("hit" if name.endswith("hits")
              else "miss" if name.endswith("misses") else None)
    if bucket is not None:
        setattr(_PACK_TLS, bucket, getattr(_PACK_TLS, bucket, 0) + n)


def begin_pack_window() -> Tuple[int, int]:
    """Start of one service.pack call on this thread: returns the
    thread-local (hits, misses) watermark."""
    return (getattr(_PACK_TLS, "hit", 0), getattr(_PACK_TLS, "miss", 0))


def end_pack_window(mark: Tuple[int, int]) -> Tuple[int, int]:
    """(hits, misses) this thread recorded since ``mark``."""
    return (getattr(_PACK_TLS, "hit", 0) - mark[0],
            getattr(_PACK_TLS, "miss", 0) - mark[1])


def pack_cache_stats() -> dict:
    with _PACK_STATS_LOCK:
        out = dict(_PACK_STATS)
    with _NODE_MATRIX_LOCK:
        out["matrix_entries"] = len(_NODE_MATRIX_CACHE)
    return out


def invalidate_pack_caches(reason: str = "") -> None:
    """Drop every cached matrix (the attached feasibility/spread/
    affinity/usage memos die with them). Wired to the breaker's
    trip/recovery edges beside the const cache; correctness never
    depends on it (caches are version/snapshot-keyed), it guarantees a
    clean re-derivation after a wedged-then-recovered transport."""
    with _NODE_MATRIX_LOCK:
        had = bool(_NODE_MATRIX_CACHE)
        _NODE_MATRIX_CACHE.clear()
    if had:
        _stat_incr("invalidations")


def note_table_write(tables, table_index: int, delta=None) -> None:
    """Unified store-write hook (state/store.py _notify_write_hooks):
    one delta-aware notification shared with the solver const cache.
    Fleet-table writes drop stale matrices here; alloc writes carry
    their (old, new) delta pairs, which the matrix-attached usage-base
    memos consume lazily via StateStore.alloc_deltas_since (the journal
    the same _bump call appended to)."""
    if "nodes" in tables:
        note_node_table_write(table_index)


def note_node_table_write(table_index: int) -> None:
    """Node-table write hook (state/store.py _bump): drop matrices (and
    their attached memos) packed under older fleet versions -- they can
    never be keyed again and would only squat on the LRU."""
    with _NODE_MATRIX_LOCK:
        stale = [k for k in _NODE_MATRIX_CACHE if k[0] < table_index]
        for k in stale:
            del _NODE_MATRIX_CACHE[k]
    if stale:
        _stat_incr("invalidations")


def journal_touched_nodes(pairs) -> set:
    """The set of node ids an alloc-delta journal span touches: the
    host-side translation of the PR-6 (old_alloc, new_alloc) pairs into
    per-node scope (ISSUE 20 delta streaming). An alloc move touches
    BOTH endpoints -- the node it left (usage freed) and the node it
    landed on (usage charged). The device-side scatter's update set is
    the authoritative bitwise diff (under the per-eval fit-order
    shuffle journal rows don't map to stable device rows), so this
    scope is the journal's observability half: how many fleet rows the
    span implicates, surfaced beside the actually-scattered element
    count in the transfer ledger's chain rows."""
    touched: set = set()
    for old, new in pairs:
        for a in (old, new):
            nid = getattr(a, "node_id", None)
            if nid:
                touched.add(nid)
    return touched


def _reset_pack_caches_for_tests() -> None:
    with _NODE_MATRIX_LOCK:
        _NODE_MATRIX_CACHE.clear()
    with _PACK_STATS_LOCK:
        for k in _PACK_STATS:
            _PACK_STATS[k] = 0


def pack_nodes_cached(nodes, node_table_index: Optional[int],
                      key_hint=None) -> NodeMatrix:
    """pack_nodes memoized by node-table version. Callers must treat the
    result as immutable (service.py copies the port bitmap before
    seeding). ``key_hint`` is the node-id tuple when the caller already
    holds it (the snapshot ready-list memo) -- rebuilding it per eval
    was an O(N) python pass of its own."""
    if node_table_index is None:
        return pack_nodes(nodes)
    key = (node_table_index,
           key_hint if key_hint is not None
           else tuple(n.id for n in nodes))
    with _NODE_MATRIX_LOCK:
        hit = _NODE_MATRIX_CACHE.get(key)
        if hit is not None:
            _NODE_MATRIX_CACHE.move_to_end(key)
    if hit is not None:
        _stat_incr("matrix_hits")
        from .. import statecheck
        if statecheck._ACTIVE:
            # served-entry version must be the version the caller's
            # snapshot pins (statecheck check e; equal by construction
            # today -- this guards the keying against refactors)
            statecheck.note_memo_served("node_matrix", key[0],
                                        node_table_index)
        return hit
    matrix = pack_nodes(nodes)
    _stat_incr("matrix_misses")
    freeze_matrix(matrix)
    with _NODE_MATRIX_LOCK:
        while len(_NODE_MATRIX_CACHE) >= _NODE_MATRIX_CACHE_MAX:
            _NODE_MATRIX_CACHE.popitem(last=False)
        _NODE_MATRIX_CACHE[key] = matrix
    return matrix


def _matrix_memo(matrix, key, build):
    """Memoize ``build()`` on the (immutable, version-keyed) NodeMatrix.
    Results are shared across concurrent evals, so cached arrays are
    frozen read-only -- every consumer copies before mutating (the
    make_node_const/state assemblers permute into fresh arrays)."""
    if matrix is None:
        return build()
    memo = matrix.__dict__.get("_pack_memo")
    if memo is None:
        memo = matrix.__dict__.setdefault("_pack_memo", {})
    hit = memo.get(key)
    if hit is not None:
        _stat_incr("hits")
        return hit[0]
    out = build()
    _freeze(out)
    _stat_incr("misses")
    if len(memo) >= _MATRIX_MEMO_MAX:
        memo.clear()
    # nomadlint: waive=version-keyed-memo -- the container itself is
    # version-scoped: it lives on a NodeMatrix that is keyed by
    # (node_table_index, node-id tuple) in _NODE_MATRIX_CACHE and dies
    # with that fleet version; keys here are job/TG spec fingerprints
    memo[key] = (out,)          # tuple-wrapped: None is a valid result
    return out


def _freeze(obj) -> None:
    """Mark cached numpy payloads read-only (shared across evals) and
    register them with the dispatch-discipline sanitizer's frozen-memo
    registry (jitcheck.py check d) when it is recording."""
    if isinstance(obj, np.ndarray):
        obj.setflags(write=False)
        _note_frozen(obj)
    elif isinstance(obj, SpreadInfo):
        for arr in (obj.value_index, obj.desired, obj.has_targets,
                    obj.weights, obj.initial_counts):
            arr.setflags(write=False)
            _note_frozen(arr)


def _note_frozen(arr) -> None:
    from .. import jitcheck, statecheck
    if jitcheck._ACTIVE:
        jitcheck.note_frozen(arr)
    if statecheck._ACTIVE:
        # frozen memo payloads are exactly the "reachable from a
        # published snapshot/memo" set the snapshot-isolation
        # sanitizer re-fingerprints (statecheck.py check b)
        statecheck.note_published(arr)


def freeze_matrix(matrix: NodeMatrix) -> None:
    """Freeze a NodeMatrix's array payloads before it enters the
    version-keyed cache: matrices are shared by every concurrent eval
    of a fleet version, and every consumer already copies (the
    make_node_const/state assemblers permute into fresh arrays,
    pack_usage copies the port bitmap, native.pack copies the
    port_words seed). The frozen-memo invariant makes that contract
    enforced instead of conventional."""
    for arr in (matrix.cpu_cap, matrix.mem_cap, matrix.disk_cap,
                matrix.dyn_free, matrix.valid, matrix.class_codes,
                matrix.port_bitmap):
        if isinstance(arr, np.ndarray):
            arr.setflags(write=False)
            _note_frozen(arr)


def freeze_usage_base(base: dict) -> None:
    """Freeze a memoized usage-base fold (solver/service.py): the base
    is shared by every eval of a snapshot and each eval copies before
    overlaying its own plan deltas -- enforce that copy-before-write
    contract like the other pack memos."""
    for k in ("used_cpu", "used_mem", "used_disk", "dyn_used"):
        base[k].setflags(write=False)
        _note_frozen(base[k])
    if base.get("ports") is not None:
        base["ports"].setflags(write=False)
        _note_frozen(base["ports"])


def _constraints_fp(constraints) -> tuple:
    return tuple((c.l_target, c.operand, str(c.r_target))
                 for c in constraints)


def pack_feasibility_cached(ctx, stack_like, tg, nodes, n_pad: int,
                            alloc_name: str = "", matrix=None
                            ) -> np.ndarray:
    """pack_feasibility memoized per (node-table version, constraint
    fingerprint): the verdict is a pure function of the job/TG spec and
    the snapshot's nodes (check_constraint reads ctx only for its regex
    cache), and the matrix IS the (version, node-subset) key. The
    fingerprint covers everything the checker stack reads: job + merged
    TG/task constraints, drivers, device asks, volumes (with the alloc
    name, which scopes per_alloc volume claims) and the network ask."""
    from ..scheduler.stack import _tg_constraints

    job = ctx.plan.job
    drivers, constraints = _tg_constraints(tg)
    key = ("feas",
           _constraints_fp(job.constraints if job else []),
           tuple(sorted(drivers)),
           _constraints_fp(constraints),
           repr([r for t in tg.tasks for r in t.resources.devices]),
           repr(tg.volumes), alloc_name if tg.volumes else "",
           repr(tg.networks[0]) if tg.networks else "")
    return _matrix_memo(matrix, key, lambda: pack_feasibility(
        ctx, stack_like, tg, nodes, n_pad, alloc_name=alloc_name,
        matrix=matrix))


def pack_spreads_cached(spreads, nodes, n_pad: int, tg_count: int,
                        existing_value_counts=None, matrix=None
                        ) -> Optional[SpreadInfo]:
    """pack_spreads memoized per (node-table version, spread-spec
    fingerprint). The existing-alloc value counts ride the key (they
    seed value tables and initial_counts), so two evals only share an
    entry when the whole SpreadInfo is provably identical."""
    if not spreads:
        return None
    key = ("spread", repr(spreads), int(tg_count),
           tuple(tuple(sorted(c.items())) for c in existing_value_counts)
           if existing_value_counts else None)
    return _matrix_memo(matrix, key, lambda: pack_spreads(
        spreads, nodes, n_pad, tg_count, existing_value_counts))


def pack_affinities_cached(affinities, ctx, nodes, n_pad: int,
                           matrix=None) -> Optional[np.ndarray]:
    """pack_affinities memoized per (node-table version, affinity-spec
    fingerprint)."""
    if not affinities:
        return None
    key = ("aff", repr(affinities))
    return _matrix_memo(matrix, key, lambda: pack_affinities(
        affinities, ctx, nodes, n_pad))


@dataclass
class UsageState:
    """Dynamic usage on the node axis: what proposed allocs consume
    (reference analog: EvalContext.ProposedAllocs -> AllocsFit used sum)."""

    used_cpu: np.ndarray       # (n_pad,) float64
    used_mem: np.ndarray
    used_disk: np.ndarray
    placed_jobtg: np.ndarray   # (n_pad,) int32 allocs of THIS job+tg per node
    placed_job: np.ndarray     # (n_pad,) int32 allocs of THIS job (any tg)
    # (n_pad, PORT_WORDS) uint32 incl. alloc ports; None when no port state
    port_bitmap: Optional[np.ndarray]
    dyn_used: np.ndarray       # (n_pad,) int32 dynamic-range ports in use

    def ensure_bitmap(self, n_pad: int) -> np.ndarray:
        if self.port_bitmap is None:
            self.port_bitmap = np.zeros((n_pad, PORT_WORDS), dtype=np.uint32)
        return self.port_bitmap


def pack_usage(matrix: NodeMatrix, proposed_by_node: Dict[str, list],
               job_id: str, tg_name: str, namespace: str = "default",
               nodes=None) -> UsageState:
    """Fold proposed allocations into usage tensors. ``proposed_by_node``
    maps node id -> list of proposed allocs (already excluding plan stops
    and client-terminal allocs, exactly what ctx.proposed_allocs returns)."""
    n_pad = matrix.n_pad
    used_cpu = np.zeros(n_pad, dtype=np.float64)
    used_mem = np.zeros(n_pad, dtype=np.float64)
    used_disk = np.zeros(n_pad, dtype=np.float64)
    placed = np.zeros(n_pad, dtype=np.int32)
    placed_job = np.zeros(n_pad, dtype=np.int32)
    ports = (matrix.port_bitmap.copy()
             if matrix.port_bitmap is not None else None)
    dyn_used = np.zeros(n_pad, dtype=np.int32)
    index = {nid: i for i, nid in enumerate(matrix.node_ids)}
    dyn_ranges = {}
    if nodes is not None:
        for node in nodes:
            dyn_ranges[node.id] = (node.node_resources.min_dynamic_port,
                                   node.node_resources.max_dynamic_port)
    for nid, allocs in proposed_by_node.items():
        i = index.get(nid)
        if i is None:
            continue
        lo, hi = dyn_ranges.get(nid, (DEFAULT_MIN_DYNAMIC_PORT,
                                      DEFAULT_MAX_DYNAMIC_PORT))
        for alloc in allocs:
            cr = alloc.allocated_resources.comparable()
            used_cpu[i] += cr.cpu_shares
            used_mem[i] += cr.memory_mb
            used_disk[i] += cr.disk_mb
            if alloc.job_id == job_id and alloc.namespace == namespace:
                placed_job[i] += 1
                if alloc.task_group == tg_name:
                    placed[i] += 1
            for v in alloc.allocated_resources.all_ports():
                if 0 <= v < 65536:
                    if ports is None:
                        ports = np.zeros((n_pad, PORT_WORDS), dtype=np.uint32)
                    word, bit = v >> 5, np.uint32(1 << (v & 31))
                    if not ports[i, word] & bit:
                        ports[i, word] |= bit
                        if lo <= v <= hi:
                            dyn_used[i] += 1
    return UsageState(used_cpu=used_cpu, used_mem=used_mem,
                      used_disk=used_disk, placed_jobtg=placed,
                      placed_job=placed_job, port_bitmap=ports,
                      dyn_used=dyn_used)


def fold_usage_base(matrix: NodeMatrix, nodes, allocs_of) -> dict:
    """Job-independent usage fold over one node list: what every
    non-client-terminal alloc consumes, vectorized (np.add.at over
    per-alloc column arrays + a deduplicated bitwise_or.at port fold)
    instead of pack_usage's per-alloc/per-port Python loop. The result
    is the per-snapshot BASE the incremental pack path memoizes; each
    eval copies it and overlays only its own plan deltas
    (solver/service.py _overlay_plan_deltas). Job-scoped placed counts
    are NOT folded here -- they depend on the asking job and are
    rebuilt per eval from its (small) alloc set."""
    n_pad = matrix.n_pad
    idx: List[int] = []
    cpu: List[float] = []
    mem: List[float] = []
    disk: List[float] = []
    port_pos: List[int] = []
    port_val: List[int] = []
    for i, node in enumerate(nodes):
        for alloc in allocs_of(node.id):
            cr = alloc.allocated_resources.comparable()
            idx.append(i)
            cpu.append(cr.cpu_shares)
            mem.append(cr.memory_mb)
            disk.append(cr.disk_mb)
            for v in alloc.allocated_resources.all_ports():
                if 0 <= v < 65536:
                    port_pos.append(i)
                    port_val.append(v)
    used_cpu = np.zeros(n_pad, dtype=np.float64)
    used_mem = np.zeros(n_pad, dtype=np.float64)
    used_disk = np.zeros(n_pad, dtype=np.float64)
    if idx:
        ii = np.asarray(idx, dtype=np.int64)
        np.add.at(used_cpu, ii, np.asarray(cpu, dtype=np.float64))
        np.add.at(used_mem, ii, np.asarray(mem, dtype=np.float64))
        np.add.at(used_disk, ii, np.asarray(disk, dtype=np.float64))
    ports = (matrix.port_bitmap.copy()
             if matrix.port_bitmap is not None else None)
    dyn_used = np.zeros(n_pad, dtype=np.int32)
    if port_pos:
        if ports is None:
            ports = np.zeros((n_pad, PORT_WORDS), dtype=np.uint32)
        pp = np.asarray(port_pos, dtype=np.int64)
        pv = np.asarray(port_val, dtype=np.int64)
        # dedupe (node, port) pairs exactly like the scalar loop's
        # already-set check: a port counts once per node
        keys = np.unique(pp * 65536 + pv)
        pp, pv = keys >> 16, keys & 0xFFFF
        words = pv >> 5
        bits = np.uint32(1) << (pv & 31).astype(np.uint32)
        already = (ports[pp, words] & bits) != 0
        np.bitwise_or.at(ports, (pp, words), bits)
        lo = np.zeros(n_pad, dtype=np.int64)
        hi = np.full(n_pad, -1, dtype=np.int64)
        for i, node in enumerate(nodes):
            lo[i] = node.node_resources.min_dynamic_port
            hi[i] = node.node_resources.max_dynamic_port
        in_dyn = (~already) & (pv >= lo[pp]) & (pv <= hi[pp])
        np.add.at(dyn_used, pp[in_dyn], 1)
    return {"used_cpu": used_cpu, "used_mem": used_mem,
            "used_disk": used_disk, "ports": ports, "dyn_used": dyn_used}


def pack_feasibility(ctx, stack_like, tg, nodes, n_pad: int,
                     alloc_name: str = "", matrix=None) -> np.ndarray:
    """Evaluate the boolean feasibility pipeline per node, memoized by
    computed class exactly like FeasibilityWrapper (feasible.go:1126).

    Host-side by design: constraint evaluation is string/regex-shaped and
    runs once per (eval, class), not per placement -- the per-placement hot
    loop (fit+score+select) is what runs on TPU."""
    from ..scheduler.feasible import (
        ConstraintChecker, DriverChecker, DeviceChecker, HostVolumeChecker,
        NetworkChecker)
    from ..scheduler.stack import _tg_constraints

    job = ctx.plan.job
    drivers, constraints = _tg_constraints(tg)
    job_check = ConstraintChecker(ctx, job.constraints if job else [])
    drv_check = DriverChecker(ctx, drivers)
    tg_check = ConstraintChecker(ctx, constraints)
    dev_check = DeviceChecker(ctx)
    dev_check.set_task_group(tg)
    vol_check = HostVolumeChecker(ctx)
    vol_check.set_volumes(alloc_name, tg.volumes)
    net_check = NetworkChecker(ctx)
    if tg.networks:
        net_check.set_network(tg.networks[0])

    out = np.zeros(n_pad, dtype=bool)
    escaped = any("unique." in (c.l_target + c.r_target)
                  for c in (job.constraints if job else []) + constraints)

    def class_verdict(node):
        return (job_check.feasible(node) and drv_check.feasible(node)
                and tg_check.feasible(node)
                and dev_check.feasible(node)
                and net_check.feasible(node))

    # vectorized path: with class-coded nodes and no escaped ("unique.")
    # constraints, evaluate the class-level checkers once per DISTINCT
    # class and broadcast through the code array -- the per-node python
    # loop was a measured ~10ms/eval fixed cost at 10K nodes. Host
    # volumes are per-node state and keep a (volume-lanes-only) loop.
    codes = matrix.class_codes if matrix is not None else None
    if (not escaped and codes is not None
            and matrix.n_real == len(nodes)
            and matrix.class_reps is not None
            and (codes[:len(nodes)] >= 0).all()):
        verdicts = np.fromiter(
            (class_verdict(nodes[rep]) for rep in matrix.class_reps),
            dtype=bool, count=len(matrix.class_reps))
        n = len(nodes)
        out[:n] = verdicts[codes[:n]] if len(verdicts) else False
        if vol_check.volumes:
            for i, node in enumerate(nodes):
                if out[i]:
                    out[i] = vol_check.feasible(node)
        return out

    class_cache: Dict[str, bool] = {}
    check_vols = bool(vol_check.volumes)
    for i, node in enumerate(nodes):
        cls = node.computed_class
        if not escaped and cls in class_cache:
            class_ok = class_cache[cls]
        else:
            class_ok = class_verdict(node)
            if not escaped and cls:
                class_cache[cls] = class_ok
        out[i] = class_ok and (not check_vols or vol_check.feasible(node))
    return out


@dataclass
class SpreadInfo:
    """Spread attributes tensorized: per spread, each node's value index into
    a padded value table plus desired counts (reference: spread.go
    computeSpreadInfo + propertyset.go)."""

    n_spreads: int
    value_index: np.ndarray    # (S, n_pad) int32; -1 = attribute missing
    n_values: int              # V (padded distinct values across spreads)
    desired: np.ndarray        # (S, V) float64; -1 = no explicit target
    has_targets: np.ndarray    # (S,) bool
    weights: np.ndarray        # (S,) float64
    sum_weights: float
    initial_counts: np.ndarray  # (S, V) int32 existing allocs per value
    values: List[List[str]]    # per spread, the value table


def pack_spreads(spreads, nodes, n_pad: int, tg_count: int,
                 existing_value_counts: Optional[List[Dict[str, int]]] = None
                 ) -> Optional[SpreadInfo]:
    """Build spread tensors; None when the TG has no spreads."""
    from ..scheduler.util import resolve_target
    if not spreads:
        return None
    S = len(spreads)
    tables: List[List[str]] = []
    per_node_vals: List[List[str]] = []
    for s in spreads:
        vals = []
        node_vals = []
        for node in nodes:
            v, ok = resolve_target(s.attribute, node)
            node_vals.append(str(v) if ok else None)
            if ok and str(v) not in vals:
                vals.append(str(v))
        # values referenced only by existing allocs still need slots
        if existing_value_counts:
            idx = len(tables)
            if idx < len(existing_value_counts):
                for v in existing_value_counts[idx]:
                    if v not in vals:
                        vals.append(v)
        tables.append(vals)
        per_node_vals.append(node_vals)
    V = max(1, max(len(t) for t in tables))
    value_index = np.full((S, n_pad), -1, dtype=np.int32)
    desired = np.full((S, V), -1.0, dtype=np.float64)
    has_targets = np.zeros(S, dtype=bool)
    weights = np.zeros(S, dtype=np.float64)
    init_counts = np.zeros((S, V), dtype=np.int32)
    for si, s in enumerate(spreads):
        table = {v: j for j, v in enumerate(tables[si])}
        for ni, v in enumerate(per_node_vals[si]):
            if v is not None:
                value_index[si, ni] = table[v]
        weights[si] = float(s.weight)
        if s.spread_target:
            has_targets[si] = True
            implicit = None
            for t in s.spread_target:
                if t.value == "*":
                    implicit = (t.percent / 100.0) * tg_count
                    continue
                if t.value in table:
                    desired[si, table[t.value]] = (t.percent / 100.0) * tg_count
            if implicit is not None:
                for v, j in table.items():
                    if desired[si, j] < 0:
                        desired[si, j] = implicit
        if existing_value_counts and si < len(existing_value_counts):
            for v, c in existing_value_counts[si].items():
                if v in table:
                    init_counts[si, table[v]] = c
    return SpreadInfo(n_spreads=S, value_index=value_index, n_values=V,
                      desired=desired, has_targets=has_targets,
                      weights=weights, sum_weights=float(weights.sum()),
                      initial_counts=init_counts, values=tables)


def pack_affinities(affinities, ctx, nodes, n_pad: int) -> Optional[np.ndarray]:
    """Per-node normalized affinity score (static within an eval)
    (reference: rank.go:756 NodeAffinityIterator)."""
    from ..scheduler.feasible import check_constraint
    from ..scheduler.util import resolve_target
    if not affinities:
        return None
    sum_weight = sum(abs(float(a.weight)) for a in affinities)
    out = np.zeros(n_pad, dtype=np.float64)
    for i, node in enumerate(nodes):
        total = 0.0
        for aff in affinities:
            lval, l_ok = resolve_target(aff.l_target, node)
            rval, r_ok = resolve_target(aff.r_target, node)
            if check_constraint(ctx, aff.operand, lval, rval, l_ok, r_ok):
                total += float(aff.weight)
        out[i] = total / sum_weight if sum_weight else 0.0
    return out
