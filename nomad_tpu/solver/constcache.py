"""Device-resident constant cache: stop re-shipping the fleet tables.

The chip solves a fused headline batch in about a millisecond, while
each dispatch ships megabytes of lane tables over the host<->device
link (solver/xferobs.py keeps the ledger). Most of those bytes are the
same bytes every time -- NodeMatrix-derived caps/feasibility/spread
columns that only change when the node table does, and usage columns
that repeat across the barrier generations of one snapshot. CvxCluster
(PAPERS.md) gets its 100-1000x by keeping the problem matrices resident
and streaming only deltas; this is that move for the dispatch path.

Mechanism: a content-addressed cache of device-resident buffers. Before
a dispatch transfers an input array, its fingerprint (BLAKE2b over
dtype/shape/bytes) is looked up; a hit reuses the pinned device buffer
(zero bytes shipped), a miss pays one ``jax.device_put`` and pins the
result. Content addressing makes the cache self-validating -- a stale
entry can never be USED for changed data, it can only sit resident --
so the version tags (the state store's ``node_table_index``, see
state/store.py StateSnapshot) exist purely for prompt memory hygiene:
a node-table write drops entries uploaded under older fleet versions,
and an LRU bound (entries + resident bytes) caps what one process pins
on device. The circuit breaker (solver/guard.py) drops everything on a
trip or recovery: buffers created through a wedged-then-recovered
transport are not trusted.

Accounting: every dispatch path reports bytes actually shipped through
``note_dispatch_bytes`` -> the ``nomad.solver.dispatch_bytes`` gauge +
``nomad.solver.dispatch_bytes_total`` counter, and hits/misses ride
``nomad.solver.const_cache_{hit,miss}`` -- so the transfer cut is
visible in /v1/agent/self and ``operator solver status`` rather than
inferred.

Bounds: NOMAD_TPU_CONST_CACHE_ENTRIES (default 64),
NOMAD_TPU_CONST_CACHE_MB (default 256). Arrays smaller than
NOMAD_TPU_CONST_CACHE_MIN_BYTES (default 4096) are always shipped
fresh -- they ARE the delta traffic the design wants on the wire, and
caching them would churn the LRU for nothing.

Mesh dispatches (ISSUE 19) ride a per-shard twin of the same design:
``device_put_sharded_cached`` keys single-device shard buffers by
(content key, shard device) in a separate pool bounded by
NOMAD_TPU_CONST_CACHE_SHARD_ENTRIES (default 512) and the shared MB
budget, so a node-table write re-uploads only the shards whose slice
content changed.

Delta streaming (ISSUE 20, ROADMAP item 3): content addressing alone
still re-ships a table whenever ANY element changed. The version chain
(``chain_apply``) closes that gap: each dispatch-tree slot keeps a
*chain entry* -- the device buffer it shipped last generation plus a
frozen host shadow -- and when the PR-6 alloc-delta journal
(state/store.py ``alloc_deltas_since``) covers the (v_old, v_new] span,
the transport ships only the bitwise-changed elements and applies them
ON DEVICE with a small jitted scatter (``_delta_scatter_program``, one
program per shape/dtype/update-count bucket). The entry at v_old plus
the applied delta IS the entry at v_new: same content-key discipline
(the promoted content's fingerprint re-registers with jitcheck and
enters the content cache), with wholesale re-upload as the fallback on
journal gaps/overflow or oversized diffs. Every delta payload is tagged
into the transfer ledger's ``delta`` tree group, so the zero-tolerance
byte parity and the fold-parity gate remain the correctness net.
"""
from __future__ import annotations

import functools
import hashlib
import os
import threading
import time
from collections import OrderedDict
from typing import List, Optional, Sequence, Tuple

import numpy as np

_LOCK = threading.Lock()
_CACHE: "OrderedDict[bytes, _Entry]" = OrderedDict()
# per-shard pool (ISSUE 19): single-device shard buffers keyed
# (content key, shard device) -- separate store so a fleet of N-shard
# slices can't LRU-churn the unsharded entries (and vice versa)
_SHARD_CACHE: "OrderedDict[bytes, _Entry]" = OrderedDict()
# version-chain pool (ISSUE 20): one entry per dispatch-tree SLOT
# (tag, dtype, shape, occurrence [, mesh]), not per content -- the
# previous generation's device buffer + frozen host shadow, delta-
# updated in place instead of re-shipped
_CHAIN: "OrderedDict[tuple, _ChainEntry]" = OrderedDict()
_STATS = {
    "hits": 0,
    "misses": 0,
    "bytes_shipped_total": 0,
    "bytes_saved_total": 0,
    "invalidations": 0,
    "evictions": 0,
    "resident_bytes": 0,
    "shard_resident_bytes": 0,
    "shard_resident_hwm": 0,
    # delta-streaming counters (ISSUE 20): promotions apply an
    # on-device scatter, reuses ship zero bytes (bitwise-identical
    # generation), fallbacks re-ship wholesale with a live chain entry
    # (gap = journal overflow/uncoverable span, size = diff payload
    # over NOMAD_TPU_DELTA_MAX_FRAC)
    "delta_promotions": 0,
    "delta_reuses": 0,
    "delta_fallbacks": 0,
    "delta_gap_fallbacks": 0,
    "delta_size_fallbacks": 0,
    "delta_bytes_total": 0,
    "delta_touched_nodes_last": 0,
    "chain_resident_bytes": 0,
}


class _Entry:
    __slots__ = ("buf", "nbytes", "version", "created_at", "hits",
                 "shard")

    def __init__(self, buf, nbytes: int, version: Optional[int],
                 shard: Optional[int] = None):
        self.buf = buf              # the pinned jax.Array
        self.nbytes = nbytes
        self.version = version      # node_table_index tag (hygiene only)
        # residency-map facts (solver/xferobs.py): age + hit count make
        # stale-version occupancy and eviction pressure first-class
        self.created_at = time.time()
        self.hits = 0
        self.shard = shard          # holding device id (per-shard pool)


class _ChainEntry:
    __slots__ = ("buf", "host", "nbytes", "version", "base_version",
                 "deltas_applied", "created_at", "hits")

    def __init__(self, buf, host: np.ndarray, nbytes: int,
                 version: Optional[int]):
        self.buf = buf              # device buffer at ``version``
        self.host = host            # frozen host shadow (diff base)
        self.nbytes = nbytes
        self.version = version      # store index the buffer is AT --
        # load-bearing here, unlike _Entry's hygiene tag: the journal
        # coverage check gates delta admission on it
        self.base_version = version  # version of the last wholesale put
        self.deltas_applied = 0      # scatters since the wholesale put
        self.created_at = time.time()
        self.hits = 0


def _max_entries() -> int:
    try:
        return max(1, int(os.environ.get(
            "NOMAD_TPU_CONST_CACHE_ENTRIES", "64")))
    except ValueError:
        return 64


def _max_bytes() -> int:
    try:
        return max(1, int(float(os.environ.get(
            "NOMAD_TPU_CONST_CACHE_MB", "256")) * 1024 * 1024))
    except ValueError:
        return 256 * 1024 * 1024


def _min_bytes() -> int:
    try:
        return int(os.environ.get("NOMAD_TPU_CONST_CACHE_MIN_BYTES",
                                  "4096"))
    except ValueError:
        return 4096


def _max_shard_entries() -> int:
    try:
        return max(1, int(os.environ.get(
            "NOMAD_TPU_CONST_CACHE_SHARD_ENTRIES", "512")))
    except ValueError:
        return 512


def _chain_max_bytes() -> int:
    try:
        return max(1, int(float(os.environ.get(
            "NOMAD_TPU_DELTA_CHAIN_MB", "64")) * 1024 * 1024))
    except ValueError:
        return 64 * 1024 * 1024


def _delta_max_frac() -> float:
    try:
        return float(os.environ.get("NOMAD_TPU_DELTA_MAX_FRAC", "0.25"))
    except ValueError:
        return 0.25


def _fingerprint(arr: np.ndarray) -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(str((arr.dtype.str, arr.shape)).encode())
    h.update(np.ascontiguousarray(arr).data)
    return h.digest()


def _bitwise_changed(old: np.ndarray, new: np.ndarray) -> np.ndarray:
    """Flat indices of elements whose BYTES differ. Not ``!=``: -0.0
    vs +0.0 compare equal and NaN never equals itself, but the kill
    switch promises BITWISE parity with the wholesale path, so the
    diff must see exactly what ``device_put`` would have shipped."""
    it = old.dtype.itemsize
    a = old.reshape((-1,)).view(np.uint8).reshape(-1, it)
    b = new.reshape((-1,)).view(np.uint8).reshape(-1, it)
    return np.flatnonzero((a != b).any(axis=1))


def _pad_updates(idx: np.ndarray, vals: np.ndarray
                 ) -> Tuple[np.ndarray, np.ndarray, int]:
    """Pad (idx, vals) up to the next power-of-two bucket (min 8) so
    the jitted scatter compiles once per bucket instead of once per
    exact nnz (jitcheck's steady-state-retrace gate). Padding repeats
    slot 0: duplicate scatter writes of the SAME value are
    deterministic under XLA, so the padded program is bit-for-bit the
    unpadded one."""
    n = int(idx.size)
    bucket = max(8, 1 << (n - 1).bit_length())
    pad = bucket - n
    idx_p = np.concatenate([idx, np.full(pad, idx[0], idx.dtype)])
    vals_p = np.concatenate([vals, np.repeat(vals[:1], pad)])
    return np.ascontiguousarray(idx_p, dtype=np.int32), \
        np.ascontiguousarray(vals_p), bucket


_SCATTER_FLIGHT = threading.Lock()


@functools.lru_cache(maxsize=None)
def _delta_scatter_program(shape: tuple, dtype_str: str, n_upd: int):
    """One jitted delta-scatter program per (table shape, dtype,
    update-count bucket) -- the device-side half of ISSUE 20's delta
    streaming. Flat-index formulation: the resident buffer is a
    single-device array here, so the reshape is free and the program
    is a single 1D scatter. No donation: the base buffer may still be
    referenced by the content cache or an in-flight dispatch. The mesh
    twin (parallel/mesh.py mesh_delta_scatter_fn) uses unraveled
    coordinates so the sharded operand never reshapes across shards."""
    import jax

    del dtype_str, n_upd  # dtypes/shapes ride the traced args; they
    #                       key the cache (same program per bucket)

    def _apply(buf, idx, vals):
        return buf.reshape((-1,)).at[idx].set(vals).reshape(shape)

    return jax.jit(_apply)


def _scatter_single(buf, shape, dtype_str, idx_p, vals_p):
    """Default (single-device) scatter applier for ``chain_apply``:
    ship the padded (idx, vals) payload, run the bucketed program.
    The explicit device_put IS the delta payload crossing the wire."""
    import jax

    with _SCATTER_FLIGHT:
        # single-flight the factory: lru_cache alone lets two pipelined
        # generations race one cold bucket into a double trace/compile
        prog = _delta_scatter_program(shape, dtype_str, int(idx_p.size))
    put_idx, put_vals = jax.device_put([idx_p, vals_p])
    return prog(buf, put_idx, put_vals)


def _evict_chain_over_bounds_locked() -> None:
    # the chain pool is slot-keyed (bounded by the dispatch-tree
    # shapes in flight), so a bytes bound suffices; entries evict LRU
    # and the next sight of that slot re-installs wholesale
    max_b = _chain_max_bytes()
    while _CHAIN and _STATS["chain_resident_bytes"] > max_b:
        _, ent = _CHAIN.popitem(last=False)
        _STATS["chain_resident_bytes"] -= ent.nbytes
        _STATS["evictions"] += 1


def chain_apply(key: tuple, arr: np.ndarray, store, token: Optional[int],
                tag: str, put_fn, scatter=None, idx_width: int = 4,
                copy_shadow: bool = False, fp: Optional[bytes] = None):
    """Version-chain transfer of ONE array (ISSUE 20): reuse or
    delta-update the device buffer this slot shipped last generation
    instead of re-shipping the table. Returns
    ``(buffer, bytes_shipped, outcome)`` with outcome one of:

    - ``reuse``: bitwise-identical content -- zero bytes shipped;
    - ``promote``: journal-covered span -- only the changed elements
      ship (idx+vals, power-of-two bucketed) and a jitted scatter
      applies them on device; the entry advances v_old -> ``token``;
    - ``install``: first sight of this slot (wholesale, not a
      fallback -- there was nothing to delta against);
    - ``gap``: journal overflow / uncoverable span / shape change ->
      wholesale (counted in ``delta_fallbacks``);
    - ``size``: diff payload >= NOMAD_TPU_DELTA_MAX_FRAC of the table
      -> wholesale (counted; also self-corrects a slot whose content
      ping-pongs between unrelated job groups).

    The admission gate is the PR-6 alloc-delta journal:
    ``store.alloc_deltas_since(entry.version, upto=token)`` must report
    the span covered, else the resident buffer is too old to trust.
    The update itself is the authoritative bitwise host diff (frozen
    shadow vs fresh transport output): under the per-eval fit-order
    shuffle (scheduler/util.py shuffled_order) journal rows do not map
    to stable device rows, so the journal gates and scopes
    (journal_touched_nodes) while the diff translates -- the scatter
    can never be wrong, only skipped.

    Locking: NEVER call this under ``_LOCK``. ``alloc_deltas_since``
    takes the store lock, which nests OUTSIDE ``_LOCK`` (store write
    hooks call note_table_write under it) -- so the entry is claimed
    (popped) under ``_LOCK``, evaluated here, and reinstalled under
    ``_LOCK``; a concurrent claimant of the same slot simply installs
    wholesale and the last writer wins.

    ``put_fn(arr) -> buffer`` performs the wholesale upload;
    ``scatter(buf, shape, dtype_str, idx_p, vals_p) -> buffer``
    overrides the single-device applier (the mesh route passes a
    parallel/mesh.py closure so the sharded put discipline holds), with
    ``idx_width`` its per-update index bytes (4 * ndim for unraveled
    mesh coordinates). ``copy_shadow`` copies ``arr`` before freezing
    -- required when the caller's array is arena-backed (mesh fuse
    buffers) rather than a fresh transport output."""
    from ..server.telemetry import metrics
    from .. import jitcheck, statecheck
    from . import xferobs

    nbytes = int(arr.nbytes)
    if copy_shadow:
        shadow = np.array(arr, copy=True)
    else:
        shadow = arr
    # frozen-memo invariant (ISSUE 10): the shadow IS a promise about
    # the resident buffer's content -- freeze before it enters _CHAIN
    shadow.setflags(write=False)
    with _LOCK:
        ce = _CHAIN.pop(key, None)
        if ce is not None:
            _STATS["chain_resident_bytes"] -= ce.nbytes

    outcome = "install"
    payload = 0
    buf = None
    if ce is not None:
        covered = False
        pairs: list = []
        if (store is not None and token is not None
                and ce.version is not None):
            try:
                covered, pairs = store.alloc_deltas_since(
                    ce.version, upto=token)
            except Exception:
                covered = False
        if not covered or ce.nbytes != nbytes \
                or ce.host.dtype != shadow.dtype:
            outcome = "gap"
        else:
            if pairs:
                from ..tensor.pack import journal_touched_nodes
                with _LOCK:
                    _STATS["delta_touched_nodes_last"] = len(
                        journal_touched_nodes(pairs))
            idx = _bitwise_changed(ce.host, shadow)
            if idx.size == 0:
                outcome = "reuse"
                buf = ce.buf
            elif shadow.size >= (1 << 31):
                outcome = "gap"   # int32 scatter indices can't address it
            else:
                idx_p, vals_p, bucket = _pad_updates(
                    idx, shadow.reshape((-1,))[idx])
                payload = bucket * (idx_width + shadow.dtype.itemsize)
                if payload >= _delta_max_frac() * nbytes:
                    outcome = "size"
                    payload = 0
                else:
                    outcome = "promote"
                    apply_fn = scatter if scatter is not None \
                        else _scatter_single
                    buf = apply_fn(ce.buf, shadow.shape,
                                   shadow.dtype.str, idx_p, vals_p)
    if buf is None:                       # install / gap / size
        buf = put_fn(shadow)
    shipped = payload if outcome in ("reuse", "promote") else nbytes

    if jitcheck._ACTIVE:
        # promoted content = base content + applied delta: re-register
        # the NEW content's fingerprint so the sampled re-hash gate
        # covers the shadow exactly as it covers wholesale uploads
        jitcheck.note_fingerprint(
            shadow, fp if fp is not None else _fingerprint(shadow))
    if statecheck._ACTIVE:
        statecheck.note_published(shadow, site="constcache.chain")
        if outcome in ("reuse", "promote"):
            # the served entry is AT the dispatch token by
            # construction -- statecheck's stale-memo gate proves it
            statecheck.note_memo_served("constcache_chain", token, token)

    with _LOCK:
        if outcome in ("reuse", "promote"):
            ne = ce
            ne.buf = buf
            ne.version = token
            ne.hits += 1
            if outcome == "promote":
                ne.host = shadow
                ne.deltas_applied += 1
        else:
            ne = _ChainEntry(buf, shadow, nbytes, token)
        if key in _CHAIN:
            # concurrent claimant reinstalled first; last writer wins
            prev = _CHAIN.pop(key)
            _STATS["chain_resident_bytes"] -= prev.nbytes
        _CHAIN[key] = ne
        _STATS["chain_resident_bytes"] += nbytes
        if outcome == "promote":
            _STATS["delta_promotions"] += 1
            _STATS["delta_bytes_total"] += payload
        elif outcome == "reuse":
            _STATS["delta_reuses"] += 1
        elif outcome != "install":
            _STATS["delta_fallbacks"] += 1
            _STATS["delta_%s_fallbacks" % outcome] += 1
        _evict_chain_over_bounds_locked()

    # ledger attribution outside _LOCK (same ordering discipline as
    # device_put_cached): a reused/promoted table is *resident* bytes,
    # its delta payload ships under the dedicated ``delta`` tree group,
    # wholesale outcomes ship under the table's own group
    if xferobs.enabled():
        if outcome in ("reuse", "promote"):
            xferobs.note_payload(tag, nbytes, resident=True)
            if payload:
                xferobs.note_payload("delta", payload)
        else:
            xferobs.note_payload(tag, nbytes)
    if outcome == "promote":
        metrics.incr("nomad.solver.delta_promotions")
        metrics.sample("nomad.solver.delta_bytes", float(payload))
    elif outcome == "reuse":
        metrics.incr("nomad.solver.delta_reuses")
    elif outcome != "install":
        metrics.incr("nomad.solver.delta_fallbacks")
    return buf, shipped, outcome


def device_put_cached(arrays: Sequence[np.ndarray],
                      version: Optional[int] = None,
                      cacheable: Optional[Sequence[bool]] = None,
                      tags: Optional[Sequence[str]] = None,
                      delta_src=None,
                      ) -> Tuple[List, int]:
    """Transfer ``arrays`` host->device, reusing pinned device buffers
    for repeated content. Returns (buffers, bytes_shipped). ``version``
    tags fresh entries with the node-table index they were uploaded
    under (hygiene eviction on table writes); ``cacheable`` masks
    per-array eligibility (the fused transport marks only const-tree
    buffers, so churning usage deltas never evict resident fleet
    tables); ``tags`` names each array's tree group for the transfer
    ledger (solver/xferobs.py) -- cache-hit bytes attribute as
    *resident*, everything else as *shipped*.

    ``delta_src`` is the ISSUE-20 delta-streaming hookup: a
    ``(store, token)`` pair -- the state store owning the alloc-delta
    journal and the dispatch's snapshot index. When set, arrays that
    miss the content cache route through the version chain
    (``chain_apply``): journal-covered generations ship only their
    bitwise diff and scatter it into the resident buffer on device,
    instead of re-uploading the table."""
    import jax

    from ..server.telemetry import metrics
    from . import xferobs

    def tag_of(i: int) -> str:
        return tags[i] if tags is not None else "untagged"

    arrays = [np.asarray(a) for a in arrays]
    from .. import jitcheck

    store = token = None
    if delta_src is not None:
        store, token = delta_src
        if token is None or not hasattr(store, "alloc_deltas_since"):
            store = token = None
    chain_on = store is not None

    min_b = _min_bytes()
    buffers: List = [None] * len(arrays)
    miss_idx: List[int] = []
    miss_fps: List[Optional[bytes]] = []
    chain_jobs: List[Tuple[int, tuple, Optional[bytes]]] = []
    occ: dict = {}
    shipped = 0
    hits = misses = saved = 0
    hit_idx: List[int] = []
    with _LOCK:
        for i, arr in enumerate(arrays):
            if arr.nbytes < min_b:
                miss_idx.append(i)
                miss_fps.append(None)           # shipped, never cached
                shipped += arr.nbytes
                continue
            fp = None
            if cacheable is None or cacheable[i]:
                fp = _fingerprint(arr)
                # frozen-memo invariant (ISSUE 10): the fingerprint IS
                # a promise about this array's content -- freeze the
                # source so a write after fingerprinting raises instead
                # of desynchronizing host intent from the resident
                # buffer. Sources here are always the fused transport's
                # fresh np.stack / compact-pack outputs, never caller
                # state.
                arr.setflags(write=False)
                if jitcheck._ACTIVE:
                    jitcheck.note_fingerprint(arr, fp)
                ent = _CACHE.get(fp)
                if ent is not None:
                    _CACHE.move_to_end(fp)
                    ent.hits += 1
                    buffers[i] = ent.buf
                    hits += 1
                    saved += ent.nbytes
                    hit_idx.append(i)
                    continue
                misses += 1
            if chain_on:
                # slot key: tree group + dtype/shape + occurrence index
                # within this call -- stable across generations because
                # the fused transports emit their trees in fixed order
                sig = (tag_of(i), arr.dtype.str, arr.shape)
                k = occ.get(sig, 0)
                occ[sig] = k + 1
                chain_jobs.append((i, sig + (k,), fp))
            else:
                miss_idx.append(i)
                miss_fps.append(fp)
                shipped += arr.nbytes
    if miss_idx:
        puts = jax.device_put([arrays[i] for i in miss_idx])
        with _LOCK:
            for j, i in enumerate(miss_idx):
                buffers[i] = puts[j]
                fp = miss_fps[j]
                if fp is None:
                    continue
                _CACHE[fp] = _Entry(puts[j], arrays[i].nbytes, version)
                _STATS["resident_bytes"] += arrays[i].nbytes
            _evict_over_bounds_locked()
    if chain_jobs:
        # version-chain transfers, each claimed/evaluated/reinstalled
        # by chain_apply OUTSIDE _LOCK (alloc_deltas_since takes the
        # store lock, which nests outside _LOCK)
        cache_adds: List[Tuple[int, bytes]] = []
        for (i, key, fp) in chain_jobs:
            buf, ship_i, outcome = chain_apply(
                key, arrays[i], store, token, tag_of(i),
                put_fn=jax.device_put, fp=fp)
            buffers[i] = buf
            shipped += ship_i
            if outcome in ("reuse", "promote"):
                saved += arrays[i].nbytes - ship_i
            if fp is not None:
                cache_adds.append((i, fp))
        if cache_adds:
            # same content-key discipline as wholesale misses: the
            # promoted (or installed) buffer enters the content cache
            # under the NEW content's fingerprint
            with _LOCK:
                for (i, fp) in cache_adds:
                    if fp not in _CACHE:
                        _CACHE[fp] = _Entry(buffers[i],
                                            arrays[i].nbytes, version)
                        _STATS["resident_bytes"] += arrays[i].nbytes
                _evict_over_bounds_locked()
    with _LOCK:
        _STATS["hits"] += hits
        _STATS["misses"] += misses
        _STATS["bytes_shipped_total"] += shipped
        _STATS["bytes_saved_total"] += saved
        resident_now = _STATS["resident_bytes"]
    # ledger attribution outside _LOCK (xferobs has its own lock; keep
    # the order leaf-like for lockcheck): hit bytes are *resident*,
    # everything in miss_idx actually crossed the wire
    for i in hit_idx:
        xferobs.note_payload(tag_of(i), arrays[i].nbytes, resident=True)
    for i in miss_idx:
        xferobs.note_payload(tag_of(i), arrays[i].nbytes)
    xferobs.note_resident_level(resident_now)
    if hits:
        metrics.incr("nomad.solver.const_cache_hit", hits)
    if misses:
        metrics.incr("nomad.solver.const_cache_miss", misses)
    note_dispatch_bytes(shipped)
    # per-eval attribution: a cold-transfer dispatch explains its own
    # latency spike (the group ctx fans this out to every fused lane)
    from ..server.tracing import tracer
    tracer.event("solver.constcache", hits=hits, misses=misses,
                 bytes_shipped=shipped, bytes_saved=saved)
    return buffers, shipped


def _evict_over_bounds_locked() -> None:
    max_e, max_b = _max_entries(), _max_bytes()
    while _CACHE and (len(_CACHE) > max_e
                      or _STATS["resident_bytes"] > max_b):
        _, ent = _CACHE.popitem(last=False)
        _STATS["resident_bytes"] -= ent.nbytes
        _STATS["evictions"] += 1


def _evict_shard_over_bounds_locked() -> None:
    # the per-shard pool shares the MB budget knob but carries its own
    # entries bound: one const tree is ~20 leaves x n_devices shards,
    # so the unsharded entries knob (64) would thrash immediately
    max_e, max_b = _max_shard_entries(), _max_bytes()
    while _SHARD_CACHE and (len(_SHARD_CACHE) > max_e
                            or _STATS["shard_resident_bytes"] > max_b):
        _, ent = _SHARD_CACHE.popitem(last=False)
        _STATS["shard_resident_bytes"] -= ent.nbytes
        _STATS["evictions"] += 1


def device_put_sharded_cached(arrays: Sequence[np.ndarray],
                              shardings: Sequence,
                              group: str = "mesh_const",
                              version: Optional[int] = None,
                              fallback_put=None,
                              ) -> Tuple[List, int]:
    """Per-shard content-addressed transfer (ISSUE 19): split each
    array into the shard slices its sharding (built by
    parallel/mesh.py -- this module never constructs one) assigns per
    device, fingerprint each slice, and reuse pinned single-device
    buffers for unchanged shards.  Cache keys are (content key, shard
    device): the same BLAKE2b content addressing as the unsharded
    cache suffixed with the holding device's id, so a node-table write
    re-uploads ONLY the shards whose slice content actually changed --
    the unchanged majority of the fleet stays resident (groundwork for
    ROADMAP-3 delta streaming).  The global jax.Array is assembled
    from the per-device buffers with
    ``jax.make_array_from_single_device_arrays`` (no re-layout, no
    wire traffic).  Returns (buffers, bytes_shipped).

    Accounting matches device_put_cached -- hit bytes are *resident*
    payload, misses are shipped payload + dispatch bytes -- plus one
    per-shard declared/actual row per device in the transfer ledger
    (xferobs.note_shard_bytes): the production-path source of the
    ``per_shard`` rows shardcheck otherwise only writes while enabled.
    ``fallback_put(arr, sharding)`` performs the whole-array sharded
    put for small arrays; callers pass a parallel/mesh.py closure so
    the no-implicit-put lint discipline holds."""
    import jax

    from ..server.telemetry import metrics
    from . import xferobs

    if fallback_put is None:
        raise TypeError("device_put_sharded_cached needs a "
                        "fallback_put(arr, sharding) closure from "
                        "parallel/mesh.py")
    from .. import jitcheck

    arrays = [np.asarray(a) for a in arrays]
    min_b = _min_bytes()
    buffers: List = [None] * len(arrays)
    shipped = 0
    hits = misses = saved = 0
    hit_bytes = 0
    miss_puts: List[Tuple[int, int, object, np.ndarray, bytes]] = []
    per_arr_parts: dict = {}
    with _LOCK:
        for i, (arr, sharding) in enumerate(zip(arrays, shardings)):
            if arr.nbytes < min_b:
                continue                     # fallback path, below
            idx_map = sharding.addressable_devices_indices_map(arr.shape)
            devs = sorted(idx_map, key=lambda d: d.id)
            parts: List = [None] * len(devs)
            fp_by_slice: dict = {}
            for j, dev in enumerate(devs):
                idx = idx_map[dev]
                slice_key = tuple(
                    (s.start, s.stop, s.step) if isinstance(s, slice)
                    else s for s in (idx or ()))
                fp = fp_by_slice.get(slice_key)
                part = None
                if fp is None:
                    part = np.ascontiguousarray(arr[idx])
                    part.setflags(write=False)
                    fp = _fingerprint(part)
                    fp_by_slice[slice_key] = fp
                    if jitcheck._ACTIVE:
                        jitcheck.note_fingerprint(part, fp)
                key = fp + dev.id.to_bytes(4, "little")
                ent = _SHARD_CACHE.get(key)
                if ent is not None:
                    _SHARD_CACHE.move_to_end(key)
                    ent.hits += 1
                    parts[j] = ent.buf
                    hits += 1
                    saved += ent.nbytes
                    hit_bytes += ent.nbytes
                else:
                    if part is None:
                        part = np.ascontiguousarray(arr[idx])
                        part.setflags(write=False)
                    miss_puts.append((i, j, dev, part, key))
                    misses += 1
                    shipped += part.nbytes
            per_arr_parts[i] = (sharding, parts)
    # host->device uploads outside _LOCK (device_put can take long;
    # the fused path batches its misses the same way)
    if miss_puts:
        put_bufs = jax.device_put([p for (_i, _j, _d, p, _k)
                                   in miss_puts],
                                  [d for (_i, _j, d, _p, _k)
                                   in miss_puts])
        with _LOCK:
            for (i, j, dev, part, key), buf in zip(miss_puts, put_bufs):
                per_arr_parts[i][1][j] = buf
                _SHARD_CACHE[key] = _Entry(buf, part.nbytes, version,
                                           shard=int(dev.id))
                _STATS["shard_resident_bytes"] += part.nbytes
            _evict_shard_over_bounds_locked()
    # assemble the sharded jax.Arrays from the per-device buffers
    for i, (sharding, parts) in per_arr_parts.items():
        buffers[i] = jax.make_array_from_single_device_arrays(
            arrays[i].shape, sharding, parts)
    # fallback: small arrays ship whole via the caller's
    # parallel/mesh.py put closure
    fresh_idx = [i for i, b in enumerate(buffers)
                 if b is None]
    for i in fresh_idx:
        buffers[i] = fallback_put(arrays[i], shardings[i])
        shipped += arrays[i].nbytes
    with _LOCK:
        _STATS["hits"] += hits
        _STATS["misses"] += misses
        _STATS["bytes_shipped_total"] += shipped
        _STATS["bytes_saved_total"] += saved
        if _STATS["shard_resident_bytes"] > _STATS["shard_resident_hwm"]:
            _STATS["shard_resident_hwm"] = _STATS["shard_resident_bytes"]
        shard_resident_now = _STATS["shard_resident_bytes"]
        resident_now = _STATS["resident_bytes"] + shard_resident_now
    # ledger attribution outside _LOCK (same ordering discipline as
    # device_put_cached): hit bytes are resident, the rest shipped
    if xferobs.enabled():
        if hit_bytes:
            xferobs.note_payload(group, hit_bytes, resident=True)
        fresh_bytes = sum(arrays[i].nbytes for i in fresh_idx)
        miss_bytes = sum(p.nbytes for (_i, _j, _d, p, _k) in miss_puts)
        if fresh_bytes or miss_bytes:
            xferobs.note_payload(group, fresh_bytes + miss_bytes)
        # per-shard declared/actual rows: declared = the spec's shard
        # bytes, actual = the bytes each device really holds -- equal
        # by construction here (the put IS by the declared sharding)
        per_dev: dict = {}
        for i, (sharding, parts) in per_arr_parts.items():
            idx_map = sharding.addressable_devices_indices_map(
                arrays[i].shape)
            for dev, part in zip(sorted(idx_map, key=lambda d: d.id),
                                 parts):
                per_dev[dev.id] = per_dev.get(dev.id, 0) + part.nbytes
        for i in fresh_idx:
            sharding = shardings[i]
            idx_map = sharding.addressable_devices_indices_map(
                arrays[i].shape)
            shard_b = int(np.prod(
                sharding.shard_shape(arrays[i].shape),
                dtype=np.int64) * arrays[i].dtype.itemsize)
            for dev in idx_map:
                per_dev[dev.id] = per_dev.get(dev.id, 0) + shard_b
        for dev_id in sorted(per_dev):
            xferobs.note_shard_bytes(group, f"d{dev_id}",
                                     per_dev[dev_id], per_dev[dev_id])
        xferobs.note_resident_level(resident_now)
    metrics.sample("nomad.solver.const_cache_shard_resident_bytes",
                   float(shard_resident_now))
    metrics.sample("nomad.solver.const_cache_shard_resident_hwm",
                   float(_STATS["shard_resident_hwm"]))
    if hits:
        metrics.incr("nomad.solver.const_cache_hit", hits)
    if misses:
        metrics.incr("nomad.solver.const_cache_miss", misses)
    note_dispatch_bytes(shipped)
    from ..server.tracing import tracer
    tracer.event("solver.constcache_sharded", hits=hits, misses=misses,
                 bytes_shipped=shipped, bytes_saved=saved)
    return buffers, shipped


def note_dispatch_bytes(n: int) -> None:
    """Record one dispatch's actual host->device payload (bytes that hit
    the wire AFTER cache hits are subtracted). Shared by the fused,
    wave and mesh-sharded transports so the metric means one thing.
    Every increment is mirrored into the transfer ledger
    (solver/xferobs.py note_shipped) as the reconciliation base its
    byte-parity gate compares the tagged decomposition against."""
    from ..server.telemetry import metrics
    from . import xferobs

    metrics.sample("nomad.solver.dispatch_bytes", float(n))
    metrics.incr("nomad.solver.dispatch_bytes_total", int(n))
    xferobs.note_shipped(int(n))


def residency() -> List[dict]:
    """Device-residency map (solver/xferobs.py): one row per pinned
    entry -- bytes, upload version, age, hit count -- so stale-version
    occupancy and eviction pressure are readable, not inferred."""
    now = time.time()
    with _LOCK:
        rows = [{"id": fp.hex()[:12], "bytes": ent.nbytes,
                 "version": ent.version,
                 "age_s": round(now - ent.created_at, 1),
                 "hits": ent.hits}
                for fp, ent in _CACHE.items()]
        rows.extend(
            {"id": key.hex()[:12], "bytes": ent.nbytes,
             "version": ent.version,
             "age_s": round(now - ent.created_at, 1),
             "hits": ent.hits, "shard": ent.shard}
            for key, ent in _SHARD_CACHE.items())
        # version-chain entries (ISSUE 20): slot-keyed rows showing the
        # base (last wholesale) version and how many deltas have been
        # applied on device since -- the residency map's proof that
        # tables are being advanced in place, not re-shipped
        rows.extend(
            {"id": "chain:%s/%s/%s#%d" % (key[0], key[1],
                                          "x".join(map(str, key[2])),
                                          key[3]),
             "bytes": ent.nbytes, "version": ent.version,
             "base_version": ent.base_version,
             "deltas_applied": ent.deltas_applied,
             "age_s": round(now - ent.created_at, 1),
             "hits": ent.hits}
            for key, ent in _CHAIN.items())
        return rows


def note_table_write(tables, table_index: int, delta=None) -> None:
    """Unified store-write hook (state/store.py _notify_write_hooks):
    every cache layer receives the same (tables, index, delta)
    notification. The const cache only reacts to fleet-table writes;
    the alloc delta context is for the incremental memo layers."""
    if "nodes" in tables:
        note_node_table_write(table_index)


def note_node_table_write(table_index: int) -> None:
    """Node-table write hook (state/store.py): drop buffers uploaded
    under an older fleet version. Correctness never depends on this
    (content addressing self-validates); it keeps dead fleet versions
    from squatting on device memory until LRU pressure finds them."""
    if not _CACHE and not _SHARD_CACHE:
        return
    # the version chain deliberately survives table writes: advancing a
    # stale-version entry by the journal span is the whole point, and
    # the alloc_deltas_since coverage gate (not this hygiene hook)
    # decides whether an old entry is still delta-reachable
    with _LOCK:
        stale = [fp for fp, ent in _CACHE.items()
                 if ent.version is not None and ent.version < table_index]
        for fp in stale:
            ent = _CACHE.pop(fp)
            _STATS["resident_bytes"] -= ent.nbytes
        # per-shard pool: same hygiene -- shards whose content DID
        # survive the write re-enter on the next dispatch as fresh
        # entries keyed by the same (unchanged) content
        stale_s = [k for k, ent in _SHARD_CACHE.items()
                   if ent.version is not None
                   and ent.version < table_index]
        for k in stale_s:
            ent = _SHARD_CACHE.pop(k)
            _STATS["shard_resident_bytes"] -= ent.nbytes
        if stale or stale_s:
            _STATS["invalidations"] += 1
        resident_now = (_STATS["resident_bytes"]
                        + _STATS["shard_resident_bytes"])
    if stale or stale_s:
        from . import xferobs
        xferobs.note_resident_level(resident_now)


def invalidate_all(reason: str = "") -> None:
    """Drop every resident buffer. Wired to breaker trips/recoveries
    (solver/guard.py): buffers that crossed a wedged-then-recovered
    transport are not trusted, and a fresh upload is cheap next to the
    outage that just ended."""
    with _LOCK:
        had = bool(_CACHE) or bool(_SHARD_CACHE) or bool(_CHAIN)
        _CACHE.clear()
        _SHARD_CACHE.clear()
        _CHAIN.clear()
        _STATS["resident_bytes"] = 0
        _STATS["shard_resident_bytes"] = 0
        _STATS["chain_resident_bytes"] = 0
        if had:
            _STATS["invalidations"] += 1
    if had:
        from . import xferobs
        xferobs.note_resident_level(0)
    if had and reason:
        from ..server.logbroker import log as _log
        _log("info", "solver",
             f"const cache invalidated ({reason}); fleet tables "
             "re-upload on next dispatch")


def stats() -> dict:
    with _LOCK:
        out = dict(_STATS)
        out["entries"] = len(_CACHE)
        out["shard_entries"] = len(_SHARD_CACHE)
        out["chain_entries"] = len(_CHAIN)
    return out


def _reset_for_tests() -> None:
    with _LOCK:
        _CACHE.clear()
        _SHARD_CACHE.clear()
        _CHAIN.clear()
        _STATS.update(hits=0, misses=0, bytes_shipped_total=0,
                      bytes_saved_total=0, invalidations=0, evictions=0,
                      resident_bytes=0, shard_resident_bytes=0,
                      shard_resident_hwm=0, delta_promotions=0,
                      delta_reuses=0, delta_fallbacks=0,
                      delta_gap_fallbacks=0, delta_size_fallbacks=0,
                      delta_bytes_total=0, delta_touched_nodes_last=0,
                      chain_resident_bytes=0)
