"""Eval-batching coordinator: fuse many evals' placements into one dispatch.

This is the production form of SURVEY.md section 7 hard part 5: a 10K-node
matrix is tiny, so the TPU win comes from coalescing many evaluations per
device dispatch. The reference's contract is one eval per Scheduler.Process
call (scheduler/scheduler.go:59-68) driven by one worker each
(nomad/worker.go:397); here K workers' schedulers run concurrently and
rendezvous at the solve point:

  - each eval's GenericScheduler runs UNCHANGED on its own thread (retries,
    blocked evals, multi-TG sequencing, plan submission all keep reference
    semantics);
  - when a scheduler reaches a dense solve it submits its PackedLane to the
    barrier and blocks;
  - when every active thread is either blocked at the barrier or finished,
    the coordinator fuses compatible lanes (equal static shapes) into one
    (E, ...) solve_eval_batch dispatch -- vmapped over the eval axis, and
    sharded over an (evals, nodes) device mesh when more than one chip is
    attached (parallel/mesh.py) -- then wakes each thread with its slice.

Evals never see each other's in-flight placements; the serialized plan
applier resolves conflicts exactly as nomad/plan_apply.go does (optimistic
concurrency, SURVEY.md section 2.6.1).
"""
from __future__ import annotations

import functools
import os
import queue
import threading
import time
from collections import OrderedDict
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..server.telemetry import metrics
from ..server.tracing import tracer
from . import stages, xferobs
from .service import PackedLane

# Pad the fused eval axis to these sizes so XLA compiles one program per
# bucket, not one per batch size.
E_BUCKETS = (1, 2, 4, 8, 16, 32)

# Safety valve: if a straggler thread neither finishes nor reaches the
# barrier within this window (a bug, not a normal state), dispatch without
# it rather than wedge every blocked eval.
BARRIER_TIMEOUT_S = 10.0


# Max fused dispatches in flight across the process: every barrier
# dispatches through the async pipeline, so one generation's host
# packing and transfer overlap another's device execution.
DISPATCH_DEPTH = 2


class _DispatchPipeline:
    """Process-global async dispatch executor: a FIFO intake thread
    starts one in-flight thread per job, never more than ``depth``
    concurrently. Jobs from different barriers (and different
    BatchWorkers) share the bound, so the device never sees more than
    ``depth`` fused dispatches at once while host-side pack/fuse of the
    next generation proceeds under an earlier one's execution."""

    def __init__(self, depth: int):
        self.depth = depth
        self._sem = threading.Semaphore(depth)
        self._q: "queue.Queue" = queue.Queue()
        self._in_flight = 0
        self._staged = 0
        self._lock = threading.Lock()
        self._thread = threading.Thread(
            target=self._intake, daemon=True,
            name="solver-dispatch-pipeline")
        self._thread.start()

    def submit(self, job, prepare=None) -> None:
        """``prepare`` (optional) is the job's host-side staging --
        the arena fill for its fused generation. The intake thread runs
        it BEFORE waiting for a dispatch slot, so generation g+1's lane
        stacking overlaps generation g's device execution instead of
        consuming a depth slot (the pack -> dispatch overlap)."""
        self._q.put((job, prepare))

    def stop(self) -> None:
        self._q.put(None)

    def in_flight(self) -> int:
        with self._lock:
            return self._in_flight

    def staged(self) -> int:
        with self._lock:
            return self._staged

    def _intake(self) -> None:
        while True:
            item = self._q.get()
            if item is None:
                return
            job, prepare = item
            if prepare is not None:
                try:
                    prepare()
                    with self._lock:
                        self._staged += 1
                except Exception:  # noqa: BLE001 -- staging is best
                    import traceback  # effort; the job re-derives (and
                    traceback.print_exc()  # fails under its watchdog)
            # nomadlint: waive=bare-acquire -- the depth slot is
            # deliberately released by the runner thread in _run_job's
            # finally; a try/finally here would double-release it
            self._sem.acquire()
            with self._lock:
                self._in_flight += 1
            threading.Thread(target=self._run_job, args=(job,),
                             daemon=True,
                             name="solver-dispatch-inflight").start()

    def _run_job(self, job) -> None:
        try:
            job()
        except Exception:  # noqa: BLE001 -- jobs guarantee their own
            import traceback  # waiter wakeups; this is belt-and-braces
            traceback.print_exc()
        finally:
            with self._lock:
                self._in_flight -= 1
            self._sem.release()


_PIPELINE: Optional[_DispatchPipeline] = None
_PIPELINE_LOCK = threading.Lock()


def _get_pipeline(depth: int) -> _DispatchPipeline:
    global _PIPELINE
    with _PIPELINE_LOCK:
        if _PIPELINE is None or _PIPELINE.depth != depth:
            if _PIPELINE is not None:
                _PIPELINE.stop()
            _PIPELINE = _DispatchPipeline(depth)
        return _PIPELINE


def pipeline_state() -> dict:
    """Pipeline snapshot for guard.state() / status surfaces."""
    with _PIPELINE_LOCK:
        pipe = _PIPELINE
    return {
        "depth": DISPATCH_DEPTH,
        "in_flight": pipe.in_flight() if pipe is not None else 0,
        "staged_total": pipe.staged() if pipe is not None else 0,
        "active": pipe is not None,
    }


def _e_bucket(e: int) -> int:
    for b in E_BUCKETS:
        if e <= b:
            return b
    return int(2 ** np.ceil(np.log2(e)))


# ---------------------------------------------------------------------------
# In-place fused-stack arena.
#
# Every fused generation used to np.empty + copy a fresh (E, ...) buffer per
# tree field (~tens of MB at the headline shape) just to throw it away after
# the dispatch. Consecutive generations overwhelmingly share a fuse_key and
# (E, P, A) shape -- the same jobs stream through the same barrier -- so the
# stacked buffers are pooled: a generation checks an entry out, fills lanes
# IN PLACE and returns it after the dispatch. Padding rows (the e_pad >
# e_real replicas of lane 0) only ever need to hold a VALID lane (their
# results are discarded and batch.active masks them inert), so once an entry
# has been fully filled its padding rows never need rewriting -- any prior
# generation's lane data is a valid inert lane.
#
# The pool is a pool (not one buffer) because the pipelined barrier fills
# generation g+1 while g's dispatch is still in flight. Bounds:
# NOMAD_TPU_PACK_ARENA_ENTRIES / NOMAD_TPU_PACK_ARENA_MB.


def _arena_max_entries() -> int:
    try:
        return max(1, int(os.environ.get(
            "NOMAD_TPU_PACK_ARENA_ENTRIES", "8")))
    except ValueError:
        return 8


def _arena_max_bytes() -> int:
    try:
        return max(1, int(float(os.environ.get(
            "NOMAD_TPU_PACK_ARENA_MB", "512")) * 1024 * 1024))
    except ValueError:
        return 512 * 1024 * 1024


class _ArenaEntry:
    __slots__ = ("key", "trees", "nbytes", "pad_valid")

    def __init__(self, key, trees, nbytes: int):
        self.key = key
        self.trees = trees          # tree name -> list of np arrays
        self.nbytes = nbytes
        self.pad_valid = False      # padding rows hold valid lane data


class _StackArena:
    """Bounded pool of reusable stacked host buffers, keyed by fused
    group shape. Thread-safe: concurrent generations check out distinct
    entries; an exhausted pool allocates fresh (never blocks)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._free: "OrderedDict[int, _ArenaEntry]" = OrderedDict()
        self._seq = 0
        self._free_bytes = 0
        self._in_use = 0
        self._stats = {"reuses": 0, "allocs": 0, "evictions": 0,
                       "pad_fills_skipped": 0}

    @staticmethod
    def _set_writeable(ent, flag: bool) -> None:
        """Pooled buffers are frozen while they sit in the free list
        (the frozen-memo invariant, ISSUE 10): a generation writing
        into a buffer it already released -- while a reused checkout or
        an in-flight transfer may still read it -- raises instead of
        silently corrupting a lane."""
        for arrs in ent.trees.values():
            for a in arrs:
                a.setflags(write=flag)

    def acquire(self, key, specs):
        """specs: tree name -> list of (shape, dtype). Returns
        (entry, reused)."""
        with self._lock:
            for tok, ent in self._free.items():
                if ent.key == key and self._specs_match(ent, specs):
                    del self._free[tok]
                    self._free_bytes -= ent.nbytes
                    self._in_use += 1
                    self._stats["reuses"] += 1
                    self._set_writeable(ent, True)
                    return ent, True
        trees = {}
        nbytes = 0
        for name, fields in specs.items():
            arrs = []
            for shape, dtype in fields:
                a = np.empty(shape, dtype=dtype)
                nbytes += a.nbytes
                arrs.append(a)
            trees[name] = arrs
        ent = _ArenaEntry(key, trees, nbytes)
        with self._lock:
            self._stats["allocs"] += 1
            self._in_use += 1
        return ent, False

    @staticmethod
    def _specs_match(ent, specs) -> bool:
        for name, fields in specs.items():
            arrs = ent.trees.get(name)
            if arrs is None or len(arrs) != len(fields):
                return False
            for a, (shape, dtype) in zip(arrs, fields):
                if a.shape != shape or a.dtype != dtype:
                    return False
        return True

    def release(self, ent) -> None:
        with self._lock:
            self._in_use -= 1
            self._set_writeable(ent, False)
            self._seq += 1
            self._free[self._seq] = ent
            self._free_bytes += ent.nbytes
            max_e, max_b = _arena_max_entries(), _arena_max_bytes()
            while self._free and (len(self._free) > max_e
                                  or self._free_bytes > max_b):
                _, old = self._free.popitem(last=False)
                self._free_bytes -= old.nbytes
                self._stats["evictions"] += 1

    def note_pad_skip(self, n: int = 1) -> None:
        with self._lock:
            self._stats["pad_fills_skipped"] += n

    def clear(self, reason: str = "") -> None:
        with self._lock:
            self._free.clear()
            self._free_bytes = 0

    def state(self) -> dict:
        with self._lock:
            out = dict(self._stats)
            out["entries"] = len(self._free)
            out["in_use"] = self._in_use
            out["resident_bytes"] = self._free_bytes
        return out


_ARENA = _StackArena()


def arena_state() -> dict:
    """Arena snapshot for guard.state() / status surfaces (the
    constcache.stats() analog for host-side stacked buffers)."""
    return _ARENA.state()


def arena_clear(reason: str = "") -> None:
    """Drop pooled (free) buffers; wired beside the const-cache
    invalidation on breaker trip/recovery edges."""
    _ARENA.clear(reason)


def _pad_placement_axis(batch, p_pad: int):
    """Grow a lane's placement axis to p_pad with inert (active=False)
    steps so different-sized evals share one compiled program."""
    p = batch.ask_cpu.shape[0]
    if p == p_pad:
        return batch

    def grow(arr, fill=0):
        out = np.full((p_pad,) + arr.shape[1:], fill, dtype=arr.dtype)
        out[:p] = arr
        return out

    return type(batch)(
        ask_cpu=grow(batch.ask_cpu), ask_mem=grow(batch.ask_mem),
        ask_disk=grow(batch.ask_disk),
        n_dyn_ports=grow(batch.n_dyn_ports),
        has_static=grow(batch.has_static, False),
        limit=grow(batch.limit), count=grow(batch.count, 1),
        penalty_idx=grow(batch.penalty_idx, -1),
        active=grow(batch.active, False),
        # 0-size means "no core asks" (a static-shape branch): keep empty
        ask_cores=(batch.ask_cores if batch.ask_cores.shape[0] == 0
                   else grow(batch.ask_cores)))


class _FusedGroup:
    """One shape-compatible lane group, fully stacked and ready to
    dispatch: the unit the pack->dispatch overlap stages ahead of its
    generation's device slot."""

    __slots__ = ("idxs", "const", "init", "batch", "ptab", "pinit",
                 "A", "e_real", "e_pad", "p_pad", "wave", "spread_alg",
                 "dtype_name", "cache_version", "delta_src", "entry",
                 "arena_reused")

    def __init__(self, **kw):
        for k in self.__slots__:
            setattr(self, k, kw.get(k))


def _fuse_group(lanes: List[PackedLane], idxs: List[int], key: tuple,
                e_pad_hint: int) -> _FusedGroup:
    """Stack one group's lanes into arena-backed (E, ...) buffers,
    filling lanes in place and skipping padding rows that already hold
    valid lane data from a prior generation."""
    lane0 = lanes[idxs[0]]
    A = 1 if lane0.ptab is not None else 0
    e_real = len(idxs)
    e_pad = _e_bucket(e_real)
    wave = lane0.wavefront_ok()
    # a whole-axis group without preemption tables is pinned like a
    # wave group: the scan's trip count is an operand, so a padded step
    # is never run, and on one device the lanes run in turn
    # (binpack._make_fused_fn), so a padded lane is not either. On a
    # mesh (parallel/mesh.mesh_solve_fn) the lanes ride the sharded eval
    # axis up to the widest lane's steps: a padded lane is inert work
    # on a device that the tight bucket would have lent to the node
    # axis (PERF.md section 6, PR 30, has both on four chips). A
    # preempting group runs its lanes in turn on one device too, so
    # every lane count meets the one program its windowed twin warmed
    # (_warm_whole_axis_twin)
    whole_axis = not wave
    if e_pad_hint:
        e_pad = max(e_pad, _e_bucket(min(e_pad_hint, E_BUCKETS[-1])))
    # floor of 32: many lane sizes share one compiled variant (an
    # inert padded step costs ~us; a fresh XLA compile costs seconds)
    p_pad = max(32, _e_bucket(max(
        lanes[i].batch.ask_cpu.shape[0] for i in idxs)))
    if whole_axis:
        # one placement width a job: a retry of what a partial commit
        # left carries the group's count (service._limit keeps it too),
        # so it lands in its first attempt's program
        p_pad = max(p_pad, _e_bucket(max(
            int(np.max(lanes[i].batch.count)) for i in idxs)))
    # gauge, not sample_ms: this is a lane COUNT; recording it
    # through the millisecond sampler made dashboards read "lanes"
    # as a latency series
    metrics.sample("nomad.solver.batch_lanes", float(e_real))
    padded = {i: _pad_placement_axis(lanes[i].batch, p_pad)
              for i in idxs}

    srcs = {"const": lambda i: lanes[i].const,
            "init": lambda i: lanes[i].init,
            "batch": lambda i: padded[i]}
    if A > 0:
        srcs["ptab"] = lambda i: lanes[i].ptab
        srcs["pinit"] = lambda i: lanes[i].pinit
    specs = {}
    for name, src in srcs.items():
        first = src(idxs[0])
        specs[name] = [((e_pad,) + np.asarray(f).shape,
                        np.asarray(f).dtype) for f in first]
    entry, reused = _ARENA.acquire((key, e_pad, p_pad), specs)
    if reused:
        metrics.incr("nomad.solver.pack_arena_reuse")
    else:
        metrics.incr("nomad.solver.pack_arena_alloc")

    skip_pad = entry.pad_valid
    if skip_pad and e_pad > e_real:
        _ARENA.note_pad_skip()
    for name, src in srcs.items():
        dsts = entry.trees[name]
        for f_i in range(len(dsts)):
            dst = dsts[f_i]
            for j, li in enumerate(idxs):
                dst[j] = np.asarray(src(li)[f_i])
            if not skip_pad:
                # fresh buffer: padding rows need SOME valid lane; once
                # filled they stay valid forever (prior generations'
                # rows are real lanes, results discarded)
                for j in range(e_real, e_pad):
                    dst[j] = dst[0]
    entry.pad_valid = True

    const = type(lane0.const)(*entry.trees["const"])
    init = type(lane0.init)(*entry.trees["init"])
    batch = type(lane0.batch)(*entry.trees["batch"])
    # padding lanes (and stale rows from a wider prior generation) must
    # not place anything
    batch.active[e_real:] = False
    ptab = type(lane0.ptab)(*entry.trees["ptab"]) if A > 0 else None
    pinit = type(lane0.pinit)(*entry.trees["pinit"]) if A > 0 else None
    return _FusedGroup(
        idxs=list(idxs), const=const, init=init, batch=batch, ptab=ptab,
        pinit=pinit, A=A, e_real=e_real, e_pad=e_pad, p_pad=p_pad,
        wave=wave, spread_alg=lane0.spread_alg,
        dtype_name=lane0.dtype_name,
        cache_version=getattr(lane0, "table_version", None),
        delta_src=getattr(lane0, "delta_src", None),
        entry=entry, arena_reused=reused)


def fuse_lanes(lanes: List[PackedLane], e_pad_hint: int = 0
               ) -> List[_FusedGroup]:
    """Host-side half of fuse_and_solve: group lanes by static-shape
    signature and stack each group into arena buffers. No device work --
    safe to run while an earlier generation's dispatch is in flight
    (the pipeline's prepare stage)."""
    groups: Dict[tuple, List[int]] = {}
    # from the barrier this runs on the pipeline's intake thread,
    # outside the dispatch timer
    with metrics.measure("nomad.solver.fuse"), tracer.span("solver.fuse"):
        for i, lane in enumerate(lanes):
            groups.setdefault(lane.fuse_key(), []).append(i)
        return [_fuse_group(lanes, idxs, key, e_pad_hint)
                for key, idxs in groups.items()]


def solve_groups(lanes: List[PackedLane], groups: List[_FusedGroup],
                 use_mesh: bool = True
                 ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Device half of fuse_and_solve: dispatch each fused group, map
    results back to input-lane order, and return arena entries to the
    pool."""
    results: List = [None] * len(lanes)
    try:
        for g in groups:
            t0 = time.perf_counter()
            # transfer-ledger record for this generation: the payload
            # notes the transports emit below land in it, and its
            # (bytes, wall-ms) pair feeds the live link model. The
            # finally guarantees the record's deferred notes fold into
            # the ledger even when the dispatch raises -- byte parity
            # vs dispatch_bytes_total must survive error paths.
            if xferobs.enabled():
                xferobs.begin_dispatch(
                    E=g.e_pad, e_real=g.e_real, P=g.p_pad,
                    wave=bool(g.wave), A=g.A,
                    in_flight=pipeline_state()["in_flight"])
            # the span is a host annotation on the profiler's timeline
            # (server/tracing.py); the stage clock inside it says which
            # of prep / put / launch / fetch held the dispatch
            with tracer.span("solver.dispatch", E=g.e_pad,
                             e_real=g.e_real, P=g.p_pad,
                             wave=bool(g.wave), A=g.A,
                             arena_reused=bool(g.arena_reused)) as sp, \
                    stages.clock():
                try:
                    out = _dispatch(g.const, g.init, g.batch,
                                    g.spread_alg, g.dtype_name, use_mesh,
                                    ptab=g.ptab, pinit=g.pinit,
                                    wave=g.wave,
                                    cache_version=g.cache_version,
                                    delta_src=g.delta_src)
                finally:
                    dt_ms = (time.perf_counter() - t0) * 1e3
                    xferobs.end_dispatch(dt_ms)
                sp.tag(slow_compile=dt_ms > 1000.0)
            metrics.sample_ms("nomad.solver.dispatch", dt_ms)
            if dt_ms > 1000.0:
                # a >1s dispatch on these shapes is an XLA compile, not
                # compute; record which variant so warm-path stalls are
                # attributable
                metrics.incr("nomad.solver.dispatch_slow")
                from ..server.logbroker import log as _log
                _log("warn", "solver",
                     f"slow dispatch {dt_ms:.0f}ms "
                     f"(E={g.e_pad} P={g.p_pad} wave={g.wave}"
                     f" A={g.A}) -- likely fresh XLA compile")
            if g.A > 0:
                chosen, scores, n_yielded, evict_rows = out
            else:
                chosen, scores, n_yielded = out
            with metrics.measure("nomad.solver.dispatch_unpack"), \
                    tracer.span("solver.dispatch_unpack"):
                for j, li in enumerate(g.idxs):
                    p_real = lanes[li].batch.ask_cpu.shape[0]
                    res = [np.asarray(chosen[j][:p_real]).astype(np.int64),
                           np.asarray(scores[j][:p_real]),
                           np.asarray(n_yielded[j][:p_real]).astype(
                               np.int64)]
                    if g.A > 0:
                        res.append(np.asarray(evict_rows[j][:p_real]))
                    results[li] = tuple(res)
    finally:
        for g in groups:
            if g.entry is not None:
                # device results were fetched (or the dispatch failed)
                # before release, so no in-flight transfer reads these
                # host buffers when the next generation refills them
                _ARENA.release(g.entry)
                g.entry = None
    return results


def fuse_and_solve(lanes: List[PackedLane], use_mesh: bool = True,
                   e_pad_hint: int = 0, staged: Optional[dict] = None
                   ) -> List[Tuple[np.ndarray, np.ndarray, np.ndarray]]:
    """Group lanes by static-shape signature (placement axes pad to a
    common bucket), solve each group as ONE batched dispatch, return
    per-lane (chosen, scores, n_yielded) in input order.

    ``e_pad_hint`` (the barrier width) pins the eval axis of WAVEFRONT
    groups to one bucket regardless of how many lanes actually arrived:
    retry batches come in arbitrary sizes, and every fresh E bucket is a
    fresh XLA program (seconds of compile stalling the whole batch) while
    an inert wave lane costs only O(B*P) padded compute. Whole-axis
    groups are pinned alike: no padded step is run, and a padded lane
    is skipped on one device and inert beside the real ones on a mesh
    (_fuse_group), preempting ones included, which run on one device.

    ``staged`` carries groups pre-filled by the pipeline's prepare stage
    (fuse_lanes run while the previous generation was in flight) so the
    dispatch slot pays only device work."""
    groups = staged.get("groups") if staged else None
    if groups is None:
        groups = fuse_lanes(lanes, e_pad_hint)
    return solve_groups(lanes, groups, use_mesh=use_mesh)


# the shapes whose whole-axis preemption program is warm
_TWINS_WARM: set = set()
_TWINS_LOCK = threading.Lock()


def _warm_whole_axis_twin(const, init, batch, ptab, pinit,
                          spread_alg: bool, dtype_name: str) -> None:
    """A preempting group that takes the windowed kernel (no node fits
    without eviction) has a twin of its shapes that takes the whole-axis
    one: the same fleet once an eviction has left a free device beside
    room for a later job. The first dispatch of a windowed group's
    shapes compiles the twin's program too, by a launch that places
    nothing, so that it does not compile (~20 s on a v5e) in front of a
    window's queue."""
    from .binpack import solve_lane_fused
    key = (tuple((np.shape(x), np.asarray(x).dtype.str)
                 for tree in (const, init, batch, ptab, pinit)
                 for x in tree), spread_alg, dtype_name)
    with _TWINS_LOCK:
        if key in _TWINS_WARM:
            return
        _TWINS_WARM.add(key)
    idle = batch._replace(active=np.zeros_like(np.asarray(batch.active)))
    solve_lane_fused(const, init, idle, ptab, pinit, spread_alg=spread_alg,
                     dtype_name=dtype_name, batched=True)


def _dispatch(const, init, batch, spread_alg: bool, dtype_name: str,
              use_mesh: bool, ptab=None, pinit=None, wave: bool = False,
              cache_version=None, delta_src=None):
    """One solve_eval_batch[_preempt] call; shards over an (evals, nodes)
    mesh when multiple devices are attached, the shapes divide the
    mesh, and NOMAD_TPU_MESH is not 0 (the pick_mesh chokepoint; off
    is bit-for-bit the single-device path). Non-preempt path only;
    preemption tables stay single-device.
    ``wave`` (homogeneous by fuse_key) routes the group through the
    wavefront kernel -- its per-step work is O(B), so it skips mesh
    sharding (nothing N-heavy to shard)."""
    import jax
    import jax.numpy as jnp

    from .binpack import active_steps, solve_lane_fused

    if ptab is not None:
        metrics.incr("nomad.solver.wavefront_preempt_dispatches" if wave
                     else "nomad.solver.dense_dispatches")
        got = solve_lane_fused(const, init, batch, ptab, pinit,
                               spread_alg=spread_alg,
                               dtype_name=dtype_name, batched=True,
                               wave=wave, cache_version=cache_version,
                               delta_src=delta_src)
        if wave:
            _warm_whole_axis_twin(const, init, batch, ptab, pinit,
                                  spread_alg, dtype_name)
        return got
    if wave:
        metrics.incr("nomad.solver.wavefront_dispatches")
        return solve_lane_fused(const, init, batch, spread_alg=spread_alg,
                                dtype_name=dtype_name, batched=True,
                                wave=True, cache_version=cache_version,
                                delta_src=delta_src)
    metrics.incr("nomad.solver.dense_dispatches")
    metrics.sample("nomad.solver.dense_steps",
                   float(active_steps(np.asarray(batch.active)).sum()))

    E = const.cpu_cap.shape[0]
    N = const.cpu_cap.shape[1]
    mesh = None
    if use_mesh and jax.device_count() > 1:
        from ..parallel.mesh import pick_mesh, shard_solver_inputs
        mesh = pick_mesh(E, N)

    with tracer.span("solver.dense_solve", E=E, P=batch.active.shape[1]):
        if mesh is None:
            return solve_lane_fused(
                const, init, batch, spread_alg=spread_alg,
                dtype_name=dtype_name, batched=True,
                cache_version=cache_version, delta_src=delta_src)
        from ..parallel.mesh import mesh_solve_fn
        metrics.incr("nomad.solver.mesh_dispatches")
        with mesh:
            stages.mark("put")
            s_const, s_init, s_batch = shard_solver_inputs(
                mesh, const, init, batch, version=cache_version,
                delta_src=delta_src)
            stages.mark("launch")
            fn = mesh_solve_fn(mesh, spread_alg, dtype_name)
            chosen, scores, n_yielded = fn(s_const, s_init, s_batch)
        stages.mark("fetch")
        from .. import jitcheck
        with jitcheck.sanctioned_fetch("mesh"):
            # the mesh path's one bulk fetch: gather + host copy
            combined = np.asarray(jnp.concatenate([
                chosen.astype(scores.dtype)[None], scores[None],
                n_yielded.astype(scores.dtype)[None]], axis=0))
        xferobs.note_fetch(combined.nbytes, "mesh")
        return combined[0], combined[1], combined[2]


def _cross_lane_fixpoint(lanes: List[PackedLane], results: List,
                         ledger: Dict[str, list], foreign=None):
    """Resolve placement conflicts BEFORE plans are submitted: among the
    lanes this barrier holds, and against what the server's other
    barrier has handed to its evals and no lane can have packed yet.

    Every lane solved from the same snapshot, so concurrent evals pile
    onto the same best-scoring nodes; the serialized applier then
    partial-rejects the losers and each rejected eval pays a full
    scheduler retry round trip (broker -> worker -> solve -> applier).
    The reference has the same race between its parallel workers
    (plan_apply.go:96 partial commits + generic_sched.go:330 retries);
    here the barrier already holds EVERY in-flight result, so it can
    settle the conflicts locally: walk lanes in plan-priority order,
    charge each placement against a shared per-node capacity ledger, and
    re-solve only the overflowing placements of wave-eligible lanes
    against the accumulated usage (one extra small cached-program
    dispatch per conflicted lane). The outcome matches what the
    applier+retry loop would have produced from this snapshot -- minus
    the control-plane round trips.

    What the barrier settles itself: conflicts among its own lanes (the
    ledger), and, with ``foreign`` (the server's in-flight bookings as
    this barrier reads them, server/inflight.py ``ForeignView``),
    conflicts with the
    placements another batch worker's barrier has delivered and the
    applier has not committed, or committed after the usage was folded:
    a node's free capacity is its ledger entry less every such booking
    that the usage the entry was made from cannot contain -- none that
    it does contain, and none of this barrier's own.

    What stays the applier's: its authoritative re-check
    (plan_apply.py _evaluate_plan) runs unchanged on every plan, and it
    alone adjudicates the lanes that the wave kernel can't re-solve
    (whole-axis scans, preemption tables, static ports,
    devices/cores/distinct_property): those only consume ledger
    capacity, and a placement of theirs over a node's capacity is left
    in the plan, uncharged, for the applier to refuse. The ledger is
    keyed by node id and persists across a batch's barrier generations
    (multi-TG evals rendezvous once per TG) so later generations see
    earlier ones' usage. Results are edited in place.

    Returns, a lane, {node id: [cpu, mem, disk, dynamic ports]} of the
    placements charged (what the barrier books for the lane's eval), or
    None when there was nothing to walk.
    """
    if len(lanes) < 2 and not ledger and foreign is None:
        return None

    order_idx = sorted(
        range(len(lanes)),
        key=lambda i: (-lanes[i].service.ctx.plan.priority, i))
    charged: List[Dict[str, list]] = [{} for _ in lanes]
    # placements that fit the ledger and not the ledger less the other
    # barrier's bookings
    cross = 0

    def free_of(nid, f):
        """The ledger entry ``f`` of ``nid`` less the other barrier's
        bookings that the usage it was made from cannot contain."""
        if foreign is None:
            return f
        d = foreign.deduction(nid, f[4])
        if d is None:
            return f
        return [f[0] - d[0], f[1] - d[1], f[2] - d[2], f[3] - d[3]]

    def charge(into, lane, nid, f, pi):
        """Try to charge placement pi to ``nid``'s ledger entry ``f``;
        returns True and subtracts (and adds to the lane's charges,
        ``into``) when it fits."""
        nonlocal cross
        need = _placement_need(lane.batch, pi)
        if not (f[0] >= need[0] and f[1] >= need[1]
                and f[2] >= need[2] and f[3] >= need[3]):
            return False
        free = free_of(nid, f)
        if free is not f and not (
                free[0] >= need[0] and free[1] >= need[1]
                and free[2] >= need[2] and free[3] >= need[3]):
            cross += 1
            return False
        got = into.get(nid)
        if got is None:
            got = into[nid] = [0.0, 0.0, 0.0, 0]
        for k in range(4):
            f[k] -= need[k]
            got[k] += need[k]
        return True

    def entry(lane, pos, nid):
        f = ledger.get(nid)
        if f is None:
            c, s = lane.const, lane.init
            u = lane.usage_index
            f = [float(c.cpu_cap[pos]) - float(s.used_cpu[pos]),
                 float(c.mem_cap[pos]) - float(s.used_mem[pos]),
                 float(c.disk_cap[pos]) - float(s.used_disk[pos]),
                 int(s.dyn_avail[pos]),
                 # what this entry's usage holds: the index the lane
                 # folded it at
                 float("inf") if u is None else u]
            ledger[nid] = f
        return f

    for i in order_idx:
        lane, res = lanes[i], results[i]
        if res is None:
            continue
        chosen = res[0]
        active = np.asarray(lane.batch.active)
        plan = lane.service.ctx.plan
        # Consumer-only lanes are never re-solved: preemption tables and
        # static ports need the applier's exact checks, and a plan
        # carrying stops/preemptions has a usage view the shared ledger
        # can't represent (its init excludes capacity that frees only if
        # ITS plan commits -- re-solving against the ledger would strand
        # that capacity and spuriously fail placements the applier would
        # have accepted).
        resolvable = (lane.ptab is None and lane.wavefront_ok()
                      and not bool(np.asarray(lane.batch.has_static)[:1]
                                   .any())
                      and not plan.node_update
                      and not plan.node_preemptions)
        order = np.asarray(lane.order)
        charge_lane = functools.partial(charge, charged[i])
        conflicted: List[int] = []
        accepted_own: List[int] = []
        unresolvable = 0
        for pi in range(chosen.shape[0]):
            pos = int(chosen[pi])
            if pos < 0 or pos >= order.shape[0] or not active[pi]:
                continue
            nid = lane.nodes[order[pos]].id
            if charge_lane(lane, nid, entry(lane, pos, nid), pi):
                accepted_own.append(pos)
            elif resolvable:
                conflicted.append(pi)
            else:
                # left for the applier to adjudicate; its capacity was
                # NOT charged (the applier will reject it)
                unresolvable += 1
        if unresolvable:
            metrics.incr("nomad.solver.fixpoint_unresolvable", unresolvable)
        if not conflicted:
            continue
        metrics.incr("nomad.solver.fixpoint_conflicts", len(conflicted))
        metrics.incr("nomad.solver.fixpoint_dispatches")
        results[i] = _resolve_lane_conflicts(
            lane, res, conflicted, accepted_own, ledger, entry,
            charge_lane, free_of, foreign)
    if cross:
        metrics.incr("nomad.solver.fixpoint_cross_batch_conflicts", cross)
    return charged


def _resolve_lane_conflicts(lane, res, conflicted, accepted_own,
                            ledger, entry, charge, free_of, foreign=None):
    """Re-solve ``conflicted`` placements of one wave lane against the
    ledger's accumulated usage (each entry read through ``free_of``:
    less the other barrier's bookings) and, on the nodes the ledger
    does not hold, against this lane's own usage less those bookings
    (``foreign``; no ledger entry is made for a node no lane of this
    barrier was charged on: an entry is some lane's view of the node,
    and only a placement there leaves the history that explains a later
    lane's move off it); returns the merged result tuple (the fused
    dispatch's arrays are read-only device-buffer views, so the merge
    copies instead of mutating)."""
    from .binpack import solve_lane_fused

    import jax

    chosen = np.array(res[0], copy=True)
    scores = np.array(res[1], copy=True)
    n_yielded = np.array(res[2], copy=True)
    order = np.asarray(lane.order)
    patch, spread_counts = _ledger_patch(
        lane, _scan_positions(lane, order), ledger, free_of, foreign,
        accepted_own)
    idx = np.asarray(conflicted, dtype=np.int64)
    sub_batch = jax.tree_util.tree_map(
        lambda a: np.asarray(a)[idx]
        if np.asarray(a).shape[:1] == (chosen.shape[0],) else a,
        lane.batch)
    c2, s2, y2 = solve_lane_fused(
        lane.const, lane.init._replace(spread_counts=spread_counts),
        sub_batch, spread_alg=lane.spread_alg, dtype_name=lane.dtype_name,
        wave=True, patch=patch)
    # Merge ONLY successful re-solves. A -1 re-solve means the ledger saw
    # no capacity -- but the ledger can be pessimistic (a consumer-only
    # lane's charge whose plan later gets rejected is never refunded), so
    # keep the ORIGINAL choice and let the authoritative applier decide:
    # a phantom conflict then commits fine, a real one costs one retry
    # round trip (exactly the pre-fixpoint behavior).
    for k, pi in enumerate(conflicted):
        pos = int(c2[k])
        if pos < 0:
            continue
        chosen[pi] = pos
        scores[pi] = s2[k]
        n_yielded[pi] = y2[k]
        # charge the fresh choice (solved against the ledger's usage, so
        # it fits; charging records it for later lanes)
        nid = lane.nodes[order[pos]].id
        charge(lane, nid, entry(lane, pos, nid), pi)
    return (chosen, scores, n_yielded)


def _ledger_patch(lane, pos_of, ledger, free_of, foreign, accepted_own):
    """A re-solve's view of ``lane`` where it differs from the lane's
    own init, as a WavePatch, and the spread counts: the ledger's
    entries (each read through ``free_of``), the other barrier's
    bookings on the nodes the ledger does not hold, and the lane's own
    accepted placements (``accepted_own``, scan positions). Each value
    is what writing it into a copy of the init's arrays would leave
    there."""
    from .binpack import WavePatch

    const, init = lane.const, lane.init
    used_cpu, used_mem = np.asarray(init.used_cpu), np.asarray(init.used_mem)
    used_disk, dyn_avail = (np.asarray(init.used_disk),
                            np.asarray(init.dyn_avail))
    usage: Dict[int, tuple] = {}
    for nid, f in ledger.items():
        p = pos_of(nid)
        if p is None:
            continue
        # re-derive this lane's view of the node from the joint ledger
        # (caps are identical across lanes -- raw node resources minus
        # reserved -- so cap - free is the joint used)
        f = free_of(nid, f)
        usage[p] = (float(const.cpu_cap[p]) - f[0],
                    float(const.mem_cap[p]) - f[1],
                    float(const.disk_cap[p]) - f[2], f[3])
    if foreign is not None:
        u = lane.usage_index
        for nid, d in foreign.deductions(
                float("inf") if u is None else u).items():
            p = None if nid in ledger else pos_of(nid)
            if p is not None:
                usage[p] = (used_cpu[p] + d[0], used_mem[p] + d[1],
                            used_disk[p] + d[2], dyn_avail[p] - d[3])
    own: Dict[int, int] = {}
    spread_counts = np.array(init.spread_counts, copy=True)
    S = spread_counts.shape[0] if spread_counts.ndim else 0
    for pos in accepted_own:
        own[pos] = own.get(pos, 0) + 1
        for s in range(S):
            v = int(const.spread_vidx[s, pos])
            if v >= 0:
                spread_counts[s, v] += 1
    pos = sorted(usage.keys() | own.keys())
    rows = [usage[p] if p in usage
            else (used_cpu[p], used_mem[p], used_disk[p], dyn_avail[p])
            for p in pos]
    placed, placed_job = np.asarray(init.placed), np.asarray(init.placed_job)
    patch = WavePatch(
        pos=np.array(pos, dtype=np.int64),
        used_cpu=np.array([r[0] for r in rows], dtype=used_cpu.dtype),
        used_mem=np.array([r[1] for r in rows], dtype=used_mem.dtype),
        used_disk=np.array([r[2] for r in rows], dtype=used_disk.dtype),
        dyn_avail=np.array([r[3] for r in rows], dtype=dyn_avail.dtype),
        placed=np.array([placed[p] + own.get(p, 0) for p in pos],
                        dtype=placed.dtype),
        placed_job=np.array([placed_job[p] + own.get(p, 0) for p in pos],
                            dtype=placed_job.dtype))
    return patch, spread_counts


def _scan_positions(lane, order):
    """node id -> the node's position in ``lane``'s scan order, or None.
    By way of the lane's node matrix, whose id index every lane and
    generation of one node table shares, and the inverse of the order
    its pack made (a walk over 10,000 nodes a conflicted lane
    otherwise)."""
    matrix = lane.matrix
    if matrix is None or lane.scan_pos is None:
        pos = {lane.nodes[i].id: p for p, i in enumerate(order.tolist())}
        return pos.get
    index = matrix.__dict__.get("_pos_index")
    if index is None:
        index = {nid: i for i, nid in enumerate(matrix.node_ids)}
        matrix._pos_index = index
    inv, n = lane.scan_pos, order.shape[0]

    def pos_of(nid):
        i = index.get(nid)
        if i is None or i >= n:
            return None
        return int(inv[i])
    return pos_of


def _placement_need(batch, pi: int) -> tuple:
    """(cpu, mem, disk, dynamic ports) placement ``pi`` takes of its
    node: a ledger entry's and a booking's four numbers."""
    return (float(batch.ask_cpu[pi]), float(batch.ask_mem[pi]),
            float(batch.ask_disk[pi]), int(batch.n_dyn_ports[pi]))


def _lane_charges(lane, res) -> Dict[str, list]:
    """{node id: [cpu, mem, disk, dynamic ports]} of everything a lane's
    result places: what a barrier books for a generation the fixpoint
    had nothing to walk in (a lone lane, nothing in flight)."""
    out: Dict[str, list] = {}
    if res is None:
        return out
    active = np.asarray(lane.batch.active)
    order = np.asarray(lane.order)
    for pi, pos in enumerate(np.asarray(res[0]).tolist()):
        if pos < 0 or pos >= order.shape[0] or not active[pi]:
            continue
        got = out.setdefault(lane.nodes[order[pos]].id,
                             [0.0, 0.0, 0.0, 0])
        for k, x in enumerate(_placement_need(lane.batch, pi)):
            got[k] += x
    return out


class SolveBarrier:
    """Rendezvous point for one batch of eval threads.

    Threads call solve() (blocking) or done() (on exit). When arrivals +
    finished == participants the batch is handed to the process-global
    dispatch pipeline and the arriving thread joins the waiters. Up to
    ``depth`` fused dispatches run in flight (each under its OWN
    guard.run_dispatch watchdog), so a later generation's host
    packing/transfer overlaps an earlier one's device execution.
    Completions apply in GENERATION ORDER: the cross-lane fixpoint
    ledger charges generation g before g+1 even when g+1's device work
    finishes first. ``depth`` is the pipeline's slot count, for tests
    (1 = a serial order to compare with); production passes none.
    ``bookings`` is the server's in-flight bookings
    (server/inflight.py): what this barrier's fixpoint accepts is
    booked there for the server's other barrier to charge, and theirs
    is charged here; the batch's owner calls retire() when it ends."""

    def __init__(self, participants: int, use_mesh: bool = True,
                 e_pad_hint: int = 0, depth: Optional[int] = None,
                 plan_group_hint=None, bookings=None):
        self._cv = threading.Condition()
        self._participants = participants
        self._finished = 0
        self._waiting: List[Tuple[PackedLane, dict]] = []
        self._use_mesh = use_mesh
        self._generation = 0
        self._depth = DISPATCH_DEPTH if depth is None else max(1, depth)
        # called with the lane count each time a generation's results
        # are delivered: each of those evals is about to submit a plan,
        # so the plan applier can hold its drain and commit the whole
        # generation as ONE group (Planner.expect_plans)
        self._plan_group_hint = plan_group_hint
        # generation-ordered completion
        self._complete_cv = threading.Condition()
        self._next_complete = 1
        # pin wave groups' eval axis to the worker's CONFIGURED width, not
        # the momentary batch size: dequeue sizes vary per iteration and
        # every fresh E bucket is a fresh XLA program
        self._e_pad_hint = e_pad_hint or participants
        # shared per-node capacity ledger for the cross-lane conflict
        # fixpoint; persists across this batch's barrier generations
        self._ledger: Dict[str, list] = {}
        self._bookings = bookings
        if bookings is not None:
            bookings.open_view(self)

    def retire(self) -> None:
        """The batch is over (or its worker is given up): nothing more
        of this barrier's is in flight."""
        if self._bookings is not None:
            self._bookings.retire(self)

    def done(self) -> None:
        """Thread finished its eval (no more solves coming)."""
        with self._cv:
            self._finished += 1
            if self._ready_locked():
                self._dispatch_locked()

    def solve(self, lane: PackedLane):
        """Block until the batch dispatches; returns this lane's
        (chosen, scores, n_yielded). A dispatch failure re-raises in EVERY
        participating thread (each eval then nacks independently)."""
        # explicit trace handoff: the eval thread's ctx rides the cell
        # so the dispatch (running on a pipeline thread)
        # can record its spans into every participating eval's trace
        cell: dict = {"trace_ctx": tracer.current()}
        t_arrive = time.time()
        with self._cv:
            self._waiting.append((lane, cell))
            if self._ready_locked():
                self._dispatch_locked()
            while "result" not in cell and "error" not in cell:
                gen = self._generation
                if not self._cv.wait(timeout=BARRIER_TIMEOUT_S):
                    # Straggler safety valve: if OUR lane is still queued
                    # (no dispatch consumed it), dispatch what we have
                    # rather than wedge. Either way the cell is
                    # re-checked under the condvar -- the old code broke
                    # out of the loop here and could read cell["result"]
                    # before any dispatch had set it when another
                    # generation raced the timeout.
                    if (self._generation == gen
                            and any(c is cell for _, c in self._waiting)):
                        self._dispatch_locked()
            if "error" in cell:
                tracer.record("solver.barrier", t_arrive,
                              (time.time() - t_arrive) * 1e3,
                              outcome="error")
                raise cell["error"]
            tracer.record("solver.barrier", t_arrive,
                          (time.time() - t_arrive) * 1e3, outcome="ok")
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

        # hand the generation to the pipeline; the caller (an eval
        # thread) falls back into its cv.wait loop and is woken by the
        # completion. The prepare stage fills this generation's arena
        # buffers on the intake thread BEFORE a dispatch slot frees up,
        # overlapping host packing with the in-flight generation's
        # device execution.
        staged: dict = {}
        e_pad_hint = self._e_pad_hint

        def _prepare():
            try:
                staged["groups"] = fuse_lanes(lanes,
                                              e_pad_hint=e_pad_hint)
            except Exception:  # noqa: BLE001 -- best effort: the
                staged.clear()  # dispatch re-derives (and raises
                raise           # under its own watchdog)

        _get_pipeline(self._depth).submit(
            functools.partial(self._dispatch_job, gen, batch, lanes,
                              staged),
            prepare=_prepare)

    def _dispatch_job(self, gen: int, batch, lanes,
                      staged: dict) -> None:
        """One in-flight generation, on a pipeline thread: fused
        dispatch under its own watchdog, then generation-ordered
        fixpoint + wakeup. Every cell gets exactly one result-or-error,
        no matter what raises where. ``staged`` carries arena buffers
        the intake thread pre-filled while the previous generation was
        in flight."""
        results = None
        err: Optional[Exception] = None
        # explicit cross-thread handoff: this runs on a PIPELINE thread;
        # the group ctx (every eval fused into this generation) was
        # captured on the eval threads and rides the batch's cells
        gctx = tracer.group([c.get("trace_ctx") for _, c in batch])
        try:
            from .guard import run_dispatch
            xfer_tok = xferobs.mark()
            with tracer.activate(gctx), \
                    tracer.span("solver.fuse_dispatch", ctx=gctx,
                                generation=gen, lanes=len(lanes),
                                depth=self._depth,
                                staged="groups" in staged,
                                in_flight=pipeline_state()["in_flight"]
                                ) as sp:
                results = run_dispatch(
                    lambda: fuse_and_solve(
                        lanes, use_mesh=self._use_mesh,
                        e_pad_hint=self._e_pad_hint, staged=staged),
                    label="solver.batch")
                # waterfall annotation: shipped/resident bytes + link
                # predicted-vs-actual for this generation's dispatches
                sp.tag(**xferobs.span_tags(xfer_tok))
        except Exception as e:  # noqa: BLE001 -- waiters must not strand
            err = e
        # Ordered-completion section: generation g's ledger charges land
        # before g+1's. A started job always finishes (the watchdog
        # bounds its device work), so the predecessor wait terminates;
        # the timeout is a last-resort anti-wedge, not a normal path.
        deadline = time.monotonic() + max(
            60.0, 2.0 * _barrier_order_timeout())
        with tracer.span("solver.order_wait", ctx=gctx, generation=gen):
            with self._complete_cv:
                while self._next_complete != gen:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0:
                        from ..server.logbroker import log as _log
                        _log("error", "solver",
                             f"dispatch generation {gen} gave up waiting "
                             f"for generation {self._next_complete} to "
                             "complete; proceeding out of order")
                        break
                    self._complete_cv.wait(remaining)
        try:
            if err is None:
                try:
                    self._settle_generation(gen, gctx, lanes, results)
                except Exception as e:  # noqa: BLE001 -- same contract
                    err = e
        finally:
            self._hint_plan_group(len(batch))
            with self._cv:
                for i, (_lane, cell) in enumerate(batch):
                    if err is not None:
                        cell["error"] = err
                    else:
                        cell["result"] = results[i]
                self._cv.notify_all()
            with self._complete_cv:
                if self._next_complete == gen:
                    self._next_complete = gen + 1
                self._complete_cv.notify_all()

    def _settle_generation(self, gen: int, gctx, lanes, results) -> None:
        """The fixpoint over one generation's results, then the booking
        of what it accepted. With a server's bookings the two are one
        section under its lock: the other barrier's fixpoint charges
        either all of this generation or none of it."""
        bookings = self._bookings
        if bookings is None:
            self._run_fixpoint(gen, gctx, lanes, results, None)
            return
        t_wait = time.time()
        with bookings.fixpoint_lock:
            # the wait for the other barrier's section: the lock's hold
            # time as this barrier meets it
            tracer.record("solver.fixpoint_lock_wait", t_wait,
                          (time.time() - t_wait) * 1e3, ctx=gctx,
                          generation=gen)
            charged = self._run_fixpoint(gen, gctx, lanes, results,
                                         bookings.foreign(self))
            for i, lane in enumerate(lanes):
                bookings.book(
                    self, lane.service.ctx.plan.eval_id,
                    charged[i] if charged is not None
                    else _lane_charges(lane, results[i]))

    def _run_fixpoint(self, gen: int, gctx, lanes, results, foreign):
        # only pay a second watchdog when the fixpoint can actually do
        # work (its own early-return conditions); its re-solves are real
        # device dispatches and deserve the same deadline as the fuse
        if not (len(lanes) >= 2 or self._ledger or foreign is not None):
            return None
        from .guard import run_dispatch
        # the fourth argument only where there is something to pass: the
        # tests' stand-ins for the fixpoint take the three
        args = (lanes, results, self._ledger)
        if foreign is not None:
            args += (foreign,)
        with tracer.activate(gctx), \
                tracer.span("solver.fixpoint", ctx=gctx, generation=gen):
            return run_dispatch(
                lambda: _cross_lane_fixpoint(*args),
                label="solver.batch.fixpoint")

    def _hint_plan_group(self, n: int) -> None:
        """A generation's results are about to wake n eval threads, each
        of which will submit a plan (the host-fallback path included) --
        tell the plan applier so they commit as one group."""
        hint = self._plan_group_hint
        if hint is None or n <= 0:
            return
        try:
            hint(n)
        except Exception:  # noqa: BLE001 -- advisory only
            pass


def _barrier_order_timeout() -> float:
    """Bound on how long a pipelined generation waits for its
    predecessor before proceeding out of order (predecessors are
    watchdog-bounded -- execution deadline plus a compile stage's own
    -- so this only fires on a bug)."""
    from .guard import COMPILE_DEADLINE_S, dispatch_deadline_s
    d = dispatch_deadline_s()
    return (d if d > 0 else 30.0) + COMPILE_DEADLINE_S


def make_solve_hook(barrier: SolveBarrier):
    """The hook GenericScheduler calls instead of service.solve(): pack on
    the calling thread, solve at the barrier, materialize on the calling
    thread. A deadline-failed dispatch degrades THIS eval to the host
    oracle (return None) -- the eval completes instead of nacking."""
    def hook(service, tg, places, nodes, penalties):
        from .guard import DispatchFailed, note_host_fallback

        with tracer.span("solver.pack", tg=tg.name,
                         places=len(places)):
            lane = service.pack(tg, places, nodes, penalties)
        if lane is None:
            return None          # not solver-eligible -> host fallback
        try:
            res = barrier.solve(lane)
        except DispatchFailed:
            note_host_fallback()
            return None
        # shadow-oracle audit (server/quality.py): sampled capture of
        # this lane's fused-solve result for background host replay
        from ..server.quality import observatory as _quality
        _quality.maybe_capture_audit(lane, res[0], res[1])
        with tracer.span("solver.materialize", tg=tg.name):
            return service.materialize(lane, *res)
    return hook
