"""TPU placement service: bridges the generic scheduler to the dense solver.

Registered behind the same boundary the reference exposes for algorithm
selection (SchedulerConfiguration.scheduler_algorithm, read at
stack.go:292/rank.go:192): algorithms ``tpu-binpack`` / ``tpu-spread`` route
eligible placement batches through nomad_tpu/solver/binpack.py; anything the
dense path does not model (devices, reserved cores, preemption, sticky-disk
preferred nodes) falls back to the host iterator stack per placement, so
behavior is always complete.
"""
from __future__ import annotations

import contextlib
import math
from typing import Dict, List, Optional, Tuple

import numpy as np

from ..structs import (
    AllocatedResources, AllocatedSharedResources, AllocatedTaskResources,
    NetworkIndex,
    CONSTRAINT_DISTINCT_HOSTS,
)
from ..tensor import (
    pack_affinities_cached, pack_feasibility_cached, pack_spreads_cached,
)
from ..scheduler.util import shuffled_order


class TpuPlacement:
    """One solved placement returned to the scheduler."""

    __slots__ = ("place", "node", "task_resources", "alloc_resources",
                 "score", "n_yielded", "preempted_allocs",
                 "resources_prebuilt")

    def __init__(self, place, node, task_resources, alloc_resources, score,
                 n_yielded, preempted_allocs=None,
                 resources_prebuilt=None):
        self.place = place
        self.node = node
        self.task_resources = task_resources
        self.alloc_resources = alloc_resources
        self.score = score
        self.n_yielded = n_yielded
        self.preempted_allocs = preempted_allocs
        # uniform simple lanes share ONE AllocatedResources across all
        # placements (committed alloc graphs are immutable-by-replace
        # already -- update_allocs_from_client's shallow copy shares the
        # same object across versions today)
        self.resources_prebuilt = resources_prebuilt


class PackedLane:
    """One (eval, task-group) batch fully marshalled for the dense solver:
    the unit the batch coordinator fuses across evals (solve_eval_batch's
    leading axis). Holds the numpy-backed solver inputs plus everything
    materialize() needs to map solved indexes back to structs."""

    __slots__ = ("service", "tg", "places", "nodes", "order", "const",
                 "init", "batch", "dtype_name", "spread_alg", "ptab",
                 "pinit", "cand_allocs", "table_version", "matrix",
                 "delta_src", "usage_index", "_wave")

    def __init__(self, service, tg, places, nodes, order, const, init,
                 batch, dtype_name, spread_alg, ptab=None, pinit=None,
                 cand_allocs=None, table_version=None, matrix=None,
                 delta_src=None, usage_index=None):
        self.service = service
        self.tg = tg
        self.places = places
        self.nodes = nodes
        self.order = order
        self.const = const
        self.init = init
        self.batch = batch
        self.dtype_name = dtype_name
        self.spread_alg = spread_alg
        # preemption tables (solve_placements_preempt) + the shuffled-order
        # candidate->Allocation mapping materialize() needs for evictions
        self.ptab = ptab
        self.pinit = pinit
        self.cand_allocs = cand_allocs
        # node-table version of the packing snapshot: tags this lane's
        # const buffers in the device-resident cache (constcache.py)
        self.table_version = table_version
        # version-keyed NodeMatrix the lane packed from: its identity is
        # the node-universe key the LP-queue tier groups lanes by, and
        # its node_ids are the canonical node axis (solver/lpq.py)
        self.matrix = matrix
        # delta-streaming source (ISSUE 20): (store, snapshot index) --
        # the alloc-delta journal + the exact version this lane's
        # tables were packed AT, so the device-resident chain can
        # advance v_old -> v_new by scatter instead of re-shipping
        self.delta_src = delta_src
        # the state index this lane's usage holds every alloc up to:
        # the live alloc table's last write when it was folded, else
        # the snapshot's. Tells the cross-lane fixpoint which of the
        # other barrier's bookings the usage cannot contain
        # (server/inflight.py); None (a lane built by hand) = all that
        # have committed
        self.usage_index = usage_index
        self._wave = None

    def wavefront_ok(self) -> bool:
        """Can this lane route through the O(B)-per-step wavefront path
        (binpack.solve_lane_wave -- host precompute + compact scan)?
        Requires uniform asks over the active prefix, a window that fits
        a buffer variant (limit+skips <= WAVE_B or WAVE_B_WIDE), and none
        of distinct_property/devices/cores/preemption. Spreads,
        affinities and reschedule penalties ARE modeled (spread counts
        ride the carry; penalties ride the scan xs)."""
        if self._wave is not None:
            return self._wave
        self._wave = self._wavefront_check()
        return self._wave

    def _wavefront_check(self) -> bool:
        from .binpack import wavefront_buffer_size
        if self.ptab is not None:
            # windowed preemption (solve_lane_wave_preempt): spreads stay
            # dense (the preempt slot kernel carries no spread columns);
            # networks/cores are excluded for preempt lanes by
            # tg_solver_eligible(preempt=True); devices ride via the
            # capacity-countdown column when _wave_devices_ok passes
            # (checked in the shared section below)
            if self.const.spread_vidx.shape[0]:
                return False
            # max_parallel penalties couple the greedy's pick ORDER to the
            # evolving per-group eviction counts; the picked set feeds
            # fit2, so a node's option status would no longer be static
            # outside the window -- the invariant the windowed design
            # rests on. Those lanes stay dense.
            if bool(np.any(np.asarray(self.ptab.maxp)[
                    np.asarray(self.ptab.valid)] > 0)):
                return False
            # the deferred zombie occupies one slot for a step: the
            # window must still fit beside it
            from .binpack import MAX_SKIP
            lim = int(np.asarray(self.batch.limit)[0])
            b = wavefront_buffer_size(lim)
            if b is None or lim + MAX_SKIP + 1 > b:
                return False
        c = self.const
        if c.dp_vidx.shape[0] or c.mhz_per_core.shape[0]:
            return False
        if c.dev_aff.shape[0] and not self._wave_devices_ok():
            return False
        b = self.batch
        act = np.asarray(b.active)
        n_act = int(act.sum())
        if n_act == 0 or not act[:n_act].all():      # active must be prefix
            return False
        for arr in (b.ask_cpu, b.ask_mem, b.ask_disk, b.n_dyn_ports,
                    b.has_static, b.limit, b.count):
            v = np.asarray(arr)[:n_act]
            if not (v == v[0]).all():
                return False
        return wavefront_buffer_size(
            int(np.asarray(b.limit)[0])) is not None

    def _wave_devices_ok(self) -> bool:
        """Uniform device asks ride the wavefront as a pure capacity
        dimension (binpack._wave_device_capacity) when the dense device
        SCORE vanishes (zero affinity weight -> the dense kernel's
        device component is exactly 0) and the host capacity replay is
        bounded. Candidate-held matching devices are rejected at pack
        time (pack returns None -> host fallback), so eviction can
        never change device availability."""
        c = self.const
        if float(np.asarray(c.dev_sum_weight)) != 0.0:
            return False
        cnt = np.asarray(c.dev_count)
        if cnt.size == 0 or (cnt <= 0).any():
            return False
        free = np.asarray(self.init.dev_free)
        if free.size == 0:
            return False
        # bounded replay: max per-node instances / min ask under the cap
        from .binpack import WAVE_DEVICE_CAP_STEPS
        per_node = np.clip(free, 0, None).sum(axis=(0, 1))
        return (int(per_node.max(initial=0)) // int(cnt.min())
                < WAVE_DEVICE_CAP_STEPS)

    def wavefront_B(self):
        """Static slot-buffer width for fusion grouping (lanes with
        different widths compile to different programs)."""
        from .binpack import wavefront_buffer_size
        if not self.wavefront_ok():
            return None
        return wavefront_buffer_size(int(np.asarray(self.batch.limit)[0]))

    def fuse_key(self) -> tuple:
        """Lanes with equal keys can fuse into one vmapped dispatch: every
        static table shape except the placement axis (which pads), plus
        the static jit args."""
        return (self.const.cpu_cap.shape[0],          # n_pad
                self.batch.ask_cores.shape[0] > 0,    # core-ask lanes
                self.const.spread_vidx.shape[0],      # S
                self.const.spread_desired.shape[1],   # V
                self.const.dp_vidx.shape[0],          # Dp
                self.init.dp_counts.shape[1] if
                self.const.dp_vidx.shape[0] else 0,   # Vd
                self.const.dev_aff.shape[:2],         # (R, Gd)
                self.ptab.cpu.shape[1] if self.ptab is not None else 0,
                self.pinit.counts.shape[0] if self.pinit is not None else 0,
                self.dtype_name, self.spread_alg,
                self.wavefront_B())


def tg_solver_eligible(tg, job=None, preempt: bool = False) -> bool:
    """Does the dense path model everything this TG asks for? The
    remaining carve-outs (host iterator fallback):
      - preemption combined with ports, devices or cores (network/device
        preemption are subset searches, preemption.go:273,475; core
        release needs id-level accounting)
      - 0%-spread targets (the host's lowest-boost scoring depends on the
        scanned-prefix order, which couples window membership to scores)
    Devices, distinct_property AND reserved cores are modeled densely
    (cores: count-exact fit + node-dependent effective cpu, with core ids
    replayed deterministically at materialize -- VERDICT r2 next #7).
    Per-task networks and multi-network TGs are REJECTED at job
    validation (server/core.py _validate_job, mirroring
    structs/job.go TaskGroup.Validate) -- the defensive gates below only
    matter for harness-constructed jobs that bypass registration.
    """
    has_cores = False
    for task in tg.tasks:
        if task.resources.cores > 0:
            has_cores = True
        if task.resources.networks:
            return False
    if len(tg.networks) > 1:
        return False
    if preempt and (tg.networks or has_cores):
        # devices + preemption ARE modeled (dense feas_nonres gates
        # device-infeasible nodes out of the eviction path exactly like
        # rank.go:443's nil PreemptForDevice; the windowed kernel
        # carries a capacity countdown column) -- EXCEPT when evicting
        # a candidate would free matching instances, which pack()
        # detects and routes to the host iterator
        return False
    spreads = list(tg.spreads) + (list(job.spreads) if job is not None else [])
    for s in spreads:
        if any(t.percent == 0 for t in s.spread_target):
            return False
    return True


def mesh_status() -> dict:
    """Mesh-execution snapshot for guard.state() / `operator solver
    status` (ISSUE 19): the NOMAD_TPU_MESH knob, attached device count,
    the (evals, nodes) grid the dispatch stack would pick for a dense
    8-lane batch, and the mesh dispatch counters for both production
    kernels (fused greedy + LPQ). Never initializes jax: when the
    backend has not been touched yet, devices reports 0 and no grid is
    probed -- status must stay callable from light control-plane
    paths."""
    import sys

    from ..parallel.mesh import mesh_enabled, pick_mesh
    from ..server.telemetry import metrics

    counters = metrics.snapshot().get("counters", {})
    out = {
        "enabled": mesh_enabled(),
        "devices": 0,
        "grid": None,
        "dispatches": counters.get("nomad.solver.mesh_dispatches", 0),
        "lpq_dispatches": counters.get("nomad.lpq.mesh_dispatches", 0),
    }
    jax = sys.modules.get("jax")
    # gate on the guard's advisory flags, NOT a live jax call: with a
    # hung/degraded backend, jax.device_count() can block for the full
    # init window -- status would stall AND its late completion would
    # read as a spurious recovery (the backend-guard reprobe drill)
    from . import guard
    checked, ok = guard._FLAGS
    if jax is None or not (checked and ok):
        return out
    try:
        out["devices"] = int(jax.device_count())
        if out["enabled"] and out["devices"] > 1:
            mesh = pick_mesh(8, 256)
            if mesh is not None:
                out["grid"] = [int(x) for x in mesh.devices.shape]
    except Exception:  # noqa: BLE001 -- status must never fail the agent
        pass
    return out


def dispatch_lane(lane: PackedLane):
    """Solve ONE lane in its own device dispatch; returns host-side numpy
    (chosen, scores, n_yielded[, evict_rows]). The batched path fuses many
    lanes through solver.batch instead. Transfers are fused (one
    device_put, one fetch -- binpack.solve_lane_fused): per-leaf transfers
    each pay a host<->device round trip, which can cost more than the
    entire compiled scan."""
    from .binpack import solve_lane_fused

    wave = lane.wavefront_ok()
    from ..server.telemetry import metrics as _tm
    if lane.ptab is not None:
        _tm.incr("nomad.solver.wavefront_preempt_dispatches" if wave
                 else "nomad.solver.dense_dispatches")
    else:
        _tm.incr("nomad.solver.wavefront_dispatches" if wave
                 else "nomad.solver.dense_dispatches")
    return solve_lane_fused(
        lane.const, lane.init, lane.batch, lane.ptab, lane.pinit,
        spread_alg=lane.spread_alg, dtype_name=lane.dtype_name,
        wave=wave, cache_version=lane.table_version,
        delta_src=lane.delta_src)


class _DeviceShim:
    """Adapter so device packing reuses DeviceChecker's static helpers."""

    def __init__(self, ctx):
        self.ctx = ctx


class TpuPlacementService:
    """Solves all of one TG's placements for one eval in a single dispatch
    (amortizing host->TPU latency, SURVEY.md section 7 hard part 5)."""

    def __init__(self, ctx, job, batch_mode: bool, spread_alg: bool,
                 dtype: Optional[str] = None, preempt: bool = False):
        self.ctx = ctx
        self.job = job
        self.batch_mode = batch_mode
        self.spread_alg = spread_alg
        self.preempt = preempt
        if dtype is None:
            # float64 on CPU (exact parity with the host oracle's float64
            # math); float32 on TPU where f64 is emulated and the MXU wants
            # narrow types.
            import jax
            dtype = ("float64" if jax.config.jax_enable_x64
                     and jax.default_backend() == "cpu" else "float32")
        self.dtype = dtype
        # The host stack's limit persists across Select calls within one
        # eval (stack.go: set_nodes sets log2 once; the spread/affinity
        # override in Select is never restored). Mirror that statefulness.
        self._current_limit: Optional[int] = None

    def solve(self, tg, places, nodes, penalty_nodes_per_place=None
              ) -> Optional[List[TpuPlacement]]:
        """Returns one TpuPlacement per place (node=None for failures), or
        None when the TG is not solver-eligible OR the device dispatch
        missed its watchdog deadline / raised (caller falls back to the
        parity-authoritative host oracle either way -- a mid-flight
        device wedge must cost one deadline, not the worker)."""
        from . import guard
        from ..server.tracing import tracer

        with tracer.span("solver.pack", tg=tg.name, places=len(places)):
            lane = self.pack(tg, places, nodes, penalty_nodes_per_place)
        if lane is None:
            return None
        try:
            with tracer.span("solver.dispatch_solo", tg=tg.name):
                out = guard.run_dispatch(lambda: dispatch_lane(lane))
        except guard.DispatchFailed:
            guard.note_host_fallback()
            return None
        # shadow-oracle audit (server/quality.py): deterministic
        # eval-id-hash sample of solved lanes, re-scored/re-solved on
        # the host in the background; no-op while detached
        from ..server.quality import observatory as _quality
        _quality.maybe_capture_audit(lane, out[0], out[1])
        with tracer.span("solver.materialize", tg=tg.name):
            return self.materialize(lane, *out)

    def solve_system(self, tg, nodes) -> Optional[List[TpuPlacement]]:
        """Dense system-job solve: one independent fit+score per node
        (scheduler_system.go semantics -- no window, no distinct-hosts,
        binpack score only). Returns one TpuPlacement per input node
        (node=None where infeasible), or None when ineligible."""
        from ..scheduler.reconcile import AllocPlaceResult
        from .binpack import solve_system as _solve

        if not nodes:
            return []
        places = [AllocPlaceResult(name=f"{self.job.id}.{tg.name}[0]",
                                   task_group=tg) for _ in nodes]
        lane = self.pack(tg, places, nodes)
        if lane is None:
            return None
        # the kernel reads only row 0 of the uniform ask arrays: slice the
        # placement axis to 1 so the compiled shape depends on the padded
        # node axis alone (not on how many nodes need placing this eval)
        import jax as _jax

        from . import guard
        batch1 = _jax.tree_util.tree_map(
            lambda a: a[:1], lane.batch)
        try:
            fit, score = guard.run_dispatch(
                lambda: _solve(lane.const, lane.init, batch1,
                               spread_alg=self.spread_alg,
                               dtype_name=lane.dtype_name),
                label="solver.dispatch.system")
        except guard.DispatchFailed:
            guard.note_host_fallback()
            return None
        fit = np.asarray(fit)
        score = np.asarray(score)
        # lane.order is the length-n shuffled order (real nodes only);
        # padding positions can never be fit (matrix.valid False)
        n = len(nodes)
        inv = np.empty(n, dtype=np.int64)
        inv[np.asarray(lane.order, dtype=np.int64)] = np.arange(n)
        chosen = np.where(fit[inv], inv, -1).astype(np.int64)
        scores = score[inv].astype(np.float64)
        return self.materialize(lane, chosen, scores,
                                np.ones(n, dtype=np.int64))

    def pack(self, tg, places, nodes, penalty_nodes_per_place=None
             ) -> Optional[PackedLane]:
        """Marshal one TG's placements into a PackedLane (numpy-backed, no
        device dispatch). Returns None when the TG is not solver-eligible.
        (Placement-axis padding for cross-eval fusing happens in
        solver/batch.py _pad_placement_axis.) Timed into
        ``nomad.solver.pack_ms`` with pack-cache hit/miss counters and a
        per-eval trace event, so the host-side packing tax (and the warm
        cut the snapshot caches buy) is measured, not inferred."""
        import time as _time

        from ..server.telemetry import metrics as _tm
        from ..server.tracing import tracer as _tracer
        from ..tensor.pack import begin_pack_window, end_pack_window

        mark = begin_pack_window()
        t0 = _time.perf_counter()
        lane = self._pack_inner(tg, places, nodes, penalty_nodes_per_place)
        dt_ms = (_time.perf_counter() - t0) * 1e3
        hits, misses = end_pack_window(mark)
        _tm.sample_ms("nomad.solver.pack_ms", dt_ms)
        if hits:
            _tm.incr("nomad.solver.pack_cache_hit", hits)
        if misses:
            _tm.incr("nomad.solver.pack_cache_miss", misses)
        _tracer.event("solver.pack_cache", tg=tg.name, ms=round(dt_ms, 3),
                      hits=hits, misses=misses,
                      eligible=lane is not None)
        return lane

    def _pack_inner(self, tg, places, nodes, penalty_nodes_per_place=None
                    ) -> Optional[PackedLane]:
        from .binpack import (
            PlacementBatch, make_node_const, make_node_state)

        if (not tg_solver_eligible(tg, self.job, preempt=self.preempt)
                or not places):
            return None

        n = len(nodes)
        state_index = self.ctx.state.latest_index()
        from ..tensor.pack import pack_nodes_cached
        key_fn = getattr(self.ctx.state, "nodes_pack_key", None)
        matrix = pack_nodes_cached(
            nodes, getattr(self.ctx.state, "node_table_index", None),
            key_hint=key_fn(nodes) if key_fn is not None else None)
        n_pad = matrix.n_pad

        # Same permutation the host stack applies in set_nodes
        # (scheduler/util.py shuffle_nodes seeded by eval id + index);
        # native Fisher-Yates when the library is built.
        from .. import native as _nat
        from ..scheduler.util import shuffle_seed
        order = _nat.shuffled_order(
            shuffle_seed(self.ctx.plan.eval_id, state_index), n)
        if order is None:
            order = shuffled_order(self.ctx.plan.eval_id, state_index, n)
        perm = np.concatenate([np.asarray(order, dtype=np.int64),
                               np.arange(n, n_pad, dtype=np.int64)])
        inv = np.empty(n_pad, dtype=np.int64)
        inv[perm] = np.arange(n_pad)

        # With preemption on (candidate tables) or core asks (per-node
        # reserved-core accounting), every node's proposed allocs are
        # needed anyway -- do that walk ONCE and reuse it for usage
        # packing too (instead of the alloc-table fast path).
        ask_cores_total = sum(t.resources.cores for t in tg.tasks)
        proposed_by_node = None
        if self.preempt or ask_cores_total > 0:
            proposed_by_node = {
                node.id: self.ctx.proposed_allocs(node.id) for node in nodes}
        table = getattr(self.ctx.state, "alloc_table", None)
        usage_index = state_index
        if (table is not None and not table.has_port_overflow
                and proposed_by_node is None):
            usage, usage_index = self._pack_usage_from_table(
                table, matrix, nodes, tg)
        else:
            # incremental path: snapshot-scoped base fold + this eval's
            # own plan deltas -- O(plan) per eval instead of O(allocs)
            usage = self._pack_usage_incremental(matrix, nodes, tg)

        feasible = pack_feasibility_cached(
            self.ctx, None, tg, nodes, n_pad,
            alloc_name=places[0].name, matrix=matrix)

        affinities = (list(self.job.affinities) + list(tg.affinities)
                      + [a for t in tg.tasks for a in t.affinities])
        spreads = list(self.job.spreads) + list(tg.spreads)
        existing_counts = self._existing_spread_counts(spreads, tg)
        affinity = pack_affinities_cached(affinities, self.ctx, nodes,
                                          n_pad, matrix=matrix)
        spread_info = pack_spreads_cached(spreads, nodes, n_pad,
                                          tg.count, existing_counts,
                                          matrix=matrix)

        distinct_job_level = any(
            c.operand == CONSTRAINT_DISTINCT_HOSTS
            and str(c.r_target).lower() != "false"
            for c in self.job.constraints)
        distinct_hosts = distinct_job_level or any(
            c.operand == CONSTRAINT_DISTINCT_HOSTS
            and str(c.r_target).lower() != "false"
            for c in tg.constraints)

        # Static port availability per node for this TG's ask
        static_ports = []
        n_dyn = 0
        if tg.networks:
            static_ports = [p.value for p in tg.networks[0].reserved_ports]
            n_dyn = len(tg.networks[0].dynamic_ports)
        static_free = np.ones(n_pad, dtype=bool)
        if static_ports and usage.port_bitmap is not None:
            from .. import native as _native
            static_free = _native.static_ports_free(
                usage.port_bitmap, np.asarray(static_ports, dtype=np.int32))

        limit = self._limit(n, tg, bool(affinities), bool(spreads))

        dtype = np.float64 if self.dtype == "float64" else np.float32
        const = make_node_const(matrix, feasible, affinity, distinct_hosts,
                                spread_info, perm, dtype=dtype,
                                distinct_job_level=distinct_job_level)
        init = make_node_state(
            usage, matrix, static_free, perm,
            spread_info.n_spreads if spread_info else 0,
            spread_info.n_values if spread_info else 1,
            spread_counts=(spread_info.initial_counts
                           if spread_info else None), dtype=dtype)

        P = len(places)
        ask = tg.total_resources()
        # core-asking tasks' cpu is REPLACED by mhz_per_core * cores on
        # the candidate node (rank.go:340-344): only non-core tasks
        # contribute to the fixed cpu ask
        ask_cpu_fixed = float(sum(
            t.resources.cpu for t in tg.tasks if t.resources.cores == 0))
        penalty = np.full(P, -1, dtype=np.int32)
        if penalty_nodes_per_place:
            id_to_pos = {nid: int(inv[i])
                         for i, nid in enumerate(matrix.node_ids)}
            for pi, pen in enumerate(penalty_nodes_per_place):
                if pen:
                    pos = id_to_pos.get(next(iter(pen)))
                    if pos is not None:
                        penalty[pi] = pos
        batch = PlacementBatch(
            ask_cpu=np.full(
                P, ask_cpu_fixed if ask_cores_total else float(ask.cpu),
                dtype=dtype),
            ask_mem=np.full(P, float(ask.memory_mb), dtype=dtype),
            ask_disk=np.full(P, float(ask.disk_mb), dtype=dtype),
            n_dyn_ports=np.full(P, n_dyn, dtype=np.int32),
            has_static=np.full(P, bool(static_ports)),
            limit=np.full(P, limit, dtype=np.int32),
            count=np.full(P, tg.count, dtype=np.int32),
            penalty_idx=penalty,
            active=np.ones(P, dtype=bool),
            ask_cores=(np.full(P, ask_cores_total, dtype=np.int32)
                       if ask_cores_total
                       else np.zeros(0, dtype=np.int32)),
        )
        if ask_cores_total:
            mhz = np.zeros(n_pad, dtype=dtype)
            cores_free = np.zeros(n_pad, dtype=np.int32)
            for pos in range(n):
                node = nodes[order[pos]]
                cpu_res = node.node_resources.cpu
                total_cores = cpu_res.total_core_count
                mhz[pos] = (cpu_res.cpu_shares // total_cores
                            if total_cores else 0)
                # same availability rule as allocs_fit + the selection
                # helper: agent-reserved cores are never free
                reservable = (set(cpu_res.reservable_cores)
                              - set(node.reserved_resources.cores))
                for alloc in proposed_by_node[node.id]:
                    for tr in alloc.allocated_resources.tasks.values():
                        reservable.difference_update(tr.reserved_cores)
                cores_free[pos] = len(reservable)
            const = const._replace(mhz_per_core=mhz)
            init = init._replace(cores_free=cores_free)
        dp = self._pack_distinct_property(tg, nodes, order, n_pad)
        if dp is not None:
            const = const._replace(dp_vidx=dp[0], dp_limit=dp[1],
                                   dp_tg_scope=dp[2])
            init = init._replace(dp_counts=dp[3])

        requests = [r for t in tg.tasks for r in t.resources.devices]
        if requests:
            if proposed_by_node is None:
                proposed_by_node = {
                    node.id: self.ctx.proposed_allocs(node.id)
                    for node in nodes}
            dev = self._pack_devices(tg, requests, nodes, order, n_pad,
                                     proposed_by_node, dtype)
            const = const._replace(dev_aff=dev[0], dev_count=dev[1],
                                   dev_sum_weight=dev[2])
            init = init._replace(dev_free=dev[3])

        ptab = pinit = cand_allocs = None
        if self.preempt:
            ptab, pinit, cand_allocs = self._pack_preemption(
                tg, nodes, order, n_pad, dtype, proposed_by_node)
            if requests and cand_allocs is not None and \
                    self._cands_hold_matching_devices(requests,
                                                      cand_allocs,
                                                      ptab):
                # evicting such a candidate frees matching device
                # instances (rank.go:443 PreemptForDevice territory) --
                # neither the dense nor the windowed preempt kernel
                # models device release; the host iterator does
                from ..server.telemetry import metrics as _tm
                _tm.incr("nomad.solver.device_preempt_host_fallback")
                return None
        # delta-streaming source (ISSUE 20): the store owning the
        # alloc-delta journal + this pack's snapshot index. Snapshots
        # expose the backing store as _store; a bare StateStore (tests,
        # single-shot paths) carries the journal itself.
        delta_store = getattr(self.ctx.state, "_store", None)
        if delta_store is None and hasattr(self.ctx.state,
                                           "alloc_deltas_since"):
            delta_store = self.ctx.state
        return PackedLane(self, tg, places, nodes, order, const, init,
                          batch, np.dtype(dtype).name, self.spread_alg,
                          ptab=ptab, pinit=pinit, cand_allocs=cand_allocs,
                          table_version=getattr(
                              self.ctx.state, "node_table_index", None),
                          matrix=matrix,
                          delta_src=(delta_store, state_index)
                          if delta_store is not None else None,
                          usage_index=usage_index)

    @staticmethod
    def _cands_hold_matching_devices(requests, cand_allocs, ptab) -> bool:
        """Only EVICTABLE candidates matter: rows _pack_preemption masked
        invalid (own job, terminal, beyond the A truncation) can never be
        evicted, so their held devices can never be freed -- scanning
        them would force host fallback for the common grow-an-existing-
        GPU-job case, where the job's own running allocs hold devices."""
        names = [r.name for r in requests]
        # evictable = valid row AND priority-eligible (the kernel's
        # eligible mask; preemption.go:678 delta >= 10 floor) -- the
        # host's PreemptForDevice filters candidates identically, so a
        # device held by an ineligible alloc is equally stuck there
        valid = (np.asarray(ptab.valid)
                 & (int(np.asarray(ptab.job_prio))
                    - np.asarray(ptab.prio) >= 10))
        A = valid.shape[1]
        for pos, cands in enumerate(cand_allocs):
            for a_i, a in enumerate(cands[:A]):
                if not valid[pos, a_i]:
                    continue
                for tr in a.allocated_resources.tasks.values():
                    for d in tr.devices:
                        if any(d.matches_request(n) for n in names):
                            return True
        return False

    def _pack_distinct_property(self, tg, nodes, order, n_pad):
        """distinct_property tables (feasible.go:661, propertyset.go):
        per constraint, a value index per node (-1 = attr missing ->
        infeasible) and current alloc counts per value, seeded from the
        job's existing allocs +/- plan deltas."""
        from ..structs import CONSTRAINT_DISTINCT_PROPERTY
        from ..scheduler.util import resolve_target

        csets = ([(c, False) for c in self.job.constraints
                  if c.operand == CONSTRAINT_DISTINCT_PROPERTY]
                 + [(c, True) for c in tg.constraints
                    if c.operand == CONSTRAINT_DISTINCT_PROPERTY])
        if not csets:
            return None
        Dp = len(csets)

        # the job's live allocs incl. plan placements, minus stops
        # (mirrors DistinctPropertyIterator._satisfies)
        allocs = [a for a in self.ctx.state.allocs_by_job(
            self.job.namespace, self.job.id) if not a.terminal_status()]
        removed = set()
        for na in self.ctx.plan.node_update.values():
            removed.update(a.id for a in na)
        allocs = [a for a in allocs if a.id not in removed]
        for na in self.ctx.plan.node_allocation.values():
            allocs.extend(na)

        vidx = np.full((Dp, n_pad), -1, dtype=np.int32)
        limits = np.ones(Dp, dtype=np.int32)
        tg_scope = np.zeros(Dp, dtype=bool)
        value_maps = []
        for d, (c, is_tg) in enumerate(csets):
            tg_scope[d] = is_tg
            try:
                limits[d] = max(1, int(c.r_target)) if c.r_target else 1
            except ValueError:
                limits[d] = 1
            vmap: Dict[str, int] = {}
            for pos in range(len(order)):
                val, ok = resolve_target(c.l_target, nodes[order[pos]])
                if not ok:
                    continue
                key = str(val)
                if key not in vmap:
                    vmap[key] = len(vmap)
                vidx[d, pos] = vmap[key]
            value_maps.append(vmap)

        Vd = max(2, int(2 ** np.ceil(np.log2(max(
            max((len(m) for m in value_maps), default=1), 1)))))
        counts = np.zeros((Dp, Vd), dtype=np.int32)
        node_cache: Dict[str, object] = {}
        for a in allocs:
            node = node_cache.get(a.node_id)
            if node is None:
                node = self.ctx.state.node_by_id(a.node_id)
                node_cache[a.node_id] = node
            if node is None:
                continue
            for d, (c, is_tg) in enumerate(csets):
                if is_tg and a.task_group != tg.name:
                    continue
                val, ok = resolve_target(c.l_target, node)
                if ok:
                    gi = value_maps[d].get(str(val))
                    if gi is not None:
                        counts[d, gi] += 1
        return vidx, limits, tg_scope, counts

    def _pack_devices(self, tg, requests, nodes, order, n_pad,
                      proposed_by_node, dtype):
        """Device tables (feasible.go:1270 DeviceChecker + device.go
        allocator): per request r and matching node group g, the affinity
        score and free instance count (capacity minus proposed usage)."""
        from ..scheduler.rank import DeviceAllocator

        R = len(requests)
        # per node: count matching groups to size the Gd axis
        per_node_groups = []
        max_g = 1
        for pos in range(len(order)):
            node = nodes[order[pos]]
            groups = list(node.node_resources.devices)
            per_node_groups.append(groups)
            max_g = max(max_g, len(groups))
        Gd = int(2 ** np.ceil(np.log2(max(max_g, 1))))

        aff = np.zeros((R, Gd, n_pad), dtype=dtype)
        free = np.full((R, Gd, n_pad), -1, dtype=np.int32)
        counts = np.asarray([r.count for r in requests], dtype=np.int32)
        sum_w = 0.0
        for r in requests:
            if r.affinities:
                sum_w += sum(abs(float(a.weight)) for a in r.affinities)

        for pos, groups in enumerate(per_node_groups):
            if not groups:
                continue
            node = nodes[order[pos]]
            allocator = DeviceAllocator(self.ctx, node)
            allocator.add_allocs(proposed_by_node[node.id])
            for g_i, group in enumerate(groups):
                used = allocator.used.get(group.id_string(), set())
                n_free = sum(1 for i in group.instance_ids if i not in used)
                for r_i, req in enumerate(requests):
                    if not group.matches_request(req.name):
                        continue
                    if req.constraints and not self._dev_constraints_ok(
                            group, req.constraints):
                        continue
                    free[r_i, g_i, pos] = n_free
                    aff[r_i, g_i, pos] = self._dev_affinity_score(
                        group, req)
        return aff, counts, np.asarray(sum_w, dtype=dtype), free

    def _dev_constraints_ok(self, group, constraints) -> bool:
        from ..scheduler.feasible import DeviceChecker
        return DeviceChecker._check_device_constraints(
            _DeviceShim(self.ctx), group, constraints)

    def _dev_affinity_score(self, group, req) -> float:
        from ..scheduler.feasible import DeviceChecker, check_constraint
        score = 0.0
        if req.affinities:
            for a in req.affinities:
                lval, l_ok = DeviceChecker._resolve_device_target(
                    a.l_target, group)
                rval, r_ok = DeviceChecker._resolve_device_target(
                    a.r_target, group)
                if check_constraint(self.ctx, a.operand, lval, rval,
                                    l_ok, r_ok):
                    score += float(a.weight)
        return score

    def _pack_preemption(self, tg, nodes, order, n_pad, dtype,
                         proposed_by_node):
        """Build PreemptTables in shuffled node order: every proposed alloc
        becomes a candidate row (rows keep proposed_allocs order so dense
        argmin ties break like the host's in-order scan); ineligible rows
        (own job, terminal) are masked invalid
        (reference: preemption.go setCandidates/filterAndGroup :666)."""
        from .binpack import PreemptState, PreemptTables
        import jax.numpy as jnp

        per_node = []          # shuffled order: list of candidate allocs
        max_a = 1
        for pos in range(n_pad):
            if pos < len(order):
                allocs = proposed_by_node[nodes[order[pos]].id]
            else:
                allocs = []
            per_node.append(allocs)
            max_a = max(max_a, len(allocs))
        A = int(2 ** np.ceil(np.log2(max(max_a, 8))))

        cpu = np.zeros((n_pad, A), dtype=dtype)
        mem = np.zeros((n_pad, A), dtype=dtype)
        disk = np.zeros((n_pad, A), dtype=dtype)
        prio = np.zeros((n_pad, A), dtype=np.int32)
        maxp = np.zeros((n_pad, A), dtype=np.int32)
        grp = np.full((n_pad, A), -1, dtype=np.int32)
        dyn_ports = np.zeros((n_pad, A), dtype=np.int32)
        static_rel = np.zeros((n_pad, A), dtype=bool)
        valid = np.zeros((n_pad, A), dtype=bool)

        group_idx: Dict[Tuple[str, str, str], int] = {}
        # dyn_ports/static_rel stay zero: preempt-eligible TGs never ask
        # for networks (tg_solver_eligible), so there are no port asks to
        # release toward; the kernel columns exist for a future dense
        # network-preemption path (preemption.go:273).

        for pos, allocs in enumerate(per_node):
            for a_i, alloc in enumerate(allocs[:A]):
                cr = alloc.allocated_resources.comparable()
                cpu[pos, a_i] = cr.cpu_shares
                mem[pos, a_i] = cr.memory_mb
                disk[pos, a_i] = cr.disk_mb
                p = alloc.job.priority if alloc.job is not None else 50
                prio[pos, a_i] = p
                mp = 0
                if alloc.job is not None:
                    atg = alloc.job.lookup_task_group(alloc.task_group)
                    if atg is not None and atg.migrate is not None:
                        mp = atg.migrate.max_parallel
                maxp[pos, a_i] = mp
                key = (alloc.namespace, alloc.job_id, alloc.task_group)
                if key not in group_idx:
                    group_idx[key] = len(group_idx)
                grp[pos, a_i] = group_idx[key]
                # host set_candidates/filter skips own-job, terminal and
                # job-less allocs (scheduler/preemption.py:58,91-94)
                valid[pos, a_i] = (
                    alloc.job is not None
                    and (alloc.namespace, alloc.job_id)
                    != (self.job.namespace, self.job.id)
                    and not alloc.terminal_status())

        G = int(2 ** np.ceil(np.log2(max(len(group_idx), 4))))
        counts = np.zeros(G, dtype=np.int32)
        for na in self.ctx.plan.node_preemptions.values():
            for a in na:
                key = (a.namespace, a.job_id, a.task_group)
                gi = group_idx.get(key)
                if gi is not None:
                    counts[gi] += 1

        ptab = PreemptTables(
            cpu=cpu, mem=mem, disk=disk, prio=prio, maxp=maxp, grp=grp,
            dyn_ports=dyn_ports, static_rel=static_rel, valid=valid,
            job_prio=np.asarray(self.job.priority, dtype=np.int32))
        pinit = PreemptState(
            evicted=np.zeros((n_pad, A), dtype=bool), counts=counts)
        return ptab, pinit, per_node

    def materialize(self, lane: PackedLane, chosen, scores, n_yielded,
                    evict_rows=None) -> List[TpuPlacement]:
        """Map solved shuffled positions back to nodes, assigning real
        ports by replaying the deterministic NetworkIndex per node; map
        eviction rows back to the Allocations to preempt."""
        tg, places, nodes, order = (lane.tg, lane.places, lane.nodes,
                                    lane.order)
        out: List[TpuPlacement] = []
        net_indexes: Dict[str, NetworkIndex] = {}
        dev_allocators: Dict[str, object] = {}
        core_used: Dict[str, set] = {}
        has_devices = any(t.resources.devices for t in tg.tasks)
        # uniform simple lane (no ports/cores/devices): every placement
        # gets IDENTICAL resources -- build the object graph once and
        # share it, instead of 3 dataclass constructions per placement
        shared_res = None
        if (not tg.networks and not has_devices
                and not any(t.resources.cores > 0 for t in tg.tasks)):
            shared_res = AllocatedResources(
                tasks={t.name: AllocatedTaskResources(
                    cpu_shares=t.resources.cpu,
                    memory_mb=t.resources.memory_mb)
                    for t in tg.tasks},
                shared=AllocatedSharedResources(
                    disk_mb=tg.ephemeral_disk.size_mb))
            # warm the instance-cached comparable view once: every
            # downstream consumer (plan verify entries, alloc-table
            # upsert derivation) hits the shared object's cache instead
            # of each paying the first-call reduction
            shared_res.comparable()
        for pi, place in enumerate(places):
            pos = int(chosen[pi])
            if pos < 0:
                out.append(TpuPlacement(place, None, None, None, 0.0,
                                        int(n_yielded[pi])))
                continue
            node = nodes[order[pos]]
            preempted = None
            if evict_rows is not None and lane.cand_allocs is not None:
                row = evict_rows[pi]
                if row.any():
                    cands = lane.cand_allocs[pos]
                    preempted = [cands[ai] for ai in np.nonzero(row)[0]
                                 if ai < len(cands)]
            if shared_res is not None:
                out.append(TpuPlacement(
                    place, node, shared_res.tasks, shared_res.shared,
                    float(scores[pi]), int(n_yielded[pi]),
                    preempted_allocs=preempted,
                    resources_prebuilt=shared_res))
                continue
            task_resources = {}
            dev_failed = False
            for task in tg.tasks:
                tr = AllocatedTaskResources(
                    cpu_shares=task.resources.cpu,
                    memory_mb=task.resources.memory_mb)
                if task.resources.cores > 0:
                    # replay the host's deterministic core selection (the
                    # SHARED helper -- core-id parity depends on it)
                    from ..scheduler.rank import select_reserved_cores
                    used = core_used.get(node.id)
                    if used is None:
                        used = set()
                        for al in self.ctx.proposed_allocs(node.id):
                            used.update(al.allocated_resources
                                        .comparable().reserved_cores)
                        core_used[node.id] = used
                    cores = select_reserved_cores(
                        node, used, task.resources.cores)
                    if cores is None:
                        dev_failed = True   # count-exact fit should
                        break               # prevent this; stay safe
                    used.update(cores)
                    tr.reserved_cores = cores
                    cpu_res = node.node_resources.cpu
                    if cpu_res.total_core_count:
                        tr.cpu_shares = (
                            cpu_res.cpu_shares
                            // cpu_res.total_core_count) * len(cores)
                if has_devices and task.resources.devices:
                    # replay the deterministic DeviceAllocator on the
                    # chosen node for exact instance ids (device.go)
                    from ..scheduler.rank import DeviceAllocator
                    allocator = dev_allocators.get(node.id)
                    if allocator is None:
                        allocator = DeviceAllocator(self.ctx, node)
                        allocator.add_allocs(
                            self.ctx.proposed_allocs(node.id))
                        dev_allocators[node.id] = allocator
                    for req in task.resources.devices:
                        offer, _sum_aff, derr = allocator.assign_device(req)
                        if offer is None:
                            dev_failed = True
                            break
                        allocator.add_reserved(offer)
                        tr.devices.append(offer)
                    if dev_failed:
                        break
                task_resources[task.name] = tr
            if dev_failed:
                out.append(TpuPlacement(place, None, None, None, 0.0,
                                        int(n_yielded[pi])))
                continue
            alloc_resources = None
            if tg.networks:
                idx = net_indexes.get(node.id)
                if idx is None:
                    idx = NetworkIndex()
                    idx.set_node(node)
                    # lazily fetch proposed allocs only for chosen nodes
                    idx.add_allocs(self.ctx.proposed_allocs(node.id))
                    net_indexes[node.id] = idx
                offer, err = idx.assign_ports([tg.networks[0]])
                if offer is None:
                    out.append(TpuPlacement(place, None, None, None, 0.0,
                                            int(n_yielded[pi])))
                    continue
                for pm in offer.ports:
                    idx.add_reserved_port(
                        pm.value, idx._network_for_ip(pm.host_ip))
                alloc_resources = AllocatedSharedResources(
                    disk_mb=tg.ephemeral_disk.size_mb, ports=offer.ports)
            out.append(TpuPlacement(place, node, task_resources,
                                    alloc_resources, float(scores[pi]),
                                    int(n_yielded[pi]),
                                    preempted_allocs=preempted))
        return out

    @staticmethod
    def _node_slots(table, matrix, nodes, n_pad):
        """node -> table-slot array for this eval's node ordering, cached
        on the (immutable, version-keyed) NodeMatrix: slots are stable for
        a node's lifetime, and the 10K-iteration Python lookup loop ran
        under the store lock on every lane pack (a top leaf in the
        headline e2e profile). Only fully-resolved maps are cached, so a
        node that registers with the table later is re-looked-up."""
        cached = getattr(matrix, "_table_slots", None)
        if cached is not None and cached[0] is table:
            return cached[1]
        slots = np.full(n_pad, -1, dtype=np.int32)
        slots[:len(nodes)] = np.fromiter(
            map(table.node_slot_of, (n.id for n in nodes)),
            dtype=np.int32, count=len(nodes))
        if len(nodes) == 0 or slots[:len(nodes)].min() >= 0:
            matrix._table_slots = (table, slots)
        return slots

    def _pack_usage_from_table(self, table, matrix, nodes, tg):
        """Fast marshalling: fold the state store's tensor-resident alloc
        table via the native kernels (nomad_tpu/native.py), then overlay
        this eval's plan deltas (stops/preemptions/placements so far) --
        equivalent to folding ctx.proposed_allocs per node, without the
        O(nodes x allocs) Python walk. Returns the usage and the state
        index it holds every alloc up to."""
        from ..tensor.pack import UsageState
        n, n_pad = len(nodes), matrix.n_pad
        store = getattr(self.ctx.state, "_store", None)
        lock = store._lock if store is not None else None
        folded_at = self.ctx.state.latest_index()

        with_ports = bool(tg.networks)
        with (lock if lock is not None else contextlib.nullcontext()):
            if store is not None:
                # the table folded here is the live one: how far its
                # last alloc write lies past the snapshot whose index
                # seeds this eval's shuffle (PERF.md open question 1)
                from ..server.telemetry import metrics as _tm
                folded_at = store._table_index.get("allocs", 0)
                _tm.sample("nomad.solver.pack_usage_ahead", float(max(
                    0, folded_at - self.ctx.state.index)))
            # fold cache: all lanes of one barrier generation pack from
            # the same table version against the same (version-keyed)
            # matrix -- fold once, hand out copies (the overlay mutates
            # usage arrays in place). Port lanes skip the cache: their
            # port_words can be 80MB and are cheaper to refold.
            cached = getattr(matrix, "_fold_cache", None)
            packed = None
            if not with_ports and cached is not None \
                    and cached[0] is table and cached[1] == table.version:
                packed = cached[2]
            if packed is not None:
                from .. import statecheck
                if statecheck._ACTIVE:
                    # the served fold's version token must match the
                    # table version this lane packs under (statecheck
                    # check e; the hit condition above guarantees it --
                    # this guards the keying against refactors)
                    statecheck.note_memo_served(
                        "fold_cache", cached[1], table.version)
            if packed is None:
                slots = self._node_slots(table, matrix, nodes, n_pad)
                packed = table.pack(n_pad, slots, with_ports,
                                    port_words_seed=matrix.port_bitmap)
                if not with_ports:
                    # the cached fold is shared across every lane of the
                    # generation; each lane copies before overlaying, so
                    # freeze the shared arrays to make that contract
                    # enforced (jitcheck/statecheck frozen-memo
                    # invariant) instead of conventional
                    from ..tensor.pack import _freeze
                    for _arr in (packed["used_cpu"], packed["used_mem"],
                                 packed["used_disk"], packed["dyn_used"],
                                 packed["row_slots"]):
                        _freeze(_arr)
                    matrix._fold_cache = (table, table.version, packed)
            placed, placed_job = table.count_placed(
                n_pad, packed["row_slots"], self.job.namespace, self.job.id,
                tg.name)
        if not with_ports:
            # cached arrays are shared across lanes: the overlay below
            # mutates usage in place, so each lane works on copies
            packed = dict(packed,
                          used_cpu=packed["used_cpu"].copy(),
                          used_mem=packed["used_mem"].copy(),
                          used_disk=packed["used_disk"].copy(),
                          dyn_used=packed["dyn_used"].copy())

        usage = UsageState(
            used_cpu=packed["used_cpu"], used_mem=packed["used_mem"],
            used_disk=packed["used_disk"], placed_jobtg=placed,
            placed_job=placed_job, port_bitmap=packed["port_words"],
            dyn_used=packed["dyn_used"])
        self._overlay_plan_deltas(usage, nodes, tg)
        return usage, folded_at

    def _pack_usage_incremental(self, matrix, nodes, tg):
        """Incremental usage packing (the pack-cache path when the alloc
        table can't serve): the job-independent base fold over the
        snapshot's allocs is memoized PER SNAPSHOT (all evals of a
        barrier generation share it), each eval copies the base, rebuilds
        its own job's placed counts from that job's (small) alloc set and
        overlays only its plan deltas -- semantically identical to
        folding ctx.proposed_allocs per node, without the per-eval
        O(nodes x allocs) walk. Bases carrying a port bitmap are refolded
        per eval rather than memoized (an 80MB bitmap per snapshot is the
        same trade _pack_usage_from_table's fold cache makes)."""
        from ..tensor.pack import (
            UsageState, _stat_incr, fold_usage_base, freeze_usage_base)

        snap = self.ctx.state
        token = snap.latest_index()
        base = None
        # matrix-attached memo: the matrix is stable across snapshots
        # while the node table is unchanged, so a base folded for an
        # EARLIER snapshot catches up by applying the alloc deltas the
        # store journaled in between (_bump delta context) -- O(changed
        # allocs) per snapshot instead of O(all allocs)
        store = getattr(snap, "_store", snap)
        ent = getattr(matrix, "_usage_base", None)
        if ent is not None and ent[0] is store:
            if ent[1] == token:
                base = ent[2]
                _stat_incr("usage_base_hits")
                from .. import statecheck
                if statecheck._ACTIVE:
                    # version-token discipline (statecheck check e): a
                    # hit must serve exactly the snapshot's index
                    statecheck.note_memo_served(
                        "usage_base", ent[1], token)
            elif ent[1] < token:
                base = self._catch_up_usage_base(
                    matrix, store, ent, token)
        if base is None:
            base = fold_usage_base(
                matrix, nodes,
                lambda nid: [a for a in snap.allocs_by_node(nid)
                             if not a.client_terminal_status()])
            _stat_incr("usage_base_misses")
            if base["ports"] is None:
                freeze_usage_base(base)
                matrix._usage_base = (store, token, base)

        n_pad = matrix.n_pad
        placed = np.zeros(n_pad, dtype=np.int32)
        placed_job = np.zeros(n_pad, dtype=np.int32)
        pos_of = matrix.__dict__.get("_pos_index")
        if pos_of is None:
            pos_of = {nid: i for i, nid in enumerate(matrix.node_ids)}
            matrix._pos_index = pos_of
        for a in snap.allocs_by_job(self.job.namespace, self.job.id):
            if a.client_terminal_status():
                continue
            i = pos_of.get(a.node_id)
            if i is None:
                continue
            placed_job[i] += 1
            if a.task_group == tg.name:
                placed[i] += 1
        usage = UsageState(
            used_cpu=base["used_cpu"].copy(),
            used_mem=base["used_mem"].copy(),
            used_disk=base["used_disk"].copy(),
            placed_jobtg=placed, placed_job=placed_job,
            port_bitmap=(base["ports"].copy()
                         if base["ports"] is not None else None),
            dyn_used=base["dyn_used"].copy())
        self._overlay_plan_deltas(usage, nodes, tg)
        return usage

    def _catch_up_usage_base(self, matrix, store, ent, token):
        """Advance a stale usage base to ``token`` by applying the
        (old, new) alloc pairs the store journaled between the base's
        index and the snapshot's -- the incremental-memo half of ISSUE
        6's delta path. Returns the caught-up base (also re-memoized on
        the matrix), or None when the journal can't cover the span or a
        delta touches port state (refold instead)."""
        from ..tensor.pack import _stat_incr

        deltas_fn = getattr(store, "alloc_deltas_since", None)
        if deltas_fn is None:
            return None
        covered, pairs = deltas_fn(ent[1], upto=token)
        if not covered:
            return None
        pos_of = matrix.__dict__.get("_pos_index")
        if pos_of is None:
            pos_of = {nid: i for i, nid in enumerate(matrix.node_ids)}
            matrix._pos_index = pos_of
        old_base = ent[2]
        uc = old_base["used_cpu"].copy()
        um = old_base["used_mem"].copy()
        ud = old_base["used_disk"].copy()
        for old, new in pairs:
            for a, sign in ((old, -1), (new, +1)):
                if a is None or a.client_terminal_status():
                    continue
                i = pos_of.get(a.node_id)
                if i is None:
                    continue
                if a.allocated_resources.all_ports():
                    return None     # port state entered the base: refold
                cr = a.allocated_resources.comparable()
                uc[i] += sign * cr.cpu_shares
                um[i] += sign * cr.memory_mb
                ud[i] += sign * cr.disk_mb
        base = {"used_cpu": uc, "used_mem": um, "used_disk": ud,
                "ports": None, "dyn_used": old_base["dyn_used"]}
        from ..tensor.pack import freeze_usage_base
        freeze_usage_base(base)
        matrix._usage_base = (store, token, base)
        _stat_incr("usage_base_delta_hits")
        return base

    def _overlay_plan_deltas(self, usage, nodes, tg) -> None:
        """Apply this eval's in-flight plan to the packed usage: stops and
        preemptions release resources, placements (incl. in-place updates,
        which REPLACE their existing row) consume them -- the semantics of
        EvalContext.proposed_allocs (context.go:176)."""
        pos_of = {node.id: i for i, node in enumerate(nodes)}
        plan = self.ctx.plan
        ns, jid, tgn = self.job.namespace, self.job.id, tg.name

        def ports_of(a):
            return a.allocated_resources.all_ports()

        def adjust(a, sign: int) -> None:
            pos = pos_of.get(a.node_id)
            if pos is None:
                return
            if sign < 0 and a.client_terminal_status():
                return  # never counted in the table
            cr = a.allocated_resources.comparable()
            usage.used_cpu[pos] += sign * cr.cpu_shares
            usage.used_mem[pos] += sign * cr.memory_mb
            usage.used_disk[pos] += sign * cr.disk_mb
            if a.namespace == ns and a.job_id == jid:
                usage.placed_job[pos] += sign
                if a.task_group == tgn:
                    usage.placed_jobtg[pos] += sign
            node = nodes[pos]
            lo = node.node_resources.min_dynamic_port
            hi = node.node_resources.max_dynamic_port
            ports = ports_of(a)
            if not ports:
                return
            bitmap = usage.ensure_bitmap(len(usage.used_cpu))
            for p in ports:
                if not 0 <= p < 65536:
                    continue
                word, bit = p >> 5, np.uint32(1 << (p & 31))
                if sign > 0:
                    if not bitmap[pos, word] & bit:
                        bitmap[pos, word] |= bit
                        if lo <= p <= hi:
                            usage.dyn_used[pos] += 1
                else:
                    if bitmap[pos, word] & bit:
                        bitmap[pos, word] &= ~bit
                        if lo <= p <= hi:
                            usage.dyn_used[pos] -= 1

        # Subtract against the STORED alloc (what the table counted) --
        # plan stop entries are narrow stubs (structs/alloc.py
        # _plan_stub) and may carry overridden client statuses. A
        # missing stored alloc is SKIPPED, matching the reference's
        # ProposedAllocs identity-set semantics (context.go:176:
        # existing-from-snapshot minus stops by id): an alloc absent
        # from state was never folded into usage, so subtracting its
        # footprint would double-free.
        seen_ids = set()
        for allocs in plan.node_update.values():
            for a in allocs:
                stored = self.ctx.state.alloc_by_id(a.id)
                if stored is not None:
                    adjust(stored, -1)
                seen_ids.add(a.id)
        for allocs in plan.node_preemptions.values():
            for a in allocs:
                if a.id not in seen_ids:
                    stored = self.ctx.state.alloc_by_id(a.id)
                    if stored is not None:
                        adjust(stored, -1)
                    seen_ids.add(a.id)
        for allocs in plan.node_allocation.values():
            for a in allocs:
                # in-place update: the plan alloc replaces the stored one
                stored = self.ctx.state.alloc_by_id(a.id)
                if stored is not None and a.id not in seen_ids:
                    adjust(stored, -1)
                adjust(a, +1)

    def _limit(self, n: int, tg, has_affinities: bool,
               has_spreads: bool) -> int:
        """(reference: stack.go:82-95 log2 limit, :176-185 spread override).
        The override is sticky across TGs within one eval, exactly like the
        host LimitIterator whose limit is never restored after a
        spread/affinity TG raises it."""
        if has_affinities or has_spreads:
            limit = tg.count if tg.count >= 100 else 100
            self._current_limit = limit
            return limit
        if self._current_limit is not None:
            return self._current_limit
        limit = 2
        if not self.batch_mode and n > 1:
            log_limit = int(math.ceil(math.log2(n)))
            if log_limit > limit:
                limit = log_limit
        return limit

    def _existing_spread_counts(self, spreads, tg):
        """Per spread: current alloc counts per attribute value
        (reference: propertyset.go UsedCount seeding)."""
        from ..scheduler.util import resolve_target
        if not spreads:
            return None
        stopped = set()
        for na in self.ctx.plan.node_update.values():
            stopped.update(a.id for a in na)
        allocs = [a for a in self.ctx.state.allocs_by_job(
            self.job.namespace, self.job.id)
            if a.id not in stopped and not a.terminal_status()
            and a.task_group == tg.name]
        out = []
        for s in spreads:
            counts: Dict[str, int] = {}
            for a in allocs:
                node = self.ctx.state.node_by_id(a.node_id)
                if node is None:
                    continue
                v, ok = resolve_target(s.attribute, node)
                if ok:
                    counts[str(v)] = counts.get(str(v), 0) + 1
            out.append(counts)
        return out
