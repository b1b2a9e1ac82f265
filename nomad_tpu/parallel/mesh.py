"""Device-mesh sharding of the solver: the multi-chip scale path.

The reference scales scheduling by running NumCPU workers per server x M
servers against snapshots (SURVEY.md section 2.6); the TPU-native analog
shards two axes over a jax.sharding.Mesh:
  - ``evals``  (data-parallel): independent evaluations, one snapshot each;
  - ``nodes``  (model-parallel): the fleet axis inside every eval -- fit and
    scoring are elementwise over nodes, and the select/argmax reductions
    become cross-shard collectives that XLA inserts automatically (psum/
    all-gather over ICI), per the standard pick-mesh -> annotate ->
    let-XLA-insert-collectives recipe.

No NCCL/MPI analog is needed: collectives ride ICI within a slice and DCN
across slices, and the host-side control plane (raft-analog, plan applier)
stays on CPU exactly as nomad/plan_apply.go stays authoritative.

This module is also the repo's ONE home for sharding intent (ISSUE 15):
``SPEC_GROUPS`` declares the intended ``PartitionSpec`` per dispatch tree
group, every ``Mesh`` is built by a factory here, and every
``jax.device_put`` carrying a ``NamedSharding`` lives here -- enforced
statically by nomadlint's spec-declared / mesh-factory / no-implicit-put
rules and at runtime by the sharding-discipline sanitizer
(nomad_tpu/shardcheck.py), which compares what XLA actually did against
what this registry declares.
"""
from __future__ import annotations

import functools
import os
import threading
from typing import Optional

import numpy as np


def mesh_enabled() -> bool:
    """The mesh-execution master switch (ISSUE 19). On (default) the
    dispatch stack shards over the device mesh whenever >1 device is
    attached and the shapes divide a grid; ``NOMAD_TPU_MESH=0`` makes
    every factory below refuse a mesh, so every solve runs the
    single-device program path bit-for-bit -- the rollback lever the
    OPERATIONS.md mesh runbook documents."""
    return os.environ.get("NOMAD_TPU_MESH", "1") != "0"


def _single_flight(fn):
    """Serialize program-factory invocations: lru_cache does not
    single-flight, so two pipelined generations racing one cold
    (mesh, statics) bucket would both trace/compile the program --
    wasted seconds of XLA work and jitcheck's fresh-identical-closure
    retrace pattern (same guard as the solver/binpack.py factories)."""
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


def make_mesh(n_devices: Optional[int] = None,
              eval_parallel: Optional[int] = None):
    """Build a 2D (evals, nodes) mesh over the available devices."""
    import jax
    from jax.sharding import Mesh

    devices = jax.devices()
    if n_devices is not None:
        devices = devices[:n_devices]
    n = len(devices)
    if eval_parallel is None:
        # favor eval-parallelism (perfectly parallel) over node sharding:
        # give the evals axis the LARGER factor of the balanced split
        eval_parallel = n
        for cand in range(int(np.floor(np.sqrt(n))), 0, -1):
            if n % cand == 0:
                eval_parallel = n // cand
                break
    node_parallel = n // eval_parallel
    dev_grid = np.asarray(devices).reshape(eval_parallel, node_parallel)
    return Mesh(dev_grid, ("evals", "nodes"))


def pick_mesh(e: int, n: int, n_devices: Optional[int] = None):
    """Choose an (evals, nodes) grid that divides THIS batch's shapes:
    e_par = largest divisor of the eval axis that fits the device count,
    n_par = largest divisor of the (padded) node axis using the remaining
    devices. Falls back to pure node-sharding for E=1, so a single big
    eval still spreads over all chips. Returns None when fewer than 2
    devices can be used. ``NOMAD_TPU_MESH=0`` always returns None --
    the one chokepoint every production mesh route picks through."""
    import jax

    if not mesh_enabled():
        return None
    d = n_devices if n_devices is not None else jax.device_count()
    if d <= 1 or e < 1 or n < 1:
        return None

    def largest_divisor(x: int, cap: int) -> int:
        return next(c for c in range(min(x, cap), 0, -1) if x % c == 0)

    # choose the split that uses the MOST devices (a greedy eval-first
    # pick can strand chips, e.g. E=3 on 8 devices -> 3x2 when 1x8 uses
    # all); prefer eval-parallelism among equals (perfectly parallel)
    best = (1, 1)
    for e_par in range(min(e, d), 0, -1):
        if e % e_par:
            continue
        n_par = largest_divisor(n, d // e_par)
        if e_par * n_par > best[0] * best[1]:
            best = (e_par, n_par)
    e_par, n_par = best
    if e_par * n_par < 2:
        return None
    return make_mesh(e_par * n_par, eval_parallel=e_par)


@functools.lru_cache(maxsize=None)
def eval_axis_mesh(n_devices: int):
    """1D ('evals',) mesh over the first ``n_devices`` devices -- the
    wave/wave-preempt compact transports shard only their fused eval
    axis (per-step work is O(B); nothing N-heavy to split)."""
    import jax
    from jax.sharding import Mesh

    return Mesh(np.asarray(jax.devices()[:n_devices]), ("evals",))


# ----------------------------------------------------------------------
# sharding-spec registry (ISSUE 15): the declared PartitionSpec per
# dispatch tree group.  ``shard_solver_inputs`` puts by these specs, the
# shardcheck sanitizer compares every mesh callable's actual shardings
# against them, and ``shardcheck --compile-audit`` prints the per-group
# per-shard byte budgets they imply.  A spec change here IS the reviewed
# sharding-contract change; constructing PartitionSpec/NamedSharding
# anywhere outside nomad_tpu/parallel/ is a lint violation
# (spec-declared).


def const_partition_specs(c):
    """NodeConst: per-node columns shard (evals, nodes); per-eval
    scalars/tables without a node axis shard (evals) only."""
    from jax.sharding import PartitionSpec as P

    return type(c)(
        cpu_cap=P("evals", "nodes"), mem_cap=P("evals", "nodes"),
        disk_cap=P("evals", "nodes"), feasible=P("evals", "nodes"),
        affinity=P("evals", "nodes"), has_affinity=P("evals"),
        distinct_hosts=P("evals"), distinct_job_level=P("evals"),
        spread_vidx=P("evals", None, "nodes"),
        spread_desired=P("evals"), spread_has_targets=P("evals"),
        spread_weights=P("evals"), spread_sum_weights=P("evals"),
        n_spreads=P("evals"),
        dp_vidx=P("evals", None, "nodes"), dp_limit=P("evals"),
        dp_tg_scope=P("evals"),
        dev_aff=P("evals", None, None, "nodes"),
        dev_count=P("evals"), dev_sum_weight=P("evals"),
        mhz_per_core=P("evals", "nodes"))


def state_partition_specs(s):
    """NodeState: usage columns shard (evals, nodes); spread/distinct
    counters are per-eval tables."""
    from jax.sharding import PartitionSpec as P

    return type(s)(
        used_cpu=P("evals", "nodes"), used_mem=P("evals", "nodes"),
        used_disk=P("evals", "nodes"), placed=P("evals", "nodes"),
        placed_job=P("evals", "nodes"),
        static_free=P("evals", "nodes"), dyn_avail=P("evals", "nodes"),
        spread_counts=P("evals"),
        dp_counts=P("evals"),
        dev_free=P("evals", None, None, "nodes"),
        cores_free=P("evals", "nodes"))


def batch_partition_specs(b):
    """PlacementBatch: every per-placement column is (E, P) --
    data-parallel on the eval axis, replicated over node shards."""
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(lambda _leaf: P("evals"), b)


def output_partition_specs(out):
    """Mesh solve outputs gather fully replicated: the select/argmax
    collectives ARE the program's sanctioned cross-shard traffic, and
    the single bulk fetch reads identical buffers from any device."""
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(lambda _leaf: P(), out)


def eval_axis_partition_specs(tree):
    """Wave/wave-preempt compact tables: leading fused-eval axis only."""
    import jax
    from jax.sharding import PartitionSpec as P

    return jax.tree.map(lambda _leaf: P("evals"), tree)


def lpq_partition_specs(tree):
    """LPQ relaxation inputs ``(V, feas, ask, pcount, free, active)``:
    the (L, N) lane-major matrices shard lanes on 'evals'; the small
    per-lane ask/count vectors and the (N, 3) free-capacity table
    replicate.  The dual-price ascent's cross-shard combine is an
    all-gather of the lane shards, NOT a psum -- gathering moves bytes
    without re-associating the float reduction, which keeps the mesh
    program bit-for-bit the single-device one (see mesh_lpq_fn)."""
    from jax.sharding import PartitionSpec as P

    if len(tree) != 6:
        raise ValueError(
            f"lpq_in expects the 6-tuple (V, feas, ask, pcount, free, "
            f"active), got {len(tree)} leaves")
    return (P("evals", None), P("evals", None), P(), P(), P(), P())


# group tag -> spec-tree builder; the tags line up with the transfer
# ledger's tree groups (solver/xferobs.py) so the shardcheck per-shard
# byte rows land next to the bytes they decompose
SPEC_GROUPS = {
    "mesh_const": const_partition_specs,
    "mesh_init": state_partition_specs,
    "mesh_batch": batch_partition_specs,
    "mesh_out": output_partition_specs,
    "compact": eval_axis_partition_specs,
    "compact_preempt": eval_axis_partition_specs,
    "lpq_in": lpq_partition_specs,
    "lpq_out": output_partition_specs,
}


def declared_specs(group: str, tree):
    """The registry's intended PartitionSpec tree for ``tree`` under
    ``group`` (KeyError on an unregistered group: a new dispatch tree
    group must declare its sharding here first)."""
    return SPEC_GROUPS[group](tree)


@_single_flight
@functools.lru_cache(maxsize=None)
def mesh_solve_fn(mesh, spread_alg: bool, dtype_name: str):
    """One jitted mesh-sharded dense-solve program per (mesh, static
    args). jax.sharding.Mesh hashes by device grid + axis names, so
    the fresh-but-equal Mesh each pick_mesh() builds hits this cache
    -- the dispatch path used to construct a new ``jax.jit`` closure
    per fused dispatch, which re-traced the whole program every
    generation (the exact steady-state-retrace class jitcheck.py
    exists to catch; nomadlint's no-callsite-jit pins the fix).

    The program returns only (chosen, scores, n_yielded): the trailing
    NodeState the single-device kernel also yields is (E, N)-sized and
    was never read by the mesh route, yet replicated out_shardings
    forced a full cross-shard all-gather of it every dispatch --
    dropping it from the traced outputs lets XLA dead-code the gather
    (the dominant output bytes at fleet-scale N)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..solver.binpack import solve_eval_batch

    return jax.jit(
        lambda c, i, b: solve_eval_batch(
            c, i, b, spread_alg=spread_alg, dtype_name=dtype_name)[:3],
        out_shardings=NamedSharding(mesh, P()))


@_single_flight
@functools.lru_cache(maxsize=None)
def mesh_delta_scatter_fn(mesh, shape: tuple, dtype_str: str,
                          n_upd: int, spec):
    """One jitted mesh-sharded delta-scatter program per (mesh, table
    shape, dtype, update-count bucket, declared spec) -- the ISSUE-20
    device-side update under NOMAD_TPU_MESH. Coordinate formulation
    (the single-device program in solver/constcache.py scatters flat
    indices): a sharded operand must never reshape to 1D across
    shards, so the host unravels the flat diff indices into per-axis
    coordinates and the program scatters in the table's native rank.
    ``out_shardings`` pins the promoted buffer to the SAME declared
    PartitionSpec as the resident table (SPEC_GROUPS discipline): the
    replicated (coords, vals) payload reaches every device and each
    nodes-axis shard keeps exactly the updates that land in its slice
    -- whatever collective XLA inserts for that routing is recorded
    and budgeted by ``shardcheck --compile-audit`` beside the solve
    programs' argmax/all-gather baselines. No donation: the base
    buffer may still be referenced by in-flight dispatches."""
    import jax
    from jax.sharding import NamedSharding

    del dtype_str, n_upd   # dtypes/shapes ride the traced args; they
    #                        key the cache (one program per bucket)
    out = NamedSharding(mesh, spec)
    ndim = len(shape)

    def _apply(buf, coords, vals):
        return buf.at[tuple(coords[d] for d in range(ndim))].set(vals)

    return jax.jit(_apply, out_shardings=out)


def _note_shard_rows(mesh, group: str, tree, specs) -> None:
    """Fold this tree's per-shard declared/actual byte rows into the
    transfer ledger (xferobs ``per_shard``): declared = what the
    registry's spec budgets per device, actual = the shard bytes the
    NamedSharding put actually gives each device. The production-path
    twin of shardcheck's audit rows (same ``d<id>`` labels), so mesh
    dispatches decompose per shard even with the sanitizer off."""
    import jax
    from jax.sharding import NamedSharding

    from ..solver import xferobs

    leaves = jax.tree_util.tree_leaves(tree)
    spec_leaves = jax.tree_util.tree_leaves(specs)
    per_dev = 0
    for leaf, spec in zip(leaves, spec_leaves):
        arr = np.asarray(leaf)
        shard_shape = NamedSharding(mesh, spec).shard_shape(arr.shape)
        per_dev += int(np.prod(shard_shape, dtype=np.int64)
                       * arr.dtype.itemsize)
    for dev in mesh.devices.flat:
        xferobs.note_shard_bytes(group, f"d{dev.id}", per_dev, per_dev)


def shard_solver_inputs(mesh, const, init, batch, version=None,
                        delta_src=None):
    """NamedShardings for solve_eval_batch inputs, by the registry's
    declared specs: leading axis (E) on 'evals'; node-axis (last dim of
    per-node arrays) on 'nodes'.

    The const tree routes through the device-resident cache's
    per-shard path (solver/constcache.py device_put_sharded_cached):
    each shard slice is content-fingerprinted and pinned per device,
    so repeated fleet tables ship zero bytes and a node-table write
    re-uploads only the shards whose slice actually changed.
    ``version`` is the packing snapshot's node_table_index (hygiene
    eviction). The usage tree (mesh_init) routes through the ISSUE-20
    version chain when ``delta_src`` (the packing snapshot's
    (store, index)) is given: journal-covered generations ship only
    the changed elements, replicated, and the mesh-sharded scatter
    (mesh_delta_scatter_fn) applies them into the resident sharded
    buffer under the SAME declared spec -- each nodes-axis shard keeps
    the updates that land in its slice. batch ships fresh -- it
    changes every generation -- but still reports payload and
    per-shard rows so ``nomad.solver.dispatch_bytes`` and the ledger's
    ``per_shard`` decomposition cover every transport path."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..solver import constcache, xferobs
    from ..solver.constcache import note_dispatch_bytes

    def put_fresh(group, tree):
        specs = declared_specs(group, tree)
        total = sum(np.asarray(leaf).nbytes
                    for leaf in jax.tree_util.tree_leaves(tree))
        if xferobs.enabled():
            xferobs.note_payload(group, total)
            _note_shard_rows(mesh, group, tree, specs)
        note_dispatch_bytes(total)
        return jax.tree.map(
            lambda leaf, s: jax.device_put(leaf, NamedSharding(mesh, s)),
            tree, specs)

    def put_chain(group, tree):
        # ISSUE-20 delta route for the usage tree: per-leaf version
        # chain (solver/constcache.py chain_apply) with a mesh-sharded
        # scatter. The fuse arena reuses these host buffers across
        # generations, so chain_apply copies its shadow
        # (copy_shadow=True). Chain keys carry the Mesh itself: a grid
        # change re-installs rather than scattering into a buffer
        # sharded under the old grid.
        store = token = None
        if delta_src is not None:
            store, token = delta_src
            if token is None or not hasattr(store, "alloc_deltas_since"):
                store = token = None
        if store is None:
            return put_fresh(group, tree)
        specs = declared_specs(group, tree)
        leaves, treedef = jax.tree_util.tree_flatten(tree)
        spec_leaves = treedef.flatten_up_to(specs)
        min_b = constcache._min_bytes()
        rep = NamedSharding(mesh, P())
        bufs = []
        shipped = 0
        small_total = 0
        for j, (leaf, spec) in enumerate(zip(leaves, spec_leaves)):
            arr = np.asarray(leaf)
            sh = NamedSharding(mesh, spec)
            if arr.nbytes < min_b:
                # small leaves ARE the delta traffic; ship by spec
                bufs.append(jax.device_put(arr, sh))
                shipped += arr.nbytes
                small_total += arr.nbytes
                continue

            def scatter(buf, shape, dtype_str, idx_p, vals_p,
                        _spec=spec):
                # unravel the flat diff indices into per-axis
                # coordinates (a sharded operand must never reshape to
                # 1D across shards); the replicated puts below ARE the
                # delta payload crossing the wire
                coords = np.ascontiguousarray(np.stack(
                    np.unravel_index(idx_p.astype(np.int64),
                                     shape)).astype(np.int32))
                pc = jax.device_put(coords, rep)
                pv = jax.device_put(vals_p, rep)
                prog = mesh_delta_scatter_fn(
                    mesh, shape, dtype_str, int(idx_p.size), _spec)
                return prog(buf, pc, pv)

            buf, ship_j, _outcome = constcache.chain_apply(
                (group, arr.dtype.str, arr.shape, j, mesh),
                arr, store, token, group,
                put_fn=lambda a, _sh=sh: jax.device_put(a, _sh),
                scatter=scatter,
                idx_width=4 * max(1, arr.ndim),
                copy_shadow=True)
            bufs.append(buf)
            shipped += ship_j
        if xferobs.enabled():
            if small_total:
                xferobs.note_payload(group, small_total)
            _note_shard_rows(mesh, group, tree, specs)
        note_dispatch_bytes(shipped)
        return jax.tree_util.tree_unflatten(treedef, bufs)

    specs = declared_specs("mesh_const", const)
    leaves, treedef = jax.tree_util.tree_flatten(const)
    shardings = [NamedSharding(mesh, s)
                 for s in treedef.flatten_up_to(specs)]
    buffers, _shipped = constcache.device_put_sharded_cached(
        leaves, shardings, group="mesh_const", version=version,
        fallback_put=lambda arr, sh: jax.device_put(arr, sh))
    s_const = jax.tree_util.tree_unflatten(treedef, buffers)
    return (s_const, put_chain("mesh_init", init),
            put_fresh("mesh_batch", batch))


@_single_flight
@functools.lru_cache(maxsize=16)
def mesh_lpq_fn(mesh, L_pad: int, N: int, steps: int):
    """One pjit LPQ-relaxation program per (mesh, shape bucket) --
    same lru + single-flight discipline as mesh_solve_fn.  Lanes (L)
    shard on 'evals' per the lpq_in registry specs; node tables
    replicate.  The per-step softmax/pricing math is shard-local
    (row-wise, bit-exact), and the dual-price load reduction is forced
    through an all-gather (with_sharding_constraint to replicated) so
    the einsum over lanes runs whole on every device: gathering moves
    bytes, not sums, so the mesh output is bit-for-bit the
    single-device program's.  A psum here would re-associate the f32
    reduction and the annealing loop amplifies that ulp noise into
    placement flips (measured on the virtual CPU mesh)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..solver.lpq import _lp_solve_body

    del L_pad  # shapes ride the traced args; L_pad keys the cache
    rep = NamedSharding(mesh, P())
    body = _lp_solve_body(
        N, steps,
        gather=lambda x: jax.lax.with_sharding_constraint(x, rep))
    return jax.jit(body, out_shardings=rep)


def shard_lpq_inputs(mesh, V, feas, ask, pcount, free, active):
    """NamedShardings for the LPQ relaxation inputs by the registry's
    ``lpq_in`` specs, with transfer-ledger attribution (one ``lpq``
    tree group + per-shard rows). No const-cache routing: V/feas are
    usage-dependent and change every solve."""
    import jax
    from jax.sharding import NamedSharding

    from ..solver import xferobs
    from ..solver.constcache import note_dispatch_bytes

    tree = (V, feas, ask, pcount, free, active)
    specs = declared_specs("lpq_in", tree)
    total = sum(np.asarray(a).nbytes for a in tree)
    if xferobs.enabled():
        xferobs.note_payload("lpq", total)
        _note_shard_rows(mesh, "lpq", tree, specs)
    note_dispatch_bytes(total)
    return tuple(jax.device_put(a, NamedSharding(mesh, s))
                 for a, s in zip(tree, specs))


def shard_eval_axis(trees, tag: str = "compact"):
    """Device-put a tuple of (possibly nested) arrays, sharding the
    leading eval axis across ALL attached devices. The fused eval axis
    is embarrassingly data-parallel: each chip runs its lanes' scans
    independently (no collectives; outputs gather on fetch). Callers
    (solver/binpack.py ``_put_eval_sharded``) gate on divisibility;
    ``tag`` is the transfer ledger's tree-group attribution."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..solver import xferobs
    from ..solver.constcache import note_dispatch_bytes

    mesh = eval_axis_mesh(jax.device_count())
    total = sum(
        np.asarray(leaf).nbytes
        for leaf in jax.tree_util.tree_leaves(trees))
    note_dispatch_bytes(total)
    xferobs.note_payload(tag, total)
    sharding = NamedSharding(mesh, P("evals"))
    out = tuple(
        jax.tree_util.tree_map(lambda a: jax.device_put(a, sharding), t)
        for t in trees)
    if xferobs.enabled():
        # per-shard ledger rows like the mesh puts write, with ACTUAL
        # read off the arrays' own shards: an even split is what the
        # spec declares, where the bytes landed is what the runtime did
        landed: dict = {}
        for leaf in jax.tree_util.tree_leaves(out):
            for shard in leaf.addressable_shards:
                landed[shard.device.id] = (landed.get(shard.device.id, 0)
                                           + shard.data.nbytes)
        for dev in mesh.devices.flat:
            xferobs.note_shard_bytes(tag, f"d{dev.id}",
                                     total // mesh.devices.size,
                                     landed.get(dev.id, 0))
    return out
