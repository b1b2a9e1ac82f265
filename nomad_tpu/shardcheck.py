"""Sharding-discipline sanitizer ("shardcheck") for the mesh solver.

ROADMAP-1 routes the fused solve through pjit over a 2D (evals, nodes)
mesh; the whole point is per-shard bytes -- fleet tables split across
chips instead of replicated onto each.  Nothing before this module
enforced that the ``PartitionSpec``s parallel/mesh.py declares match
what XLA actually does: a silently replicated fleet table burns N x the
per-shard HBM budget, an accidental steady-state all-gather re-ships
the table every generation, and a host array slipping into a mesh
callable makes XLA insert the transfer where no ledger sees it.  Each
failure keeps bit-parity -- the solve stays CORRECT -- which is exactly
why it needs a sanitizer, not a test: the fifth sibling of lockcheck /
jitcheck / statecheck / schedcheck, built BEFORE the mesh execution PR
so pjit work inherits the gate the way the multichip dryrun already
inherits jitcheck's.

What it checks while enabled:

  * **spec drift** -- the registry in parallel/mesh.py (``SPEC_GROUPS``)
    declares the intended ``PartitionSpec`` per dispatch tree group
    (const/init sharded on ``("evals", "nodes")`` columns, batch on
    ``("evals",)``, outputs replicated).  Wrapped mesh callables
    compare every argument and output leaf's actual ``.sharding``
    against the declaration and report mismatches with witness stacks;
    the replicated-when-declared-sharded case carries its
    N x-memory-amplification bytes (the exact regression ROADMAP-1's
    per-shard-bytes win dies by).
  * **implicit transfers** -- host ``np.ndarray``s or
    differently-sharded/-meshed arrays entering a mesh callable: XLA
    reshards or uploads them silently, off every ledger.  Device data
    must route through ``shard_solver_inputs`` /
    ``device_put_cached``; anything else is reported with its bytes.
  * **collective budget** -- a compile-time HLO audit
    (``compiled.as_text()`` scan + cost analysis) inventories
    all-gather / all-reduce / reduce-scatter / collective-permute /
    all-to-all instructions per compiled mesh program.  The first
    program compiled for a (mesh shape, static args) family records
    the baseline -- the cross-shard select/argmax reduction is the
    sanctioned budget -- and any later program of the same family
    exceeding it (a refactor sneaking a steady-state gather into the
    solve body) is a violation.
  * **per-shard byte parity** -- for every mesh input leaf, the bytes
    the declared spec says each device should hold vs the bytes its
    actual sharding gives it, folded into the PR-13 transfer ledger as
    per-shard rows under the ``mesh_const/init/batch`` tags
    (``xferobs.note_shard_bytes``) with the same zero-tolerance
    reconciliation (``xferobs.shard_parity()``).

Kill-switch semantics mirror the siblings: OFF by default,
``NOMAD_TPU_SHARDCHECK=0``/unset is a true no-op -- the mesh module's
``mesh_solve_fn`` / ``shard_solver_inputs`` attributes are untouched
and no wrapper is observable anywhere (bitwise-parity-tested on a real
fused dispatch and on the 8-device mesh dryrun).
``NOMAD_TPU_SHARDCHECK=1`` at process start (or ``enable()`` at
runtime, how the conftest fixture runs the multichip-dryrun and
dispatch-pipeline suites) installs the wrappers.  Call sites that
imported ``shard_solver_inputs`` by value before enable keep the raw
function (documented gap, same as jitcheck's pre-enable jits -- the
dispatch stack imports from ``parallel.mesh`` at call time, so the
paths that matter are always covered).

``compile_audit()`` / ``operator shardcheck --compile-audit`` compiles
the registered mesh programs for an 8-device CPU mesh OFFLINE and
prints the collective/bytes inventory without running a server --
the review surface for "what does this sharding contract cost".

State rides the usual surfaces: ``stats.shardcheck`` in
``/v1/agent/self``, ``operator shardcheck [--compile-audit]
[--stacks]`` CLI (exit 1 on spec drift / implicit transfers /
collective excess), the fifth row in ``operator sanitizers``,
``shardcheck.json`` in operator debug bundles, and the
``nomad.shardcheck.{spec_drift,implicit_xfer,collective_excess,
shard_parity}`` counters.

Knobs: ``NOMAD_TPU_SHARDCHECK`` (off; ``1`` installs at import),
``NOMAD_TPU_SHARDCHECK_STACK`` (16: witness stack depth),
``NOMAD_TPU_SHARDCHECK_MAX`` (256: retained reports per class),
``NOMAD_TPU_SHARDCHECK_HLO`` (1: compile-time collective audit; ``0``
skips the AOT lower/compile, which costs one duplicate XLA compile
per mesh program).
"""
from __future__ import annotations

import os
import re
import sys
import threading
import traceback
from typing import Dict, List, Optional

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_ACTIVE = False                  # module-global fast gate
_REAL: dict = {}                 # originals, captured at first enable

# checker-internal state; _slock is a leaf: nothing is acquired under
# it and no user code runs under it
_slock = threading.Lock()

_stack_depth = 16
_max_reports = 256
_hlo_audit = True

_spec_drift: List[dict] = []
_drift_keys: set = set()
_implicit: List[dict] = []
_implicit_keys: set = set()
_collective: List[dict] = []
_collective_keys: set = set()
_shard_parity_reports: List[dict] = []
_parity_keys: set = set()

# collective baselines per program FAMILY (mesh shape x static args);
# the first compiled program of a family records it -- the sanctioned
# cross-shard reduction budget every later shape bucket is held to
_baselines: Dict[tuple, Dict[str, int]] = {}
# per-program audit inventory (family + abstract signature)
_programs: Dict[tuple, dict] = {}

_counters = {
    "wrapped_dispatches": 0, "sanctioned_puts": 0, "leaves_checked": 0,
    "programs_audited": 0, "baselines_recorded": 0, "audit_errors": 0,
    "spec_drift": 0, "implicit_xfer": 0, "collective_excess": 0,
    "shard_parity": 0, "reports_dropped": 0,
}

COLLECTIVE_OPS = ("all-gather", "all-reduce", "reduce-scatter",
                  "collective-permute", "all-to-all")
# instruction forms: "op(" and the async "op-start(" (the matching
# "-done" is the same collective completing, not a second one)
_COLLECTIVE_RE = re.compile(
    r"\b(" + "|".join(COLLECTIVE_OPS) + r")(?:-start)?\(")


def _rel(path: str) -> str:
    if path.startswith(_REPO_ROOT):
        return path[len(_REPO_ROOT) + 1:]
    return path


def _metrics():
    """Telemetry sink, or None mid-teardown -- the sanitizer must
    never take the process down with it."""
    try:
        from .server.telemetry import metrics
        return metrics
    except Exception:  # noqa: BLE001
        return None

def _fmt_stack(limit: Optional[int] = None) -> str:
    try:
        return "".join(traceback.format_stack(
            sys._getframe(2), limit=limit or _stack_depth))
    except Exception:  # noqa: BLE001 -- diagnostics must never raise
        return "<stack unavailable>"


def _note(cls: str, reports: List[dict], keys: set, key: tuple,
          payload: dict) -> None:
    """Record one violation: dedup by key, cap by _max_reports, count
    every occurrence, mirror into the telemetry counter."""
    m = _metrics()
    with _slock:
        _counters[cls] += 1
        if key in keys:
            pass
        elif len(reports) >= _max_reports:
            _counters["reports_dropped"] += 1
        else:
            keys.add(key)
            payload = dict(payload,
                           thread=threading.current_thread().name)
            reports.append(payload)
    if m is not None:
        if cls == "spec_drift":
            m.incr("nomad.shardcheck.spec_drift")
        elif cls == "implicit_xfer":
            m.incr("nomad.shardcheck.implicit_xfer")
        elif cls == "collective_excess":
            m.incr("nomad.shardcheck.collective_excess")
        else:
            m.incr("nomad.shardcheck.shard_parity")


# ----------------------------------------------------------------------
# spec comparison + per-shard byte audit


def _norm_spec(spec) -> tuple:
    """PartitionSpec -> plain tuple with trailing Nones trimmed (the
    canonical form: P('evals') and P('evals', None) shard
    identically)."""
    try:
        parts = tuple(spec)
    except TypeError:
        return ("<unreadable>",)
    while parts and parts[-1] is None:
        parts = parts[:-1]
    return parts


def _spec_axes(spec) -> List[str]:
    out: List[str] = []
    for ax in _norm_spec(spec):
        if ax is None:
            continue
        out.extend(ax if isinstance(ax, tuple) else (ax,))
    return out


def _n_shards(mesh, spec) -> int:
    sizes = dict(mesh.shape)
    n = 1
    for name in _spec_axes(spec):
        n *= int(sizes.get(name, 1))
    return max(n, 1)


def _mesh_key(mesh) -> tuple:
    try:
        return (tuple(d.id for d in mesh.devices.flat),
                tuple(mesh.devices.shape), tuple(mesh.axis_names))
    except Exception:  # noqa: BLE001 -- exotic mesh stand-ins
        return (repr(mesh),)


def _leaf_nbytes(leaf) -> int:
    nbytes = getattr(leaf, "nbytes", None)
    if nbytes is not None:
        return int(nbytes)
    size = getattr(leaf, "size", None)
    itemsize = getattr(getattr(leaf, "dtype", None), "itemsize", None)
    if size is not None and itemsize is not None:
        return int(size) * int(itemsize)
    return 0


def _path_str(path) -> str:
    out = []
    for p in path:
        name = getattr(p, "name", None)
        if name is None:
            name = str(getattr(p, "idx", getattr(p, "key", p)))
        out.append(str(name))
    return ".".join(out) or "<root>"


def audit_group(mesh, group: str, tree, where: str = "input") -> None:
    """Compare every leaf of ``tree`` against the spec registry's
    declaration for ``group`` and (for inputs) fold per-shard byte
    rows into the transfer ledger.  Never raises: a leaf the audit
    cannot read counts as an audit_error, not a crash."""
    if not _ACTIVE:
        return
    import jax

    from .parallel import mesh as meshmod
    from .solver import xferobs

    try:
        specs = meshmod.declared_specs(group, tree)
    except KeyError:
        _note("spec_drift", _spec_drift, _drift_keys,
              (group, "<unregistered>"),
              {"kind": "unregistered-group", "group": group,
               "where": where, "detail":
               f"tree group {group!r} has no SPEC_GROUPS entry in "
               f"parallel/mesh.py -- declare its sharding first",
               "stack": _fmt_stack()})
        return
    mesh_key = _mesh_key(mesh)
    n_dev = int(mesh.devices.size)
    leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
    spec_leaves = jax.tree_util.tree_leaves(specs)
    stack = None            # captured lazily, once per audited group
    for (path, leaf), spec in zip(leaves, spec_leaves):
        with _slock:
            _counters["leaves_checked"] += 1
        try:
            field = _path_str(path)
            nbytes = _leaf_nbytes(leaf)
            declared = _norm_spec(spec)
            want_shards = _n_shards(mesh, spec)
            sharding = getattr(leaf, "sharding", None)
            actual_desc = None
            ok = True
            if sharding is None:
                # host array: XLA will upload (and shard or replicate)
                # it silently at dispatch -- the transfer no ledger sees
                ok = False
                if stack is None:
                    stack = _fmt_stack()
                _note("implicit_xfer", _implicit, _implicit_keys,
                      (group, field, "host-array"),
                      {"kind": "host-array", "group": group,
                       "field": field, "where": where, "bytes": nbytes,
                       "detail":
                       f"host {type(leaf).__name__} entered a mesh "
                       f"callable; route it through "
                       f"shard_solver_inputs/device_put_cached",
                       "stack": stack})
            else:
                actual_spec = getattr(sharding, "spec", None)
                smesh = getattr(sharding, "mesh", None)
                if smesh is not None and actual_spec is not None:
                    actual_desc = str(_norm_spec(actual_spec))
                    if _mesh_key(smesh) != mesh_key:
                        ok = False
                        if stack is None:
                            stack = _fmt_stack()
                        _note("implicit_xfer", _implicit,
                              _implicit_keys,
                              (group, field, "resharded"),
                              {"kind": "resharded", "group": group,
                               "field": field, "where": where,
                               "bytes": nbytes, "detail":
                               f"array arrives on a different mesh "
                               f"({getattr(smesh, 'axis_names', '?')}"
                               f" {getattr(smesh.devices, 'shape', '?')}"
                               f"); XLA reshards it over the wire",
                               "stack": stack})
                    elif _norm_spec(actual_spec) != declared:
                        ok = False
                        got_shards = _n_shards(mesh, actual_spec)
                        # replicated-where-declared-sharded: each
                        # device holds nbytes/got instead of
                        # nbytes/want -- the fleet-wide waste is the
                        # witness number ROADMAP-1 budgets against
                        amp = n_dev * max(
                            nbytes // got_shards
                            - nbytes // want_shards, 0)
                        if stack is None:
                            stack = _fmt_stack()
                        _note("spec_drift", _spec_drift, _drift_keys,
                              (group, field, str(declared),
                               str(_norm_spec(actual_spec))),
                              {"kind": "spec-mismatch", "group": group,
                               "field": field, "where": where,
                               "declared": str(declared),
                               "actual": str(_norm_spec(actual_spec)),
                               "bytes": nbytes,
                               "amplification_bytes": amp,
                               "stack": stack})
                elif where == "output" and declared == () and \
                        getattr(sharding, "is_fully_replicated", False):
                    actual_desc = "replicated"
                else:
                    ok = False
                    if stack is None:
                        stack = _fmt_stack()
                    _note("implicit_xfer", _implicit, _implicit_keys,
                          (group, field, type(sharding).__name__),
                          {"kind": type(sharding).__name__,
                           "group": group, "field": field,
                           "where": where, "bytes": nbytes, "detail":
                           f"array is not mesh-sharded "
                           f"({type(sharding).__name__}); XLA "
                           f"re-lays it out silently at dispatch",
                           "stack": stack})
            if where != "input":
                continue
            # per-shard ledger rows + zero-tolerance byte parity
            decl_per_dev = nbytes // want_shards
            if sharding is not None:
                try:
                    shard_shape = sharding.shard_shape(leaf.shape)
                    act_per_dev = int(np.prod(shard_shape)) * int(
                        leaf.dtype.itemsize)
                except Exception:  # noqa: BLE001
                    act_per_dev = nbytes
            else:
                act_per_dev = nbytes
            for d in range(n_dev):
                xferobs.note_shard_bytes(group, f"d{d}",
                                         decl_per_dev, act_per_dev)
            if act_per_dev != decl_per_dev:
                # the zero-tolerance ledger reconciliation: each
                # device holds other bytes than the registry budgets
                # (replication, uneven split, padded shard) -- its own
                # witness class even when a spec/implicit report
                # already names the leaf (ok is False): the bytes ARE
                # the regression ROADMAP-1 is judged in
                if stack is None:
                    stack = _fmt_stack()
                _note("shard_parity", _shard_parity_reports,
                      _parity_keys, (group, field),
                      {"group": group, "field": field,
                       "spec_held": ok,
                       "declared_per_device": decl_per_dev,
                       "actual_per_device": act_per_dev,
                       "devices": n_dev, "stack": stack})
        except Exception:  # noqa: BLE001 -- audits must never raise
            with _slock:
                _counters["audit_errors"] += 1


# ----------------------------------------------------------------------
# collective budget (compile-time HLO audit)


def scan_collectives(hlo_text: str) -> Dict[str, int]:
    """Collective-instruction inventory of one HLO module's text."""
    counts: Dict[str, int] = {}
    for m in _COLLECTIVE_RE.finditer(hlo_text):
        counts[m.group(1)] = counts.get(m.group(1), 0) + 1
    return counts


def audit_hlo(family: tuple, hlo_text: str,
              program: str = "") -> Dict[str, int]:
    """Audit one compiled mesh program's HLO against its family
    baseline: the first program of a (mesh shape, static args) family
    records the sanctioned collective budget; a later program
    exceeding any op's count is a collective_excess violation."""
    counts = scan_collectives(hlo_text)
    if not _ACTIVE:
        return counts
    with _slock:
        base = _baselines.get(family)
        if base is None:
            _baselines[family] = dict(counts)
            _counters["baselines_recorded"] += 1
            return counts
    over = {op: (counts.get(op, 0), base.get(op, 0))
            for op in counts
            if counts.get(op, 0) > base.get(op, 0)}
    if over:
        lines = [ln.strip() for ln in hlo_text.splitlines()
                 if _COLLECTIVE_RE.search(ln)][:6]
        _note("collective_excess", _collective, _collective_keys,
              (str(family), str(sorted(over))),
              {"family": str(family), "program": program,
               "baseline": dict(base), "got": dict(counts),
               "excess": {op: f"{got} > baseline {b}"
                          for op, (got, b) in sorted(over.items())},
               "witness_instructions": lines,
               "stack": _fmt_stack()})
    return counts


def _cost_summary(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:  # noqa: BLE001
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if not isinstance(ca, dict):
        return {}
    out = {}
    for key, name in (("flops", "flops"),
                      ("bytes accessed", "bytes_accessed")):
        v = ca.get(key)
        if isinstance(v, (int, float)):
            out[name] = float(v)
    return out


def _abstract_sig(args) -> str:
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves(args):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            parts.append(f"{dtype}{tuple(shape)}")
        else:
            parts.append(type(leaf).__name__)
    return "(" + ", ".join(parts) + ")"


def _maybe_audit_program(fn, mesh, static: tuple, args) -> None:
    """Once per (mesh, static, abstract signature): AOT-lower the mesh
    program, scan its HLO collectives against the family baseline, and
    record the inventory.  Costs one duplicate XLA compile per program
    (the jit path compiles its own executable), so it is knob-gated."""
    if not _hlo_audit:
        return
    family = (_mesh_key(mesh)[1], _mesh_key(mesh)[2]) + static
    pkey = family + (_abstract_sig(args),)
    with _slock:
        if pkey in _programs:
            return
        _programs[pkey] = {"pending": True}
        _counters["programs_audited"] += 1
    entry: dict = {"family": str(family), "signature": pkey[-1]}
    try:
        compiled = fn.lower(*args).compile()
        entry["collectives"] = audit_hlo(
            family, compiled.as_text(), program=pkey[-1])
        entry.update(_cost_summary(compiled))
    except Exception as e:  # noqa: BLE001 -- audits must never raise
        entry["audit_error"] = repr(e)
        with _slock:
            _counters["audit_errors"] += 1
    with _slock:
        _programs[pkey] = entry


# ----------------------------------------------------------------------
# wrappers over the parallel/mesh entry points


class _MeshFnWrapper:
    """Instrumented mesh-solve callable: audits arg/out shardings and
    the compiled program's collectives, then delegates.  Everything
    else (lower/clear_cache/...) passes through to the real jit."""

    def __init__(self, fn, mesh, spread_alg: bool, dtype_name: str):
        self._sc_fn = fn
        self._sc_mesh = mesh
        self._sc_static = (bool(spread_alg), str(dtype_name))

    def __call__(self, const, init, batch):
        if not _ACTIVE:
            return self._sc_fn(const, init, batch)
        with _slock:
            _counters["wrapped_dispatches"] += 1
        for group, tree in (("mesh_const", const), ("mesh_init", init),
                            ("mesh_batch", batch)):
            audit_group(self._sc_mesh, group, tree, where="input")
        _maybe_audit_program(self._sc_fn, self._sc_mesh,
                             self._sc_static, (const, init, batch))
        out = self._sc_fn(const, init, batch)
        audit_group(self._sc_mesh, "mesh_out", out, where="output")
        return out

    def __getattr__(self, name):
        return getattr(self._sc_fn, name)

    def __repr__(self):
        return f"<shardcheck.mesh_fn {self._sc_static} " \
               f"inner={self._sc_fn!r}>"


class _LpqFnWrapper:
    """Instrumented LPQ mesh callable (ISSUE 19): audits the lpq_in
    6-tuple and the replicated lpq_out pair around the real pjit
    program, sharing every detector with the dense wrapper."""

    def __init__(self, fn, mesh, L_pad: int, N: int, steps: int):
        self._sc_fn = fn
        self._sc_mesh = mesh
        self._sc_static = ("lpq", int(L_pad), int(N), int(steps))

    def __call__(self, *args):
        if not _ACTIVE:
            return self._sc_fn(*args)
        with _slock:
            _counters["wrapped_dispatches"] += 1
        audit_group(self._sc_mesh, "lpq_in", tuple(args), where="input")
        _maybe_audit_program(self._sc_fn, self._sc_mesh,
                             self._sc_static, args)
        out = self._sc_fn(*args)
        audit_group(self._sc_mesh, "lpq_out", out, where="output")
        return out

    def __getattr__(self, name):
        return getattr(self._sc_fn, name)

    def __repr__(self):
        return f"<shardcheck.lpq_fn {self._sc_static} " \
               f"inner={self._sc_fn!r}>"


def _patched_mesh_solve_fn(mesh, spread_alg: bool, dtype_name: str):
    fn = _REAL["mesh_solve_fn"](mesh, spread_alg, dtype_name)
    if not _ACTIVE:
        return fn
    return _MeshFnWrapper(fn, mesh, spread_alg, dtype_name)


def _patched_mesh_lpq_fn(mesh, L_pad: int, N: int, steps: int):
    fn = _REAL["mesh_lpq_fn"](mesh, L_pad, N, steps)
    if not _ACTIVE:
        return fn
    return _LpqFnWrapper(fn, mesh, L_pad, N, steps)


def _patched_shard_solver_inputs(mesh, const, init, batch, version=None,
                                 delta_src=None):
    out = _REAL["shard_solver_inputs"](mesh, const, init, batch,
                                       version=version,
                                       delta_src=delta_src)
    if _ACTIVE:
        with _slock:
            _counters["sanctioned_puts"] += 1
    return out


def _patched_shard_lpq_inputs(mesh, *args):
    out = _REAL["shard_lpq_inputs"](mesh, *args)
    if _ACTIVE:
        with _slock:
            _counters["sanctioned_puts"] += 1
    return out


# ----------------------------------------------------------------------
# offline compile audit


def _example_mesh_lanes(E: int, N: int, P: int, dtype: str):
    """Tiny synthetic (E, ...) solver trees covering every registered
    spec column -- the offline stand-in for a fused dispatch (the
    operator-CLI compile audit must not need a running server).  One
    lane is built, then every leaf (including the 0-size trailing
    defaults) broadcasts to the fused eval axis so ranks line up with
    the registry's specs."""
    import jax

    from .solver.binpack import NodeConst, NodeState, PlacementBatch

    f = lambda *s: np.ones(s, dtype=dtype)
    i = lambda *s: np.ones(s, dtype=np.int32)
    const = NodeConst(
        cpu_cap=f(N) * 4000, mem_cap=f(N) * 8192,
        disk_cap=f(N) * 102400, feasible=np.ones(N, dtype=bool),
        affinity=f(N) * 0, has_affinity=np.asarray(False),
        distinct_hosts=np.asarray(False),
        distinct_job_level=np.asarray(False),
        spread_vidx=i(1, N) * 0,
        spread_desired=np.full((1, 4), -1.0, dtype=dtype),
        spread_has_targets=np.zeros(1, dtype=bool),
        spread_weights=f(1) * 50,
        spread_sum_weights=np.asarray(50.0, dtype=dtype),
        n_spreads=np.asarray(1, dtype=np.int32))
    init = NodeState(
        used_cpu=f(N) * 0, used_mem=f(N) * 0, used_disk=f(N) * 0,
        placed=i(N) * 0, placed_job=i(N) * 0,
        static_free=np.ones(N, dtype=bool),
        dyn_avail=i(N) * 12000,
        spread_counts=i(1, 4) * 0)
    batch = PlacementBatch(
        ask_cpu=f(P) * 500, ask_mem=f(P) * 256, ask_disk=f(P) * 150,
        n_dyn_ports=i(P) * 0, has_static=np.zeros(P, dtype=bool),
        limit=i(P) * 6, count=i(P) * P, penalty_idx=i(P) * 0 - 1,
        active=np.ones(P, dtype=bool))
    stack = lambda t: jax.tree.map(
        lambda leaf: np.ascontiguousarray(np.broadcast_to(
            leaf, (E,) + np.shape(leaf))), t)
    return stack(const), stack(init), stack(batch)


def ensure_virtual_devices(n: int) -> None:
    """Offline compile-audit helper: ask XLA's CPU platform for ``n``
    virtual devices when jax has not initialized yet. Which platform
    jax comes up on stays its own choice (``JAX_PLATFORMS=cpu`` for the
    audit; scripts/checkup.py pins its child that way)."""
    if "jax" in sys.modules:
        return      # too late: the audit uses whatever topology exists
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n}"
        ).strip()


def compile_audit(n_devices: int = 8, evals: Optional[int] = None,
                  place: int = 8, nodes: int = 256,
                  dtype_name: str = "float32") -> dict:
    """Compile every registered mesh-solve program variant for an
    ``n_devices`` mesh and inventory its collectives + cost + declared
    per-shard bytes, with no server and no dispatch.  Returns the
    inventory dict (the ``--compile-audit`` CLI renders it)."""
    import jax

    from .parallel import mesh as meshmod

    if jax.device_count() < n_devices:
        return {"error":
                f"need {n_devices} devices, have {jax.device_count()} "
                f"(run via `operator shardcheck --compile-audit`, "
                f"which forces a virtual CPU mesh before jax "
                f"initializes)"}
    mesh = meshmod.make_mesh(n_devices)
    e_par, n_par = mesh.devices.shape
    E = evals if evals is not None else e_par
    E = max(E - E % e_par, e_par)
    N = max(nodes - nodes % n_par, n_par)
    const, init, batch = _example_mesh_lanes(E, N, place, dtype_name)
    s_const, s_init, s_batch = meshmod.shard_solver_inputs(
        mesh, const, init, batch)
    out: dict = {"devices": n_devices,
                 "mesh": [int(e_par), int(n_par)],
                 "shape": [int(E), int(place), int(N)],
                 "programs": []}
    # declared per-shard byte budget per ledger group (what ROADMAP-1
    # buys: each device holds 1/n_par of the fleet tables)
    budgets = {}
    for group, tree in (("mesh_const", const), ("mesh_init", init),
                        ("mesh_batch", batch)):
        specs = meshmod.declared_specs(group, tree)
        total = per_dev = 0
        for leaf, spec in zip(jax.tree_util.tree_leaves(tree),
                              jax.tree_util.tree_leaves(specs)):
            nbytes = _leaf_nbytes(leaf)
            total += nbytes
            per_dev += nbytes // _n_shards(mesh, spec)
        budgets[group] = {"total_bytes": total,
                          "declared_per_shard_bytes": per_dev}
    out["per_shard_budget"] = budgets
    for spread_alg in (False, True):
        fn = meshmod.mesh_solve_fn(mesh, spread_alg, dtype_name)
        family = (_mesh_key(mesh)[1], _mesh_key(mesh)[2],
                  spread_alg, dtype_name)
        entry = {"program": f"mesh_solve(spread_alg={spread_alg}, "
                            f"dtype={dtype_name})"}
        try:
            with mesh:
                compiled = fn.lower(s_const, s_init, s_batch).compile()
            entry["collectives"] = audit_hlo(
                family, compiled.as_text(), program=entry["program"]) \
                if _ACTIVE else scan_collectives(compiled.as_text())
            entry.update(_cost_summary(compiled))
        except Exception as e:  # noqa: BLE001 -- inventory over crash
            entry["audit_error"] = repr(e)
        out["programs"].append(entry)
    # the LPQ relaxation program (ISSUE 19): lanes shard on 'evals',
    # node tables replicate, the dual-ascent combine is an all-gather
    from .solver.lpq import lpq_steps
    L_pad = max(8, e_par)
    steps = lpq_steps()
    f32 = lambda *s: np.ones(s, dtype=np.float32)
    lpq_tree = (f32(L_pad, N), np.ones((L_pad, N), dtype=bool),
                f32(L_pad, 3), f32(L_pad),
                f32(N, 3), np.ones(L_pad, dtype=bool))
    lpq_specs = meshmod.declared_specs("lpq_in", lpq_tree)
    total = per_dev = 0
    for leaf, spec in zip(lpq_tree, lpq_specs):
        nbytes = _leaf_nbytes(leaf)
        total += nbytes
        per_dev += nbytes // _n_shards(mesh, spec)
    budgets["lpq_in"] = {"total_bytes": total,
                         "declared_per_shard_bytes": per_dev}
    fn = meshmod.mesh_lpq_fn(mesh, L_pad, N, steps)
    family = (_mesh_key(mesh)[1], _mesh_key(mesh)[2],
              "lpq", L_pad, N, steps)
    entry = {"program": f"mesh_lpq(L={L_pad}, N={N}, steps={steps})"}
    try:
        with mesh:
            s_in = meshmod.shard_lpq_inputs(mesh, *lpq_tree)
            compiled = fn.lower(*s_in).compile()
        entry["collectives"] = audit_hlo(
            family, compiled.as_text(), program=entry["program"]) \
            if _ACTIVE else scan_collectives(compiled.as_text())
        entry.update(_cost_summary(compiled))
    except Exception as e:  # noqa: BLE001 -- inventory over crash
        entry["audit_error"] = repr(e)
    out["programs"].append(entry)
    # the delta-scatter program (ISSUE 20): journal-covered usage-table
    # generations promote the resident sharded buffer in place instead
    # of re-shipping it.  The replicated (coords, vals) payload reaches
    # every device and each shard keeps the updates landing in its
    # slice; whatever collective XLA inserts for that routing is
    # budgeted here beside the solve/LPQ baselines.  Audit the smallest
    # update bucket against the widest mesh_init leaf.
    init_leaves = jax.tree_util.tree_leaves(init)
    init_specs = jax.tree_util.tree_leaves(
        meshmod.declared_specs("mesh_init", init))
    j, leaf, spec = max(
        ((j, lf, sp) for j, (lf, sp)
         in enumerate(zip(init_leaves, init_specs))),
        key=lambda t: _leaf_nbytes(t[1]))
    arr = np.asarray(leaf)
    n_upd = 8       # the minimum _pad_updates bucket
    ndim = max(1, arr.ndim)
    from jax.sharding import NamedSharding, PartitionSpec
    rep = NamedSharding(mesh, PartitionSpec())
    fn = meshmod.mesh_delta_scatter_fn(
        mesh, arr.shape, arr.dtype.str, n_upd, spec)
    family = (_mesh_key(mesh)[1], _mesh_key(mesh)[2],
              "delta_scatter", arr.dtype.str, _norm_spec(spec))
    entry = {"program": f"mesh_delta_scatter(shape={arr.shape}, "
                        f"dtype={arr.dtype.str}, n_upd={n_upd})"}
    try:
        with mesh:
            s_buf = jax.device_put(arr, NamedSharding(mesh, spec))
            s_coords = jax.device_put(
                np.zeros((ndim, n_upd), dtype=np.int32), rep)
            s_vals = jax.device_put(
                np.zeros((n_upd,), dtype=arr.dtype), rep)
            compiled = fn.lower(s_buf, s_coords, s_vals).compile()
        entry["collectives"] = audit_hlo(
            family, compiled.as_text(), program=entry["program"]) \
            if _ACTIVE else scan_collectives(compiled.as_text())
        entry.update(_cost_summary(compiled))
        # the delta payload crossing the wire per promote at this
        # bucket: replicated coords + vals on every device
        entry["delta_payload_bytes_per_shard"] = int(
            n_upd * (4 * ndim + arr.dtype.itemsize))
    except Exception as e:  # noqa: BLE001 -- inventory over crash
        entry["audit_error"] = repr(e)
    out["programs"].append(entry)
    return out


# ----------------------------------------------------------------------
# lifecycle


def enabled() -> bool:
    return _ACTIVE


def enable() -> None:
    """Install the wrappers over parallel/mesh.py's ``mesh_solve_fn``
    and ``shard_solver_inputs`` module attributes.  The dispatch stack
    imports both at call time, so enabling at runtime covers every
    mesh dispatch; callers that froze a by-value import before enable
    keep the raw functions (documented gap)."""
    global _ACTIVE, _stack_depth, _max_reports, _hlo_audit
    with _slock:
        if _ACTIVE:
            return
        _stack_depth = int(os.environ.get(
            "NOMAD_TPU_SHARDCHECK_STACK", "16"))
        _max_reports = int(os.environ.get(
            "NOMAD_TPU_SHARDCHECK_MAX", "256"))
        _hlo_audit = os.environ.get(
            "NOMAD_TPU_SHARDCHECK_HLO", "1") != "0"
    from .parallel import mesh as meshmod
    if not _REAL:
        _REAL["mesh_solve_fn"] = meshmod.mesh_solve_fn
        _REAL["shard_solver_inputs"] = meshmod.shard_solver_inputs
        _REAL["mesh_lpq_fn"] = meshmod.mesh_lpq_fn
        _REAL["shard_lpq_inputs"] = meshmod.shard_lpq_inputs
    meshmod.mesh_solve_fn = _patched_mesh_solve_fn
    meshmod.shard_solver_inputs = _patched_shard_solver_inputs
    meshmod.mesh_lpq_fn = _patched_mesh_lpq_fn
    meshmod.shard_lpq_inputs = _patched_shard_lpq_inputs
    _ACTIVE = True


def disable() -> None:
    """Restore the real mesh entry points.  Wrappers created while
    enabled keep working (they always delegate) but go inert."""
    global _ACTIVE
    if not _ACTIVE:
        return
    _ACTIVE = False
    from .parallel import mesh as meshmod
    meshmod.mesh_solve_fn = _REAL["mesh_solve_fn"]
    meshmod.shard_solver_inputs = _REAL["shard_solver_inputs"]
    meshmod.mesh_lpq_fn = _REAL["mesh_lpq_fn"]
    meshmod.shard_lpq_inputs = _REAL["shard_lpq_inputs"]


def maybe_install_from_env() -> None:
    if os.environ.get("NOMAD_TPU_SHARDCHECK", "0") == "1":
        enable()


# ----------------------------------------------------------------------
# reporting


def state(programs: bool = False) -> dict:
    """Full checker state (capped); rides /v1/agent/self, the operator
    CLI and debug bundles.  ``programs=True`` adds
    the per-program HLO inventory (the compile-audit view)."""
    with _slock:
        out = {
            "enabled": _ACTIVE,
            "hlo_audit": _hlo_audit,
            "wrapped_dispatches": _counters["wrapped_dispatches"],
            "sanctioned_puts": _counters["sanctioned_puts"],
            "leaves_checked": _counters["leaves_checked"],
            "programs_audited": _counters["programs_audited"],
            "baselines_recorded": _counters["baselines_recorded"],
            "audit_errors": _counters["audit_errors"],
            "spec_drift_count": len(_spec_drift),
            "implicit_xfer_count": len(_implicit),
            "collective_excess_count": len(_collective),
            "shard_parity_count": len(_shard_parity_reports),
            "reports_dropped": _counters["reports_dropped"],
            "spec_drift": [dict(r) for r in _spec_drift],
            "implicit_xfers": [dict(r) for r in _implicit],
            "collective_excess": [dict(r) for r in _collective],
            "shard_parity_reports":
                [dict(r) for r in _shard_parity_reports],
            "baselines": {str(k): dict(v)
                          for k, v in _baselines.items()},
        }
        if programs:
            out["programs"] = [dict(v, key=str(k))
                               for k, v in _programs.items()]
    return out


def _reset_for_tests() -> None:
    with _slock:
        _spec_drift.clear()
        _drift_keys.clear()
        _implicit.clear()
        _implicit_keys.clear()
        _collective.clear()
        _collective_keys.clear()
        _shard_parity_reports.clear()
        _parity_keys.clear()
        _baselines.clear()
        _programs.clear()
        for k in _counters:
            _counters[k] = 0
