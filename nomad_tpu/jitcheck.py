"""Device-dispatch discipline sanitizer ("jitcheck") for the solver.

The paper's core bet is that the scheduler inner loop runs as dense
jitted kernels; the repo now has a large jitted surface (binpack.py
fused/wave kernels, lpq.py's LP solve, the batch.py arena dispatch,
constcache, parallel/mesh.py) and -- until this module -- zero tooling
to catch the failure modes that silently destroy that bet.  Before the
ROADMAP-1 pjit/mesh refactor multiplies call sites and shape buckets,
this is the dispatch layer's analog of lockcheck.py (PR 9): a runtime
sanitizer that turns "the TPU path got slow" into a named report.

What it checks while enabled:

  * **steady-state retraces** -- every repo-constructed ``jax.jit``
    callable is wrapped to account traces per construction site, keyed
    by the call's abstract signature (leaf shapes/dtypes/weak-types +
    static args).  Tracing the SAME signature at the same site more
    than ``NOMAD_TPU_JITCHECK_WARMUP`` times means the compile cache
    was defeated (the classic bug: a fresh ``@jax.jit`` closure built
    per call), and the report carries the witness signature pair.  A
    NEW signature arriving after a site has gone steady (served a call
    from cache) is recorded as a ``late_trace`` -- report-only, since
    new shape buckets legitimately appear as a fleet grows.
  * **hot-path host syncs** -- ``jax.device_get``, explicit
    ``__array__``, ``.item()``, ``float()``/``int()``/``bool()`` on
    device values while inside a solver dispatch stage
    (``guard.run_dispatch`` marks the region), attributed to the
    enclosing PR-3 tracing span.  The designed one-fetch-per-dispatch
    sites wrap their fetch in ``with jitcheck.sanctioned_fetch():``;
    everything else is a violation.  (CPU-backend gap, documented: on
    the CPU backend ``np.asarray`` reads a jax array through the
    buffer protocol, which Python cannot intercept -- explicit fetch
    forms are still caught, and real accelerators have no buffer
    protocol so ``__array__`` fires there.)
  * **dtype drift** -- float64 leaves crossing a ``device_put`` or jit
    boundary while x64 is not deliberately enabled (on TPU f64 is
    emulated; a leaked float64 table silently doubles transfer and
    compute), plus weak-typed Python scalars passed as traced args
    (signature jitter -- each flip is a retrace waiting to happen).
  * **fingerprint-cache mutation** -- constcache fingerprint sources,
    pack-memo and usage-base arrays register here when cached; a
    sampled content re-hash detects writes after fingerprinting, and
    every registered memo array must keep ``writeable=False`` (the
    frozen-memo invariant nomadlint checks statically).

Kill-switch semantics mirror lockcheck: OFF by default,
``NOMAD_TPU_JITCHECK=0``/unset is a true no-op -- ``jax.jit``,
``jax.device_get/put`` and the array dunders are untouched and no
wrapper is observable anywhere.  ``NOMAD_TPU_JITCHECK=1`` at process
start (or ``enable()`` at runtime, how the conftest fixture runs the
dispatch-pipeline/lpq/solver-parity suites) installs the patches;
jits constructed before enable stay raw (documented gap, same as
lockcheck's pre-enable locks -- the module-level ``solve_placements``
partials are covered by nomadlint's ``no-callsite-jit`` rule instead).

State rides the usual surfaces: ``stats.jitcheck`` in
``/v1/agent/self``, ``operator jitcheck [--sites]`` CLI (exit 1 on
steady-state retraces), ``jitcheck.json`` in operator debug bundles,
and the ``nomad.jitcheck.{retrace,host_sync,x64_leak,mutated_cache}``
counters.

Knobs: ``NOMAD_TPU_JITCHECK`` (off; ``1`` installs at import),
``NOMAD_TPU_JITCHECK_WARMUP`` (1: traces allowed per (site, sig)),
``NOMAD_TPU_JITCHECK_STACK`` (16: witness stack depth),
``NOMAD_TPU_JITCHECK_MAX`` (256: retained reports per class),
``NOMAD_TPU_JITCHECK_REHASH`` (32: fingerprinted arrays re-hashed per
state() read), ``NOMAD_TPU_JITCHECK_X64`` (auto: flag float64 only
when ``jax_enable_x64`` is off; ``1`` always, ``0`` never).
"""
from __future__ import annotations

import functools
import hashlib
import os
import sys
import threading
import traceback
from collections import OrderedDict
from typing import Dict, List, Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SELF_FILE = os.path.abspath(__file__).rstrip("co")  # .pyc -> .py

_ACTIVE = False                  # module-global fast gate (one dict read)
_REAL: dict = {}                 # originals, captured at first enable

# checker-internal state; _slock is a leaf: nothing is acquired under
# it and no user code runs under it
_slock = threading.Lock()

_warmup = 1
_stack_depth = 16
_max_reports = 256
_rehash_n = 32
_x64_flag = False                # resolved at enable() from _X64 knob

_SIG_CAP = 512                   # distinct signatures retained per site

# site -> {"calls", "traces", "steady", "jits", "sigs": {sig: {...}}}
_sites: "OrderedDict[str, dict]" = OrderedDict()
_retraces: List[dict] = []
_retrace_keys: Dict[tuple, dict] = {}
_late_traces: List[dict] = []
_late_keys: set = set()
_host_syncs: List[dict] = []
_host_sync_keys: Dict[tuple, dict] = {}
_dtype_drift: List[dict] = []
_dtype_keys: set = set()
_mutations: List[dict] = []
_mutation_keys: set = set()
# id(arr) -> (arr, digest, site). numpy arrays are not weakref-able,
# so the registries hold STRONG refs under a byte budget (FIFO): an
# opt-in sanitizer pinning a bounded sample of cached arrays is the
# price of being able to re-hash them later.
_fps: "OrderedDict[int, tuple]" = OrderedDict()
_frozen: "OrderedDict[int, tuple]" = OrderedDict()
_FPS_CAP = 1024
_FPS_MAX_BYTES = 64 * 1024 * 1024
_fps_bytes = [0, 0]              # [fingerprint bytes, frozen bytes]
_rehash_cursor = [0]
_counters = {"jits": 0, "calls": 0, "traces": 0, "retraces": 0,
             "host_syncs": 0, "sanctioned_fetches": 0, "x64_leaks": 0,
             "weak_scalars": 0, "mutations": 0, "reports_dropped": 0,
             "sigs_dropped": 0}
# sanctioned-fetch counts by ledger tag (the fetch-accounted tags the
# xferobs fetch decomposition uses)
_sanct_tags: Dict[str, int] = {}

_tls = threading.local()


def _tls_state():
    st = getattr(_tls, "st", None)
    if st is None:
        st = _tls.st = {"hot": 0, "sanct": 0, "label": "",
                        "calls": []}
    return st


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


def _span_ids() -> str:
    """The enclosing PR-3 tracing span's eval ids (host-sync
    attribution), or '-' outside any traced context."""
    try:
        from .server.tracing import tracer
        return ",".join(tracer.current_ids()) or "-"
    except Exception:  # noqa: BLE001
        return "-"


def _repo_site(skip_self: bool = True) -> Optional[str]:
    """First repo frame outside this module, as 'rel/path.py:line'."""
    f = sys._getframe(2)
    for _ in range(16):
        if f is None:
            return None
        fn = f.f_code.co_filename
        if fn.startswith(_REPO_ROOT) and not (
                skip_self and os.path.abspath(fn) == _SELF_FILE):
            return f"{_rel(fn)}:{f.f_lineno}"
        f = f.f_back
    return None


def _fmt_stack(limit: Optional[int] = None) -> str:
    try:
        return "".join(traceback.format_stack(
            sys._getframe(2), limit=limit or _stack_depth))
    except Exception:  # noqa: BLE001 -- diagnostics must never raise
        return "<stack unavailable>"


# ----------------------------------------------------------------------
# abstract signatures + dtype drift

import re as _re

_ADDR_RE = _re.compile(r"0x[0-9a-f]+")


def _describe_static(v, depth: int = 0):
    """Address-free structural description of a wrapped function's
    static closure (partials' keywords, nested closures, constants).
    Two jit callables built at one factory line for DIFFERENT static
    variants (spread_alg/dtype_name/B buckets) describe differently,
    so their one-trace-each does not read as a retrace; the nested-jit
    bug (a fresh but IDENTICAL closure per call) describes identically
    every time, so its re-traces still aggregate and trip the gate."""
    if depth > 4:
        return "..."
    if isinstance(v, functools.partial):
        return ("partial", _describe_static(v.func, depth + 1),
                tuple(_describe_static(a, depth + 1) for a in v.args),
                tuple(sorted(
                    (k, _describe_static(x, depth + 1))
                    for k, x in (v.keywords or {}).items())))
    if isinstance(v, (bool, int, float, str, bytes, type(None))):
        return v
    if callable(v):
        cells = []
        for cell in (getattr(v, "__closure__", None) or ()):
            try:
                cells.append(_describe_static(cell.cell_contents,
                                              depth + 1))
            except ValueError:
                cells.append("<empty>")
        code = getattr(v, "__code__", None)
        name = (code.co_name if code is not None
                else getattr(v, "__name__", "?"))
        return ("fn", name, tuple(cells))
    try:
        return _ADDR_RE.sub("@", repr(v))[:200]
    except Exception:  # noqa: BLE001 -- exotic closure contents
        return type(v).__name__


def _abstract_sig(args, kwargs) -> str:
    """Value-independent abstract signature of one jit call: leaf
    shapes/dtypes (weak-typed leaves marked '~'), static-looking
    scalars by value (bool/str) or by kind (int/float -- traced weak
    scalars are value-independent)."""
    import jax

    parts = []
    for leaf in jax.tree_util.tree_leaves((args, kwargs)):
        shape = getattr(leaf, "shape", None)
        dtype = getattr(leaf, "dtype", None)
        if shape is not None and dtype is not None:
            weak = "~" if getattr(leaf, "weak_type", False) else ""
            parts.append(f"{weak}{dtype}{tuple(shape)}")
        elif isinstance(leaf, (bool, str)):
            parts.append(repr(leaf))
        elif isinstance(leaf, int):
            parts.append("int")
        elif isinstance(leaf, float):
            parts.append("float")
        else:
            parts.append(type(leaf).__name__)
    return "(" + ", ".join(parts) + ")"


def _note_dtype_drift(site: Optional[str], tree, where: str) -> None:
    """float64 leaves crossing a device boundary (+ weak Python-scalar
    traced args at jit boundaries). Deduped per (site, kind, where)."""
    import jax

    f64 = weak = 0
    for leaf in jax.tree_util.tree_leaves(tree):
        dtype = getattr(leaf, "dtype", None)
        if dtype is not None and str(dtype) in ("float64", "complex128"):
            f64 += 1
        elif isinstance(leaf, float) and where == "jit":
            weak += 1
    if not f64 and not weak:
        return
    site = site or "?"
    m = _metrics()
    with _slock:
        if f64 and _x64_flag:
            key = (site, "float64", where)
            if key not in _dtype_keys:
                _dtype_keys.add(key)
                if len(_dtype_drift) < _max_reports:
                    _dtype_drift.append({
                        "kind": "float64", "where": where, "site": site,
                        "leaves": f64,
                        "thread": threading.current_thread().name})
                else:
                    _counters["reports_dropped"] += 1
            _counters["x64_leaks"] += 1
            if m is not None:
                m.incr("nomad.jitcheck.x64_leak")
        if weak:
            key = (site, "weak-scalar", where)
            if key not in _dtype_keys:
                _dtype_keys.add(key)
                if len(_dtype_drift) < _max_reports:
                    _dtype_drift.append({
                        "kind": "weak-scalar", "where": where,
                        "site": site, "leaves": weak,
                        "thread": threading.current_thread().name})
                else:
                    _counters["reports_dropped"] += 1
            _counters["weak_scalars"] += 1


# ----------------------------------------------------------------------
# jit wrapping + trace accounting


class _JitWrapper:
    """Instrumented jitted callable: counts traces per abstract
    signature at its construction site. Delegates everything else to
    the real jit object (lower/clear_cache/etc. via __getattr__)."""

    def __init__(self, fun, kwargs, site):
        self._jc_site = site
        try:
            self._jc_fp = hash((
                _describe_static(fun),
                tuple(sorted((k, _describe_static(v))
                             for k, v in kwargs.items()))))
        except Exception:  # noqa: BLE001 -- unhashable description
            self._jc_fp = 0

        def _traced(*a, **k):
            # runs ONLY when jax traces (compile-cache miss)
            st = _tls_state()
            if st["calls"]:
                st["calls"][-1][2] += 1
            _counters["traces"] += 1
            return fun(*a, **k)

        try:
            functools.update_wrapper(_traced, fun)
        except Exception:  # noqa: BLE001 -- lambdas/partials vary
            pass
        self._jc_fn = _REAL["jit"](_traced, **kwargs)
        with _slock:
            _counters["jits"] += 1
            rec = _sites.get(site)
            if rec is None:
                rec = _sites[site] = {"calls": 0, "traces": 0,
                                      "jits": 0, "steady": False,
                                      "sigs": {}}
            rec["jits"] += 1

    def __call__(self, *args, **kwargs):
        if not _ACTIVE:
            return self._jc_fn(*args, **kwargs)
        sig = _abstract_sig(args, kwargs)
        _note_dtype_drift(self._jc_site, (args, kwargs), "jit")
        frame = [self._jc_site, sig, 0]
        st = _tls_state()
        st["calls"].append(frame)
        try:
            return self._jc_fn(*args, **kwargs)
        finally:
            st["calls"].pop()
            _note_call(self._jc_site, self._jc_fp, sig, frame[2])

    def __getattr__(self, name):
        return getattr(self._jc_fn, name)

    def __repr__(self):
        return f"<jitcheck.jit {self._jc_site} inner={self._jc_fn!r}>"


def _note_call(site: str, fp: int, sig: str, fired: int) -> None:
    retrace = late = None
    skey = (fp, sig)
    with _slock:
        rec = _sites.get(site)
        if rec is None:
            rec = _sites[site] = {"calls": 0, "traces": 0, "jits": 0,
                                  "steady": False, "sigs": {}}
        rec["calls"] += 1
        srec = rec["sigs"].get(skey)
        if srec is None:
            if len(rec["sigs"]) >= _SIG_CAP:
                _counters["sigs_dropped"] += 1
                rec["traces"] += fired
                return
            srec = rec["sigs"][skey] = {"traces": 0, "steady": False}
        _counters["calls"] += 1
        if not fired:
            srec["steady"] = True
            rec["steady"] = True
            return
        was_new = srec["traces"] == 0
        rec["traces"] += fired
        srec["traces"] += fired
        if srec["traces"] > _warmup:
            # same abstract signature traced again after warmup: the
            # compile cache was defeated (fresh jit per call, or an
            # unstable signature normalizing to the same abstract key)
            key = (site, sig)
            rep = _retrace_keys.get(key)
            if rep is not None:
                rep["count"] = srec["traces"]
            elif len(_retraces) >= _max_reports:
                _counters["reports_dropped"] += 1
            else:
                steady = [s for (_f, s), r in rec["sigs"].items()
                          if r["steady"]][:3]
                rep = {
                    "site": site, "signature": sig,
                    "count": srec["traces"],
                    # witness pair: the signature(s) the site already
                    # served from cache vs the one that re-traced
                    "witness": {"old": steady or [sig], "new": sig},
                    "thread": threading.current_thread().name,
                    "stack": _fmt_stack(),
                }
                _retrace_keys[key] = rep
                _retraces.append(rep)
            _counters["retraces"] += 1
            retrace = True
        elif was_new and any(
                r["steady"] for (f2, _s2), r in rec["sigs"].items()
                if f2 == fp):
            # a NEW signature at a program variant that already served
            # calls from cache: legitimate when a fresh shape bucket
            # arrives (fleet growth), so report-only
            key = (site, sig)
            if key not in _late_keys:
                _late_keys.add(key)
                if len(_late_traces) < _max_reports:
                    late = {
                        "site": site, "signature": sig,
                        "known_sigs": len(rec["sigs"]) - 1,
                        "thread": threading.current_thread().name,
                    }
                    _late_traces.append(late)
                else:
                    _counters["reports_dropped"] += 1
    if retrace:
        m = _metrics()
        if m is not None:
            m.incr("nomad.jitcheck.retrace")


def _jit_factory(fun=None, **kwargs):
    """Installed over jax.jit while enabled. Only callables constructed
    from repo frames are wrapped; stdlib/jax internals get the real
    jit. Keyword-only usage (jax.jit(static_argnames=...)) returns a
    partial, matching the real API."""
    if fun is None:
        return functools.partial(_jit_factory, **kwargs)
    if not _ACTIVE:
        return _REAL["jit"](fun, **kwargs)
    site = _repo_site()
    if site is None:
        return _REAL["jit"](fun, **kwargs)
    return _JitWrapper(fun, kwargs, site)


# ----------------------------------------------------------------------
# hot-region + host-sync detection


def note_dispatch_begin(label: str = "") -> None:
    """guard.run_dispatch entry (on the dispatch/runner thread): host
    syncs recorded until note_dispatch_end are hot-path syncs."""
    if not _ACTIVE:
        return
    st = _tls_state()
    st["hot"] += 1
    st["label"] = label


def note_dispatch_end() -> None:
    if not _ACTIVE:
        return
    st = _tls_state()
    st["hot"] = max(0, st["hot"] - 1)


class _SanctionedFetch:
    """Marks the designed one-bulk-fetch-per-dispatch sites: a
    device_get inside this block is the fused transport doing its job,
    not a hot-path sync. nomadlint's no-host-sync-hot rule recognizes
    the same marker statically, and its fetch-accounted rule requires
    every site to pass the transfer-ledger tag (``tag``) naming the
    transport, so per-tag sanctioned-fetch counts line up with the
    xferobs fetch decomposition."""

    def __init__(self, tag: str = ""):
        self._tag = tag

    def __enter__(self):
        if _ACTIVE:
            self._entered = True
            st = _tls_state()
            st["sanct"] += 1
            self._prev_tag = st.get("sanct_tag", "")
            st["sanct_tag"] = self._tag
        else:
            self._entered = False
        return self

    def __exit__(self, *exc):
        if self._entered:
            st = _tls_state()
            st["sanct"] = max(0, st["sanct"] - 1)
            st["sanct_tag"] = self._prev_tag
        return False


def sanctioned_fetch(tag: str = "") -> _SanctionedFetch:
    return _SanctionedFetch(tag)


def _note_sync(kind: str) -> None:
    if not _ACTIVE:
        return
    st = _tls_state()
    if st["hot"] <= 0:
        return
    if st["sanct"] > 0:
        _counters["sanctioned_fetches"] += 1
        tag = st.get("sanct_tag", "")
        if tag:
            with _slock:
                _sanct_tags[tag] = _sanct_tags.get(tag, 0) + 1
        return
    site = _repo_site() or "?"
    evals = _span_ids()
    m = _metrics()
    with _slock:
        key = (kind, site)
        rep = _host_sync_keys.get(key)
        if rep is not None:
            rep["count"] += 1
        elif len(_host_syncs) >= _max_reports:
            _counters["reports_dropped"] += 1
        else:
            rep = {"kind": kind, "site": site, "count": 1,
                   "label": st["label"], "evals": evals,
                   "thread": threading.current_thread().name,
                   "stack": _fmt_stack()}
            _host_sync_keys[key] = rep
            _host_syncs.append(rep)
        _counters["host_syncs"] += 1
    if m is not None:
        m.incr("nomad.jitcheck.host_sync")


def _patched_device_get(x):
    _note_sync("device_get")
    return _REAL["device_get"](x)


def _patched_device_put(x, *args, **kwargs):
    if _ACTIVE:
        _note_dtype_drift(_repo_site(), x, "device_put")
    return _REAL["device_put"](x, *args, **kwargs)


def _mk_sync_dunder(name: str):
    orig = _REAL[name]

    def patched(self, *a, **k):
        _note_sync(name)
        return orig(self, *a, **k)

    patched.__name__ = name
    return patched


# ----------------------------------------------------------------------
# fingerprint-cache mutation + frozen-memo invariant


def _digest(arr) -> bytes:
    h = hashlib.blake2b(digest_size=16)
    h.update(str((arr.dtype.str, arr.shape)).encode())
    import numpy as np
    h.update(np.ascontiguousarray(arr).data)
    return h.digest()


def note_fingerprint(arr, digest: Optional[bytes] = None) -> None:
    """A host array's content fingerprint was just taken (constcache):
    register it for sampled re-hash; a later mismatch means the source
    was written after fingerprinting."""
    if not _ACTIVE:
        return
    site = _repo_site() or "?"
    if digest is None:
        digest = _digest(arr)
    nbytes = int(getattr(arr, "nbytes", 0))
    with _slock:
        if id(arr) not in _fps:
            _fps_bytes[0] += nbytes
        _fps[id(arr)] = (arr, digest, site)
        while _fps and (len(_fps) > _FPS_CAP
                        or _fps_bytes[0] > _FPS_MAX_BYTES):
            _, (old, _d, _s) = _fps.popitem(last=False)
            _fps_bytes[0] -= int(getattr(old, "nbytes", 0))


def note_frozen(arr) -> None:
    """A host array was stored into a memo/cache: it must be frozen
    (writeable=False) and stay that way."""
    if not _ACTIVE:
        return
    site = _repo_site() or "?"
    writable_now = bool(getattr(arr, "flags", None) is not None
                        and arr.flags.writeable)
    nbytes = int(getattr(arr, "nbytes", 0))
    with _slock:
        if id(arr) not in _frozen:
            _fps_bytes[1] += nbytes
        _frozen[id(arr)] = (arr, site)
        while _frozen and (len(_frozen) > _FPS_CAP
                           or _fps_bytes[1] > _FPS_MAX_BYTES):
            _, (old, _s) = _frozen.popitem(last=False)
            _fps_bytes[1] -= int(getattr(old, "nbytes", 0))
    if writable_now:
        _note_mutation("unfrozen-memo", site,
                       "array stored into a memo without "
                       "writeable=False")


def _note_mutation(kind: str, site: str, detail: str) -> None:
    m = _metrics()
    with _slock:
        key = (kind, site)
        if key in _mutation_keys:
            _counters["mutations"] += 1
            return
        _mutation_keys.add(key)
        if len(_mutations) >= _max_reports:
            _counters["reports_dropped"] += 1
        else:
            _mutations.append({
                "kind": kind, "site": site, "detail": detail,
                "thread": threading.current_thread().name})
        _counters["mutations"] += 1
    if m is not None:
        m.incr("nomad.jitcheck.mutated_cache")


def verify_caches(sample: Optional[int] = None) -> int:
    """Re-hash a rotating sample of registered fingerprint sources and
    re-check the frozen invariant; returns the number of NEW findings.
    Called from state() (every surface read audits) and directly by
    tests."""
    if not _ACTIVE:
        return 0
    n = sample if sample is not None else _rehash_n
    with _slock:
        fps = list(_fps.items())
        frozen = list(_frozen.items())
        cursor = _rehash_cursor[0]
    found = 0
    if fps:
        for i in range(min(n, len(fps))):
            key, (arr, digest, site) = fps[(cursor + i) % len(fps)]
            try:
                fresh = _digest(arr)
            except Exception:  # noqa: BLE001 -- shrunk/retyped arrays
                fresh = b"?"
            if fresh != digest:
                _note_mutation(
                    "content-mutation", site,
                    f"fingerprinted array re-hash mismatch "
                    f"(dtype={arr.dtype}, shape={arr.shape})")
                found += 1
                with _slock:
                    # re-arm with the current content so one mutation
                    # is one finding, not one per state() read
                    if key in _fps:
                        _fps[key] = (arr, fresh, site)
        with _slock:
            _rehash_cursor[0] = (cursor + n) % max(len(_fps), 1)
    for key, (arr, site) in frozen:
        if getattr(arr, "flags", None) is not None \
                and arr.flags.writeable:
            _note_mutation("thawed-memo", site,
                           "memoized array became writeable again")
            found += 1
            with _slock:
                _frozen.pop(key, None)
    return found


# ----------------------------------------------------------------------
# lifecycle


def enabled() -> bool:
    return _ACTIVE


def enable() -> None:
    """Patch jax.jit / device_get / device_put and the jax array host-
    conversion dunders. Jitted callables constructed before enable stay
    raw (documented gap -- nomadlint's no-callsite-jit covers the
    module-level sites statically)."""
    global _ACTIVE, _warmup, _stack_depth, _max_reports, _rehash_n, \
        _x64_flag
    with _slock:
        if _ACTIVE:
            return
        _warmup = max(1, int(os.environ.get(
            "NOMAD_TPU_JITCHECK_WARMUP", "1")))
        _stack_depth = int(os.environ.get(
            "NOMAD_TPU_JITCHECK_STACK", "16"))
        _max_reports = int(os.environ.get(
            "NOMAD_TPU_JITCHECK_MAX", "256"))
        _rehash_n = max(1, int(os.environ.get(
            "NOMAD_TPU_JITCHECK_REHASH", "32")))
    import jax
    from jax._src.array import ArrayImpl
    x64_mode = os.environ.get("NOMAD_TPU_JITCHECK_X64", "auto")
    if x64_mode == "1":
        _x64_flag = True
    elif x64_mode == "0":
        _x64_flag = False
    else:
        # x64 deliberately on (CPU-parity deployments): float64 is not
        # a leak there, it is the configured compute dtype
        _x64_flag = not jax.config.jax_enable_x64
    if not _REAL:
        _REAL["jit"] = jax.jit
        _REAL["device_get"] = jax.device_get
        _REAL["device_put"] = jax.device_put
        _REAL["array_cls"] = ArrayImpl
        _REAL["dunders"] = tuple(
            name for name in ("__array__", "__bool__", "__float__",
                              "__int__", "__index__", "item")
            if getattr(ArrayImpl, name, None) is not None)
        for name in _REAL["dunders"]:
            _REAL[name] = getattr(ArrayImpl, name)
    jax.jit = _jit_factory
    jax.device_get = _patched_device_get
    jax.device_put = _patched_device_put
    for name in _REAL["dunders"]:
        setattr(ArrayImpl, name, _mk_sync_dunder(name))
    _ACTIVE = True


def disable() -> None:
    """Restore the real entry points. Wrappers created while enabled
    keep working (they always delegate) but go inert."""
    global _ACTIVE
    if not _ACTIVE:
        return
    _ACTIVE = False
    import jax
    jax.jit = _REAL["jit"]
    jax.device_get = _REAL["device_get"]
    jax.device_put = _REAL["device_put"]
    cls = _REAL.get("array_cls")
    if cls is not None:
        for name in _REAL["dunders"]:
            setattr(cls, name, _REAL[name])


def maybe_install_from_env() -> None:
    if os.environ.get("NOMAD_TPU_JITCHECK", "0") == "1":
        enable()


# ----------------------------------------------------------------------
# reporting


def state(sites: bool = False) -> dict:
    """Full checker state (capped); rides /v1/agent/self, the operator
    CLI and debug bundles. ``sites=True`` adds the
    per-site trace table (the CLI's --sites view)."""
    if _ACTIVE:
        verify_caches()
    with _slock:
        out = {
            "enabled": _ACTIVE,
            "warmup": _warmup,
            "jits": _counters["jits"],
            "calls": _counters["calls"],
            "traces": _counters["traces"],
            "site_count": len(_sites),
            "retrace_count": len(_retraces),
            "late_trace_count": len(_late_traces),
            "host_sync_count": len(_host_syncs),
            "sanctioned_fetches": _counters["sanctioned_fetches"],
            "sanctioned_by_tag": dict(_sanct_tags),
            "x64_leak_count": sum(1 for d in _dtype_drift
                                  if d["kind"] == "float64"),
            "weak_scalar_count": sum(1 for d in _dtype_drift
                                     if d["kind"] == "weak-scalar"),
            "mutation_count": len(_mutations),
            "reports_dropped": _counters["reports_dropped"],
            "retraces": [dict(r) for r in _retraces],
            "late_traces": [dict(r) for r in _late_traces],
            "host_syncs": [dict(r) for r in _host_syncs],
            "dtype_drift": [dict(r) for r in _dtype_drift],
            "mutations": [dict(r) for r in _mutations],
        }
        if sites:
            out["sites"] = [
                {"site": s, "jits": r["jits"], "calls": r["calls"],
                 "traces": r["traces"], "sigs": len(r["sigs"]),
                 "steady": r["steady"]}
                for s, r in _sites.items()]
    return out


def _reset_for_tests() -> None:
    with _slock:
        _sites.clear()
        _retraces.clear()
        _retrace_keys.clear()
        _late_traces.clear()
        _late_keys.clear()
        _host_syncs.clear()
        _host_sync_keys.clear()
        _dtype_drift.clear()
        _dtype_keys.clear()
        _mutations.clear()
        _mutation_keys.clear()
        _fps.clear()
        _frozen.clear()
        _fps_bytes[0] = _fps_bytes[1] = 0
        _rehash_cursor[0] = 0
        _sanct_tags.clear()
        for k in _counters:
            _counters[k] = 0
