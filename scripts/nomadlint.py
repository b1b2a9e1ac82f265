#!/usr/bin/env python3
"""nomadlint: the repo-invariant lint driver (AST-based).

One gate for the invariants that keep the concurrent control plane
honest -- the static complement of the runtime lock-order sanitizer
(nomad_tpu/lockcheck.py).  Scans nomad_tpu/ (rules that
read docs/tests pull those in too) and fails listing violations.

AST rules:

  fire-registered    every ``faults.fire("<point>")`` call site names
                     a literal member of nomad_tpu/faultinject.py
                     ``POINTS`` -- an unregistered point is a chaos
                     scenario nobody can arm
  killswitch-tested  every knob row in docs/OPERATIONS.md whose
                     description says "kill switch" is referenced by
                     at least one test under tests/ (a kill switch
                     without a parity test is a rollback nobody
                     verified)
  telemetry-literal  telemetry series names are string literals or
                     normalizable f-strings/ternaries (a computed name
                     can never be checked against the metrics doc)
  telemetry-kind     no series is emitted as two kinds (e.g. both
                     counter and timer) -- exactly the class of bug
                     that rendered ``batch_lanes`` as ms for 2 rounds
  sleep-under-lock   no ``time.sleep``, blocking/indefinite dequeue or
                     wait, or device dispatch statically inside a
                     ``with <lock>:`` block -- one sleeping holder
                     starves every peer for the duration
  bare-acquire       a bare ``<x>.acquire()`` statement requires a
                     try/finally releasing the same receiver (either
                     immediately following, or an enclosing try) -- an
                     exception between acquire and release wedges the
                     lock forever

Dispatch-hygiene rules (ISSUE 10, the static complement of the
runtime dispatch-discipline sanitizer nomad_tpu/jitcheck.py):

  no-callsite-jit    every ``jax.jit`` is constructed at module level
                     or inside an ``lru_cache``'d shape-bucket
                     factory -- a jit built per call defeats the
                     compile cache and re-traces every generation
  no-host-sync-hot   no ``jax.device_get`` / ``.item()`` /
                     ``block_until_ready`` inside a solver hot
                     function (one that calls a dispatch/transfer
                     primitive) or statically inside a ``with
                     <lock>:`` block; the designed one-bulk-fetch
                     sites mark themselves with
                     ``with jitcheck.sanctioned_fetch():``
  dtype-threaded     device-kernel modules (nomad_tpu/solver/,
                     nomad_tpu/parallel/) take their dtype through
                     the static ``dtype_name`` arg -- no bare
                     ``jnp.float64`` / float64 dtype literals in jnp
                     calls (on TPU f64 is emulated; a leaked float64
                     table doubles transfer and compute)
  frozen-memo        arrays stored into memo/cache containers are
                     frozen first (a freeze/setflags call in the same
                     function) -- the runtime counterpart is
                     jitcheck's writeable=False invariant
  fetch-accounted    every ``jitcheck.sanctioned_fetch(...)`` site
                     passes a non-empty string-literal ledger tag
                     (ISSUE 13): the transfer observatory attributes
                     fetched result bytes per transport, and an
                     untagged fetch is a payload the ledger cannot
                     decompose

Store-discipline rules (ISSUE 11, the static complement of the MVCC
snapshot-isolation sanitizer nomad_tpu/statecheck.py):

  no-direct-table-write  AllocTable mutators and StateStore internals
                     (``_allocs``/``_nodes``/... dict writes, alloc-
                     table column stores) are only touched from
                     ``nomad_tpu/state/`` -- everything else goes
                     through the store's locked write API
  version-keyed-memo store-derived caches (``*_CACHE``/``*memo*``
                     containers in solver/tensor/server modules) must
                     key on a table version/index/token/fingerprint
                     component -- a content-blind key serves stale
                     state forever
  no-snapshot-escape a ``state.snapshot()`` handle stored into a
                     module global or a long-lived ``self.`` attribute
                     outlives its consistency window (snapshots are
                     per-eval views, not caches)
  delta-carried      ``_bump("allocs"...)`` calls in the store carry
                     ``delta=`` (the alloc-delta journal entry) or a
                     justified waiver -- a delta-less write silently
                     degrades every incremental-memo holder to
                     wholesale rebuilds (statecheck check c is the
                     runtime twin)

Shard-hygiene rules (ISSUE 15, the static complement of the
sharding-discipline sanitizer nomad_tpu/shardcheck.py):

  spec-declared      ``PartitionSpec`` / ``NamedSharding`` are only
                     constructed inside ``nomad_tpu/parallel/`` -- the
                     spec registry (parallel/mesh.py ``SPEC_GROUPS``)
                     is the ONE home for sharding intent; an inline
                     spec elsewhere is a sharding contract no
                     sanitizer compares against
  mesh-factory       ``jax.sharding.Mesh`` is only constructed by the
                     parallel/ factories (``make_mesh`` /
                     ``pick_mesh`` / ``eval_axis_mesh``) -- an inline
                     Mesh defeats the factory's lru-cache keying and
                     silently forks the topology the registry
                     declares specs against
  no-implicit-put    ``jax.device_put`` carrying a sharding argument
                     only inside ``nomad_tpu/parallel/`` -- everything
                     else routes through ``shard_solver_inputs`` /
                     ``device_put_cached`` so the transfer ledger and
                     the per-shard byte rows see every sharded upload

Schedule-hygiene rules (ISSUE 12, the static complement of the
deterministic schedule explorer nomad_tpu/schedcheck.py):

  join-with-timeout  no indefinite ``Thread.join()`` / ``Event.wait()``
                     outside shutdown paths -- a wedged thread must
                     surface as a diagnosable stall, not an invisible
                     infinite join (and a bounded loop gives schedcheck
                     an interposition point)
  no-sleep-sync      tests/ may not synchronize threads via bare
                     ``time.sleep`` in a test body (the #1 source of
                     1-core flakes); poll loops and nested
                     simulated-work stubs are exempt
  daemon-declared    every repo ``threading.Thread(...)`` sets
                     ``daemon=`` explicitly (daemon-ness inherits from
                     the creator, so an undeclared spawn site's
                     shutdown behavior depends on its caller)

Output/maintenance flags: ``--sarif PATH`` additionally emits the kept
violations as SARIF 2.1.0 for CI/editor annotations;
``--fix-stale-waivers [--apply]`` deletes waiver comment lines whose
every named rule no longer fires there (dry-run by default).

Legacy checkers, invocable as rules under this driver (their
standalone scripts keep working; tests/test_metrics_doc.py etc. are
unchanged):

  metrics-doc        scripts/check_metrics_doc.py
  knob-doc           scripts/check_knob_doc.py

The default run (no ``--rule``) is every AST rule plus both.  Tier-1
gates the default run via tests/test_nomadlint.py.

Waivers (per rule, justification REQUIRED after ``--``)::

    something.acquire()   # nomadlint: waive=bare-acquire -- released
                          # by the runner thread when the job retires

on the violating line or the line directly above it.  A waiver without
a ``--`` justification does not suppress anything.
"""
from __future__ import annotations

import argparse
import ast
import importlib.util
import os
import re
import sys
from typing import Dict, List, Optional, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_WAIVER = re.compile(
    r"nomadlint:\s*waive=([A-Za-z0-9_,-]+)\s*--\s*\S")

# telemetry emit methods -> series kind (server/telemetry.py contract;
# _count is tracing.py's guarded incr wrapper)
_TELEMETRY_KINDS = {"incr": "counter", "sample": "gauge",
                    "sample_ms": "timer", "measure": "timer",
                    "_count": "counter"}
# receiver tails that identify a telemetry call (avoids random.sample
# and friends); _count is a self-method in tracing.py
_TELEMETRY_RECV = re.compile(r"(?:^|\.)(?:metrics|_tm|t)$")

_LOCKISH = re.compile(r"(?:lock|mutex|cv|cond|sem)\w*$", re.IGNORECASE)

_DISPATCH_CALLS = {"run_dispatch", "solve_lane_fused", "fuse_and_solve",
                   "solve_groups", "block_until_ready", "device_put"}


class Violation:
    __slots__ = ("rule", "path", "line", "msg")

    def __init__(self, rule: str, path: str, line: int, msg: str):
        self.rule = rule
        self.path = path
        self.line = line
        self.msg = msg

    def __repr__(self):
        return f"{self.path}:{self.line}: [{self.rule}] {self.msg}"


class Ctx:
    """Everything the rules read, built once per run. ``root`` is
    swappable so rule fixture tests lint a synthetic tree."""

    def __init__(self, root: str):
        self.root = root
        self.files: List[Tuple[str, str, ast.AST]] = []
        self.parse_errors: List[Violation] = []
        scan = []
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(root, "nomad_tpu")):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            scan.extend(os.path.join(dirpath, f)
                        for f in sorted(filenames) if f.endswith(".py"))
        for path in scan:
            rel = os.path.relpath(path, root)
            try:
                with open(path, encoding="utf-8") as f:
                    text = f.read()
                tree = ast.parse(text, filename=rel)
            except (OSError, SyntaxError) as e:
                self.parse_errors.append(Violation(
                    "parse", rel, getattr(e, "lineno", 0) or 0,
                    f"cannot parse: {e}"))
                continue
            self.files.append((rel, text, tree))

    # -- lazy context shared by repo-level rules -----------------------
    def doc_text(self) -> str:
        try:
            with open(os.path.join(self.root, "docs", "OPERATIONS.md"),
                      encoding="utf-8") as f:
                return f.read()
        except OSError:
            return ""

    def test_texts(self) -> Dict[str, str]:
        out = {}
        tdir = os.path.join(self.root, "tests")
        if not os.path.isdir(tdir):
            return out
        for name in sorted(os.listdir(tdir)):
            if not name.endswith(".py"):
                continue
            try:
                with open(os.path.join(tdir, name),
                          encoding="utf-8") as f:
                    out[f"tests/{name}"] = f.read()
            except OSError:
                continue
        return out

    def fire_points(self) -> Optional[set]:
        """POINTS tuple parsed from nomad_tpu/faultinject.py (None if
        the file or the assignment is absent)."""
        for rel, _text, tree in self.files:
            if rel != os.path.join("nomad_tpu", "faultinject.py"):
                continue
            for node in tree.body:
                if isinstance(node, ast.Assign) and any(
                        isinstance(t, ast.Name) and t.id == "POINTS"
                        for t in node.targets):
                    if isinstance(node.value, (ast.Tuple, ast.List)):
                        return {e.value for e in node.value.elts
                                if isinstance(e, ast.Constant)
                                and isinstance(e.value, str)}
            return None
        return None


def _unparse(node) -> str:
    try:
        return ast.unparse(node)
    except Exception:  # noqa: BLE001 -- lint must not crash on exotica
        return "<?>"


def _normalize_name(node) -> Optional[str]:
    """Literal / normalizable telemetry name, placeholders as '*';
    None when the name cannot be statically derived."""
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return re.sub(r"\{[^}]*\}", "*", node.value)
    if isinstance(node, ast.JoinedStr):
        parts = []
        for v in node.values:
            if isinstance(v, ast.Constant):
                parts.append(str(v.value))
            else:
                parts.append("*")
        return "".join(parts)
    if isinstance(node, ast.IfExp):
        a = _normalize_name(node.body)
        b = _normalize_name(node.orelse)
        if a is not None and b is not None:
            # both arms contribute; kind stability checks each
            return a if a == b else f"{a}|{b}"
        return None
    if isinstance(node, ast.BinOp) and isinstance(node.op, ast.Add):
        a = _normalize_name(node.left)
        b = _normalize_name(node.right)
        if a is not None and b is not None:
            return a + b
        return None
    return None


# ----------------------------------------------------------------------
# AST rules


def rule_fire_registered(ctx: Ctx) -> List[Violation]:
    points = ctx.fire_points()
    out: List[Violation] = []
    if points is None:
        out.append(Violation("fire-registered",
                             "nomad_tpu/faultinject.py", 0,
                             "no POINTS registry found"))
        return out
    for rel, _text, tree in ctx.files:
        if rel.endswith(os.path.join("nomad_tpu", "faultinject.py")):
            continue            # the registry/dispatcher itself
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "fire"):
                continue
            if not node.args:
                continue
            arg = node.args[0]
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                out.append(Violation(
                    "fire-registered", rel, node.lineno,
                    f"fire() point must be a string literal, got "
                    f"`{_unparse(arg)}`"))
                continue
            if arg.value not in points:
                out.append(Violation(
                    "fire-registered", rel, node.lineno,
                    f"fire point {arg.value!r} is not registered in "
                    f"faultinject.POINTS"))
    return out


def rule_killswitch_tested(ctx: Ctx) -> List[Violation]:
    doc = ctx.doc_text()
    if not doc:
        return [Violation("killswitch-tested", "docs/OPERATIONS.md", 0,
                          "docs/OPERATIONS.md missing or unreadable")]
    tests = ctx.test_texts()
    blob = "\n".join(tests.values())
    out: List[Violation] = []
    for i, line in enumerate(doc.splitlines(), 1):
        s = line.lstrip()
        if not s.startswith("|"):
            continue
        if not re.search(r"kill[ -]switch", s, re.IGNORECASE):
            continue
        for knob in re.findall(r"`(NOMAD_TPU_[A-Z0-9_]+)`", s):
            if knob not in blob:
                out.append(Violation(
                    "killswitch-tested", "docs/OPERATIONS.md", i,
                    f"kill-switch knob {knob} is not referenced by any "
                    f"test under tests/ (no parity gate)"))
    return out


def rule_telemetry(ctx: Ctx) -> List[Violation]:
    """Shared scan for telemetry-literal and telemetry-kind."""
    out: List[Violation] = []
    seen: Dict[str, Tuple[str, str, int]] = {}   # name -> (kind, at)
    for rel, _text, tree in ctx.files:
        if rel.endswith(os.path.join("nomad_tpu", "server",
                                     "telemetry.py")):
            continue            # the sink's own generic dispatch
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr in _TELEMETRY_KINDS
                    and node.args):
                continue
            recv = _unparse(node.func.value)
            if node.func.attr == "_count":
                if recv != "self":
                    continue
            elif not _TELEMETRY_RECV.search(recv):
                continue
            name = _normalize_name(node.args[0])
            if name is None:
                out.append(Violation(
                    "telemetry-literal", rel, node.lineno,
                    f"telemetry series name must be a literal or "
                    f"normalizable f-string, got "
                    f"`{_unparse(node.args[0])}`"))
                continue
            kind = _TELEMETRY_KINDS[node.func.attr]
            for arm in name.split("|"):
                if not arm.startswith("nomad."):
                    continue
                prev = seen.get(arm)
                if prev is None:
                    seen[arm] = (kind, rel, node.lineno)
                elif prev[0] != kind:
                    out.append(Violation(
                        "telemetry-kind", rel, node.lineno,
                        f"series {arm!r} emitted as {kind} here but as "
                        f"{prev[0]} at {prev[1]}:{prev[2]} -- one "
                        f"series, one kind"))
    return out


def _is_lockish(expr: ast.AST) -> bool:
    s = _unparse(expr)
    tail = s.split(".")[-1]
    return bool(_LOCKISH.search(tail))


class _UnderLockVisitor(ast.NodeVisitor):
    def __init__(self, rel: str, out: List[Violation]):
        self.rel = rel
        self.out = out
        self.lock_stack: List[str] = []
        self.ctx_stack: List[str] = []

    # don't cross into code that merely gets DEFINED under the lock
    def visit_FunctionDef(self, node):
        if not self.lock_stack:
            self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        if not self.lock_stack:
            self.generic_visit(node)

    def visit_With(self, node):
        for i in node.items:        # context exprs: not yet under it
            self.visit(i.context_expr)
        lockish = [i for i in node.items
                   if _is_lockish(i.context_expr)]
        ctxs = [_unparse(i.context_expr) for i in node.items]
        self.lock_stack.extend(_unparse(i.context_expr)
                               for i in lockish)
        self.ctx_stack.extend(ctxs)
        for stmt in node.body:
            self.visit(stmt)
        if lockish:
            del self.lock_stack[-len(lockish):]
        del self.ctx_stack[-len(ctxs):]

    visit_AsyncWith = visit_With

    def visit_Call(self, node):
        self.generic_visit(node)
        if not self.lock_stack:
            return
        held = self.lock_stack[-1]
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else "")
        if name == "sleep" and isinstance(fn, ast.Attribute) \
                and "time" in _unparse(fn.value):
            self.out.append(Violation(
                "sleep-under-lock", self.rel, node.lineno,
                f"time.sleep inside `with {held}:` -- the holder "
                f"sleeps, every waiter starves"))
        elif name == "get" and isinstance(fn, ast.Attribute):
            kw = {k.arg for k in node.keywords}
            blocking_kw = not node.args and kw <= {"block", "timeout"}
            blocking_pos = (len(node.args) == 1 and not kw
                            and isinstance(node.args[0], ast.Constant)
                            and node.args[0].value is True)
            if blocking_kw or blocking_pos:
                self.out.append(Violation(
                    "sleep-under-lock", self.rel, node.lineno,
                    f"blocking dequeue `{_unparse(fn)}(...)` inside "
                    f"`with {held}:`"))
        elif name in ("wait", "join") and isinstance(fn, ast.Attribute) \
                and not node.args and not node.keywords:
            recv = _unparse(fn.value)
            if recv not in self.ctx_stack:
                self.out.append(Violation(
                    "sleep-under-lock", self.rel, node.lineno,
                    f"indefinite `{recv}.{name}()` inside "
                    f"`with {held}:` (a condvar may wait on its own "
                    f"lock; anything else blocks the holder forever)"))
        elif name in _DISPATCH_CALLS:
            self.out.append(Violation(
                "sleep-under-lock", self.rel, node.lineno,
                f"device dispatch `{name}(...)` inside `with {held}:`"
                f" -- a dispatch can burn a full watchdog deadline"))


def rule_sleep_under_lock(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        _UnderLockVisitor(rel, out).visit(tree)
    return out


def _finally_releases(try_node: ast.Try, recv: str) -> bool:
    for stmt in try_node.finalbody:
        for node in ast.walk(stmt):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr == "release" \
                    and _unparse(node.func.value) == recv:
                return True
    return False


def rule_bare_acquire(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []

    def walk(rel: str, body: list, try_stack: list) -> None:
        for i, stmt in enumerate(body):
            if isinstance(stmt, ast.Expr) \
                    and isinstance(stmt.value, ast.Call) \
                    and isinstance(stmt.value.func, ast.Attribute) \
                    and stmt.value.func.attr == "acquire":
                recv = _unparse(stmt.value.func.value)
                ok = any(_finally_releases(t, recv) for t in try_stack)
                if not ok and i + 1 < len(body) \
                        and isinstance(body[i + 1], ast.Try) \
                        and _finally_releases(body[i + 1], recv):
                    ok = True
                if not ok:
                    out.append(Violation(
                        "bare-acquire", rel, stmt.lineno,
                        f"bare `{recv}.acquire()` without a try/finally"
                        f" releasing it -- an exception here wedges the"
                        f" lock forever"))
            for field in ("body", "orelse", "handlers", "finalbody"):
                sub = getattr(stmt, field, None)
                if not sub:
                    continue
                if field == "handlers":
                    for h in sub:
                        walk(rel, h.body, try_stack)
                    continue
                nested = try_stack
                if isinstance(stmt, ast.Try) and field in ("body",
                                                           "orelse"):
                    nested = try_stack + [stmt]
                walk(rel, sub, nested)

    for rel, _text, tree in ctx.files:
        for node in ast.walk(tree):
            if isinstance(node, (ast.FunctionDef,
                                 ast.AsyncFunctionDef)):
                walk(rel, node.body, [])
    return out


# ----------------------------------------------------------------------
# dispatch-hygiene rules (ISSUE 10)


class _JitSiteVisitor(ast.NodeVisitor):
    """no-callsite-jit: a ``jax.jit`` reference inside a function body
    is only allowed when some enclosing function is decorated with an
    ``lru_cache`` (the shape-bucket factory pattern); module level is
    always fine."""

    def __init__(self, rel: str, out: List[Violation]):
        self.rel = rel
        self.out = out
        self.fn_depth = 0
        self.lru_depth = 0

    def visit_FunctionDef(self, node):
        lru = any("lru_cache" in _unparse(d) or
                  _unparse(d).split("(")[0].endswith("cache")
                  for d in node.decorator_list)
        self.fn_depth += 1
        if lru:
            self.lru_depth += 1
        self.generic_visit(node)
        if lru:
            self.lru_depth -= 1
        self.fn_depth -= 1

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_Lambda(self, node):
        self.fn_depth += 1
        self.generic_visit(node)
        self.fn_depth -= 1

    def visit_Attribute(self, node):
        if (node.attr == "jit" and isinstance(node.ctx, ast.Load)
                and _unparse(node.value) == "jax"
                and self.fn_depth > 0 and self.lru_depth == 0):
            self.out.append(Violation(
                "no-callsite-jit", self.rel, node.lineno,
                "jax.jit constructed at a call site -- a fresh jit "
                "per call defeats the compile cache (steady-state "
                "retrace); hoist to module level or behind an "
                "lru_cache'd shape-bucket factory"))
        self.generic_visit(node)


def rule_no_callsite_jit(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        if rel.endswith(os.path.join("nomad_tpu", "jitcheck.py")):
            continue            # the patcher itself handles raw jit
        _JitSiteVisitor(rel, out).visit(tree)
    return out


# a function that calls any of these is a solver hot function: its
# body runs on (or stages for) the dispatch path
_HOT_MARKERS = {"device_put_cached", "_put_eval_sharded", "run_dispatch",
                "solve_lane_fused", "solve_lane_wave",
                "solve_lane_wave_preempt", "fuse_and_solve",
                "solve_groups", "solve_eval_batch",
                "solve_eval_batch_preempt", "mesh_solve_fn"}
_SYNC_ATTRS = {"device_get", "item", "block_until_ready"}


def _is_sanctioned_with(node: ast.With) -> bool:
    # matches both the bare marker and the tagged form the
    # fetch-accounted rule requires (sanctioned_fetch("wave"))
    return any(
        isinstance(i.context_expr, ast.Call)
        and _unparse(i.context_expr.func).endswith("sanctioned_fetch")
        for i in node.items)


class _HotSyncVisitor(ast.NodeVisitor):
    """Within ONE hot function body: flag device fetches outside a
    ``with jitcheck.sanctioned_fetch():`` block."""

    def __init__(self, rel: str, out: List[Violation]):
        self.rel = rel
        self.out = out
        self.sanct = 0

    def visit_FunctionDef(self, node):
        pass                    # nested defs get their own hot check

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_With(self, node):
        sanct = _is_sanctioned_with(node)
        if sanct:
            self.sanct += 1
        self.generic_visit(node)
        if sanct:
            self.sanct -= 1

    visit_AsyncWith = visit_With

    def visit_Call(self, node):
        self.generic_visit(node)
        if self.sanct:
            return
        fn = node.func
        if isinstance(fn, ast.Attribute) and fn.attr in _SYNC_ATTRS:
            self.out.append(Violation(
                "no-host-sync-hot", self.rel, node.lineno,
                f"host sync `{_unparse(fn)}(...)` inside a solver hot "
                f"function -- each sync serializes the dispatch "
                f"pipeline; route through the one sanctioned bulk "
                f"fetch (`with jitcheck.sanctioned_fetch():`)"))


class _SyncUnderLockVisitor(ast.NodeVisitor):
    """Device fetches statically inside ``with <lock>:`` -- a fetch can
    burn a watchdog deadline while every peer waits on the lock."""

    def __init__(self, rel: str, out: List[Violation]):
        self.rel = rel
        self.out = out
        self.lock_depth = 0

    def visit_FunctionDef(self, node):
        if not self.lock_depth:
            self.generic_visit(node)

    visit_AsyncFunctionDef = visit_FunctionDef

    def visit_With(self, node):
        for i in node.items:
            self.visit(i.context_expr)
        lockish = sum(1 for i in node.items
                      if _is_lockish(i.context_expr))
        self.lock_depth += lockish
        for stmt in node.body:
            self.visit(stmt)
        self.lock_depth -= lockish

    visit_AsyncWith = visit_With

    def visit_Call(self, node):
        self.generic_visit(node)
        if not self.lock_depth:
            return
        fn = node.func
        if isinstance(fn, ast.Attribute) and \
                fn.attr in ("device_get", "item"):
            self.out.append(Violation(
                "no-host-sync-hot", self.rel, node.lineno,
                f"device fetch `{_unparse(fn)}(...)` inside a "
                f"`with <lock>:` block -- the holder blocks on the "
                f"device while every waiter starves"))


def rule_no_host_sync_hot(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    solver_dirs = (os.path.join("nomad_tpu", "solver"),
                   os.path.join("nomad_tpu", "parallel"))
    for rel, _text, tree in ctx.files:
        if rel.startswith(solver_dirs):
            for node in ast.walk(tree):
                if not isinstance(node, (ast.FunctionDef,
                                         ast.AsyncFunctionDef)):
                    continue
                calls = {
                    (c.func.attr if isinstance(c.func, ast.Attribute)
                     else c.func.id if isinstance(c.func, ast.Name)
                     else "")
                    for c in ast.walk(node)
                    if isinstance(c, ast.Call)}
                if not calls & _HOT_MARKERS:
                    continue
                v = _HotSyncVisitor(rel, out)
                for stmt in node.body:
                    v.visit(stmt)
        _SyncUnderLockVisitor(rel, out).visit(tree)
    # a fetch can be flagged by both the hot-function and under-lock
    # scans; one report per line is enough
    seen: set = set()
    deduped = []
    for v in out:
        key = (v.path, v.line)
        if key not in seen:
            seen.add(key)
            deduped.append(v)
    return deduped


_F64_LITERALS = {"jnp.float64", "np.float64", "jax.numpy.float64"}


def rule_dtype_threaded(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    kernel_dirs = (os.path.join("nomad_tpu", "solver"),
                   os.path.join("nomad_tpu", "parallel"))
    for rel, _text, tree in ctx.files:
        if not rel.startswith(kernel_dirs):
            continue
        for node in ast.walk(tree):
            if isinstance(node, ast.Attribute) \
                    and isinstance(node.ctx, ast.Load) \
                    and _unparse(node) == "jnp.float64":
                out.append(Violation(
                    "dtype-threaded", rel, node.lineno,
                    "bare jnp.float64 in device-kernel code -- thread "
                    "the dtype through the kernel's static "
                    "`dtype_name` arg (f64 is emulated on TPU)"))
            elif isinstance(node, ast.Call):
                recv = _unparse(node.func)
                if not recv.startswith(("jnp.", "jax.numpy.")):
                    continue
                for kw in node.keywords:
                    if kw.arg != "dtype":
                        continue
                    val = _unparse(kw.value)
                    lit = (isinstance(kw.value, ast.Constant)
                           and kw.value.value == "float64")
                    if lit or val in _F64_LITERALS:
                        out.append(Violation(
                            "dtype-threaded", rel, node.lineno,
                            f"float64 dtype literal in `{recv}(...)` "
                            f"-- thread the dtype through the static "
                            f"`dtype_name` arg"))
    # a `jnp.zeros(..., dtype=jnp.float64)` call trips both scans --
    # one report per line is enough
    seen: set = set()
    deduped = []
    for v in out:
        key = (v.path, v.line)
        if key not in seen:
            seen.add(key)
            deduped.append(v)
    return deduped


_FREEZE_CALLS = {"_freeze", "setflags", "freeze_matrix",
                 "freeze_usage_base", "note_frozen", "_note_frozen",
                 "_set_writeable"}
_MEMOISH_TAIL = re.compile(r"(memos?$)|(^_?[A-Z0-9_]*CACHE$)")


def _memoish_subscript(target) -> Optional[str]:
    """The store-target name when ``target`` is a subscript into a
    memo/cache container (``memo[k] = v``, ``_X_CACHE[k] = v``)."""
    if not isinstance(target, ast.Subscript):
        return None
    base = _unparse(target.value)
    tail = base.split(".")[-1]
    if _MEMOISH_TAIL.search(tail):
        return base
    return None


def rule_frozen_memo(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            # innermost wins: don't re-scan nested defs from the outer
            body_nodes = []
            stack = list(fn.body)
            has_freeze = False
            while stack:
                node = stack.pop()
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)):
                    continue
                body_nodes.append(node)
                if isinstance(node, ast.Call):
                    name = (node.func.attr
                            if isinstance(node.func, ast.Attribute)
                            else node.func.id
                            if isinstance(node.func, ast.Name) else "")
                    if name in _FREEZE_CALLS:
                        has_freeze = True
                stack.extend(ast.iter_child_nodes(node))
            if has_freeze:
                continue
            for node in body_nodes:
                if not isinstance(node, ast.Assign):
                    continue
                for target in node.targets:
                    base = _memoish_subscript(target)
                    if base is not None:
                        out.append(Violation(
                            "frozen-memo", rel, node.lineno,
                            f"array stored into `{base}[...]` without "
                            f"a freeze -- memoized payloads are "
                            f"shared across evals and must be "
                            f"writeable=False (jitcheck invariant)"))
    return out


def rule_fetch_accounted(ctx: Ctx) -> List[Violation]:
    """Every ``sanctioned_fetch(...)`` context manager carries a
    non-empty string-literal ledger tag naming the transport: the
    transfer observatory (solver/xferobs.py) decomposes fetched result
    bytes by that tag, so an untagged site is a payload the ledger
    cannot attribute."""
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        if rel.endswith(os.path.join("nomad_tpu", "jitcheck.py")):
            continue            # the marker's own definition/dispatch
        for node in ast.walk(tree):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            for item in node.items:
                ce = item.context_expr
                if not (isinstance(ce, ast.Call)
                        and _unparse(ce.func).endswith(
                            "sanctioned_fetch")):
                    continue
                arg = ce.args[0] if ce.args else None
                ok = (isinstance(arg, ast.Constant)
                      and isinstance(arg.value, str) and arg.value)
                if not ok:
                    out.append(Violation(
                        "fetch-accounted", rel, ce.lineno,
                        "sanctioned_fetch() without a string-literal "
                        "ledger tag -- pass the transport name "
                        "(e.g. sanctioned_fetch(\"wave\")) so the "
                        "transfer ledger can attribute the fetched "
                        "bytes"))
    return out


# ----------------------------------------------------------------------
# store-discipline rules (ISSUE 11)

# AllocTable mutators; calling one on an alloc_table receiver outside
# nomad_tpu/state/ bypasses the store's locked write API
_TABLE_MUTATORS = {"upsert", "upsert_many", "remove", "register_node",
                   "compact", "preallocate", "_grow", "_fold_inc_build",
                   "_fold_inc_row", "_fold_inc_rows"}
# store-internal table dicts; subscript/attr writes to these outside
# state/ are direct index corruption. The receiver must look like a
# store/state handle: brokers and trackers own private dicts with the
# same names (broker self._evals) that are theirs to write.
_STORE_INTERNALS = re.compile(
    r"(?:store|state)\w*\._(allocs|nodes|jobs|evals|deployments|"
    r"allocs_by_node|allocs_by_job|table_index|alloc_deltas)\b")
_STATE_DIR = os.path.join("nomad_tpu", "state")


def _is_table_recv(expr: ast.AST) -> bool:
    s = _unparse(expr)
    return "alloc_table" in s or s in ("table", "t", "tbl")


def rule_no_direct_table_write(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        if rel.startswith(_STATE_DIR) or \
                rel.endswith(os.path.join("nomad_tpu", "statecheck.py")):
            continue            # the owner and its sanitizer
        for node in ast.walk(tree):
            if isinstance(node, ast.Call) \
                    and isinstance(node.func, ast.Attribute) \
                    and node.func.attr in _TABLE_MUTATORS \
                    and _is_table_recv(node.func.value):
                out.append(Violation(
                    "no-direct-table-write", rel, node.lineno,
                    f"AllocTable mutator "
                    f"`{_unparse(node.func)}(...)` outside "
                    f"nomad_tpu/state/ -- table writes go through the "
                    f"store's locked write API (upsert_allocs / "
                    f"upsert_plan_results / compact_alloc_table)"))
            elif isinstance(node, (ast.Assign, ast.AugAssign)):
                targets = (node.targets
                           if isinstance(node, ast.Assign)
                           else [node.target])
                for t in targets:
                    s = _unparse(t)
                    if ".alloc_table." in s or _STORE_INTERNALS.search(s):
                        out.append(Violation(
                            "no-direct-table-write", rel, node.lineno,
                            f"store/table internals written directly "
                            f"(`{s} = ...`) outside nomad_tpu/state/"))
    return out


_MEMO_NAME = re.compile(r"(memo|cache)", re.IGNORECASE)
_VERSION_WORDS = re.compile(
    r"version|index|token|fingerprint|\bfp\b|digest|snapshot|hash")
# module dirs whose caches derive from store state (jobspec/structs
# codecs are content-keyed and out of scope)
_STORE_DERIVED_DIRS = (os.path.join("nomad_tpu", "solver"),
                       os.path.join("nomad_tpu", "tensor"),
                       os.path.join("nomad_tpu", "server"))


def _key_mentions_version(fn: ast.AST, key_node: ast.AST) -> bool:
    """Whether the memo key expression (or, for a plain Name, any
    assignment to it inside the same function) carries a table
    version/index/token/fingerprint component."""
    if _VERSION_WORDS.search(_unparse(key_node)):
        return True
    if isinstance(key_node, ast.Name):
        for sub in ast.walk(fn):
            if isinstance(sub, ast.Assign) and any(
                    isinstance(t, ast.Name) and t.id == key_node.id
                    for t in sub.targets):
                if _VERSION_WORDS.search(_unparse(sub.value)):
                    return True
    return False


def _is_call_scoped(fn: ast.AST, base_node: ast.AST) -> bool:
    """A container freshly bound to a dict literal inside the same
    function is call-scoped (a per-call lookup memo like service.py's
    node_cache), not a cross-call cache -- staleness dies with the
    frame."""
    if not isinstance(base_node, ast.Name):
        return False
    for sub in ast.walk(fn):
        if isinstance(sub, (ast.Assign, ast.AnnAssign)):
            targets = (sub.targets if isinstance(sub, ast.Assign)
                       else [sub.target])
            value = sub.value
            if value is None:
                continue
            if any(isinstance(t, ast.Name) and t.id == base_node.id
                   for t in targets):
                if isinstance(value, ast.Dict) or (
                        isinstance(value, ast.Call)
                        and _unparse(value.func) in ("dict",
                                                     "OrderedDict",
                                                     "defaultdict")):
                    return True
    return False


def rule_version_keyed_memo(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        if not rel.startswith(_STORE_DERIVED_DIRS):
            continue
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            for node in ast.walk(fn):
                if not isinstance(node, ast.Assign):
                    continue
                for target in node.targets:
                    if isinstance(target, ast.Subscript):
                        base = _unparse(target.value)
                        tail = base.split(".")[-1]
                        if not _MEMO_NAME.search(tail):
                            continue
                        if _key_mentions_version(fn, target.slice):
                            continue
                        # the version token may ride the ENTRY instead
                        # of the key when the hit path checks it
                        # (usage-base memos store (store, token, base))
                        if _VERSION_WORDS.search(_unparse(node.value)):
                            continue
                        if _is_call_scoped(fn, target.value):
                            continue
                        out.append(Violation(
                            "version-keyed-memo", rel, node.lineno,
                            f"store-derived cache `{base}[...]` keyed "
                            f"without a table version/index/token/"
                            f"fingerprint component -- a content-blind "
                            f"key serves stale state after the next "
                            f"table write"))
                    elif isinstance(target, ast.Attribute) \
                            and _MEMO_NAME.search(target.attr):
                        if _VERSION_WORDS.search(_unparse(node.value)):
                            continue
                        out.append(Violation(
                            "version-keyed-memo", rel, node.lineno,
                            f"store-derived memo attribute "
                            f"`{_unparse(target)}` assigned without a "
                            f"version/index/token component in the "
                            f"cached value"))
    return out


_SNAPSHOT_CALL = re.compile(r"(state|store|_store)\w*\.snapshot\(\)")


def rule_no_snapshot_escape(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        for node in ast.walk(tree):
            if not isinstance(node, ast.Assign):
                continue
            if not _SNAPSHOT_CALL.search(_unparse(node.value)):
                continue
            for target in node.targets:
                s = _unparse(target)
                if not (isinstance(target, ast.Attribute)
                        and s.startswith("self.")):
                    continue
                out.append(Violation(
                    "no-snapshot-escape", rel, node.lineno,
                    f"state snapshot stored into long-lived attribute "
                    f"`{s}` -- snapshots are per-eval consistency "
                    f"windows; holding one pins every object of its "
                    f"generation and serves stale reads forever"))
        # module-level globals: snapshot call in a top-level assign
        for stmt in tree.body:
            if isinstance(stmt, ast.Assign) and \
                    _SNAPSHOT_CALL.search(_unparse(stmt.value)):
                out.append(Violation(
                    "no-snapshot-escape", rel, stmt.lineno,
                    f"state snapshot bound to module global "
                    f"`{_unparse(stmt.targets[0])}`"))
    return out


# ----------------------------------------------------------------------
# schedule-hygiene rules (ISSUE 12, the static complement of the
# deterministic schedule explorer nomad_tpu/schedcheck.py)

_SHUTDOWNISH = re.compile(
    r"shutdown|stop|close|teardown|drain|destroy|reap|finalize|"
    r"cleanup|__exit__|join|wait", re.IGNORECASE)
_EVENTISH = re.compile(
    r"(?:event|stop|stopped|done|ready|started|kill|exit)$",
    re.IGNORECASE)
_PROCISH = re.compile(r"(?:proc|process|popen)\w*$", re.IGNORECASE)


def rule_join_with_timeout(ctx: Ctx) -> List[Violation]:
    """No indefinite ``Thread.join()`` / ``Event.wait()`` outside
    shutdown paths: an argless join/wait on a wedged thread turns one
    stuck eval into an invisible control-plane wedge -- a bounded
    ``while t.is_alive(): t.join(timeout=...)`` keeps the stall
    observable (and gives schedcheck an interposition point)."""
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        for fn in ast.walk(tree):
            if not isinstance(fn, (ast.FunctionDef,
                                   ast.AsyncFunctionDef)):
                continue
            if _SHUTDOWNISH.search(fn.name):
                continue            # shutdown paths may drain forever
            for node in ast.walk(fn):
                if isinstance(node, (ast.FunctionDef,
                                     ast.AsyncFunctionDef)) \
                        and node is not fn:
                    continue
                if not (isinstance(node, ast.Call)
                        and isinstance(node.func, ast.Attribute)
                        and not node.args and not node.keywords):
                    continue
                recv = _unparse(node.func.value)
                tail = recv.split(".")[-1]
                if node.func.attr == "join":
                    if _PROCISH.search(tail):
                        continue    # subprocess reaps are not threads
                    out.append(Violation(
                        "join-with-timeout", rel, node.lineno,
                        f"indefinite `{recv}.join()` outside a "
                        f"shutdown path -- a wedged thread hangs the "
                        f"caller invisibly; use a bounded "
                        f"`while t.is_alive(): t.join(timeout=...)`"))
                elif node.func.attr == "wait" and \
                        _EVENTISH.search(tail):
                    out.append(Violation(
                        "join-with-timeout", rel, node.lineno,
                        f"indefinite `{recv}.wait()` outside a "
                        f"shutdown path -- an unset event parks the "
                        f"caller forever; pass a timeout and re-check"))
    return out


def rule_no_sleep_sync(ctx: Ctx) -> List[Violation]:
    """tests/ may not synchronize threads via bare ``time.sleep`` in a
    test body: "sleep and hope the worker got there" is the #1 source
    of 1-core flakes.  Poll loops (sleep inside while/for, wait_until)
    and simulated-work stubs (sleep inside a nested def) are fine --
    the rule flags straight-line sleeps in ``test_*`` bodies only."""
    out: List[Violation] = []
    tdir = os.path.join(ctx.root, "tests")
    if not os.path.isdir(tdir):
        return out
    for name in sorted(os.listdir(tdir)):
        if not name.endswith(".py"):
            continue
        rel = f"tests/{name}"
        try:
            with open(os.path.join(tdir, name), encoding="utf-8") as f:
                tree = ast.parse(f.read(), filename=rel)
        except (OSError, SyntaxError):
            continue                # tier-1 collection owns this
        for fn in ast.walk(tree):
            if not (isinstance(fn, (ast.FunctionDef,
                                    ast.AsyncFunctionDef))
                    and fn.name.startswith("test_")):
                continue

            def walk(node, in_loop):
                for ch in ast.iter_child_nodes(node):
                    if isinstance(ch, (ast.FunctionDef,
                                       ast.AsyncFunctionDef,
                                       ast.Lambda)):
                        continue    # nested stubs simulate work
                    loop = in_loop or isinstance(
                        node, (ast.While, ast.For))
                    if isinstance(ch, ast.Call) \
                            and isinstance(ch.func, ast.Attribute) \
                            and ch.func.attr == "sleep" \
                            and _unparse(ch.func.value) \
                            .split(".")[-1].endswith("time") \
                            and not loop:
                        out.append(Violation(
                            "no-sleep-sync", rel, ch.lineno,
                            f"bare `{_unparse(ch.func)}"
                            f"({_unparse(ch.args[0]) if ch.args else ''})`"
                            f" in a test body synchronizes threads by "
                            f"wall clock -- the #1 source of 1-core "
                            f"flakes; poll a predicate (wait_until) or "
                            f"use an event/condition"))
                    walk(ch, loop)

            walk(fn, False)
    return out


def rule_daemon_declared(ctx: Ctx) -> List[Violation]:
    """Every repo ``threading.Thread(...)`` sets ``daemon=``
    explicitly: daemon-ness is inherited from the CREATOR by default,
    so the same spawn site produces a process-pinning non-daemon
    thread or a silently-killed daemon depending on who called it."""
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and _unparse(node.func) in ("threading.Thread",
                                                "Thread")):
                continue
            if any(k.arg == "daemon" for k in node.keywords):
                continue
            out.append(Violation(
                "daemon-declared", rel, node.lineno,
                "threading.Thread(...) without an explicit daemon= -- "
                "daemon-ness inherits from the creator, so this spawn "
                "site's shutdown behavior depends on who calls it"))
    return out


def rule_delta_carried(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        if not rel.startswith(_STATE_DIR):
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "_bump"):
                continue
            touches_allocs = any(
                (isinstance(a, ast.Constant) and a.value == "allocs")
                or isinstance(a, ast.Starred)   # _bump(*TABLES)
                for a in node.args)
            if not touches_allocs:
                continue
            if any(k.arg == "delta" for k in node.keywords):
                continue
            out.append(Violation(
                "delta-carried", rel, node.lineno,
                f"`{_unparse(node.func)}(\"allocs\", ...)` without "
                f"`delta=` -- the journal entry is an uncoverable gap "
                f"and every incremental-memo holder refolds wholesale "
                f"(pass the (old, new) pairs or waive with the reason "
                f"the write is wholesale by design)"))
    return out


# ----------------------------------------------------------------------
# shard-hygiene rules (ISSUE 15)

_PARALLEL_DIR = os.path.join("nomad_tpu", "parallel") + os.sep
# the runtime sanitizer inspects shardings (it never constructs puts)
# and is allowed to name the classes it audits
_SHARDCHECK_FILE = os.path.join("nomad_tpu", "shardcheck.py")

_SHARDING_CLASSES = ("PartitionSpec", "NamedSharding", "Mesh")


def _sharding_aliases(tree: ast.AST) -> Dict[str, str]:
    """Local names bound to jax.sharding classes in this module
    (``from jax.sharding import PartitionSpec as P`` binds P), so the
    rules catch the repo's aliasing idiom, not just the full names."""
    out: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom) and node.module and \
                node.module.startswith("jax.sharding"):
            for alias in node.names:
                if alias.name in _SHARDING_CLASSES:
                    out[alias.asname or alias.name] = alias.name
    return out


def _called_sharding_class(node: ast.Call,
                           aliases: Dict[str, str]) -> Optional[str]:
    """The jax.sharding class a Call constructs, or None: a direct
    alias call (``P(...)``) or an attribute chain ending in one
    (``jax.sharding.NamedSharding(...)``)."""
    f = node.func
    if isinstance(f, ast.Name):
        return aliases.get(f.id)
    if isinstance(f, ast.Attribute) and f.attr in _SHARDING_CLASSES:
        recv = _unparse(f.value)
        if recv.endswith("sharding") or recv == "jax":
            return f.attr
    return None


def _shard_rule_scans(rel: str) -> bool:
    return not (rel.startswith(_PARALLEL_DIR)
                or rel == _SHARDCHECK_FILE)


def rule_spec_declared(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        if not _shard_rule_scans(rel):
            continue
        aliases = _sharding_aliases(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            cls = _called_sharding_class(node, aliases)
            if cls in ("PartitionSpec", "NamedSharding"):
                out.append(Violation(
                    "spec-declared", rel, node.lineno,
                    f"`{cls}(...)` constructed outside "
                    f"nomad_tpu/parallel/ -- sharding intent lives in "
                    f"the parallel/mesh.py spec registry "
                    f"(SPEC_GROUPS/declared_specs); an inline spec is "
                    f"a contract shardcheck never compares against"))
    return out


def rule_mesh_factory(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        if not _shard_rule_scans(rel):
            continue
        aliases = _sharding_aliases(tree)
        for node in ast.walk(tree):
            if not isinstance(node, ast.Call):
                continue
            if _called_sharding_class(node, aliases) == "Mesh":
                out.append(Violation(
                    "mesh-factory", rel, node.lineno,
                    f"`Mesh(...)` constructed outside the parallel/ "
                    f"factories -- build meshes via make_mesh/"
                    f"pick_mesh/eval_axis_mesh so the topology stays "
                    f"one lru-cache-keyed artifact the spec registry "
                    f"declares against"))
    return out


def rule_no_implicit_put(ctx: Ctx) -> List[Violation]:
    out: List[Violation] = []
    for rel, _text, tree in ctx.files:
        if not _shard_rule_scans(rel):
            continue
        for node in ast.walk(tree):
            if not (isinstance(node, ast.Call)
                    and isinstance(node.func, (ast.Name, ast.Attribute))):
                continue
            name = (node.func.id if isinstance(node.func, ast.Name)
                    else node.func.attr)
            if name != "device_put":
                continue
            shard_args = [a for a in node.args[1:]] + [
                k.value for k in node.keywords
                if k.arg in ("device", "sharding", "out_shardings")]
            if any(re.search(r"[Ss]harding", _unparse(a))
                   for a in shard_args):
                out.append(Violation(
                    "no-implicit-put", rel, node.lineno,
                    f"`device_put` with a sharding argument outside "
                    f"nomad_tpu/parallel/ -- route sharded uploads "
                    f"through shard_solver_inputs/shard_eval_axis (or "
                    f"device_put_cached for unsharded buffers) so the "
                    f"transfer ledger's per-shard rows see them"))
    return out


AST_RULES = {
    "fire-registered": rule_fire_registered,
    "killswitch-tested": rule_killswitch_tested,
    "telemetry": rule_telemetry,           # emits -literal and -kind
    "sleep-under-lock": rule_sleep_under_lock,
    "bare-acquire": rule_bare_acquire,
    "no-callsite-jit": rule_no_callsite_jit,
    "no-host-sync-hot": rule_no_host_sync_hot,
    "dtype-threaded": rule_dtype_threaded,
    "frozen-memo": rule_frozen_memo,
    "fetch-accounted": rule_fetch_accounted,
    "no-direct-table-write": rule_no_direct_table_write,
    "version-keyed-memo": rule_version_keyed_memo,
    "no-snapshot-escape": rule_no_snapshot_escape,
    "delta-carried": rule_delta_carried,
    "join-with-timeout": rule_join_with_timeout,
    "no-sleep-sync": rule_no_sleep_sync,
    "daemon-declared": rule_daemon_declared,
    "spec-declared": rule_spec_declared,
    "mesh-factory": rule_mesh_factory,
    "no-implicit-put": rule_no_implicit_put,
}
# ids a violation may carry (for --rule selection and waiver matching)
RULE_IDS = ("fire-registered", "killswitch-tested", "telemetry-literal",
            "telemetry-kind", "sleep-under-lock", "bare-acquire",
            "no-callsite-jit", "no-host-sync-hot", "dtype-threaded",
            "frozen-memo", "fetch-accounted", "no-direct-table-write",
            "version-keyed-memo",
            "no-snapshot-escape", "delta-carried", "join-with-timeout",
            "no-sleep-sync", "daemon-declared", "spec-declared",
            "mesh-factory", "no-implicit-put")

LEGACY_RULES = ("metrics-doc", "knob-doc")


# ----------------------------------------------------------------------
# waivers + driver


def _load_legacy(name: str):
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        f"check_{name.replace('-', '_')}.py")
    spec = importlib.util.spec_from_file_location(
        f"_nomadlint_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def run_legacy(name: str) -> int:
    mod = _load_legacy(name)
    try:
        return mod.main()
    except SystemExit as e:         # legacy argparse usage errors
        return int(e.code or 0)


def apply_waivers(root: str, violations: List[Violation],
                  used: Optional[set] = None
                  ) -> Tuple[List[Violation], int]:
    """Drop violations waived at the site (or the line above) with a
    justified `# nomadlint: waive=<rule> -- reason` comment.  When
    ``used`` is provided, every (path, line, rule) whose waiver comment
    actually suppressed something is recorded into it -- the --stats
    stale-waiver inventory is the complement of that set."""
    kept: List[Violation] = []
    waived = 0
    lines_cache: Dict[str, List[str]] = {}
    for v in violations:
        path = os.path.join(root, v.path)
        lines = lines_cache.get(path)
        if lines is None:
            try:
                with open(path, encoding="utf-8") as f:
                    lines = f.read().splitlines()
            except OSError:
                lines = []
            lines_cache[path] = lines
        def _line_waives(ln: int) -> bool:
            if not 1 <= ln <= len(lines):
                return False
            m = _WAIVER.search(lines[ln - 1])
            ok = bool(m and v.rule in m.group(1).split(","))
            if ok and used is not None:
                used.add((v.path, ln, v.rule))
            return ok

        # the violating line, then the contiguous comment block above
        # it (multi-line justifications are the norm)
        hit = _line_waives(v.line)
        ln = v.line - 1
        while not hit and 1 <= ln <= len(lines) \
                and lines[ln - 1].lstrip().startswith("#"):
            hit = _line_waives(ln)
            ln -= 1
        if hit:
            waived += 1
        else:
            kept.append(v)
    return kept, waived


def collect_waiver_comments(root: str) -> List[Tuple[str, int, str]]:
    """Every ``nomadlint: waive=<rules>`` comment in the scanned tree
    (nomad_tpu/ + tests/, which no-sleep-sync lints) as
    (rel_path, line, rule) triples -- one per rule id the comment
    names."""
    out: List[Tuple[str, int, str]] = []
    scan = []
    for sub in ("nomad_tpu", "tests"):
        for dirpath, dirnames, filenames in os.walk(
                os.path.join(root, sub)):
            dirnames[:] = [d for d in dirnames if d != "__pycache__"]
            scan.extend(os.path.join(dirpath, f)
                        for f in sorted(filenames)
                        if f.endswith(".py"))
    for path in scan:
        rel = os.path.relpath(path, root)
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines()
        except OSError:
            continue
        for i, line in enumerate(lines, 1):
            m = _WAIVER.search(line)
            if not m:
                continue
            for rule in m.group(1).split(","):
                out.append((rel, i, rule))
    return out


def _rule_scans(path: str, rule: str) -> bool:
    """Whether ``rule`` scans ``path`` at all: a waiver can only be
    stale where its rule could fire (tests/ is linted only by
    no-sleep-sync; a lint-fixture string under tests/ that happens to
    contain a waiver comment for a code rule is not a stale waiver)."""
    in_tests = path.replace(os.sep, "/").startswith("tests/")
    if rule == "no-sleep-sync":
        return in_tests
    return not in_tests


def run_stats(root: str, rules: List[str]) -> Tuple[dict, List[tuple]]:
    """--stats: per-rule fired/waived counts plus the stale-waiver
    inventory (waiver comments that no longer suppress anything on
    their line -- removable)."""
    ctx = Ctx(root)
    violations = list(ctx.parse_errors)
    for key, fn in AST_RULES.items():
        ids = (("telemetry-literal", "telemetry-kind")
               if key == "telemetry" else (key,))
        if not any(r in rules for r in ids):
            continue
        violations.extend(v for v in fn(ctx) if v.rule in rules)
    used: set = set()
    kept, _waived = apply_waivers(root, violations, used=used)
    fired: Dict[str, int] = {r: 0 for r in rules}
    kept_counts: Dict[str, int] = {r: 0 for r in rules}
    for v in violations:
        fired[v.rule] = fired.get(v.rule, 0) + 1
    for v in kept:
        kept_counts[v.rule] = kept_counts.get(v.rule, 0) + 1
    waived_by_rule = {r: fired.get(r, 0) - kept_counts.get(r, 0)
                      for r in fired}
    comments = collect_waiver_comments(root)
    used_lines = {(p, ln) for (p, ln, _r) in used}
    stale = [(p, ln, rule) for (p, ln, rule) in comments
             if rule in rules and (p, ln) not in used_lines
             and _rule_scans(p, rule)]
    stats = {"fired": fired, "waived": waived_by_rule,
             "kept": len(kept), "waiver_comments": len(comments)}
    return stats, stale


def to_sarif(violations: List[Violation], rules: List[str]) -> dict:
    """SARIF 2.1.0 document for CI/editor annotation surfaces: one run,
    one driver (nomadlint), one result per kept violation."""
    return {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0"
                    ".json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "nomadlint",
                "informationUri":
                    "https://github.com/nomad-tpu/nomad-tpu",
                "rules": [{"id": r} for r in sorted(set(rules))],
            }},
            "results": [{
                "ruleId": v.rule,
                "level": "error",
                "message": {"text": v.msg},
                "locations": [{"physicalLocation": {
                    "artifactLocation": {
                        "uri": v.path.replace(os.sep, "/")},
                    "region": {"startLine": max(1, v.line)},
                }}],
            } for v in sorted(violations,
                              key=lambda v: (v.path, v.line))],
        }],
    }


def fix_stale_waivers(root: str, rules: List[str],
                      apply: bool = False) -> List[Tuple[str, int]]:
    """Delete waiver comment lines whose every named rule no longer
    fires on their line (the --stats removable inventory).  Dry-run by
    default: returns the (path, line) list; ``apply=True`` rewrites
    the files.  A comment naming several rules is only removed when
    ALL of them are stale there."""
    _stats, stale = run_stats(root, rules)
    stale_set = {(p, ln, r) for (p, ln, r) in stale}
    by_line: Dict[Tuple[str, int], List[str]] = {}
    for (p, ln, r) in collect_waiver_comments(root):
        by_line.setdefault((p, ln), []).append(r)
    removable = sorted(
        (p, ln) for (p, ln), rs in by_line.items()
        if all(r in rules and (p, ln, r) in stale_set for r in rs))
    if not apply:
        return removable
    by_file: Dict[str, List[int]] = {}
    for p, ln in removable:
        by_file.setdefault(p, []).append(ln)
    for p, lns in by_file.items():
        path = os.path.join(root, p)
        try:
            with open(path, encoding="utf-8") as f:
                lines = f.read().splitlines(keepends=True)
        except OSError:
            continue
        for ln in sorted(lns, reverse=True):
            if not 1 <= ln <= len(lines):
                continue
            text = lines[ln - 1]
            if text.lstrip().startswith("#"):
                del lines[ln - 1]       # whole-line waiver comment
            else:
                # trailing waiver on a code line: strip the comment
                lines[ln - 1] = re.sub(
                    r"\s*#\s*nomadlint:.*$", "",
                    text.rstrip("\n")) + (
                        "\n" if text.endswith("\n") else "")
        with open(path, "w", encoding="utf-8") as f:
            f.writelines(lines)
    return removable


def run_ast_rules(root: str, rules: List[str]) -> Tuple[List[Violation],
                                                        int]:
    ctx = Ctx(root)
    violations = list(ctx.parse_errors)
    for key, fn in AST_RULES.items():
        ids = (("telemetry-literal", "telemetry-kind")
               if key == "telemetry" else (key,))
        if not any(r in rules for r in ids):
            continue
        violations.extend(v for v in fn(ctx) if v.rule in rules)
    return apply_waivers(root, violations)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="nomadlint",
        description="repo-invariant lint driver (see module docstring)")
    p.add_argument("--root", default=ROOT,
                   help="repo root to lint (fixture tests point this "
                   "at a synthetic tree)")
    p.add_argument("--rule", action="append", default=[],
                   help="run only this rule id (repeatable); default: "
                   "all AST rules + metrics-doc + knob-doc")
    p.add_argument("--list", action="store_true",
                   help="list rule ids and exit")
    p.add_argument("--stats", action="store_true",
                   help="per-rule fire/waiver inventory + stale-waiver "
                   "detection (a waiver whose rule no longer fires on "
                   "its line is removable); exit 1 when stale waivers "
                   "exist")
    p.add_argument("--sarif", metavar="PATH", default=None,
                   help="also write the kept violations as SARIF "
                   "2.1.0 to PATH ('-' = stdout) for CI/editor "
                   "annotations")
    p.add_argument("--fix-stale-waivers", action="store_true",
                   help="delete waiver comment lines --stats flags as "
                   "removable; DRY-RUN by default (lists them), pass "
                   "--apply to rewrite the files")
    p.add_argument("--apply", action="store_true",
                   help="with --fix-stale-waivers: actually rewrite")
    args = p.parse_args(argv)

    if args.fix_stale_waivers:
        rules = [r for r in (args.rule or list(RULE_IDS))
                 if r in RULE_IDS]
        removed = fix_stale_waivers(args.root, rules, apply=args.apply)
        verb = "removed" if args.apply else "would remove (dry-run; " \
            "pass --apply to rewrite)"
        for path, line in removed:
            print(f"  {path}:{line}")
        print(f"fix-stale-waivers: {len(removed)} waiver line(s) "
              f"{verb}")
        return 0

    if args.stats:
        rules = args.rule or list(RULE_IDS)
        ast_rules = [r for r in rules if r in RULE_IDS]
        stats, stale = run_stats(args.root, ast_rules)
        print(f"{'rule':24s} {'fired':>6s} {'waived':>7s} {'kept':>5s}")
        for r in ast_rules:
            f = stats["fired"].get(r, 0)
            w = stats["waived"].get(r, 0)
            print(f"{r:24s} {f:6d} {w:7d} {f - w:5d}")
        print(f"waiver comments in tree: {stats['waiver_comments']}")
        if stale:
            print(f"\nstale waivers (rule no longer fires on that "
                  f"line -- removable): {len(stale)}")
            for path, line, rule in stale:
                print(f"  {path}:{line}: waive={rule}")
            return 1
        print("no stale waivers")
        return 0

    if args.list:
        for r in RULE_IDS:
            print(r)
        for r in LEGACY_RULES:
            print(f"{r} (legacy: scripts/check_"
                  f"{r.replace('-', '_')}.py)")
        return 0

    known = set(RULE_IDS) | set(LEGACY_RULES)
    for r in args.rule:
        if r not in known:
            print(f"unknown rule {r!r} (see --list)")
            return 2
    selected = args.rule or (list(RULE_IDS) + ["metrics-doc",
                                               "knob-doc"])

    rc = 0
    ast_selected = [r for r in selected if r in RULE_IDS]
    if ast_selected:
        kept, waived = run_ast_rules(args.root, ast_selected)
        for v in sorted(kept, key=lambda v: (v.path, v.line)):
            print(f"{v.path}:{v.line}: [{v.rule}] {v.msg}")
        note = f" ({waived} waived)" if waived else ""
        if kept:
            print(f"nomadlint: {len(kept)} violation(s){note}")
            rc = 1
        else:
            print(f"nomadlint: AST rules clean{note} "
                  f"[{', '.join(ast_selected)}]")
        if args.sarif:
            import json
            doc = to_sarif(kept, ast_selected)
            if args.sarif == "-":
                print(json.dumps(doc, indent=2))
            else:
                with open(args.sarif, "w", encoding="utf-8") as f:
                    json.dump(doc, f, indent=2)
                print(f"nomadlint: SARIF written to {args.sarif} "
                      f"({len(kept)} result(s))")
    for name in LEGACY_RULES:
        if name not in selected:
            continue
        if args.root != ROOT:
            print(f"nomadlint: skipping legacy rule {name} under "
                  f"--root (it scans the real repo)")
            continue
        lrc = run_legacy(name)
        if lrc:
            print(f"nomadlint: legacy rule {name} failed (rc={lrc})")
            rc = 1
    return rc


if __name__ == "__main__":
    sys.exit(main())
