#!/usr/bin/env python3
"""checkup: the single-entry static-suite driver (ISSUE 15 satellite).

One command, one exit code for every static gate the repo carries:

  nomadlint        every AST lint rule (scripts/nomadlint.py), with
                   the usual per-site waiver semantics
  knob-doc         scripts/check_knob_doc.py -- every NOMAD_TPU_* env
                   read documented in an OPERATIONS.md knob table
  metrics-doc      scripts/check_metrics_doc.py -- every emitted
                   telemetry series in the metrics reference table
  sanitizer-gates  scripts/check_sanitizer_gates.py -- the conftest
                   sanitizer fixtures cover their pinned suites
  native           build native/ (g++), assert the ABI
                   stamp matches nomad_tpu.native.ABI_VERSION, and
                   require a registered numpy-fallback parity test for
                   every exported C kernel (skip-with-notice when no
                   C++ toolchain exists)
  compile-audit    `operator shardcheck --compile-audit` in a fresh
                   subprocess -- AOT-compile every registered mesh
                   program (greedy both spread variants + LPQ) on a
                   virtual 8-device mesh and fail on any audit error
                   or unbudgeted collective (skip-with-notice when
                   jax is unavailable)

``checkup`` runs them all (or a ``--only NAME`` subset, repeatable)
and exits nonzero when ANY component fails -- the one pre-merge gate
a contributor (or CI) needs instead of four separate invocations.
``--sarif PATH`` ('-' = stdout) merges every component's findings into
ONE SARIF 2.1.0 document: nomadlint's kept violations ride verbatim
(file/line regions intact), and each failing legacy component
contributes one result per stdout finding line under its component
name as the rule id.

The standalone scripts keep working unchanged; each stays tier-1
gated by its own test. tests/test_checkup.py gates this driver.
"""
from __future__ import annotations

import argparse
import contextlib
import importlib.util
import io
import json
import os
import sys
from typing import Callable, Dict, List, Tuple

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_SCRIPTS = os.path.dirname(os.path.abspath(__file__))


def _load(script: str):
    path = os.path.join(_SCRIPTS, script)
    spec = importlib.util.spec_from_file_location(
        f"_checkup_{script[:-3]}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _run_nomadlint() -> Tuple[int, List[str], List[dict]]:
    """(rc, finding lines, SARIF results) for the full AST rule set.
    The legacy doc checkers run as their own checkup components, so
    the lint component is rules-only (no double reporting)."""
    nl = _load("nomadlint.py")
    kept, waived = nl.run_ast_rules(ROOT, list(nl.RULE_IDS))
    lines = [repr(v) for v in sorted(kept,
                                     key=lambda v: (v.path, v.line))]
    results = nl.to_sarif(kept, list(nl.RULE_IDS))["runs"][0]["results"]
    rc = 1 if kept else 0
    lines.append(f"({waived} waived)")
    return rc, lines, results


def _run_script(script: str, component: str
                ) -> Tuple[int, List[str], List[dict]]:
    """Run a legacy checker's main() with stdout captured; on failure
    every non-empty output line becomes one SARIF result under the
    component's rule id (the legacy gates report by line, not by
    file/region)."""
    import inspect

    mod = _load(script)
    takes_argv = bool(inspect.signature(mod.main).parameters)
    buf = io.StringIO()
    try:
        with contextlib.redirect_stdout(buf):
            rc = int((mod.main([]) if takes_argv else mod.main()) or 0)
    except SystemExit as e:  # argparse usage errors
        rc = int(e.code or 0)
    lines = [ln for ln in buf.getvalue().splitlines() if ln.strip()]
    results = []
    if rc:
        results = [{
            "ruleId": component,
            "level": "error",
            "message": {"text": ln},
            "locations": [{"physicalLocation": {
                "artifactLocation": {"uri": f"scripts/{script}"},
                "region": {"startLine": 1},
            }}],
        } for ln in lines]
    return rc, lines, results


def _native_results(msgs: List[str]) -> List[dict]:
    return [{
        "ruleId": "native",
        "level": "error",
        "message": {"text": m},
        "locations": [{"physicalLocation": {
            "artifactLocation": {"uri": "native/pack_kernels.cc"},
            "region": {"startLine": 1},
        }}],
    } for m in msgs]


def _run_native() -> Tuple[int, List[str], List[dict]]:
    """The native control-plane gate (ISSUE 17): build native/ the way
    the program does (nomad_tpu.native, g++), assert the built library's
    ABI stamp matches nomad_tpu.native.ABI_VERSION, and fail when any
    exported C kernel lacks a registered numpy-fallback parity test in
    tests/test_native.py::KERNEL_PARITY_TESTS.  With no C++ toolchain
    at all the gate skips with a notice (rc 0) -- the parity-registry
    check still runs, it is pure source inspection."""
    import re
    import shutil

    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    from nomad_tpu import native

    lines: List[str] = []
    failures: List[str] = []

    # available() compiles native/pack_kernels.cc on first use and
    # refuses a library whose ABI stamp disagrees with the bindings
    built = native.available()
    if built:
        lines.append(f"built + loaded, ABI v{native.ABI_VERSION}")
    elif shutil.which("g++"):
        try:
            native.build()
            failures.append("native library builds but does not load "
                            "(ABI stamp != nomad_tpu.native.ABI_VERSION?)")
        except native.NativeBuildError as e:
            failures.append(f"g++ build failed: {e}")
    else:
        lines.append("notice: no C++ toolchain (g++) -- "
                     "native build skipped")

    # parity-registry completeness: every exported nt_* symbol must map
    # to an existing test (source inspection -- runs even toolchain-less)
    src = open(os.path.join(ROOT, "native", "pack_kernels.cc"),
               encoding="utf-8").read()
    exported = set(re.findall(
        r"^(?:void|int32_t|int64_t|double)\s+(nt_\w+)\s*\(",
        src, re.MULTILINE))
    tests_src = open(os.path.join(ROOT, "tests", "test_native.py"),
                     encoding="utf-8").read()
    m = re.search(r"KERNEL_PARITY_TESTS\s*=\s*\{(.*?)\n\}",
                  tests_src, re.DOTALL)
    registry = dict(re.findall(r'"(nt_\w+)":\s*\n?\s*"([^"]+)"',
                               m.group(1))) if m else {}
    if not m:
        failures.append("tests/test_native.py has no "
                        "KERNEL_PARITY_TESTS registry")
    for sym in sorted(exported - set(registry)):
        failures.append(f"exported kernel {sym} has no registered "
                        "parity test (KERNEL_PARITY_TESTS)")
    for sym, ref in sorted(registry.items()):
        path, _, test = ref.partition("::")
        full = os.path.join(ROOT, path)
        if not os.path.exists(full) or \
                f"def {test}(" not in open(full, encoding="utf-8").read():
            failures.append(f"{sym}: registered parity test {ref} "
                            "does not exist")

    if failures:
        return 1, lines + failures, _native_results(failures)
    return 0, lines, []


def _run_compile_audit() -> Tuple[int, List[str], List[dict]]:
    """The mesh compile-audit gate (ISSUE 19 satellite): run
    ``operator shardcheck --compile-audit`` in a FRESH subprocess
    pinned to the CPU platform (the virtual-device XLA flag only takes
    effect before jax initializes, and an attached accelerator belongs
    to one process: this driver stays off jax so the child, or whoever
    holds the chip, is never contended) and fail on a
    nonzero rc -- audit errors and unbudgeted collectives both exit 1
    there.  With jax not importable the gate skips with a notice
    (rc 0): the static suite stays runnable on doc-only checkouts."""
    import subprocess

    if importlib.util.find_spec("jax") is None:
        return 0, ["notice: jax unavailable -- mesh compile audit "
                   "skipped"], []
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    cmd = [sys.executable, "-m", "nomad_tpu.cli", "operator",
           "shardcheck", "--compile-audit"]
    try:
        proc = subprocess.run(cmd, cwd=ROOT, env=env,
                              capture_output=True, text=True,
                              timeout=300)
    except (subprocess.SubprocessError, OSError) as e:
        failures = [f"compile audit subprocess failed: {e}"]
        return 1, failures, [{
            "ruleId": "compile-audit",
            "level": "error",
            "message": {"text": failures[0]},
            "locations": [{"physicalLocation": {
                "artifactLocation": {
                    "uri": "nomad_tpu/shardcheck.py"},
                "region": {"startLine": 1},
            }}],
        }]
    out_lines = [ln for ln in (proc.stdout + proc.stderr).splitlines()
                 if ln.strip()]
    if proc.returncode:
        return 1, out_lines, [{
            "ruleId": "compile-audit",
            "level": "error",
            "message": {"text": ln},
            "locations": [{"physicalLocation": {
                "artifactLocation": {
                    "uri": "nomad_tpu/shardcheck.py"},
                "region": {"startLine": 1},
            }}],
        } for ln in out_lines
            if "error" in ln.lower() or "excess" in ln.lower()
        ] or [{
            "ruleId": "compile-audit",
            "level": "error",
            "message": {"text":
                        f"compile audit exit {proc.returncode}"},
            "locations": [{"physicalLocation": {
                "artifactLocation": {
                    "uri": "nomad_tpu/shardcheck.py"},
                "region": {"startLine": 1},
            }}],
        }]
    n_programs = sum(1 for ln in out_lines
                     if ln.startswith("program:"))
    return 0, [f"{n_programs} mesh program(s) audited clean"], []


COMPONENTS: Dict[str, Callable[[], Tuple[int, List[str], List[dict]]]] = {
    "nomadlint": _run_nomadlint,
    "knob-doc": lambda: _run_script("check_knob_doc.py", "knob-doc"),
    "metrics-doc": lambda: _run_script("check_metrics_doc.py",
                                       "metrics-doc"),
    "sanitizer-gates": lambda: _run_script("check_sanitizer_gates.py",
                                           "sanitizer-gates"),
    "native": _run_native,
    "compile-audit": _run_compile_audit,
}


def to_sarif(results: List[dict], rules: List[str]) -> dict:
    return {
        "$schema": ("https://raw.githubusercontent.com/oasis-tcs/"
                    "sarif-spec/master/Schemata/sarif-schema-2.1.0"
                    ".json"),
        "version": "2.1.0",
        "runs": [{
            "tool": {"driver": {
                "name": "checkup",
                "informationUri":
                    "https://github.com/nomad-tpu/nomad-tpu",
                "rules": [{"id": r} for r in sorted(set(rules))],
            }},
            "results": results,
        }],
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        prog="checkup",
        description="run every static gate (nomadlint + knob-doc + "
        "metrics-doc + sanitizer-gates + native + compile-audit) "
        "with one combined exit code")
    p.add_argument("--only", action="append", default=[],
                   metavar="NAME",
                   help="run only this component (repeatable); "
                   f"known: {', '.join(COMPONENTS)}")
    p.add_argument("--list", action="store_true",
                   help="list component names and exit")
    p.add_argument("--sarif", metavar="PATH", default=None,
                   help="write the merged findings as SARIF 2.1.0 to "
                   "PATH ('-' = stdout)")
    args = p.parse_args(argv)

    if args.list:
        for name in COMPONENTS:
            print(name)
        return 0
    for name in args.only:
        if name not in COMPONENTS:
            print(f"unknown component {name!r} "
                  f"(have: {', '.join(COMPONENTS)})")
            return 2
    selected = args.only or list(COMPONENTS)

    rc = 0
    all_results: List[dict] = []
    rule_ids: List[str] = []
    verdicts = []
    for name in COMPONENTS:
        if name not in selected:
            continue
        crc, lines, results = COMPONENTS[name]()
        verdicts.append((name, crc))
        all_results.extend(results)
        rule_ids.extend(r["ruleId"] for r in results)
        if crc:
            rc = 1
            print(f"== {name}: FAIL (rc={crc})")
            for ln in lines:
                print(f"   {ln}")
        else:
            print(f"== {name}: ok")
    print("checkup: " + "  ".join(
        f"{n}={'FAIL' if c else 'ok'}" for n, c in verdicts)
        + f"  -> exit {rc}")

    if args.sarif:
        doc = to_sarif(all_results, rule_ids or ["checkup"])
        if args.sarif == "-":
            print(json.dumps(doc, indent=2))
        else:
            with open(args.sarif, "w", encoding="utf-8") as f:
                json.dump(doc, f, indent=2)
            print(f"checkup: SARIF written to {args.sarif} "
                  f"({len(all_results)} result(s))")
    return rc


if __name__ == "__main__":
    sys.exit(main())
