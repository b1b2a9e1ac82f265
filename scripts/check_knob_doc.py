#!/usr/bin/env python3
"""CI gate: every ``NOMAD_TPU_*`` env knob the code reads must appear
in a docs/OPERATIONS.md knob table row.

The mirror of scripts/check_metrics_doc.py for configuration: scans
nomad_tpu/ for ``os.environ.get/.pop/[...]`` call sites
naming a ``NOMAD_TPU_*`` variable, and fails listing any knob missing
from the doc's knob tables (markdown table rows -- lines starting with
``|`` -- anywhere in OPERATIONS.md).  Prose mentions do NOT count: the
tables are the operator contract, and an undocumented knob is exactly
how a kill switch gets forgotten in an incident.

Doc tables may abbreviate families with suffix shorthand, e.g.
``| `NOMAD_TPU_CONST_CACHE_ENTRIES` / `_MB` / `_MIN_BYTES` | ... |`` --
each ``_SUF`` expands against the preceding full knob on the same row
(both ``<full>_SUF`` and ``<full-minus-last-segment>_SUF`` are
accepted, so ``NOMAD_TPU_FLAP`` / ``_THRESHOLD`` covers
``NOMAD_TPU_FLAP_THRESHOLD`` and ``NOMAD_TPU_CONST_CACHE_ENTRIES`` /
``_MB`` covers ``NOMAD_TPU_CONST_CACHE_MB``).

Exit 0: documented.  Exit 1: drift (missing knobs listed on stdout).
Knobs documented in a table but never read print as warnings only.
Tier-1 gated by tests/test_knob_doc.py.
"""
from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(ROOT, "docs", "OPERATIONS.md")

# an environ read/pop/subscript, tolerant of the call spilling to the
# next line (os.environ.get(\n    "NOMAD_TPU_X", ...))
_CALL = re.compile(
    r'environ(?:\.get|\.pop|\.setdefault)?'
    r'(?:\(\s*|\[\s*)"(NOMAD_TPU_[A-Z0-9_]+)"')
# a knob bound to a module constant and read indirectly
# (plugins/base.py MAGIC_ENV = "NOMAD_TPU_PLUGIN_MAGIC")
_CONST = re.compile(
    r'^[A-Z][A-Z0-9_]*\s*=\s*"(NOMAD_TPU_[A-Z0-9_]+)"', re.MULTILINE)

_FULL = re.compile(r"`(NOMAD_TPU_[A-Z0-9_]+)`")
_SHORT = re.compile(r"`(_[A-Z0-9_]+)`")


def code_knobs(root: str = None) -> dict:
    """knob -> first 'file:line' reading it."""
    root = root or ROOT
    out: dict = {}
    scan = []
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(root, "nomad_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        scan.extend(os.path.join(dirpath, f) for f in filenames
                    if f.endswith(".py"))
    for path in scan:
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        except OSError:
            continue
        rel = os.path.relpath(path, root)
        for pat in (_CALL, _CONST):
            for m in pat.finditer(text):
                line = text.count("\n", 0, m.start()) + 1
                out.setdefault(m.group(1), f"{rel}:{line}")
    return out


def _expand_shorthand(full: str, suffix: str) -> list:
    """Candidate expansions of a `_SUF` shorthand against the last full
    knob on the row: append to the full name, and to every
    progressively segment-stripped prefix of it."""
    out = [full + suffix]
    parts = full.split("_")
    while len(parts) > 2:           # keep at least NOMAD_TPU
        parts = parts[:-1]
        out.append("_".join(parts) + suffix)
    return out


def documented_knobs(doc_text: str) -> tuple:
    """(literal, expanded): knobs named verbatim in a markdown table
    row, and the full set including suffix-shorthand expansions.
    Membership checks use ``expanded``; staleness warnings use
    ``literal`` only (expansion candidates are over-generated by
    design and would read as phantom stale entries)."""
    literal: set = set()
    expanded: set = set()
    for line in doc_text.splitlines():
        if not line.lstrip().startswith("|"):
            continue
        last_full = None
        for m in re.finditer(r"`(_?[A-Z0-9_]+)`", line):
            tok = m.group(1)
            if tok.startswith("NOMAD_TPU_"):
                literal.add(tok)
                expanded.add(tok)
                last_full = tok
            elif tok.startswith("_") and last_full is not None:
                expanded.update(_expand_shorthand(last_full, tok))
    return literal, expanded


def main() -> int:
    knobs = code_knobs()
    try:
        with open(DOC, encoding="utf-8") as f:
            doc = f.read()
    except OSError as e:
        print(f"ERROR: cannot read {DOC}: {e}")
        return 1
    literal, expanded = documented_knobs(doc)
    missing = {k: at for k, at in sorted(knobs.items())
               if k not in expanded}
    stale = sorted(literal - set(knobs))
    for k in stale:
        print(f"warning: documented but never read: {k}")
    if missing:
        print(f"{len(missing)} env knob(s) missing from the "
              f"OPERATIONS.md knob tables:")
        for k, at in missing.items():
            print(f"  {k}  (read at {at})")
        return 1
    print(f"knob doc in sync: {len(knobs)} env knobs all documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
