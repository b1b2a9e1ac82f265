#!/usr/bin/env python3
"""CI gate: every telemetry series the code emits must appear in the
docs/OPERATIONS.md "Metrics reference" table.

Scans nomad_tpu/ for ``metrics.incr/sample/sample_ms/measure``
call sites (any local alias -- the codebase uses both ``metrics`` and
``_tm``), extracts the literal series names (f-string placeholders
normalize to ``<...>`` wildcards, ternaries contribute both arms), and
fails listing any name missing from the doc table. Undocumented drift
is exactly how the `batch_lanes`-rendered-as-ms bug survived two
rounds: nobody could diff "what we emit" against "what we documented".

Exit 0: documented. Exit 1: drift (missing names listed on stdout).
Stale doc entries (documented but never emitted) print as warnings
only -- a satellite removing a series should not be blocked by the doc
it is about to fix, but the noise is visible.
"""
from __future__ import annotations

import os
import re
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DOC = os.path.join(ROOT, "docs", "OPERATIONS.md")

# a metrics emit call (any receiver alias; _count is tracing.py's
# guarded incr wrapper), then every "nomad.*" string literal within the
# call's argument window
_CALL = re.compile(
    r"\b\w+\.(?:incr|sample_ms|sample|measure|_count)\(", re.MULTILINE)
_NAME = re.compile(r'f?"(nomad\.[A-Za-z0-9_.{}]+)"')


def _normalize(name: str) -> str:
    """f-string placeholders and doc-side <...> both become '*'."""
    name = re.sub(r"\{[^}]*\}", "*", name)
    name = re.sub(r"<[^>]*>", "*", name)
    return name


def emitted_series() -> dict:
    """name -> first 'file:line' emitting it."""
    out: dict = {}
    scan = []
    for dirpath, dirnames, filenames in os.walk(
            os.path.join(ROOT, "nomad_tpu")):
        dirnames[:] = [d for d in dirnames if d != "__pycache__"]
        scan.extend(os.path.join(dirpath, f) for f in filenames
                    if f.endswith(".py"))
    for path in scan:
        try:
            with open(path, encoding="utf-8") as f:
                text = f.read()
        except OSError:
            continue
        rel = os.path.relpath(path, ROOT)
        for m in _CALL.finditer(text):
            # argument window: enough for a multi-line ternary, short
            # enough not to swallow the next call's literals
            window = text[m.end():m.end() + 160]
            nxt = _CALL.search(window)
            if nxt:
                window = window[:nxt.start()]
            for nm in _NAME.finditer(window):
                name = _normalize(nm.group(1))
                line = text.count("\n", 0, m.start()) + 1
                out.setdefault(name, f"{rel}:{line}")
    return out


def documented_series() -> set:
    with open(DOC, encoding="utf-8") as f:
        text = f.read()
    marker = "## Metrics reference"
    idx = text.find(marker)
    if idx < 0:
        print(f"ERROR: no '{marker}' section in {DOC}")
        sys.exit(1)
    section = text[idx:]
    nxt = section.find("\n## ", len(marker))
    if nxt > 0:
        section = section[:nxt]
    return {_normalize(m.group(1))
            for m in re.finditer(r"`(nomad\.[A-Za-z0-9_.<>{}]+)`",
                                 section)}


def main() -> int:
    emitted = emitted_series()
    documented = documented_series()
    missing = {n: at for n, at in sorted(emitted.items())
               if n not in documented}
    stale = sorted(documented - set(emitted))
    if stale:
        for n in stale:
            print(f"warning: documented but never emitted: {n}")
    if missing:
        print(f"{len(missing)} emitted series missing from the "
              f"OPERATIONS.md metrics reference table:")
        for n, at in missing.items():
            print(f"  {n}  (emitted at {at})")
        return 1
    print(f"metrics doc in sync: {len(emitted)} emitted series all "
          "documented")
    return 0


if __name__ == "__main__":
    sys.exit(main())
