"""Not a fault: a probe. Times the server's periodic core GC job
(server/core.py run_gc_once, every GC_INTERVAL seconds of the server's
life) and prints when it ran and how long it held the control plane, on
this process's monotonic clock, so a run's slow stretch can be laid
beside it (PERF.md section 5)."""
import sys
import time

from nomad_tpu.server import core

_run = core.Server.run_gc_once


def timed(self, *a, **kw):
    t0 = time.monotonic()
    out = _run(self, *a, **kw)
    print(f"perfbench.probe: core gc at {t0:.3f} took "
          f"{time.monotonic() - t0:.3f}s: {out}", file=sys.stderr, flush=True)
    return out


core.Server.run_gc_once = timed
