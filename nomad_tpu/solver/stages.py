"""The clock inside one fused dispatch: which stage held it.

``nomad.solver.dispatch`` is one timer around four things. Every
transport (solve_lane_fused, solve_lane_wave, solve_lane_wave_preempt,
the mesh leg of batch._dispatch) walks the same sequence and calls
``mark`` as it passes from one to the next:

  prep    host arrays for the transport (compact-table loop, stacks);
          the clock starts in it
  put     host -> device (device_put_cached / shard_eval_axis)
  launch  the jitted call returning (dispatch is asynchronous)
  fetch   device_get: device execution + copy back + this thread
          getting the interpreter back

(The fifth, ``solver.dispatch_unpack``, is solve_groups' own slicing
after the timer has stopped.) Stages are contiguous: ``mark`` closes
the open stage and opens the next on one clock reading, so the stage
totals are the dispatch timer's. Each stage is a span
(``solver.dispatch_<stage>``, on the profiler's timeline through
tracing's annotation) and, when the dispatch succeeds, exactly one
sample of its timer, so every stage timer's count is the dispatch
timer's.

The clock belongs to ``batch.solve_groups`` (``clock()``), is found
through a thread-local, and does not exist outside it: the fixpoint's
re-solves run the same transports and are not part of the dispatch
timer, so ``mark`` is a no-op there. Off with the tracer
(NOMAD_TPU_TRACE=0).
"""
from __future__ import annotations

import contextlib
import threading
import time

from ..server.telemetry import metrics
from ..server.tracing import trace_enabled, tracer

_SPAN_OF = {
    "prep": "solver.dispatch_prep",
    "put": "solver.dispatch_put",
    "launch": "solver.dispatch_launch",
    "fetch": "solver.dispatch_fetch",
}
_TLS = threading.local()


class _Clock:
    __slots__ = ("_ms", "_open", "_span", "_t0")

    def __init__(self):
        self._ms = dict.fromkeys(_SPAN_OF, 0.0)

    def _open_stage(self, stage: str, now: float) -> None:
        self._open = stage
        self._t0 = now
        self._span = tracer.span(_SPAN_OF[stage])
        self._span.__enter__()

    def _close_stage(self, now: float) -> None:
        self._ms[self._open] += (now - self._t0) * 1e3
        self._span.__exit__(None, None, None)

    def mark(self, stage: str) -> None:
        now = time.perf_counter()
        self._close_stage(now)
        self._open_stage(stage, now)

    def __enter__(self) -> "_Clock":
        _TLS.clock = self
        self._open_stage("prep", time.perf_counter())
        return self

    def __exit__(self, exc_type, exc, tb):
        _TLS.clock = None
        self._close_stage(time.perf_counter())
        if exc_type is None:
            ms = self._ms
            metrics.sample_ms("nomad.solver.dispatch_prep", ms["prep"])
            metrics.sample_ms("nomad.solver.dispatch_put", ms["put"])
            metrics.sample_ms("nomad.solver.dispatch_launch", ms["launch"])
            metrics.sample_ms("nomad.solver.dispatch_fetch", ms["fetch"])
        return False


def clock():
    """The stage clock of one fused dispatch, as a context manager."""
    return _Clock() if trace_enabled() else contextlib.nullcontext()


def mark(stage: str) -> None:
    """Pass into ``stage`` on this thread's dispatch clock, if it has
    one."""
    c = getattr(_TLS, "clock", None)
    if c is not None:
        c.mark(stage)
