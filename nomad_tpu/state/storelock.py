"""The state store's one lock, with an account of who waited for it.

Every write of the control plane, and every read that iterates a live
table, goes through ``StateStore._lock`` (point reads and blocking
queries do not: store.py's reader contract, watch.py). This is that
RLock behind a thin wrapper that
says how long threads wait for it, which kind of thread waited, and
which store method held it meanwhile -- the convoy that
``nomad.broker.eval_wait``, ``solver.barrier`` and the HTTP handlers all
stand in, as a number.

The free path stays free: an acquire first tries without blocking, and
when that succeeds it reads no clock and allocates nothing -- it counts
itself and, when it is the thread's outermost, notes the name of the
function that took the lock (for a store method's ``with self._lock:``
that is the method). Only an acquire that has to block reads the clock,
and afterwards charges its wait to

  nomad.state.lock_wait_us.<role>        by the waiter's thread name
  nomad.state.lock_blocked_by_us.<name>  by the holder's note
  nomad.state.lock_contended             +1

``nomad.state.lock_acquires`` counts every outermost acquire, flushed
to telemetry in batches of 256 and with every contended acquire (a
telemetry call an acquire would double the wrapper's cost). The waiter
books its wait when it lets the lock go again, after the release: the
lock is what everything waits for, so nothing is booked under it.

No condition is built on this lock: the store's watchers wait on the
watch registry's own lock (watch.py), never here.

With ``NOMAD_TPU_TRACE=0`` at construction the store holds the raw RLock
(``make_store_lock``).
"""
from __future__ import annotations

import os
import sys
import threading
from time import perf_counter

from .. import schedcheck

_WAIT_SERIES = {
    "worker": "nomad.state.lock_wait_us.worker",
    "http": "nomad.state.lock_wait_us.http",
    "applier": "nomad.state.lock_wait_us.applier",
    "core": "nomad.state.lock_wait_us.core",
    "other": "nomad.state.lock_wait_us.other",
}

# holders worth a series of their own: the store methods PERF.md's stack
# samples (PR 24) and the first chip runs of the account (PR 25) name;
# the rest share `.other`
_BLOCKED_BY_SERIES = {
    "upsert_job": "nomad.state.lock_blocked_by_us.upsert_job",
    "upsert_evals": "nomad.state.lock_blocked_by_us.upsert_evals",
    "update_job_status": "nomad.state.lock_blocked_by_us.update_job_status",
    # solver/service.py folds the live alloc table under the lock
    "_pack_usage_from_table":
        "nomad.state.lock_blocked_by_us.pack_usage_from_table",
    "upsert_plan_results":
        "nomad.state.lock_blocked_by_us.upsert_plan_results",
    "apply_plan_results_batch":
        "nomad.state.lock_blocked_by_us.apply_plan_results_batch",
    "update_allocs_from_client":
        "nomad.state.lock_blocked_by_us.update_allocs_from_client",
    "snapshot": "nomad.state.lock_blocked_by_us.snapshot",
}
_BLOCKED_BY_OTHER = "nomad.state.lock_blocked_by_us.other"

_WORKER_THREADS = ("batch-", "scheduler-worker-", "lpq-eval-",
                   "solver-dispatch-", "dispatch-")
_CORE_THREADS = frozenset(("core-gc", "heartbeat", "periodic",
                           "deploy-watch", "volume-watch", "drainer"))
_FLUSH_MASK = 255


def thread_role(name: str) -> str:
    if name.startswith(_WORKER_THREADS):
        return "worker"
    if "process_request_thread" in name or name.startswith("http-"):
        return "http"
    if name.startswith("plan-"):
        return "applier"
    if name in _CORE_THREADS:
        return "core"
    return "other"


def _charge(blocker, waited_s: float, acquires: int) -> None:
    """A wait that is over, handed to telemetry by the waiter after it
    has let the lock go again: nothing is booked while the lock, which
    everything waits for, is held. ``blocker``: the holder's note read
    before the wait began; ``acquires``: the acquires counted since the
    last flush."""
    from ..server.telemetry import metrics
    us = int(waited_s * 1e6)
    # nomadlint: waive=telemetry-literal -- table dispatch; every value
    # of _WAIT_SERIES / _BLOCKED_BY_SERIES is a literal
    metrics.incr(_WAIT_SERIES[thread_role(
        threading.current_thread().name)], us)
    # nomadlint: waive=telemetry-literal -- as above
    metrics.incr(_BLOCKED_BY_SERIES.get(blocker, _BLOCKED_BY_OTHER), us)
    metrics.incr("nomad.state.lock_contended")
    metrics.incr("nomad.state.lock_acquires", acquires)


class StoreLock:
    __slots__ = ("_inner", "_acquire", "_release", "_depth", "_holder",
                 "_n", "_owed")

    def __init__(self, inner):
        self._inner = inner
        self._acquire = inner.acquire
        self._release = inner.release
        # owner-only state, written while the lock is held
        self._depth = 0
        self._n = 0
        # (blocker, seconds) of the wait the owner's outermost acquire
        # cost it, booked when it lets go
        self._owed = None
        # read by waiters without the lock: a name or None, possibly
        # one holder stale
        self._holder = None

    def __enter__(self):
        if schedcheck._ACTIVE:
            # under the schedule explorer every acquire stays a blocking
            # one: that is where lockcheck's wrapper puts the decision
            # point (no account is kept of a virtual wait)
            self._acquire()
            self._entered(sys._getframe(1).f_code.co_name)
        elif self._acquire(False):
            self._entered(sys._getframe(1).f_code.co_name)
        else:
            self._enter_blocking(sys._getframe(1).f_code.co_name)
        return self

    def __exit__(self, *exc):
        self.release()
        return False

    def acquire(self, blocking=True, timeout=-1):
        taker = sys._getframe(1).f_code.co_name
        if schedcheck._ACTIVE and blocking:
            if not self._acquire(True, timeout):    # as in __enter__
                return False
            self._entered(taker)
        elif self._acquire(False):
            self._entered(taker)
        elif not blocking:
            return False
        elif timeout is None or timeout < 0:
            self._enter_blocking(taker)
        elif self._acquire(True, timeout):
            # a bounded wait is no store method's path; uncharged
            self._entered(taker)
        else:
            return False
        return True

    def release(self):
        d = self._depth = self._depth - 1
        if d or self._owed is None:
            self._release()
            return
        owed, n = self._owed, self._n
        self._owed, self._n = None, 0
        self._release()
        _charge(*owed, n)

    def _entered(self, taker: str) -> None:
        """Lock held: count the acquire if it is this thread's
        outermost, and note who took it."""
        d = self._depth
        self._depth = d + 1
        if not d:
            self._holder = taker
            n = self._n = self._n + 1
            if not n & _FLUSH_MASK:
                from ..server.telemetry import metrics
                self._n = 0
                metrics.incr("nomad.state.lock_acquires", n)

    def _enter_blocking(self, taker: str) -> None:
        """The non-blocking try failed (so this is the thread's
        outermost acquire): wait, and owe the wait."""
        blocker = self._holder
        t0 = perf_counter()
        self._acquire()     # paired with the caller's __exit__ / release
        self._owed = (blocker, perf_counter() - t0)
        self._entered(taker)

    def __repr__(self) -> str:
        return f"<StoreLock depth={self._depth} inner={self._inner!r}>"


def make_store_lock():
    """The store's lock: accounted while the tracer is on, the raw
    RLock under NOMAD_TPU_TRACE=0 (tracing.trace_enabled's reading,
    not imported: a bare store must not pull in the server package)."""
    inner = threading.RLock()
    if os.environ.get("NOMAD_TPU_TRACE", "1") == "0":
        return inner
    return StoreLock(inner)
