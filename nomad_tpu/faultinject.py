"""Fault-injection framework: named failure points armed via env/HTTP.

A device that wedges MID-ROUND, after init succeeded, is a failure the
eval pipeline must be able to rehearse before it happens live.
Every component that can hang, error or lag in production declares a
named injection point; tests/test_chaos.py (and operators, via
/v1/operator/faults) arm faults at those points and assert the system
degrades the way the design promises -- bounded-time host fallback,
breaker trip + auto-recovery, broker nack/requeue, no lost evals.

Points wired through the codebase:

  solver.dispatch   solver/service.py + solver/batch.py -- fires INSIDE
                    the watchdog deadline, so hang faults exercise the
                    timeout path (guard.run_dispatch)
  solver.probe      solver/guard.py -- the breaker's recovery probe;
                    an armed fault keeps the breaker open (how chaos
                    tests hold "the device is still wedged")
  worker.invoke     server/worker.py invoke_scheduler -- an armed error
                    nacks the eval (broker requeue must not lose it)
  worker.crash      server/worker.py Worker.run / BatchWorker._run_batch
                    -- an armed error KILLS the worker thread mid-eval
                    (no nack: the leased eval is orphaned until the
                    broker's nack-timeout sweep redelivers it; the
                    WorkerSupervisor must restart the pool slot)
  plan.apply        server/plan_apply.py Planner.apply
  plan.commit       state/store.py apply_plan_results_batch -- fires
                    per plan BEFORE its writes stage, so an armed fault
                    splits a group commit around the injected plan
                    (survivors commit exactly once)
  broker.dequeue    server/broker.py EvalBroker.dequeue
  heartbeat         server/core.py Server.heartbeat
  raft.rpc          raft/transport.py TcpTransport.send (delay/drop)
  quality.skew      server/quality.py shadow-audit capture -- an armed
                    error corrupts the captured solve's scores the way
                    real solver numerics drift would, so chaos drills
                    prove the drift gauge + audit alert fire
                    (placements themselves are untouched)

Actions: ``error`` raises InjectedFault; ``drop`` raises InjectedDrop
(a ConnectionError, so transport callers treat it as a network failure);
``delay`` sleeps ``delay_s`` then continues; ``hang`` blocks until the
fault is disarmed (bounded by ``delay_s`` when given, else effectively
forever -- the watchdog deadline is what must save the caller).

Arming: programmatic (``faults.arm(...)``), HTTP
(``POST /v1/operator/faults``, operator:write), or the
``NOMAD_TPU_FAULT_INJECT`` env var at process start --
``point=action[:delay_s[:count]]`` entries separated by commas, e.g.
``NOMAD_TPU_FAULT_INJECT="solver.dispatch=hang,raft.rpc=delay:0.05:10"``.

The unarmed fast path is one attribute read -- safe on hot paths
(every RPC send and broker dequeue fires a point).
"""
from __future__ import annotations

import os
import threading
import time
from typing import Dict, Optional

from . import lockcheck

ACTIONS = ("error", "drop", "delay", "hang")

# The registered point inventory: every ``faults.fire(point)`` call
# site in the tree must name a member (scripts/nomadlint.py
# fire-registered rule parses this tuple; tests/test_chaos.py pins the
# chaos-suite inventory against it). Register the point HERE in the
# same change that adds the call site, with the module that fires it.
POINTS = (
    "solver.dispatch",      # solver/guard.py (inside the watchdog)
    "solver.probe",         # solver/guard.py (breaker recovery probe)
    "worker.invoke",        # server/worker.py invoke_scheduler
    "worker.crash",         # server/worker.py worker loops (kills thread)
    "plan.apply",           # server/plan_apply.py Planner.apply
    "plan.commit",          # state/store.py apply_plan_results_batch
    "broker.dequeue",       # server/broker.py EvalBroker.dequeue
    "heartbeat",            # server/core.py Server.heartbeat
    "raft.rpc",             # raft/transport.py TcpTransport.send
    "quality.skew",         # server/quality.py shadow-audit capture
)


class InjectedFault(Exception):
    """Raised at an armed injection point (action=error)."""


class InjectedDrop(ConnectionError):
    """Raised at an armed injection point (action=drop): looks like a
    network failure to transport-layer callers."""


class _Fault:
    __slots__ = ("point", "action", "delay_s", "count", "fired", "release")

    def __init__(self, point: str, action: str, delay_s: float,
                 count: Optional[int]):
        self.point = point
        self.action = action
        self.delay_s = delay_s
        self.count = count          # remaining injections; None = unlimited
        self.fired = 0
        self.release = threading.Event()    # set on disarm: wakes hangs

    def snapshot(self) -> dict:
        return {"point": self.point, "action": self.action,
                "delay_s": self.delay_s, "count": self.count,
                "fired": self.fired}


class FaultRegistry:
    """Process-global registry of armed faults, keyed by point name."""

    def __init__(self):
        self._lock = threading.Lock()
        self._faults: Dict[str, _Fault] = {}
        self._armed = False          # lock-free fast-path gate
        self._arm_from_env()

    def _arm_from_env(self) -> None:
        spec = os.environ.get("NOMAD_TPU_FAULT_INJECT", "").strip()
        if not spec:
            return
        for entry in spec.split(","):
            entry = entry.strip()
            if not entry or "=" not in entry:
                continue
            point, _, rhs = entry.partition("=")
            parts = rhs.split(":")
            action = parts[0] or "error"
            delay = float(parts[1]) if len(parts) > 1 and parts[1] else 0.0
            count = (int(parts[2])
                     if len(parts) > 2 and parts[2] else None)
            try:
                self.arm(point.strip(), action, delay_s=delay, count=count)
            except ValueError:
                continue            # a typo'd env entry must not abort boot

    # ------------------------------------------------------------------
    def arm(self, point: str, action: str = "error", delay_s: float = 0.0,
            count: Optional[int] = None) -> dict:
        if action not in ACTIONS:
            raise ValueError(f"unknown fault action {action!r} "
                             f"(one of {ACTIONS})")
        if not point:
            raise ValueError("fault point name required")
        f = _Fault(point, action, float(delay_s),
                   int(count) if count is not None else None)
        with self._lock:
            old = self._faults.get(point)
            if old is not None:
                old.release.set()
            self._faults[point] = f
            self._armed = True
        from .server.logbroker import log as _log
        _log("warn", "faultinject",
             f"armed {point}={action} delay={delay_s} count={count}")
        return f.snapshot()

    def disarm(self, point: str) -> bool:
        with self._lock:
            f = self._faults.pop(point, None)
            self._armed = bool(self._faults)
        if f is None:
            return False
        f.release.set()              # wake any thread hung at this point
        from .server.logbroker import log as _log
        _log("warn", "faultinject", f"disarmed {point}")
        return True

    def disarm_all(self) -> int:
        with self._lock:
            faults = list(self._faults.values())
            self._faults.clear()
            self._armed = False
        for f in faults:
            f.release.set()
        return len(faults)

    def snapshot(self) -> dict:
        with self._lock:
            return {"faults": [f.snapshot()
                               for f in self._faults.values()]}

    # ------------------------------------------------------------------
    def fire(self, point: str) -> None:
        """Called at an injection point. No-op unless the point is armed
        (one attribute read on the unarmed path, plus one module-attr
        read for the lock sanitizer, active only under
        NOMAD_TPU_LOCKCHECK=1)."""
        if lockcheck._ACTIVE:
            # a fault point may hang/raise BY DESIGN: holding a lock
            # across one turns an injected solver wedge into a
            # control-plane wedge (lockcheck held_across report)
            lockcheck.note_fire(point)
        if not self._armed:
            return
        with self._lock:
            f = self._faults.get(point)
            if f is None:
                return
            f.fired += 1
            if f.count is not None:
                f.count -= 1
                if f.count <= 0:
                    del self._faults[point]
                    self._armed = bool(self._faults)
                    f.release.set()
        from .server.telemetry import metrics
        metrics.incr(f"nomad.fault.injected.{point}")
        if f.action == "delay":
            time.sleep(f.delay_s)
            return
        if f.action == "hang":
            # blocks until disarmed (or delay_s when bounded); callers
            # are expected to survive via their own watchdog deadline
            f.release.wait(f.delay_s if f.delay_s > 0 else None)
            return
        if f.action == "drop":
            raise InjectedDrop(f"injected fault: {point} dropped")
        raise InjectedFault(f"injected fault: {point}")

    def _reset_for_tests(self) -> None:
        self.disarm_all()


# Process-global registry; `fire` is the hot-path entry point.
faults = FaultRegistry()
fire = faults.fire
