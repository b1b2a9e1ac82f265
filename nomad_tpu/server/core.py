"""Server core: wires state, broker, planner, workers, heartbeats, GC,
periodic dispatch and the deployment watcher into one control plane.

Semantic parity with /root/reference/nomad/server.go (NewServer :326,
setupWorkers :1793), leader.go (establishLeadership :357 -- broker/queue
enablement, GC timers :431), heartbeat.go (nodeHeartbeater :37),
core_sched.go (CoreScheduler GC :44), periodic.go (PeriodicDispatch :25),
deploymentwatcher/ and node_endpoint.go flows (Register :99, UpdateStatus
:541, UpdateAlloc :1322). Single-server dev topology: this process is
always the leader; the raft boundary is the StateStore write API.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import Dict, List, Optional

from ..state import StateStore
from ..structs import (
    Allocation, Deployment, DeploymentStatusUpdate, Evaluation, Job, Node,
    Plan, PlanResult, ScalingEvent, generate_uuid,
    ALLOC_CLIENT_FAILED, ALLOC_CLIENT_RUNNING, ALLOC_DESIRED_RUN,
    ALLOC_DESIRED_STOP, DEPLOYMENT_STATUS_FAILED,
    DEPLOYMENT_STATUS_PAUSED, DEPLOYMENT_STATUS_RUNNING,
    DEPLOYMENT_STATUS_SUCCESSFUL, EVAL_STATUS_BLOCKED, EVAL_STATUS_COMPLETE,
    EVAL_STATUS_PENDING, JOB_STATUS_DEAD, JOB_STATUS_RUNNING,
    JOB_TYPE_SERVICE, JOB_TYPE_SYSTEM,
    NODE_STATUS_DISCONNECTED, NODE_STATUS_DOWN, NODE_STATUS_READY,
    TRIGGER_DEPLOYMENT_WATCHER, TRIGGER_JOB_DEREGISTER, TRIGGER_JOB_REGISTER,
    TRIGGER_NODE_UPDATE, TRIGGER_PERIODIC_JOB,
)
from .broker import BlockedEvals, EvalBroker
from .inflight import InflightBookings
from .plan_apply import BadNodeTracker, Planner
from .worker import BatchWorker, Worker

DEFAULT_HEARTBEAT_TTL = 10.0
GC_EVAL_THRESHOLD = 3600.0
GC_INTERVAL = 60.0
SCHED_LAG_SLEEP_S = 0.05
# terminal allocs retained before the watermark GC pass kicks in
# (NOMAD_TPU_GC_ALLOC_WATERMARK overrides; 0 disables the pass)
GC_ALLOC_WATERMARK = 1_000_000


class NodeFlapTracker(BadNodeTracker):
    """Per-node flap damping (ISSUE 6): the heartbeat watcher records a
    hit on every ready->down transition (BadNodeTracker's windowed
    scoring); once a node's flap score crosses the threshold, its next
    down->ready transition is DEFERRED by an escalating quarantine
    window (exponential backoff in the score overshoot, capped), so one
    sick node cannot generate an eval storm by flapping -- each flap
    costs a node-down fan-out AND a node-up unblock sweep. Knobs:

      NOMAD_TPU_FLAP=0            kill switch: immediate transitions
                                  (today's behavior, test-gated)
      NOMAD_TPU_FLAP_THRESHOLD    flaps in window before quarantine (3)
      NOMAD_TPU_FLAP_WINDOW       scoring window seconds (300)
      NOMAD_TPU_FLAP_BASE_S       first quarantine window seconds (5)
      NOMAD_TPU_FLAP_MAX_S        quarantine cap seconds (300)
    """

    def __init__(self):
        import os
        self.enabled = os.environ.get("NOMAD_TPU_FLAP", "1") != "0"
        self.flap_threshold = int(
            os.environ.get("NOMAD_TPU_FLAP_THRESHOLD", "3"))
        window = float(os.environ.get("NOMAD_TPU_FLAP_WINDOW", "300"))
        self.base_s = float(os.environ.get("NOMAD_TPU_FLAP_BASE_S", "5"))
        self.max_s = float(os.environ.get("NOMAD_TPU_FLAP_MAX_S", "300"))
        super().__init__(threshold=self.flap_threshold, window=window)
        self._quarantine: Dict[str, float] = {}

    def record_down(self, node_id: str) -> int:
        """A node went down: record the flap; once the score crosses the
        threshold, arm/extend the quarantine with exponential backoff so
        the NEXT recovery attempt is deferred. Returns the score."""
        if not self.enabled:
            return 0
        self.add(node_id)
        score = self.score(node_id)
        if score >= self.flap_threshold:
            hold = min(self.base_s * (2 ** (score - self.flap_threshold)),
                       self.max_s)
            self._quarantine[node_id] = time.time() + hold
            from .telemetry import metrics
            metrics.incr("nomad.heartbeat.flap_quarantined")
        return score

    def quarantine_remaining(self, node_id: str) -> float:
        """Seconds of quarantine left (0 = free to transition ready).
        Expired entries are reaped on read."""
        if not self.enabled:
            return 0.0
        until = self._quarantine.get(node_id)
        if until is None:
            return 0.0
        rem = until - time.time()
        if rem <= 0:
            with self._lock:
                self._quarantine.pop(node_id, None)
            return 0.0
        return rem

    def release(self, node_id: str) -> None:
        """Operator override / deregistration: lift the quarantine."""
        with self._lock:
            self._quarantine.pop(node_id, None)

    def state(self) -> dict:
        """Operational snapshot (rides /v1/agent/self and `operator node
        flaps`, shaped like the breaker state exposure)."""
        now = time.time()
        with self._lock:
            cutoff = now - self.window
            scores = {nid: sum(1 for t in hits if t >= cutoff)
                      for nid, hits in self._hits.items()}
            quarantined = {nid: round(until - now, 3)
                           for nid, until in self._quarantine.items()
                           if until > now}
        return {
            "enabled": self.enabled,
            "threshold": self.flap_threshold,
            "window_s": self.window,
            "base_s": self.base_s,
            "max_s": self.max_s,
            "scores": {nid: s for nid, s in scores.items() if s > 0},
            "quarantined": quarantined,
        }


class WorkerSupervisor:
    """Crash-safe scheduler worker pool (ISSUE 16, ROADMAP 2a): owns
    health of the leader's N workers.  Each worker touches a progress
    heartbeat (``last_progress``) every loop iteration; the supervisor
    detects DEATH (thread exit -- a worker.crash injection, an OOM, a
    BaseException escaping the loop) and WEDGING (no progress past
    ``NOMAD_TPU_WORKER_STALL_S``, the PR-1 guard-watchdog shape) and
    respawns the slot with escalating backoff (the NodeFlapTracker
    escalation shape from PR 6: ``min(base * 2**(n-1), max)`` over
    consecutive restarts, score reset once a replacement survives).

    Exactly-once safety does NOT live here: a dead worker's leased
    evals ride the broker's nack-timeout redelivery, and a wedged
    worker that later wakes dies at the stale-lease fence
    (WorkerPlanner.submit_plan).  The supervisor only restores
    scheduling CAPACITY.  Knobs:

      NOMAD_TPU_WORKER_SUPERVISE=0     kill switch: bare pool exactly
                                       as before (no watcher thread)
      NOMAD_TPU_WORKER_STALL_S         wedge threshold seconds (30)
      NOMAD_TPU_WORKER_CHECK_S         health-check cadence s (0.5)
      NOMAD_TPU_WORKER_RESTART_BASE_S  first restart backoff s (0.25)
      NOMAD_TPU_WORKER_RESTART_MAX_S   restart backoff cap s (15)
    """

    def __init__(self, server):
        import os
        self.server = server
        self.enabled = os.environ.get(
            "NOMAD_TPU_WORKER_SUPERVISE", "1") != "0"
        self.stall_s = float(os.environ.get(
            "NOMAD_TPU_WORKER_STALL_S", "30"))
        self.check_s = float(os.environ.get(
            "NOMAD_TPU_WORKER_CHECK_S", "0.5"))
        self.base_s = float(os.environ.get(
            "NOMAD_TPU_WORKER_RESTART_BASE_S", "0.25"))
        self.max_s = float(os.environ.get(
            "NOMAD_TPU_WORKER_RESTART_MAX_S", "15"))
        self._factory = None    # slot index -> fresh unstarted worker
        self._stop_ev = threading.Event()
        self._gen = 0           # bumped per begin(): stale watchers exit
        self._thread: Optional[threading.Thread] = None
        self._pending: Dict[int, float] = {}   # slot -> respawn time
        self._consecutive: Dict[int, int] = {}
        self._spawned_at: Dict[int, float] = {}
        self.restarts_total = 0
        self.deaths_detected = 0
        self.wedges_detected = 0

    def begin(self, factory) -> None:
        """Start supervising ``server.workers`` (called under
        _leader_lock right after the pool spawns; ``factory`` rebuilds
        one worker for a slot index, same flavor as the pool)."""
        if not self.enabled:
            return
        self._factory = factory
        now = time.monotonic()
        self._pending.clear()
        self._consecutive.clear()
        self._spawned_at = {i: now
                            for i in range(len(self.server.workers))}
        self._stop_ev.clear()
        # a fresh watcher per leadership term: any previous term's
        # thread sees the generation bump and exits lazily (joining it
        # here could deadlock -- it may be waiting on _leader_lock)
        self._gen += 1
        self._thread = threading.Thread(
            target=self._run, args=(self._gen,), daemon=True,
            name=f"worker-supervisor-{self._gen}")
        self._thread.start()

    def stop(self) -> None:
        self._stop_ev.set()

    def _run(self, gen: int) -> None:
        import traceback
        while not self._stop_ev.wait(self.check_s):
            if gen != self._gen:
                return      # superseded by a newer leadership term
            try:
                self._check_once()
            except Exception:
                from .logbroker import log as _log
                _log("error", "server",
                     f"worker supervisor check error: "
                     f"{traceback.format_exc()}")

    def _check_once(self) -> None:
        from .logbroker import log as _log
        from .telemetry import metrics
        with self.server._leader_lock:
            if (not self.server._leader_active.is_set()
                    or self._stop_ev.is_set()):
                return
            now = time.monotonic()
            # a worker waiting on an XLA compile is slow, not wedged:
            # the stall clock restarts at the latest compile-stage edge
            # (sys.modules: a host-only server never imports the solver
            # for this)
            # getattr-guarded: sys.modules can hand back the module
            # while another thread is still importing it
            guard = sys.modules.get("nomad_tpu.solver.guard")
            read = getattr(guard, "last_compile_activity", None)
            last_compile = read() if read is not None else 0.0
            for i, w in enumerate(self.server.workers):
                if i in self._pending:
                    if now >= self._pending[i]:
                        self._respawn_locked(i)
                    continue
                if not w.is_alive():
                    self.deaths_detected += 1
                    metrics.incr("nomad.worker.supervisor_death")
                    _log("error", "server",
                         f"worker {w.name} DIED (thread exit); "
                         f"restarting slot {i} with backoff")
                    self._retire_bookings(w)
                    self._schedule_restart_locked(i, now)
                    continue
                age = now - max(getattr(w, "last_progress", now),
                                last_compile)
                if self.stall_s > 0 and age > self.stall_s:
                    self.wedges_detected += 1
                    metrics.incr("nomad.worker.supervisor_wedge")
                    _log("error", "server",
                         f"worker {w.name} WEDGED ({age:.1f}s without "
                         f"progress > stall threshold "
                         f"{self.stall_s:.1f}s); abandoning thread and "
                         f"restarting slot {i}")
                    # the hung thread may never exit; stop() it, leave
                    # it as an abandoned daemon -- its leased evals
                    # redeliver via nack-timeout, and any plan it wakes
                    # to submit dies at the stale-lease fence
                    w.stop()
                    self._retire_bookings(w)
                    self._schedule_restart_locked(i, now)
                    continue
                # healthy: once a replacement outlives the stall
                # window, its slot's escalation score resets
                if (self._consecutive.get(i)
                        and now - self._spawned_at.get(i, now)
                        > max(self.stall_s, 2 * self.base_s)):
                    self._consecutive.pop(i, None)

    @staticmethod
    def _retire_bookings(w) -> None:
        """A batch worker that is gone leaves its batch's bookings
        (inflight.py) behind: its evals redeliver, and nothing it booked
        commits."""
        barrier = getattr(w, "barrier", None)
        if barrier is not None:
            barrier.retire()

    def _schedule_restart_locked(self, slot: int, now: float) -> None:
        n = self._consecutive.get(slot, 0) + 1
        self._consecutive[slot] = n
        hold = min(self.base_s * (2 ** (n - 1)), self.max_s)
        self._pending[slot] = now + hold

    def _respawn_locked(self, slot: int) -> None:
        from .logbroker import log as _log
        from .telemetry import metrics
        self._pending.pop(slot, None)
        w = self._factory(slot)
        w.start()
        self.server.workers[slot] = w
        self._spawned_at[slot] = time.monotonic()
        self.restarts_total += 1
        metrics.incr("nomad.worker.supervisor_restart")
        _log("warn", "server",
             f"worker slot {slot} restarted as {w.name} "
             f"(consecutive restart #{self._consecutive.get(slot, 0)})")

    def state(self) -> dict:
        """Operational snapshot (rides /v1/agent/self, shaped like the
        node_flaps / breaker exposures)."""
        now = time.monotonic()
        workers = list(self.server.workers)
        return {
            "enabled": self.enabled,
            "stall_s": self.stall_s,
            "restart_base_s": self.base_s,
            "restart_max_s": self.max_s,
            "restarts_total": self.restarts_total,
            "deaths_detected": self.deaths_detected,
            "wedges_detected": self.wedges_detected,
            "pending_restarts": len(self._pending),
            "workers": [
                {"name": w.name, "alive": w.is_alive(),
                 "evals_processed": w.evals_processed,
                 "progress_age_s": round(
                     now - getattr(w, "last_progress", now), 3)}
                for w in workers],
        }


class EventSubscription:
    """One consumer's filtered live event queue (reference:
    nomad/stream/event_broker.go Subscription)."""

    MAX_PENDING = 1024

    def __init__(self, topics: Optional[Dict[str, List[str]]] = None):
        import queue
        self.topics = topics or {"*": ["*"]}
        self._q: "queue.Queue" = queue.Queue(maxsize=self.MAX_PENDING)
        self.closed = False

    def matches(self, event: dict) -> bool:
        for topic, keys in self.topics.items():
            if topic not in ("*", event["topic"]):
                continue
            if not keys or "*" in keys or event.get("key") in keys:
                return True
        return False

    def offer(self, event: dict) -> None:
        if self.closed or not self.matches(event):
            return
        try:
            self._q.put_nowait(event)
        except Exception:   # noqa: BLE001 -- slow consumer: drop oldest
            try:
                self._q.get_nowait()
                self._q.put_nowait(event)
            except Exception:   # noqa: BLE001
                pass

    def next(self, timeout: float = 1.0) -> Optional[dict]:
        import queue
        try:
            return self._q.get(timeout=timeout)
        except queue.Empty:
            return None


class Server:
    """(reference: nomad/server.go:105 Server)"""

    def __init__(self, num_workers: Optional[int] = None,
                 heartbeat_ttl: float = DEFAULT_HEARTBEAT_TTL,
                 logger=None, state=None, acl_enabled: bool = False,
                 region: str = "global", eval_batching: bool = True,
                 batch_width: Optional[int] = None):
        import os
        from ..acl import Resolver
        self.logger = logger
        self.region = region
        # federation: region name -> a peer region server's HTTP address
        # (reference: multi-region RPC forwarding, nomad/rpc.go forward;
        # regions discover each other via WAN serf there, via explicit
        # join here)
        self.federation: Dict[str, str] = {}
        self.wan = None                     # WAN gossip pool (enable_wan)
        self._acl_replication_thread: Optional[threading.Thread] = None
        self.state = state if state is not None else StateStore()
        self.acl_enabled = acl_enabled
        self.acl_resolver = Resolver(self.state)
        from .encrypter import Encrypter
        self.encrypter = Encrypter(self.state)
        self.broker = EvalBroker()
        self.blocked_evals = BlockedEvals(self.broker)
        self.planner = Planner(self.state)
        # what the batch workers' barriers have handed to their evals
        # and the alloc table does not hold yet (inflight.py): booked by
        # one barrier's fixpoint, charged by the other's, settled by the
        # commit under the store's lock
        self.inflight = InflightBookings()
        self.state.plan_commit_hook = self.inflight.settle
        # group commit: one blocked-evals unblock sweep per committed
        # plan BATCH (the per-plan sweep in on_plan_result is skipped
        # for batch-committed results)
        self.planner.on_batch_commit = self._on_plan_batch_commit
        self.num_workers = num_workers or max(2, (os.cpu_count() or 4))
        # Eval coalescing (solver/batch.py): one BatchWorker running
        # num_workers eval threads per batch replaces the plain worker
        # pool; dense solves fuse into one device dispatch per rendezvous.
        self.eval_batching = eval_batching
        self.batch_width = batch_width or self.num_workers
        self.workers: List[Worker] = []
        # crash-safe pool supervision (ISSUE 16): death/wedge detection
        # + escalating-backoff restarts; NOMAD_TPU_WORKER_SUPERVISE=0
        # keeps the bare unsupervised pool
        self.supervisor = WorkerSupervisor(self)
        self.heartbeat_ttl = heartbeat_ttl
        self._heartbeat_deadlines: Dict[str, float] = {}
        self._hb_lock = threading.Lock()
        # flap damping: scores fed by ready->down transitions, escalating
        # quarantine deferring down->ready (NOMAD_TPU_FLAP_* knobs)
        self.flaps = NodeFlapTracker()
        # serializes drain pacing rounds (API thread vs drainer loop):
        # both read-compute-mark, so racing ticks could overshoot
        # migrate.max_parallel
        self._drain_lock = threading.Lock()
        self._shutdown = threading.Event()
        self._threads: List[threading.Thread] = []
        self._events: List[dict] = []
        self._events_lock = threading.Lock()
        self._event_subs: List["EventSubscription"] = []
        self._periodic_last: Dict[tuple, float] = {}
        self._leader_active = threading.Event()
        self._leader_lock = threading.Lock()

    # ------------------------------------------------------------------
    def start(self) -> None:
        """Boot; the dev single-server topology is immediately the leader
        (reference: server boot + monitorLeadership leader.go:90)."""
        import gc
        # the state store pins millions of long-lived objects (alloc
        # graphs); default gen2 cadence makes the collector walk that
        # heap every ~7K allocations of scheduler churn -- observed as
        # 100ms+ pauses landing inside plan verify/commit. 100x fewer
        # full collections, same gen0/gen1 behavior.
        _, g1, _ = gc.get_threshold()
        gc.set_threshold(700, g1, 1000)
        from .logbroker import _StdlibBridge
        _StdlibBridge.install()     # stdlib logging -> /v1/agent/monitor
        # quality & saturation observatory (ISSUE 7): binds the store's
        # write-delta hook + the tracer's span sink; a no-op (prior
        # paths bit-for-bit) under NOMAD_TPU_QUALITY=0
        from .quality import observatory
        observatory.attach(self.state)
        self._start_background()
        self.establish_leadership()

    def _start_background(self) -> None:
        from .tracing import trace_enabled
        loops = [(self._run_heartbeat_watcher, "heartbeat"),
                 (self._run_gc, "core-gc"),
                 (self._run_periodic, "periodic"),
                 (self._run_deployment_watcher, "deploy-watch"),
                 (self._run_volume_watcher, "volume-watch"),
                 (self._run_drainer, "drainer")]
        from .. import schedcheck
        if trace_enabled() and not schedcheck._ACTIVE:
            # (under the schedule explorer waits are virtual: a lag of
            # the wall clock means nothing there, and a 50 ms poller
            # would only add decisions to every schedule)
            loops.append((self._run_sched_lag, "sched-lag"))
        for fn, name in loops:
            t = threading.Thread(target=self._supervised, args=(fn, name),
                                 daemon=True, name=name)
            t.start()
            self._threads.append(t)

    def _supervised(self, fn, name: str) -> None:
        """Background watchers must survive a bad iteration: a dead watcher
        silently stops deployments/GC/heartbeats (the reference's leader
        goroutines log and keep running). Restart the loop on error."""
        import traceback
        while not self._shutdown.is_set():
            try:
                fn()
                return          # clean exit (shutdown)
            except Exception:
                from .logbroker import log as _log
                _log("error", "server",
                     f"{name} watcher error (restarting): "
                     f"{traceback.format_exc()}")
                self._shutdown.wait(0.5)

    def _run_sched_lag(self) -> None:
        """How long a thread that becomes runnable waits for the
        interpreter: sleep SCHED_LAG_SLEEP_S, record how late the wake
        came (timer nomad.runtime.sched_lag, 20 samples a second). Part
        of the contention account; not started under NOMAD_TPU_TRACE=0."""
        from .telemetry import metrics
        while True:
            t0 = time.perf_counter()
            if self._shutdown.wait(SCHED_LAG_SLEEP_S):
                return
            late = time.perf_counter() - t0 - SCHED_LAG_SLEEP_S
            metrics.sample_ms("nomad.runtime.sched_lag",
                              max(0.0, late) * 1e3)

    def establish_leadership(self) -> None:
        """(reference: leader.go:357 establishLeadership -- enable broker
        and plan queue, restore evals from state :403, start workers)."""
        with self._leader_lock:
            if self._leader_active.is_set():
                return
            # a failover must not un-pause a broker the operator paused:
            # the flag lives in replicated state (reference: leader.go
            # gating broker enable on SchedulerConfig.PauseEvalBroker)
            paused = bool(getattr(self.state.scheduler_config(),
                                  "pause_eval_broker", False))
            from .logbroker import log as _log
            _log("info", "server",
                 f"cluster leadership acquired (broker "
                 f"{'paused' if paused else 'enabled'})")
            self.broker.set_enabled(not paused)
            self.blocked_evals.set_enabled(True)
            # (reference: leader.go initializeKeyring -- first leader mints
            # the root encryption key)
            self.encrypter.initialize()
            self._restore_evals()
            self._initialize_heartbeat_timers()
            self._restore_periodic_launch_times()
            if self.eval_batching:
                # TWO overlapping batch workers: a straggler eval convoys
                # only its own batch while the other worker keeps draining
                # the queue (and packs the next dispatch while the device
                # is busy with the current one).  The LP-queue tier wants
                # the OPPOSITE: one worker, so the pending queue coalesces
                # into the widest possible joint solve instead of being
                # split between competing drains (the workers re-check the
                # tier per batch, so runtime algorithm flips still work).
                from ..solver.lpq import lpq_active
                n_batch_workers = 1 if lpq_active(self.state) else 2
                if n_batch_workers == 1:
                    _log("info", "server",
                         "LP-queue scheduler tier active (tpu-lpq): "
                         "single coalescing batch worker")
                for i in range(n_batch_workers):
                    w = BatchWorker(self, i, width=self.batch_width)
                    w.start()
                    self.workers.append(w)
                spawn = self._spawn_batch_worker
            else:
                for i in range(self.num_workers):
                    w = Worker(self, i)
                    w.start()
                    self.workers.append(w)
                spawn = self._spawn_worker
            self._leader_active.set()
            self.supervisor.begin(spawn)

    def _spawn_batch_worker(self, i: int) -> BatchWorker:
        return BatchWorker(self, i, width=self.batch_width)

    def _spawn_worker(self, i: int) -> Worker:
        return Worker(self, i)

    def revoke_leadership(self) -> None:
        """(reference: leader.go revokeLeadership -- drain workers, disable
        broker; in-flight evals are nacked back by their workers)."""
        with self._leader_lock:
            if not self._leader_active.is_set():
                return
            self._leader_active.clear()
            self.supervisor.stop()
            for w in self.workers:
                w.stop()
            self.workers = []
            self.inflight.clear()
            self.broker.set_enabled(False)
            self.blocked_evals.set_enabled(False)
            with self._hb_lock:
                self._heartbeat_deadlines.clear()
            self._periodic_last.clear()

    def _restore_evals(self, reblock: bool = True) -> None:
        """Re-populate broker/blocked-evals from replicated state
        (reference: leader.go:403 restoreEvals). With reblock=False,
        state-BLOCKED evals enqueue for re-evaluation instead (they
        re-block if capacity still lacks) -- used on broker resume where
        capacity events during the pause may have been dropped."""
        for ev in self.state.evals():
            if ev.status == EVAL_STATUS_BLOCKED:
                if reblock:
                    self.blocked_evals.block(ev)
                else:
                    self.broker.enqueue(ev)
            elif ev.should_enqueue():
                self.broker.enqueue(ev)

    def _initialize_heartbeat_timers(self) -> None:
        """A fresh leader owns node liveness: every non-down node gets a
        full TTL to check in (reference: heartbeat.go:59
        initializeHeartbeatTimers)."""
        now = time.time()
        with self._hb_lock:
            for node in self.state.nodes():
                if node.status not in (NODE_STATUS_DOWN,
                                       NODE_STATUS_DISCONNECTED):
                    self._heartbeat_deadlines[node.id] = (
                        now + self.heartbeat_ttl)

    def _restore_periodic_launch_times(self) -> None:
        """Recover last-dispatch times from the periodic children already
        in replicated state so failover doesn't re-dispatch mid-interval
        (reference: periodic.go restores LaunchTime from state)."""
        for job in self.state.jobs():
            if not job.parent_id or "/periodic-" not in job.id:
                continue
            try:
                launched = float(job.id.rsplit("/periodic-", 1)[1])
            except ValueError:
                continue
            parent = self.state.job_by_id(job.namespace, job.parent_id)
            if parent is None:
                continue
            key = (job.namespace, job.parent_id)
            self._periodic_last[key] = max(
                self._periodic_last.get(key, 0.0), launched)

    def is_leader(self) -> bool:
        return self._leader_active.is_set()

    def shutdown(self) -> None:
        self._shutdown.set()
        self.supervisor.stop()
        from .quality import observatory
        observatory.detach(self.state)
        if getattr(self, "wan", None) is not None:
            self.wan.shutdown()
            self.wan = None
        for w in self.workers:
            w.stop()
        self.broker.set_enabled(False)
        self.broker.shutdown()
        self.planner.shutdown()

    # ------------------------------------------------------------------
    # ACL API (reference: nomad/acl_endpoint.go)
    def bootstrap_acl(self):
        """One-time creation of the initial management token
        (reference: acl_endpoint.go Bootstrap)."""
        from ..structs import ACL_TOKEN_TYPE_MANAGEMENT, ACLToken
        token = ACLToken.new(name="Bootstrap Token",
                             type=ACL_TOKEN_TYPE_MANAGEMENT)
        token.global_token = True
        if not self.state.bootstrap_acl_token(token):
            return None
        return token

    def apply_scheduler_config(self, cfg) -> None:
        """Store + enact runtime scheduler configuration: the
        pause_eval_broker knob stops dequeues on the live broker
        (reference: SchedulerSetConfigurationRequest + the leader's
        broker enable/disable, operator_endpoint.go). Serialized with
        leadership transitions -- every broker enable/disable takes
        _leader_lock."""
        self.state.set_scheduler_config(cfg)
        with self._leader_lock:
            if not self._leader_active.is_set():
                return
            was = self.broker.enabled
            self.broker.set_enabled(not cfg.pause_eval_broker)
            if not was and not cfg.pause_eval_broker:
                # resume: re-seed from state like a fresh leader, and
                # ENQUEUE evals that blocked before/while paused -- a
                # capacity event during the pause dropped its wakeup at
                # the disabled broker, so they must re-evaluate
                # (reference: leader.go:403 restoreEvals)
                self._restore_evals(reblock=False)

    def resolve_token(self, secret_id: Optional[str]):
        """-> (ACL, token). With ACLs disabled every request is management;
        with ACLs enabled a missing/unknown secret is anonymous deny-all
        (reference: nomad/auth/auth.go ResolveToken). Workload-identity
        JWTs are accepted in place of ACL tokens and compile to the
        implicit own-job variables policy (the reference's
        Variables-with-workload-identity model)."""
        from ..acl import ANONYMOUS_ACL, MANAGEMENT_ACL
        if not self.acl_enabled:
            return MANAGEMENT_ACL, None
        if not secret_id:
            return ANONYMOUS_ACL, None
        if secret_id.count(".") == 2:       # JWT-shaped: try identity
            acl = self._workload_identity_acl(secret_id)
            if acl is not None:
                return acl, None
        compiled, token = self.acl_resolver.resolve_secret(secret_id)
        if compiled is None:
            return ANONYMOUS_ACL, None
        return compiled, token

    def _workload_identity_acl(self, jwt: str):
        """Compile a verified workload JWT into the implicit policy: read
        access to the job's own Variables subtree, nothing else."""
        claims = self._verify_workload_claims(jwt)
        if claims is None:
            return None
        from ..acl.acl import ACL
        from ..acl.policy import VariablePathRule
        from .admission import job_variable_prefix
        ns, job_id = claims["_ns"], claims["job_id"]
        prefix = job_variable_prefix(job_id)
        acl = ACL()
        acl._ns_variables[ns] = [
            VariablePathRule(path=prefix, capabilities=["read", "list"]),
            VariablePathRule(path=prefix + "/*",
                             capabilities=["read", "list"])]
        return acl

    def _verify_workload_claims(self, jwt: str):
        """Verify signature + liveness of a workload identity JWT;
        returns claims with '_ns' resolved, or None."""
        claims = self.encrypter.verify_claims(jwt)
        if claims is None or "alloc_id" not in claims:
            return None
        alloc = self.state.alloc_by_id(claims["alloc_id"])
        if alloc is None or alloc.server_terminal_status():
            return None
        if alloc.job_id != claims.get("job_id"):
            return None
        claims["_ns"] = alloc.namespace
        return claims

    def sign_workload_identity(self, claims: dict) -> str:
        """Mint a workload identity JWT (client identity hook path).

        Claims are SERVER-AUTHORITATIVE: the caller only names an
        (alloc_id, task); everything else -- job, namespace, task group,
        expiry -- is rebuilt from replicated state, so a caller can
        neither forge another job's identity from a live alloc id of its
        own nor extend the TTL (reference: the server-side minting in
        Node.DeriveSIToken / identity signing). Raises PermissionError
        for unknown/terminal allocs or tasks not in the alloc's TG.
        Full node-binding (per-node secret IDs) is the remaining gap."""
        alloc_id = str(claims.get("alloc_id", ""))
        task_name = str(claims.get("task", ""))
        alloc = self.state.alloc_by_id(alloc_id)
        if alloc is None or alloc.server_terminal_status():
            raise PermissionError("unknown or terminal allocation")
        job = alloc.job or self.state.job_by_id(alloc.namespace,
                                                alloc.job_id)
        tg = job.lookup_task_group(alloc.task_group) if job else None
        if tg is None or not any(t.name == task_name for t in tg.tasks):
            raise PermissionError(
                f"task {task_name!r} not in allocation {alloc_id[:8]}")
        return self.encrypter.sign_claims({
            "sub": f"{alloc.namespace}:{alloc.job_id}:"
                   f"{alloc.task_group}:{task_name}",
            "alloc_id": alloc.id,
            "job_id": alloc.job_id,
            "task": task_name,
        })

    def workload_variable(self, jwt: str, path: str):
        """Read a decrypted Variable on behalf of a workload
        (reference analog: nomad/vault.go DeriveVaultToken ->
        re-based on native Variables + workload identity). Raises
        PermissionError for invalid identities or out-of-scope paths;
        returns None when the variable simply doesn't exist."""
        from .admission import job_variable_prefix
        claims = self._verify_workload_claims(jwt)
        if claims is None:
            raise PermissionError("invalid workload identity")
        prefix = job_variable_prefix(claims["job_id"])
        if path != prefix and not path.startswith(prefix + "/"):
            raise PermissionError(
                f"path {path!r} outside workload scope {prefix!r}")
        dec = self.var_get(claims["_ns"], path)
        return dict(dec.items) if dec is not None else None

    # ------------------------------------------------------------------
    # Variables API (reference: nomad/variables_endpoint.go)
    def var_put(self, namespace: str, path: str, items: Dict[str, str],
                cas_index: Optional[int] = None):
        """Encrypt+store. Returns (ok, VariableDecrypted-or-conflict)."""
        from ..structs import VariableDecrypted, VariableMetadata
        dec = VariableDecrypted(
            meta=VariableMetadata(namespace=namespace, path=path),
            items=dict(items))
        enc = self.encrypter.encrypt_variable(dec)
        ok, stored = self.state.upsert_variable(enc, cas_index)
        if not ok:
            return False, (self.encrypter.decrypt_variable(stored)
                           if stored is not None else None)
        dec.meta = stored.meta
        return True, dec

    def var_get(self, namespace: str, path: str):
        enc = self.state.variable_by_path(namespace, path)
        if enc is None:
            return None
        return self.encrypter.decrypt_variable(enc)

    def var_list(self, namespace: Optional[str] = None, prefix: str = ""):
        """Metadata only -- list never decrypts (reference:
        variables_endpoint.go List returns VariableMetadata)."""
        return [v.meta for v in self.state.variables(namespace, prefix)]

    def var_delete(self, namespace: str, path: str,
                   cas_index: Optional[int] = None) -> bool:
        ok, _ = self.state.delete_variable(namespace, path, cas_index)
        return ok

    # ------------------------------------------------------------------
    # Job API (reference: nomad/job_endpoint.go Job.Register :96)
    def register_job(self, job: Job) -> Evaluation:
        self._validate_job(job)
        # admission hooks: mutate (implicit identity, vault->template
        # injection) then validate (reference: job_endpoint_hooks.go)
        from .admission import AdmissionPipeline
        job, _warnings = AdmissionPipeline(self).apply(job)
        self.state.upsert_job(job)
        if job.is_periodic() or job.is_parameterized():
            # periodic/parameterized jobs don't get an immediate eval
            # (reference: job_endpoint.go:432 region)
            return None
        ev = Evaluation(
            id=generate_uuid(),
            namespace=job.namespace,
            priority=job.priority,
            type=job.type,
            triggered_by=TRIGGER_JOB_REGISTER,
            job_id=job.id,
            status=EVAL_STATUS_PENDING,
        )
        self.state.upsert_evals([ev])
        self.broker.enqueue(ev)
        self.publish_event("JobRegistered", {"job_id": job.id})
        return ev

    def _validate_job(self, job: Job) -> None:
        """Admission validation before anything reaches replicated state
        (reference: job_endpoint.go admission hooks / Job.Validate). Keeps
        malformed user input out of the FSM apply path."""
        ns = self.state.namespace_by_name(job.namespace)
        if ns is None:
            raise ValueError(f"namespace {job.namespace!r} does not exist")
        # node-pool admission (reference: job_endpoint_hook_node_pool.go):
        # the pool must exist and the namespace must allow it; an empty
        # pool falls back to the namespace default.
        npc = ns.node_pool_configuration
        if (not job.node_pool or job.node_pool == "default") and npc.default:
            job.node_pool = npc.default
        if job.node_pool == "all":
            # "all" is the built-in every-node pool for OPERATOR queries;
            # jobs targeting it would bypass pool isolation (reference:
            # structs/node_pool.go NodePoolAll invalid on jobs)
            raise ValueError('jobs may not target the built-in "all" pool')
        if self.state.node_pool_by_name(job.node_pool) is None:
            raise ValueError(f"node pool {job.node_pool!r} does not exist")
        if not npc.allows(job.node_pool):
            raise ValueError(
                f"namespace {job.namespace!r} does not allow node pool "
                f"{job.node_pool!r}")
        for tg in job.task_groups:
            # network validation (reference: structs/job.go
            # TaskGroup.Validate -- "Only one network resource may be
            # specified"; task-level networks are the deprecated pre-0.12
            # surface the scheduler no longer honors)
            if len(tg.networks) > 1:
                raise ValueError(
                    f"group {tg.name}: only one network block is allowed")
            for task in tg.tasks:
                if task.resources is not None and task.resources.networks:
                    raise ValueError(
                        f"task {task.name}: task-level network blocks are "
                        "not supported; use the group network block")
            sc = tg.scaling
            if sc is None:
                continue
            if not isinstance(sc, dict):
                raise ValueError(
                    f"group {tg.name}: scaling must be a block/object")
            try:
                lo = int(sc.get("min", 0) or 0)
                hi = int(sc.get("max", tg.count))
            except (TypeError, ValueError):
                raise ValueError(
                    f"group {tg.name}: scaling min/max must be integers")
            if lo < 0 or hi < lo:
                raise ValueError(
                    f"group {tg.name}: scaling bounds invalid "
                    f"(min={lo}, max={hi})")

    def deregister_job(self, namespace: str, job_id: str,
                       purge: bool = False) -> Optional[Evaluation]:
        """(reference: job_endpoint.go Job.Deregister)"""
        job = self.state.job_by_id(namespace, job_id)
        if job is None:
            return None
        stopped = job
        import copy
        stopped = copy.copy(job)
        stopped.stop = True
        self.state.upsert_job(stopped)
        if purge:
            self.state.delete_job(namespace, job_id)
        ev = Evaluation(
            id=generate_uuid(), namespace=namespace, priority=job.priority,
            type=job.type, triggered_by=TRIGGER_JOB_DEREGISTER,
            job_id=job_id, status=EVAL_STATUS_PENDING)
        self.state.upsert_evals([ev])
        self.broker.enqueue(ev)
        self.publish_event("JobDeregistered", {"job_id": job_id})
        return ev

    def plan_job(self, job: Job) -> dict:
        """Dry-run the scheduler against a copy of current state
        (reference: Job.Plan nomad/job_endpoint.go -- inserts the candidate
        job into a state snapshot and runs the scheduler with AnnotatePlan,
        capturing the plan instead of committing it)."""
        from ..raft.fsm import dump_state, restore_state
        from ..scheduler.harness import Harness
        from ..state import StateStore

        # same admission as register (including the namespace default-pool
        # rewrite) so the dry-run matches what `job run` would do
        self._validate_job(job)
        real = getattr(self.state, "_store", self.state)
        temp = StateStore()
        restore_state(temp, dump_state(real))
        h = Harness(temp)
        temp.upsert_job(job)
        ev = Evaluation(
            id=generate_uuid(), namespace=job.namespace,
            priority=job.priority, type=job.type,
            triggered_by=TRIGGER_JOB_REGISTER, job_id=job.id,
            status=EVAL_STATUS_PENDING, annotate_plan=True)
        temp.upsert_evals([ev])
        sched_type = (job.type if job.type in
                      ("service", "batch", "system", "sysbatch")
                      else "service")
        h.process(sched_type, ev)
        placed = stopped = 0
        # DesiredUpdates per task group (reference: scheduler/annotate.go
        # Annotate -- place/stop/migrate/destructive/ignore counts)
        tg_updates: Dict[str, Dict[str, int]] = {}

        def bump(tg_name: str, key: str) -> None:
            tg_updates.setdefault(tg_name, {
                "place": 0, "stop": 0, "migrate": 0,
                "preemptions": 0})[key] += 1

        for plan in h.plans:
            for allocs in plan.node_allocation.values():
                placed += len(allocs)
                for alloc in allocs:
                    bump(alloc.task_group, "place")
            for allocs in plan.node_update.values():
                stopped += len(allocs)
                for alloc in allocs:
                    bump(alloc.task_group,
                         "migrate" if (alloc.desired_transition and
                                       alloc.desired_transition.migrate)
                         else "stop")
            for allocs in plan.node_preemptions.values():
                for alloc in allocs:
                    bump(alloc.task_group, "preemptions")
        annotations = ({"desired_tg_updates": tg_updates}
                       if tg_updates else None)
        failed = {}
        for pe in h.evals:
            for tg_name, metric in (pe.failed_tg_allocs or {}).items():
                failed[tg_name] = {
                    "nodes_evaluated": metric.nodes_evaluated,
                    "nodes_filtered": metric.nodes_filtered,
                    "constraint_filtered": dict(metric.constraint_filtered),
                    "dimension_exhausted": dict(metric.dimension_exhausted),
                }
        existing = self.state.job_by_id(job.namespace, job.id)
        return {
            "placed": placed, "stopped": stopped,
            "annotations": annotations, "failed_tg_allocs": failed,
            "job_modify_index":
                existing.job_modify_index if existing else 0,
            "diff_type": ("Edited" if existing is not None else "Added"),
        }

    # ------------------------------------------------------------------
    # Job lifecycle (reference: nomad/job_endpoint.go Job.GetJobVersions,
    # Job.Revert, Job.Stable, Job.Dispatch, Job.Scale)
    def job_versions(self, namespace: str, job_id: str) -> List[Job]:
        return self.state.job_versions_by_id(namespace, job_id)

    def revert_job(self, namespace: str, job_id: str, version: int,
                   enforce_prior_version: Optional[int] = None):
        """Re-register the spec of a prior version as a NEW version
        (reference: job_endpoint.go Job.Revert -- revert is a forward
        operation, never a rollback of history)."""
        import copy
        current = self.state.job_by_id(namespace, job_id)
        if current is None:
            raise ValueError(f"job {job_id} not found")
        if enforce_prior_version is not None and \
                current.version != enforce_prior_version:
            raise ValueError(
                f"current version {current.version} != enforced "
                f"{enforce_prior_version}")
        if version == current.version:
            raise ValueError("cannot revert to the current version")
        prior = self.state.job_version(namespace, job_id, version)
        if prior is None:
            raise ValueError(f"version {version} not found")
        revert = copy.deepcopy(prior)
        revert.stop = False
        # the NEW version must re-earn stability through a deployment
        # (reference: Job.Revert registers with Stable=false)
        revert.stable = False
        return self.register_job(revert)

    def set_job_stability(self, namespace: str, job_id: str,
                          version: int, stable: bool) -> None:
        """(reference: job_endpoint.go Job.Stable)"""
        if self.state.job_version(namespace, job_id, version) is None:
            raise ValueError(
                f"job {job_id} version {version} not found")
        self.state.update_job_stability(namespace, job_id, version, stable)

    def dispatch_job(self, namespace: str, job_id: str,
                     payload: bytes = b"", meta: Optional[Dict[str, str]] = None,
                     idempotency_token: str = ""):
        """Instantiate a parameterized job as a dispatched child
        (reference: job_endpoint.go Job.Dispatch + validateDispatchRequest).
        Returns (child_job, eval-or-None)."""
        import copy
        meta = dict(meta or {})
        parent = self.state.job_by_id(namespace, job_id)
        if parent is None:
            raise ValueError(f"job {job_id} not found")
        cfg = parent.parameterized
        if cfg is None or parent.dispatched:
            raise ValueError(f"job {job_id} is not parameterized")
        if parent.stop:
            raise ValueError(f"job {job_id} is stopped")
        if cfg.payload == "required" and not payload:
            raise ValueError("payload is required")
        if cfg.payload == "forbidden" and payload:
            raise ValueError("payload is forbidden")
        if len(payload) > 16 * 1024:
            raise ValueError("payload exceeds 16KiB limit")
        required = set(cfg.meta_required or [])
        allowed = required | set(cfg.meta_optional or [])
        missing = required - set(meta)
        if missing:
            raise ValueError(f"missing required meta: {sorted(missing)}")
        extra = set(meta) - allowed
        if extra:
            raise ValueError(f"unpermitted meta keys: {sorted(extra)}")
        if idempotency_token:
            for j in self.state.jobs():
                if j.namespace == parent.namespace and \
                        j.parent_id == parent.id and \
                        j.dispatch_idempotency_token == idempotency_token:
                    return j, None
        child = copy.deepcopy(parent)
        child.id = (f"{parent.id}/dispatch-{int(time.time())}-"
                    f"{generate_uuid()[:8]}")
        child.name = child.id
        child.parent_id = parent.id
        child.dispatched = True
        child.payload = payload
        child.dispatch_idempotency_token = idempotency_token
        child.meta = {**(parent.meta or {}), **meta}
        ev = self.register_job(child)
        self.publish_event("JobDispatched",
                           {"job_id": parent.id, "dispatched_id": child.id})
        return child, ev

    def scale_job(self, namespace: str, job_id: str, group: str,
                  count: Optional[int] = None, message: str = "",
                  error: bool = False, meta: Optional[dict] = None):
        """Set a group's count, recording a scaling event
        (reference: job_endpoint.go Job.Scale). With error=True or
        count=None only the event is recorded (the autoscaler's audit
        path). Returns the eval (or None)."""
        import copy
        job = self.state.job_by_id(namespace, job_id)
        if job is None:
            raise ValueError(f"job {job_id} not found")
        tg = job.lookup_task_group(group)
        if tg is None:
            raise ValueError(f"group {group} not found in job {job_id}")
        prev_count = tg.count
        ev = None
        if count is not None and not error:
            if count < 0:
                raise ValueError("count must be >= 0")
            if tg.scaling:
                lo = int(tg.scaling.get("min", 0) or 0)
                hi = int(tg.scaling.get("max", count))
                if count < lo or count > hi:
                    raise ValueError(
                        f"count {count} outside scaling bounds "
                        f"[{lo}, {hi}]")
            if job.stop:
                raise ValueError(f"job {job_id} is stopped")
            updated = copy.deepcopy(job)
            updated.lookup_task_group(group).count = count
            ev = self.register_job(updated)
        self.state.upsert_scaling_event(
            namespace, job_id,
            ScalingEvent(
                time=time.time(), task_group=group, count=count,
                previous_count=prev_count, message=message, error=error,
                meta=dict(meta or {}), eval_id=ev.id if ev else ""))
        return ev

    def job_scale_status(self, namespace: str, job_id: str) -> Optional[dict]:
        """(reference: job_endpoint.go Job.ScaleStatus)"""
        job = self.state.job_by_id(namespace, job_id)
        if job is None:
            return None
        allocs = self.state.allocs_by_job(namespace, job_id)
        all_events = self.state.scaling_events_by_job(namespace, job_id)
        groups = {}
        for tg in job.task_groups:
            tg_allocs = [a for a in allocs if a.task_group == tg.name]
            groups[tg.name] = {
                "desired": tg.count,
                "placed": len([a for a in tg_allocs
                               if not a.terminal_status()]),
                "running": len([a for a in tg_allocs
                                if a.client_status == ALLOC_CLIENT_RUNNING]),
                "healthy": len([a for a in tg_allocs
                                if a.deployment_status is not None
                                and a.deployment_status.is_healthy()]),
                "unhealthy": len([a for a in tg_allocs
                                  if a.deployment_status is not None
                                  and a.deployment_status.is_unhealthy()]),
                "events": [
                    {"time": e.time, "count": e.count,
                     "previous_count": e.previous_count,
                     "message": e.message, "error": e.error,
                     "eval_id": e.eval_id}
                    for e in all_events if e.task_group == tg.name],
            }
        return {"job_id": job_id, "namespace": namespace,
                "job_stopped": job.stop, "task_groups": groups}

    # ------------------------------------------------------------------
    # Node API (reference: nomad/node_endpoint.go)
    def register_node(self, node: Node) -> None:
        """(reference: node_endpoint.go:99 Register)"""
        # registering into an unknown pool creates it (reference:
        # Node.Register -> NodePool upsert on missing pool)
        if node.node_pool and \
                self.state.node_pool_by_name(node.node_pool) is None:
            from ..structs import NodePool
            self.state.upsert_node_pool(NodePool(
                name=node.node_pool,
                description="created by node registration"))
        node.status = NODE_STATUS_READY
        self.state.upsert_node(node)
        # explicit re-registration is an operator/agent-restart action:
        # it lifts any flap quarantine (the heartbeat path defers; the
        # registration path is the documented override)
        self.flaps.release(node.id)
        self._reset_heartbeat(node.id)
        # new capacity -> unblock evals for this class
        self.blocked_evals.unblock(node.computed_class)
        self.publish_event("NodeRegistered", {"node_id": node.id})

    def deregister_node(self, node_id: str) -> None:
        """Purge a node from state (reference: node_endpoint.go:
        Node.Deregister): the node goes down first so its allocs
        reschedule, then the record is removed."""
        node = self.state.node_by_id(node_id)
        if node is None:
            raise ValueError(f"unknown node {node_id!r}")
        self.update_node_status(node_id, NODE_STATUS_DOWN)
        self.state.delete_node(node_id)
        self.flaps.release(node_id)
        self.publish_event("NodeDeregistered", {"node_id": node_id})

    def update_node_status(self, node_id: str, status: str) -> None:
        """(reference: node_endpoint.go:541 UpdateStatus)"""
        node = self.state.node_by_id(node_id)
        if node is None:
            return
        old = node.status
        self.state.update_node_status(node_id, status, time.time())
        if status == NODE_STATUS_READY:
            self._reset_heartbeat(node_id)
            if old != NODE_STATUS_READY:
                self.blocked_evals.unblock(node.computed_class)
                self._create_node_evals(node_id)
        elif status in (NODE_STATUS_DOWN, NODE_STATUS_DISCONNECTED):
            if old not in (NODE_STATUS_DOWN, NODE_STATUS_DISCONNECTED):
                from .logbroker import log as _log
                _log("warn", "heartbeat",
                     f"node {node_id[:8]} marked {status}")
                # flap scoring: repeated ready->down transitions arm an
                # escalating quarantine on this node's recovery
                score = self.flaps.record_down(node_id)
                if score:
                    from .telemetry import metrics
                    metrics.incr("nomad.heartbeat.flap_recorded")
            with self._hb_lock:
                self._heartbeat_deadlines.pop(node_id, None)
            self._create_node_evals(node_id)
            # a dead node's services must leave the catalog (reference:
            # state store sweep on node down) -- one node-keyed write
            if status == NODE_STATUS_DOWN:
                self.state.delete_services_by_node(node_id)
        self.publish_event("NodeStatusUpdate",
                           {"node_id": node_id, "status": status})

    def heartbeat(self, node_id: str) -> float:
        """Client TTL refresh (reference: heartbeat.go:93). Returns TTL."""
        from ..faultinject import faults
        faults.fire("heartbeat")    # chaos: stall/drop client check-ins
        node = self.state.node_by_id(node_id)
        if node is None:
            return 0.0
        if node.status in (NODE_STATUS_DOWN, NODE_STATUS_DISCONNECTED):
            # heartbeat from a down node: it must re-register its status
            # -- unless it is serving a flap quarantine, in which case
            # the recovery is DEFERRED (the node keeps heartbeating and
            # stays down; its workloads were already replaced by the
            # node-down fan-out, so deferral costs capacity, not work)
            rem = self.flaps.quarantine_remaining(node_id)
            if rem > 0:
                from .telemetry import metrics
                metrics.incr("nomad.heartbeat.quarantine_deferred")
                return self.heartbeat_ttl
            self.update_node_status(node_id, NODE_STATUS_READY)
        self._reset_heartbeat(node_id)
        return self.heartbeat_ttl

    def _reset_heartbeat(self, node_id: str) -> None:
        with self._hb_lock:
            self._heartbeat_deadlines[node_id] = (
                time.time() + self.heartbeat_ttl)

    def _create_node_evals(self, node_id: str) -> None:
        """Evals for every job with allocs on the node + system jobs
        (reference: node_endpoint.go createNodeEvals)."""
        allocs = self.state.allocs_by_node(node_id)
        jobs = {}
        for a in allocs:
            if not a.terminal_status():
                jobs[(a.namespace, a.job_id)] = a.job
        evals = []
        for (ns, job_id), job in jobs.items():
            stored = self.state.job_by_id(ns, job_id)
            if stored is None:
                continue
            evals.append(Evaluation(
                id=generate_uuid(), namespace=ns,
                priority=stored.priority, type=stored.type,
                triggered_by=TRIGGER_NODE_UPDATE, job_id=job_id,
                node_id=node_id, status=EVAL_STATUS_PENDING))
        # system jobs must consider new/changed nodes
        for job in self.state.jobs():
            if job.type in (JOB_TYPE_SYSTEM, "sysbatch") and not job.stop:
                evals.append(Evaluation(
                    id=generate_uuid(), namespace=job.namespace,
                    priority=job.priority, type=job.type,
                    triggered_by=TRIGGER_NODE_UPDATE, job_id=job.id,
                    node_id=node_id, status=EVAL_STATUS_PENDING))
        if evals:
            self.state.upsert_evals(evals)
            # node fan-outs go through storm admission: one wave admits
            # immediately, the rest release paced (a mass node-down must
            # not dump its whole fan-out on the ready queue at once)
            self.broker.enqueue_storm(evals)

    def drain_node(self, node_id: str, strategy) -> None:
        """Start/stop a drain: mark the node ineligible and let the
        drainer pace migrations per each task group's migrate.max_parallel
        until the deadline, after which everything remaining force-drains
        (reference: nomad/drainer/ NodeDrainer + drain_heap.go deadlines
        + watch_jobs.go per-TG batching)."""
        if strategy is not None:
            strategy.started_at = strategy.started_at or time.time()
            if strategy.deadline_s > 0 and not strategy.force_deadline:
                strategy.force_deadline = (strategy.started_at
                                           + strategy.deadline_s)
        self.state.update_node_drain(node_id, strategy,
                                     mark_eligible=strategy is None)
        if strategy is None:
            return
        self._drain_tick(node_id, strategy)
        self.publish_event("NodeDrain", {"node_id": node_id})

    def _run_drainer(self) -> None:
        """(reference: nomad/drainer/drainer.go run loop)"""
        while not self._shutdown.wait(0.3):
            if not self._leader_active.is_set():
                continue
            for node in self.state.nodes():
                if node.drain and node.drain_strategy is not None:
                    self._drain_tick(node.id, node.drain_strategy)

    def _drain_tick(self, node_id: str, strategy) -> None:
        """One pacing round for a draining node: per (job, tg), mark at
        most migrate.max_parallel allocs for migration at a time; past
        the force deadline everything remaining drains at once."""
        with self._drain_lock:
            self._drain_tick_locked(node_id, strategy)

    def _drain_tick_locked(self, node_id: str, strategy) -> None:
        remaining = [a for a in self.state.allocs_by_node(node_id)
                     if not a.terminal_status()
                     and (a.job is None or not strategy.ignore_system_jobs
                          or a.job.type not in (JOB_TYPE_SYSTEM,
                                                "sysbatch"))]
        if not remaining:
            # drain complete: node stays ineligible, strategy clears
            # (reference: drainer marks the node done)
            node = self.state.node_by_id(node_id)
            if node is not None and node.drain:
                self.state.update_node_drain(node_id, None,
                                             mark_eligible=False)
                self.publish_event("NodeDrainComplete",
                                   {"node_id": node_id})
            return
        forced = (strategy.force_deadline
                  and time.time() >= strategy.force_deadline)
        to_mark: List[str] = []
        by_group: Dict[tuple, List[Allocation]] = {}
        for a in remaining:
            by_group.setdefault((a.namespace, a.job_id, a.task_group),
                                []).append(a)
        for (ns, job_id, tg_name), allocs in by_group.items():
            if forced:
                to_mark.extend(a.id for a in allocs
                               if not a.desired_transition.migrate)
                continue
            job = self.state.job_by_id(ns, job_id)
            tg = job.lookup_task_group(tg_name) if job is not None else None
            limit = (tg.migrate.max_parallel
                     if tg is not None and tg.migrate is not None else 1)
            # slots busy = this group's allocs anywhere still migrating
            # (marked but not yet terminal) -- a freed slot means the
            # migrated alloc stopped (its replacement placed elsewhere)
            in_flight = sum(
                1 for a in self.state.allocs_by_job(ns, job_id)
                if a.task_group == tg_name
                and a.desired_transition.migrate
                and not a.terminal_status())
            room = max(0, limit - in_flight)
            for a in allocs:
                if room <= 0:
                    break
                if not a.desired_transition.migrate:
                    to_mark.append(a.id)
                    room -= 1
        if to_mark:
            self.state.update_alloc_desired_transition(to_mark,
                                                       migrate=True)
            self._create_node_evals(node_id)

    def update_allocs_from_client(self, allocs: List[Allocation]) -> None:
        """(reference: node_endpoint.go:1322 UpdateAlloc)"""
        self.state.update_allocs_from_client(allocs)
        # terminal allocs leave the service catalog in ONE replicated
        # write (reference: the state store deletes service registrations
        # in UpdateAllocsFromClient)
        terminal = [a.id for a in allocs if a.client_terminal_status()]
        if terminal:
            self.state.delete_services_by_allocs(terminal)
        # allocs going terminal can complete the job
        for key in {(a.namespace, a.job_id) for a in allocs}:
            self._refresh_job_status(*key)
        # failed allocs trigger reschedule evals
        evals = []
        seen = set()
        for a in allocs:
            if a.client_status == ALLOC_CLIENT_FAILED:
                stored = self.state.alloc_by_id(a.id)
                if stored is None or (stored.namespace, stored.job_id) in seen:
                    continue
                job = self.state.job_by_id(stored.namespace, stored.job_id)
                if job is None or job.stop:
                    continue
                seen.add((stored.namespace, stored.job_id))
                evals.append(Evaluation(
                    id=generate_uuid(), namespace=stored.namespace,
                    priority=job.priority, type=job.type,
                    triggered_by="alloc-failure", job_id=job.id,
                    status=EVAL_STATUS_PENDING))
        if evals:
            self.state.upsert_evals(evals)
            self.broker.enqueue_all(evals)

    # ------------------------------------------------------------------
    # Worker callbacks
    def _on_plan_batch_commit(self, results: List[PlanResult]) -> None:
        """ONE unblock sweep for a whole committed plan batch: the freed
        classes of every plan in the group union before sweeping, so N
        batched plans cost one BlockedEvals pass per class instead of N
        (called from the plan applier's committer thread)."""
        freed_classes = set()
        for result in results:
            for node_id in (list(result.node_update)
                            + list(result.node_preemptions)):
                node = self.state.node_by_id(node_id)
                if node is not None:
                    freed_classes.add(node.computed_class)
        for cls in freed_classes:
            self.blocked_evals.unblock(cls)

    def on_plan_result(self, plan: Plan, result: PlanResult) -> None:
        # Freed capacity (stops/preemptions) unblocks class-keyed evals
        # (reference: FSM hooks into BlockedEvals on alloc updates);
        # batch-committed results were already swept once per group
        if not getattr(result, "batch_unblocked", False):
            freed_classes = set()
            for node_id in (list(result.node_update)
                            + list(result.node_preemptions)):
                node = self.state.node_by_id(node_id)
                if node is not None:
                    freed_classes.add(node.computed_class)
            for cls in freed_classes:
                self.blocked_evals.unblock(cls)
        if not result.is_no_op():
            self.publish_event("PlanApplied", {
                "eval_id": plan.eval_id,
                "placed": sum(len(v) for v in result.node_allocation.values()),
                "stopped": sum(len(v) for v in result.node_update.values()),
            })

    def on_eval_update(self, ev: Evaluation) -> None:
        if ev.status == EVAL_STATUS_COMPLETE:
            self._refresh_job_status(ev.namespace, ev.job_id)
        self.publish_event("EvalUpdated",
                           {"eval_id": ev.id, "status": ev.status})

    def _refresh_job_status(self, namespace: str, job_id: str) -> None:
        """(reference: fsm job summary / setJobStatus)"""
        job = self.state.job_by_id(namespace, job_id)
        if job is None:
            return
        allocs = self.state.allocs_by_job(namespace, job_id)
        status = job.status
        if any(not a.terminal_status() for a in allocs):
            status = JOB_STATUS_RUNNING
        elif allocs and all(a.terminal_status() for a in allocs):
            # Everything ran and finished (or the job was stopped): dead --
            # unless an eval is still in flight to place more work
            # (reference: fsm setJobStatus dead conditions).
            pending = any(not e.terminal_status() for e in
                          self.state.evals_by_job(namespace, job_id))
            if job.stop or not pending:
                status = JOB_STATUS_DEAD
        if status != job.status:
            self.state.update_job_status(namespace, job_id, status)

    # ------------------------------------------------------------------
    # Namespaces + node pools (reference: nomad/namespace_endpoint.go,
    # nomad/node_pool_endpoint.go)
    def upsert_namespace(self, namespace) -> None:
        if not namespace.name or "/" in namespace.name:
            raise ValueError(f"invalid namespace name {namespace.name!r}")
        self.state.upsert_namespace(namespace)
        self.publish_event("NamespaceUpserted", {"name": namespace.name})

    def delete_namespace(self, name: str) -> None:
        if name == "default":
            raise ValueError("default namespace cannot be deleted")
        if self.state.namespace_by_name(name) is None:
            raise ValueError(f"namespace {name!r} not found")
        in_use = [j.id for j in self.state.jobs() if j.namespace == name]
        if in_use:
            raise ValueError(
                f"namespace {name!r} has {len(in_use)} non-purged jobs")
        if self.state.variables(name):
            raise ValueError(f"namespace {name!r} has variables")
        self.state.delete_namespace(name)
        self.publish_event("NamespaceDeleted", {"name": name})

    def upsert_node_pool(self, pool) -> None:
        if not pool.name or pool.name == "all":
            raise ValueError(f"invalid node pool name {pool.name!r}")
        self.state.upsert_node_pool(pool)
        self.publish_event("NodePoolUpserted", {"name": pool.name})

    def delete_node_pool(self, name: str) -> None:
        if name in ("default", "all"):
            raise ValueError(f"built-in node pool {name!r} is undeletable")
        if self.state.node_pool_by_name(name) is None:
            raise ValueError(f"node pool {name!r} not found")
        nodes = [n.id for n in self.state.nodes() if n.node_pool == name]
        if nodes:
            raise ValueError(f"node pool {name!r} has {len(nodes)} nodes")
        jobs = [j.id for j in self.state.jobs() if j.node_pool == name]
        if jobs:
            raise ValueError(f"node pool {name!r} used by {len(jobs)} jobs")
        self.state.delete_node_pool(name)
        self.publish_event("NodePoolDeleted", {"name": name})

    # ------------------------------------------------------------------
    # Native service discovery (reference:
    # nomad/service_registration_endpoint.go)
    def upsert_services(self, regs) -> None:
        regs = [r for r in regs if r.provider == "nomad" and r.service_name]
        if regs:
            self.state.upsert_service_registrations(regs)

    def service_names(self, namespace: Optional[str] = None) -> List[dict]:
        """Catalog listing: name + tag union per service
        (reference: ServiceRegistration.List)."""
        byname: Dict[tuple, dict] = {}
        for reg in self.state.service_registrations(namespace):
            entry = byname.setdefault(
                (reg.namespace, reg.service_name),
                {"namespace": reg.namespace,
                 "service_name": reg.service_name, "tags": []})
            for t in reg.tags:
                if t not in entry["tags"]:
                    entry["tags"].append(t)
        return list(byname.values())

    # ------------------------------------------------------------------
    # CSI volumes (reference: nomad/csi_endpoint.go)
    def register_csi_volume(self, vol) -> None:
        if not vol.id or not vol.plugin_id:
            raise ValueError("volume id and plugin_id are required")
        if self.state.namespace_by_name(vol.namespace) is None:
            raise ValueError(f"namespace {vol.namespace!r} does not exist")
        self.state.upsert_csi_volume(vol)
        self.publish_event("CSIVolumeRegistered",
                           {"volume_id": vol.id, "namespace": vol.namespace})

    def deregister_csi_volume(self, namespace: str, vol_id: str,
                              force: bool = False) -> None:
        vol = self.state.csi_volume_by_id(namespace, vol_id)
        if vol is None:
            raise ValueError(f"volume {vol_id!r} not found")
        if not force and (vol.read_claims or vol.write_claims):
            raise ValueError(
                f"volume {vol_id!r} has active claims (use force)")
        self.state.delete_csi_volume(namespace, vol_id)
        self.publish_event("CSIVolumeDeregistered",
                           {"volume_id": vol_id, "namespace": namespace})

    def _run_volume_watcher(self) -> None:
        """Release claims held by terminal allocs so writers can move
        (reference: nomad/volumewatcher/volumes_watcher.go)."""
        while not self._shutdown.wait(0.5):
            if not self._leader_active.is_set():
                continue
            for vol in self.state.csi_volumes():
                for alloc_id in (list(vol.read_claims)
                                 + list(vol.write_claims)):
                    alloc = self.state.alloc_by_id(alloc_id)
                    if alloc is None or alloc.terminal_status():
                        self.state.csi_volume_release(
                            vol.namespace, vol.id, alloc_id)

    # ------------------------------------------------------------------
    # Search (reference: nomad/search_endpoint.go)
    def search(self, prefix: str, context: str = "all",
               namespace: Optional[str] = None,
               allowed_contexts: Optional[List[str]] = None,
               ns_allowed=None) -> dict:
        from .search import Searcher
        return Searcher(self.state, ns_allowed).prefix_search(
            prefix, context, namespace, allowed_contexts)

    def fuzzy_search(self, text: str, context: str = "all",
                     namespace: Optional[str] = None,
                     allowed_contexts: Optional[List[str]] = None,
                     ns_allowed=None) -> dict:
        from .search import Searcher
        return Searcher(self.state, ns_allowed).fuzzy_search(
            text, context, namespace, allowed_contexts)

    # ------------------------------------------------------------------
    # Multi-region federation (reference: nomad/rpc.go cross-region
    # forwarding + leader.go ACL replication from the authoritative region)
    def join_federation(self, region: str, address: str) -> None:
        """Register a peer region's HTTP address for request forwarding."""
        if region == self.region:
            return
        self.federation[region] = address.rstrip("/")
        self.publish_event("RegionJoined", {"name": region})

    def remove_raft_peer(self, name: str) -> None:
        """(reference: operator_endpoint.go RaftRemovePeer). Real logic
        lives here so the cluster forwarding layer can invoke it on the
        leader; plain dev servers have no raft to operate on."""
        raft = getattr(self, "raft", None)
        if raft is None:
            raise ValueError("not a raft server")
        raft.remove_server(name)

    def leave_federation(self, region: str) -> None:
        if self.federation.pop(region, None) is not None:
            self.publish_event("RegionLeft", {"name": region})

    def enable_wan(self, http_addr: str, name: str = "",
                   port: int = 0):
        """Start the WAN gossip pool (reference: server.go setupSerf WAN):
        regions then discover each other via wan_join instead of explicit
        join_federation pairs. Returns the WanGossip (its .addr is the
        join target for other regions)."""
        from .wan import WanGossip
        self.wan = WanGossip(self, http_addr, name=name or None,
                             port=port)
        self.wan.start()
        return self.wan

    def wan_join(self, addr) -> int:
        if self.wan is None:
            raise RuntimeError("WAN gossip not enabled (enable_wan first)")
        return self.wan.join(addr)

    def regions(self) -> List[str]:
        return sorted([self.region] + list(self.federation))

    def forward_address(self, region: str) -> Optional[str]:
        return self.federation.get(region)

    def start_acl_replication(self, authoritative_region: str,
                              token: str = "",
                              interval: float = 5.0) -> None:
        """Pull ACL policies + global tokens from the authoritative
        region (reference: leader.go:486 replicateACLPolicies/
        replicateACLTokens). No-op when WE are authoritative."""
        if authoritative_region == self.region:
            return

        def loop():
            from ..api.client import ApiClient
            from ..structs import ACLPolicy, ACLToken
            from ..structs import codec as _codec
            # upstream modify_index per item: fetch only what changed
            # (reference: minIndex-based replication, leader.go:486)
            seen_policies: Dict[str, int] = {}
            seen_tokens: Dict[str, int] = {}
            while not self._shutdown.wait(interval):
                addr = self.federation.get(authoritative_region)
                if addr is None:
                    continue
                try:
                    api = ApiClient(addr, token=token)
                    remote_pols = api.get("/v1/acl/policies")
                    remote_names = {p["name"] for p in remote_pols}
                    for p in remote_pols:
                        idx = int(p.get("modify_index", 0))
                        if seen_policies.get(p["name"]) == idx:
                            continue
                        full = api.get(f"/v1/acl/policy/{p['name']}")
                        self.state.upsert_acl_policies(
                            [_codec.decode(ACLPolicy, full)])
                        seen_policies[p["name"]] = idx
                    # deletions propagate (reference: replication deletes
                    # rows absent from the authoritative set)
                    gone = [pl.name for pl in self.state.acl_policies()
                            if pl.name not in remote_names]
                    if gone:
                        self.state.delete_acl_policies(gone)
                        for name in gone:
                            seen_policies.pop(name, None)

                    remote_toks = api.get("/v1/acl/tokens")
                    remote_global = {t["accessor_id"] for t in remote_toks
                                     if t.get("global")}
                    for t in remote_toks:
                        if not t.get("global"):
                            continue   # only global tokens replicate
                        idx = int(t.get("modify_index", 0))
                        if seen_tokens.get(t["accessor_id"]) == idx:
                            continue
                        full = api.get(
                            f"/v1/acl/token/{t['accessor_id']}")
                        self.state.upsert_acl_tokens(
                            [_codec.decode(ACLToken, full)])
                        seen_tokens[t["accessor_id"]] = idx
                    gone_toks = [
                        tk.accessor_id for tk in self.state.acl_tokens()
                        if tk.global_token
                        and tk.accessor_id not in remote_global]
                    if gone_toks:
                        self.state.delete_acl_tokens(gone_toks)
                        for acc in gone_toks:
                            seen_tokens.pop(acc, None)
                except Exception:   # noqa: BLE001 -- peer down: retry
                    continue

        t = threading.Thread(target=loop, daemon=True,
                             name="acl-replication")
        t.start()
        self._acl_replication_thread = t

    # ------------------------------------------------------------------
    # Operator snapshot (reference: nomad/operator_endpoint.go
    # SnapshotSave/SnapshotRestore + helper/snapshot/)
    def snapshot_save(self) -> bytes:
        from ..raft.fsm import dump_state
        from .snapshot import save_archive
        real = getattr(self.state, "_store", self.state)
        blob = dump_state(real)
        return save_archive(blob, blob.get("index", 0))

    def snapshot_restore(self, data: bytes) -> dict:
        """Verify + install an archive, then rebuild leader-side volatile
        state from the restored tables (reference: the leader restores the
        raft snapshot and re-establishes leadership services)."""
        from .snapshot import load_archive
        meta, blob = load_archive(data)
        was_leader = self.is_leader()
        if was_leader:
            self.revoke_leadership()
        self.state.restore_from_snapshot(blob)
        if was_leader:
            self.establish_leadership()
        self.publish_event("SnapshotRestored", {"index": meta["index"]})
        return meta

    # ------------------------------------------------------------------
    # Event stream (reference: nomad/stream/event_broker.go EventBroker --
    # ring buffer + per-subscription queues with topic filters)
    @staticmethod
    def _event_key(payload: dict) -> str:
        for k in ("job_id", "node_id", "eval_id", "volume_id",
                  "dispatched_id", "name"):
            if payload.get(k):
                return str(payload[k])
        return ""

    def publish_event(self, topic: str, payload: dict) -> None:
        event = {"topic": topic, "key": self._event_key(payload),
                 "index": self.state.latest_index(),
                 "time": time.time(), "payload": payload}
        with self._events_lock:
            self._events.append(event)
            if len(self._events) > 4096:     # ring buffer semantics
                self._events = self._events[-2048:]
            subs = list(self._event_subs)
        for sub in subs:
            sub.offer(event)

    def events_since(self, index: int) -> List[dict]:
        with self._events_lock:
            return [e for e in self._events if e["index"] > index]

    def subscribe_events(self, topics: Optional[Dict[str, List[str]]] = None,
                         since_index: int = 0) -> "EventSubscription":
        """topics: {topic-or-*: [keys-or-*]} (reference: stream
        SubscribeRequest.Topics). Replays the ring buffer from
        since_index, then live."""
        sub = EventSubscription(topics)
        # Replay THEN register, all under one lock acquisition: publishers
        # append+snapshot subs under this lock, so no event can land in
        # neither (lost-event gap) nor jump ahead of the backlog
        # (out-of-order delivery).
        with self._events_lock:
            if since_index:
                for e in self._events:
                    if e["index"] > since_index:
                        sub.offer(e)
            self._event_subs.append(sub)
        return sub

    def unsubscribe_events(self, sub: "EventSubscription") -> None:
        with self._events_lock:
            if sub in self._event_subs:
                self._event_subs.remove(sub)

    # ------------------------------------------------------------------
    # Background loops
    def _run_heartbeat_watcher(self) -> None:
        """Server-side TTL timers (reference: heartbeat.go invalidateHeartbeat
        :138): a missed TTL marks the node down/disconnected and creates
        evals for its workloads."""
        while not self._shutdown.wait(0.2):
            if not self._leader_active.is_set():
                continue
            now = time.time()
            expired = []
            with self._hb_lock:
                for node_id, dl in list(self._heartbeat_deadlines.items()):
                    if dl <= now:
                        expired.append(node_id)
                        del self._heartbeat_deadlines[node_id]
            for node_id in expired:
                node = self.state.node_by_id(node_id)
                if node is None:
                    continue
                # disconnected when any alloc has disconnect grace
                # (reference: heartbeat.go:180 disconnectState)
                grace = False
                for a in self.state.allocs_by_node(node_id):
                    if a.terminal_status() or a.job is None:
                        continue
                    tg = a.job.lookup_task_group(a.task_group)
                    if tg is not None and tg.max_client_disconnect_s:
                        grace = True
                        break
                status = (NODE_STATUS_DISCONNECTED if grace
                          else NODE_STATUS_DOWN)
                self.update_node_status(node_id, status)

    def _run_gc(self) -> None:
        """Core GC job (reference: core_sched.go evalGC :236, nodeGC :423)."""
        while not self._shutdown.wait(GC_INTERVAL):
            if self._leader_active.is_set():
                self.run_gc_once()

    def run_gc_once(self, threshold: float = GC_EVAL_THRESHOLD,
                    terminal_watermark: Optional[int] = None) -> dict:
        """One pass of the core GC job, inside span ``core.gc`` and
        timer ``nomad.core.gc``. It works for no one eval, so the span
        has no trace to land in: it shows on the profiler's timeline
        and in the span sink, and every eval in flight while it ran
        gets a ``core.gc`` event carrying its duration, so that a
        waterfall shows the stall that slowed it."""
        from .telemetry import metrics
        from .tracing import tracer
        t0 = time.perf_counter()
        with metrics.measure("nomad.core.gc"), tracer.span("core.gc"):
            out = self._gc_pass(threshold, terminal_watermark)
        tracer.broadcast_event(
            "core.gc", dur_ms=round((time.perf_counter() - t0) * 1e3, 3))
        return out

    def _gc_pass(self, threshold: float,
                 terminal_watermark: Optional[int]) -> dict:
        from .telemetry import metrics
        cutoff = time.time() - threshold
        gone_evals = []
        evals = self.state.evals()
        walked = 0
        for ev in evals:
            if not ev.terminal_status():
                continue
            allocs = self.state.allocs_by_eval(ev.id)
            walked += len(allocs)
            if all(a.terminal_status() for a in allocs) and \
                    ev.modify_time < cutoff:
                gone_evals.append(ev.id)
        if gone_evals:
            self.state.delete_evals(gone_evals)
        gone_set = set(gone_evals)
        all_allocs = self.state.allocs()
        metrics.incr("nomad.core.gc_evals_scanned", len(evals))
        # each terminal eval's own allocs (an index by eval), then the
        # sweep below walks the whole table once
        metrics.incr("nomad.core.gc_allocs_scanned",
                     walked + len(all_allocs))
        gone_allocs = [
            a.id for a in all_allocs
            if a.terminal_status() and a.modify_time < cutoff
            and (a.eval_id in gone_set or not a.eval_id
                 or self.state.eval_by_id(a.eval_id) is None)]
        if gone_allocs:
            self.state.delete_allocs(gone_allocs)
        # dead jobs with no allocs/evals
        gone_jobs = 0
        for job in self.state.jobs():
            if job.status == JOB_STATUS_DEAD and not job.is_periodic():
                if not self.state.allocs_by_job(job.namespace, job.id) and \
                        not self.state.evals_by_job(job.namespace, job.id):
                    self.state.delete_job(job.namespace, job.id)
                    gone_jobs += 1
        # bounded state under churn (ISSUE 6): the age-based sweep above
        # retains up to an hour of terminal history -- at production
        # churn rates that is unbounded relative to the live set. The
        # watermark pass deletes the OLDEST terminal allocs beyond the
        # bound regardless of age (their history value is marginal; the
        # live fleet's memory ceiling is not), then compacts the tensor
        # table's freed rows so RSS actually returns.
        wm = self._gc_watermark(terminal_watermark)
        compacted = self.state.compact_alloc_table() \
            if hasattr(self.state, "compact_alloc_table") else None
        if compacted is not None:
            metrics.incr("nomad.gc.table_compactions")
        return {"evals": len(gone_evals), "allocs": len(gone_allocs),
                "jobs": gone_jobs, "watermark_allocs": wm,
                "compacted": compacted}

    def _gc_watermark(self, terminal_watermark: Optional[int]) -> int:
        """Delete the oldest terminal allocs beyond the retention bound
        (NOMAD_TPU_GC_ALLOC_WATERMARK, 0 disables). Returns count."""
        import os
        wm = terminal_watermark
        if wm is None:
            wm = int(os.environ.get("NOMAD_TPU_GC_ALLOC_WATERMARK",
                                    str(GC_ALLOC_WATERMARK)) or 0)
        if wm <= 0:
            return 0
        terminal = [a for a in self.state.allocs() if a.terminal_status()]
        excess = len(terminal) - wm
        if excess <= 0:
            return 0
        terminal.sort(key=lambda a: a.modify_time)
        gone = [a.id for a in terminal[:excess]]
        self.state.delete_allocs(gone)
        from .telemetry import metrics
        metrics.incr("nomad.gc.watermark_allocs_deleted", len(gone))
        return len(gone)

    def _run_periodic(self) -> None:
        """Cron-style launcher (reference: periodic.go:25). Supports
        '@every <N>s' specs; full cron parsing is a later round."""
        while not self._shutdown.wait(0.5):
            if not self._leader_active.is_set():
                continue
            now = time.time()
            for job in self.state.jobs():
                if not job.is_periodic() or job.stop:
                    continue
                p = job.periodic
                if not p.enabled or not p.spec.startswith("@every "):
                    continue
                try:
                    interval = float(p.spec[len("@every "):].rstrip("s"))
                except ValueError:
                    continue
                key = (job.namespace, job.id)
                last = self._periodic_last.get(key, 0.0)
                if now - last < interval:
                    continue
                if p.prohibit_overlap:
                    children = [j for j in self.state.jobs()
                                if j.parent_id == job.id
                                and j.status != JOB_STATUS_DEAD]
                    if children:
                        continue
                self._periodic_last[key] = now
                self._dispatch_periodic(job, now)

    def _dispatch_periodic(self, job: Job, now: float) -> None:
        """(reference: periodic.go:51 DispatchJob -> derived child job)"""
        import copy
        child = copy.deepcopy(job)
        child.id = f"{job.id}/periodic-{int(now)}"
        child.parent_id = job.id
        child.periodic = None
        self.register_job(child)

    def periodic_force(self, namespace: str, job_id: str) -> str:
        """Launch a periodic job's child NOW (reference:
        periodic_endpoint.go Force -> PeriodicDispatch.ForceRun).
        Returns the child job id."""
        job = self.state.job_by_id(namespace, job_id)
        if job is None:
            raise ValueError(f"unknown job {job_id!r}")
        if not job.is_periodic():
            raise ValueError(f"job {job_id!r} is not periodic")
        now = time.time()
        self._dispatch_periodic(job, now)
        return f"{job.id}/periodic-{int(now)}"

    def stop_alloc(self, alloc_id: str) -> Optional[str]:
        """Stop ONE allocation and let the scheduler replace it
        (reference: alloc_endpoint.go Stop -> DesiredTransition.Migrate +
        eval). Returns the created eval id, or None for unknown allocs."""
        alloc = self.state.alloc_by_id(alloc_id)
        if alloc is None:
            return None
        from ..structs import DesiredTransition
        updated = alloc.copy_skip_job()
        updated.job = alloc.job
        updated.desired_transition = DesiredTransition(migrate=True)
        self.state.upsert_allocs([updated])
        ev = Evaluation(
            id=generate_uuid(), namespace=alloc.namespace,
            job_id=alloc.job_id, priority=alloc.job.priority
            if alloc.job else 50,
            type=alloc.job.type if alloc.job else "service",
            triggered_by="alloc-stop", status=EVAL_STATUS_PENDING)
        self.state.upsert_evals([ev])
        self.broker.enqueue(ev)
        self.publish_event("AllocStopRequested", {"alloc_id": alloc_id})
        return ev.id

    def _run_deployment_watcher(self) -> None:
        """Drives rolling updates: watches alloc health within active
        deployments, advances/fails/completes them, and emits evals so the
        reconciler's max_parallel gate releases the next batch
        (reference: nomad/deploymentwatcher/deployments_watcher.go)."""
        while not self._shutdown.wait(0.3):
            if not self._leader_active.is_set():
                continue
            for d in self.state.deployments():
                if not d.active() or d.status != DEPLOYMENT_STATUS_RUNNING:
                    continue
                self._watch_deployment(d)

    def pause_deployment(self, deployment_id: str, pause: bool) -> None:
        """Pause/resume a rollout (reference: deployment_endpoint.go
        Pause -> deploymentwatcher PauseDeployment); the watcher only
        advances RUNNING deployments."""
        import copy
        d = self.state.deployment_by_id(deployment_id)
        if d is None:
            raise ValueError(f"unknown deployment {deployment_id!r}")
        if pause and d.status != DEPLOYMENT_STATUS_RUNNING:
            raise ValueError(f"deployment is {d.status}, not running")
        if not pause and d.status != DEPLOYMENT_STATUS_PAUSED:
            raise ValueError(f"deployment is {d.status}, not paused")
        nd = copy.deepcopy(d)
        nd.status = (DEPLOYMENT_STATUS_PAUSED if pause
                     else DEPLOYMENT_STATUS_RUNNING)
        nd.status_description = ("Deployment is paused" if pause
                                 else "Deployment is running")
        self.state.upsert_deployment_cas(nd, d.modify_index)
        self.publish_event("DeploymentPaused" if pause
                           else "DeploymentResumed",
                           {"deployment_id": deployment_id})

    def fail_deployment(self, deployment_id: str) -> None:
        """Operator-failed rollout (reference: deployment_endpoint.go
        Fail): marks failed and auto-reverts groups that ask for it,
        exactly like the watcher's unhealthy path."""
        import copy
        d = self.state.deployment_by_id(deployment_id)
        if d is None:
            raise ValueError(f"unknown deployment {deployment_id!r}")
        if not d.active():
            raise ValueError(f"deployment is already {d.status}")
        nd = copy.deepcopy(d)
        nd.status = DEPLOYMENT_STATUS_FAILED
        nd.status_description = "Deployment marked as failed by operator"
        if self.state.upsert_deployment_cas(nd, d.modify_index):
            if any(st.auto_revert for st in nd.task_groups.values()):
                self._revert_job(nd)
        self.publish_event("DeploymentFailed",
                           {"deployment_id": deployment_id})

    def promote_deployment(self, deployment_id: str,
                           groups: Optional[List[str]] = None) -> None:
        """Promote canaries (reference: deployment_endpoint.go Promote ->
        deploymentwatcher PromoteDeployment): every targeted group must
        have its desired canaries HEALTHY; promotion unblocks the
        reconciler's canary gate so the rollout proceeds."""
        import copy
        d = self.state.deployment_by_id(deployment_id)
        if d is None:
            raise ValueError(f"unknown deployment {deployment_id!r}")
        if d.status != DEPLOYMENT_STATUS_RUNNING:
            raise ValueError("deployment is not running")
        allocs = [a for a in self.state.allocs_by_job(
                      d.namespace, d.job_id)
                  if a.deployment_id == d.id]
        nd = copy.deepcopy(d)
        targets = groups or list(nd.task_groups)
        for tg_name in targets:
            st = nd.task_groups.get(tg_name)
            if st is None:
                raise ValueError(f"unknown task group {tg_name!r}")
            if st.desired_canaries <= 0 or st.promoted:
                continue
            healthy_canaries = sum(
                1 for a in allocs
                if a.task_group == tg_name
                and a.deployment_status is not None
                and a.deployment_status.canary
                and a.deployment_status.is_healthy())
            if healthy_canaries < st.desired_canaries:
                raise ValueError(
                    f"group {tg_name!r}: {healthy_canaries}/"
                    f"{st.desired_canaries} canaries healthy")
            st.promoted = True
        if not self.state.upsert_deployment_cas(nd, d.modify_index):
            raise ValueError("deployment changed concurrently; retry")
        job = self.state.job_by_id(nd.namespace, nd.job_id)
        if job is not None and not job.stop:
            ev = Evaluation(
                id=generate_uuid(), namespace=nd.namespace,
                priority=nd.eval_priority, type=job.type,
                triggered_by=TRIGGER_DEPLOYMENT_WATCHER,
                job_id=nd.job_id, deployment_id=nd.id,
                status=EVAL_STATUS_PENDING)
            self.state.upsert_evals([ev])
            self.broker.enqueue(ev)
        self.publish_event("DeploymentPromoted",
                           {"deployment_id": nd.id, "groups": targets})

    def _watch_deployment(self, d: Deployment) -> None:
        import copy
        allocs = [a for a in self.state.allocs_by_job(
                      d.namespace, d.job_id)
                  if a.deployment_id == d.id]
        changed = False
        nd = copy.deepcopy(d)
        failed_tg = None
        for tg_name, st in nd.task_groups.items():
            tg_allocs = [a for a in allocs if a.task_group == tg_name]
            placed = len(tg_allocs)
            healthy = sum(1 for a in tg_allocs
                          if a.deployment_status is not None
                          and a.deployment_status.is_healthy())
            unhealthy = sum(1 for a in tg_allocs
                            if a.deployment_status is not None
                            and a.deployment_status.is_unhealthy())
            if (placed, healthy, unhealthy) != (
                    st.placed_allocs, st.healthy_allocs, st.unhealthy_allocs):
                st.placed_allocs = placed
                st.healthy_allocs = healthy
                st.unhealthy_allocs = unhealthy
                changed = True
            if unhealthy > 0:
                failed_tg = tg_name
        if failed_tg is not None:
            # Unhealthy allocs fail the deployment regardless of
            # auto_revert; auto_revert only controls the rollback
            # (reference: deploymentwatcher FailDeployment).
            nd.status = DEPLOYMENT_STATUS_FAILED
            nd.status_description = (
                f"Failed due to unhealthy allocations in {failed_tg}")
            if self.state.upsert_deployment_cas(nd, d.modify_index):
                if nd.task_groups[failed_tg].auto_revert:
                    self._revert_job(nd)
            return
        job = self.state.job_by_id(nd.namespace, nd.job_id)
        complete = bool(nd.task_groups) and all(
            st.healthy_allocs >= st.desired_total
            for st in nd.task_groups.values())
        if complete and not nd.requires_promotion():
            nd.status = DEPLOYMENT_STATUS_SUCCESSFUL
            nd.status_description = "Deployment completed successfully"
            changed = True
            # a successful deployment marks the job version stable
            # (reference: deploymentwatcher setLatestEval -> Job.Stable)
            if job is not None and job.version == nd.job_version:
                self.state.update_job_stability(
                    nd.namespace, nd.job_id, nd.job_version, True)
        if changed:
            # CAS guards against a concurrent plan commit having advanced
            # the deployment while we computed counts (lost-update race);
            # on conflict just retry next tick.
            if not self.state.upsert_deployment_cas(nd, d.modify_index):
                return
            # progress -> let the reconciler release the next batch
            if job is not None and not job.stop and \
                    nd.status == DEPLOYMENT_STATUS_RUNNING:
                ev = Evaluation(
                    id=generate_uuid(), namespace=nd.namespace,
                    priority=nd.eval_priority, type=job.type,
                    triggered_by=TRIGGER_DEPLOYMENT_WATCHER,
                    job_id=nd.job_id, deployment_id=nd.id,
                    status=EVAL_STATUS_PENDING)
                self.state.upsert_evals([ev])
                self.broker.enqueue(ev)
        # auto_promote: healthy canaries promote without operator action
        # (reference: deploymentwatcher auto-promotion)
        cur = self.state.deployment_by_id(d.id)
        if cur is not None and cur.status == DEPLOYMENT_STATUS_RUNNING \
                and cur.requires_promotion() and cur.has_auto_promote():
            try:
                self.promote_deployment(cur.id)
            except ValueError:
                pass            # canaries not healthy yet; retry next tick

    def _revert_job(self, d: Deployment) -> None:
        """Auto-revert to the last stable version
        (reference: deploymentwatcher FailDeployment + job revert)."""
        job = self.state.job_by_id(d.namespace, d.job_id)
        if job is None:
            return
        for v in range(job.version - 1, -1, -1):
            prev = self.state.job_version(d.namespace, d.job_id, v)
            if prev is not None and prev.stable:
                import copy
                revert = copy.deepcopy(prev)
                self.register_job(revert)
                return
