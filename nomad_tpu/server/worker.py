"""Scheduler workers: dequeue evals, invoke the scheduler, submit plans.

Semantic parity with /root/reference/nomad/worker.go (Worker.run :397,
dequeueEvaluation :476, invokeScheduler :610, and the Planner impl
SubmitPlan :650 / UpdateEval :721 / CreateEval :760 / ReblockEval :802).
"""
from __future__ import annotations

import contextlib
import threading
import time
from typing import List, Optional, Tuple

from ..scheduler.factory import new_scheduler
from ..structs import (
    Evaluation, Plan, PlanResult, EVAL_STATUS_BLOCKED, EVAL_STATUS_COMPLETE,
    TRIGGER_JOB_REGISTER,
)
from .telemetry import metrics
from .tracing import tracer

ALL_SCHEDULERS = ["service", "batch", "system", "sysbatch", "_core"]


class WorkerCrash(BaseException):
    """Injected worker death (the ``worker.crash`` fault point).
    BaseException on purpose: it must ESCAPE the per-iteration
    ``except Exception`` guards in the worker loops and kill the thread
    the way a real segfault/OOM would -- no nack, no cleanup, leased
    evals left orphaned for the broker's nack-timeout redelivery."""


class StaleEvalToken(Exception):
    """A worker tried to submit a plan on an expired or superseded
    broker lease: its eval was redelivered after a nack-timeout
    (typically because this worker wedged past the supervisor's stall
    threshold and a replacement took over).  The plan must not commit
    -- the outstanding delivery owns the eval now (reference:
    plan_apply.go's EvalToken check against the broker's outstanding
    set).  This is what makes a wedged-then-woken zombie worker safe:
    its stale plan dies here instead of double-placing."""


def _release_bookings(server, eval_id: str) -> None:
    """Drop what ``eval_id``'s barrier booked for it and no commit has
    settled (server/inflight.py); a server without bookings (the test
    doubles) has none."""
    bookings = getattr(server, "inflight", None)
    if bookings is not None and eval_id:
        bookings.release(eval_id)


def _fire_crash_point() -> None:
    """``worker.crash`` chaos point: an armed error kills the worker
    thread mid-eval (contrast ``worker.invoke``, whose error takes the
    orderly nack path).  Armed hang/delay actions pass through fire()
    directly and wedge the loop instead -- that exercises the
    supervisor's stall detector rather than its death detector."""
    from ..faultinject import InjectedFault, faults
    try:
        faults.fire("worker.crash")
    except InjectedFault as e:
        raise WorkerCrash(str(e)) from e


class WorkerPlanner:
    """Planner interface handed to schedulers; routes through the leader's
    plan applier and raft-equivalent state writes."""

    def __init__(self, server, eval_token: str, eval_id: str = "",
                 worker_name: Optional[str] = None):
        self.server = server
        self.eval_token = eval_token
        self.eval_id = eval_id
        self.worker_name = worker_name
        # index of the snapshot the eval is first solved against, set
        # by invoke_scheduler; rides the eval's status update
        self.snapshot_index = 0

    def submit_plan(self, plan: Plan) -> Tuple[Optional[PlanResult], object]:
        # stale-lease fence (reference: the plan applier's EvalToken
        # check): a worker whose lease lapsed (nack-timeout redelivery
        # after a wedge/crash) must not commit -- exactly-once placement
        # belongs to the outstanding delivery
        if self.eval_id and not self.server.broker.token_outstanding(
                self.eval_id, self.eval_token):
            metrics.incr("nomad.plan.stale_token_rejected")
            raise StaleEvalToken(
                f"eval {self.eval_id} lease {self.eval_token} is no "
                f"longer outstanding; plan rejected")
        # (reference: worker.go:656 `nomad.plan.submit` -- wall time of the
        # whole submission incl. queue wait at the serialized applier)
        try:
            with metrics.measure("nomad.plan.submit"), \
                    tracer.span("plan.submit") as sp:
                result = self.server.planner.apply(
                    plan, worker=self.worker_name)
                sp.tag(allocs=sum(len(v)
                                  for v in result.node_allocation.values()),
                       rejected=len(result.rejected_nodes))
        finally:
            # the plan is back: what it committed took the commit's
            # index inside the store's lock (server/inflight.py), and
            # what is still booked under this eval -- a plan refused
            # whole never reaches the store, nor does one whose
            # submission raised -- will not commit
            _release_bookings(self.server, self.eval_id)
        new_state = None
        if result.rejected_nodes or (result.is_no_op() and not plan.is_no_op()):
            # partial/failed commit: scheduler refreshes its snapshot
            new_state = self.server.state.snapshot()
        with tracer.span("plan.on_result"):
            self.server.on_plan_result(plan, result)
        return result, new_state

    def update_eval(self, ev: Evaluation) -> None:
        # `ev` is the scheduler's own copy (_eval_with_status)
        ev.snapshot_index = self.snapshot_index
        with tracer.span("worker.update_eval", status=ev.status):
            self.server.state.upsert_evals([ev])
            self.server.on_eval_update(ev)

    def create_eval(self, ev: Evaluation) -> None:
        self.server.state.upsert_evals([ev])
        if ev.status == EVAL_STATUS_BLOCKED:
            self.server.blocked_evals.block(ev)
        elif ev.should_enqueue():
            self.server.broker.enqueue(ev)

    def reblock_eval(self, ev: Evaluation) -> None:
        self.server.blocked_evals.block(ev)


class Worker(threading.Thread):
    """(reference: worker.go:397 Worker.run)"""

    def __init__(self, server, worker_id: int,
                 schedulers: Optional[List[str]] = None):
        super().__init__(daemon=True, name=f"scheduler-worker-{worker_id}")
        self.server = server
        self.worker_id = worker_id
        self.schedulers = schedulers or ["service", "batch", "system",
                                         "sysbatch"]
        self._stop_ev = threading.Event()
        self.evals_processed = 0
        # progress heartbeat for the WorkerSupervisor's stall detector:
        # touched every loop iteration (idle dequeues included -- an
        # idle worker is not wedged), so only a thread hung inside
        # dequeue/invoke ages past NOMAD_TPU_WORKER_STALL_S
        self.last_progress = time.monotonic()

    def stop(self) -> None:
        self._stop_ev.set()

    def run(self) -> None:
        # One bad iteration (including a dequeue that raises -- see the
        # broker.dequeue fault point) must not silently kill the worker
        # thread and halt scheduling; same rationale as BatchWorker.run.
        while not self._stop_ev.is_set():
            self.last_progress = time.monotonic()
            try:
                ev, token = self.server.broker.dequeue(
                    self.schedulers, timeout=0.5)
            except Exception:
                import traceback
                traceback.print_exc()
                self._stop_ev.wait(0.5)
                continue
            if ev is None:
                continue
            # chaos: an armed worker.crash kills this thread HERE --
            # after the lease was minted, before any ack/nack path --
            # so the eval is orphaned exactly the way a real worker
            # death mid-eval orphans it
            _fire_crash_point()
            try:
                self._invoke_scheduler(ev, token)
                err = self.server.broker.ack(ev.id, token)
                tracer.end(ev.id, status="complete")
            except Exception as e:
                self.server.broker.nack(ev.id, token)
                tracer.end(ev.id, status="nacked",
                           error=f"{type(e).__name__}: {e}")
                from .logbroker import log as _log
                _log("error", "worker",
                     f"eval={ev.id} job={ev.job_id} scheduler invoke "
                     f"failed ({type(e).__name__}: {e}); nacked for "
                     "redelivery")
                if self.server.logger:
                    import traceback
                    traceback.print_exc()
            self.evals_processed += 1

    def _invoke_scheduler(self, ev: Evaluation, token: str) -> None:
        """(reference: worker.go:610 invokeScheduler). The snapshot must be
        at least as fresh as the eval's creation (snapshotMinIndex :591)."""
        invoke_scheduler(self.server, ev, token, worker_name=self.name)


def invoke_scheduler(server, ev: Evaluation, token: str,
                     solve_hook=None, sched_factory=None,
                     worker_name=None) -> None:
    """(reference: worker.go:610 invokeScheduler). ``sched_factory``
    overrides the factory entry used for service/batch evals -- the LPQ
    tier passes "tpu-lpq" so its evals construct through the scheduler
    factory boundary (scheduler/factory.py) like every other tier.
    ``worker_name`` identifies the owning POOL worker (not the per-eval
    thread) for the plan applier's cross-worker conflict accounting."""
    from ..faultinject import faults
    faults.fire("worker.invoke")    # chaos: raise -> nack -> requeue
    ctx = tracer.begin(ev.id, job=ev.job_id, lane=ev.type,
                       trigger=ev.triggered_by)
    with tracer.activate(ctx):
        with metrics.measure("nomad.worker.wait_for_index"), \
                tracer.span("worker.wait_for_index", ctx=ctx,
                            min_index=ev.modify_index - 1):
            server.state.block_until(ev.modify_index - 1, timeout=2.0)
        snapshot = server.state.snapshot()
        planner = WorkerPlanner(server, token, eval_id=ev.id,
                                worker_name=worker_name)
        planner.snapshot_index = snapshot.index
        sched_type = (ev.type if ev.type in
                      ("service", "batch", "system", "sysbatch")
                      else "service")
        kwargs = {}
        name = sched_type
        if sched_type in ("service", "batch"):
            if solve_hook is not None:
                kwargs["solve_hook"] = solve_hook
            if sched_factory is not None:
                name = sched_factory
                kwargs["batch"] = sched_type == "batch"
        sched = new_scheduler(name, snapshot, planner, **kwargs)
        from ..statecheck import eval_scope
        # the placing evals alone: a job's stop sends a quick
        # job-deregister eval through the timer by type as well, which
        # halves its mean
        placing = (metrics.measure("nomad.worker.invoke_register")
                   if ev.triggered_by == TRIGGER_JOB_REGISTER
                   else contextlib.nullcontext())
        with metrics.measure(
                f"nomad.worker.invoke_scheduler_{sched_type}"), placing, \
                tracer.span("worker.invoke", ctx=ctx, sched=sched_type), \
                eval_scope(snapshot):
            # snapshot-isolation sanitizer scope (statecheck.py, inert
            # no-op context when the checker is off): the eval's table
            # reads are grouped and attributed to this trace span
            sched.process(ev)


class BatchWorker(threading.Thread):
    """Eval-coalescing worker: dequeues up to `width` compatible evals and
    runs their schedulers concurrently, rendezvousing dense solves into ONE
    fused device dispatch (solver/batch.py SolveBarrier).

    This replaces the reference's one-eval-per-worker contract
    (nomad/worker.go:397 + scheduler/scheduler.go:59-68) with the
    TPU-native amortized form: per-eval semantics are unchanged (each eval
    runs the stock GenericScheduler against its own snapshot), only the
    device dispatch is shared. Cross-eval conflicts the barrier settles
    itself, before any plan is submitted, where it can see them: among
    the evals of its batch, and against what the server's other batch
    worker has delivered to its evals and the applier has not committed
    yet (``server.inflight``, server/inflight.py; every way out of an
    eval or a batch below releases what was booked for it). The
    serialized plan applier still re-checks every plan against live
    state and refuses what does not fit: conflicts with writers the
    barrier cannot see, and those of lanes the fixpoint cannot re-solve
    (solver/batch.py _cross_lane_fixpoint). With zero or one
    dense-eligible eval per batch and nothing in flight it degrades to
    exactly the old behavior."""

    def __init__(self, server, worker_id: int, width: int = 8,
                 schedulers: Optional[List[str]] = None,
                 use_mesh: bool = True):
        super().__init__(daemon=True, name=f"batch-worker-{worker_id}")
        self.server = server
        self.worker_id = worker_id
        self.width = max(1, width)
        self.schedulers = schedulers or ["service", "batch", "system",
                                         "sysbatch"]
        self.use_mesh = use_mesh
        self._stop_ev = threading.Event()
        self.evals_processed = 0
        self.batches_processed = 0
        self.barrier = None     # the batch in progress
        # supervisor progress heartbeat (see Worker.last_progress);
        # additionally touched per completed eval thread (_run_one), so
        # a long legitimate batch still shows progress
        self.last_progress = time.monotonic()

    def stop(self) -> None:
        self._stop_ev.set()

    def run(self) -> None:
        # This thread may be the server's only scheduling path: one bad
        # iteration must not silently halt all scheduling (same rationale
        # as Server._supervised for watcher threads).
        while not self._stop_ev.is_set():
            self.last_progress = time.monotonic()
            try:
                self._run_batch()
            except Exception:
                import traceback
                traceback.print_exc()
                self._stop_ev.wait(0.5)

    def _run_batch(self) -> None:
        from ..solver.batch import SolveBarrier, make_solve_hook
        from ..solver.lpq import lpq_active

        # second scheduler tier (ISSUE 8): when SchedulerConfiguration
        # picks tpu-lpq (and NOMAD_TPU_LPQ isn't killed), this worker
        # becomes the whole-queue coalescer instead; checked per batch
        # so runtime algorithm flips take effect without a restart
        if lpq_active(self.server.state):
            self._run_lpq_batch()
            return

        batch = self.server.broker.dequeue_batch(
            self.schedulers, self.width, timeout=0.5)
        if not batch:
            return
        # chaos: an armed worker.crash kills the whole BatchWorker here
        # -- every eval of the just-leased batch is orphaned at once
        # (the eval threads were never spawned, so no barrier is left
        # waiting on a dead participant)
        _fire_crash_point()
        metrics.sample("nomad.worker.batch_width", float(len(batch)))
        barrier = SolveBarrier(len(batch), use_mesh=self.use_mesh,
                               e_pad_hint=self.width,
                               plan_group_hint=getattr(
                                   self.server.planner, "expect_plans",
                                   None),
                               bookings=getattr(self.server, "inflight",
                                                None))
        # the supervisor retires it if this thread dies or is abandoned
        self.barrier = barrier
        try:
            hook = make_solve_hook(barrier)
            threads = [
                threading.Thread(
                    target=self._run_one, args=(ev, token, barrier, hook),
                    daemon=True, name=f"batch-eval-{ev.id[:8]}")
                for ev, token in batch]
            for t in threads:
                t.start()
            for t in threads:
                # bounded join (nomadlint join-with-timeout): an eval
                # thread wedged past the dispatch watchdog must surface
                # as a live diagnosable thread, not an invisible
                # infinite join
                while t.is_alive():
                    t.join(timeout=5.0)
        finally:
            barrier.retire()
        self.evals_processed += len(batch)
        self.batches_processed += 1

    def _run_lpq_batch(self) -> None:
        """One LP-queue generation: drain up to NOMAD_TPU_LPQ_BATCH
        compatible pending evals (broker.dequeue_lpq gathers briefly for
        a fuller batch), run each eval's scheduler on its own thread
        through the tpu-lpq factory entry, and rendezvous every dense
        solve into ONE whole-queue LP relaxation (solver/lpq.py)."""
        from ..solver.lpq import (
            LpqBarrier, lpq_batch_width, lpq_gather_s, make_lpq_hook,
        )

        batch = self.server.broker.dequeue_lpq(
            self.schedulers, lpq_batch_width(), timeout=0.5,
            gather_s=lpq_gather_s())
        if not batch:
            return
        # chaos: whole-batch worker death, as in _run_batch above
        _fire_crash_point()
        metrics.sample("nomad.worker.lpq_batch_width", float(len(batch)))
        barrier = LpqBarrier(len(batch),
                             plan_group_hint=getattr(
                                 self.server.planner, "expect_plans",
                                 None))
        hook = make_lpq_hook(barrier)
        threads = [
            threading.Thread(
                target=self._run_one,
                args=(ev, token, barrier, hook, "tpu-lpq"),
                daemon=True, name=f"lpq-eval-{ev.id[:8]}")
            for ev, token in batch]
        for t in threads:
            t.start()
        for t in threads:
            # bounded join (nomadlint join-with-timeout), as in
            # _run_batch above
            while t.is_alive():
                t.join(timeout=5.0)
        self.evals_processed += len(batch)
        self.batches_processed += 1

    def _run_one(self, ev: Evaluation, token: str, barrier, hook,
                 sched_factory=None) -> None:
        try:
            invoke_scheduler(self.server, ev, token, solve_hook=hook,
                             sched_factory=sched_factory,
                             worker_name=self.name)
            self.server.broker.ack(ev.id, token)
            tracer.end(ev.id, status="complete")
        except Exception as e:
            self.server.broker.nack(ev.id, token)
            tracer.end(ev.id, status="nacked",
                       error=f"{type(e).__name__}: {e}")
            from .logbroker import log as _log
            _log("error", "worker",
                 f"eval={ev.id} job={ev.job_id} batch-eval invoke "
                 f"failed ({type(e).__name__}: {e}); nacked for "
                 "redelivery")
            if self.server.logger:
                import traceback
                traceback.print_exc()
        finally:
            # whichever way the eval ended (acked with no plan, stale
            # lease, nack): nothing more of it will commit
            _release_bookings(self.server, ev.id)
            self.last_progress = time.monotonic()
            barrier.done()
