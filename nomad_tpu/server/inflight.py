"""Placements in flight between a solve barrier and the alloc table.

A barrier's cross-lane fixpoint (solver/batch.py) settles the conflicts
among the lanes it holds. Two batch workers run two barriers at once,
and what one has handed to its evals sits in the plan applier's queue
while the other packs, solves and runs its own fixpoint: the alloc
table a lane folds cannot contain it yet, so both pile onto the same
best nodes and the applier refuses the later plan its share (a whole
second round for a handful of placements). This registry is where a
barrier books what its fixpoint accepted, so that the other barrier's
fixpoint charges it too.

A booking is one (eval, node): cpu, memory, disk and dynamic ports.

  book     under ``fixpoint_lock``, once a generation's results are
           final and before its evals wake.
  settle   at the plan's commit, INSIDE the store's lock
           (``StateStore.plan_commit_hook``): the nodes the applier
           committed take the commit's index, the nodes it refused go.
           Inside the lock because a lane reads the alloc table's index
           under it: were the index attached once the submitting thread
           wakes, a lane that packed in between would hold the allocs
           in its usage and still see the booking unsettled, and count
           it twice.
  release  every exit that commits nothing: a plan refused whole, not
           submitted, a stale lease, a nack, a dead or abandoned worker,
           leadership lost.

Who subtracts what: a reader names its barrier (``foreign``) and the
alloc-table index its usage was folded at (``ForeignView.deduction``).
It is charged every booking of ANOTHER
barrier that is unsettled or settled above that index -- exactly what
its usage cannot contain. A settled booking is kept while a barrier
that was open before its commit is still open (``open_view``: the last
commit seen then is a floor under every index that barrier's lanes can
fold at) and dropped with the last of them.

Lock order: store lock -> ``_mu``; ``fixpoint_lock`` -> ``_mu``. Nothing
is taken under ``_mu``; ``fixpoint_lock`` is never taken under the store
lock nor held across a plan submission.
"""
from __future__ import annotations

import threading
from collections import deque
from typing import Deque, Dict, Iterable, List, Optional

from .telemetry import metrics


class _Booking:
    __slots__ = ("owner", "node_id", "need", "index")

    def __init__(self, owner, node_id: str, need):
        self.owner = owner
        self.node_id = node_id
        self.need = need            # (cpu, mem, disk, dynamic ports)
        self.index: Optional[int] = None    # commit index once settled


class ForeignView:
    """The other barriers' bookings as one barrier's fixpoint reads
    them, taken in one piece when its walk starts: {node id: [(need,
    commit index or None)]}. Nothing is booked meanwhile (the walk holds
    ``fixpoint_lock``); a booking that settles meanwhile settles above
    every index the walk's lanes folded at, so reads the same."""

    __slots__ = ("_by_node", "_at")

    def __init__(self, by_node: Dict[str, list]):
        self._by_node = by_node
        self._at: Dict[object, Dict[str, list]] = {}

    def deductions(self, usage_index) -> Dict[str, list]:
        """{node id: [cpu, mem, disk, dynamic ports]} of what is booked
        and a usage folded at ``usage_index`` cannot contain: unsettled,
        or settled above it. Worked out once an index (a generation's
        lanes fold at one or two)."""
        out = self._at.get(usage_index)
        if out is None:
            out = self._at[usage_index] = {}
            for node_id, recs in self._by_node.items():
                tot = None
                for need, index in recs:
                    if index is not None and index <= usage_index:
                        continue
                    if tot is None:
                        tot = out[node_id] = [0.0, 0.0, 0.0, 0]
                    for k in range(4):
                        tot[k] += need[k]
        return out

    def deduction(self, node_id: str, usage_index):
        """One node of ``deductions``; None when it holds nothing."""
        return self.deductions(usage_index).get(node_id)


class InflightBookings:
    """One per server; see the module docstring."""

    def __init__(self):
        # one fixpoint-and-book section at a time across the server's
        # barriers: charging against the other's bookings and booking
        # one's own must be atomic between them
        self.fixpoint_lock = threading.Lock()
        self._mu = threading.Lock()
        self._by_node: Dict[str, List[_Booking]] = {}
        self._unsettled: Dict[str, List[_Booking]] = {}
        self._settled: Deque[_Booking] = deque()     # in commit order
        self._floors: Dict[object, int] = {}         # open barrier -> floor
        self._count: Dict[object, int] = {}          # owner -> bookings held
        self._last_commit = 0

    # -- a barrier's life ------------------------------------------------
    def open_view(self, owner) -> None:
        """``owner`` (a barrier) starts: every lane it will hold packs
        later than now, so folds at or above the last commit seen."""
        with self._mu:
            self._floors[owner] = self._last_commit

    def retire(self, owner) -> None:
        """``owner`` is done (its batch ended, or its worker died or was
        abandoned): what it booked and no commit settled goes."""
        with self._mu:
            if self._floors.pop(owner, None) is None:
                return
            for eval_id in [e for e, recs in self._unsettled.items()
                            if recs[0].owner is owner]:
                for b in self._unsettled.pop(eval_id):
                    self._drop_locked(b)
            self._collect_locked()
            self._gauge_locked()

    def clear(self) -> None:
        """Leadership lost: nothing this server booked will commit."""
        with self._mu:
            self._by_node.clear()
            self._unsettled.clear()
            self._settled.clear()
            self._floors.clear()
            self._count.clear()
            self._gauge_locked()

    # -- book / settle / release ----------------------------------------
    def book(self, owner, eval_id: str, charges: Dict[str, list]) -> None:
        """``charges``: node id -> [cpu, mem, disk, dynamic ports] the
        fixpoint accepted for this eval's lane. Caller holds
        ``fixpoint_lock``. A retired owner books nothing (an abandoned
        worker's late generation)."""
        if not charges:
            return
        with self._mu:
            if owner not in self._floors:
                return
            recs = self._unsettled.setdefault(eval_id, [])
            for node_id, need in charges.items():
                b = _Booking(owner, node_id, tuple(need))
                recs.append(b)
                self._by_node.setdefault(node_id, []).append(b)
            self._count[owner] = self._count.get(owner, 0) + len(charges)
            self._gauge_locked()

    def settle(self, results: Iterable, index: int) -> None:
        """The store's plan-commit hook (store lock held): ``results``
        landed in the alloc table at ``index``."""
        with self._mu:
            self._last_commit = index
            if not self._unsettled:
                return
            settled = False
            for result in results:
                placed = result.node_allocation
                eval_id = next((allocs[0].eval_id
                                for allocs in placed.values() if allocs),
                               None)
                recs = self._unsettled.pop(eval_id, None)
                if not recs:
                    continue
                settled = True
                for b in recs:
                    if b.node_id in placed:
                        b.index = index
                        self._settled.append(b)
                    else:
                        self._drop_locked(b)
            if settled:     # the store's lock is held: no more than due
                self._collect_locked()
                self._gauge_locked()

    def release(self, eval_id: str) -> None:
        """Whatever ``eval_id`` has booked and no commit settled."""
        with self._mu:
            recs = self._unsettled.pop(eval_id, None)
            if not recs:
                return
            for b in recs:
                self._drop_locked(b)
            self._gauge_locked()

    # -- the fixpoint's reads -------------------------------------------
    def foreign(self, owner) -> Optional[ForeignView]:
        """What ``owner``'s fixpoint has to charge besides its own
        ledger; None while no other barrier holds a booking."""
        with self._mu:
            if sum(self._count.values()) == self._count.get(owner, 0):
                return None
            by_node = {}
            for node_id, recs in self._by_node.items():
                theirs = [(b.need, b.index) for b in recs
                          if b.owner is not owner]
                if theirs:
                    by_node[node_id] = theirs
        return ForeignView(by_node)

    def state(self) -> dict:
        """Operational snapshot (rides /v1/agent/self)."""
        with self._mu:
            return {"unsettled_evals": len(self._unsettled),
                    "settled_bookings": len(self._settled),
                    "booked_nodes": len(self._by_node),
                    "open_barriers": len(self._floors)}

    # -- internals (``_mu`` held) ---------------------------------------
    def _drop_locked(self, b: _Booking) -> None:
        recs = self._by_node.get(b.node_id)
        if recs is not None:
            recs.remove(b)
            if not recs:
                del self._by_node[b.node_id]
        n = self._count.get(b.owner, 0) - 1
        if n > 0:
            self._count[b.owner] = n
        else:
            self._count.pop(b.owner, None)

    def _collect_locked(self) -> None:
        """Drop settled bookings every open barrier's lanes contain."""
        floor = min(self._floors.values(), default=None)
        while self._settled and (floor is None
                                 or self._settled[0].index <= floor):
            self._drop_locked(self._settled.popleft())

    def _gauge_locked(self) -> None:
        metrics.sample("nomad.solver.inflight_bookings",
                       float(len(self._unsettled)))
