"""Who waits for which write: the store's blocking queries, off the
store's lock.

The reference wakes a blocking query through a memdb watch set on the
items the query read (nomad/rpc.go blockingRPC): a write to another
job does not wake it. This is that registry. It has a lock of its own,
never the store's: the lock order is store -> watch (a writer
publishes from inside ``StateStore._bump``), and a waiter holds only
the watch lock, so a woken waiter never stands in the writers' line.

Three kinds of waiter, by what ``wait`` is given:

  keys    wait for a write to one of these items -- ``("job", ns, id)``
          or ``("node", id)``. The registry keeps the index of the last
          write per key, so a write that lands between a client's reply
          and its next request is never lost: the wait returns at once
          when that index is past the client's. A key it does not know
          (never written since the last restore, or dropped with its
          job or node) answers from ``_floor``, the index of the last
          drop or restore, which is at or past that key's last write:
          early, never late.
  tables  wait for a write to one of these tables.
  neither wait until the store's index passes N, whatever table
          (the worker's wait_for_index): woken when it does, not on
          every write.

A waiter registers before its last check, both under the watch lock,
and the publisher updates what is checked under the same lock: a write
between the check and the wait still wakes it.

Counters (plain ints under the watch lock; a waiter hands what has
accumulated to telemetry after it let the lock go, a writer never
does): ``nomad.state.watch_waits`` waits that parked,
``nomad.state.watch_wakes`` waiters woken by a write,
``nomad.state.watch_wakes_spurious`` woken and what they watch had not
changed.
"""
from __future__ import annotations

import threading
from time import monotonic
from typing import Dict, Iterable, Optional, Tuple

Key = Tuple[str, ...]


class _Waiter:
    __slots__ = ("cond", "min_index", "tables", "keys", "woken")

    def __init__(self, cond, min_index: int, tables, keys):
        self.cond = cond
        self.min_index = min_index
        self.tables = tables
        self.keys = keys
        self.woken = False


class WatchRegistry:
    def __init__(self, index: int, tables: Iterable[str]):
        # constructed through threading.* so that lockcheck and
        # schedcheck instrument it like any other lock of the repo
        self._lock = threading.Lock()
        self._index = index
        self._table_index: Dict[str, int] = {t: index for t in tables}
        self._key_index: Dict[Key, int] = {}
        self._floor = index
        self._by_key: Dict[Key, list] = {}
        self._others: list = []         # table and index waiters
        self._n_parked = 0
        self.waits = 0
        self.wakes = 0
        self.spurious = 0
        self._flushed = (0, 0, 0)

    # -- the writer's side (store lock held) ----------------------------
    def publish(self, index: int, tables: Iterable[str],
                keys: Optional[Iterable[Key]],
                dropped: Iterable[Key] = ()) -> None:
        """A write at ``index`` touched ``tables`` and the items
        ``keys``; ``keys`` None: the writer cannot say which (a
        restore), so every key is forgotten and everyone is woken.
        ``dropped`` keys went with their job or node."""
        with self._lock:
            self._index = index
            for t in tables:
                self._table_index[t] = index
            if keys is None:
                self._key_index.clear()
                self._floor = index
                for waiters in self._by_key.values():
                    for w in waiters:
                        self._wake(w)
            else:
                for k in keys:
                    self._key_index[k] = index
                    for w in self._by_key.get(k, ()):
                        self._wake(w)
                for k in dropped:
                    if self._key_index.pop(k, None) is not None:
                        self._floor = index
            for w in self._others:
                if keys is None or (
                        any(t in tables for t in w.tables) if w.tables
                        else index > w.min_index):
                    self._wake(w)

    def _wake(self, w: _Waiter) -> None:
        if not w.woken:
            w.woken = True
            self.wakes += 1
            w.cond.notify()

    # -- the reader's side (no store lock) --------------------------------
    def _last_write(self, tables, keys) -> int:
        """Index of the last write to what a waiter watches (watch
        lock held)."""
        if keys:
            return max(self._key_index.get(k, self._floor) for k in keys)
        if tables:
            return max(self._table_index.get(t, 0) for t in tables)
        return self._index

    def wait(self, min_index: int, timeout: float,
             tables: Tuple[str, ...] = (),
             keys: Tuple[Key, ...] = ()) -> None:
        """Return when a write past ``min_index`` touched what is
        watched, or when ``timeout`` seconds have gone."""
        deadline = monotonic() + timeout
        with self._lock:
            if self._last_write(tables, keys) > min_index:
                return
            w = _Waiter(threading.Condition(self._lock), min_index,
                        tables, keys)
            self._register(w)
            self.waits += 1
            try:
                while True:
                    # registered, then checked, under one hold of the
                    # watch lock: no write slips between the two
                    if self._last_write(tables, keys) > min_index:
                        break
                    if w.woken:
                        self.spurious += 1
                        w.woken = False
                    remaining = deadline - monotonic()
                    if remaining <= 0:
                        break
                    w.cond.wait(remaining)
            finally:
                self._unregister(w)
            owed = self._owed()
        self._flush(owed)

    def _register(self, w: _Waiter) -> None:
        self._n_parked += 1
        if w.keys:
            for k in w.keys:
                self._by_key.setdefault(k, []).append(w)
        else:
            self._others.append(w)

    def _unregister(self, w: _Waiter) -> None:
        self._n_parked -= 1
        if w.keys:
            for k in w.keys:
                waiters = self._by_key[k]
                waiters.remove(w)
                if not waiters:
                    del self._by_key[k]
        else:
            self._others.remove(w)

    # -- the account --------------------------------------------------------
    def _owed(self):
        now = (self.waits, self.wakes, self.spurious)
        owed = tuple(a - b for a, b in zip(now, self._flushed))
        self._flushed = now
        return owed

    @staticmethod
    def _flush(owed) -> None:
        from ..server.telemetry import metrics
        waits, wakes, spurious = owed
        if waits:
            metrics.incr("nomad.state.watch_waits", waits)
        if wakes:
            metrics.incr("nomad.state.watch_wakes", wakes)
        if spurious:
            metrics.incr("nomad.state.watch_wakes_spurious", spurious)

    def parked(self) -> int:
        """Waiters parked right now (tests, /v1/agent/self)."""
        return self._n_parked
