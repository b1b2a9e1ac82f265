"""Incrementally-maintained flat allocation table: the tensor-resident
half of the state store.

Every alloc write updates fixed-width numpy rows (node slot, cpu, mem,
disk, liveness, job/tg hashes, ports), so the TPU solver's marshalling
step is a single native fold over the table (nomad_tpu/native.py
nt_pack_usage) instead of an O(nodes x allocs) Python walk per eval --
the "packed int32 tensors" marshalling of the north star maintained
incrementally at write time.
"""
from __future__ import annotations

import hashlib
import threading
from functools import lru_cache
from typing import Dict, Optional

import numpy as np

from .. import native

MAX_PORTS = native.MAX_PORTS_PER_ALLOC


@lru_cache(maxsize=65536)
def stable_hash(*parts: str) -> int:
    # memoized: the key space is (namespace, job[, tg]) tuples -- small --
    # and a 2000-alloc plan commit was spending a third of its time
    # re-hashing the same job key per alloc
    h = hashlib.blake2b(digest_size=8)
    for p in parts:
        h.update(p.encode())
        h.update(b"\0")
    return int.from_bytes(h.digest(), "little")


class AllocTable:
    """Guarded by the owning StateStore's lock; all mutators are called
    with that lock held."""

    def __init__(self, initial_capacity: int = 1024):
        cap = initial_capacity
        self._row_of: Dict[str, int] = {}
        self._free: list = []
        # bumped on every mutation: packers cache fold results per
        # version (32 lanes of one barrier generation fold identically)
        self.version = 0
        self.n_rows = 0
        self._cap = cap
        self.node_slot = np.full(cap, -1, dtype=np.int32)
        self.cpu = np.zeros(cap, dtype=np.float64)
        self.mem = np.zeros(cap, dtype=np.float64)
        self.disk = np.zeros(cap, dtype=np.float64)
        self.live = np.zeros(cap, dtype=np.uint8)
        # live by the APPLIER's filter (terminal_status: desired
        # stop/evict OR client-terminal), vs `live` which is the
        # scheduler's filter (client-terminal or evicted, ProposedAllocs)
        self.live_strict = np.zeros(cap, dtype=np.uint8)
        # any ports/networks/reserved-cores/devices on the alloc: nodes
        # carrying such rows need the full python fit walk in the plan
        # applier (the native kernel models cpu/mem/disk only)
        self.special = np.zeros(cap, dtype=np.uint8)
        self.job_hash = np.zeros(cap, dtype=np.uint64)
        self.jobtg_hash = np.zeros(cap, dtype=np.uint64)
        self.ports = np.full((cap, MAX_PORTS), -1, dtype=np.int32)
        self.rows_with_ports = 0
        self._overflow_rows: set = set()
        # node axis
        self._slot_of_node: Dict[str, int] = {}
        self.n_nodes = 0
        self._node_cap = 256
        self.dyn_lo = np.full(self._node_cap, 20000, dtype=np.int32)
        self.dyn_hi = np.full(self._node_cap, 32000, dtype=np.int32)
        # incremental per-slot fold columns (built lazily on first use,
        # then adjusted by every upsert/remove, so sustained churn pays
        # O(write) not an O(rows) refold a version): uc/um/ud under the
        # scheduler's `live` filter (serves pack()'s non-port lanes),
        # vc/vm/vd/vspec under the applier's `live_strict` filter
        # (serves _fold_verify_all). vspec is a COUNT of live special
        # rows per slot (reversible, unlike the boolean OR).
        self._fold_inc: Optional[dict] = None

    # ------------------------------------------------------------------
    def register_node(self, node) -> int:
        self.version += 1    # dyn ranges/slots feed folds too
        slot = self._slot_of_node.get(node.id)
        if slot is None:
            if self.n_nodes == self._node_cap:
                grow = self._node_cap
                self._node_cap *= 2
                self.dyn_lo = np.resize(self.dyn_lo, self._node_cap)
                self.dyn_hi = np.resize(self.dyn_hi, self._node_cap)
                inc = self._fold_inc
                if inc is not None:
                    # new slots carry zero usage by definition
                    for k, arr in inc.items():
                        inc[k] = np.concatenate(
                            [arr, np.zeros(grow, dtype=arr.dtype)])
            slot = self.n_nodes
            self._slot_of_node[node.id] = slot
            self.n_nodes += 1
        self.dyn_lo[slot] = node.node_resources.min_dynamic_port
        self.dyn_hi[slot] = node.node_resources.max_dynamic_port
        return slot

    # -- incremental fold maintenance ------------------------------------
    def _fold_inc_build(self) -> dict:
        """Full recount into the per-slot incremental fold columns; the
        ground truth every delta adjustment must stay equal to
        (fold_parity_mismatch gates that in tests and the churn bench)."""
        cap = self._node_cap
        inc = {
            "uc": np.zeros(cap), "um": np.zeros(cap), "ud": np.zeros(cap),
            "vc": np.zeros(cap), "vm": np.zeros(cap), "vd": np.zeros(cap),
            "vspec": np.zeros(cap, dtype=np.int64),
        }
        n = self.n_rows
        if n:
            slots = self.node_slot[:n]
            ok = slots >= 0
            live = (self.live[:n] > 0) & ok
            m = slots[live]
            np.add.at(inc["uc"], m, self.cpu[:n][live])
            np.add.at(inc["um"], m, self.mem[:n][live])
            np.add.at(inc["ud"], m, self.disk[:n][live])
            lives = (self.live_strict[:n] > 0) & ok
            ms = slots[lives]
            np.add.at(inc["vc"], ms, self.cpu[:n][lives])
            np.add.at(inc["vm"], ms, self.mem[:n][lives])
            np.add.at(inc["vd"], ms, self.disk[:n][lives])
            np.add.at(inc["vspec"],
                      slots[lives & (self.special[:n] > 0)], 1)
        self._fold_inc = inc
        return inc

    def _fold_inc_get(self) -> dict:
        inc = self._fold_inc
        if inc is None:
            inc = self._fold_inc_build()
        return inc

    def _fold_inc_row(self, row: int, sign: int) -> None:
        """Adjust the incremental fold by one row's CURRENT column values
        (sign -1 before overwriting/removing a row, +1 after writing)."""
        inc = self._fold_inc
        slot = int(self.node_slot[row])
        if slot < 0:
            return
        c, m, d = self.cpu[row], self.mem[row], self.disk[row]
        if self.live[row]:
            inc["uc"][slot] += sign * c
            inc["um"][slot] += sign * m
            inc["ud"][slot] += sign * d
        if self.live_strict[row]:
            inc["vc"][slot] += sign * c
            inc["vm"][slot] += sign * m
            inc["vd"][slot] += sign * d
            if self.special[row]:
                inc["vspec"][slot] += sign

    def _fold_inc_rows(self, rows: np.ndarray, sign: int) -> None:
        """Vectorized _fold_inc_row over a row-index array."""
        inc = self._fold_inc
        if inc is None or not len(rows):
            return
        slots = self.node_slot[rows]
        ok = slots >= 0
        r, s = rows[ok], slots[ok]
        if not len(r):
            return
        live = self.live[r] > 0
        np.add.at(inc["uc"], s[live], sign * self.cpu[r][live])
        np.add.at(inc["um"], s[live], sign * self.mem[r][live])
        np.add.at(inc["ud"], s[live], sign * self.disk[r][live])
        lives = self.live_strict[r] > 0
        np.add.at(inc["vc"], s[lives], sign * self.cpu[r][lives])
        np.add.at(inc["vm"], s[lives], sign * self.mem[r][lives])
        np.add.at(inc["vd"], s[lives], sign * self.disk[r][lives])
        spec = lives & (self.special[r] > 0)
        np.add.at(inc["vspec"], s[spec], sign)

    def fold_parity_mismatch(self, atol: float = 1e-6) -> int:
        """Parity gate for the delta path: compare the incrementally
        maintained fold against a fresh full recount; returns the number
        of mismatching slots (0 = parity). The fresh recount replaces
        the resident fold, so a detected drift also self-heals."""
        saved = self._fold_inc
        if saved is None:
            return 0
        fresh = self._fold_inc_build()      # re-assigns self._fold_inc
        n = self.n_nodes
        bad = np.zeros(n, dtype=bool)
        for k in ("uc", "um", "ud", "vc", "vm", "vd"):
            bad |= np.abs(saved[k][:n] - fresh[k][:n]) > atol
        bad |= (saved["vspec"][:n] > 0) != (fresh["vspec"][:n] > 0)
        return int(bad.sum())

    def node_slot_of(self, node_id: str) -> int:
        return self._slot_of_node.get(node_id, -1)

    def usage_by_node(self) -> Dict[str, tuple]:
        """Per-node-id (used_cpu, used_mem, used_disk) under the
        scheduler's `live` filter, served from the incremental fold
        columns (built on demand).  Caller holds the owning store's
        lock."""
        inc = self._fold_inc_get()
        out = {}
        for nid, slot in self._slot_of_node.items():
            out[nid] = (float(inc["uc"][slot]), float(inc["um"][slot]),
                        float(inc["ud"][slot]))
        return out

    # ------------------------------------------------------------------
    def preallocate(self, capacity: int) -> None:
        """Grow the row arrays to ``capacity`` in ONE resize. A 2M-alloc
        run otherwise pays ~11 doubling copies of every column (the ports
        matrix alone is capacity x MAX_PORTS int32) while holding the
        store lock."""
        while self._cap < capacity:
            self._grow()

    def _grow(self) -> None:
        self._cap *= 2
        for name in ("node_slot", "cpu", "mem", "disk", "live",
                     "live_strict", "special",
                     "job_hash", "jobtg_hash"):
            arr = getattr(self, name)
            setattr(self, name, np.resize(arr, self._cap))
        new_ports = np.full((self._cap, MAX_PORTS), -1, dtype=np.int32)
        new_ports[:self.ports.shape[0]] = self.ports
        self.ports = new_ports

    def upsert(self, alloc) -> None:
        self.version += 1
        row = self._row_of.get(alloc.id)
        existed = row is not None
        if row is None:
            if self._free:
                row = self._free.pop()
            else:
                if self.n_rows == self._cap:
                    self._grow()
                row = self.n_rows
                self.n_rows += 1
            self._row_of[alloc.id] = row
        if existed and self._fold_inc is not None:
            # retract the row's old contribution before overwriting
            # (fresh/freed rows contribute nothing: remove() zeroes them)
            self._fold_inc_row(row, -1)
        cr = alloc.allocated_resources.comparable()
        self.node_slot[row] = self._slot_of_node.get(alloc.node_id, -1)
        self.cpu[row] = cr.cpu_shares
        self.mem[row] = cr.memory_mb
        self.disk[row] = cr.disk_mb
        self.live[row] = 0 if alloc.released_from_node() else 1
        self.live_strict[row] = 0 if alloc.terminal_status() else 1
        self.special[row] = \
            1 if alloc.allocated_resources.has_special_dimensions() else 0
        self.job_hash[row] = stable_hash(alloc.namespace, alloc.job_id)
        self.jobtg_hash[row] = stable_hash(alloc.namespace, alloc.job_id,
                                           alloc.task_group)
        if self._fold_inc is not None:
            self._fold_inc_row(row, +1)
        had_ports = self.ports[row, 0] >= 0
        had_overflow = row in self._overflow_rows
        self.ports[row, :] = -1
        ports = alloc.allocated_resources.all_ports()
        for pi, value in enumerate(ports[:MAX_PORTS]):
            self.ports[row, pi] = value
        if len(ports) > MAX_PORTS:
            # row can't represent all ports: the solver service must fall
            # back to the exact per-node fold while any overflow exists
            self._overflow_rows.add(row)
        elif had_overflow:
            self._overflow_rows.discard(row)
        has_ports = bool(ports)
        if has_ports and not had_ports:
            self.rows_with_ports += 1
        elif had_ports and not has_ports:
            self.rows_with_ports -= 1

    def upsert_many(self, allocs) -> None:
        """Batch upsert: the per-alloc path pays ~15 scalar numpy writes
        each (~10us/alloc -- ~20ms per 2000-alloc plan commit under the
        store lock); batching turns the columns into one vectorized
        assignment apiece. Falls back to the scalar path when a batch
        repeats an alloc id (fancy-index write order would be
        unspecified) -- plans never do, but correctness must not depend
        on it."""
        if len(allocs) < 8:
            for a in allocs:
                self.upsert(a)
            return
        ids = [a.id for a in allocs]
        if len(set(ids)) != len(ids):
            for a in allocs:
                self.upsert(a)
            return
        # derive EVERYTHING before the first state mutation: a raising
        # alloc mid-batch must not leave reserved-but-unwritten rows
        # (stale resized data would fold phantom usage)
        # batches routinely share AllocatedResources objects across
        # allocs of one task group (prebuilt TPU-path resources), so
        # memoize the derived views by object identity -- the `allocs`
        # list pins every object alive for the memo's whole lifetime
        _derived: dict = {}
        crs = []
        all_ports = []
        special = []
        for a in allocs:
            ar = a.allocated_resources
            got = _derived.get(id(ar))
            if got is None:
                got = (ar.comparable(), ar.all_ports(),
                       1 if ar.has_special_dimensions() else 0)
                _derived[id(ar)] = got
            crs.append(got[0])
            all_ports.append(got[1])
            special.append(got[2])
        live = [0 if a.released_from_node() else 1 for a in allocs]
        live_strict = [0 if a.terminal_status() else 1 for a in allocs]
        job_hash = [stable_hash(a.namespace, a.job_id) for a in allocs]
        jobtg_hash = [stable_hash(a.namespace, a.job_id, a.task_group)
                      for a in allocs]
        self.version += 1
        n_new = sum(1 for i in ids if i not in self._row_of)
        while self.n_rows + n_new - len(self._free) > self._cap:
            self._grow()
        rows = np.empty(len(allocs), dtype=np.int64)
        existed = np.zeros(len(allocs), dtype=bool)
        for k, a in enumerate(allocs):
            row = self._row_of.get(a.id)
            if row is None:
                if self._free:
                    row = self._free.pop()
                else:
                    row = self.n_rows
                    self.n_rows += 1
                self._row_of[a.id] = row
            else:
                existed[k] = True
            rows[k] = row
        if self._fold_inc is not None:
            # retract reused rows' old contributions (fresh/freed rows
            # contribute nothing -- and fresh rows past the old n_rows
            # hold resize garbage, so they MUST be skipped here)
            self._fold_inc_rows(rows[existed], -1)
        slot_of = self._slot_of_node
        self.node_slot[rows] = [slot_of.get(a.node_id, -1)
                                for a in allocs]
        self.cpu[rows] = [cr.cpu_shares for cr in crs]
        self.mem[rows] = [cr.memory_mb for cr in crs]
        self.disk[rows] = [cr.disk_mb for cr in crs]
        self.live[rows] = live
        self.live_strict[rows] = live_strict
        self.special[rows] = special
        self.job_hash[rows] = job_hash
        self.jobtg_hash[rows] = jobtg_hash
        if self._fold_inc is not None:
            self._fold_inc_rows(rows, +1)
        # ports: reused rows (freed or replaced) may hold stale port
        # values -- the scalar path resets every upserted row, so the
        # batch must too (vectorized), BEFORE which the accounting
        # baseline is captured
        had_ports_arr = self.ports[rows, 0] >= 0
        self.ports[rows, :] = -1
        if not any(all_ports) and not self._overflow_rows:
            # no new ports, nothing overflowed: rows that had ports
            # simply lose them
            self.rows_with_ports -= int(had_ports_arr.sum())
        else:
            for k, ports in enumerate(all_ports):
                row = int(rows[k])
                had_overflow = row in self._overflow_rows
                for pi, value in enumerate(ports[:MAX_PORTS]):
                    self.ports[row, pi] = value
                if len(ports) > MAX_PORTS:
                    self._overflow_rows.add(row)
                elif had_overflow:
                    self._overflow_rows.discard(row)
                has_ports = bool(ports)
                had = bool(had_ports_arr[k])
                if has_ports and not had:
                    self.rows_with_ports += 1
                elif had and not has_ports:
                    self.rows_with_ports -= 1

    @property
    def has_port_overflow(self) -> bool:
        return bool(self._overflow_rows)

    def remove(self, alloc_id: str) -> None:
        row = self._row_of.pop(alloc_id, None)
        if row is None:
            return
        self.version += 1
        if self._fold_inc is not None:
            self._fold_inc_row(row, -1)
        if self.ports[row, 0] >= 0:
            self.rows_with_ports -= 1
        self._overflow_rows.discard(row)
        self.live[row] = 0
        self.live_strict[row] = 0
        self.special[row] = 0
        self.node_slot[row] = -1
        self.ports[row, :] = -1
        self._free.append(row)

    # ------------------------------------------------------------------
    def pack(self, n_pad: int, node_slots_for_pad: np.ndarray,
             with_ports: bool, port_words_seed: Optional[np.ndarray] = None):
        """Fold the table into node-axis tensors aligned to the caller's
        node ordering. node_slots_for_pad[i] = table slot of the node at
        position i (or -1). Returns dict of arrays (position-indexed)."""
        n = self.n_rows
        # remap table node slots -> caller positions (vectorized; the
        # Python per-position loop ran under the store lock per lane pack)
        remap = np.full(self.n_nodes + 1, -1, dtype=np.int32)
        valid_pad = node_slots_for_pad >= 0
        remap[node_slots_for_pad[valid_pad]] = \
            np.nonzero(valid_pad)[0].astype(np.int32)
        row_slots = self.node_slot[:n]
        mapped = np.where(row_slots >= 0, remap[np.maximum(row_slots, 0)], -1)

        dyn_lo_pos = np.full(n_pad, 20000, dtype=np.int32)
        dyn_hi_pos = np.full(n_pad, 32000, dtype=np.int32)
        valid = node_slots_for_pad >= 0
        dyn_lo_pos[valid] = self.dyn_lo[node_slots_for_pad[valid]]
        dyn_hi_pos[valid] = self.dyn_hi[node_slots_for_pad[valid]]

        # Port state only matters when the asking TG has networks; skip the
        # (potentially 80MB) bitmap fold entirely otherwise.
        use_ports = with_ports and (self.rows_with_ports > 0
                                    or port_words_seed is not None)
        if not use_ports:
            inc = self._fold_inc_get()
            # incremental path: gather the resident per-slot fold into the
            # caller's node ordering -- O(nodes) per pack instead of the
            # O(rows) native fold per table version (what sustained churn
            # defeats). Portless lanes see exactly what native.pack_usage
            # returns with ports=None: zero dyn_used, no bitmap.
            used_cpu = np.zeros(n_pad, dtype=np.float64)
            used_mem = np.zeros(n_pad, dtype=np.float64)
            used_disk = np.zeros(n_pad, dtype=np.float64)
            sel = node_slots_for_pad[valid]
            used_cpu[valid] = inc["uc"][sel]
            used_mem[valid] = inc["um"][sel]
            used_disk[valid] = inc["ud"][sel]
            return {"used_cpu": used_cpu, "used_mem": used_mem,
                    "used_disk": used_disk,
                    "dyn_used": np.zeros(n_pad, dtype=np.int32),
                    "port_words": None, "row_slots": mapped}
        used_cpu, used_mem, used_disk, dyn_used, port_words = \
            native.pack_usage(
                mapped.astype(np.int32), self.cpu[:n], self.mem[:n],
                self.disk[:n], self.live[:n],
                self.ports[:n], dyn_lo_pos, dyn_hi_pos, n_pad,
                port_words_seed=port_words_seed)
        return {"used_cpu": used_cpu, "used_mem": used_mem,
                "used_disk": used_disk, "dyn_used": dyn_used,
                "port_words": port_words, "row_slots": mapped}

    def _fold_verify_all(self):
        """Per-SLOT (used_cpu, used_mem, used_disk, special_any) under the
        applier's live_strict filter, served straight from the
        incrementally-maintained columns: no refold on a version change,
        so the group-commit applier's whole batch of plans between two
        commits reads one resident fold."""
        inc = self._fold_inc_get()
        n = self.n_nodes
        return (inc["vc"][:n], inc["vm"][:n], inc["vd"][:n],
                inc["vspec"][:n] > 0)

    def fold_verify(self, node_ids):
        """Per-node (used_cpu, used_mem, used_disk, special_any, found)
        under the APPLIER's liveness filter (live_strict: excludes
        server-terminal too, matching AllocsByNodeTerminal(false) in
        plan_apply.go) for the plan verifier's native pre-pass. Caller
        must hold the owning store's lock (a half-committed plan would
        tear the fold). ``found[k]`` False = node unknown to the table
        (no allocs ever) -- usage is zero there. Returns fresh arrays
        (callers mutate them in place while adjusting plan deltas)."""
        npos = len(node_ids)
        slots = np.fromiter(
            (self._slot_of_node.get(i, -1) for i in node_ids),
            dtype=np.int32, count=npos)
        found = slots >= 0
        base_c, base_m, base_d, base_s = self._fold_verify_all()
        if not base_c.shape[0]:
            return (np.zeros(npos), np.zeros(npos), np.zeros(npos),
                    np.zeros(npos, dtype=bool), found)
        idx = np.where(found, slots, 0)
        used_c = np.where(found, base_c[idx], 0.0)
        used_m = np.where(found, base_m[idx], 0.0)
        used_d = np.where(found, base_d[idx], 0.0)
        spec_any = found & base_s[idx]
        return used_c, used_m, used_d, spec_any, found

    # ------------------------------------------------------------------
    def compact(self) -> dict:
        """Rebuild row storage densely: surviving allocs are repacked
        into rows [0, k), freed rows vanish, and capacity shrinks to the
        smallest power-of-two bucket holding the survivors -- the memory
        actually returns (the ports matrix alone is cap x MAX_PORTS
        int32). Called by the core-gc loop via
        StateStore.compact_alloc_table once the free-row count crosses
        the watermark; caller holds the owning store's lock."""
        items = sorted(self._row_of.items(), key=lambda kv: kv[1])
        k = len(items)
        src = np.fromiter((r for _, r in items), dtype=np.int64, count=k)
        old_rows, old_cap = self.n_rows, self._cap
        new_cap = 1024
        while new_cap < k:
            new_cap *= 2
        for name, fill in (("node_slot", -1), ("cpu", 0), ("mem", 0),
                           ("disk", 0), ("live", 0), ("live_strict", 0),
                           ("special", 0), ("job_hash", 0),
                           ("jobtg_hash", 0)):
            old = getattr(self, name)
            arr = np.full(new_cap, fill, dtype=old.dtype)
            arr[:k] = old[src]
            setattr(self, name, arr)
        ports = np.full((new_cap, MAX_PORTS), -1, dtype=np.int32)
        ports[:k] = self.ports[src]
        self.ports = ports
        row_map = {int(old): i for i, old in enumerate(src)}
        self._overflow_rows = {row_map[r] for r in self._overflow_rows
                               if r in row_map}
        self._row_of = {aid: i for i, (aid, _) in enumerate(items)}
        self.rows_with_ports = int((self.ports[:k, 0] >= 0).sum()) if k \
            else 0
        self._free = []
        self.n_rows = k
        self._cap = new_cap
        self.version += 1
        self._fold_inc = None       # lazily rebuilt from the dense rows
        return {"rows_before": old_rows, "rows_after": k,
                "cap_before": old_cap, "cap_after": new_cap}

    @property
    def free_rows(self) -> int:
        return len(self._free)

    def count_placed(self, n_pad: int, mapped_slots: np.ndarray,
                     namespace: str, job_id: str, tg_name: str):
        n = self.n_rows
        return native.count_placed(
            mapped_slots.astype(np.int32), self.job_hash[:n],
            self.jobtg_hash[:n], self.live[:n],
            stable_hash(namespace, job_id),
            stable_hash(namespace, job_id, tg_name), n_pad)
