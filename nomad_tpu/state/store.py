"""MVCC state store with index-watch blocking queries.

Semantic parity with /root/reference/nomad/state/state_store.go over
go-memdb: every write bumps a monotone raft-style index, reads run against
cheap snapshots (copy-on-write dict views -- objects are replaced on write,
never mutated in place, which is what makes snapshots safe to share with
concurrently-running scheduler workers, mirroring the immutable-radix
guarantee), and watchers block until a table index advances
(reference: nomad/rpc.go:852 blockingRPC + go-memdb WatchSet).
"""
from __future__ import annotations

from typing import Callable, Dict, List, Optional, Tuple

from .. import schedcheck
from .alloc_table import AllocTable
from .storelock import make_store_lock
from .watch import WatchRegistry
from ..structs import (
    ACL_TOKEN_TYPE_MANAGEMENT, ACLPolicy, ACLToken, Allocation, Deployment,
    Evaluation, Job, Namespace, Node, NodePool, Plan, PlanResult, RootKey,
    ScalingEvent, ScalingPolicy, SchedulerConfiguration, VariableEncrypted,
    ALLOC_DESIRED_STOP, ALLOC_CLIENT_FAILED, ALLOC_CLIENT_LOST,
    ALLOC_CLIENT_COMPLETE,
    EVAL_STATUS_BLOCKED, JOB_STATUS_DEAD, JOB_STATUS_PENDING,
    JOB_STATUS_RUNNING, NODE_STATUS_DOWN,
)

TABLES = ("nodes", "jobs", "evals", "allocs", "deployments", "node_pools",
          "scheduler_config", "job_versions", "acl_policies", "acl_tokens",
          "acl_roles", "root_keys", "variables", "scaling_policies",
          "scaling_events",
          "namespaces", "csi_volumes", "csi_plugins", "services")


def _delta_journal_cap() -> int:
    """Alloc-delta journal capacity (NOMAD_TPU_DELTA_JOURNAL, default
    128 entries = the ISSUE-6 fixed bound).  One entry per alloc-table
    write: a group-committed LP batch is ONE entry regardless of pair
    count, but high write fan-out (serial applier, client updates)
    wraps the journal and forces incremental-memo holders into
    wholesale rebuilds -- watch nomad.state.delta_journal_overflow."""
    import os
    try:
        return max(8, int(os.environ.get("NOMAD_TPU_DELTA_JOURNAL",
                                         "128")))
    except ValueError:
        return 128


def _eval_keys(evals) -> list:
    """Watch keys of the jobs these evals belong to."""
    return [("job", ev.namespace, ev.job_id) for ev in evals]


def _deployment_keys(deployments) -> list:
    return [("job", d.namespace, d.job_id) for d in deployments
            if d is not None]


def _unindex(index: dict, gone) -> None:
    """Take (key, id) pairs out of a copy-on-write secondary index: one
    new tuple per touched key, the key itself when nothing is left."""
    by_key: Dict[object, set] = {}
    for k, i in gone:
        by_key.setdefault(k, set()).add(i)
    for k, ids in by_key.items():
        left = tuple(i for i in index.get(k, ()) if i not in ids)
        if left:
            index[k] = left
        else:
            index.pop(k, None)


class _DeltaAllocs:
    """Journal-patched snapshot alloc mapping (ISSUE 17): the previous
    snapshot's mapping advanced copy-on-write by the alloc-delta journal
    span, instead of rebuilt with a wholesale ``dict(store._allocs)``
    copy (~250K dict inserts per snapshot at north-star scale).

    ``base`` is a frozen plain dict shared with an earlier snapshot and
    is NEVER mutated; ``over`` holds inserted/replaced allocs; ``dead``
    tombstones ids deleted from base. Each advance copies the (bounded
    small) overlay, so chains never deepen past one level, and the store
    flattens back to a plain dict when the overlay outgrows its budget
    (StateStore._snapshot_allocs_locked). Iteration yields base order
    first, then overlay order -- replaced allocs move to the tail, which
    the snapshot read API tolerates (id-keyed lookups and unordered
    scans); the kill-switch path keeps exact dict-copy order."""

    __slots__ = ("_base", "_over", "_dead")

    def __init__(self, base: dict, over: dict, dead: set):
        self._base = base
        self._over = over
        self._dead = dead

    def get(self, key, default=None):
        v = self._over.get(key)
        if v is not None:
            return v
        if key in self._dead:
            return default
        return self._base.get(key, default)

    def __getitem__(self, key):
        v = self.get(key)
        if v is None:
            raise KeyError(key)
        return v

    def __contains__(self, key) -> bool:
        return (key in self._over
                or (key not in self._dead and key in self._base))

    def __len__(self) -> int:
        n = len(self._base) - len(self._dead)
        for k in self._over:
            if k in self._base:
                n -= 1
        return n + len(self._over)

    def __iter__(self):
        base, over, dead = self._base, self._over, self._dead
        for k in base:
            if k not in dead and k not in over:
                yield k
        yield from over

    def keys(self):
        return list(self)

    def values(self):
        base, over, dead = self._base, self._over, self._dead
        out = [v for k, v in base.items()
               if k not in dead and k not in over]
        out.extend(over.values())
        return out

    def items(self):
        base, over, dead = self._base, self._over, self._dead
        out = [(k, v) for k, v in base.items()
               if k not in dead and k not in over]
        out.extend(over.items())
        return out


class StateSnapshot:
    """An immutable point-in-time view (reference: state.StateSnapshot).

    Shares object references with the live store; safe because writes
    replace objects instead of mutating them.
    """

    def __init__(self, store: "StateStore"):
        with store._lock:
            self.index = store._index
            # node-table version: cache key for tensorized fleet tables
            # (tensor/pack.py pack_nodes_cached)
            self.node_table_index = store._table_index.get("nodes", 0)
            self._nodes = dict(store._nodes)
            self._jobs = dict(store._jobs)
            self._evals = dict(store._evals)
            self._allocs = store._snapshot_allocs_locked()
            self._deployments = dict(store._deployments)
            self._node_pools = dict(store._node_pools)
            self._scheduler_config = store._scheduler_config
            # live reference: the dense solver's fast packing path may
            # observe usage newer than this snapshot; safe because the
            # plan applier re-verifies every plan against latest state
            self.alloc_table = store.alloc_table
            self._store = store
            # secondary indexes: the store publishes an immutable id
            # tuple per key (copy-on-write), so a snapshot shares them
            # and copies only the outer dicts
            self._allocs_by_node = dict(store._allocs_by_node)
            self._allocs_by_job = dict(store._allocs_by_job)
            self._csi_volumes = dict(store._csi_volumes)
            self._csi_plugins = dict(store._csi_plugins)

    # -- read API shared with the live store ---------------------------------
    def latest_index(self) -> int:
        return self.index

    def node_by_id(self, node_id: str) -> Optional[Node]:
        return self._nodes.get(node_id)

    def nodes(self) -> List[Node]:
        return list(self._nodes.values())

    def ready_nodes_in_pool(self, pool: str = "all") -> List[Node]:
        """(reference: state_store.go ReadyNodesInDC / node pool
        filtering). Memoized per snapshot: the O(N) ready scan ran once
        per EVAL (a measured ~8ms/eval fixed cost at 10K nodes) while
        every eval of a barrier generation shares one snapshot. The
        memo also keeps the node-id tuple so pack_nodes_cached can key
        its matrix cache without rebuilding it per eval
        (nodes_pack_key)."""
        return self._ready_memoized(("pool", pool))[0]

    def _ready_memoized(self, key):
        memo = self.__dict__.setdefault("_ready_memo", {})
        ent = memo.get(key)
        if ent is None:
            kind = key[0]
            if kind == "pool":
                pool = key[1]
                out = []
                for n in self._nodes.values():
                    if not n.ready():
                        continue
                    if pool not in ("", "all") and n.node_pool != pool:
                        continue
                    out.append(n)
            else:                       # ("dcs", pool, frozenset(dcs))
                base = self._ready_memoized(("pool", key[1]))[0]
                dcs = key[2]
                out = (base if "*" in dcs else
                       [n for n in base if n.datacenter in dcs])
            ent = memo.setdefault(key, (out, tuple(n.id for n in out)))
            # id-keyed reverse map for nodes_pack_key: a single atomic
            # dict read (concurrent evals insert into the memo while
            # others look up; iterating it would race). The memo keeps
            # the list alive, so its id stays valid for this snapshot.
            self.__dict__.setdefault("_ready_by_id", {})[id(ent[0])] = \
                ent[1]
        return ent

    def ready_nodes_in_pool_dcs(self, pool: str, dcs: frozenset
                                ) -> List[Node]:
        """ready_nodes_in_pool + the job's datacenter filter
        (reference: readyNodesInDCsAndPool), memoized per snapshot so
        concurrent evals of one barrier generation share one list."""
        return self._ready_memoized(("dcs", pool, dcs))[0]

    def nodes_pack_key(self, nodes) -> object:
        """The cached node-id tuple for a list this snapshot's ready
        memo handed out (identity match), else None -- lets
        pack_nodes_cached skip the per-eval O(N) id-tuple rebuild."""
        by_id = self.__dict__.get("_ready_by_id")
        if by_id:
            return by_id.get(id(nodes))
        return None

    def job_by_id(self, namespace: str, job_id: str) -> Optional[Job]:
        return self._jobs.get((namespace, job_id))

    def jobs(self) -> List[Job]:
        return list(self._jobs.values())

    def eval_by_id(self, eval_id: str) -> Optional[Evaluation]:
        return self._evals.get(eval_id)

    def evals_by_job(self, namespace: str, job_id: str) -> List[Evaluation]:
        return [e for e in self._evals.values()
                if e.namespace == namespace and e.job_id == job_id]

    def alloc_by_id(self, alloc_id: str) -> Optional[Allocation]:
        return self._allocs.get(alloc_id)

    def allocs(self) -> List[Allocation]:
        return list(self._allocs.values())

    def allocs_by_node(self, node_id: str) -> List[Allocation]:
        return [self._allocs[i] for i in self._allocs_by_node.get(node_id, ())
                if i in self._allocs]

    def allocs_by_node_terminal(self, node_id: str,
                                terminal: bool) -> List[Allocation]:
        return [a for a in self.allocs_by_node(node_id)
                if a.terminal_status() == terminal]

    def allocs_by_job(self, namespace: str, job_id: str,
                      anyCreateIndex: bool = True) -> List[Allocation]:
        return [self._allocs[i]
                for i in self._allocs_by_job.get((namespace, job_id), ())
                if i in self._allocs]

    def allocs_by_eval(self, eval_id: str) -> List[Allocation]:
        return [a for a in self._allocs.values() if a.eval_id == eval_id]

    def deployment_by_id(self, deployment_id: str) -> Optional[Deployment]:
        return self._deployments.get(deployment_id)

    def latest_deployment_by_job(self, namespace: str,
                                 job_id: str) -> Optional[Deployment]:
        best = None
        for d in self._deployments.values():
            if d.namespace == namespace and d.job_id == job_id:
                if best is None or d.create_index > best.create_index:
                    best = d
        return best

    def deployments(self) -> List[Deployment]:
        return list(self._deployments.values())

    def node_pool_by_name(self, name: str) -> Optional[NodePool]:
        return self._node_pools.get(name)

    def scheduler_config(self) -> SchedulerConfiguration:
        return self._scheduler_config

    def csi_volume_by_id(self, namespace: str, vol_id: str):
        return self._csi_volumes.get((namespace, vol_id))

    def csi_volumes(self, namespace: Optional[str] = None):
        return sorted(
            (v for v in self._csi_volumes.values()
             if namespace in (None, "*", v.namespace)),
            key=lambda v: (v.namespace, v.id))

    def csi_plugin_by_id(self, plugin_id: str):
        return self._csi_plugins.get(plugin_id)

    def csi_plugins(self):
        return sorted(self._csi_plugins.values(), key=lambda p: p.id)


class StateStore:
    """The live, writable store. All writes go through raft in the reference
    (fsm.go:211 nomadFSM.Apply); here the FSM calls these methods directly
    under one lock, bumping the index exactly once per logical write.

    The reader contract -- a reader never waits for a writer's hand-over:
    writers hold ``_lock`` and REPLACE stored objects, never edit one in
    place. So point getters (LOCK_FREE_POINT_READS: ``node_by_id``,
    ``job_by_id``, ``eval_by_id``, ``alloc_by_id``, ``latest_index``,
    ...) are one read of one table and take no lock; a read that starts
    after a write returned sees it. ``allocs_by_job``, ``allocs_by_node``,
    ``allocs_by_eval`` and ``evals_by_job`` (LOCK_FREE_WALKS) walk a
    copy-on-write id tuple
    the writer published whole, one point read an id: whole objects, not
    one point in time. Everything that iterates a live table keeps the
    lock; several tables that must agree are read from ``snapshot()``.
    A blocking query (``block_until``) waits in the watch registry, on a
    lock of its own (order: store -> watch), and is woken by writes to
    the job or node it names, to its tables, or when the index passes
    its N -- not by every write."""

    def __init__(self) -> None:
        # the RLock behind an account of who waits for it (storelock.py)
        self._lock = make_store_lock()
        self._index = 1
        self._table_index: Dict[str, int] = {t: 1 for t in TABLES}
        self._nodes: Dict[str, Node] = {}
        self._jobs: Dict[Tuple[str, str], Job] = {}
        self._job_versions: Dict[Tuple[str, str, int], Job] = {}
        self._evals: Dict[str, Evaluation] = {}
        self._allocs: Dict[str, Allocation] = {}
        self._deployments: Dict[str, Deployment] = {}
        self._node_pools: Dict[str, NodePool] = {"default": NodePool(name="default"),
                                                 "all": NodePool(name="all")}
        self._scheduler_config = SchedulerConfiguration()
        # ACL tables (reference: state_store.go ACLPolicy/ACLToken regions)
        self._acl_policies: Dict[str, "ACLPolicy"] = {}
        self._acl_roles: Dict[str, "ACLRole"] = {}
        self._acl_tokens: Dict[str, "ACLToken"] = {}          # by accessor
        self._acl_tokens_by_secret: Dict[str, str] = {}       # secret->accessor
        self._acl_bootstrapped = False
        # keyring + secure variables (reference: state_store.go RootKeyMeta
        # and VariablesQuota regions; variables keyed (namespace, path))
        self._root_keys: Dict[str, "RootKey"] = {}
        self._variables: Dict[Tuple[str, str], "VariableEncrypted"] = {}
        # scaling (reference: state_store.go ScalingPolicies/ScalingEvents
        # regions; policies derived from jobs on UpsertJob)
        self._scaling_policies: Dict[str, ScalingPolicy] = {}
        self._scaling_events: Dict[Tuple[str, str], List[ScalingEvent]] = {}
        # namespaces; "default" always exists (reference: structs/namespace)
        self._namespaces: Dict[str, "Namespace"] = {
            "default": Namespace(name="default",
                                 description="Default shared namespace")}
        # CSI (reference: state_store.go CSIVolume/CSIPlugin regions;
        # plugins derived from node fingerprints)
        self._csi_volumes: Dict[Tuple[str, str], "CSIVolume"] = {}
        self._csi_plugins: Dict[str, "CSIPlugin"] = {}
        # native service catalog (reference: state_store.go
        # service_registration region), keyed by registration id
        self._services: Dict[str, "ServiceRegistration"] = {}
        # secondary indexes, copy-on-write: key -> tuple of ids in
        # insertion order. A writer publishes a NEW tuple per touched
        # key and never edits one in place, so a reader walks the tuple
        # it fetched without the lock (allocs_by_job, allocs_by_node,
        # allocs_by_eval, evals_by_job) and a snapshot shares it.
        self._allocs_by_node: Dict[str, Tuple[str, ...]] = {}
        self._allocs_by_job: Dict[Tuple[str, str], Tuple[str, ...]] = {}
        # by the eval that placed (or last updated in place) the alloc;
        # allocs of no eval ("") are not indexed
        self._allocs_by_eval: Dict[str, Tuple[str, ...]] = {}
        self._evals_by_job: Dict[Tuple[str, str], Tuple[str, ...]] = {}
        # snapshot cache: one StateSnapshot build per index (any write
        # invalidates)
        self._snap_cache: Optional[StateSnapshot] = None
        # (alloc table index, mapping) of the last snapshot's alloc view:
        # the base the next snapshot delta-advances from (ISSUE 17,
        # native control plane; see _snapshot_allocs_locked)
        self._snap_alloc_prev: Optional[Tuple[int, object]] = None
        # blocking queries wait here, off the store lock (watch.py);
        # lock order store -> watch
        self._watch = WatchRegistry(self._index, TABLES)
        # bounded journal of alloc-level write deltas: (index, pairs)
        # where pairs is [(old_alloc|None, new_alloc|None), ...] or None
        # for writes with no structured delta. Lets incremental memo
        # holders (solver/service.py usage base) catch a stale fold up
        # to the current index instead of refolding (ISSUE 6). Capacity
        # is a knob (NOMAD_TPU_DELTA_JOURNAL): an LP-queue batch commits
        # thousands of pairs in one plan group, and a journal sized for
        # per-eval commits silently degrades every incremental-memo
        # consumer to wholesale rebuilds (counted in
        # nomad.state.delta_journal_overflow).
        from collections import deque as _deque
        self._alloc_deltas: "_deque" = _deque(
            maxlen=_delta_journal_cap())
        # quality observatory hook (server/quality.py): set by
        # QualityObservatory.attach, receives every write's tables +
        # delta pairs alongside the module-level cache hooks. None
        # (the NOMAD_TPU_QUALITY=0 default for unattached stores) is
        # the prior path bit-for-bit.
        self._quality_hook = None
        # called with (plan results, index) when verified plans have
        # landed, lock still held: the server points it at its
        # in-flight bookings (server/inflight.py), which must learn a
        # commit's index before any lane can fold the table past it
        self.plan_commit_hook = None
        # tensor-resident alloc table (fed to the TPU solver's native
        # packing kernels; maintained incrementally on every write)
        self.alloc_table = AllocTable()

    # -- watch / blocking query ---------------------------------------------
    def latest_index(self) -> int:
        return self._index

    def table_index(self, *tables: str) -> int:
        return max(self._table_index.get(t, 0) for t in tables)

    def block_until(self, min_index: int, timeout: float = 5.0,
                    tables: Tuple[str, ...] = (),
                    keys: Tuple[Tuple[str, ...], ...] = ()) -> int:
        """Wait until a write past min_index touched what the query
        read (reference: blockingRPC nomad/rpc.go:852 over a memdb
        watch set): one of the items ``keys`` -- ``("job", ns, id)``,
        ``("node", id)`` --, else one of ``tables``, else any write at
        all (the worker's wait for index N). Returns the store's
        current index. The waiter stands on the watch registry's lock,
        never on the store's (watch.py)."""
        self._watch.wait(min_index, timeout, tables, keys)
        return self._index

    def _bump(self, *tables: str, delta=None, keys=()) -> int:
        """Advance the raft-style index for a logical write. ``delta``
        carries the write's alloc-level change set -- a list of
        (old_alloc_or_None, new_alloc_or_None) pairs -- when the caller
        knows it (plan commits, client updates, GC deletes); cache
        layers get it through ONE delta-aware notification instead of a
        bare "something changed", and the bounded journal below lets
        incremental memo holders catch a stale base up to the current
        index by applying the missed deltas instead of refolding.
        ``keys`` are the watch keys the write touched besides those of
        the delta's allocs (their job and node); an alloc write without
        a delta (a restore) cannot say, and wakes every watcher."""
        if schedcheck._ACTIVE:
            # schedule-explorer interposition: every index bump is a
            # decision point (one module-attr read when off)
            schedcheck.yield_point("store._bump")
        self._index += 1
        for t in tables:
            self._table_index[t] = self._index
        self._snap_cache = None
        if "allocs" in tables:
            # journal entry even for delta=None writes: consumers learn
            # the span is NOT coverable by deltas and must refold
            self._alloc_deltas.append((self._index, delta))
        hook = self._quality_hook
        if hook is not None:
            hook(tables, self._index, delta)
        self._notify_write_hooks(tables, self._index, delta)
        self._publish_locked(tables, delta, keys)
        return self._index

    def _publish_locked(self, tables, delta, keys) -> None:
        """Tell the watch registry which items this write touched, and
        which keys went with their job or node (nothing is left to
        watch under them). Store lock held: store -> watch."""
        if "allocs" in tables and delta is None:
            self._watch.publish(self._index, tables, None)
            return
        touched = set(keys)
        for old, new in delta or ():
            a = new if new is not None else old
            touched.add(("job", a.namespace, a.job_id))
            touched.add(("node", a.node_id))
        dropped = []
        for k in touched:
            if k[0] == "job":
                jk = k[1:]
                if jk not in self._jobs and not self._allocs_by_job.get(jk) \
                        and not self._evals_by_job.get(jk):
                    dropped.append(k)
            elif k[1] not in self._nodes and \
                    not self._allocs_by_node.get(k[1]):
                dropped.append(k)
        self._watch.publish(self._index, tables, touched, dropped)

    @staticmethod
    def _notify_write_hooks(tables, index: int, delta) -> None:
        """One delta-aware notification shared by every cache layer
        (solver const cache + host pack caches). Resolved via
        sys.modules so a store used without the solver stack never pays
        the (jax-importing) solver package import; getattr-guarded
        because sys.modules can hand back a PARTIALLY initialized module
        while another thread is mid-import (first eval racing a node
        registration burst) -- there is nothing to invalidate before
        the module finished loading anyway."""
        import sys as _sys
        for mod in ("nomad_tpu.solver.constcache", "nomad_tpu.tensor.pack"):
            m = _sys.modules.get(mod)
            hook = getattr(m, "note_table_write", None)
            if hook is not None:
                hook(tables, index, delta)

    def alloc_deltas_since(self, index: int, upto: Optional[int] = None):
        """(covered, pairs): every alloc-level (old, new) change pair
        recorded for writes in (index, upto] (upto None = current).
        ``covered`` is False when the journal doesn't reach back that
        far or a write in the span carried no structured delta -- the
        consumer must refold instead of applying deltas."""
        with self._lock:
            pairs = []
            hi = self._table_index.get("allocs", 0) if upto is None \
                else upto
            if not self._alloc_deltas:
                return (index >= self._table_index.get("allocs", 0)
                        or index >= hi), pairs
            oldest = self._alloc_deltas[0][0]
            if index < oldest - 1:
                # the journal wrapped past the consumer's base index: an
                # overflow-forced wholesale rebuild (raise
                # NOMAD_TPU_DELTA_JOURNAL if this counts up under load)
                from ..server.telemetry import metrics as _tm
                _tm.incr("nomad.state.delta_journal_overflow")
                return False, pairs
            for idx, delta in self._alloc_deltas:
                if idx <= index or idx > hi:
                    continue
                if delta is None:
                    return False, []
                pairs.extend(delta)
            return True, pairs

    def _snapshot_allocs_locked(self):
        """The alloc mapping for a snapshot under construction (caller
        holds the store lock). Native-CP path (``NOMAD_TPU_NATIVE_CP``,
        default on): delta-advance the previous snapshot's mapping by
        the journal span -- O(changed allocs) instead of the wholesale
        ~len(_allocs)-insert dict copy that dominated snapshot build at
        north-star scale. The wholesale rebuild stays as the
        journal-gap/overflow fallback AND, with the kill switch off, as
        the bit-for-bit oracle."""
        from .. import native
        if not native.native_cp_enabled():
            return dict(self._allocs)
        from ..server.telemetry import metrics as _tm
        idx = self._table_index.get("allocs", 0)
        prev = self._snap_alloc_prev
        if prev is not None:
            prev_idx, prev_map = prev
            if prev_idx == idx:
                # a write to another table invalidated the snapshot
                # cache without touching allocs: reuse the frozen map
                _tm.incr("nomad.native.snapshot_hits")
                return prev_map
            covered, pairs = self.alloc_deltas_since(prev_idx, upto=idx)
            if covered:
                if isinstance(prev_map, _DeltaAllocs):
                    base = prev_map._base
                    over = dict(prev_map._over)
                    dead = set(prev_map._dead)
                else:
                    base, over, dead = prev_map, {}, set()
                for old, new in pairs:
                    if new is not None:
                        over[new.id] = new
                        dead.discard(new.id)
                    elif old is not None:
                        over.pop(old.id, None)
                        if old.id in base:
                            dead.add(old.id)
                # flatten once the overlay outgrows its budget: lookup
                # and scan costs scale with the overlay, and a big
                # overlay means the next wholesale copy is cheap
                # relative to the churn that built it
                if len(over) + len(dead) <= max(1024, len(base) // 8):
                    view = _DeltaAllocs(base, over, dead)
                    self._snap_alloc_prev = (idx, view)
                    _tm.incr("nomad.native.snapshot_hits")
                    return view
        allocs = dict(self._allocs)
        self._snap_alloc_prev = (idx, allocs)
        _tm.incr("nomad.native.snapshot_fallbacks")
        return allocs

    def snapshot(self) -> StateSnapshot:
        with self._lock:
            if self._snap_cache is None:
                self._snap_cache = StateSnapshot(self)
            return self._snap_cache

    # -- nodes ---------------------------------------------------------------
    def upsert_node(self, node: Node) -> int:
        with self._lock:
            existing = self._nodes.get(node.id)
            if existing is not None:
                node.create_index = existing.create_index
                # re-registration must not clear operator-set drain or
                # eligibility state (reference: state_store.go UpsertNode
                # retains drain_strategy / scheduling_eligibility from the
                # existing node): clients re-register at runtime (server
                # restart recovery, fingerprint changes) with no knowledge
                # of server-side drains; eligibility only changes through
                # the drain/eligibility endpoints
                if node.drain_strategy is None:
                    if existing.drain_strategy is not None:
                        node.drain_strategy = existing.drain_strategy
                    if existing.scheduling_eligibility:
                        node.scheduling_eligibility = \
                            existing.scheduling_eligibility
            else:
                node.create_index = self._index + 1
            node.modify_index = self._index + 1
            if not node.computed_class:
                node.compute_class()
            self._nodes[node.id] = node
            self.alloc_table.register_node(node)
            idx = self._bump("nodes", keys=(("node", node.id),))
            # the recompute walks every node; skip it when this write
            # cannot change plugin state (no CSI fingerprints on the new
            # node and none aggregated fleet-wide) -- otherwise a 10K-node
            # registration burst is O(N^2)
            if node.csi_node_plugins or self._csi_plugins:
                self._recompute_csi_plugins_locked()
            return idx

    def delete_node(self, node_id: str) -> int:
        with self._lock:
            node = self._nodes.pop(node_id, None)
            idx = self._bump("nodes", keys=(("node", node_id),))
            if (node is not None and node.csi_node_plugins) \
                    or self._csi_plugins:
                self._recompute_csi_plugins_locked()
            return idx

    def update_node_status(self, node_id: str, status: str,
                           updated_at: float = 0.0) -> int:
        with self._lock:
            old = self._nodes.get(node_id)
            if old is None:
                raise KeyError(f"node {node_id} not found")
            import copy as _copy
            node = _copy.copy(old)
            node.status = status
            node.status_updated_at = updated_at
            node.modify_index = self._index + 1
            self._nodes[node_id] = node
            idx = self._bump("nodes", keys=(("node", node_id),))
            if node.csi_node_plugins or self._csi_plugins:
                self._recompute_csi_plugins_locked()
            return idx

    def update_node_eligibility(self, node_id: str, eligibility: str) -> int:
        with self._lock:
            old = self._nodes.get(node_id)
            if old is None:
                raise KeyError(f"node {node_id} not found")
            import copy as _copy
            node = _copy.copy(old)
            node.scheduling_eligibility = eligibility
            node.modify_index = self._index + 1
            self._nodes[node_id] = node
            idx = self._bump("nodes", keys=(("node", node_id),))
            if node.csi_node_plugins or self._csi_plugins:
                self._recompute_csi_plugins_locked()
            return idx

    def update_node_drain(self, node_id: str, drain_strategy,
                          mark_eligible: bool = False) -> int:
        with self._lock:
            old = self._nodes.get(node_id)
            if old is None:
                raise KeyError(f"node {node_id} not found")
            import copy as _copy
            from ..structs import NODE_SCHED_ELIGIBLE, NODE_SCHED_INELIGIBLE
            node = _copy.copy(old)
            node.drain_strategy = drain_strategy
            if drain_strategy is not None:
                node.scheduling_eligibility = NODE_SCHED_INELIGIBLE
            elif mark_eligible:
                node.scheduling_eligibility = NODE_SCHED_ELIGIBLE
            node.modify_index = self._index + 1
            self._nodes[node_id] = node
            idx = self._bump("nodes", keys=(("node", node_id),))
            if node.csi_node_plugins or self._csi_plugins:
                self._recompute_csi_plugins_locked()
            return idx

    # -- jobs ----------------------------------------------------------------
    def upsert_job(self, job: Job) -> int:
        with self._lock:
            key = (job.namespace, job.id)
            existing = self._jobs.get(key)
            if existing is not None:
                job.create_index = existing.create_index
                job.version = existing.version + 1
            else:
                job.create_index = self._index + 1
                job.version = 0
            job.modify_index = self._index + 1
            job.job_modify_index = self._index + 1
            if job.status not in (JOB_STATUS_DEAD,):
                job.status = JOB_STATUS_PENDING
            self._jobs[key] = job
            self._job_versions[(job.namespace, job.id, job.version)] = job
            self._update_job_scaling_policies_locked(job)
            return self._bump("jobs", "job_versions",
                              keys=(("job",) + key,))

    def _update_job_scaling_policies_locked(self, job: Job) -> None:
        """Re-derive the job's scaling policies from its groups' scaling
        blocks (reference: state_store.go updateJobScalingPolicies)."""
        import hashlib
        keep = set()
        for tg in job.task_groups:
            # defensive: never let a malformed block break FSM apply --
            # validation belongs to admission (Server._validate_job)
            if not tg.scaling or not isinstance(tg.scaling, dict):
                continue
            target = {"Namespace": job.namespace, "Job": job.id,
                      "Group": tg.name}
            pid = hashlib.sha1(
                f"{job.namespace}\x1f{job.id}\x1f{tg.name}".encode()
            ).hexdigest()[:36]
            keep.add(pid)
            existing = self._scaling_policies.get(pid)
            try:
                lo = int(tg.scaling.get("min", 0) or 0)
                hi = int(tg.scaling.get("max", tg.count))
            except (TypeError, ValueError):
                continue
            pol = ScalingPolicy(
                id=pid, namespace=job.namespace, job_id=job.id,
                type=str(tg.scaling.get("type", "horizontal")),
                target=target,
                min=lo, max=hi,
                policy=dict(tg.scaling.get("policy") or {}),
                enabled=bool(tg.scaling.get("enabled", True)),
                create_index=(existing.create_index if existing
                              else self._index + 1),
                modify_index=self._index + 1)
            self._scaling_policies[pid] = pol
        for pid, pol in list(self._scaling_policies.items()):
            if (pol.namespace, pol.job_id) == (job.namespace, job.id) and \
                    pid not in keep:
                del self._scaling_policies[pid]
        self._table_index["scaling_policies"] = self._index + 1

    def update_job_status(self, namespace: str, job_id: str,
                          status: str) -> int:
        """Status-only update: no new job version (reference: the FSM's
        setJobStatus path, distinct from Job.Register's version bump)."""
        with self._lock:
            key = (namespace, job_id)
            existing = self._jobs.get(key)
            if existing is None:
                return self._index
            import copy as _copy
            job = _copy.copy(existing)
            job.status = status
            job.modify_index = self._index + 1
            self._jobs[key] = job
            self._job_versions[(namespace, job_id, job.version)] = job
            return self._bump("jobs", keys=(("job",) + key,))

    def delete_job(self, namespace: str, job_id: str) -> int:
        with self._lock:
            self._jobs.pop((namespace, job_id), None)
            for k in [k for k in self._job_versions
                      if k[0] == namespace and k[1] == job_id]:
                del self._job_versions[k]
            for pid, pol in list(self._scaling_policies.items()):
                if (pol.namespace, pol.job_id) == (namespace, job_id):
                    del self._scaling_policies[pid]
            self._scaling_events.pop((namespace, job_id), None)
            return self._bump("jobs", "job_versions", "scaling_policies",
                              keys=(("job", namespace, job_id),))

    def job_version(self, namespace: str, job_id: str,
                    version: int) -> Optional[Job]:
        return self._job_versions.get((namespace, job_id, version))

    def job_versions_by_id(self, namespace: str, job_id: str) -> List[Job]:
        """All tracked versions, newest first (reference:
        state_store.go JobVersionsByID)."""
        with self._lock:
            versions = [v for (ns, jid, _), v in self._job_versions.items()
                        if (ns, jid) == (namespace, job_id)]
            return sorted(versions, key=lambda j: -j.version)

    def update_job_stability(self, namespace: str, job_id: str,
                             version: int, stable: bool) -> int:
        """(reference: state_store.go UpdateJobStability)"""
        with self._lock:
            job = self._job_versions.get((namespace, job_id, version))
            if job is None:
                return self._index
            import copy as _copy
            updated = _copy.copy(job)
            updated.stable = stable
            updated.modify_index = self._index + 1
            self._job_versions[(namespace, job_id, version)] = updated
            current = self._jobs.get((namespace, job_id))
            if current is not None and current.version == version:
                self._jobs[(namespace, job_id)] = updated
            return self._bump("jobs", "job_versions",
                              keys=(("job", namespace, job_id),))

    # -- scaling -------------------------------------------------------------
    def scaling_policies(self, namespace: Optional[str] = None
                         ) -> List[ScalingPolicy]:
        with self._lock:
            return [p for p in self._scaling_policies.values()
                    if namespace is None or p.namespace == namespace]

    def scaling_policy_by_id(self, policy_id: str
                             ) -> Optional[ScalingPolicy]:
        return self._scaling_policies.get(policy_id)

    def scaling_policies_by_job(self, namespace: str, job_id: str
                                ) -> List[ScalingPolicy]:
        with self._lock:
            return [p for p in self._scaling_policies.values()
                    if (p.namespace, p.job_id) == (namespace, job_id)]

    def upsert_scaling_event(self, namespace: str, job_id: str,
                             event: ScalingEvent) -> int:
        """Append to the job's scaling audit trail, keeping the most recent
        entries (reference: state_store.go UpsertScalingEvent, bounded by
        structs.JobTrackedScalingEvents=20)."""
        with self._lock:
            events = self._scaling_events.setdefault((namespace, job_id), [])
            events.append(event)
            if len(events) > 20:
                del events[:-20]
            return self._bump("scaling_events")

    def scaling_events_by_job(self, namespace: str, job_id: str
                              ) -> List[ScalingEvent]:
        with self._lock:
            return list(self._scaling_events.get((namespace, job_id), []))

    # -- evals ---------------------------------------------------------------
    def upsert_evals(self, evals: List[Evaluation]) -> int:
        import time as _time
        now = _time.time()
        with self._lock:
            for ev in evals:
                existing = self._evals.get(ev.id)
                if existing is not None:
                    ev.create_index = existing.create_index
                    ev.create_time = existing.create_time
                else:
                    ev.create_index = self._index + 1
                    ev.create_time = now
                ev.modify_index = self._index + 1
                ev.modify_time = now
                self._put_eval_locked(ev)
                self._update_job_summary_status(ev)
            return self._bump("evals", keys=_eval_keys(evals))

    def _put_eval_locked(self, ev: Evaluation) -> None:
        if ev.id not in self._evals:
            jk = (ev.namespace, ev.job_id)
            self._evals_by_job[jk] = \
                self._evals_by_job.get(jk, ()) + (ev.id,)
        self._evals[ev.id] = ev

    def delete_evals(self, eval_ids: List[str]) -> int:
        with self._lock:
            gone = [ev for ev in (self._evals.pop(i, None)
                                  for i in eval_ids) if ev is not None]
            _unindex(self._evals_by_job,
                     (((ev.namespace, ev.job_id), ev.id) for ev in gone))
            return self._bump("evals", keys=_eval_keys(gone))

    def _update_job_summary_status(self, ev: Evaluation) -> None:
        # Blocked eval => job still pending work; minimal summary upkeep.
        pass

    # -- allocs --------------------------------------------------------------
    def upsert_allocs(self, allocs: List[Allocation]) -> int:
        with self._lock:
            pairs = self._insert_allocs_locked(allocs)
            return self._bump("allocs", delta=pairs)

    def _insert_allocs_locked(self, allocs: List[Allocation]) -> list:
        """Returns the write's (old_alloc_or_None, new_alloc) delta pairs
        for the _bump journal."""
        import time as _time
        now = _time.time()
        pairs = []
        by_node: Dict[str, list] = {}
        by_job: Dict[Tuple[str, str], list] = {}
        by_eval: Dict[str, list] = {}
        moved = []          # an in-place update re-homes the alloc's eval
        for alloc in allocs:
            existing = self._allocs.get(alloc.id)
            if existing is not None:
                alloc.create_index = existing.create_index
                alloc.create_time = existing.create_time
            else:
                alloc.create_index = self._index + 1
                alloc.create_time = now
            alloc.modify_index = self._index + 1
            alloc.modify_time = now
            if alloc.job is None and existing is not None:
                alloc.job = existing.job
            self._allocs[alloc.id] = alloc
            pairs.append((existing, alloc))
            jk = (alloc.namespace, alloc.job_id)
            if existing is None or existing.node_id != alloc.node_id:
                by_node.setdefault(alloc.node_id, []).append(alloc.id)
            if existing is None or \
                    (existing.namespace, existing.job_id) != jk:
                by_job.setdefault(jk, []).append(alloc.id)
            if existing is None or existing.eval_id != alloc.eval_id:
                if alloc.eval_id:
                    by_eval.setdefault(alloc.eval_id, []).append(alloc.id)
                if existing is not None and existing.eval_id:
                    moved.append((existing.eval_id, alloc.id))
        if moved:
            _unindex(self._allocs_by_eval, moved)
        # one new tuple per touched key, published whole
        for index, added in ((self._allocs_by_node, by_node),
                             (self._allocs_by_job, by_job),
                             (self._allocs_by_eval, by_eval)):
            for k, ids in added.items():
                index[k] = index.get(k, ()) + tuple(ids)
        self.alloc_table.upsert_many(allocs)
        return pairs

    def update_allocs_from_client(self, allocs: List[Allocation]) -> int:
        """Client-side status updates (reference: Node.UpdateAlloc
        node_endpoint.go:1322 -> state UpdateAllocsFromClient)."""
        with self._lock:
            pairs = []
            for updated in allocs:
                existing = self._allocs.get(updated.id)
                if existing is None:
                    continue
                import copy as _copy
                alloc = _copy.copy(existing)
                alloc.client_status = updated.client_status
                alloc.client_description = updated.client_description
                alloc.task_states = dict(updated.task_states)
                alloc.network_status = updated.network_status
                if updated.deployment_status is not None:
                    alloc.deployment_status = updated.deployment_status
                if updated.client_terminal_time:
                    alloc.client_terminal_time = updated.client_terminal_time
                alloc.modify_index = self._index + 1
                import time as _time
                alloc.modify_time = _time.time()
                self._allocs[alloc.id] = alloc
                pairs.append((existing, alloc))
                self.alloc_table.upsert(alloc)
            return self._bump("allocs", delta=pairs)

    def update_alloc_desired_transition(self, alloc_ids: List[str],
                                        migrate: bool = True) -> int:
        """(reference: state AllocUpdateDesiredTransition, used by the
        drainer to request migrations)."""
        with self._lock:
            import copy as _copy
            from ..structs import DesiredTransition
            pairs = []
            for aid in alloc_ids:
                existing = self._allocs.get(aid)
                if existing is None:
                    continue
                alloc = _copy.copy(existing)
                alloc.desired_transition = DesiredTransition(migrate=migrate)
                alloc.modify_index = self._index + 1
                self._allocs[aid] = alloc
                pairs.append((existing, alloc))
            return self._bump("allocs", delta=pairs)

    def delete_allocs(self, alloc_ids: List[str]) -> int:
        with self._lock:
            pairs = []
            for aid in alloc_ids:
                a = self._allocs.pop(aid, None)
                if a is not None:
                    pairs.append((a, None))
                self.alloc_table.remove(aid)
            _unindex(self._allocs_by_node,
                     ((a.node_id, a.id) for a, _ in pairs))
            _unindex(self._allocs_by_job,
                     (((a.namespace, a.job_id), a.id) for a, _ in pairs))
            _unindex(self._allocs_by_eval,
                     ((a.eval_id, a.id) for a, _ in pairs))
            return self._bump("allocs", delta=pairs)

    # -- deployments ---------------------------------------------------------
    def upsert_deployment(self, deployment: Deployment) -> int:
        with self._lock:
            self._upsert_deployment_locked(deployment)
            return self._index

    def upsert_deployment_cas(self, deployment: Deployment,
                              expected_modify_index: int) -> bool:
        """Compare-and-swap: commit only if the stored deployment's
        modify_index still matches (lost-update guard for the watcher)."""
        with self._lock:
            existing = self._deployments.get(deployment.id)
            if existing is not None and \
                    existing.modify_index != expected_modify_index:
                return False
            self._upsert_deployment_locked(deployment)
            return True

    def _upsert_deployment_locked(self, deployment: Deployment) -> None:
        existing = self._deployments.get(deployment.id)
        if existing is not None:
            deployment.create_index = existing.create_index
        else:
            deployment.create_index = self._index + 1
        deployment.modify_index = self._index + 1
        self._deployments[deployment.id] = deployment
        self._bump("deployments", keys=_deployment_keys((deployment,)))

    def delete_deployment(self, deployment_id: str) -> int:
        with self._lock:
            gone = self._deployments.pop(deployment_id, None)
            return self._bump("deployments",
                              keys=_deployment_keys((gone,)))

    # -- node pools / config -------------------------------------------------
    def upsert_node_pool(self, pool: NodePool) -> int:
        with self._lock:
            existing = self._node_pools.get(pool.name)
            pool.create_index = (existing.create_index if existing
                                 else self._index + 1)
            pool.modify_index = self._index + 1
            self._node_pools[pool.name] = pool
            return self._bump("node_pools")

    def delete_node_pool(self, name: str) -> int:
        """Built-in pools are undeletable; the caller enforces emptiness
        (reference: node_pool_endpoint.go DeleteNodePools)."""
        with self._lock:
            if name in ("default", "all"):
                return self._index
            self._node_pools.pop(name, None)
            return self._bump("node_pools")

    def node_pools(self) -> List[NodePool]:
        with self._lock:
            return sorted(self._node_pools.values(), key=lambda p: p.name)

    # -- namespaces (reference: state_store.go Namespace region) -----------
    def upsert_namespace(self, namespace: "Namespace") -> int:
        with self._lock:
            existing = self._namespaces.get(namespace.name)
            namespace.create_index = (existing.create_index if existing
                                      else self._index + 1)
            namespace.modify_index = self._index + 1
            self._namespaces[namespace.name] = namespace
            return self._bump("namespaces")

    def delete_namespace(self, name: str) -> int:
        with self._lock:
            if name == "default":
                return self._index
            self._namespaces.pop(name, None)
            return self._bump("namespaces")

    def namespace_by_name(self, name: str) -> Optional["Namespace"]:
        return self._namespaces.get(name)

    def namespaces(self) -> List["Namespace"]:
        with self._lock:
            return sorted(self._namespaces.values(), key=lambda n: n.name)

    # -- CSI volumes + plugins (reference: state_store.go CSIVolume region,
    #    volumewatcher claim release) --------------------------------------
    def upsert_csi_volume(self, vol: "CSIVolume") -> int:
        with self._lock:
            key = (vol.namespace, vol.id)
            existing = self._csi_volumes.get(key)
            if existing is not None:
                vol.create_index = existing.create_index
                # claims survive re-registration
                vol.read_claims = dict(existing.read_claims)
                vol.write_claims = dict(existing.write_claims)
            else:
                vol.create_index = self._index + 1
            vol.modify_index = self._index + 1
            self._csi_volumes[key] = vol
            return self._bump("csi_volumes")

    def delete_csi_volume(self, namespace: str, vol_id: str) -> int:
        """Caller enforces no-claims; built to be idempotent."""
        with self._lock:
            self._csi_volumes.pop((namespace, vol_id), None)
            return self._bump("csi_volumes")

    def csi_volume_by_id(self, namespace: str, vol_id: str
                         ) -> Optional["CSIVolume"]:
        return self._csi_volumes.get((namespace, vol_id))

    def csi_volumes(self, namespace: Optional[str] = None
                    ) -> List["CSIVolume"]:
        with self._lock:
            return sorted(
                (v for v in self._csi_volumes.values()
                 if namespace in (None, "*", v.namespace)),
                key=lambda v: (v.namespace, v.id))

    def csi_volume_release(self, namespace: str, vol_id: str,
                           alloc_id: str) -> int:
        """Drop an alloc's claims (reference: CSIVolumeClaim w/ release
        state, driven by the volume watcher)."""
        with self._lock:
            vol = self._csi_volumes.get((namespace, vol_id))
            if vol is None:
                return self._index
            import copy as _copy
            nv = _copy.copy(vol)
            nv.read_claims = {k: c for k, c in vol.read_claims.items()
                              if k != alloc_id}
            nv.write_claims = {k: c for k, c in vol.write_claims.items()
                               if k != alloc_id}
            if (len(nv.read_claims), len(nv.write_claims)) == \
                    (len(vol.read_claims), len(vol.write_claims)):
                return self._index
            nv.modify_index = self._index + 1
            self._csi_volumes[(namespace, vol_id)] = nv
            return self._bump("csi_volumes")

    def _csi_claim_locked(self, alloc: Allocation) -> None:
        """Claim the CSI volumes an alloc's group requests; called from
        upsert_plan_results so claims replicate deterministically with the
        placement itself (reference: csi_hook + CSIVolume.Claim RPC)."""
        from ..structs.csi import CLAIM_READ, CLAIM_WRITE, CSIVolumeClaim
        job = alloc.job
        if job is None:
            return
        tg = job.lookup_task_group(alloc.task_group)
        if tg is None:
            return
        for req in (tg.volumes or {}).values():
            if req.type != "csi":
                continue
            source = req.source_for(alloc.name)
            vol = self._csi_volumes.get((job.namespace, source))
            if vol is None:
                continue
            import copy as _copy
            nv = _copy.copy(vol)
            nv.read_claims = dict(vol.read_claims)
            nv.write_claims = dict(vol.write_claims)
            claim = CSIVolumeClaim(
                alloc_id=alloc.id, node_id=alloc.node_id,
                mode=CLAIM_READ if req.read_only else CLAIM_WRITE)
            if req.read_only:
                nv.read_claims[alloc.id] = claim
            else:
                nv.write_claims[alloc.id] = claim
            nv.modify_index = self._index + 1
            self._csi_volumes[(job.namespace, source)] = nv
            self._table_index["csi_volumes"] = self._index + 1

    def _recompute_csi_plugins_locked(self) -> None:
        """Aggregate per-node fingerprints into fleet-wide plugin rows
        (reference: state_store.go updateNodeCSIPlugins)."""
        from ..structs.csi import CSIPlugin, plugin_healthy
        plugins: Dict[str, CSIPlugin] = {}
        for node in self._nodes.values():
            if not node.ready():
                continue
            for pid, info in (node.csi_node_plugins or {}).items():
                p = plugins.setdefault(pid, CSIPlugin(id=pid))
                if plugin_healthy(info):
                    p.nodes_healthy += 1
                    p.node_ids.append(node.id)
        self._csi_plugins = plugins
        self._table_index["csi_plugins"] = self._index

    def csi_plugins(self) -> List["CSIPlugin"]:
        with self._lock:
            return sorted(self._csi_plugins.values(), key=lambda p: p.id)

    def csi_plugin_by_id(self, plugin_id: str) -> Optional["CSIPlugin"]:
        return self._csi_plugins.get(plugin_id)

    # -- native service catalog (reference: state_store.go
    #    UpsertServiceRegistrations / DeleteServiceRegistrationByID) ------
    def upsert_service_registrations(
            self, regs: List["ServiceRegistration"]) -> int:
        with self._lock:
            for reg in regs:
                existing = self._services.get(reg.id)
                reg.create_index = (existing.create_index if existing
                                    else self._index + 1)
                reg.modify_index = self._index + 1
                self._services[reg.id] = reg
            return self._bump("services")

    def delete_service_registrations(self, reg_ids: List[str]) -> int:
        with self._lock:
            for rid in reg_ids:
                self._services.pop(rid, None)
            return self._bump("services")

    def delete_services_by_alloc(self, alloc_id: str) -> int:
        """All of one alloc's registrations at once (reference:
        DeleteServiceRegistrationByAllocID, the client-restart sweep)."""
        return self.delete_services_by_allocs([alloc_id])

    def delete_services_by_allocs(self, alloc_ids: List[str]) -> int:
        """Batch sweep: one pass, one index bump, one raft entry."""
        with self._lock:
            ids = set(alloc_ids)
            gone = [rid for rid, r in self._services.items()
                    if r.alloc_id in ids]
            for rid in gone:
                del self._services[rid]
            return self._bump("services") if gone else self._index

    def restore_from_snapshot(self, blob: dict) -> int:
        """Atomically replace ALL state with a snapshot's contents; a
        replicated write so every peer swaps identically (reference: raft
        snapshot install -> FSM Restore)."""
        from ..statecheck import mark_uncoverable
        from .restore import restore_state
        with self._lock:
            prior = self._index
            restore_state(self, blob)
            # indexes must stay monotonic for blocking-query watchers even
            # when restoring an older snapshot
            self._index = max(self._index, prior)
            # the restore replaces alloc state wholesale: its delta-less
            # journal entry is an EXPLICIT coverage gap (incremental
            # memo holders must refold), which the snapshot-isolation
            # sanitizer would otherwise flag as a silent one
            with mark_uncoverable("raft snapshot restore"):
                # nomadlint: waive=delta-carried -- wholesale restore:
                # no (old, new) pair set exists; the mark_uncoverable
                # scope makes the gap explicit to statecheck's runtime
                # journal-gap detector too
                return self._bump(*TABLES)

    def delete_services_by_node(self, node_id: str) -> int:
        """One-pass sweep of a dead node's registrations (reference:
        DeleteServiceRegistrationByNodeID)."""
        with self._lock:
            gone = [rid for rid, r in self._services.items()
                    if r.node_id == node_id]
            for rid in gone:
                del self._services[rid]
            return self._bump("services") if gone else self._index

    def service_registrations(self, namespace: Optional[str] = None
                              ) -> List["ServiceRegistration"]:
        with self._lock:
            return sorted(
                (s for s in self._services.values()
                 if namespace in (None, "*", s.namespace)),
                key=lambda s: (s.namespace, s.service_name, s.id))

    def services_by_name(self, namespace: str, name: str
                         ) -> List["ServiceRegistration"]:
        with self._lock:
            return sorted(
                (s for s in self._services.values()
                 if s.namespace == namespace and s.service_name == name),
                key=lambda s: s.id)

    # -- keyring + variables (reference: state_store.go UpsertRootKeyMeta,
    #    VarSet/VarGet/VarDelete with check-and-set semantics) -------------
    def upsert_root_key(self, key: "RootKey") -> int:
        with self._lock:
            existing = self._root_keys.get(key.key_id)
            key.create_index = (existing.create_index if existing
                                else self._index + 1)
            key.modify_index = self._index + 1
            self._root_keys[key.key_id] = key
            return self._bump("root_keys")

    def delete_root_key(self, key_id: str) -> int:
        with self._lock:
            self._root_keys.pop(key_id, None)
            return self._bump("root_keys")

    def root_key_by_id(self, key_id: str):
        return self._root_keys.get(key_id)

    def root_keys(self) -> List:
        with self._lock:
            return list(self._root_keys.values())

    def upsert_variable(self, var: "VariableEncrypted",
                        cas_index: Optional[int] = None):
        """Returns (ok, conflict_or_result). cas_index None = blind write;
        0 = create-only; N = modify_index must equal N
        (reference: VarSet CAS contract in nomad/variables_endpoint.go)."""
        with self._lock:
            key = (var.meta.namespace, var.meta.path)
            existing = self._variables.get(key)
            if cas_index is not None:
                current = existing.meta.modify_index if existing else 0
                if current != cas_index:
                    return False, existing
            import time as _time
            now = _time.time()
            if existing is not None:
                var.meta.create_index = existing.meta.create_index
                var.meta.create_time = existing.meta.create_time
            else:
                var.meta.create_index = self._index + 1
                var.meta.create_time = now
            var.meta.modify_index = self._index + 1
            var.meta.modify_time = now
            self._variables[key] = var
            self._bump("variables")
            return True, var

    def delete_variable(self, namespace: str, path: str,
                        cas_index: Optional[int] = None):
        with self._lock:
            key = (namespace, path)
            existing = self._variables.get(key)
            if cas_index is not None:
                current = existing.meta.modify_index if existing else 0
                if current != cas_index:
                    return False, existing
            if existing is not None:
                del self._variables[key]
                self._bump("variables")
            return True, existing

    def variable_by_path(self, namespace: str, path: str):
        return self._variables.get((namespace, path))

    def variables(self, namespace: Optional[str] = None,
                  prefix: str = "") -> List:
        with self._lock:
            return [v for (ns, path), v in sorted(self._variables.items())
                    if (namespace is None or ns == namespace)
                    and path.startswith(prefix)]

    # -- ACL tables (reference: state_store.go UpsertACLPolicies /
    #    UpsertACLTokens / BootstrapACLTokens regions) -----------------------
    def upsert_acl_policies(self, policies: List[ACLPolicy]) -> int:
        with self._lock:
            for p in policies:
                existing = self._acl_policies.get(p.name)
                p.create_index = (existing.create_index if existing
                                  else self._index + 1)
                p.modify_index = self._index + 1
                self._acl_policies[p.name] = p
            return self._bump("acl_policies")

    def delete_acl_policies(self, names: List[str]) -> int:
        with self._lock:
            for name in names:
                self._acl_policies.pop(name, None)
            return self._bump("acl_policies")

    def upsert_acl_roles(self, roles: List["ACLRole"]) -> int:
        with self._lock:
            for r in roles:
                existing = self._acl_roles.get(r.name)
                r.create_index = (existing.create_index if existing
                                  else self._index + 1)
                r.modify_index = self._index + 1
                self._acl_roles[r.name] = r
            return self._bump("acl_roles")

    def delete_acl_roles(self, names: List[str]) -> int:
        with self._lock:
            for name in names:
                self._acl_roles.pop(name, None)
            return self._bump("acl_roles")

    def acl_role_by_name(self, name: str) -> Optional["ACLRole"]:
        return self._acl_roles.get(name)

    def acl_roles(self) -> List["ACLRole"]:
        with self._lock:
            return list(self._acl_roles.values())

    def acl_policy_by_name(self, name: str) -> Optional[ACLPolicy]:
        return self._acl_policies.get(name)

    def acl_policies(self) -> List[ACLPolicy]:
        with self._lock:
            return list(self._acl_policies.values())

    def upsert_acl_tokens(self, tokens: List[ACLToken]) -> int:
        with self._lock:
            for t in tokens:
                existing = self._acl_tokens.get(t.accessor_id)
                t.create_index = (existing.create_index if existing
                                  else self._index + 1)
                t.modify_index = self._index + 1
                if existing is not None:
                    self._acl_tokens_by_secret.pop(existing.secret_id, None)
                self._acl_tokens[t.accessor_id] = t
                self._acl_tokens_by_secret[t.secret_id] = t.accessor_id
            return self._bump("acl_tokens")

    def delete_acl_tokens(self, accessor_ids: List[str]) -> int:
        with self._lock:
            for acc in accessor_ids:
                t = self._acl_tokens.pop(acc, None)
                if t is not None:
                    self._acl_tokens_by_secret.pop(t.secret_id, None)
            return self._bump("acl_tokens")

    def acl_token_by_accessor(self, accessor_id: str) -> Optional[ACLToken]:
        return self._acl_tokens.get(accessor_id)

    def acl_token_by_secret(self, secret_id: str) -> Optional[ACLToken]:
        with self._lock:
            acc = self._acl_tokens_by_secret.get(secret_id)
            return self._acl_tokens.get(acc) if acc else None

    def acl_tokens(self) -> List[ACLToken]:
        with self._lock:
            return list(self._acl_tokens.values())

    def bootstrap_acl_token(self, token: ACLToken) -> bool:
        """One-shot management bootstrap (reference: state_store.go
        BootstrapACLTokens -- guarded by the acl-token-bootstrap index).
        Deleting every management token re-opens bootstrap (the escape
        hatch the reference provides via bootstrap-reset)."""
        with self._lock:
            have_mgmt = any(t.type == ACL_TOKEN_TYPE_MANAGEMENT
                            and not t.is_expired()
                            for t in self._acl_tokens.values())
            if self._acl_bootstrapped and have_mgmt:
                return False
            self._acl_bootstrapped = True
            token.create_index = self._index + 1
            token.modify_index = self._index + 1
            self._acl_tokens[token.accessor_id] = token
            self._acl_tokens_by_secret[token.secret_id] = token.accessor_id
            self._bump("acl_tokens")
            return True

    def acl_bootstrapped(self) -> bool:
        return self._acl_bootstrapped

    def set_scheduler_config(self, cfg: SchedulerConfiguration) -> int:
        with self._lock:
            cfg.modify_index = self._index + 1
            self._scheduler_config = cfg
            return self._bump("scheduler_config")

    def scheduler_config(self) -> SchedulerConfiguration:
        return self._scheduler_config

    # -- plan application ----------------------------------------------------
    def _stage_plan_result_locked(self, result: PlanResult,
                                  eval_updates: Optional[List[Evaluation]]
                                  ) -> Tuple[List[Allocation],
                                             List[Allocation]]:
        """Apply one plan result's dict/object writes (stop merges,
        deployments, eval updates) WITHOUT touching the tensor table or
        secondary indexes, which the caller batches across plans. Returns
        (merged_stops, placements, delta_pairs, watch_keys) -- the first
        two for those deferred columnar writes, the pairs for the _bump
        journal, the keys of the evals' and deployments' jobs for the
        watchers. Lock held; no index bump here."""
        stops: List[Allocation] = []
        for allocs in result.node_update.values():
            stops.extend(allocs)
        for allocs in result.node_preemptions.values():
            stops.extend(allocs)
        placements: List[Allocation] = []
        for allocs in result.node_allocation.values():
            placements.extend(allocs)

        # Stops/preemptions update desired status on existing allocs
        import copy as _copy
        import time as _time
        merged = []
        pairs = []
        for stop in stops:
            existing = self._allocs.get(stop.id)
            if existing is None:
                continue
            alloc = _copy.copy(existing)
            alloc.desired_status = stop.desired_status
            alloc.desired_description = stop.desired_description
            alloc.preempted_by_allocation = stop.preempted_by_allocation
            if stop.client_status:
                alloc.client_status = stop.client_status
            if stop.followup_eval_id:
                alloc.followup_eval_id = stop.followup_eval_id
            alloc.modify_index = self._index + 1
            alloc.modify_time = _time.time()
            self._allocs[alloc.id] = alloc
            merged.append(alloc)
            pairs.append((existing, alloc))

        touched_d = []
        if result.deployment is not None:
            d = result.deployment
            touched_d.append(d)
            existing_d = self._deployments.get(d.id)
            if existing_d is not None:
                d.create_index = existing_d.create_index
            else:
                d.create_index = self._index + 1
            d.modify_index = self._index + 1
            self._deployments[d.id] = d
        for du in result.deployment_updates:
            d = self._deployments.get(du.deployment_id)
            if d is not None:
                nd = _copy.copy(d)
                nd.status = du.status
                nd.status_description = du.status_description
                nd.modify_index = self._index + 1
                self._deployments[nd.id] = nd
                touched_d.append(nd)

        if eval_updates:
            for ev in eval_updates:
                ev.modify_index = self._index + 1
                self._put_eval_locked(ev)
        return merged, placements, pairs, \
            _deployment_keys(touched_d) + _eval_keys(eval_updates or ())

    def upsert_plan_results(self, result: PlanResult,
                            eval_updates: Optional[List[Evaluation]] = None
                            ) -> int:
        """Commit a verified plan in one logical raft write
        (reference: state_store.go:382 UpsertPlanResults, applied by the FSM
        for ApplyPlanResultsRequestType)."""
        with self._lock:
            merged, placements, pairs, keys = \
                self._stage_plan_result_locked(result, eval_updates)
            # refresh the tensor rows (batched): the allocs just became
            # server-terminal, and the verify fast path's live_strict
            # column mirrors the applier's AllocsByNodeTerminal(false)
            # filter -- a stale 1 here overcounts usage on this node
            # until the client acks, which can fast-reject plans the
            # authoritative python check would accept
            # (tests/test_verify_fold.py pins this)
            self.alloc_table.upsert_many(merged)

            pairs.extend(self._insert_allocs_locked(placements))
            if self._csi_volumes:
                for alloc in placements:
                    self._csi_claim_locked(alloc)

            idx = self._bump("allocs", "deployments", "evals",
                             delta=pairs, keys=keys)
            result.alloc_index = idx
            if self.plan_commit_hook is not None:
                self.plan_commit_hook((result,), idx)
            return idx

    def apply_plan_results_batch(
            self, entries: List[Tuple[PlanResult,
                                      Optional[List[Evaluation]]]]
            ) -> Tuple[int, List[Optional[BaseException]]]:
        """Group commit (the WAL/raft batched-apply analog): N verified
        plan results land as ONE store transaction -- one lock
        acquisition, one raft-style index bump, one snapshot
        invalidation, and ONE columnar pass through
        ``AllocTable.upsert_many`` for the whole batch's stop merges and
        placements instead of one per plan.

        A plan whose staging raises (the ``plan.commit`` chaos point
        fires BEFORE its writes) is skipped -- the batch splits around
        it: surviving plans still commit exactly once, and the failing
        plan's exception rides the returned per-entry outcome list
        (None = committed)."""
        from ..faultinject import faults
        if schedcheck._ACTIVE:
            # schedule-explorer interposition: a batch commit is the
            # write-skew decision point ROADMAP-2's N workers multiply
            schedcheck.yield_point("store.apply_batch")
        with self._lock:
            outcomes: List[Optional[BaseException]] = []
            merged_all: List[Allocation] = []
            placements_all: List[Allocation] = []
            pairs_all: list = []
            keys_all: list = []
            staged: List[Tuple[PlanResult, List[Allocation]]] = []
            for result, eval_updates in entries:
                try:
                    faults.fire("plan.commit")
                    merged, placements, pairs, keys = \
                        self._stage_plan_result_locked(result, eval_updates)
                except BaseException as e:  # noqa: BLE001 -- split batch
                    outcomes.append(e)
                    continue
                merged_all.extend(merged)
                placements_all.extend(placements)
                pairs_all.extend(pairs)
                keys_all.extend(keys)
                staged.append((result, placements))
                outcomes.append(None)
            self.alloc_table.upsert_many(merged_all)
            pairs_all.extend(self._insert_allocs_locked(placements_all))
            if self._csi_volumes:
                for _, placements in staged:
                    for alloc in placements:
                        self._csi_claim_locked(alloc)
            idx = self._bump("allocs", "deployments", "evals",
                             delta=pairs_all, keys=keys_all)
            for result, _ in staged:
                result.alloc_index = idx
            if self.plan_commit_hook is not None:
                self.plan_commit_hook([r for r, _ in staged], idx)
            return idx, outcomes

    def quality_usage_by_node(self) -> Dict[str, tuple]:
        """Per-node-id live usage served from the alloc table's
        incrementally-maintained fold columns, under the store lock --
        an independent accounting the quality layer's churn parity test
        triangulates against (delta-journal dict vs wholesale store
        fold vs this tensor-table fold)."""
        with self._lock:
            return self.alloc_table.usage_by_node()

    def preallocate_allocs(self, capacity: int) -> None:
        """Grow the tensor-resident alloc table to ``capacity`` rows in
        one resize, under the store lock (a north-star-scale bench run
        otherwise pays ~11 doubling copies of every column mid-commit).
        This is the sanctioned route -- callers must not reach through
        ``store.alloc_table`` directly (no-direct-table-write)."""
        with self._lock:
            self.alloc_table.preallocate(capacity)

    def compact_alloc_table(self, min_free: int = 4096,
                            free_ratio: float = 0.5):
        """Compact the tensor-resident alloc table once freed rows
        dominate: GC'd terminal allocs leave free rows behind, and under
        sustained churn those would otherwise pin peak-row-count RSS for
        the process lifetime. Compacts only when the free-row count
        exceeds BOTH ``min_free`` and ``free_ratio`` of the row span
        (small fleets never pay the copy). Returns the compaction stats
        dict, or None when below the watermark."""
        with self._lock:
            t = self.alloc_table
            if t.free_rows < min_free or \
                    t.free_rows < free_ratio * max(1, t.n_rows):
                return None
            return t.compact()

    # -- snapshot passthrough reads (so StateStore satisfies the scheduler's
    #    State interface directly in tests) --------------------------------
    #
    # Point reads take no lock: one dict.get on one table whose values a
    # writer replaces and never edits in place (LOCK_FREE_POINT_READS;
    # tests/test_store_reads.py holds each body to that shape). Walks of
    # a copy-on-write index take none either (LOCK_FREE_WALKS): one read
    # of the published id tuple, then a point read an id. Everything
    # that iterates a live table keeps the lock.
    def node_by_id(self, node_id):
        return self._nodes.get(node_id)

    def nodes(self):
        with self._lock:
            return list(self._nodes.values())

    def ready_nodes_in_pool(self, pool: str = "all"):
        return self.snapshot().ready_nodes_in_pool(pool)

    def ready_nodes_in_pool_dcs(self, pool: str, dcs: frozenset):
        return self.snapshot().ready_nodes_in_pool_dcs(pool, dcs)

    def nodes_pack_key(self, nodes):
        return self.snapshot().nodes_pack_key(nodes)

    def job_by_id(self, namespace, job_id):
        return self._jobs.get((namespace, job_id))

    def jobs(self):
        with self._lock:
            return list(self._jobs.values())

    def eval_by_id(self, eval_id):
        return self._evals.get(eval_id)

    def evals(self):
        with self._lock:
            return list(self._evals.values())

    def evals_by_job(self, namespace, job_id):
        return _walk(self._evals_by_job.get((namespace, job_id), ()),
                     self._evals)

    def alloc_by_id(self, alloc_id):
        return self._allocs.get(alloc_id)

    def allocs(self):
        with self._lock:
            return list(self._allocs.values())

    def allocs_by_node(self, node_id):
        return _walk(self._allocs_by_node.get(node_id, ()), self._allocs)

    def allocs_by_job(self, namespace, job_id, anyCreateIndex=True):
        return _walk(self._allocs_by_job.get((namespace, job_id), ()),
                     self._allocs)

    def num_allocs_by_job(self, namespace, job_id) -> int:
        """O(1) alloc count off the secondary index (any status).
        Monitoring loops that only need a progress number must not pay
        the allocs_by_job object-list materialization per poll."""
        return len(self._allocs_by_job.get((namespace, job_id), ()))

    def allocs_by_eval(self, eval_id):
        return _walk(self._allocs_by_eval.get(eval_id, ()), self._allocs)

    def deployment_by_id(self, deployment_id):
        return self._deployments.get(deployment_id)

    def latest_deployment_by_job(self, namespace, job_id):
        return self.snapshot().latest_deployment_by_job(namespace, job_id)

    def deployments(self):
        with self._lock:
            return list(self._deployments.values())

    def node_pool_by_name(self, name):
        return self._node_pools.get(name)


def _walk(ids, table: dict) -> list:
    """The objects a published id tuple names, each one point read of
    ``table``; an id a later write deleted is left out."""
    return [o for o in map(table.get, ids) if o is not None]


# The reader contract's two lists (tests/test_store_reads.py checks the
# bodies): getters that are ONE read of ONE replace-on-write table ...
LOCK_FREE_POINT_READS = (
    "latest_index", "table_index", "node_by_id", "job_by_id", "eval_by_id",
    "alloc_by_id", "deployment_by_id", "node_pool_by_name", "job_version",
    "scaling_policy_by_id", "namespace_by_name", "csi_volume_by_id",
    "csi_plugin_by_id", "root_key_by_id", "variable_by_path",
    "acl_role_by_name", "acl_policy_by_name", "acl_token_by_accessor",
    "acl_bootstrapped", "scheduler_config", "num_allocs_by_job")
# ... and walks of a copy-on-write index
LOCK_FREE_WALKS = ("allocs_by_job", "allocs_by_node", "allocs_by_eval",
                   "evals_by_job")
