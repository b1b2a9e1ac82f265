"""Wholesale state restore: rebuild every StateStore table from a
snapshot blob.

Extracted from raft/fsm.py (ISSUE 11 ``no-direct-table-write``): this
is the ONE sanctioned writer of store internals outside the store's
own methods -- a raft snapshot install replaces the world atomically
under the store lock, and keeping it inside ``nomad_tpu/state/`` lets
the lint rule forbid direct table writes everywhere else without a
pile of waivers.  ``raft/fsm.py`` re-exports it, so the FSM surface
(and every existing import site) is unchanged.
"""
from __future__ import annotations

from typing import TYPE_CHECKING

from ..structs import (
    ACLPolicy, ACLRole, ACLToken, Allocation, CSIVolume, Deployment,
    Evaluation, Job, Namespace, Node, NodePool, RootKey,
    ScalingEvent, ScalingPolicy, SchedulerConfiguration,
    ServiceRegistration, VariableEncrypted,
)
from ..structs import codec

if TYPE_CHECKING:  # pragma: no cover
    from .store import StateStore


def restore_state(store: "StateStore", blob: dict) -> None:
    nodes = [codec.decode(Node, n) for n in blob.get("nodes", [])]
    jobs = [codec.decode(Job, j) for j in blob.get("jobs", [])]
    evals = [codec.decode(Evaluation, e) for e in blob.get("evals", [])]
    allocs = [codec.decode(Allocation, a) for a in blob.get("allocs", [])]
    deployments = [codec.decode(Deployment, d)
                   for d in blob.get("deployments", [])]
    pools = [codec.decode(NodePool, p) for p in blob.get("node_pools", [])]
    sched_cfg = codec.decode(SchedulerConfiguration,
                             blob.get("scheduler_config") or {})
    acl_policies = [codec.decode(ACLPolicy, p)
                    for p in blob.get("acl_policies", [])]
    acl_tokens = [codec.decode(ACLToken, t)
                  for t in blob.get("acl_tokens", [])]
    acl_roles = [codec.decode(ACLRole, r)
                 for r in blob.get("acl_roles", [])]
    root_keys = [codec.decode(RootKey, k)
                 for k in blob.get("root_keys", [])]
    variables = [codec.decode(VariableEncrypted, v)
                 for v in blob.get("variables", [])]
    # decode EVERYTHING before touching the store, so a malformed blob
    # raises here and leaves state untouched (restore must be atomic)
    job_versions = {}
    for k, v in blob.get("job_versions", {}).items():
        ns, jid, ver = k.split("\x1f")
        job_versions[(ns, jid, int(ver))] = codec.decode(Job, v)
    scaling_policies = {
        pol.id: pol for pol in
        (codec.decode(ScalingPolicy, raw)
         for raw in blob.get("scaling_policies", []))}
    scaling_events = {}
    for k, evs in blob.get("scaling_events", {}).items():
        ns, jid = k.split("\x1f")
        scaling_events[(ns, jid)] = [
            codec.decode(ScalingEvent, e) for e in evs]
    restored_ns = [codec.decode(Namespace, n)
                   for n in blob.get("namespaces", [])]
    csi_volumes = {
        (v.namespace, v.id): v for v in
        (codec.decode(CSIVolume, raw)
         for raw in blob.get("csi_volumes", []))}
    services = {
        svc.id: svc for svc in
        (codec.decode(ServiceRegistration, raw)
         for raw in blob.get("services", []))}
    with store._lock:
        store._root_keys = {k.key_id: k for k in root_keys}
        store._variables = {(v.meta.namespace, v.meta.path): v
                            for v in variables}
        store._acl_policies = {p.name: p for p in acl_policies}
        store._acl_roles = {r.name: r for r in acl_roles}
        store._acl_tokens = {t.accessor_id: t for t in acl_tokens}
        store._acl_tokens_by_secret = {t.secret_id: t.accessor_id
                                       for t in acl_tokens}
        store._acl_bootstrapped = blob.get("acl_bootstrapped", False)
        store._nodes = {n.id: n for n in nodes}
        store._jobs = {(j.namespace, j.id): j for j in jobs}
        store._job_versions = job_versions
        store._evals = {e.id: e for e in evals}
        store._allocs = {a.id: a for a in allocs}
        store._deployments = {d.id: d for d in deployments}
        store._node_pools = {p.name: p for p in pools}
        if sched_cfg is not None:
            store._scheduler_config = sched_cfg
        # rebuild secondary indexes (and drop the snapshot cache: it
        # refers to the replaced dicts)
        store._snap_cache = None
        by_node, by_job, by_eval, evals_by_job = {}, {}, {}, {}
        for a in allocs:
            by_node.setdefault(a.node_id, []).append(a.id)
            by_job.setdefault((a.namespace, a.job_id), []).append(a.id)
            if a.eval_id:
                by_eval.setdefault(a.eval_id, []).append(a.id)
        for e in evals:
            evals_by_job.setdefault((e.namespace, e.job_id),
                                    []).append(e.id)
        store._allocs_by_node = {k: tuple(v) for k, v in by_node.items()}
        store._allocs_by_job = {k: tuple(v) for k, v in by_job.items()}
        store._allocs_by_eval = {k: tuple(v) for k, v in by_eval.items()}
        store._evals_by_job = {k: tuple(v)
                               for k, v in evals_by_job.items()}
        # re-link alloc.job to the stored job (codec duplicates the object)
        for a in allocs:
            stored = store._jobs.get((a.namespace, a.job_id))
            if stored is not None and a.job is not None and \
                    a.job.version == stored.version:
                a.job = stored
        store._scaling_policies = scaling_policies
        store._scaling_events = scaling_events
        if restored_ns:
            store._namespaces = {n.name: n for n in restored_ns}
        else:
            store._namespaces = {"default": Namespace(name="default")}
        store._namespaces.setdefault("default", Namespace(name="default"))
        store._csi_volumes = csi_volumes
        store._recompute_csi_plugins_locked()
        store._services = services
        store._index = blob.get("index", 1)
        ti = blob.get("table_index", {})
        for t in store._table_index:
            store._table_index[t] = ti.get(t, store._index)
        # rebuild the tensor-resident alloc table
        from ..state.alloc_table import AllocTable
        table = AllocTable()
        for n in nodes:
            table.register_node(n)
        # skip only CLIENT-terminal allocs (their rows would carry
        # live=0 AND live_strict=0 -- dead weight). Server-terminal
        # but client-running allocs must keep a row: they still
        # consume capacity in the scheduler's live filter until the
        # client acks, and dropping them made solver usage tensors
        # diverge across a snapshot restore
        # (tests/test_plan_normalization.py pins this).
        table.upsert_many(
            [a for a in allocs if not a.client_terminal_status()])
        store.alloc_table = table
        # every item may have changed: forget the watch keys, wake all
        store._watch.publish(store._index, store._table_index, None)
