"""A witness beside the comparison, not a fault: the program's own state
store, read under its lock at every plan commit. For each eviction a
committed plan carries, one JSON line goes to $PERFBENCH_WITNESS:
[commit index, node, evicted alloc id, its class as [priority, cpu,
memory, disk], the ids of the plan's placements that committed on that
node]. tests/test_preempt_standin.py reads it to name the cause of an
eviction that the committed records no longer show (PERF.md section 7,
item 0 (d))."""
import json
import os

from nomad_tpu.state.store import StateStore

OUT = os.environ["PERFBENCH_WITNESS"]


def note(store, results, index):
    rows = []
    for r in results:
        placed = r.node_allocation or {}
        for node_id, victims in (r.node_preemptions or {}).items():
            for v in victims:
                a = store.alloc_by_id(v.id)
                c = a.allocated_resources.comparable()
                rows.append([index, node_id, v.id, [
                    a.job.priority if a.job else None, c.cpu_shares,
                    c.memory_mb, c.disk_mb],
                    [b.id for b in placed.get(node_id, ())]])
    if rows:
        with open(OUT, "a") as f:
            f.writelines(json.dumps(row) + "\n" for row in rows)


def one(self, result, *args, **kw):
    with self._lock:
        index = upsert(self, result, *args, **kw)
        note(self, [result], index)
        return index


def batch(self, entries, *args, **kw):
    with self._lock:
        index, outcomes = apply(self, entries, *args, **kw)
        note(self, [e[0] for e, o in zip(entries, outcomes) if o is None],
             index)
        return index, outcomes


upsert, apply = StateStore.upsert_plan_results, \
    StateStore.apply_plan_results_batch
StateStore.upsert_plan_results = one
StateStore.apply_plan_results_batch = batch
