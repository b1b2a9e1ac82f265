"""The state store's reader contract (state/store.py, state/watch.py):
point reads and walks of a copy-on-write index take no lock, and a
blocking query waits on its own key, off the store's lock, woken only
by writes to what it read."""
import ast
import inspect
import sys
import textwrap
import threading
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.state import StateStore
from nomad_tpu.state import store as store_mod
from nomad_tpu.structs import PlanResult


@pytest.fixture(autouse=True)
def clean_metrics():
    metrics.reset()
    yield
    metrics.reset()


def wait_until(cond, timeout=10.0, msg="condition"):
    deadline = time.monotonic() + timeout
    while not cond():
        assert time.monotonic() < deadline, f"timed out waiting for {msg}"
        time.sleep(0.002)


def job_key(job_id):
    return (("job", "default", job_id),)


class Watcher(threading.Thread):
    """One blocking query; `took` is how long it waited."""

    def __init__(self, store, min_index, timeout=10.0, **what):
        super().__init__(daemon=True, name="http-watcher")
        self.store, self.min_index = store, min_index
        self.timeout, self.what = timeout, what
        self.index = self.took = self.returned_at = None

    def run(self):
        t0 = time.monotonic()
        self.index = self.store.block_until(self.min_index, self.timeout,
                                            **self.what)
        self.returned_at = time.monotonic()
        self.took = self.returned_at - t0

    def park(self):
        before = self.store._watch.parked()
        self.start()
        wait_until(lambda: self.store._watch.parked() > before,
                   msg="the watcher to park")
        return self


def seeded(n_nodes=2):
    store = StateStore()
    nodes = [mock.node() for _ in range(n_nodes)]
    for n in nodes:
        store.upsert_node(n)
    return store, nodes


def write_job(store, job_id):
    store.upsert_job(mock.job(id=job_id))


def write_eval(store, job_id):
    store.upsert_evals([mock.evaluation(job_id=job_id)])


def write_alloc(store, job_id, node):
    job = store.job_by_id("default", job_id) or mock.job(id=job_id)
    store.upsert_allocs([mock.alloc_for(job, node)])


def write_plan(store, job_id, node, n=3):
    job = store.job_by_id("default", job_id) or mock.job(id=job_id)
    store.upsert_plan_results(PlanResult(node_allocation={
        node.id: [mock.alloc_for(job, node, i) for i in range(n)]}))


# ---------------------------------------------------------------------------
# (a) readers without the lock, against writers


def test_lock_free_reads_under_concurrent_writes():
    """Four writers upsert and delete jobs, evals, allocs and nodes while
    eight readers hammer every lock-free getter: no exception, every
    value is None or a whole published object of the key asked for,
    and a read issued after a write returned sees that write."""
    store, nodes = seeded(4)
    stop = threading.Event()
    errors = []
    n_reads = [0]
    ids = [f"w{w}-{i}" for w in range(4) for i in range(6)]

    def whole(obj, **want):
        assert obj.create_index > 0 and obj.modify_index >= obj.create_index
        for attr, v in want.items():
            assert getattr(obj, attr) == v, (attr, getattr(obj, attr), v)

    def writer(w):
        try:
            mine = [i for i in ids if i.startswith(f"w{w}-")]
            k = 0
            while not stop.is_set():
                jid = mine[k % len(mine)]
                k += 1
                node = mock.node()
                node.id = f"node-{jid}"
                store.upsert_node(node)
                assert store.node_by_id(node.id) is node
                job = mock.job(id=jid)
                store.upsert_job(job)
                assert store.job_by_id("default", jid) is job
                ev = mock.evaluation(job_id=jid)
                store.upsert_evals([ev])
                assert store.eval_by_id(ev.id) is ev
                assert ev in store.evals_by_job("default", jid)
                allocs = [mock.alloc_for(job, node, i) for i in range(5)]
                store.upsert_allocs(allocs)
                assert store.alloc_by_id(allocs[0].id) is allocs[0]
                got = store.allocs_by_job("default", jid)
                assert all(a in got for a in allocs)
                assert all(a in store.allocs_by_node(node.id)
                           for a in allocs)
                assert store.num_allocs_by_job("default", jid) >= 5
                store.update_node_status(node.id, "down")
                assert store.node_by_id(node.id).status == "down"
                store.delete_allocs([a.id for a in allocs[:3]])
                assert store.alloc_by_id(allocs[0].id) is None
                assert allocs[0] not in store.allocs_by_job("default", jid)
                if k % 3 == 0:
                    store.delete_evals(
                        [e.id for e in store.evals_by_job("default", jid)])
                    store.delete_allocs(
                        [a.id for a in store.allocs_by_job("default", jid)])
                    store.delete_job("default", jid)
                    assert store.job_by_id("default", jid) is None
                    store.delete_node(node.id)
                    assert store.node_by_id(node.id) is None
        except BaseException as e:      # noqa: BLE001 -- reported below
            errors.append(("writer", w, repr(e)))
            stop.set()

    def reader(r):
        try:
            last = 0
            while not stop.is_set():
                idx = store.latest_index()
                assert idx >= last
                last = idx
                assert store.table_index("jobs", "allocs") <= \
                    store.latest_index()
                for jid in ids:
                    job = store.job_by_id("default", jid)
                    if job is not None:
                        whole(job, id=jid)
                    node = store.node_by_id(f"node-{jid}")
                    if node is not None:
                        whole(node, id=f"node-{jid}")
                    for a in store.allocs_by_job("default", jid):
                        whole(a, job_id=jid)
                        got = store.alloc_by_id(a.id)
                        assert got is None or got.id == a.id
                    for a in store.allocs_by_node(f"node-{jid}"):
                        whole(a, node_id=f"node-{jid}")
                    for e in store.evals_by_job("default", jid):
                        whole(e, job_id=jid)
                        got = store.eval_by_id(e.id)
                        assert got is None or got.id == e.id
                    assert store.num_allocs_by_job("default", jid) >= 0
                    n_reads[0] += 1
                assert store.node_pool_by_name("default") is not None
                assert store.scheduler_config() is not None
                assert store.deployment_by_id("none") is None
        except BaseException as e:      # noqa: BLE001 -- reported below
            errors.append(("reader", r, repr(e)))
            stop.set()

    threads = [threading.Thread(target=writer, args=(w,), daemon=True)
               for w in range(4)]
    threads += [threading.Thread(target=reader, args=(r,), daemon=True)
                for r in range(8)]
    was = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        for t in threads:
            t.start()
        stop.wait(2.0)
        stop.set()
        for t in threads:
            t.join(20)
    finally:
        sys.setswitchinterval(was)
    assert not any(t.is_alive() for t in threads)
    assert errors == []
    assert n_reads[0] > 100
    # the indexes agree with the tables once the writers are quiet
    snap = store.snapshot()
    for jid in ids:
        assert sorted(a.id for a in store.allocs_by_job("default", jid)) \
            == sorted(a.id for a in snap.allocs()
                      if a.job_id == jid)
        assert sorted(e.id for e in store.evals_by_job("default", jid)) \
            == sorted(e.id for e in snap.evals_by_job("default", jid))


def test_readers_do_not_wait_for_a_writer_holding_the_lock():
    store, nodes = seeded()
    write_job(store, "a")
    write_alloc(store, "a", nodes[0])
    write_eval(store, "a")
    out = []

    def read():
        out.append((store.job_by_id("default", "a").id,
                    len(store.allocs_by_job("default", "a")),
                    len(store.allocs_by_node(nodes[0].id)),
                    len(store.evals_by_job("default", "a")),
                    store.node_by_id(nodes[0].id).id,
                    store.latest_index()))
    with store._lock:                   # a writer in its critical section
        t = threading.Thread(target=read, daemon=True)
        t.start()
        t.join(5)
        assert not t.is_alive()
    assert out == [("a", 1, 1, 1, nodes[0].id, store.latest_index())]


def test_secondary_indexes_are_published_whole():
    """A writer never edits a published id tuple: a reader that fetched
    one keeps a consistent (if old) view, and a snapshot shares it."""
    store, nodes = seeded()
    write_plan(store, "a", nodes[0], n=3)
    before = store._allocs_by_job[("default", "a")]
    snap = store.snapshot()
    assert isinstance(before, tuple) and len(before) == 3
    assert snap._allocs_by_job[("default", "a")] is before
    write_plan(store, "a", nodes[1], n=2)
    after = store._allocs_by_job[("default", "a")]
    assert len(before) == 3 and after[:3] == before and len(after) == 5
    assert len(snap.allocs_by_job("default", "a")) == 3
    store.delete_allocs(list(after[:4]))
    assert store._allocs_by_job[("default", "a")] == after[4:]
    assert len(after) == 5
    store.delete_allocs([after[4]])
    assert ("default", "a") not in store._allocs_by_job
    assert store._allocs_by_node == {}
    assert store.allocs_by_job("default", "a") == []
    # an upsert of a known alloc does not index it twice
    write_alloc(store, "b", nodes[0])
    (a,) = store.allocs_by_job("default", "b")
    store.upsert_allocs([a.copy_skip_job()])
    assert len(store.allocs_by_job("default", "b")) == 1
    assert len(store.allocs_by_node(nodes[0].id)) == 1
    # by eval: an in-place update by a later eval re-homes the alloc,
    # and an alloc of no eval is not indexed
    first = a.eval_id
    assert first and [x.id for x in store.allocs_by_eval(first)] == [a.id]
    again = a.copy_skip_job()
    again.eval_id = "later-eval"
    store.upsert_allocs([again])
    assert store.allocs_by_eval(first) == []
    assert first not in store._allocs_by_eval
    assert store.allocs_by_eval("later-eval")[0] is again
    loose = mock.alloc_for(mock.job(id="c"), nodes[0])
    loose.eval_id = ""
    store.upsert_allocs([loose])
    assert store.allocs_by_eval("") == [] and "" not in store._allocs_by_eval
    store.delete_allocs([again.id, loose.id])
    assert store._allocs_by_eval == {}
    # evals: replaced by id, indexed once, gone with delete_evals
    ev = mock.evaluation(job_id="b")
    store.upsert_evals([ev])
    store.upsert_evals([ev.copy()])
    assert [e.id for e in store.evals_by_job("default", "b")] == [ev.id]
    assert store.evals_by_job("default", "b")[0] is not ev
    store.delete_evals([ev.id])
    assert store.evals_by_job("default", "b") == []
    assert ("default", "b") not in store._evals_by_job


def test_restore_rebuilds_the_indexes():
    from nomad_tpu.raft.fsm import dump_state
    store, nodes = seeded()
    write_plan(store, "a", nodes[0], n=3)
    write_eval(store, "a")
    other = StateStore()
    other.restore_from_snapshot(dump_state(store))
    assert len(other.allocs_by_job("default", "a")) == 3
    assert len(other.allocs_by_node(nodes[0].id)) == 3
    assert len(other.evals_by_job("default", "a")) == 1
    eval_id = store.allocs_by_job("default", "a")[0].eval_id
    assert eval_id and len(other.allocs_by_eval(eval_id)) == 1
    assert all(isinstance(v, tuple)
               for v in other._allocs_by_job.values())


# ---------------------------------------------------------------------------
# (b) keyed watch


WRITES = {
    "alloc": lambda s, j, n: write_alloc(s, j, n),
    "eval": lambda s, j, n: write_eval(s, j),
    "job": lambda s, j, n: write_job(s, j),
    "plan": lambda s, j, n: write_plan(s, j, n),
    "client_update": lambda s, j, n: s.update_allocs_from_client(
        [a.copy_skip_job() for a in s.allocs_by_job("default", j)[:1]]),
    "job_status": lambda s, j, n: s.update_job_status(
        "default", j, "running"),
    "delete_allocs": lambda s, j, n: s.delete_allocs(
        [a.id for a in s.allocs_by_job("default", j)[:1]]),
    "delete_job": lambda s, j, n: s.delete_job("default", j),
}


@pytest.mark.parametrize("kind", sorted(WRITES))
def test_keyed_waiter_wakes_only_on_its_own_jobs_writes(kind):
    store, nodes = seeded()
    for j in ("A", "B"):
        write_job(store, j)
        write_alloc(store, j, nodes[0])
    start = store.latest_index()
    w = Watcher(store, start, keys=job_key("A")).park()
    wakes = store._watch.wakes
    for i in range(50):                 # 200 writes to the other job
        write_job(store, "B")
        write_eval(store, "B")
        write_alloc(store, "B", nodes[i % 2])
        write_plan(store, "B", nodes[1], n=1)
    assert store._watch.wakes == wakes
    assert w.is_alive() and store._watch.parked() == 1
    t0 = time.monotonic()
    WRITES[kind](store, "A", nodes[0])
    w.join(5)
    assert not w.is_alive()
    assert w.returned_at - t0 < 0.1
    assert w.index == store.latest_index() == start + 201
    assert store._watch.wakes == wakes + 1
    assert store._watch.spurious == 0 and store._watch.parked() == 0
    c = metrics.snapshot()["counters"]
    assert c["nomad.state.watch_wakes"] == 1
    assert c["nomad.state.watch_waits"] == 1
    assert "nomad.state.watch_wakes_spurious" not in c


def test_keyed_waiter_honours_its_timeout_and_returns_the_stores_index():
    store, nodes = seeded()
    write_job(store, "A")
    start = store.latest_index()
    w = Watcher(store, start, timeout=0.15, keys=job_key("A")).park()
    write_job(store, "B")
    w.join(5)
    assert not w.is_alive()
    assert 0.14 <= w.took < 1.0
    assert w.index == start + 1         # the store's index, not A's
    assert store._watch.wakes == 0 and store._watch.parked() == 0


def test_a_write_between_reply_and_next_request_is_not_lost():
    """The client's N is the whole store's index of its last reply; the
    registry keeps the index of the last write per key, so the next
    request returns at once when its key was written past N."""
    store, nodes = seeded()
    write_job(store, "A")
    n = store.latest_index()
    write_alloc(store, "A", nodes[0])           # lands before the request
    for _ in range(5):
        write_job(store, "B")
    t0 = time.monotonic()
    assert store.block_until(n, 5.0, keys=job_key("A")) == n + 6
    assert time.monotonic() - t0 < 0.1
    # asked again with the index of that reply: nothing of A's is newer
    t0 = time.monotonic()
    assert store.block_until(n + 6, 0.1, keys=job_key("A")) == n + 6
    assert time.monotonic() - t0 >= 0.09
    assert store._watch.waits == 1 and store._watch.wakes == 0


def test_unknown_and_dropped_keys_answer_early_never_late():
    store, nodes = seeded()
    # never written: waits (for a job about to be registered)
    n = store.latest_index()
    w = Watcher(store, n, keys=job_key("later")).park()
    write_job(store, "other")
    assert w.is_alive()
    write_job(store, "later")
    w.join(5)
    assert not w.is_alive() and w.index == n + 2
    # dropped with its job: a request from before the purge returns at
    # once, one from after it waits
    store.delete_job("default", "later")
    assert ("job", "default", "later") not in store._watch._key_index
    purged = store.latest_index()
    assert store.block_until(purged - 1, 5.0, keys=job_key("later")) \
        == purged
    t0 = time.monotonic()
    store.block_until(purged, 0.05, keys=job_key("later"))
    assert time.monotonic() - t0 >= 0.04
    # a job that still has allocs or evals keeps its key through a purge
    write_job(store, "kept")
    write_alloc(store, "kept", nodes[0])
    store.delete_job("default", "kept")
    assert ("job", "default", "kept") in store._watch._key_index
    store.delete_allocs([a.id for a in
                         store.allocs_by_job("default", "kept")])
    assert ("job", "default", "kept") not in store._watch._key_index
    # a restore forgets every key and wakes every watcher
    from nomad_tpu.raft.fsm import dump_state
    write_job(store, "A")
    w = Watcher(store, store.latest_index(), keys=job_key("A")).park()
    store.restore_from_snapshot(dump_state(store))
    w.join(5)
    assert not w.is_alive()
    assert store._watch._key_index == {}


def test_node_key_is_touched_by_the_nodes_allocs_and_status():
    store, nodes = seeded()
    write_job(store, "A")
    start = store.latest_index()
    key = (("node", nodes[0].id),)
    w = Watcher(store, start, keys=key).park()
    write_alloc(store, "A", nodes[1])
    store.update_node_status(nodes[1].id, "down")
    assert w.is_alive() and store._watch.wakes == 0
    write_alloc(store, "A", nodes[0])
    w.join(5)
    assert not w.is_alive() and w.index == start + 3
    w = Watcher(store, w.index, keys=key).park()
    store.update_node_status(nodes[0].id, "down")
    w.join(5)
    assert not w.is_alive()


# ---------------------------------------------------------------------------
# (c) the lost wake-up


def test_a_write_between_the_check_and_the_wait_still_wakes_the_waiter():
    """The waiter registers and checks under one hold of the watch
    lock, and the writer publishes under the same lock: a write that
    lands after the check cannot be told before the waiter is in its
    wait. Driven by a hook on the check, not by sleeps: the hook starts
    the write and lets the check go on only once the writer stands at
    the watch lock."""
    store, nodes = seeded()
    write_job(store, "A")
    start = store.latest_index()
    reg = store._watch
    real = reg._last_write
    checks = []
    writer = threading.Thread(target=write_alloc,
                              args=(store, "A", nodes[0]), daemon=True)

    def hooked(tables, keys):
        stale = real(tables, keys)
        checks.append(stale)
        if len(checks) == 2:            # the check after registering
            assert reg._by_key      # registered before this check
            writer.start()
            # the writer bumps the index, then blocks on the watch lock,
            # which this thread holds
            wait_until(lambda: store.latest_index() > start,
                       msg="the writer to reach the watch lock")
            assert real(tables, keys) == stale      # not published yet
        return stale
    reg._last_write = hooked
    w = Watcher(store, start, timeout=10.0, keys=job_key("A"))
    w.start()
    w.join(5)
    writer.join(5)
    assert not w.is_alive() and not writer.is_alive()
    assert checks[:2] == [start, start] and checks[-1] == start + 1
    assert w.took < 2.0                 # woken, not timed out
    assert w.index == start + 1
    assert reg.wakes == 1 and reg.spurious == 0


# ---------------------------------------------------------------------------
# (d) index and table waiters


def test_index_waiter_wakes_when_the_index_passes_n_and_not_before():
    store, nodes = seeded()
    start = store.latest_index()
    w = Watcher(store, start + 1).park()        # wait_for_index's form
    write_job(store, "A")                       # index == N: not past it
    assert store._watch.wakes == 0 and w.is_alive()
    write_eval(store, "A")                      # past N
    w.join(5)
    assert not w.is_alive()
    assert w.index == start + 2
    assert store._watch.wakes == 1 and store._watch.spurious == 0
    # already past: returns at once, parks nobody
    assert store.block_until(start, 5.0) == start + 2
    assert store._watch.waits == 1


def test_table_waiter_wakes_on_its_tables_only():
    store, nodes = seeded()
    start = store.latest_index()
    w = Watcher(store, start, tables=("nodes",)).park()
    write_job(store, "A")
    write_alloc(store, "A", nodes[0])
    assert store._watch.wakes == 0 and w.is_alive()
    store.update_node_status(nodes[0].id, "down")
    w.join(5)
    assert not w.is_alive() and w.index == start + 3
    assert store.table_index("nodes") == start + 3
    assert store.block_until(start, 0.05, tables=("evals",)) == start + 3


def test_many_waiters_one_write_wakes_only_the_jobs_own():
    """The drained cell's herd: sixteen watchers, one job each."""
    store, nodes = seeded()
    for i in range(16):
        write_job(store, f"j{i}")
    start = store.latest_index()
    ws = [Watcher(store, start, keys=job_key(f"j{i}")).park()
          for i in range(16)]
    write_plan(store, "j7", nodes[0])
    ws[7].join(5)
    assert not ws[7].is_alive()
    assert store._watch.wakes == 1 and store._watch.parked() == 15
    for i, w in enumerate(ws):
        if i != 7:
            write_eval(store, f"j{i}")
            w.join(5)
            assert not w.is_alive()
    assert store._watch.wakes == 16 and store._watch.spurious == 0


# ---------------------------------------------------------------------------
# (e) the declared lists hold what they say


REPLACE_ON_WRITE = {
    "_index", "_table_index", "_nodes", "_jobs", "_job_versions", "_evals",
    "_allocs", "_deployments", "_node_pools", "_scaling_policies",
    "_namespaces", "_csi_volumes", "_csi_plugins", "_root_keys",
    "_variables", "_acl_roles", "_acl_policies", "_acl_tokens",
    "_acl_bootstrapped", "_scheduler_config", "_allocs_by_job"}
COW_INDEXES = {"_allocs_by_job": "_allocs", "_allocs_by_node": "_allocs",
               "_allocs_by_eval": "_allocs", "_evals_by_job": "_evals"}


def method_ast(name):
    src = textwrap.dedent(inspect.getsource(getattr(StateStore, name)))
    return ast.parse(src).body[0]


def body_of(fn):
    body = fn.body
    if isinstance(body[0], ast.Expr) and isinstance(
            body[0].value, ast.Constant) and isinstance(
                body[0].value.value, str):
        body = body[1:]
    return body


def self_attrs(node):
    return [n.attr for n in ast.walk(node)
            if isinstance(n, ast.Attribute)
            and isinstance(n.value, ast.Name) and n.value.id == "self"]


@pytest.mark.parametrize("name", store_mod.LOCK_FREE_POINT_READS)
def test_point_read_is_one_read_of_one_table(name):
    body = body_of(method_ast(name))
    assert len(body) == 1 and isinstance(body[0], ast.Return), \
        f"{name}: a lock-free point read is one return statement"
    attrs = self_attrs(body[0])
    assert len(attrs) == 1, f"{name} reads {attrs}: one table, once"
    assert attrs[0] in REPLACE_ON_WRITE, attrs
    assert "_lock" not in attrs


@pytest.mark.parametrize("name", store_mod.LOCK_FREE_WALKS)
def test_walk_is_one_index_read_then_point_reads(name):
    body = body_of(method_ast(name))
    assert len(body) == 1 and isinstance(body[0], ast.Return)
    call = body[0].value
    assert isinstance(call, ast.Call) and call.func.id == "_walk"
    index_read, table = call.args
    assert self_attrs(index_read) and len(self_attrs(index_read)) == 1
    index = self_attrs(index_read)[0]
    assert COW_INDEXES[index] == self_attrs(table)[0]
    assert len(self_attrs(table)) == 1


def test_every_other_reader_of_a_table_takes_the_lock():
    """A public method that touches a table without the lock is on one
    of the two lists, or this fails: a later edit cannot quietly add a
    lock-free read."""
    declared = set(store_mod.LOCK_FREE_POINT_READS) | set(
        store_mod.LOCK_FREE_WALKS)
    tables = REPLACE_ON_WRITE | set(COW_INDEXES) | {
        "_scaling_events", "_services", "_acl_tokens_by_secret",
        "alloc_table", "_alloc_deltas"}
    loose = []
    for name, fn in inspect.getmembers(StateStore, inspect.isfunction):
        if name.startswith("_") or name in declared:
            continue
        node = method_ast(name)
        locked = any(
            isinstance(w, ast.With) and any(
                "_lock" in self_attrs(item.context_expr)
                for item in w.items)
            for w in ast.walk(node))
        touched = set(self_attrs(node)) & tables
        # block_until returns the index it reads after the wait
        if touched and not locked and name != "block_until":
            loose.append((name, sorted(touched)))
    assert loose == []
    assert declared <= {n for n, _ in inspect.getmembers(
        StateStore, inspect.isfunction)}


def test_writers_publish_copies_not_edits():
    """What the lock-free reads rest on: a writer that changes a stored
    node, job or alloc publishes a copy and leaves the old object as it
    was."""
    store, nodes = seeded()
    write_job(store, "A")
    write_alloc(store, "A", nodes[0])
    node, job = store.node_by_id(nodes[0].id), store.job_by_id("default", "A")
    (alloc,) = store.allocs_by_job("default", "A")
    store.update_node_status(node.id, "down")
    store.update_node_eligibility(node.id, "ineligible")
    store.update_job_status("default", "A", "running")
    upd = alloc.copy_skip_job()
    upd.client_status = "running"
    store.update_allocs_from_client([upd])
    store.update_alloc_desired_transition([alloc.id])
    stop = alloc.copy_skip_job()
    stop.desired_status = "stop"
    store.upsert_plan_results(PlanResult(node_update={node.id: [stop]}))
    assert (node.status, node.scheduling_eligibility) == \
        ("ready", "eligible")
    assert job.status == "pending"
    assert (alloc.client_status, alloc.desired_status) == ("pending", "run")
    assert not alloc.desired_transition.migrate
    now = store.alloc_by_id(alloc.id)
    assert now is not alloc and now.desired_status == "stop"
    assert store.node_by_id(node.id) is not node
    assert store.job_by_id("default", "A") is not job
