"""Job lifecycle: versions/history, revert, stability, parameterized
dispatch, scaling (reference analogs: nomad/job_endpoint.go Job.GetJobVersions,
Job.Revert, Job.Stable, Job.Dispatch, Job.Scale and the state store's
scaling-policy derivation in UpsertJob)."""
import time

import pytest

from nomad_tpu import mock
from nomad_tpu.server import Server
from nomad_tpu.structs import ParameterizedJobConfig


@pytest.fixture
def server():
    s = Server(num_workers=1, heartbeat_ttl=5.0)
    s.start()
    yield s
    s.shutdown()


def register_versions(server, n=3):
    job = mock.job(id="vjob")
    for i in range(n):
        job2 = mock.job(id="vjob")
        job2.priority = 50 + i
        server.register_job(job2)
    return server.state.job_by_id("default", "vjob")


# -- versions / revert / stability ------------------------------------------

def test_job_versions_accumulate(server):
    register_versions(server, 3)
    versions = server.job_versions("default", "vjob")
    assert [v.version for v in versions] == [2, 1, 0]
    assert versions[0].priority == 52
    assert versions[2].priority == 50


def test_job_revert_creates_new_version(server):
    register_versions(server, 3)
    ev = server.revert_job("default", "vjob", 0)
    assert ev is not None
    job = server.state.job_by_id("default", "vjob")
    assert job.version == 3            # revert is a forward operation
    assert job.priority == 50          # but carries version 0's spec


def test_job_revert_rejects_current_and_missing(server):
    register_versions(server, 2)
    with pytest.raises(ValueError):
        server.revert_job("default", "vjob", 1)   # current version
    with pytest.raises(ValueError):
        server.revert_job("default", "vjob", 99)  # missing version
    with pytest.raises(ValueError):
        server.revert_job("default", "vjob", 0, enforce_prior_version=7)


def test_job_stability(server):
    register_versions(server, 2)
    server.set_job_stability("default", "vjob", 1, True)
    assert server.state.job_version("default", "vjob", 1).stable
    assert server.state.job_by_id("default", "vjob").stable
    server.set_job_stability("default", "vjob", 1, False)
    assert not server.state.job_version("default", "vjob", 1).stable


# -- parameterized dispatch --------------------------------------------------

def make_param_job(server, payload="optional", required=(), optional=()):
    job = mock.job(id="batcher", type="batch")
    job.parameterized = ParameterizedJobConfig(
        payload=payload, meta_required=list(required),
        meta_optional=list(optional))
    ev = server.register_job(job)
    assert ev is None                  # parameterized: no immediate eval
    return job


def test_dispatch_creates_child(server):
    make_param_job(server, required=["input"])
    child, ev = server.dispatch_job("default", "batcher", b"data",
                                    {"input": "s3://x"})
    assert child.parent_id == "batcher"
    assert child.dispatched
    assert child.payload == b"data"
    assert child.meta["input"] == "s3://x"
    assert ev is not None
    assert child.id.startswith("batcher/dispatch-")
    # child is a real job in state
    assert server.state.job_by_id("default", child.id) is not None


def test_dispatch_meta_validation(server):
    make_param_job(server, required=["input"], optional=["opt"])
    with pytest.raises(ValueError):
        server.dispatch_job("default", "batcher", b"", {})      # missing
    with pytest.raises(ValueError):
        server.dispatch_job("default", "batcher", b"",
                            {"input": "x", "bad": "y"})         # unpermitted


def test_dispatch_payload_validation(server):
    make_param_job(server, payload="required")
    with pytest.raises(ValueError):
        server.dispatch_job("default", "batcher", b"", {})
    job2 = mock.job(id="nopay", type="batch")
    job2.parameterized = ParameterizedJobConfig(payload="forbidden")
    server.register_job(job2)
    with pytest.raises(ValueError):
        server.dispatch_job("default", "nopay", b"data", {})


def test_dispatch_idempotency(server):
    make_param_job(server)
    c1, _ = server.dispatch_job("default", "batcher", b"", {},
                                idempotency_token="tok-1")
    c2, ev2 = server.dispatch_job("default", "batcher", b"", {},
                                  idempotency_token="tok-1")
    assert c2.id == c1.id
    assert ev2 is None


def test_dispatch_non_parameterized_rejected(server):
    server.register_job(mock.job(id="plain"))
    with pytest.raises(ValueError):
        server.dispatch_job("default", "plain", b"", {})


# -- scaling -----------------------------------------------------------------

def test_scale_job_updates_count_and_records_event(server):
    job = mock.job(id="scaly")
    job.task_groups[0].scaling = {"min": 1, "max": 10}
    server.register_job(job)
    ev = server.scale_job("default", "scaly", job.task_groups[0].name,
                          count=5, message="scale up")
    assert ev is not None
    assert server.state.job_by_id(
        "default", "scaly").task_groups[0].count == 5
    events = server.state.scaling_events_by_job("default", "scaly")
    assert len(events) == 1
    assert events[0].count == 5 and events[0].message == "scale up"
    assert events[0].eval_id == ev.id


def test_scale_job_bounds_enforced(server):
    job = mock.job(id="scaly")
    tg = job.task_groups[0]
    tg.scaling = {"min": 2, "max": 4}
    server.register_job(job)
    with pytest.raises(ValueError):
        server.scale_job("default", "scaly", tg.name, count=1)
    with pytest.raises(ValueError):
        server.scale_job("default", "scaly", tg.name, count=9)


def test_scale_error_event_only(server):
    job = mock.job(id="scaly")
    server.register_job(job)
    before = job.task_groups[0].count
    ev = server.scale_job("default", "scaly", job.task_groups[0].name,
                          count=None, message="policy error", error=True)
    assert ev is None
    assert server.state.job_by_id(
        "default", "scaly").task_groups[0].count == before
    events = server.state.scaling_events_by_job("default", "scaly")
    assert events[0].error


def test_scaling_policies_derived_from_job(server):
    job = mock.job(id="scaly")
    tg = job.task_groups[0]
    tg.scaling = {"min": 1, "max": 8, "policy": {"cooldown": "1m"}}
    server.register_job(job)
    pols = server.state.scaling_policies_by_job("default", "scaly")
    assert len(pols) == 1
    pol = pols[0]
    assert pol.min == 1 and pol.max == 8
    assert pol.target == {"Namespace": "default", "Job": "scaly",
                          "Group": tg.name}
    assert server.state.scaling_policy_by_id(pol.id) is pol
    # removing the scaling block removes the policy
    job2 = mock.job(id="scaly")
    server.register_job(job2)
    assert server.state.scaling_policies_by_job("default", "scaly") == []


def test_scaling_policies_removed_on_delete(server):
    job = mock.job(id="scaly")
    job.task_groups[0].scaling = {"min": 1, "max": 8}
    server.register_job(job)
    assert server.state.scaling_policies()
    server.state.delete_job("default", "scaly")
    assert server.state.scaling_policies() == []


def test_scaling_events_bounded(server):
    job = mock.job(id="scaly")
    server.register_job(job)
    for i in range(25):
        server.scale_job("default", "scaly", job.task_groups[0].name,
                         count=None, message=f"e{i}", error=True)
    events = server.state.scaling_events_by_job("default", "scaly")
    assert len(events) == 20
    assert events[-1].message == "e24"


# -- fsm snapshot round-trip for the new tables ------------------------------

def test_scaling_state_survives_snapshot_roundtrip(server):
    from nomad_tpu.raft.fsm import dump_state, restore_state
    from nomad_tpu.state import StateStore

    job = mock.job(id="scaly")
    job.task_groups[0].scaling = {"min": 1, "max": 8}
    server.register_job(job)
    server.scale_job("default", "scaly", job.task_groups[0].name,
                     count=3, message="snap")
    blob = dump_state(server.state)
    import json
    blob = json.loads(json.dumps(blob))   # must be json-serializable
    fresh = StateStore()
    restore_state(fresh, blob)
    assert len(fresh.scaling_policies_by_job("default", "scaly")) == 1
    evs = fresh.scaling_events_by_job("default", "scaly")
    assert len(evs) == 1 and evs[0].count == 3
    assert [v.version for v in
            fresh.job_versions_by_id("default", "scaly")] == [1, 0]


# -- HTTP surface ------------------------------------------------------------

@pytest.fixture
def agent():
    from nomad_tpu.api.http import HttpServer
    s = Server(num_workers=1, heartbeat_ttl=5.0)
    s.start()
    http = HttpServer(s, port=0)
    http.start()
    from nomad_tpu.api.client import ApiClient
    yield s, ApiClient(f"http://127.0.0.1:{http.port}")
    http.shutdown()
    s.shutdown()


def test_http_versions_revert_scale_dispatch(agent):
    server, api = agent
    register_versions(server, 2)
    versions = api.job_versions("vjob")["versions"]
    assert [v["version"] for v in versions] == [1, 0]

    reply = api.revert_job("vjob", 0)
    assert reply["eval_id"]
    assert api.job("vjob")["version"] == 2

    api.stabilize_job("vjob", 2)
    assert api.job("vjob")["stable"] is True

    # scaling over HTTP
    job = mock.job(id="scaly")
    job.task_groups[0].scaling = {"min": 1, "max": 10}
    server.register_job(job)
    reply = api.scale_job("scaly", job.task_groups[0].name, 4, "more")
    assert reply["eval_id"]
    status = api.job_scale_status("scaly")
    tg_status = status["task_groups"][job.task_groups[0].name]
    assert tg_status["desired"] == 4
    assert tg_status["events"][0]["message"] == "more"
    pols = api.scaling_policies(job="scaly")
    assert len(pols) == 1 and pols[0]["max"] == 10
    assert api.scaling_policy(pols[0]["id"])["job_id"] == "scaly"

    # dispatch over HTTP
    pjob = mock.job(id="batcher", type="batch")
    pjob.parameterized = ParameterizedJobConfig(meta_required=["k"])
    server.register_job(pjob)
    reply = api.dispatch_job("batcher", b"payload", {"k": "v"})
    assert reply["dispatched_job_id"].startswith("batcher/dispatch-")
    child = server.state.job_by_id("default", reply["dispatched_job_id"])
    assert child.payload == b"payload"

    # bad dispatch -> 400
    from nomad_tpu.api.client import ApiError
    with pytest.raises(ApiError):
        api.dispatch_job("batcher", b"", {})


# -- review-hardening regressions -------------------------------------------

def test_revert_resets_stability(server):
    register_versions(server, 2)
    server.set_job_stability("default", "vjob", 0, True)
    server.revert_job("default", "vjob", 0)
    job = server.state.job_by_id("default", "vjob")
    assert job.version == 2
    assert job.stable is False       # must re-earn stability


def test_stability_unknown_version_rejected(server):
    register_versions(server, 1)
    with pytest.raises(ValueError):
        server.set_job_stability("default", "vjob", 42, True)
    with pytest.raises(ValueError):
        server.set_job_stability("default", "missing", 0, True)


def test_dispatch_idempotency_is_namespace_scoped(server):
    from nomad_tpu.structs import Namespace
    server.upsert_namespace(Namespace(name="other"))
    for ns in ("default", "other"):
        job = mock.job(id="etl", type="batch")
        job.namespace = ns
        job.parameterized = ParameterizedJobConfig()
        server.register_job(job)
    c1, _ = server.dispatch_job("default", "etl", b"", {},
                                idempotency_token="t1")
    c2, _ = server.dispatch_job("other", "etl", b"", {},
                                idempotency_token="t1")
    assert c1.namespace == "default" and c2.namespace == "other"
    assert c1.id != c2.id or c1.namespace != c2.namespace


def test_malformed_scaling_rejected_at_admission(server):
    job = mock.job(id="badscale")
    job.task_groups[0].scaling = {"min": "abc"}
    with pytest.raises(ValueError):
        server.register_job(job)
    assert server.state.job_by_id("default", "badscale") is None


def test_scale_events_attributed_to_group(server):
    job = mock.job(id="scaly")
    from nomad_tpu.structs import TaskGroup, Task, Resources
    import copy
    tg2 = copy.deepcopy(job.task_groups[0])
    tg2.name = "second"
    job.task_groups.append(tg2)
    server.register_job(job)
    g1 = job.task_groups[0].name
    server.scale_job("default", "scaly", g1, count=3, message="g1 up")
    status = server.job_scale_status("default", "scaly")
    assert len(status["task_groups"][g1]["events"]) == 1
    assert status["task_groups"]["second"]["events"] == []


def test_raft_replicates_stability_and_scaling_events(tmp_path):
    """update_job_stability/upsert_scaling_event must flow through raft
    so followers converge (regression: they bypassed the proposal path)."""
    from nomad_tpu.server.cluster import make_cluster, wait_for_leader

    servers = make_cluster(3)
    try:
        leader = wait_for_leader(servers)
        job = mock.job(id="repl")
        leader.register_job(job)
        leader.scale_job("default", "repl", job.task_groups[0].name,
                         count=None, message="audit", error=True)
        leader.set_job_stability("default", "repl", 0, True)

        def converged():
            for s in servers:
                evs = s.store.scaling_events_by_job("default", "repl")
                jv = s.store.job_version("default", "repl", 0)
                if not evs or jv is None or not jv.stable:
                    return False
            return True
        deadline = time.time() + 10
        while time.time() < deadline and not converged():
            time.sleep(0.1)
        assert converged(), "followers did not converge"
    finally:
        for s in servers:
            s.shutdown()


# -- blocking queries wake on their own job's or node's writes only ---------

class _BlockingGet:
    """One `GET <path>?index=N&wait=...` on a thread of its own."""

    def __init__(self, api, path):
        import json
        import threading
        import urllib.request
        self.body = self.index = self.returned_at = None

        def run():
            with urllib.request.urlopen(api.address + path,
                                        timeout=30) as r:
                self.body = json.loads(r.read())
                self.index = int(r.headers["X-Nomad-Index"])
            self.returned_at = time.monotonic()
        self.thread = threading.Thread(target=run, daemon=True)
        self.thread.start()

    def done(self, timeout):
        self.thread.join(timeout)
        return not self.thread.is_alive()


def _parked(server, n=1, timeout=10.0):
    deadline = time.monotonic() + timeout
    while server.state._watch.parked() < n:
        assert time.monotonic() < deadline, "the query never parked"
        time.sleep(0.005)


def _write_other_job(state, node, job_id="other"):
    from nomad_tpu.structs import PlanResult
    job = mock.job(id=job_id)
    state.upsert_job(job)
    state.upsert_evals([mock.evaluation(job_id=job_id)])
    state.upsert_plan_results(PlanResult(node_allocation={
        node.id: [mock.alloc_for(job, node, i) for i in range(2)]}))
    state.update_job_status("default", job_id, "running")


def test_http_job_summary_blocks_until_its_own_jobs_plan_commit(agent):
    from nomad_tpu.structs import PlanResult
    server, api = agent
    state = server.state
    nodes = [mock.node(), mock.node()]
    for n in nodes:
        state.upsert_node(n)
    job = mock.job(id="A")
    state.upsert_job(job)           # no eval: nothing schedules it
    start = state.latest_index()
    get = _BlockingGet(api, f"/v1/job/A/summary?index={start}&wait=2s")
    _parked(server)
    wakes = state._watch.wakes
    _write_other_job(state, nodes[1])
    assert not get.done(0.1)        # four writes to another job: asleep
    assert state._watch.wakes == wakes
    t0 = time.monotonic()
    state.upsert_plan_results(PlanResult(node_allocation={
        nodes[0].id: [mock.alloc_for(job, nodes[0], i) for i in range(3)]}))
    assert get.done(5)
    assert get.returned_at - t0 < 0.2
    tg = job.task_groups[0].name
    assert get.body["summary"][tg]["starting"] == 3
    assert get.index == state.latest_index() > start
    # asked again with that reply's index: A has nothing newer, the
    # other job's writes do not count, and `wait` runs out
    get = _BlockingGet(api, f"/v1/job/A/summary?index={get.index}&wait=0.3s")
    _parked(server)
    _write_other_job(state, nodes[1], "other2")
    assert get.done(5)
    assert get.body["summary"][tg]["starting"] == 3
    assert get.index == state.latest_index()


@pytest.mark.parametrize("route", ["", "/allocations", "/evaluations",
                                   "/deployment"])
def test_http_job_routes_wake_on_the_jobs_key(agent, route):
    server, api = agent
    state = server.state
    node = mock.node()
    state.upsert_node(node)
    state.upsert_job(mock.job(id="A"))
    start = state.latest_index()
    get = _BlockingGet(api, f"/v1/job/A{route}?index={start}&wait=5s")
    _parked(server)
    _write_other_job(state, node)
    assert not get.done(0.05)
    t0 = time.monotonic()
    state.upsert_evals([mock.evaluation(job_id="A")])
    assert get.done(5) and get.returned_at - t0 < 0.2
    assert get.index == state.latest_index()


def test_http_node_allocations_block_by_node(agent):
    server, api = agent
    state = server.state
    mine, other = mock.node(), mock.node()
    state.upsert_node(mine)
    state.upsert_node(other)
    job = mock.job(id="A")
    state.upsert_job(job)
    start = state.latest_index()
    get = _BlockingGet(
        api, f"/v1/node/{mine.id}/allocations?index={start}&wait=2s")
    _parked(server)
    wakes = state._watch.wakes
    _write_other_job(state, other)
    state.upsert_allocs([mock.alloc_for(job, other)])
    assert not get.done(0.1)
    assert state._watch.wakes == wakes
    t0 = time.monotonic()
    state.upsert_allocs([mock.alloc_for(job, mine)])
    assert get.done(5)
    assert get.returned_at - t0 < 0.2
    assert len(get.body["allocs"]) == 1
    assert get.index == get.body["index"] == state.latest_index() > start
    # a list route keeps waking on any write
    get = _BlockingGet(api, f"/v1/node/pools?index={get.index}&wait=5s")
    _parked(server)
    t0 = time.monotonic()
    state.upsert_job(mock.job(id="B"))
    assert get.done(5) and get.returned_at - t0 < 0.2
