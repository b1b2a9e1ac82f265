"""Scheduler Quality & Saturation Observatory (ISSUE 7).

Gates: (1) the delta-journal placement accounting stays bitwise-
consistent with a wholesale recompute under churn (upsert / client-ack
/ GC-delete cycles), triangulated against the alloc table's own
incremental fold; (2) the shadow-oracle audit is deterministic (same
eval-id sample + verdicts across two identical runs) and CLEAN on a
healthy solver; (3) an injected solver fault (``quality.skew``) makes
the drift gauge fire and the breaker-style alert latch (chaos drill);
(4) ``NOMAD_TPU_QUALITY=0`` restores the prior path bit-for-bit;
(5) the span-stream saturation attribution sees every pipeline stage;
(6) all surfaces serve the data (HTTP operator endpoint,
/v1/metrics block + prometheus p99, the observatory's report).
"""
import json
import time
import urllib.request

import numpy as np
import pytest

from nomad_tpu import mock
from nomad_tpu.faultinject import faults
from nomad_tpu.server import Server
from nomad_tpu.server.quality import (
    _replay_lane, observatory, quality_enabled,
)
from nomad_tpu.server.telemetry import metrics
from nomad_tpu.structs import SchedulerConfiguration
from nomad_tpu.structs.job import reseed_ids


def wait_until(cond, timeout=15.0, interval=0.03, msg="condition"):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if cond():
            return
        time.sleep(interval)
    raise AssertionError(f"timeout waiting for {msg}")


@pytest.fixture(autouse=True)
def _quality_env(monkeypatch):
    """Audit every solved eval (the deterministic hash sampler is
    exercised separately) and start from a clean observatory."""
    monkeypatch.setenv("NOMAD_TPU_QUALITY_AUDIT_SAMPLE", "1.0")
    metrics.reset()
    yield
    faults._reset_for_tests()
    observatory._reset_for_tests()


def make_server(workers=2, batching=True):
    """batching=False + workers=1 is the DETERMINISTIC surface: one
    worker, solo dispatches -- cross-run placement comparisons are only
    valid there (the concurrent BatchWorker path places
    nondeterministically: dequeue order -> generation composition)."""
    server = Server(num_workers=workers, heartbeat_ttl=3600.0,
                    eval_batching=batching, batch_width=workers)
    server.state.set_scheduler_config(
        SchedulerConfiguration(scheduler_algorithm="tpu-binpack"))
    server.start()
    return server


def add_fleet(server, n, cpu=8000, mem=16384):
    for i in range(n):
        node = mock.node()
        node.id = f"q-node-{i:03d}"
        node.node_resources.cpu.cpu_shares = cpu
        node.node_resources.memory.memory_mb = mem
        node.compute_class()
        server.register_node(node)


def place_job(server, job_id, count=8, cpu=100, mem=64):
    job = mock.job(id=job_id)
    tg = job.task_groups[0]
    tg.count = count
    tg.tasks[0].resources.cpu = cpu
    tg.tasks[0].resources.memory_mb = mem
    server.register_job(job)
    wait_until(
        lambda: sum(1 for a in server.state.allocs_by_job(
            job.namespace, job.id) if a.desired_status == "run") >= count,
        msg=f"{job_id} placed")
    return job


def placements_of(server, job):
    return {a.name: a.node_id
            for a in server.state.allocs_by_job(job.namespace, job.id)
            if a.desired_status == "run"}


# ---------------------------------------------------------------------------
# 1. incremental-vs-wholesale quality parity under churn
# ---------------------------------------------------------------------------

def test_placement_accounting_parity_under_churn():
    server = make_server()
    try:
        add_fleet(server, 6)
        jobs = [place_job(server, f"q-churn-{i}") for i in range(3)]

        # churn: the oldest job completes (deregister -> stop evals ->
        # client acks terminal), a new one arrives, terminal rows GC
        leaving = jobs.pop(0)
        server.deregister_job(leaving.namespace, leaving.id)
        wait_until(
            lambda: all(a.desired_status != "run"
                        for a in server.state.allocs_by_job(
                            leaving.namespace, leaving.id)),
            msg="stops applied")
        import copy
        acks = []
        for a in server.state.allocs_by_job(leaving.namespace, leaving.id):
            upd = copy.copy(a)
            upd.client_status = "complete"
            upd.client_terminal_time = time.time()
            acks.append(upd)
        server.update_allocs_from_client(acks)
        jobs.append(place_job(server, "q-churn-new"))
        server.run_gc_once(threshold=0.0)

        acct = observatory.placement
        churn = dict(acct._churn)
        assert churn["placements"] >= 32          # 4 jobs x 8
        assert churn["stops"] >= 8
        assert churn["completions"] >= 8

        # triangulation BEFORE the parity pass replaces the resident
        # state: delta-journal accounting == alloc-table incremental
        # fold (cpu/mem/disk per node, live filter)
        with acct._lock:
            mine = {nid: tuple(v[:3]) for nid, v in acct._used.items()
                    if any(abs(x) > 1e-9 for x in v[:3])}
        table = {nid: v for nid, v
                 in server.state.quality_usage_by_node().items()
                 if any(abs(x) > 1e-9 for x in v)}
        assert set(mine) == set(table)
        for nid in mine:
            assert mine[nid] == pytest.approx(table[nid], abs=1e-6)

        # the wholesale parity gate itself: mismatch must be 0
        assert acct.parity_mismatch(server.state) == 0

        report = acct.report(server.state)
        assert report["attached"]
        assert 0.0 <= report["fragmentation_index"] <= 1.0
        assert sum(report["utilization"]["cpu"]["hist"]) == \
            report["fleet"]["nodes"]
        assert report["fleet"]["live_allocs"] == len(
            [a for a in server.state.allocs()
             if not a.client_terminal_status()])
    finally:
        server.shutdown()


def test_accounting_survives_structured_delta_gaps():
    """A delta-less alloc write (snapshot restore) marks the state
    uncoverable; the next read rebuilds wholesale instead of serving
    stale numbers."""
    server = make_server()
    try:
        add_fleet(server, 3)
        place_job(server, "q-gap", count=4)
        # a raw delta-less bump on the allocs table
        with server.state._lock:
            server.state._bump("allocs")
        assert observatory.placement._needs_rebuild
        report = observatory.placement.report(server.state)
        assert report["fleet"]["live_allocs"] == 4
        assert observatory.placement.parity_mismatch(server.state) == 0
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# 2. shadow-oracle audit: clean + deterministic
# ---------------------------------------------------------------------------

def _run_audited_world(tag):
    reseed_ids(0xC0FFEE)          # identical id stream across runs
    server = make_server(workers=1, batching=False)
    try:
        add_fleet(server, 5)
        job = place_job(server, f"q-audit-{tag}", count=12)
        assert observatory.audit.wait_idle(timeout=20.0)
        results = observatory.audit.results()
        report = observatory.audit.report()
        placed = placements_of(server, job)
    finally:
        server.shutdown()
    return results, report, placed


def test_shadow_audit_clean_and_deterministic():
    res1, rep1, placed1 = _run_audited_world("a")
    assert rep1["audited"] >= 1, rep1
    # healthy solver: host replay agrees bit-for-bit (float64 CPU path)
    assert rep1["decision_mismatch_total"] == 0, rep1
    assert rep1["score_drift_max"] <= 1e-6, rep1
    assert rep1["alert"] is None

    res2, rep2, placed2 = _run_audited_world("a")
    # determinism: same eval-id sample, same verdicts, same placements
    assert set(res1) == set(res2)
    for eid in res1:
        assert res1[eid]["score_drift"] == res2[eid]["score_drift"]
        assert res1[eid]["decision_mismatches"] == \
            res2[eid]["decision_mismatches"]
    assert placed1 == placed2


def test_audit_sampling_is_deterministic_hash(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_QUALITY_AUDIT_SAMPLE", "0.5")
    wants = [observatory.audit.wants(f"eval-{i}") for i in range(200)]
    assert wants == [observatory.audit.wants(f"eval-{i}")
                     for i in range(200)]
    assert 40 < sum(wants) < 160          # roughly the asked rate
    monkeypatch.setenv("NOMAD_TPU_QUALITY_AUDIT_SAMPLE", "0")
    assert not observatory.audit.wants("eval-0")


def test_replay_lane_mirrors_kernel_semantics():
    """Unit gate on the numpy mirror: best-fit pick, anti-affinity
    divisor, usage carry, limit window."""
    from nomad_tpu.server.quality import _AuditItem

    item = _AuditItem()
    item.eval_id = "unit"
    item.job_id = "unit"
    item.tg_name = "web"
    item.node_ids = ("n0", "n1", "n2")
    item.order = np.arange(3, dtype=np.int64)
    item.cpu_cap = np.array([1000.0, 1000.0, 1000.0])
    item.mem_cap = np.array([1000.0, 1000.0, 1000.0])
    item.disk_cap = np.array([1000.0, 1000.0, 1000.0])
    item.feasible = np.array([True, True, False])
    item.used_cpu = np.array([0.0, 500.0, 0.0])
    item.used_mem = np.array([0.0, 500.0, 0.0])
    item.used_disk = np.zeros(3)
    item.placed = np.zeros(3)
    item.ask_cpu = item.ask_mem = 100.0
    item.ask_disk = 0.0
    item.count = 2
    item.limit = 2
    item.spread_alg = False
    item.chosen = np.array([1, 0], dtype=np.int64)
    item.scores = np.zeros(2)

    chosen, scores = _replay_lane(item)
    # best-fit: the half-full node 1 wins place 0; its anti-affinity
    # penalty then makes empty node 0 win place 1
    assert chosen.tolist() == [1, 0]
    assert scores[0] > 0
    # re-score pass follows the given choices and reports their scores
    follow, fscores = _replay_lane(item, follow=item.chosen)
    assert follow.tolist() == [1, 0]
    assert fscores[0] == pytest.approx(scores[0])


# ---------------------------------------------------------------------------
# 3. chaos drill: injected solver fault -> drift gauge + alert
# ---------------------------------------------------------------------------

@pytest.mark.chaos
def test_audit_drift_fires_on_injected_solver_fault(monkeypatch):
    monkeypatch.setenv("NOMAD_TPU_QUALITY_ALERT_AFTER", "1")
    faults.arm("quality.skew", "error")
    server = make_server()
    try:
        add_fleet(server, 5)
        place_job(server, "q-skew", count=12)
        assert observatory.audit.wait_idle(timeout=20.0)
        rep = observatory.audit.report()
        assert rep["audited"] >= 1
        # the +0.25 score corruption is far past the drift tolerance
        assert rep["score_drift_max"] > 0.2, rep
        assert rep["alert"] is not None, rep
        assert rep["alert"]["reason"] == "score_drift"
        snap = metrics.snapshot()
        assert snap["counters"].get("nomad.quality.audit_alert", 0) >= 1
        drift = snap["gauges"].get("nomad.quality.score_drift")
        assert drift and drift["max"] > 0.2
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# 4. kill switch: prior path bit-for-bit
# ---------------------------------------------------------------------------

def _run_world_for_killswitch():
    # the deterministic surface (1 worker, solo dispatch): cross-run
    # placement equality is only meaningful there
    reseed_ids(0xBEEF)
    server = make_server(workers=1, batching=False)
    try:
        add_fleet(server, 5)
        job = place_job(server, "q-kill", count=10)
        return placements_of(server, job), server.state._quality_hook
    finally:
        server.shutdown()


def test_killswitch_restores_prior_path(monkeypatch):
    placed_on, hook_on = _run_world_for_killswitch()
    assert hook_on is not None

    monkeypatch.setenv("NOMAD_TPU_QUALITY", "0")
    assert not quality_enabled()
    placed_off, hook_off = _run_world_for_killswitch()
    # the store hook is never installed and the observatory reports
    # disabled -- and placements are bit-for-bit identical
    assert hook_off is None
    assert observatory.report() == {"enabled": False}
    assert placed_off == placed_on

    monkeypatch.delenv("NOMAD_TPU_QUALITY")
    placed_on2, _ = _run_world_for_killswitch()
    assert placed_on2 == placed_on


# ---------------------------------------------------------------------------
# 5. saturation attribution
# ---------------------------------------------------------------------------

def test_saturation_sees_pipeline_stages():
    server = make_server()
    try:
        add_fleet(server, 4)
        place_job(server, "q-sat", count=8)
        rep = observatory.saturation.report()
        stages = rep["stages"]
        for stage in ("worker", "commit"):
            assert stage in stages, stages.keys()
            assert stages[stage]["count"] >= 1
            assert stages[stage]["kind"] == "busy"
        assert rep["bottleneck"] in stages
        for d in stages.values():
            assert d["total_ms"] >= 0.0
            assert d["littles_l"] >= 0.0
        # the tax decomposition shares sum to ~100% of recorded time
        assert sum(d["share_of_recorded_pct"]
                   for d in stages.values()) == pytest.approx(100.0,
                                                              abs=1.0)

        full = observatory.report()
        assert full["enabled"]
        assert "fragmentation_index" in full["placement"]
        assert "score_drift_max" in full["audit"]
        assert all("busy_pct" in d
                   for d in full["saturation"]["stages"].values())
    finally:
        server.shutdown()


# ---------------------------------------------------------------------------
# 6. surfaces: HTTP operator endpoint, /v1/metrics, prometheus
# ---------------------------------------------------------------------------

def _get(port, path):
    with urllib.request.urlopen(
            f"http://127.0.0.1:{port}{path}", timeout=10) as resp:
        body = resp.read()
    return body


def test_http_surfaces():
    from nomad_tpu.api.http import HttpServer

    server = make_server()
    http = HttpServer(server, port=0)
    http.start()
    try:
        add_fleet(server, 4)
        place_job(server, "q-http", count=6)
        observatory.audit.wait_idle(timeout=20.0)

        rep = json.loads(_get(http.port, "/v1/operator/quality"))
        assert rep["enabled"] and rep["attached"]
        assert rep["placement"]["fleet"]["live_allocs"] >= 6
        assert "score_drift_max" in rep["audit"]
        assert "stages" in rep["saturation"]

        m = json.loads(_get(http.port, "/v1/metrics"))
        q = m["quality"]
        assert q["enabled"]
        assert "fragmentation_index" in q
        # the report feeds the gauge series: p50/p99 render on the
        # JSON surface for the quality gauges
        frag = m["gauges"].get("nomad.quality.fragmentation")
        assert frag is None or "p99" in frag

        text = _get(http.port, "/v1/metrics?format=prometheus").decode()
        # satellite: p99 renders on the prometheus surface too
        assert "_p99_ms" in text or "_p99 " in text
    finally:
        http.shutdown()
        server.shutdown()
