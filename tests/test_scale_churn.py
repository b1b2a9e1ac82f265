"""Sustained-churn pipeline (ISSUE 6): the reduced-shape tier-1 smoke
runs the served pipeline's churn drive
(benchkit.run_scale_churn: Server + BatchWorker coalescing + group
commit + flap damper + watermark GC + table compaction + incremental
fold parity, allocations HELD live while arrivals/completions/flaps
churn); the full ~2M-live run is the same call at the ROADMAP shape,
marked slow -- mirroring test_scale_northstar's split.
"""
import pytest

from nomad_tpu.benchkit import run_scale_churn


def test_churn_smoke_holds_live_and_stays_bounded(monkeypatch):
    """A small sustained-churn run: live count held at target through
    arrivals/completions/flaps, terminal state bounded by the GC
    watermark, incremental-memo parity 0, and nothing truncated."""
    monkeypatch.setenv("NOMAD_TPU_FLAP_THRESHOLD", "2")
    monkeypatch.setenv("NOMAD_TPU_FLAP_BASE_S", "0.3")
    monkeypatch.setenv("NOMAD_TPU_FLAP_MAX_S", "0.6")
    out = run_scale_churn(1000, n_nodes=50, e_evals=4, per_eval=50,
                          rounds=4, churn_jobs=2, flap_nodes=2,
                          round_timeout_s=120.0, gc_watermark=300)
    assert out["truncated"] is False
    assert out["live_allocs"] == 1000          # held, not accumulated
    # completions can exceed the nominal count: a flap-replaced alloc
    # leaves BOTH its lost row and its replacement behind in the job
    assert out["arrivals"] == 400 and out["completions"] >= 400
    assert out["flaps"] >= 2                   # damper may defer some
    assert out["parity_mismatch"] == 0
    # bounded state: the watermark GC kept terminal history in check
    assert out["terminal_allocs"] <= out["gc_watermark"]
    assert out["submit_commit_p50_ms"] > 0
    assert out["submit_commit_p99_ms"] >= out["submit_commit_p50_ms"]
    # RSS sampled per round and not exploding across churn rounds (the
    # leak signal; a tiny allowance covers allocator noise at smoke
    # scale)
    assert len(out["rss_mb_rounds"]) == 5
    assert out["rss_growth_mb"] < 200


def test_churn_smoke_quarantine_engages(monkeypatch):
    """Flapping the same nodes every round must trip the flap damper:
    at least one recovery deferred by quarantine."""
    monkeypatch.setenv("NOMAD_TPU_FLAP_THRESHOLD", "2")
    monkeypatch.setenv("NOMAD_TPU_FLAP_BASE_S", "0.5")
    monkeypatch.setenv("NOMAD_TPU_FLAP_MAX_S", "1.0")
    out = run_scale_churn(400, n_nodes=20, e_evals=2, per_eval=40,
                          rounds=4, churn_jobs=1, flap_nodes=2,
                          round_timeout_s=120.0)
    assert out["truncated"] is False
    assert out["quarantine_deferrals"] >= 1
    assert out["parity_mismatch"] == 0


@pytest.mark.slow
def test_churn_full_scale_two_million_live():
    """The ROADMAP number under churn: ~2M live allocations HELD while
    the pipeline sustains arrivals, completions and node flaps, with
    parity 0 and RSS bounded across rounds."""
    out = run_scale_churn(2_048_000, n_nodes=10000, e_evals=32,
                          per_eval=2000, rounds=6, churn_jobs=4,
                          flap_nodes=4, round_timeout_s=600.0)
    assert out["truncated"] is False
    assert out["live_allocs"] >= 2_000_000
    assert out["parity_mismatch"] == 0
    rss = out["rss_mb_rounds"]
    # bounded, not monotonic: the last round must not sit more than 10%
    # above the first churn round
    assert rss[-1] <= rss[0] * 1.10
