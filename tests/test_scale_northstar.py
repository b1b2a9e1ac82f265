"""North-star-scale pipeline (ISSUE 5): the reduced-shape tier-1 smoke
runs the served pipeline's north-star drive
(benchkit.run_scale_northstar: Server + BatchWorker coalescing +
SolveBarrier fused dispatch + group-commit applier, allocations
accumulating LIVE across rounds with no drain); the full ~2M-alloc run
is the same call at the ROADMAP shape, marked slow."""
import pytest

from nomad_tpu.benchkit import run_scale_northstar
from nomad_tpu.server.telemetry import metrics


def _run(target, **kw):
    before = metrics.snapshot()["counters"]
    out = run_scale_northstar(target, **kw)
    after = metrics.snapshot()["counters"]
    return out, before, after


def test_northstar_smoke_accumulates_live_allocs():
    """A few thousand allocs through the accumulating pipeline: every
    round's placements land, nothing is drained between rounds, and the
    group-commit applier actually batched plans along the way."""
    out, before, after = _run(2000, n_nodes=100, e_evals=8, per_eval=50,
                              round_timeout_s=120.0)
    assert out["truncated"] is False
    assert out["allocs"] >= 2000
    assert out["placements_per_sec"] > 0
    assert out["rss_mb"] > 0
    # the smoke exercises the batched pipeline, not a degenerate
    # serial path: at least one multi-plan group committed
    batch = metrics.snapshot()["gauges"].get("nomad.plan.batch_size")
    assert batch is not None and batch["max"] >= 2


def test_northstar_smoke_truncation_is_flagged():
    """An impossible target (capacity-starved fleet) must report
    truncated=True instead of publishing a short count as complete."""
    out = run_scale_northstar(400, n_nodes=2, e_evals=2, per_eval=100,
                              round_timeout_s=10.0)
    # 2 nodes provisioned for ~200 allocs x 1.4 headroom: the second
    # round cannot fully place
    if out["allocs"] < 400:
        assert out["truncated"] is True


@pytest.mark.slow
def test_northstar_full_scale_two_million():
    """The ROADMAP number, actually executed: >= 2M live allocations
    placed through the batched pipeline, throughput and memory ceiling
    measured (the bench records the same via scale_* fields)."""
    target = 2_048_000
    out, _, _ = _run(target, n_nodes=10000, e_evals=32, per_eval=2000,
                     round_timeout_s=600.0)
    assert out["truncated"] is False
    assert out["allocs"] >= 2_000_000
    assert out["placements_per_sec"] > 0
