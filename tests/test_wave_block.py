"""Run-block wavefront kernel: bit-parity with the per-placement
compact scan (reference semantics: rank.go:205 BinPackIterator +
select.go MaxScoreIterator; the run-block shortcut and its equivalence
argument are documented at solver/binpack.py _solve_wave_block_impl).

The fuzz constructs synthetic compact tables directly (capacities down
to 1 force dense saturation/refill chains; huge prior collision counts
with tiny job counts drive scores negative to engage the skip/fallback
machinery and both threshold-crossing directions), then asserts the two
kernels' (chosen, scores, n_yielded) are identical elementwise."""
from functools import partial

import numpy as np
import pytest

from nomad_tpu.solver import binpack
from nomad_tpu.solver.binpack import (
    _solve_wave_block_impl, _solve_wave_compact_impl)


def _make_case(rng, C, B):
    compact = np.zeros((C, 8), dtype=np.float32)
    compact[:, 7] = -1.0
    n_fit = rng.integers(0, C + 1)
    ask = float(rng.choice([250.0, 500.0, 1000.0]))
    if n_fit:
        caps = rng.integers(1, 9, size=n_fit).astype(np.float32)
        cpu_cap = rng.choice([2000.0, 4000.0, 8000.0], size=n_fit)
        compact[:n_fit, 0] = np.minimum(
            caps, np.maximum(cpu_cap // ask, 1.0))
        compact[:n_fit, 1] = rng.integers(0, 3, size=n_fit) * ask
        compact[:n_fit, 2] = rng.integers(0, 3, size=n_fit) * 128.0
        compact[:n_fit, 3] = cpu_cap
        compact[:n_fit, 4] = cpu_cap * 2
        compact[:n_fit, 5] = rng.choice(
            [0.0, 0.0, 0.0, 1.0, 2.0, 50.0], size=n_fit)
        compact[:n_fit, 6] = rng.choice(
            [0.0, 0.0, 0.5, -0.25, 1.0, -1.0], size=n_fit)
        compact[:n_fit, 7] = rng.permutation(C)[:n_fit].astype(np.float32)
    count = float(rng.choice([1.0, 4.0, 30.0, 2000.0]))
    return compact, np.array([ask, 128.0, count], dtype=np.float32)


@pytest.mark.parametrize("spread_alg", [False, True])
@pytest.mark.parametrize("C,B,K,L,INNER",
                         [(40, 8, 4, 5, 64), (160, 32, 32, 14, 64),
                          (96, 32, 8, 3, 64), (360, 128, 32, 100, 64),
                          # the CPU-production shape (binpack.py
                          # _wave_block_shape non-TPU default)
                          (160, 32, 16, 14, 32)])
def test_block_matches_classic_fuzz(C, B, K, L, INNER, spread_alg):
    """spread_alg=True is the worst-fit scoring mode (falling score
    streams: runs end by losing to the runner-up instead of by
    saturation) -- a different stop-condition mix than best-fit, and a
    shipped default-on path of the gate."""
    import jax
    P = C - B
    classic = jax.jit(partial(_solve_wave_compact_impl, sp=None,
                              spread_alg=spread_alg,
                              dtype_name="float32", B=B))
    block = jax.jit(partial(_solve_wave_block_impl,
                            spread_alg=spread_alg,
                            dtype_name="float32", B=B, K=K,
                            INNER=INNER))
    for seed in range(12):
        rng = np.random.default_rng(seed * 7919 + C)
        compact, scal_f = _make_case(rng, C, B)
        n_active = int(rng.integers(1, P + 1))
        scal_i = np.array([L, n_active], dtype=np.int32)
        pen = np.full(P, -1, dtype=np.int32)
        c0 = [np.asarray(x) for x in classic(compact, scal_f, scal_i,
                                             pen)]
        c1 = [np.asarray(x) for x in block(compact, scal_f, scal_i,
                                           pen)]
        for name, a, b in zip(("chosen", "scores", "ny"), c0, c1):
            bad = np.nonzero(np.asarray(a != b))[0]
            assert not len(bad), (
                f"seed {seed} n_active {n_active}: {name} diverges at "
                f"{bad[:5]}: classic {a[bad[:5]]} block {b[bad[:5]]}")


def _pack_real_lane(kind, count, penalties=None, n_nodes=12):
    from nomad_tpu import mock
    from nomad_tpu.scheduler import Harness
    from nomad_tpu.scheduler.context import EvalContext
    from nomad_tpu.scheduler.reconcile import AllocPlaceResult
    from nomad_tpu.solver.service import TpuPlacementService
    from nomad_tpu.structs import Plan, Spread

    h = Harness()
    nodes = []
    for i in range(n_nodes):
        n = mock.node()
        n.id = f"wb-node-{i:04d}"
        n.meta["rack"] = f"rack-{i % 3}"
        n.compute_class()
        nodes.append(n)
        h.state.upsert_node(n)
    job = mock.job(id=f"wb-job-{kind}")
    tg = job.task_groups[0]
    tg.count = count
    if kind == "spread":
        tg.spreads = [Spread(attribute="${meta.rack}", weight=50)]
    plan = Plan(eval_id=f"wb-eval-{kind:>28}".replace(" ", "0"),
                priority=50, job=job)
    ctx = EvalContext(h.state.snapshot(), plan)
    places = [AllocPlaceResult(name=f"{job.id}.{tg.name}[{k}]",
                               task_group=tg) for k in range(count)]
    svc = TpuPlacementService(ctx, job, batch_mode=False,
                              spread_alg=False)
    pens = None
    if penalties:
        pens = [{nodes[penalties[k]].id} if k in penalties else set()
                for k in range(count)]
    lane = svc.pack(tg, places, nodes, pens)
    assert lane is not None
    return lane


@pytest.mark.parametrize("kind,count,want_wave", [
    ("plain", 126, True),       # limit ceil(log2 N): fits any buffer
    ("spread", 4, True),        # limit max(count, 100) + skips <= 128
    ("spread", 126, False)])    # 126 + 3 skips outgrow the widest
def test_wave_routing_is_decided_by_the_lane_window(kind, count,
                                                    want_wave):
    """Which kernel family a lane takes is read off the lane: a scan
    window wider than the widest wave buffer (a spread job of 126 or
    more placements, as `spread-drain`'s 1,200) takes the whole-axis
    scan; no user-set switch has a say."""
    lane = _pack_real_lane(kind, count, n_nodes=20)
    lim = int(np.asarray(lane.batch.limit)[0])
    assert (binpack.wavefront_buffer_size(lim) is not None) == want_wave
    assert lane.wavefront_ok() == want_wave


@pytest.mark.parametrize("kind,want_block", [
    ("plain", True), ("penalty", False), ("spread", False)])
def test_dispatch_gate_is_decided_by_the_lane(kind, want_block,
                                              monkeypatch):
    """A lane with an active reschedule penalty must take the compact
    scan (penalties couple score to the absolute placement index, which
    the run-block shortcut cannot model), and so must a lane with a
    spread (its counts ride the compact scan's carry); penalty-free
    lanes without one take the run-block kernel. Read off the
    compiled-fn cache key's use_block flag on a real lane's dispatch."""
    from nomad_tpu.solver.service import dispatch_lane

    lane = _pack_real_lane(kind, 4,
                           penalties={1: 5} if kind == "penalty" else None)
    assert lane.wavefront_ok()
    assert bool((np.asarray(lane.batch.penalty_idx) >= 0).any()) \
        == (kind == "penalty")

    seen = []
    real = binpack._wave_compact_program

    def spy(cm_shape, sp_shape, spread_alg, dtype_name, batched, B,
            use_block):
        seen.append(use_block)
        return real(cm_shape, sp_shape, spread_alg, dtype_name, batched,
                    B, use_block)

    monkeypatch.setattr(binpack, "_wave_compact_program", spy)
    chosen = dispatch_lane(lane)[0]
    assert seen == [want_block]
    assert (np.asarray(chosen) >= 0).all()
