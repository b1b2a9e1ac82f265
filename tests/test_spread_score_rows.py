"""_spread_score works a spread's boost out once a value and lays that
row over the node axis (binpack.py, SPREAD_SELECT_V). Held here, bit
for bit, to the formulation it replaced, which gathered the (V,) tables
to the node axis first and did the arithmetic there; and the lowered
step is read for what the rewrite is for: no gather from a spread table
up to the width constant, one of the row above it.
"""
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from nomad_tpu.solver import binpack
from nomad_tpu.solver.binpack import (FAST_T, NodeConst, NodeState,
                                      PlacementBatch)

DTYPE = jnp.float32


def gathered_spread_score(state, const, dtype):
    """The lines _spread_score had before the rows: every table
    gathered to the node axis, then the boost a node."""
    def one_spread(vidx, desired, has_targets, weight, counts):
        missing = vidx < 0
        safe_vidx = jnp.maximum(vidx, 0)
        used = counts[safe_vidx] + 1
        weight_frac = weight / jnp.maximum(const.spread_sum_weights, 1e-9)
        des = desired[safe_vidx]
        no_target = des < 0.0
        boost_t = jnp.where(
            no_target, -1.0,
            jnp.where(des == 0.0, -1.0,
                      (des - used.astype(dtype)) / jnp.maximum(des, 1e-9)
                      * weight_frac))
        present = counts > 0
        any_present = jnp.any(present)
        big = jnp.iinfo(jnp.int32).max
        min_c = jnp.min(jnp.where(present, counts, big))
        max_c = jnp.max(jnp.where(present, counts, 0))
        current = counts[safe_vidx]
        min_f = min_c.astype(dtype)
        max_f = max_c.astype(dtype)
        cur_f = current.astype(dtype)
        even = jnp.where(
            current != min_c,
            jnp.where(min_c == 0, -1.0,
                      (min_f - cur_f) / jnp.maximum(min_f, 1e-9)),
            jnp.where(min_c == max_c, -1.0,
                      (max_f - min_f) / jnp.maximum(min_f, 1e-9)))
        boost_e = jnp.where(any_present, even, 0.0)
        per_node = jnp.where(has_targets, boost_t, boost_e)
        return jnp.where(missing, -1.0, per_node).astype(dtype)

    boosts = jax.vmap(one_spread)(
        const.spread_vidx, const.spread_desired, const.spread_has_targets,
        const.spread_weights, state.spread_counts)
    return jnp.sum(boosts, axis=0)


def _width(name):
    at = binpack.SPREAD_SELECT_V
    return {"racks": 75, "at": at, "above": at + 1, "wide": 4 * at}[name]


def _tables(seed, n, s, v, has_targets, counts="random", desired="random",
            missing=0.05):
    """A (NodeState, NodeConst) pair holding the spread fields of a lane
    and nothing else that _spread_score reads."""
    rng = np.random.default_rng(seed)
    vidx = rng.integers(0, v, size=(s, n)).astype(np.int32)
    vidx[rng.random((s, n)) < missing] = -1
    if counts == "random":
        cnt = rng.integers(0, 40, size=(s, v))
        cnt[rng.random((s, v)) < 0.3] = 0
    elif counts == "zero":
        cnt = np.zeros((s, v))
    else:                                   # every value holds as many
        cnt = np.full((s, v), 7)
    if desired == "random":
        des = rng.integers(1, 60, size=(s, v)).astype(np.float32)
        des[rng.random((s, v)) < 0.2] = -1.0        # no target for it
        des[rng.random((s, v)) < 0.1] = 0.0         # a target of none
    else:
        des = np.full((s, v), desired, dtype=np.float32)
    weights = rng.integers(1, 100, size=s).astype(np.float32)
    zn = np.zeros(n, dtype=np.float32)
    state = NodeState(
        used_cpu=zn, used_mem=zn, used_disk=zn,
        placed=zn.astype(np.int32), placed_job=zn.astype(np.int32),
        static_free=np.ones(n, dtype=bool), dyn_avail=zn.astype(np.int32),
        spread_counts=cnt.astype(np.int32))
    const = NodeConst(
        cpu_cap=zn + 4000.0, mem_cap=zn + 8192.0, disk_cap=zn + 1e5,
        feasible=np.ones(n, dtype=bool), affinity=zn,
        has_affinity=np.bool_(False), distinct_hosts=np.bool_(False),
        distinct_job_level=np.bool_(False),
        spread_vidx=vidx, spread_desired=des,
        spread_has_targets=np.full(s, has_targets),
        spread_weights=weights,
        spread_sum_weights=np.float32(weights.sum()),
        n_spreads=np.int32(s))
    return state, const


CASES = {
    # name: (n, s, width, has_targets, kwargs of _tables, slice or None)
    "target-racks": (4096, 1, "racks", True, {}, None),
    "even-racks": (4096, 1, "racks", False, {}, None),
    "target-two-spreads": (4096, 2, "racks", True, {}, None),
    "even-two-spreads": (4096, 2, "racks", False, {}, None),
    "target-no-target-anywhere": (2048, 1, "racks", True,
                                  {"desired": -1.0}, None),
    "target-of-none-anywhere": (2048, 1, "racks", True,
                                {"desired": 0.0}, None),
    "even-counts-all-zero": (2048, 1, "racks", False,
                             {"counts": "zero"}, None),
    "even-counts-all-equal": (2048, 2, "racks", False,
                              {"counts": "equal"}, None),
    "target-counts-all-zero": (2048, 1, "racks", True,
                               {"counts": "zero"}, None),
    "even-every-node-missing": (1024, 1, "racks", False,
                                {"missing": 1.0}, None),
    "target-no-node-missing": (1024, 2, "racks", True,
                               {"missing": 0.0}, None),
    "target-at-width": (2048, 1, "at", True, {}, None),
    "even-at-width": (2048, 2, "at", False, {}, None),
    "target-above-width": (2048, 1, "above", True, {}, None),
    "even-above-width": (2048, 2, "above", False, {}, None),
    "target-wide": (2048, 2, "wide", True, {}, None),
    "even-wide": (2048, 1, "wide", False, {}, None),
    "target-fast-slice": (4096, 1, "racks", True, {}, FAST_T),
    "even-fast-slice": (4096, 2, "racks", False, {}, FAST_T),
    "even-fast-slice-above-width": (4096, 1, "above", False, {}, FAST_T),
}


ROWS = jax.jit(lambda st, c: binpack._spread_score(st, c, DTYPE))
GATHERED = jax.jit(lambda st, c: gathered_spread_score(st, c, DTYPE))


def _words(x):
    """The float32 words of x, a zero of either sign as +0.0."""
    x = np.asarray(x, dtype=np.float32)
    return np.where(x == 0.0, np.float32(0.0), x).view(np.uint32)


@pytest.mark.parametrize("case", sorted(CASES))
def test_rows_give_the_gathers_bits(case):
    n, s, width, has_targets, kwargs, hi = CASES[case]
    for seed in range(3):
        state, const = _tables(1000 * seed + len(case), n, s, _width(width),
                               has_targets, **kwargs)
        if hi is not None:      # what _scoring_parts hands the fast pass
            const = const._replace(spread_vidx=const.spread_vidx[:, 0:hi])
        got, want = ROWS(state, const), GATHERED(state, const)
        assert got.dtype == want.dtype == DTYPE
        assert got.shape == (hi or n,)
        assert np.array_equal(_words(got), _words(want)), (
            case, seed, int(np.sum(_words(got) != _words(want))))
        if not kwargs:          # random tables: boosts of many values
            assert len(np.unique(np.asarray(want))) > 8


_GATHER = re.compile(
    r'stablehlo\.gather"?\(.*?:\s*\(tensor<([^>]*)>,\s*tensor<([^>]*)>\)'
    r'\s*->\s*tensor<([^>]*)>', re.S)


def _table_gathers(text, v):
    """Operand types of the gathers in a lowered module that read a
    table with a value axis of v."""
    return [m.group(1) for m in _GATHER.finditer(text)
            if str(v) in m.group(1).split("x")[:-1]]


def _lower_solve(n, s, v, p=8, solve=None):
    state, const = _tables(5, n, s, v, False)
    batch = PlacementBatch(
        ask_cpu=np.full(p, 500.0, np.float32),
        ask_mem=np.full(p, 256.0, np.float32),
        ask_disk=np.full(p, 300.0, np.float32),
        n_dyn_ports=np.zeros(p, np.int32), has_static=np.zeros(p, bool),
        limit=np.full(p, 1200, np.int32), count=np.full(p, 1200, np.int32),
        penalty_idx=np.full(p, -1, np.int32), active=np.ones(p, bool))
    return (solve or binpack.solve_placements).lower(
        const, state, batch, spread_alg=False,
        dtype_name="float32").as_text()


def test_the_reading_finds_the_gathers_the_step_had(monkeypatch):
    """The control of the test below: the same reading of the same
    program around the gathered formulation finds its three gathers a
    pass (counts twice, targets once). Traced through a jit of its own,
    so the patched function is in nobody else's cache."""
    monkeypatch.setattr(binpack, "_spread_score", gathered_spread_score)
    solve = jax.jit(
        lambda *a, **kw: binpack._solve_placements_impl(*a, **kw),
        static_argnames=("spread_alg", "dtype_name"))
    found = _table_gathers(_lower_solve(4096, 1, 75, solve=solve), 75)
    assert sorted(found) == ["1x75xf32"] * 2 + ["1x75xi32"] * 4, found


@pytest.mark.parametrize("n,passes", [(1536, 1), (4096, 2)],
                         ids=["one-pass", "fast-pass-too"])
@pytest.mark.parametrize("s", [1, 2])
def test_the_step_gathers_from_no_spread_table_up_to_the_width(n, passes, s):
    """Backend-free: the text of the program before any compiler has
    seen it. A lane of the rack spread (V 75) holds no gather whose
    operand has the value axis; one value past the constant it holds
    one a pass over the nodes (with N above 2 x FAST_T the step scores
    the first FAST_T positions as well as the whole axis), of the
    spreads' rows together, and none of the counts or the targets."""
    assert 75 <= binpack.SPREAD_SELECT_V
    text = _lower_solve(n, s, 75)
    assert "stablehlo.while" in text
    assert _table_gathers(text, 75) == []

    v = binpack.SPREAD_SELECT_V + 1
    found = _table_gathers(_lower_solve(n, s, v), v)
    assert found == [f"{s}x{v}xf32"] * passes, found
