"""Plain reference for what a placement has to satisfy, independent of
the program: it imports nothing of nomad_tpu and takes nothing the
program computed except the answers under test (where an alloc sits,
and the score the program says it gave that node).

The scheduler's published scoring (the reference implementation's
rank.go ScoreFitBinPack, JobAntiAffinityIterator, spread.go
evenSpreadScoreBoost, ScoreNormalizationIterator) and selection
(util.go shuffleNodes seeded by eval id and state index, stack.go's
log2 scan limit, select.go LimitIterator and MaxScoreIterator), written
out in numpy in float64. `dtype` lets the control compute the same
thing in a lower precision (bfloat16, the step below the float32 the
configurations state).
"""
from __future__ import annotations

import numpy as np

BINPACK_MAX = 18.0
MASK64 = (1 << 64) - 1
SKIP_SCORE = 0.0
MAX_SKIP = 3


def _f(dtype):
    if dtype == "bfloat16":
        import ml_dtypes
        return ml_dtypes.bfloat16
    return np.dtype(dtype).type


def binpack_score(cap_cpu, cap_mem, used_cpu, used_mem, dtype="float64"):
    """20 - (10^free_cpu + 10^free_mem), clamped to [0, 18], over 18.
    `used_*` include the placement being scored."""
    t = _f(dtype)
    one, ten = t(1.0), t(10.0)
    free_cpu = t(one - t(t(used_cpu) / t(cap_cpu)))
    free_mem = t(one - t(t(used_mem) / t(cap_mem)))
    total = t(t(np.power(ten, free_cpu)) + t(np.power(ten, free_mem)))
    score = t(t(20.0) - total)
    score = min(max(score, t(0.0)), t(BINPACK_MAX))
    return t(score / t(BINPACK_MAX))


def anti_affinity(collisions: int, desired_count: int, dtype="float64"):
    """-(collisions + 1) / count for a node that already holds
    `collisions` allocs of this job's group; nothing when it holds none."""
    if collisions <= 0:
        return None
    t = _f(dtype)
    return t(-t(collisions + 1) / t(desired_count))


def even_spread_boost(counts: dict, value, dtype="float64"):
    """spread.go evenSpreadScoreBoost: `counts` are this job's allocs per
    value of the spread attribute before this placement (values with
    none are absent), `value` the candidate node's."""
    t = _f(dtype)
    if not counts:
        return t(0.0)
    if value is None:
        return t(-1.0)
    current = counts.get(value, 0)
    lo, hi = min(counts.values()), max(counts.values())
    if current != lo:
        if lo == 0:
            return t(-1.0)
        return t(t(lo - current) / t(lo))
    if lo == hi:
        return t(-1.0)
    if lo == 0:
        return t(1.0)
    return t(t(hi - lo) / t(lo))


def normalized(binpack, anti, spread, dtype="float64"):
    """Mean of the scores that were appended: binpack always, the
    anti-affinity penalty when there is one, the spread boost when it is
    not zero."""
    t = _f(dtype)
    parts = [t(binpack)]
    if anti is not None:
        parts.append(t(anti))
    if spread is not None and float(spread) != 0.0:
        parts.append(t(spread))
    acc = t(0.0)
    for p in parts:
        acc = t(acc + p)
    return t(acc / t(len(parts)))


def overcommitted(cap: tuple, events: list) -> bool:
    """`events`: (index, order, d_cpu, d_mem, d_disk), order 0 for a
    release and 1 for a placement, so that at one index capacity frees
    first. True when the node ever holds more than `cap`."""
    used = [0.0, 0.0, 0.0]
    for _idx, _order, *delta in sorted(events):
        for i in range(3):
            used[i] += delta[i]
        if any(used[i] > cap[i] + 1e-9 for i in range(3)):
            return True
    return False


def shuffled(items: list, eval_id: str, index: int) -> list:
    """`items` in the order the scheduler scans them: Fisher-Yates from
    the back, seeded by the last 8 bytes of the eval id xor the index of
    the state the eval was solved against, splitmix64 as the generator
    (the program's stated contract, scheduler/util.py)."""
    state = (int.from_bytes(eval_id.encode()[-8:].rjust(8, b"\0"), "big")
             ^ index) & MASK64
    out = list(items)
    for i in range(len(out) - 1, 0, -1):
        state = (state + 0x9E3779B97F4A7C15) & MASK64
        z = state
        z = ((z ^ (z >> 30)) * 0xBF58476D1CE4E5B9) & MASK64
        z = ((z ^ (z >> 27)) * 0x94D049BB133111EB) & MASK64
        j = (z ^ (z >> 31)) % (i + 1)
        out[i], out[j] = out[j], out[i]
    return out


def scan_limit(n_nodes: int, count: int, wide: bool) -> int:
    """How many feasible nodes one placement looks at: ceil(log2 nodes)
    and at least 2 for a service job; max(count, 100) when a spread or
    an affinity needs the wide scan (stack.go:75-95, 176-185)."""
    if wide:
        return max(int(count), 100)
    return max(2, int(np.ceil(np.log2(n_nodes))) if n_nodes > 1 else 1)


def place_sequence(order: list, usage, cap, ask: tuple, count: int,
                   n_place: int, limit: int, spread=None,
                   dtype="float64") -> list:
    """One eval's placements as the iterator stack makes them, one after
    the other, each counting on its node for the next.

    `order`: node ids as scanned; `usage(node)` -> [cpu, mem, disk,
    collisions] at the eval's snapshot (asked once a node, when the scan
    first reaches it); `cap(node)` -> (cpu, mem, disk); `spread`: None,
    or (value_of(node), counts dict at the snapshot). Per placement:
    walk `order`, pass nodes the ask does not fit, score the rest, set
    aside up to MAX_SKIP that score <= 0, stop at `limit` (the set-aside
    fill in when the scan runs dry), take the best, the first seen among
    equals. Returns [(node or None, score, window)], window the nodes
    that were compared, in scan order."""
    used: dict = {}
    full: set = set()
    counts = dict(spread[1]) if spread else None
    out = []
    for _ in range(n_place):
        seen, aside = [], []
        for node in order:
            if node in full:
                continue
            u = used.get(node)
            if u is None:
                u = used[node] = list(usage(node))
            c = cap(node)
            after = (u[0] + ask[0], u[1] + ask[1], u[2] + ask[2])
            if any(after[i] > c[i] for i in range(3)):
                full.add(node)      # usage only grows within an eval
                continue
            boost = (even_spread_boost(counts, spread[0](node), dtype)
                     if spread else None)
            score = float(normalized(
                binpack_score(c[0], c[1], after[0], after[1], dtype),
                anti_affinity(u[3], count, dtype), boost, dtype))
            if score <= SKIP_SCORE and len(aside) < MAX_SKIP:
                aside.append((node, score))
                continue
            seen.append((node, score))
            if len(seen) == limit:
                break
        seen += aside[:limit - len(seen)]
        if not seen:
            out.append((None, None, []))
            continue
        best = max(seen, key=lambda ns: ns[1])      # first among equals
        u = used[best[0]]
        for i in range(3):
            u[i] += ask[i]
        u[3] += 1
        if spread:
            value = spread[0](best[0])
            counts[value] = counts.get(value, 0) + 1
        out.append((best[0], best[1], [n for n, _ in seen]))
    return out
