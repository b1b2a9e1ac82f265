"""The filter that chooses which snapshot indexes `compare_plan` replays
(`check.scan_reach`) holds at every window the sweep has: on a synthetic
fleet and a seeded shuffle, with no server, the right index is always
replayed and a wrong one never, whether the eval looks at 14 nodes a
placement or at 1,200, and whether the whole fleet can take the ask or
three tenths of it.

The rule this one replaced (`OLD`, written out below: an eighth of the
fleet, whatever the eval) skips the right index of the 1,200-wide evals
once enough of the fleet is full (`OLD_SKIPS_THE_RIGHT_INDEX`):
`compare_plan` of the tree before PR 28 returns `index` None for them,
and `test_the_right_index_is_replayed_and_reproduces` fails there.

No JAX and nothing of the program: a later PR can run these from
`tests/` (tier 1), where they would count; the four 1,200-wide cases at
10,000 nodes replay 1,200 x 1,200 node scores, 20 s a replay."""
import functools
import math
import random

import check
import pytest
from reference import placement as ref

RIGHT = 1000          # the snapshot index the plan was solved at
PLAN = RIGHT + 40     # the index its plan committed at
N_WRONG = 200
FULL = 8              # allocs of the ask that fill a node
# (nodes, spread, count): the sweep's own 10,000 nodes at the three
# windows (14 = ceil(log2 nodes), max(300, 100), max(1200, 100)), and
# the same three shares of a 2,000-node fleet, which run in seconds
SIZES = [(2000, False, 60), (2000, True, 100), (2000, True, 240),
         (10000, False, 300), (10000, True, 300), (10000, True, 1200)]
CANNOT = [0.0, 0.25, 0.5, 0.7]
CASES = [(*size, f) for size in SIZES for f in CANNOT]


# what the old rule does to these fleets' widest evals: the plan's share
# inside an eighth of the fleet reads 0.45 and 0.30 (2,000 nodes, half and
# seven tenths full) and 0.42 (10,000 nodes, seven tenths full); at 10,000
# nodes half full it reads 0.61, as the served cell's 0.53-0.66 (PERF.md)
OLD_SKIPS_THE_RIGHT_INDEX = {(2000, 240, 0.5), (2000, 240, 0.7),
                             (10000, 1200, 0.7)}


def OLD(n):
    """The reach before PR 28, for every eval alike."""
    return max(8 * max(2, math.ceil(math.log2(n))), n // 8)


def alloc(job_id, eval_id, k, index):
    return {"name": f"{job_id}.web[{k}]", "job_id": job_id,
            "task_group": "web", "eval_id": eval_id,
            "create_index": index, "modify_index": index,
            "client_status": "running", "desired_status": "run",
            "allocated_resources": {
                "tasks": {"web": {"cpu_shares": 500, "memory_mb": 256}},
                "shared": {"disk_mb": 150}}}


@functools.lru_cache(maxsize=None)
def case(n, spread, count, cannot):
    """A fleet of which the share `cannot` is full and the rest holds
    nought to three allocs a node, a job, and its plan as the reference
    itself places it at RIGHT: what a sound program serves."""
    rng = random.Random(n * 1000 + count + int(cannot * 100))
    ids = [f"node-{i:06d}" for i in range(n)]
    full = set(rng.sample(ids, int(n * cannot)))
    fleet = {}
    for i, node_id in enumerate(ids):
        held = FULL if node_id in full else rng.randrange(4)
        fleet[node_id] = (
            {"node_resources": {"cpu": {"cpu_shares": 4000},
                                "memory": {"memory_mb": 8192},
                                "disk": {"disk_mb": 102400}},
             "meta": {"rack": f"rack-{i % 75:03d}"}},
            [alloc(f"base-{node_id}", "base-eval", k, 5) for k in range(held)])
    group = {"name": "web", "count": count,
             "ephemeral_disk": {"size_mb": 150},
             "tasks": [{"resources": {"cpu": 500, "memory_mb": 256}}]}
    if spread:
        group["spreads"] = [{"attribute": "${meta.rack}", "weight": 100}]
    job = {"id": "job-under-test", "task_groups": [group]}
    eval_id = f"eval-{n}-{count}-{int(cannot * 100):02d}-0123456789ab"
    seq = check.replay(job, "web", ref.shuffled(ids, eval_id, RIGHT), RIGHT,
                       fleet.__getitem__)
    served = {k: (node, score) for k, (node, score, _w) in enumerate(seq)}
    assert all(node is not None for node, _s in served.values())
    return ids, fleet, job, eval_id, served


def compare(n, spread, count, cannot, indexes, served=None):
    """`compare_plan` over `indexes`, and the nodes it read."""
    ids, fleet, job, eval_id, plan = case(n, spread, count, cannot)
    read = set()

    def fetch(node_id):
        read.add(node_id)
        return fleet[node_id]
    got = check.compare_plan(served or plan, job, "web", eval_id, PLAN,
                             indexes, ids, fetch)
    return got, read


def wrong_indexes():
    return [i for i in range(RIGHT - N_WRONG // 2, RIGHT + N_WRONG // 2 + 1)
            if i != RIGHT]


def old_share(n, spread, count, cannot, index):
    ids, _fleet, _job, eval_id, served = case(n, spread, count, cannot)
    return check.share_in_reach(
        served, ref.shuffled(ids, eval_id, index), OLD(n))


@pytest.mark.parametrize("n,spread,count,cannot", CASES)
def test_the_right_index_is_replayed_and_reproduces(n, spread, count, cannot):
    got, _read = compare(n, spread, count, cannot,
                         range(PLAN - 1, RIGHT - 1, -1))
    assert got["index"] == RIGHT and got["usage_index"] == RIGHT
    assert got["reproduced"] and got["mismatches"] == []
    assert len(got["same"]) == count and max(got["gaps"]) == 0.0


@pytest.mark.parametrize("n,spread,count,cannot", CASES)
def test_no_wrong_index_is_replayed(n, spread, count, cannot):
    got, read = compare(n, spread, count, cannot, wrong_indexes())
    assert got["index"] is None and not got["reproduced"]
    assert read == set()      # not one node was read for them


@pytest.mark.parametrize("n,spread,count,cannot", CASES)
def test_a_narrow_window_admits_what_the_old_rule_admitted(
        n, spread, count, cannot):
    """For the windows the accepted cells have (and every window of
    which four fit into an eighth of the fleet) the rule is the old one,
    so the same indexes are admitted; for the wide ones the old rule
    skips the right index once enough of the fleet is full."""
    limit = ref.scan_limit(n, count, spread)
    if 4 * limit <= OLD(n):
        assert check.scan_reach(n, limit) == (OLD(n), 0.5)
        assert old_share(n, spread, count, cannot, RIGHT) >= 0.5
        assert all(old_share(n, spread, count, cannot, i) < 0.5
                   for i in wrong_indexes())
    else:
        assert check.scan_reach(n, limit) == (4 * limit, 0.75)
        assert (old_share(n, spread, count, cannot, RIGHT) < 0.5) == \
            ((n, count, cannot) in OLD_SKIPS_THE_RIGHT_INDEX)


@pytest.mark.parametrize("n,spread,count,cannot", CASES)
def test_a_plan_moved_to_other_fitting_nodes_is_a_mismatch_not_unreplayed(
        n, spread, count, cannot):
    """The placements take each other's nodes in reverse order, as
    plants/altered_answer.py does to a lane: the same nodes, so the
    filter sees the same plan, and the replay must say it is another."""
    served = case(n, spread, count, cannot)[4]
    nodes = [served[k][0] for k in range(count)][::-1]
    moved = {k: (nodes[k], served[k][1]) for k in range(count)}
    got, _read = compare(n, spread, count, cannot, [RIGHT], served=moved)
    assert got["index"] == RIGHT
    assert len(got["mismatches"]) > 0


def test_a_window_wider_than_the_filter_can_tell_is_not_filtered():
    """A 1,200-alloc spread job on the sweep's 1,000-node cluster scans
    every node: its plan lies anywhere under any index."""
    assert check.scan_reach(1000, 1200) == (None, 0.0)
    assert check.scan_reach(300, 100) == (None, 0.0)    # the rehearsal's
    assert check.scan_reach(10000, 1500) == (6000, 0.75)
    assert check.scan_reach(10000, 1501) == (None, 0.0)
    assert check.scan_reach(10000, 313) == (1252, 0.75)
    assert check.scan_reach(10000, 312) == (1250, 0.5)
    assert check.scan_reach(10000, 14) == (1250, 0.5)
