"""The stand-in preemption deployment (data/preempt/config.json), added
to a copy of the benchmark by data files alone, rehearsed through the
one command on the CPU: the harness deploys classes with devices,
priority tiers and the scheduler's preemption settings from the file,
the plans evict, and `correct` compares what they evict.

Against Nomad's reference the program reads `correct` false today (its
departures, PERF.md section 7, item 0). So the wrong-eviction plant is
judged in a tree whose stand-in names data/preempt/host_departures.py,
the reference with the program's departures laid over it, where the
program's evicting placements sit on the reference's nodes, on one seed
with and without the plant. Unplanted, no placement there evicts other
allocs than the reference does at one committed state of its own node
(check.py `own_state_gap`). It may read as evicting nothing where the
reference evicts: the program's fault (d), which the state store's own
record shows (data/preempt/store_witness.py): the placement evicted
those allocs at its commit, and a later plan evicted them again and
took their record. Planted, placements that
evict where the reference evicts take allocs of other classes, and
`correct` is false."""
import json
import os

import pytest
from conftest import HERE, preempt_tree, run_cell

SEED = "2147483700"
WITNESS = os.path.join(HERE, "data", "preempt", "store_witness.py")


@pytest.fixture(scope="module")
def nomad_tree(tmp_path_factory, manifest):
    root = tmp_path_factory.mktemp("preempt")
    preempt_tree(str(root), manifest)
    return str(root)


@pytest.fixture(scope="module")
def host_tree(tmp_path_factory, manifest):
    root = tmp_path_factory.mktemp("preempt_host")
    preempt_tree(str(root), manifest, reference="host_departures")
    return str(root)


def rehearse(tree, env=None):
    return run_cell("standin-preempt", root=tree, env=env, seconds="4",
                    rehearse="0.5", seed=SEED)


def test_the_preemption_stand_in_rehearses_to_its_end(nomad_tree):
    rc, res, err = rehearse(nomad_tree)
    assert rc == 0, err[-2000:]
    assert list(res)[-1] == "compared"
    assert set(res["compared"]) == {
        "missing_allocs", "not_device", "blocked_evals",
        "overcommitted_nodes", "unreproduced_evals", "choice_mismatches",
        "score_gap_max", "eviction_mismatches"}
    assert res["info"]["set_up"]["preemption"][
        "service_scheduler_enabled"] is True
    # the comparison looked; what it reads on this program is PERF.md's
    # (section 7, item 0)
    assert res["info"]["evicting_placements"] > 0


def evicted_again(witness: str, node: str, classes: list) -> bool:
    """Whether the store's record (`store_witness`) shows, on `node`, a
    commit whose placement there evicted allocs of `classes` (the
    reference's, as check.eviction_class has them), every one of which a
    later commit evicted again, so that its record names the later
    plan's placement (or one that never committed) as what evicted it."""
    with open(witness) as f:
        rows = [json.loads(line) for line in f]
    want = sorted([c[0], *c[1]] for c in classes)
    here = [r for r in rows if r[1] == node]
    for index in {r[0] for r in here if r[4]}:
        first = [r for r in here if r[0] == index and r[4]]
        again = {r[2] for r in here if r[0] > index}
        if sorted(r[3] for r in first) == want and \
                {r[2] for r in first} <= again:
            return True
    return False


def test_a_wrong_eviction_is_not_correct(host_tree, tmp_path):
    def listed(res):
        """The eviction mismatches, [placement, node, reference's,
        program's, each own state of the node with its answer]: all of
        them (the run lists up to five)."""
        got = res["info"]["eviction_mismatches_first"]
        assert len(got) == res["compared"]["eviction_mismatches"]["value"]
        return got
    witness = str(tmp_path / "witness.jsonl")
    rc, res, err = rehearse(host_tree, env={"PERFBENCH_PLANT": WITNESS,
                                            "PERFBENCH_WITNESS": witness})
    assert rc == 0, err[-2000:]
    assert res["info"]["evicting_placements"] > 0
    assert res["info"]["walk_capped_evals"] == 0
    unplanted = listed(res)
    # in none did the program evict
    assert [m for m in unplanted if m[3]] == []
    # each is (d): what the placement evicted was evicted again later
    for _k, node, theirs, _mine, _states in unplanted:
        assert evicted_again(witness, node, theirs), (node, theirs)
    rc, res, err = rehearse(host_tree, env={"PERFBENCH_PLANT": os.path.join(
        HERE, "plants", "wrong_eviction.py")})
    assert rc == 0, err[-2000:]
    assert res["correct"] is False
    got = res["info"]["eviction_mismatches_first"]
    assert any(m[2] and m[3] and m[2] != m[3] for m in got)
