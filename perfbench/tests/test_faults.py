"""`correct` has been shown to fail: with the timed path broken
underneath a run (the look for a chip skipped, everything else driven as
a run drives it) it comes out false, and so it does when the control --
the reference one precision down -- serves the sampled evals in the
program's place and goes through the same comparison."""
import os

import check
import pytest
from conftest import HERE, run_cell, standin_tree

LIMITS = check.load_limits("served_placements")


def plant(name):
    return {"PERFBENCH_PLANT": os.path.join(HERE, "plants", f"{name}.py")}


def over(res, name):
    got = res["compared"][name]
    return got["value"] is None or got["value"] > got["limit"]


@pytest.mark.parametrize("cell", ["binpack-paced", "binpack-drain"])
def test_the_control_is_not_correct_where_the_program_is(cell):
    rc, res, err = run_cell(cell, "--control", "bfloat16")
    assert rc == 0 and res["correct"] is False, err[-2000:]
    assert res["compared"]["score_gap_max"]["value"] > \
        3 * LIMITS["score_gap_max"]
    # the same run's own answers, beside it: every number within its limit
    program = res["info"]["program"]
    assert check.verdict(program, LIMITS)[0]
    assert program["score_gap_max"] < LIMITS["score_gap_max"] / 3
    assert res["info"]["placements_compared"] >= 24


def test_the_host_answering_in_the_devices_place_is_not_correct():
    rc, res, err = run_cell("binpack-drain", env=plant("host_answers"))
    assert rc == 0 and res["correct"] is False, err[-2000:]
    assert over(res, "not_device")


def test_nodes_swapped_among_a_lanes_placements_is_not_correct():
    rc, res, err = run_cell("binpack-drain", env=plant("altered_answer"))
    assert rc == 0 and res["correct"] is False, err[-2000:]
    assert over(res, "unreproduced_evals") or over(res, "choice_mismatches")


def test_nodes_swapped_in_a_wide_window_is_a_mismatch_not_unreplayed(
        tmp_path, manifest):
    """The stand-in spread deployment, whose evals look at max(count,
    100) nodes a placement: the replay looked, and says so."""
    standin_tree(str(tmp_path), manifest)
    rc, res, err = run_cell("standin-trickle", root=str(tmp_path),
                            env=plant("altered_answer"))
    assert rc == 0 and res["correct"] is False, err[-2000:]
    assert over(res, "unreproduced_evals") or over(res, "choice_mismatches")
    assert res["info"]["unreplayed_evals"] == 0


def test_a_score_altered_where_it_is_produced_is_not_correct():
    rc, res, err = run_cell("binpack-drain", env=plant("altered_score"))
    assert rc == 0 and res["correct"] is False, err[-2000:]
    assert over(res, "score_gap_max")


def test_one_placement_on_another_node_with_its_honest_score_is_not_correct():
    rc, res, err = run_cell("binpack-drain", env=plant("second_best"))
    assert rc == 0 and res["correct"] is False, err[-2000:]
    assert over(res, "choice_mismatches")
    assert not over(res, "score_gap_max")
