"""The trace reduction on a small recorded trace, by hand: busy is the
union of the op intervals, device time goes by program name, and a gap
is named by the host annotation that covers most of it."""
import json
import os

import tracered
from conftest import HERE


def small():
    with open(os.path.join(HERE, "data", "trace_small.json")) as f:
        planes = json.load(f)["planes"]
    return {p: {ln: [tuple(e) for e in evs] for ln, evs in lines.items()}
            for p, lines in planes.items()}


def test_busy_is_the_union_not_the_sum():
    red = tracered.reduce_events(small(), [r"^jit_fn$"])
    # ops: [1000,1300) U [1200,1400) = 400, [3000,3700) = 700, 100
    assert red["busy_s"] == 1200e-9
    # window: host annotations run from 0 to 10000
    assert red["window_s"] == 10000e-9
    assert red["devices"] == 1


def test_device_time_by_program_name():
    red = tracered.reduce_events(small(), [r"^jit_fn$"])
    assert red["programs"] == {"jit_fn": [2, 1100e-9],
                               "jit__scatter": [1, 100e-9]}
    assert red["solve_events"] == 2 and red["solve_s"] == 1100e-9
    assert red["device_ops"][0] == ["%while.113", 1000e-9]


def test_gaps_are_named_by_what_the_host_did():
    red = tracered.reduce_events(small(), [])
    gaps = dict(red["idle_gaps"])
    # [0,1000) and [1400,3000) lie under worker.invoke, [3700,6000) under
    # guard.run_dispatch, [6100,10000) under plan.commit
    assert gaps == {"worker.invoke": 2600e-9, "guard.run_dispatch": 2300e-9,
                    "plan.commit": 3900e-9}
    assert abs(sum(gaps.values()) + red["busy_s"] - red["window_s"]) < 1e-15


def test_a_trace_with_no_device_plane_reads_nothing():
    planes = {p: v for p, v in small().items() if p.startswith("/host")}
    red = tracered.reduce_events(planes, [r"^jit_fn$"])
    assert red["busy_s"] == 0.0 and red["solve_events"] == 0
    import readers
    run = {"trace": red, "window": {"traced": None}}
    assert readers.trace_idle({}, run) is None
    assert readers.trace_device_time({"per_timer": "t"}, run) is None
