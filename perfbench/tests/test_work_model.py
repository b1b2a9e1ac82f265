"""The work model of the solve's roofline against bytes counted by hand
at one small shape, and the table of peaks."""
import json
import os

import pytest
import readers
from conftest import BENCH


def test_wave_scan_bytes_by_hand():
    with open(os.path.join(BENCH, "work_models", "wave_scan.json")) as f:
        model = json.load(f)
    # 2 lanes, 4 placements, a window of 3, one spread column, 5 racks:
    # table 2*(4+3)*(8+1)=126, penalties 2*4=8, scalars 2*5=10,
    # spread tables 2*1*5*2=20, results 2*3*4=24 -> 188 float32 = 752 B
    shape = {"E": 2, "P": 4, "W": 3, "S": 1, "V": 5}
    assert readers.model_bytes(model, shape) == 752.0


def test_roofline_is_bytes_over_peak_over_device_time():
    timer = {"timers": {"dispatch": [3, 0.0]}}
    run = {"trace": {"solve_s": 0.002, "solve_events": 5},
           "window": {"traced": [timer, {"timers": {"dispatch": [5, 0.0]}}]},
           "config": {"solve": {"work_model": "wave_scan",
                                "symbols": {"P": 4, "W": 3, "S": 1, "V": 5}}},
           "device": {"kind": "TPU v5 lite"},
           "snap0": {"gauges": {}},
           "snap1": {"gauges": {"lanes": [10, 20.0]}}}
    # five solve events under two fused dispatches: per dispatch
    got = readers.roofline({"lanes_gauge": "lanes",
                            "per_timer": "dispatch"}, run)
    assert got == pytest.approx(100.0 * (752.0 / 819e9) / 1e-3)
    assert 0 < got < 100


def test_an_unknown_device_kind_is_an_error():
    with pytest.raises(KeyError):
        readers.peak("TPU v9 mega")


def test_shapes_are_arithmetic_only():
    with pytest.raises(ValueError):
        readers.evaluate("__import__('os').getcwd()", {})
