"""Tier 1 runs the benchmark's own proof that `check.scan_reach` admits
the right snapshot index and no wrong one (perfbench/tests/test_filter.py:
no server, no JAX, nothing of the program): its 2,000-node cases, which
take seconds; the 10,000-node ones stay with `perfbench/tests/`."""
import importlib.util
import os
import sys

import pytest

BENCH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "perfbench")
if BENCH not in sys.path:
    sys.path.append(BENCH)      # `check`, `reference`: the benchmark's


def _load():
    spec = importlib.util.spec_from_file_location(
        "perfbench_tests_test_filter",
        os.path.join(BENCH, "tests", "test_filter.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


flt = _load()
SMALL = [c for c in flt.CASES if c[0] == 2000]
SWEPT = [name for name in dir(flt) if name.startswith("test_")
         and hasattr(getattr(flt, name), "pytestmark")]


def test_every_swept_case_of_the_filter_is_run_here():
    assert len(SMALL) == 12 and len(SWEPT) == 4


@pytest.mark.parametrize("n,spread,count,cannot", SMALL)
@pytest.mark.parametrize("name", SWEPT)
def test_filter(name, n, spread, count, cannot):
    getattr(flt, name)(n, spread, count, cannot)


def test_a_window_wider_than_the_filter_can_tell_is_not_filtered():
    flt.test_a_window_wider_than_the_filter_can_tell_is_not_filtered()
