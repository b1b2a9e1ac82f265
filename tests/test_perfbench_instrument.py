"""Tier 1 runs the benchmark's cheap proofs of its own instrument
(perfbench/tests/test_manifest.py, test_tracered.py, test_work_model.py:
no server, no JAX, under a second together): the manifest's rules, the
trace reduction on a small recorded trace and the work models'
arithmetic. The modules are loaded by path, one tier-1 test a function;
nothing under perfbench/ is edited."""
import importlib.util
import inspect
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
FILES = ("test_manifest", "test_tracered", "test_work_model")


def _by_path(name, path):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _load_all():
    """The benchmark's test modules say `from conftest import ...` and
    mean their own: lend them that name while they load, then put this
    suite's conftest and sys.path back (with the benchmark's directory
    at the end, where tests/test_perfbench_filter.py keeps it)."""
    mine, path = sys.modules.get("conftest"), list(sys.path)
    try:
        sys.modules["conftest"] = _by_path(
            "perfbench_tests_conftest",
            os.path.join(BENCH, "tests", "conftest.py"))
        return {f: _by_path("perfbench_tests_" + f,
                            os.path.join(BENCH, "tests", f + ".py"))
                for f in FILES}
    finally:
        sys.path[:] = path + ([BENCH] if BENCH not in path else [])
        if mine is not None:
            sys.modules["conftest"] = mine
        else:
            sys.modules.pop("conftest", None)


MODS = _load_all()
TESTS = [(f, name) for f in FILES for name in sorted(vars(MODS[f]))
         if name.startswith("test_") and callable(getattr(MODS[f], name))]


def test_every_test_of_the_three_files_is_run_here():
    """A `test_` function added to one of the files is collected above;
    this fails if the text of a file holds one the loader did not see."""
    for f in FILES:
        with open(os.path.join(BENCH, "tests", f + ".py")) as fh:
            written = [ln.split("(")[0][4:] for ln in fh
                       if ln.startswith("def test_")]
        assert sorted(written) == [n for g, n in TESTS if g == f]
    assert len(TESTS) >= 15


@pytest.mark.parametrize("file,name", TESTS)
def test_instrument(file, name):
    fn = getattr(MODS[file], name)
    args = {}
    if "manifest" in inspect.signature(fn).parameters:
        with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
            args["manifest"] = json.load(f)
    fn(**args)
