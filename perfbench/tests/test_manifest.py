"""The manifest is checked before it is sent (PR 22 was refused over one
arrow in it)."""
import copy
import json
import os

import manifest as mf
from conftest import BENCH


def test_committed_manifest_passes(manifest):
    assert mf.check(manifest) == []


def test_no_cell_asks_for_four_chips(manifest):
    assert all(w["chips"] == 1 for w in manifest["workloads"])


def test_every_end_to_end_metric_lists_its_cells(manifest):
    for m in manifest["end_to_end"]:
        assert m["name"] == "setup_s" or m["workloads"]


def test_the_rule_that_refused_pr_22(manifest):
    bad = copy.deepcopy(manifest)
    late = next(m for m in bad["per_layer"]
                if m["name"] == "generator_late_p95_ms.paced")
    late["workloads"].append("binpack-drain")
    faults = mf.check(bad)
    assert any("generator_late_p95_ms.paced is reported on workload "
               "binpack-drain, where submit_commit_p50_ms" in f
               for f in faults), faults


def test_names_units_files_and_bounds(manifest):
    cases = {
        "unit": lambda m: m["per_layer"][0].update(unit="lanes per dispatch"),
        "not a name": lambda m: m["workloads"][0].update(name="drain binpack"),
        "no file": lambda m: m["per_layer"][0].update(name="nothing_here.drain"),
        "end_to_end/absent_ms.json": lambda m: m["end_to_end"][0].update(
            name="absent_ms"),
        "no traffic file": lambda m: m["workloads"][0].update(traffic="absent"),
        "bound": lambda m: m["end_to_end"][0].update(bound=0.4),
        "keys": lambda m: m["per_layer"][0].update(why="because"),
        "four chips": lambda m: [w.update(chips=4) for w in m["workloads"][:3]],
        "no cell uses it": lambda m: m["configs"].append(dict(
            m["configs"][0], name="unused",
            file="perfbench/configs/unused.json")),
    }
    for want, breakit in cases.items():
        bad = copy.deepcopy(manifest)
        breakit(bad)
        faults = mf.check(bad)
        assert any(want in f for f in faults), (want, faults)


def test_every_metric_file_names_a_reader(manifest):
    import readers
    for kind, section in (("layer_metrics", "per_layer"),
                          ("end_to_end", "end_to_end")):
        for m in manifest[section]:
            with open(os.path.join(BENCH, kind, f"{m['name']}.json")) as f:
                assert json.load(f)["reader"] in readers.READERS


def test_no_cell_configuration_or_metric_is_named_in_the_python(manifest):
    names = ([w["name"] for w in manifest["workloads"]]
             + [c["name"] for c in manifest["configs"]]
             + [w["traffic"] for w in manifest["workloads"]]
             + [m["name"] for m in manifest["per_layer"]]
             + [m["name"] for m in manifest["end_to_end"]
                if m["name"] != "setup_s"])      # the contract's own name
    for fn in os.listdir(BENCH):
        if fn.endswith(".py"):
            with open(os.path.join(BENCH, fn)) as f:
                text = f.read()
            for name in names:
                assert f'"{name}"' not in text and f"'{name}'" not in text, (
                    fn, name)
