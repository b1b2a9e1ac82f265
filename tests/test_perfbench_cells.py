"""The spread corner as the benchmark lists it (BENCHMARK.json,
perfbench/): checked here because a PR may not edit the files
`perfbench/tests/` already has. The manifest's own checker passes; the
cell is the one the sweep names, whole; the drained set's per-layer
metrics it was brought with have their twins; the one command rehearses the cell on the CPU
to its end (slow: a server, a fleet, a minute)."""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "perfbench")
CELL, CONFIG = "spread-drain", "sweep10k-r75-j1200-spread"


def load(*parts):
    with open(os.path.join(*parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def manifest():
    return load(ROOT, "BENCHMARK.json")


def test_manifest_checker_passes():
    p = subprocess.run([sys.executable, os.path.join(BENCH, "manifest.py")],
                       capture_output=True, text=True, timeout=120)
    assert p.returncode == 0 and p.stdout.strip() == "manifest ok", p.stdout


def test_the_spread_corner_is_the_sweeps_own(manifest):
    entry = {c["name"]: c for c in manifest["configs"]}[CONFIG]
    assert entry["reduced"] == []
    cfg = load(ROOT, entry["file"])
    accepted = load(ROOT, manifest["configs"][0]["file"])
    assert cfg["reduced"] == []
    assert cfg["fleet"] == dict(accepted["fleet"], racks=75)
    assert cfg["job"] == dict(accepted["job"], count=1200, spread={
        "attribute": "${meta.rack}", "weight": 100})
    for key in ("base_load_allocs", "precision", "guarantees", "limits"):
        assert cfg[key] == accepted[key], key
    sched = {k: v for k, v in cfg["scheduler"].items() if k != "warm_rounds"}
    assert sched == {k: v for k, v in accepted["scheduler"].items()
                     if k != "warm_rounds"}
    # every set-up round rides the whole-axis path: its window of
    # max(count, 100) and three skips outgrows the widest wave buffer
    assert all(r["count"] + 3 > 128 and 1 <= r["lanes"] <= 8
               for r in cfg["scheduler"]["warm_rounds"])
    assert {"warm_rounds", "lead_in_s", "check_jobs"} <= set(cfg["assumed"])
    model = load(BENCH, "work_models", cfg["solve"]["work_model"] + ".json")
    assert model == load(BENCH, "tests", "data", "standin",
                         "work_model.json")


def test_the_cell_is_the_drained_mix_with_its_own_lead_in(manifest):
    cell = {w["name"]: w for w in manifest["workloads"]}[CELL]
    assert (cell["config"], cell["traffic"], cell["chips"]) == (
        CONFIG, "drain", 1)
    own = load(BENCH, "workloads", CELL + ".json")["traffic"]
    assert set(own) == {"lead_in_s", "check_jobs"}
    assert 4 <= own["lead_in_s"] <= 20 and own["check_jobs"] in (1, 2)
    mix = load(BENCH, "traffic", "drain.json")
    assert mix["submitters"] == 16 and mix["loop"] == "closed"
    e2e = {m["name"]: m for m in manifest["end_to_end"]}
    assert CELL in e2e["placements_per_s"]["workloads"]
    assert "workloads" not in e2e["setup_s"]


def test_the_cell_reports_the_drained_set_and_its_own(manifest):
    """What PR 30 added, and no more than that: a later drained metric
    or cell comes as data and needs no edit here."""
    by_name = {m["name"]: m for m in manifest["per_layer"]}
    mine = {n: m for n, m in by_name.items() if m.get("workloads") == [CELL]}
    assert all(m["moves"] == "placements_per_s" for m in mine.values())
    twins = {n: n[:-len("spread")] + "drain" for n in mine
             if n[:-len("spread")] + "drain" in by_name}
    assert len(twins) >= 38
    for twin, name in twins.items():
        assert mine[twin] == dict(by_name[name], name=twin, workloads=[CELL])
        assert load(BENCH, "layer_metrics", twin + ".json") == \
            load(BENCH, "layer_metrics", name + ".json")
    assert {"dense_dispatch_share.spread", "dense_solve_ms.spread",
            "dense_programs_in_window.spread", "dense_steps.spread",
            "fixpoint_unresolvable_share.spread"} <= set(mine) - set(twins)
    assert {"solve_roofline.spread", "compiles_in_window.spread"} <= set(
        twins)


@pytest.mark.slow
def test_the_cell_rehearses_on_the_cpu(manifest):
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "run.py"), "--workload", CELL,
         "--seed", "2147483700", "--seconds", "2", "--rehearse", "0.03",
         "--trace", "1"], cwd=ROOT, capture_output=True, text=True,
        timeout=900, env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert p.returncode == 0, p.stderr[-2000:]
    res = json.loads(p.stdout.strip().splitlines()[-1])
    assert res["device"]["platform"] == "cpu" and res["attempted"] > 0
    device = {m["name"] for m in manifest["per_layer"]
              if m["source"] == "device_trace"}
    assert not device & set(res["metrics"])
    assert "compiles_in_window.spread" in res["metrics"]
