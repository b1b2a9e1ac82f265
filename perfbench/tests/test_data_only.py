"""Adding a cell is data only: a new configuration, mix, cell and
per-layer metric dropped into a copy of the directories are listed,
checked and rehearsed with no other file touched."""
import json
import os
import shutil

import manifest as mf
from conftest import BENCH, HERE, ROOT, run_cell


def test_a_new_cell_needs_only_new_files(tmp_path, manifest):
    root = str(tmp_path)
    shutil.copytree(BENCH, os.path.join(root, "perfbench"),
                    ignore=shutil.ignore_patterns("__pycache__", "tests"))
    for part in ("nomad_tpu", "native"):
        os.symlink(os.path.join(ROOT, part), os.path.join(root, part))
    bench = os.path.join(root, "perfbench")

    def load(*parts):
        with open(os.path.join(bench, *parts)) as f:
            return json.load(f)

    def dump(obj, *parts):
        with open(os.path.join(bench, *parts), "w") as f:
            json.dump(obj, f)
    # the sweep's largest point, which the accepted benchmark leaves to a
    # later PR: its configuration, its kernel's work model, a mix and a
    # metric, each a new file
    for src, dst in (("config.json", ("configs", "sweep10k-r75-j1200-spread.json")),
                     ("work_model.json", ("work_models", "dense_scan.json"))):
        shutil.copy(os.path.join(HERE, "data", "new_cell", src),
                    os.path.join(bench, *dst))
    mix = load("traffic", "drain.json")
    mix["submitters"] = 4
    dump(mix, "traffic", "trickle.json")
    dump({"reader": "counter_ratio",
          "args": {"counters": ["nomad.plan.rejected_allocs"],
                   "per": ["nomad.scheduler.placements_tpu"], "scale": 100.0}},
         "layer_metrics", "plan_rejected_share.trickle.json")
    shutil.copy(os.path.join(bench, "layer_metrics", "solve_roofline.drain.json"),
                os.path.join(bench, "layer_metrics", "solve_roofline.trickle.json"))
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({
        "name": "sweep10k-r75-j1200-spread",
        "source": manifest["configs"][0]["source"],
        "file": "perfbench/configs/sweep10k-r75-j1200-spread.json",
        "reduced": [], "why": "the sweep's largest point"})
    grown["workloads"].append({
        "name": "spread-trickle", "config": "sweep10k-r75-j1200-spread",
        "traffic": "trickle", "chips": 1, "why": "four submitters"})
    grown["end_to_end"][0]["workloads"].append("spread-trickle")
    for name, unit, source, layer in (
            ("plan_rejected_share.trickle", "%", "program_counter",
             "Verify + commit"),
            ("solve_roofline.trickle", "%", "device_trace", "Kernels")):
        grown["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "placements_per_s",
            "workloads": ["spread-trickle"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(grown, f)

    # the copy's own check, on the copy's own files
    import subprocess
    import sys
    out = subprocess.run([sys.executable,
                          os.path.join(bench, "manifest.py")],
                         capture_output=True, text=True, cwd=root)
    assert out.stdout.strip() == "manifest ok", out.stdout + out.stderr
    assert mf.check(grown) != []      # the repo's own tree lacks the files

    # the new cell spreads over racks, so the reference's spread boost is
    # what its scores are held to
    rc, res, err = run_cell("spread-trickle", "--trace", "1", root=root)
    assert rc == 0 and res["correct"], err[-2000:]
    assert set(res["metrics"]) == {"plan_rejected_share.trickle"}
    assert res["info"]["placements_compared"] >= 24
    rc, res, err = run_cell("spread-trickle", root=root)
    assert rc == 0 and set(res["metrics"]) == {"placements_per_s", "setup_s"}
