import json
import os
import shutil
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, BENCH)


def run_cell(workload, *extra, root=ROOT, env=None, seconds="2",
             rehearse="0.03", seed="2147483700"):
    """One run of the one command, on the CPU; returns (exit code, last
    stdout line decoded or None, stderr)."""
    cmd = [sys.executable, os.path.join(root, "perfbench", "run.py"),
           "--workload", workload, "--seed", seed, "--seconds", seconds]
    if rehearse:
        cmd += ["--rehearse", rehearse]
    cmd += list(extra)
    full_env = dict(os.environ, JAX_PLATFORMS="cpu", **(env or {}))
    p = subprocess.run(cmd, cwd=root, env=full_env, capture_output=True,
                       text=True, timeout=600)
    lines = p.stdout.strip().splitlines()
    return p.returncode, (json.loads(lines[-1]) if lines else None), p.stderr


def standin_tree(root, manifest):
    """A copy of the benchmark under `root` to which a deployment has
    been added as a later PR adds one, by new files and appended entries
    alone: a stand-in for the sweep's spread corner (data/standin/: its
    configuration and its kernel's work model, under names no real
    deployment will take), a mix, a cell and two per-layer metrics.
    Returns the grown manifest, which `root`/BENCHMARK.json holds."""
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
    for src, dst in (
            ("config.json", ("configs", "standin-spread.json")),
            ("work_model.json", ("work_models", "standin_scan.json"))):
        shutil.copy(os.path.join(HERE, "data", "standin", src),
                    os.path.join(bench, *dst))
    mix = load("traffic", "drain.json")
    mix["submitters"] = 4
    dump(mix, "traffic", "standin-trickle.json")
    dump({"reader": "counter_ratio",
          "args": {"counters": ["nomad.plan.rejected_allocs"],
                   "per": ["nomad.scheduler.placements_tpu"], "scale": 100.0}},
         "layer_metrics", "plan_rejected_share.standin.json")
    dump(load("layer_metrics", "solve_roofline.drain.json"),
         "layer_metrics", "solve_roofline.standin.json")
    grown = json.loads(json.dumps(manifest))
    grown["configs"].append({
        "name": "standin-spread",
        "source": manifest["configs"][0]["source"],
        "file": "perfbench/configs/standin-spread.json",
        "reduced": [], "why": "the sweep's largest point"})
    grown["workloads"].append({
        "name": "standin-trickle", "config": "standin-spread",
        "traffic": "standin-trickle", "chips": 1, "why": "four submitters"})
    grown["end_to_end"][0]["workloads"].append("standin-trickle")
    for name, unit, source, layer in (
            ("plan_rejected_share.standin", "%", "program_counter",
             "Verify + commit"),
            ("solve_roofline.standin", "%", "device_trace", "Kernels")):
        grown["per_layer"].append({
            "name": name, "unit": unit, "better": "lower", "source": source,
            "layer": layer, "moves": "placements_per_s",
            "workloads": ["standin-trickle"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(grown, f)
    return grown


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
