import json
import os
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


@pytest.fixture(scope="session")
def manifest():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return json.load(f)
