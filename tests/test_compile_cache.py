"""The persistent XLA compile cache is placed from outside
(solver/cache.py): JAX_COMPILATION_CACHE_DIR when set, otherwise one
fixed path inside the checkout -- the path is part of the cache key, so
it must not move between processes."""
import os
import re
import subprocess
import sys

import jax

from nomad_tpu.solver import cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _recorded_updates(monkeypatch):
    calls = []
    monkeypatch.setattr(jax.config, "update",
                        lambda name, value: calls.append((name, value)))
    cache.enable_compile_cache()
    return calls


def test_env_var_set_means_code_names_no_directory(monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/x")
    calls = _recorded_updates(monkeypatch)
    assert "jax_compilation_cache_dir" not in [name for name, _ in calls]


def test_unset_means_the_checkout_default(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    calls = _recorded_updates(monkeypatch)
    assert ("jax_compilation_cache_dir",
            os.path.join(REPO, ".jax_cache")) in calls


def _cache_dir_of_a_fresh_process(**env):
    base = {k: v for k, v in os.environ.items()
            if k != "JAX_COMPILATION_CACHE_DIR"}
    proc = subprocess.run(
        [sys.executable, "-c",
         "import nomad_tpu.solver, jax; "
         "print(jax.config.jax_compilation_cache_dir)"],
        env=dict(base, **env), cwd=REPO, capture_output=True, text=True,
        timeout=120, check=True)
    return proc.stdout.strip().splitlines()[-1]


def test_path_is_stable_across_processes_and_on_before_any_compile():
    """Importing the solver package -- which every program factory sits
    behind -- is what switches the cache on; two processes agree on
    where, and nothing of the process (uid, pid, clock, tmp) is in it."""
    first = _cache_dir_of_a_fresh_process()
    second = _cache_dir_of_a_fresh_process(TMPDIR="/var/tmp")
    assert first == second == os.path.join(REPO, ".jax_cache")
    assert _cache_dir_of_a_fresh_process(
        JAX_COMPILATION_CACHE_DIR="/x") == "/x"


def test_directory_is_set_at_one_place_in_the_tree():
    hits = []
    for root in ("nomad_tpu", "scripts"):
        for dirpath, _dirs, files in os.walk(os.path.join(REPO, root)):
            hits += [os.path.join(dirpath, f) for f in files
                     if f.endswith(".py")]
    hits += [os.path.join(REPO, f) for f in
             ("chip_smoke.py", "__graft_entry__.py")]
    setters = [
        os.path.relpath(p, REPO) for p in hits
        if re.search(r"""update\(\s*["']jax_compilation_cache_dir""",
                     open(p, encoding="utf-8").read())]
    assert setters == ["nomad_tpu/solver/cache.py"]
