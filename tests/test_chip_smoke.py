"""chip_smoke.py, as far as a machine without the chip can take it: the
same phase functions the chip run drives at 10,000 nodes, here at toy
size on the virtual CPU devices, and the refusal to run off a TPU."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

import chip_smoke  # noqa: E402

SEED = 5
NODES = 200


@pytest.fixture(autouse=True)
def clean_slate():
    from nomad_tpu.faultinject import faults
    from nomad_tpu.server.telemetry import metrics
    from nomad_tpu.solver import constcache, guard, xferobs

    def reset():
        guard._reset_for_tests()
        faults._reset_for_tests()
        constcache._reset_for_tests()
        xferobs._reset_for_tests()
        metrics.reset()
    reset()
    yield
    reset()


def test_refuses_to_run_off_a_tpu():
    """JAX falls back to the CPU without raising, so the smoke has to
    look: pinned to CPU it exits non-zero naming the platform it found,
    and prints no result."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py")],
        env=dict(os.environ, JAX_PLATFORMS="cpu"), cwd=REPO,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode != 0
    assert "'cpu'" in proc.stderr and "not a TPU" in proc.stderr
    assert "platform=cpu" in proc.stdout      # what it found, first
    assert '"ok"' not in proc.stdout


def test_served_path_and_mesh_phases_at_toy_size():
    """HTTP -> broker -> batch workers -> fused dispatch -> verify ->
    commit for all four served rounds, with the device-did-the-work
    verdict after each; then, on the eight virtual devices, the
    several-chips phase: mesh dispatch taken, eval axis sharded, every
    device shipped bytes."""
    native = chip_smoke.phase_native()
    assert native["library"].startswith("native/build/")
    served = chip_smoke.phase_served(SEED, n_nodes=NODES, n_jobs=8,
                                     per_job=25, dense_per_job=8)
    assert served["allocs_run"] == 2 * 8 * 25
    assert [r["round"] for r in served["rounds"]] == [
        1, 2, "dense", "as it comes"]
    assert served["native_verify_hits"] > 0
    assert all(v == 0 for v in served["masks"].values())
    mesh = chip_smoke.phase_mesh(served)
    assert mesh["devices"] == 8
    assert mesh["mesh_dispatches"] > 0
    assert set(mesh["per_shard_bytes"]["compact"]) == {
        f"d{i}" for i in range(8)}
    json.dumps({"served": served, "mesh": mesh})     # the report line's shape


def test_last_line_is_the_verdict_and_nothing_else():
    """The driver reads the last line: exactly ``ok`` and ``device``,
    the device exactly ``platform``, ``kind`` (text) and ``count`` (a
    whole number). Everything else belongs to the report line."""
    got = json.loads(json.dumps(chip_smoke.verdict(
        chip_smoke.describe_device())))
    assert set(got) == {"ok", "device"} and got["ok"] is True
    assert set(got["device"]) == {"platform", "kind", "count"}
    assert got["device"]["platform"] == "cpu"
    assert isinstance(got["device"]["kind"], str)
    assert type(got["device"]["count"]) is int


def test_parity_and_every_program_at_toy_size():
    parity = chip_smoke.phase_parity(SEED, n_nodes=NODES, count=50)
    assert parity["mismatch"] == 0 and parity["placements"] == 50
    programs = chip_smoke.phase_programs(SEED, n_nodes=NODES, count=40,
                                         preempt_count=8)
    moved = {name: rec["counters"] for name, rec in programs.items()}
    assert set(moved) == {
        "system", "wave_compact_spread_affinity",
        "dense_distinct_property_devices", "wave_preempt_devices",
        "dense_preempt_devices"}
    assert all(n > 0 for c in moved.values() for n in c.values())
    assert programs["system"]["placements"] == NODES


def test_lpq_phase_at_toy_size():
    lpq = chip_smoke.phase_lpq(SEED, n_nodes=NODES, n_jobs=16, per_job=4)
    assert lpq["allocs_run"] == 64
    assert lpq["counters"]["nomad.lpq.solves"] > 0


def test_breaker_drill_closes_in_process(monkeypatch):
    def refuse(*a, **kw):
        raise AssertionError("the drill started a child process")
    monkeypatch.setattr(subprocess, "Popen", refuse)
    drill = chip_smoke.phase_breaker_drill(SEED)
    assert drill["tripped"] and drill["closed_in_process"]
    assert drill["recoveries"] == 1


def test_a_masked_device_fails_the_phase():
    """The verdict is not decoration: one host fallback and the phase
    raises."""
    from nomad_tpu.solver import guard

    w = chip_smoke.Window()
    assert guard.run_dispatch(lambda: 1) == 1
    chip_smoke.device_did_the_work("probe", w)
    guard.note_host_fallback()
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="host_fallback_dispatches = 1"):
        chip_smoke.device_did_the_work("probe", w)
