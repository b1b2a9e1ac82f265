"""Test configuration: force JAX onto a virtual 8-device CPU mesh so
multi-chip sharding paths are exercised without TPU hardware, and so do
the processes tests start (the env var is inherited). The chip is met
by ``chip_smoke.py``, not by this suite.
"""
import os

_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (
        _flags + " --xla_force_host_platform_device_count=8").strip()
os.environ["JAX_PLATFORMS"] = "cpu"

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)

import hashlib  # noqa: E402
import warnings  # noqa: E402

import pytest  # noqa: E402


@pytest.fixture(autouse=True)
def _seeded_ids(request):
    """Deflake (ISSUE 6 satellite): eval/alloc ids come from one PRNG
    stream, and eval ids seed the scheduler's node shuffle -- the
    tie-break ordering for equal-score nodes. Reseeding the stream per
    test (keyed by the test's nodeid) makes placements deterministic
    run-to-run under `-p no:randomly`; host and TPU paths derive the
    same shuffle from the same ids, so parity is untouched. Assertions
    where order is GENUINELY unspecified (multi-threaded e2e timing)
    still belong on sets, not sequences."""
    from nomad_tpu.structs.job import reseed_ids

    reseed_ids(int.from_bytes(
        hashlib.blake2b(request.node.nodeid.encode(),
                        digest_size=8).digest(), "little"))
    yield


# The most interleaving-heavy suites run under the lock-order
# sanitizer in tier-1 (ISSUE 9): every acquisition-order cycle the
# checker finds is a potential deadlock the ROADMAP-2 multi-worker
# refactor would turn real, so a cycle FAILS the test. Held-across and
# escaped-frame findings are report-only here (several are known true
# positives by design, e.g. plan.commit firing under the store lock so
# an armed fault splits the batch) and surface as warnings.
_LOCKCHECK_SUITES = {
    "test_chaos", "test_dispatch_pipeline", "test_plan_batch",
    "test_churn_storm",
}

# The dispatch-heavy suites run under the device-dispatch discipline
# sanitizer in tier-1 (ISSUE 10): a steady-state retrace (same abstract
# signature traced twice at one site -- the compile cache was defeated)
# or an unsanctioned hot-path host sync FAILS the test; late traces /
# dtype drift / cache mutations surface as warnings.
_JITCHECK_SUITES = {
    "test_dispatch_pipeline", "test_lpq", "test_solver_parity",
    "test_mesh_grid",
}

# The store-heaviest suites run under the MVCC snapshot-isolation
# sanitizer in tier-1 (ISSUE 11): a torn snapshot read (two table
# versions observed inside one read / one strict verify scope) or an
# aliasing write (mutation of state reachable from a published
# snapshot or version-keyed memo) FAILS the test; journal gaps,
# write-skew witnesses and stale memos surface as warnings until the
# first triage round.
_STATECHECK_SUITES = {
    "test_plan_batch", "test_pack_delta", "test_churn_storm",
    "test_lpq", "test_worker_pool",
}

# The interleaving-heaviest suites (broker-fed batch workers, the
# group-commit applier, churn storms) additionally run under the
# deterministic schedule explorer in tier-1 (ISSUE 12): each test runs
# once under ONE of four fixed exploration seeds (chosen by test
# nodeid, so the suite as a whole exercises all four and any failure
# names its seed for `operator schedcheck --replay`).  A manifested
# deadlock or replay divergence FAILS the test; park-watchdog
# preemptions surface as warnings (they mean a thread blocked outside
# the interposition set and the schedule degraded to best-effort).
_SCHEDCHECK_SUITES = {
    "test_batch_worker", "test_plan_batch", "test_churn_storm",
    "test_worker_pool",
}
_SCHEDCHECK_SEEDS = (11, 23, 37, 53)

# The mesh-dispatching suites run under the sharding-discipline
# sanitizer in tier-1 (ISSUE 15): a spec drift (actual sharding !=
# the parallel/mesh.py registry's declaration, e.g. a silently
# replicated fleet table) or an implicit transfer (host array /
# differently-sharded array entering a mesh callable) FAILS the test;
# collective-budget excess and per-shard byte-parity findings surface
# as warnings here (the multichip dryrun asserts all four classes
# zero itself).  The compile-time HLO audit doubles one XLA compile
# per mesh program, so it runs only on the dryrun (whose programs
# already pay seconds-long compiles) and stays off for the
# dispatch-pipeline suite.
_SHARDCHECK_SUITES = {
    "test_multichip_dryrun", "test_dispatch_pipeline",
    "test_mesh_grid",
}


@pytest.fixture(autouse=True)
def _schedcheck_explorer(request):
    """Fixed-seed controlled schedules for the ISSUE-12 suites.
    Defined before the sanitizer fixtures so the controlled run brackets
    the whole test body; the sanitizer fixtures collect their findings
    (with schedule witnesses embedded) independently of run state."""
    if request.module.__name__ not in _SCHEDCHECK_SUITES:
        yield
        return
    from nomad_tpu import lockcheck, schedcheck

    seed = _SCHEDCHECK_SEEDS[int.from_bytes(
        hashlib.blake2b(request.node.nodeid.encode(),
                        digest_size=2).digest(), "little")
        % len(_SCHEDCHECK_SEEDS)]
    # lockcheck's factory seam IS schedcheck's lock/condvar
    # interposition layer: arm it silently when this suite does not
    # already run under the lockcheck fixture (its findings are
    # collected only by that fixture, never here)
    lc_was = lockcheck.enabled()
    if not lc_was:
        lockcheck.enable()
    schedcheck.enable()
    schedcheck.begin_run(seed)
    try:
        yield
    finally:
        schedcheck.end_run()
        st = schedcheck.state()
        schedcheck.disable()
        schedcheck._reset_for_tests()
        if not lc_was:
            lockcheck.disable()
            lockcheck._reset_for_tests()
    if st["preemptions"]:
        warnings.warn(
            f"schedcheck (seed {seed}): {st['preemptions']} "
            f"park-watchdog preemption(s) -- a managed thread blocked "
            f"outside the interposition set; schedule was best-effort")
    problems = []
    for r in st["reports"]:
        if r.get("kind") == "deadlock":
            waiting = ", ".join(f"{w['thread']} on {w['on']}"
                                for w in r.get("waiting") or [])
            problems.append(
                f"MANIFESTED DEADLOCK under schedule seed "
                f"{r['schedule_seed']} at step {r['step']}: [{waiting}]"
                f" (replay: operator schedcheck --replay "
                f"{r['schedule_seed']})")
        elif r.get("kind") == "divergence":
            problems.append(
                f"REPLAY DIVERGENCE at seed {r['schedule_seed']}: "
                f"expected {r['expected']} got {r['got']}")
    if problems:
        pytest.fail(
            "deterministic schedule explorer found violation(s) "
            "during this test:\n" + "\n".join(problems), pytrace=False)


@pytest.fixture(autouse=True)
def _shardcheck_sanitizer(request):
    if request.module.__name__ not in _SHARDCHECK_SUITES:
        yield
        return
    from nomad_tpu import shardcheck

    hlo_prev = os.environ.get("NOMAD_TPU_SHARDCHECK_HLO")
    # the executed multichip gates (dryrun + the ISSUE-19 mesh-shape
    # parity grid) assert collective_excess == [] themselves, so the
    # compile-time HLO audit must actually run for them
    if request.module.__name__ not in ("test_multichip_dryrun",
                                       "test_mesh_grid"):
        os.environ["NOMAD_TPU_SHARDCHECK_HLO"] = "0"
    shardcheck.enable()
    try:
        yield
        st = shardcheck.state()
    finally:
        shardcheck.disable()
        shardcheck._reset_for_tests()
        if hlo_prev is None:
            os.environ.pop("NOMAD_TPU_SHARDCHECK_HLO", None)
        else:
            os.environ["NOMAD_TPU_SHARDCHECK_HLO"] = hlo_prev
    for v in (st["collective_excess"] + st["shard_parity_reports"]):
        warnings.warn(f"shardcheck finding (report-only here): {v}")
    problems = []
    for r in st["spec_drift"]:
        problems.append(
            f"SPEC DRIFT ({r['kind']}) {r['group']}.{r['field']}: "
            f"declared {r.get('declared')} actual {r.get('actual')} "
            f"(amplification {r.get('amplification_bytes')} bytes)\n"
            f"{r.get('stack', '')}")
    for r in st["implicit_xfers"]:
        problems.append(
            f"IMPLICIT TRANSFER ({r['kind']}) {r['group']}."
            f"{r['field']} ({r['bytes']} bytes): {r['detail']}\n"
            f"{r.get('stack', '')}")
    if problems:
        pytest.fail(
            "sharding-discipline sanitizer found violation(s) during "
            "this test:\n" + "\n".join(problems), pytrace=False)


@pytest.fixture(autouse=True)
def _statecheck_sanitizer(request):
    if request.module.__name__ not in _STATECHECK_SUITES:
        yield
        return
    from nomad_tpu import statecheck

    statecheck.enable()
    try:
        yield
        st = statecheck.state()
    finally:
        statecheck.disable()
        statecheck._reset_for_tests()
    for v in (st["journal_gaps"] + st["write_skews"]
              + st["stale_memos"] + st["drifts"]):
        warnings.warn(f"statecheck finding (report-only): {v}")
    problems = []
    for r in st["torn_reads"]:
        problems.append(
            f"TORN SNAPSHOT READ ({r['kind']}) in {r['op']} at "
            f"{r['site']}: versions {r['versions']} (evals "
            f"{r['evals']})\n{r['stack']}")
    for r in st["aliasing_writes"]:
        problems.append(
            f"ALIASING WRITE ({r['kind']}) at {r['site']}: "
            f"{r['detail']}\n{r.get('stack', '')}")
    if problems:
        pytest.fail(
            "snapshot-isolation sanitizer found violation(s) during "
            "this test:\n" + "\n".join(problems), pytrace=False)


@pytest.fixture(autouse=True)
def _jitcheck_sanitizer(request):
    if request.module.__name__ not in _JITCHECK_SUITES:
        yield
        return
    from nomad_tpu import jitcheck

    jitcheck.enable()
    try:
        yield
        st = jitcheck.state()
    finally:
        jitcheck.disable()
        jitcheck._reset_for_tests()
    for v in (st["late_traces"] + st["dtype_drift"] + st["mutations"]):
        warnings.warn(f"jitcheck finding (report-only): {v}")
    problems = []
    for r in st["retraces"]:
        problems.append(
            f"STEADY-STATE RETRACE at {r['site']}: signature "
            f"{r['signature']} traced {r['count']}x "
            f"(witness old={r['witness']['old']})\n{r['stack']}")
    for r in st["host_syncs"]:
        problems.append(
            f"HOT-PATH HOST SYNC {r['kind']} at {r['site']} x"
            f"{r['count']} (dispatch {r['label']!r}, evals "
            f"{r['evals']})\n{r['stack']}")
    if problems:
        pytest.fail(
            "dispatch-discipline sanitizer found violation(s) during "
            "this test:\n" + "\n".join(problems), pytrace=False)


@pytest.fixture(autouse=True)
def _lockcheck_sanitizer(request):
    if request.module.__name__ not in _LOCKCHECK_SUITES:
        yield
        return
    from nomad_tpu import lockcheck

    lockcheck.enable()
    try:
        yield
        st = lockcheck.state()
    finally:
        lockcheck.disable()
        lockcheck._reset_for_tests()
    for v in st["held_across"] + st["escaped"]:
        warnings.warn(f"lockcheck finding (report-only): {v}")
    if st["cycles"]:
        lines = []
        for i, cyc in enumerate(st["cycles"]):
            lines.append(f"CYCLE {i}: {' -> '.join(cyc['locks'])}")
            for e in cyc["edges"]:
                lines.append(f"  edge {e['from']} -> {e['to']} "
                             f"[thread {e['thread']}]")
                lines.append(e["stack"].rstrip())
        pytest.fail(
            "lock-order sanitizer found potential deadlock cycle(s) "
            "during this test:\n" + "\n".join(lines), pytrace=False)
