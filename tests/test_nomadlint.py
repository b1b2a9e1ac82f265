"""nomadlint driver gate + per-rule fixture tests (ISSUE 9).

THE tier-1 gate is ``test_repo_lint_clean``: the default driver run
(every AST rule + metrics-doc + knob-doc) must exit 0 against the real
tree.  Everything else proves the rules actually BITE: each one gets a
synthetic tree seeding the violation it exists to catch, because a
linter that never fired is indistinguishable from one that can't.
"""
import importlib.util
import json
import os
import textwrap

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

_spec = importlib.util.spec_from_file_location(
    "nomadlint", os.path.join(ROOT, "scripts", "nomadlint.py"))
nl = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(nl)

ALL_AST = list(nl.RULE_IDS)

# the registry every fixture tree shares (fire-registered parses it)
_FAULTINJECT = """
POINTS = (
    "good.point",
)
"""


def _tree(tmp_path, files):
    """Write a synthetic repo tree and return its root."""
    for rel, text in files.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return str(tmp_path)


def _rules(root, rules):
    kept, waived = nl.run_ast_rules(root, rules)
    return kept, waived


# ----------------------------------------------------------------------
# THE gate + driver surface


def test_repo_lint_clean(capsys):
    """Default run (AST rules + metrics-doc + knob-doc) exits 0 against
    the real repo -- the tier-1 exit-code gate the ISSUE wires in."""
    assert nl.main([]) == 0, capsys.readouterr().out


def test_list_names_every_rule(capsys):
    assert nl.main(["--list"]) == 0
    out = capsys.readouterr().out
    for rule in list(nl.RULE_IDS) + list(nl.LEGACY_RULES):
        assert rule in out


def test_unknown_rule_is_an_error(capsys):
    assert nl.main(["--rule", "no-such-rule"]) == 2
    assert "unknown rule" in capsys.readouterr().out


def test_legacy_rules_run_under_the_driver(capsys):
    """metrics-doc and knob-doc stay green when invoked as driver
    rules (their standalone scripts and tests are unchanged)."""
    assert nl.main(["--rule", "metrics-doc"]) == 0
    assert nl.main(["--rule", "knob-doc"]) == 0
    capsys.readouterr()


def test_legacy_rules_skipped_under_fixture_root(tmp_path, capsys):
    """--root points rules at a synthetic tree; the legacy checkers
    scan the real repo so the driver skips them rather than lint the
    wrong tree."""
    root = _tree(tmp_path, {
        "nomad_tpu/faultinject.py": _FAULTINJECT,
        "docs/OPERATIONS.md": "| `NOMAD_TPU_X` | on | a knob row |\n",
    })
    assert nl.main(["--root", root]) == 0
    assert "skipping legacy rule" in capsys.readouterr().out


def test_parse_error_is_a_violation(tmp_path, capsys):
    root = _tree(tmp_path, {"nomad_tpu/bad.py": "def broken(:\n"})
    assert nl.main(["--root", root]) == 1
    assert "[parse]" in capsys.readouterr().out


# ----------------------------------------------------------------------
# fire-registered


def test_fire_registered_fires_on_unregistered_point(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/faultinject.py": _FAULTINJECT,
        "nomad_tpu/mod.py": """
            def f(faults, name):
                faults.fire("good.point")
                faults.fire("never.registered")
                faults.fire(name)
            """,
    })
    kept, _ = _rules(root, ["fire-registered"])
    msgs = [v.msg for v in kept]
    assert len(kept) == 2
    assert any("never.registered" in m for m in msgs)
    assert any("string literal" in m for m in msgs)


def test_fire_registered_requires_a_registry(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/faultinject.py": "x = 1\n",
    })
    kept, _ = _rules(root, ["fire-registered"])
    assert len(kept) == 1 and "no POINTS registry" in kept[0].msg


def test_every_chaos_point_inventory_member_is_registered():
    """The real registry covers every fire() call site (the rule gates
    it) AND the chaos suite can arm every registered point: POINTS is
    the shared inventory."""
    from nomad_tpu.faultinject import POINTS, faults

    assert len(POINTS) == len(set(POINTS)) >= 9
    for point in POINTS:
        faults.arm(point, "error", count=0)
    try:
        armed = {f["point"] for f in faults.snapshot()["faults"]}
        assert set(POINTS) <= armed
    finally:
        faults.disarm_all()


# ----------------------------------------------------------------------
# killswitch-tested


def test_killswitch_tested_fires_without_a_parity_test(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/__init__.py": "",
        "docs/OPERATIONS.md": """
            | Knob | Default | Effect |
            |---|---|---|
            | `NOMAD_TPU_COVERED` | on | `0` is the kill switch |
            | `NOMAD_TPU_ORPHAN` | on | `0` is the kill switch |
            | `NOMAD_TPU_PLAIN` | 5 | not a rollback knob |
            """,
        "tests/test_parity.py": """
            def test_kill_switch(monkeypatch):
                monkeypatch.setenv("NOMAD_TPU_COVERED", "0")
            """,
    })
    kept, _ = _rules(root, ["killswitch-tested"])
    assert len(kept) == 1
    assert "NOMAD_TPU_ORPHAN" in kept[0].msg


# ----------------------------------------------------------------------
# telemetry-literal / telemetry-kind


def test_telemetry_literal_fires_on_computed_name(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/faultinject.py": _FAULTINJECT,
        "nomad_tpu/mod.py": """
            def f(metrics, series, point):
                metrics.incr(series)                  # computed: BAD
                metrics.incr("nomad.ok.literal")
                metrics.incr(f"nomad.ok.{point}")     # normalizable
            """,
    })
    kept, _ = _rules(root, ["telemetry-literal"])
    assert len(kept) == 1
    assert "`series`" in kept[0].msg


def test_telemetry_kind_fires_on_counter_vs_timer(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def f(metrics):
                metrics.incr("nomad.x.flips")
                metrics.sample_ms("nomad.x.flips", 3.0)
                metrics.incr("nomad.x.stable")
                metrics.incr("nomad.x.stable")
            """,
    })
    kept, _ = _rules(root, ["telemetry-kind"])
    assert len(kept) == 1
    assert "nomad.x.flips" in kept[0].msg
    assert "one series, one kind" in kept[0].msg


def test_telemetry_rules_ignore_non_telemetry_receivers(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def f(random, population, series):
                random.sample(population, 3)
                population.sample(series)
            """,
    })
    kept, _ = _rules(root, ["telemetry-literal", "telemetry-kind"])
    assert kept == []


# ----------------------------------------------------------------------
# sleep-under-lock


def test_sleep_under_lock_fires_on_each_hazard(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import time

            def f(self, q, ev):
                with self._lock:
                    time.sleep(0.5)
                    q.get()
                    q.get(timeout=1.0)
                    ev.wait()
                    run_dispatch(lambda: 1)
            """,
    })
    kept, _ = _rules(root, ["sleep-under-lock"])
    assert len(kept) == 5
    msgs = "\n".join(v.msg for v in kept)
    assert "time.sleep" in msgs
    assert "blocking dequeue" in msgs
    assert "ev.wait()" in msgs
    assert "device dispatch" in msgs


def test_sleep_under_lock_clean_cases(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import time

            def f(self, q, cv):
                with self._lock:
                    q.get_nowait()
                    q.get(False)          # non-blocking poll

                    def deferred():       # defined, not run, under it
                        time.sleep(1)
                with cv:
                    cv.wait()             # a condvar waits on its OWN
                time.sleep(0.1)           # lock; and no lock held here
            """,
    })
    kept, _ = _rules(root, ["sleep-under-lock"])
    assert kept == []


# ----------------------------------------------------------------------
# bare-acquire


def test_bare_acquire_fires_without_try_finally(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def f(self):
                self._lock.acquire()
                self.counter += 1
                self._lock.release()
            """,
    })
    kept, _ = _rules(root, ["bare-acquire"])
    assert len(kept) == 1
    assert "self._lock" in kept[0].msg


def test_bare_acquire_clean_with_try_finally(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def immediate(self):
                self._lock.acquire()
                try:
                    self.counter += 1
                finally:
                    self._lock.release()

            def enclosing(self, other):
                try:
                    self._lock.acquire()
                    other.acquire()       # released by a DIFFERENT
                finally:                  # receiver's finally: still
                    self._lock.release()  # a violation for `other`
            """,
    })
    kept, _ = _rules(root, ["bare-acquire"])
    assert len(kept) == 1
    assert "`other.acquire()`" in kept[0].msg


# ----------------------------------------------------------------------
# waivers


def test_waiver_with_justification_suppresses(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def f(self):
                # nomadlint: waive=bare-acquire -- released by the
                # runner thread when the job retires
                self._sem.acquire()
            """,
    })
    kept, waived = _rules(root, ["bare-acquire"])
    assert kept == [] and waived == 1


def test_waiver_on_the_violating_line(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": (
            "def f(self):\n"
            "    self._sem.acquire()"
            "  # nomadlint: waive=bare-acquire -- handed off\n"),
    })
    kept, waived = _rules(root, ["bare-acquire"])
    assert kept == [] and waived == 1


def test_waiver_without_justification_suppresses_nothing(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def f(self):
                # nomadlint: waive=bare-acquire
                self._sem.acquire()
            """,
    })
    kept, waived = _rules(root, ["bare-acquire"])
    assert len(kept) == 1 and waived == 0


def test_waiver_is_per_rule(tmp_path):
    """A bare-acquire waiver does not blanket-suppress other rules on
    the same line."""
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import time

            def f(self):
                with self._lock:
                    # nomadlint: waive=bare-acquire -- wrong rule
                    time.sleep(1)
            """,
    })
    kept, waived = _rules(root, ["sleep-under-lock"])
    assert len(kept) == 1 and waived == 0


# ----------------------------------------------------------------------
# no-callsite-jit (ISSUE 10)


def test_no_callsite_jit_fires_inside_plain_function(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import functools
            import jax

            SOLVE = jax.jit(lambda x: x)          # module level: fine

            @functools.lru_cache(maxsize=None)
            def factory(n_pad):                   # factory: fine
                return jax.jit(lambda x: x + n_pad)

            def bad(x):
                fn = jax.jit(lambda y: y * 2)     # per call: BAD
                return fn(x)
            """,
    })
    kept, _ = _rules(root, ["no-callsite-jit"])
    assert len(kept) == 1
    assert "lru_cache" in kept[0].msg
    assert kept[0].line == 12


def test_no_callsite_jit_partial_at_module_level_is_clean(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import functools
            import jax

            solve = functools.partial(
                jax.jit, static_argnames=("dtype_name",))(lambda x: x)
            """,
    })
    kept, _ = _rules(root, ["no-callsite-jit"])
    assert kept == []


# ----------------------------------------------------------------------
# no-host-sync-hot


def test_no_host_sync_hot_fires_in_hot_function(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/solver/mod.py": """
            import jax

            def hot(lane):
                out = run_dispatch(lambda: lane)
                v = out.item()                     # BAD: scalar pull
                return jax.device_get(out), v      # BAD: unsanctioned

            def sanctioned(jitcheck, out):
                run_dispatch(lambda: out)
                with jitcheck.sanctioned_fetch():
                    return jax.device_get(out)     # the designed fetch

            def cold(out):
                return jax.device_get(out)         # not a hot function
            """,
    })
    kept, _ = _rules(root, ["no-host-sync-hot"])
    assert len(kept) == 2
    msgs = "\n".join(v.msg for v in kept)
    assert "out.item" in msgs and "jax.device_get" in msgs


def test_no_host_sync_hot_fires_under_lock(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import jax

            def f(self, out):
                with self._lock:
                    return jax.device_get(out)
            """,
    })
    kept, _ = _rules(root, ["no-host-sync-hot"])
    assert len(kept) == 1
    assert "with <lock>" in kept[0].msg


# ----------------------------------------------------------------------
# dtype-threaded


def test_dtype_threaded_fires_on_bare_float64(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/solver/mod.py": """
            import jax.numpy as jnp
            import numpy as np

            def kernel(x):
                a = jnp.zeros(4, dtype=jnp.float64)     # BAD
                b = jnp.asarray(x, dtype="float64")     # BAD
                c = np.zeros(4, dtype=np.float64)       # host: fine
                return a, b, c

            def threaded(x, dtype_name):
                return jnp.zeros(4, dtype=jnp.dtype(dtype_name))
            """,
    })
    kept, _ = _rules(root, ["dtype-threaded"])
    assert len(kept) == 2
    assert all("dtype_name" in v.msg for v in kept)


def test_dtype_threaded_ignores_non_kernel_dirs(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/server/mod.py": """
            import jax.numpy as jnp

            def host_report(x):
                return jnp.zeros(4, dtype=jnp.float64)
            """,
    })
    kept, _ = _rules(root, ["dtype-threaded"])
    assert kept == []


# ----------------------------------------------------------------------
# frozen-memo


def test_frozen_memo_fires_without_freeze(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def cache_it(memo, key, arr):
                memo[key] = arr                     # BAD: no freeze

            def cache_frozen(memo, key, arr):
                arr.setflags(write=False)
                memo[key] = arr

            def not_a_memo(rows, key, arr):
                rows[key] = arr                     # plain container
            """,
    })
    kept, _ = _rules(root, ["frozen-memo"])
    assert len(kept) == 1
    assert "cache_it" not in kept[0].msg and kept[0].line == 3
    assert "memo" in kept[0].msg


def test_frozen_memo_module_cache_store(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            _BIG_CACHE = {}

            def store(key, arr):
                _BIG_CACHE[key] = arr               # BAD
            """,
    })
    kept, _ = _rules(root, ["frozen-memo"])
    assert len(kept) == 1
    assert "_BIG_CACHE" in kept[0].msg


def test_new_rules_listed_and_clean_on_real_tree(capsys):
    """--list names the dispatch-hygiene rules and the real tree is
    clean under them (justified waivers only) -- the acceptance gate
    for ISSUE 10's lint half. (The default run in
    test_repo_lint_clean covers them too; this pins the rule ids.)"""
    assert nl.main(["--list"]) == 0
    out = capsys.readouterr().out
    for rule in ("no-callsite-jit", "no-host-sync-hot",
                 "dtype-threaded", "frozen-memo"):
        assert rule in out
    assert nl.main(["--rule", "no-callsite-jit",
                    "--rule", "no-host-sync-hot",
                    "--rule", "dtype-threaded",
                    "--rule", "frozen-memo"]) == 0, \
        capsys.readouterr().out


# ----------------------------------------------------------------------
# fetch-accounted (ISSUE 13)


def test_fetch_accounted_fires_on_untagged_site(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/solver/mod.py": """
            def fetch(jitcheck, jax, out, tag):
                with jitcheck.sanctioned_fetch():       # BAD: no tag
                    a = jax.device_get(out)
                with jitcheck.sanctioned_fetch(""):     # BAD: empty
                    b = jax.device_get(out)
                with jitcheck.sanctioned_fetch(tag):    # BAD: computed
                    c = jax.device_get(out)
                with jitcheck.sanctioned_fetch("wave"):  # ok
                    d = jax.device_get(out)
                return a, b, c, d
            """,
    })
    kept, _ = _rules(root, ["fetch-accounted"])
    assert len(kept) == 3
    assert all("ledger tag" in v.msg for v in kept)


def test_fetch_accounted_clean_and_waivable(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/solver/mod.py": """
            def fetch(jitcheck, jax, out):
                # nomadlint: waive=fetch-accounted -- fixture reason
                with jitcheck.sanctioned_fetch():
                    return jax.device_get(out)
            """,
    })
    kept, waived = _rules(root, ["fetch-accounted"])
    assert kept == [] and waived == 1


def test_fetch_accounted_clean_on_real_tree(capsys):
    """Every real sanctioned_fetch site carries its transport tag --
    the acceptance gate for ISSUE 13's lint half."""
    assert nl.main(["--rule", "fetch-accounted"]) == 0, \
        capsys.readouterr().out


# ----------------------------------------------------------------------
# store-discipline rules (ISSUE 11)


def test_no_direct_table_write_fires_outside_state(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/rogue.py": """
            def corrupt(server, alloc):
                server.state.alloc_table.upsert(alloc)      # BAD
                server.state.alloc_table.cpu[0] = 9.0       # BAD
                store = server.state
                store._allocs[alloc.id] = alloc             # BAD

            def fine_reads(server, ids):
                return server.state.alloc_table.fold_verify(ids)
            """,
        "nomad_tpu/state/owner.py": """
            def legit(self, alloc):
                self.alloc_table.upsert(alloc)   # the owner may
            """,
    })
    kept, _ = _rules(root, ["no-direct-table-write"])
    assert len(kept) == 3, kept
    assert all(v.path == "nomad_tpu/rogue.py" for v in kept)
    assert any("mutator" in v.msg.lower() or "upsert" in v.msg
               for v in kept)


def test_no_direct_table_write_ignores_private_twins(tmp_path):
    """A broker's own ``self._evals`` dict is its to write -- only
    store/state receivers are the rule's business."""
    root = _tree(tmp_path, {
        "nomad_tpu/server/broker.py": """
            class Broker:
                def track(self, ev):
                    self._evals[ev.id] = ev     # broker-private dict
            """,
    })
    kept, _ = _rules(root, ["no-direct-table-write"])
    assert kept == []


def test_version_keyed_memo_fires_on_content_blind_key(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/solver/caches.py": """
            _SOLVE_CACHE = {}

            def remember(job_id, result):
                _SOLVE_CACHE[job_id] = result       # BAD: no version

            def remember_versioned(job_id, version, result):
                key = (version, job_id)
                _SOLVE_CACHE[key] = result          # version-keyed

            def remember_token_in_entry(job_id, token, result):
                _SOLVE_CACHE[job_id] = (token, result)  # entry-token

            def per_call_lookup(nodes):
                node_cache = {}
                for n in nodes:
                    node_cache[n.id] = n            # call-scoped
                return node_cache
            """,
    })
    kept, _ = _rules(root, ["version-keyed-memo"])
    assert len(kept) == 1, kept
    assert kept[0].line == 5


def test_version_keyed_memo_scoped_to_store_derived_dirs(tmp_path):
    """Codec/jobspec content caches are out of scope -- keys there are
    content, not fleet state."""
    root = _tree(tmp_path, {
        "nomad_tpu/structs/codec.py": """
            _HINT_CACHE = {}

            def hints(cls):
                _HINT_CACHE[cls] = dir(cls)
            """,
    })
    kept, _ = _rules(root, ["version-keyed-memo"])
    assert kept == []


def test_no_snapshot_escape_fires_on_attr_and_global(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/server/holder.py": """
            class Sched:
                def __init__(self, server):
                    self._snap = server.state.snapshot()   # BAD

                def process(self, server):
                    snap = server.state.snapshot()         # local: fine
                    return snap.nodes()
            """,
        "nomad_tpu/server/globalsnap.py": """
            import nomad_tpu.server.core as core

            SNAP = core.SERVER.state.snapshot()            # BAD
            """,
    })
    kept, _ = _rules(root, ["no-snapshot-escape"])
    assert len(kept) == 2, kept
    assert {v.path for v in kept} == {"nomad_tpu/server/holder.py",
                                      "nomad_tpu/server/globalsnap.py"}


def test_no_snapshot_escape_ignores_other_snapshots(tmp_path):
    """metrics.snapshot() / faults.snapshot() are registry dumps, not
    MVCC state views."""
    root = _tree(tmp_path, {
        "nomad_tpu/server/tele.py": """
            class Sink:
                def __init__(self, metrics):
                    self._last = metrics.snapshot()
            """,
    })
    kept, _ = _rules(root, ["no-snapshot-escape"])
    assert kept == []


def test_delta_carried_fires_on_deltaless_allocs_bump(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/state/store.py": """
            class Store:
                def delete_allocs(self, ids):
                    pairs = [(i, None) for i in ids]
                    return self._bump("allocs", delta=pairs)

                def sloppy_write(self):
                    return self._bump("allocs")            # BAD

                def node_write(self):
                    return self._bump("nodes")             # not allocs
            """,
    })
    kept, _ = _rules(root, ["delta-carried"])
    assert len(kept) == 1
    assert kept[0].line == 8


def test_store_discipline_rules_clean_on_real_tree(capsys):
    """The acceptance gate for ISSUE 11's lint half: the real tree is
    clean under all four store-discipline rules (justified waivers
    only)."""
    assert nl.main(["--list"]) == 0
    out = capsys.readouterr().out
    for rule in ("no-direct-table-write", "version-keyed-memo",
                 "no-snapshot-escape", "delta-carried"):
        assert rule in out
    assert nl.main(["--rule", "no-direct-table-write",
                    "--rule", "version-keyed-memo",
                    "--rule", "no-snapshot-escape",
                    "--rule", "delta-carried"]) == 0, \
        capsys.readouterr().out


# ----------------------------------------------------------------------
# schedule-hygiene rules (ISSUE 12)


def test_join_with_timeout_fires_and_exempts_shutdown(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def pump(self, t, proc):
                t.join()                    # BAD: indefinite join
                self._done.wait()           # BAD: indefinite event wait
                proc.wait()                 # subprocess reap: fine
                while t.is_alive():
                    t.join(timeout=5.0)     # bounded: fine

            def shutdown(self, t):
                t.join()                    # shutdown path: fine
            """,
    })
    kept, _ = _rules(root, ["join-with-timeout"])
    assert len(kept) == 2, kept
    msgs = "\n".join(v.msg for v in kept)
    assert "t.join()" in msgs and "self._done.wait()" in msgs


def test_no_sleep_sync_fires_in_test_body_only(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/__init__.py": "",
        "tests/test_mod.py": """
            import time

            def test_sync_by_sleep(server):
                server.start()
                time.sleep(0.3)             # BAD: sleep-as-sync
                assert server.done

            def test_poll_loop_is_fine(server):
                while not server.done:
                    time.sleep(0.01)        # poll interval: fine

            def test_nested_stub_is_fine(server):
                def slow_commit():
                    time.sleep(0.5)         # simulated work: fine
                server.commit_fn = slow_commit

            def helper_not_a_test():
                time.sleep(1.0)             # not a test body
            """,
    })
    kept, _ = _rules(root, ["no-sleep-sync"])
    assert len(kept) == 1, kept
    assert kept[0].path == "tests/test_mod.py" and kept[0].line == 6


def test_daemon_declared_fires_without_kwarg(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import threading

            def spawn(fn):
                t = threading.Thread(target=fn)            # BAD
                good = threading.Thread(target=fn, daemon=True)
                also = threading.Thread(target=fn, daemon=False)
                return t, good, also
            """,
    })
    kept, _ = _rules(root, ["daemon-declared"])
    assert len(kept) == 1
    assert kept[0].line == 5


# ----------------------------------------------------------------------
# shard-hygiene rules (ISSUE 15)


def test_spec_declared_fires_outside_parallel(tmp_path):
    """An inline PartitionSpec/NamedSharding outside nomad_tpu/parallel/
    is a sharding contract the registry (and shardcheck) never sees --
    including the repo's `as P` aliasing idiom."""
    root = _tree(tmp_path, {
        "nomad_tpu/solver/mod.py": """
            from jax.sharding import NamedSharding, PartitionSpec as P

            def put(mesh, x, jax):
                spec = P("evals", "nodes")                 # BAD
                return jax.device_put(x, NamedSharding(mesh, spec))
            """,
        "nomad_tpu/parallel/mesh.py": """
            from jax.sharding import NamedSharding, PartitionSpec as P

            def declared(mesh):
                return NamedSharding(mesh, P("evals"))     # home turf
            """,
    })
    kept, _ = _rules(root, ["spec-declared"])
    assert {(v.path, v.line) for v in kept} == {
        ("nomad_tpu/solver/mod.py", 5),
        ("nomad_tpu/solver/mod.py", 6)}, kept
    assert all("registry" in v.msg for v in kept)


def test_spec_declared_waivable(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/solver/mod.py": """
            from jax.sharding import PartitionSpec

            # nomadlint: waive=spec-declared -- bench-only probe spec
            spec = PartitionSpec("evals")
            """,
    })
    kept, waived = _rules(root, ["spec-declared"])
    assert kept == [] and waived == 1


def test_mesh_factory_fires_on_inline_mesh(tmp_path):
    root = _tree(tmp_path, {
        "nomad_tpu/solver/mod.py": """
            import numpy as np
            from jax.sharding import Mesh

            def topology(jax):
                return Mesh(np.asarray(jax.devices()), ("evals",))
            """,
        "nomad_tpu/parallel/mesh.py": """
            from jax.sharding import Mesh

            def make_mesh(grid):
                return Mesh(grid, ("evals", "nodes"))      # the factory
            """,
    })
    kept, _ = _rules(root, ["mesh-factory"])
    assert len(kept) == 1, kept
    assert kept[0].path == "nomad_tpu/solver/mod.py"
    assert "make_mesh" in kept[0].msg


def test_no_implicit_put_fires_on_sharded_put(tmp_path):
    """device_put carrying a sharding outside parallel/ bypasses the
    ledger's per-shard rows; plain (unsharded) puts stay legal
    everywhere."""
    root = _tree(tmp_path, {
        "nomad_tpu/solver/mod.py": """
            import jax

            def ship(x, sharding, mesh_sharding):
                a = jax.device_put(x, sharding)            # BAD
                b = jax.device_put(x, device=mesh_sharding)  # BAD
                c = jax.device_put(x)                      # plain: fine
                d = jax.device_put(x, jax.devices()[0])    # device: fine
                return a, b, c, d
            """,
        "nomad_tpu/parallel/mesh.py": """
            import jax

            def shard_eval_axis(x, sharding):
                return jax.device_put(x, sharding)         # home turf
            """,
    })
    kept, _ = _rules(root, ["no-implicit-put"])
    assert {v.line for v in kept} == {5, 6}, kept
    assert all(v.path == "nomad_tpu/solver/mod.py" for v in kept)
    assert all("shard_solver_inputs" in v.msg for v in kept)


def test_shard_hygiene_rules_clean_on_real_tree(capsys):
    """The acceptance gate for ISSUE 15's lint half: the real tree is
    clean under all three shard-hygiene rules (the binpack wave
    transport now routes through parallel/mesh.py)."""
    assert nl.main(["--rule", "spec-declared", "--rule", "mesh-factory",
                    "--rule", "no-implicit-put"]) == 0
    out = capsys.readouterr().out
    assert "clean" in out


def test_schedule_hygiene_rules_clean_on_real_tree(capsys):
    """The acceptance gate for ISSUE 12's lint half: the real tree is
    clean under all three schedule-hygiene rules (justified waivers
    only)."""
    assert nl.main(["--list"]) == 0
    out = capsys.readouterr().out
    for rule in ("join-with-timeout", "no-sleep-sync",
                 "daemon-declared"):
        assert rule in out
    assert nl.main(["--rule", "join-with-timeout",
                    "--rule", "no-sleep-sync",
                    "--rule", "daemon-declared"]) == 0, \
        capsys.readouterr().out


# ----------------------------------------------------------------------
# --sarif (ISSUE 12 satellite)


def test_sarif_round_trip_on_seeded_violation(tmp_path, capsys):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import time

            def f(self):
                with self._lock:
                    time.sleep(1)
            """,
    })
    out_path = str(tmp_path / "out.sarif")
    rc = nl.main(["--root", root, "--rule", "sleep-under-lock",
                  "--sarif", out_path])
    capsys.readouterr()
    assert rc == 1
    with open(out_path) as f:
        doc = json.load(f)
    assert doc["version"] == "2.1.0"
    run = doc["runs"][0]
    assert run["tool"]["driver"]["name"] == "nomadlint"
    assert any(r["id"] == "sleep-under-lock"
               for r in run["tool"]["driver"]["rules"])
    res = run["results"]
    assert len(res) == 1
    assert res[0]["ruleId"] == "sleep-under-lock"
    assert res[0]["level"] == "error"
    loc = res[0]["locations"][0]["physicalLocation"]
    assert loc["artifactLocation"]["uri"] == "nomad_tpu/mod.py"
    assert loc["region"]["startLine"] == 6


def test_sarif_clean_tree_has_no_results(tmp_path, capsys):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": "def fine():\n    return 1\n",
    })
    out_path = str(tmp_path / "clean.sarif")
    rc = nl.main(["--root", root, "--rule", "sleep-under-lock",
                  "--sarif", out_path])
    capsys.readouterr()
    assert rc == 0
    with open(out_path) as f:
        doc = json.load(f)
    assert doc["runs"][0]["results"] == []


# ----------------------------------------------------------------------
# --fix-stale-waivers (ISSUE 12 satellite)

_WAIVER_TREE = {
    "nomad_tpu/mod.py": """
        import time

        def live(lock):
            with lock:
                # nomadlint: waive=sleep-under-lock -- fixture
                time.sleep(1)

        def stale(x):
            # nomadlint: waive=sleep-under-lock -- nothing here
            return x

        def half_stale(lock):
            with lock:
                # nomadlint: waive=sleep-under-lock,bare-acquire -- x
                time.sleep(2)
        """,
}


def test_fix_stale_waivers_dry_run_lists_only(tmp_path, capsys):
    root = _tree(tmp_path, _WAIVER_TREE)
    before = (tmp_path / "nomad_tpu/mod.py").read_text()
    rc = nl.main(["--root", root, "--fix-stale-waivers"])
    out = capsys.readouterr().out
    assert rc == 0
    assert "dry-run" in out and "nomad_tpu/mod.py:10" in out
    assert "1 waiver line(s)" in out
    # the tree is untouched
    assert (tmp_path / "nomad_tpu/mod.py").read_text() == before


def test_fix_stale_waivers_apply_rewrites(tmp_path, capsys):
    root = _tree(tmp_path, _WAIVER_TREE)
    rc = nl.main(["--root", root, "--fix-stale-waivers", "--apply"])
    out = capsys.readouterr().out
    assert rc == 0 and "removed" in out
    text = (tmp_path / "nomad_tpu/mod.py").read_text()
    # the stale waiver line is gone; the live one (still suppressing a
    # sleep-under-lock) and the half-stale multi-rule one survive
    assert text.count("nomadlint: waive=") == 2
    assert "nothing here" not in text
    # idempotent + the tree still lints the same
    kept, waived = _rules(root, ["sleep-under-lock"])
    assert kept == [] and waived == 2


# ----------------------------------------------------------------------
# --stats (ISSUE 11 satellite)


def test_stats_inventory_and_stale_waiver(tmp_path, capsys):
    """--stats prints per-rule fired/waived/kept counts and lists
    waivers whose rule no longer fires on their line (removable),
    exiting 1 while any exist."""
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            import time

            def live_waiver(lock):
                with lock:
                    # nomadlint: waive=sleep-under-lock -- test fixture
                    time.sleep(1)

            def unwaived(lock):
                with lock:
                    time.sleep(2)

            def stale(x):
                # nomadlint: waive=sleep-under-lock -- nothing sleeps
                # here anymore
                return x
            """,
    })
    rc = nl.main(["--root", root, "--stats",
                  "--rule", "sleep-under-lock"])
    out = capsys.readouterr().out
    assert rc == 1
    assert "sleep-under-lock" in out
    # 2 fired, 1 waived, 1 kept
    import re as _re
    m = _re.search(r"sleep-under-lock\s+(\d+)\s+(\d+)\s+(\d+)", out)
    assert m and (m.group(1), m.group(2), m.group(3)) == ("2", "1", "1")
    assert "stale waivers" in out
    assert "nomad_tpu/mod.py:14" in out


def test_stats_clean_tree_exits_zero(tmp_path, capsys):
    root = _tree(tmp_path, {
        "nomad_tpu/mod.py": """
            def fine():
                return 1
            """,
    })
    assert nl.main(["--root", root, "--stats"]) == 0
    assert "no stale waivers" in capsys.readouterr().out


def test_stats_on_real_tree_has_no_stale_waivers(capsys):
    """Every standing waiver in the repo still suppresses something --
    dead waivers cannot accumulate."""
    assert nl.main(["--stats"]) == 0, capsys.readouterr().out
