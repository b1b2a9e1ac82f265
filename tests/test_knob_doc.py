"""Knob-doc CI gate (scripts/check_knob_doc.py): every NOMAD_TPU_* env
knob read in code must appear in a docs/OPERATIONS.md knob table row --
the configuration mirror of the check_metrics_doc gate, tier-1 so knob
drift fails the build, not the operator mid-incident."""
import importlib.util
import os

_spec = importlib.util.spec_from_file_location(
    "check_knob_doc",
    os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "scripts", "check_knob_doc.py"))
ckd = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ckd)


def test_repo_knob_doc_in_sync(capsys):
    """THE gate: exit 0 against the real repo."""
    assert ckd.main() == 0, capsys.readouterr().out


def test_code_knob_scan_finds_known_call_sites():
    knobs = ckd.code_knobs()
    # single-line .get, multi-line .get, subscript, pop, and the
    # module-constant indirection are all call-site shapes in-repo
    for k in ("NOMAD_TPU_LPQ", "NOMAD_TPU_LPQ_BATCH",
              "NOMAD_TPU_DELTA_JOURNAL", "NOMAD_TPU_PACK_ARENA_MB",
              "NOMAD_TPU_LEAN_ALLOC_METRICS", "NOMAD_TPU_PLUGIN_MAGIC",
              "NOMAD_TPU_PACK_ARENA_ENTRIES"):
        assert k in knobs, f"{k} not detected ({sorted(knobs)[:5]}...)"
    # locations are file:line
    assert all(":" in at for at in knobs.values())


def test_knob_census_holds_after_the_switches_went():
    """PR 32 took the thirteen kill switches no benchmark cell sets,
    each with its off side; the census may shrink, never grow back."""
    gone = {"NOMAD_TPU_" + k for k in (
        "DISPATCH_DEPTH", "BATCH_FIXPOINT", "PACK_ARENA", "PACK_CACHE",
        "PACK_DELTA", "CONST_CACHE", "DELTA_STREAM", "PLAN_BATCH",
        "WAVEFRONT", "WAVEFRONT_PREEMPT", "WAVE_BLOCK", "WAVE_UNROLL",
        "WAVE_GATHER")}
    knobs = set(ckd.code_knobs())
    assert not knobs & gone, sorted(knobs & gone)
    assert len(knobs) <= 87, len(knobs)


def test_documented_knobs_parse_tables_only():
    doc = (
        "prose mention of `NOMAD_TPU_PROSE_ONLY` does not count\n"
        "| `NOMAD_TPU_FULL` | on | a row |\n"
        "| `NOMAD_TPU_FLAP` / `_THRESHOLD` / `_WINDOW` | 3 | family |\n"
        "| `NOMAD_TPU_CONST_CACHE_ENTRIES` / `_MB` | 64 / 256 | x |\n")
    literal, expanded = ckd.documented_knobs(doc)
    assert "NOMAD_TPU_PROSE_ONLY" not in expanded
    assert "NOMAD_TPU_FULL" in literal
    # suffix shorthand expands against the row's full knob...
    assert "NOMAD_TPU_FLAP_THRESHOLD" in expanded
    assert "NOMAD_TPU_FLAP_WINDOW" in expanded
    # ...including segment-stripped bases (ENTRIES -> _MB sibling)
    assert "NOMAD_TPU_CONST_CACHE_MB" in expanded
    # expansions never count as literal (no phantom stale warnings)
    assert "NOMAD_TPU_FLAP_THRESHOLD" not in literal


def test_missing_knob_fails(tmp_path, monkeypatch, capsys):
    """A code knob absent from every table row exits 1 and names the
    knob + call site."""
    pkg = tmp_path / "nomad_tpu"
    pkg.mkdir()
    (pkg / "mod.py").write_text(
        'import os\n'
        'A = os.environ.get("NOMAD_TPU_DOCUMENTED", "1")\n'
        'B = os.environ.get(\n'
        '    "NOMAD_TPU_FORGOTTEN", "0")\n')
    docs = tmp_path / "docs"
    docs.mkdir()
    (docs / "OPERATIONS.md").write_text(
        "| `NOMAD_TPU_DOCUMENTED` | 1 | fine |\n")
    monkeypatch.setattr(ckd, "ROOT", str(tmp_path))
    monkeypatch.setattr(ckd, "DOC", str(docs / "OPERATIONS.md"))
    assert ckd.main() == 1
    out = capsys.readouterr().out
    assert "NOMAD_TPU_FORGOTTEN" in out
    assert "mod.py:3" in out
    # only the missing knob is listed as drift
    drift_lines = [ln for ln in out.splitlines() if ln.startswith("  ")]
    assert len(drift_lines) == 1, out
