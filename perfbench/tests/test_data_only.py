"""Adding a cell is data only: a new configuration, mix, cell and
per-layer metric dropped into a copy of the directories
(`conftest.standin_tree`) are listed, checked and rehearsed with no
other file touched."""
import os
import subprocess
import sys

import manifest as mf
from conftest import run_cell, standin_tree


def test_a_new_cell_needs_only_new_files(tmp_path, manifest):
    root = str(tmp_path)
    grown = standin_tree(root, manifest)

    # the copy's own check, on the copy's own files
    out = subprocess.run([sys.executable,
                          os.path.join(root, "perfbench", "manifest.py")],
                         capture_output=True, text=True, cwd=root)
    assert out.stdout.strip() == "manifest ok", out.stdout + out.stderr
    assert mf.check(grown) != []      # the repo's own tree lacks the files

    # the new cell spreads over racks, so the reference's spread boost is
    # what its scores are held to
    rc, res, err = run_cell("standin-trickle", "--trace", "1", root=root)
    assert rc == 0 and res["correct"], err[-2000:]
    assert set(res["metrics"]) == {"plan_rejected_share.standin"}
    assert res["info"]["placements_compared"] >= 24
    assert res["info"]["unreplayed_evals"] == 0
    rc, res, err = run_cell("standin-trickle", root=root)
    assert rc == 0 and set(res["metrics"]) == {"placements_per_s", "setup_s"}
