"""The one command, end to end on the CPU at a tiny fleet: both mixes,
the last line's keys, and no result when asked to measure without a
TPU."""
import pytest
from conftest import run_cell

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell,trace", [("binpack-drain", "0"),
                                        ("binpack-paced", "1"),
                                        ("binpack-drain", "1"),
                                        ("binpack-paced", "0")])
def test_cell_rehearses(cell, trace, manifest):
    rc, res, err = run_cell(cell, "--trace", trace)
    assert rc == 0, err[-2000:]
    assert KEYS <= set(res)
    assert list(res)[-1] == "compared"
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] > 0
    assert res["device"]["platform"] == "cpu"
    kind = "per_layer" if trace == "1" else "end_to_end"
    listed = {m["name"] for m in manifest[kind]
              if cell in m.get("workloads", [cell])}
    assert set(res["metrics"]) <= listed
    if trace == "0":
        assert set(res["metrics"]) == listed
        assert all(v["value"] > 0 for v in res["metrics"].values())
    else:
        # nothing from a CPU run stands under a device metric's name
        device = {m["name"] for m in manifest["per_layer"]
                  if m["source"] == "device_trace"}
        assert not device & set(res["metrics"])
        assert res["device"]["busy_s"] == 0.0


def test_no_tpu_no_result():
    rc, res, err = run_cell("binpack-drain", rehearse="")
    assert rc != 0 and res is None
    assert "cpu" in err
