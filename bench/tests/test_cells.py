"""Each cell's whole run at a tiny scale on the CPU: the program under the
configuration's plan agrees with the traffic's numpy reference, and the
result line holds the metrics the cell reports."""

import pytest

from bench import roofline
from bench.tests import tiny

SPEC = tiny.spec()
CELLS = [w["name"] for w in SPEC["workloads"]]


@pytest.mark.parametrize("name", CELLS)
def test_cell_agrees_with_its_reference(name, tmp_path):
    result, checks = tiny.run(name, tmp_path)
    assert result["correct"], checks
    assert result["failed"] == 0
    if "max_rel_err" in checks:
        assert checks["max_rel_err"]["value"] < 1e-5
    else:  # a search: levels are exact
        assert checks["mismatched_vertices"]["value"] == 0
        assert checks["missing_supersteps"]["value"] == 0
    assert checks["missing_vertices"]["value"] == 0
    want = {m["name"] for m in SPEC["end_to_end"]
            if name in m.get("workloads", [name])}
    assert set(result["metrics"]) == want
    for m in result["metrics"].values():
        assert m["value"] > 0
    if tiny.cell(name).traffic.get("halts"):
        assert result["attempted"] >= 1  # K >= 1 searches
    else:
        assert result["attempted"] >= 3  # the warm-up superstep and K >= 2
    assert set(result["device"]) >= {"platform", "kind", "count",
                                      "memory_peak_bytes"}


@pytest.mark.parametrize("name", CELLS)
def test_traced_run_reports_per_layer_metrics(name, tmp_path, monkeypatch):
    # the CPU has no entry in the peaks table: lend it the v5e's
    v5e = roofline.peaks("TPU v5 lite")
    monkeypatch.setattr(roofline, "peaks", lambda kind, path=None: v5e)
    result, _ = tiny.run(name, tmp_path, trace=True)
    assert result["correct"]
    want = {m["name"] for m in SPEC["per_layer"]
            if name in m.get("workloads", [name])}
    assert set(result["metrics"]) == want
    idle = result["metrics"]["device_idle_share"]["value"]
    assert 0 <= idle < 1
    assert 0 < result["metrics"]["step_roofline"]["value"] <= 100
    dev = result["device"]
    assert 0 < dev["busy_s"] <= dev["window_s"]
    assert abs(1 - dev["busy_s"] / dev["window_s"] - idle) < 1e-9
    bd = result["breakdown"]
    assert 0 < len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
