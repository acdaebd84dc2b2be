"""The harness's path for a ``launch="processes"`` cell, and that the
cells run in one process read what they read before it: the warm-up, the
rule for ``K``, the window and the readers."""

import jax
import pytest

from bench import harness
from bench.tests import tiny
from repro.core import GraphDJob

SPEC = tiny.spec()
CELLS = [w["name"] for w in SPEC["workloads"]]
PROCS = [n for n in CELLS
         if harness.launch_of(tiny.cell(n).config)[0] == "processes"]
# a traffic that halts times whole searches (test_search.py)
STEPPED = [n for n in CELLS if not tiny.cell(n).traffic.get("halts")]


@pytest.mark.parametrize("name", PROCS)
def test_processes_run_leaves_the_chips_to_the_workers(name, tmp_path,
                                                       monkeypatch):
    def no_open(cell):
        raise AssertionError("the benchmark's process opened a device")

    monkeypatch.setattr(harness, "open_device", no_open)
    result, checks = tiny.run(name, tmp_path)
    assert result["correct"], checks
    from jax._src import xla_bridge

    # GraphDJob pinned this process to the host CPU: no other backend
    assert jax.config.jax_platforms == "cpu"
    assert set(xla_bridge.backends()) == {"cpu"}
    # the memory peaks came from the four workers' probes
    assert len(result["device"]["memory_peak_bytes_per_chip"]) == 4


@pytest.mark.parametrize("name", STEPPED)
def test_warm_up_k_and_window(name, tmp_path, monkeypatch):
    """A cell in one process warms up one superstep and times ``K`` in a
    window that starts with the timed call; a processes cell warms up two
    and times ``1 + K``, its window starting after the first."""
    calls, runs = [], []
    job_run = GraphDJob.run

    def run(self, max_supersteps=10_000, **kw):
        calls.append(max_supersteps)
        return job_run(self, max_supersteps, **kw)

    record = harness.RunRecord

    def keep(**kw):
        runs.append(record(**kw))
        return runs[-1]

    monkeypatch.setattr(GraphDJob, "run", run)
    monkeypatch.setattr(harness, "RunRecord", keep)
    result, checks = tiny.run(name, tmp_path)
    assert result["correct"], checks
    (rec,) = runs
    k = len(rec.window)
    assert k >= 2
    procs = name in PROCS
    first = 3 if procs else 1
    assert calls == [first - int(procs), first + k]
    assert [r.step for r in rec.window] == list(range(first, first + k))
    assert result["attempted"] == first + k
    # the checks of a run of supersteps: its error, and every vertex and
    # superstep there
    assert list(checks) == ["max_rel_err", "missing_vertices",
                            "missing_supersteps"]
    assert checks["missing_supersteps"] == dict(value=0, limit=0)
    assert (rec.spawn_s is not None) == procs
    assert result["metrics"]["edges_per_s"]["value"] == pytest.approx(
        rec.window_edges / rec.window_s)
    if not procs:
        # the readers of a cell in one process, as before the processes
        # path came: each still reads its metric
        e2e = {m["name"] for m, _ in rec.cell.end_to_end}
        layer = {m["name"] for m, _ in rec.cell.per_layer}
        assert {"edges_per_s", "setup_s"} <= e2e
        assert {"partition_s", "first_step_s", "device_idle_share",
                "step_roofline"} <= layer
        assert not layer & {"spawn_s", "barrier_wait_s_per_step"}


@pytest.mark.parametrize("name", PROCS)
def test_a_probe_that_times_no_barrier_stops_the_run(name, tmp_path,
                                                      monkeypatch):
    """Where the probe finds no barrier to time (the program's
    ``wait_commit`` gone or renamed), the run stops; it does not report
    without ``barrier_wait_s_per_step``."""
    blind = tmp_path / "blind"
    blind.mkdir()
    (blind / "sitecustomize.py").write_text(
        "import runpy\n"
        f"probe = runpy.run_path({harness.PROBE + '/sitecustomize.py'!r})\n"
        "probe['_Probe']._time_barrier = lambda self: None\n")
    monkeypatch.setattr(harness, "PROBE", str(blind))
    with pytest.raises(RuntimeError, match="timed no barrier wait"):
        tiny.run(name, tmp_path)
