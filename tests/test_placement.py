"""One chip per ``launch="processes"`` worker, checked on the CPU: the
placement gives each worker a distinct chip, refuses more shards than
chips before anything is built or spawned, never respawns a worker that
cannot open its device, and reports where every worker ran."""

import os

import pytest

from repro.core import GraphDJob, HashMin, MemoryBudget, plan
from repro.core.coordinator import WorkerFailed
from repro.graph import rmat_graph
from repro.launch import placement
from repro.launch.placement import PlacementError, chips_for, worker_envs

N = 3
EDGE_BLOCK = 32


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(scale=7, edge_factor=6, seed=2)


def _processes_plan(graph, n=N):
    return plan(HashMin(), graph, MemoryBudget(n_shards=n),
                edge_block=EDGE_BLOCK, launch="processes")


def test_each_worker_gets_one_distinct_chip():
    envs = worker_envs(4, env={}, chips=4)
    assert [e["TPU_VISIBLE_CHIPS"] for e in envs] == ["0", "1", "2", "3"]
    assert len({e["TPU_PROCESS_PORT"] for e in envs}) == 4
    for e in envs:
        assert e["JAX_PLATFORMS"] == "tpu"
        assert e["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
        assert e["TPU_PROCESS_BOUNDS"] == "1,1,1"
    # fewer workers than chips: the first chips, still one each
    assert [e["TPU_VISIBLE_CHIPS"] for e in worker_envs(2, {}, 4)] == \
        ["0", "1"]


def test_cpu_pinned_or_chipless_host_places_nothing():
    assert worker_envs(5, env={"JAX_PLATFORMS": "cpu"}, chips=4) == [{}] * 5
    assert worker_envs(5, env={}, chips=0) == [{}] * 5
    assert chips_for(5, env={"JAX_PLATFORMS": "cpu"}, chips=1) is False


def test_count_chips_reads_device_nodes(tmp_path):
    assert placement.count_chips(str(tmp_path)) == 0
    (tmp_path / "vfio").mkdir()
    for name in ("0", "1", "vfio"):
        (tmp_path / "vfio" / name).touch()
    assert placement.count_chips(str(tmp_path)) == 2
    for i in range(4):
        (tmp_path / f"accel{i}").touch()
    assert placement.count_chips(str(tmp_path)) == 4


def test_more_shards_than_chips_is_refused_before_any_spawn(
        graph, tmp_path, monkeypatch):
    with pytest.raises(PlacementError, match="3 shards.*2 chips"):
        worker_envs(3, env={}, chips=2)
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(placement, "count_chips", lambda *a: 2)
    wd = tmp_path / "job"
    with pytest.raises(PlacementError, match="one worker per chip"):
        GraphDJob(HashMin(), graph, plan=_processes_plan(graph),
                  launch="processes", workdir=str(wd))
    # refused before the partition was spilled or a process started
    assert not (wd / "edges").exists()
    assert not (wd / "procs").exists()


@pytest.mark.parametrize("transport", ["files", "sockets"])
def test_worker_without_device_is_not_respawned(graph, tmp_path,
                                                monkeypatch, transport):
    """Workers are placed on chips this host does not have: each fails to
    open its device, and the launcher fails the run instead of spending
    its respawn budget on a fault no respawn can fix."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    monkeypatch.setattr(placement, "count_chips", lambda *a: N)
    job = GraphDJob(HashMin(), graph, plan=_processes_plan(graph),
                    launch="processes", checkpoint_every=1,
                    launch_opts={"transport": transport},
                    workdir=str(tmp_path / transport))
    with pytest.raises(WorkerFailed, match="could not open its device"):
        job.run()
    assert job._last_run_recoveries == 0
    job.close()


def test_summary_reports_each_workers_device(graph, tmp_path):
    with GraphDJob(HashMin(), graph, plan=_processes_plan(graph),
                   launch="processes",
                   workdir=str(tmp_path / "procs")) as job:
        res = job.run()
    devices = res.summary()["devices"]
    assert [d["shard"] for d in devices] == list(range(N))
    assert all(d["platform"] == "cpu" and d["kind"] for d in devices)
    with GraphDJob(HashMin(), graph, budget=MemoryBudget(n_shards=N),
                   edge_block=EDGE_BLOCK) as job:
        res = job.run()
    assert res.summary()["devices"] == [
        dict(platform="cpu", kind=res.devices[0]["kind"])]
    assert os.environ.get("JAX_PLATFORMS") == "cpu"
