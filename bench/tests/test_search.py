"""A traffic that halts: the BFS reference, the search's source drawn from
the traffic's seed, the harness's window of whole searches and its check
of the superstep the search halts in, and the readers of skip()."""

import os
import subprocess
import sys
from types import SimpleNamespace

import numpy as np
import pytest

from bench import graphgen, harness
from bench.tests import tiny
from repro.core import engine as eng
from repro.streams.store import EdgeStreamStore

SPEC = tiny.spec()
HALTING = [w["name"] for w in SPEC["workloads"]
           if tiny.cell(w["name"]).traffic.get("halts")]
BFS = harness.load_module(f"{harness.BENCH}/reference/bfs.py", "bfs")

# 0 - 1 - 2 - 4 and 0 - 3 - 2, undirected; 5 - 6 apart from them
EDGES = [(0, 1), (1, 2), (0, 3), (3, 2), (2, 4), (5, 6)]
SRC = np.array([u for u, v in EDGES] + [v for u, v in EDGES])
DST = np.array([v for u, v in EDGES] + [u for u, v in EDGES])
FROM_0 = dict(source_index=0)


def reader(name):
    return harness.load_module(f"{harness.BENCH}/metrics/{name}.py", name)


def test_reference_levels_on_a_known_graph():
    inf = np.inf
    assert BFS.levels(SRC, DST, 7, 0).tolist() == [0, 1, 2, 1, 3, inf, inf]
    assert BFS.supersteps(SRC, DST, 7, FROM_0) == 4
    # after 2 supersteps levels up to 2 are known, the deepest is not
    assert BFS.run(SRC, DST, 7, FROM_0, 2).tolist() == [
        0, 1, 2, 1, inf, inf, inf]
    assert BFS.run(SRC, DST, 7, FROM_0, 4).tolist() == [
        0, 1, 2, 1, 3, inf, inf]
    # a source on its own halts after its own superstep
    assert BFS.supersteps(SRC, DST, 8, dict(source_index=7)) == 1


def test_reference_active_edges_cover_the_reached_component():
    per_step = BFS.active_edges(SRC, DST, 7, FROM_0, 4)
    # the source's 2 arcs, then those of 1 and 3, of 2, and of 4
    assert per_step == [2, 4, 3, 1]
    assert sum(per_step) == 10  # every arc of the component of 0, once
    assert BFS.active_edges(SRC, DST, 7, FROM_0, 6)[4:] == [0, 0]


def test_reference_compare_counts_mismatches():
    want = BFS.run(SRC, DST, 7, FROM_0, 4)
    got = want.copy()
    assert BFS.compare(got, want, {}) == dict(mismatched_vertices=0)
    got[1], got[5], got[6] = 2.0, 1.0, np.nan  # inf != 1, NaN differs
    assert BFS.compare(got, want, {}) == dict(mismatched_vertices=3)


def test_source_is_the_same_vertex_under_every_run_seed():
    graph = dict(tiny.cell(HALTING[0]).config["graph"], scale=9)
    ids, src, dst = graphgen.structure(graph)
    n = ids.size
    traffic = tiny.cell(HALTING[0]).traffic
    got = []
    for seed in (2**31 + 5, 3):
        labels = graphgen.labels(graph, ids, seed)
        t = harness.resolve_sources(traffic, src, dst, labels)
        index = t["source_index"]
        assert t["program"]["args"]["source_new_id"] == {
            "$recoded_id": int(labels[index])}
        got.append((index, int(labels[index])))
    (a, id_a), (b, id_b) = got
    assert a == b and id_a != id_b
    # Graph500's rule: a vertex with an edge, whose search reaches half
    lv = BFS.levels(src, dst, n, a)
    assert np.count_nonzero(src == a) > 0
    assert 2 * np.isfinite(lv).sum() >= n
    # the traffic as written is left as it was
    assert "$source" in traffic["program"]["args"]["source_new_id"]


@pytest.mark.parametrize("name", HALTING)
def test_window_of_whole_searches(name, tmp_path, monkeypatch):
    """The warm-up is one whole search; the window runs ``K`` more, each
    from superstep 0 until the program halts; every one is compared."""
    calls, runs = [], []
    engine_run = eng.GraphDEngine.run

    def run(self, max_supersteps=10_000, **kw):
        out = engine_run(self, max_supersteps, **kw)
        calls.append((max_supersteps, kw.get("on_step") is not None,
                      len(out[1])))
        return out

    record = harness.RunRecord

    def keep(**kw):
        runs.append(record(**kw))
        return runs[-1]

    monkeypatch.setattr(eng.GraphDEngine, "run", run)
    monkeypatch.setattr(harness, "RunRecord", keep)
    result, checks = tiny.run(name, tmp_path, seconds=1.0)
    assert result["correct"], checks
    (rec,) = runs
    k = result["attempted"]
    assert k >= 1 and result["failed"] == 0
    warm, *timed = calls
    assert warm[1] is False and all(on for _, on, _ in timed)
    assert len(timed) == k
    steps = warm[2]
    assert all(cap == rec.n_vertices for cap, _, _ in calls)
    assert all(taken == steps for _, _, taken in calls)
    assert [r.step for r in rec.window] == list(range(steps)) * k
    assert list(checks) == ["mismatched_vertices", "missing_vertices",
                            "missing_supersteps"]
    assert all(c["value"] == 0 == c["limit"] for c in checks.values())
    assert result["metrics"]["edges_per_s"]["value"] == pytest.approx(
        rec.window_edges / rec.window_s)
    # each search sends along every arc of the searched component once
    per_search = rec.window_edges / k
    assert per_search == sum(r.n_msgs for r in rec.window[:steps]) > 0


@pytest.mark.parametrize("name", HALTING)
def test_halting_a_superstep_early_is_not_correct(name, tmp_path,
                                                  monkeypatch):
    """Levels are all known one superstep before the search halts, so only
    the superstep count tells a program that stops there."""
    engine_run = eng.GraphDEngine.run

    def early(self, max_supersteps=10_000, **kw):
        _, history = engine_run(self, max_supersteps)
        return engine_run(self, len(history) - 1, **kw)

    monkeypatch.setattr(eng.GraphDEngine, "run", early)
    result, checks = tiny.run(name, tmp_path)
    assert not result["correct"]
    assert result["failed"] == result["attempted"]
    assert checks["missing_supersteps"]["value"] == 1
    assert checks["mismatched_vertices"]["value"] == 0


@pytest.mark.parametrize("name", HALTING)
def test_skipped_share_counts_every_nonempty_block(name, tmp_path,
                                                   monkeypatch):
    """Each superstep's blocks skipped, read and served from the cache are
    the store's non-empty blocks, and skip() keeps some off the search."""
    nonempty, runs = [], []
    count = EdgeStreamStore.nonempty_blocks

    def counted(self):
        nonempty.append(count(self))
        return nonempty[-1]

    record = harness.RunRecord

    def keep(**kw):
        runs.append(record(**kw))
        return runs[-1]

    monkeypatch.setattr(EdgeStreamStore, "nonempty_blocks", counted)
    monkeypatch.setattr(harness, "RunRecord", keep)
    result, checks = tiny.run(name, tmp_path)
    assert result["correct"], checks
    (rec,) = runs
    (blocks,) = set(nonempty)
    for r in rec.window:
        assert r.blocks_skipped + r.blocks_read + r.cache_hits == blocks
    share = reader("blocks_skipped_share").read(rec)
    assert 0 < share < 1
    assert share == pytest.approx(
        sum(r.blocks_skipped for r in rec.window) / (blocks * len(rec.window)))


def test_skipped_share_reads_nothing_without_a_stream():
    read = reader("blocks_skipped_share").read
    step = SimpleNamespace(blocks_skipped=3, blocks_read=1, cache_hits=0)
    assert read(SimpleNamespace(plan_mode="streamed",
                                window=[step, step])) == 0.75
    assert read(SimpleNamespace(plan_mode="recoded", window=[step])) is None
    assert read(SimpleNamespace(plan_mode="streamed", window=[])) is None
    empty = SimpleNamespace(blocks_skipped=0, blocks_read=0, cache_hits=0)
    assert read(SimpleNamespace(plan_mode="streamed", window=[empty])) is None


def test_schedule_seconds_per_step():
    read = reader("schedule_s_per_step").read
    window = [SimpleNamespace(phase_seconds={"stream.schedule": 0.1,
                                             "stream.fold": 1.0}),
              SimpleNamespace(phase_seconds={"stream.schedule": 0.3})]
    assert read(SimpleNamespace(plan_mode="streamed",
                                window=window)) == pytest.approx(0.2)
    assert read(SimpleNamespace(plan_mode="recoded", window=window)) is None
    bare = [SimpleNamespace(seconds=1.0)]
    assert read(SimpleNamespace(plan_mode="streamed", window=bare)) is None


@pytest.mark.parametrize("name", HALTING)
def test_control_of_a_traffic_without_one_exits(name, tmp_path):
    """``--control 1`` on a traffic whose answers no narrower type changes
    stops at once, saying so, and reports no run."""
    with pytest.raises(harness.SpecError, match="no precision control"):
        tiny.run(name, tmp_path, control=True)
    p = subprocess.run(
        [sys.executable, "bench/run.py", "--workload", name, "--seed",
         str(2**31 + 7), "--seconds", "1", "--control", "1"],
        cwd=harness.ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=120)
    assert p.returncode == 2 and p.stdout == ""
    assert "no precision control" in p.stderr
