"""Named host spans (core/spans.py): the per-superstep phase seconds of the
streamed driver, the set-up seconds of a job, the trace the profiler sees,
and results that do not depend on them."""

import glob
import os
import threading

import jax
import numpy as np
import pytest

from repro.core import (
    ChannelConfig, EngineConfig, GraphDEngine, GraphDJob, MemoryBudget,
    PageRank, SecondMinLabel, StreamConfig, plan, spans,
)
from repro.graph import partition_graph_streamed, rmat_graph

# the compute thread's phases of each streamed superstep body
PIPELINED = {"stream.schedule", "stream.stage_wait", "stream.fold",
             "channel.send", "channel.barrier", "stream.apply"}
CONFIGS = {
    "pipelined": (PageRank, dict(pipeline=True), PIPELINED),
    "half_duplex": (PageRank, dict(pipeline=True, full_duplex=False),
                    PIPELINED | {"channel.digest"}),
    "unpipelined": (PageRank, dict(pipeline=False),
                    {"stream.schedule", "stream.stage_wait", "stream.fold",
                     "stream.apply"}),
    "no_combiner": (SecondMinLabel, dict(pipeline=True), PIPELINED),
    "no_combiner_unpipelined": (SecondMinLabel, dict(pipeline=False),
                                PIPELINED - {"channel.barrier"}),
}


@pytest.fixture(scope="module")
def graph():
    return rmat_graph(scale=8, edge_factor=8, seed=4)


def _engine(graph, directory, program, channel, supersteps=3):
    pg, _, store = partition_graph_streamed(graph, 4, directory,
                                            edge_block=32)
    prog = program(supersteps=supersteps) if program is PageRank else program()
    return GraphDEngine(
        pg, prog,
        config=EngineConfig(mode="streamed",
                            stream=StreamConfig(chunk_blocks=2,
                                                group_batch=4),
                            channel=ChannelConfig(**channel)),
        stream_store=store,
    )


class _NoSpan:
    """A span that neither annotates nor times."""

    seconds = 0.0

    def __init__(self, name):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        pass


def test_span_counts_on_its_own_thread_only():
    def elsewhere():
        with spans.span("c"):  # no collector on this thread: trace only
            pass

    outer, inner = {}, {}
    with spans.collect(outer):
        with spans.span("a") as s:
            pass
        with spans.collect(inner):
            with spans.span("b"):
                pass
        t = threading.Thread(target=elsewhere)
        t.start()
        t.join()
        spans.add("d", 1.0)  # seconds measured on another thread
        with spans.span("a"):
            pass
    with spans.span("e"):
        pass
    spans.add("e", 1.0)
    assert set(outer) == {"a", "d"} and set(inner) == {"b"}
    assert outer["a"] > s.seconds > 0 and outer["d"] == 1.0


@pytest.mark.parametrize("name", sorted(CONFIGS))
def test_streamed_superstep_records_its_phases(name, graph, tmp_path):
    program, channel, main = CONFIGS[name]
    eng = _engine(graph, str(tmp_path / "s"), program, channel)
    _, hist = eng.run()
    assert hist
    for rec in hist:
        ph = rec.phase_seconds
        assert set(ph) == main | {"stream.stage"}, (name, sorted(ph))
        assert all(ph[k] > 0 for k in main), ph
        # the compute thread's phases are disjoint parts of the superstep
        assert sum(ph[k] for k in main) <= rec.seconds
        assert ph["stream.stage"] >= 0  # the staging thread's, beside them


def test_phase_seconds_leave_results_bit_identical(graph, tmp_path,
                                                   monkeypatch):
    channel = dict(pipeline=True)
    eng = _engine(graph, str(tmp_path / "a"), PageRank, channel)
    (v1, a1), h1 = eng.run()
    stats1 = eng.channel_stats
    monkeypatch.setattr(spans, "span", _NoSpan)
    eng = _engine(graph, str(tmp_path / "b"), PageRank, channel)
    (v2, a2), h2 = eng.run()
    assert np.array_equal(np.asarray(v1), np.asarray(v2))
    assert np.array_equal(np.asarray(a1), np.asarray(a2))
    assert [(r.n_active, r.n_msgs, r.agg, r.blocks_read) for r in h1] == [
        (r.n_active, r.n_msgs, r.agg, r.blocks_read) for r in h2]
    assert all(r.phase_seconds == {"stream.stage": 0.0} for r in h2)
    # the channel's counters still count with the spans in place
    assert stats1.packets == eng.channel_stats.packets > 0
    assert stats1.send_seconds > 0 and stats1.recv_seconds > 0


def _threads(xplane):
    """The events ``(name, start_ns, end_ns)`` of each host thread."""
    from jax.profiler import ProfileData

    return [[(e.name, e.start_ns, e.start_ns + e.duration_ns)
             for e in line.events]
            for plane in ProfileData.from_file(xplane).planes
            if plane.name.startswith("/host:") for line in plane.lines]


def test_profiler_trace_nests_phases_in_the_run_span(graph, tmp_path):
    eng = _engine(graph, str(tmp_path / "s"), PageRank, dict(pipeline=True),
                  supersteps=2)
    eng.run(max_supersteps=1)  # compile outside the trace
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path / "trace"),
                             profiler_options=opts)
    try:
        with jax.profiler.TraceAnnotation("GraphDJob.run"):
            eng.run()
    finally:
        jax.profiler.stop_trace()
    found = glob.glob(os.path.join(tmp_path, "trace", "**", "*.xplane.pb"),
                      recursive=True)
    assert found
    threads = _threads(found[0])
    main = [ev for ev in threads
            if any(n == "GraphDJob.run" for n, _, _ in ev)]
    assert len(main) == 1
    (lo, hi), = [(a, b) for n, a, b in main[0] if n == "GraphDJob.run"]
    inside = {n for n, a, b in main[0] if lo <= a and b <= hi}
    assert {"stream.fold", "stream.stage_wait", "channel.send",
            "channel.barrier", "stream.apply",
            "stream.schedule"} <= inside
    # the other threads' spans are on lines of their own
    elsewhere = {n for ev in threads if ev is not main[0] for n, _, _ in ev}
    assert {"stream.stage", "channel.transmit",
            "channel.digest"} <= elsewhere


@pytest.mark.parametrize("streamed", [False, True])
def test_job_records_its_set_up_seconds(streamed, graph, tmp_path):
    budget = MemoryBudget(n_shards=4)
    if streamed:
        loose = plan(PageRank(supersteps=2), graph, budget, edge_block=32)
        rec = next(c for c in loose.alternatives if c.name == "recoded")
        budget = MemoryBudget(ram_per_shard=rec.ram_total - 1, n_shards=4)
    with GraphDJob(PageRank(supersteps=2), graph, budget=budget,
                   edge_block=32, workdir=str(tmp_path / "job")) as job:
        assert (job.plan.mode == "streamed") == streamed
        want = {"job.plan", "partition.recode", "partition.build",
                "partition.upload", "job.engine"}
        if streamed:
            want.add("partition.spill")
        assert set(job.setup_seconds) == want
        assert all(v > 0 for v in job.setup_seconds.values())
        res = job.run()
    assert res.summary()["setup"] == job.setup_seconds
    assert bool(res.history[0].phase_seconds) == streamed
