"""The reduction from a profiler trace to busy time, idle gaps and the
breakdown, on hand-made intervals and on a small trace recorded on the CPU
(``record_trace.py`` made it)."""

import numpy as np
import pytest

from bench import devtrace
from bench.tests.record_trace import TRACE


def test_union_merges_overlaps():
    assert devtrace.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == [[0, 3],
                                                                [5, 8]]


def test_gaps_inside_window():
    merged = devtrace.union([(2, 4), (6, 7)])
    assert devtrace.gaps(merged, 0, 10) == [(0, 2), (4, 6), (7, 10)]
    assert devtrace.gaps(merged, 3, 6.5) == [(4, 6)]
    assert devtrace.gaps([], 1, 2) == [(1, 2)]


def test_innermost_span():
    spans = [("run", 0, 100), ("step", 10, 20), ("put", 12, 14),
             ("step", 30, 40)]
    assert devtrace.innermost(spans, [5, 13, 15, 35, 50, 200]) == [
        "run", "put", "step", "step", "run", "no host span"]


def test_summary_of_hand_made_trace():
    host = [[("GraphDJob.run", 0, 100), ("on_step", 40, 50),
             ("on_step", 80, 90), ("DevicePut", 60, 70)]]
    devices = {"/device:TPU:0": [("fusion", 10, 30), ("fusion", 20, 40),
                                 ("copy", 85, 95)]}
    s = devtrace.summarize(devices, host)
    assert s.window_s == pytest.approx(90e-9)  # run start .. last step end
    assert s.busy_s == pytest.approx(35e-9)  # 10-40 and 85-90
    assert s.idle_share == pytest.approx(1 - 35 / 90)
    assert dict(map(tuple, s.device_ops)) == pytest.approx(
        {"fusion": 40e-9, "copy": 5e-9})
    # gaps 0-10 and 40-85: named by the span over their midpoints
    assert dict(map(tuple, s.idle_gaps)) == pytest.approx(
        {"GraphDJob.run": 10e-9, "DevicePut": 45e-9})


def test_two_devices_average():
    host = [[("GraphDJob.run", 0, 100)]]
    s = devtrace.summarize({"/device:TPU:0": [("a", 0, 50)],
                            "/device:TPU:1": [("a", 0, 100)]}, host)
    assert s.busy_s == pytest.approx(75e-9) and s.n_devices == 2


def test_recorded_cpu_trace():
    devices, host = devtrace.read(TRACE)
    s = devtrace.summarize(devices, host)
    # the CPU's operations count as one device
    assert list(devices) == ["/host:CPU"] and s.n_devices == 1
    # pinned: a change to the reduction shows here
    assert s.busy_s == pytest.approx(0.003529364, rel=1e-9)
    assert s.window_s == pytest.approx(0.21289145, rel=1e-9)
    # busy is the union of the operations' intervals: count it by brute
    # force on a 10-nanosecond grid of the window
    main = next(ev for ev in host
                if any(n == devtrace.RUN_SPAN for n, _, _ in ev))
    lo = min(a for n, a, _ in main if n == devtrace.RUN_SPAN)
    hi = max(b for n, _, b in main if n == devtrace.STEP_SPAN)
    grid = np.zeros(int((hi - lo) // 10) + 1, bool)
    for _, a, b in devices["/host:CPU"]:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            grid[int((a - lo) // 10):int(-(-(b - lo) // 10))] = True
    assert s.busy_s == pytest.approx(grid.sum() * 1e-8, rel=0.01)
    # the breakdown: at most ten of each, largest first
    for part in (s.device_ops, s.idle_gaps):
        assert 0 < len(part) <= 10
        secs = [v for _, v in part]
        assert secs == sorted(secs, reverse=True)
    # every idle gap is named after a host event of the harness's thread
    names = {n for n, _, _ in main}
    assert {n for n, _ in s.idle_gaps} <= names
    assert devtrace.RUN_SPAN in {n for n, _ in s.idle_gaps}
    # the gaps and the busy time fill the window; the ten names hold the
    # largest share of the gaps
    ops = devtrace.clip([(a, b) for _, a, b in devices["/host:CPU"]], lo, hi)
    idle = sum(b - a for a, b in devtrace.gaps(devtrace.union(ops), lo, hi))
    assert idle * 1e-9 + s.busy_s == pytest.approx(s.window_s, rel=1e-9)
    assert sum(v for _, v in s.idle_gaps) <= idle * 1e-9 * (1 + 1e-9)


def test_combine_sums_chips():
    a = devtrace.TraceSummary(window_s=10.0, busy_s=2.0, n_devices=1,
                              device_ops=[["fold", 1.5], ["copy", 0.5]],
                              idle_gaps=[["bench.barrier_wait", 8.0]])
    b = devtrace.TraceSummary(window_s=10.0, busy_s=4.0, n_devices=1,
                              device_ops=[["fold", 4.0]],
                              idle_gaps=[["DevicePut", 5.0],
                                         ["bench.barrier_wait", 1.0]])
    s = devtrace.combine([a, b])
    assert s.n_devices == 2 and s.window_s == 10.0 and s.busy_s == 3.0
    assert s.idle_share == pytest.approx(1 - 6 / 20)
    assert s.device_ops == [["fold", 5.5], ["copy", 0.5]]
    assert s.idle_gaps == [["bench.barrier_wait", 9.0], ["DevicePut", 5.0]]


def test_recorded_worker_traces():
    """Four workers' traces (g20-procs4.pagerank at scale 8 on the CPU)
    reduce to one summary of four devices over one wall-clock window."""
    import json

    from jax.profiler import ProfileData

    from bench.tests.record_trace import WORKERS, worker_dirs

    with open(f"{WORKERS}/window.json") as f:
        window = json.load(f)
    lo, hi = window["wall_lo"], window["wall_hi"]
    s = devtrace.summarize_workers(worker_dirs(), lo, hi)
    assert s.n_devices == 4
    assert s.window_s == pytest.approx(hi - lo, rel=1e-6)
    # each worker alone, over the same window on its own trace's clock
    alone, names, ops = [], {"no host span"}, {}
    for d in worker_dirs():
        pd = ProfileData.from_file(devtrace.find_xplane(d))
        t0 = devtrace.profile_start_ns(pd)
        devices, lines = devtrace._read(pd, devtrace.BARRIER_SPAN)
        # the probe's barrier span marks one thread: the worker's main
        assert len(devices) == 1 and len(lines) == 1
        names |= {n for n, _, _ in lines[0]}
        one = devtrace.reduce_window(devices, lines[0], lo * 1e9 - t0,
                                     hi * 1e9 - t0, top=1 << 30)
        assert 0 < one.busy_s < one.window_s
        # the gaps and the busy time fill the window
        assert (sum(v for _, v in one.idle_gaps) + one.busy_s
                == pytest.approx(one.window_s, rel=1e-9))
        for name, sec in one.device_ops:
            ops[name] = ops.get(name, 0.0) + sec
        alone.append(one)
    assert s.busy_s == pytest.approx(sum(a.busy_s for a in alone) / 4,
                                     rel=1e-12)
    assert s.idle_share == pytest.approx(
        1 - sum(a.busy_s for a in alone) / sum(a.window_s for a in alone))
    # operations summed over the chips, the ten largest, largest first
    assert 0 < len(s.device_ops) <= 10
    for name, sec in s.device_ops:
        assert sec == pytest.approx(ops[name], rel=1e-12)
    assert [v for _, v in s.device_ops] == sorted(ops.values(),
                                                  reverse=True)[:10]
    # idle gaps are named after the workers' main threads' events, and
    # the workers wait at the barrier with their devices idle
    assert 0 < len(s.idle_gaps) <= 10
    assert {n for n, _ in s.idle_gaps} <= names
    assert devtrace.BARRIER_SPAN in {n for n, _ in s.idle_gaps}
