"""From a JAX profiler trace to the device's busy time, idle gaps and ops.

The harness wraps the timed ``GraphDJob.run`` call in a
``jax.profiler.TraceAnnotation`` named :data:`RUN_SPAN` and each
``on_step`` callback in one named :data:`STEP_SPAN`. The traced window runs
from the start of the first run span to the end of the last step span:
from the first superstep's start to the last superstep's end.

* Device operations are the events of each device plane's ``XLA Ops``
  line (``/device:TPU:0`` ...). A trace with no device plane (the CPU
  backend) has its operations on host threads, as events that carry an
  ``hlo_op`` stat; they count as one device.
* Busy time is the length of the union of the operations' intervals inside
  the window, averaged over the devices; idle is the rest of the window.
* Each idle gap (a stretch of the window in which no operation of any
  device runs) is named after the innermost host event that covers its
  midpoint on the thread that holds the harness's spans: one of those
  spans, or an event JAX records inside them (a dispatch, a transfer).

Under ``launch="processes"`` each worker traces its own chip (the probe,
``bench/probe/sitecustomize.py``), and :func:`summarize_workers` reduces
those traces over one window given on the wall clock: each chip's busy
time over that window, the operations' seconds summed over the chips, and
each chip's idle gaps named after the innermost host event on its
worker's main thread (the thread that holds the probe's
:data:`BARRIER_SPAN`), summed over the chips by name.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict
from dataclasses import dataclass, field

RUN_SPAN = "GraphDJob.run"
STEP_SPAN = "on_step"
#: the probe's span around a worker's barrier wait (its main thread's)
BARRIER_SPAN = "bench.barrier_wait"
OPS_LINE = "XLA Ops"
#: host events that mark scheduling, not work, and name no gap
_NOT_WORK = ("ThreadpoolListener::",)


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # mean over the devices
    n_devices: int
    device_ops: list = field(default_factory=list)  # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)  # [[host span, seconds]]

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def union(intervals):
    """Sorted, disjoint intervals covering the same points."""
    out = []
    for lo, hi in sorted(intervals):
        if out and lo <= out[-1][1]:
            if hi > out[-1][1]:
                out[-1][1] = hi
        else:
            out.append([lo, hi])
    return out


def clip(intervals, lo, hi):
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def gaps(merged, lo, hi):
    """The stretches of ``[lo, hi]`` that ``merged`` (from :func:`union`)
    leaves uncovered."""
    out, at = [], lo
    for a, b in merged:
        if a > at:
            out.append((at, min(a, hi)))
        at = max(at, b)
        if at >= hi:
            break
    if at < hi:
        out.append((at, hi))
    return [(a, b) for a, b in out if b > a]


def innermost(spans, times):
    """For each of the sorted ``times``, the name of the innermost span
    ``(name, start, end)`` that covers it. Spans of one thread nest, so a
    sweep with a stack of the open spans finds it."""
    spans = sorted(spans, key=lambda s: (s[1], -s[2]))
    names, stack, i = [], [], 0
    for t in times:
        while i < len(spans) and spans[i][1] <= t:
            while stack and stack[-1][2] < spans[i][1]:
                stack.pop()
            stack.append(spans[i])
            i += 1
        while stack and stack[-1][2] < t:
            stack.pop()
        names.append(stack[-1][0] if stack else "no host span")
    return names


def _stat(event, key):
    for k, v in event.stats:
        if k == key:
            return v
    return None


def read(path: str):
    """``(devices, host_lines)`` from an ``.xplane.pb``: ``devices`` maps a
    device name to its operations ``[(name, start_ns, end_ns)]``, and
    ``host_lines`` lists the events of each host thread that holds a
    :data:`RUN_SPAN`."""
    from jax.profiler import ProfileData

    return _read(ProfileData.from_file(path), RUN_SPAN)


def _read(pd, mark: str):
    """:func:`read` of a loaded ``ProfileData``, keeping the host threads
    that hold a ``mark`` span."""
    devices, host_lines, cpu_ops = {}, [], []
    for plane in pd.planes:
        if plane.name.startswith("/device:"):
            # an op's event is named by its HLO text: keep the name
            ops = [(e.name.split(" = ", 1)[0], e.start_ns,
                    e.start_ns + e.duration_ns)
                   for line in plane.lines if line.name == OPS_LINE
                   for e in line.events]
            if ops:
                devices[plane.name] = ops
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            events = []
            for e in line.events:
                if e.duration_ns <= 0 or e.name.startswith(_NOT_WORK):
                    continue
                span = (e.name, e.start_ns, e.start_ns + e.duration_ns)
                op = None if devices else _stat(e, "hlo_op")
                if op is not None:
                    cpu_ops.append((str(op),) + span[1:])
                else:
                    events.append(span)
            # only the harness's thread names gaps
            if any(n == mark for n, _, _ in events):
                host_lines.append(events)
    if not devices and cpu_ops:
        devices["/host:CPU"] = cpu_ops
    return devices, host_lines


def summarize(devices: dict, host_lines: list, top: int = 10) -> TraceSummary:
    """Reduce one trace (as :func:`read` returns it) over the harness's
    window."""
    main = next((ev for ev in host_lines
                 if any(n == RUN_SPAN for n, _, _ in ev)), None)
    if main is None:
        raise ValueError(f"the trace holds no {RUN_SPAN!r} span")
    lo = min(a for n, a, _ in main if n == RUN_SPAN)
    ends = [b for n, _, b in main if n == STEP_SPAN]
    hi = max(ends) if ends else max(b for n, _, b in main if n == RUN_SPAN)
    return reduce_window(devices, main, lo, hi, top)


def reduce_window(devices: dict, main: list, lo: float, hi: float,
                  top: int = 10) -> TraceSummary:
    """Reduce one trace's ``devices`` over ``[lo, hi]`` (its own clock),
    naming idle gaps after the events of the host thread ``main``."""
    if not devices:
        raise ValueError("the trace holds no device operation")
    busy, per_op, every = 0.0, defaultdict(float), []
    for ops in devices.values():
        inside = clip([(a, b) for _, a, b in ops], lo, hi)
        merged = union(inside)
        busy += sum(b - a for a, b in merged)
        every.extend(inside)
        for name, a, b in ops:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                per_op[name] += (b - a) * 1e-9
    idle = gaps(union(every), lo, hi)
    per_gap = defaultdict(float)
    for (a, b), name in zip(idle, innermost(main, [(a + b) / 2
                                                   for a, b in idle])):
        per_gap[name] += (b - a) * 1e-9
    return TraceSummary(window_s=(hi - lo) * 1e-9,
                        busy_s=busy * 1e-9 / len(devices),
                        n_devices=len(devices),
                        device_ops=_rank(per_op, top),
                        idle_gaps=_rank(per_gap, top))


def summarize_dir(log_dir: str) -> TraceSummary:
    return summarize(*read(find_xplane(log_dir)))


def _rank(d: dict, top: int) -> list:
    return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]


def profile_start_ns(pd) -> int:
    """The wall-clock nanoseconds from which the trace's events count."""
    for plane in pd.planes:
        for k, v in plane.stats:
            if k == "profile_start_time":
                return int(v)
    raise ValueError("the trace records no profile_start_time")


def combine(summaries: list, top: int = 10) -> TraceSummary:
    """One summary of the chips of ``summaries`` (each over the same
    window): busy time averaged over all their devices, operations' and
    idle gaps' seconds summed over them by name."""
    n = sum(s.n_devices for s in summaries)
    ops, idle = defaultdict(float), defaultdict(float)
    for s in summaries:
        for name, sec in s.device_ops:
            ops[name] += sec
        for name, sec in s.idle_gaps:
            idle[name] += sec
    return TraceSummary(
        window_s=sum(s.window_s for s in summaries) / len(summaries),
        busy_s=sum(s.busy_s * s.n_devices for s in summaries) / n,
        n_devices=n, device_ops=_rank(ops, top), idle_gaps=_rank(idle, top))


def summarize_workers(log_dirs: list, wall_lo: float, wall_hi: float,
                      top: int = 10) -> TraceSummary:
    """Reduce one trace per worker (``log_dirs``) over the window from
    ``wall_lo`` to ``wall_hi`` (``time.time()`` seconds) to one summary."""
    from jax.profiler import ProfileData

    out = []
    for d in log_dirs:
        pd = ProfileData.from_file(find_xplane(d))
        t0 = profile_start_ns(pd)
        devices, lines = _read(pd, BARRIER_SPAN)
        # each worker's whole list of top entries, so the sums stay whole
        out.append(reduce_window(devices, lines[0] if lines else [],
                                 wall_lo * 1e9 - t0, wall_hi * 1e9 - t0,
                                 top=1 << 30))
    return combine(out, top)
