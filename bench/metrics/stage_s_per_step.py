"""Seconds per superstep the edge reader's staging thread spent reading
and staging blocks: the mean over the window of
``SuperstepRecord.phase_seconds["stream.stage"]`` (``StreamStats.read_seconds``
summed over the superstep's passes). It runs beside the compute thread, so
it is not part of the superstep's compute-thread time. Nothing for a plan
that keeps its edges in memory, or a program that records no phases."""

PHASE = "stream.stage"


def read(run):
    if run.plan_mode != "streamed" or not run.window:
        return None
    got = [getattr(r, "phase_seconds", {}).get(PHASE) for r in run.window]
    if None in got:
        return None
    return sum(got) / len(got)
