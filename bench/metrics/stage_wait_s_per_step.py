"""Seconds per superstep that the compute thread waited on the edge
reader's prefetch queue: the mean over the window of
``SuperstepRecord.phase_seconds["stream.stage_wait"]`` (the span around
the consumer's wait in ``StreamReader.stream``). Nothing for a plan that
keeps its edges in memory, or a program that records no phases."""

PHASE = "stream.stage_wait"


def read(run):
    if run.plan_mode != "streamed" or not run.window:
        return None
    got = [getattr(r, "phase_seconds", {}).get(PHASE) for r in run.window]
    if None in got:
        return None
    return sum(got) / len(got)
