"""Seconds per superstep of the streamed driver's scheduling on the compute
thread: the mean over the window of
``SuperstepRecord.phase_seconds["stream.schedule"]`` (the frontier's copy
off the device and the skip() test against the block manifest, before and
after the superstep). Nothing for a plan that keeps its edges in memory,
or a program that records no phases."""

PHASE = "stream.schedule"


def read(run):
    if run.plan_mode != "streamed" or not run.window:
        return None
    got = [getattr(r, "phase_seconds", {}).get(PHASE) for r in run.window]
    if None in got:
        return None
    return sum(got) / len(got)
