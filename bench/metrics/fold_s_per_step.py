"""Seconds per superstep of the host fold loop on the compute thread: the
mean over the window of ``SuperstepRecord.phase_seconds["stream.fold"]``
(each staged chunk's or batch's argument preparation, host-to-device
copies, fold dispatch and wait). Nothing for a plan that keeps its edges
in memory, or a program that records no phases."""

PHASE = "stream.fold"


def read(run):
    if run.plan_mode != "streamed" or not run.window:
        return None
    got = [getattr(r, "phase_seconds", {}).get(PHASE) for r in run.window]
    if None in got:
        return None
    return sum(got) / len(got)
