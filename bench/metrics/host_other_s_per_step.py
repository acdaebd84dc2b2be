"""Seconds per superstep of the host driver that no span names: the mean
over the window of ``SuperstepRecord.seconds`` less the sum of its
compute-thread phases (every ``phase_seconds`` entry but those measured on
other threads). It checks the other phase metrics' coverage: a large value
means a phase is missing. Nothing for a plan that keeps its edges in
memory, or a program that records no phases."""

OFF_THREAD = ("stream.stage",)  # the staging thread's, beside the others


def read(run):
    if run.plan_mode != "streamed" or not run.window:
        return None
    other = []
    for r in run.window:
        phases = getattr(r, "phase_seconds", None)
        if not phases:
            return None
        other.append(r.seconds - sum(v for k, v in phases.items()
                                     if k not in OFF_THREAD))
    return sum(other) / len(other)
