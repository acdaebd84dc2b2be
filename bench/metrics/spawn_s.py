"""Seconds a launch="processes" call pays before its supersteps run at
their pace: host clock from the timed ``GraphDJob.run`` call's start to
its first superstep's end (the workers' spawn, their JAX start, their
chips' opening and their program loads, and one superstep), less the
window's median superstep. Nothing for a cell without worker processes."""


def read(run):
    return run.spawn_s
