"""Seconds per superstep that a launch="processes" worker waits at the
coordinator's barrier: from its ``arrive`` to the commit that releases it
(``FileCoordinator.wait_commit``, timed on the worker's own host clock by
the probe, ``bench/probe/sitecustomize.py``), the mean over the window's
supersteps and the workers. The straggler wait plus the barrier's polling.
Nothing for a cell without worker processes.

The probe is a stand-in: it replaces the program's ``wait_commit`` from
outside, and the harness stops a run in which a worker timed no wait. Once
the workers put their barrier wait on the superstep's record, this reads
that record instead."""


def read(run):
    if not run.barrier_waits:
        return None
    return sum(run.barrier_waits) / len(run.barrier_waits)
