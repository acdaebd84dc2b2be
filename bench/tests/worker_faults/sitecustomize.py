"""A fault planted inside each ``launch="processes"`` worker, for the
tests only (``test_faults.py``): they put this directory in the place of
the probe's (``harness.PROBE``) and name the fault in
``GRAPHD_TEST_FAULT``. Python imports this file at each worker's start; it
wraps ``device_info``, which the worker calls once it has started its
heartbeat and before it builds its superstep loop, so that the fault is
planted in the worker's own ``_Worker`` (the module run as ``__main__``)
and kernels. Then it runs the probe, whose records the harness reads."""

import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
PROBE = os.path.join(os.path.dirname(os.path.dirname(HERE)), "probe",
                     "sitecustomize.py")


def _state_unchanged(worker, engine):
    """Every superstep after the first returns the values it was given."""
    receive = worker._receive_combined

    def frozen(self, s, values_w, active_w, inbox):
        out = receive(self, s, values_w, active_w, inbox)
        return (values_w,) + tuple(out[1:]) if s else out

    worker._receive_combined = frozen


def _half_the_edges(worker, engine):
    """Every other edge slot sends no message."""
    import jax.numpy as jnp

    gen = engine._gen_messages

    def half(program, values, degree, sp, dp, w, active, step):
        msg, dp, aact = gen(program, values, degree, sp, dp, w, active, step)
        keep = (jnp.arange(sp.shape[-1]) % 2) == 0
        e0 = jnp.asarray(program.combiner.e0, program.msg_dtype)
        return jnp.where(keep, msg, e0), dp, aact & keep

    engine._gen_messages = half


def _no_exchange(worker, engine):
    """Messages between workers are left out: a worker takes only its own."""
    pull = worker._pull_runs

    def own_only(self, s, src, inbox, receiver=None):
        if src == self.w:
            pull(self, s, src, inbox, receiver=receiver)

    worker._pull_runs = own_only


def _answer_altered(worker, engine):
    """One vertex's value is changed by 1 % where worker 0 hands it out."""
    import numpy as np

    write = worker._write_result

    def altered(self, values_w, active_w):
        if self.w == 0:
            values_w = np.array(values_w)
            i = int(np.flatnonzero(np.asarray(self.vmask))[0])
            values_w[i] *= 1.01
        write(self, values_w, active_w)

    worker._write_result = altered


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_edges": _half_the_edges,
          "no_exchange": _no_exchange,
          "answer_altered": _answer_altered}


def _plant(fault: str) -> None:
    import repro.launch.placement as placement

    device_info = placement.device_info

    def planted():
        out = device_info()
        import repro.core.engine as engine

        FAULTS[fault](sys.modules["__main__"]._Worker, engine)
        return out

    placement.device_info = planted


if "repro.launch.procs" in getattr(sys, "orig_argv", []):
    fault = os.environ.get("GRAPHD_TEST_FAULT")
    if fault:
        _plant(fault)
runpy.run_path(PROBE)
