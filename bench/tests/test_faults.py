"""The check catches a broken program: the configuration's lower-precision
path (the control) and each fault a cell can have, planted under the timed
path, make ``correct`` come out false. The look for a chip is skipped; the
rest of the run is the benchmark's own. In a ``launch="processes"`` cell
the faults are planted inside the timed call's workers
(``worker_faults/sitecustomize.py``), where the supersteps run."""

import math
import os

import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import tiny
from repro.core import engine as eng

CELLS = [w["name"] for w in tiny.spec()["workloads"]]
# the cells whose traffic has a lower-precision path to run as the control
CONTROLLED = [n for n in CELLS if "no_control" not in tiny.cell(n).traffic]
WORKER_FAULTS = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                             "worker_faults")


def _in_workers(name: str) -> bool:
    return harness.launch_of(tiny.cell(name).config)[0] == "processes"


def _state_unchanged(monkeypatch):
    """Every superstep after the first returns the values it was given."""
    init = eng.GraphDEngine.__init__

    def patched(self, *a, **kw):
        init(self, *a, **kw)
        step = self._step_dense
        if step is not None:
            def frozen(pg, values, active, s):
                out = step(pg, values, active, s)
                return (values,) + tuple(out[1:]) if int(s) else out
            self._step_dense = self._step_sparse = frozen

    monkeypatch.setattr(eng.GraphDEngine, "__init__", patched)
    pipelined = eng.GraphDEngine._superstep_streamed_comb_pipelined

    def frozen_streamed(self, values, active, s, plan):
        out = pipelined(self, values, active, s, plan)
        return (values,) + tuple(out[1:]) if s else out

    monkeypatch.setattr(eng.GraphDEngine,
                        "_superstep_streamed_comb_pipelined", frozen_streamed)


def _half_the_edges(monkeypatch):
    """Every other edge slot sends no message."""
    gen = eng._gen_messages

    def half(program, values, degree, sp, dp, w, active, step):
        msg, dp, aact = gen(program, values, degree, sp, dp, w, active, step)
        keep = (jnp.arange(sp.shape[-1]) % 2) == 0
        e0 = jnp.asarray(program.combiner.e0, program.msg_dtype)
        return jnp.where(keep, msg, e0), dp, aact & keep

    monkeypatch.setattr(eng, "_gen_messages", half)


def _no_exchange(monkeypatch):
    """Messages between shards are left out: a shard folds only its own."""
    def own_only(program, pg, values, active, step, axis, contrib,
                 digest=None):
        from jax import lax

        return contrib(lax.axis_index(axis))

    monkeypatch.setattr(eng, "_ring_exchange", own_only)
    fold = eng.GraphDEngine._fold_groups

    def own_groups(self, values, active, step, schedule, sink):
        # a group for another shard still reaches it, empty
        def sink_own(i, k, A, c):
            if i == k:
                return sink(i, k, A, c)
            return sink(i, k, jnp.zeros_like(A), jnp.zeros_like(c))
        return fold(self, values, active, step, schedule, sink_own)

    monkeypatch.setattr(eng.GraphDEngine, "_fold_groups", own_groups)


def _answer_altered(monkeypatch):
    """One vertex's value is changed by 1 % where the job hands it out: the
    first whose value that changes (finite and not 0; a search's source
    reads 0 and an unreached vertex inf)."""
    gather = eng.GraphDEngine.gather_values

    def altered(self, values):
        out = gather(self, values)
        v = next(v for v, x in out.items() if math.isfinite(x) and x != 0)
        out[v] = out[v] * 1.01
        return out

    monkeypatch.setattr(eng.GraphDEngine, "gather_values", altered)


FAULTS = {"state_unchanged": _state_unchanged,
          "half_the_edges": _half_the_edges,
          "no_exchange": _no_exchange,
          "answer_altered": _answer_altered}


@pytest.mark.parametrize("fault", sorted(FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault, tmp_path, monkeypatch):
    if _in_workers(name):
        monkeypatch.setattr(harness, "PROBE", WORKER_FAULTS)
        monkeypatch.setenv("GRAPHD_TEST_FAULT", fault)
    else:
        FAULTS[fault](monkeypatch)
    result, checks = tiny.run(name, tmp_path)
    assert not result["correct"], checks
    assert result["failed"] == result["attempted"]
    # each traffic's own numbers: PageRank's error, a search's levels or
    # its halting superstep
    own = ("max_rel_err",) if "max_rel_err" in checks else (
        "mismatched_vertices", "missing_supersteps")
    assert any(checks[k]["value"] > checks[k]["limit"] for k in own), checks


@pytest.mark.parametrize("name", CONTROLLED)
def test_control_is_not_correct(name, tmp_path):
    """The lower-precision path the configuration names (bf16 messages)
    fails the limit that the float32 program meets."""
    result, checks = tiny.run(name, tmp_path, scale=10, control=True)
    assert not result["correct"], checks
    err = checks["max_rel_err"]
    assert err["value"] > err["limit"]
