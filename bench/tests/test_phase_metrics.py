"""The readers of the streamed superstep's phase seconds: what they read
from the window's records, and nothing where there is nothing to read."""

from types import SimpleNamespace

import pytest

from bench import harness

PHASE_METRICS = ("stage_wait_s_per_step", "stage_s_per_step",
                 "fold_s_per_step", "host_other_s_per_step")


def reader(name):
    return harness.load_module(f"{harness.BENCH}/metrics/{name}.py", name)


def record(seconds, **phases):
    return SimpleNamespace(seconds=seconds, phase_seconds={
        k.replace("_", ".", 1): v for k, v in phases.items()})


WINDOW = [
    record(1.0, stream_stage_wait=0.1, stream_fold=0.5, channel_send=0.1,
           stream_apply=0.1, stream_stage=0.4),
    record(2.0, stream_stage_wait=0.3, stream_fold=1.1, channel_send=0.2,
           stream_apply=0.2, stream_stage=0.6),
]


@pytest.mark.parametrize("name, want", [
    ("stage_wait_s_per_step", 0.2),
    ("stage_s_per_step", 0.5),
    ("fold_s_per_step", 0.8),
    # 1.0 - 0.8 and 2.0 - 1.8: the staging thread's seconds are not taken
    ("host_other_s_per_step", 0.2),
])
def test_mean_over_the_window(name, want):
    run = SimpleNamespace(plan_mode="streamed", window=WINDOW)
    assert reader(name).read(run) == pytest.approx(want)


@pytest.mark.parametrize("name", PHASE_METRICS)
def test_nothing_to_read_is_none(name):
    read = reader(name).read
    # in memory, an empty window, and a program that records no phases
    assert read(SimpleNamespace(plan_mode="recoded", window=WINDOW)) is None
    assert read(SimpleNamespace(plan_mode="streamed", window=[])) is None
    bare = [SimpleNamespace(seconds=1.0), SimpleNamespace(seconds=2.0)]
    assert read(SimpleNamespace(plan_mode="streamed", window=bare)) is None
