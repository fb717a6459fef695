"""The per-layer metric readers on made-up runs, and where the traced
slice falls in the window."""

import types

import numpy as np
import pytest

from benchmarks.chip import harness

from conftest import BENCH


def _reader(name):
    return harness.load_module("metrics", name, BENCH)


def _stats(live, lanes):
    return types.SimpleNamespace(live_segments=np.float32(live),
                                 lane_segments=np.float32(lanes))


def _run(answers, window_s=10.0, trace=None):
    return types.SimpleNamespace(
        answers=answers, window_s=window_s, trace=trace,
        jobs=[object()] * len(answers), compile={"seconds": 0.5})


def test_readers_of_counts():
    a = {"stats": _stats(90, 100), "n_launched": 100, "det_rec_n":
         np.int32(4), "steps": np.int32(50), "photons": 100}
    b = dict(a, stats=_stats(60, 100), steps=np.int32(100))
    run = _run([[a], [b]])
    assert _reader("lane_occupancy").read(run) == pytest.approx(0.75)
    assert _reader("host_compile_share").read(run) == pytest.approx(0.05)
    # one answer per job: nothing for the campaign reader
    assert _reader("sweep_lockstep_waste").read(run) is None
    # a batch of two: steps 50 and 100 -> 1 - 150 / 200
    assert _reader("sweep_lockstep_waste").read(_run([[a, b]])) == \
        pytest.approx(0.25)
    # without a trace, nothing for the trace readers
    for name in ("device_idle_share", "scatter_share"):
        assert _reader(name).read(run) is None


def test_trace_readers_by_hand():
    trace = types.SimpleNamespace(slice_s=2.0, busy_s=1.5,
                                  busy_total_s=1.5,
                                  class_s={"scatter": 0.5, "fusion": 1.0})
    run = _run([[{"stats": None}]], trace=trace)
    assert _reader("device_idle_share").read(run) == pytest.approx(0.25)
    assert _reader("scatter_share").read(run) == pytest.approx(1 / 3)
    # counters not collected: no occupancy, never a made-up one
    assert _reader("lane_occupancy").read(run) is None


@pytest.mark.parametrize("first_job_s, want", [(8.5, 3.75), (12.0, 5.5),
                                               (0.4, -0.3)])
def test_slice_opens_in_the_middle_of_the_second_job(first_job_s, want):
    s = harness.Slice(jax=None, log_dir=BENCH, slice_s=1.0)
    assert s.delay(first_job_s) == pytest.approx(want)
    # a job longer than the slice holds all of it
    if first_job_s > 1.0:
        assert 0 < s.delay(first_job_s)
        assert s.delay(first_job_s) + 1.0 < first_job_s
