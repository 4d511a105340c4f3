"""The trace reduction, on a small trace recorded on a TPU v5e: three
harness rounds (``adapter.schedule`` around ``sched.step1`` and
``sched.step2``, then ``adapter.task_finished``) inside a
``bench.slice`` span, with the marker op at each end of the slice."""
from __future__ import annotations

import json
import os

import pytest

import bench_tiny  # noqa: F401
from devtrace import OUTSIDE, reduce_events

DATA = os.path.join(os.path.dirname(__file__), "data", "trace_small.json")


@pytest.fixture(scope="module")
def reduced():
    with open(DATA) as f:
        d = json.load(f)
    return reduce_events(d["device"], d["host"])


def test_busy_is_device_time_over_the_chips(reduced):
    # two marker ops of 556 ns on the one chip; the CUSTOM plane is no chip
    assert reduced["busy_s"] == pytest.approx(2 * 556e-9)
    assert reduced["window_s"] == pytest.approx(0.03455526)
    assert reduced["device_ops"] == [["broadcast_add_fusion",
                                      pytest.approx(2 * 556e-9)]]


def test_idle_time_goes_to_the_innermost_span(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert gaps["sched.step1"] == pytest.approx(
        (4069200 + 4362811 + 4371340) * 1e-9, rel=1e-6)
    assert gaps["sched.step2"] == pytest.approx(
        (2150740 + 2227620 + 2120491) * 1e-9, rel=1e-6)
    assert gaps["adapter.task_finished"] == pytest.approx(
        (1065680 + 1086231 + 1149500) * 1e-9, rel=1e-6)
    # the schedule span's own time is what its two steps leave
    assert 0 < gaps["adapter.schedule"] < 1e-3
    assert sum(gaps.values()) == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-3)
    assert gaps[OUTSIDE] > 0.01
    assert [k for k, _ in reduced["idle_gaps"]][0] == "sched.step1"


def test_no_slice_or_no_chip_reads_nothing():
    assert reduce_events({"/device:TPU:0": []}, []) is None
    assert reduce_events({"/device:CUSTOM:x": []},
                         [["bench.slice", 0, 10]]) is None
