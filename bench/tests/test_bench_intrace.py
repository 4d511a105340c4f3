"""The program's own trace (``repro.core.trace``) inside a benchmark run:
its rows are the harness's rounds, one per ``schedule()`` call, and the
device breakdown names its spans with no edit to the reduction."""
from __future__ import annotations

import json
import os
import re

import pytest

pytest.importorskip("numpy")

import bench_tiny  # noqa: E402
import harness  # noqa: E402
from devtrace import reduce_events  # noqa: E402

CELL = "k8s5000.nfcore"


@pytest.fixture
def program_trace():
    trace = pytest.importorskip("repro.core.trace")
    was_on = trace.on
    trace.enable()
    yield trace
    if not was_on:
        trace.disable()


def test_program_rows_are_the_window_rounds(tmp_path, program_trace):
    """A tiny traced run of the cell, its workflows at a tenth of Table I
    (twice the tiny scale): step-1 rounds of a millisecond or so, so that
    the harness's own step-1 wrapper, which the program's ``sched.step1``
    span holds, costs little beside them."""
    import jax
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    bench_tiny.shrink(root, CELL)
    path = os.path.join(bench, "configs", "k8s5000.json")
    with open(path) as f:
        cfg = json.load(f)
    cfg["workflow_scale"] = 2 * bench_tiny.TINY_SCALE
    with open(path, "w") as f:
        json.dump(cfg, f)
    logged: list[str] = []
    res = harness.run_cell(CELL, 2 ** 32 + 977, 0.5, True, 0.0,
                           jax.devices(), bench_dir=bench, root=root,
                           log=logged.append)
    assert res["correct"], res["checks"]
    rounds = res["attempted"]
    setup_rounds = int(re.search(r"\((\d+) rounds\)", "\n".join(logged))[1])
    # one row per schedule() call: the set-up's rounds, then the window's
    assert len(program_trace.rows()) == setup_rounds + rounds
    window = program_trace.rows(rounds)
    assert len(window) == rounds
    ours = sum(r["sched.step1"] for r in window) * 1e3 / rounds
    theirs = res["metrics"]["step1_ms_per_round"]["value"]
    assert theirs <= ours <= 1.05 * theirs, (ours, theirs)
    assert sum(r["sim.task_starts"] for r in window) > 0
    assert sum(r["step1.comps_resolved"] for r in window) > 0


def test_reduction_names_the_step1_sub_spans():
    """An idle moment goes to the innermost ``sched.`` span open on the
    host, so the program's step-1 sub-spans reach the breakdown."""
    host = [("bench.slice", 0, 1000),
            ("adapter.schedule", 100, 800),
            ("sched.step1", 110, 600),
            ("sched.step1", 120, 580),             # the harness's wrapper
            ("sched.step1.refresh", 130, 100),
            ("sched.step1.solve", 240, 400),
            ("sched.step2", 720, 100)]
    device = {"/device:TPU:0": [("marker", 0, 10), ("marker", 990, 10)]}
    gaps = dict(reduce_events(device, host)["idle_gaps"])
    assert gaps["sched.step1.solve"] == pytest.approx(400e-9)
    assert gaps["sched.step1.refresh"] == pytest.approx(100e-9)
    assert gaps["sched.step2"] == pytest.approx(100e-9)
