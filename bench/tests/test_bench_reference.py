"""The comparison that decides ``correct`` fails when the timed path is
broken, and its float32 control comes out as not correct.

Each fault is planted in the program in this process and the rest of a
run is driven as ``run.py`` drives it (minus the look for a chip), at a
tiny size on the CPU.  A single chip exchanges nothing between chips, so
that fault has no test here.
"""
from __future__ import annotations

import pytest

pytest.importorskip("numpy")

import bench_tiny  # noqa: E402
import harness  # noqa: E402

CELLS = bench_tiny.cells()


def _run(tmp_path, cell, seed=5):
    import jax
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    bench_tiny.shrink(root, cell)
    return harness.run_cell(cell, seed, 0.5, False, 0.0, jax.devices(),
                            bench_dir=bench, root=root, log=lambda m: None)


def _patch_schedule(monkeypatch, change):
    from repro.core.scheduler import WowScheduler
    orig = WowScheduler.schedule

    def schedule(self):
        return change(self, orig(self))
    monkeypatch.setattr(WowScheduler, "schedule", schedule)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell, tmp_path):
    res = _run(tmp_path, cell)
    assert res["correct"], res["checks"]


@pytest.mark.parametrize("cell", CELLS)
def test_state_left_unchanged_fails(cell, tmp_path, monkeypatch):
    from repro.core.scheduler import WowScheduler
    monkeypatch.setattr(WowScheduler, "schedule", lambda self: [])
    res = _run(tmp_path, cell)
    assert not res["correct"]
    assert res["checks"]["decision_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_half_the_batch_left_out_fails(cell, tmp_path, monkeypatch):
    _patch_schedule(monkeypatch, lambda self, acts: acts[:(len(acts) + 1)
                                                         // 2])
    res = _run(tmp_path, cell)
    assert not res["correct"]


@pytest.mark.parametrize("cell", CELLS)
def test_altered_placement_fails(cell, tmp_path, monkeypatch):
    from repro.core.types import StartTask

    def alter(self, acts):
        out = []
        for a in acts:
            if isinstance(a, StartTask) and not self.ready.get(a.task_id) \
                    and not self._finished_specs[a.task_id].inputs:
                a = StartTask(a.task_id, (a.node + 1) % len(self.nodes))
            out.append(a)
        return out
    _patch_schedule(monkeypatch, alter)
    res = _run(tmp_path, cell)
    assert not res["correct"]
    assert res["checks"]["decision_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_altered_priority_fails(cell, tmp_path, monkeypatch):
    """A priority the engine gets wrong is caught, though every decision
    then follows from it."""
    from repro.sim import engine
    orig = engine.assign_priorities

    def wrong(tasks, ranks, sizes):
        orig(tasks, ranks, sizes)
        for t in tasks:
            if t.inputs:
                t.priority += 1.0
    monkeypatch.setattr(engine, "assign_priorities", wrong)
    res = _run(tmp_path, cell)
    assert not res["correct"]
    assert res["checks"]["priority_mismatches"]["value"] > 0


@pytest.mark.parametrize("cell", CELLS)
def test_altered_completion_time_fails(cell, tmp_path, monkeypatch):
    from repro.sim import network
    fill = network._FILLS["heap"]

    def slower(flows, caps):
        fill(flows, caps)
        for f in flows:
            f.rate *= 1.0 - 1e-6
    monkeypatch.setitem(network._FILLS, "heap", slower)
    res = _run(tmp_path, cell)
    assert not res["correct"]
    assert res["checks"]["completion_gap"]["value"] > \
        res["checks"]["completion_gap"]["limit"]


@pytest.mark.parametrize("cell", CELLS)
def test_float32_control_is_not_correct(cell, tmp_path):
    import control
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    bench_tiny.shrink(root, cell)
    r = control.readings(cell, 13, 0.5, bench, root)
    assert r["sound"]["correct"], r["sound"]
    assert not r["control"]["correct"], r["control"]
