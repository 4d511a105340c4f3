"""The decision path's in-program trace (``repro.core.trace``).

* **off** (the default): no rows, and the decision path reads no clock and
  opens no ``jax.profiler.TraceAnnotation`` -- checked with both patched;
* **on**: one row per ``WowScheduler.schedule()`` call, the step-1
  sub-spans fit inside ``sched.step1``, and each counter's per-row
  differences sum to the totals the program keeps;
* decisions are bit-identical with the trace on and off.
"""
import os
import sys
import time
from collections import deque

import pytest

from repro.core import trace
from repro.sim import SimConfig, Simulation, TenantSpec, TrafficConfig

jax = pytest.importorskip("jax")

_SRC = os.path.dirname(os.path.dirname(os.path.abspath(trace.__file__)))

SUBSPANS = ("sched.step1.refresh", "sched.step1.solve",
            "sched.step1.inputless")


def _traffic():
    return TrafficConfig(
        tenants=(TenantSpec("alice", weight=2.0,
                            workflows=("rnaseq", "sarek"), scale=0.1,
                            slo=300.0),
                 TenantSpec("bob", weight=1.0, workflows=("group",),
                            scale=0.05, slo=400.0)),
        rate=0.05, n_arrivals=8, max_backlog=3, window=30.0, seed=3)


def _sim():
    return Simulation(None, SimConfig(n_nodes=8, dfs="ceph"), "wow",
                      traffic=_traffic())


def _counting_schedule(sim) -> list[int]:
    """Count the adapter's ``schedule()`` calls, one per round."""
    calls = [0]
    orig = sim.strategy.schedule

    def schedule():
        calls[0] += 1
        return orig()
    sim.strategy.schedule = schedule
    return calls


@pytest.fixture
def trace_off():
    was_on = trace.on
    trace.disable()
    yield
    if was_on:
        trace.enable()


class _Probe:
    """Counts calls of ``time.perf_counter`` made from the program's own
    modules, and constructions of ``jax.profiler.TraceAnnotation``."""

    def __init__(self, monkeypatch) -> None:
        self.clock = 0
        self.annotations = 0
        real_clock = time.perf_counter
        real_ann = jax.profiler.TraceAnnotation
        probe = self

        def clock():
            if sys._getframe(1).f_code.co_filename.startswith(_SRC):
                probe.clock += 1
            return real_clock()

        class Annotation(real_ann):
            def __init__(self, *a, **kw):
                probe.annotations += 1
                super().__init__(*a, **kw)
        monkeypatch.setattr(time, "perf_counter", clock)
        monkeypatch.setattr(jax.profiler, "TraceAnnotation", Annotation)


def test_off_reads_no_clock_and_keeps_no_rows(trace_off, monkeypatch):
    trace.enable()                       # clears the rows
    trace.disable()
    probe = _Probe(monkeypatch)
    sim = _sim()
    calls = _counting_schedule(sim)
    sim.run()
    assert calls[0] > 10
    assert trace.rows() == []
    assert probe.clock == 0
    assert probe.annotations == 0
    # the probe does see the trace's clock and annotations once it is on
    trace.enable()
    _sim().run()
    trace.disable()
    assert probe.clock > 0 and probe.annotations > 0


def test_on_one_row_per_round_and_counters_add_up(trace_off):
    trace.enable()
    sim = _sim()
    calls = _counting_schedule(sim)
    sim.run()
    trace.disable()
    rows = trace.rows()
    assert 10 < calls[0] < trace.ROWS
    assert len(rows) == calls[0]
    assert any(r.get("sched.step1.solve", 0.0) > 0 for r in rows)
    for r in rows:
        assert r["sched.step1"] > 0 and r["sched.step2"] >= 0
        sub = sum(r.get(name, 0.0) for name in SUBSPANS)
        assert sub <= r["sched.step1"] + 1e-12, r
    # close the rows the work after the last schedule() left open
    trace.end_round()
    rows = trace.rows()
    sched = sim.strategy.sched
    want = {
        "step1.comps_resolved": sched.solver_stats["comps_rebuilt"],
        "step1.cache_hits": sched.solver_stats["cache_hits"],
        "step1.cache_misses": sched.solver_stats["cache_misses"],
        "step1.uniform_solves": sched.solver_stats["uniform_solves"],
        "drain.cops_started": sched.cops_created,
        "drain.tasks_probed": sched.drain_probed,
        "drain.probes_skipped": sched.drain_skipped,
        "dps.replica_writes": sched.dps.replica_writes,
        "dps.source_transitions": sched.dps.source_transitions,
        "dps.source_files_walked": sched.dps.source_files_walked,
        "sim.task_starts": sim.task_starts,
        "sim.cops_scanned": sim.cops_scanned,
        "sim.cops_indexed": sim.cops_indexed,
    }
    got = {name: sum(r.get(name, 0.0) for r in rows) for name in want}
    assert got == want
    assert want["sim.task_starts"] == sum(
        1 for e in sim.action_log if e[1] == "task")
    assert all(want[name] > 0 for name in want), want
    # the simulator's COP index: one entry per completed COP, and every
    # COP used at a task start was visited there at least once
    assert want["sim.cops_indexed"] == len(sim.completed_cops)
    assert want["sim.cops_scanned"] >= len(sim.used_cops)
    assert (sum(r.get("step1.budget_aborts", 0.0) for r in rows)
            == sched.solver_stats["budget_aborts"])
    assert trace.totals()["sim.task_starts"] == want["sim.task_starts"]


@pytest.mark.parametrize("first", ["off", "on"])
def test_decisions_identical_with_trace_on_and_off(trace_off, first):
    runs = {}
    for mode in (first, "on" if first == "off" else "off"):
        if mode == "on":
            trace.enable()
        sim = _sim()
        res = sim.run()
        trace.disable()
        runs[mode] = (list(sim.action_log), repr(res.makespan),
                      sim.traffic_result())
    assert runs["on"][0] == runs["off"][0]
    assert runs["on"][1] == runs["off"][1]
    assert runs["on"][2] == runs["off"][2]


def test_rows_keep_the_newest(trace_off, monkeypatch):
    monkeypatch.setattr(trace, "_rows", deque(maxlen=3))
    trace.enable()
    for i in range(5):
        with trace.span("sched.step1"):
            pass
        trace.end_round()
    trace.disable()
    assert len(trace.rows()) == 3
    assert len(trace.rows(2)) == 2 and trace.rows(0) == []
    assert len(trace.rows(10)) == 3
    assert trace.totals()["sched.step1"] >= sum(
        r["sched.step1"] for r in trace.rows())
