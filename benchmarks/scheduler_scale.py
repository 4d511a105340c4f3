"""Scheduler-iteration latency vs cluster size (paper §IV-C reports 11 ms
median ILP time on 8 nodes; production target is 1000+ nodes).

Two regimes, both measured for the incremental ``WowScheduler`` and the
retained ``ReferenceWowScheduler``:

* **cold**      one ``schedule()`` over a freshly filled queue (the seed
                benchmark's original measurement),
* **sustained** per-iteration latency of a *warm* scheduler digesting a
                steady event stream (task finished + COP finished + new
                submission per iteration), which is what the per-event hot
                loop of a dynamic engine actually looks like.

Each measurement separates two phases: the **step-1 solver**
(``solver_ms_per_iter`` / ``cold_solver_ms``, plus the indexed solver's own
counters) and **steps 2-3** (``step23_ms_per_iter`` -- the COP-placement /
speculative-ordering share this PR's indexed ready set targets).  The
incremental scheduler's phases are its own ``repro.core.trace`` spans
(``sched.step1.solve``, ``sched.step2`` + ``sched.step3``; each measuring
function switches the trace on); the frozen
reference scheduler is measured by temporarily wrapping its ``solve``
symbol and step-2/3 methods.

Two further scenarios cover this PR's other step-1 paths:

* ``run_inputless`` -- a sustained backlog of *input-less* tasks (a
  workflow fan-out phase).  The indexed scheduler routes these through the
  capacity-only fast path (no DPS, no component machinery); the reference
  rebuilds every candidate list per event.  Headline keys
  ``inputless_ms_per_iter_{indexed,reference}`` / ``inputless_speedup``.
* ``run_live_rm`` -- the declined-placement path, end to end: bursty task
  arrivals hit a throttled resource manager that declines every placement
  for several scheduling rounds (the ``core/adapter.py`` decline-requeue
  contract), then recovers and drains the backlog with out-of-order
  completions.  Runs the full ``WowScheduler`` twice -- ``strict_parity=
  True`` (cold) vs ``False`` (B&B incumbent seeded from the dissolved
  assignment) -- on identical storm instances.  Records solver ms per
  storm event, re-solve counters and warm seeds, and asserts objective
  safety (warm never worse; equal whenever the B&B stays inside its node
  budget).  Headline key ``live_rm``, row scenario ``live_rm``.
* ``run_dfs_churn`` -- orig/cws/wow end-to-end on Ceph rep=2 with an
  injected node failure, recording the failure-aware DFS counters
  (degraded-read + re-replication bytes per strategy; headline key
  ``dfs_churn``, row scenario ``dfs_churn``).
* ``run_sim_throughput`` -- **end-to-end simulation wall-clock**: full
  ``group`` workflow runs (one wave of input-less generator tasks + DFS
  merges) for orig/cws/wow at 256/1024/4096 nodes, on both the incremental
  heap fill and the retained ``flow_fill="scan"`` pre-heap engine.  Rows
  carry wall seconds, events/sec and the FlowManager health counters;
  makespans are asserted bit-identical between fills.  Headline key
  ``sim_throughput`` with ``sim_speedup`` = the minimum scan/heap wall
  ratio over the DFS-bound strategies (orig, cws) at the largest size both
  fills ran (wow is reported but excluded from the ratio: its node-local
  I/O keeps flow components tiny by design, so there is little fill time
  to win back).  The scan fill is omitted beyond
  ``_SIM_SCAN_MAX_NODES`` -- at 4096 nodes one pre-heap run takes tens of
  minutes, which is precisely the regression this scenario guards against.
  ``BENCH_SMOKE=1`` restricts the scenario to the smallest size so CI
  stays fast (full-scale rows are a local/nightly tier).
* ``run_sampled_recompute`` -- per-event recompute latency at
  4096/16384/65536 nodes via *sampled-recompute timing*: instead of whole
  runs it times a fixed sample of schedule() recomputes against a
  jittered busy-cluster snapshot, for ``WowScheduler`` and the frozen
  reference, asserting the action streams stay bit-identical.  Headline
  key ``sampled_recompute``.

Results land in BENCH_scheduler_scale.json; headline numbers are the
sustained speedup and the phase times on the (1024 nodes, 4096 ready
tasks) row.
"""
from __future__ import annotations

import contextlib
import os
import random
import sys
import time

import repro.core.reference as _reference
from repro.core import (DataPlacementService, FileSpec, NodeState,
                        ReferenceWowScheduler, StartTask, TaskSpec,
                        WowScheduler, trace)

from .common import emit, write_json

GiB = 1024 ** 3
# sized so nodes fit ~2 tasks: a large ready backlog persists, which is the
# regime where per-event cost matters
TASK_MEM = 48 * GiB
TASK_CORES = 6.0

SIZES = [(8, 64), (32, 256), (128, 1024), (512, 2048), (1024, 4096)]
HEADLINE = (1024, 4096)


@contextlib.contextmanager
def _timed_reference_solver():
    """Accumulate wall time spent in the reference scheduler's (monolithic)
    step-1 solver without touching the frozen module's code."""
    acc = {"s": 0.0}
    orig = _reference.solve

    def timed(problem):
        t0 = time.perf_counter()
        try:
            return orig(problem)
        finally:
            acc["s"] += time.perf_counter() - t0

    _reference.solve = timed
    try:
        yield acc
    finally:
        _reference.solve = orig


@contextlib.contextmanager
def _timed_reference_steps23():
    """Accumulate wall time in the reference scheduler's steps 2-3 by
    wrapping the (frozen) class methods for the duration."""
    acc = {"s": 0.0}
    orig2 = ReferenceWowScheduler._step2_prepare_for_free_compute
    orig3 = ReferenceWowScheduler._step3_speculative_prepare

    def timed2(self, actions, started):
        t0 = time.perf_counter()
        try:
            return orig2(self, actions, started)
        finally:
            acc["s"] += time.perf_counter() - t0

    def timed3(self, actions):
        t0 = time.perf_counter()
        try:
            return orig3(self, actions)
        finally:
            acc["s"] += time.perf_counter() - t0

    ReferenceWowScheduler._step2_prepare_for_free_compute = timed2
    ReferenceWowScheduler._step3_speculative_prepare = timed3
    try:
        yield acc
    finally:
        ReferenceWowScheduler._step2_prepare_for_free_compute = orig2
        ReferenceWowScheduler._step3_speculative_prepare = orig3


@contextlib.contextmanager
def _traced():
    """The program's trace on for the block or decorated function (and off
    again after, if it was off): the indexed scheduler's phase times are
    its spans."""
    was_on = trace.on
    if not was_on:
        trace.enable()
    try:
        yield
    finally:
        if not was_on:
            trace.disable()


def _span_seconds(*names: str) -> float:
    """Seconds in the named trace spans since the trace was enabled."""
    totals = trace.totals()
    return sum(totals.get(n, 0.0) for n in names)


def _solver_seconds(sched, acc) -> float:
    if isinstance(sched, WowScheduler):
        return _span_seconds("sched.step1.solve")
    return acc["s"]


def _step23_seconds(sched, acc23) -> float:
    if isinstance(sched, WowScheduler):
        return _span_seconds("sched.step2", "sched.step3")
    return acc23["s"]


def build(n_nodes: int, n_ready: int, cls, seed: int = 0,
          inputless: bool = False):
    rng = random.Random(seed)
    nodes = {i: NodeState(i, 128 * GiB, 16.0) for i in range(n_nodes)}
    dps = DataPlacementService(seed=seed)
    sched = cls(nodes, dps)
    for t in range(n_ready):
        if inputless:
            inputs: tuple[int, ...] = ()
        else:
            fid = t
            host = rng.randrange(n_nodes)
            dps.register_file(FileSpec(id=fid, size=rng.randint(1, 4) * GiB,
                                       producer=-1), host)
            inputs = (fid,)
        task = TaskSpec(id=t, abstract="a", mem=TASK_MEM, cores=TASK_CORES,
                        inputs=inputs, priority=rng.uniform(1, 10))
        sched.submit(task)
    return sched, dps, rng


def drive_event(sched, dps, rng, n_nodes: int, next_id: int,
                inputless: bool = False) -> list:
    """One sustained event round: finish a task, finish a COP, submit a
    fresh task (single-input whose file lands on a random node, or
    input-less in the fan-out scenario), then schedule().  Returns the
    actions of that schedule().  The single definition of the event
    protocol -- used by the sustained measurements and the equivalence
    sanity check, so both exercise the same workload."""
    if sched.running:
        tid = next(iter(sched.running))
        sched.on_task_finished(tid, sched.running[tid])
    if sched.active_cops:
        cid = next(iter(sched.active_cops))
        sched.on_cop_finished(sched.active_cops[cid], ok=True)
    if inputless:
        inputs: tuple[int, ...] = ()
    else:
        host = rng.randrange(n_nodes)
        dps.register_file(FileSpec(id=next_id, size=rng.randint(1, 4) * GiB,
                                   producer=-1), host)
        inputs = (next_id,)
    sched.submit(TaskSpec(id=next_id, abstract="a", mem=TASK_MEM,
                          cores=TASK_CORES, inputs=inputs,
                          priority=rng.uniform(1, 10)))
    return sched.schedule()


@_traced()
def run_cold(n_nodes: int, n_ready: int, cls, seed: int = 0):
    """Returns (total ms, solver ms, #actions) for one cold schedule()."""
    sched, _, _ = build(n_nodes, n_ready, cls, seed)
    with _timed_reference_solver() as acc:
        solver_s0 = _solver_seconds(sched, acc)
        t0 = time.perf_counter()
        actions = sched.schedule()
        total_ms = (time.perf_counter() - t0) * 1000
        solver_ms = (_solver_seconds(sched, acc) - solver_s0) * 1000
    return total_ms, solver_ms, len(actions)


@_traced()
def run_sustained(n_nodes: int, n_ready: int, cls, iters: int,
                  seed: int = 0, inputless: bool = False) -> dict:
    """Warm scheduler, then `iters` event rounds: finish one task, finish
    one COP, submit one fresh task, schedule().  Returns per-iteration
    averages: ``ms``, ``solver_ms``, ``step23_ms``, ``actions``, plus the
    indexed solver's counter deltas (``stats``).

    Warm-up is the initial cold schedule *plus one unmeasured event round*:
    the first event after a cold start is a one-off outlier for any
    incremental implementation (the cold reservations dirtied every node, so
    everything must be refreshed once), while the measurement target is the
    steady per-event cost of a long-running engine."""
    sched, dps, rng = build(n_nodes, n_ready, cls, seed, inputless=inputless)
    with _timed_reference_solver() as acc, \
            _timed_reference_steps23() as acc23:
        next_id = n_ready
        sched.schedule()                  # warm-up: initial placements/COPs
        drive_event(sched, dps, rng, n_nodes, next_id,
                    inputless=inputless)  # post-cold refresh
        next_id += 1
        solver_s0 = _solver_seconds(sched, acc)
        step23_s0 = _step23_seconds(sched, acc23)
        stats0 = (dict(sched.solver_stats)
                  if isinstance(sched, WowScheduler) else None)
        less0 = (dict(sched.inputless_stats)
                 if isinstance(sched, WowScheduler) else None)
        actions = 0
        t0 = time.perf_counter()
        for _ in range(iters):
            actions += len(drive_event(sched, dps, rng, n_nodes, next_id,
                                       inputless=inputless))
            next_id += 1
        dt_ms = (time.perf_counter() - t0) * 1000
        solver_ms = (_solver_seconds(sched, acc) - solver_s0) * 1000
        step23_ms = (_step23_seconds(sched, acc23) - step23_s0) * 1000
    # stats cover the measured window only (delta vs the warm-up snapshot),
    # matching the scope of solver_ms_per_iter
    stats = ({k: v - stats0[k] for k, v in sched.solver_stats.items()}
             if stats0 is not None else None)
    less_stats = ({k: v - less0[k] for k, v in sched.inputless_stats.items()}
                  if less0 is not None else None)
    return {"ms": dt_ms / iters, "solver_ms": solver_ms / iters,
            "step23_ms": step23_ms / iters, "actions": actions / iters,
            "stats": stats, "inputless_stats": less_stats}


def run_inputless(n_nodes: int, n_ready: int, cls, iters: int,
                  seed: int = 0) -> dict:
    """Sustained fan-out phase: the whole backlog is input-less tasks, so
    every step-1 decision is pure capacity placement."""
    return run_sustained(n_nodes, n_ready, cls, iters, seed, inputless=True)


# ------------------------------------------- end-to-end simulation throughput
# (cluster size, workflow scale): ~1 generator task per node at 256/1024, a
# half-wave at 4096 to keep the full tier affordable.  The scan (pre-heap)
# baseline is only affordable up to _SIM_SCAN_MAX_NODES.
SIM_SIZES = [(256, 2.56), (1024, 10.24), (4096, 20.48)]
SIM_WORKFLOW = "group"
_SIM_SCAN_MAX_NODES = 1024
SIM_HEADLINE_STRATEGIES = ("orig", "cws")


def bench_smoke() -> bool:
    """True when BENCH_SMOKE=1: CI tier, full-scale rows skipped."""
    return os.environ.get("BENCH_SMOKE", "") == "1"


def run_sim_throughput(sizes: list[tuple[int, float]] | None = None,
                       ) -> tuple[list[dict], dict]:
    """Full-workflow simulation wall-clock, heap vs scan fill.

    Returns (rows, headline): one row per (strategy, nodes, fill) with wall
    seconds, events/sec and FlowManager health counters, and a headline
    dict whose ``sim_speedup`` is the minimum scan/heap wall ratio over
    the DFS-bound strategies at the largest size both fills ran.  Asserts
    that both fills produce bit-identical makespans and event counts --
    the cheap in-bench guard; the full proof is tests/test_flow_fill.py.
    """
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    if sizes is None:
        sizes = SIM_SIZES[:1] if bench_smoke() else SIM_SIZES
    rows: list[dict] = []
    speedups: dict[int, dict[str, float]] = {}
    emit("scheduler_scale,sim_throughput,strategy,nodes,fill,wall_s,"
         "events,events_per_s,makespan,flow_recomputes,mean_component")
    for n_nodes, scale in sizes:
        for strat in ("orig", "cws", "wow"):
            walls: dict[str, float] = {}
            results: dict[str, object] = {}
            fills = ["heap"] + (["scan"] if n_nodes <= _SIM_SCAN_MAX_NODES
                                else [])
            for fill in fills:
                wf = make_workflow(SIM_WORKFLOW, scale=scale)
                cfg = SimConfig(n_nodes=n_nodes, dfs="ceph", flow_fill=fill)
                t0 = time.perf_counter()
                r = Simulation(wf, cfg, strat).run()
                wall = time.perf_counter() - t0
                walls[fill] = wall
                results[fill] = r
                rows.append({
                    "impl": strat, "scenario": "sim_throughput",
                    "nodes": n_nodes, "tasks": r.tasks_total, "fill": fill,
                    "wall_s": wall, "events": r.sim_steps,
                    "events_per_s": r.sim_steps / max(wall, 1e-9),
                    "makespan": r.makespan,
                    "flow_recomputes": r.flow_recomputes,
                    "flow_compactions": r.flow_compactions,
                    "flow_mean_component": r.flow_mean_component,
                })
                emit(f"scheduler_scale,sim_throughput,{strat},{n_nodes},"
                     f"{fill},{wall:.2f},{r.sim_steps},"
                     f"{r.sim_steps / max(wall, 1e-9):.0f},"
                     f"{r.makespan:.2f},{r.flow_recomputes},"
                     f"{r.flow_mean_component:.1f}")
            if "scan" in results:
                rh, rs = results["heap"], results["scan"]
                assert rh.makespan == rs.makespan, (
                    f"{strat}@{n_nodes}: heap fill changed the makespan")
                assert rh.sim_steps == rs.sim_steps, (
                    f"{strat}@{n_nodes}: heap fill changed the event count")
                speedups.setdefault(n_nodes, {})[strat] = (
                    walls["scan"] / max(walls["heap"], 1e-9))
    head_nodes = max(speedups) if speedups else None
    sim_speedup = None
    if head_nodes is not None:
        sim_speedup = min(speedups[head_nodes][s]
                          for s in SIM_HEADLINE_STRATEGIES
                          if s in speedups[head_nodes])
        emit(f"scheduler_scale,sim_speedup_{head_nodes}n,{sim_speedup:.1f}x")
    headline = {
        "workflow": SIM_WORKFLOW,
        "sizes": [n for n, _ in sizes],
        "scan_max_nodes": _SIM_SCAN_MAX_NODES,
        "speedups": {str(n): sp for n, sp in sorted(speedups.items())},
        "sim_speedup_nodes": head_nodes,
        "sim_speedup": sim_speedup,
    }
    return rows, headline


# --------------------------------------------------- DFS churn (rep=2 Ceph)
def run_dfs_churn(fail_t: float = 30.0, fail_node: int = 1) -> dict:
    """orig/cws/wow on Ceph rep=2 with an injected node failure: the
    failure-aware DFS serves degraded reads off surviving replicas and
    re-replicates under-replicated objects through the shared flow network.
    Records the churn counters per strategy (the orig/cws baselines must
    show nonzero degraded-read + re-replication bytes; WOW keeps
    intermediates node-local, so its DFS repair traffic is zero)."""
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    out: dict[str, dict] = {}
    for strat in ("orig", "cws", "wow"):
        wf = make_workflow("group", scale=0.25)
        sim = Simulation(wf, SimConfig(dfs="ceph", ceph_replication=2), strat)
        sim.schedule_failure(fail_t, fail_node)
        r = sim.run()
        out[strat] = {
            "makespan": r.makespan,
            "degraded_reads": r.degraded_reads,
            "degraded_read_bytes": r.degraded_read_bytes,
            "rereplication_bytes": r.rereplication_bytes,
            "repairs_completed": r.repairs_completed,
            "dfs_lost_files": r.dfs_lost_files,
        }
    for strat in ("orig", "cws"):
        assert out[strat]["degraded_read_bytes"] > 0, (
            f"{strat}: expected degraded reads under churn")
        assert out[strat]["rereplication_bytes"] > 0, (
            f"{strat}: expected re-replication traffic under churn")
    return out


# --------------------------------------- sampled-recompute at extreme scale
# Timing whole runs past 4096 nodes is unaffordable, so this scenario times
# a *fixed sample of recompute events* against a synthetic mid-run cluster
# snapshot instead:
#
# * every node is partially busy with jittered free capacities, so each
#   fitting query faces ~one capacity class per node -- one masked pass
#   over ``NodeCapacityArray``;
# * each sampled event submits ``RECOMP_K`` input-less tasks (the fan-out
#   shape that dominates large waves), times one ``schedule()`` recompute,
#   then finishes the placed tasks so the snapshot returns to steady state.
#
# Rows cover ``WowScheduler`` and the frozen reference (few samples; capped
# at ``_RECOMP_REFERENCE_MAX_NODES`` -- its per-event rebuild is O(n) per
# ready task).  Both consume one shared RNG schedule, and the bench asserts
# the reference's per-event action stream is a prefix of the scheduler's.
# Headline key ``sampled_recompute``.
RECOMP_SIZES = [4096, 16384, 65536]
RECOMP_SMOKE_SIZES = [512]
RECOMP_K = 32                       # tasks per sampled recompute event
RECOMP_SAMPLES = {"indexed": 20, "reference": 3}
_RECOMP_REFERENCE_MAX_NODES = 16384


def build_busy(n_nodes: int, cls, seed: int = 0):
    """A mid-run cluster snapshot: every node partially busy with jittered
    free capacities (distinct (free_mem, free_cores) pairs => ~one
    capacity class per node), every node still fitting the probe shape (so
    candidate lists stay O(n), like a real half-loaded wave)."""
    rng = random.Random(seed)
    nodes: dict[int, NodeState] = {}
    for i in range(n_nodes):
        s = NodeState(i, 128 * GiB, 16.0)
        s.free_mem = (48 + rng.randrange(0, 33)) * GiB
        s.free_cores = 6.0 + 0.5 * rng.randrange(0, 13)
        nodes[i] = s
    dps = DataPlacementService(seed=seed)
    return cls(nodes, dps), rng


def _sampled_recompute_one(n_nodes: int, impl: str, samples: int,
                           seed: int = 0) -> dict:
    """Time ``samples`` recompute events (plus one unmeasured warm-up) and
    return per-event ms and the summarized action stream for the parity
    assertion."""
    cls = ReferenceWowScheduler if impl == "reference" else WowScheduler
    sched, rng = build_busy(n_nodes, cls, seed)
    next_id = 0
    log: list[list] = []
    total = 0.0
    for i in range(samples + 1):
        for _ in range(RECOMP_K):
            sched.submit(TaskSpec(id=next_id, abstract="a", mem=TASK_MEM,
                                  cores=TASK_CORES, inputs=(),
                                  priority=rng.uniform(1, 10)))
            next_id += 1
        t0 = time.perf_counter()
        actions = sched.schedule()
        dt = time.perf_counter() - t0
        if i > 0:                       # warm-up event is unmeasured
            total += dt
        log.append(_summarize(actions))
        for tid in list(sched.running):
            sched.on_task_finished(tid, sched.running[tid])
    return {"ms_per_recompute": total * 1000 / samples, "log": log}


def run_sampled_recompute(sizes: list[int] | None = None,
                          ) -> tuple[list[dict], dict]:
    """Sampled-recompute timing per cluster size; returns (rows, headline)."""
    if sizes is None:
        sizes = RECOMP_SMOKE_SIZES if bench_smoke() else RECOMP_SIZES
    rows: list[dict] = []
    per_size_ms: dict[int, dict[str, float]] = {}
    emit("scheduler_scale,sampled_recompute,impl,nodes,k,samples,"
         "ms_per_recompute")
    for n_nodes in sizes:
        res: dict[str, dict] = {}
        for impl in ("indexed", "reference"):
            if impl == "reference" and n_nodes > _RECOMP_REFERENCE_MAX_NODES:
                continue
            samples = RECOMP_SAMPLES[impl]
            res[impl] = _sampled_recompute_one(n_nodes, impl, samples)
            rows.append({"impl": impl, "scenario": "sampled_recompute",
                         "nodes": n_nodes, "k": RECOMP_K, "samples": samples,
                         "ms_per_recompute": res[impl]["ms_per_recompute"]})
            emit(f"scheduler_scale,sampled_recompute,{impl},{n_nodes},"
                 f"{RECOMP_K},{samples},"
                 f"{res[impl]['ms_per_recompute']:.3f}")
        # bit-parity on the shared event schedule
        if "reference" in res:
            k = len(res["reference"]["log"])
            assert res["reference"]["log"] == res["indexed"]["log"][:k], (
                f"sampled_recompute@{n_nodes}: scheduler diverged from "
                f"the reference")
        per_size_ms[n_nodes] = {i: r["ms_per_recompute"]
                                for i, r in res.items()}
    headline = {
        "k": RECOMP_K,
        "sizes": sizes,
        "ms_per_recompute": {str(n): ms
                             for n, ms in sorted(per_size_ms.items())},
    }
    return rows, headline


# ------------------------------------------------- hierarchical topology
# Same full-workflow runs as sim_throughput, but under the hierarchical
# topology layer (sim/topology.py): flat vs 2-level (racks, oversubscribed
# uplinks) vs multi-site (racks + shared cores + WAN).  Three measurements:
#
# * per-(size, topology, strategy) rows with makespan, events/sec and the
#   per-locality-tier traffic split (``tier_bytes``) -- the paper-side
#   point: WOW's locality-aware placement keeps bytes off the
#   oversubscribed tiers, the DFS-bound baselines pay them;
# * an oversubscription sweep at the smallest size asserting the
#   WOW-vs-orig makespan gap *widens* as the rack uplinks shrink;
# * heap-vs-scan fill at the largest oversubscribed point: bit-identical
#   makespans asserted, and the path-constrained heap fill must stay
#   >= ``_TOPO_FILL_MIN_SPEEDUP``x the scan fill in events/sec (full tier
#   only -- the smoke tier runs both fills but skips the ratio floor).
TOPO_SIZES = [(256, 2.56), (1024, 10.24)]
TOPO_SMOKE_SIZES = [(256, 2.56)]
TOPO_CONFIGS: dict[str, dict | None] = {
    "flat": None,
    "rack": {"rack_size": 32, "oversubscription": 8.0},
    "site": {"rack_size": 32, "racks_per_site": 4, "oversubscription": 8.0,
             "core_oversubscription": 2.0},
}
TOPO_SWEEP_OVERSUB = [1.0, 4.0, 16.0]
_TOPO_FILL_MIN_SPEEDUP = 2.0


def run_topology(sizes: list[tuple[int, float]] | None = None,
                 ) -> tuple[list[dict], dict]:
    """Topology-aware end-to-end runs; returns (rows, headline)."""
    from repro.sim import SimConfig, Simulation, TopologySpec
    from repro.workloads import make_workflow

    smoke = bench_smoke()
    if sizes is None:
        sizes = TOPO_SMOKE_SIZES if smoke else TOPO_SIZES

    def one(n_nodes, scale, strat, spec, fill="heap"):
        wf = make_workflow(SIM_WORKFLOW, scale=scale)
        cfg = SimConfig(n_nodes=n_nodes, dfs="ceph", topology=spec,
                        flow_fill=fill)
        t0 = time.perf_counter()
        r = Simulation(wf, cfg, strat).run()
        return r, time.perf_counter() - t0

    rows: list[dict] = []
    makespans: dict[tuple[int, str, str], float] = {}
    emit("scheduler_scale,topology,strategy,nodes,topo,fill,wall_s,events,"
         "events_per_s,makespan,network_bytes,wan_bytes")
    for n_nodes, scale in sizes:
        for topo_name, params in TOPO_CONFIGS.items():
            spec = TopologySpec(**params) if params else None
            for strat in ("orig", "cws", "wow"):
                r, wall = one(n_nodes, scale, strat, spec)
                makespans[(n_nodes, topo_name, strat)] = r.makespan
                rows.append({
                    "impl": strat, "scenario": "topology", "nodes": n_nodes,
                    "topo": topo_name, "fill": "heap", "wall_s": wall,
                    "events": r.sim_steps,
                    "events_per_s": r.sim_steps / max(wall, 1e-9),
                    "makespan": r.makespan,
                    "network_bytes": r.network_bytes,
                    "tier_bytes": dict(r.tier_bytes),
                })
                emit(f"scheduler_scale,topology,{strat},{n_nodes},"
                     f"{topo_name},heap,{wall:.2f},{r.sim_steps},"
                     f"{r.sim_steps / max(wall, 1e-9):.0f},"
                     f"{r.makespan:.2f},{r.network_bytes:.0f},"
                     f"{r.tier_bytes.get('wan', 0.0):.0f}")

    # --- oversubscription sweep: the WOW advantage must widen as the rack
    # uplinks shrink (smallest size keeps the sweep affordable everywhere)
    n_sweep, scale_sweep = sizes[0]
    gaps: dict[float, float] = {}
    for ov in TOPO_SWEEP_OVERSUB:
        spec = TopologySpec(rack_size=32, oversubscription=ov)
        ms: dict[str, float] = {}
        for strat in ("orig", "wow"):
            r, wall = one(n_sweep, scale_sweep, strat, spec)
            ms[strat] = r.makespan
            rows.append({
                "impl": strat, "scenario": "topology_sweep",
                "nodes": n_sweep, "oversubscription": ov, "wall_s": wall,
                "makespan": r.makespan,
                "tier_bytes": dict(r.tier_bytes),
            })
        gaps[ov] = ms["orig"] / max(ms["wow"], 1e-9)
        emit(f"scheduler_scale,topology_sweep,{n_sweep},oversub,{ov},"
             f"orig,{ms['orig']:.2f},wow,{ms['wow']:.2f},"
             f"gap,{gaps[ov]:.2f}x")
    seq = [gaps[ov] for ov in TOPO_SWEEP_OVERSUB]
    assert all(b >= a - 1e-9 for a, b in zip(seq, seq[1:])), (
        f"topology: WOW-vs-orig makespan gap did not widen with "
        f"oversubscription: {gaps}")

    # --- heap vs scan on the most path-constrained point run (site
    # topology, largest size): bit-identity plus the events/sec floor
    n_fill, scale_fill = sizes[-1]
    spec = TopologySpec(**TOPO_CONFIGS["site"])
    fill_eps: dict[str, float] = {}
    fill_res: dict[str, object] = {}
    for fill in ("heap", "scan"):
        r, wall = one(n_fill, scale_fill, "orig", spec, fill=fill)
        fill_eps[fill] = r.sim_steps / max(wall, 1e-9)
        fill_res[fill] = r
        rows.append({
            "impl": "orig", "scenario": "topology", "nodes": n_fill,
            "topo": "site", "fill": fill, "wall_s": wall,
            "events": r.sim_steps, "events_per_s": fill_eps[fill],
            "makespan": r.makespan, "network_bytes": r.network_bytes,
            "tier_bytes": dict(r.tier_bytes),
        })
        emit(f"scheduler_scale,topology,orig,{n_fill},site,{fill},"
             f"{wall:.2f},{r.sim_steps},{fill_eps[fill]:.0f},"
             f"{r.makespan:.2f},{r.network_bytes:.0f},"
             f"{r.tier_bytes.get('wan', 0.0):.0f}")
    rh, rs = fill_res["heap"], fill_res["scan"]
    assert rh.makespan == rs.makespan, (
        f"topology@{n_fill}: heap fill changed the makespan under topology")
    assert rh.sim_steps == rs.sim_steps, (
        f"topology@{n_fill}: heap fill changed the event count")
    fill_speedup = fill_eps["heap"] / max(fill_eps["scan"], 1e-9)
    emit(f"scheduler_scale,topology_fill_speedup_{n_fill}n,"
         f"{fill_speedup:.1f}x")
    # A claim about clean timings: under cProfile the ratio measures
    # per-call hook overhead, not the fill.
    if not smoke and sys.getprofile() is None:
        assert fill_speedup >= _TOPO_FILL_MIN_SPEEDUP, (
            f"topology@{n_fill}: path-constrained heap fill only "
            f"{fill_speedup:.2f}x the scan fill (floor "
            f"{_TOPO_FILL_MIN_SPEEDUP}x)")

    head_nodes = max(n for n, _ in sizes)
    headline = {
        "workflow": SIM_WORKFLOW,
        "sizes": [n for n, _ in sizes],
        "configs": {k: (v or {}) for k, v in TOPO_CONFIGS.items()},
        "makespans": {f"{n}:{t}:{s}": m
                      for (n, t, s), m in sorted(makespans.items())},
        "oversub_gap": {str(ov): gaps[ov] for ov in TOPO_SWEEP_OVERSUB},
        "gap_widens": True,
        "fill_nodes": n_fill,
        "fill_speedup": fill_speedup,
        "wow_vs_orig_site": (
            makespans[(head_nodes, "site", "orig")]
            / max(makespans[(head_nodes, "site", "wow")], 1e-9)),
        "wow_vs_orig_flat": (
            makespans[(head_nodes, "flat", "orig")]
            / max(makespans[(head_nodes, "flat", "wow")], 1e-9)),
    }
    return rows, headline


# ------------------------------------------- open-loop multi-tenant traffic
# Three tenants sharing one cluster under a seeded Poisson arrival stream:
# a weight-2 "batch" tenant (group/fork patterns), a weight-1 "ml" tenant
# (roofline-costed mlpipe pipelines) and a weight-1 "svc" tenant (short
# chains with the tightest SLO).  All three strategies consume the *same*
# ``TrafficConfig`` -- ``arrival_schedule`` is a pure function of it, so the
# arrival stream (times, tenants, per-instance workflow seeds) is identical
# across orig/cws/wow by construction.  ``max_backlog`` is sized so the
# admission gate binds: backlog saturates under the slowest strategy, and
# the fast one wins by *draining* (more admissions, lower p99) rather than
# by seeing friendlier traffic.  Headline key ``multi_tenant``; asserts
# WOW's p99 completion latency is no worse than orig's at the saturated
# operating point (the largest size run).
MT_SIZES = [256, 1024]
MT_SMOKE_SIZES = [256]
MT_CONFIGS = {
    256: {"rate": 0.25, "n_arrivals": 40, "max_backlog": 10, "scale": 0.2},
    1024: {"rate": 0.5, "n_arrivals": 64, "max_backlog": 16, "scale": 0.4},
}


def _mt_traffic(n_nodes: int):
    from repro.sim import TenantSpec, TrafficConfig

    c = MT_CONFIGS[n_nodes]
    s = c["scale"]
    return TrafficConfig(
        tenants=(
            TenantSpec("batch", weight=2.0, workflows=("group", "fork"),
                       scale=s, slo=600.0),
            TenantSpec("ml", weight=1.0, workflows=("mlpipe_mamba",),
                       scale=s, slo=900.0),
            TenantSpec("svc", weight=1.0, workflows=("chain",),
                       scale=s / 2, slo=300.0),
        ),
        rate=c["rate"], n_arrivals=c["n_arrivals"],
        max_backlog=c["max_backlog"], window=60.0, seed=n_nodes)


def run_multi_tenant(sizes: list[int] | None = None,
                     ) -> tuple[list[dict], dict]:
    """orig/cws/wow under identical seeded arrival streams; returns
    (rows, headline) with events/sec, p99 completion latency and fairness
    per (strategy, size)."""
    from repro.sim import run_traffic

    if sizes is None:
        sizes = MT_SMOKE_SIZES if bench_smoke() else MT_SIZES
    rows: list[dict] = []
    per_size: dict[int, dict[str, dict]] = {}
    emit("scheduler_scale,multi_tenant,strategy,nodes,admitted,rejected,"
         "completed,p50,p99,slo_attainment,jain,gini,events_per_s")
    for n_nodes in sizes:
        traffic = _mt_traffic(n_nodes)
        per_size[n_nodes] = {}
        for strat in ("orig", "cws", "wow"):
            t0 = time.perf_counter()
            sres, tres = run_traffic(traffic, strategy=strat,
                                     n_nodes=n_nodes, dfs="ceph")
            wall = time.perf_counter() - t0
            assert tres.completed > 0, (
                f"multi_tenant {strat}@{n_nodes}: nothing completed")
            row = {
                "impl": strat, "scenario": "multi_tenant", "nodes": n_nodes,
                "wall_s": wall, "events": sres.sim_steps,
                "events_per_s": sres.sim_steps / max(wall, 1e-9),
                "arrivals": tres.arrivals, "admitted": tres.admitted,
                "rejected": tres.rejected, "completed": tres.completed,
                "p50": tres.latency_p50, "p99": tres.latency_p99,
                "slo_attainment": tres.slo_attainment,
                "slo_violations": tres.slo_violations,
                "starved": tres.starved,
                "fairness_jain": tres.fairness_jain,
                "fairness_gini": tres.fairness_gini,
                "queue_depth_max": tres.queue_depth_max,
                "queue_depth_mean": tres.queue_depth_mean,
                "horizon": tres.horizon,
                "per_tenant": {t: {k: d[k] for k in
                                   ("admitted", "rejected", "completed",
                                    "p99", "starved", "service_cpu_s")}
                               for t, d in tres.per_tenant.items()},
            }
            rows.append(row)
            per_size[n_nodes][strat] = row
            emit(f"scheduler_scale,multi_tenant,{strat},{n_nodes},"
                 f"{tres.admitted},{tres.rejected},{tres.completed},"
                 f"{tres.latency_p50:.1f},{tres.latency_p99:.1f},"
                 f"{tres.slo_attainment if tres.slo_attainment is None else round(tres.slo_attainment, 3)},"
                 f"{tres.fairness_jain:.3f},{tres.fairness_gini:.3f},"
                 f"{sres.sim_steps / max(wall, 1e-9):.0f}")
    # the saturated operating point: the largest size run.  The gate binds
    # there (orig saturates its backlog), and WOW must not trade fairness
    # for its throughput: p99 no worse than the original scheduler's.
    head_nodes = max(per_size)
    sat = per_size[head_nodes]
    assert sat["orig"]["rejected"] > 0, (
        "multi_tenant: admission gate never bound under orig -- "
        "not a saturated operating point")
    assert sat["wow"]["p99"] <= sat["orig"]["p99"], (
        f"multi_tenant@{head_nodes}: wow p99 {sat['wow']['p99']:.1f} worse "
        f"than orig {sat['orig']['p99']:.1f}")
    headline = {
        "sizes": sizes,
        "per_size": {str(n): {s: {k: r[k] for k in
                                  ("p50", "p99", "slo_attainment",
                                   "fairness_jain", "fairness_gini",
                                   "admitted", "rejected", "completed",
                                   "events_per_s")}
                              for s, r in by.items()}
                     for n, by in per_size.items()},
        "saturated_nodes": head_nodes,
        "p99_orig": sat["orig"]["p99"],
        "p99_wow": sat["wow"]["p99"],
        "wow_p99_vs_orig": sat["wow"]["p99"] / max(sat["orig"]["p99"], 1e-9),
        "admitted_orig": sat["orig"]["admitted"],
        "admitted_wow": sat["wow"]["admitted"],
    }
    return rows, headline


# --------------------------------------------- live RM (declined backlogs)
LIVE_RM_SMOKE = {"bursts": 3, "storms": 4}


def _drift_node(sched: WowScheduler, node: int, cores: float) -> None:
    """Bench-driver capacity nudge: overwrite one node's free cores the way
    a co-tenant RM would, through the scheduler's sanctioned dirty path."""
    state = sched.nodes[node]
    state.free_cores = cores
    if sched._cap_array is not None:
        sched._cap_array.refresh_from(node, state)
    sched._dirty_nodes.add(node)


def _reset_cluster(sched: WowScheduler) -> None:
    """The RM recovers between bursts: the next burst arrives on an idle
    cluster, making burst-start state exactly identical across modes."""
    for n, state in sched.nodes.items():
        state.free_mem = state.mem
        state.free_cores = state.cores
        if sched._cap_array is not None:
            sched._cap_array.refresh_from(n, state)
        sched._dirty_nodes.add(n)


@_traced()
def run_live_rm(n_nodes: int = 12, bursts: int = 5,
                storms: int = 6, hot_pool: int = 8, seed: int = 0) -> dict:
    """Measure the ``strict_parity=False`` B&B warm start on *real* bursty
    decline backlogs, through the full scheduler + adapter boundary
    (``core/adapter.py``) -- the regime the CWS-style runtime exists for.

    Each burst submits ``2 * hot_pool`` data-bound tasks whose inputs are
    replicated in a staircase over the first ``hot_pool`` nodes (task pair
    ``j`` can run on nodes ``j`` and ``j+1 mod hot_pool`` -- a pipeline
    locality pattern).  The staircase welds one ring component inside the
    exact gate where a perfect assignment always exists (every node fits
    its two primary tasks) but the priority-ordered B&B has to *search*
    for one -- while the warm run's incumbent, rebuilt from the dissolved
    previous assignment, already attains the all-assigned upper bound and
    closes the search immediately.  That asymmetry is exactly what
    incumbent seeding buys on a decline-heavy runtime.  A
    throttled RM then declines *every* placement for ``storms`` scheduling
    rounds -- each decline reverts the reservation and requeues the task
    per the decline contract, and one node's free cores drift per round so
    the component fingerprint misses the cache and the B&B really re-runs.
    After the storm the RM recovers: placements are acked and completed
    out-of-order until the backlog drains, then the cluster idles before
    the next burst.

    ``c_node=0`` keeps COPs (and thus DPS randomness) out of the loop, so
    the storm-round instances are identical between the strict and warm
    runs and their objectives are directly comparable.  Reported:
    solver ms per storm event for both modes, re-solve counters, the
    warm-seed count, and ``objective_safe`` (warm never worse; equal
    whenever the B&B stays inside its node budget)."""
    results: dict = {}
    objectives: dict[str, list[float]] = {}
    storm_events = bursts * storms
    burst = 2 * hot_pool
    for mode, strict in (("cold", True), ("warm", False)):
        rng = random.Random(seed)
        nodes = {i: NodeState(i, 128 * GiB, 16.0) for i in range(n_nodes)}
        dps = DataPlacementService(seed=seed)
        sched = WowScheduler(nodes, dps, c_node=0, strict_parity=strict)
        specs: dict[int, TaskSpec] = {}
        objs: list[float] = []
        declines = 0
        backlog_max = 0
        solver_s = 0.0
        sched_s = 0.0
        next_tid = 0
        for b in range(bursts):
            for j in range(burst):
                tid = next_tid
                next_tid += 1
                f = FileSpec(id=tid, size=1 << 20, producer=-1)
                locs = sorted({j // 2, (j // 2 + 1) % hot_pool})
                dps.register_file(f, locs[0])
                for n in locs[1:]:
                    dps.add_replica(f.id, n)
                t = TaskSpec(id=tid, abstract="burst", mem=TASK_MEM,
                             cores=TASK_CORES, inputs=(tid,),
                             priority=rng.uniform(1, 10))
                specs[tid] = t
                sched.submit(t)
            for s_i in range(storms):
                ev = b * storms + s_i
                _drift_node(sched, ev % hot_pool, 16.0 - 1e-9 * (ev + 1))
                s0 = _span_seconds("sched.step1.solve")
                t0 = time.perf_counter()
                actions = sched.schedule()
                sched_s += time.perf_counter() - t0
                solver_s += _span_seconds("sched.step1.solve") - s0
                starts = [a for a in actions if isinstance(a, StartTask)]
                objs.append(sum(specs[a.task_id].priority for a in starts))
                backlog_max = max(backlog_max,
                                  len(starts) + len(sched.ready))
                # the throttled RM nacks everything: decline-requeue path
                for a in starts:
                    sched.decline(a.task_id, a.node, "rm_throttled")
                    declines += 1
            # RM recovers: ack placements, complete out-of-order, drain
            stalls = 0
            while sched.ready:
                starts = [a for a in sched.schedule()
                          if isinstance(a, StartTask)]
                if not starts:
                    stalls += 1
                    assert stalls < 3, "live_rm drain stalled"
                    continue
                for a in starts:
                    sched.task_started(a.task_id, a.node)
                for a in reversed(starts):
                    sched.task_finished(a.task_id, a.node)
            _reset_cluster(sched)
        stats = sched.solver_stats
        results[f"{mode}_solver_ms_per_event"] = (
            solver_s * 1000 / storm_events)
        results[f"{mode}_sched_ms_per_event"] = (
            sched_s * 1000 / storm_events)
        results[f"{mode}_resolves"] = {
            k: int(stats[k]) for k in ("events", "comps_rebuilt",
                                       "exact_solves", "cache_hits",
                                       "cache_misses")}
        objectives[mode] = objs
        if not strict:
            results["warm_seeds"] = int(stats["warm_seeds"])
            results["declines"] = declines
            results["backlog_max"] = backlog_max
    # objective safety: seeding may only match or improve the objective
    # (it matches exactly whenever the B&B stays inside its node budget)
    assert all(w >= c - 1e-9 for c, w in zip(objectives["cold"],
                                             objectives["warm"])), (
        "warm start regressed the step-1 objective")
    results["objective_safe"] = True
    results["storm_events"] = storm_events
    results["warm_vs_cold"] = (
        results["warm_solver_ms_per_event"]
        / max(results["cold_solver_ms_per_event"], 1e-9))
    return results


def _summarize(action_list):
    from repro.core import StartCop, StartTask
    out = []
    for a in action_list:
        if isinstance(a, StartTask):
            out.append(("task", a.task_id, a.node))
        elif isinstance(a, StartCop):
            out.append(("cop", a.plan.task_id, a.plan.target))
    return out


def sanity_check_equivalence(n_nodes: int = 32, n_ready: int = 256,
                             sustained_iters: int = 8,
                             inputless: bool = False) -> None:
    """Cheap guard: both implementations must make identical decisions on
    the benchmark workload, cold *and* across a stream of dirty events (the
    full proof lives in the test suite)."""
    s_new, dps_new, rng_new = build(n_nodes, n_ready, WowScheduler,
                                    inputless=inputless)
    s_ref, dps_ref, rng_ref = build(n_nodes, n_ready, ReferenceWowScheduler,
                                    inputless=inputless)
    a_new = _summarize(s_new.schedule())
    a_ref = _summarize(s_ref.schedule())
    assert a_new == a_ref, "incremental scheduler diverged from reference"
    next_id = n_ready
    for _ in range(sustained_iters):
        a_new = _summarize(drive_event(s_new, dps_new, rng_new,
                                       n_nodes, next_id,
                                       inputless=inputless))
        a_ref = _summarize(drive_event(s_ref, dps_ref, rng_ref,
                                       n_nodes, next_id,
                                       inputless=inputless))
        assert a_new == a_ref, ("incremental scheduler diverged from "
                                "reference under sustained events")
        next_id += 1


def main() -> list[dict]:
    sanity_check_equivalence()
    sanity_check_equivalence(inputless=True)
    rows = []
    emit("scheduler_scale,impl,n_nodes,n_ready_tasks,cold_ms,cold_solver_ms,"
         "sustained_ms_per_iter,solver_ms_per_iter,step23_ms_per_iter,"
         "actions_per_iter")
    impls = {"indexed": WowScheduler, "reference": ReferenceWowScheduler}
    headline_stats = None
    for n_nodes, n_ready in SIZES:
        # keep the slow reference affordable at the largest scales
        iters = {8: 50, 32: 50, 128: 20, 512: 10, 1024: 6}[n_nodes]
        for name, cls in impls.items():
            cold_ms, cold_solver_ms, _cold_actions = run_cold(
                n_nodes, n_ready, cls)
            sus = run_sustained(n_nodes, n_ready, cls, iters)
            if name == "indexed" and (n_nodes, n_ready) == HEADLINE:
                headline_stats = sus["stats"]
            rows.append({"impl": name, "nodes": n_nodes, "tasks": n_ready,
                         "cold_ms": cold_ms,
                         "cold_solver_ms": cold_solver_ms,
                         "sustained_ms": sus["ms"],
                         "solver_ms_per_iter": sus["solver_ms"],
                         "step23_ms_per_iter": sus["step23_ms"],
                         "iters": iters, "actions_per_iter": sus["actions"]})
            emit(f"scheduler_scale,{name},{n_nodes},{n_ready},"
                 f"{cold_ms:.1f},{cold_solver_ms:.2f},{sus['ms']:.2f},"
                 f"{sus['solver_ms']:.3f},{sus['step23_ms']:.3f},"
                 f"{sus['actions']:.1f}")
    by_key = {(r["impl"], r["nodes"], r["tasks"]): r for r in rows}
    ref = by_key[("reference", *HEADLINE)]
    new = by_key[("indexed", *HEADLINE)]
    speedup = ref["sustained_ms"] / max(new["sustained_ms"], 1e-9)
    solver_speedup = (ref["solver_ms_per_iter"]
                      / max(new["solver_ms_per_iter"], 1e-9))
    step23_speedup = (ref["step23_ms_per_iter"]
                      / max(new["step23_ms_per_iter"], 1e-9))
    emit(f"scheduler_scale,sustained_speedup_{HEADLINE[0]}n,"
         f"{speedup:.1f}x")
    emit(f"scheduler_scale,solver_speedup_{HEADLINE[0]}n,"
         f"{solver_speedup:.1f}x")
    emit(f"scheduler_scale,step23_speedup_{HEADLINE[0]}n,"
         f"{step23_speedup:.1f}x")

    # fan-out phase: input-less backlog through the capacity-only path
    less_iters = {"indexed": 6, "reference": 4}
    less: dict[str, dict] = {}
    for name, cls in impls.items():
        less[name] = run_inputless(*HEADLINE, cls, less_iters[name])
        rows.append({"impl": name, "nodes": HEADLINE[0], "tasks": HEADLINE[1],
                     "scenario": "inputless",
                     "sustained_ms": less[name]["ms"],
                     "solver_ms_per_iter": less[name]["solver_ms"],
                     "step23_ms_per_iter": less[name]["step23_ms"],
                     "iters": less_iters[name],
                     "actions_per_iter": less[name]["actions"]})
        emit(f"scheduler_scale,inputless_{name},{HEADLINE[0]},{HEADLINE[1]},"
             f",,{less[name]['ms']:.2f},{less[name]['solver_ms']:.3f},"
             f"{less[name]['step23_ms']:.3f},{less[name]['actions']:.1f}")
    inputless_speedup = (less["reference"]["ms"]
                         / max(less["indexed"]["ms"], 1e-9))
    emit(f"scheduler_scale,inputless_speedup_{HEADLINE[0]}n,"
         f"{inputless_speedup:.1f}x")

    # end-to-end simulation throughput: heap fill vs the pre-heap engine
    sim_rows, sim_head = run_sim_throughput()
    rows.extend(sim_rows)

    # sampled-recompute timing at extreme scale (scheduler vs reference)
    rec_rows, rec_head = run_sampled_recompute()
    rows.extend(rec_rows)

    # open-loop multi-tenant traffic: identical arrival streams, three
    # strategies, SLO/fairness service metrics
    mt_rows, mt_head = run_multi_tenant()
    rows.extend(mt_rows)

    # hierarchical topology: flat vs rack vs multi-site, oversubscription
    # sweep, heap-vs-scan fill on path-constrained flows
    topo_rows, topo_head = run_topology()
    rows.extend(topo_rows)

    # warm start on real bursty decline backlogs (full scheduler + adapter)
    live = run_live_rm(**(LIVE_RM_SMOKE if bench_smoke() else {}))
    rows.append({"impl": "wow-scheduler", "scenario": "live_rm",
                 **{k: v for k, v in live.items()}})
    emit(f"scheduler_scale,live_rm,cold_ms,"
         f"{live['cold_solver_ms_per_event']:.3f},warm_ms,"
         f"{live['warm_solver_ms_per_event']:.3f},warm_seeds,"
         f"{live['warm_seeds']},declines,{live['declines']}")

    # node churn on Ceph rep=2: degraded reads + re-replication traffic
    churn = run_dfs_churn()
    for strat, c in churn.items():
        rows.append({"impl": strat, "scenario": "dfs_churn", **c})
        emit(f"scheduler_scale,dfs_churn,{strat},makespan,"
             f"{c['makespan']:.1f},degraded_read_bytes,"
             f"{c['degraded_read_bytes']:.0f},rereplication_bytes,"
             f"{c['rereplication_bytes']:.0f},repairs,"
             f"{c['repairs_completed']}")

    write_json("scheduler_scale", {
        "rows": rows,
        "headline": {"nodes": HEADLINE[0], "tasks": HEADLINE[1],
                     "sustained_ms_reference": ref["sustained_ms"],
                     "sustained_ms_indexed": new["sustained_ms"],
                     "sustained_speedup": speedup,
                     "sustained_solver_ms_reference": ref["solver_ms_per_iter"],
                     "sustained_solver_ms_indexed": new["solver_ms_per_iter"],
                     "solver_speedup": solver_speedup,
                     "step23_ms_reference": ref["step23_ms_per_iter"],
                     "step23_ms_indexed": new["step23_ms_per_iter"],
                     "step23_speedup": step23_speedup,
                     "inputless_ms_per_iter_reference": less["reference"]["ms"],
                     "inputless_ms_per_iter_indexed": less["indexed"]["ms"],
                     "inputless_speedup": inputless_speedup,
                     "inputless_stats": less["indexed"]["inputless_stats"],
                     "sim_throughput": sim_head,
                     "sampled_recompute": rec_head,
                     "multi_tenant": mt_head,
                     "topology": topo_head,
                     "live_rm": live,
                     "dfs_churn": churn,
                     "solver_stats": headline_stats},
    })
    return rows


if __name__ == "__main__":
    main()
