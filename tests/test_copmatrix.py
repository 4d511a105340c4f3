"""Batched COP drain (core/copmatrix.py): mirror + bit-parity test campaign.

Layers of proof that the blocked drain makes the decisions of the per-task
drain it replaced:

* **matrix mirror property test** -- a randomized DPS mutation stream
  (register/replica add+remove/track/untrack/node drop/invalidate/gc);
  after every event the ``CopMatrix`` must equal the dict indices
  cell-for-cell (``check_against``), including column recycling after
  ``drop_node``.
* **kernel unit surface** -- null-column gathers read 0 like
  ``dict.get(node, 0)``; untracked tasks return the fallback sentinels;
  the winner and the emptiness witness match brute-force walks;
  ``SlotColMap`` rebuilds exactly when a version counter moves.
* **full-sim bit-identity** -- actions (``sim.action_log``), makespans and
  event counts identical to the frozen reference core
  (``SimConfig(reference_core=True)``) across workloads, with churn
  (failure + elastic join), under a hierarchical topology, through the
  sentinel fallthroughs and a constrained pool; plus a randomized
  property sweep.
"""
from __future__ import annotations

import random

import pytest

from repro.core import (DataPlacementService, FileSpec, NodeState, StartCop,
                        TaskSpec, WowScheduler)

from _hyp import given, settings, st

GiB = 1024 ** 3
MB = 1024 ** 2


# ------------------------------------------------------ matrix mirror property
def _random_dps_stream(seed: int, n_events: int = 120):
    """Drive a DPS + enabled matrix through a random mutation stream,
    checking the full mirror invariant after every event."""
    rng = random.Random(seed)
    dps = DataPlacementService(seed=seed)
    mx = dps.enable_matrix()
    nodes = list(range(8))
    files: list[int] = []
    tracked: list[int] = []
    next_f, next_t = 0, 0
    for _ in range(n_events):
        op = rng.randrange(8)
        if op == 0 or not files:                      # new file
            fid = next_f
            next_f += 1
            dps.register_file(FileSpec(fid, rng.randrange(1, 64) * MB, 0),
                              rng.choice(nodes))
            files.append(fid)
        elif op == 1:                                 # replica add
            dps.add_replica(rng.choice(files), rng.choice(nodes))
        elif op == 2:                                 # replica remove
            fid = rng.choice(files)
            locs = dps.locations(fid)
            if locs:
                dps.remove_replica(fid, rng.choice(sorted(locs)))
        elif op == 3 or not tracked:                  # track a task
            tid = next_t
            next_t += 1
            k = rng.randrange(1, 5)
            inputs = tuple(rng.choice(files) for _ in range(k))
            dps.track_task(tid, inputs)
            tracked.append(tid)
        elif op == 4:                                 # untrack
            dps.untrack_task(tracked.pop(rng.randrange(len(tracked))))
        elif op == 5:                                 # node leaves
            dps.drop_node(rng.choice(nodes))
        elif op == 6:                                 # invalidate to one holder
            fid = rng.choice(files)
            locs = dps.locations(fid)
            if locs:
                dps.invalidate(fid, sorted(locs)[0])
        else:                                         # replica GC
            dps.delete_replicas(rng.choice(files), keep=1)
        mx.check_against(dps)
    return dps, mx


@settings(max_examples=15)
@given(st.integers(0, 10 ** 6))
def test_matrix_mirrors_dps_indices(seed):
    _random_dps_stream(seed)


def test_matrix_rebuild_equals_incremental():
    """enable_matrix() on an already-populated DPS == the incrementally
    maintained state (rebuild is the from-scratch oracle)."""
    dps, mx = _random_dps_stream(99, n_events=60)
    snap = {tid: mx.snapshot(tid) for tid in mx._row_of}
    mx.rebuild(dps)
    mx.check_against(dps)
    assert snap == {tid: mx.snapshot(tid) for tid in mx._row_of}


def test_matrix_column_recycled_after_drop():
    dps = DataPlacementService(seed=0)
    mx = dps.enable_matrix()
    dps.register_file(FileSpec(1, 10 * MB, 0), 3)
    dps.track_task(1, (1,))
    col = mx.col_of(3)
    assert col > 0
    dps.drop_node(3)
    assert mx.col_of(3) == 0                   # back to the null column
    dps.register_file(FileSpec(2, 5 * MB, 0), 4)
    dps.track_task(2, (2,))
    assert mx.col_of(4) == col                 # freed column recycled
    mx.check_against(dps)


def test_null_column_reads_zero():
    dps = DataPlacementService(seed=0)
    mx = dps.enable_matrix()
    dps.register_file(FileSpec(1, 10 * MB, 0), 0)
    dps.track_task(7, (1,))
    row = mx.row_of(7)
    # node 5 holds nothing -> no column -> gather through col 0 reads 0,
    # exactly dict.get(5, 0)
    assert mx.col_of(5) == 0
    assert int(mx.cnt[row, mx.col_of(5)]) == 0
    assert int(mx.pbytes[row, mx.col_of(5)]) == 0


# --------------------------------------------------------- kernel unit surface
def _mini_sched(n_nodes=4):
    nodes = {i: NodeState(i, 8 * GiB, 8.0) for i in range(n_nodes)}
    dps = DataPlacementService(seed=0)
    sched = WowScheduler(nodes, dps)
    return sched, dps, nodes


def test_batched_defaults_on_with_vectorized():
    """Every scheduler builds the capacity array, the DPS matrix and the
    blocked kernel over them: there is no other drain."""
    from repro.core.copmatrix import BlockedDrainKernel
    from repro.core.nodearray import NodeCapacityArray
    sched, dps, _ = _mini_sched()
    assert isinstance(sched._cap_array, NodeCapacityArray)
    assert isinstance(sched._kernel, BlockedDrainKernel)
    assert sched._kernel.cap is sched._cap_array
    assert sched._kernel.mx is dps.matrix


def test_untracked_task_returns_fallback_sentinels():
    sched, dps, _ = _mini_sched()
    kern = sched._kernel
    kern.begin()
    t = TaskSpec(id=9, abstract="a", mem=GiB, cores=1.0, inputs=(1,),
                 priority=1.0)
    assert kern.step2_winner(9, t, dps) == -1
    assert kern.step3_candidates(9, t) is None


def test_step2_winner_matches_oracle_sort():
    """Winner == first element of the oracle's (missing, node) sort, on a
    mixed present-bytes instance (some candidates hold bytes, some none)."""
    sched, dps, nodes = _mini_sched(n_nodes=5)
    dps.register_file(FileSpec(1, 100 * MB, 0), 0)
    dps.register_file(FileSpec(2, 50 * MB, 0), 1)
    dps.add_replica(2, 2)
    sched.submit(TaskSpec(id=1, abstract="a", mem=GiB, cores=1.0,
                          inputs=(1, 2), priority=1.0))
    kern = sched._kernel
    kern.begin()
    t = TaskSpec(id=1, abstract="a", mem=GiB, cores=1.0, inputs=(1, 2),
                 priority=1.0)
    tb = dps.task_input_bytes(1)
    present = dps.present_bytes_map(1)
    oracle = sorted((n for n in nodes), key=lambda n: (tb - present.get(n, 0),
                                                       n))
    assert kern.step2_winner(1, t, dps) == oracle[0]
    # and step-3 candidates come back in canonical order
    assert kern.step3_candidates(1, t) == sorted(nodes)


def test_step2_winner_matches_locality_sort():
    """Under a rack topology the winner is the first element of the
    ``(dps.locality_missing_cost, node)`` sort over the candidates: the
    kernel's cost row, built file by file, against the per-node cost."""
    from repro.sim import Topology, TopologySpec
    rng = random.Random(5)
    for _ in range(10):
        sched, dps, nodes = _mini_sched(n_nodes=8)
        dps.set_topology(Topology(TopologySpec(rack_size=2,
                                               racks_per_site=2), 8, 1e9))
        for fid in range(1, 4):
            hosts = rng.sample(range(8), rng.randint(1, 3))
            dps.register_file(FileSpec(fid, rng.randint(1, 90) * MB, 0),
                              hosts[0])
            for h in hosts[1:]:
                dps.add_replica(fid, h)
        t = TaskSpec(id=1, abstract="a", mem=GiB, cores=1.0,
                     inputs=(1, 2, 3), priority=1.0)
        sched.submit(t)
        kern = sched._kernel
        kern.begin()
        prepped = dps.prepared_node_set(1)
        cands = [n for n in nodes if n not in prepped]
        want = min(cands, key=lambda n: (dps.locality_missing_cost(1, n), n))
        assert kern.step2_winner(1, t, dps) == want


@settings(max_examples=20)
@given(st.integers(0, 10 ** 6))
def test_free_slot_fit_node_matches_bruteforce(seed):
    """The step-2 emptiness witness: some free-COP-slot node whose free
    resources fit the shape exactly when a walk over the nodes finds one,
    and the node it names is such a node."""
    rng = random.Random(seed)
    nodes = {i: NodeState(i, 8 * GiB, 8.0,
                          free_mem=rng.randrange(0, 9) * GiB,
                          free_cores=float(rng.randrange(0, 9)),
                          active_cops=rng.randrange(0, 3))
             for i in range(rng.randrange(1, 12))}
    sched = WowScheduler(nodes, DataPlacementService(seed=0), c_node=2)
    kern = sched._kernel
    kern.begin()
    for _ in range(10):
        mem, cores = rng.randrange(0, 9) * GiB, float(rng.randrange(0, 9))
        fitting = [n for n, s in nodes.items()
                   if s.active_cops < 2 and s.fits(TaskSpec(
                       id=0, abstract="a", mem=mem, cores=cores))]
        w = kern.free_slot_fit_node(mem, cores)
        if fitting:
            assert w in fitting
        else:
            assert w is None


def test_slotcolmap_rebuilds_only_on_version_change():
    from repro.core.copmatrix import SlotColMap
    sched, dps, _ = _mini_sched()
    mx = dps.matrix
    cap = sched._cap_array
    sm = SlotColMap(cap, mx)
    v1 = sm.refresh()
    assert sm.refresh() is v1                     # cached: versions static
    dps.register_file(FileSpec(1, MB, 0), 2)
    dps.track_task(1, (1,))                       # new column -> col_version
    v2 = sm.refresh()
    assert v2 is not v1
    assert int(v2[cap.slot_of[2]]) == mx.col_of(2) > 0
    cap.add(99, NodeState(99, GiB, 1.0))          # new slot -> cap.version
    v3 = sm.refresh()
    assert v3 is not v2 and len(v3) >= len(v2)


# ------------------------------------------------------- full-sim bit-identity
def _sim_run(reference_core, *, workflow="group", scale=0.6, n_nodes=14,
             seed=0, churn=False, topology=None, dfs="ceph", patch=None):
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    wf = make_workflow(workflow, scale=scale, seed=seed)
    sim = Simulation(wf, SimConfig(n_nodes=n_nodes, dfs=dfs, seed=seed,
                                   reference_core=reference_core,
                                   topology=topology),
                     "wow")
    if patch is not None:
        patch(sim.strategy.sched)
    if churn:
        sim.schedule_failure(15.0, 3)
        sim.schedule_join(30.0, n_nodes)
    r = sim.run()
    return sim.action_log, r.makespan, r.sim_steps, r.cops_created


@pytest.mark.parametrize("workflow", ["group", "fork", "syn_montage",
                                      "chipseq"])
@pytest.mark.parametrize("churn", [False, True])
def test_full_sim_bit_identity(workflow, churn):
    a = _sim_run(True, workflow=workflow, churn=churn)
    b = _sim_run(False, workflow=workflow, churn=churn)
    assert a == b


def test_full_sim_bit_identity_topology():
    from repro.sim import TopologySpec
    topo = TopologySpec(rack_size=4, racks_per_site=2)
    for churn in (False, True):
        a = _sim_run(True, topology=topo, churn=churn)
        b = _sim_run(False, topology=topo, churn=churn)
        assert a == b


def test_blocked_matches_reference_core():
    """Blocked drain vs the frozen reference scheduler (transitively: the
    kernel changes no decision the original per-task code made)."""
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    logs = {}
    for ref in (False, True):
        wf = make_workflow("group", scale=0.4)
        sim = Simulation(wf, SimConfig(n_nodes=10, reference_core=ref), "wow")
        r = sim.run()
        logs[ref] = (sim.action_log, r.makespan)
    assert logs[False] == logs[True]


def _answer_sentinel(sched, query, sentinel, calls):
    """Make the kernel's ``query`` answer ``sentinel`` (its "no matrix
    row" answer) every time, counting the calls."""
    def answer(*args):
        calls[query] += 1
        return sentinel
    setattr(sched._kernel, query, answer)


@pytest.mark.parametrize("query,sentinel",
                         [("step2_winner", -1), ("step3_candidates", None)],
                         ids=["step2", "step3"])
def test_sentinel_falls_through_to_pool_filter(query, sentinel):
    """A kernel answer of "no matrix row" (-1 in step 2, None in step 3)
    hands the task to the per-task pool filter; with every answer forced to
    the sentinel, whole runs on the 8-node testbed (with churn, flat and in
    racks) still make the reference's decisions."""
    from repro.sim import TopologySpec
    calls = {query: 0}
    for topo in (None, TopologySpec(rack_size=4, racks_per_site=2)):
        kw = dict(workflow="sarek", scale=0.3, n_nodes=8, churn=True,
                  topology=topo)
        want = _sim_run(True, **kw)
        got = _sim_run(False, patch=lambda s: _answer_sentinel(
            s, query, sentinel, calls), **kw)
        assert got == want
    assert calls[query] > 0


def test_constrained_step2_pool_matches_reference():
    """Task 2's only input sits on node 1 alone.  Task 1's step-2 COP takes
    node 1's slot as its source, so when step 2 visits task 2 in the same
    pass its input has no free-slot source: the pool is constrained to the
    holders (``cop_feasible_targets``), the kernel is bypassed and the
    per-task probe finds no candidate -- exactly as the reference does."""
    from repro.core import ReferenceWowScheduler

    def build(cls):
        nodes = {i: NodeState(i, 8 * GiB, 8.0,
                              free_cores=0.0 if i == 1 else 8.0)
                 for i in range(4)}
        dps = DataPlacementService(seed=0)
        dps.register_file(FileSpec(1, 10 * MB, 0), 1)
        dps.register_file(FileSpec(2, 20 * MB, 0), 1)
        sched = cls(nodes, dps)
        sched.submit(TaskSpec(id=1, abstract="a", mem=GiB, cores=1.0,
                              inputs=(1,), priority=2.0))
        sched.submit(TaskSpec(id=2, abstract="a", mem=GiB, cores=1.0,
                              inputs=(2,), priority=1.0))
        sched._step3_speculative_prepare = lambda actions: None
        return sched

    sched = build(WowScheduler)
    pools = []
    probe = sched._step2_probe_task

    def spy(tid, t, feas, pool, actions):
        pools.append((tid, feas, set(pool)))
        return probe(tid, t, feas, pool, actions)
    sched._step2_probe_task = spy
    got = _plans(sched.schedule())
    want = _plans(build(ReferenceWowScheduler).schedule())
    assert pools == [(2, {1}, set())]
    assert got == want
    assert [(task, target) for _, task, target, _ in got] == [(1, 0)]


def _plans(actions):
    return [(a.plan.id, a.plan.task_id, a.plan.target, a.plan.transfers)
            for a in actions if isinstance(a, StartCop)]


@pytest.mark.parametrize("racks", [False, True], ids=["blocked", "rack"])
@pytest.mark.parametrize("workflow,scale", [("sarek", 0.3),
                                            ("rangeland", 0.1)])
def test_pretest_matches_reference_core(workflow, scale, racks):
    """The drain's emptiness pre-test against the frozen reference, on the
    paper's 8-node testbed with one COP per node, where scarce slots and
    cores leave most visited tasks without a candidate: the skips must be
    real in both steps and change no decision -- flat, and in racks of
    four, where the kernel's locality cost row picks the step-2 winners."""
    from repro.sim import SimConfig, Simulation, TopologySpec
    from repro.workloads import make_workflow

    topo = TopologySpec(rack_size=4) if racks else None
    runs = {}
    skipped = {2: 0, 3: 0}
    for ref in (False, True):
        cfg = SimConfig(n_nodes=8, c_node=1, reference_core=ref,
                        topology=topo)
        sim = Simulation(make_workflow(workflow, scale=scale), cfg, "wow")
        if not ref:
            sched = sim.strategy.sched

            def counted(step, run_step):
                def run(*args):
                    before = sched.drain_skipped
                    run_step(*args)
                    skipped[step] += sched.drain_skipped - before
                return run
            sched._step2_prepare_for_free_compute = counted(
                2, sched._step2_prepare_for_free_compute)
            sched._step3_speculative_prepare = counted(
                3, sched._step3_speculative_prepare)
        r = sim.run()
        runs[ref] = (sim.action_log, r.makespan)
    assert runs[False] == runs[True]
    assert skipped[2] > 0 and skipped[3] > 0, skipped


@settings(max_examples=8, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_blocked_parity_property(seed):
    """Randomized workloads x cluster sizes x churn x topology: the blocked
    drain and the frozen reference must agree action-for-action."""
    from repro.sim import TopologySpec

    rng = random.Random(seed)
    workflow = rng.choice(["group", "fork", "chain", "syn_blast",
                           "syn_montage", "rnaseq"])
    n_nodes = rng.choice([6, 10, 16])
    scale = rng.choice([0.3, 0.5, 0.8])
    churn = rng.random() < 0.5
    topo = TopologySpec(rack_size=rng.choice([2, 4]),
                        racks_per_site=rng.choice([0, 2])) \
        if rng.random() < 0.5 else None
    kw = dict(workflow=workflow, scale=scale, n_nodes=n_nodes,
              seed=seed % 1000, churn=churn, topology=topo)
    assert _sim_run(True, **kw) == _sim_run(False, **kw)
