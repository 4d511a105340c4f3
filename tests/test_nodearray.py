"""Vectorized hot node state (core/nodearray.py): parity + property tests.

Four layers of proof that the array node state makes the decisions a walk
over the ``NodeState`` dict makes:

* ``NodeCapacityArray`` property test -- a randomized add/drop/rejoin/
  mutate stream; after every event the array must equal a from-scratch
  rebuild of the reference dict state, keep canonical (NodeOrder) slot
  order, and answer every query bit-identically to brute force.
* compaction test -- mass drops push the array through ``_compact`` while
  the same invariants hold.
* scheduler-stream property test -- a full simulation with node failure +
  elastic join; after *every* ``schedule()`` the array mirrors the live
  ``NodeState`` dict exactly.
* full-sim bit-identity -- actions (``sim.action_log``), makespans and
  event counts are identical to the frozen reference core
  (``SimConfig(reference_core=True)``) across workflows, with and without
  churn; plus truncation parity: a multi-shape input-less component past
  the exact gate is solved via ``_truncate_component`` yet matches the
  untruncated ``ilp.solve`` and the reference's decisions.
"""
from __future__ import annotations

import copy
import random

import pytest

from repro.core import (DataPlacementService, NodeOrder, NodeState,
                        ReferenceWowScheduler, StartTask, TaskSpec,
                        WowScheduler)
from repro.core.ilp import AssignmentProblem, solve as ilp_solve

from _hyp import given, settings, st

GiB = 1024 ** 3
C_NODE = 2


def _mirror(nodes: dict[int, NodeState]) -> dict:
    return {n: (s.free_mem, s.free_cores, s.active_cops)
            for n, s in nodes.items()}


def _check_queries(cap, nodes, order, rng) -> None:
    """One random probe shape: every query surface vs brute force over the
    canonical enumeration."""
    mem = rng.randrange(0, 9) * GiB
    cores = rng.uniform(0.0, 17.0)
    brute = [n for n in order
             if nodes[n].free_mem >= mem and nodes[n].free_cores >= cores]
    assert cap.fitting(mem, cores) == brute
    assert cap.free_slot_fit_ids(mem, cores) == [
        n for n in brute if nodes[n].active_cops < C_NODE]
    slots = cap.slots_of(brute)
    assert [int(cap._node_of[s]) for s in slots] == brute
    sub = [n for n in order if rng.random() < 0.5]
    assert cap.filter_fitting(sub, mem, cores) == [
        n for n in sub
        if nodes[n].free_mem >= mem and nodes[n].free_cores >= cores]


@settings(max_examples=10)
@given(st.integers(0, 10 ** 9))
def test_nodearray_random_stream(seed):
    from repro.core import NodeCapacityArray

    rng = random.Random(seed)
    nodes: dict[int, NodeState] = {}
    order = NodeOrder()
    cap = NodeCapacityArray(nodes, order, c_node=C_NODE)
    next_id = 0
    dropped: list[int] = []

    def add_node(nid: int | None = None) -> None:
        nonlocal next_id
        if nid is None:
            nid = next_id
            next_id += 1
        s = NodeState(nid, rng.randrange(1, 9) * GiB,
                      float(rng.randrange(1, 17)))
        nodes[nid] = s
        order.add(nid)
        cap.add(nid, s)

    for _ in range(6):
        add_node()
    for _ in range(80):
        op = rng.randrange(6)
        if op == 0:
            add_node()
        elif op == 1 and nodes:                       # fail
            nid = rng.choice(sorted(nodes))
            del nodes[nid]
            order.discard(nid)
            cap.drop(nid)
            dropped.append(nid)
        elif op == 2 and dropped:                     # rejoin: fresh slot
            add_node(dropped.pop(rng.randrange(len(dropped))))
        elif op == 3 and nodes:                       # free-capacity change
            nid = rng.choice(sorted(nodes))
            s = nodes[nid]
            s.free_mem = rng.randrange(0, s.mem + 1)
            s.free_cores = rng.uniform(0.0, s.cores)
            cap.refresh_from(nid, s)
        elif op == 4 and nodes:                       # COP slot change
            nid = rng.choice(sorted(nodes))
            s = nodes[nid]
            s.active_cops = max(0, s.active_cops + rng.choice([-1, 1]))
            cap.refresh_from(nid, s)
        elif op == 5 and nodes:                       # dirty-drain batch
            sel = [n for n in sorted(nodes) if rng.random() < 0.5]
            for n in sel:
                nodes[n].free_mem = rng.randrange(0, nodes[n].mem + 1)
            # unknown ids must be skipped, like a drained dirty set that
            # still names an already-failed node
            cap.refresh_many(sel + [10 ** 9], nodes)
        assert cap.snapshot() == _mirror(nodes)
        assert cap.live_ids() == list(order)
        assert len(cap) == len(nodes)
        _check_queries(cap, nodes, order, rng)


def test_nodearray_compaction():
    from repro.core import NodeCapacityArray
    from repro.core.nodearray import _MIN_COMPACT

    rng = random.Random(42)
    nodes = {i: NodeState(i, 4 * GiB, 8.0) for i in range(220)}
    order = NodeOrder(nodes)
    cap = NodeCapacityArray(nodes, order, c_node=C_NODE)
    victims = rng.sample(range(220), 200)
    compacted = False
    for nid in victims:
        del nodes[nid]
        order.discard(nid)
        cap.drop(nid)
        compacted = compacted or cap._dead == 0 and cap._n == len(nodes)
        assert cap.snapshot() == _mirror(nodes)
        assert cap.live_ids() == list(order)
    assert compacted, "the drop stream never triggered _compact"
    assert cap._dead <= max(_MIN_COMPACT, len(nodes))
    # the compacted array still answers and accepts re-joins
    _check_queries(cap, nodes, order, rng)
    for nid in victims[:10]:
        s = NodeState(nid, 4 * GiB, 8.0)
        nodes[nid] = s
        order.add(nid)
        cap.add(nid, s)
    assert cap.snapshot() == _mirror(nodes)
    assert cap.live_ids() == list(order)


@settings(max_examples=5)
@given(st.integers(0, 10 ** 6))
def test_scheduler_stream_mirrors_nodes(seed):
    """Full simulation with failure + elastic join; after every schedule()
    the array state equals the live NodeState dict (the write-through choke
    points missed nothing)."""
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    rng = random.Random(seed)
    wf = make_workflow("group", scale=0.4, seed=seed % 97)
    sim = Simulation(wf, SimConfig(n_nodes=10, dfs="ceph"), "wow")
    sim.schedule_failure(rng.uniform(5.0, 40.0), rng.randrange(10))
    sim.schedule_join(rng.uniform(10.0, 60.0), 10)
    sched = sim.strategy.sched
    cap = sched._cap_array
    orig_schedule = sched.schedule
    checks = {"n": 0}

    def checked_schedule():
        actions = orig_schedule()
        assert cap.snapshot() == _mirror(sched.nodes)
        assert cap.live_ids() == list(sched.node_order)
        checks["n"] += 1
        return actions

    sched.schedule = checked_schedule
    sim.run()
    assert checks["n"] > 0


@pytest.mark.parametrize("workflow", ["rnaseq", "syn_blast", "chain"])
@pytest.mark.parametrize("churn", [False, True])
def test_full_sim_bit_identity(workflow, churn):
    """Actions, makespan and event count identical to the frozen reference
    core, on workflows the drain tests do not run, through a node failure
    and an elastic join."""
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    runs = {}
    for ref in (False, True):
        wf = make_workflow(workflow, scale=0.6)
        sim = Simulation(wf, SimConfig(n_nodes=14, dfs="ceph",
                                       reference_core=ref), "wow")
        if churn:
            sim.schedule_failure(15.0, 3)
            sim.schedule_join(30.0, 14)
        r = sim.run()
        runs[ref] = (sim.action_log, r.makespan, r.sim_steps)
    assert runs[True][0] == runs[False][0], "action log diverged"
    assert runs[True][1] == runs[False][1], "makespan diverged"
    assert runs[True][2] == runs[False][2], "event count diverged"


# --------------------------------------------------------- truncation parity
def _trunc_setup(seed: int, cls=WowScheduler):
    """A multi-shape input-less backlog far beyond cluster capacity, on a
    jittered cluster, past the exact gate -- the truncation path's regime."""
    rng = random.Random(seed)
    nodes = {}
    for i in range(12):
        s = NodeState(i, 16 * GiB, 16.0)
        s.free_mem = rng.randrange(8, 13) * GiB
        s.free_cores = float(rng.randrange(2, 5))
        nodes[i] = s
    dps = DataPlacementService(seed=0)
    sched = cls(nodes, dps)
    shapes = [(4 * GiB, 1.0), (8 * GiB, 2.0), (6 * GiB, 1.5)]
    specs = []
    tid = 0
    for _ in range(40):
        for mem, cores in shapes:
            t = TaskSpec(id=tid, abstract=f"s{cores}", mem=mem, cores=cores,
                         inputs=(), priority=rng.uniform(1.0, 10.0))
            specs.append(t)
            sched.submit(t)
            tid += 1
    return sched, nodes, specs


def _placed(actions) -> dict[int, int]:
    return {a.task_id: a.node for a in actions if isinstance(a, StartTask)}


@pytest.mark.parametrize("seed", [7, 11])
def test_truncation_matches_untruncated_solve(seed):
    sched, nodes, specs = _trunc_setup(seed)
    # oracle: the untruncated tiered solve on a snapshot of the same state
    oracle_nodes = {n: copy.deepcopy(s) for n, s in nodes.items()}
    cand = {t.id: [n for n in range(12)
                   if oracle_nodes[n].free_mem >= t.mem
                   and oracle_nodes[n].free_cores >= t.cores]
            for t in specs}
    expected = ilp_solve(AssignmentProblem(
        list(specs), cand, oracle_nodes))
    placed = _placed(sched.schedule())
    assert sched.inputless_stats["trunc_solves"] >= 1, (
        "instance did not exercise the truncation path")
    assert 0 < len(placed) < len(specs), "backlog should exceed capacity"
    assert placed == expected


def test_truncation_vectorized_matches_dict():
    """The truncated solve places what the frozen reference places on the
    same backlog (its monolithic solve is past its exact gate, so greedy
    over the untruncated instance)."""
    sched, _, _ = _trunc_setup(7)
    ref, _, _ = _trunc_setup(7, ReferenceWowScheduler)
    placed = _placed(sched.schedule())
    assert sched.inputless_stats["trunc_solves"] >= 1
    assert placed == _placed(ref.schedule())
