"""Unit + property tests for the paper-faithful core (priorities, ILP, DPS,
three-step scheduler invariants)."""
import random

import pytest
from _hyp import given, settings, st

from repro.core import (AssignmentProblem, DataPlacementService, FileSpec,
                        NodeState, StartCop, TaskSpec, WowScheduler,
                        abstract_ranks, priority_value, solve, solve_exact,
                        solve_greedy)
from repro.core.ilp import objective

GiB = 1024 ** 3


# ------------------------------------------------------------------ ranks
def test_abstract_ranks_chain():
    edges = {"a": {"b"}, "b": {"c"}, "c": set()}
    r = abstract_ranks(edges)
    assert r == {"a": 2, "b": 1, "c": 0}


def test_abstract_ranks_diamond():
    edges = {"s": {"a", "b"}, "a": {"t"}, "b": {"x"}, "x": {"t"},
             "t": set()}
    r = abstract_ranks(edges)
    assert r["s"] == 3 and r["t"] == 0 and r["b"] == 2 and r["a"] == 1


def test_abstract_ranks_cycle_raises():
    with pytest.raises(ValueError):
        abstract_ranks({"a": {"b"}, "b": {"a"}})


def test_priority_lexicographic():
    # rank dominates input size; size breaks ties (paper §III-B)
    assert priority_value(2, 0) > priority_value(1, 10 ** 15)
    assert priority_value(1, 2 * 10 ** 9) > priority_value(1, 10 ** 9)
    assert priority_value(0, 0) > 0


# -------------------------------------------------------------------- ILP
def _mk_problem(rng, n_tasks, n_nodes):
    nodes = {i: NodeState(i, mem=rng.randint(4, 16) * GiB,
                          cores=rng.randint(2, 16)) for i in range(n_nodes)}
    tasks, prepared = [], {}
    for t in range(n_tasks):
        task = TaskSpec(id=t, abstract="a",
                        mem=rng.randint(1, 8) * GiB,
                        cores=rng.randint(1, 8),
                        priority=rng.uniform(0.1, 10.0))
        tasks.append(task)
        k = rng.randint(0, n_nodes)
        prepared[t] = rng.sample(range(n_nodes), k)
    return AssignmentProblem(tasks, prepared, nodes)


def _brute_force(problem):
    p = problem
    best = [0.0]

    def rec(i, free_mem, free_cores, val):
        best[0] = max(best[0], val)
        if i == len(p.tasks):
            return
        t = p.tasks[i]
        rec(i + 1, free_mem, free_cores, val)
        for n in p.prepared.get(t.id, []):
            if free_mem[n] >= t.mem and free_cores[n] >= t.cores:
                free_mem[n] -= t.mem
                free_cores[n] -= t.cores
                rec(i + 1, free_mem, free_cores, val + t.priority)
                free_mem[n] += t.mem
                free_cores[n] += t.cores

    rec(0, {n: s.free_mem for n, s in p.nodes.items()},
        {n: s.free_cores for n, s in p.nodes.items()}, 0.0)
    return best[0]


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 7), st.integers(1, 4))
def test_ilp_exact_matches_brute_force(seed, n_tasks, n_nodes):
    rng = random.Random(seed)
    problem = _mk_problem(rng, n_tasks, n_nodes)
    exact = solve_exact(problem)
    assert exact is not None
    assert abs(objective(problem, exact) - _brute_force(problem)) < 1e-9


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 14), st.integers(1, 5))
def test_solvers_feasible(seed, n_tasks, n_nodes):
    rng = random.Random(seed)
    problem = _mk_problem(rng, n_tasks, n_nodes)
    for solver in (solve_greedy, solve):
        assign = solver(problem)
        used_mem = {n: 0 for n in problem.nodes}
        used_cores = {n: 0.0 for n in problem.nodes}
        by_id = {t.id: t for t in problem.tasks}
        for tid, n in assign.items():
            assert n in problem.prepared[tid]      # only prepared nodes
            used_mem[n] += by_id[tid].mem
            used_cores[n] += by_id[tid].cores
        for n, s in problem.nodes.items():
            assert used_mem[n] <= s.free_mem       # capacity respected
            assert used_cores[n] <= s.free_cores


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_greedy_not_catastrophic(seed):
    rng = random.Random(seed)
    problem = _mk_problem(rng, 6, 3)
    opt = _brute_force(problem)
    g = objective(problem, solve_greedy(problem))
    assert g >= 0.5 * opt - 1e-9   # greedy is a 2-approx in practice


# -------------------------------------------------------------------- DPS
def _dps_with_files(sizes_locs):
    dps = DataPlacementService(seed=1)
    for fid, (size, locs) in enumerate(sizes_locs):
        dps.register_file(FileSpec(id=fid, size=size, producer=0),
                          locs[0])
        for n in locs[1:]:
            dps._locations[fid].add(n)
    return dps


def test_dps_prepared_and_missing():
    dps = _dps_with_files([(100, [0]), (200, [0, 1]), (300, [2])])
    assert dps.is_prepared((0, 1), 0)
    assert not dps.is_prepared((0, 2), 0)
    assert dps.prepared_nodes((1,), [0, 1, 2]) == [0, 1]
    assert dps.missing_bytes((0, 1, 2), 1) == 400
    assert dps.prepared_nodes((), [0, 1]) == [0, 1]   # no inputs: anywhere


def test_dps_plan_cop_covers_missing_and_commit():
    dps = _dps_with_files([(100, [0]), (200, [1]), (300, [2])])
    plan = dps.plan_cop(7, (0, 1, 2), target=2)
    assert plan is not None
    assert {t.file_id for t in plan.transfers} == {0, 1}
    assert plan.total_bytes == 300
    for t in plan.transfers:
        assert t.dst == 2 and t.src != 2
    dps.commit_cop(plan)
    assert dps.is_prepared((0, 1, 2), 2)
    assert dps.cop_bytes_total == 300


def test_dps_plan_price_components():
    # all files on node 0 -> max load == total traffic, price = sum halves
    dps = _dps_with_files([(100, [0]), (50, [0])])
    plan = dps.plan_cop(1, (0, 1), target=3)
    assert plan.price == pytest.approx(0.5 * 150 + 0.5 * 150)
    # two sources available -> load spread lowers the max-load component
    dps2 = _dps_with_files([(100, [0]), (100, [1])])
    plan2 = dps2.plan_cop(1, (0, 1), target=3)
    assert plan2.price == pytest.approx(0.5 * 200 + 0.5 * 200)


def test_dps_source_load_balancing():
    # 4 equal files all replicated on nodes 0 and 1: greedy must alternate
    dps = _dps_with_files([(100, [0, 1])] * 4)
    plan = dps.plan_cop(1, (0, 1, 2, 3), target=5)
    from collections import Counter
    srcs = Counter(t.src for t in plan.transfers)
    assert srcs[0] == 2 and srcs[1] == 2


def test_dps_allowed_sources_none_possible():
    dps = _dps_with_files([(100, [0])])
    assert dps.plan_cop(1, (0,), target=2, allowed_sources=set()) is None


def test_dps_invalidate_and_gc():
    dps = _dps_with_files([(100, [0, 1, 2])])
    dps.invalidate(0, only_valid=1)
    assert dps.locations(0) == {1}
    freed = dps.delete_replicas(0, keep=0)
    assert freed == 100
    assert not dps.locations(0)


# -------------------------------------------------- DPS property tests
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 8), st.integers(1, 6),
       st.integers(1, 6))
def test_dps_plan_properties(seed, n_files, n_nodes, extra_replicas):
    """For any replica layout: a planned COP (i) covers exactly the missing
    files, (ii) never sources from the target, (iii) has price >= half the
    traffic, and committing it prepares the target."""
    rng = random.Random(seed)
    dps = DataPlacementService(seed=seed)
    fids = []
    for f in range(n_files):
        size = rng.randint(1, 10 ** 9)
        home = rng.randrange(n_nodes)
        dps.register_file(FileSpec(id=f, size=size, producer=0), home)
        for _ in range(rng.randint(0, extra_replicas)):
            dps._locations[f].add(rng.randrange(n_nodes))
        fids.append(f)
    target = rng.randrange(n_nodes + 1)
    missing = {f for f in fids if target not in dps.locations(f)}
    plan = dps.plan_cop(99, tuple(fids), target)
    if any(not (dps.locations(f) - {target}) for f in missing):
        assert plan is None or all(
            t.src != target for t in plan.transfers)
        return
    assert plan is not None
    assert {t.file_id for t in plan.transfers} == missing
    assert all(t.src != target and t.dst == target
               for t in plan.transfers)
    assert plan.price >= 0.5 * plan.total_bytes - 1e-6
    dps.commit_cop(plan)
    assert dps.is_prepared(tuple(fids), target)


@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(2, 5), st.integers(1, 12))
def test_dps_greedy_balances_sources(seed, n_nodes, n_files):
    """When every file is replicated everywhere, greedy source choice keeps
    the max per-source load within one max-file-size of the mean."""
    rng = random.Random(seed)
    dps = DataPlacementService(seed=seed)
    sizes = [rng.randint(1, 100) for _ in range(n_files)]
    for f, size in enumerate(sizes):
        dps.register_file(FileSpec(id=f, size=size, producer=0), 0)
        dps._locations[f] = set(range(n_nodes))
    plan = dps.plan_cop(1, tuple(range(n_files)), target=n_nodes)
    loads = {}
    for t in plan.transfers:
        loads[t.src] = loads.get(t.src, 0) + t.size
    total = sum(sizes)
    assert max(loads.values()) <= total / n_nodes + max(sizes)


# ------------------------------------------- step-2 partial-present sort
def _racks_of_two(n_nodes):
    """Racks of two nodes under one site (cost weights 1 in-rack, 4 across
    racks): the topology under which the kernel computes its locality cost
    row."""
    from repro.sim import Topology, TopologySpec
    return Topology(TopologySpec(rack_size=2), n_nodes, 1.25e8)


@pytest.mark.parametrize("topology", [None, "rack"])
def test_step2_partial_present_bytes_order(topology):
    """Step-2's *mixed* sort branch: some candidates hold input bytes, some
    none -- the key is ``(task_bytes - present.get(n, 0), n)``, so the node
    missing the fewest bytes wins and equal-missing ties split by node id.
    Under racks of two the key is the locality-weighted missing cost
    (``dps.locality_missing_cost``), with the same tie-break."""
    MB = 1024 ** 2

    def build(cls):
        nodes = {i: NodeState(i, mem=8 * GiB, cores=8.0) for i in range(4)}
        dps = DataPlacementService(seed=0)
        if topology:
            dps.set_topology(_racks_of_two(4))
        # file A (100 MB) on nodes 2 and 3; file B (50 MB) on node 1; node
        # 0 holds nothing.  Missing bytes: n0=150M, n1=100M, n2=n3=50M;
        # in racks {0, 1} and {2, 3}: n0=450M, n1=400M, n2=n3=200M.
        dps.register_file(FileSpec(1, 100 * MB, 0), 2)
        dps.add_replica(1, 3)
        dps.register_file(FileSpec(2, 50 * MB, 0), 1)
        sched = cls(nodes, dps, c_task=1)
        sched.submit(TaskSpec(id=1, abstract="a", mem=GiB, cores=1.0,
                              inputs=(1, 2), priority=1.0))
        return sched, nodes, dps

    sched, nodes, dps = build(WowScheduler)
    actions = sched.schedule()
    cops = [a for a in actions if isinstance(a, StartCop)]
    assert len(cops) == 1
    plan = cops[0].plan
    # the sort key, computed independently
    if topology:
        cost = {n: dps.locality_missing_cost(1, n) for n in nodes}
        assert cost == {0: 450 * MB, 1: 400 * MB, 2: 200 * MB, 3: 200 * MB}
    else:
        present = dps.present_bytes_map(1)
        cost = {n: dps.task_input_bytes(1) - present.get(n, 0)
                for n in nodes}
    oracle = min(nodes, key=lambda n: (cost[n], n))
    assert oracle == 2          # tie between 2 and 3 splits by id
    assert plan.target == 2
    from repro.core import ReferenceWowScheduler
    ref = build(ReferenceWowScheduler)[0]
    assert _cops(actions) == _cops(ref.schedule())
    # node 2 already holds A, so the COP moves exactly file B from node 1
    assert [(t.file_id, t.src, t.dst) for t in plan.transfers] == \
        [(2, 1, 2)]


# ------------------------------------------- step-2/3 emptiness pre-test
def _pretest_sched(cls, free_cores, files, tasks, active_cops=(),
                   topology=None, **kw):
    """Nodes of 8 GiB and 8 cores with the given free cores (and a busy
    COP slot on the nodes in ``active_cops``), 1 MB files ``{id:
    holders}``, and data-bound tasks ``(id, inputs, priority)`` of one
    shape, 1 GiB and 1 core; ``topology="rack"`` puts the nodes in racks
    of two."""
    nodes = {i: NodeState(i, mem=8 * GiB, cores=8.0, free_cores=fc,
                          active_cops=int(i in active_cops))
             for i, fc in enumerate(free_cores)}
    dps = DataPlacementService(seed=0)
    if topology:
        dps.set_topology(_racks_of_two(len(nodes)))
    for fid, holders in files.items():
        dps.register_file(FileSpec(fid, 1024 ** 2, 0), holders[0])
        for n in holders[1:]:
            dps.add_replica(fid, n)
    sched = cls(nodes, dps, **kw)
    for tid, inputs, prio in tasks:
        sched.submit(TaskSpec(id=tid, abstract="a", mem=GiB, cores=1.0,
                              inputs=inputs, priority=prio))
    return sched


def _cops(actions):
    return [(a.plan.id, a.plan.task_id, a.plan.target,
             [(t.file_id, t.src, t.dst) for t in a.plan.transfers])
            for a in actions if isinstance(a, StartCop)]


def _schedule_pretest(topology, *case, **kw):
    """One ``schedule()`` of the case on the scheduler under test, with its
    pre-test skips per step, beside the frozen reference's COPs."""
    from repro.core import ReferenceWowScheduler
    sched = _pretest_sched(WowScheduler, *case, topology=topology, **kw)
    skipped = {2: 0, 3: 0}

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
    got = _cops(sched.schedule())
    want = _cops(_pretest_sched(ReferenceWowScheduler, *case,
                                topology=topology).schedule())
    return sched, got, want, skipped


class _CountingSet(set):
    """A prepared set that counts the subset tests made against it."""
    tests = 0

    def __ge__(self, other):
        _CountingSet.tests += 1
        return set.__ge__(self, other)


def _count_subset_tests(sched, monkeypatch):
    read = sched.dps.prepared_node_set
    monkeypatch.setattr(sched.dps, "prepared_node_set",
                        lambda tid: _CountingSet(read(tid)))
    monkeypatch.setattr(_CountingSet, "tests", 0)


@pytest.mark.parametrize("topology", [None, "rack"])
def test_step2_shape_dies_mid_pass(topology):
    """Node 0 alone has free cores.  Task 1's COP takes its slot, which
    kills the shape: task 2's probe comes back empty and proves it, and
    tasks 3 and 4, of the same shape, are skipped -- with the reference's
    COPs, ids and transfers unchanged."""
    sched, got, want, skipped = _schedule_pretest(
        topology, [8.0, 0.0, 0.0, 0.0], {1: [1], 2: [2], 3: [3], 4: [2]},
        [(1, (1,), 4.0), (2, (2,), 3.0), (3, (3,), 2.0), (4, (4,), 1.0)])
    assert skipped == {2: 2, 3: 0}
    assert got == want
    # step 2 prepared task 1 on node 0; step 3 then found node 3 for task 2
    assert [(task, target) for _, task, target, _ in got] == [(1, 0), (2, 3)]


@pytest.mark.parametrize("topology", [None, "rack"])
def test_step3_task_prepared_on_every_free_slot_node_is_skipped(topology):
    """No node has free cores, and task 1's input is on both nodes: step 3
    sees its prepared set cover the free-slot set and skips it."""
    sched, got, want, skipped = _schedule_pretest(
        topology, [0.0, 0.0], {1: [0, 1]}, [(1, (1,), 1.0)])
    assert skipped == {2: 0, 3: 1}
    assert got == want == []
    assert sched.drain_probed == 2            # one visit in each step


@pytest.mark.parametrize("topology", [None, "rack"])
def test_step3_task_prepared_on_all_but_one_free_slot_node_is_probed(
        topology, monkeypatch):
    """Task 1 is prepared on three nodes and the free-slot set has three,
    but node 3's slot is busy and node 2 lacks the input: the subset test
    runs, fails, and the probe starts a COP to node 2."""
    case = ([0.0] * 4, {1: [0, 1, 3]}, [(1, (1,), 1.0)])
    sched = _pretest_sched(WowScheduler, *case, active_cops=(3,),
                           topology=topology)
    _count_subset_tests(sched, monkeypatch)
    got = _cops(sched.schedule())
    assert _CountingSet.tests == 1
    assert sched.drain_skipped == 0
    from repro.core import ReferenceWowScheduler
    ref = _pretest_sched(ReferenceWowScheduler, *case, active_cops=(3,),
                         topology=topology)
    assert got == _cops(ref.schedule())
    assert [(task, target) for _, task, target, _ in got] == [(1, 2)]


@pytest.mark.parametrize("topology", [None, "rack"])
def test_step3_length_guard_decides_without_subset_test(topology,
                                                        monkeypatch):
    """Four free-slot nodes against one prepared node: the length guard
    answers and no subset test is made; the task is probed as before."""
    sched = _pretest_sched(WowScheduler, [0.0] * 4, {1: [0]},
                           [(1, (1,), 1.0)], topology=topology)
    _count_subset_tests(sched, monkeypatch)
    got = _cops(sched.schedule())
    assert _CountingSet.tests == 0
    assert sched.drain_skipped == 0
    assert [(task, target) for _, task, target, _ in got] == [(1, 1)]
    from repro.core import ReferenceWowScheduler
    ref = _pretest_sched(ReferenceWowScheduler, [0.0] * 4, {1: [0]},
                         [(1, (1,), 1.0)], topology=topology)
    assert got == _cops(ref.schedule())


# ------------------------------------------- one implementation, no switches
@pytest.mark.parametrize("key", ["vectorized", "batched"])
def test_scheduler_and_adapter_refuse_removed_switches(key):
    """The scheduler has one node state and one drain: the keywords that
    used to select between implementations are unknown."""
    from repro.core import make_adapter
    nodes = {0: NodeState(0, mem=8 * GiB, cores=8.0)}
    with pytest.raises(TypeError, match=key):
        WowScheduler(nodes, DataPlacementService(seed=0), **{key: False})
    with pytest.raises(TypeError, match=key):
        make_adapter("wow", nodes, **{key: False})


@pytest.mark.parametrize("key", ["vectorized", "batched"])
def test_simconfig_refuses_removed_switches(key):
    from repro.sim import SimConfig
    with pytest.raises(TypeError, match=key):
        SimConfig(**{key: False})


# ------------------------------------- free-COP-slot transitions: the walk
def _testbed_stream(reference_core=False):
    """The paper's 8-node testbed (one COP per node, Ceph) under a short
    stream of the four Table I workflows."""
    from repro.sim import SimConfig, Simulation, TenantSpec, TrafficConfig
    tenants = tuple(TenantSpec(wf, workflows=(wf,), scale=0.1, slo=1e9)
                    for wf in ("rnaseq", "sarek", "chipseq", "rangeland"))
    traffic = TrafficConfig(tenants=tenants, rate=0.002, n_arrivals=8,
                            max_backlog=3, seed=11)
    cfg = SimConfig(n_nodes=8, c_node=1, dfs="ceph",
                    reference_core=reference_core)
    return Simulation(None, cfg, "wow", traffic=traffic)


def test_slot_transition_walks_exactly_the_waited_files_on_the_node():
    """Every free-COP-slot transition of a testbed run walks the files that
    some tracked task waits on and that have a replica on the node -- no
    other file the node holds."""
    sim = _testbed_stream()
    dps = sim.strategy.sched.dps
    seen = {"transitions": 0, "walked": 0, "held": 0}

    def checked(note):
        def run(node):
            waited = {f for inputs in dps._task_inputs.values()
                      for f in inputs if node in dps.locations(f)}
            held = sum(1 for f in dps.file_ids()
                       if node in dps.locations(f))
            before = (dps.source_transitions, dps.source_files_walked)
            note(node)
            steps = dps.source_transitions - before[0]
            walked = dps.source_files_walked - before[1]
            if steps:
                assert (steps, walked) == (1, len(waited)), node
                seen["transitions"] += 1
                seen["walked"] += walked
                seen["held"] += held
        return run
    dps.note_source_freed = checked(dps.note_source_freed)
    dps.note_source_busy = checked(dps.note_source_busy)
    sim.run()
    assert seen["transitions"] == dps.source_transitions > 100
    assert seen["walked"] == dps.source_files_walked
    # the node holds far more files than its waited ones
    assert 0 < seen["walked"] < seen["held"] / 2, seen


def test_testbed_stream_decisions_match_reference():
    """The same testbed run makes the frozen reference's decisions."""
    runs = {}
    for ref in (False, True):
        sim = _testbed_stream(reference_core=ref)
        res = sim.run()
        runs[ref] = (sim.action_log, repr(res.makespan),
                     sim.traffic_result())
    assert runs[False] == runs[True]
