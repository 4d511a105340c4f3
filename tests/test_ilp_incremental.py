"""Determinism/parity tests for the decomposed + incremental step-1 solver
(core/ilp.py) and the FlowManager heap compaction.

Three claims are exercised, each against an independently computed oracle:

* decomposition is sound: components partition the feasible tasks, share no
  nodes, and composing per-component solutions reproduces the monolithic
  solver bit-for-bit whenever the monolithic exact gate applies (and never
  loses objective value beyond it);
* the *stateful* `IncrementalAssignmentSolver`, driven through the
  scheduler's dirty-set contract across successive events, returns exactly
  what a from-scratch `solve()` of each event's instance returns (strict
  mode), and at least the same objective in warm-start mode;
* fingerprint-cache reuse answers isomorphic recurring components without
  re-searching, and identical event streams produce identical outputs.
"""
import json
import random

import pytest
from _hyp import given, settings, st

from benchmarks.run import aggregate_report
from repro.core import (AssignmentProblem, IncrementalAssignmentSolver,
                        NodeState, TaskSpec, decompose, solve, solve_greedy,
                        solve_monolithic)
from repro.core import ilp
from repro.core.ilp import objective
from repro.sim import FlowManager, build_links

GiB = 1024 ** 3


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
        prepared[t] = sorted(rng.sample(range(n_nodes),
                                        rng.randint(0, min(3, n_nodes))))
    return AssignmentProblem(tasks, prepared, nodes)


def _recursive_exact(problem, node_budget, incumbent=None):
    """The recursive branch & bound ``ilp.solve_exact`` replaced by an
    explicit-stack loop: the oracle of the same search order, bound and
    node budget."""
    p = ilp._feasible(problem)
    tasks = sorted(p.tasks, key=lambda t: -t.priority)
    n_ids = sorted({n for cands in p.prepared.values() for n in cands})
    free_mem, free_cores = ilp._free_maps(p.nodes, n_ids, p.cap)
    suffix = [0.0] * (len(tasks) + 1)
    for i in range(len(tasks) - 1, -1, -1):
        suffix[i] = suffix[i + 1] + tasks[i].priority
    best_val = -1.0
    best_assign = {}
    if incumbent:
        best_assign = {tid: n for tid, n in incumbent.items()
                       if n in p.prepared.get(tid, ())}
        best_val = 0.0
        for i in range(len(tasks) - 1, -1, -1):
            if tasks[i].id in best_assign:
                best_val = best_val + tasks[i].priority
    cur_assign = {}
    visited = 0
    aborted = False

    def rec(i, val):
        nonlocal best_val, best_assign, visited, aborted
        if aborted:
            return
        visited += 1
        if visited > node_budget:
            aborted = True
            return
        if val + suffix[i] <= best_val:
            return
        if i == len(tasks):
            if val > best_val:
                best_val = val
                best_assign = dict(cur_assign)
            return
        t = tasks[i]
        cands = sorted(
            (n for n in p.prepared[t.id]
             if free_mem[n] >= t.mem and free_cores[n] >= t.cores),
            key=lambda n: (-(free_cores[n]), -(free_mem[n]), n))
        for n in cands:
            free_mem[n] -= t.mem
            free_cores[n] -= t.cores
            cur_assign[t.id] = n
            rec(i + 1, val + t.priority)
            del cur_assign[t.id]
            free_mem[n] += t.mem
            free_cores[n] += t.cores
            if aborted:
                return
        rec(i + 1, val)

    rec(0, 0.0)
    return None if aborted else best_assign


@settings(max_examples=60, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 14), st.integers(1, 5),
       st.integers(1, 150), st.booleans())
def test_exact_search_matches_recursive_oracle(seed, n_tasks, n_nodes,
                                               budget, seeded):
    """Same answer, same dict order, and the same budget abort as the
    recursive search, with and without a seeded incumbent."""
    problem = _mk_problem(random.Random(seed), n_tasks, n_nodes)
    incumbent = solve_greedy(problem) if seeded else None
    for b in (budget, ilp._EXACT_NODE_BUDGET):
        got = ilp.solve_exact(problem, b, incumbent=incumbent)
        want = _recursive_exact(problem, b, incumbent=incumbent)
        assert got == want
        if got is not None:
            assert list(got.items()) == list(want.items())


# ------------------------------------------------------------- decomposition
@settings(max_examples=30, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 16), st.integers(1, 6))
def test_decompose_partitions_feasible_tasks(seed, n_tasks, n_nodes):
    rng = random.Random(seed)
    problem = _mk_problem(rng, n_tasks, n_nodes)
    comps = decompose(problem)
    seen_tasks: set[int] = set()
    seen_nodes: set[int] = set()
    for sub in comps:
        tids = {t.id for t in sub.tasks}
        nids = set(sub.nodes)
        assert not tids & seen_tasks          # tasks partitioned
        assert not nids & seen_nodes          # components share no nodes
        seen_tasks |= tids
        seen_nodes |= nids
        for t in sub.tasks:                   # candidates stay inside
            assert set(sub.prepared[t.id]) <= nids
    # feasible tasks (some fitting prepared node) are exactly covered
    feasible = {t.id for t in problem.tasks
                if any(problem.nodes[n].free_mem >= t.mem
                       and problem.nodes[n].free_cores >= t.cores
                       for n in problem.prepared[t.id])}
    assert seen_tasks == feasible


@settings(max_examples=40, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(1, 10), st.integers(1, 5))
def test_decomposed_matches_monolithic_in_exact_regime(seed, n_tasks, n_nodes):
    """Within the monolithic exact gate the decomposed solve must be
    bit-identical (same assignment, not just same objective): per-component
    B&B composes into the monolithic depth-first optimum."""
    rng = random.Random(seed)
    problem = _mk_problem(rng, n_tasks, n_nodes)
    assert solve(problem) == solve_monolithic(problem)


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(25, 60), st.integers(2, 6))
def test_decomposed_never_worse_than_monolithic(seed, n_tasks, n_nodes):
    """Beyond the monolithic gate (greedy regime) decomposition may solve
    small components exactly -- the objective can only improve."""
    rng = random.Random(seed)
    problem = _mk_problem(rng, n_tasks, n_nodes)
    d = objective(problem, solve(problem))
    m = objective(problem, solve_monolithic(problem))
    assert d >= m - 1e-9


def test_out_of_gate_divergence_is_tie_equivalent():
    """Beyond the monolithic exact gate the reference greedy best-fits onto
    the *tightest* candidate while per-component exact branches most-free
    first: assignments may differ, the objective must not.  This pins the
    deliberate, documented scope of reference bit-parity (DESIGN.md
    "Scope of reference bit-parity")."""
    # 33 single-task components of 2 nodes each: 33 tasks / 66 candidate
    # slots puts the *monolithic* solver beyond its exact gate (all-greedy)
    # while every *component* is trivially exact.
    nodes = {}
    prepared = {}
    tasks = []
    for i in range(33):
        nodes[2 * i] = NodeState(2 * i, mem=8 * GiB, cores=16.0)
        nodes[2 * i + 1] = NodeState(2 * i + 1, mem=8 * GiB, cores=2.0)
        tasks.append(TaskSpec(id=i, abstract="a", mem=GiB, cores=1.0,
                              priority=1.0))
        prepared[i] = [2 * i, 2 * i + 1]
    problem = AssignmentProblem(tasks, prepared, nodes)
    d = solve(problem)
    m = solve_monolithic(problem)
    assert len(d) == len(m) == 33                  # everything starts
    assert objective(problem, d) == pytest.approx(objective(problem, m))
    assert d == {i: 2 * i for i in range(33)}      # exact: most-free node
    assert m == {i: 2 * i + 1 for i in range(33)}  # greedy: tightest node


# --------------------------------------------- incremental solver vs oracle
def _event_script(rng, n_nodes, n_events):
    """Deterministic schedule of scheduler-contract events."""
    script = []
    for _ in range(n_events):
        r = rng.random()
        if r < 0.35:
            script.append(("finish",))
        elif r < 0.75:
            prep = sorted(rng.sample(range(n_nodes),
                                     rng.randint(1, min(3, n_nodes))))
            script.append(("submit", rng.randint(1, 8) * GiB,
                           rng.randint(1, 8), rng.uniform(0.1, 10.0), prep))
        else:
            script.append(("replica", rng.randrange(10 ** 6),
                           rng.randrange(n_nodes)))
    return script


class _Harness:
    """Mimics the scheduler's side of the solver contract: maintains ready
    tasks, prepared sets, candidate lists and dirty sets, and applies the
    returned assignments.  ``decline_rate`` > 0 exercises the
    resource-manager-rejection path: a declined entry is not applied, the
    task stays ready, and (per the contract) it is marked dirty again on
    the next event — the only path on which warm-start seeds can fire."""

    def __init__(self, n_nodes, solver_cls=IncrementalAssignmentSolver,
                 decline_rate=0.0, decline_seed=0, **solver_kw):
        self.nodes = {i: NodeState(i, mem=10 * GiB, cores=10.0)
                      for i in range(n_nodes)}
        self.solver = solver_cls(self.nodes, **solver_kw)
        self.ready: dict[int, TaskSpec] = {}
        self.prep: dict[int, list[int]] = {}
        self.candidates: dict[int, list[int]] = {}
        self.seq: dict[int, int] = {}
        self.running: dict[int, tuple[int, TaskSpec]] = {}
        self._next_id = 0
        self._decline_rate = decline_rate
        self._decline_rng = random.Random(decline_seed)
        self._declined: set[int] = set()

    def _refresh(self, dirty_tasks, dirty_nodes):
        expanded = set(dirty_tasks)
        for t in list(self.ready):
            if set(self.prep[t]) & dirty_nodes:
                expanded.add(t)
        for t in expanded:
            spec = self.ready.get(t)
            if spec is None:
                self.candidates.pop(t, None)
                continue
            cands = [n for n in self.prep[t] if self.nodes[n].fits(spec)]
            if cands:
                self.candidates[t] = cands
            else:
                self.candidates.pop(t, None)
        return expanded

    def step(self, event, carry=()):
        """One event round; ``carry`` is the set of nodes dirtied by the
        previous round's reservations (the scheduler's _dirty_nodes carry
        them into the next schedule() the same way)."""
        dirty_tasks: set[int] = set(self._declined)   # decline contract
        self._declined = set()
        dirty_nodes: set[int] = set(carry)
        if event[0] == "finish":
            if self.running:
                tid = next(iter(self.running))
                node, spec = self.running.pop(tid)
                self.nodes[node].free_mem += spec.mem
                self.nodes[node].free_cores += spec.cores
                dirty_nodes.add(node)
        elif event[0] == "submit":
            _, mem, cores, prio, prep = event
            tid = self._next_id
            self._next_id += 1
            spec = TaskSpec(id=tid, abstract="a", mem=mem, cores=cores,
                            priority=prio)
            self.ready[tid] = spec
            self.prep[tid] = prep
            self.seq[tid] = tid
            dirty_tasks.add(tid)
        else:  # replica arrival: a ready task gains a prepared node
            _, pick, node = event
            if self.ready:
                tids = sorted(self.ready)
                tid = tids[pick % len(tids)]
                if node not in self.prep[tid]:
                    self.prep[tid] = sorted(self.prep[tid] + [node])
                    dirty_tasks.add(tid)
        expanded = self._refresh(dirty_tasks, dirty_nodes)
        assign = self.solver.solve_event(self.ready, self.candidates,
                                         self.seq, expanded, dirty_nodes)
        # oracles are evaluated BEFORE applying: the snapshot references the
        # live NodeState objects, which the apply step below mutates
        order = sorted(self.candidates, key=self.seq.__getitem__)
        snapshot = AssignmentProblem(
            [self.ready[t] for t in order],
            {t: list(self.candidates[t]) for t in order},
            self.nodes)
        expected = solve(snapshot)
        n_cand = sum(len(v) for v in snapshot.prepared.values())
        in_mono_gate = n_cand <= 64 or len(snapshot.tasks) <= 24
        mono = solve_monolithic(snapshot) if in_mono_gate else None
        feasible = self._feasible_against(snapshot, assign)
        record = {
            "assign": assign,
            "expected": expected,
            "mono": mono,
            "obj_got": objective(snapshot, assign),
            "obj_expected": objective(snapshot, expected),
            "feasible": feasible,
        }
        # apply, exactly like the scheduler does -- minus declined entries
        applied_nodes = set()
        for tid, n in sorted(assign.items()):
            if (self._decline_rate
                    and self._decline_rng.random() < self._decline_rate):
                self._declined.add(tid)   # stays ready; dirty next event
                continue
            spec = self.ready.pop(tid)
            self.candidates.pop(tid, None)
            self.seq.pop(tid, None)
            node = self.nodes[n]
            node.free_mem -= spec.mem
            node.free_cores -= spec.cores
            self.running[tid] = (n, spec)
            applied_nodes.add(n)
        # NOTE: applying dirties the assigned nodes for the *next* event
        self._pending_dirty = applied_nodes
        return record

    @staticmethod
    def _feasible_against(snapshot, assign) -> bool:
        used_mem = {n: 0 for n in snapshot.nodes}
        used_cores = {n: 0.0 for n in snapshot.nodes}
        by_id = {t.id: t for t in snapshot.tasks}
        for tid, n in assign.items():
            if tid not in by_id or n not in snapshot.prepared[tid]:
                return False
            used_mem[n] += by_id[tid].mem
            used_cores[n] += by_id[tid].cores
        return all(used_mem[n] <= s.free_mem
                   and used_cores[n] <= s.free_cores
                   for n, s in snapshot.nodes.items())

    def run(self, script):
        results = []
        carry: set[int] = set()
        for event in script:
            results.append(self.step(event, carry))
            carry = self._pending_dirty
        return results


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(2, 5), st.integers(6, 18))
def test_incremental_matches_stateless_across_events(seed, n_nodes, n_events):
    """Dirty-set driven re-solving (with cache + clean-component reuse)
    must equal a from-scratch decomposed solve of every event's snapshot --
    identical assignments, and identical to the monolithic solver's
    objective when its exact gate applies."""
    rng = random.Random(seed)
    script = _event_script(rng, n_nodes, n_events)
    h = _Harness(n_nodes)
    for rec in h.run(script):
        assert rec["assign"] == rec["expected"]
        if rec["mono"] is not None:
            assert rec["assign"] == rec["mono"]


@settings(max_examples=12, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(2, 5), st.integers(8, 18))
def test_warm_start_preserves_objective(seed, n_nodes, n_events):
    """strict_parity=False may pick different tie-equivalent optima but can
    never lose objective value versus the from-scratch solve.  A 50%
    decline rate keeps previously assigned tasks in the candidate set, so
    the B&B incumbent seeding actually fires (applied tasks leave the
    instance and can never seed -- see the class docstring)."""
    rng = random.Random(seed)
    script = _event_script(rng, n_nodes, n_events)
    h = _Harness(n_nodes, strict_parity=False, decline_rate=0.5,
                 decline_seed=seed)
    for rec in h.run(script):
        assert rec["obj_got"] >= rec["obj_expected"] - 1e-9
        assert rec["feasible"]


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(2, 5), st.integers(8, 18))
def test_strict_mode_survives_declined_starts(seed, n_nodes, n_events):
    """Declined assignments re-enter as dirty tasks; strict mode must keep
    matching the from-scratch solve of every snapshot."""
    rng = random.Random(seed)
    script = _event_script(rng, n_nodes, n_events)
    h = _Harness(n_nodes, decline_rate=0.4, decline_seed=seed)
    for rec in h.run(script):
        assert rec["assign"] == rec["expected"]


def test_warm_seed_fires_on_declined_start():
    """Deterministic activation of the warm-start path: an assignment is
    computed, declined by the caller, and the task's component re-solved
    (with a changed fingerprint) seeds the B&B incumbent from it."""
    nodes = {0: NodeState(0, mem=8 * GiB, cores=8.0)}
    solver = IncrementalAssignmentSolver(nodes, strict_parity=False)
    t1 = TaskSpec(id=1, abstract="a", mem=GiB, cores=1.0, priority=3.0)
    r1 = solver.solve_event({1: t1}, {1: [0]}, {1: 1}, {1}, set())
    assert r1 == {1: 0}
    assert solver.stats["warm_seeds"] == 0
    # the caller declines the start: task 1 stays ready and is re-marked
    # dirty; a second task joins the component, so the fingerprint changes
    # (no cache hit) and the previous assignment seeds the incumbent
    t2 = TaskSpec(id=2, abstract="a", mem=GiB, cores=1.0, priority=1.0)
    r2 = solver.solve_event({1: t1, 2: t2}, {1: [0], 2: [0]},
                            {1: 1, 2: 2}, {1, 2}, set())
    assert r2 == {1: 0, 2: 0}
    assert solver.stats["warm_seeds"] == 1


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6), st.integers(2, 4), st.integers(8, 15))
def test_incremental_determinism(seed, n_nodes, n_events):
    """Identical event streams on identical solvers produce identical
    assignments and identical counter trajectories."""
    rng = random.Random(seed)
    script = _event_script(rng, n_nodes, n_events)
    h1, h2 = _Harness(n_nodes), _Harness(n_nodes)
    r1 = [rec["assign"] for rec in h1.run(script)]
    r2 = [rec["assign"] for rec in h2.run(script)]
    assert r1 == r2
    assert h1.solver.stats == h2.solver.stats


def test_fingerprint_cache_hits_isomorphic_components():
    """A recurring component that is isomorphic (same shapes, priorities,
    candidate structure, node free resources -- different ids) is answered
    from the cache."""
    nodes = {0: NodeState(0, mem=8 * GiB, cores=8.0)}
    solver = IncrementalAssignmentSolver(nodes)
    t1 = TaskSpec(id=1, abstract="a", mem=GiB, cores=1.0, priority=3.0)
    r1 = solver.solve_event({1: t1}, {1: [0]}, {1: 1}, {1}, set())
    assert r1 == {1: 0}
    assert solver.stats["cache_misses"] == 1
    # do NOT apply, so node 0's free resources are unchanged; retire task 1
    # and submit an isomorphic task 2
    t2 = TaskSpec(id=2, abstract="a", mem=GiB, cores=1.0, priority=3.0)
    r2 = solver.solve_event({2: t2}, {2: [0]}, {2: 2}, {1, 2}, set())
    assert r2 == {2: 0}
    assert solver.stats["cache_hits"] == 1
    assert solver.stats["cache_misses"] == 1    # no new search


def test_fingerprint_cache_unit_roundtrip_and_lru():
    """FingerprintCache (the shared machinery behind both the step-1 solver
    and the input-less path): position-relative decode onto different ids,
    and LRU eviction at capacity."""
    from repro.core import FingerprintCache, component_fingerprint
    nodes = {5: NodeState(5, mem=8 * GiB, cores=8.0),
             9: NodeState(9, mem=8 * GiB, cores=8.0)}
    t1 = TaskSpec(id=11, abstract="a", mem=GiB, cores=1.0, priority=3.0)
    t2 = TaskSpec(id=12, abstract="a", mem=GiB, cores=1.0, priority=2.0)
    cand = {11: [5, 9], 12: [9]}
    fp, nlist, npos = component_fingerprint([11, 12], {11: t1, 12: t2},
                                            cand, nodes)
    cache = FingerprintCache(size=2)
    assert cache.get(fp, [11, 12], nlist) is None
    cache.put(fp, [11, 12], npos, {11: 5, 12: 9})
    assert cache.get(fp, [11, 12], nlist) == {11: 5, 12: 9}
    # same structure under different ids decodes onto the new ids
    assert cache.get(fp, [21, 22], nlist) == {21: 5, 22: 9}
    # isomorphic instance (different ids, same ranks/shapes) fingerprints
    # identically
    t3 = TaskSpec(id=31, abstract="a", mem=GiB, cores=1.0, priority=3.0)
    t4 = TaskSpec(id=32, abstract="a", mem=GiB, cores=1.0, priority=2.0)
    fp2, _, _ = component_fingerprint([31, 32], {31: t3, 32: t4},
                                      {31: [5, 9], 32: [9]}, nodes)
    assert fp2 == fp
    # LRU: two more inserts evict the oldest
    for k in range(2):
        cache.put(("filler", k), [1], {5: 0}, {1: 5})
    assert len(cache) == 2
    assert cache.get(fp, [11, 12], nlist) is None


def test_sustained_scenario_cache_stays_cold():
    """Regression companion to the benchmark headline's
    ``solver_stats.cache_hits == 0`` (BENCH_scheduler_scale.json).

    In the sustained scenario every re-solved component either (a) contains
    the event's freshly submitted task, whose priority is a fresh
    ``uniform(1, 10)`` draw -- making the fingerprint a.s. unique -- or (b)
    was dissolved precisely *because* a member node's free resources
    changed (task finish / step-1 reservation), so its node-capacity tuple
    differs from every earlier solve of the same task set.  Identical
    (shape, priority, capacity) instances therefore never recur and the
    cache cannot fire: zero hits is expected behaviour, not a defect.  The
    cache targets *recurring isomorphic* subproblems -- quantized
    priorities, declined-placement streams, steady fan-out -- covered by
    `test_fingerprint_cache_hits_isomorphic_components` and the input-less
    cache tests in tests/test_readyset.py."""
    from benchmarks.scheduler_scale import build, drive_event
    from repro.core import WowScheduler
    n_nodes, n_ready = 32, 128
    sched, dps, rng = build(n_nodes, n_ready, WowScheduler)
    sched.schedule()
    next_id = n_ready
    for _ in range(30):
        drive_event(sched, dps, rng, n_nodes, next_id)
        next_id += 1
    assert sched.solver_stats["cache_misses"] > 0   # components were solved
    assert sched.solver_stats["cache_hits"] == 0    # ...and never recurred


@pytest.mark.parametrize("seed", range(4))
def test_mixed_event_uniform_component_matches_reference(seed):
    """A mixed event: up to 24 input-less tasks of one shape fitting on
    more than 64 nodes, solved jointly with a startable data-bound task
    whose only prepared node the shape does not fit.  The input-less
    component takes the uniform tier, and the actions equal the
    reference scheduler's (whose monolithic search stays inside its gate
    and budget: integral priorities keep the bound's sums exact)."""
    from repro.core import (DataPlacementService, FileSpec,
                            ReferenceWowScheduler, StartCop, StartTask,
                            WowScheduler)
    rng = random.Random(seed)
    n_nodes = 80
    cores = [float(rng.randint(8, 16)) for _ in range(n_nodes)]
    host = rng.randrange(n_nodes)
    cores[host] = 4.0               # fits the data-bound task only

    def build(cls):
        nodes = {i: NodeState(i, mem=64 * GiB, cores=cores[i])
                 for i in range(n_nodes)}
        dps = DataPlacementService(seed=seed)
        dps.register_file(FileSpec(id=0, size=GiB, producer=-1), host)
        return cls(nodes, dps)

    def summary(actions):
        return [("task", a.task_id, a.node) if isinstance(a, StartTask)
                else ("cop", a.plan.task_id, a.plan.target)
                for a in actions if isinstance(a, (StartTask, StartCop))]

    new, ref = build(WowScheduler), build(ReferenceWowScheduler)
    n_less = rng.randint(12, 23)
    specs = [dict(id=0, abstract="b", mem=GiB, cores=2.0, inputs=(0,),
                  priority=5.0)]
    specs += [dict(id=t, abstract="a", mem=2 * GiB, cores=8.0, inputs=(),
                   priority=1.0) for t in range(1, n_less + 1)]
    for spec in specs:
        new.submit(TaskSpec(**spec))
        ref.submit(TaskSpec(**spec))
    got = summary(new.schedule())
    assert got == summary(ref.schedule())
    assert ("task", 0, host) in got
    assert new.inputless_stats["joint_events"] == 1
    assert new.solver_stats["uniform_solves"] >= 2


def test_clean_components_are_not_resolved():
    """Components untouched by the dirty sets are skipped wholesale."""
    nodes = {i: NodeState(i, mem=8 * GiB, cores=8.0) for i in range(4)}
    solver = IncrementalAssignmentSolver(nodes)
    # two independent single-node components, neither can start (too big)
    big = 16 * GiB
    t1 = TaskSpec(id=1, abstract="a", mem=big, cores=1.0, priority=1.0)
    t2 = TaskSpec(id=2, abstract="a", mem=big, cores=1.0, priority=1.0)
    tasks = {1: t1, 2: t2}
    cands = {}          # neither fits anywhere: no candidates at all
    assert solver.solve_event(tasks, cands, {1: 1, 2: 2}, {1, 2}, set()) == {}
    # startable variants on distinct nodes
    t3 = TaskSpec(id=3, abstract="a", mem=GiB, cores=1.0, priority=1.0)
    t4 = TaskSpec(id=4, abstract="a", mem=GiB, cores=1.0, priority=1.0)
    tasks = {3: t3, 4: t4}
    out = solver.solve_event(tasks, {3: [0], 4: [2]}, {3: 3, 4: 4},
                             {3, 4}, set())
    assert out == {3: 0, 4: 2}
    rebuilt = solver.stats["comps_rebuilt"]
    # an event whose dirty sets touch only node 1 leaves both components
    # alone (nothing pending -> no re-solve, empty delta)
    assert solver.solve_event(tasks, {3: [0], 4: [2]}, {3: 3, 4: 4},
                              set(), {1}) == {}
    assert solver.stats["comps_rebuilt"] == rebuilt
    assert solver.stats["comps_reused"] >= 2


# ------------------------------------------------------ FlowManager heaps
def test_flowmanager_heap_compaction_bounds_growth():
    """A long-lived flow re-rated every round leaves one stale heap entry
    per round; compaction must keep both heaps bounded by the live-flow
    count (regression for the ROADMAP 'Heap compaction' item).  The
    link-disjoint bystander keeps each recompute's component *partial* --
    a component spanning every live flow takes the heap-rebuild fast path
    instead, which leaves no garbage to compact at all."""
    caps = build_links(4, net_bw=100.0, disk_read_bw=1e6, disk_write_bw=1e6)
    fm = FlowManager(caps)
    long_flow = fm.add((("up", 0), ("down", 1)), 1e12, "long")
    bystander = fm.add((("up", 1), ("down", 0)), 1e13, "bystander")
    fm.recompute()
    for i in range(400):
        # churn flow shares ("up", 0): every recompute re-rates the long
        # flow, bumping its epoch and stranding its previous heap entries
        churn = fm.add((("up", 0), ("down", 2 + i % 2)), 10.0, ("churn", i))
        fm.recompute()
        dt, nxt = fm.next_completion()
        assert nxt is not None
        done = fm.advance(dt)
        assert [f.id for f in done] == [churn.id]
        bound = max(64, 4 * len(fm.flows))
        assert len(fm._completions) <= bound
        assert len(fm._horizon) <= bound
    assert fm.compactions > 0
    assert long_flow.id in fm.flows             # still running, still live
    assert bystander.id in fm.flows             # untouched component intact
    dt, nxt = fm.next_completion()
    assert nxt.id == long_flow.id               # its live entry survived


# ------------------------------------------------------ benchmark report
def test_aggregate_report_renders_rows_and_scalars(tmp_path):
    payload = {"rows": [{"impl": "indexed", "sustained_ms": 1.5},
                        {"impl": "reference", "sustained_ms": 120.0}],
               "headline": {"sustained_speedup": 80.0},
               "note": "demo"}
    (tmp_path / "BENCH_demo.json").write_text(json.dumps(payload))
    path = aggregate_report(root=str(tmp_path))
    assert path is not None
    text = (tmp_path / "BENCH_REPORT.md").read_text()
    assert "## BENCH_demo.json" in text
    assert "| impl | sustained_ms |" in text
    assert "- sustained_speedup: 80" in text
    assert "- note: demo" in text
    # no JSON files -> no report
    empty = tmp_path / "empty"
    empty.mkdir()
    assert aggregate_report(root=str(empty)) is None


def test_scheduler_scale_reports_solver_phase():
    """The benchmark's sustained runner must expose the solver- and
    step-2/3-phase clocks and stats for both implementations (keys the CI
    smoke job asserts on BENCH_scheduler_scale.json)."""
    from benchmarks.scheduler_scale import run_cold, run_sustained
    from repro.core import ReferenceWowScheduler, WowScheduler
    for cls in (WowScheduler, ReferenceWowScheduler):
        cold_ms, cold_solver_ms, _ = run_cold(4, 8, cls)
        assert cold_solver_ms >= 0.0
        sus = run_sustained(4, 8, cls, iters=2)
        assert sus["solver_ms"] >= 0.0
        assert sus["step23_ms"] >= 0.0
        assert sus["ms"] >= sus["solver_ms"]
        assert sus["ms"] >= sus["step23_ms"]
        if cls is WowScheduler:
            assert sus["stats"] is not None \
                and "comps_rebuilt" in sus["stats"]
        else:
            assert sus["stats"] is None


def test_scheduler_scale_inputless_and_live_rm_rows():
    """The fan-out (input-less) scenario must run both implementations to
    identical decisions at small scale, and the declined-placement live-RM
    scenario must report its keys with objective safety and warm seeds."""
    from benchmarks.scheduler_scale import (run_inputless, run_live_rm,
                                            sanity_check_equivalence)
    from repro.core import ReferenceWowScheduler, WowScheduler
    sanity_check_equivalence(n_nodes=6, n_ready=24, sustained_iters=6,
                             inputless=True)
    for cls in (WowScheduler, ReferenceWowScheduler):
        sus = run_inputless(4, 8, cls, iters=2)
        assert sus["ms"] >= 0.0
    live = run_live_rm(bursts=2, storms=3)
    assert live["objective_safe"]
    assert live["warm_seeds"] > 0
    assert live["declines"] == 2 * 3 * 16
    assert live["storm_events"] == 6
    assert live["cold_solver_ms_per_event"] > 0.0
    assert live["warm_solver_ms_per_event"] > 0.0
    for mode in ("cold", "warm"):
        assert live[f"{mode}_resolves"]["exact_solves"] > 0
