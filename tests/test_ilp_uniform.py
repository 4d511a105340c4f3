"""The step-1 solver's uniform tier (core/ilp.py) against the branch & bound
it stands in for.

A uniform component's tasks share one shape, one positive priority and one
candidate list.  The tier counts the search instead of running it, so on
every seeded instance it must return what ``solve_exact`` returns, ``None``
(budget exhausted) included, and ``_solve_component`` must return the same
(assignment, tier) as the loop path.  The budgets straddle the loop's own
visit count: the count the tier reports, and one less.
"""
import random

import pytest

np = pytest.importorskip("numpy")

from repro.core import ilp  # noqa: E402
from repro.core.nodearray import NodeCapacityArray  # noqa: E402
from repro.core.types import NodeState, TaskSpec  # noqa: E402

GiB = 1024 ** 3
# priorities whose forward and backward float sums differ by an ulp
PRIOS = (18.00000514773409, 0.1, 1.0, 2.5)
CASES = 30          # parametrised cases, each of PER_CASE instances
PER_CASE = 20
BUDGET = 5_000


def _instance(rng: random.Random):
    """Tasks of one shape and priority sharing one candidate list (node
    ids and task ids out of order), on nodes admitting 0 to 8 placements,
    memory or cores binding; tie-heavy free values."""
    n_nodes = rng.choice((1, 1, 2, 3, 4, 6, 10, 16, 40, 120, 120, 300, 300))
    n_tasks = rng.randint(1, 64 // n_nodes if n_nodes <= 2 else 24)
    mem = rng.choice((1, 2, 3)) * GiB
    cores = rng.choice((0.5, 1.0, 1.5, 2.0, 3.0))
    prio = rng.choice(PRIOS + (rng.uniform(0.1, 30.0),))
    nodes = {}
    for nid in rng.sample(range(1000), n_nodes):
        k = rng.randint(0, 8)
        s = NodeState(nid, mem=64 * GiB, cores=64.0)
        if rng.random() < 0.5:
            s.free_mem = mem * k + rng.choice((0, 1, mem // 2))
            s.free_cores = cores * 9 + rng.choice((0.0, 0.1))
        else:
            s.free_mem = mem * 9
            s.free_cores = cores * k + rng.choice((0.0, 0.1, 0.25))
        nodes[nid] = s
    lst = list(nodes)
    tasks = [TaskSpec(id=tid, abstract="a", mem=mem, cores=cores,
                      priority=prio)
             for tid in rng.sample(range(1000, 2000), n_tasks)]
    cap = NodeCapacityArray(nodes, lst) if rng.random() < 0.5 else None
    return tasks, {t.id: lst for t in tasks}, nodes, cap


def _loop_path(tasks, cand, nodes, cap, budget):
    """What `_solve_component` returned before the uniform tier."""
    prob = ilp.AssignmentProblem(tasks, cand, nodes, cap)
    exact = ilp.solve_exact(prob, budget)
    if exact is not None:
        return exact, "exact"
    return ilp.solve_greedy(prob), "aborted"


def _check(tasks, cand, nodes, cap, budget, monkeypatch):
    lst = ilp._uniform_list(tasks, cand)
    assert lst is not None
    got = ilp._solve_uniform(tasks, lst, nodes, cap, budget)
    want = _loop_path(tasks, cand, nodes, cap, budget)
    if got is None:             # nothing fits: the loop answers
        assert want == ({}, "exact")
        return None
    assign, tier, visits = got
    assert (assign, tier) == want
    assert list(assign.items()) == list(want[0].items())
    assert (tier == "aborted") == (visits > budget)
    # the dispatcher: same (assignment, tier) with the tier and without
    assert ilp._solve_component(tasks, cand, nodes, node_budget=budget,
                                cap=cap) == want
    with monkeypatch.context() as m:
        m.setattr(ilp, "_uniform_list", lambda *a: None)
        assert ilp._solve_component(tasks, cand, nodes, node_budget=budget,
                                    cap=cap) == want
    return visits


@pytest.mark.parametrize("case", range(CASES))
def test_uniform_tier_equals_branch_and_bound(case, monkeypatch):
    for k in range(PER_CASE):
        tasks, cand, nodes, cap = _instance(
            random.Random(case * PER_CASE + k))
        got = ilp._solve_uniform(tasks, ilp._uniform_list(tasks, cand),
                                 nodes, cap, BUDGET)
        if got is None or got[2] > BUDGET:
            _check(tasks, cand, nodes, cap, BUDGET, monkeypatch)
        else:
            # straddle the loop's own count: the search completes at
            # `visits` and aborts one node earlier
            visits = got[2]
            assert _check(tasks, cand, nodes, cap, visits - 1,
                          monkeypatch) == visits
            assert _check(tasks, cand, nodes, cap, visits,
                          monkeypatch) == visits


def test_uniform_instances_cover_both_outcomes():
    """The seeded instances above hold at least 500 (instance, budget)
    pairs, 100 of them aborting at their budget and 100 completing after
    more than 1,000 visited search nodes."""
    aborts = long_exact = pairs = 0
    for seed in range(CASES * PER_CASE):
        tasks, cand, nodes, cap = _instance(random.Random(seed))
        got = ilp._solve_uniform(tasks, ilp._uniform_list(tasks, cand),
                                 nodes, cap, BUDGET)
        if got is None:
            pairs += 1
            continue
        visits = got[2]
        pairs += 1 if visits > BUDGET else 2
        aborts += 1
        long_exact += visits <= BUDGET and visits > 1_000
    assert pairs >= 500 and aborts >= 100 and long_exact >= 100, (
        pairs, aborts, long_exact)


def test_uniform_tier_at_the_default_budget():
    """Instances like the benchmark's: many identical tasks on one node
    (aborts) and 24 input-less tasks on ~300 fitting nodes."""
    budget = ilp._EXACT_NODE_BUDGET
    one = {0: NodeState(0, mem=128 * GiB, cores=16.0)}
    one[0].free_mem, one[0].free_cores = 8 * GiB, 8.0
    tasks = [TaskSpec(id=t, abstract="a", mem=GiB, cores=1.0,
                      priority=18.00000514773409) for t in range(26)]
    cand = {t.id: [0] for t in tasks}
    assert ilp._solve_uniform(tasks, [0], one, None, budget)[1] == "aborted"
    assert ilp._solve_component(tasks, cand, one) == _loop_path(
        tasks, cand, one, None, budget)
    rng = random.Random(7)
    nodes = {}
    for n in range(300):
        s = NodeState(n, mem=128 * GiB, cores=16.0)
        s.free_cores = float(rng.randint(2, 16))
        nodes[n] = s
    lst = list(nodes)
    for prio in (1.0, 18.00000514773409):
        tasks = [TaskSpec(id=t, abstract="a", mem=GiB, cores=2.0,
                          priority=prio) for t in range(24)]
        cand = {t.id: lst for t in tasks}
        cap = NodeCapacityArray(nodes, lst)
        assert ilp._solve_component(tasks, cand, nodes, cap=cap) \
            == _loop_path(tasks, cand, nodes, cap, budget)


def test_non_uniform_components_take_the_loop():
    """A differing shape, priority or candidate list, a zero priority or a
    repeated candidate leaves the component to the search."""
    nodes = {n: NodeState(n, mem=8 * GiB, cores=8.0) for n in range(3)}
    base = dict(abstract="a", mem=GiB, cores=1.0, priority=2.0)
    a = TaskSpec(id=1, **base)
    lst = [0, 1, 2]
    assert ilp._uniform_list([a, TaskSpec(id=2, **base)],
                             {1: lst, 2: lst}) is lst
    assert ilp._uniform_list([a, TaskSpec(id=2, **base)],
                             {1: lst, 2: list(lst)}) is lst
    for other, cands in (
            (dict(base, mem=2 * GiB), lst), (dict(base, cores=2.0), lst),
            (dict(base, priority=3.0), lst), (base, [0, 1])):
        assert ilp._uniform_list([a, TaskSpec(id=2, **other)],
                                 {1: lst, 2: cands}) is None
    zero = TaskSpec(id=3, **dict(base, priority=0.0))
    assert ilp._uniform_list([zero], {3: lst}) is None
    assert ilp._uniform_list([a], {1: [0, 0, 1]}) is None


@pytest.mark.parametrize("with_cap", [False, True])
@pytest.mark.parametrize("one_priority", [False, True])
def test_greedy_uniform_equals_solve_greedy(with_cap, one_priority):
    """The shared uniform best-fit greedy (the uniform tier's fallback and
    the scheduler's input-less fast path) is ``solve_greedy`` on
    single-shape components, read from the node dict or the capacity
    array."""
    for seed in range(60):
        rng = random.Random(seed)
        mem = rng.choice((1, 2)) * GiB
        cores = rng.choice((0.5, 1.0, 1.5, 2.0))
        nodes = {}
        for nid in rng.sample(range(100), rng.randint(1, 40)):
            s = NodeState(nid, mem=64 * GiB, cores=64.0)
            s.free_mem = mem * rng.randint(0, 6) + rng.choice((0, 1))
            s.free_cores = cores * rng.randint(0, 6) + rng.choice((0.0, 0.1))
            nodes[nid] = s
        lst = list(nodes)
        prio = rng.uniform(1.0, 5.0)
        tasks = [TaskSpec(id=tid, abstract="a", mem=mem, cores=cores,
                          priority=prio if one_priority
                          else rng.choice((prio, 1.0, 7.5)))
                 for tid in rng.sample(range(100, 200), rng.randint(1, 40))]
        cap = NodeCapacityArray(nodes, lst) if with_cap else None
        prob = ilp.AssignmentProblem(tasks, {t.id: lst for t in tasks},
                                     nodes, cap)
        want = ilp.solve_greedy(prob)
        fm, fc = ilp._free_arrays(nodes, lst, cap, mem)
        order = [t.id for t in sorted(tasks, key=lambda t: (-t.priority,
                                                            t.id))]
        got = ilp.greedy_uniform(mem, cores, order,
                                 np.asarray(lst, dtype=np.int64), fm, fc)
        assert list(got.items()) == list(want.items())


@pytest.mark.parametrize("seed", range(6))
def test_shared_candidate_lists_read_once_like_copies(seed):
    """Tasks holding one candidate list object (the input-less path's) are
    grouped, fingerprinted and solved exactly as tasks holding equal
    copies, which are read one by one."""
    rng = random.Random(seed)
    lists = [rng.sample(range(40), rng.randint(1, 12)) for _ in range(4)]
    tids = list(range(30))
    shared = {t: lists[rng.randrange(4)] for t in tids}
    copies = {t: list(c) for t, c in shared.items()}
    assert ilp.group_by_shared_nodes(tids, shared.__getitem__) \
        == ilp.group_by_shared_nodes(tids, copies.__getitem__)
    nodes = {n: NodeState(n, mem=8 * GiB, cores=8.0) for n in range(40)}
    tasks = {t: TaskSpec(id=t, abstract="a", mem=GiB * rng.randint(1, 3),
                         cores=2.0, priority=rng.choice((1.0, 2.0)))
             for t in tids}
    assert ilp.component_fingerprint(tids, tasks, shared, nodes) \
        == ilp.component_fingerprint(tids, tasks, copies, nodes)
    prob = ilp.AssignmentProblem([tasks[t] for t in tids], shared, nodes)
    assert ilp.solve(prob) == ilp.solve(ilp.AssignmentProblem(
        [tasks[t] for t in tids], copies, nodes))
