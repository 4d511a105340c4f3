"""Indexed ready-set subsystem tests (core/readyset.py + scheduler wiring).

Four layers:

* ReadySet / NodeOrder / the capacity array's fitting query as data
  structures, against from-scratch oracles over random operation streams.
* DPS source-feasibility index (`_free_rep` / `_unsourced` / `cop_blocked`)
  against brute-force recomputation over random replica + slot mutations.
* The scheduler's materialized step-2/3 visit orders against a full sort of
  every snapshot (the reference's semantics), across randomized event
  streams -- including the guarantee that every task the index parks as
  *blocked* would indeed fail its COP probe.
* Input-less fast path and canonical node order: randomized
  capacity-tight mixed (input-less + data-bound) event streams and
  out-of-order node enumeration / node re-join under an old id, all
  bit-compared against ``ReferenceWowScheduler``.
"""
import random

import pytest

from repro.core import (DataPlacementService, FileSpec, NodeCapacityArray,
                        NodeOrder, NodeState, ReadySet,
                        ReferenceWowScheduler, ShapeIndex, StartCop,
                        StartTask, TaskSpec, WowScheduler)
from repro.sim import SimConfig, Simulation
from repro.workloads import make_workflow

GiB = 1024 ** 3


# ----------------------------------------------------------------- NodeOrder
def test_node_order_basic():
    order = NodeOrder([3, 0, 2])
    assert list(order) == [3, 0, 2]
    assert order.sort({0, 2, 3}) == [3, 0, 2]
    assert order.position(0) == 1
    order.add(3)                       # idempotent
    assert list(order) == [3, 0, 2]
    order.discard(0)
    order.add(0)                       # re-join lands last, like dict re-add
    assert list(order) == [3, 2, 0]
    assert order.sort([0, 3]) == [3, 0]
    assert 2 in order and 7 not in order and len(order) == 3


# ------------------------------------------------------- capacity classes
@pytest.mark.parametrize("seed", range(5))
def test_capacity_classes_match_bruteforce(seed):
    """The input-less path's capacity classes -- "all nodes whose free
    resources fit (m, c)", in canonical order -- answered by the capacity
    array under the scheduler's refresh/drop calls."""
    rng = random.Random(seed)
    nodes = {i: NodeState(i, mem=rng.randint(4, 10), cores=float(
        rng.randint(2, 8))) for i in range(rng.randint(2, 8))}
    order = NodeOrder(nodes)
    cap = NodeCapacityArray(nodes, order)
    for _ in range(60):
        op = rng.randrange(3)
        if op == 0 and nodes:                    # mutate free resources
            n = rng.choice(list(nodes))
            nodes[n].free_mem = rng.randint(0, 10)
            nodes[n].free_cores = float(rng.randint(0, 8))
            cap.refresh_many([n], nodes)
        elif op == 1:                            # add a node
            n = max(nodes, default=-1) + 1
            nodes[n] = NodeState(n, mem=rng.randint(4, 10),
                                 cores=float(rng.randint(2, 8)))
            order.add(n)
            cap.add(n, nodes[n])
        elif op == 2 and len(nodes) > 1:         # drop a node
            n = rng.choice(list(nodes))
            del nodes[n]
            order.discard(n)
            cap.drop(n)
        mem, cores = rng.randint(0, 10), float(rng.randint(0, 8))
        expect = [n for n in order
                  if nodes[n].free_mem >= mem and nodes[n].free_cores >= cores]
        assert cap.fitting(mem, cores) == expect


# ----------------------------------------------------------------- ReadySet
def _oracle_orders(info):
    """From-scratch sorts of the {tid: (prep, cops, prio, blocked)} map."""
    live = [(tid, v) for tid, v in info.items() if not v[3]]
    o2 = [tid for tid, v in sorted(
        live, key=lambda kv: (kv[1][0], kv[1][1], -kv[1][2], kv[0]))]
    o3 = [tid for tid, v in sorted(
        live, key=lambda kv: (-kv[1][2], kv[0]))]
    return o2, o3


@pytest.mark.parametrize("seed", range(10))
def test_readyset_orders_match_oracle(seed):
    rng = random.Random(seed)
    rs = ReadySet()
    info: dict[int, list] = {}
    prios = [rng.uniform(1, 5) for _ in range(6)]   # few values: tie stress
    for _ in range(300):
        op = rng.randrange(6)
        if op == 0 or not info:
            tid = rng.randrange(40)
            prep, cops = rng.randrange(5), rng.randrange(3)
            prio, blocked = rng.choice(prios), rng.random() < 0.3
            info[tid] = [prep, cops, prio, blocked]
            rs.add(tid, prio, prep, cops, blocked=blocked)
        elif op == 1:
            tid = rng.choice(list(info))
            del info[tid]
            rs.discard(tid)
        elif op == 2:
            tid = rng.choice(list(info))
            info[tid][0] = rng.randrange(5)
            rs.update_prep(tid, info[tid][0])
        elif op == 3:
            tid = rng.choice(list(info))
            info[tid][1] = rng.randrange(3)
            rs.update_cops(tid, info[tid][1])
        elif op == 4:
            tid = rng.choice(list(info))
            info[tid][3] = rng.random() < 0.5
            rs.set_blocked(tid, info[tid][3])
        else:
            rs.discard(rng.randrange(40))           # maybe-absent discard
        info = {t: v for t, v in info.items() if t in rs}
        o2, o3 = _oracle_orders({t: tuple(v) for t, v in info.items()})
        assert rs.step2_order() == o2
        assert rs.step3_order() == o3
        assert len(rs) == len(info)


# --------------------------------------------- DPS source-feasibility index
def _check_source_index(dps, free):
    """`_free_rep` (kept for waited files only), `_node_wfiles` and
    `_unsourced` must equal brute-force recomputation, and `cop_blocked`
    must imply an empty feasible-target pool."""
    waited = {f for inputs in dps._task_inputs.values() for f in inputs}
    assert set(dps._waiting) == waited
    for f in waited:
        expect = sum(1 for n in dps.locations(f) if n in free)
        assert dps._free_rep.get(f, 0) == expect, f"free_rep[{f}]"
    assert set(dps._free_rep) <= waited, "count kept for an unwaited file"
    assert all(c > 0 for c in dps._free_rep.values())
    want_wfiles = {}
    for f in waited:
        for n in dps.locations(f):
            want_wfiles.setdefault(n, set()).add(f)
    got_wfiles = {n: fs for n, fs in dps._node_wfiles.items() if fs}
    assert got_wfiles == want_wfiles, "node_wfiles"
    for tid, inputs in dps._task_inputs.items():
        expect = sum(1 for f in set(inputs)
                     if not (dps.locations(f) & free))
        assert dps._unsourced.get(tid) == expect, f"unsourced[{tid}]"
        if dps.cop_blocked(tid):
            feas = dps.cop_feasible_targets(inputs, free)
            assert feas is not None and not (feas & free), \
                "blocked task has a feasible COP target"


@pytest.mark.parametrize("seed", range(10))
def test_dps_source_feasibility_index_matches_bruteforce(seed):
    rng = random.Random(100 + seed)
    n_nodes, n_files = rng.randint(2, 6), rng.randint(2, 10)
    dps = DataPlacementService(seed=seed)
    free = set(range(n_nodes))
    dps.sync_free_sources(free)
    for f in range(n_files):
        dps.register_file(FileSpec(id=f, size=rng.randint(1, 100),
                                   producer=-1), rng.randrange(n_nodes))
    tracked: dict[int, tuple] = {}
    for tid in range(rng.randint(1, 5)):
        inputs = tuple(rng.sample(range(n_files),
                                  rng.randint(1, min(4, n_files))))
        dps.track_task(tid, inputs)
        tracked[tid] = inputs
    for _ in range(150):
        op = rng.randrange(7)
        fid, node = rng.randrange(n_files), rng.randrange(n_nodes)
        if op == 0:
            dps.add_replica(fid, node)
        elif op == 1:
            dps.remove_replica(fid, node)
        elif op == 2:
            dps.drop_node(node)
        elif op == 3:                       # slot transition
            walked = dps.source_files_walked
            if node in free:
                free.discard(node)
                dps.note_source_busy(node)
            else:
                free.add(node)
                dps.note_source_freed(node)
            assert dps.source_files_walked - walked == len(
                _waited_on(dps, node))
        elif op == 4 and tracked:
            tid = rng.choice(list(tracked))
            plan = dps.plan_cop(tid, tracked[tid], target=node,
                                allowed_sources=free)
            if plan is not None:
                dps.commit_cop(plan)
        elif op == 5:
            tid = rng.randint(0, 6)
            if tid in tracked and rng.random() < 0.5:
                dps.untrack_task(tid)
                del tracked[tid]
            else:
                inputs = tuple(rng.sample(range(n_files),
                                          rng.randint(1, min(4, n_files))))
                dps.track_task(tid, inputs)
                tracked[tid] = inputs
        else:
            dps.register_file(FileSpec(id=fid, size=rng.randint(1, 100),
                                       producer=-1), node)
        _check_source_index(dps, free)


def _waited_on(dps, node):
    """Brute force: files some tracked task waits on with a replica on
    ``node``."""
    return {f for inputs in dps._task_inputs.values() for f in inputs
            if node in dps.locations(f)}


def _source_dps(files, free, n_nodes=4):
    """1-byte files ``{id: holders}`` on ``n_nodes`` nodes, with the
    source-feasibility index synced to ``free``; returns the DPS and the
    mirrored free set, which the caller keeps in step."""
    dps = DataPlacementService(seed=0)
    for fid, holders in files.items():
        dps.register_file(FileSpec(id=fid, size=1, producer=-1), holders[0])
        for n in holders[1:]:
            dps.add_replica(fid, n)
    free = set(free)
    dps.sync_free_sources(free)
    _check_source_index(dps, free)
    return dps, free


def _flip(dps, free, node):
    """One slot transition, mirrored as the scheduler does; returns the
    files it walked."""
    walked = dps.source_files_walked
    if node in free:
        free.discard(node)
        dps.note_source_busy(node)
    else:
        free.add(node)
        dps.note_source_freed(node)
    _check_source_index(dps, free)
    return dps.source_files_walked - walked


def test_first_waiter_counts_free_and_busy_holders():
    """File 0 sits on free nodes 0 and 2 and on busy node 1; file 1 only on
    busy node 1.  The first waiter finds file 0's count at 2 and file 1
    with none, and later slot flips walk only the waited files."""
    dps, free = _source_dps({0: [0, 1, 2], 1: [1], 2: [0]}, {0, 2, 3})
    assert dps._free_rep == {} and dps.source_transitions == 0
    dps.track_task(7, (0, 1))
    _check_source_index(dps, free)
    assert dps._free_rep == {0: 2}
    assert dps._node_wfiles[1] == {0, 1} and dps._node_wfiles[0] == {0}
    assert dps._unsourced[7] == 1 and dps.cop_blocked(7)
    assert _flip(dps, free, 1) == 2             # node 1 frees: files 0, 1
    assert dps._free_rep == {0: 3, 1: 1} and not dps.cop_blocked(7)
    assert _flip(dps, free, 0) == 1             # file 2 is not waited
    assert _flip(dps, free, 2) == 1
    assert dps._free_rep == {0: 1, 1: 1}
    assert dps.source_transitions == 3


def test_last_waiter_leaves_and_a_new_one_sees_unseen_flips():
    """File 0 loses its last waiter, then slots flip on both of its holders
    unseen: a new waiter's count is computed from the locations alone."""
    dps, free = _source_dps({0: [0, 1]}, {0, 1})
    dps.track_task(1, (0,))
    dps.track_task(2, (0,))
    assert dps._free_rep == {0: 2}
    dps.untrack_task(1)
    assert dps._free_rep == {0: 2}              # task 2 still waits
    dps.untrack_task(2)
    _check_source_index(dps, free)
    assert dps._free_rep == {} and not any(dps._node_wfiles.values())
    for node in (0, 1, 0):                      # busy 0, busy 1, free 0
        assert _flip(dps, free, node) == 0
    dps.track_task(3, (0,))
    _check_source_index(dps, free)
    assert dps._free_rep == {0: 1} and not dps.cop_blocked(3)
    assert _flip(dps, free, 0) == 1
    assert dps.cop_blocked(3)


def test_unwaited_replica_writes_on_a_free_node_keep_no_count():
    """Adding or removing a replica of an unwaited file on a free node
    touches no count; the same write to a waited file does."""
    dps, free = _source_dps({0: [0], 1: [0]}, {0, 1})
    dps.track_task(5, (1,))
    dps.add_replica(0, 1)
    _check_source_index(dps, free)
    assert 0 not in dps._free_rep and 1 not in dps._node_wfiles
    dps.add_replica(1, 1)
    _check_source_index(dps, free)
    assert dps._free_rep[1] == 2 and 1 in dps._node_wfiles[1]
    dps.remove_replica(0, 0)
    dps.remove_replica(1, 0)
    _check_source_index(dps, free)
    assert dps._free_rep == {1: 1}
    dps.track_task(6, (0,))                     # only node 1 holds file 0
    _check_source_index(dps, free)
    assert dps._free_rep == {0: 1, 1: 1}


def test_sync_after_tracking_indexes_the_waited_files():
    """Activating the index over tasks tracked before it counts their files
    and no others."""
    dps = DataPlacementService(seed=0)
    for fid, node in ((0, 0), (1, 1), (2, 1)):
        dps.register_file(FileSpec(id=fid, size=1, producer=-1), node)
    dps.track_task(4, (0, 1))
    free = {1, 2}
    dps.sync_free_sources(free)
    _check_source_index(dps, free)
    assert dps._free_rep == {1: 1}
    assert dps._node_wfiles == {0: {0}, 1: {1}}
    assert dps.cop_blocked(4)
    assert _flip(dps, free, 0) == 1 and not dps.cop_blocked(4)


def test_drop_node_with_waited_and_unwaited_files():
    """Node 0 holds waited files 0 (also on node 1) and 2 (its only copy)
    and unwaited file 1.  Dropping it, then marking its slot busy as the
    scheduler does, leaves file 0 sourced from node 1 and task 9 blocked
    on the lost file 2."""
    dps, free = _source_dps({0: [0, 1], 1: [0, 2], 2: [0]}, {0, 1, 2})
    dps.track_task(8, (0,))
    dps.track_task(9, (0, 2))
    assert dps._free_rep == {0: 2, 2: 1}
    assert dps.drop_node(0) == [2]
    _check_source_index(dps, free)
    assert 0 not in dps._node_wfiles
    assert dps._free_rep == {0: 1}
    assert _flip(dps, free, 0) == 0
    assert not dps.cop_blocked(8) and dps.cop_blocked(9)
    assert dps._node_wfiles == {1: {0}}


# -------------------------------------- scheduler visit orders vs snapshot
def _scheduler_oracle_orders(sched):
    """Reference semantics: sort the whole data-bound backlog under both
    step keys, keeping only tasks with every input sourceable from a
    free-slot node (any unsourced input makes the probe provably fail)."""
    dps = sched.dps
    free = sched._free_slot_nodes

    def unsourced(t):
        return sum(1 for f in set(t.inputs)
                   if not (dps.locations(f) & free))

    waiting = [t for t in sched.ready.values() if t.inputs]
    eligible = [t for t in waiting if unsourced(t) == 0]
    o2 = [t.id for t in sorted(
        eligible, key=lambda t: (dps.prep_count(t.id),
                                 sched.cops_per_task.get(t.id, 0),
                                 -t.priority, t.id))]
    o3 = [t.id for t in sorted(eligible, key=lambda t: (-t.priority, t.id))]
    blocked = [t for t in waiting if unsourced(t) > 0]
    return o2, o3, blocked


def _random_stream_scheduler(seed, n_nodes=5, steps=80):
    rng = random.Random(seed)
    nodes = {i: NodeState(i, 8 * GiB, 8.0) for i in range(n_nodes)}
    dps = DataPlacementService(seed=seed)
    sched = WowScheduler(nodes, dps, c_node=1, c_task=2)
    next_file, next_task = 0, 0

    def new_file():
        nonlocal next_file
        dps.register_file(FileSpec(id=next_file, size=rng.randint(1, 4),
                                   producer=-1), rng.randrange(n_nodes))
        next_file += 1
        return next_file - 1

    for f in range(4):
        new_file()
    for step in range(steps):
        op = rng.randrange(4)
        if op == 0:                                   # submit a task
            k = rng.randint(1, min(3, next_file))
            inputs = tuple(rng.sample(range(next_file), k))
            sched.submit(TaskSpec(
                id=next_task, abstract="a",
                mem=rng.randint(1, 5) * GiB, cores=float(rng.randint(1, 6)),
                inputs=inputs, priority=rng.uniform(1, 10)))
            next_task += 1
        elif op == 1 and sched.running:               # finish a task
            tid = rng.choice(list(sched.running))
            sched.on_task_finished(tid, sched.running[tid])
        elif op == 2 and sched.active_cops:           # finish a COP
            cid = rng.choice(list(sched.active_cops))
            sched.on_cop_finished(sched.active_cops[cid],
                                  ok=rng.random() < 0.9)
        else:
            new_file()
        sched.schedule()
        yield sched


@pytest.mark.parametrize("seed", range(8))
def test_scheduler_visit_orders_match_snapshot_sort(seed):
    """The indexed ready-set must yield the same step-2/3 visit order as a
    from-scratch sort of every snapshot, and every task it parks as
    blocked must fail its probe."""
    for sched in _random_stream_scheduler(seed):
        sched._sync_ready_index()
        o2, o3, blocked = _scheduler_oracle_orders(sched)
        assert sched._ready_index.step2_order() == o2
        assert sched._ready_index.step3_order() == o3
        for t in blocked:
            assert sched._ready_index.is_blocked(t.id)
            _feas, pool = sched._cop_target_pool(t)
            assert not pool, \
                "indexed ready-set parked a task with a feasible probe"


# ------------------------------------------------- input-less fast path
def _summarize(actions):
    out = []
    for a in actions:
        if isinstance(a, StartTask):
            out.append(("task", a.task_id, a.node))
        elif isinstance(a, StartCop):
            out.append(("cop", a.plan.task_id, a.plan.target))
    return out


def _drive_mixed_pair(seed, n_nodes=4, steps=60):
    """Randomized capacity-tight stream mixing input-less and data-bound
    submissions, replayed identically against both scheduler cores."""
    def build():
        nodes = {i: NodeState(i, 8 * GiB, 8.0) for i in range(n_nodes)}
        dps = DataPlacementService(seed=seed)
        return nodes, dps

    nodes_a, dps_a = build()
    nodes_b, dps_b = build()
    new = WowScheduler(nodes_a, dps_a)
    ref = ReferenceWowScheduler(nodes_b, dps_b)
    rng = random.Random(seed)
    next_file, next_task = 0, 0
    for step in range(steps):
        op = rng.randrange(5)
        if op in (0, 1):                              # submit (often)
            # shapes sized so nodes hold ~2 tasks: backlogs persist and
            # input-less + data-bound tasks compete for capacity (the
            # mixed events that exercise the joint-solve fallback)
            mem = rng.randint(2, 5) * GiB
            cores = float(rng.randint(2, 6))
            if rng.random() < 0.5:
                inputs: tuple[int, ...] = ()
            else:
                size = rng.randint(1, 4)
                host = rng.randrange(n_nodes)
                for dps in (dps_a, dps_b):
                    dps.register_file(
                        FileSpec(id=next_file, size=size, producer=-1), host)
                inputs = (next_file,)
                next_file += 1
            prio = rng.uniform(1, 10)
            for sched in (new, ref):
                sched.submit(TaskSpec(id=next_task, abstract="a", mem=mem,
                                      cores=cores, inputs=inputs,
                                      priority=prio))
            next_task += 1
        elif op == 2 and new.running:                 # finish a task
            tid = rng.choice(sorted(new.running))
            assert new.running[tid] == ref.running[tid]
            new.on_task_finished(tid, new.running[tid])
            ref.on_task_finished(tid, ref.running[tid])
        elif op == 3 and new.active_cops:             # finish a COP
            cid = rng.choice(sorted(new.active_cops))
            new.on_cop_finished(new.active_cops[cid], ok=True)
            ref.on_cop_finished(ref.active_cops[cid], ok=True)
        else:                                         # elastic join
            if len(nodes_a) < n_nodes + 2 and rng.random() < 0.3:
                nid = max(nodes_a) + 1
                nodes_a[nid] = NodeState(nid, 8 * GiB, 8.0)
                nodes_b[nid] = NodeState(nid, 8 * GiB, 8.0)
                new.note_node_added(nid)
                ref.note_node_added(nid)
        a_new = _summarize(new.schedule())
        a_ref = _summarize(ref.schedule())
        assert a_new == a_ref, f"diverged at step {step}: {a_new} != {a_ref}"


@pytest.mark.parametrize("seed", range(10))
def test_inputless_fast_path_parity_with_reference(seed):
    """Capacity-tight mixed input-less/data-bound streams: the fast path
    (and its joint-solve fallback on mixed events) must keep decisions
    bit-identical to the reference scheduler."""
    _drive_mixed_pair(seed)


@pytest.mark.parametrize("seed", range(6))
def test_shape_index_matches_bruteforce(seed):
    """ShapeIndex buckets == from-scratch grouping + sort of a shadow dict
    under random add/discard/resubmit streams."""
    rng = random.Random(700 + seed)
    idx = ShapeIndex()
    shadow: dict[int, tuple[int, float, float]] = {}  # tid -> mem,cores,prio
    shapes = [(2 * GiB, 2.0), (2 * GiB, 4.0), (6 * GiB, 2.0)]
    for step in range(200):
        op = rng.random()
        tid = rng.randrange(40)
        if op < 0.55:
            mem, cores = rng.choice(shapes)
            prio = rng.choice([1.0, 2.5, 2.5, rng.uniform(0, 10)])
            idx.add(tid, mem, cores, prio)      # resubmission replaces
            shadow[tid] = (mem, cores, prio)
        else:
            idx.discard(tid)                    # idempotent
            shadow.pop(tid, None)
        assert len(idx) == len(shadow)
        groups: dict[tuple, list] = {}
        for t, (m, c, p) in shadow.items():
            groups.setdefault((m, c), []).append((-p, t))
        assert set(idx.shapes()) == set(groups)
        for shape, expect in groups.items():
            assert idx.group(shape) == sorted(expect)
            assert idx.tasks_of(shape) == [t for _, t in sorted(expect)]
        for t in shadow:
            assert t in idx
            assert idx.shape_of(t) == shadow[t][:2]


def _drive_inputless_pair(seed, n_nodes, steps, shapes, n_ready=0):
    """Pure input-less streams (optionally pre-filled backlog) replayed
    against both scheduler cores; multiple shapes exercise the multi-shape
    fallback, a large single-shape backlog the uniform greedy branch."""
    nodes_a = {i: NodeState(i, 8 * GiB, 8.0) for i in range(n_nodes)}
    nodes_b = {i: NodeState(i, 8 * GiB, 8.0) for i in range(n_nodes)}
    new = WowScheduler(nodes_a, DataPlacementService(seed=seed))
    ref = ReferenceWowScheduler(nodes_b, DataPlacementService(seed=seed))
    rng = random.Random(seed)
    next_task = 0

    def submit():
        nonlocal next_task
        mem, cores = rng.choice(shapes)
        prio = rng.choice([rng.uniform(1, 10), 5.0])   # priority ties too
        for sched in (new, ref):
            sched.submit(TaskSpec(id=next_task, abstract="a", mem=mem,
                                  cores=cores, inputs=(), priority=prio))
        next_task += 1

    for _ in range(n_ready):
        submit()
    for step in range(steps):
        op = rng.randrange(4)
        if op in (0, 1):
            submit()
        elif op == 2 and new.running:
            tid = rng.choice(sorted(new.running))
            assert new.running[tid] == ref.running[tid]
            new.on_task_finished(tid, new.running[tid])
            ref.on_task_finished(tid, ref.running[tid])
        a_new = _summarize(new.schedule())
        a_ref = _summarize(ref.schedule())
        assert a_new == a_ref, f"diverged at step {step}"
    return new


@pytest.mark.parametrize("seed", range(8))
def test_inputless_multi_shape_parity_with_reference(seed):
    """2-3 distinct shapes whose fitting-node sets overlap: the shape
    components collapse to one, taking the generic (cached ilp.solve)
    tier -- decisions must match the reference exactly."""
    _drive_inputless_pair(seed, n_nodes=5, steps=50,
                          shapes=[(2 * GiB, 2.0), (2 * GiB, 4.0),
                                  (6 * GiB, 6.0)])


@pytest.mark.parametrize("seed", range(4))
def test_inputless_uniform_greedy_parity_with_reference(seed):
    """A single-shape backlog past the exact gate (> 24 tasks, > 64
    candidate slots): the analytic uniform fast path must reproduce the
    reference's greedy assignment bit-for-bit."""
    sched = _drive_inputless_pair(seed, n_nodes=16, steps=25,
                                  shapes=[(3 * GiB, 3.0)], n_ready=60)
    assert sched.inputless_stats["fast_solves"] > 0, (
        "uniform fast path never fired -- gate sizing drifted?")


def test_inputless_fingerprint_cache_hits_recurring_fanout():
    """Steady-state fan-out with quantized priorities: after a task of a
    shape is placed, finishes, and an identical task (same shape/priority,
    same id rank, same node capacities) arrives, the capacity subproblem
    is id-relative-isomorphic to the previous event's -- the fingerprint
    cache must answer it without re-solving."""
    nodes = {0: NodeState(0, 8 * GiB, 8.0)}
    sched = WowScheduler(nodes, DataPlacementService())
    for tid in (100, 101):
        sched.submit(TaskSpec(id=tid, abstract="a", mem=8 * GiB, cores=8.0,
                              inputs=(), priority=5.0))
        actions = sched.schedule()
        assert _summarize(actions) == [("task", tid, 0)]
        sched.on_task_finished(tid, 0)
    assert sched.inputless_stats["cache_misses"] == 1
    assert sched.inputless_stats["cache_hits"] == 1


def test_inputless_fast_path_exercised():
    """White-box: a pure input-less backlog must be solved without the
    incremental solver's component machinery seeing any of it."""
    nodes = {i: NodeState(i, 8 * GiB, 8.0) for i in range(4)}
    sched = WowScheduler(nodes, DataPlacementService())
    for t in range(10):
        sched.submit(TaskSpec(id=t, abstract="a", mem=4 * GiB, cores=4.0,
                              inputs=(), priority=float(t)))
    actions = sched.schedule()
    assert len([a for a in actions if isinstance(a, StartTask)]) == 8
    assert sched._solver.stats["comps_rebuilt"] == 0
    assert not sched._solver._comp_tasks       # nothing welded
    # leftover backlog is re-examined only when capacity changes
    assert not sched._less_stale
    tid = next(iter(sched.running))
    sched.on_task_finished(tid, sched.running[tid])
    started = [a for a in sched.schedule() if isinstance(a, StartTask)]
    assert len(started) == 1


# ------------------------------------------------- canonical node order
def test_non_ascending_node_enumeration_matches_reference():
    """Node dicts enumerated out of ascending-id order: the canonical
    node-order object must keep the incremental scheduler bit-identical to
    the reference's dict scans (the old sorted(self.nodes) did not)."""
    ids = [3, 0, 2, 1]
    for seed in range(5):
        rng = random.Random(seed)

        def build(cls):
            nodes = {i: NodeState(i, 8 * GiB, 8.0) for i in ids}
            order = NodeOrder(nodes)
            dps = DataPlacementService(seed=seed, node_order=order)
            return cls(nodes, dps, node_order=order), dps

        new, dps_a = build(WowScheduler)
        ref, dps_b = build(ReferenceWowScheduler)
        for t in range(30):
            host = rng.choice(ids)
            for dps in (dps_a, dps_b):
                dps.register_file(FileSpec(id=t, size=rng.randint(1, 4),
                                           producer=-1), host)
            spec = dict(id=t, abstract="a", mem=rng.randint(1, 4) * GiB,
                        cores=float(rng.randint(1, 4)), inputs=(t,),
                        priority=rng.uniform(1, 10))
            new.submit(TaskSpec(**spec))
            ref.submit(TaskSpec(**spec))
            a_new = _summarize(new.schedule())
            a_ref = _summarize(ref.schedule())
            assert a_new == a_ref
            if new.running and rng.random() < 0.5:
                tid = rng.choice(sorted(new.running))
                new.on_task_finished(tid, new.running[tid])
                ref.on_task_finished(tid, ref.running[tid])
            if new.active_cops and rng.random() < 0.5:
                cid = rng.choice(sorted(new.active_cops))
                new.on_cop_finished(new.active_cops[cid])
                ref.on_cop_finished(ref.active_cops[cid])


def test_rejoin_under_old_node_id_matches_reference():
    """A failed node re-joining under its *old (lower) id* lands last in
    enumeration order; with the engine-owned node order both scheduler
    cores must still make identical decisions (this is exactly the case
    the old ascending-id convention could not express)."""
    def scenario(cfg):
        wf = make_workflow("group", scale=0.3)
        sim = Simulation(wf, cfg, "wow")
        sim.schedule_failure(25.0, node=0)
        sim.schedule_join(60.0, node_id=0)
        res = sim.run()
        return sim, res

    sim_new, res_new = scenario(SimConfig())
    sim_ref, res_ref = scenario(SimConfig(reference_core=True))
    assert [(k, t, n) for _, k, t, n in sim_new.action_log] \
        == [(k, t, n) for _, k, t, n in sim_ref.action_log]
    assert res_new.makespan == res_ref.makespan
    assert list(sim_new.node_order)[-1] == 0     # rejoined id enumerates last


# ------------------------------------------------- failure: orig / cws
@pytest.mark.parametrize("strategy", ["orig", "cws", "wow"])
def test_failure_and_join_smoke_all_strategies(strategy):
    """Node failure + elastic join must complete the workflow under every
    strategy (previously only WOW supported failure injection)."""
    wf = make_workflow("group", scale=0.25)
    sim = Simulation(wf, SimConfig(), strategy)
    sim.schedule_failure(30.0, node=1)
    sim.schedule_join(45.0, node_id=8)
    res = sim.run()
    assert res.tasks_total == len(wf.tasks)
    assert 1 in sim.failed_nodes
    assert 1 not in sim.nodes and 8 in sim.nodes


@pytest.mark.parametrize("strategy", ["orig", "cws"])
def test_failure_flow_refactor_equivalence(strategy):
    """Under node churn, the heap-driven FlowManager must produce the same
    virtual timeline as the reference for the baseline strategies."""
    def scenario(cfg):
        wf = make_workflow("group", scale=0.25)
        sim = Simulation(wf, cfg, strategy)
        sim.schedule_failure(30.0, node=1)
        sim.schedule_join(45.0, node_id=8)
        return sim.run()

    res_new = scenario(SimConfig())
    res_ref = scenario(SimConfig(reference_flow=True))
    assert res_new.tasks_total == res_ref.tasks_total
    assert res_new.makespan == pytest.approx(res_ref.makespan, rel=1e-9)
