"""Batched COP drain (core/copmatrix.py): mirror + bit-parity test campaign.

Layers of proof that ``batched=True`` changes nothing but speed:

* **matrix mirror property test** -- a randomized DPS mutation stream
  (register/replica add+remove/track/untrack/node drop/invalidate/gc);
  after every event the ``CopMatrix`` must equal the dict indices
  cell-for-cell (``check_against``), including column recycling after
  ``drop_node``.
* **kernel unit surface** -- null-column gathers read 0 like
  ``dict.get(node, 0)``; untracked tasks return the oracle-fallback
  sentinels; ``SlotColMap`` rebuilds exactly when a version counter moves;
  ``batched=True`` without ``vectorized`` refuses loudly.
* **full-sim bit-identity** -- actions (``sim.action_log``), makespans and
  event counts identical for blocked vs per-task drain across workloads,
  with churn (failure + elastic join), under a hierarchical topology, and
  against the frozen reference core; plus a randomized property sweep.
* **jax twin** -- the jitted winner reduction picks the same nodes as the
  staged numpy reduction, one-ulp near ties included, and leaves the
  process-wide x64 flag unchanged (skipped when jax is unavailable).
"""
from __future__ import annotations

import random

import pytest

from repro.core import (DataPlacementService, FileSpec, NodeState, TaskSpec,
                        WowScheduler)
from repro.core.copmatrix import HAVE_NUMPY

from _hyp import given, settings, st

pytestmark = pytest.mark.skipif(
    not HAVE_NUMPY, reason="numpy not available: the batched drain is off "
                           "and the dict oracle is covered elsewhere")

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
def _mini_sched(batched=True, n_nodes=4):
    nodes = {i: NodeState(i, 8 * GiB, 8.0) for i in range(n_nodes)}
    dps = DataPlacementService(seed=0)
    sched = WowScheduler(nodes, dps, batched=batched)
    return sched, dps, nodes


def test_batched_requires_vectorized():
    nodes = {0: NodeState(0, 8 * GiB, 8.0)}
    with pytest.raises(RuntimeError):
        WowScheduler(nodes, DataPlacementService(seed=0),
                     vectorized=False, batched=True)


def test_batched_defaults_on_with_vectorized():
    sched, _, _ = _mini_sched(batched=None)
    assert sched.batched and sched._kernel is not None
    nodes = {0: NodeState(0, 8 * GiB, 8.0)}
    off = WowScheduler(nodes, DataPlacementService(seed=0), vectorized=False)
    assert not off.batched and off._kernel is None


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
def _sim_run(batched, *, workflow="group", scale=0.6, n_nodes=14, seed=0,
             churn=False, topology=None, dfs="ceph"):
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    wf = make_workflow(workflow, scale=scale, seed=seed)
    sim = Simulation(wf, SimConfig(n_nodes=n_nodes, dfs=dfs, seed=seed,
                                   batched=batched, topology=topology),
                     "wow")
    if churn:
        sim.schedule_failure(15.0, 3)
        sim.schedule_join(30.0, n_nodes)
    r = sim.run()
    return sim.action_log, r.makespan, r.sim_steps, r.cops_created


@pytest.mark.parametrize("workflow", ["group", "fork", "syn_montage",
                                      "chipseq"])
@pytest.mark.parametrize("churn", [False, True])
def test_full_sim_bit_identity(workflow, churn):
    a = _sim_run(False, workflow=workflow, churn=churn)
    b = _sim_run(None, workflow=workflow, churn=churn)   # auto: blocked
    assert a == b


def test_full_sim_bit_identity_topology():
    from repro.sim import TopologySpec
    topo = TopologySpec(rack_size=4, racks_per_site=2)
    for churn in (False, True):
        a = _sim_run(False, topology=topo, churn=churn)
        b = _sim_run(None, topology=topo, churn=churn)
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


@pytest.mark.parametrize("batched", [None, False], ids=["blocked", "dict"])
@pytest.mark.parametrize("workflow,scale", [("sarek", 0.3),
                                            ("rangeland", 0.1)])
def test_pretest_matches_reference_core(workflow, scale, batched):
    """The drain's emptiness pre-test against the frozen reference, on the
    paper's 8-node testbed with one COP per node, where scarce slots and
    cores leave most visited tasks without a candidate: the skips must be
    real in both steps and change no decision."""
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    runs = {}
    skipped = {2: 0, 3: 0}
    for ref in (False, True):
        cfg = SimConfig(n_nodes=8, c_node=1, reference_core=ref,
                        vectorized=None if batched is None else False,
                        batched=batched)
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
    and per-task drains must agree action-for-action."""
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
    assert _sim_run(False, **kw) == _sim_run(None, **kw)


# ----------------------------------------------------------------- jax twin
def test_jax_winner_matches_numpy():
    """Whole-sim parity of the jax twin, which must also leave the
    process-wide x64 flag as it found it (x64 is scoped to each call)."""
    jax = pytest.importorskip("jax")
    from repro.sim import SimConfig, Simulation
    from repro.workloads import make_workflow

    before = jax.config.jax_enable_x64
    a = _sim_run(True, workflow="group", scale=0.4, n_nodes=10)
    b = _sim_run("jax", workflow="group", scale=0.4, n_nodes=10)
    assert a == b
    assert jax.config.jax_enable_x64 == before
    sim = Simulation(make_workflow("group", scale=0.4),
                     SimConfig(n_nodes=10, batched="jax"), "wow")
    sim.run()
    stats = sim.strategy.sched.device_stats
    assert stats["dispatches"] > 0
    assert stats["platforms"] == [jax.devices()[0].platform]
    assert jax.config.jax_enable_x64 == before


def _winner_oracle(key, ids):
    import numpy as np
    m0 = key.min()
    return int(np.where(key == m0, ids, np.iinfo(np.int64).max).min())


def test_jax_winner_padding_unit():
    pytest.importorskip("jax")
    import numpy as np
    from repro.core.copmatrix import JaxWinner
    winner = JaxWinner()
    rng = np.random.default_rng(0)
    for n in (1, 3, 7, 16, 33):
        key = rng.integers(0, 5, n).astype(np.float64)
        ids = rng.permutation(n).astype(np.int64)
        assert winner(key, ids) == _winner_oracle(key, ids)
        ikey = rng.integers(0, 5, n).astype(np.int64)
        assert winner(ikey, ids) == _winner_oracle(ikey, ids)
    assert winner.dispatches == 10


def test_jax_winner_near_ties():
    """Keys one ulp apart stay apart and exact ties split by id -- the
    cases the TPU's emulated f64 comparison got wrong."""
    pytest.importorskip("jax")
    import numpy as np
    from repro.core.copmatrix import JaxWinner
    winner = JaxWinner()
    rng = np.random.default_rng(1)
    for trial in range(20):
        n = 64
        base = rng.uniform(1e9, 1e11)
        key = np.full(n, 3 * base)
        idx = rng.choice(n, 6, replace=False)
        key[idx[:3]] = base
        key[idx[3:]] = np.nextafter(base, np.inf)
        if trial % 2:
            key[idx[0]] = np.nextafter(base, -np.inf)
        key[rng.choice(n, 8, replace=False)] = np.inf
        ids = rng.permutation(n).astype(np.int64)
        assert winner(key, ids) == _winner_oracle(key, ids)


def test_ordered_int64_keeps_order_and_ties():
    import numpy as np
    from repro.core.copmatrix import ordered_int64
    rng = np.random.default_rng(2)
    base = rng.normal(size=200) * 10.0 ** rng.integers(-5, 12, 200)
    vals = np.concatenate([
        base, np.nextafter(base, np.inf), np.nextafter(base, -np.inf),
        [0.0, -0.0, np.inf, -np.inf, 5e-324, -5e-324, 1.0, 1.0]])
    ik = ordered_int64(vals)
    assert ik.dtype == np.int64
    assert ((ik[:, None] < ik[None, :])
            == (vals[:, None] < vals[None, :])).all()
    assert ((ik[:, None] == ik[None, :])
            == (vals[:, None] == vals[None, :])).all()
