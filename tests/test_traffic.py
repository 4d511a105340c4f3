"""Open-loop multi-tenant traffic layer: bit-identity, determinism and
service-metric test campaign.

Covers the traffic PR's guarantees:

* **bit-identity** -- with traffic absent *or* a disabled ``TrafficConfig``,
  every pre-PR golden (``tests/data/churn_goldens.json``: 3 strategies x
  2 DFS x 2 workflows) reproduces exactly, and the bench configurations
  (dfs_churn with failure injection, sim_throughput smoke) match the
  action goldens captured pre-change in ``tests/data/traffic_goldens.json``;
* **determinism + parity** -- the arrival schedule is a pure function of
  the ``TrafficConfig`` (same seed => identical stream), a full traffic
  run replays bit-identically (action log and ``TrafficResult``), and the
  wow strategy agrees with the frozen reference core under traffic;
* **admission semantics** -- arrivals are conserved (admitted + rejected
  == schedule length) and nothing is silently dropped: every admitted
  instance either completes or is reported in ``incomplete`` with a
  reason;
* **metrics** -- windowed p50/p99, per-tenant and fairness aggregates
  match brute-force recomputation on randomized synthetic event streams;
  ``gini`` obeys its textbook O(n^2) definition plus scale invariance;
  ``percentile`` matches the count-based nearest-rank definition;
* **namespacing** -- ``Workflow.namespaced`` rebases ids and prefixes
  abstract names without structural damage, and ``Workflow.validate``
  rejects every fuzzed mutation class (double-produced file, cycle,
  unproduced input, inconsistent consumer set).
"""
import dataclasses
import hashlib
import json
import math
import os
import random

import pytest
from _hyp import given, settings, st

from repro.sim import (SimConfig, Simulation, TenantSpec, TrafficConfig,
                       arrival_schedule, compute_traffic_result, gini, jain,
                       percentile, run_traffic)
from repro.sim.traffic import InstanceRecord
from repro.workloads import make_workflow

_DATA = os.path.join(os.path.dirname(__file__), "data")
with open(os.path.join(_DATA, "churn_goldens.json")) as _f:
    CHURN_GOLDENS = json.load(_f)["scenarios"]
with open(os.path.join(_DATA, "traffic_goldens.json")) as _f:
    TRAFFIC_GOLDENS = json.load(_f)["scenarios"]

_SCALES = {"group": 0.25, "chain": 0.3}

DISABLED = TrafficConfig(tenants=(TenantSpec("t"),), enabled=False)


def _small_traffic(seed=0, n_arrivals=8, max_backlog=None, process="poisson",
                   rate=0.05):
    return TrafficConfig(
        tenants=(TenantSpec("alice", weight=2.0, workflows=("chain", "fork"),
                            scale=0.05, slo=300.0),
                 TenantSpec("bob", weight=1.0, workflows=("group",),
                            scale=0.05, slo=400.0)),
        rate=rate, n_arrivals=n_arrivals, process=process,
        max_backlog=max_backlog, window=30.0, seed=seed)


# ------------------------------------------------------------- bit-identity
@pytest.mark.parametrize("key", sorted(CHURN_GOLDENS))
@pytest.mark.parametrize("mode", ["absent", "disabled"])
def test_disabled_traffic_reproduces_churn_goldens(key, mode):
    """The traffic plumbing must be invisible when off: both ``traffic=None``
    and a disabled ``TrafficConfig`` reproduce the pre-PR goldens bit for
    bit (action log hash, makespan repr, network-bytes repr)."""
    wf_name, strategy, dfs = key.split(":")
    wf = make_workflow(wf_name, scale=_SCALES[wf_name])
    sim = Simulation(wf, SimConfig(dfs=dfs), strategy,
                     traffic=None if mode == "absent" else DISABLED)
    res = sim.run()
    g = CHURN_GOLDENS[key]
    assert len(sim.action_log) == g["n_actions"]
    assert hashlib.sha256(
        repr(sim.action_log).encode()).hexdigest() == g["action_log_sha256"]
    assert repr(res.makespan) == g["makespan"]
    assert repr(res.network_bytes) == g["network_bytes"]
    assert sim.traffic is None            # disabled config is normalized away


@pytest.mark.parametrize("strategy", ["orig", "cws", "wow"])
def test_dfs_churn_bench_rows_action_identical(strategy):
    """The dfs_churn bench configuration (group@0.25, ceph rep=2, failure at
    t=30 on node 1) produces the exact pre-PR action stream."""
    wf = make_workflow("group", scale=0.25)
    sim = Simulation(wf, SimConfig(dfs="ceph", ceph_replication=2), strategy)
    sim.schedule_failure(30.0, 1)
    res = sim.run()
    g = TRAFFIC_GOLDENS[f"dfs_churn:{strategy}"]
    assert len(sim.action_log) == g["n_actions"]
    assert hashlib.sha256(
        repr(sim.action_log).encode()).hexdigest() == g["action_log_sha256"]
    assert repr(res.makespan) == g["makespan"]
    assert repr(res.network_bytes) == g["network_bytes"]


@pytest.mark.parametrize("strategy", ["orig", "cws", "wow"])
def test_sim_throughput_bench_rows_action_identical(strategy):
    """The sim_throughput smoke row (group@2.56, 256 nodes, heap fill) is
    action-identical to the pre-PR capture -- the arrival-event plumbing
    must not perturb the single-workflow event order."""
    wf = make_workflow("group", scale=2.56)
    sim = Simulation(wf, SimConfig(n_nodes=256, dfs="ceph",
                                   flow_fill="heap"), strategy)
    res = sim.run()
    g = TRAFFIC_GOLDENS[f"sim_throughput:{strategy}"]
    assert len(sim.action_log) == g["n_actions"]
    assert hashlib.sha256(
        repr(sim.action_log).encode()).hexdigest() == g["action_log_sha256"]
    assert repr(res.makespan) == g["makespan"]
    assert res.sim_steps == g["sim_steps"]


# ------------------------------------------------- determinism & parity
@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000), st.floats(0.01, 2.0),
       st.sampled_from(["poisson", "diurnal"]))
def test_arrival_schedule_pure_function_of_config(seed, rate, process):
    cfg = _small_traffic(seed=seed, rate=rate, process=process,
                         n_arrivals=30)
    s1, s2 = arrival_schedule(cfg), arrival_schedule(cfg)
    assert s1 == s2
    assert len(s1) == 30
    times = [a.time for a in s1]
    assert times == sorted(times) and times[0] > 0
    names = {t.name for t in cfg.tenants}
    assert all(a.tenant in names for a in s1)
    assert all(a.index == i for i, a in enumerate(s1))


def test_arrival_schedule_horizon_and_seed_sensitivity():
    cfg = _small_traffic(seed=1, n_arrivals=50)
    full = arrival_schedule(cfg)
    cut = arrival_schedule(dataclasses.replace(cfg, horizon=full[24].time))
    assert len(cut) <= 25 and cut == full[:len(cut)]
    other = arrival_schedule(dataclasses.replace(cfg, seed=2))
    assert other != full


@settings(max_examples=4, deadline=None)
@given(st.sampled_from(["orig", "cws", "wow"]), st.integers(0, 999))
def test_traffic_run_replays_bit_identically(strategy, seed):
    """Same seed => identical action log and TrafficResult across two
    independent engine instances (instances list included)."""
    tr = _small_traffic(seed=seed, max_backlog=4)
    logs, results = [], []
    for _ in range(2):
        cfg = SimConfig(n_nodes=16)
        sim = Simulation(None, cfg, strategy, traffic=tr)
        sim.run()
        logs.append(repr(sim.action_log))
        results.append(sim.traffic_result())
    assert logs[0] == logs[1]
    assert dataclasses.asdict(results[0]) == dataclasses.asdict(results[1])


def test_traffic_vectorized_parity():
    """wow and the frozen reference core agree under traffic."""
    tr = _small_traffic(seed=3, max_backlog=4)
    outs = {}
    for ref in (False, True):
        sim = Simulation(None, SimConfig(n_nodes=16, reference_core=ref),
                         "wow", traffic=tr)
        sim.run()
        outs[ref] = (repr(sim.action_log),
                     dataclasses.asdict(sim.traffic_result()))
    assert outs[False] == outs[True]


def test_arrival_stream_identical_across_strategies():
    """All strategies consume the same admission-relevant stream: per-tenant
    arrivals (admitted + rejected) match the pure schedule exactly."""
    tr = _small_traffic(seed=5, n_arrivals=10, max_backlog=3)
    sched = arrival_schedule(tr)
    per_tenant_expected = {t.name: sum(1 for a in sched if a.tenant == t.name)
                           for t in tr.tenants}
    for strategy in ("orig", "cws", "wow"):
        _, tres = run_traffic(tr, strategy, n_nodes=16)
        assert tres.arrivals == len(sched)
        assert tres.admitted + tres.rejected == len(sched)
        for name, n in per_tenant_expected.items():
            assert tres.per_tenant[name]["arrivals"] == n


@settings(max_examples=4, deadline=None)
@given(st.sampled_from(["orig", "cws", "wow"]), st.integers(2, 6))
def test_admission_gate_never_silently_starves(strategy, backlog):
    """Every admitted instance either completes or is reported in
    ``incomplete`` with a reason; the gate itself only ever rejects at
    arrival time (rejected == arrivals - admitted)."""
    tr = _small_traffic(seed=11, n_arrivals=10, max_backlog=backlog)
    _, tres = run_traffic(tr, strategy, n_nodes=16)
    assert tres.admitted + tres.rejected == tres.arrivals
    assert tres.completed + len(tres.incomplete) == tres.admitted
    for row in tres.incomplete:
        assert row["reason"]
    # live backlog never exceeded the gate: depth samples are capped
    assert all(r["latency"] is None or r["latency"] >= 0
               for r in tres.instances)


def test_backpressure_gate_binds_and_unlimited_admits_all():
    tr = _small_traffic(seed=4, n_arrivals=12, max_backlog=2, rate=0.5)
    _, gated = run_traffic(tr, "orig", n_nodes=8)
    assert gated.rejected > 0
    _, open_ = run_traffic(dataclasses.replace(tr, max_backlog=None),
                           "orig", n_nodes=8)
    assert open_.rejected == 0 and open_.admitted == open_.arrivals


def test_traffic_config_validation():
    t = (TenantSpec("a"),)
    with pytest.raises(ValueError):
        TrafficConfig(tenants=())
    with pytest.raises(ValueError):
        TrafficConfig(tenants=t, process="weekly")
    with pytest.raises(ValueError):
        TrafficConfig(tenants=t, rate=0.0)
    with pytest.raises(ValueError):
        TrafficConfig(tenants=t, diurnal_amplitude=1.0)


# ----------------------------------------------------- metrics brute force
def _brute_percentile(xs, q):
    if not xs:
        return None
    xs = sorted(xs)
    k = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[k - 1]


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 40), st.integers(0, 10_000),
       st.sampled_from([50.0, 90.0, 99.0, 100.0]))
def test_percentile_matches_count_definition(n, seed, q):
    rng = random.Random(seed)
    xs = [rng.uniform(0, 100) for _ in range(n)]
    p = percentile(xs, q)
    assert p == _brute_percentile(xs, q)
    if xs:
        # nearest-rank: p is the smallest value covering >= q% of the mass
        assert sum(1 for x in xs if x <= p) >= math.ceil(q / 100.0 * n)
        assert p in xs


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 30), st.integers(0, 10_000), st.floats(0.1, 1000.0))
def test_gini_textbook_definition_and_properties(n, seed, k):
    rng = random.Random(seed)
    xs = [rng.uniform(0, 10) for _ in range(n)]
    g = gini(xs)
    mu = sum(xs) / n
    if mu > 0:
        brute = (sum(abs(a - b) for a in xs for b in xs)
                 / (2.0 * n * n * mu))
        assert abs(g - brute) < 1e-9
        assert abs(gini([k * x for x in xs]) - g) < 1e-9   # scale invariant
    assert 0.0 <= g < 1.0
    assert gini([5.0] * n) == pytest.approx(0.0, abs=1e-12)


@settings(max_examples=25, deadline=None)
@given(st.integers(1, 30), st.integers(0, 10_000))
def test_jain_bounds_and_equal_allocation(n, seed):
    rng = random.Random(seed)
    xs = [rng.uniform(0, 10) for _ in range(n)]
    j = jain(xs)
    assert 0.0 < j <= 1.0 + 1e-12
    assert jain([3.0] * n) == pytest.approx(1.0)
    assert jain([]) == 1.0 and jain([0.0, 0.0]) == 1.0
    # one-hot allocation is the unfairest: 1/n
    assert jain([7.0] + [0.0] * (n - 1)) == pytest.approx(1.0 / n)


def _random_stream(seed, n_tenants=3, n_records=25):
    """A synthetic event stream: InstanceRecords + rejections, no engine."""
    rng = random.Random(seed)
    tenants = tuple(
        TenantSpec(f"t{i}", weight=rng.choice([0.5, 1.0, 2.0]),
                   slo=rng.choice([None, 50.0, 120.0]))
        for i in range(n_tenants))
    cfg = TrafficConfig(tenants=tenants, window=25.0,
                        starvation_factor=3.0, seed=seed)
    records, rejections = [], []
    for i in range(n_records):
        t0 = rng.uniform(0, 200)
        name = tenants[rng.randrange(n_tenants)].name
        if rng.random() < 0.2:
            rejections.append((t0, name))
            continue
        rec = InstanceRecord(id=i, tenant=name, workflow="chain",
                             arrival_t=t0, n_tasks=3,
                             task_ids=frozenset((3 * i, 3 * i + 1)))
        if rng.random() < 0.8:
            rec.completed_t = t0 + rng.uniform(1, 300)
            rec.cpu_seconds = rng.uniform(0, 50)
        records.append(rec)
    end = max([200.0] + [r.completed_t for r in records
                         if r.completed_t is not None])
    return cfg, records, rejections, end


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_traffic_result_matches_brute_force(seed):
    """Windowed p50/p99, per-tenant aggregates and weighted fairness all
    match a from-scratch recomputation over the raw event stream."""
    cfg, records, rejections, end = _random_stream(seed)
    res = compute_traffic_result(cfg, records, rejections, [], end)

    done = [r for r in records if r.completed_t is not None]
    lats = [r.completed_t - r.arrival_t for r in done]
    assert res.arrivals == len(records) + len(rejections)
    assert res.admitted == len(records)
    assert res.completed == len(done)
    assert res.latency_p50 == _brute_percentile(lats, 50)
    assert res.latency_p99 == _brute_percentile(lats, 99)

    # weighted fairness over service/weight, brute-forced
    norm = []
    for t in cfg.tenants:
        service = sum(r.cpu_seconds for r in done if r.tenant == t.name)
        norm.append(service / t.weight)
        pt = res.per_tenant[t.name]
        mine = [r for r in records if r.tenant == t.name]
        mdone = [r for r in mine if r.completed_t is not None]
        assert pt["admitted"] == len(mine)
        assert pt["completed"] == len(mdone)
        assert pt["rejected"] == sum(1 for _, n in rejections if n == t.name)
        assert pt["p99"] == _brute_percentile(
            [r.completed_t - r.arrival_t for r in mdone], 99)
        assert pt["service_cpu_s"] == pytest.approx(service)
        # starvation: blown budget (latency > factor*slo) or never finished
        if t.slo is not None:
            exp = (sum(1 for r in mdone if (r.completed_t - r.arrival_t)
                       > cfg.starvation_factor * t.slo)
                   + (len(mine) - len(mdone)))
        else:
            exp = len(mine) - len(mdone)
        assert pt["starved"] == exp
    assert res.fairness_jain == pytest.approx(jain(norm))
    assert res.fairness_gini == pytest.approx(gini(norm))

    # windowed series: every bucket recomputed from scratch
    n_windows = max(1, math.ceil(end / cfg.window))
    assert len(res.windows) == n_windows
    for i, w in enumerate(res.windows):
        t0, t1 = i * cfg.window, (i + 1) * cfg.window
        wdone = [r for r in done if t0 <= r.completed_t < t1]
        wlats = [r.completed_t - r.arrival_t for r in wdone]
        assert w["admitted"] == sum(1 for r in records
                                    if t0 <= r.arrival_t < t1)
        assert w["rejected"] == sum(1 for t, _ in rejections if t0 <= t < t1)
        assert w["completions"] == len(wdone)
        assert w["p50"] == _brute_percentile(wlats, 50)
        assert w["p99"] == _brute_percentile(wlats, 99)
    # SLO accounting is conserved
    slo_done = [r for r in done
                if dict((t.name, t.slo) for t in cfg.tenants)[r.tenant]
                is not None]
    if slo_done:
        hits = sum(1 for r in slo_done
                   if (r.completed_t - r.arrival_t)
                   <= dict((t.name, t.slo) for t in cfg.tenants)[r.tenant])
        assert res.slo_attainment == pytest.approx(hits / len(slo_done))
        assert res.slo_violations == len(slo_done) - hits


def test_traffic_result_windows_from_real_run():
    """End-to-end: a real run's windowed completions sum to its totals."""
    tr = _small_traffic(seed=7, n_arrivals=10)
    _, tres = run_traffic(tr, "wow", n_nodes=16)
    assert tres.completed > 0
    assert sum(w["completions"] for w in tres.windows) == tres.completed
    assert sum(w["admitted"] for w in tres.windows) == tres.admitted
    assert sum(w["rejected"] for w in tres.windows) == tres.rejected


# --------------------------------------------------- namespacing + validate
def test_namespaced_rebases_ids_and_prefixes_abstracts():
    wf = make_workflow("group", scale=0.25)
    t_span, f_span = wf.id_bounds()
    ns = wf.namespaced(t_span, f_span, prefix="tenant/3:")
    ns.validate()
    assert set(ns.tasks).isdisjoint(wf.tasks)
    assert set(ns.files).isdisjoint(wf.files)
    assert all(t.abstract.startswith("tenant/3:")
               for t in ns.tasks.values())
    assert all(a.startswith("tenant/3:") for a in ns.abstract_edges)
    # structure is preserved: same shapes, same sizes, shifted ids
    for tid, t in wf.tasks.items():
        r = ns.tasks[tid + t_span]
        assert r.inputs == tuple(f + f_span for f in t.inputs)
        assert r.outputs == tuple(f + f_span for f in t.outputs)
        assert (r.mem, r.cores, r.compute_time) == (
            t.mem, t.cores, t.compute_time)
    for fid, f in wf.files.items():
        r = ns.files[fid + f_span]
        assert r.size == f.size
        assert r.producer == f.producer + t_span
        assert r.consumers == {c + t_span for c in f.consumers}


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000),
       st.sampled_from(["double_produce", "cycle", "unproduced_input",
                        "bad_consumers"]))
def test_validate_rejects_fuzzed_dag_mutations(seed, mutation):
    """Each structural-damage class must raise from Workflow.validate."""
    rng = random.Random(seed)
    wf = make_workflow(rng.choice(["chain", "fork", "group"]),
                       scale=0.25, seed=seed)
    wf.validate()                          # healthy before mutation
    tasks = sorted(wf.tasks.values(), key=lambda t: t.id)
    with_out = [t for t in tasks if t.outputs]
    with_in = [t for t in tasks if t.inputs]
    if mutation == "double_produce":
        victim, thief = with_out[0], tasks[-1]
        wf.tasks[thief.id] = dataclasses.replace(
            thief, outputs=thief.outputs + (victim.outputs[0],))
    elif mutation == "cycle":
        # a task consuming its own output: the tightest cycle the Kahn
        # check must reject (the two-task cycle has its own test below)
        t = with_out[rng.randrange(len(with_out))]
        f = t.outputs[0]
        wf.tasks[t.id] = dataclasses.replace(t, inputs=t.inputs + (f,))
        wf.files[f].consumers.add(t.id)
    elif mutation == "unproduced_input":
        ghost = 1 + max(wf.files)
        victim = with_in[rng.randrange(len(with_in))]
        wf.tasks[victim.id] = dataclasses.replace(
            victim, inputs=victim.inputs + (ghost,))
    elif mutation == "bad_consumers":
        victim = with_in[rng.randrange(len(with_in))]
        wf.files[victim.inputs[0]].consumers.discard(victim.id)
    with pytest.raises(ValueError):
        wf.validate()


def test_validate_rejects_two_task_cycle():
    from repro.core.types import FileSpec, TaskSpec
    from repro.sim.workflow import Workflow

    f0 = FileSpec(id=0, size=1, producer=0, consumers={1})
    f1 = FileSpec(id=1, size=1, producer=1, consumers={0})
    t0 = TaskSpec(id=0, abstract="a", mem=1, cores=1.0,
                  inputs=(1,), outputs=(0,))
    t1 = TaskSpec(id=1, abstract="b", mem=1, cores=1.0,
                  inputs=(0,), outputs=(1,))
    wf = Workflow("cycle", {0: t0, 1: t1}, {0: f0, 1: f1},
                  {"a": {"b"}, "b": {"a"}})
    with pytest.raises(ValueError, match="cycle"):
        wf.validate()
