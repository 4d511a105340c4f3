"""Hierarchical topology layer: path construction, fill parity, flat
bit-identity, locality-aware placement, retry satellite.

Covers the topology PR's guarantees:

* **Topology geometry** -- rack/site assignment, distance/weight classes,
  path construction and the ``expand`` splice are what DESIGN.md says;
  a flat spec (rack_size 0 or >= node count) inserts no links anywhere.
* **Fill parity** -- ``_heap_fill`` stays bit-identical to the retained
  ``_progressive_fill`` scan on randomized hierarchical topologies (direct
  allocator parity, FlowManager op streams, and whole simulations with
  failure/join churn for all three strategies).
* **Flat bit-identity** -- runs configured with a *flat* ``TopologySpec``
  reproduce the pre-topology goldens exactly (churn goldens for all
  strategy x DFS x workflow combinations, plus the dfs_churn traffic
  capture), because the engine drops a flat topology entirely.
* **Locality** -- Ceph spreads replicas across racks and serves reads from
  the nearest replica; repair destinations prefer fresh racks; the DPS
  plans COPs from minimum-distance sources and prices them with weighted
  bytes; the tracked locality cost matches the from-scratch reference.
* **Satellite** -- ``RetryPolicy`` (seeded capped backoff, retry counters
  in ``TrafficResult``).
"""
import hashlib
import json
import os
import random

import pytest
from _hyp import given, settings, st

from repro.core import DataPlacementService, FileSpec
from repro.sim import (CephModel, FlowManager, RetryPolicy, SimConfig,
                       Simulation, TenantSpec, Topology, TopologySpec,
                       TrafficConfig, build_links, run_traffic)
from repro.sim.network import Flow, _heap_fill, _progressive_fill
from repro.workloads import make_workflow

_DATA = os.path.join(os.path.dirname(__file__), "data")
with open(os.path.join(_DATA, "churn_goldens.json")) as _f:
    CHURN_GOLDENS = json.load(_f)["scenarios"]
with open(os.path.join(_DATA, "traffic_goldens.json")) as _f:
    TRAFFIC_GOLDENS = json.load(_f)["scenarios"]

_SCALES = {"group": 0.25, "chain": 0.3}

# 8 nodes, 2 per rack, 2 racks per site => racks 0-3, sites 0-1
SPEC8 = TopologySpec(rack_size=2, racks_per_site=2, oversubscription=4.0)


def _topo8(net_bw: float = 100.0) -> Topology:
    return Topology(SPEC8, 8, net_bw)


# ------------------------------------------------------------------ geometry
def test_hierarchy_mapping():
    t = _topo8()
    assert t.nonuniform
    assert t.n_racks == 4 and t.n_sites == 2
    assert [t.rack_of(n) for n in range(8)] == [0, 0, 1, 1, 2, 2, 3, 3]
    assert [t.site_of(n) for n in range(8)] == [0, 0, 0, 0, 1, 1, 1, 1]
    assert t.distance(3, 3) == 0          # same node
    assert t.distance(2, 3) == 1          # same rack
    assert t.distance(0, 3) == 2          # same site, different rack
    assert t.distance(0, 4) == 3          # different site
    assert t.weight(3, 3) == 0.0
    assert t.weight(2, 3) == SPEC8.w_rack
    assert t.weight(0, 3) == SPEC8.w_site
    assert t.weight(0, 4) == SPEC8.w_wan
    assert t.max_weight == SPEC8.w_wan
    # positional assignment extends to elastic-join ids past n_nodes
    assert t.rack_of(9) == 4 and t.site_of(9) == 2


@pytest.mark.parametrize("spec", [
    TopologySpec(),                          # default: rack_size 0
    TopologySpec(rack_size=8),               # one rack covering the cluster
    TopologySpec(rack_size=50, racks_per_site=2, oversubscription=9.0),
])
def test_flat_spec_collapses(spec):
    """rack_size 0 or >= node count => single rack, no links, no rewrite."""
    t = Topology(spec, 8, 100.0)
    assert not t.nonuniform
    assert t.n_racks == 1 and t.n_sites == 1
    assert t.path(0, 7) == ()
    links = (("dr", 0), ("up", 0), ("down", 7), ("dw", 7))
    assert t.expand(links) == links
    caps: dict = {}
    t.ensure_node(3, caps)
    assert caps == {}


def test_path_construction():
    t = _topo8()
    assert t.path(0, 1) == ()                              # same rack
    assert t.path(0, 2) == (("rku", 0), ("core", 0), ("rkd", 1))
    assert t.path(1, 6) == (("rku", 0), ("core", 0), ("wanu", 0),
                            ("wand", 1), ("core", 1), ("rkd", 3))


def test_path_cache_matches_uncached_oracle():
    """The per-(rack, rack) ``path`` memo must be invisible: every node
    pair returns exactly what the retained ``_path_uncached`` oracle
    derives, and the cache holds at most one entry per rack pair."""
    t = _topo8()
    assert t._path_cache == {}              # lazy: nothing precomputed
    for src in range(8):
        for dst in range(8):
            assert t.path(src, dst) == t._path_uncached(src, dst)
    # 4 racks -> at most 16 entries, and hits are the cached objects
    assert 0 < len(t._path_cache) <= 16
    for (r_src, r_dst), links in t._path_cache.items():
        assert t.path(2 * r_src, 2 * r_dst) is links
    # expand routes through the cache: same splice, warm or cold
    links = (("up", 1), ("down", 6), ("up", 6), ("down", 3))
    assert t.expand(links) == _topo8().expand(links)


def test_expand_splices_every_up_down_pair():
    t = _topo8()
    # intra-rack transfer: untouched
    links = (("dr", 0), ("up", 0), ("down", 1), ("dw", 1))
    assert t.expand(links) == links
    # inter-site transfer: the 6-link WAN path lands between up and down
    links = (("dr", 0), ("up", 0), ("down", 5), ("dw", 5))
    assert t.expand(links) == (
        ("dr", 0), ("up", 0),
        ("rku", 0), ("core", 0), ("wanu", 0),
        ("wand", 1), ("core", 1), ("rkd", 2),
        ("down", 5), ("dw", 5))
    # multiple hops each get their own splice (e.g. a relayed path)
    links = (("up", 0), ("down", 2), ("up", 2), ("down", 4))
    out = t.expand(links)
    assert out == (("up", 0), ("rku", 0), ("core", 0), ("rkd", 1),
                   ("down", 2),
                   ("up", 2), ("rku", 1), ("core", 0), ("wanu", 0),
                   ("wand", 1), ("core", 1), ("rkd", 2), ("down", 4))


def test_tier_classification():
    t = _topo8()
    assert t.tier((("dr", 0), ("dw", 0))) == "local"
    assert t.tier(t.expand((("up", 0), ("down", 1)))) == "rack"
    assert t.tier(t.expand((("up", 0), ("down", 2)))) == "site"
    assert t.tier(t.expand((("up", 0), ("down", 4)))) == "wan"


def test_ensure_node_capacities():
    t = _topo8(net_bw=100.0)
    assert t.rack_up_bw == 2 * 100.0 / 4.0
    assert t.core_bw == 2 * t.rack_up_bw
    caps: dict = {}
    t.ensure_node(5, caps)                   # rack 2, site 1
    assert caps == {("rku", 2): t.rack_up_bw, ("rkd", 2): t.rack_up_bw,
                    ("core", 1): t.core_bw,
                    ("wanu", 1): t.wan_bw, ("wand", 1): t.wan_bw}
    # idempotent, and never overwrites an existing capacity
    caps[("rku", 2)] = 1.0
    t.ensure_node(4, caps)
    assert caps[("rku", 2)] == 1.0


def test_build_links_registers_topology_links():
    t = _topo8(net_bw=100.0)
    caps = build_links(8, 100.0, 200.0, 150.0, topology=t)
    for r in range(4):
        assert caps[("rku", r)] == t.rack_up_bw
        assert caps[("rkd", r)] == t.rack_up_bw
    for s in range(2):
        assert caps[("core", s)] == t.core_bw
        assert caps[("wanu", s)] == t.wan_bw
    # flat topology (or None) registers nothing extra
    flat = build_links(8, 100.0, 200.0, 150.0,
                       topology=Topology(TopologySpec(), 8, 100.0))
    assert flat == build_links(8, 100.0, 200.0, 150.0)


def test_spec_validation():
    with pytest.raises(ValueError):
        TopologySpec(oversubscription=0.0)
    with pytest.raises(ValueError):
        TopologySpec(core_oversubscription=-1.0)
    with pytest.raises(ValueError):
        TopologySpec(wan_bw=0.0)


# ------------------------------------------------------- fill parity (direct)
def _random_topology(rng: random.Random, n_nodes: int) -> Topology:
    spec = TopologySpec(
        rack_size=rng.randint(1, max(2, n_nodes // 2)),
        racks_per_site=rng.randint(0, 3),
        oversubscription=rng.choice([1.0, 2.0, 4.0, 8.0]),
        core_oversubscription=rng.choice([1.0, 2.0]),
        wan_bw=rng.choice([None, 37.0]))
    return Topology(spec, n_nodes, 100.0)


def _random_flow_links(rng: random.Random, topo: Topology,
                       n_nodes: int) -> tuple:
    src = rng.randrange(n_nodes)
    dst = rng.randrange(n_nodes)
    while dst == src:
        dst = rng.randrange(n_nodes)
    links = (("dr", src), ("up", src), ("down", dst), ("dw", dst))
    return topo.expand(links)


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000))
def test_property_heap_fill_matches_scan_on_random_topologies(seed):
    """Path-constrained flows (rack/core/WAN links spliced in): the heap
    fill's rate vector is float-for-float the scan fill's."""
    rng = random.Random(seed)
    n_nodes = rng.randint(4, 16)
    topo = _random_topology(rng, n_nodes)
    caps = build_links(n_nodes, 100.0, 200.0, 150.0,
                       topology=topo if topo.nonuniform else None)
    flows_a, flows_b = [], []
    for i in range(rng.randint(5, 40)):
        links = _random_flow_links(rng, topo, n_nodes)
        nbytes = rng.uniform(1.0, 1e6)
        flows_a.append(Flow(i, links, nbytes, tag=i))
        flows_b.append(Flow(i, links, nbytes, tag=i))
    _heap_fill(flows_a, caps)
    _progressive_fill(flows_b, caps)
    assert {f.id: f.rate for f in flows_a} == \
        {f.id: f.rate for f in flows_b}
    # shared-infrastructure sanity: no rack uplink is over-filled
    for l, cap in caps.items():
        used = sum(f.rate for f in flows_a if l in f.links)
        assert used <= cap * (1 + 1e-9)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10_000))
def test_property_flowmanager_streams_identical_across_fills(seed):
    """Randomized add/advance/remove op streams over topology paths: both
    FlowManager fills agree on every rate and every completion time."""
    rng = random.Random(seed)
    n_nodes = rng.randint(4, 12)
    topo = _random_topology(rng, n_nodes)
    caps = build_links(n_nodes, 100.0, 200.0, 150.0,
                       topology=topo if topo.nonuniform else None)
    fm_h = FlowManager(dict(caps), fill="heap")
    fm_s = FlowManager(dict(caps), fill="scan")
    live: list[int] = []
    for _ in range(40):
        op = rng.random()
        if op < 0.5 or not live:
            links = _random_flow_links(rng, topo, n_nodes)
            nbytes = rng.uniform(1.0, 1e6)
            fh = fm_h.add(links, nbytes, tag=None)
            fs = fm_s.add(links, nbytes, tag=None)
            assert fh.id == fs.id
            live.append(fh.id)
        elif op < 0.7:
            fid = live.pop(rng.randrange(len(live)))
            fm_h.remove(fid)
            fm_s.remove(fid)
        else:
            fm_h.recompute()
            fm_s.recompute()
            dt_h, f_h = fm_h.next_completion()
            dt_s, f_s = fm_s.next_completion()
            assert dt_h == dt_s
            assert (f_h is None) == (f_s is None)
            if f_h is not None:
                done_h = {f.id for f in fm_h.advance(dt_h)}
                done_s = {f.id for f in fm_s.advance(dt_s)}
                assert done_h == done_s
                live = [i for i in live if i not in done_h]
        assert {i: fm_h.flows[i].rate for i in fm_h.flows} == \
            {i: fm_s.flows[i].rate for i in fm_s.flows}


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 10 ** 6))
def test_property_vectorized_fill_matches_scan(seed):
    """The welded-component vectorized fill (normally engaged only past
    the ``_VEC_MIN_MEMBERS`` membership threshold) stays bit-identical to
    the scan fill when forced on for every recompute, and never leaks
    numpy scalars into flow state."""
    import repro.sim.network as network

    if network._np is None:
        pytest.skip("numpy unavailable")
    rng = random.Random(seed)
    n_nodes = rng.randint(4, 12)
    topo = _random_topology(rng, n_nodes)
    caps = build_links(n_nodes, 100.0, 200.0, 150.0,
                       topology=topo if topo.nonuniform else None)
    fm_h = FlowManager(dict(caps), fill="heap")
    fm_s = FlowManager(dict(caps), fill="scan")
    old = network._VEC_MIN_MEMBERS
    network._VEC_MIN_MEMBERS = 0
    fm_h._has_shared = True    # force the vectorized path even on flat draws
    try:
        live: list[int] = []
        for _ in range(40):
            op = rng.random()
            if op < 0.5 or not live:
                links = _random_flow_links(rng, topo, n_nodes)
                nbytes = rng.uniform(1.0, 1e6)
                fh = fm_h.add(links, nbytes, tag=None)
                fs = fm_s.add(links, nbytes, tag=None)
                live.append(fh.id)
            elif op < 0.7:
                fid = live.pop(rng.randrange(len(live)))
                fm_h.remove(fid)
                fm_s.remove(fid)
            else:
                fm_h.recompute()
                fm_s.recompute()
                dt_h, f_h = fm_h.next_completion()
                dt_s, f_s = fm_s.next_completion()
                assert repr(dt_h) == repr(dt_s)
                if f_h is not None:
                    done_h = {f.id for f in fm_h.advance(dt_h)}
                    done_s = {f.id for f in fm_s.advance(dt_s)}
                    assert done_h == done_s
                    live = [i for i in live if i not in done_h]
            for i, f in fm_h.flows.items():
                g = fm_s.flows[i]
                assert repr(f.rate) == repr(g.rate)
                assert type(f.rate) is float       # no np.float64 leakage
    finally:
        network._VEC_MIN_MEMBERS = old


# ------------------------------------------------ whole-sim heap/scan parity
def _run_topo(strategy, fill, spec=SPEC8, churn=False, dfs="ceph"):
    wf = make_workflow("group", scale=0.25)
    sim = Simulation(wf, SimConfig(dfs=dfs, topology=spec, flow_fill=fill),
                     strategy)
    if churn:
        sim.schedule_failure(30.0, 1)
        sim.schedule_join(45.0, 9)
    return sim, sim.run()


@pytest.mark.parametrize("strategy", ["orig", "cws", "wow"])
@pytest.mark.parametrize("churn", [False, True])
def test_sim_heap_scan_bit_identical_under_topology(strategy, churn):
    """The scan fill is the bit-identity oracle on every topology: whole
    simulations (with and without node churn) agree action-for-action."""
    sim_h, res_h = _run_topo(strategy, "heap", churn=churn)
    sim_s, res_s = _run_topo(strategy, "scan", churn=churn)
    assert sim_h.topo is not None         # the topology actually engaged
    assert sim_h.action_log == sim_s.action_log
    assert repr(res_h.makespan) == repr(res_s.makespan)
    assert repr(res_h.network_bytes) == repr(res_s.network_bytes)
    assert res_h.tier_bytes == res_s.tier_bytes
    assert sum(res_h.tier_bytes.values()) == pytest.approx(
        res_h.network_bytes)


def test_topology_changes_the_run():
    """Sanity that the parity above is not vacuous: an oversubscribed
    topology must actually slow the DFS-bound baseline down."""
    wf = make_workflow("group", scale=0.25)
    flat = Simulation(wf, SimConfig(dfs="ceph"), "orig").run()
    _, topo = _run_topo("orig", "heap")
    assert topo.makespan > flat.makespan
    assert topo.tier_bytes           # rack/site/wan bytes were accounted


# ------------------------------------------------- flat-spec golden identity
@pytest.mark.parametrize("key", sorted(CHURN_GOLDENS))
def test_flat_spec_runs_match_pre_topology_goldens(key):
    """A flat ``TopologySpec`` must be dropped by the engine entirely:
    action log, makespan, and network bytes reproduce the pre-topology
    goldens bit-for-bit for every strategy x DFS x workflow."""
    wf_name, strategy, dfs = key.split(":")
    wf = make_workflow(wf_name, scale=_SCALES[wf_name])
    sim = Simulation(wf, SimConfig(dfs=dfs, topology=TopologySpec()),
                     strategy)
    res = sim.run()
    assert sim.topo is None               # flat spec normalized away
    g = CHURN_GOLDENS[key]
    assert len(sim.action_log) == g["n_actions"]
    assert hashlib.sha256(
        repr(sim.action_log).encode()).hexdigest() == g["action_log_sha256"]
    assert repr(res.makespan) == g["makespan"]
    assert repr(res.network_bytes) == g["network_bytes"]
    assert res.tier_bytes == {}


@pytest.mark.parametrize("strategy", ["orig", "cws", "wow"])
def test_flat_spec_churn_runs_match_traffic_goldens(strategy):
    """Same under injected node failure (the dfs_churn capture): a
    single-rack spec (rack_size >= node count) is flat too."""
    wf = make_workflow("group", scale=0.25)
    sim = Simulation(wf, SimConfig(dfs="ceph", ceph_replication=2,
                                   topology=TopologySpec(rack_size=64)),
                     strategy)
    sim.schedule_failure(30.0, 1)
    res = sim.run()
    assert sim.topo is None
    g = TRAFFIC_GOLDENS[f"dfs_churn:{strategy}"]
    assert len(sim.action_log) == g["n_actions"]
    assert hashlib.sha256(
        repr(sim.action_log).encode()).hexdigest() == g["action_log_sha256"]
    assert repr(res.makespan) == g["makespan"]
    assert repr(res.network_bytes) == g["network_bytes"]


# --------------------------------------------------- locality-aware DFS
def test_ceph_spreads_replicas_across_racks():
    topo = _topo8()
    ceph = CephModel(n_nodes=8, replication=2, seed=0, topology=topo)
    for fid in range(60):
        ceph.write_paths(fid, 10, writer=0)
        reps = ceph._placement[fid]
        assert len({topo.rack_of(n) for n in reps}) == len(reps)


def test_ceph_reads_prefer_nearest_replica():
    topo = _topo8()
    ceph = CephModel(n_nodes=8, replication=2, seed=0, topology=topo)
    for fid in range(40):
        ceph.write_paths(fid, 100, writer=0)
        reps = ceph._placement[fid]
        for reader in range(8):
            paths = ceph.read_paths(fid, 100, reader)
            srcs = {l[1] for links, _ in paths for l in links
                    if l[0] in ("dr", "up")}
            if reader in reps:
                assert srcs == {reader}   # local replica: disk-only read
            else:
                (src,) = srcs
                assert topo.distance(src, reader) == min(
                    topo.distance(r, reader) for r in reps)


def test_ceph_repair_prefers_fresh_rack_and_close_source():
    topo = _topo8()
    ceph = CephModel(n_nodes=8, replication=2, seed=3, topology=topo)
    for fid in range(30):
        ceph.write_paths(fid, 50, writer=fid % 8)
    victim = 0
    repairs, _ = ceph.fail_node(victim)
    assert repairs
    for fid, src, dst, _size in repairs:
        holders = set(ceph._placement[fid])
        assert src in holders and dst not in holders
        # destination rack disjoint from the surviving holders' racks
        assert topo.rack_of(dst) not in {topo.rack_of(h) for h in holders}


# --------------------------------------------------- locality-aware DPS
def _dps_with_topo():
    dps = DataPlacementService(seed=0)
    dps.set_topology(_topo8())
    return dps


def test_set_topology_flat_detaches():
    dps = DataPlacementService(seed=0)
    dps.set_topology(Topology(TopologySpec(), 8, 100.0))
    assert dps.topology is None
    dps.set_topology(_topo8())
    assert dps.topology is not None
    dps.set_topology(None)
    assert dps.topology is None


def test_plan_cop_prefers_nearest_source_and_weighted_price():
    dps = _dps_with_topo()
    # file 1: replicas at node 1 (rack of target 0) and node 4 (other site)
    dps.register_file(FileSpec(id=1, size=100, producer=-1), 1)
    dps._idx_add(1, 4)
    plan = dps.plan_cop(7, (1,), target=0)
    assert [t.src for t in plan.transfers] == [1]
    # price = 0.5 * weighted traffic + 0.5 * max load
    assert plan.price == 0.5 * 100 * SPEC8.w_rack + 0.5 * 100
    # same plan against a WAN-only holder pays the WAN multiplier
    dps2 = _dps_with_topo()
    dps2.register_file(FileSpec(id=1, size=100, producer=-1), 4)
    plan2 = dps2.plan_cop(7, (1,), target=0)
    assert [t.src for t in plan2.transfers] == [4]
    assert plan2.price == 0.5 * 100 * SPEC8.w_wan + 0.5 * 100


@settings(max_examples=15, deadline=None)
@given(st.integers(0, 10_000))
def test_property_locality_cost_tracked_matches_reference(seed):
    """The incrementally-tracked locality cost equals the from-scratch
    reference on random replica layouts (and reduces to missing bytes
    without a topology)."""
    rng = random.Random(seed)
    topo = _random_topology(rng, 8)
    dps = DataPlacementService(seed=0)
    dps.set_topology(topo)
    inputs = []
    for fid in range(rng.randint(1, 6)):
        size = rng.randint(1, 1000)
        holders = rng.sample(range(8), rng.randint(1, 3))
        dps.register_file(FileSpec(id=fid, size=size, producer=-1),
                          holders[0])
        for h in holders[1:]:
            dps._idx_add(fid, h)
        inputs.extend([fid] * rng.randint(1, 2))
    inputs = tuple(inputs)
    dps.track_task(1, inputs)
    for node in range(8):
        tracked = dps.locality_missing_cost(1, node)
        reference = dps.locality_missing_cost_reference(inputs, node)
        assert tracked == reference
        if dps.topology is None:          # flat draw: plain byte counts
            assert tracked == float(dps.missing_bytes(inputs, node))


def test_locality_cost_charges_max_weight_for_holderless_files():
    dps = _dps_with_topo()
    dps.register_file(FileSpec(id=1, size=10, producer=-1), 0)
    dps._locations[1].clear()             # every replica gone
    dps.track_task(1, (1,))
    assert dps.locality_missing_cost(1, 3) == 10 * SPEC8.w_wan


# ------------------------------------------------------- retry satellite
def test_retry_policy_delay_deterministic_and_capped():
    p = RetryPolicy(max_attempts=4, backoff=10.0, multiplier=2.0, cap=25.0)
    for seed in (0, 7, 12345):
        for k in range(4):
            d1, d2 = p.delay(seed, k), p.delay(seed, k)
            assert d1 == d2               # pure in (seed, attempt)
            base = min(25.0, 10.0 * 2.0 ** k)
            assert 0.5 * base <= d1 < 1.5 * base
    assert p.delay(0, 10) < 1.5 * 25.0    # capped
    with pytest.raises(ValueError):
        RetryPolicy(max_attempts=0)
    with pytest.raises(ValueError):
        RetryPolicy(multiplier=0.5)
    with pytest.raises(ValueError):
        RetryPolicy(backoff=0.0)


def _retry_traffic(retry):
    return TrafficConfig(
        tenants=(TenantSpec("alice", weight=1.0, workflows=("chain",),
                            scale=0.05, slo=300.0, retry=retry),),
        rate=0.5, n_arrivals=10, max_backlog=1, window=30.0, seed=2)


def test_retry_resubmits_rejected_arrivals():
    policy = RetryPolicy(max_attempts=3, backoff=20.0)
    _, base = run_traffic(_retry_traffic(None), "wow", n_nodes=4)
    _, tres = run_traffic(_retry_traffic(policy), "wow", n_nodes=4)
    assert base.rejected > 0              # the gate binds in this config
    assert base.retries == 0 and base.retry_admitted == 0
    assert tres.retries > 0
    # each rejection triggers at most max_attempts - 1 re-submissions
    assert tres.retries <= (policy.max_attempts - 1) * base.rejected
    # accounting: every attempt is an arrival; retried attempts included
    assert tres.arrivals == tres.admitted + tres.rejected
    assert tres.arrivals == 10 + tres.retries
    assert tres.per_tenant["alice"]["retries"] == tres.retries
    # instances admitted on a retry carry their attempt count
    multi = [r for r in tres.instances if r["attempts"] > 1]
    assert len(multi) == tres.retry_admitted
    for r in multi:
        assert r["attempts"] <= policy.max_attempts


def test_retry_run_replays_bit_identically():
    cfg = _retry_traffic(RetryPolicy(max_attempts=3, backoff=20.0))
    runs = [run_traffic(cfg, "wow", n_nodes=4) for _ in range(2)]
    (r1, t1), (r2, t2) = runs
    assert repr(r1.makespan) == repr(r2.makespan)
    assert t1 == t2
