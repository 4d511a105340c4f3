"""The benchmark's traffic generators and arithmetic, on the CPU."""
from __future__ import annotations

import json
import os

import pytest

pytest.importorskip("numpy")

import bench_tiny  # noqa: E402,F401  -- puts bench/ and src/ on sys.path
import plugins  # noqa: E402
from harness import percentile  # noqa: E402
from workload.arrivals import block_counts, service_arrivals  # noqa: E402

WORKFLOWS = sorted(n[:-3] for n in os.listdir(
    os.path.join(bench_tiny.BENCH, "workflows")) if n.endswith(".py"))


def _build(wf: str, **kw):
    return plugins.load("workflows", wf).build(**kw)


def _mix(name: str) -> dict:
    with open(os.path.join(bench_tiny.BENCH, "traffic", name + ".json")) as f:
        return json.load(f)


@pytest.mark.parametrize("tenants", [None, "all"])
def test_service_arrivals_deterministic_by_seed(tenants):
    """The arrivals are the same on every call, and no run's seed enters
    them; each block of arrivals follows the tenants' weights."""
    mix = dict(_mix("nfcore"), n_arrivals=64)
    if tenants:                       # one tenant per Table I workflow
        mix["tenants"] = [{"name": w, "workflow": w, "weight": 1,
                           "scale": 0.1} for w in WORKFLOWS]
    a = service_arrivals(mix)
    assert a == service_arrivals(mix)
    times = [x["time"] for x in a]
    assert times == sorted(times) and len(set(times)) == len(times)
    assert len({x["seed"] for x in a}) == len(a)
    want = block_counts(mix["tenants"], 4)
    for b in range(0, 64, 4):
        got = [sum(1 for x in a[b:b + 4] if x["tenant"] == t["name"])
               for t in mix["tenants"]]
        assert got == want


def test_block_counts_follow_weights():
    tenants = [{"weight": 2}, {"weight": 1}, {"weight": 1}]
    assert block_counts(tenants, 4) == [2, 1, 1]
    assert sum(block_counts(tenants, 7)) == 7


@pytest.mark.parametrize("wf", WORKFLOWS)
def test_dag_generators_deterministic_by_seed(wf):
    one = _build(wf, scale=0.05, seed=3)
    two = _build(wf, scale=0.05, seed=3)
    other = _build(wf, scale=0.05, seed=4)
    shape = lambda w: [(t.id, t.inputs, t.outputs, t.compute_time)  # noqa
                       for t in w.tasks.values()]
    assert shape(one) == shape(two)
    assert shape(one) != shape(other)
    one.validate()


def test_dag_generators_match_table_one_volumes():
    # Table I of the WOW paper: input GB at scale 1.0
    wf = _build("rnaseq", scale=1.0, seed=0)
    assert abs(wf.total_input_bytes() / 1e9 - 139.1) < 0.5
    wf = _build("sarek", scale=1.0, seed=0)
    assert abs(wf.total_input_bytes() / 1e9 - 205.9) < 0.5


def _shapes(wf) -> dict:
    """``{abstract task: sorted (input bytes, output bytes, cores)}``."""
    out: dict = {}
    for t in wf.tasks.values():
        key = (t.dfs_inputs, tuple(wf.files[f].size for f in t.outputs),
               t.cores)
        out.setdefault(t.abstract, set()).add(key)
    return {k: sorted(v) for k, v in out.items()}


@pytest.mark.parametrize("wf", WORKFLOWS)
def test_scale_keeps_every_task_shape(wf):
    """A scale cut changes how many tasks there are, not what each task
    reads and writes: every task's sizes are those of scale 1.0."""
    full, cut = _shapes(_build(wf, scale=1.0)), _shapes(_build(wf, scale=0.1))
    assert set(cut) == set(full)
    for abstract, shapes in cut.items():
        assert set(shapes) <= set(full[abstract]), abstract
    assert len(_build(wf, scale=0.1).tasks) < len(_build(wf, scale=1.0).tasks)


@pytest.mark.parametrize("wf", WORKFLOWS)
def test_dag_generators_match_the_program_at_scale_one(wf):
    """At scale 1.0 each copied builder gives the program's own DAG."""
    from repro.workloads import make_workflow
    ours, theirs = _build(wf, scale=1.0, seed=7), make_workflow(wf, 1.0, 7)
    shape = lambda w: [(t.abstract, t.inputs, t.outputs, t.dfs_inputs,  # noqa
                        t.compute_time, t.cores, t.mem)
                       for t in w.tasks.values()]
    assert shape(ours) == shape(theirs)
    assert {f: x.size for f, x in ours.files.items()} == \
        {f: x.size for f, x in theirs.files.items()}


def test_fanin_replay_deterministic_by_seed(tmp_path):
    replay = plugins.load("runners", "replay")
    cfg = {"n_nodes": 16, "cores": 16.0, "mem": 128 * 1024 ** 3,
           "c_node": 1, "c_task": 2}
    mix = dict(_mix("fanin"), ready_per_node=2, batch=4, warmup_rounds=1)
    logs = [replay.run(cfg, mix, seed, 0.3).log for seed in (5, 5, 6)]
    n = min(map(len, logs))
    assert n > 100
    logs = [log[:n] for log in logs]
    assert logs[0] == logs[1]
    assert logs[0] != logs[2]
    assert any(e[0] == "round" and e[2] for e in logs[0])


@pytest.mark.parametrize("q,expect", [(50, 3), (99, 5), (1, 1), (100, 5)])
def test_percentile_nearest_rank(q, expect):
    assert percentile([5, 1, 4, 2, 3], q) == expect


def test_percentile_empty():
    assert percentile([], 50) is None


METRICS = sorted(n[:-3] for n in os.listdir(
    os.path.join(bench_tiny.BENCH, "metrics")) if n.endswith(".py"))


def _summary(rounds: list[float]) -> dict:
    return {"simulated": True, "rounds": len(rounds),
            "events": 3 * len(rounds),
            "window_s": 10.0, "adapter_s": sum(rounds),
            "callbacks_s": 0.1 * sum(rounds), "round_s": rounds,
            "spans": {"step1": 0.5, "step2": 0.25} if rounds else {},
            "setup_s": 7.5}


@pytest.mark.parametrize("name", METRICS)
def test_metric_reader_reads_a_summary(name):
    read = plugins.load("metrics", name).read
    v = read(_summary([0.001 * i for i in range(1, 101)]))
    assert isinstance(v, float) and v > 0
    empty = read(dict(_summary([]), window_s=0.0))
    assert empty is None or name == "setup_s"


def test_round_percentile_readers():
    run = _summary([0.001 * i for i in range(1, 101)])
    assert plugins.load("metrics", "round_p50_ms").read(run) == 50.0
    assert plugins.load("metrics", "round_p99_ms").read(run) == 99.0
    assert plugins.load("metrics", "events_per_s").read(run) == 30.0


def test_service_run_is_the_same_for_every_seed(tmp_path):
    """The run's seed enters neither the traffic nor the simulator: two
    seeds give one decision log, as far as both windows reach."""
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    bench_tiny.shrink(root, "k8s5000.nfcore")
    import harness
    cfg, mix, runner = harness.resolve(harness.load_benchmark(root),
                                       "k8s5000.nfcore", bench)
    logs = [runner.run(cfg, mix, seed, 0.3, None, bench).log
            for seed in (1, 2 ** 31 + 7)]
    n = min(map(len, logs))
    assert n > 100
    assert logs[0][:n] == logs[1][:n]
