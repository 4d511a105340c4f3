"""Cells, configurations, mixes and metrics resolve by name, and new ones
are found as new files, with no edit to an existing file."""
from __future__ import annotations

import json
import os
import re

import pytest

pytest.importorskip("numpy")

import bench_tiny  # noqa: E402
import harness  # noqa: E402


def _bench() -> dict:
    return harness.load_benchmark(bench_tiny.ROOT)


@pytest.mark.parametrize("cell", bench_tiny.cells())
def test_cell_resolves(cell):
    cfg, mix, runner = harness.resolve(_bench(), cell)
    assert cfg["name"] == harness.find_cell(_bench(), cell)["config"]
    assert callable(runner.run)
    assert mix["check_rounds"] > 0


def test_configs_and_metrics_resolve():
    b = _bench()
    for c in b["configs"]:
        assert os.path.exists(os.path.join(bench_tiny.ROOT, c["file"]))
        assert harness.load_json("configs", c["name"])["name"] == c["name"]
    for m in b["end_to_end"] + b["per_layer"]:
        assert callable(harness.load_metric(m["name"]))


def test_unknown_names_are_refused():
    with pytest.raises(harness.SetupError):
        harness.find_cell(_bench(), "nope.none")
    with pytest.raises(harness.SetupError):
        harness.load_json("traffic", "nope")
    with pytest.raises(harness.SetupError):
        harness.load_metric("nope")
    with pytest.raises(harness.SetupError):
        harness.load_metric("../harness")
    with pytest.raises(harness.SetupError):
        harness.resolve({"workloads": [{"name": "x.y", "config": "k8s5000",
                                        "traffic": "nope"}]}, "x.y")


def test_benchmark_json_shape():
    b = _bench()
    assert set(b) == {"command", "paths", "run_seconds", "configs",
                      "workloads", "end_to_end", "per_layer"}
    names = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
    seen = set()
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in b[group]:
            assert names.match(e["name"]), e["name"]
            assert e["name"] not in seen
            seen.add(e["name"])
    e2e = {m["name"] for m in b["end_to_end"]}
    assert "setup_s" in e2e
    for m in b["end_to_end"]:
        assert 0.01 <= m["bound"] <= 0.25
        assert m["source"] in ("host_clock", "device_trace")
    cells = {w["name"] for w in b["workloads"]}
    for m in b["per_layer"]:
        assert m["moves"] in e2e
        assert set(m.get("workloads", cells)) <= cells
    for w in b["workloads"]:
        assert w["chips"] in (1, 4) and len(w["why"]) <= 200
    assert 1 <= b["run_seconds"] <= 51


# a workflow shape of its own, as a later PR would add it
PAIRJOIN = """
from workload.maker import GB, WorkflowMaker


def build(scale=1.0, seed=0):
    b = WorkflowMaker("pairjoin", seed)
    outs = []
    for _ in range(2):
        _, o = b.task("make", dfs_inputs=GB, out_sizes=[GB], compute=5.0)
        outs.extend(o)
    b.task("join", inputs=outs, out_sizes=[GB], compute=5.0)
    return b.build()
"""


def test_new_files_are_found_without_editing(tmp_path):
    """A config, a workflow builder, a mix, a runner and a metric added as
    files of their own, plus one new ``workloads`` entry, make a cell that
    runs."""
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    before = {}
    for d, _, files in os.walk(bench):
        for n in files:
            p = os.path.join(d, n)
            before[p] = open(p, "rb").read()
    cfg = harness.load_json("configs", "k8s5000", bench)
    cfg.update(name="tiny4", n_nodes=4, workflow_scale=0.05)
    with open(os.path.join(bench, "configs", "tiny4.json"), "w") as f:
        json.dump(cfg, f)
    with open(os.path.join(bench, "workflows", "pairjoin.py"), "w") as f:
        f.write(PAIRJOIN)
    # a runner of its own: the service runner under another name
    with open(os.path.join(bench, "runners", "service2.py"), "w") as f:
        f.write("import plugins\n"
                "_s = plugins.load('runners', 'service')\n"
                "SIMULATED = True\n"
                "run = _s.run\n")
    mix = harness.load_json("traffic", "nfcore", bench)
    mix.update(runner="service2", max_backlog=2, warmup_sim_s=100.0,
               check_rounds=4)
    mix["tenants"] = [mix["tenants"][0],
                      {"name": "pj", "workflow": "pairjoin", "weight": 1,
                       "scale": 1.0}]
    with open(os.path.join(bench, "traffic", "pair.json"), "w") as f:
        json.dump(mix, f)
    with open(os.path.join(bench, "metrics", "rounds_per_s.py"), "w") as f:
        f.write("def read(run):\n"
                "    return run['rounds'] / run['window_s']\n")
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["configs"].append({"name": "tiny4", "source": "test",
                         "file": "bench/configs/tiny4.json", "reduced": [],
                         "why": "test"})
    b["workloads"].append({"name": "tiny4.pair", "config": "tiny4",
                           "traffic": "pair", "chips": 1, "why": "test"})
    b["per_layer"].append({"name": "rounds_per_s", "unit": "1/s",
                           "better": "higher", "source": "host_clock",
                           "layer": "adapter boundary",
                           "moves": "events_per_s",
                           "workloads": ["tiny4.pair"]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    import jax
    res = harness.run_cell("tiny4.pair", 3, 0.3, True, 0.0,
                           jax.devices(), bench_dir=bench, root=root,
                           log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["checks"]["priority_mismatches"]["value"] == 0
    assert res["metrics"]["rounds_per_s"]["value"] > 0
    assert "step1_ms_per_round" not in res["metrics"]  # not this cell's
    for p, data in before.items():
        assert open(p, "rb").read() == data, p


def _cfg(**kw) -> dict:
    cfg = harness.load_json("configs", "k8s5000")
    cfg.update(kw)
    return cfg


def test_config_keys_pass_to_the_simulator():
    from workload.simconfig import sim_config
    sc = sim_config(_cfg(n_nodes=12, gc_replicas=True,
                         topology={"rack_size": 4}), 2 ** 31 + 3)
    assert (sc.n_nodes, sc.gc_replicas, sc.seed) == (12, True, 2 ** 31 + 3)
    assert sc.topology.rack_size == 4
    assert sc.mem == 128 * 1024 ** 3 and sc.net_bw == 125e6


@pytest.mark.parametrize("key,value", [("n_nodez", 3), ("batched", "jax"),
                                       ("seed", 1), ("nodes", 8),
                                       ("n_nodes", {"a": 1})])
def test_config_keys_unknown_or_options_are_refused(key, value):
    from workload.simconfig import sim_config
    with pytest.raises(harness.SetupError):
        sim_config(_cfg(**{key: value}), 1)


@pytest.mark.parametrize("extra", [{"topology": {"rack_size": 4}},
                                   {"dfs": "nfs"}, {"gc_replicas": True}])
def test_config_the_reference_does_not_model_is_refused(extra, tmp_path):
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    bench_tiny.shrink(root, "k8s5000.nfcore")
    cfg = harness.load_json("configs", "k8s5000", bench)
    cfg.update(extra)
    with open(os.path.join(bench, "configs", "k8s5000.json"), "w") as f:
        json.dump(cfg, f)
    with pytest.raises(harness.SetupError):
        harness.run_cell("k8s5000.nfcore", 1, 0.1, False, 0.0, [],
                         bench_dir=bench, root=root, log=lambda m: None)


def test_fanin_cell_is_data_only(tmp_path):
    """``k8s5000.fanin`` needs only a ``workloads`` entry: the replay
    runner and the fan-in mix are there."""
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        b = json.load(f)
    b["workloads"].append({"name": "k8s5000.fanin", "config": "k8s5000",
                           "traffic": "fanin", "chips": 1, "why": "test"})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(b, f)
    bench_tiny.shrink(root, "k8s5000.fanin")
    import jax
    res = harness.run_cell("k8s5000.fanin", 9, 0.5, False, 0.0,
                           jax.devices(), bench_dir=bench, root=root,
                           log=lambda m: None)
    assert res["correct"], res["checks"]
    assert "completion_gap" not in res["checks"]
    assert res["metrics"]["events_per_s"]["value"] > 0
