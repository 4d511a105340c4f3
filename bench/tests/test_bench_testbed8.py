"""The cell ``testbed8.tableI`` at a tiny size on the CPU: the WOW paper's
8-node testbed under the Table I stream runs ``correct``, its step-2/3
drain shows in a traced run, and the run's seed does not change the run."""
from __future__ import annotations

import pytest

pytest.importorskip("numpy")

import bench_tiny  # noqa: E402
import harness  # noqa: E402

CELL = "testbed8.tableI"


def test_tiny_cell_is_correct_and_reads_its_drain(tmp_path):
    import jax
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    bench_tiny.shrink(root, CELL)
    res = harness.run_cell(CELL, 2 ** 31 + 101, 1.0, True, 0.0,
                           jax.devices(), bench_dir=bench, root=root,
                           log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert all(v["value"] == 0 for v in res["checks"].values())
    share = res["metrics"]["drain_share"]
    assert share["unit"] == "share"
    assert 0 < share["value"] < 1


def test_tableI_run_is_the_same_for_every_seed(tmp_path):
    """As for the nf-core service: two seeds give one decision log, as far
    as both windows reach."""
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    bench_tiny.shrink(root, CELL)
    cfg, mix, runner = harness.resolve(harness.load_benchmark(root), CELL,
                                       bench)
    logs = [runner.run(cfg, mix, seed, 0.3, None, bench).log
            for seed in (3, 2 ** 31 + 29)]
    n = min(map(len, logs))
    assert n > 100
    assert logs[0][:n] == logs[1][:n]


def test_tableI_stream_holds_the_four_table_one_workflows():
    cfg, mix, _ = harness.resolve(harness.load_benchmark(), CELL)
    assert cfg["n_nodes"] == 8 and cfg["reduced"] == ["workflow_scale"]
    from workload.arrivals import service_arrivals
    arrivals = service_arrivals(mix)
    assert len(arrivals) == mix["n_arrivals"]
    first = {a["workflow"] for a in arrivals[:4]}
    assert first == {"rnaseq", "sarek", "chipseq", "rangeland"}
    # the stream outlasts any window: 5,000 arrivals at 0.002/s
    assert arrivals[-1]["time"] > 2_000_000
