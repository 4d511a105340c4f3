"""Every cell builds and runs its first rounds at a tiny size on the CPU,
and the measuring entry refuses to run without a TPU."""
from __future__ import annotations

import os
import subprocess
import sys

import pytest

pytest.importorskip("numpy")

import bench_tiny  # noqa: E402
import harness  # noqa: E402


@pytest.mark.parametrize("trace", [0, 1])
@pytest.mark.parametrize("cell", bench_tiny.cells())
def test_cell_runs_tiny(cell, trace, tmp_path):
    import jax
    root, bench = bench_tiny.copy_bench(str(tmp_path))
    bench_tiny.shrink(root, cell)
    res = harness.run_cell(cell, 2 ** 31 + 11, 0.5, bool(trace), 0.0,
                           jax.devices(), bench_dir=bench, root=root,
                           log=lambda m: None)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert res["device"]["platform"] == "cpu"
    assert list(res)[-1] == "checks"
    b = harness.load_benchmark(root)
    if trace:
        want = {m["name"] for m in b["per_layer"]
                if cell in m.get("workloads", [cell])}
        assert set(res["metrics"]) == want
        assert all(v["value"] > 0 for v in res["metrics"].values())
    else:
        want = {m["name"] for m in b["end_to_end"]
                if cell in m.get("workloads", [cell])}
        assert set(res["metrics"]) == want
        assert all(v["value"] > 0 for v in res["metrics"].values())


def _run(cwd: str, env_extra: dict) -> subprocess.CompletedProcess:
    env = dict(os.environ, JAX_PLATFORMS="cpu", **env_extra)
    env.pop("PYTHONPATH", None)
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", bench_tiny.cells()[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=120)


def test_run_exits_nonzero_without_tpu():
    p = _run(bench_tiny.ROOT, {})
    assert p.returncode != 0
    assert "no TPU" in p.stderr
    assert not p.stdout.strip()


def test_run_exits_nonzero_without_the_program(tmp_path):
    root, _ = bench_tiny.copy_bench(str(tmp_path))
    p = _run(root, {})
    assert p.returncode != 0
    assert not p.stdout.strip()
