"""Helpers for the benchmark's CPU tests: a copy of the benchmark in a
temporary directory with its cells cut to a tiny size."""
from __future__ import annotations

import json
import os
import shutil
import sys

TESTS = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(TESTS)
ROOT = os.path.dirname(BENCH)
for p in (BENCH, os.path.join(ROOT, "src")):
    if p not in sys.path:
        sys.path.insert(0, p)

# per runner: what a tiny rehearsal of a cell changes
TINY = {
    "service": {"configs": {"n_nodes": 16},
                "traffic": {"max_backlog": 2, "warmup_sim_s": 200.0,
                            "check_rounds": 6}},
    "replay": {"configs": {"n_nodes": 16},
               "traffic": {"ready_per_node": 2, "batch": 4,
                           "warmup_rounds": 1, "check_rounds": 4}},
}
# workflow scale small enough to run in a test, large enough for a few
# whole tasks
TINY_SCALE = 0.05


def copy_bench(dst: str) -> tuple[str, str]:
    """Copy ``BENCHMARK.json`` and ``bench/`` into ``dst``; returns the
    copy's (root, bench dir)."""
    shutil.copytree(BENCH, os.path.join(dst, "bench"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), dst)
    return dst, os.path.join(dst, "bench")


def shrink(root: str, cell_name: str) -> None:
    """Cut one cell of the copy at ``root`` to a tiny size, in place."""
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = next(c for c in bench["workloads"] if c["name"] == cell_name)
    path = os.path.join(root, "bench", "traffic", cell["traffic"] + ".json")
    with open(path) as f:
        runner = json.load(f)["runner"]
    for kind, name in (("configs", cell["config"]),
                       ("traffic", cell["traffic"])):
        path = os.path.join(root, "bench", kind, name + ".json")
        with open(path) as f:
            d = json.load(f)
        d.update(TINY[runner][kind])
        if kind == "configs" and "workflow_scale" in d:
            d["workflow_scale"] = TINY_SCALE
        with open(path, "w") as f:
            json.dump(d, f)


def cells() -> list[str]:
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        return [c["name"] for c in json.load(f)["workloads"]]
