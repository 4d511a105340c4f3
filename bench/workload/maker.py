"""A small builder of workflow DAGs, shared by the files in ``workflows/``.

Copied from the program's workload DSL (``src/repro/workloads/builder.py``)
so that the benchmark's traffic does not move when the program's own
generators change.  It emits the program's input types (``TaskSpec``,
``FileSpec``, ``Workflow``), since that is what the system under test
consumes.

``scale`` in a builder scales how many samples, intervals or images a
workflow has.  Every task keeps its Table I compute time and the size of
every file it reads and writes, so the workflow's total volume scales with
its task count.
"""
from __future__ import annotations

import random

GB = 1_000_000_000
GiB = 1024 ** 3
MEM = 8 * GiB


def scaled_count(n: int, scale: float, minimum: int = 1) -> int:
    return max(minimum, round(n * scale))


class WorkflowMaker:
    def __init__(self, name: str, seed: int = 0) -> None:
        self.name = name
        self.rng = random.Random(seed)
        self.tasks: dict = {}
        self.files: dict = {}
        self.abstract_edges: dict[str, set[str]] = {}
        self._next_task = 0
        self._next_file = 0
        self._file_producer_abstract: dict[int, str] = {}

    def task(self, abstract: str, inputs=None, out_sizes=None,
             dfs_inputs: int = 0, dfs_outputs: int = 0, compute: float = 0.0,
             cores: float = 2.0, mem: int = MEM) -> tuple[int, list[int]]:
        from repro.core.types import FileSpec, TaskSpec
        inputs = inputs or []
        out_sizes = out_sizes or []
        tid = self._next_task
        self._next_task += 1
        out_ids: list[int] = []
        for size in out_sizes:
            fid = self._next_file
            self._next_file += 1
            self.files[fid] = FileSpec(id=fid, size=int(size), producer=tid)
            self._file_producer_abstract[fid] = abstract
            out_ids.append(fid)
        for f in inputs:
            self.files[f].consumers.add(tid)
            src = self._file_producer_abstract[f]
            if src != abstract:
                self.abstract_edges.setdefault(src, set()).add(abstract)
        self.abstract_edges.setdefault(abstract, set())
        self.tasks[tid] = TaskSpec(
            id=tid, abstract=abstract, mem=int(mem), cores=float(cores),
            inputs=tuple(inputs), dfs_inputs=int(dfs_inputs),
            outputs=tuple(out_ids), dfs_outputs=int(dfs_outputs),
            compute_time=float(compute))
        return tid, out_ids

    def uniform(self, lo: float, hi: float) -> float:
        return self.rng.uniform(lo, hi)

    def chain(self, steps: list[str], dfs_in: int, sizes: list[int],
              compute: float, cores: float = 4.0) -> list[int]:
        """One sample's linear chain of ``steps``; returns the last output."""
        prev: list[int] | None = None
        for i, s in enumerate(steps):
            if prev is None:
                _, prev = self.task(s, dfs_inputs=dfs_in,
                                    out_sizes=[sizes[i]],
                                    compute=self.uniform(0.7, 1.3) * compute,
                                    cores=cores)
            else:
                _, prev = self.task(s, inputs=prev, out_sizes=[sizes[i]],
                                    compute=self.uniform(0.7, 1.3) * compute,
                                    cores=cores)
        return prev

    def build(self):
        from repro.sim.workflow import Workflow
        return Workflow(self.name, self.tasks, self.files,
                        self.abstract_edges)
