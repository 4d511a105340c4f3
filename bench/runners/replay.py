"""Runner ``replay``: no simulator; the runner itself plays the cluster.

It calls ``submit``, ``task_finished``, ``cop_finished`` and ``schedule``
on ``make_adapter("wow", ...)`` (default options) and places input
replicas through the DPS, as the fan-in mix (``traffic/fanin.json``)
describes: 2-input join tasks whose inputs lie on seeded random hosts, a
constant ready backlog of ``ready_per_node`` tasks per node, and per round
the completions of ``batch`` running tasks and ``batch`` COPs, with as
many new tasks submitted.  The first ``warmup_rounds`` rounds are set-up.
"""
from __future__ import annotations

import random

import plugins
from record import Recorder, WindowClosed
from workload.simconfig import sim_config

SIMULATED = False
GiB = 1024 ** 3
FILE_BASE = 10 ** 9                       # file ids disjoint from task ids


def run(cfg: dict, mix: dict, seed: int, seconds: float, tracer=None,
        bench_dir: str = plugins.BENCH) -> Recorder:
    from repro.core import FileSpec, NodeState, TaskSpec, make_adapter

    sc = sim_config(cfg, seed)
    n = sc.n_nodes
    nodes = {i: NodeState(i, sc.mem, sc.cores) for i in range(n)}
    wow = make_adapter("wow", nodes, c_node=sc.c_node, c_task=sc.c_task,
                       seed=seed)
    clock = [0.0]
    rec = Recorder(seconds,
                   setup_done=lambda r: r >= int(mix["warmup_rounds"]),
                   sim_time=lambda: clock[0], tracer=tracer)
    rec.install(wow, wow.sched, spans=tracer is not None)
    rng = random.Random(seed)
    lo, hi = mix["input_gib"]
    next_file = FILE_BASE

    def submit(tid: int) -> None:
        nonlocal next_file
        inputs = []
        for _ in range(int(mix["inputs"])):
            hosts = rng.sample(range(n), int(mix["replicas"]))
            wow.dps.register_file(
                FileSpec(id=next_file, size=rng.randint(lo, hi) * GiB,
                         producer=-1), hosts[0])
            for h in hosts[1:]:
                wow.dps.add_replica(next_file, h)
            inputs.append(next_file)
            next_file += 1
        wow.submit(TaskSpec(id=tid, abstract="join",
                            mem=int(mix["task_mem_gib"] * GiB),
                            cores=float(mix["task_cores"]),
                            inputs=tuple(inputs),
                            priority=rng.uniform(1.0, 10.0)))

    backlog = int(mix["ready_per_node"]) * n
    for tid in range(backlog):
        submit(tid)
    next_id = backlog
    running: dict[int, int] = {}         # started tasks, in start order
    cops: dict[int, object] = {}         # COPs in flight, in start order
    batch = int(mix["batch"])
    try:
        while True:
            for act in wow.schedule():
                plan = getattr(act, "plan", None)
                if plan is None:
                    running[act.task_id] = act.node
                else:
                    cops[plan.id] = plan
            clock[0] += 1.0
            done = list(running.items())[:batch]
            for tid, node in done:
                del running[tid]
                wow.task_finished(tid, node)
            for pid in list(cops)[:batch]:
                wow.cop_finished(cops.pop(pid), True)
            for _ in done:
                submit(next_id)
                next_id += 1
    except WindowClosed:
        rec.close()
        return rec
