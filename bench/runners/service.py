"""Runner ``service``: the simulated cluster behind an admission gate.

``repro.sim.Simulation`` owns the WOW adapter (``make_adapter("wow", ...)``
with default options), and the mix's seeded stream of workflow instances
(``workload/arrivals.py``) arrives through the simulator's admission gate.
Each tenant's workflow is built by ``workflows/<workflow>.py``.  The loop
is closed: the engine calls the scheduler after every event and waits for
its decisions.  The ramp up to the mix's ``warmup_sim_s`` of simulated
time is set-up; the window opens at the first round after it.

Every run is the same run: the arrivals (``workload/arrivals.py``) and the
simulator's and DPS's random streams (``SIM_SEED``) do not depend on the
run's seed, which draws only the rounds the reference checks.  The loop is
chaotic: a different tie-break stream alone moved a window's events per
second by a fifth.
"""
from __future__ import annotations

import plugins
from record import Recorder, WindowClosed
from workload.arrivals import service_arrivals
from workload.simconfig import sim_config

SIMULATED = True
SIM_SEED = 0


def tenant_workflows(mix: dict, bench_dir: str = plugins.BENCH) -> dict:
    """``{workflow name: builder}`` for the mix's tenants."""
    return {t["workflow"]: plugins.load("workflows", t["workflow"],
                                        bench_dir).build
            for t in mix["tenants"]}


def run(cfg: dict, mix: dict, seed: int, seconds: float, tracer=None,
        bench_dir: str = plugins.BENCH) -> Recorder:
    import repro.workloads as workloads
    from repro.sim.engine import Simulation
    from repro.sim.traffic import ArrivalSpec, TenantSpec, TrafficConfig

    # the engine builds each admitted instance by name: point the names at
    # the benchmark's own builders
    for name, build in tenant_workflows(mix, bench_dir).items():
        workloads._REGISTRY["bench." + name] = build
    scale = cfg["workflow_scale"]
    tenants = tuple(TenantSpec(t["name"], t["weight"],
                               ("bench." + t["workflow"],),
                               t["scale"] * scale)
                    for t in mix["tenants"])
    warm = float(mix["warmup_sim_s"])
    traffic = TrafficConfig(tenants=tenants, rate=mix["rate"], n_arrivals=1,
                            max_backlog=mix["max_backlog"], seed=0)
    sim = Simulation(None, sim_config(cfg, SIM_SEED), "wow",
                     traffic=traffic)
    sim._arrival_specs = [
        ArrivalSpec(index=i, time=a["time"], tenant=a["tenant"],
                    workflow="bench." + a["workflow"],
                    scale=a["scale"] * scale, seed=a["seed"])
        for i, a in enumerate(service_arrivals(mix))]
    rec = Recorder(seconds, setup_done=lambda _r: sim.time >= warm,
                   sim_time=lambda: sim.time,
                   file_size=sim.file_sizes.__getitem__, tracer=tracer)
    rec.sim_seed = SIM_SEED
    rec.install(sim.strategy, sim.strategy.sched, spans=tracer is not None)
    try:
        sim.run()
    except WindowClosed:
        rec.close()
        return rec
    if not rec.window:
        raise RuntimeError("the simulation ended before the window opened")
    rec.cut_short = True                  # the stream ran dry in the window
    rec.close()
    return rec
