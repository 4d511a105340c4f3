"""Resolve a cell by name and run it once: set-up, window, reference check.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own, found by the name ``BENCHMARK.json``
gives it:

* ``bench/configs/<config>.json``: the deployment (cluster, resources,
  network, DFS, WOW's ``c_node`` / ``c_task``, its source);
* ``bench/traffic/<traffic>.json``: the traffic mix, data read by the
  runner its ``runner`` key names, ``bench/runners/<runner>.py``; a
  service mix names each tenant's workflow, ``bench/workflows/<name>.py``;
* ``bench/metrics/<metric>.py``: a reader ``read(run) -> float | None``
  over the run summary of :meth:`record.Recorder.summary` (with the
  run's ``setup_s``), for end-to-end and per-layer metrics alike.

``plugins.load`` finds each of these files by its name.
"""
from __future__ import annotations

import gc
import json
import math
import os
import sys
import tempfile
import time

import plugins
from plugins import SetupError

BENCH = plugins.BENCH
ROOT = os.path.dirname(BENCH)
CACHE_DIR = os.path.join(BENCH, ".cache", "jax")


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def find_cell(bench: dict, name: str) -> dict:
    for cell in bench["workloads"]:
        if cell["name"] == name:
            return cell
    raise SetupError(f"no workload {name!r} in BENCHMARK.json")


def load_json(kind: str, name: str, bench_dir: str = BENCH) -> dict:
    path = os.path.join(bench_dir, kind, name + ".json")
    if not os.path.exists(path):
        raise SetupError(f"no {kind} file {path}")
    with open(path) as f:
        return json.load(f)


def load_metric(name: str, bench_dir: str = BENCH):
    return plugins.load("metrics", name, bench_dir).read


def percentile(values: list[float], q: float) -> float | None:
    """Nearest rank: the ceil(q/100 * n)-th smallest value (copied from
    ``src/repro/sim/metrics.py``)."""
    if not values:
        return None
    xs = sorted(values)
    rank = max(1, math.ceil(q / 100.0 * len(xs)))
    return xs[min(rank, len(xs)) - 1]


def tpu_devices(chips: int):
    """The accelerator devices, or :class:`SetupError` when JAX finds no
    TPU or fewer chips than the cell asks for.  Never falls back."""
    import jax
    try:
        devs = jax.devices()
    except RuntimeError as e:
        raise SetupError(f"JAX finds no accelerator: {e}") from None
    if not devs or devs[0].platform != "tpu":
        raise SetupError(f"JAX finds no TPU (platform "
                         f"{devs[0].platform if devs else None!r})")
    if len(devs) < chips:
        raise SetupError(f"the cell asks for {chips} chips, JAX finds "
                         f"{len(devs)}")
    return devs[:chips]


def enable_compile_cache() -> None:
    import jax
    jax.config.update("jax_compilation_cache_dir",
                      os.environ.get("JAX_COMPILATION_CACHE_DIR", CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def resolve(bench: dict, name: str, bench_dir: str = BENCH):
    """``(config, traffic mix, runner module)`` of the cell ``name``."""
    cell = find_cell(bench, name)
    cfg = load_json("configs", cell["config"], bench_dir)
    mix = load_json("traffic", cell["traffic"], bench_dir)
    return cfg, mix, plugins.load("runners", mix.get("runner"), bench_dir)


def tenant_ranks(mix: dict, cfg: dict, bench_dir: str = BENCH) -> dict | None:
    """``{tenant: {abstract task: rank}}`` of a service mix's workflows, from
    the benchmark's own builders, for the reference's priorities; ``None``
    for a mix whose runner sets priorities itself."""
    from reference.sched import abstract_ranks
    if "tenants" not in mix:
        return None
    out = {}
    for t in mix["tenants"]:
        wf = plugins.load("workflows", t["workflow"], bench_dir).build(
            scale=t["scale"] * cfg["workflow_scale"], seed=0)
        out[t["name"]] = abstract_ranks(wf.abstract_edges)
    return out


def run_cell(name: str, seed: int, seconds: float, trace: bool,
             t_start: float, devices=None, bench_dir: str = BENCH,
             root: str = ROOT, log=print, t_devices: float | None = None
             ) -> dict:
    """Run one cell once and return the result line's dict.  ``devices``
    is what :func:`tpu_devices` returned (tests pass CPU devices)."""
    from reference.check import Unmodeled, compare, refuse_unmodeled, verdict

    bench = load_benchmark(root)
    cfg, mix, runner = resolve(bench, name, bench_dir)
    try:
        refuse_unmodeled(cfg)
    except Unmodeled as e:
        raise SetupError(str(e)) from None
    # the metrics of this run: the cell's end-to-end metrics, or with
    # ``trace`` its per-layer ones, each read by ``metrics/<name>.py``
    listed = [m for m in bench["per_layer" if trace else "end_to_end"]
              if name in m.get("workloads", [name])]
    readers = {m["name"]: (load_metric(m["name"], bench_dir), m["unit"])
               for m in listed}
    tracer = None
    if trace:
        from devtrace import TRACE_AT, TRACE_S, Tracer
        tracer = Tracer(TRACE_AT * seconds, TRACE_S,
                        tempfile.mkdtemp(prefix="bench-trace-"))
    rec = runner.run(cfg, mix, seed, seconds, tracer, bench_dir)
    if tracer is not None:
        tracer.finish()
    setup_s = rec.t0 - t_start
    simulated = bool(getattr(runner, "SIMULATED", False))
    run = dict(rec.summary(simulated), setup_s=setup_s)
    device = {"platform": devices[0].platform,
              "kind": devices[0].device_kind, "count": len(devices),
              "memory_peak_bytes": max(
                  (d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                  for d in devices)}
    result: dict = {}
    metrics = {}
    for mname, (read, unit) in readers.items():
        v = read(run)
        if v is not None:
            metrics[mname] = {"value": v, "unit": unit}
    if trace:
        red = tracer.reduce()
        if red is not None:
            device["busy_s"] = red["busy_s"]
            device["window_s"] = red["window_s"]
            result["breakdown"] = {"device_ops": red["device_ops"],
                                   "idle_gaps": red["idle_gaps"]}
    # the reference runs once the window has closed and the program's
    # state is gone
    window_start = rec.window_log_start
    sim_seed = rec.sim_seed
    logged = rec.log
    rec_install, rec_t0 = rec.t_install, rec.t0
    del rec
    gc.collect()
    numbers = compare(cfg, logged, window_start, seed, mix["check_rounds"],
                      simulated=simulated,
                      ranks=tenant_ranks(mix, cfg, bench_dir),
                      sim_seed=sim_seed or 0)
    ok, checks = verdict(numbers)
    t_dev = t_start if t_devices is None else t_devices
    log(f"set-up {setup_s:.2f} s: imports and TPU init "
        f"{t_dev - t_start:.2f} s, cluster and traffic built "
        f"{rec_install - t_dev:.2f} s, ramp {rec_t0 - rec_install:.2f} s")
    log(f"reference: {numbers['rounds_checked']} rounds checked in "
        f"{numbers['reference_s']:.1f} s; window {run['rounds']} rounds, "
        f"{run['events']} events, set-up {setup_s:.1f} s "
        f"({run['setup_rounds']} rounds)")
    if run["round_s"]:
        log(f"rounds: p50 {percentile(run['round_s'], 50) * 1e3:.5f} ms, "
            f"p99 {percentile(run['round_s'], 99) * 1e3:.4f} ms, max "
            f"{max(run['round_s']) * 1e3:.1f} ms, adapter "
            f"{run['adapter_s']:.2f} s of {run['window_s']:.2f} s")
    log(f"window: simulated time {run['sim_span'][0]:.1f} to "
        f"{run['sim_span'][1]:.1f} s; in fifths, events/s (rounds): "
        + ", ".join(f"{ev:.1f} ({r})" for ev, r in run["slices"]))
    if run["cut_short"]:
        log("the traffic ran dry before the window's end: the window is "
            "shorter than asked")
    if numbers["first_mismatch"]:
        log("first mismatch: " + numbers["first_mismatch"])
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} (limit {lim!r})")
    result.update({"correct": ok, "attempted": run["rounds"],
                   "failed": sum(numbers.get(k, 0) for k in (
                       "decision_mismatches", "priority_mismatches",
                       "completion_misses")),
                   "metrics": metrics, "device": device,
                   "checks": {k: {"value": v, "limit": lim}
                              for k, (v, lim) in checks.items()}})
    return result


def main(argv: list[str], t_start: float) -> int:
    import argparse
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    sys.path.insert(0, os.path.join(ROOT, "src"))

    def log(msg: str) -> None:
        print(msg, file=sys.stderr, flush=True)
    try:
        bench = load_benchmark()
        cell = find_cell(bench, args.workload)
        import repro  # noqa: F401  -- the system under test must be here
        devices = tpu_devices(cell["chips"])
        enable_compile_cache()
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), t_start, devices, log=log,
                          t_devices=time.perf_counter())
    except (SetupError, ImportError, FileNotFoundError) as e:
        log(f"bench: {e}")
        return 2
    # numbers compared, beside their limits, as the last lines of stderr
    for k, v in result["checks"].items():
        log(f"{k} {v['value']!r} limit {v['limit']!r}")
    print(json.dumps(result), flush=True)
    return 0
