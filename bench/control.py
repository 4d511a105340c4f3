"""Readings for the limits of ``correct``: sound runs and the control.

    python bench/control.py --workload <cell> --seconds <s> --seeds 1,2,3

For each seed, in one process, runs the cell's window as ``run.py`` does
and compares the log twice: with the reference (the sound reading) and
with the control, the reference computed in float32
(``reference/check.py``).  Prints one JSON line per seed.  The control
has to come out as not correct on every seed.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import harness  # noqa: E402


def readings(name: str, seed: int, seconds: float, bench_dir=harness.BENCH,
             root=harness.ROOT) -> dict:
    from reference.check import compare, verdict
    bench = harness.load_benchmark(root)
    cfg, mix, runner = harness.resolve(bench, name, bench_dir)
    rec = runner.run(cfg, mix, seed, seconds, None, bench_dir)
    log, start, sim_seed = rec.log, rec.window_log_start, rec.sim_seed
    out = {"seed": seed, "rounds": len(rec.round_s)}
    del rec
    gc.collect()
    for side, control in (("sound", False), ("control", True)):
        nums = compare(cfg, log, start, seed, mix["check_rounds"],
                       simulated=runner.SIMULATED, control=control,
                       ranks=harness.tenant_ranks(mix, cfg, bench_dir),
                       sim_seed=sim_seed or 0)
        ok, _ = verdict(nums)
        nums["correct"] = ok
        out[side] = nums
    return out


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    args = ap.parse_args()
    sys.path.insert(0, os.path.join(harness.ROOT, "src"))
    try:
        cell = harness.find_cell(harness.load_benchmark(), args.workload)
        harness.tpu_devices(cell["chips"])
    except harness.SetupError as e:
        print(f"control: {e}", file=sys.stderr)
        return 2
    for s in args.seeds.split(","):
        print(json.dumps(readings(args.workload, int(s), args.seconds)),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
