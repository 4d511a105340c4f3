"""Benchmark aggregator: one function per paper table/figure + the
framework-side benches.  Prints ``name,...`` CSV lines and collects every
``BENCH_*.json`` at the repo root into one markdown report
(``BENCH_REPORT.md``, format documented in README.md "Benchmarks").

    PYTHONPATH=src python -m benchmarks.run [--only table2,table3,...]
    PYTHONPATH=src python -m benchmarks.run --report   # report only
    PYTHONPATH=src python -m benchmarks.run --only scheduler --profile
                                           # + cProfile per scenario

``--profile`` wraps each selected scenario in cProfile and writes the
top-``--profile-top`` functions by cumulative time to
``BENCH_profile.json`` (picked up by the report aggregator like every
other ``BENCH_*.json``), so "what is the top non-fill cost now?" is one
flag away instead of an ad-hoc script.
"""
from __future__ import annotations

import argparse
import cProfile
import glob
import json
import os
import pstats
import time


def roofline_summary(dryrun_dir: str = "experiments/dryrun") -> None:
    """Summarize the dry-run roofline JSONs (if the matrix has been run)."""
    files = sorted(glob.glob(f"{dryrun_dir}/*.json"))
    if not files:
        print("roofline,missing,run `python -m repro.launch.dryrun --all "
              "--multi-pod both --out experiments/dryrun` first")
        return
    print("roofline,arch,shape,mesh,compute_ms,memory_ms,collective_ms,"
          "bottleneck,useful_ratio,peak_fraction")
    for fn in files:
        with open(fn) as f:
            r = json.load(f)
        if r.get("status") != "ok":
            continue
        rl = r["roofline"]
        print(f"roofline,{r['arch']},{r['shape']},{r['mesh']},"
              f"{rl['compute_s'] * 1e3:.1f},{rl['memory_s'] * 1e3:.1f},"
              f"{rl['collective_s'] * 1e3:.1f},{rl['bottleneck']},"
              f"{rl['useful_ratio']:.2f},{rl['peak_fraction']:.4f}")


# ------------------------------------------------------------- profiling
def profile_call(name: str, fn, top_n: int = 15) -> list[dict]:
    """Run ``fn()`` under cProfile; return the top ``top_n`` functions by
    cumulative time as report rows (and echo them as CSV lines)."""
    prof = cProfile.Profile()
    prof.enable()
    try:
        fn()
    finally:
        prof.disable()
    stats = pstats.Stats(prof)
    rows: list[dict] = []
    print(f"profile,{name},ncalls,tottime_s,cumtime_s,function")
    for (fn_file, fn_line, fn_name), (cc, nc, tt, ct, _callers) in sorted(
            stats.stats.items(), key=lambda kv: -kv[1][3])[:top_n]:
        loc = f"{os.path.basename(fn_file)}:{fn_line}:{fn_name}"
        rows.append({"scenario": name, "function": loc, "ncalls": nc,
                     "tottime_s": round(tt, 4), "cumtime_s": round(ct, 4)})
        print(f"profile,{name},{nc},{tt:.3f},{ct:.3f},{loc}")
    return rows


# ----------------------------------------------------------- report writing
def _fmt(v) -> str:
    if isinstance(v, float):
        return f"{v:.4g}"
    if isinstance(v, (dict, list)):
        return "`" + json.dumps(v, sort_keys=True) + "`"
    return str(v)


def _rows_table(rows: list[dict]) -> list[str]:
    """Markdown table over the union of row keys (insertion-ordered)."""
    cols: list[str] = []
    for r in rows:
        for k in r:
            if k not in cols:
                cols.append(k)
    out = ["| " + " | ".join(cols) + " |",
           "|" + "|".join("---" for _ in cols) + "|"]
    for r in rows:
        out.append("| " + " | ".join(_fmt(r.get(k, "")) for k in cols) + " |")
    return out


def _scenario_tables(rows: list[dict]) -> list[str]:
    """One table per ``scenario`` (first-appearance order; rows without a
    scenario key form the leading base table).  Scenario rows carry
    scenario-specific columns -- one union table over all of them is
    unreadably sparse, which is why sim_throughput/dfs_churn/... get their
    own tables."""
    groups: dict[str, list[dict]] = {}
    for r in rows:
        scenario = r.get("scenario", "")
        groups.setdefault(scenario, []).append(
            {k: v for k, v in r.items() if k != "scenario"})
    out: list[str] = []
    for scenario, group in groups.items():
        if scenario:
            out.append(f"**scenario: {scenario}**")
            out.append("")
        out.extend(_rows_table(group))
        out.append("")
    return out


def _bullets(key, val, indent: int = 0) -> list[str]:
    """Nested-dict bullet rendering (sim_throughput/live_rm headlines)."""
    pad = "  " * indent
    if isinstance(val, dict):
        out = [f"{pad}- {key}:"]
        for k, v in val.items():
            out.extend(_bullets(k, v, indent + 1))
        return out
    return [f"{pad}- {key}: {_fmt(val)}"]


def aggregate_report(root: str | None = None,
                     out_name: str = "BENCH_REPORT.md") -> str | None:
    """Collect every BENCH_*.json under ``root`` into one markdown report.

    Per file: any ``rows`` list becomes a table, every other top-level key
    becomes a ``key: value`` bullet (nested dicts one bullet per leaf).
    Returns the report path, or None when no benchmark JSON exists yet.
    """
    if root is None:
        root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    files = sorted(glob.glob(os.path.join(root, "BENCH_*.json")))
    if not files:
        return None
    lines = ["# Benchmark report",
             "",
             "Auto-generated by `python -m benchmarks.run` from the "
             "`BENCH_*.json` files at the repo root; see README.md "
             "\"Benchmarks\" for how each file is produced.",
             ""]
    for fn in files:
        with open(fn) as f:
            payload = json.load(f)
        lines.append(f"## {os.path.basename(fn)}")
        lines.append("")
        if isinstance(payload, dict):
            for key, val in payload.items():
                if key == "rows" and isinstance(val, list) and val \
                        and all(isinstance(r, dict) for r in val):
                    lines.extend(_scenario_tables(val))
                elif isinstance(val, dict):
                    lines.append(f"**{key}**")
                    lines.append("")
                    for k, v in val.items():
                        lines.extend(_bullets(k, v))
                    lines.append("")
                else:
                    lines.append(f"- {key}: {_fmt(val)}")
        else:
            lines.append("```json")
            lines.append(json.dumps(payload, indent=2, sort_keys=True))
            lines.append("```")
        lines.append("")
    path = os.path.join(root, out_name)
    with open(path, "w") as f:
        f.write("\n".join(lines).rstrip() + "\n")
    print(f"# wrote {path}")
    return path


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma list: table2,table3,fig4,fig5,"
                         "scheduler,kernels,roofline")
    ap.add_argument("--report", action="store_true",
                    help="only regenerate BENCH_REPORT.md from existing "
                         "BENCH_*.json files")
    ap.add_argument("--profile", action="store_true",
                    help="wrap each selected scenario in cProfile and write "
                         "the top cumulative rows to BENCH_profile.json")
    ap.add_argument("--profile-top", type=int, default=15,
                    help="rows kept per profiled scenario (default 15)")
    args = ap.parse_args()
    if args.report:
        if aggregate_report() is None:
            print("report,missing,no BENCH_*.json at repo root yet")
        return
    only = set(args.only.split(",")) if args.only else None

    def want(name: str) -> bool:
        return only is None or name in only

    profile_rows: list[dict] = []

    def run_scenario(name: str, fn) -> None:
        if args.profile:
            profile_rows.extend(profile_call(name, fn,
                                             top_n=args.profile_top))
        else:
            fn()

    t0 = time.time()
    if want("table2"):
        from .table2_execution import main as t2
        run_scenario("table2", t2)
    if want("table3"):
        from .table3_network import main as t3
        run_scenario("table3", t3)
    if want("fig4"):
        from .fig4_overhead import main as f4
        run_scenario("fig4", f4)
    if want("fig5"):
        from .fig5_scaling import main as f5
        run_scenario("fig5", f5)
    if want("scheduler"):
        from .scheduler_scale import main as ss
        run_scenario("scheduler", ss)
    if want("kernels"):
        from .kernels import main as km
        run_scenario("kernels", km)
    if want("roofline"):
        roofline_summary()
    if args.profile and profile_rows:
        from .common import write_json
        write_json("profile", {
            "rows": profile_rows,
            "top_n": args.profile_top,
            "note": "top functions by cumulative time per scenario, "
                    "collected by `python -m benchmarks.run --profile`",
        })
    aggregate_report()
    print(f"benchmarks,total_wall_s,{time.time() - t0:.1f}")


if __name__ == "__main__":
    main()
