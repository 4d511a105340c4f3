"""nf-core/sarek, Table I of the WOW paper: 205.9 GB in, 918.8 GB out,
49 abstract and 8656 physical tasks at scale 1.0 (12 samples, 60
intervals, three callers).

Copied from ``src/repro/workloads/realworld.py``; per-task sizes are kept
at their Table I values under ``scale`` (``workload/maker.py``)."""
from workload.maker import GB, WorkflowMaker, scaled_count

SAMPLES = 12
INTERVALS = 60
ALIGN = ["fastp", "bwamem", "sort", "markdup", "bqsr_table", "apply_bqsr"]
SHARE = (0.10, 0.35, 0.25, 0.15, 0.05, 0.10)
CALLERS = ("haplotypecaller", "deepvariant", "strelka")
OUT = 918.8 * GB


def build(scale: float = 1.0, seed: int = 0):
    b = WorkflowMaker("sarek", seed)
    n_samples = scaled_count(SAMPLES, scale, 2)
    n_intervals = scaled_count(INTERVALS, scale, 4)
    sample_in = 205.9 * GB / SAMPLES
    sample_out = OUT * 0.55 / SAMPLES
    interval_bytes = OUT * 0.40 / (SAMPLES * INTERVALS * len(CALLERS))
    for _ in range(n_samples):
        bam = b.chain(ALIGN, dfs_in=int(sample_in),
                      sizes=[int(sample_out * w) for w in SHARE],
                      compute=240.0)
        calls = []
        for _ in range(n_intervals):
            for caller in CALLERS:
                _, out = b.task(caller, inputs=bam,
                                out_sizes=[int(interval_bytes)],
                                compute=b.uniform(30, 90))
                calls.append(out[0])
        _, merged = b.task("merge_vcf", inputs=calls,
                           out_sizes=[int(OUT * 0.04 / SAMPLES)],
                           compute=60.0)
        b.task("annotate", inputs=merged,
               out_sizes=[int(OUT * 0.01 / SAMPLES)], compute=60.0)
    return b.build()
