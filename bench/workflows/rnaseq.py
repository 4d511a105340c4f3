"""nf-core/rnaseq, Table I of the WOW paper: 139.1 GB in, 598.3 GB out,
53 abstract and 1269 physical tasks at scale 1.0 (24 samples).

Copied from ``src/repro/workloads/realworld.py``; per-task sizes are kept
at their Table I values under ``scale`` (``workload/maker.py``)."""
from workload.maker import GB, WorkflowMaker, scaled_count

SAMPLES = 24
MAIN = ["fastqc", "trimgalore", "star_align", "samtools_sort",
        "samtools_index", "markduplicates", "stringtie", "salmon_quant",
        "bigwig", "qualimap", "dupradar", "featurecounts"]
SIDE = [["rseqc_bamstat", "rseqc_innerdist", "rseqc_junction", "rseqc_dist"],
        ["preseq", "picard_metrics", "biotype_qc", "misc_qc"]]
SHARE = (0.02, 0.10, 0.26, 0.22, 0.01, 0.20, 0.05, 0.06, 0.05, 0.01, 0.01,
         0.01)


def build(scale: float = 1.0, seed: int = 0):
    b = WorkflowMaker("rnaseq", seed)
    n_samples = scaled_count(SAMPLES, scale, 2)
    sample_in = 139.1 * GB / SAMPLES
    sample_out = 598.3 * GB * 0.95 / SAMPLES
    finals = []
    for _ in range(n_samples):
        last = b.chain(MAIN, dfs_in=int(sample_in),
                       sizes=[int(sample_out * w) for w in SHARE],
                       compute=180.0)
        finals.extend(last)
        for side in SIDE:
            prev = last
            for s in side:
                _, prev = b.task(s, inputs=prev, out_sizes=[int(0.2 * GB)],
                                 compute=b.uniform(20, 60))
            finals.extend(prev)
    _, mq = b.task("multiqc", inputs=finals, out_sizes=[int(1 * GB)],
                   compute=60.0)
    b.task("report", inputs=mq, out_sizes=[int(0.2 * GB)], compute=20.0)
    return b.build()
