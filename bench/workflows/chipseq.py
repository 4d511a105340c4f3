"""nf-core/chipseq, Table I of the WOW paper: 141.2 GB in, 787.2 GB out,
48 abstract and 3537 physical tasks at scale 1.0 (30 samples).

Copied from ``src/repro/workloads/realworld.py``; per-task sizes are kept
at their Table I values under ``scale`` (``workload/maker.py``)."""
from workload.maker import GB, WorkflowMaker, scaled_count

SAMPLES = 30
STEPS = ["fastqc", "trimgalore", "bwa_align", "sort", "merge_bam", "markdup",
         "filter_bam", "bigwig", "macs2", "homer_annotate"]
SHARE = (0.02, 0.12, 0.28, 0.22, 0.05, 0.10, 0.10, 0.06, 0.03, 0.02)
EXTRA = ("phantompeak", "plotfingerprint", "featurecounts_qc")


def build(scale: float = 1.0, seed: int = 0):
    b = WorkflowMaker("chipseq", seed)
    n_samples = scaled_count(SAMPLES, scale, 2)
    sample_in = 141.2 * GB / SAMPLES
    sample_out = 787.2 * GB * 0.9 / SAMPLES
    peak_files = []
    for _ in range(n_samples):
        last = b.chain(STEPS, dfs_in=int(sample_in),
                       sizes=[int(sample_out * w) for w in SHARE],
                       compute=150.0)
        peak_files.extend(last)
        for extra in EXTRA:
            b.task(extra, inputs=last, out_sizes=[int(0.3 * GB)],
                   compute=b.uniform(20, 60))
    _, consensus = b.task("consensus_peaks", inputs=peak_files,
                          out_sizes=[int(2 * GB)], compute=90.0)
    b.task("multiqc", inputs=consensus, out_sizes=[int(1 * GB)],
           compute=30.0)
    return b.build()
