"""FORCE/Rangeland, Table I of the WOW paper: 303.2 GB in, 274.0 GB out,
8 abstract and 3184 physical tasks at scale 1.0 (1500 images, 520 tiles).

Copied from ``src/repro/workloads/realworld.py``; per-task sizes are kept
at their Table I values under ``scale`` (``workload/maker.py``)."""
from workload.maker import GB, WorkflowMaker, scaled_count

IMAGES = 1500
TILES = 520
OUT = 274.0 * GB


def build(scale: float = 1.0, seed: int = 0):
    b = WorkflowMaker("rangeland", seed)
    n_imgs = scaled_count(IMAGES, scale, 6)
    n_tiles = scaled_count(TILES, scale, 4)
    l2_outs = []
    for _ in range(n_imgs):
        _, o = b.task("level2", dfs_inputs=int(303.2 * GB / IMAGES),
                      out_sizes=[int(OUT * 0.45 / IMAGES)],
                      compute=b.uniform(60, 180), cores=4.0)
        l2_outs.append(o[0])
    mosaics = []
    for i in range(n_tiles):
        part = l2_outs[i::n_tiles]
        if not part:
            continue
        _, cube = b.task("cube", inputs=part,
                         out_sizes=[int(OUT * 0.25 / TILES)],
                         compute=b.uniform(30, 90))
        _, tsa = b.task("tsa", inputs=cube,
                        out_sizes=[int(OUT * 0.20 / TILES)],
                        compute=b.uniform(60, 150), cores=4.0)
        _, trend = b.task("trend", inputs=tsa,
                          out_sizes=[int(OUT * 0.08 / TILES)],
                          compute=b.uniform(30, 90))
        mosaics.append(trend[0])
    _, mos = b.task("mosaic", inputs=mosaics, out_sizes=[int(OUT * 0.015)],
                    compute=120.0, cores=4.0)
    _, pyr = b.task("pyramid", inputs=mos, out_sizes=[int(OUT * 0.004)],
                    compute=60.0)
    b.task("report", inputs=pyr, out_sizes=[int(0.5 * GB)], compute=30.0)
    return b.build()
