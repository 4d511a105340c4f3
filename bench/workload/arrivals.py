"""The arrival stream of the service mixes: the same for every run.

The Poisson arrival process is copied from ``src/repro/sim/traffic.py``
(``arrival_schedule``, constant-rate branch): inter-arrival gaps are
``Exp(rate)`` drawn from one stream RNG.  ``ARRIVAL_SEED`` fixes the
arrival times, the sequence of tenants (shuffled inside consecutive
blocks of ``BLOCK`` whose tenant counts follow the weights exactly) and
each arrival's workflow instance (its instance seed, which sets the
tasks' compute times).

The run's seed takes no part: the closed loop of engine, simulator and
scheduler is chaotic, and any change to its input, even only the order of
the same instances, changes how much work a window holds.
"""
from __future__ import annotations

import random

ARRIVAL_SEED = 2025
BLOCK = 4


def poisson_times(rate: float, n: int, rng: random.Random) -> list[float]:
    out: list[float] = []
    t = 0.0
    while len(out) < n:
        t += rng.expovariate(rate)
        out.append(t)
    return out


def block_counts(tenants: list[dict], block: int) -> list[int]:
    """Instances of each tenant in one block, by largest remainder."""
    total = sum(t["weight"] for t in tenants)
    exact = [block * t["weight"] / total for t in tenants]
    counts = [int(x) for x in exact]
    order = sorted(range(len(tenants)), key=lambda i: (counts[i] - exact[i], i))
    for i in order[:block - sum(counts)]:
        counts[i] += 1
    return counts


def service_arrivals(mix: dict) -> list[dict]:
    """The mix's arrivals.  Each is a dict with ``time``, ``tenant``,
    ``workflow``, ``scale`` and ``seed`` (the instance's own seed)."""
    tenants = mix["tenants"]
    n = int(mix["n_arrivals"])
    fixed = random.Random(ARRIVAL_SEED)
    times = poisson_times(float(mix["rate"]), n, fixed)
    counts = block_counts(tenants, BLOCK)
    kinds: list[int] = []
    while len(kinds) < n:
        slots = [i for i, c in enumerate(counts) for _ in range(c)]
        fixed.shuffle(slots)
        kinds.extend(slots)
    taken = [0] * len(tenants)
    out: list[dict] = []
    for t, i in zip(times, kinds[:n]):
        ten = tenants[i]
        out.append({"time": t, "tenant": ten["name"],
                    "workflow": ten["workflow"], "scale": ten["scale"],
                    "seed": ARRIVAL_SEED * 1_000_003 + i * 100_003
                    + taken[i]})
        taken[i] += 1
    return out
