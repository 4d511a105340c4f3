"""The comparison that decides ``correct``: the run's log against the reference.

``compare`` replays the whole log of a run (set-up and window) through
:class:`~.sched.Replay` and, for simulated cells, :class:`~.flows.FlowModel`,
and returns the numbers compared, each beside its limit:

* ``decision_mismatches``: rounds, of those drawn for the check, whose
  decisions differ from the reference's; limit 0;
* ``priority_mismatches``: submitted tasks whose priority differs from the
  one the reference computes from the workflow's abstract DAG and the
  task's input bytes; limit 0 (cells whose environment is the program's
  engine);
* ``completion_misses``: completions in the window that the program
  reported and the model does not predict by then, or the other way
  round; limit 0 (simulated cells);
* ``completion_gap``: the largest gap between a completion time that the
  program reported in the window and the model's, as a share of the task's
  or COP's duration (simulated cells).

With ``control=True`` the reference computes every float it compares in
float32 (:func:`~.sched.f32`): the control that has to come out as not
correct.
"""
from __future__ import annotations

import random
import time

from .flows import FlowModel
from .ilp import identity
from .sched import Mismatch, Replay, f32

# Limits, set from readings of sound runs and of the control on the chip
# (PERF.md, section 2, lists the readings each was set from).
LIMITS = {"decision_mismatches": 0, "priority_mismatches": 0,
          "completion_misses": 0, "completion_gap": 0.0}

# what a configuration may state for the reference to model it: keys that
# describe, and the simulator's keys below; anything else (a topology,
# NFS, replica garbage collection) needs a reference that models it
MODELED = {"name", "source", "deployment", "precision", "guarantees",
           "assumed", "reduced", "reduced_why", "workflow_scale", "n_nodes",
           "cores", "mem", "net_bw", "disk_read_bw", "disk_write_bw", "dfs",
           "ceph_replication", "c_node", "c_task"}


class Unmodeled(Exception):
    """The configuration states something the reference does not model."""


def refuse_unmodeled(cfg: dict) -> None:
    extra = sorted(set(cfg) - MODELED)
    if extra or cfg.get("dfs", "ceph") != "ceph":
        raise Unmodeled(f"configuration {cfg.get('name')!r}: the reference "
                        f"models no {extra or 'dfs ' + repr(cfg['dfs'])}")


def draw_rounds(log: list, start: int, k: int, seed: int) -> set[int]:
    """Log indices of the window rounds to check, drawn from the seed:
    the round with the most decisions, then three quarters of ``k`` from
    rounds that decided something and the rest from rounds that did not."""
    busy, idle = [], []
    for i in range(start, len(log)):
        e = log[i]
        if e[0] == "round":
            (busy if e[2] else idle).append(i)
    rng = random.Random(seed * 7919 + 17)
    out: set[int] = set()
    if busy:
        out.add(max(busy, key=lambda i: len(log[i][2])))
    n_busy = min(len(busy), max(0, (3 * k) // 4))
    out.update(rng.sample(busy, n_busy))
    out.update(rng.sample(idle, min(len(idle), max(0, k - len(out)))))
    return out


def compare(cfg: dict, log: list, window_start: int, seed: int,
            check_rounds: int, simulated: bool, control: bool = False,
            ranks: dict | None = None, sim_seed: int = 0) -> dict:
    """Replay ``log`` and compare (see the module docstring).  ``seed``
    draws the rounds to check; ``sim_seed`` is the seed of the simulator's
    own random picks (Ceph's remote replica)."""
    t0 = time.perf_counter()
    refuse_unmodeled(cfg)
    key = f32 if control else identity
    rep = Replay(cfg["n_nodes"], int(cfg["mem"]), float(cfg["cores"]),
                 cfg["c_node"], cfg["c_task"], control=control, ranks=ranks)
    model = None
    if simulated:
        model = FlowModel(cfg["n_nodes"], cfg["net_bw"], cfg["disk_read_bw"],
                          cfg["disk_write_bw"], cfg["ceph_replication"], sim_seed,
                          key=key)
    sample = draw_rounds(log, window_start, check_rounds, seed)
    mismatches = checked = 0
    first = ""
    gap = 0.0
    misses = 0
    reported: set = set()
    starts: dict[tuple, float] = {}
    for i, e in enumerate(log):
        kind = e[0]
        if kind == "round":
            if model is not None:
                model.advance_to(e[1])
            if i in sample:
                checked += 1
                try:
                    rep.check_round(e[2])
                except (Mismatch, KeyError, ValueError) as err:
                    mismatches += 1
                    first = first or f"round at log {i}: {err}"
            try:
                if model is not None:
                    for a in e[2]:
                        if a[0] == "T":
                            t = rep.ready[a[1]]
                            starts[("T", a[1])] = e[1]
                            model.start_task(t, a[2], rep.size.__getitem__)
                        else:
                            starts[("C", a[1])] = e[1]
                            model.start_cop(a[1], a[4])
                rep.apply_round(e[2])
            except KeyError as err:
                mismatches += 1
                first = first or f"round at log {i}: unknown task {err}"
                break
            continue
        if model is not None and kind in ("tfin", "cfin"):
            owner = ("T" if kind == "tfin" else "C", e[1])
            model.advance_to(e[-1])
            if i >= window_start:
                reported.add(owner)
                got = model.finished.get(owner)
                if got is None:
                    misses += 1
                    first = first or f"{owner} reported at {e[-1]!r}, " \
                                     f"the model has it running"
                elif owner in starts:
                    span = max(e[-1] - starts[owner], 1e-3)
                    g = abs(got - e[-1]) / span
                    if g > gap:
                        gap = g
        rep.apply(e)
    out = {"decision_mismatches": mismatches, "rounds_checked": checked}
    if ranks is not None:
        out["priority_mismatches"] = rep.priority_mismatches
        first = first or rep.first_priority
    if model is not None:
        # what the model finishes inside the window the program must have
        # reported: from the round that opened the window to the last
        # simulated time the log reached
        begin = log[window_start - 1][1]
        end = max(e[1] if e[0] == "round" else e[-1]
                  for e in log[window_start - 1:]
                  if e[0] in ("round", "tfin", "cfin"))
        for owner, t in model.finished.items():
            if begin < t < end and owner not in reported:
                misses += 1
                first = first or f"{owner} finishes at {t!r} in the model, " \
                                 f"never reported"
        out["completion_misses"] = misses
        out["completion_gap"] = gap
    out["reference_s"] = time.perf_counter() - t0
    out["first_mismatch"] = first
    return out


def verdict(numbers: dict) -> tuple[bool, dict]:
    """``(correct, {name: [value, limit]})`` for the numbers compared."""
    checks = {}
    ok = numbers["rounds_checked"] > 0
    for name, limit in LIMITS.items():
        if name in numbers:
            checks[name] = [numbers[name], limit]
            ok = ok and numbers[name] <= limit
    return ok, checks
