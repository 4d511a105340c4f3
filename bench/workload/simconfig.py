"""A configuration file's keys, passed to the program's ``SimConfig``.

Every key of ``bench/configs/<name>.json`` is one of three kinds:

* a description (``DESCRIPTIVE``): read by people and by the benchmark's
  records, never by the program;
* a key of the benchmark's own (``BENCH_KEYS``);
* a field of ``repro.sim.SimConfig``, passed through as it stands.  A
  JSON object becomes the field's dataclass (``topology``).

Any other key is refused, and so are the run's seed and the program's
implementation switches (``OPTIONS``): a configuration states a
deployment, and the program runs it with its default options.
"""
from __future__ import annotations

import dataclasses
import typing

from plugins import SetupError

DESCRIPTIVE = {"name", "source", "deployment", "precision", "guarantees",
               "assumed", "reduced", "reduced_why"}
BENCH_KEYS = {"workflow_scale"}
OPTIONS = {"seed", "reference_flow", "reference_core", "flow_fill",
           "vectorized", "batched"}


def _dataclass_of(hint):
    for t in typing.get_args(hint) or (hint,):
        if dataclasses.is_dataclass(t):
            return t
    return None


def sim_config(cfg: dict, seed: int):
    """``SimConfig`` of the configuration ``cfg`` for a run with ``seed``;
    :class:`SetupError` on a key that is none of the three kinds."""
    from repro.sim import SimConfig
    fields = {f.name for f in dataclasses.fields(SimConfig)}
    hints = typing.get_type_hints(SimConfig)
    kw = {}
    for k, v in cfg.items():
        if k in DESCRIPTIVE or k in BENCH_KEYS:
            continue
        if k in OPTIONS:
            raise SetupError(f"configuration {cfg.get('name')!r} sets "
                             f"{k!r}, the run's seed or a program option")
        if k not in fields:
            raise SetupError(f"configuration {cfg.get('name')!r}: unknown "
                             f"key {k!r}")
        if isinstance(v, dict):
            cls = _dataclass_of(hints[k])
            if cls is None:
                raise SetupError(f"configuration key {k!r} takes no object")
            v = cls(**v)
        kw[k] = v
    return SimConfig(seed=seed, **kw)
