"""Files of the benchmark found by name: ``bench/<kind>/<name>.py``.

Runners (``runners/``), workflow builders (``workflows/``) and per-layer
metric readers (``metrics/``) are each a file of their own, so that a new
one is added as a new file and no existing file is edited.
"""
from __future__ import annotations

import importlib.util
import os
import re

BENCH = os.path.dirname(os.path.abspath(__file__))
_NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
_loaded: dict[str, object] = {}


class SetupError(Exception):
    """The cell cannot be set up: a name, a file or a key is wrong."""


def load(kind: str, name: str, bench_dir: str = BENCH):
    """The module ``<bench_dir>/<kind>/<name>.py``, loaded once."""
    if not isinstance(name, str) or not _NAME.match(name):
        raise SetupError(f"bad {kind} name {name!r}")
    path = os.path.join(bench_dir, kind, name + ".py")
    if not os.path.exists(path):
        raise SetupError(f"no {kind} file {path}")
    mod = _loaded.get(path)
    if mod is None:
        spec = importlib.util.spec_from_file_location(
            f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        _loaded[path] = mod
    return mod
