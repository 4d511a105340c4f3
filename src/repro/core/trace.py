"""Spans and counters of the decision path, one row per scheduling round.

Off by default.  A span site tests the module-level flag ``on`` and, when
it is false, does nothing else: no clock read, no annotation object::

    if trace.on:
        with trace.span("sched.step1"):
            started = self._step1_start_prepared(actions)
    else:
        started = self._step1_start_prepared(actions)

:func:`enable` turns tracing on and clears the records.  Then

* ``span(name)`` adds the block's host-clock seconds to the current row
  and opens a ``jax.profiler.TraceAnnotation(name)``, so that inside a
  profiler session the span lands in the same trace as the device ops;
* counters stay where the work is, as plain integer adds that are always
  on (``solver.stats``, ``scheduler.cops_created``, ...).
  :func:`counter` registers one by name with a function that reads its
  running total; only when tracing is on is it read, once per row;
* :func:`end_round`, called at the end of ``WowScheduler.schedule()``,
  closes the row: each span's seconds and each counter's difference since
  the previous row.  A row thus holds one ``schedule()`` call and all the
  callbacks and simulator work since the previous one.

The newest :data:`ROWS` rows are kept, each an ``array('d')`` of one float
per column (spans and counters, about a dozen): some 170 bytes a row,
about 22 MB when the ring is full.
"""
from __future__ import annotations

import time
import weakref
from array import array
from collections import deque
from operator import add

ROWS = 1 << 17

on = False
_annotation = None              # jax.profiler.TraceAnnotation, when present
_index: dict[str, int] = {}     # column name -> column
_acc: list[float] = []          # the open row
_counters: dict[str, list] = {}  # name -> [column, owner ref, read, last]
_rows: deque = deque(maxlen=ROWS)
_totals: list[float] = []       # every closed row since enable(), summed


def _column(name: str) -> int:
    col = _index.get(name)
    if col is None:
        col = _index[name] = len(_index)
        _acc.append(0.0)
        _totals.append(0.0)
    return col


def _read(c: list) -> float:
    owner = c[1]()
    return c[3] if owner is None else c[2](owner)


def enable() -> None:
    """Turn tracing on and clear the rows, the open row and the totals;
    counters count from here."""
    global on, _annotation
    try:
        from jax.profiler import TraceAnnotation as _annotation
    except ImportError:                                 # pragma: no cover
        _annotation = None
    _rows.clear()
    _acc[:] = [0.0] * len(_acc)
    _totals[:] = [0.0] * len(_totals)
    for c in _counters.values():
        c[3] = _read(c)
    on = True


def disable() -> None:
    """Turn tracing off; the rows stay readable."""
    global on
    on = False


def counter(name: str, owner, read) -> None:
    """Register the counter ``name``: ``read(owner)`` is its running total.
    The owner is held weakly, and a later registration of the same name
    (a newer scheduler, say) replaces an earlier one."""
    _counters[name] = [_column(name), weakref.ref(owner), read, read(owner)]


class span:
    """``with span(name):`` -- see the module docstring.  Only for use
    while tracing is on."""
    __slots__ = ("col", "ann", "t0")

    def __init__(self, name: str) -> None:
        self.col = _column(name)
        self.ann = _annotation(name) if _annotation is not None else None

    def __enter__(self) -> None:
        if self.ann is not None:
            self.ann.__enter__()
        self.t0 = time.perf_counter()

    def __exit__(self, *exc) -> None:
        _acc[self.col] += time.perf_counter() - self.t0
        if self.ann is not None:
            self.ann.__exit__(*exc)


def end_round() -> None:
    """Close the open row (tracing on)."""
    for c in _counters.values():
        total = _read(c)
        _acc[c[0]] = total - c[3]
        c[3] = total
    _rows.append(array("d", _acc))
    _totals[:] = map(add, _totals, _acc)
    _acc[:] = [0.0] * len(_acc)


def rows(last: int | None = None) -> list[dict[str, float]]:
    """The kept rows, oldest first (only the ``last`` ones if given), each
    ``{column: value}``."""
    kept = list(_rows)
    if last is not None:
        kept = kept[max(0, len(kept) - last):] if last > 0 else []
    names = list(_index)
    return [dict(zip(names, r)) for r in kept]


def totals() -> dict[str, float]:
    """Each column summed over every row closed since :func:`enable`."""
    return dict(zip(_index, _totals))
