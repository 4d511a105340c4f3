"""Vectorized hot node state: contiguous arrays of per-node free capacity.

At 4096+ nodes the scheduler's dominant per-event cost is walking the
per-node hot state (``NodeState.free_mem``/``free_cores``, COP slots) one
dict entry at a time: capacity-class walks, the step-2/3 free-slot pool
scans and the input-less best-fit loops all touch O(nodes) Python objects
per event.  :class:`NodeCapacityArray` mirrors that state into flat numpy
arrays indexed by a dense *slot map* so those walks become masked array
queries (DESIGN.md "Vectorized hot state").

Slot-map invariants (the bit-parity load-bearing part):

* **Slot order is canonical order.**  Slots are append-only: the i-th live
  slot (in slot-index order) is the i-th node of the canonical
  ``readyset.NodeOrder`` enumeration.  ``add`` appends -- exactly like
  ``NodeOrder.add`` -- and ``drop`` marks a slot dead without moving the
  others, so ``np.flatnonzero(mask)`` yields node candidates already in
  canonical order with no sort.  A node that re-joins after a failure gets
  a *fresh* slot at the end, matching ``NodeOrder``'s re-append semantics.
* **Dead slots are masked, then compacted.**  ``drop`` only clears the
  ``alive`` bit; when dead slots outnumber live ones the arrays are
  compacted in slot order, which preserves the canonical-order invariant.
* **Values are written through at the scheduler's existing choke points**
  (``on_task_finished``, step-1 reservations, ``_start_cop`` /
  ``on_cop_finished``, ``note_node_added`` / ``note_node_removed``), plus
  an idempotent ``refresh_many`` on the dirty-node drain, so array values
  equal the live ``NodeState`` values whenever a consumer reads them --
  including *mid-event* between a step-1 reservation and the step-2/3
  scans, which lazy dirty-refresh alone would miss.

Queries read the same values a walk over the ``NodeState`` dict reads and
tie-break the same way (property-tested against brute force in
``tests/test_nodearray.py``; whole runs against
``core.reference.ReferenceWowScheduler``).
"""
from __future__ import annotations

from typing import Iterable

import numpy as np

from .types import NodeId, NodeState

_MIN_COMPACT = 64


class NodeCapacityArray:
    """Flat mirrors of per-node hot state under a dense node->slot map."""

    def __init__(self, nodes: dict[int, NodeState], order: Iterable[NodeId],
                 c_node: int = 1) -> None:
        self.c_node = c_node
        self.slot_of: dict[NodeId, int] = {}
        n = len(nodes)
        cap = max(16, 2 * n)
        self._node_of = np.zeros(cap, dtype=np.int64)
        self.free_mem = np.zeros(cap, dtype=np.int64)
        self.free_cores = np.zeros(cap, dtype=np.float64)
        self.mem = np.zeros(cap, dtype=np.int64)
        self.cores = np.zeros(cap, dtype=np.float64)
        self.active_cops = np.zeros(cap, dtype=np.int64)
        self.alive = np.zeros(cap, dtype=bool)
        self._n = 0          # slots handed out (live + dead)
        self._dead = 0
        # bumped whenever the node->slot mapping changes shape (append or
        # compaction); consumers caching slot-indexed derived arrays
        # (core/copmatrix.SlotColMap, tier ids) rebuild on it.  Plain drops
        # only mask `alive` and need no bump -- stale derived entries for
        # dead slots are unreachable through alive-rooted masks.
        self.version = 0
        for nid in order:    # canonical enumeration = slot order
            self.add(nid, nodes[nid])

    # ------------------------------------------------------------- slot map
    def __len__(self) -> int:
        return self._n - self._dead

    def __contains__(self, node: NodeId) -> bool:
        return node in self.slot_of

    def add(self, node: NodeId, state: NodeState) -> None:
        """Append a slot for ``node`` (idempotent: a live node is
        refreshed in place, like ``NodeOrder.add``)."""
        if node in self.slot_of:
            self.refresh_from(node, state)
            return
        if self._n == len(self.alive):
            self._grow()
        s = self._n
        self._n += 1
        self.version += 1
        self.slot_of[node] = s
        self._node_of[s] = node
        self.alive[s] = True
        self._write(s, state)

    def drop(self, node: NodeId) -> None:
        s = self.slot_of.pop(node, None)
        if s is None:
            return
        self.alive[s] = False
        self._dead += 1
        if self._dead > max(_MIN_COMPACT, self._n - self._dead):
            self._compact()

    def _grow(self) -> None:
        new = max(16, 2 * len(self.alive))
        for name in ("_node_of", "free_mem", "free_cores", "mem", "cores",
                     "active_cops", "alive"):
            old = getattr(self, name)
            arr = np.zeros(new, dtype=old.dtype)
            arr[:len(old)] = old
            setattr(self, name, arr)

    def _compact(self) -> None:
        """Drop dead slots; live slots keep their relative (= canonical)
        order, so queries are unaffected."""
        keep = np.flatnonzero(self.alive[:self._n])
        m = len(keep)
        for name in ("_node_of", "free_mem", "free_cores", "mem", "cores",
                     "active_cops"):
            arr = getattr(self, name)
            arr[:m] = arr[keep]
        self.alive[:m] = True
        self.alive[m:self._n] = False
        self._n = m
        self._dead = 0
        self.version += 1
        ids = self._node_of[:m].tolist()
        self.slot_of = {nid: i for i, nid in enumerate(ids)}

    # --------------------------------------------------------- write-through
    def _write(self, slot: int, state: NodeState) -> None:
        self.free_mem[slot] = state.free_mem
        self.free_cores[slot] = state.free_cores
        self.mem[slot] = state.mem
        self.cores[slot] = state.cores
        self.active_cops[slot] = state.active_cops

    def refresh_from(self, node: NodeId, state: NodeState) -> None:
        self._write(self.slot_of[node], state)

    def refresh_many(self, nodes: Iterable[NodeId],
                     states: dict[int, NodeState]) -> None:
        """One batch pass over the dirty nodes (unknown/removed ids are
        skipped -- their ``drop`` already happened)."""
        so = self.slot_of
        for n in nodes:
            s = so.get(n)
            st = states.get(n)
            if s is not None and st is not None:
                self._write(s, st)

    def set_free(self, node: NodeId, free_mem: int, free_cores: float) -> None:
        s = self.slot_of[node]
        self.free_mem[s] = free_mem
        self.free_cores[s] = free_cores

    # --------------------------------------------------------------- queries
    def _live(self) -> "np.ndarray":
        return self.alive[:self._n]

    def fit_mask(self, mem: int, cores: float) -> "np.ndarray":
        n = self._n
        return (self._live() & (self.free_mem[:n] >= mem)
                & (self.free_cores[:n] >= cores))

    def fitting(self, mem: int, cores: float) -> list[NodeId]:
        """All nodes whose free resources fit ``(mem, cores)``, in canonical
        order (slot order *is* canonical order -- no sort)."""
        return self._node_of[np.flatnonzero(self.fit_mask(mem, cores))].tolist()

    def free_slot_fit_ids(self, mem: int, cores: float) -> list[NodeId]:
        """Free-COP-slot nodes whose *free* resources fit -- the step-2
        candidate pool scan, in canonical order."""
        n = self._n
        mask = (self._live() & (self.active_cops[:n] < self.c_node)
                & (self.free_mem[:n] >= mem) & (self.free_cores[:n] >= cores))
        return self._node_of[np.flatnonzero(mask)].tolist()

    def filter_fitting(self, cands: list[NodeId], mem: int,
                       cores: float) -> list[NodeId]:
        """``cands`` restricted to nodes whose free resources fit -- the
        `ilp._feasible` candidate filter as one masked gather.  Returns the
        input list unchanged (no copy) when everything fits, which is the
        common case for candidate lists built from :meth:`fitting`."""
        k = len(cands)
        if k == 0:
            return cands
        so = self.slot_of
        slots = np.fromiter((so[n] for n in cands), dtype=np.int64, count=k)
        keep = (self.free_mem[slots] >= mem) & (self.free_cores[slots] >= cores)
        if keep.all():
            return cands
        return [n for n, ok in zip(cands, keep.tolist()) if ok]

    def slots_of(self, nodes: list[NodeId]) -> "np.ndarray":
        so = self.slot_of
        return np.fromiter((so[n] for n in nodes), dtype=np.int64,
                           count=len(nodes))

    # ------------------------------------------------------------ validation
    def snapshot(self) -> dict[int, tuple[int, float, int]]:
        """Live ``{node: (free_mem, free_cores, active_cops)}`` -- what the
        property tests compare against a from-scratch rebuild."""
        out = {}
        for nid, s in self.slot_of.items():
            out[nid] = (int(self.free_mem[s]), float(self.free_cores[s]),
                        int(self.active_cops[s]))
        return out

    def live_ids(self) -> list[NodeId]:
        """Live node ids in slot (= canonical) order."""
        return self._node_of[np.flatnonzero(self._live())].tolist()
