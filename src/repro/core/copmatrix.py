"""Batched COP drain: array-backed step-2/3 cost state + blocked kernel.

Steps 2-3 of the WOW scheduler (paper §IV-C) were the last hot path still
executed task-at-a-time: per ready task the scheduler built a Python
candidate list over the free-slot pool, sorted it with a per-node lambda
key (``locality_missing_cost`` / ``present_bytes_map``) and probed
``plan_cop`` node by node.  This module batches that inner machinery
(DESIGN.md "Batched COP drain") while staying **bit-identical** to that
per-task sort, which ``core.reference.ReferenceWowScheduler`` keeps:

* :class:`CopMatrix` -- dense ``(tracked task row) x (node column)``
  mirrors of the DPS per-(task, node) present-input counters and
  present-byte totals (``dps._present_cnt`` / ``dps._present_bytes``).
  Maintained by the DPS at its existing replica-mutation choke points
  (``_idx_add`` / ``_idx_remove`` / ``track_task`` / ``untrack_task`` /
  ``drop_node``) with exactly the same ``+- mult`` / ``+- size * mult``
  deltas the dicts apply, so a cell reaches 0 precisely when the dict
  entry is popped -- the same-pattern twin of ``core/nodearray.py``.
  Column 0 is a permanent all-zero *null column*: nodes that hold no
  tracked bytes have no column, their gathers read 0 through it, which is
  exactly the ``dict.get(node, 0)`` the per-task sort computes.
* :class:`SlotColMap` -- the cached ``capacity slot -> matrix column``
  translation (int64 array), rebuilt when either side's version counter
  moves.  Stale entries for dead slots are harmless: every kernel mask
  starts from ``cap.alive``.
* :class:`BlockedDrainKernel` -- the blocked placement kernel.  Per step-2
  task it builds the candidate mask (free COP slot x free-resource fit x
  not inflight x not prepared) as array ops, computes the full cost row
  (missing bytes, or the locality-weighted cost under a topology) and
  selects the winner by the same staged masked reductions
  ``ilp.greedy_uniform`` uses -- ``key min, then node-id
  min`` -- so float ties split exactly as the per-task
  ``(cost, node)`` tuple sort does.  Only the *winning* node is then
  probed through the scalar ``plan_cop``, which is the only probe the
  per-task loop performs too (an unconstrained step-2 probe always
  succeeds: see the scheduler's step 2), so COP-id and tie-break-RNG
  consumption are
  unchanged.  Per step-3 task only the candidate-mask construction is
  batched: every feasible probe consumes a COP id (and possibly an RNG
  draw), so the probe loop itself must stay scalar and in canonical slot
  order.

Float bit-exactness of the locality cost row: ``dps.locality_missing_cost``
iterates ``dps._task_mult[task].items()`` and accumulates ``cost += size * m * w``
per missing file.  The kernel iterates the same dict in the same order and
adds one length-N contribution vector per file, so every element sees the
identical sequence of IEEE-754 additions; present holders contribute an
exact ``0.0`` (safe: the accumulator is never ``-0.0``, all contributions
are ``>= 0``), and the per-candidate weight is selected *without float
arithmetic* -- the minimum over the locality classes any holder offers
(rack / site / WAN membership counted in integers), which equals the
per-node ``min(topo.weight(h, node) for h in holders)`` for arbitrary
user-set class weights.
"""
from __future__ import annotations

import numpy as np

from .types import NodeId

_MIN_COLS = 16
_MIN_ROWS = 16


class CopMatrix:
    """Dense mirrors of ``dps._present_cnt`` / ``dps._present_bytes``.

    Rows are tracked tasks, columns are nodes that hold (or held) tracked
    input bytes; both are allocated from free lists and recycled zeroed.
    Column 0 is reserved as the permanent null column (see module
    docstring), so ``col_of`` returning 0 means "no bytes anywhere" and
    gathers need no membership test.

    Single consumer: one scheduler's :class:`SlotColMap` keys its cache on
    ``col_version``; the matrix itself is owned by the DPS.
    """

    def __init__(self) -> None:
        self._row_of: dict[int, int] = {}
        self._col_of: dict[NodeId, int] = {}
        self._free_rows: list[int] = []
        self._free_cols: list[int] = []
        self._nrows = 0
        self._ncols = 1                       # col 0 = null column
        # counts fit int32 (bounded by len(task.inputs)); bytes need int64
        self.cnt = np.zeros((_MIN_ROWS, _MIN_COLS), dtype=np.int32)
        self.pbytes = np.zeros((_MIN_ROWS, _MIN_COLS), dtype=np.int64)
        # bumped whenever the node->column mapping changes (new column
        # assigned or a column freed); SlotColMap rebuilds on it
        self.col_version = 0

    # ------------------------------------------------------------- mapping
    def row_of(self, task_id: int) -> int | None:
        return self._row_of.get(task_id)

    def col_of(self, node: NodeId) -> int:
        """Matrix column of ``node`` (0 = the null column: no bytes)."""
        return self._col_of.get(node, 0)

    def _ensure_col(self, node: NodeId) -> int:
        col = self._col_of.get(node)
        if col is not None:
            return col
        if self._free_cols:
            col = self._free_cols.pop()
        else:
            col = self._ncols
            self._ncols += 1
            if col >= self.cnt.shape[1]:
                self._grow_cols()
        self._col_of[node] = col
        self.col_version += 1
        return col

    def _grow_cols(self) -> None:
        rows, cols = self.cnt.shape
        new = max(_MIN_COLS, 2 * cols)
        for name in ("cnt", "pbytes"):
            old = getattr(self, name)
            arr = np.zeros((rows, new), dtype=old.dtype)
            arr[:, :cols] = old
            setattr(self, name, arr)

    def _grow_rows(self) -> None:
        rows, cols = self.cnt.shape
        new = max(_MIN_ROWS, 2 * rows)
        for name in ("cnt", "pbytes"):
            old = getattr(self, name)
            arr = np.zeros((new, cols), dtype=old.dtype)
            arr[:rows] = old
            setattr(self, name, arr)

    # ------------------------------------------------------- DPS choke hooks
    def cell_add(self, task_id: int, node: NodeId, d_cnt: int,
                 d_bytes: int) -> None:
        """``_idx_add`` delta for one (waiting task, node) pair -- the same
        ``+mult`` / ``+size*mult`` the dict indices apply."""
        row = self._row_of.get(task_id)
        if row is None:
            return
        col = self._ensure_col(node)
        self.cnt[row, col] += d_cnt
        self.pbytes[row, col] += d_bytes

    def cell_sub(self, task_id: int, node: NodeId, d_cnt: int,
                 d_bytes: int) -> None:
        """``_idx_remove`` delta.  The dict path pops entries when the
        count reaches 0; subtracting the same deltas leaves exactly 0 here
        (a removed file was added with the same ``mult`` earlier), so the
        mirror invariant is cell == ``dict.get(node, 0)`` cell-for-cell."""
        row = self._row_of.get(task_id)
        if row is None:
            return
        col = self._col_of.get(node)
        if col is None:
            return
        self.cnt[row, col] -= d_cnt
        self.pbytes[row, col] -= d_bytes

    def track(self, task_id: int, cnt: dict[NodeId, int],
              pbytes: dict[NodeId, int]) -> None:
        """Copy the just-built ``track_task`` dicts into a fresh row."""
        if task_id in self._row_of:
            self.untrack(task_id)
        if self._free_rows:
            row = self._free_rows.pop()     # recycled rows are zeroed
        else:
            row = self._nrows
            self._nrows += 1
            if row >= self.cnt.shape[0]:
                self._grow_rows()
        self._row_of[task_id] = row
        for n, c in cnt.items():
            col = self._ensure_col(n)
            self.cnt[row, col] = c
            self.pbytes[row, col] = pbytes.get(n, 0)

    def untrack(self, task_id: int) -> None:
        row = self._row_of.pop(task_id, None)
        if row is None:
            return
        self.cnt[row, :] = 0
        self.pbytes[row, :] = 0
        self._free_rows.append(row)

    def drop_node(self, node: NodeId) -> None:
        """Node left the cluster: free its column (``dps.drop_node``
        already zeroed every tracked cell through :meth:`cell_sub`; the
        explicit column clear below is defensive)."""
        col = self._col_of.pop(node, None)
        if col is None:
            return
        self.cnt[:, col] = 0
        self.pbytes[:, col] = 0
        self._free_cols.append(col)
        self.col_version += 1

    def rebuild(self, dps) -> None:
        """Full resync from the DPS dict indices (used when the matrix is
        enabled on a DPS that already tracks tasks, and by the property
        tests as the from-scratch oracle)."""
        self._row_of.clear()
        self._col_of.clear()
        self._free_rows.clear()
        self._free_cols.clear()
        self._nrows = 0
        self._ncols = 1
        self.cnt = np.zeros((_MIN_ROWS, _MIN_COLS), dtype=np.int32)
        self.pbytes = np.zeros((_MIN_ROWS, _MIN_COLS), dtype=np.int64)
        self.col_version += 1
        for tid, cnt in dps._present_cnt.items():
            self.track(tid, cnt, dps._present_bytes[tid])

    # ----------------------------------------------------------- validation
    def snapshot(self, task_id: int) -> tuple[dict, dict] | None:
        """``({node: cnt}, {node: pbytes})`` of one row, nonzero-count
        cells only -- the dict-index form the property tests compare
        against ``dps._present_cnt`` / ``dps._present_bytes`` (the dicts
        hold an entry exactly while the count is positive)."""
        row = self._row_of.get(task_id)
        if row is None:
            return None
        cnt_d: dict[NodeId, int] = {}
        pb_d: dict[NodeId, int] = {}
        for n, col in self._col_of.items():
            c = int(self.cnt[row, col])
            if c > 0:
                cnt_d[n] = c
                pb_d[n] = int(self.pbytes[row, col])
        return cnt_d, pb_d

    def check_against(self, dps) -> None:
        """Assert the full mirror invariant (test helper)."""
        assert set(self._row_of) == set(dps._present_cnt), (
            set(self._row_of), set(dps._present_cnt))
        for tid in self._row_of:
            snap = self.snapshot(tid)
            assert snap is not None
            cnt_d, pb_d = snap
            assert cnt_d == dps._present_cnt[tid], (tid, cnt_d)
            assert pb_d == dps._present_bytes[tid], (tid, pb_d)


class SlotColMap:
    """Cached ``capacity slot -> matrix column`` int64 translation.

    Rebuilt (O(live nodes)) whenever the capacity array's slot map or the
    matrix's column map changed since the last refresh; both sides expose a
    version counter, so steady-state refreshes are two int compares.
    Dead slots may keep stale columns -- harmless, every kernel mask is
    rooted in ``cap.alive``.
    """

    def __init__(self, cap, mx: CopMatrix) -> None:
        self.cap = cap
        self.mx = mx
        self._cap_version = -1
        self._col_version = -1
        self._colv = np.zeros(0, dtype=np.int64)

    def refresh(self) -> "np.ndarray":
        cap, mx = self.cap, self.mx
        if (self._cap_version != cap.version
                or self._col_version != mx.col_version):
            colv = np.zeros(len(cap.alive), dtype=np.int64)
            col_of = mx._col_of
            for nid, s in cap.slot_of.items():
                c = col_of.get(nid)
                if c is not None:
                    colv[s] = c
            self._colv = colv
            self._cap_version = cap.version
            self._col_version = mx.col_version
        return self._colv


class BlockedDrainKernel:
    """The blocked step-2/3 placement kernel (see module docstring).

    Owned by one scheduler; reads the scheduler's capacity array, the DPS
    matrix, and the per-task inflight-target sets the scheduler maintains.
    ``begin()`` must be called once per ``schedule()`` before the step-2/3
    loops: it refreshes the slot->column map and drops the per-shape fit
    caches (free resources are frozen *during* steps 2-3 -- only step-1
    reservations change them -- but change between events).  COP-slot
    occupancy does change mid-loop (every ``_start_cop`` bumps
    ``active_cops``), so the free-slot mask is re-read per task.
    """

    def __init__(self, cap, mx: CopMatrix, c_node: int,
                 inflight_by_task: dict[int, set[int]]) -> None:
        self.cap = cap
        self.mx = mx
        self.c_node = c_node
        self._inflight = inflight_by_task
        self._slotcol = SlotColMap(cap, mx)
        self._colv: "np.ndarray" = self._slotcol.refresh()
        # per-shape masks, valid for one schedule() (cleared in begin())
        self._fit2: dict[tuple[int, float], "np.ndarray"] = {}
        self._fit3: dict[tuple[int, float], "np.ndarray"] = {}
        # per-slot locality tier ids, keyed on (topology, cap.version)
        self._tier_key: tuple | None = None
        self._racks: "np.ndarray" | None = None
        self._sites: "np.ndarray" | None = None

    # ---------------------------------------------------------- per event
    def begin(self) -> None:
        self._colv = self._slotcol.refresh()
        self._fit2.clear()
        self._fit3.clear()

    # ------------------------------------------------------------- masks
    def _free_vec(self) -> "np.ndarray":
        cap = self.cap
        return cap.active_cops[:cap._n] < self.c_node

    def _fit2_mask(self, mem: int, cores: float) -> "np.ndarray":
        m = self._fit2.get((mem, cores))
        if m is None:
            cap = self.cap
            n = cap._n
            m = (cap.alive[:n] & (cap.free_mem[:n] >= mem)
                 & (cap.free_cores[:n] >= cores))
            self._fit2[(mem, cores)] = m
        return m

    def _fit3_mask(self, mem: int, cores: float) -> "np.ndarray":
        m = self._fit3.get((mem, cores))
        if m is None:
            cap = self.cap
            n = cap._n
            m = (cap.alive[:n] & (cap.mem[:n] >= mem)
                 & (cap.cores[:n] >= cores))
            self._fit3[(mem, cores)] = m
        return m

    def _candidate_mask(self, tid: int, t, fit: "np.ndarray",
                        ) -> "np.ndarray | None":
        """fit x free COP slot x not prepared x not inflight, or None when
        the task has no matrix row (untracked: the caller's fallback)."""
        row = self.mx.row_of(tid)
        if row is None:
            return None
        cap = self.cap
        n = cap._n
        cntv = self.mx.cnt[row].take(self._colv[:n])
        # prepared <=> per-occurrence count == len(inputs), the dict
        # invariant (`_prep` membership); tracked tasks have >= 1 input so
        # null-column zeros can never look prepared
        mask = fit & self._free_vec() & (cntv != len(t.inputs))
        infl = self._inflight.get(tid)
        if infl:
            slot_of = cap.slot_of
            for nid in infl:
                s = slot_of.get(nid)
                if s is not None:
                    mask[s] = False
        return mask

    # ---------------------------------------------------------- cost rows
    def _locality_cost_row(self, dps, tid: int) -> "np.ndarray":
        """Length-N locality-weighted missing-byte cost, bit-identical to
        ``dps.locality_missing_cost(tid, node)`` per element (same file
        iteration order, same IEEE additions -- see module docstring)."""
        topo = dps.topology
        cap = self.cap
        n = cap._n
        racks, sites = self._slot_tiers(topo)
        spec = topo.spec
        w_rack, w_site, w_wan = spec.w_rack, spec.w_site, spec.w_wan
        maxw = topo.max_weight
        rps = topo.racks_per_site
        slot_of = cap.slot_of
        cost = np.zeros(n, dtype=np.float64)
        files = dps._files
        locations = dps._locations
        for f, m in dps._task_mult[tid].items():
            locs = locations.get(f)
            fspec = files.get(f)
            size = fspec.size if fspec is not None else 0
            sm = float(size * m)
            if not locs:
                # no holder anywhere: worst-case placement assumption
                cost += sm * maxw
                continue
            hr = np.fromiter((h // topo.rack_size for h in locs),
                             dtype=np.int64, count=len(locs))
            hs = hr // rps if rps > 0 else np.zeros_like(hr)
            rack_cnt = (racks[:, None] == hr[None, :]).sum(axis=1)
            site_cnt = (sites[:, None] == hs[None, :]).sum(axis=1)
            # exact weight-class selection, no float arithmetic: a class is
            # available iff some holder sits at that distance; the classes
            # partition the holder count, so at least one is available and
            # no inf survives the minimum
            w = np.where(rack_cnt > 0, w_rack, np.inf)
            w = np.minimum(w, np.where(site_cnt > rack_cnt, w_site, np.inf))
            w = np.minimum(w, np.where(site_cnt < len(locs), w_wan, np.inf))
            contrib = sm * w
            for h in locs:
                # present on the candidate itself: the per-node cost skips
                # the file (contributes nothing); holders outside the slot map
                # (e.g. the NFS server) still count toward the classes
                s = slot_of.get(h)
                if s is not None:
                    contrib[s] = 0.0
            cost += contrib
        return cost

    def _slot_tiers(self, topo) -> tuple["np.ndarray", "np.ndarray"]:
        cap = self.cap
        key = (id(topo), cap.version)
        if self._tier_key != key:
            ids = cap._node_of[:cap._n]
            racks = ids // topo.rack_size      # nonuniform => rack_size > 0
            rps = topo.racks_per_site
            sites = racks // rps if rps > 0 else np.zeros_like(racks)
            self._racks, self._sites = racks, sites
            self._tier_key = key
        n = cap._n
        return self._racks[:n], self._sites[:n]

    # ------------------------------------------------------------ queries
    def step2_winner(self, tid: int, t, dps) -> int | None:
        """Node id the per-task step-2 sort would probe first; None when
        the candidate set is empty (nothing can start); -1 when the task has
        no matrix row -- the caller must fall back to the per-task probe,
        which recomputes candidates from the DPS dicts."""
        mask = self._candidate_mask(tid, t, self._fit2_mask(t.mem, t.cores))
        if mask is None:
            return -1
        if not mask.any():
            return None
        cap = self.cap
        n = cap._n
        big = np.iinfo(np.int64).max
        if dps.topology is not None:
            key = np.where(mask, self._locality_cost_row(dps, tid), np.inf)
        else:
            # missing bytes == total - present; the null column makes the
            # gather read 0 for colless nodes, like dict.get(node, 0).
            # Candidates holding nothing share the key, so the tie-break
            # degenerates to id order -- the per-task plain sort.
            row = self.mx.row_of(tid)
            tb = dps.task_input_bytes(tid)
            key = np.where(mask, tb - self.mx.pbytes[row].take(self._colv[:n]),
                           big)
        ids = cap._node_of[:n]
        # staged reduction, ordered like ilp.greedy_uniform: min key
        # first, then min node id among the ties -- exactly the
        # tuple-compare (cost, node)
        m0 = key.min()
        tie = key == m0
        return int(np.where(tie, ids, big).min())

    def free_slot_fit_node(self, mem: int, cores: float) -> int | None:
        """A free-COP-slot node whose *free* resources fit the shape, or
        None when there is none -- which proves every step-2 candidate
        mask of the shape empty for the rest of the pass (the step-2
        emptiness pre-test)."""
        mask = self._fit2_mask(mem, cores) & self._free_vec()
        s = int(mask.argmax())
        return int(self.cap._node_of[s]) if mask[s] else None

    def step3_candidates(self, tid: int, t) -> list[int] | None:
        """Step-3 candidate node ids in canonical (slot) order, or None
        when the task has no matrix row.  Mask construction only: the
        caller must keep probing every candidate through the scalar
        ``plan_cop`` -- each feasible probe consumes a COP id and possibly
        an RNG draw, so probes cannot be batched or elided."""
        mask = self._candidate_mask(tid, t, self._fit3_mask(t.mem, t.cores))
        if mask is None:
            return None
        cap = self.cap
        return cap._node_of[np.flatnonzero(mask)].tolist()
