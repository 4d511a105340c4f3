"""Plain WOW scheduler semantics, replayed over the decision log.

The three steps of the WOW scheduler (Lehmann et al., arXiv:2503.13072,
section III-B) and the Data Placement Service's COP planning
(section III-C), written from their definitions as the program's frozen
reference ``src/repro/core/reference.py`` states them, with the step-1
solver of ``ilp.py`` beside this file.  It imports nothing of the program.

:class:`Replay` follows the run's log call by call, keeping the state a
scheduler sees (free resources, the ready queue in submit order, replica
locations, COPs in flight).  At a round drawn for the check it computes
the round's decisions from that state and compares them with the
program's; every round, checked or not, then applies the program's own
decisions, so each checked round is judged on the state the program
really had.  What the comparison covers of a COP:

* its task and target, and each transfer's file, size and destination,
  exactly;
* each transfer's source: a node that holds the file, has a free COP slot
  and carries the least load already assigned within this COP.  Which of
  several such nodes is taken is the DPS's seeded random tie-break; it
  is not replayed, since its stream also advances on probes that start
  no COP;
* its price, exactly;
* its id: COP ids count every feasible probe, so within a round they
  follow from the first one.
"""
from __future__ import annotations

import dataclasses
from collections import deque

import numpy as np

from .ilp import identity, solve

W_TRAFFIC = 0.5
W_MAXLOAD = 0.5
SIZE_SCALE = float(2 ** 50)


def abstract_ranks(edges: dict) -> dict:
    """Longest path to a sink of each abstract task (WOW's rank, section
    III-B): sinks 0, a task one more than its highest successor."""
    nodes = set(edges)
    for succs in edges.values():
        nodes |= set(succs)
    indeg = {n: 0 for n in nodes}
    for succs in edges.values():
        for b in succs:
            indeg[b] += 1
    order = []
    q = deque(sorted(n for n in nodes if indeg[n] == 0))
    while q:
        n = q.popleft()
        order.append(n)
        for b in sorted(edges.get(n, ())):
            indeg[b] -= 1
            if indeg[b] == 0:
                q.append(b)
    rank = {n: 0 for n in nodes}
    for n in reversed(order):
        for b in edges.get(n, ()):
            rank[n] = max(rank[n], rank[b] + 1)
    return rank


def priority_value(rank: int, input_bytes: int) -> float:
    """WOW's task priority: rank first, total input bytes break ties, as
    one float (rank + 1 + bytes / 2**50)."""
    return float(rank) + 1.0 + min(float(input_bytes),
                                   SIZE_SCALE - 1.0) / SIZE_SCALE


def f32(x: float) -> float:
    return float(np.float32(x))


@dataclasses.dataclass
class Task:
    id: int
    mem: int
    cores: float
    inputs: tuple
    dfs_inputs: int
    outputs: tuple
    out_sizes: tuple
    dfs_outputs: int
    compute_time: float
    priority: float
    abstract: str


class Mismatch(Exception):
    pass


class Replay:
    def __init__(self, n_nodes: int, mem: int, cores: float, c_node: int,
                 c_task: int, control: bool = False,
                 ranks: dict | None = None) -> None:
        self.n = n_nodes
        self.mem = mem
        self.cores = cores
        self.c_node = c_node
        self.c_task = c_task
        self.key = f32 if control else identity
        self.free_mem = np.full(n_nodes, mem, dtype=np.int64)
        self.free_cores = np.full(n_nodes, float(cores), dtype=np.float64)
        self.active = np.zeros(n_nodes, dtype=np.int64)
        self.ready: dict[int, Task] = {}
        self.running: dict[int, tuple] = {}
        self.locs: dict[int, set] = {}
        self.size: dict[int, int] = {}
        self.cops: dict[int, tuple] = {}
        self.cops_per_task: dict[int, int] = {}
        self.inflight: dict[int, set] = {}
        self.max_cop_id = -1
        self.tasks: dict[int, Task] = {}
        # {tenant: {abstract task: rank}}: priorities are recomputed from
        # them; None where the environment sets priorities itself
        self.ranks = ranks
        self.priority_mismatches = 0
        self.first_priority = ""

    # ------------------------------------------------------------ events
    def apply(self, e: tuple) -> None:
        kind = e[0]
        if kind == "submit":
            t = Task(*e[1:])
            if self.ranks is not None:
                self._priority(t)
            self.tasks[t.id] = t
            self.ready[t.id] = t
        elif kind == "tfin":
            run = self.running.pop(e[1], None)
            if run is not None:
                node, mem, cores = run
                self.free_mem[node] += mem
                self.free_cores[node] += cores
        elif kind == "cfin":
            plan = self.cops.pop(e[1], None)
            if plan is None:
                return
            task, target, nodes, transfers = plan
            self.cops_per_task[task] = max(
                0, self.cops_per_task.get(task, 0) - 1)
            for n in nodes:
                self.active[n] = max(0, self.active[n] - 1)
            infl = self.inflight.get(task)
            if infl is not None:
                infl.discard(target)
                if not infl:
                    del self.inflight[task]
            if e[2]:
                for fid, _size, _src, dst in transfers:
                    self.locs.setdefault(fid, set()).add(dst)
        elif kind == "forget":
            if e[1] not in self.ready and e[1] not in self.running:
                self.cops_per_task.pop(e[1], None)
        elif kind == "reg":
            self.locs[e[1]] = {e[3]}
            self.size[e[1]] = e[2]
        elif kind == "rep":
            self.locs.setdefault(e[1], set()).add(e[2])
        elif kind == "del":
            locs = self.locs.get(e[1])
            if locs:
                for n in sorted(locs)[e[2]:]:
                    locs.discard(n)
                if e[2] == 0:
                    self.locs.pop(e[1], None)
        elif kind == "decline":
            run = self.running.pop(e[1], None)
            if run is not None:
                node, mem, cores = run
                self.free_mem[node] += mem
                self.free_cores[node] += cores
                t = self.tasks[e[1]]
                self.ready.pop(t.id, None)
                self.ready[t.id] = t
        elif kind in ("ack", "nadd", "nrem"):
            pass
        else:
            raise ValueError(f"unknown log entry {kind!r}")

    def _priority(self, t: Task) -> None:
        """Recompute ``t``'s priority from its instance's abstract DAG and
        its input bytes; count a submitted priority that differs."""
        tenant, _, rest = t.abstract.partition("/")
        rank = self.ranks[tenant][rest.partition(":")[2]]
        size = t.dfs_inputs + sum(self.size[f] for f in t.inputs)
        p = priority_value(rank, size)
        if p != t.priority:
            self.priority_mismatches += 1
            self.first_priority = self.first_priority or (
                f"task {t.id} ({t.abstract}) submitted with priority "
                f"{t.priority!r}, the reference {p!r}")
        t.priority = p

    def apply_round(self, actions: tuple) -> None:
        """Apply the program's decisions (teacher forcing)."""
        for a in actions:
            if a[0] == "T":
                t = self.ready.pop(a[1])
                self.free_mem[a[2]] -= t.mem
                self.free_cores[a[2]] -= t.cores
                self.running[t.id] = (a[2], t.mem, t.cores)
            else:
                self._start_cop(a[1], a[2], a[3], a[4])

    def _start_cop(self, pid, task, target, transfers, active=None,
                   cpt=None, infl=None) -> None:
        active = self.active if active is None else active
        nodes = {target} | {tr[2] for tr in transfers}
        for n in nodes:
            active[n] += 1
        if cpt is None:
            self.cops[pid] = (task, target, nodes, transfers)
            self.cops_per_task[task] = self.cops_per_task.get(task, 0) + 1
            self.inflight.setdefault(task, set()).add(target)
            self.max_cop_id = max(self.max_cop_id, pid)
        else:
            cpt[task] = cpt.get(task, self.cops_per_task.get(task, 0)) + 1
            infl.setdefault(task, set(self.inflight.get(task, ()))).add(target)

    # --------------------------------------------------------- decisions
    def _prepared(self, inputs) -> set:
        if not inputs:
            return set(range(self.n))
        sets = sorted((self.locs.get(f, set()) for f in inputs), key=len)
        inter = set(sets[0])
        for s in sets[1:]:
            inter &= s
            if not inter:
                break
        return inter

    def check_round(self, actions: tuple) -> None:
        """Compute this round's decisions and raise :class:`Mismatch` at the
        first one that differs from ``actions``.  State is unchanged."""
        key = self.key
        fm = self.free_mem.copy()
        fc = self.free_cores.copy()
        act = self.active.copy()
        cpt: dict[int, int] = {}
        infl: dict[int, set] = {}
        got = list(actions)
        pos = 0

        def expect(kind):
            nonlocal pos
            if pos >= len(got) or got[pos][0] != kind:
                raise Mismatch(f"decision {pos}: the reference makes a "
                               f"{kind!r} decision, the program "
                               f"{got[pos] if pos < len(got) else 'none'}")
            pos += 1
            return got[pos - 1]

        # ---- step 1: start ready tasks on prepared nodes
        tasks, cands, shape_fit, prep = [], {}, {}, {}
        for t in self.ready.values():
            if t.inputs:
                p = self._prepared(t.inputs)
                prep[t.id] = p
                lst = [n for n in sorted(p)
                       if fm[n] >= t.mem and fc[n] >= t.cores]
            else:
                shape = (t.mem, t.cores)
                lst = shape_fit.get(shape)
                if lst is None:
                    lst = shape_fit[shape] = np.nonzero(
                        (fm >= t.mem) & (fc >= t.cores))[0].tolist()
            if lst:
                tasks.append(t)
                cands[t.id] = lst
        assign = solve(tasks, cands, fm, fc, key) if tasks else {}
        started = set()
        for tid, n in sorted(assign.items()):
            a = expect("T")
            if a[1:] != (tid, n):
                raise Mismatch(f"step 1: reference starts task {tid} on "
                               f"node {n}, program {a[1:]}")
            t = self.ready[tid]
            fm[n] -= t.mem
            fc[n] -= t.cores
            started.add(tid)

        # ---- COP bookkeeping shared by steps 2 and 3
        cop_base = [None]

        def budget(tid) -> bool:
            return cpt.get(tid, self.cops_per_task.get(tid, 0)) < self.c_task

        def inflight_of(tid) -> set:
            return infl.get(tid, self.inflight.get(tid, set()))

        def feasible_targets(inputs, free):
            constraint = None
            for f in set(inputs):
                srcs = self.locs.get(f, set())
                if any(free[s] for s in srcs):
                    continue
                constraint = set(srcs) if constraint is None \
                    else constraint & srcs
                if not constraint:
                    return constraint
            return constraint

        def missing(t, n) -> int:
            return sum(self.size[f] for f in t.inputs
                       if n not in self.locs.get(f, ()))

        def check_plan(t, target, free, offset, consumed):
            a = expect("C")
            pid, task, tgt, transfers, price = a[1:]
            if (task, tgt) != (t.id, target):
                raise Mismatch(f"COP for task {task} on {tgt}; the reference "
                               f"prepares task {t.id} on {target}")
            if cop_base[0] is None:          # ids continue from here
                cop_base[0] = pid - offset
                if cop_base[0] <= self.max_cop_id:
                    raise Mismatch(f"COP id {pid} reuses an earlier id")
            if pid != cop_base[0] + offset:
                raise Mismatch(f"COP id {pid}, the reference counts "
                               f"{cop_base[0] + offset}")
            cop_base[0] += consumed
            miss = sorted(((self.size[f], f) for f in t.inputs
                           if target not in self.locs.get(f, ())),
                          key=lambda sf: (-sf[0], sf[1]))
            if len(miss) != len(transfers):
                raise Mismatch(f"COP {pid}: {len(transfers)} transfers, the "
                               f"reference moves {len(miss)} files")
            load: dict[int, int] = {}
            total = 0
            for (size, f), (fid, tsize, src, dst) in zip(miss, transfers):
                if (fid, tsize, dst) != (f, size, target):
                    raise Mismatch(f"COP {pid}: transfer {(fid, tsize, dst)}"
                                   f", the reference {(f, size, target)}")
                srcs = {s for s in self.locs.get(f, ())
                        if (free[s] or s == target) and s != target}
                lo = min(load.get(s, 0) for s in srcs)
                if src not in srcs or load.get(src, 0) != lo:
                    raise Mismatch(f"COP {pid}: file {f} from {src}, not a "
                                   f"least-loaded free holder")
                load[src] = load.get(src, 0) + size
                total += size
            load[target] = total
            ref_price = W_TRAFFIC * total + W_MAXLOAD * max(load.values())
            if price != ref_price:
                raise Mismatch(f"COP {pid}: price {price!r}, the reference "
                               f"{ref_price!r}")
            self._start_cop(pid, t.id, target, transfers, act, cpt, infl)

        def node_mask(t, free, need_fit: bool) -> np.ndarray:
            m = free.copy()
            if need_fit:
                m &= (fm >= t.mem) & (fc >= t.cores)
            elif t.mem > self.mem or t.cores > self.cores:
                m[:] = False
            for n in inflight_of(t.id):
                m[n] = False
            for n in prep[t.id]:
                m[n] = False
            return m

        # ---- step 2: prepare waiting tasks on nodes with free compute
        waiting = [t for t in self.ready.values()
                   if t.id not in started and t.inputs]
        order2 = sorted(waiting, key=lambda t: (
            len(prep[t.id]), self.cops_per_task.get(t.id, 0),
            -key(t.priority), t.id))
        for t in order2:
            if not budget(t.id):
                continue
            free = act < self.c_node
            if not free.any():
                break
            feas = feasible_targets(t.inputs, free)
            m = node_mask(t, free, True)
            if feas is not None:
                keep = np.zeros(self.n, dtype=bool)
                keep[list(feas)] = True
                m &= keep
            cand = np.nonzero(m)[0]
            if cand.size == 0:
                continue
            holders = set()
            for f in t.inputs:
                holders |= self.locs.get(f, set())
            total = sum(self.size[f] for f in t.inputs)
            best = None
            for n in holders:
                if m[n]:
                    k = (key(float(missing(t, n))), n)
                    if best is None or k < best:
                        best = k
            others = cand[~np.isin(cand, list(holders))] if holders else cand
            if others.size:
                k = (key(float(total)), int(others[0]))
                if best is None or k < best:
                    best = k
            check_plan(t, best[1], free, 0, 1)

        # ---- step 3: speculative preparation with leftover COP slots
        todo = sorted((t for t in self.ready.values()
                       if t.id not in started and t.inputs
                       and budget(t.id)),
                      key=lambda t: (-key(t.priority), t.id))
        for t in todo:
            free = act < self.c_node
            if not free.any():
                break
            if not budget(t.id):
                continue
            feas = feasible_targets(t.inputs, free)
            m = node_mask(t, free, False)
            if feas is not None:
                keep = np.zeros(self.n, dtype=bool)
                keep[list(feas)] = True
                m &= keep
            probes = np.nonzero(m)[0]
            if probes.size == 0:
                continue
            total = sum(self.size[f] for f in t.inputs)
            price = np.full(probes.size, float(total))
            holders = set()
            for f in t.inputs:
                holders |= self.locs.get(f, set())
            for i in np.nonzero(np.isin(probes, list(holders)))[0]:
                price[i] = float(missing(t, int(probes[i])))
            if key is not identity:
                price = price.astype(np.float32).astype(np.float64)
            off = int(np.argmin(price))
            check_plan(t, int(probes[off]), free, off, int(probes.size))

        if pos != len(got):
            raise Mismatch(f"the program makes {len(got) - pos} decisions "
                           f"more than the reference: {got[pos]}")
