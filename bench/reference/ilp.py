"""Plain step-1 assignment solver: the semantics the scheduler's step 1 has.

Copied from the stateless solver of ``src/repro/core/ilp.py`` (``solve``:
the task <-> candidate-node graph split into connected components, each
solved by depth-first branch and bound when it is small enough, else by
priority-descending best fit with one repair pass), without the program's
incremental state, caches or capacity arrays.  Two changes, neither of
which changes an answer: components are found with one pass per distinct
candidate list, and long candidate lists are scanned with numpy.

``key`` maps every float the solver compares (priorities, their sums and
the best-fit keys) into the precision under test: the identity for the
reference, a rounding to float32 for the control.
"""
from __future__ import annotations

import numpy as np

EXACT_CAND_LIMIT = 64
EXACT_TASK_LIMIT = 24
EXACT_NODE_BUDGET = 200_000
_VEC = 64                       # candidate lists this long are scanned by numpy


def exact_gate(n_tasks: int, n_cand: int) -> bool:
    return n_cand <= EXACT_CAND_LIMIT or n_tasks <= EXACT_TASK_LIMIT


def identity(x: float) -> float:
    return x


def components(tasks: list, cands: dict) -> list[list]:
    """Connected components of the task <-> node graph.  Components are
    ordered by their earliest task, tasks inside one by input order."""
    parent = list(range(len(tasks)))

    def find(i: int) -> int:
        while parent[i] != i:
            parent[i] = parent[parent[i]]
            i = parent[i]
        return i

    owner: dict[int, int] = {}
    first_of_list: dict[int, int] = {}
    for i, t in enumerate(tasks):
        lst = cands[t.id]
        j = first_of_list.get(id(lst))
        if j is not None:                 # same node list: same component
            a, b = find(i), find(j)
            if a != b:
                parent[max(a, b)] = min(a, b)
            continue
        first_of_list[id(lst)] = i
        for n in lst:
            o = owner.setdefault(n, i)
            if o != i:
                a, b = find(i), find(o)
                if a != b:
                    parent[max(a, b)] = min(a, b)
    groups: dict[int, list] = {}
    for i, t in enumerate(tasks):
        groups.setdefault(find(i), []).append(t)
    return [groups[r] for r in sorted(groups)]


def solve(tasks: list, cands: dict, free_mem: dict, free_cores: dict,
          key=identity) -> dict[int, int]:
    """``tasks`` in submit order, ``cands[tid]`` the fitting prepared nodes
    in node order (non-empty), free maps by node id (mutated, then
    restored).  Returns ``{task id: node}``."""
    out: dict[int, int] = {}
    for comp in components(tasks, cands):
        n_cand = sum(len(cands[t.id]) for t in comp)
        if exact_gate(len(comp), n_cand):
            sub = solve_exact(comp, cands, free_mem, free_cores, key)
            if sub is None:
                sub = solve_greedy(comp, cands, free_mem, free_cores, key)
        else:
            sub = solve_greedy(comp, cands, free_mem, free_cores, key)
        out.update(sub)
    return out


def _local_free(comp, cands, free_mem, free_cores):
    ids = set()
    for t in comp:
        ids.update(cands[t.id])
    return ({n: free_mem[n] for n in ids}, {n: free_cores[n] for n in ids})


def solve_exact(comp, cands, free_mem, free_cores, key=identity,
                budget: int = EXACT_NODE_BUDGET):
    tasks = sorted(comp, key=lambda t: -key(t.priority))
    fm, fc = _local_free(comp, cands, free_mem, free_cores)
    prio = [key(t.priority) for t in tasks]
    suffix = [0.0] * (len(tasks) + 1)
    for i in range(len(tasks) - 1, -1, -1):
        suffix[i] = key(suffix[i + 1] + prio[i])
    best = [-1.0, {}]
    cur: dict[int, int] = {}
    visited = [0]

    def order(t):
        lst = cands[t.id]
        if len(lst) >= _VEC:
            ids = np.asarray(lst)
            m = np.array([fm[n] for n in lst])
            c = np.array([fc[n] for n in lst])
            ok = (m >= t.mem) & (c >= t.cores)
            ids, m, c = ids[ok], m[ok], c[ok]
            if key is not identity:
                c = np.array([key(x) for x in c.tolist()])
                m = np.array([key(x) for x in m.tolist()])
            return ids[np.lexsort((ids, -m, -c))].tolist()
        ok = [n for n in lst if fm[n] >= t.mem and fc[n] >= t.cores]
        return sorted(ok, key=lambda n: (-key(fc[n]), -key(fm[n]), n))

    def rec(i: int, val: float) -> bool:
        visited[0] += 1
        if visited[0] > budget:
            return False
        if key(val + suffix[i]) <= best[0]:
            return True
        if i == len(tasks):
            if val > best[0]:
                best[0] = val
                best[1] = dict(cur)
            return True
        t = tasks[i]
        for n in order(t):
            fm[n] -= t.mem
            fc[n] -= t.cores
            cur[t.id] = n
            ok = rec(i + 1, key(val + prio[i]))
            del cur[t.id]
            fm[n] += t.mem
            fc[n] += t.cores
            if not ok:
                return False
        return rec(i + 1, val)

    if not rec(0, 0.0):
        return None
    return best[1]


def solve_greedy(comp, cands, free_mem, free_cores, key=identity):
    tasks = sorted(comp, key=lambda t: (-key(t.priority), t.id))
    nodes = sorted({n for t in comp for n in cands[t.id]})
    pos = {n: i for i, n in enumerate(nodes)}
    fm = np.array([free_mem[n] for n in nodes], dtype=np.int64)
    fc = np.array([free_cores[n] for n in nodes], dtype=np.float64)
    ids = np.asarray(nodes, dtype=np.int64)
    where: dict[int, np.ndarray] = {}    # id(list) -> positions, in order
    assign: dict[int, int] = {}

    def positions(lst) -> np.ndarray:
        p = where.get(id(lst))
        if p is None:
            p = where[id(lst)] = np.fromiter((pos[n] for n in lst),
                                             dtype=np.int64, count=len(lst))
        return p

    def try_place(t) -> bool:
        lst = cands[t.id]
        if len(lst) >= _VEC:
            p = positions(lst)
            mem, cor = fm[p], fc[p]
            ok = (mem >= t.mem) & (cor >= t.cores)
            if not ok.any():
                return False
            k1 = cor - t.cores
            k2 = (mem - t.mem).astype(np.float64)
            if key is not identity:
                k1 = k1.astype(np.float32).astype(np.float64)
                k2 = k2.astype(np.float32).astype(np.float64)
            k1 = np.where(ok, k1, np.inf)
            k2 = np.where(k1 == k1.min(), k2, np.inf)
            i = int(p[int(np.argmax(k2 == k2.min()))])  # first: ids ascend
        else:
            ok = [pos[n] for n in lst
                  if fm[pos[n]] >= t.mem and fc[pos[n]] >= t.cores]
            if not ok:
                return False
            i = min(ok, key=lambda i: (key(float(fc[i]) - t.cores),
                                       key(int(fm[i]) - t.mem), nodes[i]))
        assign[t.id] = int(ids[i])
        fm[i] -= t.mem
        fc[i] -= t.cores
        return True

    skipped = [t for t in tasks if not try_place(t)]
    by_id = {t.id: t for t in tasks}
    low = min((key(by_id[tid].priority) for tid in assign), default=None)
    for t in skipped:
        pt = key(t.priority)
        if low is None or low >= pt:
            continue                     # nothing of lower priority placed
        cset = set(cands[t.id])
        placed_here = [(tid, n) for tid, n in assign.items()
                       if n in cset and key(by_id[tid].priority) < pt]
        done = False
        for tid, n in sorted(placed_here,
                             key=lambda kv: key(by_id[kv[0]].priority)):
            other = by_id[tid]
            i = pos[n]
            for m in cands[other.id]:
                if m == n:
                    continue
                j = pos[m]
                if fm[j] >= other.mem and fc[j] >= other.cores:
                    fm[i] += other.mem
                    fc[i] += other.cores
                    fm[j] -= other.mem
                    fc[j] -= other.cores
                    assign[other.id] = m
                    if fm[i] >= t.mem and fc[i] >= t.cores:
                        assign[t.id] = n
                        fm[i] -= t.mem
                        fc[i] -= t.cores
                        low = min(low, pt)
                        done = True
                    break
            if done:
                break
    return assign
