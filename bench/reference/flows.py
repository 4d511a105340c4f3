"""Plain flow-level model of the simulated cluster, replayed from the log.

It predicts when each started task and COP finishes, from the decisions
and start times in the log alone, and so checks the simulator's flow fill
and task model.  Written from the model the program's simulator states
(``src/repro/sim/engine.py``, ``sim/network.py``, ``sim/dfs.py``), with
the max-min fair progressive fill copied from ``_progressive_fill``:

* links: every node has a network uplink and downlink (``net_bw``) and a
  local disk read and write link (``disk_read_bw``, ``disk_write_bw``);
* a task with node-local I/O reads its intermediate inputs from the local
  disk and its workflow inputs from Ceph (the ``replication / n`` share
  locally, the rest from one seeded random other node), then computes for
  its compute time, then writes its outputs to the local disk;
* a COP moves each file as one flow over the source's disk read link and
  uplink and the target's downlink and disk write link;
* flows share links max-min fairly, re-filled over each connected
  component whenever a flow starts or ends;
* time steps to the earliest flow end or timer, and every flow within
  half a byte of done ends in that step.

``key`` rounds the fill's arithmetic into the precision under test.
"""
from __future__ import annotations

import heapq
import random

from .ilp import identity

# a flow within this many bytes of done completes with the flow that ends
# the simulator's step (sim/network.py)
DUST = 0.5


class FlowModel:
    def __init__(self, n_nodes: int, net_bw: float, disk_read_bw: float,
                 disk_write_bw: float, ceph_replication: int, seed: int,
                 key=identity) -> None:
        self.cap = {}
        for n in range(n_nodes):
            self.cap[("up", n)] = net_bw
            self.cap[("down", n)] = net_bw
            self.cap[("dr", n)] = disk_read_bw
            self.cap[("dw", n)] = disk_write_bw
        self.n = n_nodes
        self.rep = min(ceph_replication, n_nodes)
        self.rng = random.Random(seed)
        self.key = key
        self.now = 0.0
        self.flows: dict[int, list] = {}   # id -> [links, rem, rate, at, owner]
        self.link_flows: dict[tuple, set] = {}
        self.dirty: set = set()
        self.etas: list = []               # (eta, flow id, epoch)
        self.halves: list = []             # (eta of all but DUST bytes, ...)
        self.epoch: dict[int, int] = {}
        self.timers: list = []             # (time, seq, task id)
        self.seq = 0
        self.next_flow = 0
        self.runs: dict[int, list] = {}    # task -> [pending, node, task]
        self.copruns: dict[int, set] = {}  # cop id -> pending flows
        self.finished: dict[tuple, float] = {}

    # ------------------------------------------------------------ flows
    def _add(self, links: tuple, nbytes: float, owner: tuple):
        if nbytes <= 0:
            return None
        fid = self.next_flow
        self.next_flow += 1
        self.flows[fid] = [links, float(nbytes), 0.0, self.now, owner]
        for l in links:
            self.link_flows.setdefault(l, set()).add(fid)
        self.dirty.update(links)
        return fid

    def _remove(self, fid: int) -> None:
        links = self.flows.pop(fid)[0]
        for l in links:
            s = self.link_flows[l]
            s.discard(fid)
            if not s:
                del self.link_flows[l]
        self.dirty.update(links)

    def _refill(self) -> None:
        if not self.dirty:
            return
        comp: set = set()
        frontier = set(self.dirty)
        seen = set(frontier)
        self.dirty.clear()
        while frontier:
            new = set()
            for l in frontier:
                new |= self.link_flows.get(l, set())
            new -= comp
            comp |= new
            nxt = set()
            for fid in new:
                nxt.update(self.flows[fid][0])
            nxt -= seen
            seen |= nxt
            frontier = nxt
        if not comp:
            return
        key = self.key
        flows = [self.flows[fid] for fid in sorted(comp)]
        for f in flows:                      # settle bytes at the old rate
            if f[2] > 0 and self.now > f[3]:
                f[1] = max(key(f[1] - f[2] * (self.now - f[3])), 0.0)
            f[3] = self.now
        ids = sorted(comp)
        remaining_cap: dict = {}
        link_of: dict = {}
        for fid, f in zip(ids, flows):
            for l in f[0]:
                link_of.setdefault(l, set()).add(fid)
                remaining_cap.setdefault(l, self.cap[l])
        unfrozen = set(ids)
        while unfrozen:
            best, best_link = float("inf"), None
            for l, fids in link_of.items():
                if fids:
                    share = key(remaining_cap[l] / len(fids))
                    if share < best:
                        best, best_link = share, l
            if best_link is None:
                break
            for fid in list(link_of[best_link]):
                f = self.flows[fid]
                f[2] = best
                unfrozen.discard(fid)
                for l in f[0]:
                    link_of[l].discard(fid)
                    remaining_cap[l] = max(key(remaining_cap[l] - best), 0.0)
            link_of[best_link].clear()
        for fid, f in zip(ids, flows):
            e = self.epoch.get(fid, 0) + 1
            self.epoch[fid] = e
            if f[1] <= DUST:
                heapq.heappush(self.etas, (self.now, fid, e))
                heapq.heappush(self.halves, (self.now, fid, e))
            elif f[2] > 0:
                heapq.heappush(self.etas, (f[3] + key(f[1] / f[2]), fid, e))
                heapq.heappush(self.halves,
                               (f[3] + key((f[1] - DUST) / f[2]), fid, e))

    def _next_eta(self) -> float:
        while self.etas:
            eta, fid, e = self.etas[0]
            if fid in self.flows and self.epoch[fid] == e:
                return eta
            heapq.heappop(self.etas)
        return float("inf")

    # ------------------------------------------------------- task model
    def start_task(self, task, node: int, size_of) -> None:
        pending: set = set()
        self.runs[task.id] = [pending, node, task]
        local = sum(size_of(f) for f in task.inputs)
        for links, nbytes in [((("dr", node),), local)] + \
                self._dfs_input_paths(task.dfs_inputs, node):
            fid = self._add(links, nbytes, ("T", task.id))
            if fid is not None:
                pending.add(fid)
        if not pending:
            self._compute(task.id)

    def _dfs_input_paths(self, size: int, reader: int) -> list:
        if size <= 0:
            return []
        local = size * min(1.0, self.rep / self.n)
        remote = size - local
        paths = []
        if local > 0:
            paths.append(((("dr", reader),), local))
        if remote > 0:
            r = self.rng.randrange(self.n)
            while r == reader and self.n > 1:
                r = self.rng.randrange(self.n)
            paths.append(((("dr", r), ("up", r), ("down", reader)), remote))
        return paths

    def _compute(self, tid: int) -> None:
        task = self.runs[tid][2]
        if task.compute_time > 0:
            self.seq += 1
            heapq.heappush(self.timers,
                           (self.now + task.compute_time, self.seq, tid))
        else:
            self._write(tid)

    def _write(self, tid: int) -> None:
        pending, node, task = self.runs[tid]
        total = sum(task.out_sizes) + task.dfs_outputs
        fid = self._add((("dw", node),), total, ("W", tid))
        if fid is not None:
            pending.add(fid)
        else:
            self._finish(("T", tid))

    def _finish(self, owner: tuple) -> None:
        self.finished[owner] = self.now
        if owner[0] == "T":
            self.runs.pop(owner[1], None)
        else:
            self.copruns.pop(owner[1], None)

    def start_cop(self, pid: int, transfers: tuple) -> None:
        pending: set = set()
        self.copruns[pid] = pending
        for _fid, size, src, dst in transfers:
            links = (("dr", src), ("up", src), ("down", dst), ("dw", dst))
            fid = self._add(links, size, ("C", pid))
            if fid is not None:
                pending.add(fid)
        if not pending:
            self._finish(("C", pid))

    # ------------------------------------------------------------ clock
    def advance_to(self, t: float) -> None:
        """Run the model's own events up to simulated time ``t``."""
        while True:
            self._refill()
            t_flow = self._next_eta()
            t_timer = self.timers[0][0] if self.timers else float("inf")
            t_next = min(t_flow, t_timer)
            if t_next > t:
                break
            self.now = max(self.now, t_next)
            done = []
            while self.halves and self.halves[0][0] <= self.now:
                eta, fid, e = heapq.heappop(self.halves)
                if fid in self.flows and self.epoch[fid] == e:
                    done.append(fid)
            for fid in sorted(done):
                owner = self.flows[fid][4]
                self._remove(fid)
                if owner[0] == "C":
                    pend = self.copruns[owner[1]]
                    pend.discard(fid)
                    if not pend:
                        self._finish(owner)
                else:
                    pend = self.runs[owner[1]][0]
                    pend.discard(fid)
                    if not pend:
                        if owner[0] == "T":
                            self._compute(owner[1])
                        else:
                            self._finish(("T", owner[1]))
            while self.timers and self.timers[0][0] <= self.now + 1e-9:
                _, _, tid = heapq.heappop(self.timers)
                if tid in self.runs:
                    self._write(tid)
        self.now = max(self.now, t)
