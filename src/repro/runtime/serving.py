"""Continuous-batching serving engine.

The WOW idea applied to inference: the *slot* is the resource, the request
is the task, and prefill is the "COP" that prepares a slot while decode
steps for other requests keep running.  A fixed pool of B cache slots
decodes in lock-step; freed slots are refilled from a priority queue
(shortest-prompt-first by default, mirroring the paper's input-size
prioritization) without stopping the decode batch.

Pure-host orchestration around the jitted prefill/decode steps; works on
the CPU smoke configs (tests) and shards like serve_step at scale.
"""
from __future__ import annotations

import dataclasses
import functools
import heapq

import jax
import jax.numpy as jnp
import numpy as np

from ..models import ArchConfig, Model


@dataclasses.dataclass
class Request:
    id: int
    prompt: np.ndarray            # (len,) int32
    max_new: int = 16
    priority: float = 0.0         # smaller = sooner

    def __lt__(self, other: "Request") -> bool:
        return (self.priority, self.id) < (other.priority, other.id)


@dataclasses.dataclass
class Completion:
    id: int
    tokens: list[int]


class ServingEngine:
    """Slot-based continuous batching with greedy decoding."""

    def __init__(self, cfg: ArchConfig, params, slots: int = 4,
                 max_len: int = 128) -> None:
        self.cfg = cfg
        self.model = Model(cfg)
        self.params = params
        self.slots = slots
        self.max_len = max_len
        self.cache = self.model.init_decode_cache(slots, max_len)
        from ..launch.steps import make_serve_step
        self._prefill = jax.jit(functools.partial(self.model.prefill,
                                                  pad_to=max_len))
        self._decode = jax.jit(make_serve_step(self.model))
        self._queue: list[Request] = []
        self._active: dict[int, dict] = {}      # slot -> request state
        self._free = list(range(slots))
        self._last_tok = np.zeros((slots, 1), np.int32)
        self._done: list[Completion] = []
        self._next_id = 0

    # ----------------------------------------------------------------- API
    def submit(self, prompt: np.ndarray, max_new: int = 16,
               priority: float | None = None) -> int:
        rid = self._next_id
        self._next_id += 1
        pr = float(len(prompt)) if priority is None else priority
        heapq.heappush(self._queue,
                       Request(rid, np.asarray(prompt, np.int32), max_new,
                               pr))
        return rid

    def step(self) -> list[Completion]:
        """Admit waiting requests into free slots (prefill), run one decode
        step for all active slots, retire finished requests."""
        self._admit()
        out: list[Completion] = []
        if self._active:
            tok = jnp.asarray(self._last_tok)
            next_tok, self.cache = self._decode(self.params, tok,
                                                self.cache)
            nxt = np.asarray(next_tok)
            for slot, st in list(self._active.items()):
                t = int(nxt[slot, 0])
                st["tokens"].append(t)
                if len(st["tokens"]) >= st["req"].max_new:
                    out.append(Completion(st["req"].id, st["tokens"]))
                    self._retire(slot)
                else:
                    self._last_tok[slot, 0] = t
        self._done.extend(out)
        return out

    def run_until_drained(self, max_steps: int = 10_000) -> list[Completion]:
        steps = 0
        while (self._queue or self._active) and steps < max_steps:
            self.step()
            steps += 1
        return self._done

    @property
    def utilization(self) -> float:
        return len(self._active) / self.slots

    # ------------------------------------------------------------ internal
    def _admit(self) -> None:
        while self._free and self._queue:
            req = heapq.heappop(self._queue)
            slot = self._free.pop()
            # prefill the single request, then splice its cache row into
            # the batch cache at `slot` (the COP analogue: preparing the
            # slot overlaps with other slots' decoding at engine level)
            batch = {"tokens": jnp.asarray(req.prompt[None, :])}
            logits, cache1 = self._prefill(self.params, batch)
            self._splice(slot, cache1)
            first = int(np.asarray(jnp.argmax(logits, -1))[0])
            self._last_tok[slot, 0] = first
            self._active[slot] = {"req": req, "tokens": [first]}
            if req.max_new <= 1:
                self._done.append(Completion(req.id, [first]))
                self._retire(slot)

    def _splice(self, slot: int, cache1) -> None:
        def put(big, one, batch_axis):
            idx = [slice(None)] * big.ndim
            idx[batch_axis] = slice(slot, slot + 1)
            return big.at[tuple(idx)].set(one)

        new = {}
        hybrid = self.cfg.family == "hybrid"
        for key, big in self.cache.items():
            one = cache1[key]
            if key == "pos":
                new[key] = big.at[slot].set(one[0])
            elif key in ("k", "v", "xk", "xv"):
                new[key] = put(big, one, 1)
            elif key in ("conv", "ssm"):
                new[key] = put(big, one, 2 if hybrid else 1)
            else:
                new[key] = big
        self.cache = new

    def _retire(self, slot: int) -> None:
        self._active.pop(slot, None)
        self._free.append(slot)
