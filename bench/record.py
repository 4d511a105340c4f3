"""The benchmark's own spans, counters and decision log at the adapter boundary.

A :class:`Recorder` wraps the entry points of one adapter instance (and the
DPS write-through entry points the environment calls beside them) from
outside the program.  It keeps:

* rounds: a round is every adapter call since the previous ``schedule()``
  returned, up to and including the next ``schedule()``.  Its adapter
  time is the sum of the time spent inside those calls;
* events: calls of the input entry points ``submit``, ``task_finished``,
  ``cop_finished``, ``decline``, ``node_added`` and ``node_removed`` (not
  the ``task_started`` acknowledgement);
* in a traced run, spans of the scheduler's step methods, each also
  written as a ``jax.profiler.TraceAnnotation``;
* the log of every call and every decision from the start of the run,
  which the reference check replays once the window has closed.  During
  the run the log keeps each call's own arguments and each round's own
  list of actions, with the simulated time where the reference needs it;
  :meth:`Recorder.close` turns them into plain tuples after the window,
  so that no conversion is timed.

The window opens at the first round boundary at which ``setup_done()``
holds and closes at the first ``schedule()`` call after ``seconds`` have
passed; that call and the round it would end are not run, so the window
ends where the last whole round ended.
"""
from __future__ import annotations

import time

EVENTS = ("submit", "task_finished", "cop_finished", "decline",
          "node_added", "node_removed")
ACKS = ("task_started", "forget_task")
DPS_CALLS = ("register_file", "add_replica", "delete_replicas")
TIMED = ("task_finished", "cop_finished")     # logged with simulated time
STEP_SPANS = (("_step1_start_prepared", "step1"),
              ("_step2_prepare_for_free_compute", "step2"),
              ("_step3_speculative_prepare", "step3"))


class WindowClosed(Exception):
    """Raised from the wrapped ``schedule()`` once the window is over."""


def _action(act) -> tuple:
    plan = getattr(act, "plan", None)
    if plan is None:
        return ("T", act.task_id, act.node)
    return ("C", plan.id, plan.task_id, plan.target,
            tuple((tr.file_id, tr.size, tr.src, tr.dst)
                  for tr in plan.transfers), plan.price)


class Recorder:
    def __init__(self, seconds: float, setup_done, sim_time,
                 file_size=None, tracer=None) -> None:
        self.seconds = float(seconds)
        self.setup_done = setup_done
        self.sim_time = sim_time
        self.file_size = file_size
        self.tracer = tracer
        self.window = False
        self.t0 = self.t_end = 0.0
        self.excluded_s = 0.0           # profiler start/stop inside window
        self.log: list[tuple] = []      # every call since the start
        self.window_log_start = None    # log index where the window opens
        self.round_s: list[float] = []  # adapter time of each window round
        self.round_end: list[float] = []  # when each window round ended
        self.round_events: list[int] = []
        self.callbacks_s = 0.0          # window time in non-schedule calls
        self.spans: dict[str, float] = {}
        self.setup_rounds = 0
        self.cut_short = False          # the runner ran dry in the window
        self.sim_span = [0.0, 0.0]      # simulated time the window covers
        self.sim_seed = None            # the simulator's seed, if it has one
        self._cur_s = 0.0
        self._cur_events = 0
        self._cur_cb = 0.0

    # ------------------------------------------------------------ install
    def install(self, adapter, sched=None, spans: bool = False) -> None:
        self.t_install = time.perf_counter()     # the cluster is built
        for name in EVENTS + ACKS:
            self._wrap(adapter, name, name in EVENTS)
        dps = getattr(adapter, "dps", None)
        if dps is not None:
            for name in DPS_CALLS:
                if hasattr(dps, name):
                    self._wrap(dps, name, False)
        orig = adapter.schedule

        def schedule():
            return self._schedule(orig)
        adapter.schedule = schedule
        if spans and sched is not None:
            for meth, span in STEP_SPANS:
                if hasattr(sched, meth):
                    self._wrap_span(sched, meth, span)

    def _wrap(self, obj, name: str, event: bool) -> None:
        orig = getattr(obj, name)
        log = self.log
        clock = time.perf_counter
        now = self.sim_time if name in TIMED else None
        annotate = self.tracer.annotation if self.tracer else None

        def call(*args, **kw):
            log.append((name, args, kw, now() if now else None))
            if annotate is not None:
                with annotate("adapter." + name):
                    t = clock()
                    out = orig(*args, **kw)
                    dt = clock() - t
            else:
                t = clock()
                out = orig(*args, **kw)
                dt = clock() - t
            self._cur_s += dt
            self._cur_cb += dt
            if event:
                self._cur_events += 1
            return out
        setattr(obj, name, call)

    def _wrap_span(self, obj, name: str, span: str) -> None:
        orig = getattr(obj, name)
        clock = time.perf_counter
        annotate = self.tracer.annotation

        def call(*args, **kw):
            with annotate("sched." + span):
                t = clock()
                out = orig(*args, **kw)
                dt = clock() - t
            if self.window:
                self.spans[span] = self.spans.get(span, 0.0) + dt
            return out
        setattr(obj, name, call)

    # ---------------------------------------------------------- schedule
    def _schedule(self, orig):
        clock = time.perf_counter
        t = clock()
        if self.window and t - self.t0 - self.excluded_s >= self.seconds:
            raise WindowClosed
        if self.tracer is not None:
            with self.tracer.annotation("adapter.schedule"):
                out = orig()
                t1 = clock()
        else:
            out = orig()
            t1 = clock()
        dt = t1 - t
        self.log.append(("round", self.sim_time(), out))
        if self.window:
            self.round_s.append(self._cur_s + dt)
            self.round_end.append(t1)
            self.sim_span[1] = self.sim_time()
            self.round_events.append(self._cur_events)
            self.callbacks_s += self._cur_cb
            self.t_end = t1
            if self.tracer is not None:
                self.excluded_s += self.tracer.tick(
                    t1 - self.t0 - self.excluded_s)
        else:
            self.setup_rounds += 1
            if self.setup_done(self.setup_rounds):
                self.window = True
                self.window_log_start = len(self.log)
                self.sim_span = [self.sim_time(), self.sim_time()]
                self.t0 = self.t_end = clock()
        self._cur_s = self._cur_cb = 0.0
        self._cur_events = 0
        return out

    # ------------------------------------------------------ log entries
    def close(self) -> None:
        """Turn the log into plain tuples, once the window has closed.

        Output file sizes come from the run's own ``register_file`` calls,
        or, for files not yet written, from ``file_size``: the simulator
        forgets a retired instance's files, so the log keeps what it
        needs."""
        written = {}
        for e in self.log:
            if e[0] == "register_file":
                f = e[1][0] if e[1] else e[2]["f"]
                written[f.id] = f.size
        out = []
        for e in self.log:
            if e[0] == "round":
                out.append(("round", e[1], tuple(_action(a) for a in e[2])))
            else:
                name, args, kw, t = e
                out.append(getattr(self, "_log_" + name)(
                    *args, **kw, _t=t, _sizes=written))
        self.log = out

    def _log_submit(self, t, _t=None, _sizes=None):
        outs = tuple(t.outputs)
        sizes = tuple(_sizes[f] if f in _sizes else self.file_size(f)
                      for f in outs)
        return ("submit", t.id, t.mem, t.cores, tuple(t.inputs),
                t.dfs_inputs, outs, sizes, t.dfs_outputs, t.compute_time,
                t.priority, t.abstract)

    def _log_task_finished(self, task_id, node, _t=None, _sizes=None):
        return ("tfin", task_id, node, _t)

    def _log_cop_finished(self, plan, ok=True, _t=None, _sizes=None):
        return ("cfin", plan.id, bool(ok), _t)

    def _log_decline(self, task_id, node, reason="", _t=None, _sizes=None):
        return ("decline", task_id, node)

    def _log_node_added(self, node, _t=None, _sizes=None):
        return ("nadd", node)

    def _log_node_removed(self, node, _t=None, _sizes=None):
        return ("nrem", node)

    def _log_task_started(self, task_id, node, _t=None, _sizes=None):
        return ("ack", task_id, node)

    def _log_forget_task(self, task_id, _t=None, _sizes=None):
        return ("forget", task_id)

    def _log_register_file(self, f, location, _t=None, _sizes=None):
        return ("reg", f.id, f.size, location)

    def _log_add_replica(self, file_id, node, _t=None, _sizes=None):
        return ("rep", file_id, node)

    def _log_delete_replicas(self, file_id, keep=0, _t=None, _sizes=None):
        return ("del", file_id, keep)

    # ---------------------------------------------------------- summary
    @property
    def window_s(self) -> float:
        return self.t_end - self.t0 - self.excluded_s

    def slices(self, n: int = 5) -> list[tuple[float, int]]:
        """``(events per second, rounds)`` in each of ``n`` equal parts of
        the window, to show whether the window is stationary.  A traced
        run's profiler pause falls into the part it interrupts."""
        span = (self.t_end - self.t0) / n
        out = [[0, 0] for _ in range(n)]
        for t, ev in zip(self.round_end, self.round_events):
            i = min(int((t - self.t0) / span), n - 1) if span > 0 else 0
            out[i][0] += ev
            out[i][1] += 1
        return [(ev / span if span > 0 else 0.0, r) for ev, r in out]

    def summary(self, simulated: bool) -> dict:
        """What the end-to-end metrics and the per-layer readers read;
        ``simulated`` says whether a simulator ran beside the adapter."""
        return {"simulated": simulated, "rounds": len(self.round_s),
                "events": sum(self.round_events), "window_s": self.window_s,
                "adapter_s": sum(self.round_s),
                "callbacks_s": self.callbacks_s,
                "round_s": self.round_s, "spans": dict(self.spans),
                "slices": self.slices(), "sim_span": tuple(self.sim_span),
                "setup_rounds": self.setup_rounds,
                "cut_short": self.cut_short}
