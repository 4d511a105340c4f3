"""Profiler slice of a traced run, and its reduction to device busy time.

A :class:`Tracer` starts ``jax.profiler`` at a round boundary some way into
the window and stops it a few seconds later; the harness's spans are
written into the same trace as ``jax.profiler.TraceAnnotation`` s, so host
and device share one clock.  :func:`reduce_events` turns the slice into:

* ``busy_s``: the union of the intervals in which an operation ran on a
  device, over the slice, averaged over the devices;
* ``window_s``: the slice's length (the ``bench.slice`` span);
* ``device_ops``: device time by operation name, largest first;
* ``idle_gaps``: the slice's device-idle time by what the host was doing,
  largest first.  Each idle moment goes to the innermost harness span open
  on the host then (``sched.step1``, ``adapter.task_finished``, ...), or to
  ``outside adapter`` when none is: the simulator and the harness.

The default decision path issues no device work, so the harness runs one
tiny jitted operation at each end of the slice (``bench_marker``), which
the reduction reports like any other device operation.
"""
from __future__ import annotations

import glob
import os
import shutil
import time

TRACE_AT = 0.4                 # the slice starts this share into the window
TRACE_S = 3.0                  # and lasts this many seconds
SLICE = "bench.slice"
CHIP = "/device:TPU:"          # device planes of the chips (not CUSTOM ones)
OUTSIDE = "outside adapter"


def marker_op():
    """A compiled no-op the slice runs on the device at each end."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def bench_marker(x):
        return x + 1.0

    x = jnp.zeros((8, 128), jnp.float32)
    return lambda: bench_marker(x).block_until_ready()


class Tracer:
    def __init__(self, at_s: float, slice_s: float, logdir: str) -> None:
        import jax
        self._jax = jax
        self.at_s = at_s
        self.slice_s = slice_s
        self.logdir = logdir
        self.marker = marker_op()
        self.marker()                     # compile in set-up
        self.state = "before"
        self._span = None

    def annotation(self, name: str):
        return self._jax.profiler.TraceAnnotation(name)

    def tick(self, elapsed: float) -> float:
        """Called at each window round boundary with the window time so far;
        returns the seconds it spent starting or stopping the profiler."""
        if self.state == "before" and elapsed >= self.at_s:
            t = time.perf_counter()
            # host spans and device ops only: the Python tracer would slow
            # every call inside the slice and write an event for each
            opts = self._jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            self._jax.profiler.start_trace(self.logdir,
                                           profiler_options=opts)
            self._span = self.annotation(SLICE)
            self._span.__enter__()
            self.marker()
            self.state = "on"
            self.started = time.perf_counter()
            return self.started - t
        if self.state == "on" and elapsed >= self.at_s + self.slice_s:
            t = time.perf_counter()
            self.finish()
            return time.perf_counter() - t
        return 0.0

    def finish(self) -> None:
        if self.state != "on":
            return
        self.marker()
        self._span.__exit__(None, None, None)
        self._jax.profiler.stop_trace()
        self.state = "done"

    def reduce(self) -> dict | None:
        paths = glob.glob(os.path.join(self.logdir, "**", "*.xplane.pb"),
                          recursive=True)
        if not paths:
            return None
        device, host = read_xplane(max(paths, key=os.path.getmtime))
        shutil.rmtree(self.logdir, ignore_errors=True)
        return reduce_events(device, host)


def read_xplane(path: str) -> tuple[dict, list]:
    """``({device plane: [(op, start_ns, dur_ns)]}, [(span, start_ns,
    dur_ns)])`` from one profile.  Device planes are the ``/device:``
    planes other than the host CPU; their ``XLA Ops`` line holds the
    operations."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    device: dict[str, list] = {}
    host: list = []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            lines = list(plane.lines)
            ops = [l for l in lines if l.name == "XLA Ops"] or lines
            device[plane.name] = [(e.name, e.start_ns, e.duration_ns)
                                  for l in ops for e in l.events]
        elif plane.name.startswith("/host:"):
            for l in plane.lines:
                for e in l.events:
                    if e.name.startswith(("bench.", "adapter.", "sched.")):
                        host.append((e.name, e.start_ns, e.duration_ns))
    return device, host


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def reduce_events(device: dict, host: list) -> dict | None:
    """The slice's device busy time and breakdown (see module docstring).
    ``None`` when the trace holds no slice span or no device."""
    slices = [(s, s + d) for name, s, d in host if name == SLICE]
    chips = {k: v for k, v in device.items() if k.startswith(CHIP)}
    if not slices or not chips:
        return None
    w0, w1 = slices[0]
    window = (w1 - w0) * 1e-9
    busy_total = 0.0
    op_time: dict[str, float] = {}
    busy_sets = []
    for ops in chips.values():
        # the trace spans the slice; device clocks run a little apart from
        # the host's, so device time is not clipped to the host span
        iv = []
        for name, s, d in ops:
            iv.append((s, s + d))
            name = name.split(" = ")[0].lstrip("%")
            op_time[name] = op_time.get(name, 0.0) + d * 1e-9
        u = _union(iv)
        busy_sets.append(u)
        busy_total += sum(b - a for a, b in u) * 1e-9
    busy = busy_total / len(chips)
    # who the host was: the innermost open span, from one sweep over the
    # span boundaries (spans of the one harness thread nest properly)
    marks = []
    for name, s, d in host:
        if name != SLICE and s + d > w0 and s < w1:
            marks.append((max(s, w0), 1, -(s + d), name))
            marks.append((min(s + d, w1), 0, 0, name))
    marks.sort()
    owners = []                      # (t0, t1, name) covering the slice
    stack: list[str] = []
    cur = w0
    for t, is_start, _, name in marks:
        if t > cur:
            owners.append((cur, t, stack[-1] if stack else OUTSIDE))
            cur = t
        if is_start:
            stack.append(name)
        elif name in stack:
            stack.reverse()
            stack.remove(name)
            stack.reverse()
    if w1 > cur:
        owners.append((cur, w1, stack[-1] if stack else OUTSIDE))
    idle = []
    cur = w0
    for a, b in busy_sets[0] + [(w1, w1)]:
        a, b = min(max(a, w0), w1), min(max(b, w0), w1)
        if a > cur:
            idle.append((cur, a))
        cur = max(cur, b)
    gaps: dict[str, float] = {}
    j = 0
    for g0, g1 in idle:
        while j < len(owners) and owners[j][1] <= g0:
            j += 1
        k = j
        while k < len(owners) and owners[k][0] < g1:
            o0, o1, name = owners[k]
            ov = min(o1, g1) - max(o0, g0)
            if ov > 0:
                gaps[name] = gaps.get(name, 0.0) + ov * 1e-9
            k += 1
    top = lambda d: [[k, v] for k, v in  # noqa: E731
                     sorted(d.items(), key=lambda kv: -kv[1])[:10]]
    return {"busy_s": busy, "window_s": window, "device_ops": top(op_time),
            "idle_gaps": top(gaps)}
