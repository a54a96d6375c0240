"""The program's own spans in a ``jax.profiler`` trace, and the chip's
idle time by the span the host was in.

The program opens ``jax.profiler.TraceAnnotation`` spans whose names
match ``PROGRAM_SPAN``: ``engine.*`` in ``serving/engine.py`` (a request,
its prefill, each decode step, each sampling, the final wait) and
``placement.*`` in ``core/estimator.py``.  They lie on the profiler's
host clock beside the device events.  Over the window that
``bench/trace_reduce.py`` reads (the host span ``bench_window``):

* ``spans``: for each program span, the count and the summed host
  seconds of those that start in the window;
* ``idle_by_span``: the window's idle time (its stretches with no
  operation on the chip, found as ``trace_reduce`` finds them, so the
  keys sum to ``window_s - busy_s``), averaged over chips, by cause.  A
  gap whose middle lies inside a program on the chip (an ``XLA Modules``
  event: loop control, copies the program waits on) is ``(in-program)``;
  any other gap is the chip waiting for the host, keyed by the innermost
  program span open at its middle, or ``(none)`` where none was, which
  is outside the program (the benchmark's own code between requests).

Gaps shorter than the offset between the host's and the device's clocks
(``dispatch_offsets``) are attributed only approximately.
"""

from __future__ import annotations

import collections
import dataclasses
import functools
import glob
import math
import os
import re
import tempfile

from bench.trace_reduce import (DEVICE_PREFIX, MODULES_LINE, OPS_LINE,
                                WINDOW_SPAN, _union, find_xplane,
                                program_name)

PROGRAM_SPAN = re.compile(r"^(engine|placement)\.")
IN_PROGRAM = "(in-program)"
OUTSIDE = "(none)"
REQUEST = "engine.generate"
# the directory prefix of ``bench/run.py``'s traced window
RUN_TRACE_DIR = "bench_trace_"


@dataclasses.dataclass
class Spans:
    window_s: float
    busy_s: float                       # mean over chips
    spans: dict                         # name -> [count, host seconds]
    idle_by_span: dict                  # cause -> seconds (mean over chips)

    def count(self, name) -> int:
        return self.spans.get(name, (0, 0.0))[0]

    def seconds(self, name) -> float:
        return self.spans.get(name, (0, 0.0))[1]

    @property
    def requests(self) -> int:
        """The requests of the window: ``engine.generate`` spans that
        start in it."""
        return self.count(REQUEST)

    def engine_idle_s(self) -> float:
        """Idle time while the host was inside an ``engine.*`` span."""
        return sum(t for k, t in self.idle_by_span.items()
                   if k.startswith("engine."))


def _host_events(planes):
    for plane in planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                yield from line.events


def _devices(planes):
    return [p for p in planes if p.name.startswith(DEVICE_PREFIX)
            and p.name[len(DEVICE_PREFIX):].isdigit()]


class _Sweep:
    """The innermost of ``intervals`` (start, end, name) open at each of
    a rising sequence of instants."""

    def __init__(self, intervals):
        self.todo = sorted(intervals)
        self.k = 0
        self.open = []

    def at(self, t):
        while self.k < len(self.todo) and self.todo[self.k][0] <= t:
            self.open.append(self.todo[self.k])
            self.k += 1
        self.open = [iv for iv in self.open if iv[1] >= t]
        if not self.open:
            return None
        return min(self.open, key=lambda iv: iv[1] - iv[0])[2]


def reduce_spans(path: str) -> Spans:
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    window = None
    program = []                        # (start, end, name)
    for ev in _host_events(planes):
        if ev.name == WINDOW_SPAN:
            window = (ev.start_ns, ev.end_ns)
        elif PROGRAM_SPAN.match(ev.name):
            program.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"no host span {WINDOW_SPAN!r} in {path}")
    w0, w1 = window
    devices = _devices(planes)
    if not devices:
        raise ValueError(f"no {DEVICE_PREFIX}<n> plane in {path}")
    spans = collections.defaultdict(lambda: [0, 0.0])
    for s, e, name in program:
        if w0 <= s < w1:
            spans[name][0] += 1
            spans[name][1] += (e - s) * 1e-9
    busy = 0.0
    idle = collections.defaultdict(float)
    for plane in devices:
        ops, modules = [], []
        for line in plane.lines:
            for ev in line.events:
                if line.name == MODULES_LINE:
                    modules.append((ev.start_ns, ev.end_ns, IN_PROGRAM))
                elif line.name == OPS_LINE:
                    s, e = max(ev.start_ns, w0), min(ev.end_ns, w1)
                    if e > s:
                        ops.append((s, e))
        merged = _union(ops)
        busy += sum(e - s for s, e in merged) * 1e-9
        in_module, in_span = _Sweep(modules), _Sweep(program)
        edge = w0
        for s, e in merged + [[w1, w1]]:
            if s > edge:
                mid = (edge + s) / 2
                cause = in_module.at(mid) or in_span.at(mid) or OUTSIDE
                idle[cause] += (s - edge) * 1e-9 / len(devices)
            edge = max(edge, e)
    return Spans((w1 - w0) * 1e-9, busy / len(devices), dict(spans),
                 dict(idle))


def dispatch_offsets(path: str, span: str = "engine.prefill",
                     program: str = "prefill"):
    """Seconds from the start of each host span ``span`` to the start of
    the device program ``program`` it dispatched (the k-th program to
    the k-th span, on the first chip), or None where their counts
    differ.  Negative where the device event appears before its span:
    the profiler places device events on the host's clock only so
    closely."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    starts = sorted(ev.start_ns for ev in _host_events(planes)
                    if ev.name == span)
    runs = sorted(ev.start_ns for line in _devices(planes)[0].lines
                  if line.name == MODULES_LINE for ev in line.events
                  if program_name(ev.name) == program)
    if len(starts) != len(runs):
        return None
    return [(d - h) * 1e-9 for h, d in zip(starts, runs)]


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int) -> Spans:
    return reduce_spans(path)


def of_run(ctx):
    """The spans of the traced run whose per-layer metrics are being
    read, or None where none can be found.

    ``bench/run.py`` hands a reader the reduced trace, not its file; it
    keeps the file in a ``bench_trace_*`` directory under the run's
    temporary directory until the readers have run.  The newest such
    trace is taken, and only if its window is the one the reader was
    given."""
    dirs = glob.glob(os.path.join(tempfile.gettempdir(),
                                  RUN_TRACE_DIR + "*"))
    for d in sorted(dirs, key=os.path.getmtime, reverse=True):
        try:
            path = find_xplane(d)
        except FileNotFoundError:
            continue
        red = _reduced(path, os.stat(path).st_mtime_ns)
        if math.isclose(red.window_s, ctx.trace.window_s, rel_tol=1e-12):
            return red
        return None
    return None


def per_request_ms(ctx, seconds):
    """``seconds(spans)`` per request of the window, in ms; None where
    the trace holds no request."""
    red = of_run(ctx)
    if red is None or not red.requests:
        return None
    return 1e3 * seconds(red) / red.requests
