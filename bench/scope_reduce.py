"""Device time of the program's named scopes in a ``jax.profiler`` trace.

The program wraps parts of its layers in ``jax.named_scope`` (for the
MLA and expert layers: ``mla.project``, ``mla.latent_attend``,
``moe.route``, ``moe.experts``, ``moe.shared``).  A scope reaches the
compiled program only as the ``op_name`` metadata of its instructions;
the trace's ``XLA Ops`` events name each operation by its HLO text, not
its scope.  So the system hands the readers, in its counters, a map
from instruction name to scope for each program (``scopes``: program ->
{instruction -> scope}, read from the compiled program's HLO), and this
reducer puts each operation event of the window on the program it ran
in (the ``XLA Modules`` event around it on the same chip) and sums the
device time of the operations of each (program, scope).

Only operations that do work count: a loop or a call (``while``,
``conditional``, ``call``) is an event around the events of the
operations it runs, so it is left out.  Like ``bench/trace_reduce.py``,
an operation counts whole when it starts in the window (the host span
``bench_window``), and times are summed over chips.
"""

from __future__ import annotations

import bisect
import collections
import functools
import glob
import math
import os
import re
import tempfile

from bench.span_reduce import RUN_TRACE_DIR, _devices, _host_events
from bench.trace_reduce import (MODULES_LINE, OPS_LINE, WINDOW_SPAN,
                                find_xplane, program_name)

CONTROL = ("while", "conditional", "call")
_OP = re.compile(r"%?([\w.\-]+) = .*? ([a-z][a-z0-9\-]*)\(")


def reduce_scopes(path: str, scopes: dict):
    """(window seconds, {(program, scope): [count, device seconds]}) of
    the trace at ``path``; ``scopes`` maps each program to its
    instruction names' scopes."""
    from jax.profiler import ProfileData

    planes = list(ProfileData.from_file(path).planes)
    window = next(((ev.start_ns, ev.end_ns) for ev in _host_events(planes)
                   if ev.name == WINDOW_SPAN), None)
    if window is None:
        raise ValueError(f"no host span {WINDOW_SPAN!r} in {path}")
    w0, w1 = window
    out = collections.defaultdict(lambda: [0, 0.0])
    for plane in _devices(planes):
        modules = sorted((ev.start_ns, ev.end_ns, program_name(ev.name))
                         for line in plane.lines if line.name == MODULES_LINE
                         for ev in line.events)
        starts = [m[0] for m in modules]
        for line in plane.lines:
            if line.name != OPS_LINE:
                continue
            for ev in line.events:
                if not w0 <= ev.start_ns < w1:
                    continue
                m = _OP.match(ev.name)
                if m is None or m.group(2) in CONTROL:
                    continue
                k = bisect.bisect_right(starts, ev.start_ns) - 1
                if k < 0 or ev.start_ns > modules[k][1]:
                    continue
                program = modules[k][2]
                scope = scopes.get(program, {}).get(m.group(1))
                if scope is not None:
                    out[(program, scope)][0] += 1
                    out[(program, scope)][1] += ev.duration_ns * 1e-9
    return (w1 - w0) * 1e-9, dict(out)


@functools.lru_cache(maxsize=1)
def _reduced(path: str, mtime_ns: int, scopes_key):
    return reduce_scopes(path, dict((p, dict(m)) for p, m in scopes_key))


def scope_seconds(ctx, program: str, scope: str):
    """Device seconds of ``scope``'s operations in ``program`` over the
    traced window whose per-layer metrics are being read, or None where
    the run's trace or the system's scope map cannot be found (a program
    without the scope reads 0)."""
    scopes = ctx.counters.get("scopes")
    if not scopes:
        return None
    dirs = glob.glob(os.path.join(tempfile.gettempdir(),
                                  RUN_TRACE_DIR + "*"))
    for d in sorted(dirs, key=os.path.getmtime, reverse=True):
        try:
            path = find_xplane(d)
        except FileNotFoundError:
            continue
        key = tuple(sorted((p, tuple(sorted(m.items())))
                           for p, m in scopes.items()))
        window_s, got = _reduced(path, os.stat(path).st_mtime_ns, key)
        if not math.isclose(window_s, ctx.trace.window_s, rel_tol=1e-12):
            return None
        return got.get((program, scope), (0, 0.0))[1]
    return None
