"""Serving (repro.serving.engine): the engine's own host time between
decode steps.  Device idle time inside the ``loop.iter`` spans that lie
in the traced window (host times mapped onto the trace's clock), per
decode step of the window, averaged over devices, in ms."""

import bisect

from bench import trace as tr


def idle(busy, starts, a, b):
    """ns of [a, b] outside ``busy`` (sorted, disjoint [start, end]
    intervals; ``starts`` their starts)."""
    covered = 0.0
    for s, e in busy[max(0, bisect.bisect_right(starts, a) - 1):]:
        if s >= b:
            break
        covered += max(0.0, min(e, b) - max(s, a))
    return (b - a) - covered


def read(run):
    if not run.trace_window or not run.trace:
        return None
    lo, hi = run.trace_window
    iters = [s for s in run.spans
             if s.name == "loop.iter" and lo <= s.t0 and s.t1 <= hi]
    steps = sum(s.attrs.get("decoded", 0) for s in iters)
    devs = list(run.trace["devices"].values())
    if not steps or not devs:
        return None
    w_lo, w_hi = tr.window(run.trace)
    ns = 0.0
    for dev in devs:
        busy = tr.union(dev["ops"], w_lo, w_hi)
        starts = [s for s, _ in busy]
        ns += sum(idle(busy, starts, run.host_to_trace(s.t0),
                       run.host_to_trace(s.t1)) for s in iters)
    return 1e-6 * ns / len(devs) / steps
