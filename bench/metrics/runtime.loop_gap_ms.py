"""Runtime and dispatch sharing the engine's event loop (repro.core,
repro.dispatch): device idle time between consecutive ``loop.iter``
spans of one engine, where the earlier one ended with requests still
live (``live`` > 0): the time the loop spent away from the engine while
a batch waited.  Over the iterations that lie in the traced window (host
times mapped onto the trace's clock), per decode step, averaged over
devices, in ms."""

import bisect
from collections import defaultdict

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
    engines = defaultdict(list)
    for s in iters:
        engines[s.track].append(s)
    gaps = []
    for its in engines.values():
        its.sort(key=lambda s: s.t0)
        gaps += [(run.host_to_trace(a.t1), run.host_to_trace(b.t0))
                 for a, b in zip(its, its[1:])
                 if a.attrs.get("live", 0) > 0]
    w_lo, w_hi = tr.window(run.trace)
    ns = 0.0
    for dev in devs:
        busy = tr.union(dev["ops"], w_lo, w_hi)
        starts = [s for s, _ in busy]
        ns += sum(idle(busy, starts, a, b) for a, b in gaps if b > a)
    return 1e-6 * ns / len(devs) / steps
