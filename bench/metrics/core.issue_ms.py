"""Runtime (repro.core): per program, from its start to the last call of
its first stage (the fan-out) reaching the dispatcher; the median over
programs, in ms."""

import statistics


def read(run):
    vals = []
    for p in run.due_programs():
        n = len(p.spec.stages[0])
        if not p.done:
            continue
        ds = sorted(c.dispatched for c in run.calls if c.program is p)
        if len(ds) >= n:
            vals.append((ds[n - 1] - p.start) * 1e3)
    return statistics.median(vals) if vals else None
