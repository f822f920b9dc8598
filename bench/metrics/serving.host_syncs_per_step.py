"""Serving (repro.serving.engine): blocking device-to-host reads the
engine's loop makes per decode step.  The sum of the ``syncs`` of the
``loop.iter`` spans that lie in the traced window, over those of them
that decoded (``decoded`` = 1)."""


def read(run):
    if not run.trace_window:
        return None
    lo, hi = run.trace_window
    iters = [s for s in run.spans
             if s.name == "loop.iter" and lo <= s.t0 and s.t1 <= hi]
    steps = sum(s.attrs.get("decoded", 0) for s in iters)
    if not steps:
        return None
    return sum(s.attrs.get("syncs", 0) for s in iters) / steps
