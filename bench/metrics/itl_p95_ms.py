"""End to end: the 95th percentile of every gap between consecutive
tokens of one call, over the calls of the programs due in the window,
in ms."""

import statistics


def read(run):
    v = [(b - a) * 1e3 for c in run.window_calls()
         for a, b in zip(c.times, c.times[1:])]
    if len(v) < 2:
        return v[0] if v else None
    return statistics.quantiles(v, n=100, method="inclusive")[94]
