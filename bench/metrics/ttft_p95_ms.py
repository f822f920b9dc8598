"""End to end: the 95th percentile, over every ``llm()`` call of the
programs due in the window, of the time from the call reaching the
dispatcher to its first token, in ms."""

import statistics


def read(run):
    v = [(c.times[0] - c.dispatched) * 1e3 for c in run.window_calls()
         if c.times]
    if len(v) < 2:
        return v[0] if v else None
    return statistics.quantiles(v, n=100, method="inclusive")[94]
