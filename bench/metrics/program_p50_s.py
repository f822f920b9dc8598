"""End to end: the median latency of the programs due in the window, in
s, from each one's due time on the open-loop schedule to its return
(programs are drained after the close; one that never returns is a
failed program and has no latency)."""

import statistics


def read(run):
    lat = [p.end - p.due for p in run.due_programs() if p.done]
    return statistics.median(lat) if lat else None
