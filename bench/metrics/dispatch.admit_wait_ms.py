"""Dispatch (repro.dispatch): the mean ``admission.wait`` span per call,
in ms (the dispatcher's own span, recorded in the traced run)."""


def read(run):
    waits = [s.dur for s in run.spans if s.name == "admission.wait"]
    return 1e3 * sum(waits) / len(waits) if waits else None
