"""Serving (repro.serving.engine): the engine's share of the time to
first token.  The mean, over the ``request`` spans begun in the traced
window, of the time from the span's start (the call reaching the engine)
to ``first_token_t``, the host time its first token was sampled, in
ms."""


def read(run):
    if not run.trace_window:
        return None
    lo, hi = run.trace_window
    v = [s.attrs["first_token_t"] - s.t0 for s in run.spans
         if s.name == "request" and lo <= s.t0 < hi
         and s.attrs.get("first_token_t")]
    return 1e3 * sum(v) / len(v) if v else None
