"""Serving (repro.serving.engine): prompt tokens served from the prefix
cache, as a share of all prompt tokens prefilled in the window, in %."""


def read(run):
    reused = run.counters["prefill_tokens_reused"]
    total = reused + run.counters["prefill_tokens_computed"]
    return 100.0 * reused / total if total else None
