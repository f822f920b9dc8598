"""Model (repro.models): FLOPs of the real prompt tokens prefilled in the
traced window, over the device time of the prefill programs times the
chip's peak, in %."""

from bench import trace as tr


def read(run):
    lo_h, hi_h = run.trace_window
    flops = sum(run.shape.prefill_flops(new, cached)
                for t, new, cached in run.prefills if lo_h <= t < hi_h)
    lo, hi = tr.window(run.trace)
    ns = tr.module_ns(run.trace, "_px_fn", lo, hi)
    if not flops or not ns:
        return None
    return 100.0 * flops / (ns * 1e-9 * run.peaks["bf16_flops"])
