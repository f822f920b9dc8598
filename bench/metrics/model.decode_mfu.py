"""Model (repro.models): FLOPs of the live tokens decoded in the traced
window, over the device time of the decode programs times the chip's
peak, in %."""

from bench import trace as tr


def read(run):
    steps = [run.steps[k] for k in run.traced_steps()]
    flops = sum(run.shape.decode_flops(s.contexts) for s in steps)
    lo, hi = tr.window(run.trace)
    ns = tr.module_ns(run.trace, "_decode_paged_fn", lo, hi)
    if not flops or not ns:
        return None
    return 100.0 * flops / (ns * 1e-9 * run.peaks["bf16_flops"])
