"""Kernels (the whole paged decode program, for now): the least time a
step could take on the chip, the larger of its FLOPs over peak FLOP/s
and its least bytes (weights with the LM head, live keys and values read,
new ones written) over peak HBM bandwidth, summed over the traced
window's steps, as a share of the decode programs' device time, in %."""

from bench import trace as tr


def read(run):
    pk = run.peaks
    least = sum(max(run.shape.decode_flops(s.contexts) / pk["bf16_flops"],
                    run.shape.decode_bytes(s.contexts)
                    / pk["hbm_bytes_per_s"])
                for s in (run.steps[k] for k in run.traced_steps()))
    lo, hi = tr.window(run.trace)
    ns = tr.module_ns(run.trace, "_decode_paged_fn", lo, hi)
    if not least or not ns:
        return None
    return 100.0 * least / (ns * 1e-9)
