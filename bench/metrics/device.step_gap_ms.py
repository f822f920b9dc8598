"""Device: mean time with no op on the device between consecutive decode
programs while a batch stays live, in ms, over the traced window.  Each
host decode step is matched to the decode program that overlaps it most
(the host waits for that program before the step returns; the device's
clock may run a millisecond or so apart from the host's)."""

from bench import trace as tr


def overlap(e, a, b):
    return min(e[1] + e[2], b) - max(e[1], a)


def read(run):
    lo, hi = tr.window(run.trace)
    gaps = []
    for dev in run.trace["devices"].values():
        mods = tr.module_events(dev, "_decode_paged_fn", lo, hi)
        matched, live = [], []
        for k in run.traced_steps():
            s = run.steps[k]
            a, b = run.host_to_trace(s.t0), run.host_to_trace(s.t1)
            best = max(mods, key=lambda e: overlap(e, a, b), default=None)
            if best is not None and overlap(best, a, b) > best[2] / 2:
                matched.append(best)
                live.append(run.live_after[k])
            elif matched:             # a step with no program: break pairs
                live[-1] = 0
        gaps += tr.step_gaps(dev, matched, live)
    return 1e-6 * sum(gaps) / len(gaps) if gaps else None
