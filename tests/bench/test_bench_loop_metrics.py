"""The per-layer readers of the engine's loop spans
(``bench/metrics/serving.host_syncs_per_step.py``,
``serving.host_gap_ms.py``, ``runtime.loop_gap_ms.py``,
``serving.first_token_ms.py``) on a hand-made run: ``loop.iter`` and
``request`` spans on the host clock, and a trace whose window the host
window maps onto."""

import pytest

from bench import spec
from bench.run import Run
from repro.obs.spans import Span

T0 = 50.0          # host perf_counter seconds at the traced window's open
US = 1e-6

# one device, times in µs from the window's open: decode [0,20], prefill
# [32,40], decode [45,60], decode [75,90]; the window is [0,100]
OPS = [[0, 20], [32, 8], [45, 15], [75, 15]]
TRACE = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit__decode_paged_fn(1)", 0, 20_000],
                    ["jit__px_fn(2)", 32_000, 8_000],
                    ["jit__decode_paged_fn(1)", 45_000, 15_000],
                    ["jit__decode_paged_fn(1)", 75_000, 15_000]],
        "ops": [[f"op.{i}", s * 1000, d * 1000]
                for i, (s, d) in enumerate(OPS)]}},
    "host": [["bench.trace_window", 0, 100_000]],
}


def span(name, a, b, sid, **attrs):
    return Span(name=name, cat="test", t0=T0 + a * US, t1=T0 + b * US,
                span_id=sid, track="engine", attrs=attrs)


def it(a, b, sid, *, live, decoded, syncs):
    return span("loop.iter", a, b, sid, live=live, decoded=decoded,
                syncs=syncs)


def run_of(spans):
    run = Run(cell={}, shape=None, seconds=1.0)
    run.spans, run.trace = spans, TRACE
    run.trace_window = (T0, T0 + 100 * US)
    return run


def read(name, run):
    return spec.load_reader(name)(run)


# idle inside: [20,25] 5 µs; [28,32] + [40,45] + [60,62] 11; [70,75] +
# [90,92] 7; [96,99] 3.  Between: [25,28] 3 and [62,70] 8 while live,
# [92,96] 4 after a pass that left nothing live.
ITERS = [
    it(-10, -2, 1, live=1, decoded=1, syncs=99),     # before the window
    it(2, 25, 2, live=2, decoded=1, syncs=5),
    it(28, 62, 3, live=1, decoded=1, syncs=6),
    it(70, 92, 4, live=0, decoded=1, syncs=4),
    it(96, 99, 5, live=0, decoded=0, syncs=0),
]


def test_idle_splits_inside_and_between_iterations():
    run = run_of(ITERS)
    steps = 3
    assert read("serving.host_syncs_per_step", run) == (5 + 6 + 4) / steps
    assert read("serving.host_gap_ms", run) == pytest.approx(
        (5 + 11 + 7 + 3) / steps * 1e-3)
    # the gap after a pass with nothing live is not the runtime's
    assert read("runtime.loop_gap_ms", run) == pytest.approx(
        (3 + 8) / steps * 1e-3)


def test_inside_plus_between_is_the_idle_per_step():
    # passes cover the window but for the gaps between them, each left
    # a batch live: every idle ns falls inside or between them
    run = run_of([it(0, 25, 1, live=2, decoded=1, syncs=5),
                  it(28, 62, 2, live=1, decoded=1, syncs=6),
                  it(70, 100, 3, live=1, decoded=1, syncs=4)])
    busy = sum(d for _, d in OPS)
    per_step = (100 - busy) / 3 * 1e-3
    total = read("serving.host_gap_ms", run) + read("runtime.loop_gap_ms",
                                                    run)
    assert total == pytest.approx(per_step)


def test_first_token_is_the_mean_over_requests_begun_in_the_window():
    run = run_of(ITERS + [
        span("request", 10, 60, 10, first_token_t=T0 + 30 * US),
        span("request", 50, 95, 11, first_token_t=T0 + 80 * US),
        span("request", -5, 40, 12, first_token_t=T0 + 20 * US),
        span("request", 20, 30, 13)])                 # no first token
    assert read("serving.first_token_ms", run) == pytest.approx(
        (20 + 30) / 2 * 1e-3)


@pytest.mark.parametrize("name", ["serving.host_syncs_per_step",
                                  "serving.host_gap_ms",
                                  "runtime.loop_gap_ms",
                                  "serving.first_token_ms"])
def test_a_run_without_the_loop_spans_reads_none(name):
    assert read(name, run_of([])) is None
    # spans of a program without the loop spans: a request span on a
    # clock relative to its tracer, with no first token
    old = Span(name="request", t0=0.5, t1=1.5, span_id=1)
    assert read(name, run_of([old])) is None
