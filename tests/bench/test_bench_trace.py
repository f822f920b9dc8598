"""The trace reduction (bench/trace.py): busy union, idle share, step
gaps, top ops and idle gaps by host span.  A hand-made trace checks the
arithmetic exactly; a slice of a trace recorded on a TPU v5e, checked
in, checks the same on the real layout of planes and names."""

import json
from pathlib import Path

import pytest

from bench import trace as tr

DATA = Path(__file__).resolve().parent / "data"

# one device; ns.  ops: [0,10] [5,20] [30,40] [50,60] -> busy 20+10+10
HAND = {
    "devices": {"/device:TPU:0": {
        "modules": [["jit__decode_paged_fn(1)", 0, 20],
                    ["jit__px_fn(2)", 30, 10],
                    ["jit__decode_paged_fn(1)", 50, 10]],
        "ops": [["fusion.1", 0, 10], ["fusion.2", 5, 15],
                ["dot.3", 30, 10], ["fusion.1", 50, 10]]}},
    "host": [["bench.trace_window", 0, 70], ["bench.decode_step", 0, 22],
             ["bench.admit", 22, 6], ["bench.prefill_step", 28, 14],
             ["bench.decode_step", 42, 20]],
}


def test_union_and_busy():
    dev = HAND["devices"]["/device:TPU:0"]
    assert tr.union(dev["ops"], 0, 70) == [[0, 20], [30, 40], [50, 60]]
    assert tr.busy_ns(dev, 0, 70) == 40
    assert tr.busy_ns(dev, 8, 35) == 12 + 5
    assert tr.window(HAND) == (0.0, 70.0)


def test_step_gaps_count_idle_only_while_live():
    dev = HAND["devices"]["/device:TPU:0"]
    steps = tr.module_events(dev, "_decode_paged_fn", 0, 70)
    assert [s[1] for s in steps] == [0, 50]
    # between the two decode programs: [20, 50] minus the prefill [30, 40]
    assert tr.step_gaps(dev, steps, [1, 0]) == [20]
    assert tr.step_gaps(dev, steps, [0, 0]) == []
    assert tr.module_ns(HAND, "_px_fn", 0, 70) == 10


def test_top_ops_and_idle_gaps():
    assert tr.top_ops(HAND, 0, 70) == [["fusion.1", 20e-9],
                                       ["fusion.2", 15e-9],
                                       ["dot.3", 10e-9]]
    # idle [20,30]: its middle 25 lies in admit [22,28]; [40,50]: 45 in
    # decode_step [42,62]; [60,70]: 65 in no host span
    assert tr.idle_gaps(HAND, 0, 70) == [["bench.admit", 10e-9],
                                         ["bench.decode_step", 10e-9],
                                         ["host: other", 10e-9]]


def recorded():
    path = DATA / "v5e_stablelm_fanout_slice.json"
    if not path.exists():
        pytest.fail(f"missing recorded trace {path}")
    return json.loads(path.read_text())


def test_recorded_v5e_slice_adds_up():
    rec = recorded()
    t = rec["trace"]
    lo, hi = tr.window(t)
    (dev,) = t["devices"].values()
    busy = tr.busy_ns(dev, lo, hi)
    assert 0 < busy <= hi - lo
    gaps = tr.idle_gaps(t, lo, hi, n=100)
    # every idle ns is labelled once: busy + idle is the window
    assert sum(s for _, s in gaps) * 1e9 == pytest.approx(hi - lo - busy)
    steps = tr.module_events(dev, "_decode_paged_fn", lo, hi)
    assert len(steps) >= 2
    live = [1] * len(steps)
    g = tr.step_gaps(dev, steps, live)
    assert len(g) == len(steps) - 1 and all(x >= 0 for x in g)
    ops = tr.top_ops(t, lo, hi)
    assert ops == sorted(ops, key=lambda x: -x[1])
    # the same busy time, counted on a 100 ns timeline when it was recorded
    assert rec["expected"]["busy_ns"] == pytest.approx(busy, rel=1e-3)
    assert rec["expected"]["decode_programs"] == len(steps)
