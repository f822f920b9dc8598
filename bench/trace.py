"""Reduce a profiler trace to device busy and idle time, step gaps, time
per program, the top device ops, and idle gaps labelled by what the host
was doing.

:func:`load` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
keeps only what the reduction needs, as plain lists (``[name, start_ns,
duration_ns]``), so that a small recorded trace can be checked in and
the reduction tested without JAX or a chip.  All times are nanoseconds on
the trace's own clock; host annotations (``bench.*``) share it.
"""

from __future__ import annotations

import glob
import os
from collections import defaultdict

DEVICE_PREFIX = "/device:TPU:"
MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"
HOST_PREFIX = "bench."
WINDOW = "bench.trace_window"


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {paths}")
    return paths[0]


def load(path: str) -> dict:
    """{"devices": {plane: {"modules": [...], "ops": [...]}}, "host":
    [...]}, events as [name, start_ns, duration_ns]."""
    from jax.profiler import ProfileData

    out = {"devices": {}, "host": []}
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(DEVICE_PREFIX):
            dev = {"modules": [], "ops": []}
            for line in plane.lines:
                key = {MODULE_LINE: "modules", OP_LINE: "ops"}.get(line.name)
                if key:
                    dev[key] += [[e.name, e.start_ns, e.duration_ns]
                                 for e in line.events]
            dev["ops"] = name_ops(dev["modules"], dev["ops"])
            out["devices"][plane.name] = dev
        else:
            for line in plane.lines:
                out["host"] += [[e.name, e.start_ns, e.duration_ns]
                                for e in line.events
                                if e.name.startswith(HOST_PREFIX)]
    return out


def name_ops(modules: list, ops: list) -> list:
    """Short op names: ``<program>/<op>`` (``jit__decode_paged_fn/
    %while.14``), the program being the module running when the op
    starts, and the op its HLO name without the instruction text."""
    import bisect
    mods = sorted(modules, key=lambda e: e[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, d in ops:
        i = bisect.bisect_right(starts, s) - 1
        prog = mods[i][0].split("(")[0] \
            if i >= 0 and s < mods[i][1] + mods[i][2] else "?"
        out.append([f"{prog}/{name.split(' = ')[0]}", s, d])
    return out


def structure(path: str) -> list:
    """Plane and line names with event counts: what to look at before
    trusting the reduction on a new device or JAX version."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            evs = list(line.events)
            rows.append([plane.name, line.name, len(evs),
                         [e.name for e in evs[:3]]])
    return rows


def window(tr: dict) -> tuple[float, float]:
    """(start, end) ns of the harness's ``bench.trace_window`` span."""
    for name, t, d in tr["host"]:
        if name == WINDOW:
            return float(t), float(t + d)
    raise ValueError("the trace holds no bench.trace_window span")


def union(intervals, lo: float, hi: float) -> list:
    """Merged [start, end] intervals, clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in intervals
                if s < hi and s + d > lo)
    out = []
    for s, e in iv:
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return out


def busy_ns(dev: dict, lo: float, hi: float) -> float:
    return sum(e - s for s, e in union(dev["ops"], lo, hi))


def module_events(dev: dict, pattern: str, lo: float, hi: float) -> list:
    """Executions of programs whose name holds ``pattern``, starting in
    the window, in time order."""
    return sorted((e for e in dev["modules"]
                   if pattern in e[0] and lo <= e[1] < hi),
                  key=lambda e: e[1])


def idle_between(dev: dict, a: float, b: float) -> float:
    """ns in [a, b] with no op running on the device."""
    return (b - a) - busy_ns(dev, a, b) if b > a else 0.0


def step_gaps(dev: dict, steps: list, live_after: list) -> list:
    """Idle ns between consecutive decode programs ``steps`` (module
    events), for the pairs across which the batch stayed live
    (``live_after[k]``: calls still live after step k)."""
    return [idle_between(dev, steps[k][1] + steps[k][2], steps[k + 1][1])
            for k in range(len(steps) - 1) if live_after[k]]


def top_ops(tr: dict, lo: float, hi: float, n: int = 10) -> list:
    """[name, seconds] of the device ops that took most time, summed over
    devices (averaged per device)."""
    tot = defaultdict(float)
    devs = tr["devices"].values()
    for dev in devs:
        for name, s, d in dev["ops"]:
            if lo <= s < hi:
                tot[name] += d
    k = max(1, len(devs))
    return [[name, ns / k / 1e9] for name, ns in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


def idle_gaps(tr: dict, lo: float, hi: float, n: int = 10) -> list:
    """[label, seconds]: device idle time in the window, by the innermost
    host ``bench.*`` span open at each gap's middle ("host: other" where
    none is), largest first; averaged over devices."""
    host = [h for h in tr["host"] if h[0] != WINDOW]
    tot = defaultdict(float)
    devs = list(tr["devices"].values())
    for dev in devs:
        busy = union(dev["ops"], lo, hi)
        edges = [lo] + [x for iv in busy for x in iv] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            if b <= a:
                continue
            mid = (a + b) / 2
            open_ = [h for h in host if h[1] <= mid < h[1] + h[2]]
            label = min(open_, key=lambda h: h[2])[0] if open_ \
                else "host: other"
            tot[label] += b - a
    k = max(1, len(devs))
    return [[name, ns / k / 1e9] for name, ns in
            sorted(tot.items(), key=lambda x: -x[1])[:n]]


def module_ns(tr: dict, pattern: str, lo: float, hi: float) -> float:
    """Device ns of the programs whose name holds ``pattern``, started in
    the window, averaged over devices (0 without a device)."""
    devs = list(tr["devices"].values())
    if not devs:
        return 0.0
    return sum(d for dev in devs for _, _, d in
               module_events(dev, pattern, lo, hi)) / len(devs)
