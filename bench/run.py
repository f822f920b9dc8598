"""Run one cell of the chip benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> \
        --trace <0|1>

The cell (``BENCHMARK.json``) names a configuration and a traffic mix;
both are files found by name (``bench/spec.py``), and so is the builder
of the serving stack that the configuration names.  The run:

1. set-up: checks that JAX sees a TPU with the chips the cell asks for
   (otherwise it exits 2 and prints no result), makes the weights from
   the seed on the device, builds the serving stack, fills the prefix
   cache with the mix's shared preambles, and serves one request of
   every prompt length the window will send, so that every program the
   window runs is compiled and nothing compiles inside it;
2. the window: the mix's programs (``bench/programs.py``) run through
   ``repro.core.engine.run_poppy_async`` → ``Dispatcher`` → the
   backends → ``ServingEngine`` on one asyncio loop, each started at its
   due time on the open-loop schedule; programs due in the window are
   then drained;
3. the check: a sample of the finished calls is scored against the
   float32 reference (``bench/reference.py``), and every program's
   result against PopPy's sequential semantics given the same answers;
4. the result: earlier lines say what was measured; the last line of
   standard output is one JSON object (``correct``, ``attempted``,
   ``failed``, ``metrics``, ``device``, and with ``--trace 1``
   ``breakdown``), its last key ``checks`` holding each compared number
   beside its limit, which also end standard error.

With ``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` its per-layer metrics.  Each is read by its own reader,
``bench/metrics/<name>.py``, from the run's record (and with
``--trace 1`` from a profiler trace of part of the window).
"""

from __future__ import annotations

import time

T_PROCESS = time.perf_counter()      # before any heavy import

import argparse  # noqa: E402
import asyncio  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass, field  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for _p in (str(ROOT), str(ROOT / "src")):
    if _p not in sys.path:
        sys.path.insert(0, _p)

from bench import spec, traffic  # noqa: E402
from bench.cost import Shape  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"   # fixed, inside the checkout
DRAIN_S = 60.0                   # how long past the close a due program may take
SAMPLE_TOKENS = 300              # served tokens the reference scores, at least
SAMPLE_MAX = 12                  # calls it scores, at most
WARM_PROGRAMS = 3                # whole programs run in set-up
REHEARSE_TOKENS = 8              # answer length of set-up's programs, at least


class NoChip(RuntimeError):
    pass


class CompileMeter:
    """Compile seconds and persistent-cache hits and misses, from JAX's
    monitoring events (a cache hit's load counts as compile time)."""

    def __init__(self, jax):
        self.seconds, self.compiles, self.hits, self.misses = 0.0, 0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds
            self.compiles += 1

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self) -> dict:
        return {"compile_s": self.seconds, "compiles": self.compiles,
                "cache_hits": self.hits, "cache_misses": self.misses}


def delta(a: dict, b: dict) -> dict:
    return {k: b[k] - a[k] for k in a}


@dataclass
class ProgramRun:
    spec: traffic.Program
    due: float                        # absolute host time
    start: float = 0.0
    end: float = 0.0
    result: object = None
    error: str = ""
    done: bool = False


@dataclass
class Run:
    """Everything a metric reader may read (``bench/metrics/*.py``)."""

    cell: dict
    shape: Shape
    seconds: float
    setup_s: float = 0.0              # process start to the window's open
    t0: float = 0.0                   # window opens (host perf_counter)
    t1: float = 0.0                   # window closes
    programs: list = field(default_factory=list)
    calls: list = field(default_factory=list)       # observe.Call
    steps: list = field(default_factory=list)       # observe.Step
    prefills: list = field(default_factory=list)
    live_after: list = field(default_factory=list)
    counters: dict = field(default_factory=dict)    # engine counter deltas
    spans: list = field(default_factory=list)       # repro.obs spans
    trace: dict | None = None                       # bench.trace.load()
    trace_window: tuple = ()                        # host (start, end)
    trace_dir: str = ""
    peaks: dict | None = None

    # -- shared helpers for readers -------------------------------------------

    def due_programs(self):
        return [p for p in self.programs if p.due < self.t1]

    def window_calls(self):
        """Calls of the programs due in the window."""
        due = {id(p) for p in self.due_programs()}
        return [c for c in self.calls if id(c.program) in due]

    def host_to_trace(self, t: float) -> float:
        lo_host, _ = self.trace_window
        from bench.trace import window
        lo_ns, _ = window(self.trace)
        return lo_ns + (t - lo_host) * 1e9

    def traced_steps(self):
        """Indices of the decode steps inside the traced span."""
        lo, hi = self.trace_window
        return [k for k, s in enumerate(self.steps)
                if lo <= s.t0 and s.t1 <= hi]


# -- set-up --------------------------------------------------------------------


def check_devices(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU found (JAX platform {devs[0].platform!r}); "
                     f"the benchmark runs only on a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell asks for {chips} chips, JAX sees "
                     f"{len(devs)}")
    return devs[:chips]


def use_cache():
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def build(conf: dict, shape: Shape, seed: int, strict: bool):
    import jax

    from bench import weights
    from repro.models import build_model
    cfg = spec.model_config(conf, strict=strict)
    model = build_model(cfg)
    params = jax.block_until_ready(
        weights.program_params(shape, seed, model))
    return model, params


def warm_prompts(sched: list, seed: int, vocab: int) -> list:
    """[(cached head or None, prompts)]: one prompt of every length class
    the window will send, grouped by the app preamble it starts with.
    Each prompt starts with the tokens the window's call will send alike
    in every program of its app (so the prefix cache matches as it will
    then) and continues with random tokens; each length also comes once
    with nothing cached, as a call finds it when the cache has evicted
    its preamble meanwhile."""
    import numpy as np
    rng = np.random.default_rng([seed % (1 << 63), 3])

    def rand(n):
        return [int(t) for t in rng.integers(0, vocab, n)]

    groups, seen = {}, set()
    for p in sched:
        for s, c in traffic.calls(p):
            n = traffic.prompt_length(p, s, c)
            base = list(traffic.head(p, s, c))
            first = traffic.expand(p, s, c)[0] if base else None
            key = tuple(first.tokens) if first is not None else None
            if key is not None and (key, n) not in seen:
                seen.add((key, n))
                groups.setdefault(key, []).append(base + rand(n - len(base)))
            if n not in seen:
                seen.add(n)
                groups.setdefault(None, []).append(rand(n))
    return list(groups.items())


def rehearsal(p: traffic.Program, rand) -> traffic.Program:
    """A program shaped like ``p`` with fresh random tokens (so the window
    finds none of its own prompts cached), each answer as long as a later
    prompt reads of it and no longer (at most ``REHEARSE_TOKENS`` when no
    prompt reads it)."""
    need = {}
    for st in p.stages:
        for call in st:
            for part in call.parts:
                if isinstance(part, traffic.Answer):
                    key = (part.stage, part.call)
                    n = part.n if part.n is not None else math.inf
                    need[key] = max(need.get(key, 0), n)
    stages = []
    for s, st in enumerate(p.stages):
        stages.append([traffic.Call(
            [traffic.Text(tuple(rand(len(x.tokens))), False)
             if isinstance(x, traffic.Text) and not x.shared else x
             for x in call.parts],
            min(call.out, max(REHEARSE_TOKENS, need.get((s, c), 0))))
            for c, call in enumerate(st)])
    return dataclasses.replace(p, stages=stages)


def rehearsed(sched: list) -> list:
    """The programs set-up rehearses: the first ``WARM_PROGRAMS``, and one
    of every shape whose prompts carry an earlier prompt (a conversation's
    history, which the prefix cache finds at lengths no single prompt
    shows)."""
    out, shapes = list(sched[:WARM_PROGRAMS]), set()
    for p in sched[WARM_PROGRAMS:]:
        if not any(isinstance(x, traffic.Prompt)
                   for st in p.stages for call in st for x in call.parts):
            continue
        shape = (p.app, tuple((traffic.prompt_length(p, s, c),
                               p.stages[s][c].out)
                              for s, c in traffic.calls(p)))
        if shape not in shapes:
            shapes.add(shape)
            out.append(p)
    return out


async def warm(engines, sched, seed, vocab, run_program):
    """Serve every prompt length once (two tokens each) on every engine,
    each app's with its preamble freshly cached, then rehearse whole
    programs shaped like the window's (:func:`rehearsed`), then cache
    every preamble again."""
    import numpy as np
    n = 0
    groups = warm_prompts(sched, seed, vocab)
    for engine in engines:
        B = engine.max_slots
        for pre, prompts in groups:
            for i in range(0, len(prompts), B):
                if pre is not None:
                    await engine.warm_prefix(list(pre))
                await asyncio.gather(*(engine.generate(t, max_new_tokens=2)
                                       for t in prompts[i:i + B]))
            n += len(prompts)
    rng = np.random.default_rng([seed % (1 << 63), 5])

    def rand(k):
        return [int(t) for t in rng.integers(0, vocab, k)]

    await asyncio.gather(*(run_program(ProgramRun(rehearsal(p, rand), 0.0))
                           for p in rehearsed(sched)))
    for engine in engines:
        for pre, _ in groups:
            if pre is not None:
                await engine.warm_prefix(list(pre))
    return n


# -- the window ----------------------------------------------------------------


def engine_counts(engines) -> dict:
    """The engines' counters that the readers use, summed."""
    out = dict.fromkeys(("prefill_tokens_reused", "prefill_tokens_computed",
                         "admit_stalls", "page_evicts"), 0)
    for e in engines:
        st = e.stats()
        out["prefill_tokens_reused"] += st["prefill_tokens_reused"]
        out["prefill_tokens_computed"] += st["prefill_tokens_computed"]
        out["admit_stalls"] += st["paged"]["admit_stalls"]
        out["page_evicts"] += st["paged"]["page_evicts"]
    return out


async def serve(run: Run, conf, mix, seed, model, params, devices, *,
                trace: bool, meter, log, root=ROOT, fault=None):
    import jax

    from bench import observe
    from bench.programs import CharTokenizer, call_args
    from repro.core.ai import use_dispatcher
    from repro.core.engine import run_poppy_async
    from repro.obs.spans import tracing

    shape = run.shape
    tok = CharTokenizer(shape.vocab)
    stack = spec.load_builder(conf, root)(model, params, conf, tok, devices)
    dispatcher, engines = stack["dispatcher"], stack["engines"]
    obs = observe.Observer()
    obs.attach_dispatcher(dispatcher)
    for engine in engines:
        obs.attach_engine(engine)
    if fault is not None:
        fault(engine=engines[0], backend=stack["backends"][0],
              dispatcher=dispatcher)
    sched = traffic.schedule(mix, seed, run.seconds, shape.vocab)

    async def run_program(pr: ProgramRun):
        observe.PROGRAM.set(pr)
        pr.start = time.perf_counter()
        fn, args = call_args(pr.spec)
        try:
            pr.result = await run_poppy_async(fn, args, {})
            pr.done = True
        except Exception as e:          # a failed program, not a crash
            pr.error = repr(e)
        pr.end = time.perf_counter()

    with use_dispatcher(dispatcher):
        c0 = meter.snapshot()
        n_warm = await warm(engines, sched, seed, shape.vocab, run_program)
        log("warmup", {"prompts": n_warm, **delta(c0, meter.snapshot())})
        for record in (obs.calls, obs.steps, obs.prefills):
            record.clear()
        live_after = []
        for engine in engines:
            def retire_and_note(engine=engine, retire=engine._retire_finished):
                retire()
                live_after.append(len(engine.active))
            engine._retire_finished = retire_and_note
        counts0 = engine_counts(engines)
        m0 = meter.snapshot()

        compiled = watch_compiles()
        trz_ctx = tracing() if trace else None
        trz = trz_ctx.__enter__() if trz_ctx else None
        t0 = time.perf_counter()
        run.t0, run.t1 = t0, t0 + run.seconds
        tasks, late = [], []
        tracer = asyncio.ensure_future(
            profile(run, engines)) if trace else None
        for p in sched:
            due = t0 + p.due
            now = time.perf_counter()
            if due > now:
                await asyncio.sleep(due - now)
            late.append(time.perf_counter() - due)
            pr = ProgramRun(p, due)
            run.programs.append(pr)
            tasks.append(asyncio.ensure_future(run_program(pr)))
        now = time.perf_counter()
        if run.t1 > now:
            await asyncio.sleep(run.t1 - now)
        m1 = meter.snapshot()
        names = compiled()
        if tracer is not None:
            await tracer
        _, pending = await asyncio.wait(tasks, timeout=DRAIN_S) \
            if tasks else (set(), set())
        for t in pending:
            t.cancel()
        await asyncio.gather(*tasks, return_exceptions=True)
        if trz_ctx:
            run.spans = trz.closed_spans()
            trz_ctx.__exit__(None, None, None)
        for engine in engines:
            await engine.stop()
    for engine in engines:
        jax.block_until_ready(engine.kv_pages)
    if trace:
        jax.profiler.stop_trace()

    run.calls, run.steps, run.prefills = obs.calls, obs.steps, obs.prefills
    run.live_after = live_after
    run.counters = delta(counts0, engine_counts(engines))
    log("window", {"in_window": delta(m0, m1), "compiled": names,
                   "late_s": {"max": max(late, default=0.0),
                              "mean": sum(late) / max(1, len(late))},
                   "programs_started": len(run.programs),
                   "admit_stalls": run.counters["admit_stalls"],
                   "page_evicts": run.counters["page_evicts"]})
    return delta(m0, m1)


def watch_compiles():
    """Collect the name and shapes of every program JAX lowers from now
    on, silently; the returned function stops and returns them (at most
    20)."""
    import logging

    import jax
    names = []

    class Grab(logging.Handler):
        def emit(self, record):
            msg = record.getMessage()
            if msg.startswith("Compiling "):
                names.append(msg[:200])

    logger = logging.getLogger("jax")
    saved = (logger.handlers[:], logger.propagate, logger.level)
    logger.handlers = [Grab()]
    logger.propagate = False
    logger.setLevel(logging.WARNING)
    jax.config.update("jax_log_compiles", True)

    def stop():
        jax.config.update("jax_log_compiles", False)
        logger.handlers, logger.propagate, level = saved
        logger.setLevel(level)
        return names[:20]
    return stop


async def profile(run: Run, engines):
    """Trace the middle of the window, from 20% to 80% of it: at the
    fan-out cells' rates that span always holds an arrival (the longest
    gap of their schedules is shorter) and so a prefill."""
    import jax
    lo = run.t0 + 0.2 * run.seconds
    span = 0.6 * run.seconds
    await asyncio.sleep(max(0.0, lo - time.perf_counter()))
    d = tempfile.mkdtemp(prefix="bench_trace_")
    jax.block_until_ready([e.kv_pages for e in engines])
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    jax.profiler.start_trace(d, profiler_options=opts)
    ann = jax.profiler.TraceAnnotation("bench.trace_window")
    ann.__enter__()
    a = time.perf_counter()
    await asyncio.sleep(span)
    jax.block_until_ready([e.kv_pages for e in engines])
    b = time.perf_counter()
    ann.__exit__(None, None, None)
    run.trace_window = (a, b)
    run.trace_dir = d       # serve() stops the trace once the run drains


# -- checks --------------------------------------------------------------------


def sample_calls(calls: list, seed: int) -> list:
    """Finished calls for the reference: the longest, then others drawn
    from the seed until ``SAMPLE_TOKENS`` served tokens."""
    import numpy as np
    ok = [c for c in calls if c.out and not c.error]
    if not ok:
        return []
    longest = max(ok, key=lambda c: len(c.prompt) + len(c.out))
    rest = [c for c in ok if c is not longest]
    rng = np.random.default_rng([seed % (1 << 63), 4])
    order = rng.permutation(len(rest))
    out, n = [longest], len(longest.out)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX:
            break
        out.append(rest[i])
        n += len(rest[i].out)
    return out


def check_programs(run: Run, vocab: int) -> tuple[int, int]:
    """(programs compared, mismatches) against sequential semantics,
    given the answers the engine served for each prompt."""
    from bench.programs import CharTokenizer, expected
    tok = CharTokenizer(vocab)
    served = {}
    for c in run.calls:
        if c.out:
            served.setdefault(c.prompt, tok.decode(c.out))
    n = bad = 0
    for p in run.programs:
        if not p.done:
            continue
        n += 1
        try:
            want = expected(p.spec, lambda t: served[tuple(tok.encode(t))])
        except KeyError:
            bad += 1
            continue
        bad += want != p.result
    return n, bad


def load_limits(cell: str, root: Path = ROOT) -> dict:
    return json.loads((root / "bench" / "limits" / f"{cell}.json")
                      .read_text())["limits"]


# -- the run -------------------------------------------------------------------


def run_cell(bm: dict, w: dict, seed: int, seconds: float, trace: bool, *,
             root: Path = ROOT, strict: bool = True, fault=None,
             chips_check: bool = True, log=None, limits=None,
             dump_trace: str = "", control: str | None = None,
             mix_override: dict | None = None) -> dict:
    """One run of cell ``w``; returns the result object.  With ``control``
    (``"fp8"``, ``bench/calibrate.py`` and the tests) the reference at
    that lower precision takes the program's place in the logit check:
    the check scores the tokens it puts first, at the same positions of
    the same sampled calls, and the run must come out not correct.
    ``mix_override`` (``bench/sweep.py``) changes parameters of the
    traffic mix."""
    import jax

    log = log or (lambda name, rec: print(json.dumps({"phase": name, **rec}),
                                          flush=True))
    devices = check_devices(w["chips"]) if chips_check \
        else jax.devices()[:w["chips"]]
    dev = devices[0]
    meter = CompileMeter(jax)
    conf = spec.load_config(w["config"], bm, root)
    mix = {**spec.load_traffic(w["traffic"], root), **(mix_override or {})}
    shape = Shape.from_config(conf)
    log("device", {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices), "jax": jax.__version__})
    c0 = meter.snapshot()
    model, params = build(conf, shape, seed, strict)
    log("weights", {"params": sum(x.size for x in jax.tree.leaves(params)),
                    "s": time.perf_counter() - T_PROCESS,
                    **delta(c0, meter.snapshot())})
    run = Run(cell=w, shape=shape, seconds=seconds)
    if trace:
        from bench.peaks import peaks
        run.peaks = peaks(dev.device_kind)
    in_window = asyncio.run(serve(
        run, conf, mix, seed, model, params, devices, trace=trace,
        meter=meter, log=log, root=root, fault=fault))
    mem = [d.memory_stats() or {} for d in devices]
    peak = max((m.get("peak_bytes_in_use", 0) for m in mem), default=0)
    log("memory", {"peak_bytes_in_use": [m.get("peak_bytes_in_use")
                                         for m in mem]})
    run.setup_s = run.t0 - T_PROCESS

    # program state (weights, pool, loaded programs) goes before the
    # reference runs on the same chip
    del params, model
    gc.collect()
    jax.clear_caches()

    due = run.due_programs()
    failed = [p for p in due if not p.done]
    calls = run.window_calls()
    log("calls", {"attempted": len(calls),
                  "succeeded": sum(1 for c in calls if c.out),
                  "failed": sum(1 for c in calls if c.error and not c.out),
                  "programs_attempted": len(due),
                  "programs_failed": len(failed),
                  "errors": sorted({p.error[:300] for p in failed})[:3]})

    from bench.reference import served_gaps
    t_ref, c_ref = time.perf_counter(), meter.snapshot()
    sample = sample_calls([c for c in calls if c.done <= run.t1 + DRAIN_S],
                          seed)
    served = [(c.prompt, c.out) for c in sample]
    gaps = served_gaps(shape, seed, served) if sample else []
    record = {"calls": len(sample), "tokens": len(gaps),
              "logit_gap": float(max(gaps)) if len(gaps) else None}
    if control and sample:
        gaps = served_gaps(shape, seed, served, control=control)
        record["control"] = {control: float(max(gaps))}
    n_prog, bad = check_programs(run, shape.vocab)
    limits = limits or load_limits(w["name"], root)
    checks = {
        "logit_gap": {"value": float(max(gaps)) if len(gaps) else math.inf,
                      "limit": limits["logit_gap"]},
        "programs_wrong": {"value": bad, "limit": 0},
        "programs_failed": {"value": len(failed), "limit": 0},
        "compiles_in_window": {"value": in_window["compiles"]
                               + in_window["cache_hits"], "limit": 0},
    }
    log("reference", {**record, "programs_compared": n_prog,
                      "s": time.perf_counter() - t_ref,
                      **delta(c_ref, meter.snapshot())})
    correct = all(c["value"] <= c["limit"] for c in checks.values()) \
        and len(gaps) > 0 and n_prog > 0

    ends, layers = spec.cell_metrics(bm, w["name"])
    result = {"correct": correct, "attempted": len(due),
              "failed": len(failed)}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        from bench import trace as tr
        xplane = tr.find_xplane(run.trace_dir)
        run.trace = tr.load(xplane)
        if dump_trace:
            Path(dump_trace).parent.mkdir(parents=True, exist_ok=True)
            Path(dump_trace).write_text(json.dumps({
                "structure": tr.structure(xplane), "trace": run.trace,
                "host_window": run.trace_window,
                "steps": [[s.t0, s.t1, s.live] for s in run.steps],
                "live_after": run.live_after}))
        shutil.rmtree(run.trace_dir, ignore_errors=True)
        lo, hi = tr.window(run.trace)
        busy = [tr.busy_ns(d, lo, hi) for d in run.trace["devices"].values()]
        result["breakdown"] = {"device_ops": tr.top_ops(run.trace, lo, hi),
                               "idle_gaps": tr.idle_gaps(run.trace, lo, hi)}
        device.update(busy_s=sum(busy) / max(1, len(busy)) / 1e9,
                      window_s=(hi - lo) / 1e9)
    metrics = {}
    for m in (layers if trace else ends):
        v = spec.load_reader(m["name"], root)(run)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    result["metrics"] = metrics
    result["device"] = device
    if mix_override is not None:
        result["programs"] = {"latency_s": [p.end - p.due for p in due
                                            if p.done]}
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--dump-trace", default="",
                    help="also write the reduced trace and its plane and "
                         "line names to this JSON file")
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("bench: the system under test (src/repro) is not in this "
              "checkout", file=sys.stderr)
        return 2
    bm = spec.load_benchmark()
    w = spec.cell(bm, args.workload)
    faults = spec.validate(bm)
    if faults:
        print("benchmark is not whole: " + "; ".join(faults),
              file=sys.stderr)
        return 2
    try:
        check_devices(w["chips"])
    except NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    use_cache()
    result = run_cell(bm, w, args.seed, args.seconds, bool(args.trace),
                      dump_trace=args.dump_trace)
    for name, c in result["checks"].items():
        print(f"check {name} {c['value']} limit {c['limit']}",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
