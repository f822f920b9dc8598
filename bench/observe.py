"""The harness's own view of a run: when each call reached the
dispatcher, when each of its tokens was sampled, and what each decode
step and prefill chunk carried.

The serving engine publishes no first-token or per-token time (its
``Request`` has only submitted, started and finished times).  So the
observer wraps, on the engine instance and from the benchmark's files,
the method that samples a call's first token (``_begin_decode``), the
decode step that samples one token for every live call
(``_decode_once``), and the prefill of one chunk (``_run_prefill``).  If
one of them is gone, :meth:`Observer.attach_engine` raises: the harness
does not guess.  Each wrapped method also runs inside a
``jax.profiler.TraceAnnotation``, so a traced run can say what the host
was doing in each idle gap of the device.
"""

from __future__ import annotations

import contextvars
import time
from dataclasses import dataclass, field

import jax

PROGRAM: contextvars.ContextVar = contextvars.ContextVar("bench_program",
                                                         default=None)
_CALL: contextvars.ContextVar = contextvars.ContextVar("bench_call",
                                                       default=None)

ENGINE_HOOKS = ("_begin_decode", "_decode_once", "_run_prefill",
                "_prefill_step", "_drain_queue", "_retire_finished",
                "generate")


@dataclass
class Call:
    """One ``llm()`` call as it passed the dispatcher."""

    program: object
    dispatched: float
    done: float = 0.0
    prompt: tuple = ()
    out: list = field(default_factory=list)
    times: list = field(default_factory=list)   # host time of each token
    error: str = ""


@dataclass
class Step:
    t0: float
    t1: float
    live: int
    contexts: list          # positions each live call attends


class Observer:
    def __init__(self, clock=time.perf_counter):
        self.clock = clock
        self.calls: list[Call] = []
        self.steps: list[Step] = []
        self.prefills: list = []       # (t, new tokens, cached tokens)
        self._times: dict = {}         # id(req) -> (req, its token times)

    # -- dispatcher -----------------------------------------------------------

    def attach_dispatcher(self, dispatcher):
        orig = dispatcher.generate

        async def generate(prompt, **kw):
            call = Call(PROGRAM.get(), self.clock())
            self.calls.append(call)
            token = _CALL.set(call)
            try:
                return await orig(prompt, **kw)
            except BaseException as e:
                call.error = repr(e)
                raise
            finally:
                _CALL.reset(token)
                call.done = self.clock()

        dispatcher.generate = generate

    # -- engine ---------------------------------------------------------------

    def attach_engine(self, engine):
        missing = [h for h in ENGINE_HOOKS if not hasattr(engine, h)]
        if missing:
            raise RuntimeError(
                f"the serving engine has no {missing}: the benchmark reads "
                f"first-token and per-token times there and will not guess")
        clock, times = self.clock, self._times

        def annotated(method, label):
            def run(*a, **kw):
                with jax.profiler.TraceAnnotation(label):
                    return method(*a, **kw)
            return run

        begin = engine._begin_decode

        def begin_decode(req, slot, logits):
            with jax.profiler.TraceAnnotation("bench.first_token"):
                begin(req, slot, logits)
            times[id(req)] = (req, [clock()])

        decode = engine._decode_once

        def decode_once():
            live = list(engine.active.values())
            ctx = [len(r.prompt_tokens) + len(r.out_tokens) for r in live]
            t0 = clock()
            with jax.profiler.TraceAnnotation("bench.decode_step"):
                decode()
            t1 = clock()
            for r in live:
                times[id(r)][1].append(t1)
            self.steps.append(Step(t0, t1, len(live), ctx))

        run_prefill = engine._run_prefill

        def prefill(seg, prefix_kv, prefix_len, prefix_key=()):
            self.prefills.append((clock(), len(seg), int(prefix_len)))
            with jax.profiler.TraceAnnotation("bench.prefill_enqueue"):
                return run_prefill(seg, prefix_kv, prefix_len, prefix_key)

        generate = engine.generate

        async def engine_generate(prompt_tokens, **kw):
            out = await generate(prompt_tokens, **kw)
            call = _CALL.get()
            entry = next((v for v in times.values() if v[0].out_tokens is out),
                         None)
            if call is not None:
                call.prompt = tuple(prompt_tokens)
                call.out = list(out)
                call.times = entry[1] if entry else []
            if entry is not None:
                del times[id(entry[0])]
            return out

        engine._begin_decode = begin_decode
        engine._decode_once = decode_once
        engine._run_prefill = prefill
        engine._prefill_step = annotated(engine._prefill_step,
                                         "bench.prefill_step")
        engine._drain_queue = annotated(engine._drain_queue, "bench.admit")
        engine._retire_finished = annotated(engine._retire_finished,
                                            "bench.retire")
        engine.generate = engine_generate
