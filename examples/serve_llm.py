"""End-to-end driver: serve a small JAX model with batched requests.

A PopPy compound-AI program fans out `@unordered` llm() calls; they route
through a `repro.dispatch.Dispatcher` (admission control, result cache +
coalescing, hedged retries) into the LocalEngineBackend, whose requests
share continuous-batching decode steps on a real (reduced-config) model —
PopPy's extracted parallelism becomes decode-batch occupancy on the
engine, and the dispatcher makes the burst production-shaped.

    PYTHONPATH=src:. python examples/serve_llm.py [--arch stablelm-3b]
"""

import argparse
import time

import jax

from repro.configs import get_config
from repro.core import poppy, sequential
from repro.core.ai import llm, use_dispatcher
from repro.dispatch import AdmissionPolicy, Dispatcher, HedgePolicy
from repro.launch.compile_cache import use_compile_cache
from repro.models import build_model
from repro.serving import LocalEngineBackend, ServingEngine


@sequential
def report(line):
    print(line)
    return None


@poppy
def summarize_documents(n_docs):
    summaries = tuple()
    for i in range(n_docs):
        s = llm(f"summarize document {i}", max_tokens=8)
        report(f"doc {i}: {len(s)} chars")
        summaries += (s,)
    overall = llm(f"combine: {summaries}", max_tokens=12)
    report(f"combined: {len(overall)} chars")
    return overall


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--docs", type=int, default=4)
    args = ap.parse_args()

    use_compile_cache()
    cfg = get_config(args.arch).reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg.serve_param_dtype)
    # max_len must cover the longest prompt (the combine call grows with
    # --docs) plus decode room — the engine rejects prompts that don't fit
    engine = ServingEngine(model, params, max_slots=4, max_len=256,
                           prefix_cache_budget=16 << 20, prefill_chunk=64)
    backend = LocalEngineBackend(engine)
    # production dispatch in front of the engine: admit at most max_slots
    # concurrent requests (backpressure instead of queue stampede), cache
    # identical temperature-0 prompts, hedge stragglers
    dispatcher = Dispatcher(
        [backend],
        cache=True,
        admission=AdmissionPolicy(max_concurrency=engine.max_slots),
        hedge=HedgePolicy(delay_s=30.0),
    )
    print(f"serving reduced {args.arch} "
          f"({model.num_params()/1e6:.1f}M params), "
          f"{engine.max_slots} slots\n")

    with use_dispatcher(dispatcher):
        t0 = time.perf_counter()
        summarize_documents(args.docs)
        dt = time.perf_counter() - t0

    print(f"\n{args.docs}+1 LLM calls in {dt:.2f}s — "
          f"{engine.decode_tokens} tokens over {engine.steps} decode steps, "
          f"mean batch occupancy "
          f"{engine.occupancy_sum / max(engine.steps, 1):.2f} "
          f"(max {engine.max_occupancy}): PopPy's parallel calls shared "
          "decode batches")
    es = engine.stats()
    print(f"prefill: {es['prefill_tokens_computed']} tokens computed, "
          f"{es['prefill_tokens_reused']} reused from the radix cache, "
          f"{es['prefill_compilations']} compiled shapes "
          f"(bound {es['prefill_shape_bound']})")
    print(dispatcher.stats.report())


if __name__ == "__main__":
    main()
