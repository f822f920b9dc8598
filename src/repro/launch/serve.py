"""Serving launcher: bring up the continuous-batching engine (or a routed
replica fleet) and run a demo workload of concurrent requests through it.
The model is the reduced config unless ``--full`` asks for the published
widths; parameters are random, made in ``cfg.serve_param_dtype``.

    python -m repro.launch.serve --arch stablelm-3b --requests 8
    python -m repro.launch.serve --replicas 4 --router-policy prefix_affinity
    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        python -m repro.launch.serve --tp 2 --replicas 2
    python -m repro.launch.serve --full      # published widths, on a chip
"""

from __future__ import annotations

import argparse
import asyncio
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-3b")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new-tokens", type=int, default=16)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--full", action="store_true",
                    help="published widths instead of the reduced config")
    ap.add_argument("--tp", type=int, default=1,
                    help="devices per engine (tensor parallelism)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the dispatch router")
    ap.add_argument("--router-policy", default="prefix_affinity",
                    choices=["prefix_affinity", "least_outstanding",
                             "weighted"])
    args = ap.parse_args()

    import jax
    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model
    from repro.serving.fleet import EngineFleet
    from repro.serving.tokenizer import ByteTokenizer

    use_compile_cache()
    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0), cfg.serve_param_dtype)
    fleet = EngineFleet(model, params, replicas=args.replicas, tp=args.tp,
                        policy=args.router_policy, max_slots=args.slots,
                        max_len=128)
    tok = ByteTokenizer(cfg.vocab_size)
    backend = fleet.dispatcher

    async def client(i):
        prompt = f"request {i % max(1, args.requests // 4)}: hello"
        t0 = time.perf_counter()
        out = await backend.generate(prompt,
                                     max_tokens=args.max_new_tokens,
                                     temperature=0.0, stop=None)
        dt = time.perf_counter() - t0
        return i, dt, tok.encode(out)

    async def run():
        results = await asyncio.gather(*[client(i)
                                         for i in range(args.requests)])
        await fleet.stop()
        return results

    t0 = time.perf_counter()
    results = asyncio.run(run())
    wall = time.perf_counter() - t0
    for i, dt, out in results:
        print(f"req {i}: {dt*1e3:7.1f} ms  {len(out)} tokens")
    steps = sum(e.steps for e in fleet.engines)
    toks = sum(e.decode_tokens for e in fleet.engines)
    print(f"\n{args.requests} requests in {wall:.2f}s over "
          f"{args.replicas} replica(s) (tp={args.tp}); "
          f"{toks} decode tokens over {steps} steps")
    print(fleet.stats.report())


if __name__ == "__main__":
    main()
