"""Smoke test on the chip: serve stablelm-3b at its published widths.

    python chip_smoke.py [--seed N]             # one TPU chip
    python chip_smoke.py --chips 4 [--seed N]   # four chips of one host

On one chip it runs three phases, all in this one process (a chip serves
one process at a time):

* ``kernels`` — the paged decode, flash prefill and decode attention
  kernels at stablelm-3b widths, compiled for the chip (the program must
  hold a Mosaic kernel: no interpret mode), each against its pure-jnp
  oracle at the bf16 tolerances of ``tests/test_kernels.py``.
* ``serve`` — a ``@poppy`` fan-out program (8 ``llm()`` calls sharing a
  ~300-token preamble, then a combine call; greedy) through
  ``Dispatcher`` → ``LocalEngineBackend`` → ``ServingEngine`` (paged KV)
  → the model, with random bf16 weights made from ``--seed``.  Every call
  must come back with its full token count.  For one prompt, the engine's
  first-token logits and the logits of one decode step (read through the
  engine's page table, over prefix pages it shares) must agree with a
  direct ``Model.prefill`` within ``LOGITS_TOL``.
* ``pallas`` — the same program through an engine built with
  ``attention_impl="pallas"``.  Its first-token logits must agree with
  the XLA engine's, and its decode-step logits (the paged kernel over
  the engine's page table) with the direct prefill, within
  ``LOGITS_TOL``.  Where its tokens first differ from the XLA engine's is
  printed, not gated: random weights have near-ties.

``--chips 4`` runs only the four-chip paths, each with what it is
compared with:

* ``fleet`` — ``EngineFleet(replicas=4, tp=1)``, each replica's params
  and KV pool on its own chip, must give the one-replica fleet's tokens.
* ``tp`` — a tp=4 engine against the tp=1 engine: first-token logits
  within ``LOGITS_TOL``, and where the tokens first diverge (printed).

Each phase prints one JSON line (compile and run seconds, persistent-cache
hits, peak device bytes, what it checked).  The last line is
``{"ok": true, "device": {...}}`` as JAX reports the device.  The script
exits non-zero without that line when JAX finds no TPU, the repository's
``src/`` is missing, or any phase fails.  Compiled programs go to JAX's
persistent cache (``JAX_COMPILATION_CACHE_DIR``, else ``.jax_cache/`` in
the checkout), so a second run on the same machine loads them.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.core import poppy  # noqa: E402
from repro.core.ai import llm  # noqa: E402
from repro.serving.tokenizer import ByteTokenizer  # noqa: E402

ARCH = "stablelm-3b"
ENGINE = dict(max_slots=8, max_len=1024, page_size=16,
              prefix_cache_budget=256 << 20)
DOCS, DOC_TOKENS, COMBINE_TOKENS = 8, 8, 16
PREAMBLE_CHARS = 300
# first-token logits of two bf16 programs computing the same function
# (bucket-padded vs exact-length prefill; tp=4 vs tp=1): the rounding of
# 32 bf16 layers, bounded relative to the logits' own range
LOGITS_TOL = 0.05
KERNEL_TOL = dict(rtol=2e-2, atol=2e-2)   # tests/test_kernels.py, bf16
WORDS = ("market", "report", "quarter", "growth", "supply", "chain",
         "energy", "policy", "risk", "model", "forecast", "revenue",
         "customer", "network", "storage", "latency", "region", "demand",
         "audit", "contract", "budget", "pricing", "vendor", "launch")


@poppy
def summarize_documents(preamble, docs, doc_tokens, combine_tokens):
    summaries = tuple()
    for i in range(len(docs)):
        s = llm(f"{preamble}\nSummarize document {i}: {docs[i]}",
                max_tokens=doc_tokens)
        summaries += (s,)
    overall = llm(f"{preamble}\nCombine the summaries: {summaries}",
                  max_tokens=combine_tokens)
    return summaries + (overall,)


class IdTokenizer(ByteTokenizer):
    """Byte-level prompts; completions written out as their token ids.

    Random weights mostly emit ids past the byte range, which
    ``ByteTokenizer.decode`` drops; this keeps every generated token in
    the program's strings, so counts and divergences are read there."""

    def decode(self, ids) -> str:
        return " ".join(str(int(i)) for i in ids)


def make_prompts(seed):
    import numpy as np
    rng = np.random.default_rng(seed)
    words = ["You", "summarize", "business", "documents."]
    while len(" ".join(words)) < PREAMBLE_CHARS:
        words.append(str(rng.choice(WORDS)))
    preamble = " ".join(words)[:PREAMBLE_CHARS]
    docs = tuple(" ".join(rng.choice(WORDS, 6)) for _ in range(DOCS))
    return preamble, docs


def first_divergence(a, b):
    """Index of the first differing token of two id strings, None if
    equal."""
    a, b = a.split(), b.split()
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            return i
    return None if len(a) == len(b) else min(len(a), len(b))


class CompileMeter:
    """Compile seconds and persistent-cache hits/misses, read from JAX's
    monitoring events (a cache hit's load time counts as compile time)."""

    def __init__(self, jax):
        self.seconds, self.hits, self.misses = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, seconds, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.seconds += seconds

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            self.hits += 1
        elif event == "/jax/compilation_cache/cache_misses":
            self.misses += 1

    def snapshot(self):
        return self.seconds, self.hits, self.misses


def run_phase(name, fn, meter, devices):
    c0, h0, m0 = meter.snapshot()
    t0 = time.perf_counter()
    record = fn()
    wall = time.perf_counter() - t0
    c1, h1, m1 = meter.snapshot()
    gc.collect()   # engines hold their KV pools through reference cycles
    # per device: the peak since the process started, and what the phase
    # left behind
    mem = [d.memory_stats() or {} for d in devices]
    print(json.dumps({
        "phase": name, "wall_s": wall, "compile_s": c1 - c0,
        "run_s": wall - (c1 - c0), "cache_hits": h1 - h0,
        "cache_misses": m1 - m0,
        "peak_bytes_in_use": [m.get("peak_bytes_in_use") for m in mem],
        "bytes_in_use": [m.get("bytes_in_use") for m in mem],
        **record}), flush=True)


# -- phases -------------------------------------------------------------------


def kernel_phase(cfg, seed):
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.decode_attention import ops as da_ops
    from repro.kernels.decode_attention.ref import decode_attention_ref
    from repro.kernels.flash_attention import ops as fa_ops
    from repro.kernels.flash_attention.ref import attention_ref
    from repro.kernels.paged_attention import ops as pa_ops
    from repro.kernels.paged_attention.ref import paged_decode_attention_ref

    H, KVH, d = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    B, C, ps = ENGINE["max_slots"], ENGINE["max_len"], ENGINE["page_size"]
    N, bf16 = C // ps, jnp.bfloat16
    P = B * N + 1
    ks = jax.random.split(jax.random.PRNGKey(seed), 8)
    lengths = jnp.asarray([C, C - 24, 700, 513, 300, 17, 16, 1][:B],
                          jnp.int32)
    table = jax.random.permutation(ks[0], jnp.arange(1, P)).reshape(B, N)
    q1 = jax.random.normal(ks[1], (B, 1, H, d), bf16)
    pages = [jax.random.normal(k, (P, ps, KVH, d), bf16) for k in ks[2:4]]
    cache = [jax.random.normal(k, (B, C, KVH, d), bf16) for k in ks[4:6]]
    S = 512
    qs = jax.random.normal(ks[6], (1, S, H, d), bf16)
    kvs = [jax.random.normal(k, (1, S, KVH, d), bf16)
           for k in jax.random.split(ks[7])]

    def prefix_ref(q, k, v, ln):
        return decode_attention_ref(q, k, v,
                                    jnp.arange(C)[None, :] < ln[:, None])

    cases = {
        "paged_decode": (pa_ops.paged_decode_attention,
                         paged_decode_attention_ref,
                         (q1, *pages, table, lengths)),
        "flash_prefill": (fa_ops.flash_attention,
                          lambda q, k, v: attention_ref(q, k, v,
                                                        causal=True),
                          (qs, *kvs)),
        "decode": (da_ops.decode_attention, prefix_ref,
                   (q1, *cache, lengths)),
    }
    out = {"heads": H, "kv_heads": KVH, "head_dim": d}
    for name, (kernel, ref, args) in cases.items():
        compiled = jax.jit(kernel).lower(*args).compile()
        if "tpu_custom_call" not in compiled.as_text():
            raise AssertionError(f"{name}: no Mosaic kernel in the program")
        got = np.asarray(compiled(*args), np.float32)
        want = np.asarray(jax.jit(ref)(*args), np.float32)
        out[f"{name}_max_abs_err"] = float(np.max(np.abs(got - want)))
        np.testing.assert_allclose(got, want, err_msg=name, **KERNEL_TOL)
    return out


def engine_dispatcher(engine, tok):
    """A dispatcher in front of one engine, admitting up to its slots."""
    from repro.dispatch import AdmissionPolicy, Dispatcher
    from repro.serving import LocalEngineBackend
    return Dispatcher(
        [LocalEngineBackend(engine, tok)],
        admission=AdmissionPolicy(max_concurrency=engine.max_slots))


def serve_program(dispatcher, prompts):
    """Run the fan-out program through ``dispatcher``; return its nine
    completions (8 documents, then the combine)."""
    from repro.core.ai import use_dispatcher

    preamble, docs = prompts
    with use_dispatcher(dispatcher):
        outs = summarize_documents(preamble, docs, DOC_TOKENS,
                                   COMBINE_TOKENS)
    want = [DOC_TOKENS] * DOCS + [COMBINE_TOKENS]
    got = [len(o.split()) for o in outs]
    if got != want:
        raise AssertionError(f"token counts {got}, expected {want}")
    return list(outs)


def logits_error(got, want, vocab):
    """max |got - want| over the first ``vocab`` logits, relative to their
    range in ``want`` (the padded vocabulary columns hold -1e30)."""
    import numpy as np
    got = np.asarray(got, np.float32)[..., :vocab]
    want = np.asarray(want, np.float32)[..., :vocab]
    return float(np.max(np.abs(got - want))
                 / max(float(np.max(np.abs(want))), 1e-6))


def direct_logits(model, params, tokens, state):
    """Logits [1, V] for the token after ``tokens``, from a direct
    ``Model.prefill`` of the XLA model (one jitted program, kept in
    ``state``)."""
    import jax
    import jax.numpy as jnp
    if "prefill" not in state:
        state["prefill"] = jax.jit(lambda p, t: model.prefill(
            p, {"tokens": t}, capacity=t.shape[1])[0])
    return state["prefill"](params, jnp.asarray([tokens], jnp.int32))


def decode_logits_error(engine, model, params, prompt, state):
    """Serve ``prompt`` for two tokens after the program has filled the
    prefix cache, and compare the one decode step's logits with a direct
    prefill of the same tokens.  That step reads its attention through the
    engine's own page table, over preamble pages the prefix cache shares."""
    import asyncio

    import numpy as np
    if not engine.paged_kv:
        raise AssertionError("the engine did not take the paged KV layout")
    step = engine._decode_paged
    seen = []

    def recording_step(p, pools, toks, pos, table):
        seqs = {slot: req.prompt_tokens + req.out_tokens
                for slot, req in engine.active.items()}
        logits, pools = step(p, pools, toks, pos, table)
        seen.append((logits, seqs))
        return logits, pools

    engine._decode_paged = recording_step
    try:
        out = asyncio.run(engine.generate(prompt, max_new_tokens=2))
    finally:
        engine._decode_paged = step
    if len(seen) != 1 or len(seen[0][1]) != 1:
        raise AssertionError(f"expected one decode step for one request, "
                             f"saw {[sorted(s) for _, s in seen]}")
    (logits, seqs), = seen
    (slot, seq), = seqs.items()
    got = np.asarray(logits, np.float32).reshape(engine.max_slots, -1)
    got = got[slot:slot + 1]
    if int(np.argmax(got[0, :model.cfg.vocab_size])) != out[1]:
        raise AssertionError("decode logits argmax is not the served token")
    return logits_error(got, direct_logits(model, params, seq, state),
                        model.cfg.vocab_size)


def serve_phase(model, params, prompts, state):
    import jax.numpy as jnp
    from repro.serving import ServingEngine

    cfg = model.cfg
    tok = IdTokenizer(cfg.vocab_size)
    engine = ServingEngine(model, params, **ENGINE)
    outs = serve_program(engine_dispatcher(engine, tok), prompts)
    state["xla_outputs"] = outs

    preamble, docs = prompts
    prompt = tok.encode(f"{preamble}\nSummarize document 0: {docs[0]}")
    got = engine.prompt_logits(prompt)
    err = logits_error(got, direct_logits(model, params, prompt, state),
                       cfg.vocab_size)
    first = int(jnp.argmax(got[0]))
    served_first = int(outs[0].split()[0])
    if first != served_first:
        raise AssertionError(f"engine prompt logits argmax {first} is not "
                             f"the served first token {served_first}")
    if not err <= LOGITS_TOL:
        raise AssertionError(f"engine vs direct prefill logits: relative "
                             f"max error {err} > {LOGITS_TOL}")
    state["xla_logits"] = got
    stats = engine.stats()
    dec_err = decode_logits_error(engine, model, params, prompt, state)
    if not dec_err <= LOGITS_TOL:
        raise AssertionError(f"engine decode step vs direct prefill logits: "
                             f"relative max error {dec_err} > {LOGITS_TOL}")
    return {"requests": len(outs),
            "tokens": sum(len(o.split()) for o in outs),
            "prompt_tokens": len(prompt),
            "logits_rel_err_vs_prefill": err,
            "decode_logits_rel_err_vs_prefill": dec_err,
            "logits_tol": LOGITS_TOL,
            "decode_steps": stats["steps"],
            "prefill_tokens_computed": stats["prefill_tokens_computed"],
            "prefill_tokens_reused": stats["prefill_tokens_reused"],
            "prefill_shapes": sorted(engine.prefill_shapes)}


def pallas_phase(model, params, prompts, state):
    from repro.models import build_model
    from repro.serving import ServingEngine

    cfg = model.cfg.replace(attention_impl="pallas")
    tok = IdTokenizer(cfg.vocab_size)
    engine = ServingEngine(build_model(cfg), params, **ENGINE)
    preamble, docs = prompts
    prompt = tok.encode(f"{preamble}\nSummarize document 0: {docs[0]}")
    err = logits_error(engine.prompt_logits(prompt), state["xla_logits"],
                       cfg.vocab_size)
    if not err <= LOGITS_TOL:
        raise AssertionError(f"pallas vs xla engine logits: relative max "
                             f"error {err} > {LOGITS_TOL}")
    outs = serve_program(engine_dispatcher(engine, tok), prompts)
    # the paged kernel, through the engine's page tables, against the
    # XLA model's direct prefill
    dec_err = decode_logits_error(engine, model, params, prompt, state)
    if not dec_err <= LOGITS_TOL:
        raise AssertionError(f"pallas engine decode step vs direct prefill "
                             f"logits: relative max error {dec_err} > "
                             f"{LOGITS_TOL}")
    return {"requests": len(outs),
            "logits_rel_err_vs_xla": err,
            "decode_logits_rel_err_vs_prefill": dec_err,
            "logits_tol": LOGITS_TOL,
            "first_divergence_vs_xla": [
                first_divergence(a, b)
                for a, b in zip(outs, state["xla_outputs"])]}


def fleet_phase(model, params, prompts):
    import jax
    from repro.serving import EngineFleet

    tok = IdTokenizer(model.cfg.vocab_size)

    def serve(replicas):
        fleet = EngineFleet(model, params, replicas=replicas, tp=1,
                            tokenizer=tok, **ENGINE)
        placed = [sorted({d.id for x in jax.tree.leaves((e.params,
                                                         e.kv_pages))
                          for d in x.devices()})
                  for e in fleet.engines]
        outs = serve_program(fleet.dispatcher, prompts)
        routed = {name: b.get("routed")
                  for name, b in fleet.stats.snapshot()["backends"].items()}
        return outs, placed, routed

    one, _, _ = serve(1)
    gc.collect()   # free the one-replica fleet's KV pool on device 0
    four, placed, routed = serve(4)
    want = [[d.id] for d in jax.devices()[:4]]
    if placed != want:
        raise AssertionError(f"replica devices {placed}, expected {want}")
    diverged = [first_divergence(a, b) for a, b in zip(four, one)]
    if any(i is not None for i in diverged):
        raise AssertionError(f"4-replica fleet tokens differ from the "
                             f"1-replica fleet at {diverged}")
    return {"replica_devices": placed, "routed": routed,
            "requests": len(four)}


def tp_phase(model, params, prompts):
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import ServingEngine

    tok = IdTokenizer(model.cfg.vocab_size)
    preamble, docs = prompts
    prompt = tok.encode(f"{preamble}\nSummarize document 0: {docs[0]}")
    results = {}
    for tp in (1, 4):
        mesh = make_serving_mesh(tp) if tp > 1 else None
        engine = ServingEngine(model, params, mesh=mesh, **ENGINE)
        logits = engine.prompt_logits(prompt)
        results[tp] = (logits, serve_program(
            engine_dispatcher(engine, tok), prompts))
        del engine
        gc.collect()
    err = logits_error(results[4][0], results[1][0], model.cfg.vocab_size)
    if not err <= LOGITS_TOL:
        raise AssertionError(f"tp=4 vs tp=1 logits: relative max error "
                             f"{err} > {LOGITS_TOL}")
    return {"logits_rel_err_tp4_vs_tp1": err, "logits_tol": LOGITS_TOL,
            "first_divergence_tp4_vs_tp1": [
                first_divergence(a, b)
                for a, b in zip(results[4][1], results[1][1])]}


# -- driver -------------------------------------------------------------------


def main():
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: only the fleet and tensor-parallel phases")
    args = ap.parse_args()

    import jax
    devices = jax.devices()
    dev = devices[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX platform {dev.platform!r}); "
              "this check runs only on a TPU", file=sys.stderr)
        return 2
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} but JAX sees "
              f"{len(devices)} device(s)", file=sys.stderr)
        return 2

    from repro.configs import get_config
    from repro.launch.compile_cache import use_compile_cache
    from repro.models import build_model

    cache_dir = use_compile_cache()
    meter = CompileMeter(jax)
    cfg = get_config(ARCH)          # published widths
    model = build_model(cfg)
    print(json.dumps({
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(devices)},
        "jax": jax.__version__, "compile_cache": cache_dir,
        "config": {"name": cfg.name, "layers": cfg.num_layers,
                   "d_model": cfg.d_model, "heads": cfg.num_heads,
                   "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
                   "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
                   "param_dtype": cfg.serve_param_dtype},
        "engine": ENGINE}), flush=True)

    state = {}

    def init():
        state["params"] = jax.block_until_ready(model.init(
            jax.random.PRNGKey(args.seed), cfg.serve_param_dtype))
        leaves = jax.tree.leaves(state["params"])
        return {"params": sum(x.size for x in leaves),
                "param_bytes": sum(x.nbytes for x in leaves)}

    prompts = make_prompts(args.seed)
    used = devices[:args.chips]
    run_phase("init", init, meter, used)
    params = state["params"]
    if args.chips == 1:
        run_phase("kernels", lambda: kernel_phase(cfg, args.seed), meter,
                  used)
        run_phase("serve", lambda: serve_phase(model, params, prompts,
                                               state), meter, used)
        run_phase("pallas", lambda: pallas_phase(model, params, prompts,
                                                 state), meter, used)
    else:
        run_phase("fleet", lambda: fleet_phase(model, params, prompts),
                  meter, used)
        run_phase("tp", lambda: tp_phase(model, params, prompts), meter,
                  used)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
