"""Fig. 14: paged KV cache — admitted users at fixed KV memory, with
zero-copy prefix sharing (beyond-paper; DESIGN.md §3.3, EXPERIMENTS.md
§Fig. 14).

PopPy's fan-out burst (N parallel ``@unordered`` llm() calls sharing a
long context) is memory-bound on the serving side: a contiguous KV cache
reserves ``max_len`` tokens per slot, so N users sharing a 200-token
prefix store it N times and the decode batch is capped by slots × slab.
The block-paged engine (``kv_layout="paged"``) stores KV in fixed-size
pages with per-slot page tables: the shared prefix occupies its pages
*once* and every user's page table references them — admission appends
page ids (``kv_admit_copies == 0``, asserted), so the same pool bytes
admit far more concurrent users.

Two engines over the same real (reduced-config) JAX model, with **equal
KV pool bytes** (asserted):

  contig   kv_layout="contiguous", max_slots=4 · max_len=256 slabs
  paged    page_size=16, num_pages=64 (= the same 1024 KV tokens),
           max_slots=16

plus a sequential-mode oracle on the contiguous engine.  Every trial
asserts token-exact equality of all three runs, ≡_A trace equivalence of
both PopPy runs, the prefill-compilation bucket bound on both engines,
the paged gather/fill shape bound, and the zero-copy counters (paged
``kv_admit_copies == 0`` while contiguous splices one copy per admit).

Metrics: ``admitted_users_ratio`` — peak concurrent decode occupancy at
fixed memory (deterministic: the contiguous engine is slot-capped while
the paged engine admits the whole burst) — and the decode step-time
ratio (reported, not gated: CPU timing noise).  The acceptance bar is
admitted ≥1.5× at N=16; smoke measures ~4×.

    PYTHONPATH=src:. python benchmarks/fig14_paged_kv.py
"""

from __future__ import annotations

import json
import statistics
import time
from pathlib import Path

import jax

from repro import obs
from repro.core import batching, equivalent, poppy, recording, \
    sequential_mode
from repro.core.ai import llm, use_backend, use_dispatcher
from repro.dispatch import Dispatcher
from repro.models import build_model
from repro.serving import LocalEngineBackend, ServingEngine
from repro.serving.prefix_cache import tree_nbytes

from benchmarks.common import maybe_tracing

N_FANOUT = 16
PREFIX_CHARS = 192          # shared prompt tokens (byte tokenizer, 1:1)
MAX_NEW_TOKENS = 20         # > N so the burst fully overlaps in decode
MAX_LEN = 256
PAGE_SIZE = 16
CONTIG_SLOTS = 4            # contiguous: 4 × 256-token slabs
PAGED_SLOTS = 16            # paged: same bytes as 64 × 16-token pages


def make_prefix(chars: int) -> str:
    base = ("System: you are a terse planner. Shared context: inventory "
            "levels, supplier lead times, and open orders for region. ")
    s = base
    while len(s) < chars:
        s += base
    return s[:chars]


def suffixes(n: int):
    return [f"Q{i:02d}: {'y' * (i % 5)} restock item {i}?"
            for i in range(n)]


@poppy
def fanout(prefix, queries):
    outs = tuple()
    for q in queries:
        outs += (llm(prefix + q, max_tokens=MAX_NEW_TOKENS),)
    return outs


def build(arch="stablelm-3b", *, layout: str):
    from repro.configs import get_config
    cfg = get_config(arch).reduced().replace(
        num_layers=4, d_model=256, num_heads=8, head_dim=32, d_ff=512)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(13))
    if layout == "paged":
        engine = ServingEngine(
            model, params, max_slots=PAGED_SLOTS, max_len=MAX_LEN,
            page_size=PAGE_SIZE,
            num_pages=CONTIG_SLOTS * MAX_LEN // PAGE_SIZE)
        assert engine.paged_kv
    else:
        engine = ServingEngine(
            model, params, max_slots=CONTIG_SLOTS, max_len=MAX_LEN,
            kv_layout="contiguous")
        assert not engine.paged_kv
    return engine, LocalEngineBackend(engine)


def _run_once(mode, backend, prefix, queries):
    """Result, seconds, trace, and the engine's ``decode.step`` spans of
    the run (recorded on the ``--trace-out`` tracer, else on one of its
    own)."""
    d = Dispatcher()
    trz = obs.current_tracer() or obs.Tracer()
    n0 = len(trz.spans)
    with obs.tracing(trz), use_backend(backend), use_dispatcher(d), \
            recording() as tr:
        t0 = time.perf_counter()
        if mode == "plain":
            with sequential_mode():
                result = fanout(prefix, queries)
        else:
            with batching():
                result = fanout(prefix, queries)
        dt = time.perf_counter() - t0
    steps = [s for s in trz.spans[n0:]
             if s.name == "decode.step" and not s.open]
    return result, dt, tr, steps


def _assert_compile_bounds(eng, label):
    bound = eng.prefill_shape_bound
    assert eng.prefill_compilations <= bound, (
        f"{label}: {eng.prefill_compilations} prefill compilations exceed "
        f"the bucket bound {bound} — recompile-per-length regression")
    if eng.paged_kv:
        assert len(eng.page_op_shapes) <= eng.page_op_shape_bound, (
            f"{label}: {len(eng.page_op_shapes)} page-op shapes exceed "
            f"bound {eng.page_op_shape_bound}")


def bench(n=N_FANOUT, *, trials=3, prefix_chars=PREFIX_CHARS):
    prefix = make_prefix(prefix_chars)
    queries = suffixes(n)
    eng_ct, be_ct = build(layout="contiguous")
    eng_pg, be_pg = build(layout="paged")

    # identical KV pool bytes (the paged pool carries one extra scratch
    # page that admission can never hand out)
    ct_bytes = tree_nbytes(eng_ct.cache)
    pg_bytes = tree_nbytes(eng_pg.kv_pages) \
        * eng_pg.num_pages // (eng_pg.num_pages + 1)
    assert ct_bytes == pg_bytes, (ct_bytes, pg_bytes)

    # warm the compiled shapes once; timing/occupancy measured per trial
    for be in (be_ct, be_pg):
        _run_once("poppy", be, prefix, queries[:2])

    times = {"plain": [], "contig": [], "paged": []}
    occ, decode_ms = {"contig": [], "paged": []}, {}
    for _ in range(trials):
        for eng in (eng_ct, eng_pg):
            eng.reset_prefix_cache()  # cold radix cache every trial
        r_ref, dt, tr_ref, _ = _run_once("plain", be_ct, prefix, queries)
        times["plain"].append(dt)
        r_ct, dt, tr_ct, steps_ct = _run_once("contig", be_ct, prefix,
                                              queries)
        times["contig"].append(dt)
        r_pg, dt, tr_pg, steps_pg = _run_once("paged", be_pg, prefix,
                                              queries)
        times["paged"].append(dt)

        assert r_ct == r_ref, \
            f"contiguous diverges from oracle: {r_ct!r} vs {r_ref!r}"
        assert r_pg == r_ref, (
            f"paged engine not token-exact vs oracle: "
            f"{r_pg!r} vs {r_ref!r}")
        ok, why = equivalent(tr_ref, tr_ct)
        assert ok, f"contiguous trace not ≡_A: {why}"
        ok, why = equivalent(tr_ref, tr_pg)
        assert ok, f"paged trace not ≡_A: {why}"
        # zero-copy sharing: the paged engine never copies KV at admit;
        # the contiguous engine splices one copy per admitted request
        assert eng_pg.kv_admit_copies == 0, \
            f"paged engine copied KV {eng_pg.kv_admit_copies}× at admit"
        assert eng_ct.kv_admit_copies > 0
        assert eng_pg.prefix_cache.stats()["tokens_matched"] > 0, \
            "paged radix cache never matched the shared prefix"
        _assert_compile_bounds(eng_ct, "contig")
        _assert_compile_bounds(eng_pg, "paged")
        for label, steps in (("contig", steps_ct), ("paged", steps_pg)):
            occ[label].append(max((s.attrs["occupancy"] for s in steps),
                                  default=0))
            decode_ms.setdefault(label, []).extend(s.dur for s in steps)

    med = {m: statistics.median(ts) for m, ts in times.items()}
    peak = {m: max(os) for m, os in occ.items()}
    step = {m: statistics.median(v) for m, v in decode_ms.items()}
    return {
        "n_fanout": n,
        "prefix_chars": prefix_chars,
        "max_new_tokens": MAX_NEW_TOKENS,
        "kv_pool_bytes": ct_bytes,
        **{f"{m}_s": t for m, t in med.items()},
        "admitted_users_contig": peak["contig"],
        "admitted_users_paged": peak["paged"],
        "admitted_users_ratio": peak["paged"] / max(peak["contig"], 1),
        "decode_step_contig_ms": step["contig"] * 1e3,
        "decode_step_paged_ms": step["paged"] * 1e3,
        "decode_step_ratio": step["contig"] / max(step["paged"], 1e-12),
        "kv_admit_copies_paged": eng_pg.kv_admit_copies,
        "kv_admit_copies_contig": eng_ct.kv_admit_copies,
        "prefill_compilations": eng_pg.prefill_compilations,
        "prefill_shape_bound": eng_pg.prefill_shape_bound,
        "jit_headroom": eng_pg.prefill_shape_bound
        / max(eng_pg.prefill_compilations, 1),
        "page_op_shapes": len(eng_pg.page_op_shapes),
        "page_op_shape_bound": eng_pg.page_op_shape_bound,
        "paged_stats": eng_pg.stats()["paged"],
        "prefix_cache": eng_pg.prefix_cache.stats(),
    }


def run(out_dir="experiments/apps", trials=3, n=N_FANOUT,
        prefix_chars=PREFIX_CHARS, smoke=False, trace_out=None):
    with maybe_tracing(trace_out):
        return _run(out_dir, trials, n, prefix_chars, smoke)


def _run(out_dir, trials, n, prefix_chars, smoke):
    r = bench(n, trials=trials, prefix_chars=prefix_chars)
    print(f"N={r['n_fanout']:3d}  admitted users {r['admitted_users_contig']}"
          f" (contig) → {r['admitted_users_paged']} (paged) = "
          f"{r['admitted_users_ratio']:.2f}× at {r['kv_pool_bytes']} KV "
          f"bytes;  decode step {r['decode_step_contig_ms']:.2f}ms → "
          f"{r['decode_step_paged_ms']:.2f}ms;  admit copies "
          f"{r['kv_admit_copies_contig']} → {r['kv_admit_copies_paged']}  "
          f"({r['page_op_shapes']} page-op shapes ≤ "
          f"{r['page_op_shape_bound']})", flush=True)
    # equality, ≡_A, zero-copy, and both compile bounds were asserted
    # every trial; the capacity bar holds even at smoke scale because it
    # counts users, not seconds
    assert r["admitted_users_ratio"] >= 1.5, (
        f"acceptance: paged KV must admit ≥1.5× the users of the "
        f"contiguous engine at equal memory, got "
        f"{r['admitted_users_ratio']:.2f}×")
    if not smoke:
        print(f"\nN={n} acceptance: "
              f"{r['admitted_users_ratio']:.2f}× ≥ 1.5× ✓")
    out = Path(out_dir)
    out.mkdir(parents=True, exist_ok=True)
    (out / "fig14.json").write_text(json.dumps(r, indent=1))
    return r


if __name__ == "__main__":
    import argparse

    ap = argparse.ArgumentParser()
    ap.add_argument("--trials", type=int, default=3)
    ap.add_argument("--n", type=int, default=N_FANOUT)
    ap.add_argument("--prefix-chars", type=int, default=PREFIX_CHARS)
    ap.add_argument("--trace-out", default=None,
                    help="write a Perfetto trace of the run here")
    args = ap.parse_args()
    run(trials=args.trials, n=args.n, prefix_chars=args.prefix_chars,
        trace_out=args.trace_out)
