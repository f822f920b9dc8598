"""Paged-KV serving tests: allocator invariants under exhaustion, the
page-granular radix trie (zero-copy sharing, pinning, LRU), and engine
admission backpressure — outputs must stay token-exact through all of it."""

import asyncio

import jax
import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.models import build_model
from repro.obs import spans as spans_mod
from repro.obs.metrics import MetricsRegistry
from repro.serving.engine import PageAllocator, ServingEngine
from repro.serving.prefix_cache import PagedPrefixCache


@pytest.fixture(scope="module")
def served():
    cfg = get_config("stablelm-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    return cfg, model, params


# ---------------------------------------------------------------------------
# allocator


def test_allocator_alloc_free_refcount():
    a = PageAllocator(8, 16)
    assert a.free_count == 8
    ids = a.alloc(3)
    assert len(ids) == 3 and 0 not in ids, "page 0 is reserved scratch"
    assert a.free_count == 5
    assert all(a.refcount(i) == 1 for i in ids)
    a.incref(ids)
    assert a.decref(ids) == 0, "still referenced — nothing freed"
    assert a.free_count == 5
    assert a.decref(ids) == 3
    assert a.free_count == 8


def test_allocator_all_or_nothing_exhaustion():
    a = PageAllocator(4, 16)
    ids = a.alloc(3)
    assert a.alloc(2) is None, "partial grants would deadlock admission"
    assert a.free_count == 1, "failed alloc must not consume pages"
    more = a.alloc(1)
    assert more is not None
    a.decref(ids)
    a.decref(more)
    assert a.free_count == 4


def test_allocator_double_free_asserts():
    a = PageAllocator(4, 16)
    ids = a.alloc(1)
    a.decref(ids)
    with pytest.raises(AssertionError):
        a.decref(ids)


# ---------------------------------------------------------------------------
# paged radix trie


def _trie(num_pages=32, ps=4, budget=None):
    a = PageAllocator(num_pages, ps)
    return a, PagedPrefixCache(a, budget)


def test_trie_insert_takes_page_refs_not_copies():
    a, px = _trie(ps=4)
    toks = tuple(range(100, 116))  # 16 tokens = 4 pages
    ids = a.alloc(4)
    assert px.insert(toks, ids)
    assert all(a.refcount(i) == 2 for i in ids), \
        "insert shares via incref — the only ownership transfer"
    a.decref(ids)  # the slot retires; trie keeps the pages alive
    assert all(a.refcount(i) == 1 for i in ids)
    assert a.free_count == 32 - 4

    m, pages, h = px.match_and_pin(toks + (1, 2))
    assert m == 16 and list(pages) == ids
    px.release(h)


def test_trie_matches_and_splits_at_page_boundaries():
    a, px = _trie(ps=4)
    toks = tuple(range(100, 116))
    ids = a.alloc(4)
    px.insert(toks, ids)
    a.decref(ids)

    # divergence inside a page floors to the boundary: tokens 0..13 agree,
    # page 3 (tokens 12..15) is only partially matched -> matched = 12
    probe = toks[:14] + (999, 998)
    m, pages, h = px.match_and_pin(probe)
    assert m == 12 and list(pages) == ids[:3]
    px.release(h)
    assert px.splits == 1, "edge split at the 12-token page boundary"
    # the split repartitioned page ownership without allocator traffic
    assert all(a.refcount(i) == 1 for i in ids)

    # a shorter aligned probe re-uses the refined node, no further splits
    m2, pages2, h2 = px.match_and_pin(toks[:8])
    assert m2 == 8 and list(pages2) == ids[:2]
    px.release(h2)
    assert px.splits == 2  # 8 is inside the [0,12) node: one more split


def test_trie_pinned_paths_survive_reclaim():
    a, px = _trie(num_pages=8, ps=4)
    hot = tuple(range(10, 18))    # 2 pages
    cold = tuple(range(50, 58))   # 2 pages
    for toks in (hot, cold):
        ids = a.alloc(2)
        px.insert(toks, ids)
        a.decref(ids)
    assert a.free_count == 4
    m, hot_pages, pin = px.match_and_pin(hot)
    assert m == 8

    # demand more than free: only the unpinned (cold) path may go
    px.reclaim(6)
    assert a.free_count == 6, "cold leaf evicted"
    assert all(a.refcount(i) == 1 for i in hot_pages), \
        "pinned pages must never be reclaimed"
    px.reclaim(8)  # impossible while the pin is held
    assert a.free_count == 6
    px.release(pin)
    px.reclaim(8)
    assert a.free_count == 8 and px.pages == 0


def test_trie_budget_evicts_lru_and_balances_refs():
    a, px = _trie(num_pages=32, ps=4, budget=4)
    seqs = [tuple(range(100 * k, 100 * k + 8)) for k in range(3)]
    rows = []
    for toks in seqs:
        ids = a.alloc(2)
        rows.append(ids)
        assert px.insert(toks, ids)
        a.decref(ids)
    assert px.pages == 4, "budget of 4 pages: LRU seq evicted"
    m0, _, h0 = px.match_and_pin(seqs[0])
    assert m0 == 0, "oldest insert was evicted"
    px.release(h0)
    m2, pages2, h2 = px.match_and_pin(seqs[2])
    assert m2 == 8 and list(pages2) == rows[2]
    px.release(h2)
    # every page the trie dropped went back to the free list
    assert a.free_count == 32 - px.pages


def test_trie_concurrent_split_keeps_release_balanced():
    """A pin taken before a later insert splits its node must release
    cleanly across the refined path (the token-walk release)."""
    a, px = _trie(ps=4)
    long = tuple(range(0, 16))
    ids = a.alloc(4)
    px.insert(long, ids)
    a.decref(ids)
    m, _, pin = px.match_and_pin(long)           # pins the single edge
    assert m == 16
    short = long[:8] + (777, 778, 779, 780)      # forces a split at 8
    ids2 = a.alloc(1)
    px.insert(short[:12], list(ids[:2]) + ids2)
    a.decref(ids2)
    assert px.splits == 1
    px.release(pin)                              # walks the refined path
    px.drop_unpinned()
    assert px.pages == 0
    assert a.free_count == 32


# ---------------------------------------------------------------------------
# engine: admission backpressure, ownership balance, rejects


def _drain_check(engine):
    """After the engine quiesces, every page is either free or owned by
    exactly the trie — slots hold nothing."""
    assert not engine._slot_pages
    assert not engine._wait_pages
    trie_pages = engine.prefix_cache.pages \
        if engine.prefix_cache is not None else 0
    assert engine.allocator.free_count == engine.num_pages - trie_pages
    if engine.prefix_cache is not None:
        stack = list(engine.prefix_cache.root.children.values())
        while stack:
            nd = stack.pop()
            stack.extend(nd.children.values())
            assert nd.refs == 0, "leaked pin"
            for p in nd.pages:
                assert engine.allocator.refcount(p) == 1, \
                    "trie must be the sole owner after drain"


def test_page_exhaustion_backpressures_admission(served):
    """More concurrent demand than the page pool: admission stalls (never
    a scheduler crash), requests complete as pages retire, and outputs
    are token-exact vs an uncontended contiguous engine."""
    cfg, model, params = served
    rng = np.random.RandomState(3)
    prompts = [[int(t) for t in rng.randint(1, 200, size=40)]
               for _ in range(4)]

    async def run(**kw):
        eng = ServingEngine(model, params, max_slots=4, max_len=64, **kw)
        outs = await asyncio.gather(*[
            eng.generate(p, max_new_tokens=8) for p in prompts])
        await eng.stop()
        return outs, eng

    # 40 + 8 tokens -> 3 pages each; 8-page pool fits 2 requests at a time
    tight, eng = asyncio.run(run(page_size=16, num_pages=8))
    assert eng.admit_stalls > 0, "the pool was never exhausted"
    assert eng.allocator.page_faults > 0
    roomy, _ = asyncio.run(run(kv_layout="contiguous"))
    assert tight == roomy, "backpressure must not change tokens"
    _drain_check(eng)


def test_cancelled_and_completed_requests_balance_refcounts(served):
    """Hedge losers / dropped clients mid-flight: their slot pages and
    trie pins are returned; the pool balances to free + trie-owned."""
    cfg, model, params = served
    prefix = list(range(40, 72))  # page-aligned 32-token shared prefix

    async def go():
        eng = ServingEngine(model, params, max_slots=4, max_len=64,
                            page_size=16)
        await eng.warm_prefix(prefix)
        keep = [asyncio.create_task(
            eng.generate(prefix + [100 + i], max_new_tokens=6))
            for i in range(2)]
        drop = [asyncio.create_task(
            eng.generate(prefix + [200 + i], max_new_tokens=24))
            for i in range(2)]
        await asyncio.sleep(0)    # let them enqueue/admit
        for t in drop:
            t.cancel()
        outs = await asyncio.gather(*keep)
        await asyncio.gather(*drop, return_exceptions=True)
        await eng.stop()
        return outs, eng

    outs, eng = asyncio.run(go())
    assert all(len(o) == 6 for o in outs)
    _drain_check(eng)
    px = eng.prefix_cache.stats()
    assert px["tokens_matched"] > 0, "survivors shared the warmed prefix"


def test_overlong_for_pool_rejected_at_page_granularity(served):
    """Regression (ISSUE 7 satellite): a request whose eager page need
    (prompt + max_new, page-rounded) exceeds the whole pool can never be
    admitted — it must be rejected at submission, not stall forever."""
    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=2, max_len=64,
                           page_size=16, num_pages=2)

    async def go():
        # 20 + 20 = 40 tokens -> 3 pages > 2-page pool
        with pytest.raises(ValueError, match="pages"):
            await engine.generate(list(range(20)), max_new_tokens=20)
        # the same prompt with a page-fitting budget is served fine
        out = await engine.generate(list(range(20)), max_new_tokens=8)
        await engine.stop()
        return out

    out = asyncio.run(go())
    assert len(out) == 8


def test_unsupported_models_fall_back_to_contiguous(served):
    cfg, model, params = served
    rec = get_config("recurrentgemma-9b").reduced()
    rmodel = build_model(rec)
    rparams = rmodel.init(jax.random.PRNGKey(0))
    eng = ServingEngine(rmodel, rparams, max_slots=2, max_len=32)
    assert eng.kv_layout == "contiguous" and not eng.paged_kv

    # and paged stays an explicit opt-out on supported models
    eng2 = ServingEngine(model, params, max_slots=2, max_len=32,
                         kv_layout="contiguous")
    assert not eng2.paged_kv and eng2.cache is not None
    with pytest.raises(ValueError, match="kv_layout"):
        ServingEngine(model, params, kv_layout="blocked")


def test_paged_decode_timing_and_gauges(served):
    """Observability rides along: decode steps are counted and the
    metrics registry carries the page gauges/counters."""
    cfg, model, params = served
    reg = MetricsRegistry()
    engine = ServingEngine(model, params, max_slots=2, max_len=64,
                           page_size=16, metrics=reg)

    async def go():
        out = await engine.generate([3, 1, 4, 1, 5], max_new_tokens=4)
        await engine.stop()
        return out

    out = asyncio.run(go())
    assert len(out) == 4
    assert engine.steps >= 3
    snap = reg.snapshot()
    assert "serving_pages_free" in snap
    free = engine.allocator.free_count
    assert snap["serving_pages_free"]["value"] == free


@pytest.mark.parametrize("kv_layout", ["paged", "contiguous"])
def test_traced_loop_counts_every_host_sync(served, kv_layout):
    """A traced run records the loop's passes: every ``decode.step`` lies
    inside a ``loop.iter`` with a ``device.wait`` child, and a pass's
    ``syncs`` are the reads it made: 1 for a decode step (its sampled
    tokens) and 1 per first token sampled; the host knows every slot's
    position and current token, so it never reads them back.  Their sum
    is ``host_syncs``; ``first_token_t`` lies inside its request span."""
    cfg, model, params = served
    reg = MetricsRegistry()
    engine = ServingEngine(model, params, max_slots=4, max_len=64,
                           page_size=16, prefill_chunk=16, metrics=reg,
                           kv_layout=kv_layout)
    prompts = [[3, 1, 4, 1, 5], list(range(1, 40)), [9, 8, 7]]
    max_new = [3, 5, 7]

    async def go():
        outs = await asyncio.gather(*[
            engine.generate(p, max_new_tokens=m)
            for p, m in zip(prompts, max_new)])
        await engine.stop()
        return outs

    with obs.tracing() as trz:
        outs = asyncio.run(go())
    assert [len(o) for o in outs] == max_new
    spans = trz.closed_spans()
    iters = [s for s in spans if s.name == "loop.iter"]
    reqs = [s for s in spans if s.name == "request"]
    steps = {s.parent_id: s for s in spans if s.name == "decode.step"}
    assert len(steps) == engine.steps >= max(max_new) - 1
    by_id = {s.span_id: s for s in iters}
    for it_id, st in steps.items():
        it = by_id[it_id]
        assert it.t0 <= st.t0 and st.t1 <= it.t1
        assert [s.name for s in spans if s.parent_id == st.span_id] \
            == ["device.wait"]
    for r in reqs:
        assert r.t0 < r.attrs["first_token_t"] < r.t1
    for it in iters:
        firsts = [r for r in reqs
                  if it.t0 <= r.attrs["first_token_t"] <= it.t1]
        assert it.attrs["decoded"] == (it.span_id in steps)
        assert it.attrs["syncs"] == len(firsts) + it.attrs["decoded"]
    total = sum(it.attrs["syncs"] for it in iters)
    assert engine.stats()["host_syncs"] == total == engine.steps + len(reqs)
    assert reg.snapshot()["serving_host_syncs"] == total
    occ = [s.attrs["occupancy"] for s in steps.values()]
    assert engine.stats()["max_occupancy"] == max(occ) >= 2
    assert engine.occupancy_sum == sum(occ)
    assert reg.snapshot()["serving_batch_occupancy"]["count"] == len(occ)


def test_untraced_generate_allocates_no_spans(served):
    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=2, max_len=64)

    async def go():
        out = await engine.generate([3, 1, 4, 1, 5], max_new_tokens=4)
        await engine.stop()
        return out

    before = spans_mod.SPAN_ALLOCS
    assert len(asyncio.run(go())) == 4
    assert spans_mod.SPAN_ALLOCS == before
    assert engine.stats()["host_syncs"] > 0
