"""Serving engine tests: continuous batching must produce exactly the
tokens that isolated greedy decoding produces, and concurrent requests must
actually share decode steps."""

import asyncio

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import build_model
from repro.serving.engine import ServingEngine


@pytest.fixture(scope="module")
def served():
    cfg = get_config("stablelm-3b").reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(7))
    return cfg, model, params


def greedy_reference(model, params, prompt, n_new):
    """Isolated greedy decode via teacher-forcing forward (no cache)."""
    toks = list(prompt)
    for _ in range(n_new):
        logits, _ = model.forward(
            params, {"tokens": jnp.asarray([toks], jnp.int32)})
        toks.append(int(jnp.argmax(logits[0, -1])))
    return toks[len(prompt):]


def test_single_request_matches_reference(served):
    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=4, max_len=64)

    async def go():
        out = await engine.generate([5, 17, 31], max_new_tokens=8)
        await engine.stop()
        return out

    out = asyncio.run(go())
    ref = greedy_reference(model, params, [5, 17, 31], 8)
    assert out == ref


def test_concurrent_requests_match_isolated(served):
    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=4, max_len=64)
    prompts = [[1, 2, 3], [9, 8, 7], [42, 5, 6], [3, 1, 4]]

    async def go():
        outs = await asyncio.gather(*[
            engine.generate(p, max_new_tokens=4) for p in prompts])
        await engine.stop()
        return outs

    outs = asyncio.run(go())
    for p, o in zip(prompts, outs):
        ref = greedy_reference(model, params, p, 4)
        assert o == ref, f"prompt {p}: batched {o} != isolated {ref}"
    # requests overlapped: some decode steps served >1 sequence
    assert engine.max_occupancy >= 2


def test_more_requests_than_slots(served):
    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=2, max_len=64)
    prompts = [[i, i + 1] for i in range(5)]

    async def go():
        outs = await asyncio.gather(*[
            engine.generate(p, max_new_tokens=4) for p in prompts])
        await engine.stop()
        return outs

    outs = asyncio.run(go())
    for p, o in zip(prompts, outs):
        assert o == greedy_reference(model, params, p, 4)


def test_engine_backed_llm_through_poppy(served):
    """End-to-end: PopPy program → ai.llm → serving engine; parallel calls
    share batches."""
    cfg, model, params = served
    from repro.core import poppy
    from repro.core.ai import llm, use_backend
    from repro.serving.backend import LocalEngineBackend

    engine = ServingEngine(model, params, max_slots=4, max_len=64)
    backend = LocalEngineBackend(engine)

    @poppy
    def fanout(n):
        outs = tuple()
        for i in range(n):
            outs += (llm(f"prompt {i}", max_tokens=4),)
        return outs

    with use_backend(backend):
        outs = fanout(4)
    assert len(outs) == 4
    # untrained model → arbitrary ids; specials (≥256) decode to ""
    assert all(isinstance(o, str) for o in outs)
    assert engine.decode_tokens > 0
    assert engine.max_occupancy >= 2, \
        "parallel PopPy calls did not share decode batches"


def test_engine_backed_llm_autobatched(served):
    """A PopPy batch window lands on the serving engine as one admission
    burst (DESIGN.md §2.3): results match the unbatched run and the burst
    shares decode steps."""
    cfg, model, params = served
    from repro.core import batching, poppy
    from repro.core.ai import llm, use_backend
    from repro.serving.backend import LocalEngineBackend

    def run(batched):
        engine = ServingEngine(model, params, max_slots=4, max_len=64)
        backend = LocalEngineBackend(engine)

        @poppy
        def fanout(n):
            outs = tuple()
            for i in range(n):
                outs += (llm(f"prompt {i}", max_tokens=4),)
            return outs

        with use_backend(backend):
            if batched:
                with batching():
                    outs = fanout(4)
            else:
                outs = fanout(4)
        return outs, engine

    ref, _ = run(False)
    outs, engine = run(True)
    assert outs == ref
    assert engine.max_occupancy >= 2, \
        "batched PopPy calls did not share decode batches"


def test_traced_serving_spans(served):
    """Span tracing across the serving engine (DESIGN.md §4): each request
    gets a ``serving.request`` span carrying slot/queue attrs, prefill
    chunks record under the loop pass that ran them on the slot's lane,
    naming their request, decode steps record under their pass on the
    shared ``decode`` track with batch occupancy, and admissions land as
    instant events."""
    from repro import obs

    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=4, max_len=64,
                           prefill_chunk=2)
    prompts = [[1, 2, 3, 4, 5], [9, 8, 7], [42, 5, 6, 11]]

    async def go():
        outs = await asyncio.gather(*[
            engine.generate(p, max_new_tokens=4) for p in prompts])
        await engine.stop()
        return outs

    with obs.tracing() as trz:
        outs = asyncio.run(go())
    for p, o in zip(prompts, outs):
        assert o == greedy_reference(model, params, p, 4)

    spans = trz.closed_spans()
    reqs = [s for s in spans if s.cat == "serving.request"]
    assert len(reqs) == len(prompts)
    for sp in reqs:
        assert sp.attrs["n_out"] == 4
        assert "slot" in sp.attrs and "queue_s" in sp.attrs
    req_ids = {s.span_id for s in reqs}
    iters = {s.span_id: s for s in spans if s.name == "loop.iter"}
    assert all(s.parent_id == 0 for s in iters.values())
    prefills = [s for s in spans if s.cat == "serving.prefill"]
    assert prefills, "no prefill.chunk spans recorded"
    for sp in prefills:
        assert sp.parent_id in iters and sp.attrs["request"] in req_ids
        assert sp.track.startswith("slot:")
        assert sp.attrs["new"] <= 2      # chunked at prefill_chunk
    decodes = [s for s in spans if s.name == "decode.step"]
    assert decodes, "no decode.step spans recorded"
    for sp in decodes:
        # decode steps serve the whole batch: under the loop's pass (not
        # under any one request), on one track
        assert sp.parent_id in iters and sp.track == "decode"
    assert max(sp.attrs["occupancy"] for sp in decodes) >= 2
    admits = [e for e in trz.instants if e.cat == "serving.admit"]
    assert len(admits) == len(prompts)
    assert {e.parent_id for e in admits} <= req_ids


def _check_slot_mirrors(engine):
    """After every decode step, assert that the host's positions and
    current tokens equal the device arrays the next step reads, for every
    live slot (the test reads the device; the engine does not)."""
    step = engine._decode_once

    def checked():
        step()
        live = sorted(engine.active)
        assert (engine._pos_host[live]
                == np.array(engine.positions)[live]).all()
        assert (engine._cur_host[live]
                == np.array(engine.cur_tokens)[live]).all()

    engine._decode_once = checked


@pytest.mark.parametrize("kv_layout", ["paged", "contiguous"])
def test_position_stop_reads_no_device_state(served, kv_layout):
    """A request whose prompt plus ``max_new_tokens`` runs past
    ``max_len`` retires at position ``max_len - 1`` with the isolated
    greedy tokens, and the stop check reads nothing from the device: the
    engine's only reads are one per decode step and one per first
    token."""
    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=2, max_len=32,
                           kv_layout=kv_layout)
    _check_slot_mirrors(engine)
    prompt = [(7 * i + 3) % 50 + 1 for i in range(24)]

    async def go():
        out = await engine.generate(prompt, max_new_tokens=40)
        await engine.stop()
        return out

    out = asyncio.run(go())
    assert len(out) == 32 - len(prompt)
    assert out == greedy_reference(model, params, prompt, len(out))
    assert engine.host_syncs == engine.steps + 1


@pytest.mark.parametrize("kv_layout", ["paged", "contiguous"])
def test_reused_slots_keep_their_own_tokens(served, kv_layout):
    """Two slots, five requests that retire at different steps: queued
    requests take the freed slots mid-batch, and each still gets the
    tokens it gets when decoded alone."""
    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=2, max_len=64,
                           kv_layout=kv_layout)
    _check_slot_mirrors(engine)
    work = [([1, 2, 3], 2), ([9, 8, 7, 6], 6), ([42, 5], 3),
            ([3, 1, 4, 1], 5), ([7, 7], 4)]

    async def go():
        outs = await asyncio.gather(*[
            engine.generate(p, max_new_tokens=n) for p, n in work])
        await engine.stop()
        return outs

    outs = asyncio.run(go())
    for (p, n), o in zip(work, outs):
        assert o == greedy_reference(model, params, p, n), p
    assert engine.max_occupancy == 2
    assert engine.host_syncs == engine.steps + len(work)


@pytest.mark.parametrize("kv_layout", ["paged", "contiguous"])
def test_prompt_logits_match_direct_prefill(served, kv_layout):
    """The engine's first-token logits (bucket-padded jitted prefill) equal
    an exact-length ``Model.prefill`` of the same prompt."""
    cfg, model, params = served
    engine = ServingEngine(model, params, max_slots=2, max_len=64,
                           kv_layout=kv_layout)
    prompt = [5, 17, 31, 2, 99, 4, 8]          # pads to the 16 bucket
    got = engine.prompt_logits(prompt)
    want, _ = model.prefill(params, {"tokens": jnp.asarray([prompt])},
                            capacity=len(prompt))
    assert got.shape == want.shape == (1, cfg.vocab_padded)
    assert jnp.allclose(got, want, rtol=1e-5, atol=1e-5)


def test_init_in_serving_dtype(served):
    """``Model.init(rng, dtype)`` makes every leaf directly in ``dtype``,
    at the scale of the default dtype's draws, and the same seed gives
    the same weights."""
    cfg, model, _ = served
    f32 = model.init(jax.random.PRNGKey(3))
    bf16 = model.init(jax.random.PRNGKey(3), cfg.serve_param_dtype)
    again = model.init(jax.random.PRNGKey(3), cfg.serve_param_dtype)
    for a, b, c in zip(jax.tree.leaves(f32), jax.tree.leaves(bf16),
                       jax.tree.leaves(again)):
        assert b.dtype == jnp.bfloat16 and b.shape == a.shape
        assert bool(jnp.all(b == c))
        sa, sb = float(jnp.std(a)), float(jnp.std(b.astype(jnp.float32)))
        assert abs(sa - sb) <= 0.1 * sa + 1e-6, (sa, sb)
