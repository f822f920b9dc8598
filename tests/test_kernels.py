"""Per-kernel validation: Pallas (interpret mode, CPU) vs pure-jnp oracle,
swept over shapes and dtypes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.decode_attention.ref import decode_attention_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.paged_attention import ops as pa_ops
from repro.kernels.paged_attention.ref import paged_decode_attention_ref
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.rglru import ops as lru_ops
from repro.kernels.rglru.ref import rglru_scan_ref
from repro.kernels.ssd import ops as ssd_ops
from repro.kernels.ssd.ref import ssd_chunked_ref


def tol(dtype):
    return dict(rtol=2e-2, atol=2e-2) if dtype == jnp.bfloat16 \
        else dict(rtol=2e-5, atol=2e-5)


# ---------------------------------------------------------------------------
# flash attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,S,H,KVH,d,window", [
    (1, 128, 4, 4, 32, 0),
    (2, 256, 4, 2, 64, 0),      # GQA
    (1, 256, 8, 1, 32, 0),      # MQA
    (2, 128, 4, 4, 32, 64),     # sliding window
    (1, 192, 2, 2, 16, 0),      # non-multiple of block
])
def test_flash_attention_matches_ref(B, S, H, KVH, d, window, dtype):
    rng = jax.random.PRNGKey(0)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, S, H, d), dtype)
    k = jax.random.normal(kk, (B, S, KVH, d), dtype)
    v = jax.random.normal(kv, (B, S, KVH, d), dtype)
    out = fa_ops.flash_attention(q, k, v, True, window, True)
    ref = attention_ref(q, k, v, causal=True, window=window)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **tol(dtype))


def test_flash_attention_grads_match_ref():
    rng = jax.random.PRNGKey(1)
    kq, kk, kv = jax.random.split(rng, 3)
    B, S, H, d = 1, 64, 2, 16
    q = jax.random.normal(kq, (B, S, H, d), jnp.float32)
    k = jax.random.normal(kk, (B, S, H, d), jnp.float32)
    v = jax.random.normal(kv, (B, S, H, d), jnp.float32)

    def f_kernel(q, k, v):
        return (fa_ops.flash_attention(q, k, v, True, 0, True) ** 2).sum()

    def f_ref(q, k, v):
        return (attention_ref(q, k, v, causal=True) ** 2).sum()

    g1 = jax.grad(f_kernel, argnums=(0, 1, 2))(q, k, v)
    g2 = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g1, g2):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# decode attention


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,C,H,KVH,d,fill", [
    (2, 256, 4, 4, 32, 200),
    (2, 512, 8, 2, 64, 512),
    (1, 384, 4, 1, 32, 100),    # MQA, partially filled, ragged C
])
def test_decode_attention_matches_ref(B, C, H, KVH, d, fill, dtype):
    rng = jax.random.PRNGKey(2)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, 1, H, d), dtype)
    k = jax.random.normal(kk, (B, C, KVH, d), dtype)
    v = jax.random.normal(kv, (B, C, KVH, d), dtype)
    lengths = jnp.full((B,), fill, jnp.int32)
    valid = jnp.arange(C)[None, :] < lengths[:, None]
    out = da_ops.decode_attention(q, k, v, lengths, interpret=True)
    ref = decode_attention_ref(q, k, v, valid)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32),
        **tol(dtype))


# ---------------------------------------------------------------------------
# paged decode attention


def _paged_case(B, ps, N, H, KVH, d, dtype, seed=4):
    """A page pool with page 0 reserved and per-sequence *shuffled* page
    tables (interleaved across sequences, like a real allocator's free
    list), so a kernel that ignored the table would read wrong pages."""
    rng = jax.random.PRNGKey(seed)
    kq, kk, kv, kp = jax.random.split(rng, 4)
    P = B * N + 3  # page 0 scratch + a couple of unreferenced spares
    q = jax.random.normal(kq, (B, 1, H, d), dtype)
    k_pages = jax.random.normal(kk, (P, ps, KVH, d), dtype)
    v_pages = jax.random.normal(kv, (P, ps, KVH, d), dtype)
    perm = jax.random.permutation(kp, jnp.arange(1, P))[: B * N]
    page_table = perm.reshape(B, N).astype(jnp.int32)
    return q, k_pages, v_pages, page_table


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("B,ps,N,H,KVH,d,lengths,window", [
    (2, 16, 4, 4, 4, 32, (64, 37), 0),   # full + ragged last page
    (2, 8, 6, 8, 2, 64, (48, 41), 0),    # GQA 4:1, small pages
    (1, 32, 3, 4, 1, 32, (70,), 0),      # MQA, big pages, ragged
    (2, 16, 4, 4, 4, 32, (64, 50), 24),  # sliding window across pages
    (1, 16, 2, 2, 2, 16, (1,), 0),       # single valid token
])
def test_paged_decode_attention_matches_ref(B, ps, N, H, KVH, d, lengths,
                                            window, dtype):
    q, k_pages, v_pages, page_table = _paged_case(B, ps, N, H, KVH, d,
                                                  dtype)
    lens = jnp.asarray(lengths, jnp.int32)
    ref = paged_decode_attention_ref(q, k_pages, v_pages, page_table, lens,
                                     window=window)
    out_pl = pa_ops.paged_decode_attention(q, k_pages, v_pages, page_table,
                                           lens, window=window,
                                           interpret=True)
    out_xla = pa_ops.paged_decode_attention_xla(q, k_pages, v_pages,
                                                page_table, lens,
                                                window=window)
    np.testing.assert_allclose(
        np.asarray(out_pl, np.float32), np.asarray(ref, np.float32),
        **tol(dtype))
    np.testing.assert_allclose(
        np.asarray(out_xla, np.float32), np.asarray(ref, np.float32),
        **tol(dtype))


@pytest.mark.parametrize("lengths,window", [((64, 37), 0), ((50, 1), 24)])
def test_paged_decode_attention_ignores_nonfinite_stale_slots(lengths,
                                                              window):
    """Pool slots no sequence may read (a recycled page's tail, scratch
    page 0, spare pages) can hold NaN or inf; the kernel's output must be
    what the clean pool gives."""
    B, ps, N, H, KVH, d = 2, 16, 4, 4, 2, 32
    q, k_pages, v_pages, page_table = _paged_case(B, ps, N, H, KVH, d,
                                                  jnp.float32)
    lens = jnp.asarray(lengths, jnp.int32)
    pos = jnp.arange(N * ps).reshape(1, N, ps)
    lo = jnp.maximum(lens - window, 0) if window else jnp.zeros_like(lens)
    readable = (pos < lens[:, None, None]) & (pos >= lo[:, None, None])
    live = jnp.zeros(k_pages.shape[:2], bool).at[page_table].max(readable)
    junk = jnp.where(jnp.arange(ps) % 2 == 0, jnp.nan, jnp.inf)
    poison = jnp.where(live[..., None, None], 0.0,
                       junk[None, :, None, None])
    out = pa_ops.paged_decode_attention(q, k_pages + poison,
                                        v_pages + poison, page_table, lens,
                                        window=window, interpret=True)
    ref = paged_decode_attention_ref(q, k_pages, v_pages, page_table, lens,
                                     window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               **tol(jnp.float32))


def test_paged_decode_attention_equals_contiguous():
    """Gathering the referenced pages into a contiguous cache and running
    the contiguous decode oracle must agree with the paged oracle — the
    layouts are different addressings of the same attention."""
    B, ps, N, H, KVH, d = 2, 16, 4, 4, 2, 32
    q, k_pages, v_pages, page_table = _paged_case(B, ps, N, H, KVH, d,
                                                  jnp.float32)
    lens = jnp.asarray([64, 29], jnp.int32)
    k = k_pages[page_table].reshape(B, N * ps, KVH, d)
    v = v_pages[page_table].reshape(B, N * ps, KVH, d)
    valid = jnp.arange(N * ps)[None, :] < lens[:, None]
    ref_contig = decode_attention_ref(q, k, v, valid)
    ref_paged = paged_decode_attention_ref(q, k_pages, v_pages, page_table,
                                           lens)
    np.testing.assert_allclose(np.asarray(ref_paged),
                               np.asarray(ref_contig), rtol=2e-5,
                               atol=2e-5)


# ---------------------------------------------------------------------------
# RG-LRU scan


@pytest.mark.parametrize("B,S,W", [(2, 64, 128), (1, 256, 64), (2, 96, 256)])
def test_rglru_scan_matches_ref(B, S, W):
    rng = jax.random.PRNGKey(3)
    ka, kb = jax.random.split(rng)
    a = jax.nn.sigmoid(jax.random.normal(ka, (B, S, W), jnp.float32))
    b = jax.random.normal(kb, (B, S, W), jnp.float32)
    out = lru_ops.rglru_scan(a, b, True)
    ref = rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_rglru_long_dependency():
    """The carried state must propagate across seq blocks (S > block_s)."""
    B, S, W = 1, 600, 128
    a = jnp.full((B, S, W), 0.999, jnp.float32)
    b = jnp.zeros((B, S, W), jnp.float32).at[:, 0].set(1.0)
    out = lru_ops.rglru_scan(a, b, True)
    ref = rglru_scan_ref(a, b)
    np.testing.assert_allclose(np.asarray(out[:, -1]),
                               np.asarray(ref[:, -1]), rtol=1e-5)


# ---------------------------------------------------------------------------
# SSD chunk scan


@pytest.mark.parametrize("B,S,H,P,N,chunk", [
    (1, 128, 2, 16, 16, 32),
    (2, 64, 4, 32, 8, 16),
    (1, 256, 1, 64, 32, 64),
])
def test_ssd_matches_ref(B, S, H, P, N, chunk):
    rng = jax.random.PRNGKey(4)
    ks = jax.random.split(rng, 5)
    xh = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    a_log = jax.random.normal(ks[2], (H,), jnp.float32) * 0.5
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    y, h = ssd_ops.ssd_chunked(xh, dt, a_log, Bm, Cm, chunk=chunk,
                               interpret=True)
    yr, hr = ssd_chunked_ref(xh, dt, a_log, Bm, Cm, chunk=chunk)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=2e-4, atol=2e-4)


def test_ssd_initial_state():
    B, S, H, P, N, chunk = 1, 64, 2, 16, 8, 16
    rng = jax.random.PRNGKey(5)
    ks = jax.random.split(rng, 6)
    xh = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H), jnp.float32))
    a_log = jax.random.normal(ks[2], (H,), jnp.float32) * 0.5
    Bm = jax.random.normal(ks[3], (B, S, N), jnp.float32)
    Cm = jax.random.normal(ks[4], (B, S, N), jnp.float32)
    h0 = jax.random.normal(ks[5], (B, H, P, N), jnp.float32)
    y, h = ssd_ops.ssd_chunked(xh, dt, a_log, Bm, Cm, chunk=chunk,
                               initial_state=h0, interpret=True)
    yr, hr = ssd_chunked_ref(xh, dt, a_log, Bm, Cm, chunk=chunk,
                             initial_state=h0)
    np.testing.assert_allclose(np.asarray(y), np.asarray(yr),
                               rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(h), np.asarray(hr),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# model-level: pallas_interpret end-to-end equals xla path


@pytest.mark.parametrize("arch", ["qwen3-14b", "recurrentgemma-9b",
                                  "mamba2-2.7b"])
def test_model_pallas_interpret_matches_xla(arch):
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config(arch).reduced()
    rng = jax.random.PRNGKey(0)
    tokens = jax.random.randint(rng, (2, 32), 0, cfg.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}

    m_x = build_model(cfg.replace(attention_impl="xla"))
    params = m_x.init(rng)
    lx, _ = m_x.forward(params, batch)
    m_p = build_model(cfg.replace(attention_impl="pallas_interpret"))
    lp, _ = m_p.forward(params, batch)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               rtol=5e-4, atol=5e-4,
                               err_msg=f"{arch}: pallas path diverges")


@pytest.mark.parametrize("B,C,H,KVH,d,fill", [
    (2, 256, 4, 2, 32, 200),
    (1, 512, 8, 8, 64, 300),
    (1, 384, 4, 2, 32, 300),    # ragged C: the last block reads padding
])
def test_decode_attention_int8_matches_dequant_ref(B, C, H, KVH, d, fill):
    """int8-KV kernel (in-kernel dequant) vs reference over the
    dequantized cache."""
    from repro.models.attention import dequantize_kv, quantize_kv

    rng = jax.random.PRNGKey(6)
    kq, kk, kv = jax.random.split(rng, 3)
    q = jax.random.normal(kq, (B, 1, H, d), jnp.float32)
    k = jax.random.normal(kk, (B, C, KVH, d), jnp.float32)
    v = jax.random.normal(kv, (B, C, KVH, d), jnp.float32)
    qk, sk = quantize_kv(k)
    qv, sv = quantize_kv(v)
    lengths = jnp.full((B,), fill, jnp.int32)
    valid = jnp.arange(C)[None, :] < lengths[:, None]
    out = da_ops.decode_attention_int8(q, qk, qv, sk, sv, lengths,
                                       interpret=True)
    kd = dequantize_kv(qk, sk, jnp.float32)
    vd = dequantize_kv(qv, sv, jnp.float32)
    ref = decode_attention_ref(q, kd, vd, valid)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_model_decode_int8_pallas_matches_xla():
    """Full model decode: int8 cache + pallas-interpret kernel ≡ int8
    cache + XLA dequant path."""
    from repro.configs import get_config
    from repro.models import build_model

    cfg = get_config("qwen3-14b").reduced().replace(kv_cache_dtype="int8")
    rng = jax.random.PRNGKey(9)
    m_x = build_model(cfg.replace(attention_impl="xla"))
    params = m_x.init(rng)
    toks = jax.random.randint(rng, (2, 12), 0, cfg.vocab_size)
    _, cache = m_x.prefill(params, {"tokens": toks[:, :8]}, capacity=12)
    pos = jnp.full((2,), 8, jnp.int32)
    lx, _ = m_x.decode_step(params, cache, toks[:, 8:9], pos)
    m_p = build_model(cfg.replace(attention_impl="pallas_interpret"))
    lp, _ = m_p.decode_step(params, cache, toks[:, 8:9], pos)
    np.testing.assert_allclose(np.asarray(lx), np.asarray(lp),
                               rtol=5e-4, atol=5e-4)
