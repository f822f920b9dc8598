"""Grouped-query attention with qk-norm / QKV-bias / sliding-window / cross
variants, full-sequence and cached-decode paths.

The full-sequence path dispatches on ``cfg.attention_impl``:
  * ``xla``              — pure-jnp reference (also the dry-run path: Pallas
                           TPU kernels don't lower on the CPU host platform)
  * ``pallas``           — TPU flash-attention kernel (repro.kernels)
  * ``pallas_interpret`` — same kernel, interpreter mode (CPU validation)
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from .common import PSpec, apply_rope, rmsnorm, rope_cos_sin, shard_hint

NEG_INF = -2.0e38


def attn_schema(cfg, *, cross=False) -> dict:
    D, H, KVH, hd = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    s = {
        "wq": PSpec((D, H, hd), ("embed", "heads", "head_dim")),
        "wk": PSpec((D, KVH, hd), ("embed", "kv_heads", "head_dim")),
        "wv": PSpec((D, KVH, hd), ("embed", "kv_heads", "head_dim")),
        "wo": PSpec((H, hd, D), ("heads", "head_dim", "embed"),
                    fan_in_axes=(0, 1)),
    }
    if cfg.qkv_bias and not cross:
        s["bq"] = PSpec((H, hd), ("heads", "head_dim"), "zeros")
        s["bk"] = PSpec((KVH, hd), ("kv_heads", "head_dim"), "zeros")
        s["bv"] = PSpec((KVH, hd), ("kv_heads", "head_dim"), "zeros")
    if cfg.qk_norm and not cross:
        s["q_norm"] = PSpec((hd,), ("head_dim",), "zeros")
        s["k_norm"] = PSpec((hd,), ("head_dim",), "zeros")
    return s


def _project_q(cfg, p, x):
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(x.dtype))
    if "bq" in p:
        q = q + p["bq"].astype(x.dtype)
    if "q_norm" in p:
        q = rmsnorm(q, p["q_norm"], cfg.norm_eps)
    return q


def _project_kv(cfg, p, x):
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(x.dtype))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(x.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(x.dtype)
        v = v + p["bv"].astype(x.dtype)
    if "k_norm" in p:
        k = rmsnorm(k, p["k_norm"], cfg.norm_eps)
    return k, v


def mha_reference(q, k, v, *, mask=None):
    """Pure-jnp grouped-query attention.  q: [B,S,H,hd]; k,v: [B,T,KVH,hd];
    mask: [B,1,S,T] or [1,1,S,T] additive-compatible boolean (True=keep)."""
    B, S, H, hd = q.shape
    KVH = k.shape[2]
    G = H // KVH
    q = q.reshape(B, S, KVH, G, hd)
    scale = hd ** -0.5
    scores = jnp.einsum("bskgd,btkd->bkgst", q, k).astype(jnp.float32) * scale
    if mask is not None:
        scores = jnp.where(mask[:, :, None] if mask.ndim == 4 else mask,
                           scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    out = jnp.einsum("bkgst,btkd->bskgd", probs.astype(v.dtype), v)
    return out.reshape(B, S, H, hd)


_CHUNK_THRESHOLD = 1 << 24  # S·T above this → KV-streamed XLA attention


def mha_kv_streamed(q, k, v, *, causal, window, offset=0, kv_chunk=1024):
    """Flash-style attention in pure XLA for long sequences: scan over KV
    chunks with an online softmax, materializing only [.., S, kv_chunk]
    scores.  Chunking slices the *KV* sequence dim — replicated (ulysses)
    or head-sharded K/V keeps every slice shard-aligned, unlike q-chunking,
    which would cut through a sequence-sharded q.  Used where the Pallas
    kernel can't lower (CPU host platform / dry-run)."""
    B, S, H, hd = q.shape
    T, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    C = min(kv_chunk, T)
    if T % C:
        C = T
    nk = T // C
    scale = hd ** -0.5
    qg = q.reshape(B, S, KVH, G, hd).astype(jnp.float32)
    kc = k.transpose(0, 2, 1, 3).reshape(B, KVH, nk, C, hd)
    vc = v.transpose(0, 2, 1, 3).reshape(B, KVH, nk, C, hd)
    qpos = offset + jnp.arange(S)

    def step(carry, inp):
        m, l, acc = carry
        kb, vb, ki = inp            # [B,KVH,C,hd] ×2, scalar
        s = jnp.einsum("bskgd,bkcd->bkgsc", qg,
                       kb.astype(jnp.float32)) * scale
        kpos = ki * C + jnp.arange(C)
        keep = jnp.ones((S, C), bool)
        if causal:
            keep &= kpos[None, :] <= qpos[:, None]
        if window > 0:
            keep &= kpos[None, :] > qpos[:, None] - window
        s = jnp.where(keep[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
        p = jnp.where(keep[None, None, None], jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m - m_new)
        l = l * alpha + p.sum(axis=-1, keepdims=True)
        acc = acc * alpha + jnp.einsum("bkgsc,bkcd->bkgsd", p,
                                       vb.astype(jnp.float32))
        return (m_new, l, acc), None

    m0 = jnp.full((B, KVH, G, S, 1), NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, KVH, G, S, 1), jnp.float32)
    a0 = jnp.zeros((B, KVH, G, S, hd), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        step, (m0, l0, a0),
        (kc.transpose(2, 0, 1, 3, 4), vc.transpose(2, 0, 1, 3, 4),
         jnp.arange(nk)))
    out = acc / jnp.maximum(l, 1e-30)
    return out.transpose(0, 3, 1, 2, 4).reshape(B, S, H, hd).astype(q.dtype)


def causal_mask(S, T, *, offset=0, window=0):
    """[1, 1, S, T] boolean keep-mask.  offset = (T - S) for prefix caches."""
    qpos = jnp.arange(S)[:, None] + offset
    kpos = jnp.arange(T)[None, :]
    keep = kpos <= qpos
    if window > 0:
        keep &= kpos > qpos - window
    return keep[None, None]


def prefix_causal_mask(S, Tpad, prefix_len):
    """[1, 1, S, Tpad+S] keep-mask for suffix queries over a padded KV
    prefix followed by the suffix's own keys: prefix key j is valid iff
    j < prefix_len (``prefix_len`` may be a traced scalar — padding beyond
    it is masked out), suffix keys are causal."""
    keep_prefix = jnp.broadcast_to(
        jnp.arange(Tpad)[None, :] < prefix_len, (S, Tpad))
    qpos = jnp.arange(S)[:, None]
    keep_self = jnp.arange(S)[None, :] <= qpos
    return jnp.concatenate([keep_prefix, keep_self], axis=1)[None, None]


def full_attention(cfg, p, x, *, positions, kv_x=None, causal=True,
                   window=0, return_kv=False, prefix_kv=None,
                   prefix_len=None):
    """Full-sequence attention (training / prefill / encoder / cross).

    kv_x: source of keys/values (cross-attention) — defaults to x.
    return_kv: also return the (post-RoPE) K/V for cache filling.
    prefix_kv: optional ``(k, v)`` of an already-prefilled prompt prefix
        ([B, Tpad, KVH, hd], post-RoPE, zero-padded beyond ``prefix_len``)
        — x is then the prompt *suffix* whose queries attend the prefix
        keys plus their own causal keys.  ``return_kv`` returns only the
        suffix K/V (the caller already owns the prefix).  Requires
        ``causal`` and global attention (window == 0).  With Tpad == 0
        it is ordinary causal attention (and takes the kernel path).
    """
    B, S, D = x.shape
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, kv_x if kv_x is not None else x)
    T = k.shape[1]
    if cfg.use_rope and kv_x is None:
        cos, sin = rope_cos_sin(positions, cfg.head_dim, cfg.rope_theta,
                                x.dtype)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    q = shard_hint(q, "act_qkv")
    # two-step constraint: project K/V from the (possibly seq-sharded)
    # input locally, then gather — the collective moves the kv_dim-wide
    # tensors (e.g. 1024) instead of the d_model-wide hidden (e.g. 7168)
    k = shard_hint(shard_hint(k, "act_qkv"), "act_kv")
    v = shard_hint(shard_hint(v, "act_qkv"), "act_kv")

    # an empty prefix (nothing cached yet) is plain causal attention over
    # the prompt, which the kernel below serves
    if prefix_kv is not None and prefix_kv[0].shape[1] > 0:
        assert causal and window == 0 and kv_x is None, \
            "prefix attention is causal global self-attention only"
        pk, pv = prefix_kv
        Tpad = pk.shape[1]
        mask = prefix_causal_mask(S, Tpad, prefix_len)
        out = mha_reference(q, jnp.concatenate([pk.astype(k.dtype), k], 1),
                            jnp.concatenate([pv.astype(v.dtype), v], 1),
                            mask=mask)
        out = shard_hint(out, "act_qkv")
        out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
        if return_kv:
            return out, (k, v)
        return out

    impl = cfg.attention_impl
    if impl.startswith("pallas") and kv_x is None and causal:
        from repro.kernels.flash_attention import ops as fa_ops
        out = fa_ops.flash_attention(
            q, k, v, causal=True, window=window,
            interpret=(impl == "pallas_interpret"))
    elif S * T >= _CHUNK_THRESHOLD:
        out = mha_kv_streamed(q, k, v, causal=causal, window=window,
                              offset=T - S)
    else:
        mask = causal_mask(S, T, offset=T - S, window=window) if causal \
            else None
        out = mha_reference(q, k, v, mask=mask)
    out = shard_hint(out, "act_qkv")
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    if return_kv:
        return out, (k, v)
    return out


# ---------------------------------------------------------------------------
# cached decode


def init_kv_cache(cfg, batch, capacity, dtype):
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        return {
            "k": jnp.zeros((batch, capacity, KVH, hd), jnp.int8),
            "v": jnp.zeros((batch, capacity, KVH, hd), jnp.int8),
            "k_scale": jnp.zeros((batch, capacity, KVH), jnp.float32),
            "v_scale": jnp.zeros((batch, capacity, KVH), jnp.float32),
        }
    return {
        "k": jnp.zeros((batch, capacity, KVH, hd), dtype),
        "v": jnp.zeros((batch, capacity, KVH, hd), dtype),
    }


def abstract_kv_cache(cfg, batch, capacity, dtype):
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    if cfg.kv_cache_dtype == "int8":
        st = jax.ShapeDtypeStruct((batch, capacity, KVH, hd), jnp.int8)
        sc = jax.ShapeDtypeStruct((batch, capacity, KVH), jnp.float32)
        return {"k": st, "v": st, "k_scale": sc, "v_scale": sc}
    st = jax.ShapeDtypeStruct((batch, capacity, KVH, hd), jnp.dtype(dtype))
    return {"k": st, "v": st}


def quantize_kv(x):
    """Per-(position, head) symmetric int8 (KIVI-style).  x: [..., hd] →
    (q int8 [..., hd], scale f32 [...])."""
    amax = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1)
    scale = amax / 127.0 + 1e-12
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize_kv(q, scale, dtype):
    # dequantize directly in the activation dtype: avoids materializing an
    # f32 copy of the whole cache on the XLA fallback path (the Pallas
    # decode kernel would dequantize in-register anyway)
    return q.astype(dtype) * scale[..., None].astype(dtype)


def pack_kv(cfg, k, v):
    """Cache leaves for freshly computed K/V [B,S,KVH,hd]."""
    if cfg.kv_cache_dtype == "int8":
        qk, sk = quantize_kv(k)
        qv, sv = quantize_kv(v)
        return {"k": qk, "v": qv, "k_scale": sk, "v_scale": sv}
    return {"k": k, "v": v}


def _write_slot(cache_arr, new, slots):
    """cache_arr: [B, C, KVH, hd]; new: [B, 1, KVH, hd]; slots: [B]."""
    def upd(c, n, s):
        return jax.lax.dynamic_update_slice(c, n, (s, 0, 0))
    return jax.vmap(upd)(cache_arr, new, slots)


def decode_attention(cfg, p, x, cache, positions, *, window=0):
    """One-token decode: x [B,1,D]; cache k/v [B,C,KVH,hd]; positions [B]
    is the index of the *current* token.  Returns (out [B,1,D], new_cache).

    For windowed attention the cache is a ring buffer of capacity = window;
    keys are stored post-RoPE so ring storage order is irrelevant given the
    validity mask.
    """
    B = x.shape[0]
    C = cache["k"].shape[1]
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    if cfg.use_rope:
        cos, sin = rope_cos_sin(positions[:, None], cfg.head_dim,
                                cfg.rope_theta, x.dtype)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    # head-dim placement for tensor-parallel serving (no-op without rules):
    # keeps the cache write and the attention itself local to each shard
    q = shard_hint(q, "act_qkv")
    k = shard_hint(k, "act_kv")
    v = shard_hint(v, "act_kv")
    slots = positions % C if window > 0 else positions
    packed = pack_kv(cfg, k, v)
    new_cache = {}
    for name, new in packed.items():
        if new.ndim == 3:  # scales [B,1,KVH]
            new_cache[name] = jax.vmap(
                lambda c, n, s: jax.lax.dynamic_update_slice(c, n, (s, 0))
            )(cache[name], new, slots)
        else:
            new_cache[name] = _write_slot(cache[name], new, slots)
    impl = cfg.attention_impl
    # validity is a prefix of the cache: a full cache holds slots
    # [0, pos]; a ring holds positions (pos-C, pos], i.e. every slot once
    # pos >= C
    lengths = jnp.minimum(positions + 1, C)
    if cfg.kv_cache_dtype == "int8":
        if impl.startswith("pallas"):
            # in-kernel dequantization: HBM reads stay int8
            from repro.kernels.decode_attention import ops as da_ops
            out = da_ops.decode_attention_int8(
                q, new_cache["k"], new_cache["v"], new_cache["k_scale"],
                new_cache["v_scale"], lengths,
                interpret=(impl == "pallas_interpret"))
            out = shard_hint(out, "act_qkv")
            out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
            return out, new_cache
        ck = dequantize_kv(new_cache["k"], new_cache["k_scale"], x.dtype)
        cv = dequantize_kv(new_cache["v"], new_cache["v_scale"], x.dtype)
    else:
        ck, cv = new_cache["k"], new_cache["v"]

    if impl.startswith("pallas"):
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(
            q, ck, cv, lengths, interpret=(impl == "pallas_interpret"))
    else:
        valid = jnp.arange(C)[None, :] < lengths[:, None]
        # [B,1,1,C] → broadcast over (k-heads, S)
        out = mha_reference(q, ck, cv, mask=valid[:, None, None, :])
    out = shard_hint(out, "act_qkv")
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_cache


# ---------------------------------------------------------------------------
# paged decode (block-paged KV pools, DESIGN.md §3.3)


def init_paged_kv_cache(cfg, num_pages, page_size, dtype):
    """Block-paged KV pool: [num_pages, page_size, KVH, hd] per leaf.  A
    sequence's cache is the pages its table references, so the pool's
    "batch" axis is the page axis — per-slot slabs disappear.  Gated to
    un-quantized global attention (the serving engine checks
    ``Model.prefix_seq_axes``)."""
    assert cfg.kv_cache_dtype != "int8", "paged KV requires unquantized KV"
    KVH, hd = cfg.num_kv_heads, cfg.head_dim
    return {
        "k": jnp.zeros((num_pages, page_size, KVH, hd), dtype),
        "v": jnp.zeros((num_pages, page_size, KVH, hd), dtype),
    }


def paged_decode_attention(cfg, p, x, pools, layer, positions, page_table):
    """One-token decode over paged KV at layer ``layer`` (int or traced
    scalar) of the stacked pools: x [B,1,D]; pools k/v
    [L,P,ps,KVH,hd]; positions [B] (index of the current token);
    page_table [B,N] int32 — entry n holds the pool page storing positions
    [n·ps, (n+1)·ps).  Returns (out [B,1,D], new_pools).

    The current token's K/V is scatter-written into layer ``layer``, page
    ``table[b, pos // ps]``, at offset ``pos % ps`` (always a slot-private
    page: shared prefix pages are full by construction, so decode never
    writes into them).  Retired slots point every table entry at the
    reserved scratch page 0, where their dead writes land harmlessly.
    Nothing here takes the layer's slice of the pool, so a caller that
    carries the donated pools through its layer loop updates them in
    place (DESIGN.md §3.3).
    """
    B = x.shape[0]
    ps = pools["k"].shape[2]
    N = page_table.shape[1]
    q = _project_q(cfg, p, x)
    k, v = _project_kv(cfg, p, x)
    if cfg.use_rope:
        cos, sin = rope_cos_sin(positions[:, None], cfg.head_dim,
                                cfg.rope_theta, x.dtype)
        q = apply_rope(q, cos, sin)
        k = apply_rope(k, cos, sin)
    # tensor-parallel serving: heads over the model axis — the page-pool
    # leaves carry the matching KVH sharding (sharding.rules cache_pspecs
    # layout="paged"), so the scatter below stays shard-local
    q = shard_hint(q, "act_qkv")
    k = shard_hint(k, "act_kv")
    v = shard_hint(v, "act_kv")
    page_ids = jnp.take_along_axis(
        page_table, jnp.minimum(positions // ps, N - 1)[:, None], axis=1
    )[:, 0]
    offs = positions % ps
    new_pools = {
        "k": pools["k"].at[layer, page_ids, offs].set(
            k[:, 0].astype(pools["k"].dtype)),
        "v": pools["v"].at[layer, page_ids, offs].set(
            v[:, 0].astype(pools["v"].dtype)),
    }
    lengths = positions + 1
    impl = cfg.attention_impl
    if impl.startswith("pallas"):
        # the kernel takes one layer's pool: this slice is a per-layer
        # copy until the kernel takes the layer index as an operand
        from repro.kernels.paged_attention import ops as pa_ops
        out = pa_ops.paged_decode_attention(
            q, new_pools["k"][layer], new_pools["v"][layer], page_table,
            lengths, interpret=(impl == "pallas_interpret"))
    else:
        # XLA gather fallback: dense [B, N·ps] view of the referenced
        # pages, gathered straight from the stacked pool, + the
        # contiguous path's mha_reference — with N·ps equal to the
        # contiguous capacity and an identical validity mask, the logits
        # are bitwise those of the contiguous engine
        ck = new_pools["k"][layer, page_table].reshape(
            B, N * ps, -1, cfg.head_dim)
        cv = new_pools["v"][layer, page_table].reshape(
            B, N * ps, -1, cfg.head_dim)
        valid = jnp.arange(N * ps)[None, :] < lengths[:, None]
        out = mha_reference(q, ck, cv, mask=valid[:, None, None, :])
    out = shard_hint(out, "act_qkv")
    out = jnp.einsum("bshk,hkd->bsd", out, p["wo"].astype(x.dtype))
    return out, new_pools


def cross_attention_cache(cfg, p, enc_out):
    """Precompute cross-attention K/V from encoder output (whisper decode)."""
    k, v = _project_kv(cfg, p, enc_out)
    return {"k": k, "v": v}
