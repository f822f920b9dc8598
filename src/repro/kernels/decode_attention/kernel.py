"""Decode attention kernel (TPU Pallas) — flash-decoding-style KV split.

One new query token per sequence attends a long KV cache.  At decode shapes
the MXU is batch-starved, so the kernel splits the *cache length* across
grid steps (split-K): grid (batch, kv_heads, kv_blocks), each step streams
one [block_kv, d] tile of K/V through VMEM against the [G, d] query block
of that KV head's q-group (GQA folded into the q BlockSpec), maintaining
online-softmax partials in VMEM scratch.

Validity is a per-sequence length, scalar-prefetched into SMEM: slot j is
valid iff j < lengths[b].  That covers a partially filled cache and a ring
buffer alike (a full ring has length C), masks by 2-D iotas in the
kernel, and lets blocks past the length skip their compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _online_softmax_step(s, v, row_ok, m_scr, l_scr, acc_scr, p_scale=None):
    """Fold one [G, block_kv] score tile into the running partials.
    ``p_scale`` ([1, block_kv]) rescales the probabilities feeding p·v
    only (the int8 kernel's per-position v scales)."""
    s = jnp.where(row_ok, s, NEG_INF)
    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
    # explicit zero for masked columns: OOB v-rows may be NaN-padded
    p = jnp.where(row_ok, jnp.exp(s - m_new), 0.0)
    alpha = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
    # the scales of OOB positions may be NaN-padded too: 0 * NaN is NaN
    pv = p if p_scale is None else jnp.where(row_ok, p * p_scale, 0.0)
    acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
        pv, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_scr[...] = m_new


def _masks(start, length, G, block_kv, d):
    """Row mask for the [G, block_kv] scores and column mask for the
    [block_kv, d] v tile, both from 2-D iotas."""
    row = start + jax.lax.broadcasted_iota(jnp.int32, (G, block_kv), 1)
    col = start + jax.lax.broadcasted_iota(jnp.int32, (block_kv, d), 0)
    return row < length, col < length


def _dec_kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                acc_scr, *, block_kv, n_kv, scale):
    b = pl.program_id(0)
    ki = pl.program_id(2)
    length = len_ref[b]
    start = ki * block_kv

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [block_kv, d]
        v = v_ref[0, 0].astype(jnp.float32)
        row_ok, col_ok = _masks(start, length, q.shape[0], block_kv,
                                v.shape[1])
        # zero invalid v rows: NaN padding/uninitialized slots would
        # poison p@v
        v = jnp.where(col_ok, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        _online_softmax_step(s, v, row_ok, m_scr, l_scr, acc_scr)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_fwd(q, k, v, lengths, *, block_kv=256,
                         interpret=False):
    """q: [B, 1, H, d]; k,v: [B, C, KVH, d]; lengths: [B] int32 (slot j of
    row b is valid iff j < lengths[b]) → [B, 1, H, d]."""
    B, _, H, d = q.shape
    C, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    block_kv = min(block_kv, C)
    n_kv = pl.cdiv(C, block_kv)
    scale = d ** -0.5

    # [B, KVH, G, d] — the q-group of each kv head; q layout is
    # h = kv_head * G + g (the models' reshape convention)
    qt = q[:, 0].reshape(B, KVH, G, d)
    kt = k.transpose(0, 2, 1, 3)                   # [B, KVH, C, d]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(_dec_kernel, block_kv=block_kv, n_kv=n_kv,
                               scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KVH, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, ki, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, ki, ln: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, ki, ln: (b, h, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d),
                               lambda b, h, ki, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qt, kt, vt)
    return out.reshape(B, 1, H, d)


def _dec_int8_kernel(len_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref, o_ref,
                     m_scr, l_scr, acc_scr, *, block_kv, n_kv, scale):
    """int8-KV variant: K/V arrive quantized (per-vector scales) and are
    dequantized in-register after the VMEM load — HBM traffic is the int8
    payload + one f32 scale per (position, head), ~2× less than bf16.  A
    per-position scale multiplies a score column (k) or a probability
    column (v), so it is applied to the [G, block_kv] tiles, never as a
    column vector."""
    b = pl.program_id(0)
    ki = pl.program_id(2)
    length = len_ref[b]
    start = ki * block_kv

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(start < length)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [G, d]
        k = k_ref[0, 0].astype(jnp.float32)            # [block_kv, d]
        v = v_ref[0, 0].astype(jnp.float32)
        ksc = ks_ref[0, 0].astype(jnp.float32)         # [1, block_kv]
        vsc = vs_ref[0, 0].astype(jnp.float32)
        row_ok, col_ok = _masks(start, length, q.shape[0], block_kv,
                                v.shape[1])
        v = jnp.where(col_ok, v, 0.0)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        _online_softmax_step(s * ksc * scale, v, row_ok, m_scr, l_scr,
                             acc_scr, p_scale=vsc)

    @pl.when(ki == n_kv - 1)
    def _finalize():
        o_ref[0, 0] = (acc_scr[...]
                       / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def decode_attention_int8_fwd(q, k_q, v_q, k_scale, v_scale, lengths, *,
                              block_kv=256, interpret=False):
    """q: [B,1,H,d]; k_q,v_q: [B,C,KVH,d] int8; scales: [B,C,KVH] f32;
    lengths: [B] int32 → [B,1,H,d]."""
    B, _, H, d = q.shape
    C, KVH = k_q.shape[1], k_q.shape[2]
    G = H // KVH
    block_kv = min(block_kv, C)
    n_kv = pl.cdiv(C, block_kv)
    scale = d ** -0.5

    qt = q[:, 0].reshape(B, KVH, G, d)
    kt = k_q.transpose(0, 2, 1, 3)                 # [B,KVH,C,d] int8
    vt = v_q.transpose(0, 2, 1, 3)
    # [B,KVH,1,C]: a (1, block_kv) block over the trailing dims is legal
    kst = k_scale.transpose(0, 2, 1)[:, :, None, :]
    vst = v_scale.transpose(0, 2, 1)[:, :, None, :]

    kernel = functools.partial(_dec_int8_kernel, block_kv=block_kv,
                               n_kv=n_kv, scale=scale)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(B, KVH, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, G, d), lambda b, h, ki, ln: (b, h, 0, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, ki, ln: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, ki, ln: (b, h, ki, 0)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b, h, ki, ln: (b, h, 0, ki)),
            pl.BlockSpec((1, 1, 1, block_kv),
                         lambda b, h, ki, ln: (b, h, 0, ki)),
        ],
        out_specs=pl.BlockSpec((1, 1, G, d),
                               lambda b, h, ki, ln: (b, h, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, 1), jnp.float32),
            pltpu.VMEM((G, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, d), q.dtype),
        interpret=interpret,
    )(lengths.astype(jnp.int32), qt, kt, vt, kst, vst)
    return out.reshape(B, 1, H, d)
