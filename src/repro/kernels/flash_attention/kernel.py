"""Flash attention forward kernel (TPU Pallas).

TPU-native adaptation of FlashAttention [arXiv:2205.14135]: online-softmax
tiles sized for VMEM with MXU-aligned (multiples of 128) matmul dims, not a
CUDA warp port.  Grid is (batch, q_heads, q_blocks, kv_blocks) with the
kv_blocks dimension innermost so the output block revisits across kv steps;
running max / sum / accumulator live in VMEM scratch and are initialized at
the first kv block and finalized at the last (the canonical TPU Pallas
accumulation pattern).  GQA is handled in the k/v BlockSpec index maps
(q head h reads kv head h // group).  Causal and sliding-window masks are
applied per tile; fully-masked tiles short-circuit.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _fa_kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
               block_q, block_kv, n_kv, seq_q, seq_kv, causal, window,
               scale):
    qi = pl.program_id(2)
    ki = pl.program_id(3)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = qi * block_q
    k_start = ki * block_kv

    run = True
    if causal:
        # tile is live unless entirely above the diagonal
        run = k_start <= q_start + block_q - 1
    if window > 0:
        run = jnp.logical_and(
            run, k_start + block_kv - 1 > q_start - window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)          # [block_q, d]
        k = k_ref[0, 0].astype(jnp.float32)          # [block_kv, d]
        v = v_ref[0, 0].astype(jnp.float32)
        # zero OOB v rows: block padding may be NaN and 0·NaN = NaN in the
        # p@v reduction
        # (a 2-D iota: Mosaic cannot reshape a 1-D mask into a column)
        vrow = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_kv, v.shape[1]), 0)
        v = jnp.where(vrow < seq_kv, v, 0.0)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale  # [bq, bkv]

        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32,
                                                  (block_q, block_kv), 1)
        mask = kpos < seq_kv
        if causal:
            mask &= kpos <= qpos
        if window > 0:
            mask &= kpos > qpos - window
        s = jnp.where(mask, s, NEG_INF)

        m_prev = m_scr[...]                           # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        # explicit zero for masked columns: OOB v-rows may be NaN-padded
        p = jnp.where(mask, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * alpha + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ki == n_kv - 1)
    def _finalize():
        denom = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / denom).astype(o_ref.dtype)


def flash_attention_fwd(q, k, v, *, causal=True, window=0, block_q=128,
                        block_kv=128, interpret=False):
    """q: [B, S, H, d]; k, v: [B, T, KVH, d] → [B, S, H, d]."""
    B, S, H, d = q.shape
    T, KVH = k.shape[1], k.shape[2]
    group = H // KVH
    block_q = min(block_q, S)
    block_kv = min(block_kv, T)
    n_q = pl.cdiv(S, block_q)
    n_kv = pl.cdiv(T, block_kv)
    scale = d ** -0.5

    # layout: heads-major so each grid step reads one (head, tile)
    qt = q.transpose(0, 2, 1, 3)   # [B, H, S, d]
    kt = k.transpose(0, 2, 1, 3)   # [B, KVH, T, d]
    vt = v.transpose(0, 2, 1, 3)

    kernel = functools.partial(
        _fa_kernel, block_q=block_q, block_kv=block_kv, n_kv=n_kv,
        seq_q=S, seq_kv=T, causal=causal, window=window, scale=scale)

    out = pl.pallas_call(
        kernel,
        grid=(B, H, n_q, n_kv),
        in_specs=[
            pl.BlockSpec((1, 1, block_q, d),
                         lambda b, h, qi, ki: (b, h, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
            pl.BlockSpec((1, 1, block_kv, d),
                         lambda b, h, qi, ki, g=group: (b, h // g, ki, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, block_q, d),
                               lambda b, h, qi, ki: (b, h, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((B, H, S, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),   # running max m
            pltpu.VMEM((block_q, 1), jnp.float32),   # running sum l
            pltpu.VMEM((block_q, d), jnp.float32),   # output accumulator
        ],
        interpret=interpret,
    )(qt, kt, vt)
    return out.transpose(0, 2, 1, 3)
