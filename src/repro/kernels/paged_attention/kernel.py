"""Paged decode-attention kernel (TPU Pallas) — page-table gather at decode.

The serving engine stores KV in fixed-size *pages* (a pool of
[num_pages, page_size, KVH, d] blocks) with a per-sequence page table
instead of one contiguous [max_len] slab per slot.  At decode, each grid
step streams one whole page (every KV head) of K/V through VMEM: the page
table and the per-sequence lengths ride in as *scalar-prefetched* operands
(``pltpu.PrefetchScalarGridSpec``), so the K/V BlockSpec index maps read
``page_table[b, pi]`` to pick which pool block the DMA fetches — the
gather happens in the memory system, never materializing a contiguous
copy of the cache.

Grid (batch, n_pages).  A block spans the pool's trailing (KVH, d) dims
in full, which is what the TPU's (8, 128) tiling rule admits for any head
count and head_dim (a one-head block would put a 1 in the tiled
second-minor dim).  One query per head makes the scores a batched
mat-vec, so the math is VPU work on [KVH, d] tiles: each page position
contributes a [KVH, 1] score column, and the online-softmax split-K
accumulation (flash-decoding) runs with the split at page granularity.
Validity is a scalar per position (``pos < length``), and pages wholly
outside the valid range skip their compute.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1.0e30


def _paged_kernel(pt_ref, len_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr,
                  acc_scr, *, page_size, n_pages, groups, scale, window):
    b = pl.program_id(0)
    pi = pl.program_id(1)
    length = len_ref[b]
    lo = length - window if window > 0 else 0
    start = pi * page_size

    @pl.when(pi == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(jnp.logical_and(start < length, start + page_size > lo))
    def _compute():
        qs = [q_ref[0, g].astype(jnp.float32) * scale        # [KVH, d]
              for g in range(groups)]
        # per-position validity is a scalar predicate: selecting on it
        # changes no boolean vector's shape inside the kernel.  Stale or
        # unwritten slots (a recycled page's tail, scratch page 0) may hold
        # anything, non-finite values included, so invalid scores and v
        # rows are selected away, never multiplied by 0.
        oks, vs, scores = [], [], [[] for _ in range(groups)]
        for j in range(page_size):
            pos = start + j
            ok = jnp.logical_and(pos < length, pos >= lo)
            oks.append(ok)
            k = k_ref[0, j].astype(jnp.float32)              # [KVH, d]
            vs.append(jnp.where(ok, v_ref[0, j].astype(jnp.float32), 0.0))
            for g in range(groups):
                s = jnp.sum(qs[g] * k, axis=-1, keepdims=True)  # [KVH, 1]
                scores[g].append(jnp.where(ok, s, NEG_INF))
        for g in range(groups):
            m_prev = m_scr[g]                                # [KVH, 1]
            m_new = functools.reduce(jnp.maximum, scores[g], m_prev)
            alpha = jnp.exp(m_prev - m_new)
            l_new = l_scr[g] * alpha
            acc = acc_scr[g] * alpha
            for j in range(page_size):
                p = jnp.where(oks[j], jnp.exp(scores[g][j] - m_new), 0.0)
                l_new = l_new + p
                acc = acc + p * vs[j]
            m_scr[g] = m_new
            l_scr[g] = l_new
            acc_scr[g] = acc

    @pl.when(pi == n_pages - 1)
    def _finalize():
        o_ref[0] = (acc_scr[...]
                    / jnp.maximum(l_scr[...], 1e-30)).astype(o_ref.dtype)


def paged_decode_attention_fwd(q, k_pages, v_pages, page_table, lengths, *,
                               window=0, interpret=False):
    """q: [B,1,H,d]; k_pages,v_pages: [P,ps,KVH,d]; page_table: [B,N] int32;
    lengths: [B] int32 → [B,1,H,d]."""
    B, _, H, d = q.shape
    ps, KVH = k_pages.shape[1], k_pages.shape[2]
    N = page_table.shape[1]
    G = H // KVH
    scale = d ** -0.5

    # [B, G, KVH, d] — head h = kv_head * G + g (the models' convention),
    # group-major so each group's queries are one [KVH, d] tile
    qt = q[:, 0].reshape(B, KVH, G, d).transpose(0, 2, 1, 3)

    kernel = functools.partial(_paged_kernel, page_size=ps, n_pages=N,
                               groups=G, scale=scale, window=window)
    # page_table / lengths are scalar-prefetched: available to the K/V
    # index maps, which select pool block pt[b, pi] for grid step (b, pi)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(B, N),
        in_specs=[
            pl.BlockSpec((1, G, KVH, d), lambda b, pi, pt, ln: (b, 0, 0, 0)),
            pl.BlockSpec((1, ps, KVH, d),
                         lambda b, pi, pt, ln: (pt[b, pi], 0, 0, 0)),
            pl.BlockSpec((1, ps, KVH, d),
                         lambda b, pi, pt, ln: (pt[b, pi], 0, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, G, KVH, d),
                               lambda b, pi, pt, ln: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((G, KVH, 1), jnp.float32),
            pltpu.VMEM((G, KVH, 1), jnp.float32),
            pltpu.VMEM((G, KVH, d), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, G, KVH, d), q.dtype),
        interpret=interpret,
    )(page_table.astype(jnp.int32), lengths.astype(jnp.int32),
      qt, k_pages, v_pages)
    return out.transpose(0, 2, 1, 3).reshape(B, 1, H, d)
