"""Jit'd public wrapper for decode attention (inference-only: no VJP)."""

from __future__ import annotations

from .kernel import decode_attention_fwd, decode_attention_int8_fwd


def decode_attention(q, k, v, lengths, *, block_kv=256, interpret=False):
    """q: [B,1,H,d]; k,v: [B,C,KVH,d]; lengths: [B] int32 valid-prefix
    lengths → [B,1,H,d]."""
    return decode_attention_fwd(q, k, v, lengths, block_kv=block_kv,
                                interpret=interpret)


def decode_attention_int8(q, k_q, v_q, k_scale, v_scale, lengths, *,
                          block_kv=256, interpret=False):
    """int8-KV decode attention with in-kernel dequantization."""
    return decode_attention_int8_fwd(q, k_q, v_q, k_scale, v_scale, lengths,
                                     block_kv=block_kv, interpret=interpret)
