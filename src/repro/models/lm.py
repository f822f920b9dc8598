"""Decoder-only language model assembly for the dense / MoE / VLM / hybrid /
SSM families.

Layers iterate under ``jax.lax.scan`` with stacked parameters (compile-time
feasibility at 40–64 layers × 512 devices); hybrid architectures scan over
*super-blocks* of the repeating pattern (e.g. RecurrentGemma's
(rglru, rglru, attn)) with any remainder blocks unrolled."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import attention as att
from . import mlp as mlpmod
from . import moe as moemod
from . import rglru as rgmod
from . import ssd as ssdmod
from .common import (
    PSpec,
    apply_norm,
    norm_schema,
    shard_hint,
    stack_schema,
)


# ---------------------------------------------------------------------------
# block schemas


def block_kinds(cfg) -> list[str]:
    """The per-layer block kinds, in order."""
    if cfg.family in ("dense", "vlm"):
        return ["attn_mlp"] * cfg.num_layers
    if cfg.family == "moe":
        return ["attn_moe"] * cfg.num_layers
    if cfg.family == "ssm":
        return ["ssd"] * cfg.num_layers
    if cfg.family == "hybrid":
        pat = list(cfg.block_pattern)
        kinds = []
        while len(kinds) < cfg.num_layers:
            kinds.extend(pat)
        return [("rglru_mlp" if k == "rglru" else "attn_mlp_local")
                for k in kinds[:cfg.num_layers]]
    raise ValueError(cfg.family)


def block_schema(cfg, kind: str) -> dict:
    if kind == "attn_mlp":
        return {"ln1": norm_schema(cfg), "attn": att.attn_schema(cfg),
                "ln2": norm_schema(cfg), "mlp": mlpmod.mlp_schema(cfg)}
    if kind == "attn_mlp_local":
        return {"ln1": norm_schema(cfg), "attn": att.attn_schema(cfg),
                "ln2": norm_schema(cfg), "mlp": mlpmod.mlp_schema(cfg)}
    if kind == "attn_moe":
        return {"ln1": norm_schema(cfg), "attn": att.attn_schema(cfg),
                "ln2": norm_schema(cfg), "moe": moemod.moe_schema(cfg)}
    if kind == "rglru_mlp":
        return {"ln1": norm_schema(cfg), "rglru": rgmod.rglru_schema(cfg),
                "ln2": norm_schema(cfg), "mlp": mlpmod.mlp_schema(cfg)}
    if kind == "ssd":
        return {"ln1": norm_schema(cfg), "ssd": ssdmod.ssd_schema(cfg)}
    raise ValueError(kind)


def _layer_groups(cfg):
    """(group_kinds, n_groups, tail_kinds): scan over n_groups super-blocks
    of group_kinds, then unroll tail_kinds."""
    kinds = block_kinds(cfg)
    if cfg.family == "hybrid":
        pat_len = len(cfg.block_pattern)
        n_groups = cfg.num_layers // pat_len
        tail = kinds[n_groups * pat_len:]
        return kinds[:pat_len], n_groups, tail
    return [kinds[0]], cfg.num_layers, []


def lm_schema(cfg) -> dict:
    V, D = cfg.vocab_padded, cfg.d_model
    group_kinds, n_groups, tail_kinds = _layer_groups(cfg)
    group = {f"b{i}": block_schema(cfg, k) for i, k in enumerate(group_kinds)}
    s = {
        "embed": PSpec((V, D), ("vocab", "embed"), "embed"),
        "final_norm": norm_schema(cfg),
    }
    if not cfg.tie_embeddings:
        s["lm_head"] = PSpec((D, V), ("embed", "vocab"))
    if cfg.scan_layers:
        s["layers"] = stack_schema(group, n_groups)
    else:
        s["layers"] = {f"g{i}": group for i in range(n_groups)}
    for i, k in enumerate(tail_kinds):
        s[f"tail{i}"] = block_schema(cfg, k)
    return s


# ---------------------------------------------------------------------------
# block application (full sequence)


def apply_block(cfg, kind, p, h, *, positions, aux_sum):
    if kind in ("attn_mlp", "attn_mlp_local", "attn_moe"):
        window = cfg.attn_window if kind == "attn_mlp_local" else 0
        a = att.full_attention(cfg, p["attn"], apply_norm(cfg, p["ln1"], h),
                               positions=positions, causal=True,
                               window=window)
        h = h + a
        x = apply_norm(cfg, p["ln2"], h)
        if kind == "attn_moe":
            m, aux = moemod.apply_moe(cfg, p["moe"], x)
            aux_sum = aux_sum + aux
        else:
            m = mlpmod.apply_mlp(cfg, p["mlp"], x)
        h = h + m
    elif kind == "rglru_mlp":
        r = rgmod.apply_rglru(cfg, p["rglru"], apply_norm(cfg, p["ln1"], h))
        h = h + r
        h = h + mlpmod.apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], h))
    elif kind == "ssd":
        h = h + ssdmod.apply_ssd(cfg, p["ssd"], apply_norm(cfg, p["ln1"], h))
    else:  # pragma: no cover
        raise ValueError(kind)
    return shard_hint(h, "act_hidden"), aux_sum


def _remat(cfg, fn):
    if cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        policy = jax.checkpoint_policies.checkpoint_dots_with_no_batch_dims
        return jax.checkpoint(fn, policy=policy)
    return jax.checkpoint(fn)


def backbone(cfg, params, h, positions):
    """Apply all layers to hidden states h [B,S,D] → (h, aux_loss)."""
    group_kinds, n_groups, tail_kinds = _layer_groups(cfg)

    def group_fn(carry, gp):
        h, aux = carry
        for i, kind in enumerate(group_kinds):
            h, aux = apply_block(cfg, kind, gp[f"b{i}"], h,
                                 positions=positions, aux_sum=aux)
        return (h, aux), None

    group_fn = _remat(cfg, group_fn)
    aux0 = jnp.zeros((), jnp.float32)
    if cfg.scan_layers:
        (h, aux), _ = jax.lax.scan(group_fn, (h, aux0), params["layers"])
    else:
        carry = (h, aux0)
        for i in range(n_groups):
            carry, _ = group_fn(carry, params["layers"][f"g{i}"])
        h, aux = carry
    for i, kind in enumerate(tail_kinds):
        h, aux = apply_block(cfg, kind, params[f"tail{i}"], h,
                             positions=positions, aux_sum=aux)
    return h, aux


def embed_inputs(cfg, params, batch):
    tokens = batch["tokens"]
    B, S = tokens.shape
    h = params["embed"].astype(cfg.activation_dtype)[tokens]
    if cfg.frontend == "patch_stub" and "patch_embeds" in batch:
        pe = batch["patch_embeds"].astype(h.dtype)
        n = pe.shape[1]
        h = jnp.concatenate([pe, h[:, n:]], axis=1)
    positions = jnp.arange(S, dtype=jnp.int32)[None, :].repeat(B, 0)
    return shard_hint(h, "act_hidden"), positions


def logits_from_hidden(cfg, params, h):
    if cfg.tie_embeddings:
        w = params["embed"].astype(h.dtype)  # [V,D]
        logits = jnp.einsum("bsd,vd->bsv", h, w)
    else:
        logits = jnp.einsum("bsd,dv->bsv", h,
                            params["lm_head"].astype(h.dtype))
    logits = mask_vocab_padding(cfg, logits)
    return shard_hint(logits, "act_logits")


def mask_vocab_padding(cfg, logits):
    if cfg.vocab_padded == cfg.vocab_size:
        return logits
    pad_mask = jnp.arange(cfg.vocab_padded) < cfg.vocab_size
    return jnp.where(pad_mask, logits, -1e30)


def forward(cfg, params, batch):
    """Teacher-forcing forward → (logits [B,S,V], aux_loss)."""
    h, positions = embed_inputs(cfg, params, batch)
    h, aux = backbone(cfg, params, h, positions)
    h = apply_norm(cfg, params["final_norm"], h)
    return logits_from_hidden(cfg, params, h), aux


# ---------------------------------------------------------------------------
# caches


def block_cache(cfg, kind, batch, capacity, dtype, abstract):
    if kind == "attn_mlp" or kind == "attn_moe":
        f = att.abstract_kv_cache if abstract else att.init_kv_cache
        return f(cfg, batch, capacity, dtype)
    if kind == "attn_mlp_local":
        cap = min(capacity, cfg.attn_window) if cfg.attn_window else capacity
        f = att.abstract_kv_cache if abstract else att.init_kv_cache
        return f(cfg, batch, cap, dtype)
    if kind == "rglru_mlp":
        f = rgmod.abstract_rglru_cache if abstract else rgmod.init_rglru_cache
        return f(cfg, batch, dtype)
    if kind == "ssd":
        f = ssdmod.abstract_ssd_cache if abstract else ssdmod.init_ssd_cache
        return f(cfg, batch, dtype)
    raise ValueError(kind)


def _stack_cache(tree_list):
    return jax.tree.map(lambda *xs: jnp.stack(xs), *tree_list)


def _abstract_stack(tree, n):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct((n,) + s.shape, s.dtype), tree)


def init_cache(cfg, batch, capacity, *, abstract=False):
    """Cache pytree mirroring the layer grouping."""
    dtype = cfg.activation_dtype
    group_kinds, n_groups, tail_kinds = _layer_groups(cfg)
    group = {f"b{i}": block_cache(cfg, k, batch, capacity, dtype, abstract)
             for i, k in enumerate(group_kinds)}
    if abstract:
        stacked = _abstract_stack(group, n_groups)
    else:
        stacked = jax.tree.map(
            lambda s: jnp.broadcast_to(s, (n_groups,) + s.shape).copy(),
            group)
    cache = {"layers": stacked}
    for i, k in enumerate(tail_kinds):
        cache[f"tail{i}"] = block_cache(cfg, k, batch, capacity, dtype,
                                        abstract)
    return cache


def init_paged_cache(cfg, num_pages, page_size):
    """Block-paged KV pool pytree: {"layers": [n_groups, P, ps, KVH, hd]}
    leaves (same structure as :func:`init_cache` with the batch axis
    reinterpreted as the page axis).  Attention-only families — recurrent /
    hybrid / windowed state has no positional page decomposition."""
    group_kinds, n_groups, tail_kinds = _layer_groups(cfg)
    if tail_kinds or any(k not in ("attn_mlp", "attn_moe")
                         for k in group_kinds):
        raise ValueError(
            f"{cfg.name}: paged KV requires uniform global-attention "
            f"blocks, got {group_kinds} + tail {tail_kinds}")
    dtype = cfg.activation_dtype
    group = {f"b{i}": att.init_paged_kv_cache(cfg, num_pages, page_size,
                                              dtype)
             for i, k in enumerate(group_kinds)}
    stacked = jax.tree.map(
        lambda s: jnp.broadcast_to(s, (n_groups,) + s.shape).copy(), group)
    return {"layers": stacked}


# ---------------------------------------------------------------------------
# decode


def decode_block(cfg, kind, p, h, cache, positions):
    if kind in ("attn_mlp", "attn_mlp_local", "attn_moe"):
        window = cfg.attn_window if kind == "attn_mlp_local" else 0
        a, new_kv = att.decode_attention(
            cfg, p["attn"], apply_norm(cfg, p["ln1"], h), cache, positions,
            window=window)
        h = h + a
        x = apply_norm(cfg, p["ln2"], h)
        if kind == "attn_moe":
            m, _ = moemod.apply_moe(cfg, p["moe"], x)
        else:
            m = mlpmod.apply_mlp(cfg, p["mlp"], x)
        return h + m, new_kv
    if kind == "rglru_mlp":
        r, new_c = rgmod.decode_rglru(cfg, p["rglru"],
                                      apply_norm(cfg, p["ln1"], h), cache)
        h = h + r
        h = h + mlpmod.apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["ln2"], h))
        return h, new_c
    if kind == "ssd":
        s, new_c = ssdmod.decode_ssd(cfg, p["ssd"],
                                     apply_norm(cfg, p["ln1"], h), cache)
        return h + s, new_c
    raise ValueError(kind)


def decode_step(cfg, params, cache, tokens, positions):
    """One decode step: tokens [B,1], positions [B] (current index).
    Returns (logits [B,V], new_cache)."""
    B = tokens.shape[0]
    h = params["embed"].astype(cfg.activation_dtype)[tokens]
    h = shard_hint(h, "act_hidden")
    group_kinds, n_groups, tail_kinds = _layer_groups(cfg)

    def group_fn(h, inp):
        gp, gcache = inp
        new_caches = {}
        for i, kind in enumerate(group_kinds):
            h, nc = decode_block(cfg, kind, gp[f"b{i}"], h,
                                 gcache[f"b{i}"], positions)
            new_caches[f"b{i}"] = nc
        return h, new_caches

    if cfg.scan_layers:
        h, new_stacked = jax.lax.scan(
            group_fn, h, (params["layers"], cache["layers"]))
    else:
        new_list = []
        for i in range(n_groups):
            h, nc = group_fn(h, (params["layers"][f"g{i}"],
                                 jax.tree.map(lambda c: c[i],
                                              cache["layers"])))
            new_list.append(nc)
        new_stacked = _stack_cache(new_list)
    new_cache = {"layers": new_stacked}
    for i, kind in enumerate(tail_kinds):
        h, nc = decode_block(cfg, kind, params[f"tail{i}"], h,
                             cache[f"tail{i}"], positions)
        new_cache[f"tail{i}"] = nc
    h = apply_norm(cfg, params["final_norm"], h)
    logits = logits_from_hidden(cfg, params, h)
    return logits[:, 0], new_cache


def decode_step_paged(cfg, params, cache, tokens, positions, page_table):
    """One decode step over block-paged KV pools: tokens [B,1], positions
    [B], page_table [B,N] int32 (shared by every layer — pages are
    allocated per sequence, and each layer's pool leaf stores that
    sequence's pages at the same ids).  Returns (logits [B,V],
    new_cache).

    The stacked pools ride in the layer loop's carry and each layer
    scatters its new token into them at its own index: with the pools
    donated, the step updates them in place and copies no layer of them
    (DESIGN.md §3.3)."""
    h = params["embed"].astype(cfg.activation_dtype)[tokens]
    h = shard_hint(h, "act_hidden")
    group_kinds, n_groups, _ = _layer_groups(cfg)

    def group_fn(carry, inp):
        h, pools = carry
        gp, layer = inp
        pools = dict(pools)
        for i, kind in enumerate(group_kinds):
            p = gp[f"b{i}"]
            a, pools[f"b{i}"] = att.paged_decode_attention(
                cfg, p["attn"], apply_norm(cfg, p["ln1"], h),
                pools[f"b{i}"], layer, positions, page_table)
            h = h + a
            x = apply_norm(cfg, p["ln2"], h)
            if kind == "attn_moe":
                m, _ = moemod.apply_moe(cfg, p["moe"], x)
            else:
                m = mlpmod.apply_mlp(cfg, p["mlp"], x)
            h = h + m
        return (h, pools), None

    carry = (h, cache["layers"])
    if cfg.scan_layers:
        carry, _ = jax.lax.scan(
            group_fn, carry, (params["layers"], jnp.arange(n_groups)))
    else:
        for i in range(n_groups):
            carry, _ = group_fn(carry, (params["layers"][f"g{i}"], i))
    h, pools = carry
    h = apply_norm(cfg, params["final_norm"], h)
    logits = logits_from_hidden(cfg, params, h)
    return logits[:, 0], {"layers": pools}


# ---------------------------------------------------------------------------
# prefill (full-sequence forward that also fills the cache)


def prefill(cfg, params, batch, capacity, *, prefix=None, prefix_len=None,
            last_index=None):
    """Run the prompt through the model, returning (last_logits [B,V],
    cache filled up to S).  For recurrent blocks the cache holds the final
    state; for attention blocks the K/V of every position.

    Prefix-aware mode (serving radix cache, attention-only families):
    ``prefix`` is a cache pytree of already-prefilled K/V for the first
    ``prefix_len`` prompt positions (zero-padded along the sequence axis to
    a bucketed static length); the batch then holds only the prompt
    *suffix*, whose positions start at ``prefix_len`` (traced), and the
    returned cache covers the suffix alone.  ``last_index`` (traced)
    selects which suffix position's logits to return (for pad-to-bucket
    prompts); default is the last.
    """
    tokens = batch["tokens"]
    B, S = tokens.shape
    h, positions = embed_inputs(cfg, params, batch)
    if prefix_len is not None:
        positions = positions + jnp.asarray(prefix_len, jnp.int32)
    group_kinds, n_groups, tail_kinds = _layer_groups(cfg)
    dtype = cfg.activation_dtype

    def fill_block(cfg, kind, p, h, positions, pfx=None):
        if kind in ("attn_mlp", "attn_mlp_local", "attn_moe"):
            window = cfg.attn_window if kind == "attn_mlp_local" else 0
            xn = apply_norm(cfg, p["ln1"], h)
            a, (k, v) = att.full_attention(
                cfg, p["attn"], xn, positions=positions, causal=True,
                window=window, return_kv=True,
                prefix_kv=(pfx["k"], pfx["v"]) if pfx is not None else None,
                prefix_len=prefix_len)
            h = h + a
            x = apply_norm(cfg, p["ln2"], h)
            if kind == "attn_moe":
                m, _ = moemod.apply_moe(cfg, p["moe"], x)
            else:
                m = mlpmod.apply_mlp(cfg, p["mlp"], x)
            h = h + m
            cap = (min(capacity, cfg.attn_window)
                   if kind == "attn_mlp_local" and cfg.attn_window
                   else capacity)
            packed = att.pack_kv(cfg, k, v)
            return h, {name: _seq_to_cache(leaf, cap, S)
                       for name, leaf in packed.items()}
        if kind == "rglru_mlp":
            r, st = rgmod.apply_rglru(cfg, p["rglru"],
                                      apply_norm(cfg, p["ln1"], h),
                                      return_state=True)
            h = h + r
            h = h + mlpmod.apply_mlp(cfg, p["mlp"],
                                     apply_norm(cfg, p["ln2"], h))
            return h, st
        if kind == "ssd":
            s, st = ssdmod.apply_ssd(cfg, p["ssd"],
                                     apply_norm(cfg, p["ln1"], h),
                                     return_state=True)
            return h + s, st
        raise ValueError(kind)

    def group_fn(h, inp):
        gp, gpfx = inp if prefix is not None else (inp, None)
        caches = {}
        for i, kind in enumerate(group_kinds):
            h, c = fill_block(cfg, kind, gp[f"b{i}"], h, positions,
                              pfx=gpfx[f"b{i}"] if gpfx is not None else None)
            caches[f"b{i}"] = c
        return h, caches

    if cfg.scan_layers:
        xs = params["layers"] if prefix is None \
            else (params["layers"], prefix["layers"])
        h, stacked = jax.lax.scan(group_fn, h, xs)
    else:
        outs = []
        for i in range(n_groups):
            gp = params["layers"][f"g{i}"]
            inp = gp if prefix is None else \
                (gp, jax.tree.map(lambda t: t[i], prefix["layers"]))
            h, c = group_fn(h, inp)
            outs.append(c)
        stacked = _stack_cache(outs)
    cache = {"layers": stacked}
    for i, kind in enumerate(tail_kinds):
        h, c = fill_block(cfg, kind, params[f"tail{i}"], h, positions,
                          pfx=prefix.get(f"tail{i}")
                          if prefix is not None else None)
        cache[f"tail{i}"] = c
    h = apply_norm(cfg, params["final_norm"], h)
    if last_index is None:
        h_last = h[:, -1:]
    else:
        h_last = jax.lax.dynamic_slice_in_dim(h, last_index, 1, axis=1)
    logits = logits_from_hidden(cfg, params, h_last)
    return logits[:, 0], cache


def _seq_to_cache(kv, capacity, S):
    """Place [B,S,KVH,hd] K/V into a capacity-sized cache buffer (ring
    semantics when capacity < S: keep the last `capacity` positions at
    slots pos % capacity)."""
    B = kv.shape[0]
    if capacity == S:
        return kv
    if capacity > S:
        pad = jnp.zeros((B, capacity - S) + kv.shape[2:], kv.dtype)
        return jnp.concatenate([kv, pad], axis=1)
    tail = kv[:, S - capacity:]
    # position of slot j should be ≡ j (mod capacity)
    start = (S - capacity) % capacity
    return jnp.roll(tail, shift=start, axis=1)
