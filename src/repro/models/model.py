"""Model facade: one object per architecture config, dispatching to the
family implementation (lm.py / encdec.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from . import encdec, lm
from .common import abstract_tree, axes_tree, init_tree


class Model:
    def __init__(self, cfg):
        self.cfg = cfg

    # -- parameters -----------------------------------------------------------

    def schema(self) -> dict:
        if self.cfg.family == "enc_dec":
            return encdec.encdec_schema(self.cfg)
        return lm.lm_schema(self.cfg)

    def init(self, rng, dtype=None):
        """Random parameters from ``rng``, created directly in ``dtype``
        (default ``cfg.param_dtype``).  Serving passes
        ``cfg.serve_param_dtype``, so a bf16 server never holds an f32
        copy of its weights."""
        return init_tree(rng, self.schema(),
                         jnp.dtype(dtype or self.cfg.param_dtype))

    def abstract_params(self, dtype=None):
        return abstract_tree(self.schema(), dtype or self.cfg.param_dtype)

    def param_logical_axes(self):
        return axes_tree(self.schema())

    def num_params(self) -> int:
        total = 0
        for leaf in jax.tree.leaves(self.abstract_params()):
            n = 1
            for d in leaf.shape:
                n *= d
            total += n
        return total

    # -- compute ---------------------------------------------------------------

    def forward(self, params, batch):
        """→ (logits [B,S,V], aux_loss)."""
        if self.cfg.family == "enc_dec":
            return encdec.forward(self.cfg, params, batch)
        return lm.forward(self.cfg, params, batch)

    def loss_fn(self, params, batch):
        logits, aux = self.forward(params, batch)
        targets = batch["targets"]
        logits = logits.astype(jnp.float32)
        logz = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(logits, targets[..., None],
                                   axis=-1)[..., 0]
        nll = (logz - gold).mean()
        zloss = 1e-4 * jnp.square(logz).mean()
        loss = nll + zloss + 1e-2 * aux
        return loss, {"nll": nll, "aux": aux, "zloss": zloss}

    # -- serving ----------------------------------------------------------------

    def init_cache(self, batch, capacity, *, abstract=False):
        if self.cfg.family == "enc_dec":
            return encdec.init_cache(self.cfg, batch, capacity,
                                     abstract=abstract)
        return lm.init_cache(self.cfg, batch, capacity, abstract=abstract)

    def prefill(self, params, batch, capacity, *, prefix=None,
                prefix_len=None, last_index=None):
        """→ (last_logits [B,V], cache).

        ``prefix``/``prefix_len``/``last_index`` enable prefix-aware
        suffix-only prefill for the serving radix cache (see
        :func:`repro.models.lm.prefill`); only models for which
        :meth:`prefix_seq_axes` returns a tree support them."""
        if self.cfg.family == "enc_dec":
            if prefix is not None or last_index is not None:
                raise ValueError(
                    "prefix-aware prefill is not supported for enc_dec")
            return encdec.prefill(self.cfg, params, batch, capacity)
        if prefix is not None and self.prefix_seq_axes() is None:
            # recurrent/hybrid blocks would silently ignore the prefix and
            # int8 K/V would be consumed without dequantization — refuse
            # rather than return wrong logits (trace-time check only)
            raise ValueError(
                f"{self.cfg.name}: KV is not positionally sliceable "
                f"(prefix_seq_axes() is None) — prefix-aware prefill "
                f"unsupported")
        return lm.prefill(self.cfg, params, batch, capacity, prefix=prefix,
                          prefix_len=prefix_len, last_index=last_index)

    def prefix_seq_axes(self):
        """Per-leaf sequence-axis pytree of the serving cache, or ``None``
        when per-position KV reuse is unsound for this model: recurrent /
        hybrid state is not positionally sliceable, windowed attention
        uses ring buffers, enc_dec has cross-attention memory, and int8
        KV would break token-exactness between cached and cold prefills
        (the cold path attends unquantized K/V)."""
        cfg = self.cfg
        if cfg.family == "enc_dec" or cfg.kv_cache_dtype == "int8" \
                or cfg.attn_window:
            return None
        if any(k not in ("attn_mlp", "attn_moe")
               for k in lm.block_kinds(cfg)):
            return None
        a = self.init_cache(1, 8, abstract=True)
        b = self.init_cache(1, 16, abstract=True)

        def axis(x, y):
            diff = [i for i, (p, q) in enumerate(zip(x.shape, y.shape))
                    if p != q]
            return diff[0] if len(diff) == 1 else -1

        axes = jax.tree.map(axis, a, b)
        if any(v < 0 for v in jax.tree.leaves(axes)):
            return None
        return axes

    def decode_step(self, params, cache, tokens, positions):
        """tokens [B,1], positions [B] → (logits [B,V], new_cache)."""
        if self.cfg.family == "enc_dec":
            return encdec.decode_step(self.cfg, params, cache, tokens,
                                      positions)
        return lm.decode_step(self.cfg, params, cache, tokens, positions)

    # -- paged KV (block-paged serving layout, DESIGN.md §3.3) -----------------

    def init_paged_cache(self, num_pages, page_size):
        """Block-paged KV pool: leaves [n_groups, num_pages, page_size,
        KVH, hd].  Only for models whose cache is positionally sliceable
        (:meth:`prefix_seq_axes` is not None) — recurrent/hybrid/enc_dec/
        int8-KV/windowed models have no page decomposition and stay on the
        contiguous engine."""
        if self.prefix_seq_axes() is None:
            raise ValueError(
                f"{self.cfg.name}: KV is not positionally sliceable — "
                f"paged layout unsupported")
        return lm.init_paged_cache(self.cfg, num_pages, page_size)

    def decode_step_paged(self, params, cache, tokens, positions,
                          page_table):
        """tokens [B,1], positions [B], page_table [B,N] int32 →
        (logits [B,V], new_cache)."""
        return lm.decode_step_paged(self.cfg, params, cache, tokens,
                                    positions, page_table)


def build_model(cfg) -> Model:
    return Model(cfg)
