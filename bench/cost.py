"""Operations and bytes of prefill and decode, from a configuration's
shapes and the real token counts.

Nothing here knows how the program computes: no buckets, no padded
slots, no padded vocabulary, no gathered copies.  So the counts stay the
same whatever implements the step, and a roofline or utilization built
on them can only rise when the program does the same work in less time.
"""

from __future__ import annotations

from dataclasses import dataclass

BF16 = 2   # bytes per weight and per cached key/value element, as served


@dataclass(frozen=True)
class Shape:
    """The sizes of a dense decoder, as a configuration file states
    them."""

    layers: int
    d_model: int
    d_ff: int
    heads: int
    kv_heads: int
    head_dim: int
    vocab: int
    norm: str                  # "layernorm" (scale and bias) | "rmsnorm"
    norm_eps: float
    rope_theta: float

    @classmethod
    def from_config(cls, conf: dict) -> "Shape":
        return cls(
            layers=conf["num_hidden_layers"], d_model=conf["hidden_size"],
            d_ff=conf["intermediate_size"],
            heads=conf["num_attention_heads"],
            kv_heads=conf["num_key_value_heads"],
            head_dim=conf["hidden_size"] // conf["num_attention_heads"],
            vocab=conf["vocab_size"], norm=conf["norm"],
            norm_eps=float(conf.get("layer_norm_eps",
                                    conf.get("rms_norm_eps"))),
            rope_theta=float(conf["rope_theta"]))

    # -- parameters -----------------------------------------------------------

    @property
    def norm_params(self) -> int:
        return self.d_model * (2 if self.norm == "layernorm" else 1)

    @property
    def attn_params(self) -> int:
        D, q, kv = self.d_model, self.heads * self.head_dim, \
            self.kv_heads * self.head_dim
        return D * q + 2 * D * kv + q * D

    @property
    def mlp_params(self) -> int:
        return 3 * self.d_model * self.d_ff          # gate, up, down

    @property
    def layer_params(self) -> int:
        return self.attn_params + self.mlp_params + 2 * self.norm_params

    @property
    def params(self) -> int:
        """Every parameter: layers, embedding, final norm, LM head."""
        return (self.layers * self.layer_params + self.norm_params
                + 2 * self.vocab * self.d_model)

    # -- per token ------------------------------------------------------------

    @property
    def kv_bytes_per_token_layer(self) -> int:
        return 2 * self.kv_heads * self.head_dim * BF16

    @property
    def kv_bytes_per_token(self) -> int:
        return self.layers * self.kv_bytes_per_token_layer

    @property
    def matmul_flops_per_token(self) -> int:
        """Projections and MLP of every layer, for one token."""
        return 2 * self.layers * (self.attn_params + self.mlp_params)

    @property
    def head_flops(self) -> int:
        return 2 * self.d_model * self.vocab

    def attn_flops(self, keys: int) -> int:
        """Scores and the weighted sum of one query over ``keys`` keys,
        every layer."""
        return 4 * self.layers * self.heads * self.head_dim * keys


def prefill_flops(s: Shape, new: int, cached: int) -> int:
    """One prefill of ``new`` prompt tokens after ``cached`` tokens whose
    keys and values are already cached: causal attention over the cache
    and the new tokens, logits for the last position only."""
    keys = new * cached + new * (new + 1) // 2
    return (new * s.matmul_flops_per_token
            + 4 * s.layers * s.heads * s.head_dim * keys + s.head_flops)


def decode_flops(s: Shape, contexts) -> int:
    """One decode step of the live sequences; ``contexts[i]`` is the
    number of positions sequence i attends, its new token included."""
    contexts = list(contexts)
    return sum(s.matmul_flops_per_token + s.attn_flops(c) + s.head_flops
               for c in contexts)


def decode_bytes(s: Shape, contexts) -> int:
    """The least bytes one decode step must move: every weight it uses
    once (the LM head included, the embedding as the rows it reads), the
    cached keys and values each sequence attends, and the new ones
    written."""
    contexts = list(contexts)
    weights = (s.layers * s.layer_params + s.norm_params
               + s.vocab * s.d_model) * BF16
    embed_rows = len(contexts) * s.d_model * BF16
    return weights + embed_rows + sum(contexts) * s.kv_bytes_per_token
