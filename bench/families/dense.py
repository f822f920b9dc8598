"""The dense decoder family: pre-norm blocks of grouped-query attention
with RoPE by half rotation over the whole head and a SwiGLU MLP, under
LayerNorm with bias or RMSNorm (Yi, the StableLM registry model, and
every configuration that names no ``"family"``).

A family is one file, ``bench/families/<name>.py``, that a configuration
names under ``"family"`` (default ``dense``).  It defines ``Shape``: the
sizes as the configuration file states them, and everything the harness
needs to know of the architecture's mathematics, as methods:

* the counts: :meth:`Shape.prefill_flops`, :meth:`Shape.decode_flops`
  and :meth:`Shape.decode_bytes`, from shapes and real token counts
  only (no buckets, padded slots, padded vocabulary or gathered copies),
  so a roofline or utilization built on them rises only when the program
  does the same work in less time;
* :meth:`Shape.registry_fields`: the widths the registry model must have;
* the weights' leaves and their ``repro.models`` layout, which the shared
  generator (``bench/weights.py``) fills from the seed;
* the float32 reference, :meth:`Shape.logits_at`, and its float8 control.

The reference imports nothing of the program and takes nothing the
program made.  Matrix products run at ``highest`` precision, so a TPU
computes them in float32.  Each step of a block is a method (:meth:`norm`,
:meth:`rope`, :meth:`attention`, :meth:`mlp`), so a family that differs in
one step is a new file that subclasses this ``Shape`` and overrides it.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference as R
from bench import weights as W

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

    MATRICES = ("q", "k", "v", "o", "gate", "up", "down")   # fp8 control

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

    def registry_fields(self, conf: dict) -> dict:
        """The ``repro.configs.ModelConfig`` fields that the configuration
        file fixes (``bench.spec.model_config``)."""
        return {"d_model": self.d_model, "d_ff": self.d_ff,
                "num_heads": self.heads, "num_kv_heads": self.kv_heads,
                "head_dim": self.head_dim, "vocab_size": self.vocab,
                "rope_theta": self.rope_theta, "norm_type": self.norm,
                "tie_embeddings": conf["tie_word_embeddings"]}

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

    # -- counts ---------------------------------------------------------------

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

    def prefill_flops(self, new: int, cached: int) -> int:
        """One prefill of ``new`` prompt tokens after ``cached`` tokens
        whose keys and values are already cached: causal attention over
        the cache and the new tokens, logits for the last position
        only."""
        keys = new * cached + new * (new + 1) // 2
        return (new * self.matmul_flops_per_token + self.attn_flops(keys)
                + self.head_flops)

    def decode_flops(self, contexts) -> int:
        """One decode step of the live sequences; ``contexts[i]`` is the
        number of positions sequence i attends, its new token included."""
        return sum(self.matmul_flops_per_token + self.attn_flops(c)
                   + self.head_flops for c in contexts)

    def decode_bytes(self, contexts) -> int:
        """The least bytes one decode step must move: every weight it
        uses once (the LM head included, the embedding as the rows it
        reads), the cached keys and values each sequence attends, and the
        new ones written."""
        contexts = list(contexts)
        weights = (self.layers * self.layer_params + self.norm_params
                   + self.vocab * self.d_model) * BF16
        embed_rows = len(contexts) * self.d_model * BF16
        return weights + embed_rows + sum(contexts) * self.kv_bytes_per_token

    # -- weights --------------------------------------------------------------
    # Leaves in a plain layout: ``q`` is [d_model, heads * head_dim], heads
    # major; ``down`` is [d_ff, d_model].

    def layer_leaves(self) -> dict:
        """name -> (shape, std, offset) of one layer's leaves."""
        D, F = self.d_model, self.d_ff
        q, kv = self.heads * self.head_dim, self.kv_heads * self.head_dim
        out = {"q": ((D, q), D ** -0.5, 0.0), "k": ((D, kv), D ** -0.5, 0.0),
               "v": ((D, kv), D ** -0.5, 0.0), "o": ((q, D), q ** -0.5, 0.0),
               "gate": ((D, F), D ** -0.5, 0.0),
               "up": ((D, F), D ** -0.5, 0.0),
               "down": ((F, D), F ** -0.5, 0.0)}
        out.update(self._norm_leaves("ln1"))
        out.update(self._norm_leaves("ln2"))
        return out

    def _norm_leaves(self, name: str) -> dict:
        # layernorm: a weight near 1 and a bias; rmsnorm: the weight's
        # offset from 1 (the program and the reference both multiply by
        # 1 + w)
        if self.norm == "layernorm":
            return {f"{name}_w": ((self.d_model,), 0.1, 1.0),
                    f"{name}_b": ((self.d_model,), 0.1, 0.0)}
        return {f"{name}_w": ((self.d_model,), 0.1, 0.0)}

    def global_leaves(self) -> dict:
        out = {"embed": ((self.vocab, self.d_model), 1.0, 0.0),
               "head": ((self.d_model, self.vocab), self.d_model ** -0.5,
                        0.0)}
        out.update(self._norm_leaves("final"))
        return out

    def _program_norm(self, w: dict, name: str) -> dict:
        if self.norm == "layernorm":
            return {"scale": w[f"{name}_w"], "bias": w[f"{name}_b"]}
        return {"scale": w[f"{name}_w"]}

    def to_program(self, glob: dict, layers: dict, vocab_padded: int):
        """The ``repro.models`` parameter tree (stacked layers, padded
        vocabulary rows and columns set to zero)."""
        L, D, H, KVH, hd = (self.layers, self.d_model, self.heads,
                            self.kv_heads, self.head_dim)
        pad = vocab_padded - self.vocab
        block = {
            "ln1": self._program_norm(layers, "ln1"),
            "attn": {"wq": layers["q"].reshape(L, D, H, hd),
                     "wk": layers["k"].reshape(L, D, KVH, hd),
                     "wv": layers["v"].reshape(L, D, KVH, hd),
                     "wo": layers["o"].reshape(L, H, hd, D)},
            "ln2": self._program_norm(layers, "ln2"),
            "mlp": {"w_gate": layers["gate"], "w_up": layers["up"],
                    "w_down": layers["down"]},
        }
        return {"embed": jnp.pad(glob["embed"], ((0, pad), (0, 0))),
                "lm_head": jnp.pad(glob["head"], ((0, 0), (0, pad))),
                "final_norm": self._program_norm(glob, "final"),
                "layers": {"b0": block}}

    # -- the reference, float32 -----------------------------------------------

    def norm_of(self, x, w: dict, name: str):
        """The norm ``name`` (``ln1``, ``ln2``, ``final``) of x."""
        if self.norm == "layernorm":
            mu = jnp.mean(x, axis=-1, keepdims=True)
            var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
            return ((x - mu) * jax.lax.rsqrt(var + self.norm_eps)
                    * w[f"{name}_w"] + w[f"{name}_b"])
        ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
        return x * jax.lax.rsqrt(ms + self.norm_eps) * (1.0 + w[f"{name}_w"])

    def rope(self, x, pos):
        """Half rotation over the whole of x's last axis, x: [..., S,
        heads, dim]."""
        half = x.shape[-1] // 2
        inv = self.rope_theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
        ang = pos.astype(jnp.float32)[:, None] * inv
        c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
        x1, x2 = x[..., :half], x[..., half:]
        return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)

    def attention(self, q, k, v):
        """Causal attention of one sequence: q [S, heads, hd], k, v [S,
        kv_heads, hd] (already rotated)."""
        S = q.shape[0]
        pos = jnp.arange(S)
        g = self.heads // self.kv_heads
        k = jnp.repeat(k, g, axis=1)
        v = jnp.repeat(v, g, axis=1)
        scores = jnp.einsum("shd,thd->hst", q, k) * self.head_dim ** -0.5
        causal = pos[None, :] <= pos[:, None]
        scores = jnp.where(causal[None], scores, -jnp.inf)
        return jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v)

    def mlp(self, w: dict, x):
        return (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]

    def block(self, w: dict, h):
        """One layer over sequences h: [B, S, D] (float32).  The
        projections and the MLP take every position of every sequence at
        once; attention runs one sequence at a time."""
        B, S, D = h.shape
        pos = jnp.arange(S)
        x = self.norm_of(h, w, "ln1")
        q = (x @ w["q"]).reshape(B, S, self.heads, self.head_dim)
        k = (x @ w["k"]).reshape(B, S, self.kv_heads, self.head_dim)
        v = (x @ w["v"]).reshape(B, S, self.kv_heads, self.head_dim)
        q, k = self.rope(q, pos), self.rope(k, pos)
        att = jax.lax.map(lambda a: self.attention(*a), (q, k, v))
        h = h + att.reshape(B, S, -1) @ w["o"]
        return h + self.mlp(w, self.norm_of(h, w, "ln2"))

    def logits_at(self, seed: int, seqs, rows, *, control=None,
                  pad_to: int = 1024):
        """Reference logits [len(rows), vocab] for the token after
        position ``p`` of sequence ``b``, for each ``(b, p)`` in ``rows``
        (``bench.reference.by_sequence`` pads and groups them).  With
        ``control="fp8"`` every weight matrix is rounded to float8
        e4m3."""
        key = W.base_key(seed)
        out = np.zeros((len(rows), self.vocab), np.float32)
        for toks, mine, pos in R.by_sequence(seqs, rows, pad_to):
            h = _embed(self, key, control, jnp.asarray(toks))
            for layer in range(self.layers):
                h = _layer(self, key, jnp.int32(layer), control, h)
            out[mine] = np.asarray(
                _logits(self, key, control, h, jnp.asarray(pos)))[:len(mine)]
        return out


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(s: Shape, key, layer, control, h):
    with jax.default_matmul_precision("highest"):
        w = {n: x.astype(jnp.float32)
             for n, x in W.layer_weights(s, key, layer).items()}
        if control == "fp8":
            w.update({n: R.fp8(w[n]) for n in s.MATRICES})
        return s.block(w, h)


@functools.partial(jax.jit, static_argnums=(0, 2))
def _embed(s: Shape, key, control, tokens):
    del control
    return W.global_weights(s, key)["embed"].astype(jnp.float32)[tokens]


@functools.partial(jax.jit, static_argnums=(0, 2))
def _logits(s: Shape, key, control, h, pos):
    """Logits at positions ``pos`` of one sequence's hidden states h [1,
    S, D]."""
    with jax.default_matmul_precision("highest"):
        g = {n: x.astype(jnp.float32)
             for n, x in W.global_weights(s, key).items()}
        head = R.fp8(g["head"]) if control == "fp8" else g["head"]
        x = s.norm_of(h[0, pos], g, "final")
        return x @ head
