"""Random weights from ``--seed``, the same for the program and the
reference.

Each leaf is made from integer random bits by exact arithmetic: the sum
of two uniform 16-bit integers (a triangular distribution), times a power
of two, is exact in float32, and its rounding to bfloat16 is a single
deterministic step.  So the served weights do not depend on how XLA fuses
the generator, and the reference, which makes each layer alone after the
program has been freed, reads exactly the weights that were served.

Leaves are kept in a plain layout (``q`` is ``[d_model, heads *
head_dim]``, heads major; ``down`` is ``[d_ff, d_model]``).
:func:`program_params` makes all of them on the device in one jitted call
and lays them out as ``repro.models`` expects.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.cost import Shape

TRI_STD = 65535 / math.sqrt(6)     # std of u1 + u2 - 65535, u uniform 16-bit


def base_key(seed: int):
    """A key for any whole seed below 2**62 (more than 32 bits)."""
    key = jax.random.PRNGKey(seed % (1 << 31))
    return jax.random.fold_in(key, seed >> 31)


def _tri(key, shape, std: float, offset: float = 0.0):
    """bf16 of ``offset + n * 2**-k``, ``n`` triangular on [-65535, 65535]
    and ``2**-k`` the power of two that brings its std nearest ``std``."""
    k1, k2 = jax.random.split(key)
    n = (jax.random.bits(k1, shape, jnp.uint16).astype(jnp.int32)
         + jax.random.bits(k2, shape, jnp.uint16).astype(jnp.int32)
         - 65535)
    scale = 2.0 ** round(math.log2(std / TRI_STD))
    return (offset + n.astype(jnp.float32) * scale).astype(jnp.bfloat16)


def layer_leaves(s: Shape) -> dict:
    """name -> (shape, std, offset) of one layer's leaves."""
    D, F = s.d_model, s.d_ff
    q, kv = s.heads * s.head_dim, s.kv_heads * s.head_dim
    out = {"q": ((D, q), D ** -0.5, 0.0), "k": ((D, kv), D ** -0.5, 0.0),
           "v": ((D, kv), D ** -0.5, 0.0), "o": ((q, D), q ** -0.5, 0.0),
           "gate": ((D, F), D ** -0.5, 0.0), "up": ((D, F), D ** -0.5, 0.0),
           "down": ((F, D), F ** -0.5, 0.0)}
    out.update(_norm_leaves(s, "ln1"))
    out.update(_norm_leaves(s, "ln2"))
    return out


def _norm_leaves(s: Shape, name: str) -> dict:
    # layernorm: a weight near 1 and a bias; rmsnorm: the weight's offset
    # from 1 (the program and the reference both multiply by 1 + w)
    if s.norm == "layernorm":
        return {f"{name}_w": ((s.d_model,), 0.1, 1.0),
                f"{name}_b": ((s.d_model,), 0.1, 0.0)}
    return {f"{name}_w": ((s.d_model,), 0.1, 0.0)}


def global_leaves(s: Shape) -> dict:
    out = {"embed": ((s.vocab, s.d_model), 1.0, 0.0),
           "head": ((s.d_model, s.vocab), s.d_model ** -0.5, 0.0)}
    out.update(_norm_leaves(s, "final"))
    return out


def _make(key, leaves: dict) -> dict:
    return {name: _tri(jax.random.fold_in(key, i), shape, std, off)
            for i, (name, (shape, std, off)) in enumerate(sorted(
                leaves.items()))}


def layer_weights(s: Shape, key, layer):
    """One layer's leaves, bf16 (``layer`` may be traced)."""
    return _make(jax.random.fold_in(key, layer + 1), layer_leaves(s))


def global_weights(s: Shape, key):
    return _make(jax.random.fold_in(key, 0), global_leaves(s))


def _norm(s: Shape, w: dict, name: str) -> dict:
    if s.norm == "layernorm":
        return {"scale": w[f"{name}_w"], "bias": w[f"{name}_b"]}
    return {"scale": w[f"{name}_w"]}


def to_program(s: Shape, glob: dict, layers: dict, vocab_padded: int):
    """The ``repro.models`` parameter tree (stacked layers, padded
    vocabulary rows and columns set to zero)."""
    L, D, H, KVH, hd = s.layers, s.d_model, s.heads, s.kv_heads, s.head_dim
    pad = vocab_padded - s.vocab
    block = {
        "ln1": _norm(s, layers, "ln1"),
        "attn": {"wq": layers["q"].reshape(L, D, H, hd),
                 "wk": layers["k"].reshape(L, D, KVH, hd),
                 "wv": layers["v"].reshape(L, D, KVH, hd),
                 "wo": layers["o"].reshape(L, H, hd, D)},
        "ln2": _norm(s, layers, "ln2"),
        "mlp": {"w_gate": layers["gate"], "w_up": layers["up"],
                "w_down": layers["down"]},
    }
    return {"embed": jnp.pad(glob["embed"], ((0, pad), (0, 0))),
            "lm_head": jnp.pad(glob["head"], ((0, 0), (0, pad))),
            "final_norm": _norm(s, glob, "final"),
            "layers": {"b0": block}}


def program_params(s: Shape, seed: int, model):
    """Every weight the program serves, made on the device in one jitted
    call, in ``repro.models``' layout.  The layout is checked against
    the model's own abstract parameters: a mismatch is an error."""
    key = base_key(seed)

    @jax.jit
    def make(key):
        layers = jax.lax.map(lambda i: layer_weights(s, key, i),
                             jnp.arange(s.layers))
        return to_program(s, global_weights(s, key), layers,
                          model.cfg.vocab_padded)

    want = model.abstract_params(jnp.bfloat16)
    got = jax.eval_shape(make, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("bench weights do not match the model's parameter "
                         "layout")
    return make(key)
