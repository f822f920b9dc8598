"""Random weights from ``--seed``, the same for the program and the
reference.

Each leaf is made from integer random bits by exact arithmetic: the sum
of two uniform 16-bit integers (a triangular distribution), times a power
of two, is exact in float32, and its rounding to bfloat16 is a single
deterministic step.  So the served weights do not depend on how XLA fuses
the generator, and the reference, which makes each layer alone after the
program has been freed, reads exactly the weights that were served.

Which leaves there are, their sizes and scales, and how the program
lays them out are the family's (``bench/families/<family>.py``: the
shape's ``layer_leaves``, ``global_leaves`` and ``to_program``).
:func:`program_params` makes all of them on the device in one jitted call
and checks the layout against the model's own.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

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


def _make(key, leaves: dict) -> dict:
    return {name: _tri(jax.random.fold_in(key, i), shape, std, off)
            for i, (name, (shape, std, off)) in enumerate(sorted(
                leaves.items()))}


def layer_weights(s, key, layer):
    """One layer's leaves of the family's shape ``s``, bf16 (``layer``
    may be traced)."""
    return _make(jax.random.fold_in(key, layer + 1), s.layer_leaves())


def global_weights(s, key):
    return _make(jax.random.fold_in(key, 0), s.global_leaves())


def program_params(s, seed: int, model):
    """Every weight the program serves, made on the device in one jitted
    call, in ``repro.models``' layout.  The layout is checked against
    the model's own abstract parameters: a mismatch is an error."""
    key = base_key(seed)

    @jax.jit
    def make(key):
        layers = jax.lax.map(lambda i: layer_weights(s, key, i),
                             jnp.arange(s.layers))
        return s.to_program(global_weights(s, key), layers,
                            model.cfg.vocab_padded)

    want = model.abstract_params(jnp.bfloat16)
    got = jax.eval_shape(make, key)
    if jax.tree.structure(got) != jax.tree.structure(want) or any(
            (a.shape, a.dtype) != (b.shape, b.dtype)
            for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want))):
        raise ValueError("bench weights do not match the model's parameter "
                         "layout")
    return make(key)
