"""The plain reference: the decoder in float32 ``jax.numpy``, layer by
layer, on the weights ``bench/weights.py`` makes from the seed.

It imports nothing of the program and takes nothing the program made.
It follows the published description of a pre-norm decoder (RoPE by
half rotation over the whole head, grouped-query attention, SwiGLU MLP,
LayerNorm with bias or RMSNorm).
Matrix products run at ``highest`` precision, so a TPU computes them in
float32.

:func:`served_gaps` scores what a run served: for every served token, how
far its reference logit lies below the reference's best logit at that
position.  ``control="fp8"`` computes the same forward with each weight
matrix rounded to float8 e4m3 (per output channel scale), the step below
the bfloat16 the configuration states, and scores the token that it puts
first instead.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import weights as W
from bench.cost import Shape

MATRICES = ("q", "k", "v", "o", "gate", "up", "down")


def _fp8(w):
    """Round a [in, out] matrix to float8 e4m3 with one scale per output
    channel (the largest magnitude maps to e4m3's largest finite 448)."""
    scale = jnp.max(jnp.abs(w), axis=0, keepdims=True) / 448.0
    scale = jnp.where(scale > 0, scale, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _norm(s: Shape, x, w, b=None):
    if s.norm == "layernorm":
        mu = jnp.mean(x, axis=-1, keepdims=True)
        var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + s.norm_eps) * w + b
    ms = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(ms + s.norm_eps) * (1.0 + w)


def _rope(s: Shape, x, pos):
    """Half rotation over the whole head, x: [..., S, heads, hd]."""
    half = s.head_dim // 2
    inv = s.rope_theta ** -(jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv
    c, sn = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * sn, x2 * c + x1 * sn], axis=-1)


def _ln_args(s: Shape, w, name):
    return (w[f"{name}_w"], w.get(f"{name}_b"))


def _attention(s: Shape, q, k, v):
    """Causal attention of one sequence: q [S, heads, hd], k, v [S,
    kv_heads, hd] (already rotated)."""
    S = q.shape[0]
    pos = jnp.arange(S)
    g = s.heads // s.kv_heads
    k = jnp.repeat(k, g, axis=1)
    v = jnp.repeat(v, g, axis=1)
    scores = jnp.einsum("shd,thd->hst", q, k) * s.head_dim ** -0.5
    causal = pos[None, :] <= pos[:, None]
    scores = jnp.where(causal[None], scores, -jnp.inf)
    return jnp.einsum("hst,thd->shd", jax.nn.softmax(scores, axis=-1), v)


def _block(s: Shape, w, h):
    """One layer over sequences h: [B, S, D] (float32).  The projections
    and the MLP take every position of every sequence at once; attention
    runs one sequence at a time."""
    B, S, D = h.shape
    pos = jnp.arange(S)
    x = _norm(s, h, *_ln_args(s, w, "ln1"))
    q = (x @ w["q"]).reshape(B, S, s.heads, s.head_dim)
    k = (x @ w["k"]).reshape(B, S, s.kv_heads, s.head_dim)
    v = (x @ w["v"]).reshape(B, S, s.kv_heads, s.head_dim)
    q, k = _rope(s, q, pos), _rope(s, k, pos)
    att = jax.lax.map(lambda a: _attention(s, *a), (q, k, v))
    h = h + att.reshape(B, S, -1) @ w["o"]
    x = _norm(s, h, *_ln_args(s, w, "ln2"))
    return h + (jax.nn.silu(x @ w["gate"]) * (x @ w["up"])) @ w["down"]


@functools.partial(jax.jit, static_argnums=(0, 3))
def _layer(s: Shape, key, layer, control, h):
    with jax.default_matmul_precision("highest"):
        w = {n: x.astype(jnp.float32)
             for n, x in W.layer_weights(s, key, layer).items()}
        if control == "fp8":
            w.update({n: _fp8(w[n]) for n in MATRICES})
        return _block(s, w, h)


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
        head = _fp8(g["head"]) if control == "fp8" else g["head"]
        x = _norm(s, h[0, pos], *_ln_args(s, g, "final"))
        return x @ head


def _bucket(n: int, lo: int) -> int:
    b = lo
    while b < n:
        b *= 2
    return b


def logits_at(s: Shape, seed: int, seqs, rows, *, control=None,
              pad_to: int = 1024):
    """Reference logits [len(rows), vocab] for the token after position
    ``p`` of sequence ``b``, for each ``(b, p)`` in ``rows``.  Sequences
    run one at a time, each padded at the end to a multiple of ``pad_to``
    (causal attention never reads the padding), and positions are read
    in power-of-two groups, so a few compiled shapes serve every run."""
    key = W.base_key(seed)
    out = np.zeros((len(rows), s.vocab), np.float32)
    for b, t in enumerate(seqs):
        n = -(-len(t) // pad_to) * pad_to
        toks = np.zeros((1, n), np.int32)
        toks[0, :len(t)] = t
        h = _embed(s, key, control, jnp.asarray(toks))
        for layer in range(s.layers):
            h = _layer(s, key, jnp.int32(layer), control, h)
        mine = [i for i, (bb, _) in enumerate(rows) if bb == b]
        pos = np.zeros(_bucket(len(mine), 64), np.int32)
        pos[:len(mine)] = [rows[i][1] for i in mine]
        out[mine] = np.asarray(
            _logits(s, key, control, h, jnp.asarray(pos)))[:len(mine)]
    return out


def served_gaps(s: Shape, seed: int, served, *, control=None):
    """Gaps for ``served``: a list of (prompt tokens, served tokens).
    Without ``control``: reference best logit minus the reference logit
    of each served token.  With it: reference best minus the reference
    logit of the token the control puts first, at the same positions of
    the same sequences.  Returns a flat float64 array, one gap per served
    token."""
    seqs, rows, toks = [], [], []
    for b, (prompt, out) in enumerate(served):
        seqs.append(list(prompt) + list(out[:-1]))
        for j, t in enumerate(out):
            rows.append((b, len(prompt) - 1 + j))
            toks.append(t)
    ref = logits_at(s, seed, seqs, rows).astype(np.float64)
    if control is not None:
        toks = np.argmax(logits_at(s, seed, seqs, rows, control=control),
                         axis=-1)
    return ref.max(axis=-1) - ref[np.arange(len(rows)), np.asarray(toks)]
