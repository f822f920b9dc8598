"""Shared model building blocks: parameter schema, initializers, norms, RoPE.

Parameters are plain pytrees (nested dicts of jnp arrays).  Every module
declares a *schema* — ``{name: PSpec(shape, logical_axes, init)}`` — from
which real initialization (smoke tests), abstract initialization (dry-run)
and sharding PartitionSpecs (repro.sharding.rules) all derive, so the three
can never drift apart.
"""

from __future__ import annotations

import math
import zlib
from dataclasses import dataclass

import jax
import jax.numpy as jnp


@dataclass(frozen=True)
class PSpec:
    shape: tuple
    axes: tuple          # logical axis names, parallel to shape
    init: str = "normal"  # normal | zeros | ones | embed
    fan_in_axes: tuple = ()  # dims to treat as fan-in for scaling


def _path_rng(rng, path: str):
    # crc32, not hash(): str hashes are salted per process, and the same
    # seed must give the same weights in every run
    return jax.random.fold_in(rng, zlib.crc32(path.encode()) & 0x7FFFFFFF)


def init_param(rng, path: str, spec: PSpec, dtype):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    r = _path_rng(rng, path)
    if spec.init == "embed":
        return (jax.random.normal(r, spec.shape, dtype) * 0.02).astype(dtype)
    # lecun-normal-ish: scale by fan-in (first axis unless specified)
    fan_axes = spec.fan_in_axes or (0,)
    fan_in = 1
    for a in fan_axes:
        fan_in *= spec.shape[a]
    scale = 1.0 / math.sqrt(max(fan_in, 1))
    return (jax.random.normal(r, spec.shape, dtype) * scale).astype(dtype)


def init_tree(rng, schema: dict, dtype, prefix=""):
    out = {}
    for k, v in schema.items():
        path = f"{prefix}/{k}"
        if isinstance(v, dict):
            out[k] = init_tree(rng, v, dtype, path)
        else:
            out[k] = init_param(rng, path, v, dtype)
    return out


def abstract_tree(schema: dict, dtype):
    out = {}
    for k, v in schema.items():
        if isinstance(v, dict):
            out[k] = abstract_tree(v, dtype)
        else:
            out[k] = jax.ShapeDtypeStruct(v.shape, jnp.dtype(dtype))
    return out


def axes_tree(schema: dict):
    out = {}
    for k, v in schema.items():
        if isinstance(v, dict):
            out[k] = axes_tree(v)
        else:
            out[k] = v.axes
    return out


def stack_schema(schema: dict, n: int, axis_name: str = "layers") -> dict:
    """Prepend a stacked-layer axis to every leaf (for lax.scan over layers)."""
    out = {}
    for k, v in schema.items():
        if isinstance(v, dict):
            out[k] = stack_schema(v, n, axis_name)
        else:
            out[k] = PSpec((n,) + v.shape, (axis_name,) + v.axes, v.init,
                           tuple(a + 1 for a in (v.fan_in_axes or (0,))))
    return out


# ---------------------------------------------------------------------------
# norms


def rmsnorm(x, scale, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(jnp.square(x), axis=-1, keepdims=True)
    out = x * jax.lax.rsqrt(var + eps)
    return (out * (1.0 + scale.astype(jnp.float32))).astype(dt)


def layernorm(x, scale, bias, eps):
    dt = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    out = (x - mu) * jax.lax.rsqrt(var + eps)
    return (out * scale.astype(jnp.float32)
            + bias.astype(jnp.float32)).astype(dt)


def norm_schema(cfg, d=None) -> dict:
    d = d or cfg.d_model
    if cfg.norm_type == "layernorm":
        return {"scale": PSpec((d,), ("embed",), "ones"),
                "bias": PSpec((d,), ("embed",), "zeros")}
    return {"scale": PSpec((d,), ("embed",), "zeros")}


def apply_norm(cfg, p, x):
    if cfg.norm_type == "layernorm":
        return layernorm(x, p["scale"], p["bias"], cfg.norm_eps)
    return rmsnorm(x, p["scale"], cfg.norm_eps)


# ---------------------------------------------------------------------------
# rotary position embeddings (NeoX half-rotation, llama/qwen convention)


def rope_cos_sin(positions, head_dim, theta, dtype):
    """positions: [...,] int32 → cos/sin [..., head_dim/2]."""
    half = head_dim // 2
    freqs = jnp.arange(half, dtype=jnp.float32) / half
    inv = theta ** -freqs                      # [half]
    ang = positions.astype(jnp.float32)[..., None] * inv
    return jnp.cos(ang).astype(dtype), jnp.sin(ang).astype(dtype)


def apply_rope(x, cos, sin):
    """x: [B, S, H, D]; cos/sin: [B, S, D/2] (or broadcastable)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[..., None, :]
    s = sin[..., None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def sinusoidal_positions(positions, d_model, dtype):
    """Whisper-style sinusoidal embeddings, computed on the fly for any
    length (learned tables don't extend to assigned 32k decode contexts;
    deviation noted in DESIGN.md)."""
    half = d_model // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(10000.0) / max(half - 1, 1)))
    ang = positions.astype(jnp.float32)[..., None] * freqs
    return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], axis=-1).astype(dtype)


def shard_hint(x, spec_name: str):
    """Logical activation-sharding hook; resolved by repro.sharding.rules
    when a mesh context is active, identity otherwise."""
    from repro.sharding.rules import constrain_activation
    return constrain_activation(x, spec_name)
