"""Production mesh construction.

Single pod: (16, 16) = ("data", "model") — 256 TPU v5e chips.
Multi-pod:  (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
axis carries only data parallelism (gradient all-reduce over DCN).

Defined as functions (not module constants) so importing never touches jax
device state.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType


def _mesh(shape, axes, devices):
    # Auto axes: the sharding rules place arrays with NamedSharding and
    # with_sharding_constraint, which Explicit axes (make_mesh's default
    # under JAX 0.9) reject
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(jax.devices())}; "
            "the dry-run sets --xla_force_host_platform_device_count=512 "
            "before importing jax")
    return _mesh(shape, axes, devices)


def make_serving_mesh(tp: int = 1, *, devices=None):
    """A ``(1, tp)`` = ("data", "model") mesh for one serving engine.

    Serving shards only over the tensor axis (decode batch sizes are too
    small and too dynamic for data parallelism inside one engine; the
    fleet scales out with whole replicas instead).  Pass ``devices`` to
    carve disjoint slices of the host's devices for fleet replicas.
    """
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    pool = list(devices) if devices is not None else jax.devices()
    if len(pool) < tp:
        raise RuntimeError(
            f"serving mesh tp={tp} needs {tp} devices, have {len(pool)}; "
            "set XLA_FLAGS=--xla_force_host_platform_device_count before "
            "importing jax to simulate more on CPU")
    return _mesh((1, tp), ("data", "model"), pool[:tp])


def make_host_mesh():
    """A trivial 1-device mesh for CPU smoke/integration runs."""
    return _mesh((1, 1), ("data", "model"), jax.devices()[:1])


def hardware_constants():
    """TPU v5e per-chip roofline constants (targets, not the CPU host)."""
    return {
        "peak_flops_bf16": 197e12,   # FLOP/s
        "hbm_bandwidth": 819e9,      # B/s
        "ici_link_bandwidth": 50e9,  # B/s per link
        "hbm_bytes": 16e9,
    }
