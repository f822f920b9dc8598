"""Compile rehearsals for the TPU: the attention kernels of the serving
path, compiled for a described (not attached) TPU v5e chip at serving
widths — stablelm-3b's (H = KVH = 32, head_dim 80) and a GQA width
(H 40, KVH 8, head_dim 128).  The chip's compiler refuses what interpret
mode accepts (a block that breaks the (8, 128) tiling, a mask reshape
Mosaic cannot lay out), so these guard every change to the kernels
without a chip.  Nothing runs: a pass says the kernels lower, not that
they are right (``test_kernels.py`` checks that in interpret mode).

The topology is described inside a module-scoped fixture, never at
import: only one process at a time may load the TPU compiler library.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.kernels.decode_attention import ops as da_ops
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.paged_attention import ops as pa_ops

# (H, KVH, head_dim): stablelm-3b, and a GQA width
WIDTHS = {"stablelm-3b": (32, 32, 80), "gqa": (40, 8, 128)}
# the serving engine's shape: 8 slots × 1024 positions in 16-token pages
SLOTS, MAX_LEN, PAGE, PREFILL = 8, 1024, 16, 512


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile for a described chip cannot be read back from the
    # persistent cache without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache as cc
    enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", enabled)
    cc.reset_cache()


def _compile(fn, one_chip, *shapes):
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), "no Pallas kernel"


@pytest.mark.parametrize("width", WIDTHS)
def test_paged_decode_attention_compiles(width, one_chip):
    H, KVH, d = WIDTHS[width]
    pages = SLOTS * MAX_LEN // PAGE + 1
    _compile(pa_ops.paged_decode_attention, one_chip,
             ((SLOTS, 1, H, d), jnp.bfloat16),
             ((pages, PAGE, KVH, d), jnp.bfloat16),
             ((pages, PAGE, KVH, d), jnp.bfloat16),
             ((SLOTS, MAX_LEN // PAGE), jnp.int32),
             ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("width", WIDTHS)
def test_flash_attention_compiles(width, one_chip):
    H, KVH, d = WIDTHS[width]
    _compile(fa_ops.flash_attention, one_chip,
             ((1, PREFILL, H, d), jnp.bfloat16),
             ((1, PREFILL, KVH, d), jnp.bfloat16),
             ((1, PREFILL, KVH, d), jnp.bfloat16))


@pytest.mark.parametrize("width", WIDTHS)
def test_decode_attention_compiles(width, one_chip):
    H, KVH, d = WIDTHS[width]
    _compile(da_ops.decode_attention, one_chip,
             ((SLOTS, 1, H, d), jnp.bfloat16),
             ((SLOTS, MAX_LEN, KVH, d), jnp.bfloat16),
             ((SLOTS, MAX_LEN, KVH, d), jnp.bfloat16),
             ((SLOTS,), jnp.int32))


@pytest.mark.parametrize("width", WIDTHS)
def test_decode_attention_int8_compiles(width, one_chip):
    H, KVH, d = WIDTHS[width]
    _compile(da_ops.decode_attention_int8, one_chip,
             ((SLOTS, 1, H, d), jnp.bfloat16),
             ((SLOTS, MAX_LEN, KVH, d), jnp.int8),
             ((SLOTS, MAX_LEN, KVH, d), jnp.int8),
             ((SLOTS, MAX_LEN, KVH), jnp.float32),
             ((SLOTS, MAX_LEN, KVH), jnp.float32),
             ((SLOTS,), jnp.int32))
