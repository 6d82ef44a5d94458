"""The cells' kernels compile for the chip at their real widths.

The TPU's compiler is installed here and compiles for a described, not
attached, ``v5e:2x2``: the flash forward and backward at GPT-2-medium's
shapes (16 heads of 64, 1024 positions) and the paged kernel at OPT-1.3B's
(32 heads of 64, a 128-entry block table a row), decode and prefill-chunk
shapes. Nothing runs; no time or result comes of it. The topology is
described inside a module fixture, never at import (one process at a time
may load the TPU's library), and every test is in this one file.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

F32 = jnp.float32


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    from jax.experimental.compilation_cache import compilation_cache as cc
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _has_kernel(compiled):
    return "tpu_custom_call" in compiled.as_text()


# GPT-2-medium: per-chip batch 16, 16 heads, 1024 positions, head dim 64
FLASH = (16, 16, 1024, 64)


def test_flash_forward_compiles_at_gpt2_medium_shapes(one_chip):
    from bigdl_tpu.kernels.flash_attention import flash_attention_fused
    q = _sds(FLASH, F32, one_chip)
    c = jax.jit(lambda q, k, v: flash_attention_fused(
        q, k, v, causal=True)).lower(q, q, q).compile()
    assert _has_kernel(c)


def test_flash_backward_compiles_at_gpt2_medium_shapes(one_chip):
    from bigdl_tpu.kernels.flash_attention import flash_attention_fused
    q = _sds(FLASH, F32, one_chip)
    grad = jax.grad(lambda q, k, v, w: (flash_attention_fused(
        q, k, v, causal=True) * w).sum(), argnums=(0, 1, 2))
    c = jax.jit(grad).lower(q, q, q, q).compile()
    assert c.as_text().count("tpu_custom_call") >= 3    # fwd, dkv, dq


# OPT-1.3B: 32 heads of 64, block 16, max_seq_len 2048 -> 128 table entries
@pytest.mark.parametrize("rows,chunk", [(16, 1), (2, 1), (1, 128), (1, 2)])
def test_paged_kernel_compiles_at_opt_1_3b_shapes(one_chip, rows, chunk):
    from bigdl_tpu.kernels.paged_attention import paged_decode_attention
    heads, d, bs, table, pool = 32, 64, 16, 128, 577
    q = _sds((rows, heads, chunk, d), F32, one_chip)
    pages = _sds((pool, heads, bs, d), F32, one_chip)
    tables = _sds((rows, table), jnp.int32, one_chip)
    pos = _sds((rows,), jnp.int32, one_chip)
    c = jax.jit(paged_decode_attention).lower(
        q, pages, pages, tables, pos).compile()
    assert _has_kernel(c)
