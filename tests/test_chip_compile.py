"""Compile the main path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: block shapes off the
(8, 128) tiling, reshapes Mosaic cannot lay out, primitives it cannot lower,
programs that outgrow the chip.  These tests compile each Pallas kernel with
``interpret=False`` at real widths, and smollm-360m's paged decode step at
the cell's size from ``eval_shape`` shapes, for one chip of a ``v5e:2x2``
topology.  Nothing runs, so they say nothing about results or times.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and every test worker
imports this file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs.registry import get_arch
from repro.models import model as model_lib
from repro.quant.convert import quantize_params
from repro.serving.kv_cache import pages_needed

SMOLLM = get_arch("smollm-360m")
HEADS, KV_HEADS = SMOLLM.n_heads, SMOLLM.n_kv_heads
D_HEAD = SMOLLM.d_head


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
    return SingleDeviceSharding(topo.devices[0])


def _on(tree, sharding):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _kernel_cases():
    from repro.kernels.decode_attention.ops import (decode_attention_op,
                                                    paged_decode_attention_op)
    from repro.kernels.ecc_decode.ecc_decode import ecc_decode_pages
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.int8_pagegemv.int8_pagegemv import paged_int8_gemm
    from repro.kernels.ssd_scan.ssd_scan import ssd_intra_chunk
    from repro.kernels.w4a16_gemv.w4a16_gemv import w4a16_gemm

    bf16, i32, u8 = jnp.bfloat16, jnp.int32, jnp.uint8
    mamba = get_arch("mamba2-130m")
    m_heads = mamba.ssm_expand * mamba.d_model // mamba.ssm_headdim
    return {
        # smollm-360m decode: 4 slots x 512 positions, d_head 64
        "decode_attention": (decode_attention_op, [
            ((4, HEADS, D_HEAD), bf16), ((4, 512, KV_HEADS, D_HEAD), bf16),
            ((4, 512, KV_HEADS, D_HEAD), bf16), ((4,), i32)]),
        # the cell's paged decode read: 16 slots x 128 pages of 16 over a
        # 2049-page pool, smollm-360m heads
        "paged_decode_attention": (paged_decode_attention_op, [
            ((16, HEADS, D_HEAD), bf16),
            ((2049, 16, KV_HEADS, D_HEAD), bf16),
            ((2049, 16, KV_HEADS, D_HEAD), bf16), ((16, 128), i32),
            ((16,), i32)]),
        # smollm-360m prefill of a 256-token prompt
        "flash_attention": (flash_attention_op, [
            ((1, HEADS, 256, D_HEAD), bf16), ((1, KV_HEADS, 256, D_HEAD), bf16),
            ((1, KV_HEADS, 256, D_HEAD), bf16)]),
        # the paper's 256 x 2048 page tile over smollm's d_ff rows
        "int8_pagegemv": (paged_int8_gemm, [
            ((SMOLLM.d_ff, 2048), jnp.int8), ((2048, 4), jnp.int8)]),
        "w4a16_gemv": (w4a16_gemm, [
            ((SMOLLM.d_ff, 1024), u8), ((SMOLLM.d_ff, 16), jnp.float32),
            ((2048, 4), bf16)]),
        # 16 KB flash pages, 163 protected outliers each (paper §VI)
        "ecc_decode": (ecc_decode_pages, [
            ((4, 16384), u8), ((4,), i32), ((4, 163), i32), ((4, 163), u8),
            ((4, 163), u8)]),
        # mamba2-130m: 24 heads x headdim 64, state 128, 4 chunks of 128
        "ssd_scan": (ssd_intra_chunk, [
            ((m_heads, 4, 128), jnp.float32),
            ((1, 4, 128, mamba.ssm_state), jnp.float32),
            ((1, 4, 128, mamba.ssm_state), jnp.float32),
            ((m_heads, 4, 128, mamba.ssm_headdim), jnp.float32)]),
    }


@pytest.mark.parametrize("name", ["decode_attention",
                                  "paged_decode_attention", "flash_attention",
                                  "int8_pagegemv", "w4a16_gemv", "ecc_decode",
                                  "ssd_scan"])
def test_kernel_compiles_for_v5e(name, one_chip):
    fn, shapes = _kernel_cases()[name]
    args = [jax.ShapeDtypeStruct(s, d, sharding=one_chip) for s, d in shapes]
    _compile(lambda *a: fn(*a, interpret=False), *args)


@pytest.mark.parametrize("h,hkv,d", [
    (64, 8, 128), (48, 8, 128), (32, 2, 128), (16, 16, 128), (32, 32, 112),
    (96, 8, 128)])
def test_paged_decode_attention_compiles_for_registry_heads(h, hkv, d,
                                                           one_chip):
    """The other paged GQA head shapes of the registry (dense, moe, vlm,
    hybrid), 8 slots x 128 pages of 16."""
    from repro.kernels.decode_attention.ops import paged_decode_attention_op

    shapes = [((8, h, d), jnp.bfloat16), ((1025, 16, hkv, d), jnp.bfloat16),
              ((1025, 16, hkv, d), jnp.bfloat16), ((8, 128), jnp.int32),
              ((8,), jnp.int32)]
    args = [jax.ShapeDtypeStruct(s, t, sharding=one_chip) for s, t in shapes]
    _compile(paged_decode_attention_op, *args)


def test_smollm_paged_decode_step_compiles_for_v5e(one_chip):
    from repro.serving.core import _jit_decode_paged

    cfg, max_batch, max_seq, page = SMOLLM, 16, 2048, 16
    params = jax.eval_shape(lambda: quantize_params(model_lib.init_params(
        cfg, jax.random.PRNGKey(0), max_seq=max_seq), mode="w8a8"))
    cache = jax.eval_shape(lambda: model_lib.init_paged_cache(
        cfg, max_batch, max_seq, page_size=page))
    cache["block"] = jax.ShapeDtypeStruct(
        (max_batch, pages_needed(max_seq, page)), jnp.int32)
    compiled = _jit_decode_paged(cfg).lower(
        _on(params, one_chip),
        jax.ShapeDtypeStruct((max_batch,), jnp.int32, sharding=one_chip),
        _on(cache, one_chip),
        jax.ShapeDtypeStruct((max_batch,), jnp.bool_, sharding=one_chip),
    ).compile()
    # the paged attention read lowers to the Pallas kernel on the chip, and
    # no f32 copy of every slot's full block row is left in the step
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert not re.search(r"f32\[[0-9,]*\b2048,[0-9,]*\]", text)
    mem = compiled.memory_analysis()
    # weights + KV pool + step temporaries fit one 16 GB chip
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16e9, used
