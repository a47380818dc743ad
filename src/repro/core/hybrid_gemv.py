"""Hybrid NPU+flash GeMV as a composable JAX module (paper C1).

A weight matrix is partitioned by the §V plan into a *flash region*
(``flash_rows`` rows, executed tile-by-tile by the paged int8 kernel — the
compute-core analogue, with the outlier-ECC decode fused in front) and an
*NPU region* (remaining rows, plain dense GeMV — the weights that stream over
the channel).  Numerically the two paths agree exactly; structurally they
mirror the hardware mapping, and the flash path's Pallas kernel is the TPU
hot-spot implementation.
"""

from __future__ import annotations

from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core import ecc as ecc_mod
from repro.core import tiling
from repro.core.hw import FlashSpec
from repro.quant.int8 import quantize_weight


class HybridWeights(NamedTuple):
    """A planned, quantized, (optionally) ECC-protected weight matrix."""

    flash_wq: jax.Array        # int8 [flash_rows, w]
    flash_scale: jax.Array     # f32  [flash_rows]
    npu_wq: jax.Array          # int8 [npu_rows, w]
    npu_scale: jax.Array       # f32  [npu_rows]
    ecc: Optional[ecc_mod.PageECC]  # sidecar for the flash region's pages
    tile_h: int
    tile_w: int


def plan_and_quantize(w: jax.Array, flash: FlashSpec,
                      with_ecc: bool = False,
                      plan: tiling.MatrixPlan | None = None) -> HybridWeights:
    """Quantize + split a float weight matrix per the §V plan."""
    h, width = w.shape
    plan = plan or tiling.plan_matrix(h, width, flash)
    q = quantize_weight(w)
    fr = plan.flash_rows
    flash_wq, npu_wq = q.w_q[:fr], q.w_q[fr:]
    flash_scale, npu_scale = q.scale[:fr], q.scale[fr:]
    ecc = None
    if with_ecc and fr:
        pages = _to_pages(flash_wq)
        ecc = ecc_mod.encode_pages(pages)
    return HybridWeights(flash_wq=flash_wq, flash_scale=flash_scale,
                         npu_wq=npu_wq, npu_scale=npu_scale, ecc=ecc,
                         tile_h=plan.tile.h, tile_w=plan.tile.w)


def _to_pages(w_q: jax.Array, page_elems: int = ecc_mod.PAGE_ELEMS) -> jax.Array:
    flat = jax.lax.bitcast_convert_type(w_q.reshape(-1), jnp.uint8)
    pad = (-flat.shape[0]) % page_elems
    flat = jnp.pad(flat, (0, pad))
    return flat.reshape(-1, page_elems)


def _from_pages(pages: jax.Array, shape: tuple[int, int]) -> jax.Array:
    n = shape[0] * shape[1]
    flat = pages.reshape(-1)[:n]
    return jax.lax.bitcast_convert_type(flat, jnp.int8).reshape(shape)


def corrupt_flash_region(hw: HybridWeights, ber: float, key: jax.Array,
                         corrupt_ecc: bool = True) -> HybridWeights:
    """Inject NAND bit flips into the flash-resident region (+ its ECC)."""
    pages = _to_pages(hw.flash_wq)
    k1, k2 = jax.random.split(key)
    noisy = ecc_mod.inject_bitflips(pages, ber, k1)
    new_ecc = hw.ecc
    if hw.ecc is not None and corrupt_ecc:
        new_ecc = ecc_mod.inject_ecc_bitflips(hw.ecc, ber, k2)
    return hw._replace(flash_wq=_from_pages(noisy, hw.flash_wq.shape),
                       ecc=new_ecc)


def hybrid_gemv(hw: HybridWeights, x: jax.Array,
                use_kernel: bool = True, interpret: bool = False) -> jax.Array:
    """y = W x through the two paths; ECC decode precedes the flash path."""
    flash_wq = hw.flash_wq
    if hw.ecc is not None and flash_wq.shape[0]:
        pages = _to_pages(flash_wq)
        corrected = ecc_mod.decode_pages(pages, hw.ecc)
        flash_wq = _from_pages(corrected, flash_wq.shape)
    parts = []
    if flash_wq.shape[0]:
        if use_kernel:
            from repro.kernels.int8_pagegemv.ops import paged_int8_gemv
            y_f = paged_int8_gemv(flash_wq, hw.flash_scale, x,
                                  tile_h=hw.tile_h, interpret=interpret)
        else:
            from repro.kernels.int8_pagegemv.ref import paged_int8_gemv_ref
            y_f = paged_int8_gemv_ref(flash_wq, hw.flash_scale, x)
        parts.append(y_f)
    if hw.npu_wq.shape[0]:
        from repro.kernels.int8_pagegemv.ref import paged_int8_gemv_ref
        parts.append(paged_int8_gemv_ref(hw.npu_wq, hw.npu_scale, x))
    return jnp.concatenate(parts) if len(parts) > 1 else parts[0]
