"""W4A16 GeMV Pallas kernel (paper §VIII-B / Fig. 11).

Packed int4 weights (two nibbles per byte) are unpacked and dequantized
in-VMEM with group-wise scales, then matmul'd against 16-bit activations.
Tiles follow the same page-derived shapes as the int8 kernel (a page holds
2x the elements at 4 bits — the planner's bytes_per_elem=0.5 mode).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(wp_ref, scale_ref, xe_ref, xo_ref, out_ref, *, group):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    # byte k of a row packs columns 2k (low nibble) and 2k+1 (high nibble);
    # rather than interleave the nibbles back (a reshape Mosaic refuses),
    # each half multiplies the matching even / odd rows of x
    packed = wp_ref[...].astype(jnp.int32)     # [th, tw//2]
    lo = ((packed & 0xF) - 8).astype(jnp.float32)
    hi = (((packed >> 4) & 0xF) - 8).astype(jnp.float32)
    th, half = lo.shape
    scales = scale_ref[...]                    # [th, tw//group]
    ng = scales.shape[1]
    # a scale group of `group` columns spans group//2 bytes
    s = jnp.broadcast_to(scales[:, :, None], (th, ng, group // 2)
                         ).reshape(th, half)
    dn = (((1,), (0,)), ((), ()))
    out_ref[...] += (
        jax.lax.dot_general(lo * s, xe_ref[...].astype(jnp.float32), dn,
                            preferred_element_type=jnp.float32)
        + jax.lax.dot_general(hi * s, xo_ref[...].astype(jnp.float32), dn,
                              preferred_element_type=jnp.float32))


@functools.partial(jax.jit, static_argnames=("tile_h", "tile_w", "group",
                                             "interpret"))
def w4a16_gemm(w_packed: jax.Array, scales: jax.Array, x: jax.Array,
               tile_h: int = 256, tile_w: int = 2048, group: int = 128,
               interpret: bool = False) -> jax.Array:
    """f32[h, b] = dequant(int4[h, w]) @ f16/f32[w, b].

    w_packed: uint8 [h, w//2]; scales: f32 [h, w//group]; x: [w, b].
    Pre-padded to tile multiples (see ops.py)."""
    h, wb = w_packed.shape
    w = wb * 2
    b = x.shape[1]
    assert h % tile_h == 0 and w % tile_w == 0
    grid = (h // tile_h, w // tile_w)
    return pl.pallas_call(
        functools.partial(_kernel, group=group),
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_h, tile_w // 2), lambda i, j: (i, j)),
            pl.BlockSpec((tile_h, tile_w // group), lambda i, j: (i, j)),
            pl.BlockSpec((tile_w // 2, b), lambda i, j: (j, 0)),
            pl.BlockSpec((tile_w // 2, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_h, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, b), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(w_packed, scales, x[0::2], x[1::2])
