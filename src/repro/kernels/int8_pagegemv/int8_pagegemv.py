"""Paged W8A8 GeMV/GeMM Pallas TPU kernel — the flash compute-core analogue.

The paper's atomic tile (one 16KB page per compute core; optimal full tile
256x2048 for Cambricon-LLM-S) becomes the VMEM BlockSpec: each grid step
loads a (tile_h, tile_w) int8 weight block — exactly a channel's worth of
pages — multiplies against the resident int8 activation block on the MXU
(int8 x int8 -> int32), and accumulates into the output block, mirroring the
read-compute request pipeline (§IV-B steps 1-5).

Grid: (h_tiles, w_tiles); w is the reduction ("arbitrary") dimension.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(w_ref, x_ref, out_ref):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_ref[...] = jnp.zeros_like(out_ref)

    acc = jax.lax.dot_general(
        w_ref[...], x_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )
    out_ref[...] += acc


@functools.partial(jax.jit, static_argnames=("tile_h", "tile_w", "interpret"))
def paged_int8_gemm(w_q: jax.Array, x_q: jax.Array,
                    tile_h: int = 256, tile_w: int = 2048,
                    interpret: bool = False) -> jax.Array:
    """int32[h, b] = int8[h, w] @ int8[w, b] with paged VMEM tiling.

    Inputs must be pre-padded so tile sizes divide (h, w); see ops.py.
    """
    h, w = w_q.shape
    b = x_q.shape[1]
    assert h % tile_h == 0 and w % tile_w == 0, (h, w, tile_h, tile_w)
    grid = (h // tile_h, w // tile_w)
    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((tile_h, tile_w), lambda i, j: (i, j)),
            pl.BlockSpec((tile_w, b), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((tile_h, b), lambda i, j: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((h, b), jnp.int32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(w_q, x_q)
