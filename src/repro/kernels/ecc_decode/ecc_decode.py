"""On-die ECC decode Pallas kernel (paper §VI / Fig. 8b).

Per 16KB page: (1) fake-outlier suppression — any unprotected value whose
|magnitude| exceeds the majority-voted threshold is clamped to 0; (2) outlier
restoration — the 163 protected entries are re-written with the per-bit
majority vote of {in-page value, copy0, copy1} at their (Hamming-corrected)
addresses via an in-kernel fori_loop of dynamic stores.

The address Hamming correction and threshold majority are tiny bit-twiddling
ops done outside the kernel (core/ecc.py); the kernel fuses the page-wide
clamp + scatter, which is the part that touches all 16K elements.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _kernel(page_ref, thr_ref, addr_ref, voted_ref, valid_ref, out_ref):
    b = pl.program_id(0)
    page = page_ref[0].astype(jnp.int32)             # [R, 128] bit patterns
    vals = jnp.where(page >= 128, page - 256, page)  # as signed int8 values
    out = jnp.where(jnp.abs(vals) > thr_ref[b], 0, page)
    r, c = page.shape
    pos = (jax.lax.broadcasted_iota(jnp.int32, (r, c), 0) * c
           + jax.lax.broadcasted_iota(jnp.int32, (r, c), 1))

    # a masked select per protected entry: a dynamic single-byte store is
    # refused by Mosaic (it cannot prove the sublane index a multiple of 8);
    # later entries win at colliding addresses, as a scatter in order would
    def body(i, out):
        hit = (pos == addr_ref[b, i]) & (valid_ref[b, i] != 0)
        return jnp.where(hit, voted_ref[b, i], out)

    out = jax.lax.fori_loop(0, addr_ref.shape[1], body, out)
    out_ref[0] = out.astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ecc_decode_pages(pages: jax.Array, thr: jax.Array, addr: jax.Array,
                     voted: jax.Array, valid: jax.Array,
                     interpret: bool = False) -> jax.Array:
    """pages: uint8 [B, P]; thr: int32 [B]; addr: int32 [B, K];
    voted: uint8 [B, K]; valid: bool->uint8 [B, K].  Returns corrected pages.

    The scatter inside the clamp region restores protected outliers; entries
    with valid=0 keep the clamped value (paper: 2-bit address errors discard
    the protection).  P must divide by 128: a page is tiled [P/128, 128].
    """
    b, p = pages.shape
    if p % 128:
        raise ValueError(f"page of {p} bytes does not tile by 128 lanes")
    tiled = pages.reshape(b, p // 128, 128)
    block = pl.BlockSpec((1, p // 128, 128), lambda i: (i, 0, 0))
    smem = pl.BlockSpec(memory_space=pltpu.SMEM)
    return pl.pallas_call(
        _kernel,
        grid=(b,),
        in_specs=[block, smem, smem, smem, smem],
        out_specs=block,
        out_shape=jax.ShapeDtypeStruct(tiled.shape, jnp.uint8),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",)),
    )(tiled, thr, addr, voted.astype(jnp.int32),
      valid.astype(jnp.int32)).reshape(b, p)
