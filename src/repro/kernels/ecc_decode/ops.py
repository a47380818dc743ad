"""jit'd wrapper: ECC sidecar decode (bit ops) + fused page clamp/scatter."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.core import ecc as ecc_mod
from repro.kernels.ecc_decode.ecc_decode import ecc_decode_pages


@functools.partial(jax.jit, static_argnames=("interpret",))
def ecc_decode_op(pages: jax.Array, ecc: ecc_mod.PageECC,
                  interpret: bool = False) -> jax.Array:
    """pages: uint8 [B, P] + batched PageECC -> corrected uint8 [B, P]."""
    thr = jax.vmap(lambda t: ecc_mod._majority_bits(t, axis=-1))(ecc.threshold)
    addr, valid = jax.vmap(ecc_mod.hamming_correct)(ecc.addr, ecc.addr_parity)
    addr = jnp.minimum(addr.astype(jnp.int32), pages.shape[-1] - 1)
    in_page = jnp.take_along_axis(pages, addr, axis=1)
    voted = ecc_mod._majority3_u8(in_page, ecc.copies[..., 0],
                                  ecc.copies[..., 1])
    return ecc_decode_pages(pages, thr.astype(jnp.int32), addr, voted,
                            valid.astype(jnp.uint8), interpret=interpret)
