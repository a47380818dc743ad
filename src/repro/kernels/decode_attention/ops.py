"""jit'd wrappers for the decode-attention kernels.

``decode_attention_op`` pads Smax to the kv block and accepts either a shared
scalar cursor or a per-slot lengths vector [B] over a contiguous cache.
``paged_decode_attention_op`` is the block-table kernel (``paged.py``,
q: [B, H, D]; k/v_pages: [P, page, Hkv, D]; block_table: [B, pages_per_slot];
lengths: [B]): it reads each slot's pages straight from the shared pool, only
as far as the slot's length.  It is the one on the served path:
``models.attention.paged_decode_attention`` lowers to it on a TPU.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import gqa_decode_attention
from repro.kernels.decode_attention.paged import (  # noqa: F401
    paged_decode_attention_kernel as paged_decode_attention_op)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention_op(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                        length: jax.Array, block_k: int = 512,
                        interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; caches [B, Smax, Hkv, D]; length: scalar or [B]."""
    smax = k_cache.shape[1]
    bk = min(block_k, smax)
    pad = (-smax) % bk
    if pad:
        k_cache = jnp.pad(k_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v_cache = jnp.pad(v_cache, ((0, 0), (0, pad), (0, 0), (0, 0)))
    return gqa_decode_attention(q, k_cache, v_cache, length, block_k=bk,
                                interpret=interpret)
