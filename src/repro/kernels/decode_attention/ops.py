"""jit'd wrappers for the decode-attention kernel.

``decode_attention_op`` pads Smax to the kv block and accepts either a shared
scalar cursor or a per-slot lengths vector [B] (continuous batching).
``paged_decode_attention_op`` is the block-table front-end: it gathers each
slot's pages from the shared page pool into the contiguous [B, Smax] layout
the kernel streams over, then masks per-slot valid lengths.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention.decode_attention import gqa_decode_attention


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


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def paged_decode_attention_op(q: jax.Array, k_pages: jax.Array,
                              v_pages: jax.Array, block_table: jax.Array,
                              lengths: jax.Array, block_k: int = 512,
                              interpret: bool = False) -> jax.Array:
    """Paged decode attention over a shared page pool.

    q: [B, H, D]; k/v_pages: [P, page, Hkv, D]; block_table: [B, pages_per
    slot] int32 page ids; lengths: [B] valid tokens per slot.
    """
    from repro.models.attention import gather_paged_kv

    k, v = gather_paged_kv(k_pages, v_pages, block_table)
    return decode_attention_op(q, k, v, lengths, block_k=block_k,
                               interpret=interpret)
