"""GQA decode attention Pallas kernel (flash-decoding style).

One query token per (batch, head) against a long KV cache: grid
(B*H, kv_blocks), kv sequential with online-softmax scratch.  Positions at or
beyond the slot's valid length are masked (the cache is pre-allocated to
max_seq).  ``length`` may be a scalar (shared cursor, the paper's single-batch
decode) or a per-slot vector [B] (continuous batching: each slot is at a
different position in its own sequence).  K/V BlockSpecs fold grouped heads
onto their kv head (no repeat).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(len_ref, q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, block_k, n_heads):
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)          # [1, d]
    k = k_ref[0].astype(jnp.float32)          # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    d = q.shape[-1]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * (d ** -0.5)
    ki = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
    my_len = len_ref[pl.program_id(0) // n_heads]  # this slot's valid prefix
    s = jnp.where(ki < my_len, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(1) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def gqa_decode_attention(q: jax.Array, k_cache: jax.Array,
                         v_cache: jax.Array, length: jax.Array,
                         block_k: int = 512, interpret: bool = False
                         ) -> jax.Array:
    """q: [B, H, D]; caches [B, Smax, Hkv, D]; length: scalar or [B] int32.

    Returns [B, H, D].  Smax must divide block_k (ops.py pads)."""
    b, h, d = q.shape
    smax, hkv = k_cache.shape[1], k_cache.shape[2]
    n_rep = h // hkv
    block_k = min(block_k, smax)
    grid = (b * h, smax // block_k)
    qr = q.reshape(b * h, 1, d)
    kr = k_cache.transpose(0, 2, 1, 3).reshape(b * hkv, smax, d)
    vr = v_cache.transpose(0, 2, 1, 3).reshape(b * hkv, smax, d)

    def kv_map(bh, j):
        return ((bh // n_rep) % hkv + (bh // h) * hkv, j, 0)

    lens = jnp.broadcast_to(
        jnp.asarray(length, jnp.int32).reshape(-1), (b,))
    return pl.pallas_call(
        functools.partial(_kernel, block_k=block_k, n_heads=h),
        grid=grid,
        in_specs=[
            pl.BlockSpec(memory_space=pltpu.SMEM),
            pl.BlockSpec((1, 1, d), lambda bh, j: (bh, 0, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, 1, d), lambda bh, j: (bh, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, 1, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1,), jnp.float32),
            pltpu.VMEM((1, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(lens, qr, kr, vr).reshape(b, h, d)
