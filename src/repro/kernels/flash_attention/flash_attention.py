"""Flash attention (prefill) Pallas TPU kernel.

Grid (B*H, q_blocks, kv_blocks); the kv dimension is sequential ("arbitrary")
and carries online-softmax state (m, l, acc) in VMEM scratch.  GQA is free:
the K/V BlockSpec index_map folds the q-head onto its kv-head, so grouped
heads share K/V blocks without materializing the repeat.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref,
            *, sm_scale, block_q, block_k, causal):
    i, j = pl.program_id(1), pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0].astype(jnp.float32)            # [bq, d]
    k = k_ref[0].astype(jnp.float32)            # [bk, d]
    v = v_ref[0].astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * sm_scale
    if causal:
        qi = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        ki = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qi >= ki, s, NEG_INF)
    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None])
    corr = jnp.exp(m_prev - m_new)
    l_ref[...] = l_ref[...] * corr + p.sum(axis=1)
    acc_ref[...] = acc_ref[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)[:, None]
                    ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "causal", "block_q", "block_k", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    causal: bool = True, block_q: int = 512,
                    block_k: int = 512, interpret: bool = False) -> jax.Array:
    """q: [B, H, Sq, D]; k/v: [B, Hkv, Skv, D] -> [B, H, Sq, D].

    Sq/Skv must divide by the block sizes (ops.py pads).
    """
    b, h, sq, d = q.shape
    hkv, skv = k.shape[1], k.shape[2]
    n_rep = h // hkv
    block_q = min(block_q, sq)
    block_k = min(block_k, skv)
    grid = (b * h, sq // block_q, skv // block_k)
    qr = q.reshape(b * h, sq, d)
    kr = k.reshape(b * hkv, skv, d)
    vr = v.reshape(b * hkv, skv, d)

    def kv_map(bh, i, j):
        return ((bh // n_rep) % hkv + (bh // h) * hkv, j, 0)

    return pl.pallas_call(
        functools.partial(_kernel, sm_scale=d ** -0.5, block_q=block_q,
                          block_k=block_k, causal=causal),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
            pl.BlockSpec((1, block_k, d), kv_map),
            pl.BlockSpec((1, block_k, d), kv_map),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda bh, i, j: (bh, i, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(qr, kr, vr).reshape(b, h, sq, d)
