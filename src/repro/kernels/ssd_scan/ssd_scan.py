"""Mamba-2 SSD intra-chunk Pallas kernel (the quadratic "duality" part).

Per (batch*head, chunk): with log-decays a, inputs x, and B/C projections,
    L[i,j] = exp(cumsum(a)_i - cumsum(a)_j)  for i >= j (else 0)
    y      = ((C @ B^T) * L) @ x
The inter-chunk recurrence (linear part) stays in jnp (models/ssm.py); this
kernel covers the FLOPs-dominant blockwise attention-like contraction.
B/C BlockSpecs fold grouped heads onto their group (ngroups < nheads).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(a_ref, b_ref, c_ref, x_ref, o_ref):
    a = a_ref[0, 0].astype(jnp.float32)       # [1, Q]
    bmat = b_ref[0, 0].astype(jnp.float32)    # [Q, N]
    cmat = c_ref[0, 0].astype(jnp.float32)    # [Q, N]
    x = x_ref[0, 0].astype(jnp.float32)       # [Q, P]
    q = a.shape[1]
    ii = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0)
    jj = jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    # cumsum as a masked row sum: Mosaic has no cumsum lowering
    cs = jnp.sum(jnp.where(jj <= ii, jnp.broadcast_to(a, (q, q)), 0.0),
                 axis=1, keepdims=True)               # [Q, 1]
    diff = cs - jnp.transpose(cs)
    ell = jnp.where(ii >= jj, jnp.exp(diff), 0.0)
    s = jax.lax.dot_general(cmat, bmat, (((1,), (1,)), ((), ())),
                            preferred_element_type=jnp.float32) * ell
    y = jax.lax.dot_general(s, x, (((1,), (0,)), ((), ())),
                            preferred_element_type=jnp.float32)
    o_ref[0, 0] = y.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def ssd_intra_chunk(a: jax.Array, b_mat: jax.Array, c_mat: jax.Array,
                    x: jax.Array, interpret: bool = False) -> jax.Array:
    """a: [BH, C, Q]; b_mat/c_mat: [BG, C, Q, N]; x: [BH, C, Q, P].

    BH = batch*heads, BG = batch*groups; heads fold onto groups in the
    BlockSpec index maps.  ``a`` rides as [BH, C, 1, Q] so its block's last
    two dims equal the array's, as the TPU lowering requires.  Returns
    y_diag [BH, C, Q, P] (f32)."""
    bh, nc, qq = a.shape
    bg, n = b_mat.shape[0], b_mat.shape[3]
    p = x.shape[3]
    rep = bh // bg
    grid = (bh, nc)

    def group_map(i, c):
        return ((i // rep) % bg, c, 0, 0)

    return pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, qq), lambda i, c: (i, c, 0, 0)),
            pl.BlockSpec((1, 1, qq, n), group_map),
            pl.BlockSpec((1, 1, qq, n), group_map),
            pl.BlockSpec((1, 1, qq, p), lambda i, c: (i, c, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, qq, p), lambda i, c: (i, c, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((bh, nc, qq, p), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
    )(a.reshape(bh, nc, 1, qq), b_mat, c_mat, x)
