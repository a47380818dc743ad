"""jit'd wrapper for the SSD intra-chunk kernel."""

from __future__ import annotations

import functools

import jax

from repro.kernels.ssd_scan.ssd_scan import ssd_intra_chunk


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_intra_chunk_op(x: jax.Array, a: jax.Array, b_mat: jax.Array,
                       c_mat: jax.Array, chunk: int = 128,
                       interpret: bool = False) -> jax.Array:
    """Layout adapter: [B,S,H,P]/[B,S,H]/[B,S,G,N] -> chunked kernel call.

    S must divide by ``chunk``. Returns y_diag [B, S, H, P] (f32)."""
    b, s, h, p = x.shape
    g, n = b_mat.shape[2], b_mat.shape[3]
    nc = s // chunk
    xr = x.reshape(b, nc, chunk, h, p).transpose(0, 3, 1, 2, 4) \
        .reshape(b * h, nc, chunk, p)
    ar = a.reshape(b, nc, chunk, h).transpose(0, 3, 1, 2) \
        .reshape(b * h, nc, chunk)
    br = b_mat.reshape(b, nc, chunk, g, n).transpose(0, 3, 1, 2, 4) \
        .reshape(b * g, nc, chunk, n)
    cr = c_mat.reshape(b, nc, chunk, g, n).transpose(0, 3, 1, 2, 4) \
        .reshape(b * g, nc, chunk, n)
    y = ssd_intra_chunk(ar, br, cr, xr, interpret=interpret)
    return y.reshape(b, h, nc, chunk, p).transpose(0, 2, 3, 1, 4) \
        .reshape(b, s, h, p)
