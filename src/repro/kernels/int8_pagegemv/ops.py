"""jit'd public wrapper for the paged W8A8 GeMV kernel."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.int8_pagegemv.int8_pagegemv import paged_int8_gemm
from repro.quant.int8 import quantize_activation


def _pad_to(a: jax.Array, axis: int, mult: int) -> jax.Array:
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return jnp.pad(a, widths)


@functools.partial(jax.jit,
                   static_argnames=("tile_h", "tile_w", "interpret"))
def paged_int8_gemv(w_q: jax.Array, scale: jax.Array, x: jax.Array,
                    tile_h: int = 256, tile_w: int = 2048,
                    interpret: bool = False) -> jax.Array:
    """W8A8 GeMV/GeMM through the Pallas kernel.

    w_q: int8 [h, w]; scale: f32 [h]; x: float [w] or [w, b] -> f32 [h(, b)].
    Pads to tile multiples, quantizes activations per column (one dynamic
    scale per token), dequantizes the int32 accumulators with
    ``scale[h] ⊗ x_scale[b]`` (paper §IV-B compute-core flow).
    """
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    h, w = w_q.shape
    th, tw = min(tile_h, max(h, 8)), min(tile_w, max(w, 128))
    x_q, x_scale = quantize_activation(x)
    w_p = _pad_to(_pad_to(w_q, 0, th), 1, tw)
    x_p = _pad_to(x_q, 0, tw)
    acc = paged_int8_gemm(w_p, x_p, tile_h=th, tile_w=tw,
                          interpret=interpret)[:h]
    y = acc.astype(jnp.float32) * scale[:, None] * x_scale[None, :]
    return y[:, 0] if squeeze else y
