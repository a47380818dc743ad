"""jit'd wrapper for the W4A16 kernel."""

from __future__ import annotations


import jax
import jax.numpy as jnp

from repro.kernels.w4a16_gemv.w4a16_gemv import w4a16_gemm
from repro.quant.int4 import GROUP, QuantizedLinear4


def w4a16_gemv(q: QuantizedLinear4, x: jax.Array, tile_h: int = 256,
               tile_w: int = 2048, interpret: bool = False) -> jax.Array:
    """Not jitted at this level: q.h/q.w are static python ints that drive
    padding/tiling; the inner pallas_call wrapper is jitted."""
    squeeze = x.ndim == 1
    if squeeze:
        x = x[:, None]
    h, w = q.h, q.w
    group = min(GROUP, w)
    # round the tile width down to a multiple of `group` (the scale-group
    # granularity the kernel reshapes by; group is even, so the nibble-pair
    # constraint rides along), flooring at one group.  The old
    # `tw -= tw % (2 * group) or 0; tw = max(tw, 2 * group)` bounce had a
    # dead `or 0` and inflated padding ~2x whenever w < 2 * group
    # (e.g. w == group padded to 2 * group).
    tw = max((min(tile_w, w) // group) * group, group)
    th = min(tile_h, h)
    ph = (-h) % th
    pw = (-w) % tw
    wp = jnp.pad(q.w_packed, ((0, ph), (0, pw // 2)))
    sc = jnp.pad(q.scale, ((0, ph), (0, pw // group)))
    xp = jnp.pad(x, ((0, pw), (0, 0)))
    y = w4a16_gemm(wp, sc, xp, tile_h=th, tile_w=tw, group=group,
                   interpret=interpret)[:h]
    return y[:, 0] if squeeze else y
