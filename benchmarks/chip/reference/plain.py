"""Plain float32 pieces shared by the references: norms, rotary embedding,
causal attention, and the weight precisions a reference runs in.

Nothing here imports the serving program.  Matmuls run under
``jax.default_matmul_precision("highest")``, set by the caller, so float32
means float32 on the TPU too.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

import weights as W

# one step below each stored precision: what the control computes in
LOWER = {"int8": "int4", "bf16": "int8", "f32": "bf16"}
BITS = {"int8": 8, "int4": 4}


def quant_dequant(w: jax.Array, bits: int, axis: int) -> jax.Array:
    """Symmetric round-to-nearest with one scale per output channel (the
    absmax along the summed ``axis``), returned dequantized in f32."""
    qmax = 2.0 ** (bits - 1) - 1
    w = w.astype(jnp.float32)
    s = jnp.maximum(jnp.max(jnp.abs(w), axis=axis, keepdims=True) / qmax,
                    1e-8)
    return jnp.clip(jnp.round(w / s), -qmax, qmax) * s


def as_run(t: W.Tensor, w: jax.Array, mode: str) -> jax.Array:
    """A tensor as the reference computes with it: ``served`` keeps the
    serving configuration's precision (w8a8 linears quantized per output
    channel), ``control`` drops every weight one precision lower, and
    ``stored`` takes the checkpoint's values as they are."""
    prec = {"served": t.served, "control": LOWER[t.served],
            "stored": t.stored}[mode]
    if w.ndim < 2 or prec == "f32":
        return w.astype(jnp.float32)
    if prec == "bf16":
        return w.astype(jnp.bfloat16).astype(jnp.float32)
    return quant_dequant(w, BITS[prec], t.contract)


def layer_weights(tensors, key, layer, mode: str) -> dict:
    """One layer's tensors (the per-layer ones held at ``layer``) in f32."""
    return {t.name: as_run(t, W.make(t, key, layer if t.layers else None),
                           mode) for t in tensors}


def rms_norm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    """x [T, H, D]: rotate adjacent feature pairs (2i, 2i+1) by pos *
    theta^(-2i/D)."""
    d = x.shape[-1]
    ang = pos[:, None].astype(jnp.float32) * (
        theta ** (-jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                     -1).reshape(x.shape)


def causal_softmax(s):
    """s [H, T, T] scores -> probabilities, query t sees keys 0..t."""
    t = s.shape[-1]
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]
    return jax.nn.softmax(jnp.where(mask, s, -jnp.inf), axis=-1)


def swiglu(x, wg, wu, wd):
    return (jax.nn.silu(x @ wg) * (x @ wu)) @ wd
