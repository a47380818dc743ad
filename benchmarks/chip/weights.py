"""Seeded random weights, made on the device, named as in the published
checkpoint.

Every tensor is drawn from its own key: the run's seed, folded with a hash
of the tensor's name and, for a per-layer tensor, with the layer index.  So
the serving program's weights (made all at once, layers stacked, in one
jitted call) and the plain reference's (made one layer at a time, after the
program is gone) hold the same values without either taking anything from
the other.
"""

from __future__ import annotations

import dataclasses
import zlib

import jax
import jax.numpy as jnp

DTYPES = {"bf16": jnp.bfloat16, "f32": jnp.float32}


@dataclasses.dataclass(frozen=True)
class Tensor:
    """One checkpoint tensor.  ``std`` None means ones (a norm's gain).
    ``layers`` lists the layers that hold one each (empty: a single
    tensor).
    ``stored`` is the checkpoint's dtype; ``served`` is what the serving
    configuration keeps it in (``int8`` for a w8a8 linear), read by the
    reference and by the byte counts.  ``contract`` is the axis a matmul
    sums over: a quantization scale is shared along it."""

    name: str
    shape: tuple
    std: float | None
    layers: tuple = ()
    stored: str = "bf16"
    served: str = "bf16"
    contract: int = -2


def seed_key(seed: int) -> jax.Array:
    """A key for any whole number: ``PRNGKey`` keeps 32 bits, so the high
    bits are folded in separately."""
    if seed < 0:
        raise ValueError(f"seed must be >= 0, got {seed}")
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def _key(key: jax.Array, t: Tensor) -> jax.Array:
    return jax.random.fold_in(key, zlib.crc32(t.name.encode()) & 0x7FFFFFFF)


def make(t: Tensor, key: jax.Array, layer: int | None = None) -> jax.Array:
    """One tensor (one layer's, where ``layer`` is given) in its stored
    dtype."""
    dtype = DTYPES[t.stored]
    if t.std is None:
        return jnp.ones(t.shape, dtype)
    k = _key(key, t)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    return (jax.random.normal(k, t.shape, jnp.float32) * t.std).astype(dtype)


def make_stacked(t: Tensor, key: jax.Array) -> jax.Array:
    """Every layer's copy of a per-layer tensor, stacked on a leading axis;
    equal, value for value, to :func:`make` per layer."""
    return jax.vmap(lambda i: make(t, key, i))(jnp.asarray(t.layers))


def make_all(tensors, key: jax.Array) -> dict:
    """name -> array (stacked where per-layer), for the serving program."""
    return {t.name: make_stacked(t, key) if t.layers else make(t, key)
            for t in tensors}
