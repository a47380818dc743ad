"""Double-buffered weight streaming — the TPU realization of Slice Control.

The paper interleaves NPU-bound weight reads into the channel bubbles left by
read-compute requests.  On a TPU mesh the same idea: while layer k computes
on its (already gathered) weights, layer k+1's ZeRO-3-sharded weights
all-gather in the background.  Expressed with shard_map + ppermute-based ring
all-gather structured so XLA can overlap the collective with the compute
(the collective for step k+1 has no data dependency on step k's compute).

``streamed_matmul_chain`` is the demonstrable primitive: y = x @ W1 @ W2 ...
with every Wi sharded over ``axis`` and gathered one step ahead.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def ring_all_gather(shard: jax.Array, axis: str) -> jax.Array:
    """All-gather along ``axis`` via ppermute ring (overlappable)."""
    n = jax.lax.axis_size(axis)
    idx = jax.lax.axis_index(axis)
    perm = [(i, (i + 1) % n) for i in range(n)]

    def body(i, carry):
        acc, buf = carry
        buf = jax.lax.ppermute(buf, axis, perm)
        src = (idx - i - 1) % n
        acc = jax.lax.dynamic_update_index_in_dim(acc, buf, src, 0)
        return acc, buf

    acc0 = jnp.zeros((n,) + shard.shape, shard.dtype)
    acc0 = jax.lax.dynamic_update_index_in_dim(acc0, shard, idx, 0)
    acc, _ = jax.lax.fori_loop(1, n, lambda i, c: body(i - 1, c),
                               (acc0, shard))
    return acc.reshape((n * shard.shape[0],) + shard.shape[1:])


def streamed_matmul_chain(x: jax.Array, weight_shards: list[jax.Array],
                          mesh: Mesh, axis: str = "data") -> jax.Array:
    """x: [B, D0]; weight_shards[i]: [Di/n, Di+1] sharded on ``axis``.

    Gathers W_{i+1} while computing x @ W_i (double buffering): inside
    shard_map the gather for the next layer is issued before the current
    matmul, so the scheduler can overlap them.
    """

    def body(x_loc, *shards):
        nxt = ring_all_gather(shards[0], axis)
        for i in range(len(shards)):
            w = nxt
            if i + 1 < len(shards):
                nxt = ring_all_gather(shards[i + 1], axis)  # prefetch
            x_loc = x_loc @ w.astype(x_loc.dtype)
        return x_loc

    in_specs = tuple([P(None, None)] + [P(axis, None)] * len(weight_shards))
    return shard_map(body, mesh=mesh, in_specs=in_specs,
                     out_specs=P(None, None),
                     check_vma=False)(x, *weight_shards)


def alpha_split_matmul(x: jax.Array, w: jax.Array, mesh: Mesh,
                       alpha: float, axis_store: str = "data",
                       axis_tp: str = "model") -> jax.Array:
    """Paper's α-split on a TPU mesh (core/partition_plan.py decides α).

    Rows [0, αH) run ship-activations (weights stay sharded on
    ``axis_store``, partial matvec + psum — "read-compute request"); rows
    [αH, H) run ship-weights (all-gather then local matmul — "read request").
    Numerically identical to x @ w; structurally the two collective schedules
    coexist so the compiler can overlap them (the paper's channel-bubble
    filling).
    """
    d, h = w.shape
    h_act = int(alpha * h)

    def body(x_full, w_act_shard, w_gat_shard):
        parts = []
        if h_act:
            # "read-compute": W sharded on the contraction dim; every shard
            # computes a partial GeMM on resident weights, small output psum'd
            n = jax.lax.axis_size(axis_store)
            i = jax.lax.axis_index(axis_store)
            x_slice = jax.lax.dynamic_slice_in_dim(
                x_full, i * (d // n), d // n, axis=1)
            parts.append(jax.lax.psum(
                x_slice @ w_act_shard.astype(x_full.dtype), axis_store))
        if h_act < h:
            # "read": stream (gather) the weight rows, compute locally
            w_gat = ring_all_gather(w_gat_shard, axis_store)
            parts.append(x_full @ w_gat.astype(x_full.dtype))
        return parts[0] if len(parts) == 1 else jnp.concatenate(parts, -1)

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(None, None), P(axis_store, None), P(axis_store, None)),
        out_specs=P(None, None), check_vma=False,
    )(x, w[:, :h_act], w[:, h_act:])
