"""Mixture-of-Experts layer: top-k routing + ragged_dot grouped GEMM.

Two execution paths sharing the same math:

* **Local** (no mesh): tokens sorted by expert, one ``jax.lax.ragged_dot``
  against the stacked expert weights.  Used by smoke tests and examples.

* **Expert-parallel shard_map** (mesh in ctx): activations replicated over
  the ``model`` axis, experts sharded over it; every model shard locally
  sorts the (token, slot) pairs that hit *its* experts into a fixed
  ``capacity``-bounded buffer (2x balanced load; overflow drops, standard
  capacity-style MoE), runs the local ragged GEMM, scatters back, and the
  shards' partial outputs are ``psum``'d over ``model`` — the same collective
  pattern as dense TP-FFN, so MoE costs no extra collective class.  This
  avoids GSPMD's global-argsort gather (which blew per-device memory to
  ~77 GB on qwen2-moe train before this path existed).

Expert padding: non-divisible routed-expert counts (qwen2's 60) pad to the
mesh multiple with router logits pinned to -inf.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distributed import ctx
from repro.models.layers import init_linear, linear

CAPACITY_FACTOR = 2.0


def padded_experts(cfg: ModelConfig, divisor: int = 16) -> int:
    return -(-cfg.n_experts // divisor) * divisor


def init_moe(key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    e = padded_experts(cfg)
    dff = cfg.moe_d_ff
    ks = jax.random.split(key, 8)
    s_in = cfg.d_model ** -0.5
    s_dn = dff ** -0.5

    def w(k, shape, s):
        return (jax.random.normal(k, shape, jnp.float32) * s).astype(dtype)

    p = {
        "router": init_linear(ks[0], cfg.d_model, e, False, jnp.float32),
        "up": w(ks[1], (e, cfg.d_model, dff), s_in),
        "down": w(ks[2], (e, dff, cfg.d_model), s_dn),
    }
    if cfg.gated_ffn:
        p["gate"] = w(ks[3], (e, cfg.d_model, dff), s_in)
    if cfg.n_shared_experts:
        sh_ff = cfg.moe_d_ff * cfg.n_shared_experts
        p["shared"] = {
            "up": init_linear(ks[4], cfg.d_model, sh_ff, False, dtype),
            "down": init_linear(ks[5], sh_ff, cfg.d_model, False, dtype),
        }
        if cfg.gated_ffn:
            p["shared"]["gate"] = init_linear(ks[6], cfg.d_model, sh_ff,
                                              False, dtype)
    return p


def _route(params, xt, cfg: ModelConfig, e: int):
    logits = xt.astype(jnp.float32) @ params["router"]["w"]
    if e > cfg.n_experts:
        pad_mask = jnp.arange(e) >= cfg.n_experts
        logits = jnp.where(pad_mask[None, :], -1e30, logits)
    topw, topi = jax.lax.top_k(logits, cfg.top_k)
    probs = jax.nn.softmax(topw, axis=-1)
    return topi, probs


def _expert_gemm(params, xs, group_sizes, cfg: ModelConfig):
    h_up = jax.lax.ragged_dot(xs, params["up"].astype(xs.dtype), group_sizes)
    if cfg.gated_ffn:
        h_g = jax.lax.ragged_dot(xs, params["gate"].astype(xs.dtype),
                                 group_sizes)
        h = jax.nn.silu(h_g.astype(jnp.float32)).astype(xs.dtype) * h_up
    else:
        h = jax.nn.gelu(h_up.astype(jnp.float32)).astype(xs.dtype)
    return jax.lax.ragged_dot(h, params["down"].astype(xs.dtype), group_sizes)


def _shared_ffn(params, xt, cfg: ModelConfig):
    sh = params["shared"]
    u = linear(sh["up"], xt)
    if cfg.gated_ffn:
        g = linear(sh["gate"], xt)
        hs = jax.nn.silu(g.astype(jnp.float32)).astype(xt.dtype) * u
    else:
        hs = jax.nn.gelu(u.astype(jnp.float32)).astype(xt.dtype)
    return linear(sh["down"], hs)


def _moe_local(params, xt, cfg: ModelConfig, e: int) -> jax.Array:
    n, d = xt.shape
    k = cfg.top_k
    topi, probs = _route(params, xt, cfg, e)
    flat_expert = topi.reshape(-1)
    order = jnp.argsort(flat_expert)
    inv = jnp.argsort(order)
    xs = jnp.repeat(xt, k, axis=0)[order]
    group_sizes = jnp.bincount(flat_expert, length=e).astype(jnp.int32)
    ye = _expert_gemm(params, xs, group_sizes, cfg)
    ye = ye[inv].reshape(n, k, d)
    return jnp.einsum("nkd,nk->nd", ye.astype(jnp.float32), probs)


def _moe_expert_parallel(params, xt, cfg: ModelConfig, e: int) -> jax.Array:
    """Per-(data,model) shard body. xt local tokens [n_loc, d]; expert stacks
    are the LOCAL slices [e_loc, ...]."""
    n, d = xt.shape
    k = cfg.top_k
    e_loc = params["up"].shape[0]
    n_shards = e // e_loc
    shard = jax.lax.axis_index("model")
    e0 = shard * e_loc
    topi, probs = _route(params, xt, cfg, e)   # router is replicated

    flat_expert = topi.reshape(-1)              # [n*k] global expert ids
    local_e = flat_expert - e0
    mine = (local_e >= 0) & (local_e < e_loc)
    sort_key = jnp.where(mine, local_e, e_loc)  # dump bucket sorts last
    # 2x balanced load, floored at 64 so small/imbalanced batches (decode,
    # randomly-initialized routers) never drop; capped at n*k (zero drops)
    capacity = max(int(-(-n * k * CAPACITY_FACTOR // n_shards)), 64)
    capacity = min(capacity, n * k)
    order = jnp.argsort(sort_key)[:capacity]    # hits first, then dumps
    key_sel = sort_key[order]
    token_idx = order // k
    xs = xt[token_idx]
    group_sizes = jnp.bincount(key_sel, length=e_loc).astype(jnp.int32)
    ye = _expert_gemm(params, xs, group_sizes, cfg)
    # zero out dump-bucket rows (they ran through the last real expert's tail
    # group implicitly — ragged_dot leaves rows past the groups at garbage,
    # so mask by selection validity) and combine with router probs.
    valid = (key_sel < e_loc)[:, None]
    w = probs.reshape(-1)[order][:, None]
    contrib = ye.astype(jnp.float32) * w * valid
    y = jnp.zeros((n, d), jnp.float32).at[token_idx].add(contrib)
    return jax.lax.psum(y, "model")


def moe_ffn(params: dict, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    """x: [B, S, D] -> [B, S, D]."""
    b, s, d = x.shape
    e = params["up"].shape[0] if "up" in params else padded_experts(cfg)
    mesh = ctx.mesh()
    xt = x.reshape(-1, d)
    if mesh is not None and "model" in mesh.shape:
        dp = ("pod", "data") if "pod" in mesh.shape else ("data",)
        routed = {"router": params["router"], "up": params["up"],
                  "down": params["down"]}
        specs = {"router": {"w": P()}, "up": P("model", None, None),
                 "down": P("model", None, None)}
        if "gate" in params:
            routed["gate"] = params["gate"]
            specs["gate"] = P("model", None, None)

        def body(p, xloc):
            nl, dd = xloc.shape[0] * xloc.shape[1], xloc.shape[2]
            y = _moe_expert_parallel(p, xloc.reshape(nl, dd), cfg, e)
            return y.reshape(xloc.shape).astype(x.dtype)

        y = shard_map(
            body, mesh=mesh,
            in_specs=(specs, P(dp, None, None)),
            out_specs=P(dp, None, None),
        )(routed, x)
        y = y.reshape(-1, d).astype(jnp.float32)
    else:
        y = _moe_local(params, xt, cfg, e)
    if "shared" in params:
        y = y + _shared_ffn(params, xt, cfg).astype(jnp.float32)
    return y.astype(x.dtype).reshape(b, s, d)
