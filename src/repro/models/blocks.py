"""Attention blocks (GQA, MLA) shared by every transformer family."""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map

from repro.configs.base import ModelConfig
from repro.models.attention import (chunked_attention,
                                    decode_attention, gather_paged_rows,
                                    paged_chunk_attention,
                                    paged_decode_attention,
                                    scatter_chunk_rows, write_paged_kv,
                                    write_paged_rows)
from repro.models.layers import (apply_mrope, apply_rope, init_linear,
                                 layer_norm, linear, rms_norm)


def norm(cfg: ModelConfig, params: dict, x: jax.Array) -> jax.Array:
    if cfg.norm == "ln":
        return layer_norm(x, params["g"], params["b"], cfg.norm_eps)
    return rms_norm(x, params["g"], cfg.norm_eps)


def init_norm(cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    p = {"g": jnp.ones((cfg.d_model,), dtype)}
    if cfg.norm == "ln":
        p["b"] = jnp.zeros((cfg.d_model,), dtype)
    return p


# ---------------------------------------------------------------------------
# GQA attention block
# ---------------------------------------------------------------------------


def init_attn(key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 4)
    qd = cfg.n_heads * cfg.d_head
    kvd = cfg.n_kv_heads * cfg.d_head
    return {
        "q": init_linear(ks[0], cfg.d_model, qd, cfg.use_bias, dtype),
        "k": init_linear(ks[1], cfg.d_model, kvd, cfg.use_bias, dtype),
        "v": init_linear(ks[2], cfg.d_model, kvd, cfg.use_bias, dtype),
        "o": init_linear(ks[3], qd, cfg.d_model, False, dtype),
    }


def _mrope_decode_pos(cfg: ModelConfig, pos):
    """M-RoPE position of the text token at cache index ``pos``: prefill
    assigns text tokens ``idx - n_vision + side`` (layers.mrope_positions),
    and decode must continue that stream, not the raw cache index."""
    from repro.models.layers import mrope_grid_side

    return pos - cfg.n_vision_tokens + mrope_grid_side(cfg.n_vision_tokens)


def _rope_qk(cfg: ModelConfig, q, k, positions):
    if cfg.rope_mode == "standard":
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_fraction)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_fraction)
    elif cfg.rope_mode == "mrope":
        q = apply_mrope(q, positions, cfg.rope_theta)
        k = apply_mrope(k, positions, cfg.rope_theta)
    return q, k


def attn_full(params: dict, x: jax.Array, cfg: ModelConfig,
              positions: jax.Array, causal: bool = True,
              kv_override: jax.Array | None = None
              ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence attention. Returns (out, k, v) so callers can build the
    cache. ``kv_override``: cross-attention source states [B, Senc, D]."""
    b, s, _ = x.shape
    from repro.distributed import ctx

    src = kv_override if kv_override is not None else x
    q = linear(params["q"], x).reshape(b, s, cfg.n_heads, cfg.d_head)
    k = linear(params["k"], src).reshape(b, src.shape[1], cfg.n_kv_heads,
                                         cfg.d_head)
    v = linear(params["v"], src).reshape(b, src.shape[1], cfg.n_kv_heads,
                                         cfg.d_head)
    if kv_override is None:
        q, k = _rope_qk(cfg, q, k, positions)
    # constraint policy "heads" won the §Perf bake-off: the alternative
    # (q seq-sharded + K/V gathered) measured WORSE (48.8 vs 24.4 GB/layer
    # of all-gather on command-r train) because the o-proj/FFN TP dims then
    # conflict with the sequence sharding on the same mesh axis.
    q = ctx.constrain(q, kind="heads")
    k = ctx.constrain(k, kind="heads")
    v = ctx.constrain(v, kind="heads")
    out = chunked_attention(q, k, v, causal=causal and kv_override is None)
    out = ctx.constrain(out, kind="heads")
    out = linear(params["o"], out.reshape(b, s, -1))
    return out, k, v


def attn_decode(params: dict, x: jax.Array, cfg: ModelConfig,
                k_cache: jax.Array, v_cache: jax.Array, pos: jax.Array
                ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention. x: [B, D]; caches [B, Smax, Hkv, Dh]; pos [].

    Returns (out [B, D], new k_cache, new v_cache)."""
    b = x.shape[0]
    q = linear(params["q"], x).reshape(b, 1, cfg.n_heads, cfg.d_head)
    k = linear(params["k"], x).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    v = linear(params["v"], x).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    posb = jnp.broadcast_to(jnp.asarray(pos).reshape(1, 1), (b, 1))
    if cfg.rope_mode == "mrope":
        mpos = _mrope_decode_pos(cfg, jnp.asarray(pos))
        pos3 = jnp.broadcast_to(mpos.reshape(1, 1, 1), (3, b, 1))
        q, k = _rope_qk(cfg, q, k, pos3)
    else:
        q, k = _rope_qk(cfg, q, k, posb)
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k.astype(k_cache.dtype), pos, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v.astype(v_cache.dtype), pos, axis=1)
    out = decode_attention(q[:, 0], k_cache, v_cache, pos + 1)
    out = linear(params["o"], out.reshape(b, -1))
    return out, k_cache, v_cache


def attn_decode_paged(params: dict, x: jax.Array, cfg: ModelConfig,
                      k_pages: jax.Array, v_pages: jax.Array,
                      block_table: jax.Array, lengths: jax.Array,
                      active: jax.Array
                      ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token attention against a paged per-slot cache.

    x: [B, D]; k/v_pages: [P, page, Hkv, Dh]; block_table: [B,
    pages_per_slot]; lengths: [B] per-slot valid lengths (the new token's
    write position); active: [B] bool.  Unlike ``attn_decode`` every slot
    carries its own position, so mixed-progress slots decode in one batch.

    Returns (out [B, D], new k_pages, new v_pages)."""
    b = x.shape[0]
    q = linear(params["q"], x).reshape(b, 1, cfg.n_heads, cfg.d_head)
    k = linear(params["k"], x).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    v = linear(params["v"], x).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    posb = lengths.reshape(b, 1)
    if cfg.rope_mode == "mrope":
        pos3 = jnp.broadcast_to(_mrope_decode_pos(cfg, posb)[None], (3, b, 1))
        q, k = _rope_qk(cfg, q, k, pos3)
    else:
        q, k = _rope_qk(cfg, q, k, posb)
    k_pages, v_pages = write_paged_kv(k_pages, v_pages, k[:, 0], v[:, 0],
                                      block_table, lengths, active)
    out = paged_decode_attention(q[:, 0], k_pages, v_pages, block_table,
                                 lengths + active.astype(jnp.int32))
    out = linear(params["o"], out.reshape(b, -1))
    return out, k_pages, v_pages


def attn_prefill_chunk_paged(params: dict, x: jax.Array, cfg: ModelConfig,
                             k_pages: jax.Array, v_pages: jax.Array,
                             block_row: jax.Array, positions: jax.Array,
                             valid: jax.Array
                             ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Chunked-prefill attention for ONE slot against the paged pool.

    x: [1, C, D] chunk hidden states (C is any shape bucket; rows past
    the chunk carry ``valid=False``); k/v_pages: [P, page, Hkv, Dh];
    block_row: [pages_per_slot] the slot's block-table row; positions:
    [1, C] global cache positions (start + arange(C)); valid: [C] bool.

    The chunk's K/V is scattered into the slot's pages FIRST (invalid rows
    are redirected to the reserved null page 0), then every query attends
    the gathered block row under a per-position causal mask — so each
    position's math is identical no matter how the prompt was chunked.
    Returns (out [1, C, D], new k_pages, new v_pages).
    """
    b, c, _ = x.shape
    q = linear(params["q"], x).reshape(b, c, cfg.n_heads, cfg.d_head)
    k = linear(params["k"], x).reshape(b, c, cfg.n_kv_heads, cfg.d_head)
    v = linear(params["v"], x).reshape(b, c, cfg.n_kv_heads, cfg.d_head)
    q, k = _rope_qk(cfg, q, k, positions)
    page = (k_pages[0] if isinstance(k_pages, tuple) else k_pages).shape[1]
    pps = block_row.shape[0]
    gpos = positions[0]
    pid = jnp.where(valid, block_row[jnp.clip(gpos // page, 0, pps - 1)], 0)
    off = gpos % page
    k_pages = scatter_chunk_rows(k_pages, k[0], pid, off)
    v_pages = scatter_chunk_rows(v_pages, v[0], pid, off)
    out = paged_chunk_attention(q, k_pages, v_pages, block_row[None],
                                positions)
    out = linear(params["o"], out.reshape(b, c, -1))
    return out, k_pages, v_pages


def paged_pool_names(cache: dict) -> tuple[str, str]:
    """The two layer-stacked page pools a paged cache spills/prefetches.

    GQA families page full K/V; MLA pages the compressed (ckv, krope) pair
    instead — a page row is [page, R] + [page, Dr] rather than
    2x[page, Hkv, Dh], which is exactly why flash-resident KV is cheapest
    per token for the MLA family (the spilled bytes shrink with the cache).
    """
    return ("ckv", "krope") if "ckv" in cache else ("k", "v")


def paged_pool_keys(cache: dict) -> tuple[str, ...]:
    """Every cache key whose pages move on spill/snapshot — the two data
    pools plus, under kv_dtype="int8", their f32 scale pools.  A page
    payload is one array per key, in THIS order; everything that carries
    payloads (tier, snapshots, wire) treats them as an opaque tuple, which
    is how quantized pages ride the machinery unchanged."""
    a, b = paged_pool_names(cache)
    keys = (a, b)
    if a + "_scale" in cache:
        keys = keys + (a + "_scale", b + "_scale")
    return keys


def kv_swap_out(cache: dict, page_ids: jax.Array) -> tuple[jax.Array, ...]:
    """Spill path of the tiered KV cache: gather whole pages from the pool.

    cache: the paged cache dict (layer-stacked pools); page_ids: [n].
    Returns one page payload per pool key bound for the flash tier —
    ([L, n, page, Hkv, Dh] x2) for GQA k/v pools, ([L, n, page, R],
    [L, n, page, Dr]) for MLA ckv/krope, plus the matching [L, n, page,
    ...] f32 scale payloads when the pools are int8.  The pool itself is
    untouched — the freed pids are simply handed back to the hot allocator.
    """
    return tuple(jnp.take(cache[key], page_ids, axis=1)
                 for key in paged_pool_keys(cache))


def kv_swap_in(cache: dict, page_ids: jax.Array, *payloads: jax.Array
               ) -> dict:
    """Prefetch path: scatter fetched page payloads into (new) hot pages.

    The pages come back on *different* pids than they were spilled from; the
    engine remaps the owning slot's block-table row, which is what keeps
    decode math bit-identical to the all-resident run — attention only ever
    sees the gathered values, not the pids.  ``payloads`` is one array per
    ``paged_pool_keys`` entry, exactly as ``kv_swap_out`` returned them.
    """
    keys = paged_pool_keys(cache)
    assert len(payloads) == len(keys), (len(payloads), keys)
    out = {**cache}
    for key, payload in zip(keys, payloads):
        pool = cache[key]
        out[key] = pool.at[:, page_ids].set(payload.astype(pool.dtype))
    return out


def cross_attn_decode(params: dict, x: jax.Array, cfg: ModelConfig,
                      k_cache: jax.Array, v_cache: jax.Array,
                      enc_len: int) -> jax.Array:
    """Decoder cross-attention against fixed encoder K/V."""
    b = x.shape[0]
    q = linear(params["q"], x).reshape(b, cfg.n_heads, cfg.d_head)
    return linear(params["o"],
                  decode_attention(q, k_cache, v_cache, enc_len
                                   ).reshape(b, -1))


# ---------------------------------------------------------------------------
# MLA (DeepSeek multi-head latent attention)
# ---------------------------------------------------------------------------


def init_mla(key: jax.Array, cfg: ModelConfig, dtype=jnp.bfloat16) -> dict:
    ks = jax.random.split(key, 5)
    qk_head = cfg.qk_nope_dim + cfg.qk_rope_dim
    return {
        "q": init_linear(ks[0], cfg.d_model, cfg.n_heads * qk_head, False, dtype),
        "kv_a": init_linear(ks[1], cfg.d_model,
                            cfg.kv_lora_rank + cfg.qk_rope_dim, False, dtype),
        "kv_a_norm": jnp.ones((cfg.kv_lora_rank,), dtype),
        "kv_b": init_linear(ks[2], cfg.kv_lora_rank,
                            cfg.n_heads * (cfg.qk_nope_dim + cfg.v_head_dim),
                            False, dtype),
        "o": init_linear(ks[3], cfg.n_heads * cfg.v_head_dim, cfg.d_model,
                         False, dtype),
    }


def mla_full(params: dict, x: jax.Array, cfg: ModelConfig,
             positions: jax.Array) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full-sequence MLA. Returns (out, c_kv, k_rope) for the compressed cache."""
    b, s, _ = x.shape
    h = cfg.n_heads
    qk_head = cfg.qk_nope_dim + cfg.qk_rope_dim
    q = linear(params["q"], x).reshape(b, s, h, qk_head)
    q_nope, q_rope = jnp.split(q, [cfg.qk_nope_dim], axis=-1)
    kv = linear(params["kv_a"], x)
    c_kv, k_rope = jnp.split(kv, [cfg.kv_lora_rank], axis=-1)
    c_kv = rms_norm(c_kv, params["kv_a_norm"], cfg.norm_eps)
    kvb = linear(params["kv_b"], c_kv).reshape(
        b, s, h, cfg.qk_nope_dim + cfg.v_head_dim)
    k_nope, v = jnp.split(kvb, [cfg.qk_nope_dim], axis=-1)
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)
    k_rope = apply_rope(k_rope.reshape(b, s, 1, cfg.qk_rope_dim), positions,
                        cfg.rope_theta)
    k_rope_h = jnp.broadcast_to(k_rope, (b, s, h, cfg.qk_rope_dim))
    qf = jnp.concatenate([q_nope, q_rope], axis=-1)
    kf = jnp.concatenate([k_nope, k_rope_h], axis=-1)
    # pad v to qk_head so we can reuse chunked_attention, then slice back
    vpad = jnp.pad(v, ((0, 0), (0, 0), (0, 0), (0, qk_head - cfg.v_head_dim)))
    # chunked_attention applies the qk_head^-0.5 scale (the MLA convention)
    out = chunked_attention(qf, kf, vpad, causal=True)[..., :cfg.v_head_dim]
    out = linear(params["o"], out.reshape(b, s, -1))
    return out, c_kv, k_rope[:, :, 0, :]


def _mla_decode_qkv(params: dict, x: jax.Array, cfg: ModelConfig,
                    posb: jax.Array):
    """Shared decode-token projections: (q_nope, roped q_rope, normed c_kv,
    roped k_rope) for one token per lane at per-lane positions ``posb``
    ([B, 1])."""
    b = x.shape[0]
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr = cfg.qk_nope_dim, cfg.qk_rope_dim
    q = linear(params["q"], x).reshape(b, h, dn + dr)
    q_nope, q_rope = jnp.split(q, [dn], axis=-1)
    kv = linear(params["kv_a"], x)
    c_kv, k_rope = jnp.split(kv, [r], axis=-1)
    c_kv = rms_norm(c_kv, params["kv_a_norm"], cfg.norm_eps)
    q_rope = apply_rope(q_rope[:, None], posb, cfg.rope_theta)[:, 0]
    k_rope = apply_rope(k_rope[:, None, None, :], posb, cfg.rope_theta)[:, 0, 0]
    return q_nope, q_rope, c_kv, k_rope


def _mla_absorbed_attend(params: dict, x: jax.Array, cfg: ModelConfig,
                         q_nope: jax.Array, q_rope: jax.Array,
                         ckv: jax.Array, krope: jax.Array,
                         valid_len: jax.Array) -> jax.Array:
    """Absorbed-matrix attention against a contiguous compressed cache view.

    ckv: [B, Smax, R]; krope: [B, Smax, Dr]; valid_len: [] or [B] tokens
    (new token included).  Per-token FLOPs scale with R + Dr instead of the
    H*(Dn+Dr) decompressed cache width.
    """
    b = x.shape[0]
    h, r = cfg.n_heads, cfg.kv_lora_rank
    dn, dr, dv = cfg.qk_nope_dim, cfg.qk_rope_dim, cfg.v_head_dim
    # absorb W_UK into the query: q_c[b,h,r] = q_nope . W_uk
    from repro.models.layers import dense_weight
    wkb = dense_weight(params["kv_b"]).reshape(r, h, dn + dv)
    w_uk, w_uv = wkb[..., :dn], wkb[..., dn:]
    q_c = jnp.einsum("bhd,rhd->bhr", q_nope.astype(jnp.float32),
                     w_uk.astype(jnp.float32))
    scores = (jnp.einsum("bhr,bsr->bhs", q_c, ckv.astype(jnp.float32))
              + jnp.einsum("bhd,bsd->bhs", q_rope.astype(jnp.float32),
                           krope.astype(jnp.float32)))
    scores = scores * ((dn + dr) ** -0.5)
    smax = ckv.shape[1]
    valid = jnp.arange(smax)[None, :] < jnp.asarray(valid_len).reshape(-1, 1)
    scores = jnp.where(valid[:, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    ctx = jnp.einsum("bhs,bsr->bhr", p, ckv.astype(jnp.float32))
    out = jnp.einsum("bhr,rhd->bhd", ctx, w_uv.astype(jnp.float32))
    return linear(params["o"], out.reshape(b, -1).astype(x.dtype))


def mla_decode(params: dict, x: jax.Array, cfg: ModelConfig,
               ckv_cache: jax.Array, krope_cache: jax.Array, pos: jax.Array
               ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Absorbed-matrix MLA decode: queries hit the compressed cache directly.

    ckv_cache: [B, Smax, R]; krope_cache: [B, Smax, Dr].
    """
    b = x.shape[0]
    posb = jnp.broadcast_to(jnp.asarray(pos).reshape(1, 1), (b, 1))
    q_nope, q_rope, c_kv, k_rope = _mla_decode_qkv(params, x, cfg, posb)
    ckv_cache = jax.lax.dynamic_update_slice_in_dim(
        ckv_cache, c_kv[:, None].astype(ckv_cache.dtype), pos, axis=1)
    krope_cache = jax.lax.dynamic_update_slice_in_dim(
        krope_cache, k_rope[:, None].astype(krope_cache.dtype), pos, axis=1)
    out = _mla_absorbed_attend(params, x, cfg, q_nope, q_rope, ckv_cache,
                               krope_cache, pos + 1)
    return out, ckv_cache, krope_cache


def mla_decode_paged(params: dict, x: jax.Array, cfg: ModelConfig,
                     ckv_pages: jax.Array, krope_pages: jax.Array,
                     block_table: jax.Array, lengths: jax.Array,
                     active: jax.Array
                     ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """One-token MLA decode against a paged compressed cache.

    ckv_pages: [P, page, R]; krope_pages: [P, page, Dr]; block_table: [B,
    pages_per_slot]; lengths: [B] per-slot valid lengths (the new token's
    write position); active: [B] bool.  Pages carry compressed
    [page, R + Dr] rows instead of full K/V, and decode attends the gathered
    compressed block row — the math the all-resident ``mla_decode`` does,
    with per-slot positions instead of the shared cursor.

    Returns (out [B, D], new ckv_pages, new krope_pages)."""
    b = x.shape[0]
    posb = lengths.reshape(b, 1)
    q_nope, q_rope, c_kv, k_rope = _mla_decode_qkv(params, x, cfg, posb)
    ckv_pages = write_paged_rows(ckv_pages, c_kv, block_table, lengths,
                                 active)
    krope_pages = write_paged_rows(krope_pages, k_rope, block_table, lengths,
                                   active)
    ckv = gather_paged_rows(ckv_pages, block_table)      # [B, Smax, R]
    krope = gather_paged_rows(krope_pages, block_table)  # [B, Smax, Dr]
    out = _mla_absorbed_attend(params, x, cfg, q_nope, q_rope, ckv, krope,
                               lengths + jnp.asarray(active, jnp.int32))
    return out, ckv_pages, krope_pages


# ---------------------------------------------------------------------------
# shard_map split-K decode attention (flash-decoding over the model axis)
# ---------------------------------------------------------------------------
#
# GSPMD cannot partition a dynamic-position dynamic_update_slice on the
# sharded sequence dim of the KV cache: it all-gathers the cache, updates,
# and re-scatters (≈2 GB/layer/token on command-r decode_32k — the dominant
# §Roofline collective).  The explicit version below keeps every cache shard
# local: each model shard owns S/16 of the sequence, performs the update only
# if the write position lands in its slice, computes its partial
# online-softmax, and the shards combine with tiny (m, l, o) reductions.


NEG_INF = -1e30


def attn_decode_sharded(params: dict, x: jax.Array, cfg: ModelConfig,
                        k_cache: jax.Array, v_cache: jax.Array,
                        pos: jax.Array, mesh, batch_axes
                        ) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Split-K decode attention. caches [B, Smax, Hkv, Dh] sharded
    (batch_axes, 'model', None, None); x [B, D] sharded (batch_axes,)."""
    from jax.sharding import PartitionSpec as P

    b = x.shape[0]
    q = linear(params["q"], x).reshape(b, 1, cfg.n_heads, cfg.d_head)
    k = linear(params["k"], x).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    v = linear(params["v"], x).reshape(b, 1, cfg.n_kv_heads, cfg.d_head)
    posb = jnp.broadcast_to(jnp.asarray(pos).reshape(1, 1), (b, 1))
    if cfg.rope_mode == "mrope":
        mpos = _mrope_decode_pos(cfg, jnp.asarray(pos))
        pos3 = jnp.broadcast_to(mpos.reshape(1, 1, 1), (3, b, 1))
        q, k = _rope_qk(cfg, q, k, pos3)
    else:
        q, k = _rope_qk(cfg, q, k, posb)

    n_rep = cfg.n_heads // cfg.n_kv_heads

    def body(qb, kb, vb, kc, vc, p):
        s_loc = kc.shape[1]
        shard = jax.lax.axis_index("model")
        s0 = shard * s_loc
        idx = jnp.clip(p - s0, 0, s_loc - 1)
        in_range = (p >= s0) & (p < s0 + s_loc)
        cur_k = jax.lax.dynamic_slice_in_dim(kc, idx, 1, axis=1)
        cur_v = jax.lax.dynamic_slice_in_dim(vc, idx, 1, axis=1)
        new_k = jnp.where(in_range, kb.astype(kc.dtype), cur_k)
        new_v = jnp.where(in_range, vb.astype(vc.dtype), cur_v)
        kc = jax.lax.dynamic_update_slice_in_dim(kc, new_k, idx, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(vc, new_v, idx, axis=1)
        # local online softmax over this shard's sequence slice; GQA via
        # grouped einsum — a materialized repeat would read the cache n_rep
        # times over (12x HBM amplification on command-r's 96q/8kv)
        bq = qb[:, 0].reshape(qb.shape[0], cfg.n_kv_heads, n_rep, cfg.d_head)
        s = jnp.einsum("bgrd,bsgd->bgrs", bq.astype(jnp.float32),
                       kc.astype(jnp.float32)) * (cfg.d_head ** -0.5)
        valid = (s0 + jnp.arange(s_loc))[None, :] < (p + 1)
        s = jnp.where(valid[:, None, None, :], s, NEG_INF)
        m_loc = s.max(-1)                                  # [B, G, R]
        p_exp = jnp.exp(s - m_loc[..., None])
        l_loc = p_exp.sum(-1)
        o_loc = jnp.einsum("bgrs,bsgd->bgrd", p_exp, vc.astype(jnp.float32))
        m = jax.lax.pmax(m_loc, "model")
        scale = jnp.exp(m_loc - m)
        l = jax.lax.psum(l_loc * scale, "model")
        o = jax.lax.psum(o_loc * scale[..., None], "model")
        o = o / jnp.maximum(l, 1e-20)[..., None]
        o = o.reshape(o.shape[0], cfg.n_heads * cfg.d_head)
        return o.astype(x.dtype), kc, vc

    bspec = batch_axes
    cache_spec = P(bspec, "model", None, None)
    out, k_cache, v_cache = shard_map(
        body, mesh=mesh,
        in_specs=(P(bspec, None, None, None), P(bspec, None, None, None),
                  P(bspec, None, None, None), cache_spec, cache_spec, P()),
        out_specs=(P(bspec, None), cache_spec, cache_spec),
        check_vma=False,
    )(q, k, v, k_cache, v_cache, jnp.asarray(pos, jnp.int32))
    out = linear(params["o"], out)
    return out, k_cache, v_cache
