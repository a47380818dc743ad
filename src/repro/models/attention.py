"""Attention: chunked (flash-style) full attention + single-token decode.

Pure-jnp implementations used by every model and by the dry-run lowering.
One Pallas kernel is on the served path: lowered for a TPU,
``paged_decode_attention`` over a plain page pool runs the block-table
kernel in kernels/decode_attention/paged.py, checked against the jnp path in
tests.  int8 pools, the MLA decode, chunked-prefill and full-sequence
attention stay on the jnp code here; kernels/flash_attention and the
contiguous-cache decode kernel are not called from models/.

Memory discipline: scores materialize only per (q_chunk, kv_chunk) block via
a double ``lax.scan`` with online softmax, so prefill_32k fits. Causality is
mask-based inside blocks (upper-triangle blocks are computed-then-masked;
see EXPERIMENTS.md §Perf for the accounting).
"""

from __future__ import annotations


import jax
import jax.numpy as jnp

NEG_INF = -1e30


def _repeat_kv(k: jax.Array, n_rep: int) -> jax.Array:
    """[B, S, Hkv, D] -> [B, S, Hkv*n_rep, D] (GQA head sharing)."""
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                      causal: bool = True, q_chunk: int = 1024,
                      kv_chunk: int = 1024,
                      positions_q: jax.Array | None = None,
                      positions_kv: jax.Array | None = None) -> jax.Array:
    """Online-softmax attention over chunks.

    q: [B, Sq, H, D]; k/v: [B, Skv, Hkv, D]. Returns [B, Sq, H, D].
    """
    b, sq, h, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    q_chunk = min(q_chunk, sq)
    kv_chunk = min(kv_chunk, skv)
    nq = -(-sq // q_chunk)
    nkv = -(-skv // kv_chunk)
    pad_q = nq * q_chunk - sq
    pad_kv = nkv * kv_chunk - skv
    if positions_q is None:
        positions_q = jnp.broadcast_to(jnp.arange(sq), (b, sq))
    if positions_kv is None:
        positions_kv = jnp.broadcast_to(jnp.arange(skv), (b, skv))
    qp = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    pq = jnp.pad(positions_q, ((0, 0), (0, pad_q)), constant_values=-1)
    pkv = jnp.pad(positions_kv, ((0, 0), (0, pad_kv)), constant_values=2**30)

    qs = qp.reshape(b, nq, q_chunk, h, d).transpose(1, 0, 3, 2, 4)  # [nq,B,H,Cq,D]
    ks = kp.reshape(b, nkv, kv_chunk, h, d).transpose(1, 0, 3, 2, 4)
    vs = vp.reshape(b, nkv, kv_chunk, h, d).transpose(1, 0, 3, 2, 4)
    pqs = pq.reshape(b, nq, q_chunk).transpose(1, 0, 2)   # [nq, B, Cq]
    pkvs = pkv.reshape(b, nkv, kv_chunk).transpose(1, 0, 2)

    sm_scale = d ** -0.5

    def q_step(_, qc):
        q_blk, pq_blk = qc  # [B,H,Cq,D], [B,Cq]

        # checkpoint: recompute s/p during backward instead of storing the
        # [B,H,Cq,Ckv] probabilities for every (q,kv) chunk pair (which is
        # what turns a 32k-token prefill into tens of GB of residuals)
        @jax.checkpoint
        def kv_step(carry, kc):
            m, l, acc = carry
            k_blk, v_blk, pk_blk = kc
            s = jnp.einsum("bhqd,bhkd->bhqk", q_blk.astype(jnp.float32),
                           k_blk.astype(jnp.float32)) * sm_scale
            if causal:
                mask = pq_blk[:, None, :, None] >= pk_blk[:, None, None, :]
            else:
                mask = (pk_blk < 2**30)[:, None, None, :]
            s = jnp.where(mask, s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p, v_blk.astype(jnp.float32))
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, q_chunk), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, q_chunk), jnp.float32)
        a0 = jnp.zeros((b, h, q_chunk, d), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0), (ks, vs, pkvs))
        out = acc / jnp.maximum(l, 1e-20)[..., None]
        return None, out

    _, outs = jax.lax.scan(q_step, None, (qs, pqs))  # [nq, B, H, Cq, D]
    out = outs.transpose(1, 0, 3, 2, 4).reshape(b, nq * q_chunk, h, d)
    return out[:, :sq].astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     length: jax.Array) -> jax.Array:
    """One-token attention against a cache.

    q: [B, H, D]; k_cache/v_cache: [B, Smax, Hkv, D]; length: [] or [B]
    (valid prefix length per slot, the new token's kv already written).
    A slot with length 0 produces a garbage-but-finite row (uniform softmax
    over masked scores) — callers ignore inactive slots' outputs.
    """
    b, smax, hkv, d = k_cache.shape
    h = q.shape[1]
    k = _repeat_kv(k_cache, h // hkv)
    v = _repeat_kv(v_cache, h // hkv)
    s = jnp.einsum("bhd,bshd->bhs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    pos = jnp.arange(smax)
    valid = pos[None, :] < jnp.asarray(length).reshape(-1, 1)
    s = jnp.where(valid[:, None, :], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhs,bshd->bhd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# paged KV cache (block-table indirection, per-slot lengths)
#
# A pool is either a plain array [P, page, *row] or — for kv_dtype="int8" —
# a (data int8 [P, page, *row], scale f32 [P, page, *row[:-1]]) pair with
# one symmetric per-row scale over the last axis (per-page-per-head for GQA
# pools, per-page-row for MLA's compressed rows).  Rows quantize ONCE at
# write (prefill scatter + decode append) and dequantize at the gathered
# block-row attend; the scale depends only on the row's own values, so the
# page bits are a pure function of the token span — the property that keeps
# spill/prefetch, migration, and prefix sharing bit-identical.
# ---------------------------------------------------------------------------


def quantize_rows(rows: jax.Array) -> tuple[jax.Array, jax.Array]:
    """float [..., D] -> (int8 [..., D], f32 [...] per-row scale)."""
    rf = rows.astype(jnp.float32)
    absmax = jnp.max(jnp.abs(rf), axis=-1)
    scale = jnp.maximum(absmax / 127.0, 1e-8)
    q = jnp.clip(jnp.round(rf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale.astype(jnp.float32)


def dequantize_rows(q: jax.Array, scale: jax.Array) -> jax.Array:
    return q.astype(jnp.float32) * scale[..., None]


def gather_paged_rows(pages, block_table: jax.Array) -> jax.Array:
    """Materialize each slot's contiguous view from ONE page pool.

    pages: [P, page, *row] (or an int8 ``(data, scale)`` pool — the view
    comes back dequantized to f32); block_table: [B, pages_per_slot] int32
    page ids (0 = the reserved null page).  Returns [B, Smax, *row] with
    Smax = pages_per_slot * page.  Row shape is free — [Hkv, D] for a GQA
    K or V pool, [R] for an MLA compressed-ckv pool, [Dr] for its krope.
    """
    if isinstance(pages, tuple):
        data, scale = pages
        return dequantize_rows(gather_paged_rows(data, block_table),
                               gather_paged_rows(scale, block_table))
    b, pages_per_slot = block_table.shape
    page = pages.shape[1]
    rest = pages.shape[2:]
    return pages[block_table].reshape(b, pages_per_slot * page, *rest)


def write_paged_rows(pages, rows: jax.Array,
                     block_table: jax.Array, lengths: jax.Array,
                     active: jax.Array):
    """Scatter one new token's row per slot into its current page.

    pages: [P, page, *row] or an int8 ``(data, scale)`` pool (rows quantize
    at this write); rows: [B, *row] (this step's values); lengths: [B]
    write positions (= valid length before this token); active: [B] bool.
    Inactive slots are redirected to the reserved null page 0 so their
    garbage never lands in a page owned by a live request.
    """
    page = (pages[0] if isinstance(pages, tuple) else pages).shape[1]
    b = rows.shape[0]
    page_idx = block_table[jnp.arange(b), lengths // page]
    page_idx = jnp.where(active, page_idx, 0)
    offset = lengths % page
    if isinstance(pages, tuple):
        data, scale = pages
        q, s = quantize_rows(rows)
        return (data.at[page_idx, offset].set(q),
                scale.at[page_idx, offset].set(s))
    return pages.at[page_idx, offset].set(rows.astype(pages.dtype))


def scatter_chunk_rows(pages, rows: jax.Array, pid: jax.Array,
                       off: jax.Array):
    """Scatter a chunk's rows at explicit (page, offset) indices — the
    chunked-prefill write path.  rows: [C, *row]; pid/off: [C]."""
    if isinstance(pages, tuple):
        data, scale = pages
        q, s = quantize_rows(rows)
        return data.at[pid, off].set(q), scale.at[pid, off].set(s)
    return pages.at[pid, off].set(rows.astype(pages.dtype))


def gather_paged_kv(k_pages: jax.Array, v_pages: jax.Array,
                    block_table: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Materialize each slot's contiguous KV view from the shared page pool.

    k/v_pages: [P, page, Hkv, D]; block_table: [B, pages_per_slot] int32 page
    ids (0 = the reserved null page).  Returns [B, Smax, Hkv, D] with
    Smax = pages_per_slot * page.
    """
    return (gather_paged_rows(k_pages, block_table),
            gather_paged_rows(v_pages, block_table))


def paged_decode_attention_jnp(q: jax.Array, k_pages, v_pages,
                               block_table: jax.Array,
                               lengths: jax.Array) -> jax.Array:
    """``paged_decode_attention`` in jnp: gather each slot's whole block row
    (dequantized for int8 pools), then ``decode_attention``."""
    k, v = gather_paged_kv(k_pages, v_pages, block_table)
    return decode_attention(q, k, v, lengths)


def paged_decode_attention(q: jax.Array, k_pages, v_pages,
                           block_table: jax.Array,
                           lengths: jax.Array) -> jax.Array:
    """One-token attention against a paged cache.

    q: [B, H, D]; k/v_pages: [P, page, Hkv, D] (or int8 ``(data, scale)``
    pools); block_table: [B, pages_per_slot]; lengths: [B] valid tokens per
    slot (new token included).

    Lowered for a TPU, a plain pool runs the Pallas kernel that reads only
    each slot's live pages (``kernels/decode_attention/paged.py``); every
    other platform, and int8 pools, run ``paged_decode_attention_jnp``.
    """
    if isinstance(k_pages, tuple):
        return paged_decode_attention_jnp(q, k_pages, v_pages, block_table,
                                          lengths)
    from repro.kernels.decode_attention.paged import (
        paged_decode_attention_kernel)
    return jax.lax.platform_dependent(
        q, k_pages, v_pages, block_table, lengths,
        tpu=paged_decode_attention_kernel, default=paged_decode_attention_jnp)


def paged_chunk_attention(q: jax.Array, k_pages: jax.Array,
                          v_pages: jax.Array, block_table: jax.Array,
                          q_positions: jax.Array) -> jax.Array:
    """Multi-query attention for one chunk of prefill against a paged cache.

    q: [B, C, H, D] chunk queries; k/v_pages: [P, page, Hkv, D];
    block_table: [B, pages_per_slot]; q_positions: [B, C] global (cache)
    positions of the chunk queries.  Each query attends exactly the cache
    positions <= its own — all keys are read from the gathered block row, so
    a given position's math is independent of how the prompt was split into
    chunks (the bit-identity contract of chunked prefill; see
    ``models.model.prefill_chunk_into_slot``).
    """
    b, c, h, d = q.shape
    k, v = gather_paged_kv(k_pages, v_pages, block_table)  # [B, Smax, Hkv, D]
    k = _repeat_kv(k, h // k.shape[2])
    v = _repeat_kv(v, h // v.shape[2])
    s = jnp.einsum("bchd,bshd->bhcs", q.astype(jnp.float32),
                   k.astype(jnp.float32)) * (d ** -0.5)
    key_pos = jnp.arange(k.shape[1])
    mask = key_pos[None, None, None, :] <= q_positions[:, None, :, None]
    s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhcs,bshd->bchd", p, v.astype(jnp.float32))
    return out.astype(q.dtype)


def gather_kv_pages(k_pages: jax.Array, v_pages: jax.Array,
                    page_ids: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Pull whole pages out of the pool (spill path of the flash KV tier).

    k/v_pages: [L, P, page, Hkv, D] (the layer-stacked pool); page_ids: [n]
    int32.  Returns ([L, n, page, Hkv, D], same for v).  Callers may pad
    ``page_ids`` with the null page 0 to hit a shape bucket — the junk rows
    are sliced off host-side.
    """
    return jnp.take(k_pages, page_ids, axis=1), \
        jnp.take(v_pages, page_ids, axis=1)


def scatter_kv_pages(k_pages: jax.Array, v_pages: jax.Array,
                     page_ids: jax.Array, ks: jax.Array, vs: jax.Array
                     ) -> tuple[jax.Array, jax.Array]:
    """Write whole pages back into the pool (prefetch path of the KV tier).

    k/v_pages: [L, P, page, Hkv, D]; page_ids: [n]; ks/vs: [L, n, page, Hkv,
    D].  Bucket padding uses the null page 0 with zero payloads — duplicate
    scatters to page 0 write identical values, so the result stays
    deterministic, and null-page contents are never read unmasked.
    """
    return (k_pages.at[:, page_ids].set(ks.astype(k_pages.dtype)),
            v_pages.at[:, page_ids].set(vs.astype(v_pages.dtype)))


def write_paged_kv(k_pages: jax.Array, v_pages: jax.Array, k: jax.Array,
                   v: jax.Array, block_table: jax.Array, lengths: jax.Array,
                   active: jax.Array) -> tuple[jax.Array, jax.Array]:
    """Scatter one new token's K/V per slot into its current page.

    k/v: [B, Hkv, D] (this step's projections); lengths: [B] write positions
    (= valid length before this token); active: [B] bool.  Inactive slots are
    redirected to the reserved null page 0 so their garbage never lands in a
    page owned by a live request.
    """
    return (write_paged_rows(k_pages, k, block_table, lengths, active),
            write_paged_rows(v_pages, v, block_table, lengths, active))
