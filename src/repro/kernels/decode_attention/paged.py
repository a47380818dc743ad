"""Paged GQA decode attention that reads only each slot's live pages.

One query token per slot attends its own prefix straight out of the shared
page pool ``[P, page, Hkv, D]``, which stays in HBM in the layout the cache
keeps it in.  A kv block is ``pages_per_block`` pages, each fetched by its
own BlockSpec whose index map reads a page id from scalar-prefetched tables
(``_schedule``).  The grid walks the live blocks only, slot after slot, so
its length is the number of live blocks, not ``slots x blocks per slot``.
A page of a partial last block that lies past the slot's length keeps the
id that input fetched on the step before, so the pipeline sees an unchanged
block and issues no DMA.  A slot of length 0 has no step and gets a zero
row.

Why BlockSpecs and not page DMAs in an in-kernel loop: the chip's compiler
keeps the pool in HBM as padded (8, 128) tiles and will not slice one page
out of it where the last two dims are off the tiling (smollm's 5 x 64 rows);
the BlockSpec pipeline makes the same fetches.

Queries are grouped ``[Hkv, n_rep, D]`` against their own KV head, so K and V
are never repeated.  QK and P·V run on the MXU with the pool's dtype as
operands and f32 accumulation; the online softmax is f32.  Positions at or
past the length are masked by ``where`` in the scores and in V, so whatever
an unwritten or stale row holds (NaN included) never reaches the output.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30

# a kv block spans about this many positions ...
_BLOCK_POSITIONS = 256
# ... and one block of K (as laid out in VMEM: rows padded to 8 x 128
# tiles) holds at most this many bytes, so wide-head pools take fewer pages
_BLOCK_BYTES = 1 << 19


def _pages_per_block(page: int, hkv: int, d: int,
                     pages_per_slot: int) -> int:
    """Pages in one kv block, from the page size and the head shape."""
    row_bytes = -(-hkv // 8) * 8 * -(-d // 128) * 128 * 2
    ppb = max(1, _BLOCK_POSITIONS // page)
    while ppb > 1 and ppb * page * row_bytes > _BLOCK_BYTES:
        ppb //= 2
    return min(ppb, pages_per_slot)


def _schedule(block_table: jax.Array, lengths: jax.Array, page: int,
              ppb: int, nb: int):
    """The grid's walk over live kv blocks only, slot after slot.

    Returns (steps, step_slot, step_block, walk): ``steps`` [1] the number
    of live (slot, block) pairs; for each of the ``B * nb`` step slots the
    slot and block it computes (slots past ``steps`` repeat the last live
    step and compute nothing); and, flattened ``[step * ppb + j]``, the page
    id page input j fetches.  Input j reads page ``block * ppb + j`` of the
    step's slot while that page holds a position below the slot's length;
    otherwise it repeats the id it held on the step before (page 0 before
    any), which the pipeline skips as an unchanged block."""
    b, pps = block_table.shape
    live_pages = -(-lengths // page)                          # [B]
    blocks = -(-live_pages // ppb)                            # [B]
    ends = jnp.cumsum(blocks)
    steps = ends[-1]
    t = jnp.minimum(jnp.arange(b * nb), jnp.maximum(steps - 1, 0))
    # compares, not searchsorted: no loop, so the compiler can lift the
    # whole schedule out of a layer scan (it depends on no layer)
    slot = jnp.minimum((t[:, None] >= ends[None, :]).sum(axis=1), b - 1)
    block = t - (ends - blocks)[slot]
    idx = block[:, None] * ppb + jnp.arange(ppb)              # [B*nb, ppb]
    live = ((idx < live_pages[slot][:, None])
            & (jnp.arange(b * nb) < steps)[:, None])
    pid = block_table[slot[:, None], jnp.minimum(idx, pps - 1)]
    step = jnp.arange(b * nb)[:, None]
    last = jax.lax.cummax(jnp.where(live, step, -1), axis=0)
    walk = jnp.where(last >= 0,
                     jnp.take_along_axis(pid, jnp.maximum(last, 0), axis=0),
                     0)
    return (steps.reshape(1).astype(jnp.int32), slot.astype(jnp.int32),
            block.astype(jnp.int32), walk.reshape(-1).astype(jnp.int32))


def _kernel(lens_ref, steps_ref, slot_ref, block_ref, walk_ref, q_ref, *refs,
            ppb, page, hkv, sm_scale):
    del walk_ref  # read by the index maps only
    k_refs, v_refs = refs[:ppb], refs[ppb:2 * ppb]
    o_ref, m_ref, l_ref, acc_ref = refs[2 * ppb:]
    t = pl.program_id(0)
    i = block_ref[t]
    blk = ppb * page
    length = lens_ref[slot_ref[t]]
    rest = length - i * blk

    @pl.when(t < steps_ref[0])
    def _block():
        @pl.when(i == 0)
        def _init():
            m_ref[...] = jnp.full_like(m_ref, NEG_INF)
            l_ref[...] = jnp.zeros_like(l_ref)
            acc_ref[...] = jnp.zeros_like(acc_ref)

        k = jnp.concatenate([r[0] for r in k_refs], axis=0)   # [blk, Hkv, D]
        v = jnp.concatenate([r[0] for r in v_refs], axis=0)
        for h in range(hkv):
            q = q_ref[0, h].astype(k.dtype)                   # [n_rep, D]
            kh, vh = k[:, h, :], v[:, h, :]                   # [blk, D]
            s = jax.lax.dot_general(
                q, kh, (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * sm_scale
            s = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, s.shape, 1) < rest,
                s, NEG_INF)
            vh = jnp.where(
                jax.lax.broadcasted_iota(jnp.int32, vh.shape, 0) < rest,
                vh.astype(jnp.float32), 0.0).astype(vh.dtype)
            m_prev = m_ref[h]                                 # [n_rep, 1]
            m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
            p = jnp.exp(s - m_new)
            corr = jnp.exp(m_prev - m_new)
            l_ref[h] = l_ref[h] * corr + p.sum(axis=1, keepdims=True)
            acc_ref[h] = acc_ref[h] * corr + jax.lax.dot_general(
                p.astype(vh.dtype), vh, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32)
            m_ref[h] = m_new

        @pl.when(rest <= blk)
        def _finish():
            o_ref[0] = (acc_ref[...] / jnp.maximum(l_ref[...], 1e-20)
                        ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_decode_attention_kernel(q: jax.Array, k_pages: jax.Array,
                                  v_pages: jax.Array, block_table: jax.Array,
                                  lengths: jax.Array,
                                  interpret: bool = False) -> jax.Array:
    """q: [B, H, D]; k/v_pages: [P, page, Hkv, D]; block_table: [B,
    pages_per_slot] int32 page ids; lengths: [B] valid tokens per slot (the
    new token included).  Returns [B, H, D] in q's dtype."""
    b, h, d = q.shape
    _, page, hkv, _ = k_pages.shape
    n_rep = h // hkv
    pps = block_table.shape[1]
    ppb = _pages_per_block(page, hkv, d, pps)
    nb = -(-pps // ppb)
    lengths = lengths.astype(jnp.int32)
    sched = _schedule(block_table.astype(jnp.int32), lengths, page, ppb, nb)
    # on the chip the grid is as long as the live blocks; the interpreter
    # takes only static grids, so it runs every step slot (the ones past
    # the live steps compute nothing)
    grid = (b * nb,) if interpret else (jnp.maximum(sched[0][0], 1),)

    def page_map(j):
        return lambda t, lens, steps, slot, block, walk: (
            walk[t * ppb + j], 0, 0, 0)

    pages = [pl.BlockSpec((1, page, hkv, d), page_map(j)) for j in range(ppb)]
    slot = pl.BlockSpec((1, hkv, n_rep, d),
                        lambda t, lens, steps, slot, *_: (slot[t], 0, 0, 0))
    out = pl.pallas_call(
        functools.partial(_kernel, ppb=ppb, page=page, hkv=hkv,
                          sm_scale=d ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[slot, *pages, *pages],
            out_specs=slot,
            scratch_shapes=[
                pltpu.VMEM((hkv, n_rep, 1), jnp.float32),
                pltpu.VMEM((hkv, n_rep, 1), jnp.float32),
                pltpu.VMEM((hkv, n_rep, d), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hkv, n_rep, d), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="paged_decode_attention",
    )(lengths, *sched, q.reshape(b, hkv, n_rep, d),
      *([k_pages] * ppb), *([v_pages] * ppb))
    # a slot of length 0 has no step: its row was never written
    out = jnp.where((lengths > 0)[:, None, None, None], out, 0)
    return out.reshape(b, h, d)
