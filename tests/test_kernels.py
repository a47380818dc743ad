"""Per-kernel shape/dtype sweeps: Pallas (interpret) vs pure-jnp oracles."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.quant.int8 import quantize_weight
from repro.quant.int4 import quantize_weight4

# the parametrized interpret-mode sweeps take minutes and carry the slow
# marker (`pytest -m "not slow"` is the fast tier); the paged/lengths decode
# attention checks added with the paged-KV PR stay in the fast tier
slow = pytest.mark.slow

KEY = jax.random.PRNGKey(0)


# --------------------------------------------------------------- int8 GeMV
@pytest.mark.parametrize("h,w,b", [
    (256, 2048, 1),     # the paper's -S optimal tile
    (512, 4096, 4),
    (300, 1000, 1),     # ragged -> padding path
    (64, 128, 8),
    (1024, 512, 128),   # decode_32k batch
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@slow
def test_int8_pagegemv(h, w, b, dtype):
    from repro.kernels.int8_pagegemv.ops import paged_int8_gemv
    from repro.kernels.int8_pagegemv.ref import paged_int8_gemv_ref

    k1, k2 = jax.random.split(jax.random.fold_in(KEY, h * w + b))
    W = (jax.random.normal(k1, (h, w)) * 0.1).astype(dtype)
    x = jax.random.normal(k2, (w, b) if b > 1 else (w,)).astype(dtype)
    q = quantize_weight(W.astype(jnp.float32))
    y_k = paged_int8_gemv(q.w_q, q.scale, x, interpret=True)
    y_r = paged_int8_gemv_ref(q.w_q, q.scale, x)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


# ---------------------------------------------------------- flash attention
@pytest.mark.parametrize("b,h,hkv,s,d", [
    (1, 4, 4, 256, 64),
    (2, 8, 2, 512, 64),    # GQA 4:1
    (1, 15, 5, 128, 64),   # smollm heads
    (2, 4, 1, 384, 128),   # MQA, ragged seq -> pad
])
@pytest.mark.parametrize("causal", [True, False])
@slow
def test_flash_attention(b, h, hkv, s, d, causal):
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import attention_ref

    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, s * h), 3)
    q = jax.random.normal(k1, (b, h, s, d), jnp.float32)
    k = jax.random.normal(k2, (b, hkv, s, d), jnp.float32)
    v = jax.random.normal(k3, (b, hkv, s, d), jnp.float32)
    out = flash_attention_op(q, k, v, causal=causal, block_q=128, block_k=128,
                             interpret=True)
    ref = attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@slow
def test_flash_attention_bf16():
    from repro.kernels.flash_attention.ops import flash_attention_op
    from repro.kernels.flash_attention.ref import attention_ref

    k1, k2, k3 = jax.random.split(KEY, 3)
    q = jax.random.normal(k1, (1, 4, 256, 64), jnp.bfloat16)
    k = jax.random.normal(k2, (1, 4, 256, 64), jnp.bfloat16)
    v = jax.random.normal(k3, (1, 4, 256, 64), jnp.bfloat16)
    out = flash_attention_op(q, k, v, block_q=128, block_k=128,
                             interpret=True)
    ref = attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=3e-2, atol=3e-2)


# --------------------------------------------------------- decode attention
@pytest.mark.parametrize("b,h,hkv,smax,d,length", [
    (2, 8, 8, 512, 64, 300),
    (1, 16, 2, 1024, 64, 1000),   # GQA 8:1
    (4, 15, 5, 256, 64, 256),     # full cache
    (2, 8, 1, 300, 128, 77),      # MQA + ragged smax
])
@slow
def test_decode_attention(b, h, hkv, smax, d, length):
    from repro.kernels.decode_attention.ops import decode_attention_op
    from repro.models.attention import decode_attention as ref_fn

    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, smax + h), 3)
    q = jax.random.normal(k1, (b, h, d), jnp.float32)
    kc = jax.random.normal(k2, (b, smax, hkv, d), jnp.float32)
    vc = jax.random.normal(k3, (b, smax, hkv, d), jnp.float32)
    out = decode_attention_op(q, kc, vc, jnp.int32(length), block_k=128,
                              interpret=True)
    ref = ref_fn(q, kc, vc, jnp.int32(length))
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("b,h,hkv,smax,d", [
    (3, 8, 8, 512, 64),
    (2, 16, 2, 256, 64),    # GQA 8:1
    (4, 15, 5, 300, 64),    # ragged smax
])
def test_decode_attention_lengths_vector(b, h, hkv, smax, d):
    """Per-slot lengths [B] (continuous batching) vs the oracle, including a
    zero-length (inactive) slot whose output is ignored."""
    from repro.kernels.decode_attention.ops import decode_attention_op
    from repro.kernels.decode_attention.ref import decode_attention_ref

    k1, k2, k3, k4 = jax.random.split(jax.random.fold_in(KEY, smax * h), 4)
    q = jax.random.normal(k1, (b, h, d), jnp.float32)
    kc = jax.random.normal(k2, (b, smax, hkv, d), jnp.float32)
    vc = jax.random.normal(k3, (b, smax, hkv, d), jnp.float32)
    lens = jax.random.randint(k4, (b,), 1, smax + 1).astype(jnp.int32)
    lens = lens.at[0].set(0)  # inactive slot lane
    out = decode_attention_op(q, kc, vc, lens, block_k=128, interpret=True)
    ref = decode_attention_ref(q, kc, vc, lens)
    np.testing.assert_allclose(np.asarray(out[1:]), np.asarray(ref[1:]),
                               rtol=2e-5, atol=2e-5)
    assert not bool(jnp.isnan(out).any())  # inactive lane finite, not equal


def test_paged_decode_attention_matches_dense():
    """The block-table kernel == dense cache with the same contents; slots
    point at scattered pages of a shared pool."""
    from repro.kernels.decode_attention.ops import paged_decode_attention_op
    from repro.kernels.decode_attention.ref import decode_attention_ref

    b, h, hkv, d, page, pps = 3, 8, 2, 64, 16, 4
    n_pages = b * pps + 1
    k1, k2, k3 = jax.random.split(KEY, 3)
    k_pages = jax.random.normal(k1, (n_pages, page, hkv, d), jnp.float32)
    v_pages = jax.random.normal(k2, (n_pages, page, hkv, d), jnp.float32)
    # interleaved page assignment exercises the indirection
    block = jnp.arange(1, b * pps + 1, dtype=jnp.int32
                       ).reshape(pps, b).T    # slot i -> pages i+1, i+1+b, ...
    q = jax.random.normal(k3, (b, h, d), jnp.float32)
    lens = jnp.asarray([page * pps, 7, 23], jnp.int32)
    out = paged_decode_attention_op(q, k_pages, v_pages, block, lens,
                                    interpret=True)
    k_dense = k_pages[block].reshape(b, pps * page, hkv, d)
    v_dense = v_pages[block].reshape(b, pps * page, hkv, d)
    ref = decode_attention_ref(q, k_dense, v_dense, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


PAGE = 16
# lengths 0, 1, page-1, page, page+1, mid-table and full capacity
WALK_LENS = (0, 1, PAGE - 1, PAGE, PAGE + 1, 21 * PAGE + 5, 40 * PAGE)


def _walk_pools(h, hkv, d, dtype, pps=40):
    """An interleaved block table over a pool whose every row past a
    slot's length, and every unallocated page, is NaN; the same pool with
    those rows zeroed; and the queries."""
    b = len(WALK_LENS)
    n_pages = b * pps + 3              # three pages no slot owns
    k1, k2, k3 = jax.random.split(jax.random.fold_in(KEY, h * d + hkv), 3)
    block = np.arange(1, b * pps + 1, dtype=np.int32).reshape(pps, b).T
    live = np.zeros((n_pages, PAGE), bool)
    for s, n in enumerate(WALK_LENS):
        pos = np.arange(n)
        live[block[s, pos // PAGE], pos % PAGE] = True
    live = jnp.asarray(live)[:, :, None, None]
    pools = []
    for k in (k1, k2):
        x = jax.random.normal(k, (n_pages, PAGE, hkv, d), jnp.float32)
        pools.append((jnp.where(live, x, jnp.nan).astype(dtype),
                      jnp.where(live, x, 0.0).astype(dtype)))
    q = jax.random.normal(k3, (b, h, d), jnp.float32).astype(dtype)
    return q, pools, jnp.asarray(block), jnp.asarray(WALK_LENS, jnp.int32)


@pytest.mark.parametrize("h,hkv,d", [
    (15, 5, 64),     # smollm-360m, n_rep 3
    (64, 8, 128),    # n_rep 8
    (16, 16, 128),   # n_rep 1
    (32, 32, 112),   # n_rep 1, D 112
    (96, 8, 128),    # n_rep 12
])
def test_paged_decode_attention_walk_stops_at_length(h, hkv, d):
    """The kernel on a pool that is NaN past every slot's length matches
    the gathered jnp path on the zero-filled pool: it reads no row at or
    past a length, and a slot of length 0 gives a zero row."""
    from repro.kernels.decode_attention.ops import paged_decode_attention_op
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    q, ((k_nan, k_zero), (v_nan, v_zero)), block, lens = _walk_pools(
        h, hkv, d, jnp.float32)
    out = paged_decode_attention_op(q, k_nan, v_nan, block, lens,
                                    interpret=True)
    ref = paged_decode_attention_ref(q, k_zero, v_zero, block, lens)
    out = np.asarray(out)
    assert np.isfinite(out).all()
    np.testing.assert_array_equal(out[0], 0.0)
    np.testing.assert_allclose(out[1:], np.asarray(ref)[1:],
                               rtol=2e-5, atol=2e-5)


def test_paged_decode_attention_bf16_pool():
    """bf16 pool and queries, as served: bf16 MXU operands with f32
    accumulation against the jnp path's f32 arithmetic on the same values;
    the bound is bf16 rounding of the softmax weights (2^-8 relative)."""
    from repro.kernels.decode_attention.ops import paged_decode_attention_op
    from repro.kernels.decode_attention.ref import paged_decode_attention_ref

    q, ((k_nan, k_zero), (v_nan, v_zero)), block, lens = _walk_pools(
        15, 5, 64, jnp.bfloat16)
    out = paged_decode_attention_op(q, k_nan, v_nan, block, lens,
                                    interpret=True)
    ref = paged_decode_attention_ref(q, k_zero, v_zero, block, lens)
    np.testing.assert_allclose(np.asarray(out, np.float32)[1:],
                               np.asarray(ref, np.float32)[1:],
                               rtol=2e-2, atol=2e-2)


# ----------------------------------------------------------------- W4A16
@pytest.mark.parametrize("h,w,b", [
    (256, 2048, 1), (128, 512, 4), (300, 1024, 1), (64, 256, 2),
])
@slow
def test_w4a16_gemv(h, w, b):
    from repro.kernels.w4a16_gemv.ops import w4a16_gemv
    from repro.kernels.w4a16_gemv.ref import w4a16_gemv_ref

    k1, k2 = jax.random.split(jax.random.fold_in(KEY, h + w))
    W = jax.random.normal(k1, (h, w)) * 0.1
    x = jax.random.normal(k2, (w, b) if b > 1 else (w,))
    q = quantize_weight4(W)
    y_k = w4a16_gemv(q, x, interpret=True)
    y_r = w4a16_gemv_ref(q, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)


# -------------------------------------------------------------- ECC decode
@pytest.mark.parametrize("ber", [0.0, 1e-4, 5e-4])
@slow
def test_ecc_decode_kernel(ber):
    from repro.core import ecc
    from repro.kernels.ecc_decode.ops import ecc_decode_op

    key = jax.random.fold_in(KEY, int(ber * 1e6))
    pages = []
    for i in range(3):
        k0, k1, k2 = jax.random.split(jax.random.fold_in(key, i), 3)
        bulk = (jax.random.normal(k0, (16384,)) * 10).round().clip(-127, 127)
        pos = jax.random.choice(k1, 16384, (64,), replace=False)
        w = bulk.at[pos].set(115.0).astype(jnp.int8)
        pages.append(jax.lax.bitcast_convert_type(w, jnp.uint8))
    pages = jnp.stack(pages)
    e = ecc.encode_pages(pages)
    if ber > 0:
        pages_n = ecc.inject_bitflips(pages, ber, jax.random.fold_in(key, 9))
        e = ecc.inject_ecc_bitflips(e, ber, jax.random.fold_in(key, 10))
    else:
        pages_n = pages
    out_k = np.asarray(ecc_decode_op(pages_n, e, interpret=True))
    out_r = np.asarray(ecc.decode_pages(pages_n, e))
    # Corrupted addresses may collide post-Hamming-correction; write order at
    # collisions is implementation-defined, so exclude colliding positions.
    addr, _ = jax.vmap(ecc.hamming_correct)(e.addr, e.addr_parity)
    for b in range(pages.shape[0]):
        a = np.asarray(addr[b])
        vals, counts = np.unique(a, return_counts=True)
        collide = set(vals[counts > 1].tolist())
        mask = np.ones(pages.shape[1], bool)
        for c in collide:
            mask[int(c)] = False
        np.testing.assert_array_equal(out_k[b][mask], out_r[b][mask])


# ---------------------------------------------------------------- SSD scan
@pytest.mark.parametrize("b,s,h,g,p,n,chunk", [
    (1, 256, 4, 1, 32, 16, 64),
    (2, 128, 8, 2, 16, 32, 32),
    (1, 64, 2, 1, 64, 128, 64),   # mamba2-130m-ish dims
])
@slow
def test_ssd_intra_chunk(b, s, h, g, p, n, chunk):
    from repro.kernels.ssd_scan.ops import ssd_intra_chunk_op
    from repro.kernels.ssd_scan.ref import ssd_intra_chunk_ref

    keys = jax.random.split(jax.random.fold_in(KEY, s * h), 4)
    x = jax.random.normal(keys[0], (b, s, h, p), jnp.float32)
    a = -jnp.abs(jax.random.normal(keys[1], (b, s, h))) * 0.1
    bm = jax.random.normal(keys[2], (b, s, g, n), jnp.float32) * 0.3
    cm = jax.random.normal(keys[3], (b, s, g, n), jnp.float32) * 0.3
    y_k = ssd_intra_chunk_op(x, a, bm, cm, chunk=chunk, interpret=True)
    nc = s // chunk
    ar = a.reshape(b, nc, chunk, h).transpose(0, 3, 1, 2).reshape(b * h, nc, chunk)
    br = bm.reshape(b, nc, chunk, g, n).transpose(0, 3, 1, 2, 4).reshape(b * g, nc, chunk, n)
    cr = cm.reshape(b, nc, chunk, g, n).transpose(0, 3, 1, 2, 4).reshape(b * g, nc, chunk, n)
    xr = x.reshape(b, nc, chunk, h, p).transpose(0, 3, 1, 2, 4).reshape(b * h, nc, chunk, p)
    y_r = ssd_intra_chunk_ref(ar, br, cr, xr)
    y_r = y_r.reshape(b, h, nc, chunk, p).transpose(0, 2, 3, 1, 4).reshape(b, s, h, p)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=1e-4, atol=1e-4)


@slow
def test_ssd_kernel_matches_model_diag_plus_offdiag():
    """Kernel y_diag + jnp inter-chunk == models/ssm.ssd_chunked output."""
    from repro.kernels.ssd_scan.ops import ssd_intra_chunk_op
    from repro.models.ssm import ssd_chunked

    b, s, h, g, p, n, chunk = 1, 128, 4, 1, 16, 8, 128  # single chunk
    keys = jax.random.split(KEY, 4)
    x = jax.random.normal(keys[0], (b, s, h, p), jnp.float32)
    a = -jnp.abs(jax.random.normal(keys[1], (b, s, h))) * 0.1
    bm = jax.random.normal(keys[2], (b, s, g, n), jnp.float32) * 0.3
    cm = jax.random.normal(keys[3], (b, s, g, n), jnp.float32) * 0.3
    y_full, _ = ssd_chunked(x, a, bm, cm, chunk=chunk)
    y_diag = ssd_intra_chunk_op(x, a, bm, cm, chunk=chunk,
                                interpret=True)
    # single chunk -> no inter-chunk term: y_diag must equal the full output
    np.testing.assert_allclose(np.asarray(y_diag), np.asarray(y_full, np.float32),
                               rtol=1e-4, atol=1e-4)
