"""Quant-layer conformance: round-trip properties, the per-column activation
scale fix, the w4a16 tile clamp, and path-predicate router exemption.

These are small/fast (no slow marker) so `make test-quant` rides tier-1;
the big interpret-mode tile sweeps stay in test_kernels.py under -m slow.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.quant.int4 import GROUP, dequantize4, int4_matvec, quantize_weight4
from repro.quant.int8 import (dequantize, int8_matvec, quantize_activation,
                              quantize_weight)

KEY = jax.random.PRNGKey(7)


# ------------------------------------------------------------ round trips
@pytest.mark.parametrize("h,w", [(8, 64), (5, 130), (16, GROUP - 2),
                                 (4, 2 * GROUP + 2)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int8_weight_round_trip(h, w, dtype):
    W = (jax.random.normal(jax.random.fold_in(KEY, h * w), (h, w))
         * 0.3).astype(dtype).astype(jnp.float32)
    q = quantize_weight(W)
    assert q.w_q.dtype == jnp.int8 and q.scale.shape == (h,)
    err = jnp.abs(dequantize(q.w_q, q.scale) - W)
    # symmetric rounding: reconstruction error <= half a quantization step
    assert bool(jnp.all(err <= q.scale[:, None] * 0.5 + 1e-7))


def test_int8_all_zero_rows_hit_scale_clamp():
    W = jnp.zeros((4, 32), jnp.float32).at[1].set(0.5)
    q = quantize_weight(W)
    zero_rows = np.array([0, 2, 3])
    np.testing.assert_allclose(np.asarray(q.scale)[zero_rows], 1e-8)
    deq = np.asarray(dequantize(q.w_q, q.scale))
    np.testing.assert_array_equal(deq[zero_rows], 0.0)
    np.testing.assert_allclose(np.asarray(deq[1]), np.asarray(W[1]),
                               atol=0.5 / 127 / 2 + 1e-7)


@pytest.mark.parametrize("h,w", [(8, GROUP), (6, GROUP - 2),
                                 (4, 2 * GROUP + 2), (3, 390)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_int4_weight_round_trip(h, w, dtype):
    W = (jax.random.normal(jax.random.fold_in(KEY, h + w), (h, w))
         * 0.2).astype(dtype).astype(jnp.float32)
    q = quantize_weight4(W)
    deq = dequantize4(q)
    assert deq.shape == (h, w)
    g = min(GROUP, w)
    ng = -(-w // g)
    Wp = jnp.pad(W, ((0, 0), (0, ng * g - w))).reshape(h, ng, g)
    step = jnp.maximum(jnp.max(jnp.abs(Wp), axis=2) / 7.0, 1e-8)  # [h, ng]
    err = jnp.abs(deq - W)
    bound = jnp.repeat(step, g, axis=1)[:, :w] * 0.5 + 1e-7
    assert bool(jnp.all(err <= bound))


def test_int4_all_zero_rows_round_trip_to_zero():
    W = jnp.zeros((2, GROUP + 2), jnp.float32)
    q = quantize_weight4(W)
    np.testing.assert_array_equal(np.asarray(dequantize4(q)), 0.0)


def test_quantize_activation_per_column():
    x = jax.random.normal(KEY, (64, 5), jnp.float32)
    x = x.at[:, 2].multiply(100.0)  # outlier column
    x_q, x_scale = quantize_activation(x)
    assert x_scale.shape == (5,)
    # each column reconstructs within half its own step — the outlier
    # column does not degrade its batchmates
    err = jnp.abs(x_q.astype(jnp.float32) * x_scale[None, :] - x)
    assert bool(jnp.all(err <= x_scale[None, :] * 0.5 + 1e-7))
    # 1-D input keeps the scalar-scale contract
    xq1, s1 = quantize_activation(x[:, 0])
    assert s1.ndim == 0
    np.testing.assert_array_equal(np.asarray(xq1), np.asarray(x_q[:, 0]))


# ------------------------------------- the per-column outlier bugfix pin
def test_int8_matvec_outlier_batch_accuracy():
    """One 100x-outlier column must not crush the other columns' resolution:
    max-abs-error vs the f32 reference is pinned far below what the old
    per-tensor activation scale produced."""
    h, w, b = 96, 256, 8
    k1, k2 = jax.random.split(KEY)
    W = jax.random.normal(k1, (h, w), jnp.float32) * 0.1
    x = jax.random.normal(k2, (w, b), jnp.float32)
    x = x.at[:, 3].multiply(100.0)
    q = quantize_weight(W)
    y_ref = dequantize(q.w_q, q.scale) @ x  # weight-quant-only f32 reference

    y = int8_matvec(q, x)
    normal = [j for j in range(b) if j != 3]
    err_new = float(jnp.max(jnp.abs(y - y_ref)[:, normal]))

    # the old per-tensor path, reproduced inline as the baseline
    s_pt = jnp.maximum(jnp.max(jnp.abs(x)) / 127.0, 1e-8)
    xq_pt = jnp.clip(jnp.round(x / s_pt), -127, 127).astype(jnp.int8)
    acc = jax.lax.dot_general(q.w_q.astype(jnp.int32),
                              xq_pt.astype(jnp.int32),
                              (((1,), (0,)), ((), ())))
    y_pt = acc.astype(jnp.float32) * q.scale[:, None] * s_pt
    err_old = float(jnp.max(jnp.abs(y_pt - y_ref)[:, normal]))

    assert err_new < err_old / 10, (err_new, err_old)
    assert err_new < 0.15, err_new


def test_paged_int8_gemv_outlier_matches_ref_and_is_accurate():
    from repro.kernels.int8_pagegemv.ops import paged_int8_gemv
    from repro.kernels.int8_pagegemv.ref import paged_int8_gemv_ref

    h, w, b = 64, 256, 4
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 3))
    W = jax.random.normal(k1, (h, w), jnp.float32) * 0.1
    x = jax.random.normal(k2, (w, b), jnp.float32)
    x = x.at[:, 1].multiply(100.0)
    q = quantize_weight(W)
    y_k = paged_int8_gemv(q.w_q, q.scale, x, interpret=True)
    y_r = paged_int8_gemv_ref(q.w_q, q.scale, x)
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))
    y_ref = dequantize(q.w_q, q.scale) @ x
    normal = [j for j in range(b) if j != 1]
    assert float(jnp.max(jnp.abs(y_k - y_ref)[:, normal])) < 0.15
    # kernel output equals int8_matvec bit-for-bit (same quant decisions)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(int8_matvec(q, x)),
                               rtol=1e-6, atol=1e-6)


# --------------------------------------------- w4a16 tile clamp bugfix
@pytest.mark.parametrize("w", [GROUP - 2, GROUP, 2 * GROUP + 2, 390])
def test_w4a16_gemv_tile_clamp_width_sweep(w):
    """Parity vs the dequantize oracle across the clamp's edge widths —
    including w == group, which the old subtract-then-max bounce padded 2x."""
    from repro.kernels.w4a16_gemv.ops import w4a16_gemv
    from repro.kernels.w4a16_gemv.ref import w4a16_gemv_ref

    h = 16
    k1, k2 = jax.random.split(jax.random.fold_in(KEY, w))
    W = jax.random.normal(k1, (h, w), jnp.float32) * 0.1
    x = jax.random.normal(k2, (w, 3), jnp.float32)
    q = quantize_weight4(W)
    y_k = w4a16_gemv(q, x, interpret=True)
    y_r = w4a16_gemv_ref(q, x)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_r),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(np.asarray(int4_matvec(q, x)),
                               np.asarray(y_r), rtol=2e-5, atol=2e-5)


def test_w4a16_tile_width_no_inflation_at_group():
    """The clamp must not round w == group up to 2*group."""
    from repro.kernels.w4a16_gemv import ops as w4ops

    seen = {}
    orig = w4ops.w4a16_gemm

    def spy(wp, sc, xp, *, tile_h, tile_w, group, interpret):
        seen["tile_w"] = tile_w
        seen["padded_w"] = wp.shape[1] * 2
        return orig(wp, sc, xp, tile_h=tile_h, tile_w=tile_w, group=group,
                    interpret=interpret)

    q = quantize_weight4(jnp.ones((8, GROUP), jnp.float32))
    x = jnp.ones((GROUP,), jnp.float32)
    w4ops.w4a16_gemm, _ = spy, None
    try:
        w4ops.w4a16_gemv(q, x, interpret=True)
    finally:
        w4ops.w4a16_gemm = orig
    assert seen["tile_w"] == GROUP
    assert seen["padded_w"] == GROUP  # zero padding, not 2x


# --------------------------------------------- router path exemption
def _moe_tree():
    k = jax.random.PRNGKey(0)
    layer = lambda i: {
        "router": {"w": jax.random.normal(jax.random.fold_in(k, i),
                                          (16, 4), jnp.float32)},
        "up": {"w": jax.random.normal(jax.random.fold_in(k, 10 + i),
                                      (16, 32), jnp.float32)},
        "experts": jax.random.normal(jax.random.fold_in(k, 20 + i),
                                     (4, 16, 32), jnp.float32),
    }
    return {"embed": jax.random.normal(k, (8, 16), jnp.float32),
            "layers": [layer(0), layer(1)]}


def test_quantize_params_router_exempt_through_lists():
    from repro.quant.convert import quantize_params

    qp = quantize_params(_moe_tree())
    for lyr in qp["layers"]:
        # routers nested under the layer *list* keep their float weights
        assert "w" in lyr["router"] and "w_q" not in lyr["router"]
        # ordinary linears in the same layer are quantized
        assert "w_q" in lyr["up"] and lyr["up"]["w_q"].dtype == jnp.int8
        assert lyr["up"]["scale"].shape == (32,)
        # raw expert stacks pass through untouched
        assert lyr["experts"].dtype == jnp.float32
    assert qp["embed"].dtype == jnp.float32


def test_quantize_params_w4a16_mode_same_seam():
    from repro.quant.convert import quantize_params

    qp = quantize_params(_moe_tree(), mode="w4a16")
    for lyr in qp["layers"]:
        assert "w" in lyr["router"]
        assert "w_p4" in lyr["up"] and lyr["up"]["w_p4"].dtype == jnp.uint8
    with pytest.raises(ValueError):
        quantize_params(_moe_tree(), mode="w2a2")


def test_quantized_linear_dispatch_matches_float():
    from repro.models.layers import linear
    from repro.quant.convert import quantize_params

    k1, k2 = jax.random.split(jax.random.fold_in(KEY, 9))
    p = {"w": jax.random.normal(k1, (64, 32), jnp.float32) * 0.2,
         "b": jnp.ones((32,), jnp.float32) * 0.1}
    x = jax.random.normal(k2, (4, 64), jnp.float32)
    x = x.at[0].multiply(50.0)  # outlier token
    y_f = linear(p, x)
    # w4a16's looser bound is the 4-bit weight error, not activation quant
    for mode, bound in (("w8a8", 0.25), ("w4a16", 1.0)):
        y_q = linear(quantize_params(p, mode=mode), x)
        assert y_q.shape == y_f.shape
        # per-token act quant keeps the non-outlier rows tight
        err = float(jnp.max(jnp.abs(y_q - y_f)[1:]))
        assert err < bound, (mode, err)
