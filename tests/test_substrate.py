"""Substrate: quantization, hybrid GeMV + ECC, training, checkpoint/fault,
serving engine, grad compression, planner."""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import ARCHS, ASSIGNED_ARCHS

KEY = jax.random.PRNGKey(0)


# ------------------------------------------------------------------ quant
def test_int8_quant_roundtrip_error():
    from repro.quant.int8 import dequantize, quantize_weight

    w = jax.random.normal(KEY, (64, 128)) * 0.3
    q = quantize_weight(w)
    err = float(jnp.abs(dequantize(q.w_q, q.scale) - w).max())
    step = float((jnp.abs(w).max(axis=1) / 127.0).max())
    assert err <= step * 0.51


def test_int4_pack_unpack_exact():
    from repro.quant.int4 import pack_nibbles, unpack_nibbles

    w_q = jax.random.randint(KEY, (16, 64), -8, 8).astype(jnp.int8)
    np.testing.assert_array_equal(
        np.asarray(unpack_nibbles(pack_nibbles(w_q))), np.asarray(w_q))


def test_quantize_params_structure():
    from repro.models import model as M
    from repro.quant.convert import quantize_params

    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    p = M.init_params(cfg, KEY, max_seq=32)
    q = quantize_params(p)
    lw = q["layers"]["attn"]["q"]
    assert "w_q" in lw and lw["w_q"].dtype == jnp.int8
    assert lw["scale"].dtype == jnp.float32
    # quantized params still run the full model
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    logits = M.forward(q, cfg, toks, {})
    assert not bool(jnp.isnan(logits).any())


def test_quantized_vs_float_model_close():
    from repro.models import model as M
    from repro.quant.convert import quantize_params

    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    p = M.init_params(cfg, KEY, dtype=jnp.float32, max_seq=32)
    q = quantize_params(p)
    toks = jax.random.randint(KEY, (1, 8), 0, cfg.vocab_size)
    lf = M.forward(p, cfg, toks, {})
    lq = M.forward(q, cfg, toks, {})
    # logits agree in ranking for the top token most of the time
    agree = (jnp.argmax(lf, -1) == jnp.argmax(lq, -1)).mean()
    assert float(agree) > 0.7


# ------------------------------------------------------- hybrid GeMV + ECC
def test_hybrid_gemv_paths_match():
    from repro.core.hw import CAMBRICON_LLM_S
    from repro.core.hybrid_gemv import hybrid_gemv, plan_and_quantize

    w = jax.random.normal(KEY, (512, 2048)) * 0.05
    x = jax.random.normal(jax.random.fold_in(KEY, 1), (2048,))
    hw = plan_and_quantize(w, CAMBRICON_LLM_S)
    y_kernel = hybrid_gemv(hw, x, use_kernel=True, interpret=True)
    y_ref = hybrid_gemv(hw, x, use_kernel=False)
    np.testing.assert_array_equal(np.asarray(y_kernel), np.asarray(y_ref))
    rel = float(jnp.linalg.norm(y_ref - w @ x) / jnp.linalg.norm(w @ x))
    assert rel < 0.05  # int8 quantization noise only


def test_hybrid_gemv_ecc_recovers():
    from repro.core.hw import CAMBRICON_LLM_S
    from repro.core.hybrid_gemv import (corrupt_flash_region, hybrid_gemv,
                                        plan_and_quantize)

    w = jax.random.normal(KEY, (1024, 2048)) * 0.05
    x = jax.random.normal(jax.random.fold_in(KEY, 2), (2048,))
    ref = w @ x
    hw = plan_and_quantize(w, CAMBRICON_LLM_S, with_ecc=True)
    noisy = corrupt_flash_region(hw, 2e-4, jax.random.fold_in(KEY, 3))
    err_ecc = float(jnp.linalg.norm(
        hybrid_gemv(noisy, x, interpret=True) - ref))
    err_raw = float(jnp.linalg.norm(
        hybrid_gemv(noisy._replace(ecc=None), x, interpret=True) - ref))
    assert err_ecc < err_raw


# ------------------------------------------------------------- training
def test_train_step_decreases_loss():
    from repro.models import model as M
    from repro.training.optimizer import init_adamw
    from repro.training.train_loop import make_train_step
    from repro.training.data import DataState, make_batch

    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    params = M.init_params(cfg, KEY, dtype=jnp.float32, max_seq=64)
    opt = init_adamw(params)
    step = jax.jit(make_train_step(cfg, microbatches=1, lr=1e-3, remat=False),
                   static_argnames=())
    ds = DataState(seed=0, step=0)
    losses = []
    for i in range(8):
        toks, ds = make_batch(ds, 4, 32, cfg.vocab_size)
        params, opt, loss = step(params, opt, toks, None)
        losses.append(float(loss))
    assert losses[-1] < losses[0], losses


def test_train_step_microbatched_equivalence():
    from repro.models import model as M
    from repro.training.optimizer import init_adamw
    from repro.training.train_loop import make_train_step

    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    params = M.init_params(cfg, KEY, dtype=jnp.float32, max_seq=64)
    toks = jax.random.randint(KEY, (4, 16), 0, cfg.vocab_size)
    p1, _, l1 = make_train_step(cfg, microbatches=1, remat=False)(
        params, init_adamw(params), toks)
    p2, _, l2 = make_train_step(cfg, microbatches=2, remat=False)(
        params, init_adamw(params), toks)
    assert abs(float(l1) - float(l2)) < 1e-4
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)))
    assert d < 1e-4


def test_remat_matches_no_remat():
    from repro.distributed import ctx
    from repro.training.train_loop import loss_fn
    from repro.models import model as M

    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    params = M.init_params(cfg, KEY, dtype=jnp.float32, max_seq=64)
    toks = jax.random.randint(KEY, (2, 16), 0, cfg.vocab_size)
    g1 = jax.grad(loss_fn)(params, cfg, toks)
    with ctx.lowering_ctx(remat=True):
        g2 = jax.grad(loss_fn)(params, cfg, toks)
    d = max(float(jnp.abs(a - b).max())
            for a, b in zip(jax.tree.leaves(g1), jax.tree.leaves(g2)))
    assert d < 1e-5


# ------------------------------------------------------------ checkpoint
def test_checkpoint_roundtrip(tmp_path):
    from repro.distributed.checkpoint import (latest_step, restore_checkpoint,
                                              save_checkpoint)

    tree = {"a": jnp.arange(6).reshape(2, 3).astype(jnp.bfloat16),
            "b": {"c": jnp.ones((4,), jnp.int8)}}
    save_checkpoint(str(tmp_path), 3, tree, extra={"data_step": 17})
    save_checkpoint(str(tmp_path), 7, tree, extra={"data_step": 42})
    assert latest_step(str(tmp_path)) == 7
    like = jax.tree.map(jnp.zeros_like, tree)
    restored, extra = restore_checkpoint(str(tmp_path), like)
    assert extra["data_step"] == 42
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_checkpoint_keep_last_k(tmp_path):
    from repro.distributed.checkpoint import save_checkpoint, latest_step

    tree = {"a": jnp.zeros((2,))}
    for s in range(6):
        save_checkpoint(str(tmp_path), s, tree, keep=2)
    dirs = sorted(d for d in os.listdir(tmp_path) if d.startswith("step_"))
    assert len(dirs) == 2 and latest_step(str(tmp_path)) == 5


def test_checkpoint_crash_leaves_no_partial(tmp_path):
    """A failed save must not corrupt the latest checkpoint (atomicity)."""
    from repro.distributed.checkpoint import (restore_checkpoint,
                                              save_checkpoint)

    tree = {"a": jnp.ones((2,))}
    save_checkpoint(str(tmp_path), 1, tree)

    class Boom(Exception):
        pass

    bad = {"a": _Exploding()}
    with pytest.raises(Exception):
        save_checkpoint(str(tmp_path), 2, bad)
    restored, _ = restore_checkpoint(str(tmp_path), jax.tree.map(
        jnp.zeros_like, tree))
    np.testing.assert_array_equal(np.asarray(restored["a"]),
                                  np.ones((2,), np.float32))
    leftovers = [d for d in os.listdir(tmp_path) if d.startswith(".tmp_")]
    assert not leftovers


class _Exploding:
    shape = (2,)
    dtype = "float32"

    def __array__(self, *a, **k):
        raise RuntimeError("disk died mid-save")


def test_checkpoint_shape_mismatch_detected(tmp_path):
    from repro.distributed.checkpoint import restore_checkpoint, save_checkpoint

    save_checkpoint(str(tmp_path), 1, {"a": jnp.ones((2,))})
    with pytest.raises(ValueError):
        restore_checkpoint(str(tmp_path), {"a": jnp.ones((3,))})


def test_checkpoint_bf16_restore_bit_identical(tmp_path):
    """Saved-then-restored bf16 payloads (incl. strided views, the shape
    fleet SlotSnapshot page payloads arrive in) are bit-identical — the
    uint16 round-trip must not touch a single bit pattern."""
    import ml_dtypes

    from repro.distributed.checkpoint import restore_checkpoint, save_checkpoint

    rng = np.random.default_rng(0)
    base = rng.standard_normal((4, 8)).astype(ml_dtypes.bfloat16)
    tree = {"page_k": base, "page_v": base[:, ::-1],      # strided view
            "blob": rng.integers(0, 256, 64).astype(np.uint8),
            "special": np.array([np.inf, -np.inf, np.nan, -0.0, 1e-38],
                                dtype=ml_dtypes.bfloat16)}
    save_checkpoint(str(tmp_path), 1, tree)
    like = jax.tree.map(np.zeros_like, tree)
    restored, _ = restore_checkpoint(str(tmp_path), like)
    for a, b in zip(jax.tree.leaves(tree), jax.tree.leaves(restored)):
        assert np.asarray(a).dtype == np.asarray(b).dtype
        # bitwise, not value-wise: NaN payloads and -0.0 must survive too
        np.testing.assert_array_equal(
            np.ascontiguousarray(a).view(np.uint8),
            np.ascontiguousarray(b).view(np.uint8))


def test_checkpoint_dtype_and_treedef_guards(tmp_path):
    """Restore refuses silent reinterpretation: a like_tree whose dtype
    or structure disagrees with the manifest raises instead of viewing
    the stored bytes into the wrong meaning."""
    import ml_dtypes

    from repro.distributed.checkpoint import restore_checkpoint, save_checkpoint

    tree = {"a": np.ones((2, 2), ml_dtypes.bfloat16),
            "b": np.zeros(3, np.int32)}
    save_checkpoint(str(tmp_path), 1, tree)
    with pytest.raises(ValueError, match="dtype"):
        restore_checkpoint(str(tmp_path),
                           {"a": np.ones((2, 2), np.uint16),   # same bytes!
                            "b": np.zeros(3, np.int32)})
    with pytest.raises(ValueError, match="treedef"):
        restore_checkpoint(str(tmp_path),
                           {"x": np.ones((2, 2), ml_dtypes.bfloat16),
                            "b": np.zeros(3, np.int32)})


def test_plan_remesh_shapes():
    from repro.distributed.elastic import plan_remesh

    # small survivor counts: 2-axis mesh, model axis = gcd with prefer
    assert plan_remesh(8, prefer_model=4) == ((2, 4), ("data", "model"))
    assert plan_remesh(6, prefer_model=4) == ((3, 2), ("data", "model"))
    # pod-scale with an even data axis splits out a pod axis of 2
    shape, names = plan_remesh(1024, prefer_model=16)
    assert names == ("pod", "data", "model")
    assert shape == (2, 32, 16)
    assert shape[0] * shape[1] * shape[2] == 1024
    # odd data axis at pod scale stays 2-axis
    shape, names = plan_remesh(528, prefer_model=16)
    assert names == ("data", "model") and shape == (33, 16)


def test_data_pipeline_resumable():
    from repro.training.data import DataState, make_batch

    s = DataState(seed=5, step=2)
    b1, s1 = make_batch(s, 2, 8, 100)
    b2, _ = make_batch(DataState(seed=5, step=2), 2, 8, 100)
    np.testing.assert_array_equal(np.asarray(b1), np.asarray(b2))
    assert s1.step == 3


# --------------------------------------------------------------- serving
def test_serving_engine_end_to_end():
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine

    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    params = M.init_params(cfg, KEY, max_seq=64)
    eng = ServingEngine(cfg, params, max_batch=2, max_seq=48, eos_id=-1)
    reqs = [Request(rid=i, prompt=[3, 5, 7][: i + 1], max_new_tokens=5)
            for i in range(3)]
    for r in reqs:
        eng.submit(r)
    stats = eng.run()
    assert all(r.done for r in reqs)
    assert all(len(r.out_tokens) == 5 for r in reqs)
    assert stats.tokens_out >= 3 * 4


def test_serving_straggler_redispatch():
    from repro.models import model as M
    from repro.serving.engine import Request, ServingEngine

    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    params = M.init_params(cfg, KEY, max_seq=64)
    fired = []

    def watchdog(step, dt):
        if step == 1 and not fired:
            fired.append(step)
            return True
        return False

    eng = ServingEngine(cfg, params, max_batch=1, max_seq=48, eos_id=-1,
                        watchdog=watchdog)
    r = Request(rid=0, prompt=[1, 2, 3], max_new_tokens=4)
    eng.submit(r)
    stats = eng.run()
    assert r.done and stats.straggler_events == 1


# -------------------------------------------------------- grad compression
def test_grad_compress_error_feedback_unbiased():
    from repro.distributed.grad_compress import make_error_feedback_transform

    init_state, transform = make_error_feedback_transform()
    params = {"w": jnp.zeros((64,))}
    g_true = {"w": jax.random.normal(KEY, (64,)) * 0.1}
    err = init_state(params)
    acc = jnp.zeros((64,))
    for i in range(50):
        g_c, err = transform(g_true, err)
        acc = acc + g_c["w"]
    # error feedback: accumulated compressed grads converge to the truth
    rel = float(jnp.linalg.norm(acc / 50 - g_true["w"])
                / jnp.linalg.norm(g_true["w"]))
    assert rel < 0.02


# ----------------------------------------------------------- partition plan
def test_tpu_alpha_plan_regimes():
    from repro.core.partition_plan import alpha_tpu

    # decode (tokens=1): ship-activations strictly wins
    p = alpha_tpu(4096, 4096, tokens=1, n_shards=16)
    assert p.schedule == "ship_activations"
    # huge-batch training: gathering weights beats shipping activations
    p2 = alpha_tpu(4096, 4096, tokens=1_000_000, n_shards=16)
    assert p2.t_ship_weights < p2.t_ship_act
    assert p2.alpha <= 0.5


def test_planner_streams_match_matrices():
    """decode_execution_stream totals == model_matrices active params."""
    from repro.core import planner

    for name in ("llama2-70b", "deepseek-v2-lite-16b", "zamba2-7b",
                 "whisper-small", "qwen2-moe-a2.7b", "mamba2-130m"):
        cfg = ARCHS[name]
        stream_params = sum(h * w for kind, *dims in
                            planner.decode_execution_stream(cfg)
                            if kind == "gemv" for h, w in [dims])
        mat_params = sum(m.active_params for m in
                         planner.model_matrices(cfg))
        assert stream_params == mat_params, name
