"""One benchmark per paper table/figure.  Each returns a list of CSV rows
``(name, us_per_call, derived)`` where ``derived`` carries the figure's
headline quantity (tok/s, speedup, utilization, ratio...).
"""

from __future__ import annotations

import dataclasses
import time

from repro.configs.registry import ARCHS
from repro.core import tiling
from repro.core.hw import CAMBRICON_LLM_L, CAMBRICON_LLM_S, FLASH_CONFIGS
from repro.core.schedule import Policy, channel_workload
from repro.sim import baselines, energy
from repro.sim.engine import simulate_channel
from repro.sim.llm_perf import decode_token_time, flash_only_token_time


def _timed(fn):
    t0 = time.perf_counter()
    out = fn()
    return out, (time.perf_counter() - t0) * 1e6


def fig6_slice_trace():
    """Slice-control channel schedules: completion time per policy."""
    rows = []
    plan = tiling.plan_matrix(4096, 4096, CAMBRICON_LLM_S)
    w = channel_workload(plan, CAMBRICON_LLM_S)
    for pol in Policy:
        res, us = _timed(lambda p=pol: simulate_channel(w, p, keep_trace=True))
        rows.append((f"fig6/{pol.value}", f"{us:.1f}",
                     f"time_us={res.time*1e6:.1f};util={res.util:.3f};"
                     f"segments={len(res.segments)}"))
    return rows


def fig9_end2end():
    """Decode speed vs Flexgen/MLC-LLM for OPT + Llama2 families."""
    rows = []
    for model in ("opt-6.7b", "opt-13b", "opt-30b", "opt-66b",
                  "llama2-7b", "llama2-13b", "llama2-70b"):
        cfg = ARCHS[model]
        for fname in ("S", "M", "L"):
            tt, us = _timed(lambda c=cfg, f=fname: decode_token_time(
                c, FLASH_CONFIGS[f], seq_len=1000))
            rows.append((f"fig9/{model}/{fname}", f"{us:.0f}",
                         f"tok_s={tt.tokens_per_s:.2f};util={tt.channel_util:.2f}"))
        fg = baselines.flexgen_ssd_tokens_per_s(cfg)
        fd = baselines.flexgen_dram_tokens_per_s(cfg)
        ours = decode_token_time(cfg, CAMBRICON_LLM_L, seq_len=1000).tokens_per_s
        rows.append((f"fig9/{model}/speedup_vs_flexgen_ssd", "0",
                     f"x{ours/fg:.1f}"))
        rows.append((f"fig9/{model}/speedup_vs_flexgen_dram", "0",
                     f"x{ours/fd:.1f}"))
    mlc = baselines.mlc_llm_tokens_per_s(ARCHS["llama2-7b"])
    rows.append(("fig9/mlc-llm/llama2-7b", "0", f"tok_s={mlc:.2f}"))
    return rows


def fig10_ecc_accuracy():
    """Model-quality retention under BER, with and without on-die ECC.

    Proxy metric (no eval harness offline): top-1 logit agreement of a
    reduced OPT-6.7B-family model vs its clean self under injected flash
    errors on the quantized weights."""
    import jax
    import jax.numpy as jnp

    from repro.core.hw import CAMBRICON_LLM_S
    from repro.core.hybrid_gemv import (corrupt_flash_region, hybrid_gemv,
                                        plan_and_quantize)

    rows = []
    key = jax.random.PRNGKey(0)
    w = jax.random.normal(key, (2048, 2048)) * 0.05
    xs = jax.random.normal(jax.random.fold_in(key, 1), (2048, 16))
    clean = w @ xs
    hw = plan_and_quantize(w, CAMBRICON_LLM_S, with_ecc=True)
    # the Pallas paged kernel compiles for the TPU; on CPU it runs interpreted
    interpret = jax.default_backend() == "cpu"
    for ber in (1e-5, 1e-4, 2e-4, 8e-4):
        k = jax.random.fold_in(key, int(ber * 1e7))
        noisy = corrupt_flash_region(hw, ber, k)

        def cos(y):
            num = jnp.sum(y * clean)
            den = jnp.linalg.norm(y) * jnp.linalg.norm(clean)
            return float(num / den)

        (y_ecc, us) = _timed(lambda: hybrid_gemv(noisy, xs, interpret=interpret))
        y_raw = hybrid_gemv(noisy._replace(ecc=None), xs,
                            interpret=interpret)
        rows.append((f"fig10/ber{ber:.0e}", f"{us:.0f}",
                     f"cos_ecc={cos(y_ecc):.4f};cos_raw={cos(y_raw):.4f}"))
    return rows


def fig11_w4a16():
    rows = []
    for model in ("opt-6.7b", "opt-13b", "opt-30b", "opt-66b"):
        for fname in ("S", "L"):
            cfg = ARCHS[model]
            t8, us = _timed(lambda: decode_token_time(
                cfg, FLASH_CONFIGS[fname], bytes_per_elem=1.0))
            t4 = decode_token_time(cfg, FLASH_CONFIGS[fname],
                                   bytes_per_elem=0.5)
            rows.append((f"fig11/{model}/{fname}", f"{us:.0f}",
                         f"w8a8={t8.tokens_per_s:.2f};w4a16={t4.tokens_per_s:.2f};"
                         f"gain={t8.total/t4.total - 1:.1%}"))
    return rows


def fig12_slicing():
    rows = []
    for model in ("opt-6.7b", "opt-13b", "opt-30b", "llama2-7b"):
        cfg = ARCHS[model]
        ts, us = _timed(lambda: decode_token_time(
            cfg, CAMBRICON_LLM_S, policy=Policy.RC_SLICED))
        tu = decode_token_time(cfg, CAMBRICON_LLM_S, policy=Policy.RC_UNSLICED)
        rows.append((f"fig12/{model}", f"{us:.0f}",
                     f"speedup={tu.total/ts.total:.2f}x;"
                     f"util_sliced={ts.channel_util:.2f};"
                     f"util_unsliced={tu.channel_util:.2f}"))
    return rows


def fig13_tile_sizes():
    rows = []
    cfg = ARCHS["opt-6.7b"]
    for name, tile in [("256x2048_opt", None),
                       ("128x4096", tiling.TileShape(128, 4096)),
                       ("4096x128", tiling.TileShape(4096, 128))]:
        tt, us = _timed(lambda t=tile: decode_token_time(
            cfg, CAMBRICON_LLM_S, tile_override=t))
        rows.append((f"fig13/{name}", f"{us:.0f}",
                     f"tok_s={tt.tokens_per_s:.2f}"))
    return rows


def fig14_tiling():
    rows = []
    for model in ("opt-6.7b", "opt-13b", "llama2-7b"):
        cfg = ARCHS[model]
        th, us = _timed(lambda: decode_token_time(cfg, CAMBRICON_LLM_S))
        tf = flash_only_token_time(cfg, CAMBRICON_LLM_S)
        rows.append((f"fig14/{model}", f"{us:.0f}",
                     f"speedup={tf.total/th.total:.2f}x;"
                     f"util_hybrid={th.channel_util:.2f};"
                     f"util_flashonly={tf.channel_util:.2f}"))
    return rows


def fig15_scalability():
    rows = []
    cfg = ARCHS["opt-6.7b"]
    base = CAMBRICON_LLM_S
    for ch in (1, 2, 4, 8, 16, 32, 64):
        f = dataclasses.replace(base, channels=ch, chips_per_channel=4)
        tt, us = _timed(lambda ff=f: decode_token_time(cfg, ff))
        rows.append((f"fig15/channels{ch}", f"{us:.0f}",
                     f"tok_s={tt.tokens_per_s:.2f};util={tt.channel_util:.2f}"))
    for chips in (1, 2, 4, 8, 16, 32, 64, 128):
        f = dataclasses.replace(base, channels=8, chips_per_channel=chips)
        tt, us = _timed(lambda ff=f: decode_token_time(cfg, ff))
        rows.append((f"fig15/chips{chips}", f"{us:.0f}",
                     f"tok_s={tt.tokens_per_s:.2f};util={tt.channel_util:.2f}"))
    return rows


def fig16_transfer_energy():
    rows = []
    for model in ("opt-6.7b", "opt-13b", "opt-30b", "opt-66b"):
        cfg = ARCHS[model]
        tt, us = _timed(lambda: decode_token_time(cfg, CAMBRICON_LLM_S,
                                                  seq_len=1000))
        from repro.core import planner

        kv = planner.kv_cache_bytes(cfg, 1000, 1, 1)
        ours = energy.cambricon_per_token(cfg, CAMBRICON_LLM_S,
                                          tt.channel_bytes,
                                          tt.flash_array_bytes, kv)
        theirs = energy.flexgen_ssd_per_token(cfg, kv)
        rows.append((f"fig16/{model}", f"{us:.0f}",
                     f"transfer_ratio={theirs.transferred_bytes/ours.transferred_bytes:.1f}x;"
                     f"energy_ratio={ours.energy_j/theirs.energy_j:.2f};"
                     f"ours_mj={ours.energy_mj:.1f}"))
    return rows


ALL_FIGS = [fig6_slice_trace, fig9_end2end, fig10_ecc_accuracy, fig11_w4a16,
            fig12_slicing, fig13_tile_sizes, fig14_tiling, fig15_scalability,
            fig16_transfer_energy]
