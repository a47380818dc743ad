"""Quickstart: the paper's technique end to end on one weight matrix.

1. Plan a GeMV with the §V hardware-aware tiling (optimal tile + α split);
2. quantize to INT8 and protect the flash-resident region with the §VI
   outlier ECC;
3. inject NAND-grade bit flips, run the hybrid NPU+flash GeMV (Pallas paged
   kernel for the flash path), and watch ECC keep the result accurate;
4. estimate the end-to-end decode speed of Llama2-70B on Cambricon-LLM-L.

Run:  PYTHONPATH=src python examples/quickstart.py
"""

import jax
import jax.numpy as jnp

from repro.configs.registry import ARCHS
from repro.core import tiling
from repro.core.hw import CAMBRICON_LLM_L, CAMBRICON_LLM_S
from repro.core.hybrid_gemv import (corrupt_flash_region, hybrid_gemv,
                                    plan_and_quantize)
from repro.sim.llm_perf import decode_token_time

key = jax.random.PRNGKey(0)
# the Pallas paged kernel compiles for the TPU; on CPU it runs interpreted
INTERPRET = jax.default_backend() == "cpu"

# -- 1. plan ---------------------------------------------------------------
h, w = 4096, 4096
plan = tiling.plan_matrix(h, w, CAMBRICON_LLM_S)
print(f"matrix {h}x{w} on Cambricon-LLM-S:")
print(f"  optimal tile  : {plan.tile.h} x {plan.tile.w} "
      f"(paper Fig.13 optimum: 256 x 2048)")
print(f"  alpha (flash) : {plan.alpha:.2f} -> {plan.flash_rows} rows in-flash,"
      f" {plan.npu_rows} rows streamed to NPU")

# -- 2/3. quantize + ECC + errors + hybrid execution ------------------------
W = jax.random.normal(key, (h, w)) * 0.05
x = jax.random.normal(jax.random.fold_in(key, 1), (w,))
ref = W @ x
hw = plan_and_quantize(W, CAMBRICON_LLM_S, with_ecc=True)
noisy = corrupt_flash_region(hw, ber=2e-4, key=jax.random.fold_in(key, 2))


def rel(y):
    return float(jnp.linalg.norm(y - ref) / jnp.linalg.norm(ref))


print(f"\nhybrid GeMV rel-error vs float:")
print(f"  clean weights        : {rel(hybrid_gemv(hw, x, interpret=INTERPRET)):.4f} (int8 noise)")
print(f"  BER 2e-4, with ECC   : {rel(hybrid_gemv(noisy, x, interpret=INTERPRET)):.4f}")
print(f"  BER 2e-4, without ECC: "
      f"{rel(hybrid_gemv(noisy._replace(ecc=None), x, interpret=INTERPRET)):.4f}")

# -- 4. end-to-end estimate --------------------------------------------------
tt = decode_token_time(ARCHS["llama2-70b"], CAMBRICON_LLM_L, seq_len=1000)
print(f"\nLlama2-70B INT8 on Cambricon-LLM-L: {tt.tokens_per_s:.2f} tok/s "
      f"(paper: 3.44), channel util {tt.channel_util:.0%}")
