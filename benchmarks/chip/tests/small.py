"""Small stand-ins for the benchmark's configurations and mixes, for the
CPU tests: the same keys at toy widths."""

from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "llama": {"hidden_size": 64, "intermediate_size": 128,
              "num_hidden_layers": 2, "num_attention_heads": 4,
              "num_key_value_heads": 2, "head_dim": 16, "vocab_size": 256},
    "deepseek_v2": {"hidden_size": 64, "intermediate_size": 128,
                    "num_hidden_layers": 3, "num_attention_heads": 4,
                    "kv_lora_rank": 32, "qk_nope_head_dim": 16,
                    "qk_rope_head_dim": 8, "v_head_dim": 16,
                    "n_routed_experts": 32, "num_experts_per_tok": 6,
                    "moe_intermediate_size": 32, "n_shared_experts": 1,
                    "vocab_size": 256,
                    # the serving program's only path: plain RoPE and
                    # renormalised top-k gates (the published model has
                    # YaRN and unnormalised gates, which it cannot run)
                    "rope_scaling": None, "norm_topk_prob": True},
}


def config(name: str) -> dict:
    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        c = json.load(f)
    c.update(TINY[c["model_type"]], registry=None)
    c["engine"] = dict(c["engine"], max_batch=4, max_seq=128)
    return c


def mix(loop: str = "open") -> dict:
    m = {"loop": loop, "warmup_s": 0.5,
         "prompt_len": {"dist": "lognormal", "median": 24, "sigma": 0.8,
                        "min": 4, "max": 60},
         "output_len": {"dist": "lognormal", "median": 8, "sigma": 0.8,
                        "min": 2, "max": 24},
         "sampling": {"temperature": 0.7, "top_p": 0.9, "greedy_every": 2}}
    if loop == "open":
        m["rate_per_s"] = 4.0
    else:
        m.update(clients=4, stagger=True, requests_per_client=400,
                 prompt_len={"dist": "uniform", "min": 4, "max": 16},
                 output_len={"dist": "uniform", "min": 8, "max": 32},
                 sampling={"temperature": 0.0})
    return m
