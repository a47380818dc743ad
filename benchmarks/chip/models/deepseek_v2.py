"""DeepSeek-V2 decoders (latent attention, shared and routed experts):
checkpoint tensors, and how they map onto the serving program's
configuration and parameter tree.

The mapping is the benchmark's checkpoint loader.  It reads the program's
layout (``repro.models.model.init_params``) only to check that it still
matches, and fails loudly where it does not.
"""

from __future__ import annotations

from weights import Tensor

def check_supported(c: dict) -> None:
    want = {"rope_scaling": None, "hidden_act": "silu", "q_lora_rank": None,
            "scoring_func": "softmax", "topk_method": "greedy",
            "norm_topk_prob": True, "routed_scaling_factor": 1,
            "moe_layer_freq": 1, "n_group": 1, "topk_group": 1,
            "attention_bias": False}
    bad = {k: c.get(k) for k, v in want.items() if c.get(k) != v}
    if bad:
        raise ValueError(f"{c['name']}: the serving program has no path "
                         f"for {bad}")


def tensors(c: dict) -> list[Tensor]:
    d, v = c["hidden_size"], c["vocab_size"]
    h, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    e, fm = c["n_routed_experts"], c["moe_intermediate_size"]
    fs = fm * c["n_shared_experts"]
    n, k0 = c["num_hidden_layers"], c["first_k_dense_replace"]
    every, dense, moe = (tuple(range(n)), tuple(range(k0)),
                         tuple(range(k0, n)))

    def lin(name, d_in, d_out, layers):
        return Tensor(name, (d_in, d_out), d_in ** -0.5, layers,
                      served="int8")

    def experts(name, d_in, d_out):
        return Tensor(name, (e, d_in, d_out), d_in ** -0.5, moe)

    return [
        Tensor("model.embed_tokens", (v, d), 0.02, contract=-1),
        Tensor("model.norm", (d,), None),
        Tensor("lm_head", (d, v), d ** -0.5, served="int8"),
        Tensor("layers.input_layernorm", (d,), None, every),
        lin("layers.self_attn.q_proj", d, h * (dn + dr), every),
        lin("layers.self_attn.kv_a_proj_with_mqa", d, r + dr, every),
        Tensor("layers.self_attn.kv_a_layernorm", (r,), None, every),
        lin("layers.self_attn.kv_b_proj", r, h * (dn + dv), every),
        lin("layers.self_attn.o_proj", h * dv, d, every),
        Tensor("layers.post_attention_layernorm", (d,), None, every),
        lin("layers.mlp.gate_proj", d, c["intermediate_size"], dense),
        lin("layers.mlp.up_proj", d, c["intermediate_size"], dense),
        lin("layers.mlp.down_proj", c["intermediate_size"], d, dense),
        Tensor("layers.mlp.gate", (d, e), d ** -0.5, moe, stored="f32",
               served="f32"),
        experts("layers.mlp.experts.gate_proj", d, fm),
        experts("layers.mlp.experts.up_proj", d, fm),
        experts("layers.mlp.experts.down_proj", fm, d),
        lin("layers.mlp.shared_experts.gate_proj", d, fs, moe),
        lin("layers.mlp.shared_experts.up_proj", d, fs, moe),
        lin("layers.mlp.shared_experts.down_proj", fs, d, moe),
    ]


def program_config(c: dict):
    from repro.configs.base import ModelConfig
    from repro.configs.registry import get_arch

    check_supported(c)
    cfg = ModelConfig(
        name=c["name"], family="mla_moe", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"],
        d_head=c["qk_nope_head_dim"] + c["qk_rope_head_dim"],
        d_ff=c["moe_intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=float(c["rms_norm_eps"]),
        n_experts=c["n_routed_experts"],
        n_shared_experts=c["n_shared_experts"],
        top_k=c["num_experts_per_tok"],
        moe_d_ff=c["moe_intermediate_size"],
        first_k_dense=c["first_k_dense_replace"],
        dense_d_ff=c["intermediate_size"], kv_lora_rank=c["kv_lora_rank"],
        qk_nope_dim=c["qk_nope_head_dim"], qk_rope_dim=c["qk_rope_head_dim"],
        v_head_dim=c["v_head_dim"])
    if c.get("registry") is None:
        return cfg
    reg = get_arch(c["registry"])
    for k in ("d_model", "n_heads", "n_kv_heads", "d_head", "vocab_size",
              "tie_embeddings", "rope_theta", "n_experts",
              "n_shared_experts", "top_k", "moe_d_ff", "first_k_dense",
              "dense_d_ff", "kv_lora_rank", "qk_nope_dim", "qk_rope_dim",
              "v_head_dim"):
        if getattr(cfg, k) != getattr(reg, k):
            raise ValueError(f"{c['name']}: {k}={getattr(cfg, k)} but the "
                             f"program's {reg.name} has {getattr(reg, k)}")
    return cfg


def to_program(c: dict, w: dict) -> dict:
    """Checkpoint tensors (layers stacked) -> the program's float tree."""
    k0 = c["first_k_dense_replace"]

    def layers(sl, dense: bool) -> dict:
        a = "layers.self_attn."
        lp = {"attn_norm": {"g": w["layers.input_layernorm"][sl]},
              "attn": {"q": {"w": w[a + "q_proj"][sl]},
                       "kv_a": {"w": w[a + "kv_a_proj_with_mqa"][sl]},
                       "kv_a_norm": w[a + "kv_a_layernorm"][sl],
                       "kv_b": {"w": w[a + "kv_b_proj"][sl]},
                       "o": {"w": w[a + "o_proj"][sl]}},
              "ffn_norm": {"g": w["layers.post_attention_layernorm"][sl]}}
        m = "layers.mlp."
        if dense:
            lp["ffn"] = {k: {"w": w[f"{m}{k}_proj"]}
                         for k in ("gate", "up", "down")}
        else:
            lp["moe"] = {
                "router": {"w": w[m + "gate"]},
                **{k: w[f"{m}experts.{k}_proj"]
                   for k in ("gate", "up", "down")},
                "shared": {k: {"w": w[f"{m}shared_experts.{k}_proj"]}
                           for k in ("gate", "up", "down")}}
        return lp

    return {"embed": w["model.embed_tokens"],
            "final_norm": {"g": w["model.norm"]},
            "lm_head": {"w": w["lm_head"]},
            "dense_layers": layers(slice(0, k0), True),
            "layers": layers(slice(k0, None), False)}
