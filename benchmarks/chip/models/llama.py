"""Llama-architecture decoders (SmolLM): checkpoint tensors, and how they
map onto the serving program's configuration and parameter tree.

The mapping is the benchmark's checkpoint loader.  It reads the program's
layout (``repro.models.model.init_params``) only to check that it still
matches, and fails loudly where it does not.
"""

from __future__ import annotations

from weights import Tensor

def check_supported(c: dict) -> None:
    want = {"rope_scaling": None, "hidden_act": "silu",
            "attention_bias": False}
    bad = {k: c.get(k) for k, v in want.items() if c.get(k) != v}
    if bad:
        raise ValueError(f"{c['name']}: the serving program has no path "
                         f"for {bad}")


def tensors(c: dict) -> list[Tensor]:
    d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
    h, kv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                 c["head_dim"])
    every = tuple(range(c["num_hidden_layers"]))

    def lin(name, d_in, d_out):
        return Tensor(name, (d_in, d_out), d_in ** -0.5, every,
                      served="int8")

    out = [Tensor("model.embed_tokens", (v, d), 0.02, contract=-1),
           Tensor("model.norm", (d,), None),
           Tensor("layers.input_layernorm", (d,), None, every),
           lin("layers.self_attn.q_proj", d, h * dh),
           lin("layers.self_attn.k_proj", d, kv * dh),
           lin("layers.self_attn.v_proj", d, kv * dh),
           lin("layers.self_attn.o_proj", h * dh, d),
           Tensor("layers.post_attention_layernorm", (d,), None, every),
           lin("layers.mlp.gate_proj", d, f),
           lin("layers.mlp.up_proj", d, f),
           lin("layers.mlp.down_proj", f, d)]
    if not c["tie_word_embeddings"]:
        out.append(Tensor("lm_head", (d, v), d ** -0.5, served="int8"))
    return out


def program_config(c: dict):
    from repro.configs.base import ModelConfig
    from repro.configs.registry import get_arch

    check_supported(c)
    cfg = ModelConfig(
        name=c["name"], family="dense", n_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], n_heads=c["num_attention_heads"],
        n_kv_heads=c["num_key_value_heads"], d_head=c["head_dim"],
        d_ff=c["intermediate_size"], vocab_size=c["vocab_size"],
        rope_theta=float(c["rope_theta"]),
        tie_embeddings=c["tie_word_embeddings"],
        norm_eps=float(c["rms_norm_eps"]))
    if c.get("registry") is None:
        return cfg
    reg = get_arch(c["registry"])
    for k in ("d_model", "n_heads", "n_kv_heads", "d_head", "d_ff",
              "vocab_size", "tie_embeddings", "rope_theta"):
        if getattr(cfg, k) != getattr(reg, k):
            raise ValueError(f"{c['name']}: {k}={getattr(cfg, k)} but the "
                             f"program's {reg.name} has {getattr(reg, k)}")
    return cfg


def to_program(c: dict, w: dict) -> dict:
    """Checkpoint tensors (layers stacked) -> the program's float tree."""
    p = {"embed": w["model.embed_tokens"],
         "final_norm": {"g": w["model.norm"]},
         "layers": {
             "attn_norm": {"g": w["layers.input_layernorm"]},
             "attn": {k: {"w": w[f"layers.self_attn.{k}_proj"]}
                      for k in ("q", "k", "v", "o")},
             "ffn_norm": {"g": w["layers.post_attention_layernorm"]},
             "ffn": {k: {"w": w[f"layers.mlp.{k}_proj"]}
                     for k in ("gate", "up", "down")}}}
    if not c["tie_word_embeddings"]:
        p["lm_head"] = {"w": w["lm_head"]}
    return p
