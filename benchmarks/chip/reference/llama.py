"""Plain float32 reference of a Llama-architecture decoder (SmolLM).

Pre-norm blocks: RMSNorm, grouped-query attention with rotary embedding
on q and k, residual; RMSNorm, SwiGLU MLP, residual.  Final RMSNorm, then
the output head (the embedding, where tied).  No cache, no batching
tricks: every sequence attends over its whole prefix.
"""

from __future__ import annotations

import jax.numpy as jnp

from reference import plain as P


def embed(c, w, tokens):
    return w["model.embed_tokens"][tokens]


def layer(c, w, x):
    """x [T, D] one sequence -> [T, D]."""
    t = x.shape[0]
    h_, kv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                  c["head_dim"])
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a = "layers.self_attn."
    pos = jnp.arange(t)
    h = P.rms_norm(x, w["layers.input_layernorm"], eps)
    q = P.rope((h @ w[a + "q_proj"]).reshape(t, h_, dh), pos, theta)
    k = P.rope((h @ w[a + "k_proj"]).reshape(t, kv, dh), pos, theta)
    v = (h @ w[a + "v_proj"]).reshape(t, kv, dh)
    k = jnp.repeat(k, h_ // kv, axis=1)
    v = jnp.repeat(v, h_ // kv, axis=1)
    p = P.causal_softmax(jnp.einsum("thd,shd->hts", q, k) * dh ** -0.5)
    o = jnp.einsum("hts,shd->thd", p, v).reshape(t, h_ * dh)
    x = x + o @ w[a + "o_proj"]
    h = P.rms_norm(x, w["layers.post_attention_layernorm"], eps)
    m = "layers.mlp."
    return x + P.swiglu(h, w[m + "gate_proj"], w[m + "up_proj"],
                        w[m + "down_proj"])


def head(c, w, x):
    """Final norm and output head: x [..., D] -> logits [..., V]."""
    h = P.rms_norm(x, w["model.norm"], c["rms_norm_eps"])
    if c["tie_word_embeddings"]:
        return h @ w["model.embed_tokens"].T
    return h @ w["lm_head"]
