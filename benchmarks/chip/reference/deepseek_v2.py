"""Plain float32 reference of a DeepSeek-V2 decoder (MLA + MoE).

Each block: RMSNorm; multi-head latent attention (queries projected
directly, no q low-rank; keys and values from a 512-wide latent after its
own RMSNorm, plus one shared rotary key of 64 per token; scores over
nope and rope parts scaled by (128+64)^-0.5); residual; RMSNorm; then a
SwiGLU MLP in the first ``first_k_dense_replace`` layers and a mixture of
experts after: softmax router over all experts, the top ``k`` by score,
their weights renormalised to sum to one (``norm_topk_prob``), each routed
expert a SwiGLU MLP, plus the shared experts as one wider SwiGLU MLP.
Every expert is computed for every token and weighted by its gate (zero
where not chosen): no sort, no capacity, no drops.  Rotary scaling is
plain RoPE only: a configuration with ``rope_scaling`` (DeepSeek-V2-Lite
as published has YaRN) is refused, not judged against the wrong model.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from reference import plain as P

TOKEN_BLOCK = 256  # rows per block of the all-expert computation


def embed(c, w, tokens):
    return w["model.embed_tokens"][tokens]


def _attention(c, w, x):
    t = x.shape[0]
    h_, r = c["num_attention_heads"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    if c.get("rope_scaling"):
        raise NotImplementedError(f"{c['name']}: the reference has no "
                                  f"{c['rope_scaling'].get('type')} rope "
                                  f"scaling")
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    a = "layers.self_attn."
    pos = jnp.arange(t)
    h = P.rms_norm(x, w["layers.input_layernorm"], eps)
    q = (h @ w[a + "q_proj"]).reshape(t, h_, dn + dr)
    q_nope, q_rope = q[..., :dn], P.rope(q[..., dn:], pos, theta)
    kv = h @ w[a + "kv_a_proj_with_mqa"]
    latent = P.rms_norm(kv[:, :r], w[a + "kv_a_layernorm"], eps)
    k_rope = P.rope(kv[:, None, r:], pos, theta)[:, 0]      # [T, dr]
    kvb = (latent @ w[a + "kv_b_proj"]).reshape(t, h_, dn + dv)
    k_nope, v = kvb[..., :dn], kvb[..., dn:]
    s = (jnp.einsum("thd,shd->hts", q_nope, k_nope)
         + jnp.einsum("thd,sd->hts", q_rope, k_rope)) * (dn + dr) ** -0.5
    o = jnp.einsum("hts,shd->thd", P.causal_softmax(s), v)
    return x + o.reshape(t, h_ * dv) @ w[a + "o_proj"]


def _experts(c, w, h):
    """Routed experts, h [T, D] -> [T, D]."""
    m = "layers.mlp."
    e, k = c["n_routed_experts"], c["num_experts_per_tok"]
    scores = jax.nn.softmax(h @ w[m + "gate"], axis=-1)       # [T, E]
    top, idx = jax.lax.top_k(scores, k)
    if c["norm_topk_prob"]:
        top = top / jnp.sum(top, -1, keepdims=True)
    gates = jnp.zeros_like(scores).at[
        jnp.arange(h.shape[0])[:, None], idx].set(top)
    wg, wu, wd = (w[m + "experts.gate_proj"], w[m + "experts.up_proj"],
                  w[m + "experts.down_proj"])

    def block(args):
        hb, gb = args
        act = (jax.nn.silu(jnp.einsum("bd,edf->bef", hb, wg))
               * jnp.einsum("bd,edf->bef", hb, wu))
        return jnp.einsum("be,bef,efd->bd", gb, act, wd)

    t = h.shape[0]
    nb = -(-t // TOKEN_BLOCK)
    pad = nb * TOKEN_BLOCK - t
    hb = jnp.pad(h, ((0, pad), (0, 0))).reshape(nb, TOKEN_BLOCK, -1)
    gb = jnp.pad(gates, ((0, pad), (0, 0))).reshape(nb, TOKEN_BLOCK, e)
    return jax.lax.map(block, (hb, gb)).reshape(nb * TOKEN_BLOCK, -1)[:t]


def layer(c, w, x):
    """x [T, D] one sequence -> [T, D]."""
    x = _attention(c, w, x)
    h = P.rms_norm(x, w["layers.post_attention_layernorm"],
                   c["rms_norm_eps"])
    m = "layers.mlp."
    if m + "gate_proj" in w:
        return x + P.swiglu(h, w[m + "gate_proj"], w[m + "up_proj"],
                            w[m + "down_proj"])
    s = m + "shared_experts."
    return x + _experts(c, w, h) + P.swiglu(
        h, w[s + "gate_proj"], w[s + "up_proj"], w[s + "down_proj"])


def head(c, w, x):
    """Final norm and output head: x [..., D] -> logits [..., V]."""
    return P.rms_norm(x, w["model.norm"], c["rms_norm_eps"]) @ w["lm_head"]
