"""Operations and fewest bytes of the served model, from the
configuration's published sizes and the traffic's lengths (never from the
program's parameter tree, so a change to the program cannot move them).

Counts are model operations: every matmul of a token's forward pass once
(2 operations per multiply-add), attention over the keys the token sees,
nothing for padding or recomputation.  Each matmul is placed at the peak of
the dtype its weight is served in: int8 for w8a8 linears, bf16 for
embeddings, the tied or untied head where not int8, routed experts and the
attention scores; the f32 router is counted at the bf16 peak.
"""

from __future__ import annotations

import dataclasses

BYTES = {"int8": 1, "bf16": 2, "f32": 4}


@dataclasses.dataclass
class Ops:
    int8: float = 0.0
    bf16: float = 0.0

    def __add__(self, o: "Ops") -> "Ops":
        return Ops(self.int8 + o.int8, self.bf16 + o.bf16)

    def __mul__(self, k: float) -> "Ops":
        return Ops(self.int8 * k, self.bf16 * k)

    def seconds(self, peaks: dict) -> float:
        """Least time at the chip's peaks."""
        return self.int8 / peaks["int8_ops"] + self.bf16 / peaks["bf16_flops"]


class Work:
    def __init__(self, c: dict, quant: str, kv_dtype: str):
        self.c = c
        self.lin = "int8" if quant == "w8a8" else "bf16"
        self.kv = "int8" if kv_dtype == "int8" else "bf16"
        if c["model_type"] == "llama":
            self._llama()
        elif c["model_type"] == "deepseek_v2":
            self._deepseek()
        else:
            raise ValueError(f"no operation count for {c['model_type']!r}")

    # -- per-model sizes ------------------------------------------------
    def _llama(self):
        c = self.c
        d, f, v = c["hidden_size"], c["intermediate_size"], c["vocab_size"]
        h, kv, dh = (c["num_attention_heads"], c["num_key_value_heads"],
                     c["head_dim"])
        n = c["num_hidden_layers"]
        self.layers = n
        # weights every token multiplies by, per dtype
        self.lin_params = n * (d * h * dh + 2 * d * kv * dh + h * dh * d
                               + 3 * d * f)
        self.head_params = d * v
        self.head_dtype = "bf16" if c["tie_word_embeddings"] else self.lin
        self.expert_params_active = 0
        self.expert_params_each = 0
        self.moe_layers = 0
        self.router_params = 0
        # attention score operations per (token, key) pair, all layers
        self.score_ops = n * 2 * (h * dh + h * dh)
        # KV cache elements per token, all layers
        self.kv_elems = n * 2 * kv * dh

    def _deepseek(self):
        c = self.c
        d, v = c["hidden_size"], c["vocab_size"]
        h, r = c["num_attention_heads"], c["kv_lora_rank"]
        dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                      c["v_head_dim"])
        n, k0 = c["num_hidden_layers"], c["first_k_dense_replace"]
        fm, e = c["moe_intermediate_size"], c["n_routed_experts"]
        fs = fm * c["n_shared_experts"]
        attn = (d * h * (dn + dr) + d * (r + dr) + r * h * (dn + dv)
                + h * dv * d)
        self.layers = n
        self.lin_params = (n * attn + k0 * 3 * d * c["intermediate_size"]
                           + (n - k0) * 3 * d * fs)
        self.head_params = d * v
        self.head_dtype = self.lin
        self.moe_layers = n - k0
        self.n_experts, self.top_k = e, c["num_experts_per_tok"]
        self.expert_params_each = 3 * d * fm
        self.expert_params_active = self.top_k * self.expert_params_each
        self.router_params = d * e
        self.score_ops = n * 2 * (h * (dn + dr) + h * dv)
        self.kv_elems = n * (r + dr)

    # -- operations -----------------------------------------------------
    def _at(self, dtype: str, ops: float) -> Ops:
        return Ops(int8=ops) if dtype == "int8" else Ops(bf16=ops)

    def body_ops(self) -> Ops:
        """One token through every layer, attention scores left out."""
        return (self._at(self.lin, 2 * self.lin_params)
                + Ops(bf16=2 * self.moe_layers * (self.expert_params_active
                                                  + self.router_params)))

    def head_ops(self) -> Ops:
        return self._at(self.head_dtype, 2 * self.head_params)

    def token_ops(self, ctx: int) -> Ops:
        """One decoded token, attending ``ctx`` keys (itself included),
        through the head."""
        return self.body_ops() + self.head_ops() + Ops(
            bf16=self.score_ops * ctx)

    def prompt_ops(self, length: int) -> Ops:
        """A prompt of ``length`` tokens, causal (token p sees p keys); the
        head runs for the last token only, as a prefill does."""
        pairs = length * (length + 1) / 2
        return (self.body_ops() * length + self.head_ops()
                + Ops(bf16=self.score_ops * pairs))

    # -- bytes ------------------------------------------------------------
    def experts_hit(self, tokens: int) -> float:
        """Expected distinct experts of one layer that ``tokens`` tokens
        reach under uniform top-k routing: E (1 - (1 - k/E)^tokens)."""
        if not self.moe_layers or tokens <= 0:
            return 0.0
        e, k = self.n_experts, self.top_k
        return e * (1.0 - (1.0 - k / e) ** tokens)

    def weight_bytes(self, tokens: int) -> float:
        """Weights one decode step over ``tokens`` active sequences must
        read: every linear, the head, the routers, and the experts hit."""
        b = self.lin_params * BYTES[self.lin]
        b += self.head_params * BYTES[self.head_dtype]
        b += self.moe_layers * self.router_params * BYTES["f32"]
        b += (self.moe_layers * self.experts_hit(tokens)
              * self.expert_params_each * BYTES["bf16"])
        return b

    def decode_bytes(self, tokens: int, keys: int) -> float:
        """Fewest bytes of one decode step: the weights it needs and the
        live KV of its ``tokens`` sequences, ``keys`` positions in all."""
        return (self.weight_bytes(tokens)
                + keys * self.kv_elems * BYTES[self.kv])
