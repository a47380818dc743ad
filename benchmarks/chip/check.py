"""Whether what the timed path served is right: the plain reference, run
once the window has closed, over a sample of the finished greedy requests.

The number compared is the widest logit gap: over every compared token,
how far the reference's logit for the token the program served lies below
the reference's best logit at that position.  A served token that is the
reference's argmax has gap 0; one that rounding flipped between two
near-equal logits has a small gap; a wrong computation serves tokens the
reference ranks far down.
"""

from __future__ import annotations

import importlib

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
from reference import plain as P

SAMPLE = 8  # requests compared per run


def pick(finished: list, seed: int, n: int = SAMPLE) -> list:
    """The longest finished request and ``n - 1`` more drawn from the seed.
    Each entry is ``(prompt, served_tokens)``."""
    if not finished:
        return []
    order = sorted(range(len(finished)),
                   key=lambda i: -(len(finished[i][0])
                                   + len(finished[i][1])))
    rest = order[1:]
    rng = np.random.default_rng(seed)
    rest = [rest[i] for i in rng.permutation(len(rest))[:n - 1]]
    return [finished[i] for i in [order[0]] + sorted(rest)]


def _bucket(n: int) -> int:
    b = 128
    while b < n:
        b *= 2
    return b


class Reference:
    """The configuration's plain reference, weights drawn from the seed
    one layer at a time, in the precision ``mode`` names."""

    def __init__(self, cfg: dict, model, seed: int):
        self.c = cfg
        self.ref = importlib.import_module(f"reference.{cfg['model_type']}")
        self.tensors = model.tensors(cfg)
        self.key = W.seed_key(seed)
        self._gen = {}
        c, ref = self.c, self.ref
        self._layer = jax.jit(lambda w, x: jax.lax.map(
            lambda xi: ref.layer(c, w, xi), x))

    def _weights(self, layer, mode: str) -> dict:
        ts = tuple(t for t in self.tensors
                   if (layer in t.layers if layer is not None
                       else not t.layers))
        names = tuple(t.name for t in ts)
        if (names, mode) not in self._gen:
            self._gen[names, mode] = jax.jit(
                lambda key, l: P.layer_weights(ts, key, l, mode))
        return self._gen[names, mode](self.key, 0 if layer is None
                                      else layer)

    def rows(self, tokens: np.ndarray, pos: np.ndarray, mode: str):
        """Final hidden rows [N, S, D] at ``pos`` of ``tokens`` [N, T]."""
        glob = self._weights(None, mode)
        x = jax.jit(lambda w, t: self.ref.embed(self.c, w, t))(glob, tokens)
        for layer in range(self.c["num_hidden_layers"]):
            x = self._layer(self._weights(layer, mode), x)
        return glob, jnp.take_along_axis(x, pos[:, :, None], axis=1)

    def head(self, glob, rows):
        return self.ref.head(self.c, glob, rows)


def _inputs(sample: list):
    seqs = [p + s[:-1] for p, s in sample]
    t = _bucket(max(map(len, seqs)))
    s = max(len(s) for _, s in sample)
    tokens = np.zeros((len(sample), t), np.int32)
    pos = np.zeros((len(sample), s), np.int32)
    served = np.zeros((len(sample), s), np.int32)
    valid = np.zeros((len(sample), s), bool)
    for i, (p, out) in enumerate(sample):
        seq = p + out[:-1]
        tokens[i, :len(seq)] = seq
        pos[i, :len(out)] = np.arange(len(p) - 1, len(p) - 1 + len(out))
        served[i, :len(out)] = out
        valid[i, :len(out)] = True
    return tokens, pos, served, valid


def logit_gaps(cfg: dict, model, seed: int, sample: list,
               control: bool = False) -> dict:
    """Widest gap of the served tokens (``control``: of the tokens the
    one-precision-lower reference ranks first) below the reference's best.
    Returns the reading with how many tokens and requests it covers."""
    tokens, pos, served, valid = _inputs(sample)
    ref = Reference(cfg, model, seed)

    @jax.jit
    def served_gap(logits, tok):
        return (jnp.max(logits, -1)
                - jnp.take_along_axis(logits, tok[..., None], -1)[..., 0])

    with jax.default_matmul_precision("highest"):
        glob, rows = ref.rows(tokens, pos, "served")
        logits = jax.jit(ref.head)(glob, rows)
        if control:
            glob_c, rows_c = ref.rows(tokens, pos, "control")
            tok = jnp.argmax(jax.jit(ref.head)(glob_c, rows_c), -1)
        else:
            tok = jnp.asarray(served)
        gaps = np.asarray(served_gap(logits, tok))
    gaps = np.where(valid, gaps, 0.0)
    return {"max_logit_gap": float(gaps.max()),
            "tokens": int(valid.sum()), "requests": len(sample),
            "per_request": [float(g) for g in gaps.max(axis=1)]}
