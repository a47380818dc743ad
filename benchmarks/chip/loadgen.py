"""The one traffic generator: turns a mix's data file and a seed into the
requests of a run.

A mix file (``traffic/<name>.json``) gives:

* ``loop``: ``open`` (requests due on a schedule, whether or not earlier
  ones finished) or ``closed`` (``clients`` callers, each sending its next
  request when the last one completes);
* ``rate_per_s`` (open loop): mean arrivals per second, exponential gaps;
* ``warmup_s``: traffic run before the window opens, so the window starts
  with the batch in its steady state;
* ``prompt_len`` / ``output_len``: ``{"dist": "lognormal", "median",
  "sigma", "min", "max"}`` or ``{"dist": "uniform", "min", "max"}``;
* ``sampling``: ``temperature``, ``top_p``, ``top_k`` and ``greedy_every``
  (every n-th request is greedy, so the reference can check it);
* ``stagger`` (closed loop): client i's first request asks for (i+1)/n of
  its drawn output length, so completions are spread from the start.

Sizes and gaps are a fixed set of quantiles of their distributions, put
in one fixed order (a permutation drawn from the constant ``ORDER_KEY``,
not from the seed): every seed offers the same requests at the same
times, and the seed draws only their token ids.  With a few dozen
requests in a window, the order alone would move the window's tokens
and its TTFT median from seed to seed.  In an open loop the warm-up and
the window each get their own set, spread over exactly their length.
"""

from __future__ import annotations

import dataclasses
import math

import numpy as np
from statistics import NormalDist

ORDER_KEY = 20240923  # the one order of every mix's sizes and gaps


@dataclasses.dataclass
class Spec:
    """One request: its prompt, how many tokens it asks for, its sampling,
    and (open loop) when it is due, in seconds from the start of traffic."""

    prompt: list
    max_new: int
    temperature: float
    top_p: float
    top_k: int
    due: float = 0.0
    client: int = -1

    @property
    def greedy(self) -> bool:
        return self.temperature <= 0.0


def _quantiles(dist: dict, n: int) -> np.ndarray:
    """``n`` sizes at the mid-quantiles (i + 1/2)/n of ``dist``."""
    u = (np.arange(n) + 0.5) / n
    if dist["dist"] == "uniform":
        x = dist["min"] + u * (dist["max"] + 1 - dist["min"])
        x = np.floor(x)
    elif dist["dist"] == "lognormal":
        z = np.asarray([NormalDist().inv_cdf(float(q)) for q in u])
        x = np.round(dist["median"] * np.exp(dist["sigma"] * z))
    else:
        raise ValueError(f"unknown distribution {dist['dist']!r}")
    return np.clip(x, dist["min"], dist["max"]).astype(int)


def prompt_range(mix: dict) -> tuple[int, int]:
    """Shortest and longest prompt the mix can send."""
    d = mix["prompt_len"]
    return int(d["min"]), int(d["max"])


class Traffic:
    def __init__(self, mix: dict, seed: int, seconds: float, vocab: int):
        self.mix = mix
        self.rng = np.random.default_rng(seed)      # token ids only
        order = np.random.default_rng(ORDER_KEY)
        self.vocab = vocab
        self.warmup_s = float(mix.get("warmup_s", 0.0))
        self.closed = mix["loop"] == "closed"
        if self.closed:
            self.clients = int(mix["clients"])
            self.phases = [(0.0, self.clients
                            * int(mix.get("requests_per_client", 64)))]
        else:
            rate = float(mix["rate_per_s"])
            self.phases = [(d, max(1, round(rate * d)))
                           for d in (self.warmup_s, seconds) if d > 0]
        self.n = sum(n for _, n in self.phases)
        self.prompts = np.concatenate([
            order.permutation(_quantiles(mix["prompt_len"], n))
            for _, n in self.phases])
        self.outputs = np.concatenate([
            order.permutation(_quantiles(mix["output_len"], n))
            for _, n in self.phases])
        self.gaps = [order.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
                     for _, n in self.phases]
        self._i = 0

    def _spec(self, i: int) -> Spec:
        s = self.mix.get("sampling", {})
        every = int(s.get("greedy_every", 1))
        temp = float(s.get("temperature", 0.0))
        if every and i % every == 0:
            temp = 0.0
        prompt = self.rng.integers(0, self.vocab, int(self.prompts[i]))
        return Spec(prompt=prompt.tolist(), max_new=int(self.outputs[i]),
                    temperature=temp, top_p=float(s.get("top_p", 1.0)),
                    top_k=int(s.get("top_k", 0)))

    def open_loop(self) -> list[Spec]:
        """Every request of the run with its due time: in each phase,
        exponential gaps at the mid-quantiles in the fixed order, scaled
        to span the phase, the first due at its start."""
        due, start = [], 0.0
        for (length, _), gaps in zip(self.phases, self.gaps):
            at = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
            due.append(start + length * at / gaps.sum())
            start += length
        due = np.concatenate(due)
        specs = []
        for i in range(self.n):
            sp = self._spec(i)
            sp.due = float(due[i])
            specs.append(sp)
        return specs

    def next_for(self, client: int, first: bool) -> Spec:
        """Closed loop: the next request of ``client``."""
        if self._i >= self.n:
            raise RuntimeError("closed-loop traffic ran out of requests; "
                               "raise requests_per_client")
        sp = self._spec(self._i)
        self._i += 1
        sp.client = client
        if first and self.mix.get("stagger"):
            sp.max_new = max(1, math.ceil(sp.max_new * (client + 1)
                                          / self.clients))
        return sp
