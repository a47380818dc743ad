"""The plain references agree with the served path before they judge it.

At toy widths on the CPU, the serving program is fed the reference's own
weights in float32, unquantized, and serves three greedy requests through
the paged engine: their first-token logits (batched prefill into the paged
cache) and every decode step's logits (decode through the cache) must match
the reference's full forward pass over prompt and served tokens.

Prefill attends the fresh float32 keys and values, so its logits must
agree to float32 rounding (1e-5 of the reference's logit spread).  Decode
reads them back from the program's bf16 pages (2^-9 relative rounding),
which moved decode logits by up to 1.8% of the spread here; its limit is
3%.  In the MoE model that rounding can also flip a near-tied expert
choice, which moves that one step's logits by a fifth of the spread, so
there one decode step per request may exceed the limit.  A wrong rotary
layout, head split or expert routing differs by the whole spread at every
step, the first included.
"""

import importlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import check
import run
import small


def served_logits(cfg, model, seed, prompts, max_new):
    from repro.launch import serve
    from repro.serving.scheduler import SamplingParams

    pcfg, params = run.make_params(cfg, model, seed, "none")
    params = jax.tree.map(lambda a: a.astype(jnp.float32)
                          if jnp.issubdtype(a.dtype, jnp.floating) else a,
                          params)
    engine = dict(cfg["engine"], quant="none")
    client = serve.build_client(run.serve_args(engine), pcfg, params)
    core = client.router.cores[0]
    got = {}   # (slot, step) -> logits
    slot_of = {}
    pre, dec = core._prefill_slots, core._decode

    def prefill(p, toks, lens, cache, slots):
        logits, out = pre(p, toks, lens, cache, slots)
        for row, s in enumerate(np.asarray(slots)):
            got.setdefault((int(s), 0), np.asarray(logits[row]))
        return logits, out

    def decode(p, last, cache, active):
        logits, out = dec(p, last, cache, active)
        for s in np.flatnonzero(np.asarray(active)):
            n = 1 + sum(1 for k in got if k[0] == s)
            got[int(s), n - 1] = np.asarray(logits[s])
        return logits, out

    core._prefill_slots, core._decode = prefill, decode
    handles = [client.submit(p, max_new_tokens=max_new,
                             sampling=SamplingParams(temperature=0.0))
               for p in prompts]
    client.pump()   # all three admitted together: slots 0, 1, 2
    client.run()
    streams = [h.request.out_tokens for h in handles]
    logits = [np.stack([got[i, t] for t in range(max_new)])
              for i in range(len(prompts))]
    return streams, logits


@pytest.mark.parametrize("name", ["smollm-360m", "deepseek-v2-lite-pp4"])
def test_reference_matches_served_path(name):
    cfg = small.config(name)
    model = importlib.import_module(f"models.{cfg['model_type']}")
    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, cfg["vocab_size"], n).tolist()
               for n in (9, 30, 17)]
    seed, max_new = 2**40 + 3, 6
    streams, served = served_logits(cfg, model, seed, prompts, max_new)
    tokens, pos, _, _ = check._inputs(list(zip(prompts, streams)))
    ref = check.Reference(cfg, model, seed)
    with jax.default_matmul_precision("highest"):
        glob, rows = ref.rows(tokens, pos, "stored")
        want = np.asarray(ref.head(glob, rows))
    spread = want.std()
    for i in range(len(prompts)):
        err = np.abs(served[i] - want[i]).max(axis=-1)
        assert err[0] < 1e-5 * spread, (name, i, err, spread)
        flips = int((err[1:] >= 0.03 * spread).sum())
        assert flips <= (1 if "n_routed_experts" in cfg else 0), (
            name, i, err, spread)
