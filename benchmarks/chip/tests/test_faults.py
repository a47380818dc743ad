"""A run whose timed path is broken comes out not correct.

Each test drives a whole run on the CPU at toy widths (the harness's look
for a chip skipped) with one fault planted in the serving engine under
the client, and reads ``correct`` from the result.  The sound run, with the
same limit, comes out correct.  The faults a one-chip serving cell can
have: a decode step that returns its cache unchanged, half of the batch
left out of the decode step (its rows handed the other half's results), and a token altered where it is sampled.  (No
cell exchanges anything between chips.)
"""

import numpy as np
import pytest

import run
import small

LIMIT = 0.05  # the toy Llama's sound runs read 0.01 on the CPU


@pytest.fixture(autouse=True)
def toy_limit(monkeypatch):
    monkeypatch.setattr(run, "cell_limit", lambda workload: LIMIT)


def result(tamper=None, loop="closed"):
    cfg = small.config("smollm-360m")
    cell = {"name": "toy", "config": "smollm-360m", "traffic": "toy",
            "chips": 1}
    bench = {"end_to_end": [], "per_layer": []}
    return run.run_cell(bench, cell, cfg, small.mix(loop), 2**33 + 17, 3.0,
                        False, allow_cpu=True, tamper=tamper)


def each_core(fn):
    def tamper(client):
        for core in client.router.cores:
            fn(core)
    return tamper


def unchanged_state(core):
    dec = core._decode

    def step(p, tok, cache, active):
        logits, _ = dec(p, tok, cache, active)
        return logits, dict(cache)
    core._decode = step


def half_batch(core):
    """The decode step computes the first half of its rows and hands the
    second half the first half's results."""
    dec = core._decode

    def step(p, tok, cache, active):
        active = np.asarray(active).copy()
        half = len(active) // 2
        active[half:] = False
        logits, out = dec(p, tok, cache, active)
        return logits.at[half:].set(logits[:2 * half - half]), out
    core._decode = step


def altered_token(core):
    sample = core._sample_rows

    def rows(logits, items):
        toks = np.array(sample(logits, items))
        for row, req in items:
            if len(req.out_tokens) == 2:
                toks[row] = (toks[row] + 1) % logits.shape[-1]
        return toks
    core._sample_rows = rows


@pytest.mark.parametrize("loop", ["open", "closed"])
def test_sound_run_is_correct(loop):
    r = result(loop=loop)
    assert r["correct"], r["checks"]
    assert r["checks"]["max_logit_gap"]["value"] < LIMIT


@pytest.mark.parametrize("fault", [unchanged_state, half_batch,
                                   altered_token])
def test_fault_is_not_correct(fault):
    r = result(each_core(fault))   # closed loop: every slot in use
    assert not r["correct"], r["checks"]
