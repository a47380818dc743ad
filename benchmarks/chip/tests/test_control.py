"""The control comes out not correct: the reference computed one precision
below the configuration's (int8 weights as int4, bf16 as int8), put in the
program's place, reads a widest logit gap well above the program's own.

At toy widths on the CPU, on three seeds: the program serves greedy
requests through the paged engine (w8a8, bf16 KV, as configured), and on
the same prompts and tokens the control's first choice at each position is
read against the reference.  On the chip the same comparison runs at the
cells' own sizes (``control.py``); the limits in the configurations are set
from those readings.
"""

import importlib

import numpy as np
import pytest

import check
import run
import small


def serve_greedy(cfg, model, seed, n=6, max_new=12):
    from repro.launch import serve
    from repro.serving.scheduler import SamplingParams

    pcfg, params = run.make_params(cfg, model, seed, cfg["engine"]["quant"])
    client = serve.build_client(run.serve_args(cfg["engine"]), pcfg, params)
    rng = np.random.default_rng(seed)
    prompts = [rng.integers(0, cfg["vocab_size"], int(k)).tolist()
               for k in rng.integers(8, 48, n)]
    hs = [client.submit(p, max_new_tokens=max_new,
                        sampling=SamplingParams(temperature=0.0))
          for p in prompts]
    client.run()
    return [(p, h.request.out_tokens) for p, h in zip(prompts, hs)]


@pytest.mark.parametrize("name", ["smollm-360m", "deepseek-v2-lite-pp4"])
def test_control_reads_far_above_the_program(name):
    cfg = small.config(name)
    model = importlib.import_module(f"models.{cfg['model_type']}")
    prog, ctl = [], []
    for seed in (5, 2**31 + 9, 77):
        sample = serve_greedy(cfg, model, seed)
        prog.append(check.logit_gaps(cfg, model, seed, sample)
                    ["max_logit_gap"])
        ctl.append(check.logit_gaps(cfg, model, seed, sample, control=True)
                   ["max_logit_gap"])
    print(name, "program", prog, "control", ctl)
    assert min(ctl) > 3 * max(prog), (prog, ctl)
