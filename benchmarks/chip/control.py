#!/usr/bin/env python3
"""The readings a cell's correctness limit is set from, in one process.

    python3 benchmarks/chip/control.py --workload smollm-360m.chat \\
        --seeds 1,2,3,4,5,6,7,8,9,10,11,12 --seconds 20

For each seed: the weights and a client as a run makes them, the cell's
traffic at its own load for ``--seconds``, then no new requests until every
admitted one has finished, so the mix's longest requests are among those
compared.  On the same sample of finished greedy requests it reads the
program's widest logit gap (the lower reading) and the control's: the
reference computed one precision lower (int8 weights as int4, bf16 as
int8), ranking its own first token at each position (the upper reading).
The benchmark's runs do not run the control.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args()

    import check
    import loadgen
    from repro.launch import serve

    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = R.load_json(HERE, "configs", f"{cell['config']}.json")
    mix = R.load_json(HERE, "traffic", f"{cell['traffic']}.json")
    R.check_device(int(cell["chips"]), False)
    R.use_compile_cache()
    engine = cfg["engine"]
    model = importlib.import_module(f"models.{cfg['model_type']}")
    warmed = False
    for seed in [int(s) for s in args.seeds.split(",")]:
        t0 = time.monotonic()
        pcfg, params = R.make_params(cfg, model, seed, engine["quant"])
        client = serve.build_client(R.serve_args(engine), pcfg, params)
        del params
        traffic = loadgen.Traffic(mix, seed, args.seconds, cfg["vocab_size"])
        drv = R.Driver(client, traffic)
        if not warmed:
            R.warm_up(drv, mix, engine)
            warmed = True
        start = time.monotonic()
        if traffic.closed:
            for c in range(traffic.clients):
                drv.submit(traffic.next_for(c, first=True), start)
        else:
            drv.pending = traffic.open_loop()
        drv.run_until(start + traffic.warmup_s + args.seconds, start, True)
        drv.pending, drv.idle_clients = [], []
        drv.traffic = None
        while client.has_work:
            drv.pump(True, False)
        finished = [(s.spec.prompt, s.tokens) for s in drv.served.values()
                    if s.spec.greedy and s.finish is not None]
        del client, drv
        gc.collect()
        sample = check.pick(finished, seed)
        prog = check.logit_gaps(cfg, model, seed, sample)
        ctl = check.logit_gaps(cfg, model, seed, sample, control=True)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "program": prog["max_logit_gap"],
            "control": ctl["max_logit_gap"],
            "tokens": prog["tokens"], "requests": prog["requests"],
            "program_per_request": prog["per_request"],
            "control_per_request": ctl["per_request"],
            "seconds": time.monotonic() - t0}), flush=True)


if __name__ == "__main__":
    main()
