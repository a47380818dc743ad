#!/usr/bin/env python3
"""Knee sweep: the highest arrival rate a configuration sustains under an
open-loop mix, found once when a cell is defined (the cell then offers a
fixed rate below it).

    python3 benchmarks/chip/sweep.py --config smollm-360m --traffic chat \\
        --rates 2,3,4,5,6 --seconds 30

One process builds the client once (as ``run.py`` does) and offers the mix
at each rate in turn, each for ``--seconds`` after the previous rate's
requests have drained.  Per rate it prints the offered and completed
request rates, output tokens per second, the TTFT and inter-token tails,
and the backlog left when the window closed.  A rate is sustained when
that backlog is at most three seconds of arrivals (three requests at
least) and the 90th-percentile TTFT is under ten seconds: a queue that
grows through the window fails both.  The sweep stops at the first rate
not sustained; the knee is the highest rate sustained, printed last.
"""

from __future__ import annotations

import argparse
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
    ap.add_argument("--config", required=True)
    ap.add_argument("--traffic", required=True)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seconds", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    import loadgen
    import numpy as np
    import work as work_mod
    from repro.launch import serve

    cfg = R.load_json(HERE, "configs", f"{args.config}.json")
    mix = R.load_json(HERE, "traffic", f"{args.traffic}.json")
    info, pk = R.check_device(1, False)
    R.use_compile_cache()
    engine = cfg["engine"]
    model = importlib.import_module(f"models.{cfg['model_type']}")
    pcfg, params = R.make_params(cfg, model, args.seed, engine["quant"])
    client = serve.build_client(R.serve_args(engine), pcfg, params)
    del params
    drv = R.Driver(client, None)
    R.warm_up(drv, mix, engine)
    read = {m: R.load_metric(m) for m in ("ttft_p50_ms", "itl_p99_ms",
                                          "output_tok_s")}
    knee = None
    for rate in [float(r) for r in args.rates.split(",")]:
        m = dict(mix, rate_per_s=rate, warmup_s=0.0)
        traffic = loadgen.Traffic(m, args.seed, args.seconds,
                                  cfg["vocab_size"])
        drv.traffic, drv.pending = traffic, traffic.open_loop()
        drv.served.clear()
        drv.pumps.clear()
        w0 = time.monotonic()
        drv.run_until(w0 + args.seconds, w0, True)
        w1 = time.monotonic()
        served = list(drv.served.values())
        backlog = sum(1 for s in served if not s.times)
        run = R.Run(cell={"name": "sweep"}, cfg=cfg, mix=m,
                    work=work_mod.Work(cfg, engine["quant"],
                                       engine["kv_dtype"]),
                    peaks=pk, slots=engine["max_batch"],
                    pool_pages=drv.pool, w0=w0, w1=w1, setup_s=0.0,
                    pumps=drv.pumps, served=served, admission_waits=[])
        due = sum(1 for s in served if w0 <= s.due < w1)
        done = sum(1 for s in served if s.finish is not None)
        row = {"rate": rate, "due_per_s": due / (w1 - w0),
               "finished_per_s": done / (w1 - w0),
               "backlog_at_close": backlog + len(drv.pending),
               **{k: f(run) for k, f in read.items()}}
        ttft = [(s.times[0] if s.times and s.times[0] <= w1 else w1)
                - s.due for s in served if w0 <= s.due < w1]
        row["ttft_p90_ms"] = float(np.percentile(ttft, 90)) * 1e3
        row["sustained"] = (row["backlog_at_close"] <= max(3, 3 * rate)
                            and row["ttft_p90_ms"] < 10_000)
        print(json.dumps(row), flush=True)
        if not row["sustained"]:
            break
        knee = rate
        drv.pending = []
        while client.has_work:
            drv.pump(False, False)
    print(json.dumps({"knee": knee}), flush=True)


if __name__ == "__main__":
    main()
