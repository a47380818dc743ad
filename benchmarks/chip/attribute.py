#!/usr/bin/env python3
"""One traced window of a cell, read by the program's own span and program
names (``engine_trace.py``) beside the benchmark's outside-in reduction.

    python3 benchmarks/chip/attribute.py --workload smollm-360m.longgen \\
        --seed 7 --seconds 50

The client, weights, warm-up and traffic are made as ``run.py`` makes them
and the window is traced as ``run.py --trace 1`` traces it; the reference
check is left out.  Standard error gets the self-time table of the spans
and the device time per program; the last line of standard output is one
JSON object: the end-to-end metrics of the traced window, the readings of
the benchmark's trace reduction (``trace.py``), the engine's own readings
(device ms per call of the decode, prefill and sampler programs, median
device-idle ms in decode-only steps, the prefill pad share and the median
first-token hold from ``EngineStats``), the alignment share, the share of
the pumps' device-idle time inside a program span, and the longest idle
gaps tagged by pump and innermost span.
"""

from __future__ import annotations

import argparse
import gc
import importlib
import json
import os
import shutil
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)

import run as R  # noqa: E402

TRACE_DIR = os.path.join(R.ROOT, ".bench_trace", "attribute")


def counters(client) -> tuple:
    """(prefill tokens, prefill computed tokens, first-token holds so far),
    over the replicas."""
    st = client.router.stats
    return (sum(s.prefill_tokens for s in st),
            sum(s.prefill_computed_tokens for s in st),
            [list(s.first_token_hold_s) for s in st])


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--gaps", type=int, default=10)
    args = ap.parse_args()

    import jax
    from jax.profiler import ProfileData

    import engine_trace as E
    import loadgen
    import work as work_mod
    from repro.launch import serve

    bench = R.load_json(R.ROOT, "BENCHMARK.json")
    cell = next(w for w in bench["workloads"] if w["name"] == args.workload)
    cfg = R.load_json(HERE, "configs", f"{cell['config']}.json")
    mix = R.load_json(HERE, "traffic", f"{cell['traffic']}.json")
    info, pk = R.check_device(int(cell["chips"]), False)
    R.use_compile_cache()
    engine = cfg["engine"]
    model = importlib.import_module(f"models.{cfg['model_type']}")
    pcfg, params = R.make_params(cfg, model, args.seed, engine["quant"])
    client = serve.build_client(R.serve_args(engine), pcfg, params)
    del params
    traffic = loadgen.Traffic(mix, args.seed, args.seconds,
                              cfg["vocab_size"])
    drv = R.Driver(client, traffic)
    R.warm_up(drv, mix, engine)
    t_zero = time.monotonic()
    if traffic.closed:
        for c in range(traffic.clients):
            drv.submit(traffic.next_for(c, first=True), t_zero)
    else:
        drv.pending = traffic.open_loop()
    drv.run_until(t_zero + traffic.warmup_s, t_zero, False)
    gc.collect()
    gc.freeze()
    tok0, comp0, holds0 = counters(client)
    waits0 = [len(s.admission_wait_s) for s in client.router.stats]
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(TRACE_DIR)
    w0 = time.monotonic()
    with jax.profiler.TraceAnnotation("bench.window"):
        drv.run_until(w0 + args.seconds, t_zero, True, annotate=True)
    w1 = time.monotonic()
    jax.profiler.stop_trace()
    gc.unfreeze()
    tok1, comp1, holds1 = counters(client)
    holds = [h for a, b in zip(holds0, holds1) for h in b[len(a):]]

    kinds = [p.kind for p in drv.pumps if p.in_window]
    trace_mod = R.load_file("trace.py", "bench_trace")
    pd = ProfileData.from_file(trace_mod.find_xplane(TRACE_DIR))
    outside = trace_mod.reduce(pd, kinds)
    inside = E.reduce(pd, kinds)
    del pd
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    print(E.table(inside), file=sys.stderr, flush=True)

    run = R.Run(cell=cell, cfg=cfg, mix=mix,
                work=work_mod.Work(cfg, engine["quant"], engine["kv_dtype"]),
                peaks=pk,
                slots=engine["max_batch"] * engine.get("replicas", 1),
                pool_pages=drv.pool,
                w0=w0, w1=w1, setup_s=0.0, pumps=drv.pumps,
                served=list(drv.served.values()),
                admission_waits=[w for s, n0 in zip(client.router.stats,
                                                    waits0)
                                 for w in s.admission_wait_s[n0:]],
                trace=outside)
    metrics = {m: R.load_metric(m)(run) for m in (
        "ttft_p50_ms", "itl_p99_ms", "output_tok_s", "step.decode_ms",
        "step.prefill_ms", "device.idle_share", "device.idle_in_step_share")}
    metrics.update({
        "step.decode_program_ms": E.program_ms(inside, E.DECODE_PROGRAMS),
        "step.prefill_program_ms": E.program_ms(inside, E.PREFILL_PROGRAMS),
        "sampler.program_ms": E.program_ms(inside, E.SAMPLER_PROGRAMS),
        "engine.host_gap_ms": E.host_gap_ms(inside),
        "sched.prefill_pad_share": E.pad_share(tok1 - tok0, comp1 - comp0),
        "sched.first_token_hold_ms": E.first_token_hold_ms(holds)})
    print(json.dumps({
        "workload": args.workload, "seed": args.seed, "device": info,
        "window_s": inside.window_s, "busy_s": outside.busy_s,
        "metrics": metrics, "aligned": inside.aligned,
        "idle_in_pumps_s": inside.idle_in_pumps_s,
        "idle_in_spans_share": E.span_share(inside),
        "decode_only_steps": sum(1 for _, _, p, d in inside.steps
                                 if d and not p),
        "prefill_tokens": tok1 - tok0,
        "prefill_computed_tokens": comp1 - comp0,
        "first_token_holds": len(holds),
        "gaps": inside.gaps[:args.gaps],
        "programs": inside.programs}), flush=True)


if __name__ == "__main__":
    main()
