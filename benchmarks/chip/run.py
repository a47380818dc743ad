#!/usr/bin/env python3
"""One run of one cell of ``BENCHMARK.json`` on the chip.

    python3 benchmarks/chip/run.py --workload smollm-360m.chat --seed 7 \\
        --seconds 30 --trace 0

Everything a cell is made of is found by name: the configuration in
``configs/<config>.json``, the traffic mix in ``traffic/<traffic>.json``
(read by the one generator in ``loadgen.py``), each metric's reader in
``metrics/<metric>.py`` and the model's plain reference in
``reference/<model_type>.py``.  Adding a configuration, a mix or a metric
is adding files and entries, never editing these.

A run: check the device (a TPU whose ``device_kind`` is in ``peaks.py``,
as many chips as the cell asks for, or exit non-zero before anything is
measured); make the weights on the device from ``--seed`` in one jitted
call; build the client exactly as ``repro.launch.serve`` builds it, with
the configuration's ``engine`` options set and ``serve``'s defaults for
the rest; warm up every prefill bucket the mix's prompt lengths can reach,
decode and each sampler variant; run the mix's warm-up traffic; then
measure for ``--seconds``.
With ``--trace 1`` the window is traced and the per-layer metrics are read
from the trace; with ``--trace 0`` the end-to-end metrics are printed.
After the window the program's state is freed and the plain reference
checks a sample of the finished greedy requests (``check.py``) against
the cell's limit in ``limits/<workload>.json``.

The last line of standard output is the result, one JSON object.  The
numbers compared for ``correct`` are printed last on standard error, and
under ``checks`` at the end of the result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path.insert(0, os.path.join(ROOT, "src"))
sys.path.insert(0, HERE)

CACHE_DIR = os.path.join(ROOT, ".jax_cache")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")


def fail(msg: str) -> None:
    """Exit non-zero without a result line."""
    print(f"benchmark: {msg}", file=sys.stderr, flush=True)
    sys.exit(2)


def load_json(*parts: str) -> dict:
    with open(os.path.join(*parts)) as f:
        return json.load(f)


def load_file(rel: str, name: str):
    """A module of the benchmark by its file (names may hold dots, and
    ``trace`` would otherwise be the standard library's)."""
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(HERE, rel))
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod
    spec.loader.exec_module(mod)
    return mod


def load_metric(name: str):
    return load_file(os.path.join("metrics", f"{name}.py"),
                     f"metric_{name}").read


class CompileMeter:
    """Backend compiles and their seconds, programs loaded from the
    persistent cache, and traces, from JAX's monitoring events."""

    EVENT = "/jax/core/compile/backend_compile_duration"
    TRACE = "/jax/core/compile/jaxpr_trace_duration"
    CACHE_HIT = "/jax/compilation_cache/cache_hits"

    def __init__(self):
        import jax
        self.count, self.secs, self.loads, self.traces = 0, 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on(self, event, duration, **_):
        if event == self.EVENT:
            self.count += 1
            self.secs += duration
        elif event == self.TRACE:
            self.traces += 1

    def _on_event(self, event, **_):
        if event == self.CACHE_HIT:
            self.loads += 1


@dataclasses.dataclass
class Pump:
    """One ``client.pump()``: host times, kind, and what it delivered."""

    t0: float
    t1: float
    kind: str               # prefill | decode | idle
    decode_steps: int
    decode_tokens: int      # token events after a request's first
    keys: int               # positions those decode tokens attended
    prompt_tokens: list     # prompt lengths prefilled in this pump
    free_pages: int
    in_window: bool


@dataclasses.dataclass
class Served:
    spec: object
    due: float              # absolute monotonic time it was due
    times: list = dataclasses.field(default_factory=list)
    tokens: list = dataclasses.field(default_factory=list)
    finish: str | None = None
    t_finish: float = 0.0


@dataclasses.dataclass
class Run:
    """What a metric reader sees."""

    cell: dict
    cfg: dict
    mix: dict
    work: object
    peaks: dict | None
    slots: int
    pool_pages: int
    w0: float
    w1: float
    setup_s: float
    pumps: list
    served: list
    admission_waits: list
    trace: object = None

    @property
    def window_s(self) -> float:
        return self.w1 - self.w0

    @property
    def open_loop(self) -> bool:
        return self.mix["loop"] == "open"


def cell_limit(workload: str):
    """The cell's limit on the widest logit gap (``limits/<workload>.json``,
    set from the readings it records), or None where none is set yet."""
    path = os.path.join(HERE, "limits", f"{workload}.json")
    if not os.path.exists(path):
        return None
    return load_json(path)["max_logit_gap"]


def use_compile_cache() -> None:
    """JAX's persistent compilation cache at a fixed path in the checkout
    (the path is part of the cache key), whatever the environment says."""
    import jax
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CACHE_DIR)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)


def check_device(chips: int, allow_cpu: bool):
    import jax
    import peaks as peaks_mod
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", file=sys.stderr, flush=True)
    try:
        pk = peaks_mod.lookup(info["platform"], info["kind"], allow_cpu)
    except peaks_mod.UnknownDevice as e:
        fail(str(e))
    if info["count"] < chips:
        fail(f"the cell asks for {chips} chips, JAX sees {info['count']}")
    return info, pk


def serve_args(engine: dict):
    """``repro.launch.serve``'s arguments with the configuration's engine
    options set (``max_batch`` -> ``--max-batch``; a true boolean is a bare
    flag) and its defaults for everything else."""
    from repro.launch import serve
    argv = ["--reduced", "0"]
    for k, v in engine.items():
        flag = "--" + k.replace("_", "-")
        if v is True:
            argv.append(flag)
        elif v is not False:
            argv += [flag, str(v)]
    return serve.parse_args(argv)


def make_params(cfg: dict, model, seed: int, quant: str):
    """The program's weights, on the device, in one jitted call."""
    import jax
    import weights as W
    from repro.models import model as model_lib
    from repro.quant.convert import quantize_params

    tensors = model.tensors(cfg)
    pcfg = model.program_config(cfg)

    def build(key):
        p = model.to_program(cfg, W.make_all(tensors, key))
        return quantize_params(p, mode=quant) if quant != "none" else p

    key = W.seed_key(seed)
    want = jax.eval_shape(lambda k: model_lib.init_params(pcfg, k), key)
    got = jax.eval_shape(lambda k: model.to_program(
        cfg, W.make_all(tensors, k)), key)
    if (jax.tree.structure(want) != jax.tree.structure(got)
            or jax.tree.leaves(want) != jax.tree.leaves(got)):
        raise ValueError(f"{cfg['name']}: checkpoint loader and the "
                         f"program's parameter layout disagree")
    params = jax.jit(build)(key)
    jax.block_until_ready(params)
    return pcfg, params


class Driver:
    """Feeds the client from the traffic and keeps what it delivered."""

    def __init__(self, client, traffic):
        self.client = client
        self.traffic = traffic
        self.cores = client.router.cores
        self.pool = sum(c.num_pages - 1 for c in self.cores)
        self.served: dict[int, Served] = {}
        self.pumps: list[Pump] = []
        self.pending = []        # open loop: specs not yet submitted
        self.idle_clients = []   # closed loop: clients ready to send

    def submit(self, spec, due: float) -> None:
        from repro.serving.scheduler import SamplingParams
        h = self.client.submit(
            spec.prompt, max_new_tokens=spec.max_new,
            sampling=SamplingParams(temperature=spec.temperature,
                                    top_k=spec.top_k, top_p=spec.top_p))
        self.served[h.rid] = Served(spec=spec, due=due)

    def _stats(self):
        st = self.client.router.stats
        return (sum(s.prefills for s in st), sum(s.decode_steps for s in st))

    def pump(self, in_window: bool, annotate: bool) -> None:
        import jax
        p0, d0 = self._stats()
        t0 = time.monotonic()
        if annotate:
            with jax.profiler.TraceAnnotation("bench.pump"):
                outs = self.client.pump()
        else:
            outs = self.client.pump()
        t1 = time.monotonic()
        p1, d1 = self._stats()
        decode_tokens = keys = 0
        prompts = []
        for ev in outs:
            s = self.served.get(ev.rid)
            if s is None:
                continue
            if ev.token is not None:
                s.times.append(t1)
                s.tokens.append(ev.token)
                if ev.n_out == 1:
                    prompts.append(len(s.spec.prompt))
                else:
                    decode_tokens += 1
                    keys += len(s.spec.prompt) + ev.n_out - 1
            if ev.finished:
                s.finish = ev.finish_reason
                s.t_finish = t1
                if s.spec.client >= 0:
                    self.idle_clients.append(s.spec.client)
        kind = ("prefill" if p1 > p0 else "decode" if d1 > d0 else "idle")
        self.pumps.append(Pump(
            t0=t0, t1=t1, kind=kind, decode_steps=d1 - d0,
            decode_tokens=decode_tokens, keys=keys,
            prompt_tokens=prompts,
            free_pages=sum(c.free_pages for c in self.cores),
            in_window=in_window))

    def run_until(self, t_end: float, t_zero: float, in_window: bool,
                  annotate: bool = False) -> None:
        """Offer the traffic and pump until ``t_end``."""
        while True:
            now = time.monotonic()
            if now >= t_end:
                return
            while self.pending and t_zero + self.pending[0].due <= now:
                sp = self.pending.pop(0)
                self.submit(sp, t_zero + sp.due)
            while self.idle_clients and self.traffic is not None:
                c = self.idle_clients.pop(0)
                self.submit(self.traffic.next_for(c, first=False), now)
            if self.client.has_work:
                self.pump(in_window, annotate)
            else:
                nxt = (t_zero + self.pending[0].due if self.pending
                       else t_end)
                time.sleep(max(0.0, min(nxt, t_end) - now))


def prefill_buckets(lo: int, hi: int, engine: dict) -> list[int]:
    from repro.serving.kv_cache import prefill_bucket
    cap = engine["max_seq"]
    return sorted({min(prefill_bucket(n), cap) for n in range(lo, hi + 1)})


def warm_up(drv: Driver, mix: dict, engine: dict) -> None:
    """Compile every shape the window can use: one prefill per bucket the
    mix's prompt lengths reach, greedy and (where the mix samples) sampled,
    each followed by a decode step."""
    import loadgen
    lo, hi = loadgen.prompt_range(mix)
    s = mix.get("sampling", {})
    variants = [0.0]
    if float(s.get("temperature", 0.0)) > 0 and int(
            s.get("greedy_every", 1)) != 1:
        variants.append(float(s["temperature"]))
    from repro.serving.kv_cache import prefill_bucket
    for b in prefill_buckets(lo, hi, engine):
        length = max(lo, min(b, hi))
        while min(prefill_bucket(length), engine["max_seq"]) != b:
            length -= 1
        for temp in variants:
            spec = loadgen.Spec(prompt=[1] * length, max_new=2,
                                temperature=temp,
                                top_p=float(s.get("top_p", 1.0)),
                                top_k=int(s.get("top_k", 0)))
            drv.submit(spec, time.monotonic())
            while drv.client.has_work:
                drv.pump(False, False)
    drv.served.clear()
    drv.pumps.clear()
    drv.idle_clients.clear()


def gap_tail(pumps, served, w0: float, w1: float) -> str:
    """The top 2% of the window's token gaps, grouped by the pump that
    ended them (``decode``, or ``prefill@<bucket>`` of its longest prompt),
    with the rank of the 99th percentile: where that rank falls between
    two groups, the percentile flips with a few gaps more or less."""
    from repro.serving.kv_cache import prefill_bucket
    label = {}
    for p in pumps:
        label[p.t1] = (f"prefill@{prefill_bucket(max(p.prompt_tokens))}"
                       if p.prompt_tokens else p.kind)
    gaps = sorted(((b - a, label.get(b, "?")) for s in served
                   for a, b in zip(s.times, s.times[1:]) if w0 <= b <= w1),
                  reverse=True)
    if not gaps:
        return "itl tail: no gaps"
    groups = []
    for g, lab in gaps[:max(1, len(gaps) // 50)]:
        if groups and groups[-1][0] == lab:
            groups[-1][1].append(g)
        else:
            groups.append((lab, [g]))
    return (f"itl tail: {len(gaps)} gaps, p99 at rank "
            f"{0.01 * (len(gaps) - 1):.1f} from the top; top 2% by pump: "
            + ", ".join(f"{lab} x{len(v)} {max(v) * 1e3:.1f}-"
                        f"{min(v) * 1e3:.1f} ms" for lab, v in groups[:24]))


def memory_peak(devices) -> int:
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def read_metrics(bench: dict, run: Run, trace: bool) -> dict:
    name = run.cell["name"]
    out = {}
    for m in bench["per_layer" if trace else "end_to_end"]:
        if "workloads" in m and name not in m["workloads"]:
            continue
        if m["name"] == "setup_s":
            value = run.setup_s
        else:
            value = load_metric(m["name"])(run)
        if value is not None:
            out[m["name"]] = {"value": value, "unit": m["unit"]}
    return out


def breakdown(red) -> dict:
    return {"device_ops": [[n, s] for n, s in red.top_ops],
            "idle_gaps": [[t, s] for t, s in red.idle_gaps]}


def run_cell(bench: dict, cell: dict, cfg: dict, mix: dict, seed: int,
             seconds: float, trace: bool, allow_cpu: bool = False,
             tamper=None) -> dict:
    """One run; returns the result object.  ``allow_cpu`` and ``tamper``
    (a function given the built client, to break the timed path) are for
    the benchmark's own tests only."""
    import jax

    marks = [("imports", time.monotonic())]
    info, pk = check_device(int(cell["chips"]), allow_cpu)
    marks.append(("device", time.monotonic()))
    if info["platform"] == "tpu":
        use_compile_cache()
    meter = CompileMeter()

    import check
    import loadgen
    import work as work_mod
    from repro.launch import serve

    engine = cfg["engine"]
    model = importlib.import_module(f"models.{cfg['model_type']}")
    marks.append(("program_imports", time.monotonic()))
    pcfg, params = make_params(cfg, model, seed, engine["quant"])
    marks.append(("weights", time.monotonic()))
    client = serve.build_client(serve_args(engine), pcfg, params)
    del params
    marks.append(("client", time.monotonic()))
    if tamper is not None:
        tamper(client)
    traffic = loadgen.Traffic(mix, seed, seconds, cfg["vocab_size"])
    drv = Driver(client, traffic)
    warm_up(drv, mix, engine)
    marks.append(("warm_up_shapes", time.monotonic()))

    t_zero = time.monotonic()
    if traffic.closed:
        for c in range(traffic.clients):
            drv.submit(traffic.next_for(c, first=True), t_zero)
    else:
        drv.pending = traffic.open_loop()
    drv.run_until(t_zero + traffic.warmup_s, t_zero, False)
    gc.collect()
    gc.freeze()
    waits0 = [len(s.admission_wait_s) for s in client.router.stats]
    compiles0, loads0, traces0 = meter.count, meter.loads, meter.traces
    if trace:
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        jax.profiler.start_trace(TRACE_DIR)
    w0 = time.monotonic()
    setup_s = w0 - T_START
    marks.append(("warm_up_traffic", w0))
    print("setup: " + ", ".join(
        f"{name} {t - t_prev:.2f} s" for (name, t), t_prev in
        zip(marks, [T_START] + [t for _, t in marks])), file=sys.stderr,
        flush=True)
    if trace:
        with jax.profiler.TraceAnnotation("bench.window"):
            drv.run_until(w0 + seconds, t_zero, True, annotate=True)
    else:
        drv.run_until(w0 + seconds, t_zero, True)
    w1 = time.monotonic()
    if trace:
        jax.profiler.stop_trace()
    compiles = meter.count - compiles0
    longest = max(((p.t1 - p.t0, p.kind) for p in drv.pumps
                   if p.in_window), default=(0.0, "none"))
    print(f"window: {w1 - w0:.3f} s, {len(drv.pumps)} pumps, "
          f"compiles in window {compiles}, cache loads in window "
          f"{meter.loads - loads0}, traces in window "
          f"{meter.traces - traces0}, longest pump {longest[0]:.3f} s "
          f"({longest[1]}), set-up compiles {compiles0} "
          f"({meter.secs:.1f} s)", file=sys.stderr, flush=True)
    gc.unfreeze()
    print(gap_tail(drv.pumps, drv.served.values(), w0, w1), file=sys.stderr,
          flush=True)

    devices = sorted({d for core in drv.cores
                      for leaf in jax.tree.leaves(core.params)
                      for d in leaf.devices()}, key=lambda d: d.id)
    info["memory_peak_bytes"] = memory_peak(devices)
    waits = [w for s, n0 in zip(client.router.stats, waits0)
             for w in s.admission_wait_s[n0:]]
    served = list(drv.served.values())
    run = Run(cell=cell, cfg=cfg, mix=mix,
              work=work_mod.Work(cfg, engine["quant"], engine["kv_dtype"]),
              peaks=pk, slots=engine["max_batch"] * engine.get("replicas", 1),
              pool_pages=drv.pool, w0=w0, w1=w1, setup_s=setup_s,
              pumps=drv.pumps, served=served, admission_waits=waits)
    if trace:
        trace_mod = load_file("trace.py", "bench_trace")
        kinds = [p.kind for p in drv.pumps if p.in_window]
        run.trace = trace_mod.read(TRACE_DIR, kinds)
        shutil.rmtree(TRACE_DIR, ignore_errors=True)

    attempted = [s for s in served if w0 <= s.due < w1]
    failed = [s for s in attempted if s.finish in ("rejected", "aborted")]
    finished = [(s.spec.prompt, s.tokens) for s in served
                if s.spec.greedy and s.finish is not None
                and w0 <= s.t_finish <= w1]
    del client, drv
    gc.collect()

    sample = check.pick(finished, seed)
    limit = cell_limit(cell["name"])
    if sample:
        t0 = time.monotonic()
        got = check.logit_gaps(cfg, model, seed, sample)
        print(f"reference: {got['requests']} requests, {got['tokens']} "
              f"tokens, per request {got['per_request']} "
              f"({time.monotonic() - t0:.1f} s)", file=sys.stderr,
              flush=True)
        gap = got["max_logit_gap"]
    else:
        gap = None
    correct = gap is not None and limit is not None and gap <= limit
    result = {"correct": bool(correct), "attempted": len(attempted),
              "failed": len(failed),
              "metrics": (read_metrics(bench, run, trace)
                          if info["platform"] == "tpu" else {}),
              "device": info}
    if trace:
        info["busy_s"] = run.trace.busy_s
        info["window_s"] = run.trace.window_s
        result["breakdown"] = breakdown(run.trace)
    result["checks"] = {
        "requests_compared": {"value": len(sample), "limit": 1},
        "max_logit_gap": {"value": gap, "limit": limit}}
    for k, v in result["checks"].items():
        print(f"check {k}={v['value']} limit={v['limit']}", file=sys.stderr,
              flush=True)
    return result


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=[0, 1], default=0)
    args = ap.parse_args(argv)
    try:
        bench = load_json(ROOT, "BENCHMARK.json")
        cell = next(w for w in bench["workloads"]
                    if w["name"] == args.workload)
        cfg = load_json(HERE, "configs", f"{cell['config']}.json")
        mix = load_json(HERE, "traffic", f"{cell['traffic']}.json")
    except (OSError, StopIteration, KeyError) as e:
        fail(f"cannot set up {args.workload!r}: {e!r}")
    try:
        import repro  # noqa: F401
    except ImportError as e:
        fail(f"the serving program is not in this checkout: {e}")
    result = run_cell(bench, cell, cfg, mix, args.seed, args.seconds,
                      bool(args.trace))
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
