"""Serving benchmarks: admission, tiered-KV capacity, and policy traces.

Three traces, all Poisson arrivals:

* ``admission`` — wave vs continuous admission.  Wave (the legacy
  shared-cursor cache) only starts new requests when the whole batch drains;
  continuous (paged per-slot KV cache) refills any freed slot immediately.
  At batch pressure > 1 the paged engine keeps slots busy and should be no
  slower end-to-end while cutting admission latency.
* ``kvtier`` — the KV-capacity-constrained trace: the hot page pool is sized
  BELOW total trace demand (``--pool-pages``), and three engines race it:
  ``reject`` fails requests the pool can't take (the flash-less baseline),
  ``requeue`` restarts starved requests later (graceful but stally), and
  ``tiered`` spills cold pages to the simulated NAND flash tier and
  prefetches them back through the Slice Control bubbles
  (``kv_tier="flash"``).  Tiered must complete 100% of the trace; the report
  prices its spill/prefetch traffic with the channel simulator
  (``sim.llm_perf.kv_swap_overhead_s``) to show the bubble-bandwidth cost of
  every evicted page.
* ``policy`` — the scheduler bake-off: mixed prompt lengths (including long
  prompts that exercise chunked prefill), mixed priorities, and per-request
  SLO deadlines race the capacity-constrained tiered pool under each
  admission policy (fcfs / priority / sjf / drr / edf,
  ``serving.scheduler``).  Every policy must complete 100% of the trace;
  the report compares per-policy TTFT and latency percentiles, the
  deadline-miss rate (the EDF policy's target metric), plus
  per-priority-class TTFT p99 so the priority policy's SLO effect is
  visible.
* ``overlap`` — the overlapped decode loop (``overlap=True``): the fused
  decode+sample dispatch with one-step-delayed host readback vs the
  synchronous two-dispatch loop, same trace.  Both must complete 100% with
  bit-identical outputs; the report pins the tentpole metric — jitted
  dispatches per decode step drop from 2 (decode + sample) to 1 — and
  shows dispatches per decoded token.  ``--overlap`` additionally runs the
  admission trace's continuous engine overlapped.
* ``prefix`` — the prefix-caching trace: multi-turn chat sessions (a shared
  per-session system prompt plus history grown from each run's own outputs,
  with an immediate "regenerate" of every turn) race four engines: ``cold``
  (prefix caching off), ``warm`` (``prefix_cache=True``), ``warm-tiered``
  (a hot pool sized below the working set, so idle shared pages spill to
  flash and prefetch back on the next hit), and ``warm-2rep`` (two replicas
  under ``session_affinity`` routing — the replica whose cache holds the
  session's pages wins).  All variants must complete 100% with outputs
  bit-identical to cold (greedy AND seed-pinned stochastic sessions), and
  hit-turn TTFT p50 must improve >= 2x over the cold run — regenerates are
  exact-prompt resume hits (zero prefill dispatches), follow-up turns are
  partial page hits that only prefill the uncached suffix.
* ``router`` — multi-replica serving through the Router/EngineCore split:
  ``--replicas N`` small replicas under least-loaded routing with
  cross-replica slot migration vs ONE N-wide replica with the same total
  slot and page budget.  Both must complete 100%; the report compares
  wall clock and TTFT p99 and counts slot migrations (each one drains a
  page-starved replica's victim slot into a peer with headroom,
  bit-identical — the N-replica fleet should hold the single-replica
  latency profile despite the partitioned KV pools).

* ``quant`` — the int8-KV trace: bf16 vs ``kv_dtype="int8"`` page pools on
  the capacity-constrained tiered pool (the kvtier workload), plus an
  int8-KV + w8a8-weight engine.  Every variant must complete 100%; the
  int8 tiered outputs must be bit-identical to an int8 all-resident run
  (spill/prefetch relocates quantized pages, it never re-quantizes), and
  the int8 runs must spill >= 1.8x fewer bytes than bf16 (each page moves
  1B/elem + 4B/row scales instead of 2B/elem — 2*Dh/(Dh+4)); the report
  shows the TTFT/throughput deltas and reprices the spill traffic on the
  flash channel model.

* ``fleet`` — the failover trace (serving/fleet/): N workers behind the
  fleet transport (``--transport loopback`` in-process behind the wire
  codec, ``socket`` real subprocesses), one worker killed once ~40% of
  the trace's tokens have been delivered.  The fleet must complete 100%
  of the requests with every stream bit-identical to an undisturbed
  single-engine run (greedy AND seed-pinned stochastic); the report
  prices the failover: recovery latency and tokens replayed.

Run:  PYTHONPATH=src python benchmarks/bench_serving.py \
          --arch smollm-360m --requests 12 --rate 4 --max-batch 4
      PYTHONPATH=src python benchmarks/bench_serving.py --smoke
      PYTHONPATH=src python benchmarks/bench_serving.py --trace policy --smoke
      PYTHONPATH=src python benchmarks/bench_serving.py --trace router \
          --smoke --replicas 2
"""

from __future__ import annotations

import argparse
import os
import time

import numpy as np

import jax

from repro.configs.registry import get_arch
from repro.core.hw import CAMBRICON_LLM_S
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.serving.engine import Request, ServingEngine
from repro.serving.router import Router
from repro.serving.scheduler import POLICIES, SamplingParams, make_scheduler
from repro.sim.llm_perf import kv_swap_overhead_s, prefill_ttft_s

# a small prompt-length menu keeps the per-shape jit retrace count bounded
PROMPT_LENS = (4, 6, 8, 12)


def poisson_arrivals(n: int, rate_rps: float, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate_rps, size=n)
    return np.cumsum(gaps)


def make_requests(n: int, cfg, max_new: int, seed: int) -> list[Request]:
    rng = np.random.RandomState(seed + 1)
    reqs = []
    for rid in range(n):
        plen = int(rng.choice(PROMPT_LENS))
        prompt = rng.randint(0, cfg.vocab_size, size=plen).tolist()
        # ragged decode lengths are what hurt wave admission: the whole
        # batch drains at the pace of its longest request
        n_new = int(rng.randint(max(2, max_new // 4), max_new + 1))
        reqs.append(Request(rid=rid, prompt=prompt, max_new_tokens=n_new))
    return reqs


def drive(eng, reqs: list[Request], arrivals: np.ndarray) -> float:
    """Feed requests at their arrival times; returns wall seconds.

    ``eng`` is anything with the ``submit(req) / step() / has_work``
    surface — a ServingEngine or a multi-replica Router.
    """
    t0 = time.monotonic()
    i = 0
    while True:
        now = time.monotonic() - t0
        while i < len(reqs) and arrivals[i] <= now:
            eng.submit(reqs[i])
            i += 1
        eng.step()
        if not eng.has_work:
            if i >= len(reqs):
                break
            wait = arrivals[i] - (time.monotonic() - t0)
            time.sleep(max(0.0, min(0.001, wait)))
    return time.monotonic() - t0


def _warm(cfg, params, args, **eng_kw):
    # warmup pass populates the shared jit caches (prefill shape buckets,
    # decode step) so the timed pass measures steady-state serving
    warm = ServingEngine(cfg, params, max_batch=args.max_batch,
                         max_seq=args.max_seq, eos_id=-1,
                         page_size=args.page_size, **eng_kw)
    # one warmup request per prompt length, each run to completion, so wave
    # mode compiles every [B, plen] prefill shape the trace can produce
    for i, plen in enumerate(PROMPT_LENS):
        warm.submit(Request(rid=-1 - i, prompt=[1] * plen, max_new_tokens=2))
        warm.run()


def bench_mode(mode: str, cfg, params, args, timed_seed: int) -> dict:
    overlap = bool(getattr(args, "overlap", False)) and mode == "continuous"
    _warm(cfg, params, args, mode=mode, overlap=overlap)
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=args.max_seq, eos_id=-1, mode=mode,
                        page_size=args.page_size, overlap=overlap)
    reqs = make_requests(args.requests, cfg, args.max_new, timed_seed)
    arrivals = poisson_arrivals(args.requests, args.rate, timed_seed)
    wall = drive(eng, reqs, arrivals)
    s = eng.stats
    assert all(r.done for r in reqs)
    return {
        "mode": mode,
        "wall_s": wall,
        "tokens": s.tokens_out,
        "tok_per_s": s.tokens_out / wall,
        "tok_per_step": s.tokens_out / max(s.decode_steps, 1),
        "prefills": s.prefills,
        "admission_p50": s.percentiles("admission_wait_s")["p50"],
        "admission_p99": s.percentiles("admission_wait_s")["p99"],
        "latency_p50": s.percentiles("latency_s")["p50"],
        "latency_p99": s.percentiles("latency_s")["p99"],
    }


def bench_admission(cfg, params, args) -> list[dict]:
    pressure = args.requests / args.max_batch
    print(f"[admission] arch={cfg.name} requests={args.requests} "
          f"rate={args.rate}/s max_batch={args.max_batch} "
          f"batch_pressure={pressure:.1f}")

    rows = [bench_mode(m, cfg, params, args, timed_seed=args.seed)
            for m in ("wave", "continuous")]
    hdr = ("mode", "wall_s", "tok/s", "tok/step", "prefills",
           "adm_p50", "adm_p99", "lat_p50", "lat_p99")
    print(" ".join(f"{h:>10}" for h in hdr))
    for r in rows:
        print(f"{r['mode']:>10} {r['wall_s']:>10.2f} {r['tok_per_s']:>10.1f} "
              f"{r['tok_per_step']:>10.2f} "
              f"{r['prefills']:>10d} "
              f"{r['admission_p50']:>10.3f} {r['admission_p99']:>10.3f} "
              f"{r['latency_p50']:>10.3f} {r['latency_p99']:>10.3f}")
    wave, cont = rows
    speedup = cont["tok_per_s"] / wave["tok_per_s"]
    occup = cont["tok_per_step"] / wave["tok_per_step"]
    print(f"\ncontinuous/wave: throughput x{speedup:.2f}, "
          f"occupancy x{occup:.2f}, admission p99 "
          f"{wave['admission_p99']:.3f}s -> {cont['admission_p99']:.3f}s")
    if pressure > 1 and speedup < 0.95:  # 5% = wall-clock noise floor
        print("WARNING: continuous materially slower than wave "
              "at batch pressure > 1")
    return rows


def bench_overlap_variant(name: str, cfg, params, args, overlap: bool) -> dict:
    _warm(cfg, params, args, mode="continuous", overlap=overlap)
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=args.max_seq, eos_id=-1, mode="continuous",
                        page_size=args.page_size, overlap=overlap)
    reqs = make_requests(args.requests, cfg, args.max_new, args.seed)
    arrivals = poisson_arrivals(args.requests, args.rate, args.seed)
    wall = drive(eng, reqs, arrivals)
    s = eng.stats
    assert all(r.done for r in reqs)
    return {
        "variant": name, "wall_s": wall,
        "completed_pct": 100.0 * sum(1 for r in reqs if not r.rejected)
        / len(reqs),
        "tokens": s.tokens_out, "tok_per_s": s.tokens_out / wall,
        "decode_steps": s.decode_steps, "dispatches": s.decode_dispatches,
        "disp_per_step": s.decode_dispatches / max(s.decode_steps, 1),
        "disp_per_tok": s.decode_dispatches / max(s.tokens_out, 1),
        "latency_p99": s.percentiles("latency_s")["p99"],
        "out_tokens": {r.rid: list(r.out_tokens) for r in reqs
                       if not r.rejected},
    }


def bench_overlap(cfg, params, args) -> list[dict]:
    """Synchronous two-dispatch loop vs the overlapped fused loop."""
    print(f"\n[overlap] arch={cfg.name} requests={args.requests} "
          f"max_batch={args.max_batch}")
    rows = [bench_overlap_variant("sync", cfg, params, args, False),
            bench_overlap_variant("overlap", cfg, params, args, True)]
    hdr = ("variant", "wall_s", "done%", "tokens", "tok/s", "steps",
           "disp", "disp/step", "disp/tok", "lat_p99")
    print(" ".join(f"{h:>9}" for h in hdr))
    for r in rows:
        print(f"{r['variant']:>9} {r['wall_s']:>9.2f} "
              f"{r['completed_pct']:>9.1f} {r['tokens']:>9d} "
              f"{r['tok_per_s']:>9.1f} {r['decode_steps']:>9d} "
              f"{r['dispatches']:>9d} {r['disp_per_step']:>9.2f} "
              f"{r['disp_per_tok']:>9.3f} {r['latency_p99']:>9.3f}")
    sync, olap = rows
    for r in rows:
        assert r["completed_pct"] == 100.0, \
            f"{r['variant']} dropped requests on the overlap trace"
    # the overlapped loop relocates WHEN tokens are read back, never WHAT
    # they are: outputs must match the synchronous loop bit for bit
    assert olap["out_tokens"] == sync["out_tokens"], \
        "overlapped outputs diverge from the synchronous loop"
    assert sync["disp_per_step"] == 2.0  # decode + sample
    assert olap["disp_per_step"] == 1.0  # the fused step: tentpole metric
    print(f"\noverlap: 100% completed, bit-identical; dispatches per decode "
          f"step 2 -> 1 ({sync['disp_per_tok']:.3f} -> "
          f"{olap['disp_per_tok']:.3f} per decoded token)")
    return rows


def make_kv_requests(n: int, cfg, max_new: int, seed: int) -> list[Request]:
    """Uniform worst-case requests: every one carries the full prompt and
    decode budget, so concurrent footprint reliably exceeds the pool."""
    rng = np.random.RandomState(seed + 2)
    plen = max(PROMPT_LENS)
    return [Request(rid=rid,
                    prompt=rng.randint(0, cfg.vocab_size, size=plen).tolist(),
                    max_new_tokens=max_new)
            for rid in range(n)]


def bench_kvtier_variant(name: str, cfg, params, args, pool: int) -> dict:
    kw = {"resident": dict(),  # unconstrained pool: the reference run
          "reject": dict(num_pages=pool + 1, exhaust_policy="reject"),
          "requeue": dict(num_pages=pool + 1, exhaust_policy="requeue"),
          "tiered": dict(num_pages=pool + 1, kv_tier="flash")}[name]
    _warm(cfg, params, args, mode="continuous")
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=args.max_seq, eos_id=-1, mode="continuous",
                        page_size=args.page_size, **kw)
    reqs = make_kv_requests(args.requests, cfg, args.max_new, args.seed)
    arrivals = poisson_arrivals(args.requests, args.rate, args.seed)
    wall = drive(eng, reqs, arrivals)
    s = eng.stats
    assert all(r.done for r in reqs)
    ok = sum(1 for r in reqs if not r.rejected)
    return {
        "variant": name, "wall_s": wall, "eng": eng,
        "completed_pct": 100.0 * ok / len(reqs),
        "tokens": s.tokens_out,
        "pool_exhausted": s.pool_exhausted, "rejected": s.rejected,
        "preemptions": s.preemptions,
        "spill_pages": s.kv_spill_pages, "prefetch_pages": s.kv_prefetch_pages,
        "spill_bytes": s.kv_spill_bytes, "prefetch_bytes": s.kv_prefetch_bytes,
        "out_tokens": {r.rid: list(r.out_tokens) for r in reqs
                       if not r.rejected},
    }


def bench_kvtier(cfg, params, args) -> list[dict]:
    # demand: every request's whole-lifetime page footprint at once
    from repro.serving.kv_cache import kv_page_elems, pages_needed
    per_req = pages_needed(min(args.max_seq, max(PROMPT_LENS) + args.max_new),
                           args.page_size)
    demand = args.requests * per_req
    pool = args.pool_pages
    if pool <= 0:
        # default: one request's lifetime footprint + 1 page — any two
        # concurrent requests exceed the pool, so the tier must work
        pool = per_req + 1
    print(f"\n[kvtier] arch={cfg.name} requests={args.requests} "
          f"hot_pool={pool} pages (trace demand ~{demand} pages)")

    rows = [bench_kvtier_variant(v, cfg, params, args, pool)
            for v in ("resident", "reject", "requeue", "tiered")]
    hdr = ("variant", "wall_s", "done%", "tokens", "exhaust", "rejected",
           "preempt", "spill_pg", "fetch_pg")
    print(" ".join(f"{h:>9}" for h in hdr))
    for r in rows:
        print(f"{r['variant']:>9} {r['wall_s']:>9.2f} "
              f"{r['completed_pct']:>9.1f} {r['tokens']:>9d} "
              f"{r['pool_exhausted']:>9d} {r['rejected']:>9d} "
              f"{r['preemptions']:>9d} {r['spill_pages']:>9d} "
              f"{r['prefetch_pages']:>9d}")

    resident, reject, requeue, tiered = rows
    assert tiered["completed_pct"] == 100.0, "tiered must complete the trace"
    # spill/prefetch roundtrips must not change a single output token: the
    # tier relocates pages, it never approximates (unlike requeue's restart,
    # where prefill-vs-decode numerics can flip a near-tie argmax)
    assert tiered["out_tokens"] == resident["out_tokens"], \
        "tiered outputs diverge from the all-resident run"

    # price the tiered engine's page traffic on the paper's flash channels
    s = tiered["eng"].stats
    kv_pg = tiered["eng"].kv_page_bytes
    per_tok_spill = s.kv_spill_bytes / max(s.tokens_out, 1)
    per_tok_fetch = s.kv_prefetch_bytes / max(s.tokens_out, 1)
    cost = kv_swap_overhead_s(cfg, CAMBRICON_LLM_S, per_tok_spill,
                              per_tok_fetch, seq_len=args.max_seq)
    print(f"\ntiered: 100% completed (reject baseline "
          f"{reject['completed_pct']:.0f}%); "
          f"{s.kv_spill_pages} pages spilled / {s.kv_prefetch_pages} "
          f"prefetched ({(s.kv_spill_bytes + s.kv_prefetch_bytes) / 1e6:.2f} "
          f"MB at {kv_pg / 1024:.0f} KiB/page)")
    print(f"simulated bubble-bandwidth cost: {cost * 1e6:.2f} us/token "
          f"({per_tok_spill + per_tok_fetch:.0f} B/token through the "
          f"Slice Control bubbles)")
    if cfg.family in ("mla_moe", "hybrid"):
        # the page-byte accounting is family-aware: MLA spills compressed
        # ckv+krope rows, hybrid only its shared-attn groups — show how much
        # cheaper each evicted page is than a full-K/V page of the same arch
        itemsize = kv_pg // max(1, kv_page_elems(cfg, args.page_size))
        full = (2 * cfg.n_layers * args.page_size * cfg.n_kv_heads
                * cfg.d_head * itemsize)
        print(f"{cfg.family} page: {kv_pg} B vs full-K/V equivalent "
              f"{full} B — x{full / kv_pg:.1f} cheaper per evicted page")
    return rows


def _policy_prompt_lens(max_seq: int, max_new: int) -> list[int]:
    """Prompt-length menu for the policy trace; bench_policy sizes the hot
    pool from this same list, so every request passes the submit guard."""
    long_lens = (max_seq // 4, max_seq // 2 - max_new)
    return list(PROMPT_LENS) + [p for p in long_lens
                                if p > max(PROMPT_LENS)]


def make_policy_requests(n: int, cfg, max_new: int, seed: int,
                         max_seq: int, page_size: int) -> list[Request]:
    """Mixed trace: short interactive prompts AND long prompts (chunked
    prefill territory), priorities 0..2, and a per-request SLO deadline
    (tight for the short interactive requests, loose for the long ones) —
    the workload where admission policy actually changes TTFT and where
    the EDF policy has deadlines to order by."""
    rng = np.random.RandomState(seed + 3)
    lens = _policy_prompt_lens(max_seq, max_new)
    reqs = []
    for rid in range(n):
        plen = int(lens[rid % len(lens)])
        n_new = int(rng.randint(max(2, max_new // 4), max_new + 1))
        # SLO scales with the request's own service demand (so misses
        # measure scheduling, not model speed), floored for tiny requests
        deadline = max(1.0, 0.15 * (plen + n_new))
        reqs.append(Request(
            rid=rid, prompt=rng.randint(0, cfg.vocab_size, size=plen).tolist(),
            max_new_tokens=n_new, priority=int(rng.randint(0, 3)),
            deadline_s=float(deadline)))
    return reqs


def bench_policy_variant(policy: str, cfg, params, args, pool: int) -> dict:
    sched = make_scheduler(policy, chunk_tokens=args.chunk_prefill or None)
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=args.max_seq, eos_id=-1, mode="continuous",
                        page_size=args.page_size, num_pages=pool + 1,
                        kv_tier="flash", scheduler=sched)
    reqs = make_policy_requests(args.requests, cfg, args.max_new, args.seed,
                                args.max_seq, args.page_size)
    arrivals = poisson_arrivals(args.requests, args.rate, args.seed)
    wall = drive(eng, reqs, arrivals)
    s = eng.stats
    assert all(r.done for r in reqs)
    ok = sum(1 for r in reqs if not r.rejected)
    by_prio = {}
    for p in sorted({r.priority for r in reqs}):
        xs = [r.ttft_s for r in reqs if r.priority == p and not r.rejected]
        by_prio[p] = float(np.percentile(xs, 99)) if xs else 0.0
    with_slo = [r for r in reqs if r.deadline_s is not None
                and not r.rejected]
    missed = sum(1 for r in with_slo if r.deadline_missed)
    return {
        "policy": policy, "wall_s": wall,
        "completed_pct": 100.0 * ok / len(reqs),
        "miss_pct": 100.0 * missed / max(1, len(with_slo)),
        "tokens": s.tokens_out,
        "ttft_p50": s.percentiles("ttft_s")["p50"],
        "ttft_p99": s.percentiles("ttft_s")["p99"],
        "latency_p50": s.percentiles("latency_s")["p50"],
        "latency_p99": s.percentiles("latency_s")["p99"],
        "preemptions": s.preemptions,
        "prefill_chunks": s.prefill_chunks,
        "ttft_p99_by_prio": by_prio,
    }


def bench_policy(cfg, params, args) -> list[dict]:
    """Scheduler bake-off on the capacity-constrained tiered pool."""
    from repro.serving.kv_cache import pages_needed
    long_plen = max(_policy_prompt_lens(args.max_seq, args.max_new))
    per_req = pages_needed(min(args.max_seq, long_plen + args.max_new),
                           args.page_size)
    pool = args.pool_pages if args.pool_pages > 0 else per_req + 1
    print(f"\n[policy] arch={cfg.name} requests={args.requests} "
          f"hot_pool={pool} pages chunk_prefill="
          f"{args.chunk_prefill or 'off'} policies={sorted(POLICIES)}")

    # extra warmup: compile the chunked-prefill trace + the tiered paths
    # once so the per-policy runs measure scheduling, not compilation
    warm = ServingEngine(cfg, params, max_batch=args.max_batch,
                         max_seq=args.max_seq, eos_id=-1, mode="continuous",
                         page_size=args.page_size, num_pages=pool + 1,
                         kv_tier="flash",
                         scheduler=make_scheduler(
                             "fcfs", chunk_tokens=args.chunk_prefill or None))
    warm.submit(Request(rid=-1, prompt=[1] * long_plen, max_new_tokens=2))
    warm.run()

    rows = [bench_policy_variant(p, cfg, params, args, pool)
            for p in sorted(POLICIES)]
    hdr = ("policy", "wall_s", "done%", "miss%", "tokens", "ttft_p50",
           "ttft_p99", "lat_p50", "lat_p99", "preempt", "chunks")
    print(" ".join(f"{h:>9}" for h in hdr))
    for r in rows:
        print(f"{r['policy']:>9} {r['wall_s']:>9.2f} "
              f"{r['completed_pct']:>9.1f} {r['miss_pct']:>9.1f} "
              f"{r['tokens']:>9d} "
              f"{r['ttft_p50']:>9.3f} {r['ttft_p99']:>9.3f} "
              f"{r['latency_p50']:>9.3f} {r['latency_p99']:>9.3f} "
              f"{r['preemptions']:>9d} {r['prefill_chunks']:>9d}")
    for r in rows:
        prio = " ".join(f"p{k}={v:.3f}s"
                        for k, v in r["ttft_p99_by_prio"].items())
        print(f"  {r['policy']}: TTFT p99 by priority class: {prio}")
    for r in rows:
        assert r["completed_pct"] == 100.0, \
            f"{r['policy']} dropped requests on the tiered trace"
    return rows


def bench_router_variant(name: str, cfg, params, args, pool: int,
                         replicas: int, route: str = "least_loaded") -> dict:
    """One Poisson run over a Router fleet.  ``replicas`` small replicas
    vs one replica holding the same TOTAL slot+page budget."""
    if replicas == 1:
        eng = Router.build(cfg, params, replicas=1,
                           max_batch=args.max_batch * args.replicas,
                           max_seq=args.max_seq, eos_id=-1,
                           mode="continuous", page_size=args.page_size,
                           num_pages=args.replicas * pool + 1,
                           kv_tier="flash")
    else:
        eng = Router.build(cfg, params, replicas=replicas, policy=route,
                           max_batch=args.max_batch, max_seq=args.max_seq,
                           eos_id=-1, mode="continuous",
                           page_size=args.page_size, num_pages=pool + 1,
                           kv_tier="flash")
    reqs = make_kv_requests(args.requests, cfg, args.max_new, args.seed)
    if route == "session_affinity":
        # skewed session mix: most requests belong to one hot session, so
        # affinity piles them onto one replica — the hotspot slot migration
        # exists to drain (the cold replica is the donor)
        for r in reqs:
            r.session = "hot" if r.rid % 4 else f"cold-{r.rid}"
    arrivals = poisson_arrivals(args.requests, args.rate, args.seed)
    wall = drive(eng, reqs, arrivals)
    assert all(r.done for r in reqs)
    ok = [r for r in reqs if not r.rejected]
    ttft = [r.ttft_s for r in ok]
    tokens = sum(s.tokens_out for s in eng.stats)
    return {
        "variant": name, "wall_s": wall,
        "completed_pct": 100.0 * len(ok) / len(reqs),
        "tokens": tokens, "tok_per_s": tokens / wall,
        "ttft_p50": float(np.percentile(ttft, 50)) if ttft else 0.0,
        "ttft_p99": float(np.percentile(ttft, 99)) if ttft else 0.0,
        "migrations": eng.migrations,
        "preemptions": sum(s.preemptions for s in eng.stats),
        "out_tokens": {r.rid: list(r.out_tokens) for r in ok},
    }


def bench_router(cfg, params, args) -> list[dict]:
    """Multi-replica Router vs one wide replica, same total budget."""
    from repro.serving.kv_cache import pages_needed
    per_req = pages_needed(min(args.max_seq, max(PROMPT_LENS) + args.max_new),
                           args.page_size)
    pool = args.pool_pages if args.pool_pages > 0 else per_req + 1
    print(f"\n[router] arch={cfg.name} requests={args.requests} "
          f"replicas={args.replicas} x (batch={args.max_batch}, "
          f"pool={pool}) vs 1 x (batch={args.replicas * args.max_batch}, "
          f"pool={args.replicas * pool})")
    _warm(cfg, params, args, mode="continuous")
    n = args.replicas
    rows = [bench_router_variant("1-wide", cfg, params, args, pool, 1),
            bench_router_variant(f"{n}-balanced", cfg, params, args, pool,
                                 n, route="least_loaded"),
            bench_router_variant(f"{n}-affinity", cfg, params, args, pool,
                                 n, route="session_affinity")]
    hdr = ("variant", "wall_s", "done%", "tokens", "tok/s", "ttft_p50",
           "ttft_p99", "preempt", "migrate")
    print(" ".join(f"{h:>10}" for h in hdr))
    for r in rows:
        print(f"{r['variant']:>10} {r['wall_s']:>10.2f} "
              f"{r['completed_pct']:>10.1f} {r['tokens']:>10d} "
              f"{r['tok_per_s']:>10.1f} {r['ttft_p50']:>10.3f} "
              f"{r['ttft_p99']:>10.3f} {r['preemptions']:>10d} "
              f"{r['migrations']:>10d}")
    wide = rows[0]
    for r in rows:
        assert r["completed_pct"] == 100.0, \
            f"{r['variant']} dropped requests on the router trace"
        # partitioning the pool must not change any output: migration
        # relocates a slot's pages across replicas exactly like the tier
        # relocates them across pids — never approximates
        assert r["out_tokens"] == wide["out_tokens"], \
            f"{r['variant']} outputs diverge from the single-replica run"
    fleet, skew = rows[1], rows[2]
    print(f"\n{n}-replica fleet: 100% completed on both routes; "
          f"TTFT p99 {wide['ttft_p99']:.3f}s (1-wide) -> "
          f"{fleet['ttft_p99']:.3f}s (balanced) / {skew['ttft_p99']:.3f}s "
          f"(skewed affinity, {skew['migrations']} hotspot slot "
          f"migration(s) drained)")
    return rows


def make_prefix_sessions(cfg, args, n_turns: int = 3, user_len: int = 4):
    """Static skeleton of the multi-turn chat trace: per-session system
    prompts (the cacheable mass, page-aligned so full pages hit) and
    per-turn user spans.  Histories are grown live from each run's OWN
    outputs, so a variant's prompts depend only on its outputs — which the
    bit-identity assertion pins to the cold run's."""
    n_sessions = max(2, args.max_batch)
    m = min(args.max_new, 4)
    ps = args.page_size
    # final turn must fit: sys + n_turns * (user + out) <= max_seq
    sys_len = ((args.max_seq - n_turns * (user_len + m)) // ps) * ps
    assert sys_len >= ps, "max_seq too small for the prefix trace"
    rng = np.random.RandomState(args.seed + 5)
    sessions = []
    for s in range(n_sessions):
        sessions.append({
            "sid": f"sess-{s}",
            "system": rng.randint(0, cfg.vocab_size, size=sys_len).tolist(),
            "users": [rng.randint(0, cfg.vocab_size, size=user_len).tolist()
                      for _ in range(n_turns)],
            # odd sessions sample stochastically with a pinned seed — the
            # resume replay must stay bit-identical under BOTH modes
            "sampling": (None if s % 2 == 0 else
                         SamplingParams(temperature=0.8, top_k=20,
                                        seed=1000 + s)),
        })
    return sessions, m, n_turns, user_len


def bench_prefix_variant(name: str, cfg, params, args, make_eng) -> dict:
    """One pass over the chat trace: sessions interleave turn by turn (so a
    session's idle pages feel other sessions' allocation pressure between
    its own turns — the tiered variant spills and prefetches them), and
    every turn is immediately regenerated (exact-prompt resubmission, the
    resume-hit case)."""
    eng = make_eng()
    sessions, m, n_turns, _ = make_prefix_sessions(cfg, args)
    history = {s["sid"]: list(s["system"]) for s in sessions}
    recs: list[tuple[str, float]] = []   # (cold|hit, ttft_s)
    outs: dict[int, list[int]] = {}
    rid = 0
    t0 = time.monotonic()
    for t in range(n_turns):
        for sess in sessions:
            prompt = history[sess["sid"]] + sess["users"][t]
            first_out = None
            for kind in ("turn", "regen"):
                req = Request(rid=rid, prompt=list(prompt),
                              max_new_tokens=m, session=sess["sid"],
                              sampling=sess["sampling"])
                rid += 1
                eng.submit(req)
                while eng.has_work:
                    eng.step()
                assert req.done and not req.rejected, \
                    f"{name}: request {req.rid} did not complete"
                outs[req.rid] = list(req.out_tokens)
                # a warm cache only ever misses each session's very first
                # submission; every later turn shares pages with it
                recs.append(("cold" if (t == 0 and kind == "turn")
                             else "hit", req.ttft_s))
                if kind == "turn":
                    first_out = list(req.out_tokens)
            history[sess["sid"]] = prompt + first_out
    wall = time.monotonic() - t0
    stats = eng.stats
    if isinstance(stats, list):  # Router: sum the fleet's counters
        agg = {k: sum(getattr(s, k) for s in stats)
               for k in ("prefix_lookups", "prefix_hits", "prefix_hit_pages",
                         "prefix_tokens_reused", "cow_copies",
                         "kv_spill_pages", "kv_prefetch_pages")}
    else:
        agg = {k: getattr(stats, k)
               for k in ("prefix_lookups", "prefix_hits", "prefix_hit_pages",
                         "prefix_tokens_reused", "cow_copies",
                         "kv_spill_pages", "kv_prefetch_pages")}
    hit = sorted(t for k, t in recs if k == "hit")
    return {
        "variant": name, "wall_s": wall, "outs": outs,
        "n_requests": rid, "completed_pct": 100.0,
        "ttft_hit_p50": float(np.percentile(hit, 50)),
        "ttft_hit_p99": float(np.percentile(hit, 99)),
        **agg,
    }


def bench_prefix(cfg, params, args) -> list[dict]:
    """Prefix caching: warm variants must be bit-identical to cold with
    hit-turn TTFT collapsing >= 2x."""
    from repro.serving.kv_cache import pages_needed
    # the trace needs a system prompt with real prefill mass (the thing the
    # cache elides) even under --smoke, so it floors max_seq independently
    args = argparse.Namespace(**{**vars(args),
                                 "max_seq": max(args.max_seq, 256)})
    sessions, m, n_turns, user_len = make_prefix_sessions(cfg, args)
    sys_len = len(sessions[0]["system"])
    final_plen = sys_len + (n_turns - 1) * (user_len + m) + user_len
    per_req = pages_needed(min(args.max_seq, final_plen + m), args.page_size)
    # roomy pool for the untiered variants (every session's cache stays
    # hot); the tiered pool is sized BELOW the combined working set so
    # idle shared pages must spill to flash between a session's turns
    roomy = 2 * len(sessions) * per_req
    tight = per_req + 2
    print(f"\n[prefix] arch={cfg.name} sessions={len(sessions)} "
          f"turns={n_turns} (+1 regenerate each) sys_prompt={sys_len} tok "
          f"tiered_pool={tight} pages (working set ~"
          f"{len(sessions) * per_req})")

    def mk(**kw):
        base = dict(max_batch=args.max_batch, max_seq=args.max_seq,
                    eos_id=-1, mode="continuous", page_size=args.page_size)
        return lambda: ServingEngine(cfg, params, **{**base, **kw})

    factories = {
        "cold": mk(num_pages=roomy + 1),
        "warm": mk(num_pages=roomy + 1, prefix_cache=True),
        "warm-tiered": mk(num_pages=tight + 1, kv_tier="flash",
                          prefix_cache=True),
        "warm-2rep": lambda: Router.build(
            cfg, params, replicas=2, policy="session_affinity",
            max_batch=args.max_batch, max_seq=args.max_seq, eos_id=-1,
            mode="continuous", page_size=args.page_size,
            num_pages=roomy + 1, prefix_cache=True),
    }
    rows = []
    for name, f in factories.items():
        bench_prefix_variant(name, cfg, params, args, f)  # compile warmup
        rows.append(bench_prefix_variant(name, cfg, params, args, f))
    hdr = ("variant", "wall_s", "done%", "reqs", "hits", "hit_pg", "tok_re",
           "cow", "spill", "fetch", "ttft_hit_p50", "ttft_hit_p99")
    print(" ".join(f"{h:>12}" for h in hdr))
    for r in rows:
        print(f"{r['variant']:>12} {r['wall_s']:>12.2f} "
              f"{r['completed_pct']:>12.1f} {r['n_requests']:>12d} "
              f"{r['prefix_hits']:>12d} {r['prefix_hit_pages']:>12d} "
              f"{r['prefix_tokens_reused']:>12d} {r['cow_copies']:>12d} "
              f"{r['kv_spill_pages']:>12d} {r['kv_prefetch_pages']:>12d} "
              f"{r['ttft_hit_p50']:>12.4f} {r['ttft_hit_p99']:>12.4f}")
    cold = rows[0]
    for r in rows[1:]:
        # the whole point: reusing pages must never change a token — every
        # warm variant (incl. tiered spill/prefetch and 2-replica affinity)
        # replays the cold run bit for bit, greedy and stochastic sessions
        assert r["outs"] == cold["outs"], \
            f"{r['variant']} outputs diverge from the cold-cache run"
        assert r["prefix_hits"] > 0, f"{r['variant']} never hit the cache"
    warm = rows[1]
    speedup = cold["ttft_hit_p50"] / max(warm["ttft_hit_p50"], 1e-9)
    tiered = rows[2]
    assert tiered["kv_spill_pages"] > 0 and tiered["kv_prefetch_pages"] > 0, \
        "tiered prefix variant never exercised the flash tier"
    hit_rate = warm["prefix_hits"] / max(warm["prefix_lookups"], 1)
    print(f"\nprefix: 100% completed, all warm variants bit-identical to "
          f"cold; hit rate {100 * hit_rate:.0f}% "
          f"({warm['prefix_hits']}/{warm['prefix_lookups']}), "
          f"{warm['prefix_tokens_reused']} prompt tokens served from cache, "
          f"{warm['cow_copies']} copy-on-write page copies")
    print(f"hit-turn TTFT p50 {cold['ttft_hit_p50'] * 1e3:.2f} ms (cold) -> "
          f"{warm['ttft_hit_p50'] * 1e3:.2f} ms (warm): x{speedup:.1f}")
    assert speedup >= 2.0, \
        f"hit-turn TTFT p50 improved only x{speedup:.2f} (< 2x)"
    # the channel model prices the same collapse: cached tokens drop their
    # serialized NPU attention phases out of the prefill critical path
    t_cold = prefill_ttft_s(cfg, CAMBRICON_LLM_S, final_plen)
    t_warm = prefill_ttft_s(cfg, CAMBRICON_LLM_S, final_plen,
                            cached_tokens=sys_len)
    print(f"modeled TTFT ({final_plen}-token prompt, {sys_len} cached): "
          f"{t_cold * 1e3:.2f} ms -> {t_warm * 1e3:.2f} ms "
          f"(x{t_cold / t_warm:.1f})")
    return rows


def bench_quant_variant(name: str, cfg, params, args, pool: int) -> dict:
    kw = {"bf16-tiered": dict(num_pages=pool + 1, kv_tier="flash"),
          "int8-resident": dict(kv_dtype="int8"),
          "int8-tiered": dict(num_pages=pool + 1, kv_tier="flash",
                              kv_dtype="int8"),
          "int8+w8a8": dict(num_pages=pool + 1, kv_tier="flash",
                            kv_dtype="int8")}[name]
    if name == "int8+w8a8":
        from repro.quant.convert import quantize_params
        params = quantize_params(params, mode="w8a8")
    _warm(cfg, params, args, mode="continuous",
          kv_dtype=kw.get("kv_dtype", "bf16"))
    eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                        max_seq=args.max_seq, eos_id=-1, mode="continuous",
                        page_size=args.page_size, **kw)
    reqs = make_kv_requests(args.requests, cfg, args.max_new, args.seed)
    arrivals = poisson_arrivals(args.requests, args.rate, args.seed)
    wall = drive(eng, reqs, arrivals)
    s = eng.stats
    assert all(r.done for r in reqs)
    ok = sum(1 for r in reqs if not r.rejected)
    return {
        "variant": name, "wall_s": wall, "eng": eng,
        "completed_pct": 100.0 * ok / len(reqs),
        "tokens": s.tokens_out, "tok_per_s": s.tokens_out / wall,
        "ttft_p50": s.percentiles("ttft_s")["p50"],
        "ttft_p99": s.percentiles("ttft_s")["p99"],
        "spill_pages": s.kv_spill_pages, "prefetch_pages": s.kv_prefetch_pages,
        "spill_bytes": s.kv_spill_bytes, "prefetch_bytes": s.kv_prefetch_bytes,
        "page_bytes": eng.kv_page_bytes,
        "out_tokens": {r.rid: list(r.out_tokens) for r in reqs
                       if not r.rejected},
    }


def bench_quant(cfg, params, args) -> list[dict]:
    """bf16 vs int8 KV pages under KV-capacity pressure."""
    import dataclasses

    from repro.serving.kv_cache import pages_needed
    from repro.sim.llm_perf import family_kv_page_bytes

    # reduced configs pin d_head=16, where the int8 page (1B/elem payload
    # plus a 4B per-row scale) is only 1.6x smaller than bf16; real archs
    # carry d_head 64-128, so the trace bumps d_head to 64 and prices the
    # paper-scale ratio (2*Dh/(Dh+4) = 1.88x) while staying CPU-sized
    if cfg.d_head < 36:
        cfg = dataclasses.replace(cfg, name=cfg.name + "-qkv", d_head=64)
        params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                       max_seq=args.max_seq)
    per_req = pages_needed(min(args.max_seq, max(PROMPT_LENS) + args.max_new),
                           args.page_size)
    pool = args.pool_pages if args.pool_pages > 0 else per_req + 1
    print(f"\n[quant] arch={cfg.name} d_head={cfg.d_head} "
          f"requests={args.requests} hot_pool={pool} pages")

    rows = [bench_quant_variant(v, cfg, params, args, pool)
            for v in ("bf16-tiered", "int8-resident", "int8-tiered",
                      "int8+w8a8")]
    hdr = ("variant", "wall_s", "done%", "tokens", "tok/s", "ttft_p50",
           "ttft_p99", "spill_pg", "spill_MB", "pg_KiB")
    print(" ".join(f"{h:>13}" for h in hdr))
    for r in rows:
        print(f"{r['variant']:>13} {r['wall_s']:>13.2f} "
              f"{r['completed_pct']:>13.1f} {r['tokens']:>13d} "
              f"{r['tok_per_s']:>13.1f} {r['ttft_p50']:>13.3f} "
              f"{r['ttft_p99']:>13.3f} {r['spill_pages']:>13d} "
              f"{r['spill_bytes'] / 1e6:>13.3f} "
              f"{r['page_bytes'] / 1024:>13.1f}")

    bf16, resident, int8, w8 = rows
    for r in rows:
        assert r["completed_pct"] == 100.0, \
            f"{r['variant']} dropped requests on the quant trace"
    # the tier relocates quantized pages, it never re-quantizes: the
    # int8 engine's outputs must survive spill/prefetch bit for bit
    assert int8["out_tokens"] == resident["out_tokens"], \
        "int8 tiered outputs diverge from the int8 all-resident run"
    assert int8["spill_pages"] > 0, "quant trace never exercised the tier"
    ratio = bf16["spill_bytes"] / max(int8["spill_bytes"], 1)
    page_ratio = bf16["page_bytes"] / int8["page_bytes"]
    assert ratio >= 1.8, \
        f"int8 KV spilled only x{ratio:.2f} fewer bytes (< 1.8x)"
    # greedy streams on random prompts may flip argmax near-ties; report
    # agreement rather than asserting it (the serving tests pin exact
    # matches on margin-checked prompts)
    agree = sum(1 for k, v in int8["out_tokens"].items()
                if bf16["out_tokens"].get(k) == v)
    print(f"\nquant: 100% completed on all variants; int8 tiered "
          f"bit-identical to int8 resident; spill bytes "
          f"{bf16['spill_bytes'] / 1e6:.3f} MB -> "
          f"{int8['spill_bytes'] / 1e6:.3f} MB (x{ratio:.2f} less, "
          f"x{page_ratio:.2f}/page); {agree}/{len(int8['out_tokens'])} "
          f"greedy streams match bf16")
    print(f"TTFT p50 {bf16['ttft_p50'] * 1e3:.2f} ms (bf16) -> "
          f"{int8['ttft_p50'] * 1e3:.2f} ms (int8 KV) -> "
          f"{w8['ttft_p50'] * 1e3:.2f} ms (int8 KV + w8a8); tok/s "
          f"{bf16['tok_per_s']:.1f} -> {int8['tok_per_s']:.1f} -> "
          f"{w8['tok_per_s']:.1f}")
    # reprice the same traffic on the flash channel model: the halved page
    # moves the per-token tier cost with it
    for r, dt in ((bf16, "bf16"), (int8, "int8")):
        sim_pg = family_kv_page_bytes(cfg, args.page_size, kv_dtype=dt)
        assert sim_pg == r["page_bytes"], \
            f"sim {dt} page bytes {sim_pg} != engine {r['page_bytes']}"
        s = r["eng"].stats
        cost = kv_swap_overhead_s(
            cfg, CAMBRICON_LLM_S, s.kv_spill_bytes / max(s.tokens_out, 1),
            s.kv_prefetch_bytes / max(s.tokens_out, 1),
            seq_len=args.max_seq)
        print(f"modeled bubble-bandwidth cost ({dt} pages): "
              f"{cost * 1e6:.2f} us/token")
    return rows


def bench_fleet(cfg, params, args) -> list[dict]:
    """The fleet failover trace: N workers behind the fleet transport,
    one of them killed mid-trace.  The fleet must complete 100% of the
    requests with every output stream bit-identical to an undisturbed
    single-engine run (greedy AND seed-pinned stochastic); the report
    prices the failover — recovery latency and tokens replayed."""
    import os as _os
    import signal as _signal

    from repro.serving.fleet.router import FleetRouter

    def mk_reqs():
        base = make_requests(args.requests, cfg, args.max_new, args.seed)
        # odd rids go stochastic with pinned seeds: failover replay must
        # hold bit-identity for sampled streams too
        return [Request(rid=r.rid, prompt=list(r.prompt),
                        max_new_tokens=r.max_new_tokens,
                        sampling=None if r.rid % 2 == 0 else SamplingParams(
                            temperature=0.8, top_k=20, seed=1000 + r.rid))
                for r in base]

    if args.transport == "socket" and jax.default_backend() != "cpu":
        # main() already put params and the reference engine on the chip,
        # and a chip serves one process at a time
        raise SystemExit(
            f"bench_fleet --transport socket: this process holds the "
            f"{jax.default_backend()} device, so its subprocess workers "
            f"could not get it; use --transport loopback on a chip host")
    print(f"[fleet] arch={cfg.name} requests={args.requests} "
          f"workers={args.workers} spares={args.spares} "
          f"transport={args.transport}")
    _warm(cfg, params, args)

    # reference: ONE undisturbed in-process engine (per-request streams
    # are batch-composition-invariant, so this is the oracle)
    solo_reqs = mk_reqs()
    solo = ServingEngine(cfg, params, max_batch=args.max_batch,
                         max_seq=args.max_seq, eos_id=-1,
                         page_size=args.page_size)
    for r in solo_reqs:
        solo.submit(r)
    t0 = time.monotonic()
    solo.run()
    solo_wall = time.monotonic() - t0
    ref = {r.rid: list(r.out_tokens) for r in solo_reqs}

    if args.transport == "socket":
        fl = FleetRouter.build_socket(
            args.arch, workers=args.workers, spares=args.spares,
            checkpoint_every=4, migrate=False, reduced=bool(args.reduced),
            max_batch=args.max_batch, max_seq=args.max_seq,
            page_size=args.page_size, eos_id=-1)
    else:
        fl = FleetRouter.build_loopback(
            cfg, params, workers=args.workers, spares=args.spares,
            checkpoint_every=4, migrate=False, max_batch=args.max_batch,
            max_seq=args.max_seq, eos_id=-1, page_size=args.page_size)
    reqs = mk_reqs()
    arrivals = poisson_arrivals(args.requests, args.rate, args.seed)
    total_expected = sum(r.max_new_tokens for r in reqs)
    t0 = time.monotonic()
    i = 0
    killed = False
    while True:
        now = time.monotonic() - t0
        while i < len(reqs) and arrivals[i] <= now:
            fl.submit(reqs[i])
            i += 1
        fl.step()
        delivered = sum(len(r.out_tokens) for r in reqs)
        if not killed and delivered >= 0.4 * total_expected:
            w = fl.workers[0]
            if args.transport == "socket":
                _os.kill(w.transport.pid, _signal.SIGKILL)
            else:
                w.transport.kill()
            killed = True
        if not fl.has_work:
            if i >= len(reqs):
                break
            time.sleep(max(0.0, min(0.001,
                                    arrivals[i] - (time.monotonic() - t0))))
    wall = time.monotonic() - t0
    assert killed, "trace finished before the scripted kill fired"
    assert all(r.done for r in reqs), \
        f"lost requests: {[r.rid for r in reqs if not r.done]}"
    for r in reqs:
        assert list(r.out_tokens) == ref[r.rid], \
            f"rid {r.rid} diverged after failover"
    tokens = sum(len(r.out_tokens) for r in reqs)
    recovery = float(np.median(fl.recovery_s)) if fl.recovery_s else 0.0
    rows = [{
        "transport": args.transport,
        "workers": args.workers,
        "wall_s": wall,
        "solo_wall_s": solo_wall,
        "tokens": tokens,
        "tok_per_s": tokens / wall,
        "workers_lost": fl.fleet.workers_lost,
        "failovers": fl.fleet.failovers,
        "requests_replayed": fl.fleet.requests_replayed,
        "tokens_replayed": fl.fleet.tokens_replayed,
        "recovery_s": recovery,
    }]
    print(f"  completed 100% ({len(reqs)} requests, {tokens} tokens), "
          f"all streams bit-identical to the undisturbed run")
    print(f"  wall {wall:.1f}s (solo {solo_wall:.1f}s)  "
          f"failovers={fl.fleet.failovers} "
          f"requests_replayed={fl.fleet.requests_replayed} "
          f"tokens_replayed={fl.fleet.tokens_replayed} "
          f"recovery={recovery * 1e3:.0f} ms")
    print(fl.summary())
    fl.close()
    return rows


def bench_sanitize(cfg, params, args) -> dict:
    """Sanitizer-rails smoke: the overlapped + tiered + prefix-cache decode
    path (every rail armed at once — shadow allocators, dispatch aliasing
    guard, retrace budget) driven under ``REPRO_SANITIZE=1`` and raced
    against the identical un-sanitized engine.  Asserts the rails actually
    ran, reported nothing, changed no output token, and cost < 2x wall."""
    from repro.serving.kv_cache import pages_needed

    # the shared prefix must cover whole pages to be cacheable: two pages
    # of system prompt + a short random tail per request
    common_len = 2 * args.page_size
    tail_len = 4
    rng = np.random.RandomState(args.seed + 7)
    common = rng.randint(0, cfg.vocab_size, size=common_len).tolist()
    per_req = pages_needed(min(args.max_seq,
                               common_len + tail_len + args.max_new),
                           args.page_size)
    pool = per_req + 1  # two concurrent requests exceed it even with the
    # common pages deduped by the prefix cache, so the tier must spill

    def trace(sanitized: bool) -> tuple[float, dict, object]:
        prev = os.environ.get("REPRO_SANITIZE")
        os.environ["REPRO_SANITIZE"] = "1" if sanitized else "0"
        try:
            kw = dict(mode="continuous", overlap=True, kv_tier="flash",
                      num_pages=pool, prefix_cache=True)
            _warm(cfg, params, args, **kw)
            eng = ServingEngine(cfg, params, max_batch=args.max_batch,
                                max_seq=args.max_seq, eos_id=-1,
                                page_size=args.page_size, **kw)
            # shared system-prompt prefix + random tail: requests hit the
            # prefix cache against each other, so the refcounted/CoW page
            # path runs under the shadow allocator too
            req_rng = np.random.RandomState(args.seed + 8)
            reqs = [Request(rid=rid, prompt=common + req_rng.randint(
                        0, cfg.vocab_size, size=tail_len).tolist(),
                        max_new_tokens=args.max_new)
                    for rid in range(args.requests)]
            # everything arrives at once: max concurrency, so the tight
            # pool actually forces spill/prefetch traffic under the shadow
            arrivals = np.zeros(args.requests)
            wall = drive(eng, reqs, arrivals)
            assert all(r.done and not r.rejected for r in reqs)
            outs = {r.rid: list(r.out_tokens) for r in reqs}
            return wall, outs, eng
        finally:
            if prev is None:
                os.environ.pop("REPRO_SANITIZE", None)
            else:
                os.environ["REPRO_SANITIZE"] = prev

    print(f"\n[sanitize] arch={cfg.name} requests={args.requests} "
          f"hot_pool={pool} pages, overlapped+tiered+prefix, rails armed")
    from repro import _sanitize
    prev = os.environ.get("REPRO_SANITIZE")
    os.environ["REPRO_SANITIZE"] = "1"   # load() gates on the env var
    try:
        san = _sanitize.load()
    finally:
        if prev is None:
            os.environ.pop("REPRO_SANITIZE", None)
        else:
            os.environ["REPRO_SANITIZE"] = prev
    assert san is not None, "tools.analysis.sanitize not importable"
    trace(sanitized=False)  # discarded: first pass pays the jit compiles
    plain_wall, plain_outs, _ = trace(sanitized=False)
    san.reset_counters()
    san_wall, san_outs, eng = trace(sanitized=True)

    assert san.report_count() == 0, \
        f"sanitizer reported {san.report_count()} violation(s) on a clean run"
    assert san.check_count() > 0, "rails never executed — hooks are dead"
    assert getattr(eng.allocator, "_shadow", None) is not None or \
        getattr(getattr(eng.allocator, "hot", None), "_shadow", None) \
        is not None, "page shadow not attached"
    assert san_outs == plain_outs, \
        "sanitized run changed output tokens — rails must be pure observers"
    slowdown = san_wall / max(plain_wall, 1e-9)
    print(f"{'variant':>10} {'wall_s':>8} {'checks':>8} {'reports':>8}")
    print(f"{'plain':>10} {plain_wall:>8.2f} {'-':>8} {'-':>8}")
    print(f"{'sanitized':>10} {san_wall:>8.2f} {san.check_count():>8d} "
          f"{san.report_count():>8d}")
    print(f"[sanitize] slowdown {slowdown:.2f}x "
          f"(spill={eng.stats.kv_spill_pages} pages, "
          f"prefetch={eng.stats.kv_prefetch_pages} pages, "
          f"prefix_hits={eng.stats.prefix_hits})")
    assert slowdown < 2.0, \
        f"sanitizer slowdown {slowdown:.2f}x breaches the 2x budget"
    return {"wall_plain_s": plain_wall, "wall_sanitized_s": san_wall,
            "slowdown": slowdown, "checks": san.check_count(),
            "reports": san.report_count()}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", type=int, default=1)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--rate", type=float, default=16.0,
                    help="Poisson arrival rate, requests/s")
    ap.add_argument("--max-new", type=int, default=24)
    ap.add_argument("--max-batch", type=int, default=4)
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--page-size", type=int, default=16)
    ap.add_argument("--pool-pages", type=int, default=0,
                    help="hot KV pool size for the kvtier trace "
                         "(0 = auto, sized below trace demand)")
    ap.add_argument("--replicas", type=int, default=2,
                    help="replica count for the router trace (raced "
                         "against ONE replica with the same total "
                         "slot+page budget)")
    ap.add_argument("--workers", type=int, default=2,
                    help="fleet trace: workers behind the fleet transport")
    ap.add_argument("--spares", type=int, default=1,
                    help="fleet trace: hot spares promoted on failover")
    ap.add_argument("--transport", default="loopback",
                    choices=("loopback", "socket"),
                    help="fleet trace transport (socket = real subprocess "
                         "workers, SIGKILLed mid-trace)")
    ap.add_argument("--trace", choices=("admission", "overlap", "kvtier",
                                        "policy", "prefix", "router",
                                        "quant", "fleet", "sanitize",
                                        "all"),
                    default="all")
    ap.add_argument("--overlap", action="store_true",
                    help="run the admission trace's continuous engine with "
                         "the overlapped decode loop (fused dispatch, "
                         "one-step-delayed readback)")
    ap.add_argument("--chunk-prefill", type=int, default=8,
                    help="chunked-prefill token budget for the policy "
                         "trace (0 = one-shot prefill)")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny fast preset for CI (overrides sizes)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    if args.smoke:
        args.requests = min(args.requests, 8)
        args.max_new = min(args.max_new, 10)
        args.max_batch = min(args.max_batch, 3)
        args.max_seq = min(args.max_seq, 64)
        args.page_size = min(args.page_size, 8)
        args.rate = 32.0

    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   max_seq=args.max_seq)
    out = {}
    if args.trace in ("admission", "all"):
        out["admission"] = bench_admission(cfg, params, args)
    if args.trace in ("overlap", "all"):
        out["overlap"] = bench_overlap(cfg, params, args)
    if args.trace in ("kvtier", "all"):
        out["kvtier"] = bench_kvtier(cfg, params, args)
    if args.trace in ("policy", "all"):
        out["policy"] = bench_policy(cfg, params, args)
    if args.trace in ("prefix", "all"):
        out["prefix"] = bench_prefix(cfg, params, args)
    if args.trace in ("router", "all"):
        out["router"] = bench_router(cfg, params, args)
    if args.trace in ("quant", "all"):
        out["quant"] = bench_quant(cfg, params, args)
    if args.trace in ("fleet", "all"):
        out["fleet"] = bench_fleet(cfg, params, args)
    if args.trace in ("sanitize", "all"):
        out["sanitize"] = bench_sanitize(cfg, params, args)
    return out


if __name__ == "__main__":
    main()
