"""Serving driver over the ServingClient surface.

This is the user-facing end of the three-layer serving API
(``ServingClient -> Router -> EngineCore``, see serving/engine.py and the
ROADMAP design note): the client allocates globally unique request ids —
and derives each stochastic request's sampling seed from its id, so seeds
never collide across replicas — the router spreads requests over
``--replicas N`` engine replicas under ``--route`` (round_robin /
least_loaded / session_affinity) and migrates slots off page-starved
replicas, and each replica runs the paged/tiered KV serving loop under the
``--policy`` scheduler (fcfs / priority / sjf / drr / edf).  ``--overlap``
switches every replica to the overlapped decode loop — decode + sampling
fused into ONE jitted dispatch per step, sampled tokens held on device and
read back one step late, so step N+1 is enqueued before step N's token
reaches the host; outputs stay bit-identical to the synchronous loop.

``--prefix-cache`` turns on refcounted prefix caching in every replica:
prefill-written KV pages are registered in a per-replica prefix index
(sha256 chain over page-aligned token spans), a repeated prompt re-maps
those shared pages instead of re-prefilling them (an exact repeat skips
prefill dispatches entirely and replays the stored first-token logits),
writes into shared pages copy-on-write, and released pages park idle in
the index — spillable to the flash tier and prefetched back on the next
hit.  Outputs stay bit-identical to a cold-cache run; under
``--route session_affinity`` the replica whose cache holds the session's
pages wins the routing decision.

``--quant w8a8`` serves from quantized weights: ``quantize_params``
rewrites every non-router linear as int8 weights + per-output-channel
scales (``w4a16`` packs int4 nibbles + per-group scales instead), the
layers dispatch to the quantized matmuls, and the router/gate weights
stay full precision.  ``--kv-dtype int8`` additionally quantizes the
paged KV pool itself: pages hold int8 rows plus a per-row f32 scale,
written once at prefill/decode time and dequantized at the attend, so
spill, prefetch, prefix sharing, migration, and fleet snapshots all
move the half-sized ``(payload, scale)`` pages unchanged — decode
streams stay bit-identical to themselves across every relocation path.

``--workers N`` switches to fleet mode (serving/fleet/): N workers
behind the versioned wire protocol — in-process under
``--transport loopback``, real subprocesses under ``--transport
socket`` — with heartbeat health tracking, ``--spares K`` hot spares,
and snapshot-based failover that keeps every recovered token stream
bit-identical to an undisturbed run.

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --requests 8 --max-new 16 --replicas 2 --route least_loaded \
      --policy edf --deadline 5.0 --chunk-prefill 8 \
      --temperature 0.8 --top-k 40 --stream

  PYTHONPATH=src python -m repro.launch.serve --arch smollm-360m \
      --requests 8 --workers 2 --spares 1 --transport socket

Typical surface usage (what this driver does):

    client = ServingClient(cfg, params, replicas=2, route="least_loaded",
                           max_batch=4, max_seq=128, scheduler="edf")
    h = client.submit(prompt, max_new_tokens=16, deadline_s=5.0,
                      sampling=SamplingParams(temperature=0.8))
    for out in client.stream():   # or: for tok in h.tokens()
        ...
"""

from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs.registry import get_arch
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model as model_lib
from repro.quant.convert import quantize_params
from repro.serving.client import ServingClient
from repro.serving.router import ROUTE_POLICIES
from repro.serving.scheduler import POLICIES, SamplingParams, make_scheduler


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="smollm-360m")
    ap.add_argument("--reduced", type=int, default=1)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-batch", type=int, default=4,
                    help="slots PER replica")
    ap.add_argument("--max-seq", type=int, default=128)
    ap.add_argument("--replicas", type=int, default=1,
                    help="engine replicas behind the router")
    ap.add_argument("--route", default="round_robin",
                    choices=ROUTE_POLICIES,
                    help="router policy distributing requests over "
                         "replicas")
    ap.add_argument("--no-migrate", action="store_true",
                    help="disable cross-replica slot migration")
    ap.add_argument("--workers", type=int, default=0,
                    help="fleet mode: N workers behind the fleet wire "
                         "protocol with heartbeat health tracking and "
                         "snapshot-based failover (0 = classic in-process "
                         "replicas)")
    ap.add_argument("--transport", default="loopback",
                    choices=["loopback", "socket"],
                    help="fleet transport: loopback = in-process workers "
                         "behind the byte-faithful wire codec; socket = "
                         "real subprocess workers over TCP")
    ap.add_argument("--spares", type=int, default=0,
                    help="hot spare workers promoted on failover")
    ap.add_argument("--mode", default="auto",
                    choices=["auto", "wave", "continuous"],
                    help="auto = continuous where the family supports a "
                         "paged KV cache, else wave")
    ap.add_argument("--page-size", type=int, default=16,
                    help="tokens per KV page (continuous mode)")
    ap.add_argument("--overlap", action="store_true",
                    help="overlapped decode loop: fused decode+sample "
                         "dispatch with one-step-delayed host readback — "
                         "1 jitted dispatch per decode step instead of 2, "
                         "bit-identical outputs")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="refcounted prefix caching: repeated prompts "
                         "re-map shared KV pages instead of re-prefilling "
                         "(copy-on-write on writes, bit-identical outputs)")
    ap.add_argument("--policy", default="fcfs", choices=sorted(POLICIES),
                    help="per-replica admission/preemption policy "
                         "(serving.scheduler)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="per-request SLO budget in seconds "
                         "(0 = none; pair with --policy edf)")
    ap.add_argument("--chunk-prefill", type=int, default=0,
                    help="chunked-prefill token budget per step "
                         "(0 = one-shot prefill)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="per-request sampling temperature (0 = greedy)")
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--top-p", type=float, default=1.0)
    ap.add_argument("--seed", type=int, default=0,
                    help="sampling seed base; the CLIENT derives each "
                         "request's seed as base + global rid, so streams "
                         "never collide across replicas")
    ap.add_argument("--stream", action="store_true",
                    help="print each RequestOutput token event")
    ap.add_argument("--quant", default="w8a8",
                    choices=["none", "w8a8", "w4a16", "int8"],
                    help="weight quantization mode for quantize_params "
                         "(router/gate weights stay full precision; "
                         "'int8' is the legacy alias for w8a8)")
    ap.add_argument("--kv-dtype", default="bf16", choices=["bf16", "int8"],
                    help="paged KV pool dtype: int8 stores quantized page "
                         "rows + per-row f32 scales (half the spill bytes, "
                         "self-bit-identical across every relocation path)")
    args = ap.parse_args(argv)
    if args.quant == "int8":
        args.quant = "w8a8"
    return args


def make_params(cfg, args):
    """Seeded random weights at ``cfg``'s widths, quantized per ``--quant``."""
    params = model_lib.init_params(cfg, jax.random.PRNGKey(0),
                                   max_seq=args.max_seq)
    if args.quant != "none":
        params = quantize_params(params, mode=args.quant)
    return params


def make_prompts(n: int, vocab: int, lo: int = 2, hi: int = 9,
                 seed: int = 42) -> list[list[int]]:
    """``n`` seeded prompts with lengths in ``[lo, hi)``.  Plain numpy, so a
    parent that hands the device to socket workers never touches JAX."""
    rng = np.random.default_rng(seed)
    return [rng.integers(0, vocab, int(rng.integers(lo, hi))).tolist()
            for _ in range(n)]


def build_client(args, cfg, params=None, **engine_kw) -> ServingClient:
    """The client ``main`` serves through.  Socket fleets rebuild params in
    their worker processes; otherwise ``params`` defaults to
    :func:`make_params`.  ``engine_kw`` reaches every ``EngineCore``."""
    if args.workers and args.transport == "socket":
        # subprocess workers rebuild params themselves from (arch, seed)
        # and quantize locally, so nothing heavy ships over the wire;
        # prefix-cache / mode are per-worker features the worker CLI does
        # not expose yet
        from repro.serving.fleet.router import FleetRouter
        router = FleetRouter.build_socket(
            args.arch, workers=args.workers, spares=args.spares,
            policy=args.route, migrate=not args.no_migrate,
            sched_policy=args.policy, reduced=bool(args.reduced),
            max_batch=args.max_batch, max_seq=args.max_seq,
            page_size=args.page_size, eos_id=-1, overlap=args.overlap,
            chunk_prefill=args.chunk_prefill,
            kv_dtype=args.kv_dtype, quant=args.quant)
        return ServingClient(router=router, seed_base=args.seed)
    if params is None:
        params = make_params(cfg, args)
    return ServingClient(
        cfg, params, replicas=args.replicas, route=args.route,
        migrate=not args.no_migrate, seed_base=args.seed,
        workers=args.workers, transport=args.transport,
        spares=args.spares,
        max_batch=args.max_batch, max_seq=args.max_seq, eos_id=-1,
        mode=args.mode, page_size=args.page_size, overlap=args.overlap,
        prefix_cache=args.prefix_cache, kv_dtype=args.kv_dtype,
        scheduler=make_scheduler(args.policy,
                                 chunk_tokens=args.chunk_prefill or None),
        **engine_kw)


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    client = build_client(args, cfg)
    for i, prompt in enumerate(make_prompts(args.requests, cfg.vocab_size)):
        client.submit(
            prompt, max_new_tokens=args.max_new,
            priority=i % 3,  # mixed priorities exercise the policy
            deadline_s=args.deadline or None,
            session=f"user-{i % 4}",  # affinity demo under --route
            sampling=SamplingParams(temperature=args.temperature,
                                    top_k=args.top_k, top_p=args.top_p))
    t0 = time.time()
    for out in client.stream():
        if out.finished:
            print(f"rid={out.rid} done n_out={out.n_out} "
                  f"reason={out.finish_reason} "
                  f"ttft={out.ttft_s if out.ttft_s is not None else -1:.3f}s "
                  f"chunks={out.sched['chunks']} "
                  f"preempt={out.sched['preemptions']}")
        elif args.stream:
            print(f"rid={out.rid} tok[{out.n_out - 1}]={out.token}")
    dt = time.time() - t0
    tokens = sum(s.tokens_out for s in client.router.stats)
    steps = sum(s.decode_steps for s in client.router.stats)
    print(f"requests={args.requests} tokens_out={tokens} "
          f"decode_steps={steps} wall={dt:.1f}s tok/s={tokens/dt:.1f}")
    print(client.summary())
    fleet = getattr(client.router, "fleet", None)
    if fleet is not None:   # fleet mode: surface the failover counters
        print(f"fleet shutdown: workers_lost={fleet.workers_lost} "
              f"failovers={fleet.failovers} "
              f"requests_replayed={fleet.requests_replayed} "
              f"tokens_replayed={fleet.tokens_replayed} "
              f"heartbeat_misses={fleet.heartbeat_misses}")
        client.router.close()


if __name__ == "__main__":
    main()
