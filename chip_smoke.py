#!/usr/bin/env python3
"""Bring-up smoke test: serve smollm-360m at its published widths on a TPU.

Drives the path a user calls -- ``ServingClient -> Router -> EngineCore``,
continuous batching over paged KV -- built exactly as ``python -m
repro.launch.serve`` builds it, over seeded random weights, and checks what
comes out:

* phase A: ``serve``'s defaults (w8a8 weights, bf16 KV, fcfs, synchronous
  loop) at ``--reduced 0``: 8 greedy requests with 16-256 prompt tokens and
  32 new tokens each, some admitted while others decode.
* phase B: the same requests through the overlapped loop (one donated fused
  decode+sample dispatch per step) over a flash-tiered KV pool smaller than
  the demand, so pages spill and prefetch back; slot checkpoints
  (``snapshot_slot``) are taken mid-run.  Its streams must equal phase A's.
* reference: ``models.model.forward`` over the same weights in f32 at the
  highest matmul precision, no cache.  Each request's served first-token
  logits must lie within ``LOGIT_TOL`` of the reference's, and every greedy
  token must be the reference's argmax wherever the reference's top-2 margin
  exceeds ``LOGIT_TOL``.

``--chips 4`` runs only the replica phase: 16 greedy requests through a
4-replica ``least_loaded`` router (replica i on device i) and through one
replica; the streams must be equal.

    python3 chip_smoke.py              # one TPU chip
    python3 chip_smoke.py --chips 4    # four TPU chips on one host

Earlier lines print set-up figures per phase (compile and wall seconds,
tokens out, compile-cache hits); they are not benchmark numbers.  The last
line is one JSON object, ``{"ok": true, "device": {...}}``.  Any failure
raises and the script exits non-zero.  Without a TPU it exits non-zero
before serving anything; the tests rehearse it on the CPU with ``--cpu``,
and ``--reduced`` swaps in the config's smoke-test widths.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs.registry import get_arch  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.models import model as model_lib  # noqa: E402
from repro.serving.kv_cache import pages_needed  # noqa: E402
from repro.serving.scheduler import SamplingParams  # noqa: E402

ARCH = "smollm-360m"
MAX_NEW = 32
PAGE = 16
# max |served - reference| over a request's first-token logits, and the
# top-2 margin above which a greedy token must be the reference's argmax.
# A CPU run at the published widths measured a worst first-token error of
# 0.178 over the 8 phase-A requests: bf16 activations and KV (and the int8
# activation rounding they move) through 32 layers, against the f32
# reference.  The limit leaves about 2x headroom for the chip's own bf16
# rounding.
LOGIT_TOL = 0.35

_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"


class CompileMeter:
    """Backend compile seconds and persistent-cache lookups/hits, from JAX's
    monitoring events (a compile served from the cache counts its read)."""

    def __init__(self):
        self.secs, self.lookups, self.hits = 0.0, 0, 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **_):
        if event == _COMPILE_EVENT:
            self.secs += duration

    def _event(self, event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            self.lookups += 1
        elif event == "/jax/compilation_cache/cache_hits":
            self.hits += 1

    def reading(self) -> tuple[float, int, int]:
        return self.secs, self.lookups, self.hits


def report(name: str, meter: CompileMeter, since, t0: float,
           tokens: int) -> None:
    secs, lookups, hits = (a - b for a, b in zip(meter.reading(), since))
    print(f"phase {name}: compile_s={secs:.1f} wall_s="
          f"{time.perf_counter() - t0:.1f} tokens_out={tokens} "
          f"compile_cache hits={hits}/{lookups} "
          f"(set-up figures, not benchmark numbers)", flush=True)


def device_info(allow_cpu: bool, chips: int) -> dict:
    devs = jax.devices()
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}
    print(f"device platform={info['platform']} kind={info['kind']} "
          f"count={info['count']}", flush=True)
    if info["platform"] != "tpu" and not allow_cpu:
        sys.exit(f"chip_smoke: no TPU (JAX platform {info['platform']!r}); "
                 f"this smoke test runs on the chip only")
    if info["count"] < chips:
        sys.exit(f"chip_smoke: --chips {chips} needs {chips} devices, "
                 f"JAX sees {info['count']}")
    return info


def serve_args(reduced: bool, *extra: str):
    return serve.parse_args(["--reduced", str(int(reduced)), "--max-batch",
                             "4", "--max-seq", "512", "--page-size",
                             str(PAGE), *extra])


def drive(client, prompts, first: int, lead_steps: int = 4,
          every_step=None) -> list[list[int]]:
    """Submit ``first`` prompts, step the fleet ``lead_steps`` times, submit
    the rest (so they are admitted while the first ones decode) and run to
    completion.  Returns each request's greedy token stream, in order."""
    greedy = SamplingParams(temperature=0.0)
    streams: dict[int, list[int]] = {}
    rids = []

    def pump():
        for ev in client.pump():
            if ev.token is not None:
                streams.setdefault(ev.rid, []).append(ev.token)
        if every_step is not None:
            every_step()

    def submit(batch):
        for p in batch:
            rids.append(client.submit(p, max_new_tokens=MAX_NEW,
                                      sampling=greedy).rid)

    submit(prompts[:first])
    for _ in range(lead_steps):
        pump()
    submit(prompts[first:])
    for _ in range(100_000):
        if not client.has_work:
            break
        pump()
    pump()  # terminal events of the last step
    out = [streams.get(r, []) for r in rids]
    short = [(r, len(s)) for r, s in zip(rids, out) if len(s) != MAX_NEW]
    if short:
        raise AssertionError(f"requests not served to {MAX_NEW} tokens: "
                             f"(rid, tokens) {short}")
    return out


def record_prefills(core, log: list) -> None:
    """Keep the first-token logits the engine's prefill produces, and how
    many slots were decoding when each prefill ran."""
    inner = core._prefill_slots

    def prefill(params, toks, true_lens, cache, slot_ids):
        busy = sum(s is not None for s in core.slots)
        logits, out = inner(params, toks, true_lens, cache, slot_ids)
        log.append((np.asarray(toks), np.asarray(true_lens),
                    np.asarray(logits, np.float32), busy))
        return logits, out

    core._prefill_slots = prefill


def first_logits(prompts, log) -> list[np.ndarray]:
    rows = {}
    for toks, lens, logits, _ in log:
        for r in range(toks.shape[0]):
            rows.setdefault(tuple(toks[r, :lens[r]].tolist()), logits[r])
    return [rows[tuple(p)] for p in prompts]


def compare_streams(name: str, want, got) -> None:
    diffs = []
    for rid, (a, b) in enumerate(zip(want, got)):
        for pos, (x, y) in enumerate(zip(a, b)):
            if x != y:
                diffs.append(f"rid={rid} pos={pos}: {x} != {y}")
                break
    print(f"{name}: {len(want) - len(diffs)}/{len(want)} streams equal",
          flush=True)
    for d in diffs:
        print(f"  stream differs at {d}", flush=True)
    if diffs:
        raise AssertionError(f"{name}: {len(diffs)} streams differ")


def reference_check(cfg, params, prompts, streams, served_first) -> None:
    """f32 no-cache forward over prompt + served tokens (teacher forced)."""
    f32 = jax.tree.map(lambda a: a.astype(jnp.float32)
                       if jnp.issubdtype(a.dtype, jnp.floating) else a,
                       params)
    seqs = [p + s[:-1] for p, s in zip(prompts, streams)]
    toks = np.zeros((len(seqs), max(map(len, seqs))), np.int32)
    for i, s in enumerate(seqs):
        toks[i, :len(s)] = s
    at = np.asarray([[len(p) - 1 + t for t in range(MAX_NEW)]
                     for p in prompts], np.int32)

    @jax.jit
    def ref(p, toks, at):
        logits = model_lib.forward(p, cfg, toks)
        return jnp.take_along_axis(logits, at[:, :, None], axis=1)

    with jax.default_matmul_precision("highest"):
        logits = np.asarray(ref(f32, toks, at))           # [R, MAX_NEW, V]
    errors, checked, flips = [], 0, []
    for i, (stream, served) in enumerate(zip(streams, served_first)):
        errors.append(float(np.max(np.abs(served - logits[i, 0]))))
        for t, tok in enumerate(stream):
            row = logits[i, t]
            top2 = np.partition(row, -2)[-2:]
            if top2[1] - top2[0] > LOGIT_TOL:
                checked += 1
                if int(np.argmax(row)) != tok:
                    flips.append(f"rid={i} pos={t}: served {tok}, "
                                 f"reference {int(np.argmax(row))}")
    print(f"reference: first-token max|served-ref| per request "
          f"{[round(e, 5) for e in errors]} (limit {LOGIT_TOL}; reference "
          f"logit std {float(np.std(logits[:, 0])):.4f})", flush=True)
    print(f"reference: greedy tokens checked {checked}/"
          f"{sum(map(len, streams))} (top-2 margin > {LOGIT_TOL}), "
          f"{len(flips)} differ", flush=True)
    for f in flips:
        print(f"  greedy token differs at {f}", flush=True)
    bad = [i for i, e in enumerate(errors) if e > LOGIT_TOL]
    if bad or flips:
        raise AssertionError(f"reference check failed: first-token logits "
                             f"out of limit for rids {bad}, "
                             f"{len(flips)} greedy tokens differ")


def one_chip(cfg, reduced: bool, meter: CompileMeter) -> None:
    args = serve_args(reduced)
    prompts = serve.make_prompts(8, cfg.vocab_size, lo=16, hi=257, seed=7)
    print(f"requests: 8, prompt tokens {[len(p) for p in prompts]}, "
          f"{MAX_NEW} new tokens each, greedy", flush=True)
    params = serve.make_params(cfg, args)

    since, t0 = meter.reading(), time.perf_counter()
    client = serve.build_client(args, cfg, params)
    log: list = []
    record_prefills(client.router.cores[0], log)
    streams_a = drive(client, prompts, first=3)
    report("A (sync, resident KV)", meter, since, t0,
           sum(s.tokens_out for s in client.router.stats))
    mid = sum(1 for *_, busy in log if busy)
    print(f"phase A: {len(log)} prefills, {mid} admitted while other slots "
          f"decoded", flush=True)
    if not mid:
        raise AssertionError("phase A admitted nothing mid-stream")

    # hot pool: one request's worst case plus a little, well under the
    # demand of four concurrent slots, so the flash tier must work
    need = [pages_needed(len(p) + MAX_NEW, PAGE) for p in prompts]
    hot = max(max(need) + 4, sum(sorted(need)[-4:]) // 2) + 1
    since, t0 = meter.reading(), time.perf_counter()
    client = serve.build_client(serve_args(reduced, "--overlap"), cfg,
                                params, kv_tier="flash", num_pages=hot)
    core = client.router.cores[0]
    snaps = []

    def checkpoint():
        # a fleet failover checkpoint: drains the in-flight donated step
        # and copies one live slot out while it keeps running here
        live = [r for r in core.slots if r is not None]
        if live and core.stats.decode_steps % 8 == 0:
            snaps.append(len(core.snapshot_slot(live[0].rid,
                                                release=False).to_bytes()))

    streams_b = drive(client, prompts, first=3, every_step=checkpoint)
    st = core.stats
    report("B (overlap, flash tier)", meter, since, t0, st.tokens_out)
    print(f"phase B: hot pool {hot - 1} pages for a demand of {sum(need)}; "
          f"spilled {st.kv_spill_pages} pages, prefetched "
          f"{st.kv_prefetch_pages}, preemptions {st.preemptions}, "
          f"{len(snaps)} slot checkpoints", flush=True)
    if not (st.kv_spill_pages and st.kv_prefetch_pages and snaps):
        raise AssertionError("phase B did not spill, prefetch and "
                             "checkpoint")
    compare_streams("phase A vs B", streams_a, streams_b)

    since, t0 = meter.reading(), time.perf_counter()
    reference_check(cfg, params, prompts, streams_a,
                    first_logits(prompts, log))
    report("reference", meter, since, t0, 0)


def four_chips(cfg, reduced: bool, meter: CompileMeter) -> None:
    prompts = serve.make_prompts(16, cfg.vocab_size, lo=16, hi=257, seed=11)
    params = serve.make_params(cfg, serve_args(reduced))
    devices = jax.devices()
    streams = {}
    for n in (4, 1):
        since, t0 = meter.reading(), time.perf_counter()
        client = serve.build_client(
            serve_args(reduced, "--replicas", str(n), "--route",
                       "least_loaded"), cfg, params)
        for i, core in enumerate(client.router.cores):
            held = {d for leaf in jax.tree.leaves(core.cache)
                    for d in leaf.devices()}
            print(f"replicas={n}: replica {i} cache on "
                  f"{sorted(str(d) for d in held)}", flush=True)
            if held != {devices[i]}:
                raise AssertionError(f"replica {i}'s cache is on {held}, "
                                     f"not {devices[i]}")
        streams[n] = drive(client, prompts, first=len(prompts))
        report(f"{n} replica(s)", meter, since, t0,
               sum(s.tokens_out for s in client.router.stats))
    compare_streams("4 replicas vs 1", streams[1], streams[4])


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=[1, 4],
                    help="4 runs only the 4-replica router phase")
    ap.add_argument("--cpu", action="store_true",
                    help="rehearse on the CPU backend (tests only)")
    ap.add_argument("--reduced", action="store_true",
                    help="the config's smoke-test widths (tests only)")
    args = ap.parse_args(argv)
    enable_compile_cache()
    info = device_info(args.cpu, args.chips)
    cfg = get_arch(ARCH)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"config {cfg.name}: {cfg.n_layers}L d_model={cfg.d_model} "
          f"heads={cfg.n_heads}/{cfg.n_kv_heads} vocab={cfg.vocab_size}",
          flush=True)
    meter = CompileMeter()
    if args.chips == 4:
        four_chips(cfg, args.reduced, meter)
    else:
        one_chip(cfg, args.reduced, meter)
    print(json.dumps({"ok": True, "device": info}), flush=True)


if __name__ == "__main__":
    main()
