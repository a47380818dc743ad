"""EngineCore: the per-replica synchronous serving loop.

This is the bottom layer of the three-tier serving API::

    ServingClient (serving/client.py)   user-facing handles + global ids
        │ submit / stream / abort
    Router        (serving/router.py)   N replicas, routing policy,
        │                               cross-replica slot migration
    EngineCore    (this module)         ONE replica: slots, paged/tiered KV,
                                        chunked prefill, scheduler calls

The core owns a fixed-size slot table (the batch).  Requests enter a
queue, claim free slots, prefill (in one shot or in chunks), and decode
step-by-step; finished slots free immediately.  WHICH queued request claims
a slot, WHICH slot gives up its pages under pool pressure, and HOW MANY
prompt tokens a slot may prefill per step are policy decisions delegated to
a :class:`repro.serving.scheduler.Scheduler` (FCFS / priority / SJF / DRR /
EDF; ``scheduler=`` in the constructor).  The core enforces feasibility —
free slots, free pages, exhaust policy — the scheduler decides order.

Command surface (what the router drives — deliberately narrow, so a future
cross-host deployment can put it behind an RPC boundary):

* ``add_request(req)`` — enqueue; ``abort_request(rid)`` — cancel queued or
  running, emitting exactly one terminal ``finish_reason="aborted"`` event.
* ``step() -> list[RequestOutput]`` — one admit+decode round; returns the
  events it produced.  (The legacy bool-returning loop survives as
  ``_advance`` / the ``ServingEngine`` shim in ``serving/engine.py``.)
* ``snapshot_slot(rid) -> SlotSnapshot`` / ``inject_slot(snap)`` — drain a
  slot's entire serving state (request, KV page payloads, SSM checkpoint,
  sampler cursor) into host arrays and resume it on ANOTHER core,
  bit-identical.  This packages the existing tiered-KV seam
  (``swap_out_pages`` / ``swap_in_pages`` / ``checkpoint_slot_state``) into
  the wire format a cross-replica — and eventually cross-host — slot move
  ships.
* load introspection for routing: ``free_pages`` / ``queue_depth`` /
  ``n_active`` / ``n_free_slots`` / ``has_work`` / ``page_starved`` /
  ``migration_candidate()``.

Two admission modes:

* ``continuous`` (default where the family supports it) — the paged per-slot
  KV cache (block table into a shared page pool + per-slot length vector)
  lets a new request prefill into ANY free slot while the other slots keep
  decoding: single-slot prefill-into-cache, per-slot masked decode
  attention, page free on completion.  Covers dense/vlm/moe (full K/V
  pages), mla_moe (compressed ckv+krope pages), and hybrid (shared-attn KV
  pages + a slot-indexed Mamba state pool whose lanes are masked by
  ``active`` and checkpointed/restored across preempt-resume).
* ``wave`` — the legacy shared-cursor cache: one length cursor for the whole
  batch, so new requests only start when the batch drains.  Kept for the
  pure-SSM and encoder-decoder families and as the benchmark baseline.

Chunked prefill (``scheduler.chunk_tokens``): a prompt longer than the
policy's per-step budget is admitted into a slot and prefilled in
fixed-budget chunks, one chunk per engine step, interleaved with the decode
steps of the other slots — a long prompt never stalls active decode.  The
chunk math reads every key from the gathered block row exactly as decode
does, so logits are bit-identical to one-shot prefill regardless of the
chunk schedule (``models.model.prefill_chunk_into_slot``; pinned by
tests/test_chunked_prefill.py).

Streaming output contract: every emitted token appends a
:class:`RequestOutput` event (token id, per-request progress, finish reason
and scheduler stats on the final event).  Consume ``step()``'s return, or
``for out in core.stream(): ...``, or drain explicitly via
``drain_outputs()``; ``run()`` still returns aggregate ``EngineStats``.
Per-request sampling honors ``Request.sampling``
(:class:`repro.serving.scheduler.SamplingParams`): temperature / top-k /
top-p rows are sampled in one vectorized call with seed-pinned keys
(``fold_in(PRNGKey(seed), output_index)``), greedy rows stay bit-identical
to the historical global-greedy path.  Seed-pinning is also what makes a
migrated slot's stochastic continuation bit-identical: the key depends only
on (seed, output index), never on which replica or slot samples it.

Tiered KV (``kv_tier="flash"``): the hot page pool may be sized BELOW total
demand (``num_pages``); when it runs out the core preempts-by-eviction —
it suspends a victim slot (chosen by ``scheduler.victim``), spills its LRU
pages to the simulated NAND flash tier (host blobs standing in for the
dies), and prefetches them back through the Slice Control channel bubbles
before the slot's next decode step.  Spill and prefetch ride
``models.model.swap_out_pages`` / ``swap_in_pages``; the block table is
remapped to whatever hot pids the pages come back on, so decode math stays
bit-identical to the all-resident run.  The simulated bubble-bandwidth cost
of that traffic is priced by ``sim.llm_perf`` (``kv_swap_overhead_s``) from
the ``kv_spill_bytes`` / ``kv_prefetch_bytes`` counters below.

Pool-exhaustion policy without a flash tier (``exhaust_policy``):
``"requeue"`` (default) puts the starved request back in the queue (a
mid-decode slot restarts later with its generated prefix folded into the
prompt — deterministic continuation: greedy and seed-pinned sampling both
regenerate the same tokens, though near-tie argmaxes can flip where prefill
and decode numerics differ; only the flash tier preserves exact logits);
``"reject"`` fails it, the capacity-constrained baseline the tiered
benchmark compares against.  Both count ``EngineStats.pool_exhausted``
instead of crashing the engine loop.

Prefix caching (``prefix_cache=True``): finished requests leave their
PREFILL-written KV pages behind in a content-addressed
:class:`repro.serving.kv_cache.PrefixIndex` (sha256 rolling hash over
page-aligned token spans), refcounted at the allocator.  Admission matches
the longest cached prefix: an exact-prompt hit replays a stored "resume
point" — shared full pages mapped into the block table (incref), a private
copy-on-write copy of the partial tail page, the prefill's final logits
(sampling replays from the stored bits) and, for hybrid, the post-prefill
SSM checkpoint — admitting with ZERO prefill dispatches; a partial hit
(families with chunked prefill) shares the cached pages and prefills only
the uncached suffix through the chunk path, whose any-schedule bit-identity
contract makes warm output bit-identical to a cold run.  Decode-written
pages are never registered (prefill/decode numerics may differ off the
flash tier — see the requeue caveat above — and registering them would
poison the bit-identity oracle), which also means every write frontier sits
strictly beyond the shared region: the ``_ensure_pages`` COW guard exists
for safety, not for a hot path.  Idle (refcount-0) cached pages are
reclaimed LRU under pool pressure, or — under ``kv_tier="flash"`` — spilled
to the cold tier under ``("px", chain_key)`` and prefetched back on the
next hit.  Migration snapshots carry each shared page's chain key so inject
re-shares against the target's index (or re-registers the carried payload).

Overlapped decode (``overlap=True``): the synchronous loop pays two jitted
dispatches and one host sync per decode step (decode, then sample, then
``np.asarray`` on the tokens).  The overlapped loop fuses decode + per-
request sampling into ONE jitted step whose sampled tokens stay on device
and chain straight into the next dispatch (``where(use_dev, tok_dev,
tok_host)``), and reads tokens back one step LATE: step N+1 is dispatched
before step N's tokens are read, so the readback overlaps the compute.
Consequences, all bounded by the single in-flight step: finishes are
detected at the lagged drain (length/capacity are host-predicted one step
ahead and masked out of the next dispatch; an eos'd slot runs one
speculative step whose writes stay behind the lens mask and whose token a
slot-epoch check discards); host mirrors (``slot_len`` / ``last_np`` /
``out_tokens``) trail by the undrained token, so scheduler views are one
step stale; ``snapshot_slot`` drains first so the migration wire format
stays fully materialized.  Token streams are bit-identical to the
synchronous loop for every paged family, greedy and seed-pinned stochastic
(tests/test_overlap.py); incompatible with ``watchdog`` (no retained
pre-step cache to replay).

Fault hooks: per-step heartbeat timestamps; a pluggable ``watchdog`` sees
(step, wall_time) and may trigger re-dispatch — tests inject artificial
stragglers through it.  Re-dispatch replays the step from the retained
pre-step cache, so it is idempotent.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Callable, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import model as model_lib
from repro.serving import sampler, spans
from repro import _sanitize
from repro.serving.kv_cache import (OutOfPages, PageAllocator, PrefixIndex,
                                    ResumeEntry, TieredPageAllocator,
                                    pages_needed, prefill_bucket)
from repro.serving.scheduler import (SamplingParams, Scheduler, SlotView,
                                     make_scheduler)


@dataclasses.dataclass(eq=False)
class Request:
    rid: int
    prompt: list[int]
    max_new_tokens: int = 32
    priority: int = 0          # higher wins under priority/DRR policies
    # trace arrival time (any monotone clock, 0.0 is a valid instant);
    # None -> the engine stamps time.monotonic() at submit
    arrival_s: Optional[float] = None
    # SLO budget in seconds (None = no deadline).  The EDF policy orders
    # admission by arrival_s + deadline_s (relative comparisons, so any
    # shared arrival clock works); ``deadline_missed`` measures the budget
    # from SUBMISSION — identical to from-arrival in live serving (the
    # engine stamps arrival_s at submit) and in trace replay that submits
    # at arrival instants (``bench_serving.drive``)
    deadline_s: Optional[float] = None
    # session id for router affinity (requests of one conversation land on
    # the replica that already holds its context)
    session: Optional[str] = None
    sampling: Optional[SamplingParams] = None  # None -> greedy
    temperature: float = 0.0   # legacy alias, folded into ``sampling``
    out_tokens: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    rejected: bool = False  # failed admission under exhaust_policy="reject"
    # eos | length | capacity | rejected | aborted
    finish_reason: Optional[str] = None
    n_folded: int = 0  # out_tokens already folded into prompt by restarts
    # per-request scheduler stats, surfaced on the final RequestOutput
    n_chunks: int = 0      # chunked-prefill passes run for this request
    n_preempted: int = 0   # restarts + tiered suspensions suffered
    n_migrated: int = 0    # cross-replica slot moves suffered
    # lifecycle timestamps (time.monotonic), filled by the engine
    t_submit: float = 0.0
    t_admit: float = 0.0
    t_first_token: float = 0.0
    t_done: float = 0.0
    # when the first token's event left the engine (``drain_outputs``)
    t_first_out: float = 0.0

    def __post_init__(self):
        if self.sampling is None:
            self.sampling = SamplingParams(temperature=self.temperature)

    @property
    def admission_wait_s(self) -> float:
        return self.t_admit - self.t_submit

    @property
    def ttft_s(self) -> float:
        return self.t_first_token - self.t_submit

    @property
    def latency_s(self) -> float:
        return self.t_done - self.t_submit

    @property
    def deadline_missed(self) -> bool:
        """True when the request finished more than ``deadline_s`` seconds
        after submission (False without a deadline or before completion).
        See ``deadline_s`` for the submission-vs-arrival clock contract."""
        return (self.deadline_s is not None and self.t_done > 0.0
                and self.latency_s > self.deadline_s)


@dataclasses.dataclass(frozen=True)
class RequestOutput:
    """One streamed event of a request's lifetime.

    Token events carry the freshly sampled ``token`` (``n_out`` is the
    cumulative count including it).  The final event has ``finished=True``
    with the ``finish_reason`` and the request's scheduler stats; a
    rejected or aborted request emits exactly one final event with
    ``token=None``.
    """

    rid: int
    token: Optional[int]
    n_out: int
    finished: bool = False
    finish_reason: Optional[str] = None
    # populated on the final event only
    ttft_s: Optional[float] = None
    latency_s: Optional[float] = None
    sched: Optional[dict] = None   # {"chunks", "preemptions", "wait_s"}


@dataclasses.dataclass
class SlotSnapshot:
    """One slot's entire serving state as host arrays — the migration wire
    format.

    ``pages[j]`` is the payload tuple of the slot's j-th allocated page
    exactly as ``swap_out_pages`` gathers it — one numpy array per
    ``paged_pool_keys`` component: ``(k, v)`` for bf16 pools (for MLA the
    compressed ``(ckv, krope)`` rows), and ``(k, v, k_scale, v_scale)``
    under ``kv_dtype="int8"``; ``ssm`` is the
    ``checkpoint_slot_state`` snapshot for families with per-slot recurrent
    state.  Everything here is numpy / plain python — serializing this
    struct across a socket IS the future cross-host slot move; no device
    state leaks into it.
    """

    req: Request
    slot_len: int          # valid cache length (prefill_pos mid-prefill)
    last_token: int        # next decode step's input token
    prefilling: bool       # still mid chunked-prefill
    prefill_pos: int
    pages: list            # [tuple of numpy arrays] per page (pool order)
    ssm: object            # checkpoint_slot_state payload (None if none)
    page_size: int
    family: str
    # prefix-cache chain keys of the slot's SHARED pages ({page_idx: key},
    # None when the source engine has no prefix cache): inject re-shares
    # against the target's index when it already holds the key, or registers
    # the carried payload — new fields go at the end, defaulted, so older
    # snapshots keep deserializing
    prefix_keys: Optional[dict] = None

    @property
    def n_pages(self) -> int:
        return len(self.pages)

    def to_bytes(self) -> bytes:
        """Standalone byte format (versioned header carrying the geometry
        — family, page_size, page dtype — then the encoded fields); the
        fleet transport and the failover checkpoints both speak it."""
        from repro.serving.fleet import wire
        return wire.snapshot_to_bytes(self)

    @classmethod
    def from_bytes(cls, data: bytes, expect_family: str = None,
                   expect_page_size: int = None,
                   expect_dtype: str = None) -> "SlotSnapshot":
        """Inverse of :meth:`to_bytes`.  ``expect_*`` is the geometry
        guard: a receiver that knows its own family / page_size / page
        dtype gets a ``ValueError`` on mismatch before the body decodes."""
        from repro.serving.fleet import wire
        return wire.snapshot_from_bytes(
            data, expect_family=expect_family,
            expect_page_size=expect_page_size, expect_dtype=expect_dtype)


def _batch_extras(cfg: ModelConfig, batch: int) -> dict:
    if cfg.family == "vlm":
        return {"vision_embeds": jnp.zeros(
            (batch, cfg.n_vision_tokens, cfg.d_model), jnp.bfloat16)}
    if cfg.family == "audio":
        return {"frames": jnp.zeros(
            (batch, cfg.encoder_seq, cfg.d_model), jnp.bfloat16)}
    return {}


# jitted step functions are shared per-config (ModelConfig is frozen and
# hashable) so every replica of a router — and rebuilt engines, e.g. the
# wave-vs-continuous benchmark — reuses compile caches instead of retracing.
# Each wraps a NAMED function: its XLA module is ``jit_<name>``, the name the
# device trace attributes device time by, whatever the code around it is
# called (tests/test_tracing.py pins every name)
@functools.lru_cache(maxsize=None)
def _jit_decode(cfg: ModelConfig):
    def decode(p, t, c):
        return model_lib.decode_step(p, cfg, t, c)
    return jax.jit(decode)


@functools.lru_cache(maxsize=None)
def _jit_decode_paged(cfg: ModelConfig):
    def decode_paged(p, t, c, a):
        return model_lib.decode_step_paged(p, cfg, t, c, a)
    return jax.jit(decode_paged)


@functools.lru_cache(maxsize=None)
def _jit_prefill_slots(cfg: ModelConfig):
    def prefill_slots(p, toks, tls, c, ss):
        return model_lib.prefill_into_slots(
            p, cfg, toks, tls, c, ss, _batch_extras(cfg, toks.shape[0]))
    return jax.jit(prefill_slots)


@functools.lru_cache(maxsize=None)
def _jit_prefill_chunk(cfg: ModelConfig):
    # one trace per chunk-length bucket (power-of-two, floor = page size):
    # start/chunk_len/slot are traced scalars, so the trace count stays
    # O(log max_seq) while per-chunk compute scales with the budget
    def prefill_chunk(p, toks, start, clen, c, slot):
        return model_lib.prefill_chunk_into_slot(p, cfg, toks, start, clen,
                                                 c, slot)
    return jax.jit(prefill_chunk)


@functools.lru_cache(maxsize=None)
def _jit_prefill(cfg: ModelConfig):
    def prefill(p, toks, c, batch):
        return model_lib.prefill(p, cfg, toks, c, _batch_extras(cfg, batch))
    return jax.jit(prefill, static_argnames=("batch",))


def sample(logits, seeds, counts, temps, topk, topp):
    """The synchronous loop's sampler program (``sampler.sample_batch``)."""
    return sampler.sample_batch(logits, seeds, counts, temps, topk, topp)


# swap ops retrace per page-id bucket (power-of-two padded with the null
# page), so the trace count stays O(log pool) like the prefill buckets
_jit_swap_out = jax.jit(model_lib.swap_out_pages)
_jit_swap_in = jax.jit(model_lib.swap_in_pages)
_jit_sample = jax.jit(sample)


# the overlapped loop's fused decode+sample step: ONE jitted dispatch per
# decode step instead of decode followed by a separate sample dispatch.
# ``greedy_only`` is static (all-greedy batches trace a bare argmax); the
# cache buffers are donated to XLA for in-place reuse on every backend, so
# the CPU tests exercise the same buffer lifetimes as the chip
@functools.lru_cache(maxsize=None)
def _jit_decode_sample_paged(cfg: ModelConfig):
    def decode_sample_paged(p, tok_host, tok_dev, use_dev, c, a, seeds,
                            counts, temps, topk, topp, greedy_only):
        return model_lib.decode_and_sample_paged(
            p, cfg, tok_host, tok_dev, use_dev, c, a,
            lambda lg: sampler.fused_sample(
                lg, seeds, counts, temps, topk, topp,
                greedy_only=greedy_only))
    return jax.jit(decode_sample_paged, static_argnames=("greedy_only",),
                   donate_argnums=(4,))


@functools.lru_cache(maxsize=None)
def _jit_decode_sample(cfg: ModelConfig):
    def decode_sample(p, tok_host, tok_dev, use_dev, c, seeds, counts, temps,
                      topk, topp, greedy_only):
        return model_lib.decode_and_sample(
            p, cfg, tok_host, tok_dev, use_dev, c,
            lambda lg: sampler.fused_sample(
                lg, seeds, counts, temps, topk, topp,
                greedy_only=greedy_only))
    return jax.jit(decode_sample, static_argnames=("greedy_only",),
                   donate_argnums=(4,))


class _LazyPagePayload:
    """A spilled page's payload still on its way to the host — one array
    per ``paged_pool_keys`` component ((k, v) for bf16 pools, (k, v,
    k_scale, v_scale) under kv_dtype="int8").

    ``copy_to_host_async`` starts the device→host DMA at spill time; the
    numpy materialization happens only when the payload is actually needed
    (prefetch scatter or migration snapshot), so the spill itself never
    blocks the engine loop on a device sync.
    """

    __slots__ = ("arrays",)

    def __init__(self, *arrays):
        self.arrays = arrays
        for a in arrays:
            a.copy_to_host_async()

    def materialize(self) -> tuple[np.ndarray, ...]:
        return tuple(np.asarray(a) for a in self.arrays)


def _payload_np(payload) -> tuple[np.ndarray, ...]:
    if isinstance(payload, _LazyPagePayload):
        return payload.materialize()
    return payload


@dataclasses.dataclass
class EngineStats:
    prefills: int = 0
    prefill_chunks: int = 0    # chunked-prefill passes (chunk granularity)
    decode_steps: int = 0
    # jitted dispatches attributable to decoding (decode + sample in the
    # synchronous loop = 2 per step; the overlapped fused step = 1).
    # ``decode_dispatches / decode_steps`` is the dispatches-per-decoded-
    # token figure the overlap benchmark reports.
    decode_dispatches: int = 0
    tokens_out: int = 0
    straggler_events: int = 0
    admitted: int = 0
    completed: int = 0
    mode: str = ""
    policy: str = ""
    # pool pressure / tiered KV accounting
    pool_exhausted: int = 0    # OutOfPages events absorbed (requeue/reject)
    rejected: int = 0
    aborted: int = 0           # abort_request() cancellations
    preemptions: int = 0       # slots suspended (tiered) or restarted
    resumes: int = 0           # suspended slots brought back hot
    migrated_out: int = 0      # slots drained via snapshot_slot
    migrated_in: int = 0       # slots resumed via inject_slot
    kv_spill_pages: int = 0
    kv_prefetch_pages: int = 0
    kv_spill_bytes: float = 0.0
    kv_prefetch_bytes: float = 0.0
    # prefix-cache accounting
    prefix_lookups: int = 0    # admissions that consulted the index
    prefix_hits: int = 0       # admissions served any cached prefix
    prefix_hit_pages: int = 0  # shared pages mapped instead of re-prefilled
    prefix_tokens_reused: int = 0  # prompt tokens whose prefill was skipped
    cow_copies: int = 0        # private copies made of (tail) shared pages
    # fleet health / failover accounting (populated by the FleetRouter's
    # fleet-level stats object; always 0 on a single in-process engine)
    workers_lost: int = 0      # workers declared dead (SIGKILL, hang, EOF)
    failovers: int = 0         # failover passes run (one per lost worker)
    requests_replayed: int = 0  # requests re-dispatched by failover
    tokens_replayed: int = 0   # re-decoded tokens suppressed as duplicates
    heartbeat_misses: int = 0  # reply deadlines blown (straggle signal)
    # prefill padding: prompt positions prefilled, and positions the
    # prefill programs computed (batched: rows x bucket, padding rows and
    # positions included; chunked: the chunk's bucket); the pad is the
    # difference
    prefill_tokens: int = 0
    prefill_computed_tokens: int = 0
    # paged decode KV reads: per decode step, the pages holding each active
    # slot's attended positions (``ceil(len / page)``, the new token
    # included), and the block table's size (slots x pages per slot) that a
    # read of every slot's full row would touch
    decode_kv_live_pages: int = 0
    decode_kv_table_pages: int = 0
    # per-request latency samples, appended at completion
    admission_wait_s: list = dataclasses.field(default_factory=list)
    ttft_s: list = dataclasses.field(default_factory=list)
    latency_s: list = dataclasses.field(default_factory=list)
    # per request, first token sampled -> its event drained from the engine
    # (``Request.t_first_out - t_first_token``), appended at the drain
    first_token_hold_s: list = dataclasses.field(default_factory=list)

    def percentiles(self, series: str = "latency_s",
                    qs: tuple = (50, 90, 99)) -> dict:
        """Per-request latency percentiles, e.g. ``percentiles("ttft_s")``."""
        xs = getattr(self, series)
        return {f"p{q}": float(np.percentile(xs, q)) if xs else 0.0
                for q in qs}

    def summary(self) -> str:
        lat = self.percentiles("latency_s")
        adm = self.percentiles("admission_wait_s")
        s = (f"[{self.mode}] policy={self.policy or 'fcfs'} "
             f"requests={self.completed} "
             f"tokens={self.tokens_out} steps={self.decode_steps} "
             f"latency p50/p90/p99="
             f"{lat['p50']:.3f}/{lat['p90']:.3f}/{lat['p99']:.3f}s "
             f"admission p50/p99={adm['p50']:.3f}/{adm['p99']:.3f}s")
        if self.prefill_chunks:
            s += f" prefill_chunks={self.prefill_chunks}"
        if self.prefill_computed_tokens:
            pad = 1 - self.prefill_tokens / self.prefill_computed_tokens
            s += (f" prefill tokens={self.prefill_tokens} "
                  f"computed={self.prefill_computed_tokens} pad={pad:.1%}")
        if self.decode_kv_table_pages:
            live = self.decode_kv_live_pages / self.decode_kv_table_pages
            s += (f" kv pages live={self.decode_kv_live_pages}"
                  f"/{self.decode_kv_table_pages} ({live:.1%})")
        if self.first_token_hold_s:
            hold = self.percentiles("first_token_hold_s", (50, 99))
            s += (f" first-token hold p50/p99="
                  f"{hold['p50']:.3f}/{hold['p99']:.3f}s")
        if self.kv_spill_pages or self.pool_exhausted or self.rejected:
            s += (f" pool_exhausted={self.pool_exhausted} "
                  f"rejected={self.rejected} preempt={self.preemptions} "
                  f"spill/prefetch pages={self.kv_spill_pages}"
                  f"/{self.kv_prefetch_pages}")
        if self.migrated_out or self.migrated_in:
            s += (f" migrated out/in={self.migrated_out}"
                  f"/{self.migrated_in}")
        if self.prefix_lookups:
            s += (f" prefix hits={self.prefix_hits}/{self.prefix_lookups}"
                  f" pages={self.prefix_hit_pages}"
                  f" tokens={self.prefix_tokens_reused}"
                  f" cow={self.cow_copies}")
        if self.workers_lost or self.failovers or self.heartbeat_misses:
            s += (f" workers_lost={self.workers_lost} "
                  f"failovers={self.failovers} replayed "
                  f"req/tok={self.requests_replayed}/{self.tokens_replayed} "
                  f"heartbeat_misses={self.heartbeat_misses}")
        return s


class EngineCore:
    """Single-replica engine over the functional model API.

    For the multi-chip case the jitted step functions are the pjit'd ones
    from launch/dryrun.build_step; here the defaults run on local devices.
    Multi-replica serving stacks a :class:`repro.serving.router.Router`
    over N of these.
    """

    def __init__(self, cfg: ModelConfig, params, max_batch: int = 4,
                 max_seq: int = 512, eos_id: int = 2,
                 watchdog: Optional[Callable[[int, float], bool]] = None,
                 straggler_timeout_s: float = 5.0, mode: str = "auto",
                 page_size: int = 16, num_pages: Optional[int] = None,
                 kv_tier: str = "none", exhaust_policy: str = "requeue",
                 flash_pages: Optional[int] = None,
                 scheduler: "Scheduler | str | None" = None,
                 overlap: bool = False, prefix_cache: bool = False,
                 kv_dtype: str = "bf16"):
        if overlap and watchdog is not None:
            raise ValueError(
                "overlap=True keeps one decode step in flight past the host "
                "readback, so the watchdog's replay-from-pre-step-cache "
                "re-dispatch contract cannot hold; use the synchronous loop "
                "with a watchdog")
        if mode == "auto":
            mode = ("continuous" if model_lib.supports_paged(cfg) else "wave")
        if mode == "continuous" and not model_lib.supports_paged(cfg):
            raise ValueError(
                f"continuous mode needs a paged KV cache; family "
                f"{cfg.family!r} has recurrent state tied to the shared "
                f"cursor — use mode='wave'")
        if kv_tier not in ("none", "flash"):
            raise ValueError(f"kv_tier {kv_tier!r} not in ('none', 'flash')")
        if kv_dtype not in ("bf16", "int8"):
            raise ValueError(f"kv_dtype {kv_dtype!r} not in ('bf16', 'int8')")
        if kv_dtype == "int8" and mode != "continuous":
            raise ValueError("kv_dtype='int8' needs mode='continuous' (only "
                             "the paged pools quantize per page row)")
        if exhaust_policy not in ("requeue", "reject"):
            raise ValueError(f"exhaust_policy {exhaust_policy!r}")
        if kv_tier == "flash" and mode != "continuous":
            raise ValueError("kv_tier='flash' needs mode='continuous'")
        if prefix_cache and mode != "continuous":
            raise ValueError(
                "prefix_cache=True needs mode='continuous' (the wave cache "
                "has no page pool to share)")
        self.cfg = cfg
        self.params = params
        # a replica runs on the device that holds its params (Router.build
        # spreads replicas over the local devices); its cache lives there too
        self.device = next(iter(jax.tree.leaves(params)[0].devices()))
        self.max_batch = max_batch
        self.max_seq = max_seq
        self.eos_id = eos_id
        self.watchdog = watchdog
        self.straggler_timeout_s = straggler_timeout_s
        self.mode = mode
        self.overlap = overlap
        # overlapped-loop state: at most ONE dispatched-but-undrained fused
        # step; per-slot in-flight token counts (0 or 1) and release epochs
        # that invalidate pending rows whose slot was reassigned in between
        self._pending: Optional[dict] = None
        self._inflight: list[int] = [0] * max_batch
        self._slot_epoch: list[int] = [0] * max_batch
        self.kv_tier = kv_tier
        self.kv_dtype = kv_dtype
        self.exhaust_policy = exhaust_policy
        self.scheduler = make_scheduler(scheduler)
        self.stats = EngineStats(mode=mode, policy=self.scheduler.name)
        self.queue: list[Request] = []
        self.slots: list[Optional[Request]] = [None] * max_batch
        self._events: list[RequestOutput] = []
        # requests whose first token's event awaits the next drain
        self._first_out: list[Request] = []
        self._chunk_ok = (mode == "continuous"
                          and model_lib.supports_chunked_prefill(cfg))
        self._px: Optional[PrefixIndex] = None  # set in the continuous branch
        if mode == "continuous":
            self.page_size = page_size
            self.pages_per_slot = pages_needed(max_seq, page_size)
            full_pool = max_batch * self.pages_per_slot + 1
            self.num_pages = full_pool if num_pages is None else num_pages
            self.cache = jax.device_put(model_lib.init_paged_cache(
                cfg, max_batch, max_seq, page_size=page_size,
                num_pages=self.num_pages, kv_dtype=kv_dtype), self.device)
            self.kv_page_bytes = model_lib.kv_page_bytes(
                cfg, page_size, model_lib.paged_pool_dtype(self.cache))
            # hybrid: per-slot Mamba state checkpoints, filled on suspend
            self._has_state = model_lib.has_slot_state(cfg)
            self._ssm_ckpt: dict[int, object] = {}
            # hot-loop bookkeeping lives host-side in numpy (block table,
            # last tokens, active mask): mutating them costs nothing and they
            # ride into each jitted call as inputs, so the only per-step
            # device work is the decode step itself
            self.block = np.zeros((max_batch, self.pages_per_slot), np.int32)
            del self.cache["block"]
            self.last_np = np.zeros((max_batch,), np.int32)
            if kv_tier == "flash":
                self.allocator = TieredPageAllocator(self.num_pages,
                                                     flash_pages)
            else:
                self.allocator = PageAllocator(self.num_pages)
            # prefix cache: the content-addressed page index plus, per slot,
            # {page_idx: chain key} of the pages it maps from the index
            self._px = PrefixIndex(page_size) if prefix_cache else None
            self.slot_shared: list[dict[int, bytes]] = [
                {} for _ in range(max_batch)]
            self._px_pin: set[bytes] = set()  # keys mid-acquire (shed shield)
            # per-slot page lists mirror the block table; a 0 entry marks a
            # page currently cold (spilled to the flash tier)
            self.slot_pages: list[list[int]] = [[] for _ in range(max_batch)]
            self.slot_len: list[int] = [0] * max_batch  # host mirror of lens
            self.suspended: list[bool] = [False] * max_batch
            self.resume_order: list[int] = []  # FIFO of suspended slots
            self._resumed_now: set[int] = set()
            self._idle_steps = 0  # consecutive steps with nothing decodable
            # chunked-prefill state: a slot with prefilling=True holds a
            # request whose prompt is only prefilled up to prefill_pos
            self.prefilling: list[bool] = [False] * max_batch
            self.prefill_pos: list[int] = [0] * max_batch
            self._decode = _jit_decode_paged(cfg)
            self._prefill_slots = _jit_prefill_slots(cfg)
            self._prefill_chunk = (_jit_prefill_chunk(cfg)
                                   if self._chunk_ok else None)
        else:
            self.cache = jax.device_put(
                model_lib.init_cache(cfg, max_batch, max_seq), self.device)
            self.last_token = jnp.zeros((max_batch,), jnp.int32)
            self._wave_last_np = np.zeros((max_batch,), np.int32)
            self._wave_len = 0  # host prediction of cache["len"]
            self._decode = _jit_decode(cfg)
        if overlap:
            self._decode_sample = (
                _jit_decode_sample_paged(cfg)
                if mode == "continuous" else _jit_decode_sample(cfg))
        self._san = _sanitize.load()  # None unless REPRO_SANITIZE=1

    # ------------------------------------------------------------------
    # command surface: add / abort
    # ------------------------------------------------------------------
    def add_request(self, req: Request) -> None:
        if self._cache_len0(req) >= self.max_seq:
            raise ValueError(f"prompt ({len(req.prompt)}) does not fit "
                             f"max_seq ({self.max_seq})")
        if self.mode == "continuous":
            # the whole-lifetime page demand of ONE request must fit the hot
            # pool, or pool-exhaustion recovery (requeue / suspend+resume)
            # could never make progress on it
            worst = min(self.max_seq,
                        self._cache_len0(req) + req.max_new_tokens)
            if pages_needed(worst, self.page_size) > self.num_pages - 1:
                raise ValueError(
                    f"request needs up to {pages_needed(worst, self.page_size)}"
                    f" pages, hot pool has {self.num_pages - 1}")
        req.t_submit = time.monotonic()
        if req.arrival_s is None:
            req.arrival_s = req.t_submit
        self.queue.append(req)

    # the historical name; Router and new code use add_request
    submit = add_request

    def abort_request(self, rid: int) -> bool:
        """Cancel a queued or running request: frees its slot/pages and
        emits exactly one terminal event with ``finish_reason="aborted"``.
        Returns False when ``rid`` is not queued or active here."""
        for req in self.queue:
            if req.rid == rid:
                self.queue.remove(req)
                self._abort(req)
                return True
        for i, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                if self.mode == "continuous":
                    self._release_slot(i)
                else:
                    self.slots[i] = None
                self._abort(req)
                return True
        return False

    def _abort(self, req: Request) -> None:
        req.done = True
        req.finish_reason = "aborted"
        req.t_done = time.monotonic()
        self.stats.aborted += 1
        self._emit(req, None, finished=True)

    def _cache_len0(self, req: Request) -> int:
        """Valid cache length right after prefill (vision tokens included)."""
        extra = (self.cfg.n_vision_tokens if self.cfg.family == "vlm" else 0)
        return len(req.prompt) + extra

    # ------------------------------------------------------------------
    # command surface: load introspection (what the router routes on)
    # ------------------------------------------------------------------
    @property
    def has_work(self) -> bool:
        return bool(self.queue) or any(s is not None for s in self.slots)

    @property
    def queue_depth(self) -> int:
        return len(self.queue)

    @property
    def n_active(self) -> int:
        return sum(1 for s in self.slots if s is not None)

    @property
    def n_free_slots(self) -> int:
        return sum(1 for s in self.slots if s is None)

    @property
    def free_pages(self) -> int:
        """Hot pages currently allocatable (slot-count bound in wave mode,
        where there is no page pool)."""
        if self.mode != "continuous":
            return self.n_free_slots
        return self.allocator.available

    @property
    def page_starved(self) -> bool:
        """True when this replica cannot make progress on everything it
        holds: a suspended slot is waiting for pages to come back, or
        NOTHING in the queue can claim a slot + its prefill pages (checked
        against every queued request, not just the head — admission order
        is the scheduler's, so if ANY entry fits the policy can still make
        progress).  The router uses this as the migration trigger."""
        if self.mode != "continuous":
            return False
        if any(self.suspended):
            return True
        if not self.queue:
            return False
        if self.n_free_slots == 0:
            return True
        need = min(pages_needed(self._cache_len0(r), self.page_size)
                   for r in self.queue)
        return need > self.allocator.available + self._px_reclaimable

    def migration_candidate(self) -> Optional[tuple[int, int]]:
        """``(rid, n_pages)`` of the slot this replica would rather hand to
        a peer, or None.  Suspended slots first (they are already preempted
        — moving one relieves pool pressure AND resumes it sooner); with a
        backlogged queue and no free slot, the scheduler's ``victim`` seam
        picks among active slots — deliberately the same policy decision as
        local pool-pressure eviction."""
        if self.mode != "continuous":
            return None
        if self.resume_order:
            i = self.resume_order[0]
        elif self.queue and self.n_free_slots == 0:
            views = [self._slot_view(j) for j, r in enumerate(self.slots)
                     if r is not None and not self.suspended[j]]
            if not views:
                return None
            i = self.scheduler.victim(views)
            if self.slots[i] is None:  # defensive: policy returned junk
                return None
        else:
            return None
        return self.slots[i].rid, len(self.slot_pages[i])

    def can_accept(self, n_pages: int) -> bool:
        """Whether ``inject_slot`` of an ``n_pages`` snapshot would succeed
        without evicting anyone local: a free slot plus the pages, with one
        page of growth headroom."""
        return (self.mode == "continuous" and not self.page_starved
                and self.n_free_slots > 0
                and n_pages <= self.pages_per_slot
                and (self.allocator.available + self._px_reclaimable
                     >= n_pages + 1))

    @property
    def _px_reclaimable(self) -> int:
        """Idle (refcount-0) HOT prefix-cache pages — freeable on demand, so
        pool-pressure predicates count them as available."""
        return self._px.n_idle_hot if self._px is not None else 0

    # ------------------------------------------------------------------
    # command surface: snapshot / inject (cross-replica slot migration)
    # ------------------------------------------------------------------
    def snapshot_slot(self, rid: int, release: bool = True) -> SlotSnapshot:
        """Drain request ``rid``'s slot into a :class:`SlotSnapshot` and
        release it locally (the request is NOT finished — it continues
        wherever the snapshot is injected).

        Page payloads come from the same two paths the flash tier uses:
        hot pages through one bucketed ``swap_out_pages`` gather, cold
        pages straight out of the allocator's blob store — so a partially
        spilled (suspended) slot snapshots without prefetching first.

        ``release=False`` is the CHECKPOINT variant (periodic fleet
        failover snapshots): the slot keeps running here and the cold
        store keeps its payloads — the snapshot aliases live state
        (``req``, cold payload arrays), so serialize it before the engine
        steps again.
        """
        if self.mode != "continuous":
            raise ValueError("snapshot_slot needs mode='continuous'")
        # the wire format is fully drained state: an in-flight fused step's
        # token must land in out_tokens / slot_len / last_np before they are
        # copied out, or the migrated run would drop it
        self._drain_pending()
        for i, req in enumerate(self.slots):
            if req is not None and req.rid == rid:
                break
        else:
            raise KeyError(f"request {rid} is not active in any slot")
        n_pages = len(self.slot_pages[i])
        pages: list = [None] * n_pages
        hot = [(j, pid) for j, pid in enumerate(self.slot_pages[i])
               if pid != 0]
        if hot:
            payloads = self._gather_pages([pid for _, pid in hot])
            for (j, _pid), payload in zip(hot, payloads):
                pages[j] = payload
        for j, pid in enumerate(self.slot_pages[i]):
            if pid == 0:  # cold: payload already host-side (or in DMA flight)
                pages[j] = _payload_np(self.allocator.fetch((i, j)) if release
                                       else self.allocator.peek((i, j)))
        snap = SlotSnapshot(
            req=req, slot_len=self.slot_len[i],
            last_token=int(self.last_np[i]),
            prefilling=self.prefilling[i], prefill_pos=self.prefill_pos[i],
            pages=pages,
            ssm=(model_lib.checkpoint_slot_state(self.cache, i)
                 if self._has_state else None),
            page_size=self.page_size, family=self.cfg.family,
            prefix_keys=(dict(self.slot_shared[i]) if self._px is not None
                         else None))
        if release:
            self._release_slot(i)
            req.n_migrated += 1
            self.stats.migrated_out += 1
        return snap

    def inject_slot(self, snap: SlotSnapshot) -> int:
        """Resume a snapshotted request in a free slot here; returns the
        slot index.  Decode continues bit-identical to the unmigrated run:
        the pages scatter onto fresh pids (block-table remap, exactly the
        prefetch path), ``lens`` and the sampler cursor restore from the
        snapshot, and recurrent state comes back via
        ``restore_slot_state``."""
        if self.mode != "continuous":
            raise ValueError("inject_slot needs mode='continuous'")
        if snap.family != self.cfg.family or snap.page_size != self.page_size:
            raise ValueError(
                f"snapshot ({snap.family}, page_size={snap.page_size}) does "
                f"not match replica ({self.cfg.family}, {self.page_size})")
        if snap.n_pages > self.pages_per_slot:
            raise ValueError(f"snapshot holds {snap.n_pages} pages, slots "
                             f"here cap at {self.pages_per_slot}")
        free = [i for i in range(self.max_batch) if self.slots[i] is None]
        if not free:
            raise OutOfPages("no free slot to inject into")
        i = free[0]
        # re-share against the local prefix index where it already holds a
        # carried chain key (equal keys imply bit-identical page contents —
        # both replicas prefilled the same tokens with the same params), and
        # deep-copy the rest; carried keys the index lacks REGISTER the fresh
        # copy, so a slot move spreads the cache instead of privatizing it
        shared_map = (dict(snap.prefix_keys or {})
                      if self._px is not None else {})
        reuse_idx = sorted(j for j, k in shared_map.items()
                           if j < snap.n_pages
                           and self._px.get(k) is not None)
        acquired = self._px_acquire([shared_map[j] for j in reuse_idx])
        fresh_idx = [j for j in range(snap.n_pages) if j not in set(reuse_idx)]
        try:
            fresh = self._alloc_pages(len(fresh_idx))
        except OutOfPages:
            for j in reuse_idx:
                self._px_release_key(shared_map[j])
            raise
        if fresh_idx:
            self._scatter_pages(fresh, [snap.pages[j] for j in fresh_idx])
        pids = [0] * snap.n_pages
        for j, pid in zip(reuse_idx, acquired):
            pids[j] = pid
        for j, pid in zip(fresh_idx, fresh):
            pids[j] = pid
        self.slot_shared[i] = {j: shared_map[j] for j in reuse_idx}
        if self._px is not None:
            for j, key in shared_map.items():
                if (j not in self.slot_shared[i] and j < snap.n_pages
                        and self._px.get(key) is None):
                    self._px.insert(key, pids[j])
                    self.slot_shared[i][j] = key
        self.slot_pages[i] = pids
        self.block[i, :snap.n_pages] = pids
        self.slot_len[i] = snap.slot_len
        self.cache["lens"] = self.cache["lens"].at[i].set(snap.slot_len)
        self.last_np[i] = snap.last_token
        self.prefilling[i] = snap.prefilling
        self.prefill_pos[i] = snap.prefill_pos
        if self._has_state and snap.ssm is not None:
            self.cache = model_lib.restore_slot_state(self.cache, i,
                                                      snap.ssm)
        self.slots[i] = snap.req
        self.stats.migrated_in += 1
        return i

    # ------------------------------------------------------------------
    # streaming output contract
    # ------------------------------------------------------------------
    # undelivered events are bounded: a consumer that never drains (run()/
    # bare step() loops reading Request.out_tokens + EngineStats instead)
    # must not leak one RequestOutput per generated token forever — the
    # oldest events are dropped past this cap.  Streaming consumers drain
    # every step and never get near it.
    MAX_PENDING_EVENTS = 1 << 16

    def _emit(self, req: Request, token: Optional[int],
              finished: bool = False) -> None:
        if len(self._events) >= self.MAX_PENDING_EVENTS:
            # shed the oldest half, but keep its finished=True events: the
            # lifecycle contract (every request gets a terminal event with
            # finish_reason + stats) survives overflow; only token-stream
            # events are droppable
            half = self.MAX_PENDING_EVENTS // 2
            finals = [e for e in self._events[:half] if e.finished]
            self._events = finals + self._events[half:]
        if token is not None and len(req.out_tokens) == 1:
            self._first_out.append(req)  # stamped by drain_outputs
        sched = None
        ttft = lat = None
        if finished:
            sched = {"chunks": req.n_chunks, "preemptions": req.n_preempted,
                     "wait_s": (req.admission_wait_s if req.t_admit
                                else None)}
            ttft = req.ttft_s if req.t_first_token else None
            lat = req.latency_s
        self._events.append(RequestOutput(
            rid=req.rid, token=token, n_out=len(req.out_tokens),
            finished=finished,
            finish_reason=req.finish_reason if finished else None,
            ttft_s=ttft, latency_s=lat, sched=sched))

    def drain_outputs(self) -> list[RequestOutput]:
        """Pop all RequestOutput events accumulated since the last drain.

        The drain is where a request's first token leaves the engine: it
        stamps ``t_first_out`` and records the hold since the token was
        sampled (``EngineStats.first_token_hold_s``)."""
        ev, self._events = self._events, []
        if self._first_out:
            now = time.monotonic()
            for req in self._first_out:
                req.t_first_out = now
                self.stats.first_token_hold_s.append(now - req.t_first_token)
            self._first_out = []
        return ev

    def step(self) -> list[RequestOutput]:
        """One admit + decode round; returns the events it produced.

        This is the router-facing command: the legacy bool ("was there
        work?") survives as ``_advance`` and on the ``ServingEngine``
        shim's ``step``.
        """
        self._advance()
        return self.drain_outputs()

    def stream(self, max_steps: int = 10_000):
        """Run the engine, yielding RequestOutput events as they happen."""
        steps = 0
        while self.has_work and steps < max_steps:
            if not self._advance():
                break
            steps += 1
            yield from self.drain_outputs()
        yield from self.drain_outputs()

    # ------------------------------------------------------------------
    # per-request sampling
    # ------------------------------------------------------------------
    def _sample_rows(self, logits, items: list[tuple[int, Request]]
                     ) -> np.ndarray:
        """Sample one token per (row, request) pair from logits [B, V].

        Rows not named in ``items`` return garbage (callers ignore them).
        All-greedy batches take the historical argmax path unchanged; any
        stochastic row switches the whole batch to the vectorized
        ``sampler.sample_batch`` (greedy rows still argmax inside it).
        """
        with spans.span(spans.SAMPLE):
            tok = self._sample_dispatch(logits, items)
        with spans.span(spans.READBACK):
            return np.asarray(tok)

    def _sample_dispatch(self, logits, items: list[tuple[int, Request]]):
        if all(it[1].sampling.temperature <= 0.0 for it in items):
            return sampler.greedy(logits)
        b = logits.shape[0]
        temps = np.zeros((b,), np.float32)
        seeds = np.zeros((b,), np.int32)
        counts = np.zeros((b,), np.int32)
        topk = np.zeros((b,), np.int32)
        topp = np.ones((b,), np.float32)
        for row, req in items:
            sp = req.sampling
            temps[row] = sp.temperature
            seeds[row] = sp.seed if sp.seed is not None else req.rid
            counts[row] = len(req.out_tokens)
            topk[row] = sp.top_k
            topp[row] = sp.top_p
        return _jit_sample(logits, seeds, counts, temps, topk, topp)

    # ------------------------------------------------------------------
    # scheduler views
    # ------------------------------------------------------------------
    def _slot_view(self, i: int) -> SlotView:
        r = self.slots[i]
        if self.mode == "continuous":
            # a mid-chunked-prefill slot already holds its WHOLE prompt's
            # pages, so victim heuristics keyed on seq_len ("longest frees
            # the most pages") must see the allocated footprint, not the
            # prefill progress
            prefilling = self.prefilling[i]
            seq = self._cache_len0(r) if prefilling else self.slot_len[i]
            suspended = self.suspended[i]
        else:
            seq = len(r.prompt) + len(r.out_tokens)
            prefilling = suspended = False
        return SlotView(index=i, rid=r.rid, priority=r.priority,
                        arrival_s=r.arrival_s, seq_len=seq,
                        n_out=len(r.out_tokens),
                        remaining=r.max_new_tokens - len(r.out_tokens),
                        prefilling=prefilling, suspended=suspended,
                        deadline_s=(r.arrival_s + r.deadline_s
                                    if r.deadline_s is not None else None))

    def _views(self) -> list[Optional[SlotView]]:
        return [self._slot_view(i) if r is not None else None
                for i, r in enumerate(self.slots)]

    # ------------------------------------------------------------------
    # tiered KV: spill / prefetch / suspend / resume
    # ------------------------------------------------------------------
    def _bucket_pids(self, pids: list[int]) -> np.ndarray:
        """Pad a page-id list to a power-of-two bucket with the null page."""
        n = prefill_bucket(len(pids), floor=1)
        return np.asarray(pids + [0] * (n - len(pids)), np.int32)

    def _gather_pages(self, pids: list[int]) -> list[tuple[np.ndarray, ...]]:
        """Gather hot pages as per-page host payload tuples (one array per
        pool component — (k, v), plus scale payloads when int8) — ONE
        bucketed ``swap_out_pages`` call; each column is copied out so a
        payload doesn't pin the whole bucket buffer.  The payload format is
        shared by the flash tier's cold store and the migration snapshot."""
        arrays = [np.asarray(a)
                  for a in _jit_swap_out(self.cache, self._bucket_pids(pids))]
        return [tuple(a[:, j].copy() for a in arrays)
                for j in range(len(pids))]

    def _scatter_pages(self, pids: list[int], payloads: list) -> None:
        """Scatter per-page payload tuples onto freshly allocated hot pids —
        ONE bucketed ``swap_in_pages`` call (null-page padded); the caller
        remaps the owning block-table row.  Shared by tier prefetch and
        migration inject."""
        payloads = [_payload_np(p) for p in payloads]
        comps = [np.stack([p[c] for p in payloads], axis=1)
                 for c in range(len(payloads[0]))]
        bpids = self._bucket_pids(pids)
        pad = len(bpids) - len(pids)
        if pad:
            def padded(a):
                widths = [(0, 0)] * a.ndim
                widths[1] = (0, pad)
                return np.pad(a, widths)
            comps = [padded(a) for a in comps]
        # device_put starts the host→device transfer asynchronously; the
        # swap_in scatter then composes with it by dataflow instead of the
        # jit call blocking on an implicit synchronous upload
        self.cache = _jit_swap_in(self.cache, bpids,
                                  *(jax.device_put(a, self.device)
                                    for a in comps))

    def _spill(self, items: list[tuple[tuple[int, int], int]]) -> int:
        """Swap ``(key=(slot, page_idx), pid)`` hot pages out to flash;
        returns how many actually moved.  With a bounded flash tier, items
        past its capacity go back on the eviction queue instead of
        half-spilling (which would leak their hot pids)."""
        room = self.allocator.flash_available
        if room is not None and len(items) > room and self._px is not None:
            # cold cached-prefix payloads are droppable (nobody references
            # them — a re-miss just re-prefills): shed LRU ones for room
            for key in self._px.cold_idle_keys(len(items) - room):
                if key in self._px_pin:
                    continue  # mid-acquire, about to prefetch
                self.allocator.drop_slot(
                    lambda k, key=key: k == ("px", key))
                self._px.drop(key)
            room = self.allocator.flash_available
        if room is not None and len(items) > room:
            for key, pid in items[room:]:
                self.allocator.mark_evictable(key, pid)
            items = items[:room]
        if not items:
            return 0
        pids = [pid for _, pid in items]
        # one bucketed gather, then per-page device columns wrapped as lazy
        # payloads: the device→host copies run asynchronously and only
        # materialize when prefetch / snapshot actually reads them, so a
        # spill never stalls the loop behind a blocking gather
        arrays = _jit_swap_out(self.cache, self._bucket_pids(pids))
        for j, (key, _pid) in enumerate(items):
            self.allocator.store(
                key, _LazyPagePayload(*(a[:, j] for a in arrays)))
            if key[0] == "px":
                # an idle cached-prefix page going cold: no block-table row
                # to clear, just the index residency flip
                self._px.mark_cold(key[1])
            else:
                slot, page_idx = key
                self.block[slot, page_idx] = 0
                self.slot_pages[slot][page_idx] = 0
        self.allocator.free(pids)
        self.stats.kv_spill_pages += len(pids)
        self.stats.kv_spill_bytes += len(pids) * self.kv_page_bytes
        return len(items)

    def _prefetch_slot(self, i: int) -> bool:
        """Bring all of slot ``i``'s cold pages back hot (before its next
        decode step); returns False when the hot pool can't take them yet."""
        keys = self.allocator.cold_keys(lambda k: k[0] == i)
        if not keys:
            return True
        need = len(keys)
        if self.allocator.available < need:
            short = need - self.allocator.available
            self._spill(self.allocator.pop_evictable(
                short, exclude=lambda k: k[0] == i))
        if self.allocator.available < need:
            return False
        keys.sort(key=lambda k: k[1])
        pids = self.allocator.alloc(need)
        self._scatter_pages(pids, [self.allocator.fetch(k) for k in keys])
        # residency-aware block-table remap: the pages came back on new pids
        for key, pid in zip(keys, pids):
            self.block[i, key[1]] = pid
            self.slot_pages[i][key[1]] = pid
        self.stats.kv_prefetch_pages += need
        self.stats.kv_prefetch_bytes += need * self.kv_page_bytes
        return True

    def _suspend(self, i: int) -> None:
        """Preempt slot ``i``: it stops decoding and its pages become LRU
        eviction candidates, oldest (lowest page index) first, tail last.
        A hybrid slot's Mamba state is checkpointed host-side so resume can
        restore it bit-identically (the state pool never pages — it is tiny
        and per-slot — but the checkpoint pins the resume contract even if
        something scribbles the lane while suspended)."""
        self.suspended[i] = True
        self.resume_order.append(i)
        self.stats.preemptions += 1
        self.slots[i].n_preempted += 1
        if self._has_state:
            self._ssm_ckpt[i] = model_lib.checkpoint_slot_state(self.cache, i)
        for page_idx, pid in enumerate(self.slot_pages[i]):
            # shared prefix pages stay pinned hot while mapped (other slots
            # may be reading them); they become spill candidates only when
            # their refcount parks at 0 in the index idle-LRU
            if pid != 0 and page_idx not in self.slot_shared[i]:
                self.allocator.mark_evictable((i, page_idx), pid)

    def _resume_suspended(self) -> None:
        """Head-of-line resume: the oldest suspended slot gets first claim on
        freed pages (with eviction assist against other suspended slots), so
        every preempted request is guaranteed to come back."""
        while self.resume_order:
            i = self.resume_order[0]
            if not self._prefetch_slot(i):
                break
            self.resume_order.pop(0)
            self.suspended[i] = False
            self.allocator.unmark_slot(lambda k, i=i: k[0] == i)
            if self._has_state and i in self._ssm_ckpt:
                self.cache = model_lib.restore_slot_state(
                    self.cache, i, self._ssm_ckpt.pop(i))
            self._resumed_now.add(i)
            self.stats.resumes += 1

    def _make_room(self, n: int, avoid: frozenset = frozenset()) -> None:
        """Free hot pages until ``n`` are available: spill LRU eviction
        candidates first, then preempt the policy's victim slot and retry
        (``scheduler.victim``; default = longest sequence).  ``avoid``
        shields slots (e.g. ones resumed this very step)."""
        while self.allocator.available < n:
            short = n - self.allocator.available
            items = self.allocator.pop_evictable(short)
            if items:
                if self._spill(items) == 0:
                    return  # flash tier full: eviction can't free anything
                continue
            victims = [i for i, r in enumerate(self.slots)
                       if r is not None and not self.suspended[i]
                       and i not in avoid]
            if not victims:
                return
            choice = self.scheduler.victim(
                [self._slot_view(i) for i in victims])
            if choice not in victims:  # defensive: policy returned junk
                choice = max(victims, key=lambda i: self.slot_len[i])
            self._suspend(choice)

    def _alloc_pages(self, n: int, avoid: frozenset = frozenset()) -> list[int]:
        if self.kv_tier == "flash" and self.allocator.available < n:
            self._make_room(n, avoid)
        if self._px is not None and self.allocator.available < n:
            # LRU-reclaim idle cached-prefix pages: live slots always beat
            # the cache (a reclaimed prefix just re-prefills on its next
            # miss; resume entries citing it die lazily at lookup)
            self._px_reclaim(n - self.allocator.available)
        return self.allocator.alloc(n)

    def _px_reclaim(self, n: int) -> None:
        ents = self._px.pop_idle_hot(n)
        if not ents:
            return
        if self.kv_tier == "flash":
            keys = {("px", key) for key, _pid in ents}
            self.allocator.unmark_slot(lambda k: k in keys)
        self.allocator.free([pid for _key, pid in ents])

    # ------------------------------------------------------------------
    # prefix cache: lookup / acquire / release / register / COW
    # ------------------------------------------------------------------
    def _key_tokens(self, req: Request) -> list[int]:
        """Token sequence the prefix hash chains over — one entry per cache
        position (``_cache_len0`` long).  vlm prepends a ``-1`` sentinel per
        vision token: the vision embeds are config-constant here, so equal
        sentinels imply equal page contents for vlm exactly like real
        tokens do for the text families."""
        if self.cfg.family == "vlm":
            return [-1] * self.cfg.n_vision_tokens + list(req.prompt)
        return list(req.prompt)

    def _px_lookup(self, req: Request, len0: int):
        """Match ``req`` against the index: ``("resume", entry)`` for an
        exact-prompt resume point (all five families — admission replays the
        stored bits with zero prefill dispatches), ``("partial", keys)`` for
        a leading run of cached full pages (chunk-capable families only —
        the uncached suffix must prefill through the chunk path), or None.
        A resume entry citing any reclaimed page entry dies lazily here."""
        if self._px is None:
            return None
        self.stats.prefix_lookups += 1
        kt = self._key_tokens(req)
        rkey = self._px.resume_key(kt)
        rent = self._px.get_resume(rkey)
        if rent is not None:
            if all(self._px.get(k) is not None for k in rent.page_keys):
                return ("resume", rent)
            self._px.drop_resume(rkey)
        if not self._chunk_ok:
            return None
        if self.kv_dtype == "int8":
            # a partial hit prefills only the uncached suffix through the
            # chunk path; under int8 pools the full-prompt one-shot prefill
            # and the chunked suffix replay agree only to quantization
            # precision, so partial reuse would break the "a prompt's pages
            # are a pure function of its tokens" sharing contract.  Resume
            # hits stay: they replay stored bits exactly.
            return None
        keys = self._px.page_keys(kt)
        # cap so at least one token remains to prefill: the suffix chunk is
        # what produces the first-token logits on a partial hit
        n = min(self._px.match(keys), (len0 - 1) // self.page_size)
        if n >= 1:
            return ("partial", keys[:n])
        return None

    def _px_acquire(self, keys: list[bytes],
                    avoid: frozenset = frozenset()) -> list[int]:
        """Map cached page entries into a slot: incref hot ones (an idle
        entry leaves the idle-LRU and withdraws its spill candidacy),
        prefetch cold ones onto fresh pids.  Returns pids in ``keys`` order;
        OutOfPages rolls the partial acquisition back completely."""
        done: list[bytes] = []
        cold: list[bytes] = []
        for k in keys:
            ent = self._px.get(k)
            if ent.cold:
                cold.append(k)
                continue
            if self.allocator.incref(ent.pid) == 1:
                self._px.unpark(k)
                if self.kv_tier == "flash":
                    self.allocator.unmark_slot(
                        lambda kk, k=k: kk == ("px", k))
            done.append(k)
        if cold:
            # pop payloads BEFORE allocating: _make_room may shed cold
            # prefix payloads for flash room, and _px_pin shields entries
            # mid-acquire from that shed
            payloads = [self.allocator.fetch(("px", k)) for k in cold]
            self._px_pin.update(cold)
            try:
                npids = self._alloc_pages(len(cold), avoid=avoid)
            except OutOfPages:
                for k, p in zip(cold, payloads):
                    self.allocator.store(("px", k), p)
                for k in done:
                    self._px_release_key(k)
                raise
            finally:
                self._px_pin.difference_update(cold)
            self._scatter_pages(npids, payloads)
            for k, pid in zip(cold, npids):
                self._px.mark_hot(k, pid)
            self.stats.kv_prefetch_pages += len(cold)
            self.stats.kv_prefetch_bytes += len(cold) * self.kv_page_bytes
        return [self._px.get(k).pid for k in keys]

    def _px_release_key(self, key: bytes) -> None:
        """Drop one slot's reference; at 0 the page parks on the idle-LRU
        (cached for the next hit) and becomes a spill candidate."""
        ent = self._px.get(key)
        if self.allocator.decref(ent.pid) == 0:
            self._px.park(key)
            if self.kv_tier == "flash":
                self.allocator.mark_evictable(("px", key), ent.pid)

    def _px_register_prompt(self, i: int, req: Request, len0: int,
                            logits_row) -> None:
        """Register slot ``i``'s freshly PREFILL-written prompt pages and an
        exact-prompt resume point.  Called right after one-shot prefill and
        at chunked-prefill completion — never for decode-written pages
        (their bits may differ from a prefill of the same tokens, which
        would break warm-vs-cold bit-identity on reuse)."""
        kt = self._key_tokens(req)
        keys = self._px.page_keys(kt)
        shared = self.slot_shared[i]
        for j, key in enumerate(keys):
            if j in shared:
                continue  # a partial hit already maps the index's page here
            if self._px.get(key) is None:
                self._px.insert(key, self.slot_pages[i][j])
                shared[j] = key
            # else: an identical page is registered under another pid (e.g.
            # twin prompts admitted in one group); ours stays exclusive
        rkey = self._px.resume_key(kt)
        if self._px.peek_resume(rkey) is None:
            tail_len = len0 - len(keys) * self.page_size
            tail = (self._gather_pages([self.slot_pages[i][len(keys)]])[0]
                    if tail_len else None)
            self._px.put_resume(rkey, ResumeEntry(
                page_keys=keys, tail=tail, tail_len=tail_len,
                logits=np.asarray(logits_row).copy(), length=len0,
                ssm=(model_lib.checkpoint_slot_state(self.cache, i)
                     if self._has_state else None)))

    def _px_cow(self, i: int, pj: int) -> None:
        """Copy-on-write: give slot ``i`` a private copy of its shared page
        ``pj`` before a write dirties it.  Registration only ever covers
        full prefill-written pages strictly behind every write frontier, so
        this is a safety net rather than a hot path — but any future flow
        that writes into the shared span goes through here, never through
        an in-place write."""
        key = self.slot_shared[i][pj]
        payload = self._gather_pages([self.slot_pages[i][pj]])[0]
        pid = self._alloc_pages(
            1, avoid=frozenset({i}) | self._resumed_now)[0]
        self._scatter_pages([pid], [payload])
        del self.slot_shared[i][pj]
        self._px_release_key(key)
        self.slot_pages[i][pj] = pid
        self.block[i, pj] = pid
        self.stats.cow_copies += 1

    def _admit_resume_hit(self, i: int, req: Request, len0: int,
                          rent: ResumeEntry, now: float) -> None:
        """Admit an exact-prompt hit with ZERO prefill dispatches: map the
        shared full pages, scatter the stored tail-page copy onto a private
        page (the COW copy of the partially filled shared span), restore
        recurrent state, and sample the first token from the stored prefill
        logits — bit-identical to a cold admission for any sampling params
        because every consumed bit is the cold run's bit."""
        avoid = frozenset(self._resumed_now)
        shared = self._px_acquire(rent.page_keys, avoid=avoid)
        tail: list[int] = []
        if rent.tail_len:
            try:
                tail = self._alloc_pages(1, avoid=avoid)
            except OutOfPages:
                for k in rent.page_keys:
                    self._px_release_key(k)
                raise
            self._scatter_pages(tail, [rent.tail])
            self.stats.cow_copies += 1
        pids = shared + tail
        self.slot_pages[i] = pids
        self.block[i, :len(pids)] = pids
        self.slot_shared[i] = dict(enumerate(rent.page_keys))
        self.slot_len[i] = rent.length
        self.cache["lens"] = self.cache["lens"].at[i].set(rent.length)
        self.prefilling[i] = False
        self.prefill_pos[i] = 0
        if self._has_state and rent.ssm is not None:
            self.cache = model_lib.restore_slot_state(self.cache, i,
                                                      rent.ssm)
        self.slots[i] = req
        self.stats.admitted += 1
        self.stats.prefix_hits += 1
        self.stats.prefix_hit_pages += len(shared)
        self.stats.prefix_tokens_reused += rent.length
        tok = int(self._sample_rows(rent.logits[None], [(0, req)])[0])
        t1 = time.monotonic()
        if req.t_admit == 0.0:  # restarts keep their first-admit times
            req.t_admit = now
            req.t_first_token = t1
        req.out_tokens.append(tok)
        self.stats.tokens_out += 1
        self.last_np[i] = tok
        reason = self._finish_reason_for(req, tok, rent.length)
        if reason is not None:
            self._finish(i, req, reason, token=tok)
        else:
            self._emit(req, tok)

    def _admit_partial_hit(self, i: int, req: Request, len0: int,
                           keys: list[bytes], now: float) -> None:
        """Admit a partial hit: map the cached leading pages, allocate the
        rest, and enter the chunked-prefill path at the cached length — the
        suffix prefills through ``_prefill_chunks`` whose any-schedule
        bit-identity contract keeps warm output equal to a cold one-shot."""
        avoid = frozenset(self._resumed_now)
        cached = len(keys) * self.page_size
        shared = self._px_acquire(keys, avoid=avoid)
        try:
            fresh = self._alloc_pages(
                pages_needed(len0, self.page_size) - len(keys), avoid=avoid)
        except OutOfPages:
            for k in keys:
                self._px_release_key(k)
            raise
        pids = shared + fresh
        self.slot_pages[i] = pids
        self.block[i, :len(pids)] = pids
        self.slot_shared[i] = dict(enumerate(keys))
        self.slots[i] = req
        self.prefilling[i] = True
        self.prefill_pos[i] = cached
        self.slot_len[i] = cached
        self.cache["lens"] = self.cache["lens"].at[i].set(cached)
        if req.t_admit == 0.0:
            req.t_admit = now
        self.stats.admitted += 1
        self.stats.prefix_hits += 1
        self.stats.prefix_hit_pages += len(keys)
        self.stats.prefix_tokens_reused += cached

    def prefix_hit_estimate(self, req: Request) -> int:
        """Prompt tokens this replica's prefix cache could serve ``req``
        without prefilling — the router folds this into ``least_loaded``
        scoring and ``session_affinity`` placement.  LRU-neutral (scoring N
        replicas must not perturb any cache's eviction order)."""
        if self.mode != "continuous" or self._px is None:
            return 0
        kt = self._key_tokens(req)
        rent = self._px.peek_resume(self._px.resume_key(kt))
        if rent is not None and all(
                self._px.get(k) is not None for k in rent.page_keys):
            return rent.length
        if not self._chunk_ok:
            return 0
        n = min(self._px.match(self._px.page_keys(kt)),
                (self._cache_len0(req) - 1) // self.page_size)
        return n * self.page_size

    def clear_prefix_cache(self) -> int:
        """Drop every IDLE cached prefix page (hot and cold) and all resume
        entries; pages still mapped by live slots stay shared.  Returns the
        number of page entries dropped — after a full drain this returns
        the whole index and the pool recycles completely (tests pin that)."""
        if self._px is None:
            return 0
        ents = self._px.pop_idle_hot(1 << 30)
        if ents:
            if self.kv_tier == "flash":
                keys = {("px", k) for k, _pid in ents}
                self.allocator.unmark_slot(lambda kk: kk in keys)
            self.allocator.free([pid for _k, pid in ents])
        cold = self._px.cold_idle_keys(1 << 30)
        for key in cold:
            self.allocator.drop_slot(lambda k, key=key: k == ("px", key))
            self._px.drop(key)
        self._px.clear_resume()
        return len(ents) + len(cold)

    # ------------------------------------------------------------------
    # continuous admission: prefill requests into free slots (one batched
    # pass for one-shot prompts; chunked slots claim now, prefill over the
    # following steps) while the rest of the batch keeps decoding
    # ------------------------------------------------------------------
    def _release_slot(self, i: int) -> None:
        # a pending fused-step row for this slot is now stale: the epoch
        # bump makes the lagged drain skip it (the slot may already host a
        # different request by then)
        self._slot_epoch[i] += 1
        self._inflight[i] = 0
        self.slots[i] = None
        # shared pages decref (at 0 they park on the index idle-LRU, cached
        # for the next hit); exclusively owned pages free outright — the
        # allocator's refcount guard makes a misclassified shared page a
        # loud ValueError, never a silent corruption
        own = [p for j, p in enumerate(self.slot_pages[i])
               if p != 0 and j not in self.slot_shared[i]]
        self.allocator.free(own)
        for j in self.slot_shared[i]:
            self._px_release_key(self.slot_shared[i][j])
        self.slot_shared[i] = {}
        if self.kv_tier == "flash":
            self.allocator.drop_slot(lambda k, i=i: k[0] == i)
            if self.suspended[i]:
                self.suspended[i] = False
                self.resume_order.remove(i)
        self.slot_pages[i] = []
        self.slot_len[i] = 0
        self.prefilling[i] = False
        self.prefill_pos[i] = 0
        self.block[i] = 0
        self._ssm_ckpt.pop(i, None)
        self.cache["lens"] = self.cache["lens"].at[i].set(0)

    def _finish(self, i: int, req: Request, reason: str,
                token: Optional[int] = None) -> None:
        now = time.monotonic()
        req.done = True
        req.finish_reason = reason
        req.t_done = now
        self.stats.completed += 1
        self.stats.admission_wait_s.append(req.admission_wait_s)
        self.stats.ttft_s.append(req.ttft_s)
        self.stats.latency_s.append(req.latency_s)
        if self.mode == "continuous":
            self._release_slot(i)
        else:
            self.slots[i] = None
        self._emit(req, token, finished=True)

    def _reject(self, req: Request) -> None:
        req.done = True
        req.rejected = True
        req.finish_reason = "rejected"
        req.t_done = time.monotonic()
        self.stats.rejected += 1
        self._emit(req, None, finished=True)

    def _preempt_restart(self, i: int, req: Request) -> None:
        """Pool exhausted mid-decode without a flash tier (or a priority
        preemption): fold the generated prefix into the prompt and requeue —
        greedy decode and seed-pinned sampling are both deterministic, so
        the request's final ``out_tokens`` are unchanged."""
        self.stats.preemptions += 1
        req.n_preempted += 1
        req.prompt = req.prompt + req.out_tokens[req.n_folded:]
        req.n_folded = len(req.out_tokens)
        self._release_slot(i)
        self.queue.insert(0, req)

    def _finish_reason_for(self, req: Request, tok: int, seq_len: int) -> \
            Optional[str]:
        if tok == self.eos_id:
            return "eos"
        if len(req.out_tokens) >= req.max_new_tokens:
            return "length"
        if seq_len >= self.max_seq - 1:
            return "capacity"
        return None

    def _admit_continuous(self) -> None:
        """Admit queued requests into free slots in the scheduler's order:
        one-shot prompts prefill together in ONE batched prefill-into-cache
        pass (right-padded, per-row 0-based positions); prompts longer than
        the policy's chunk budget claim their slot and pages now and
        prefill chunk-by-chunk over the following steps.  Occupied slots
        keep their decode state untouched throughout."""
        with spans.span(spans.ADMIT):
            group, now = self._claim_slots()
            if not group:
                return
            toks, slot_ids, true_lens, computed = self._group_prompts(group)
        with spans.span(spans.PREFILL):
            logits, out_cache = self._prefill_slots(
                self.params, toks, true_lens,
                {**self.cache, "block": self.block}, slot_ids)
        out_cache.pop("block")  # authoritative copy stays host-side
        self.cache = out_cache
        self.stats.prefills += 1
        self.stats.admitted += len(group)
        self.stats.prefill_tokens += sum(len0 for i, req, len0 in group)
        self.stats.prefill_computed_tokens += computed
        toks_out = self._sample_rows(
            logits, [(row, req) for row, (i, req, len0) in enumerate(group)])
        logits_np = None
        if self._px is not None:
            with spans.span(spans.READBACK):
                logits_np = np.asarray(logits)
        t1 = time.monotonic()
        with spans.span(spans.EMIT):
            for row, ((i, req, len0), tok) in enumerate(zip(group, toks_out)):
                tok = int(tok)
                if req.t_admit == 0.0:  # restarts keep first-admit times
                    req.t_admit = now
                    req.t_first_token = t1
                req.out_tokens.append(tok)
                self.stats.tokens_out += 1
                self.last_np[i] = tok
                self.slot_len[i] = len0
                self.slots[i] = req
                if self._px is not None:
                    # register BEFORE any finish below: the pages must
                    # outlive the slot as cached entries even for one-token
                    # requests
                    self._px_register_prompt(i, req, len0, logits_np[row])
                reason = self._finish_reason_for(req, tok, len0)
                if reason is not None:
                    self._finish(i, req, reason, token=tok)
                else:
                    self._emit(req, tok)

    def _claim_slots(self) -> tuple[list, float]:
        """Admission's host half: the scheduler's plan, its preemptions,
        and a free slot plus pages for each request it admits.  Prefix hits
        and chunked prompts are admitted here outright; returns the group of
        ``(slot, request, cache length)`` for the one-shot batched prefill,
        and the admission instant."""
        plan = self.scheduler.admit(list(self.queue), self._views(),
                                    self.allocator.available)
        head = next((r for r in plan.order if r in self.queue), None)
        for vi in plan.preempt:
            if (not 0 <= vi < self.max_batch or self.slots[vi] is None
                    or self.suspended[vi]):
                continue
            if head is not None and self.kv_tier != "flash":
                # futility gate: without a flash tier, restart-preempting a
                # victim whose freed pages still don't cover the arrival's
                # prefill just throws the victim's progress away (the slot
                # would sit idle on OutOfPages); the tiered path can always
                # _make_room by spilling, so it skips the gate
                victim_hot = sum(1 for p in self.slot_pages[vi] if p != 0)
                need = pages_needed(self._cache_len0(head), self.page_size)
                if self.allocator.available + victim_hot < need:
                    continue
            self._preempt_restart(vi, self.slots[vi])
        free = [i for i in range(self.max_batch) if self.slots[i] is None]
        group = []
        now = time.monotonic()
        for req in plan.order:
            if not free:
                break
            if req not in self.queue:  # defensive: stale plan entry
                continue
            i = free[0]
            len0 = self._cache_len0(req)
            hit = self._px_lookup(req, len0)
            try:
                if hit is not None and hit[0] == "resume":
                    self._admit_resume_hit(i, req, len0, hit[1], now)
                    free.pop(0)
                    self.queue.remove(req)
                    continue
                if hit is not None and hit[0] == "partial":
                    self._admit_partial_hit(i, req, len0, hit[1], now)
                    free.pop(0)
                    self.queue.remove(req)
                    continue
                pids = self._alloc_pages(
                    pages_needed(len0, self.page_size),
                    avoid=frozenset(self._resumed_now))
            except OutOfPages:
                self.stats.pool_exhausted += 1
                if self.exhaust_policy == "reject":
                    self.queue.remove(req)
                    self._reject(req)
                    continue
                break  # starved request keeps its queue spot for next step
            free.pop(0)
            self.queue.remove(req)
            self.slot_pages[i] = pids
            self.block[i, :len(pids)] = pids
            budget = self.scheduler.prefill_budget(SlotView(
                index=i, rid=req.rid, priority=req.priority,
                arrival_s=req.arrival_s, seq_len=0, n_out=0,
                remaining=req.max_new_tokens, prefilling=True,
                suspended=False,
                deadline_s=(req.arrival_s + req.deadline_s
                            if req.deadline_s is not None else None)))
            if self._chunk_ok and budget < len0:
                # chunked admission: slot + pages claimed, prompt prefills
                # in budget-sized chunks interleaved with decode steps
                self.slots[i] = req
                self.prefilling[i] = True
                self.prefill_pos[i] = 0
                self.slot_len[i] = 0
                if req.t_admit == 0.0:
                    req.t_admit = now
                self.stats.admitted += 1
            else:
                group.append((i, req, len0))
        return group, now

    def _group_prompts(self, group: list):
        """The batched prefill's inputs for ``group``: prompts padded to a
        common bucket, slot ids and true lengths, and the positions the
        prefill will compute (rows x (bucket + vision tokens))."""
        # common bucket for the group, capped so bucket + vision tokens still
        # fits a slot's block-table row (tail-pad pages beyond an allocation
        # fall on the null page, but the row itself must not overflow)
        extra = max(len0 - len(req.prompt) for i, req, len0 in group)
        cap = self.pages_per_slot * self.page_size - extra
        bucket = min(max(prefill_bucket(len(req.prompt))
                         for i, req, len0 in group), cap)
        # pad the group to max_batch rows by REPEATING row 0 (its duplicate
        # scatters write identical values, so the result is deterministic):
        # the jitted prefill then only ever sees (max_batch, bucket) shapes,
        # one trace per bucket instead of one per group size
        rows = group + [group[0]] * (self.max_batch - len(group))
        toks = np.asarray(
            [req.prompt + [0] * (bucket - len(req.prompt))
             for i, req, len0 in rows], np.int32)
        slot_ids = np.asarray([i for i, req, len0 in rows], np.int32)
        true_lens = np.asarray([len0 for i, req, len0 in rows], np.int32)
        return toks, slot_ids, true_lens, len(rows) * (bucket + extra)

    def _prefill_chunks(self) -> int:
        """Run one prefill chunk for every mid-prefill slot (the policy's
        per-step token budget each).  A slot whose prompt completes samples
        its first token and joins decode from the next lane mask on."""
        ran = 0
        for i in range(self.max_batch):
            req = self.slots[i]
            if req is None or not self.prefilling[i] or self.suspended[i]:
                continue
            len0 = self._cache_len0(req)
            pos = self.prefill_pos[i]
            with spans.span(spans.ADMIT):
                budget = max(1, int(self.scheduler.prefill_budget(
                    self._slot_view(i))))
                clen = min(budget, len0 - pos)
                cap = self.pages_per_slot * self.page_size
                # pad the chunk to a power-of-two bucket (floor = page
                # size): per-step compute scales with the BUDGET, not the
                # slot capacity, and the trace count stays O(log max_seq)
                # like the group-prefill buckets.  Bit-identity is
                # per-position, so the bucket shape is free to vary
                # (tests/test_chunked_prefill.py pins identity across
                # differently-bucketed schedules).
                cb = min(prefill_bucket(clen, floor=self.page_size), cap)
                toks = np.zeros((cb,), np.int32)
                toks[:clen] = req.prompt[pos:pos + clen]
            with spans.span(spans.PREFILL):
                logits, out_cache = self._prefill_chunk(
                    self.params, toks, np.int32(pos), np.int32(clen),
                    {**self.cache, "block": self.block}, np.int32(i))
            out_cache.pop("block")
            self.cache = out_cache
            req.n_chunks += 1
            self.stats.prefill_chunks += 1
            self.stats.prefill_tokens += clen
            self.stats.prefill_computed_tokens += cb
            ran += 1
            pos += clen
            self.prefill_pos[i] = pos
            self.slot_len[i] = pos
            if pos >= len0:
                self.prefilling[i] = False
                if self._px is not None:
                    self._px_register_prompt(i, req, len0,
                                             np.asarray(logits))
                tok = int(self._sample_rows(
                    jnp.asarray(logits)[None], [(0, req)])[0])
                with spans.span(spans.EMIT):
                    if req.t_first_token == 0.0:
                        req.t_first_token = time.monotonic()
                    req.out_tokens.append(tok)
                    self.stats.tokens_out += 1
                    self.last_np[i] = tok
                    reason = self._finish_reason_for(req, tok, pos)
                    if reason is not None:
                        self._finish(i, req, reason, token=tok)
                    else:
                        self._emit(req, tok)
        return ran

    def _ensure_pages(self) -> None:
        """Allocate the page each active slot's next write lands in; on a dry
        pool, preempt (tiered: suspend + spill; untiered: requeue/reject)."""
        for i in range(self.max_batch):
            req = self.slots[i]
            if req is None or self.suspended[i] or self.prefilling[i]:
                continue
            # the next write position counts the in-flight token the host
            # has not drained yet (slot_len is the DRAINED length)
            pj = (self.slot_len[i] + self._inflight[i]) // self.page_size
            try:
                if pj < len(self.slot_pages[i]):
                    if self._px is not None and pj in self.slot_shared[i]:
                        # the next decode write lands in a SHARED page:
                        # copy-on-write before it can dirty other readers
                        self._px_cow(i, pj)
                    continue
                pid = self._alloc_pages(
                    1, avoid=frozenset({i}) | self._resumed_now)[0]
            except OutOfPages:
                self.stats.pool_exhausted += 1
                if self.kv_tier == "flash":
                    self._suspend(i)
                elif self.exhaust_policy == "reject":
                    self._reject(req)
                    self._release_slot(i)
                else:
                    self._preempt_restart(i, req)
                continue
            self.slot_pages[i].append(pid)
            self.block[i, pj] = pid

    # ------------------------------------------------------------------
    # overlapped decode: dispatch step N+1 before reading step N's tokens
    # ------------------------------------------------------------------
    def _sampling_rows(self, items: list[tuple[int, Request]],
                       lag: Callable[[int], int]
                       ) -> tuple[bool, tuple[np.ndarray, ...]]:
        """Per-row sampling parameter arrays for a fused dispatch.

        ``lag(i)`` is how many of slot i's tokens are still in flight: the
        sampler cursor (``counts``) must index the token ABOUT to be
        sampled, which trails ``len(out_tokens)`` by the undrained ones.
        """
        b = self.max_batch
        temps = np.zeros((b,), np.float32)
        seeds = np.zeros((b,), np.int32)
        counts = np.zeros((b,), np.int32)
        topk = np.zeros((b,), np.int32)
        topp = np.ones((b,), np.float32)
        for i, req in items:
            sp = req.sampling
            temps[i] = sp.temperature
            seeds[i] = sp.seed if sp.seed is not None else req.rid
            counts[i] = len(req.out_tokens) + lag(i)
            topk[i] = sp.top_k
            topp[i] = sp.top_p
        greedy_only = all(r.sampling.temperature <= 0.0 for _, r in items)
        return greedy_only, (seeds, counts, temps, topk, topp)

    def _drain_pending(self) -> None:
        """Read back and account the in-flight fused step (no-op without).

        This is the lagged finish point: eos shows up here one engine call
        after the token was computed, and the speculative extra step a
        to-be-finished slot may have run in between is discarded via the
        slot-epoch check when its release bumps the epoch.
        """
        pend, self._pending = self._pending, None
        if pend is not None:
            self._drain_rows(pend)

    def _drain_rows(self, pend: dict) -> None:
        if self._san is not None and pend.get("san") is not None:
            # the step is about to be read back: its numpy args must be
            # bit-identical to what was dispatched (aliasing guard)
            self._san.check_drain(pend["san"])
        with spans.span(spans.READBACK):
            tok_np = np.asarray(pend["tok"])  # blocks on THIS step only;
            # any younger dispatch keeps running behind it
        with spans.span(spans.EMIT):
            self._account_rows(pend["rows"], tok_np)

    def _account_rows(self, rows: list, tok_np: np.ndarray) -> None:
        if self.mode == "continuous":
            for i, req, seq_after, epoch in rows:
                if (req.done or self.slots[i] is not req
                        or self._slot_epoch[i] != epoch):
                    continue  # slot reassigned/released since dispatch
                self._inflight[i] -= 1
                t = int(tok_np[i])
                self.last_np[i] = t
                req.out_tokens.append(t)
                self.stats.tokens_out += 1
                self.slot_len[i] = seq_after
                reason = self._finish_reason_for(req, t, seq_after)
                if reason is not None:
                    self._finish(i, req, reason, token=t)
                else:
                    self._emit(req, t)
        else:
            for i, req, seq_after in rows:
                if req.done or self.slots[i] is not req:
                    continue
                t = int(tok_np[i])
                self._wave_last_np[i] = t
                req.out_tokens.append(t)
                self.stats.tokens_out += 1
                reason = None
                if t == self.eos_id:
                    reason = "eos"
                elif len(req.out_tokens) >= req.max_new_tokens:
                    reason = "length"
                elif seq_after >= self.max_seq - 1:
                    reason = "capacity"
                if reason is not None:
                    self._finish(i, req, reason, token=t)
                else:
                    self._emit(req, t)

    def _overlap_round_continuous(self, active_list: list[bool]) -> None:
        """One overlapped round: fused-dispatch the next decode step, THEN
        drain the previous one — its host readback runs concurrently with
        the compute just enqueued, so the device never waits on the host
        between steps."""
        items = [(i, self.slots[i]) for i in range(self.max_batch)
                 if active_list[i]]
        greedy_only, sp_rows = self._sampling_rows(
            items, lag=lambda i: self._inflight[i])
        use_dev = np.asarray([n > 0 for n in self._inflight])
        old, self._pending = self._pending, None
        tok_dev = (old["tok"] if old is not None
                   else np.zeros((self.max_batch,), np.int32))
        # numpy args MUST be snapshotted: on the CPU backend jit wraps host
        # buffers zero-copy, so the async-executing step would otherwise read
        # ``last_np`` / ``block`` concurrently with the in-place mutations
        # the drain / spill below performs (a real, observed data race)
        last_np = self.last_np.copy()
        block = self.block.copy()
        active = np.asarray(active_list)
        with spans.span(spans.DECODE):
            tok, cache = self._decode_sample(
                self.params, last_np, tok_dev, use_dev,
                {**self.cache, "block": block},
                active, *sp_rows, greedy_only=greedy_only)
        san = None
        if self._san is not None:
            san = self._san.guard_dispatch(
                self.stats.decode_steps, last_np=last_np, block=block,
                use_dev=use_dev, active=active, seeds=sp_rows[0],
                counts=sp_rows[1], temps=sp_rows[2], topk=sp_rows[3],
                topp=sp_rows[4])
            self._san.check_retrace(self._decode_sample, "decode_sample")
        cache.pop("block")  # authoritative copy stays host-side
        self.cache = cache
        self.stats.decode_steps += 1
        self.stats.decode_dispatches += 1
        self._count_kv_pages(active_list)
        rows = []
        for i, req in items:
            seq_after = self.slot_len[i] + self._inflight[i] + 1
            rows.append((i, req, seq_after, self._slot_epoch[i]))
            self._inflight[i] += 1
        self._pending = {"tok": tok, "rows": rows, "san": san}
        if old is not None:
            self._drain_rows(old)

    def _count_kv_pages(self, active_list: list[bool]) -> None:
        """Count one paged decode step's KV reads: each active slot attends
        its dispatched length (drained + in flight) plus the new token."""
        self.stats.decode_kv_live_pages += sum(
            pages_needed(self.slot_len[i] + self._inflight[i] + 1,
                         self.page_size)
            for i in range(self.max_batch) if active_list[i])
        self.stats.decode_kv_table_pages += (self.max_batch
                                             * self.pages_per_slot)

    def _mask_predicted_finishes(self, active_list: list[bool]) -> None:
        """Exclude slots whose undrained token already finishes them.

        Length and capacity are host-predictable one step ahead, so those
        slots must not run a wasted extra step; eos is only discoverable at
        drain — an eos'd slot runs one speculative step whose writes land
        beyond its lens mask (or are re-prefilled by the next occupant) and
        whose token the epoch check discards."""
        for i in range(self.max_batch):
            if not active_list[i] or not self._inflight[i]:
                continue
            req = self.slots[i]
            if (len(req.out_tokens) + self._inflight[i]
                    >= req.max_new_tokens
                    or self.slot_len[i] + self._inflight[i]
                    >= self.max_seq - 1):
                active_list[i] = False

    def _step_continuous(self) -> bool:
        self._resumed_now = set()
        if self.kv_tier == "flash":
            self._resume_suspended()
        self._admit_continuous()
        chunks_ran = self._prefill_chunks()
        if all(s is None for s in self.slots):
            self._drain_pending()  # discard a stale speculative step
            return bool(self.queue)
        self._ensure_pages()
        active_list = [self.slots[i] is not None and not self.suspended[i]
                       and not self.prefilling[i]
                       for i in range(self.max_batch)]
        if self.overlap:
            self._mask_predicted_finishes(active_list)
        if not any(active_list):
            had_pending = self._pending is not None
            self._drain_pending()  # lagged finishes still need to land
            if chunks_ran or had_pending:
                self._idle_steps = 0  # chunk/drain progress is progress
                return True
            # everything suspended and nothing resumed: with an unbounded
            # flash tier the head-of-line resume always succeeds within one
            # step (eviction assist reaches every other suspended slot), but
            # a FULL bounded tier can wedge — no spill room, no free hot
            # pages.  After a second consecutive zero-progress step, escape
            # by restarting the head slot, which frees its pages outright.
            self._idle_steps += 1
            if self.resume_order and self._idle_steps >= 2:
                i = self.resume_order[0]
                self.stats.pool_exhausted += 1
                self._preempt_restart(i, self.slots[i])
                self._idle_steps = 0
            return True
        self._idle_steps = 0
        if self.overlap:
            self._overlap_round_continuous(active_list)
            return True
        active = np.asarray(active_list)
        pre_cache = {**self.cache, "block": self.block}  # for re-dispatch
        with spans.span(spans.DECODE):
            t0 = time.monotonic()
            logits, cache = self._decode(self.params, self.last_np,
                                         pre_cache, active)
            dt = time.monotonic() - t0
            if self.watchdog is not None and self.watchdog(
                    self.stats.decode_steps, dt):
                self.stats.straggler_events += 1
                logits, cache = self._decode(self.params, self.last_np,
                                             pre_cache, active)
        cache.pop("block")  # authoritative copy stays host-side
        self.cache = cache
        self.stats.decode_steps += 1
        self.stats.decode_dispatches += 2  # decode + separate sample
        self._count_kv_pages(active_list)
        tok_np = self._sample_rows(  # one sync per step
            logits, [(i, r) for i, r in enumerate(self.slots)
                     if r is not None and active_list[i]])
        with spans.span(spans.EMIT):
            for i, req in enumerate(self.slots):
                if req is None or not active_list[i]:
                    continue
                t = int(tok_np[i])
                self.last_np[i] = t
                req.out_tokens.append(t)
                self.stats.tokens_out += 1
                self.slot_len[i] += 1
                reason = self._finish_reason_for(req, t, self.slot_len[i])
                if reason is not None:
                    self._finish(i, req, reason, token=t)
                else:
                    self._emit(req, t)
        return True

    # ------------------------------------------------------------------
    # legacy wave admission over the shared-cursor cache
    # ------------------------------------------------------------------
    def _admit_wave(self) -> None:
        """The shared length cursor (cache["len"]) forces lockstep decode, so
        new requests only start when the whole batch drains.  The scheduler
        still orders the wave (preemption does not apply: there is no
        per-slot cache to evict)."""
        if any(s is not None for s in self.slots):
            return
        # with overlap, the call that drains a wave's last tokens has
        # already dispatched one speculative step; all its rows are stale
        # now (every request finished) — retire it before re-priming
        self._drain_pending()
        if not self.queue:
            return
        plan = self.scheduler.admit(list(self.queue), self._views(),
                                    1 << 30)
        order = [r for r in plan.order if r in self.queue]
        wave = order[:self.max_batch]
        for r in wave:
            self.queue.remove(r)
        if not wave:
            return
        now = time.monotonic()
        # right-align prompts to a common prefill length
        plen = max(len(r.prompt) for r in wave)
        toks = jnp.array(
            [([0] * (plen - len(r.prompt)) + r.prompt) for r in wave]
            + [[0] * plen] * (self.max_batch - len(wave)), jnp.int32)
        self.cache = model_lib.init_cache(self.cfg, self.max_batch,
                                          self.max_seq)
        with spans.span(spans.PREFILL):
            logits, self.cache = _jit_prefill(self.cfg)(
                self.params, toks, self.cache, self.max_batch)
        self.stats.prefills += 1
        self.stats.admitted += len(wave)
        self.stats.prefill_tokens += sum(len(r.prompt) for r in wave)
        self.stats.prefill_computed_tokens += self.max_batch * plen
        tok_np = self._sample_rows(
            logits, [(row, r) for row, r in enumerate(wave)])
        self.last_token = jnp.asarray(tok_np)
        self._wave_last_np = np.asarray(tok_np, np.int32).copy()
        self._wave_len = plen  # host prediction of cache["len"]
        t1 = time.monotonic()
        for i, r in enumerate(wave):
            self.slots[i] = r
            r.t_admit = now
            r.t_first_token = t1
            tok = int(tok_np[i])
            r.out_tokens.append(tok)
            self.stats.tokens_out += 1
            reason = self._finish_reason_for(r, tok, len(r.prompt))
            if reason == "capacity":
                reason = None  # wave cursor checked against cache len below
            if reason is not None:
                self._finish(i, r, reason, token=tok)
            else:
                self._emit(r, tok)

    def _overlap_round_wave(self) -> None:
        """Wave-mode overlapped round: same dispatch-then-drain shape as
        continuous, minus slot churn (no admission mid-wave, no epochs —
        row liveness is just ``slots[i] is req``)."""
        items = [(i, r) for i, r in enumerate(self.slots) if r is not None]
        old, self._pending = self._pending, None
        live = set()
        if old is not None:
            for i, req, _sa in old["rows"]:
                if self.slots[i] is req and not req.done:
                    live.add(i)
        if self._wave_len >= self.max_seq - 1:
            # shared cursor at capacity: nothing more may be dispatched (a
            # write at max_seq would overflow the cache); the drain below
            # capacity-finishes every surviving row
            if old is not None:
                self._drain_rows(old)
            return
        greedy_only, sp_rows = self._sampling_rows(
            items, lag=lambda i: 1 if i in live else 0)
        use_dev = np.asarray([i in live for i in range(self.max_batch)])
        tok_dev = (old["tok"] if old is not None
                   else np.zeros((self.max_batch,), np.int32))
        # snapshot: CPU jit aliases numpy inputs zero-copy and the drain
        # below mutates ``_wave_last_np`` while this step is still running
        wave_last = self._wave_last_np.copy()
        with spans.span(spans.DECODE):
            tok, cache = self._decode_sample(
                self.params, wave_last, tok_dev, use_dev,
                self.cache, *sp_rows, greedy_only=greedy_only)
        san = None
        if self._san is not None:
            san = self._san.guard_dispatch(
                self.stats.decode_steps, wave_last=wave_last,
                use_dev=use_dev, seeds=sp_rows[0], counts=sp_rows[1],
                temps=sp_rows[2], topk=sp_rows[3], topp=sp_rows[4])
            self._san.check_retrace(self._decode_sample, "decode_sample")
        self.cache = cache
        self.stats.decode_steps += 1
        self.stats.decode_dispatches += 1
        self._wave_len += 1
        self._pending = {"tok": tok, "san": san,
                         "rows": [(i, r, self._wave_len) for i, r in items]}
        if old is not None:
            self._drain_rows(old)

    def _step_wave(self) -> bool:
        self._admit_wave()
        if all(s is None for s in self.slots):
            return bool(self.queue)
        if self.overlap:
            self._overlap_round_wave()
            return True
        pre_cache = self.cache
        with spans.span(spans.DECODE):
            t0 = time.monotonic()
            logits, cache = self._decode(self.params, self.last_token,
                                         pre_cache)
            dt = time.monotonic() - t0
            if self.watchdog is not None and self.watchdog(
                    self.stats.decode_steps, dt):
                self.stats.straggler_events += 1
                logits, cache = self._decode(self.params, self.last_token,
                                             pre_cache)
        self.cache = cache
        self.stats.decode_steps += 1
        self.stats.decode_dispatches += 2  # decode + separate sample
        tok_np = self._sample_rows(
            logits, [(i, r) for i, r in enumerate(self.slots)
                     if r is not None])
        self.last_token = jnp.asarray(tok_np)
        for i, r in enumerate(self.slots):
            if r is None:
                continue
            t = int(tok_np[i])
            r.out_tokens.append(t)
            self.stats.tokens_out += 1
            reason = None
            if t == self.eos_id:
                reason = "eos"
            elif len(r.out_tokens) >= r.max_new_tokens:
                reason = "length"
            elif int(self.cache["len"]) >= self.max_seq - 1:
                reason = "capacity"
            if reason is not None:
                self._finish(i, r, reason, token=t)
            else:
                self._emit(r, t)
        return True

    # ------------------------------------------------------------------
    def _advance(self) -> bool:
        """Admit + one decode step over the active batch; True if any work."""
        with spans.span(spans.STEP):
            if self.mode == "continuous":
                return self._step_continuous()
            return self._step_wave()

    def run(self, max_steps: int = 10_000) -> EngineStats:
        steps = 0
        while self.has_work and steps < max_steps:
            if not self._advance():
                break
            steps += 1
        return self.stats
