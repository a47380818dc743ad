"""Fleet serving: wire codec, transports, worker protocol, and failover.

Load-bearing checks, per the fleet contract (serving/fleet/README.md):

* The wire codec round-trips the full command surface — scalars,
  ndarrays (incl. bf16), and the serving dataclasses — and the frame
  decoder survives adversity: byte-by-byte feeds, messages split across
  recv boundaries, oversized payloads, and garbage bytes all either
  reassemble cleanly or raise ProtocolError (never hang).
* SlotSnapshot.to_bytes()/from_bytes() round-trips byte-identically for
  every paged family, and the versioned header's geometry guard
  (family / page_size / dtype) rejects mismatched receivers before the
  body is decoded.
* Killing one loopback worker mid-decode loses zero requests: queued
  requests replay from the client's record, in-flight slots restore
  from the periodic checkpoint, and every recovered stream is
  bit-identical to an undisturbed single-engine run — greedy AND
  seed-pinned stochastic, for every paged family.
* A straggler (blown reply deadlines under the miss limit) recovers
  without failover — its late replies are delivered and counted as
  heartbeat misses; past the miss limit it is failed over like a death.
* The socket transport drives real subprocess workers, and SIGKILLing
  one mid-decode meets the same zero-loss bit-identity bar
  (``-k sock``; dense + the recurrent hybrid family).
"""

import os
import signal

import jax
import numpy as np
import pytest

try:
    import ml_dtypes
except ImportError:  # pragma: no cover - ships with jax
    ml_dtypes = None

from repro.configs.registry import ASSIGNED_ARCHS
from repro.models import model as M
from repro.serving.client import ServingClient
from repro.serving.core import (EngineCore, Request, RequestOutput,
                                SlotSnapshot)
from repro.serving.fleet import wire
from repro.serving.fleet.router import FleetRouter
from repro.serving.fleet.transport import (LoopbackTransport, RemoteError,
                                           TransportClosed, unwrap)
from repro.serving.fleet.worker import WorkerHost
from repro.serving.scheduler import SamplingParams

KEY = jax.random.PRNGKey(0)
ENG_KW = dict(max_batch=2, max_seq=48, eos_id=-1, page_size=8)


@pytest.fixture(scope="module")
def smollm():
    cfg = ASSIGNED_ARCHS["smollm-360m"].reduced()
    params = M.init_params(cfg, KEY, max_seq=64)
    return cfg, params


def _reqs(n, max_new=10, stochastic=True):
    """Mixed greedy/stochastic requests; odd rids get pinned seeds so
    failover replay is checked for sampled streams too."""
    out = []
    for rid in range(n):
        sp = None
        if stochastic and rid % 2 == 1:
            sp = SamplingParams(temperature=0.9, top_k=20, seed=100 + rid)
        out.append(Request(rid=rid, prompt=[2 + rid, 5, 9 + rid],
                           max_new_tokens=max_new, sampling=sp))
    return out


def _solo_ref(cfg, params, reqs):
    """The oracle: one undisturbed in-process engine, same requests."""
    eng = EngineCore(cfg, params, **ENG_KW)
    for r in reqs:
        eng.add_request(r)
    eng.run()
    assert all(r.done for r in reqs)
    return {r.rid: list(r.out_tokens) for r in reqs}


# ------------------------------------------------------------- wire codec
def test_codec_roundtrips_scalars_containers_and_arrays():
    objs = [None, True, False, np.bool_(True), 0, -7, 2**40, 3.5, "héllo",
            b"\x00\xff", [1, "a", None], (1, (2, 3)),
            {"k": [1.5, b"x"], "n": None},
            np.arange(12, dtype=np.int32).reshape(3, 4),
            np.zeros((0, 4), dtype=np.float64)]
    if ml_dtypes is not None:
        objs.append((np.arange(8).astype(ml_dtypes.bfloat16) * 1.5)
                    .astype(ml_dtypes.bfloat16))
    for o in objs:
        d = wire.decode(wire.encode(o))
        if isinstance(o, np.ndarray):
            assert d.dtype == o.dtype and d.shape == o.shape
            assert (d == o).all()
        else:
            assert d == o


def test_codec_roundtrips_serving_dataclasses():
    sp = SamplingParams(temperature=0.8, top_k=40, seed=7)
    req = Request(rid=3, prompt=[1, 2, 3], max_new_tokens=8, sampling=sp,
                  session="s1", priority=2)
    req.out_tokens.extend([5, 6])
    r2 = wire.decode(wire.encode(req))
    assert (r2.rid, r2.prompt, r2.out_tokens) == (3, [1, 2, 3], [5, 6])
    assert r2.sampling.temperature == 0.8 and r2.sampling.seed == 7
    ev = RequestOutput(rid=1, token=9, n_out=2, finished=True,
                       finish_reason="eos",
                       sched={"chunks": 1, "preemptions": 0, "wait_s": 0.1})
    e2 = wire.decode(wire.encode(ev))
    assert e2.token == 9 and e2.finish_reason == "eos"
    assert e2.sched["chunks"] == 1


def test_codec_rejects_truncation_and_unknown_tags():
    with pytest.raises(wire.ProtocolError):
        wire.decode(wire.encode([1, 2, 3])[:-2])
    with pytest.raises(wire.ProtocolError):
        wire.decode(b"\xffgarbage")
    with pytest.raises(wire.ProtocolError):
        wire.decode(wire.encode("x") + b"trailing")


# ------------------------------------------------------ framing adversity
def test_frame_decoder_byte_by_byte():
    payload = wire.encode({"a": [1, 2], "b": "x"})
    f = wire.frame(payload)
    dec = wire.FrameDecoder()
    outs = []
    for i in range(len(f)):
        outs += dec.feed(f[i:i + 1])
    assert len(outs) == 1 and outs[0] == payload


def test_frame_decoder_split_across_recv_boundaries():
    f = wire.frame(wire.encode([1, 2])) + wire.frame(wire.encode("x")) \
        + wire.frame(wire.encode(None))
    for cut in range(1, len(f) - 1):
        dec = wire.FrameDecoder()
        outs = dec.feed(f[:cut]) + dec.feed(f[cut:])
        assert [wire.decode(p) for p in outs] == [[1, 2], "x", None]


def test_frame_decoder_rejects_oversized_payload():
    with pytest.raises(wire.ProtocolError):
        wire.FrameDecoder(max_payload=4).feed(
            wire.frame(wire.encode("this is way past four bytes")))
    # the frame() side refuses to build it too
    with pytest.raises(wire.ProtocolError):
        wire.frame(b"x" * 8, max_payload=4)


def test_frame_decoder_rejects_garbage_bytes():
    with pytest.raises(wire.ProtocolError):
        wire.FrameDecoder().feed(b"\x00" * wire.HEADER_SIZE)
    # bad version in an otherwise valid header
    hdr = bytearray(wire.frame(wire.encode(1)))
    hdr[2] = 99
    with pytest.raises(wire.ProtocolError):
        wire.FrameDecoder().feed(bytes(hdr))


def test_worker_replies_cleanly_to_malformed_command(smollm):
    """A garbage command through the transport gets an error REPLY (with
    the load heartbeat), not a hang or a worker crash."""
    cfg, params = smollm
    host = WorkerHost(EngineCore(cfg, params, **ENG_KW))
    rep = host.handle("not-a-command-dict")
    assert rep["ok"] is False and rep["e"]["type"] == "ProtocolError"
    assert "queue_depth" in rep["load"]
    with pytest.raises(RemoteError):
        unwrap(rep)
    # and the host still serves real commands afterwards
    t = LoopbackTransport(host)
    assert unwrap(t.call("ping", {})) == "worker"


# ------------------------------------------- snapshot bytes (per family)
def test_snapshot_bytes_roundtrip_all_families(fam):
    """Property test: snapshots taken at random decode depths round-trip
    byte-identically (to_bytes -> from_bytes -> to_bytes) for every
    paged family, and the geometry guard rejects wrong receivers."""
    family, cfg, params = fam
    rng = np.random.RandomState(0)
    eng = EngineCore(cfg, params, **ENG_KW)
    reqs = _reqs(2, max_new=12)
    for r in reqs:
        eng.add_request(r)
    for round_ in range(3):
        for _ in range(int(rng.randint(1, 4))):
            eng.step()
        active = [r for r in eng.slots if r is not None]
        if not active:
            break
        req = active[int(rng.randint(len(active)))]
        snap = eng.snapshot_slot(req.rid, release=False)
        blob = snap.to_bytes()
        hdr, _ = wire.peek_snapshot_header(blob)
        assert hdr["family"] == family
        assert hdr["page_size"] == ENG_KW["page_size"]
        s2 = SlotSnapshot.from_bytes(
            blob, expect_family=family,
            expect_page_size=ENG_KW["page_size"], expect_dtype=hdr["dtype"])
        assert s2.to_bytes() == blob, "re-encode is not byte-identical"
        assert s2.slot_len == snap.slot_len
        assert s2.req.out_tokens == snap.req.out_tokens
        assert len(s2.pages) == len(snap.pages)
        for (k1, v1), (k2, v2) in zip(snap.pages, s2.pages):
            k1, v1 = np.asarray(k1), np.asarray(v1)
            assert k1.dtype == k2.dtype and (k1 == k2).all()
            assert v1.dtype == v2.dtype and (v1 == v2).all()
        with pytest.raises(ValueError):
            SlotSnapshot.from_bytes(blob, expect_family="no-such-family")
        with pytest.raises(ValueError):
            SlotSnapshot.from_bytes(
                blob, expect_page_size=ENG_KW["page_size"] + 1)
        with pytest.raises(ValueError):
            SlotSnapshot.from_bytes(blob, expect_dtype="no-such-dtype")
        with pytest.raises(wire.ProtocolError):
            SlotSnapshot.from_bytes(blob[:len(blob) // 2])


def test_checkpoint_snapshot_is_non_destructive(smollm):
    """release=False must leave the slot running: the request finishes
    normally after being checkpointed every step."""
    cfg, params = smollm
    eng = EngineCore(cfg, params, **ENG_KW)
    reqs = _reqs(2, max_new=6)
    for r in reqs:
        eng.add_request(r)
    ref = _solo_ref(cfg, params, _reqs(2, max_new=6))
    steps = 0
    while eng.has_work and steps < 200:
        eng.step()
        for r in eng.slots:
            if r is not None:
                eng.snapshot_slot(r.rid, release=False)
        steps += 1
    assert all(r.done for r in reqs)
    assert {r.rid: list(r.out_tokens) for r in reqs} == ref
    assert eng.stats.migrated_out == 0   # checkpoints are not migrations


# ------------------------------------------------- loopback fleet + kill
def test_loopback_kill_mid_decode_bit_identical(fam):
    """THE acceptance bar, per family: kill one of two loopback workers
    mid-decode; zero requests lost, every stream (greedy and seed-pinned
    stochastic) bit-identical to the undisturbed single-engine run."""
    family, cfg, params = fam
    ref = _solo_ref(cfg, params, _reqs(4))
    fl = FleetRouter.build_loopback(cfg, params, workers=2, spares=1,
                                    checkpoint_every=3, **ENG_KW)
    reqs = _reqs(4)
    for r in reqs:
        fl.submit(r)
    steps, killed = 0, False
    while fl.has_work and steps < 500:
        fl.step()
        steps += 1
        if not killed and steps == 5:
            fl.workers[0].transport.kill()
            killed = True
    assert all(r.done for r in reqs), \
        f"lost: {[r.rid for r in reqs if not r.done]}"
    assert {r.rid: list(r.out_tokens) for r in reqs} == ref
    assert fl.fleet.workers_lost == 1 and fl.fleet.failovers == 1
    assert fl.fleet.requests_replayed >= 1
    assert fl.spares_left == 0          # the spare was promoted
    assert len(fl.recovery_s) == 1
    s = fl.summary()
    assert "workers_lost=1" in s and "failovers=1" in s
    fl.close()


def test_from_scratch_replay_without_checkpoints(smollm):
    """checkpoint_every=0 disables snapshots entirely: failover falls
    back to replaying from the client's request record — slower (every
    delivered token re-decodes) but still bit-identical."""
    cfg, params = smollm
    ref = _solo_ref(cfg, params, _reqs(4))
    fl = FleetRouter.build_loopback(cfg, params, workers=2, spares=0,
                                    checkpoint_every=0, migrate=False,
                                    **ENG_KW)
    reqs = _reqs(4)
    for r in reqs:
        fl.submit(r)
    steps, killed = 0, False
    while fl.has_work and steps < 500:
        fl.step()
        steps += 1
        if not killed and steps == 6:
            w0 = fl.workers[0]
            n_delivered = sum(len(r.out_tokens) for r in reqs
                              if fl._owner.get(r.rid) is w0)
            w0.transport.kill()
            killed = True
    assert all(r.done for r in reqs)
    assert {r.rid: list(r.out_tokens) for r in reqs} == ref
    # every token delivered before the kill was re-decoded and suppressed
    assert fl.fleet.tokens_replayed >= n_delivered
    fl.close()


def test_straggler_recovers_without_failover(smollm):
    """Blown deadlines under the miss limit mark the worker SUSPECT and
    count heartbeat misses; its late replies are then delivered and the
    output stays bit-identical — no failover."""
    cfg, params = smollm
    ref = _solo_ref(cfg, params, _reqs(4))
    fl = FleetRouter.build_loopback(cfg, params, workers=2, spares=0,
                                    checkpoint_every=0, migrate=False,
                                    miss_limit=10, **ENG_KW)
    reqs = _reqs(4)
    for r in reqs:
        fl.submit(r)
    steps, stalled = 0, False
    while fl.has_work and steps < 500:
        fl.step()
        steps += 1
        if not stalled and steps == 4:
            fl.workers[0].transport.stall(3)
            stalled = True
    assert all(r.done for r in reqs)
    assert {r.rid: list(r.out_tokens) for r in reqs} == ref
    assert fl.fleet.heartbeat_misses == 3
    assert fl.fleet.workers_lost == 0 and fl.fleet.failovers == 0
    assert all(w.state == "alive" for w in fl.workers)
    fl.close()


def test_straggler_past_miss_limit_fails_over(smollm):
    """A straggler that never comes back crosses the miss limit and is
    failed over exactly like a death — with the same bit-identity bar."""
    cfg, params = smollm
    ref = _solo_ref(cfg, params, _reqs(4))
    fl = FleetRouter.build_loopback(cfg, params, workers=2, spares=0,
                                    checkpoint_every=3, migrate=False,
                                    miss_limit=2, **ENG_KW)
    reqs = _reqs(4)
    for r in reqs:
        fl.submit(r)
    steps, stalled = 0, False
    while fl.has_work and steps < 500:
        fl.step()
        steps += 1
        if not stalled and steps == 5:
            fl.workers[0].transport.stall(1000)   # never recovers
            stalled = True
    assert all(r.done for r in reqs)
    assert {r.rid: list(r.out_tokens) for r in reqs} == ref
    assert fl.fleet.failovers == 1 and fl.fleet.heartbeat_misses >= 3
    fl.close()


def test_fleet_abort_and_duplicate_rid_guard(smollm):
    cfg, params = smollm
    fl = FleetRouter.build_loopback(cfg, params, workers=2, spares=0,
                                    **ENG_KW)
    reqs = _reqs(3, max_new=12)
    for r in reqs:
        fl.submit(r)
    with pytest.raises(ValueError, match="already submitted"):
        fl.submit(Request(rid=0, prompt=[1], max_new_tokens=2))
    for _ in range(3):
        fl.step()
    assert fl.abort(1)
    steps = 0
    events = []
    while fl.has_work and steps < 200:
        events += fl.step()
        steps += 1
    finals = {e.rid: e for e in events if e.finished}
    assert finals[1].finish_reason == "aborted"
    assert reqs[0].done and reqs[2].done
    assert sum(1 for e in events if e.finished and e.rid == 1) == 1
    fl.close()


def test_serving_client_over_loopback_fleet(smollm):
    """The client surface composes with the fleet unchanged: workers=N
    builds a loopback FleetRouter, handles stream through a mid-run
    worker kill, and the summary surfaces the fleet counters."""
    cfg, params = smollm
    solo = ServingClient(cfg, params, replicas=1, seed_base=7, **ENG_KW)
    ref_handles = [solo.submit([3 + i, 5], max_new_tokens=8,
                               sampling=SamplingParams(temperature=0.8,
                                                       top_k=20)
                               if i % 2 else None)
                   for i in range(4)]
    solo.run()
    ref = {h.rid: list(h.request.out_tokens) for h in ref_handles}

    client = ServingClient(cfg, params, workers=2, spares=1, seed_base=7,
                           **ENG_KW)
    assert isinstance(client.router, FleetRouter)
    handles = [client.submit([3 + i, 5], max_new_tokens=8,
                             sampling=SamplingParams(temperature=0.8,
                                                     top_k=20)
                             if i % 2 else None)
               for i in range(4)]
    for _ in range(4):
        client.pump()
    client.router.workers[0].transport.kill()
    client.run()
    assert all(h.finished for h in handles)
    assert {h.rid: list(h.request.out_tokens) for h in handles} == ref
    assert client.router.fleet.workers_lost == 1
    assert "fleet:" in client.summary()
    client.router.close()

    with pytest.raises(ValueError, match="loopback fleets only"):
        ServingClient(cfg, params, workers=2, transport="socket", **ENG_KW)


# --------------------------------------------- socket workers (-k sock)
@pytest.mark.parametrize("arch", ["smollm-360m", "zamba2-7b"],
                         ids=["sock_dense", "sock_hybrid"])
def test_socket_sigkill_mid_decode_bit_identical(arch):
    """Real subprocess workers over TCP: SIGKILL one mid-decode; zero
    requests lost, all streams bit-identical to an undisturbed run."""
    cfg = ASSIGNED_ARCHS[arch].reduced()
    params = M.init_params(cfg, KEY, max_seq=ENG_KW["max_seq"])
    ref = _solo_ref(cfg, params, _reqs(4, max_new=8))
    fl = FleetRouter.build_socket(arch, workers=2, spares=0,
                                  checkpoint_every=3, migrate=False,
                                  max_batch=ENG_KW["max_batch"],
                                  max_seq=ENG_KW["max_seq"],
                                  page_size=ENG_KW["page_size"])
    try:
        reqs = _reqs(4, max_new=8)
        for r in reqs:
            fl.submit(r)
        steps, killed = 0, False
        while fl.has_work and steps < 500:
            fl.step()
            steps += 1
            if not killed and steps == 5:
                os.kill(fl.workers[0].transport.pid, signal.SIGKILL)
                killed = True
        assert all(r.done for r in reqs), \
            f"lost: {[r.rid for r in reqs if not r.done]}"
        assert {r.rid: list(r.out_tokens) for r in reqs} == ref
        assert fl.fleet.workers_lost == 1
    finally:
        fl.close()


def test_socket_worker_without_a_device_fails_fast(monkeypatch):
    """A worker whose backend cannot start exits at once, and its error is
    in the READY-wait failure, not a startup timeout later."""
    import time

    from repro.serving.fleet.transport import TransportError, spawn_worker

    monkeypatch.setenv("JAX_PLATFORMS", "cuda")  # no such backend here
    t0 = time.monotonic()
    with pytest.raises(TransportError) as e:
        spawn_worker("smollm-360m", startup_timeout_s=300.0)
    assert time.monotonic() - t0 < 60.0
    assert "exited with 1 before ready" in str(e.value)
    assert "acquiring a device" in str(e.value)


def test_socket_serve_parent_never_initialises_jax():
    """``serve --transport socket`` leaves the device to its workers: the
    parent builds prompts with numpy and never starts a JAX backend."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run([sys.executable, "-c", """
from repro.launch import serve
serve.main(['--requests', '2', '--max-new', '4', '--workers', '1',
            '--transport', 'socket', '--max-seq', '64'])
from jax._src import xla_bridge
assert not xla_bridge._backends, sorted(xla_bridge._backends)
print('parent clean')
"""], env=dict(os.environ, PYTHONPATH=os.path.join(repo, "src")),
        capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stdout + r.stderr
    assert "requests=2 tokens_out=8" in r.stdout
    assert "parent clean" in r.stdout


def test_socket_transport_survives_split_frames(smollm):
    """Socket-level framing adversity: a reply split across many tiny
    TCP segments reassembles; the decoder never delivers a torn frame."""
    # pure FrameDecoder drill at socket-realistic sizes: a big ndarray
    # reply chopped into 7-byte segments
    arr = np.arange(4096, dtype=np.float32).reshape(64, 64)
    f = wire.frame(wire.encode({"r": arr, "ok": True}))
    dec = wire.FrameDecoder()
    outs = []
    for i in range(0, len(f), 7):
        outs += dec.feed(f[i:i + 7])
    assert len(outs) == 1
    rep = wire.decode(outs[0])
    assert rep["ok"] is True and (rep["r"] == arr).all()


def test_transport_closed_after_kill(smollm):
    cfg, params = smollm
    t = LoopbackTransport(WorkerHost(EngineCore(cfg, params, **ENG_KW)))
    assert unwrap(t.call("ping", {})) == "worker"
    t.kill()
    with pytest.raises(TransportClosed):
        t.call("ping", {})
