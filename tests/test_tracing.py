"""The engine's tracing surface: stable program names, host spans on the
profiler's clock, and the prefill-pad and first-token-hold counters.

A device trace attributes time to programs by their XLA module name and to
host work by the engine's ``TraceAnnotation`` spans (``serving/spans.py``),
so a rename or a lost span must fail here rather than silently null a
measurement.  CPU only, toy widths.
"""

import gc
import glob
import os
import re

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from repro.serving import core, spans
from repro.serving.core import EngineCore, Request
from repro.serving.kv_cache import prefill_bucket
from repro.serving.scheduler import FCFSScheduler, SamplingParams

from conftest import load_family

CHILDREN = (spans.ADMIT, spans.PREFILL, spans.DECODE, spans.SAMPLE,
            spans.READBACK, spans.EMIT)


@pytest.fixture(scope="module")
def dense():
    return load_family("dense")


def _i32(*xs):
    return np.asarray(xs, np.int32)


def _program(name, cfg, params):
    """(jitted program, args, kwargs) exactly as the engine calls it."""
    b = 2
    cont = EngineCore(cfg, params, max_batch=b, max_seq=32, page_size=4,
                      mode="continuous")
    paged = {**cont.cache, "block": cont.block}
    active = np.ones((b,), bool)
    rows = (_i32(0, 1), _i32(0, 0), np.zeros((b,), np.float32),
            _i32(0, 0), np.ones((b,), np.float32))
    if name == "prefill_slots":
        return (core._jit_prefill_slots(cfg),
                (params, np.zeros((b, 8), np.int32), _i32(5, 3), paged,
                 _i32(0, 1)), {})
    if name == "prefill_chunk":
        return (core._jit_prefill_chunk(cfg),
                (params, np.zeros((8,), np.int32), np.int32(0), np.int32(5),
                 paged, np.int32(0)), {})
    if name == "decode_paged":
        return (core._jit_decode_paged(cfg),
                (params, cont.last_np, paged, active), {})
    if name == "decode_sample_paged":
        return (core._jit_decode_sample_paged(cfg),
                (params, cont.last_np, cont.last_np, active, paged, active,
                 *rows), {"greedy_only": False})
    if name == "sample":
        return (core._jit_sample,
                (np.zeros((b, cfg.vocab_size), np.float32), *rows), {})
    pids = _i32(1, 2)
    if name == "swap_out_pages":
        return core._jit_swap_out, (cont.cache, pids), {}
    if name == "swap_in_pages":
        comps = jax.eval_shape(core._jit_swap_out, cont.cache, pids)
        return (core._jit_swap_in,
                (cont.cache, pids, *(np.zeros(c.shape, c.dtype)
                                     for c in comps)), {})
    wave = EngineCore(cfg, params, max_batch=b, max_seq=32, mode="wave")
    if name == "prefill":
        return (core._jit_prefill(cfg),
                (params, np.zeros((b, 4), np.int32), wave.cache),
                {"batch": b})
    if name == "decode":
        return core._jit_decode(cfg), (params, wave.last_token,
                                       wave.cache), {}
    assert name == "decode_sample"
    return (core._jit_decode_sample(cfg),
            (params, wave._wave_last_np, wave._wave_last_np, active,
             wave.cache, *rows), {"greedy_only": True})


@pytest.mark.parametrize("name", [
    "prefill_slots", "prefill_chunk", "decode_paged", "decode_sample_paged",
    "sample", "swap_out_pages", "swap_in_pages", "prefill", "decode",
    "decode_sample"])
def test_program_lowers_to_its_name(dense, name):
    cfg, params = dense
    fn, args, kw = _program(name, cfg, params)
    text = fn.lower(*args, **kw).as_text()
    assert re.search(rf"^module @jit_{name}\b", text, re.M), text[:200]


# ---------------------------------------------------------------- spans
def _reqs(n=3, max_new=5):
    return [Request(rid=i, prompt=[3 + i, 5, 7, 2, 9][:3 + i % 3],
                    max_new_tokens=max_new,
                    sampling=(SamplingParams(temperature=0.8, seed=i)
                              if i % 2 else None))
            for i in range(n)]


def _serve(eng, reqs):
    """Submit ``reqs`` one step apart (a prefill lands beside decode
    steps) and step until done; returns the number of steps."""
    pending, steps = list(reqs), 0
    while pending or eng.has_work:
        if pending:
            eng.add_request(pending.pop(0))
        eng.step()
        steps += 1
    return steps


def _traced_spans(tmp_path, fn):
    """Run ``fn`` under the profiler; return the host line's spans whose
    names the engine owns, as sorted (name, start, end)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(tmp_path), "plugins", "profile", "*",
                                   "*.xplane.pb"))
    names = set(CHILDREN) | {spans.STEP, spans.GC}
    out = []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                out += [(e.name, e.start_ns, e.end_ns) for e in line.events
                        if e.name in names]
    return sorted(out, key=lambda s: (s[1], -s[2]))


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_step_spans_nest(dense, tmp_path, overlap):
    cfg, params = dense
    kw = dict(max_batch=2, max_seq=32, page_size=4, eos_id=-1,
              overlap=overlap)
    _serve(EngineCore(cfg, params, **kw), _reqs())   # compile untraced
    eng = EngineCore(cfg, params, **kw)
    holder = {}
    got = _traced_spans(tmp_path,
                        lambda: holder.update(steps=_serve(eng, _reqs())))
    steps = [s for s in got if s[0] == spans.STEP]
    assert len(steps) == holder["steps"]
    kids = {s: [c for c in got if c[0] in CHILDREN
                and s[1] <= c[1] and c[2] <= s[2]] for s in steps}
    # every child lies inside a step, and a step's children do not overlap
    assert sum(len(v) for v in kids.values()) == sum(
        1 for c in got if c[0] in CHILDREN)
    for v in kids.values():
        for a, b in zip(v, v[1:]):
            assert a[2] <= b[1], (a, b)
    st = eng.stats
    decodes = [sum(c[0] == spans.DECODE for c in v) for v in kids.values()]
    assert max(decodes) == 1 and sum(decodes) == st.decode_steps > 0
    # one readback per decode step, plus one per prefill's first tokens
    reads = sum(c[0] == spans.READBACK for v in kids.values() for c in v)
    assert reads == st.decode_steps + st.prefills
    assert sum(c[0] == spans.PREFILL for v in kids.values()
               for c in v) == st.prefills > 0
    if not overlap:
        for v in kids.values():
            names = [c[0] for c in v]
            if spans.DECODE in names and spans.PREFILL not in names:
                assert names == [spans.ADMIT, spans.DECODE, spans.SAMPLE,
                                 spans.READBACK, spans.EMIT], names


def test_gc_span(tmp_path):
    got = _traced_spans(tmp_path, gc.collect)
    assert got and {s[0] for s in got} == {spans.GC}
    assert all(end > start for _, start, end in got)


# ------------------------------------------------------------- counters
@pytest.mark.parametrize("chunk", [None, 4], ids=["batched", "chunked"])
def test_prefill_pad_counters(dense, chunk):
    cfg, params = dense
    sched = FCFSScheduler(chunk_tokens=chunk) if chunk else None
    eng = EngineCore(cfg, params, max_batch=4, max_seq=64, page_size=4,
                     eos_id=-1, scheduler=sched)
    lens = (5, 10)
    for rid, n in enumerate(lens):
        eng.add_request(Request(rid=rid, prompt=list(range(2, 2 + n)),
                                max_new_tokens=2))
    while eng.has_work:
        eng.step()
    st = eng.stats
    assert st.prefill_tokens == sum(lens)
    if chunk is None:
        # one group: 4 rows (two repeats of row 0) x the bucket of 10
        assert st.prefills == 1
        pad = 4 * prefill_bucket(max(lens)) - sum(lens)
    else:
        # chunks of at most 4, each padded to the page size (4): 5 -> 4+1,
        # 10 -> 4+4+2
        assert st.prefill_chunks == 5
        pad = 5 * 4 - sum(lens)
    assert st.prefill_computed_tokens - st.prefill_tokens == pad
    assert "pad=" in st.summary()


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_decode_kv_page_counters(dense, overlap):
    """Hand count, pages of 4, 8 pages a slot: prompts of 5 and 3 tokens,
    4 new tokens each (the first from the prefill), so three decode steps
    attend 6, 7, 8 and 4, 5, 6 positions: 2+2+2 and 1+2+2 live pages
    against 2 slots x 8 pages a step.  The overlapped loop dispatches each
    step one token ahead of its drain and must count the same."""
    cfg, params = dense
    eng = EngineCore(cfg, params, max_batch=2, max_seq=32, page_size=4,
                     eos_id=-1, overlap=overlap)
    for rid, n in enumerate((5, 3)):
        eng.add_request(Request(rid=rid, prompt=list(range(2, 2 + n)),
                                max_new_tokens=4))
    while eng.has_work:
        eng.step()
    st = eng.stats
    assert st.decode_steps == 3
    assert st.decode_kv_live_pages == 6 + 5
    assert st.decode_kv_table_pages == 3 * 2 * 8
    assert "kv pages live=11/48 (22.9%)" in st.summary()


@pytest.mark.parametrize("overlap", [False, True], ids=["sync", "overlap"])
def test_first_token_hold(dense, overlap):
    cfg, params = dense
    eng = EngineCore(cfg, params, max_batch=2, max_seq=32, page_size=4,
                     eos_id=-1, overlap=overlap)
    reqs = _reqs(n=4, max_new=3)
    _serve(eng, reqs)
    hold = eng.stats.first_token_hold_s
    assert len(hold) == len(reqs)
    assert all(h >= 0 for h in hold)
    for r in reqs:
        assert r.t_first_out >= r.t_first_token > 0
    assert "first-token hold" in eng.stats.summary()
