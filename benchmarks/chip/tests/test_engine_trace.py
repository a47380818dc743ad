"""The reduction by the program's own names (``engine_trace.py``): exact
numbers on a hand-built trace with engine spans and named programs."""

from types import SimpleNamespace as NS

import pytest

import engine_trace as E

LAUNCH = E._trace.LAUNCH
SKEW = 1000   # the device's clock runs this far ahead of the host's


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end, duration_ns=end - start)


# (program, start, end) on the host's clock, and each one's launch
MODULES = [("decode_paged", 130, 250, 130), ("sample", 250, 280, 145),
           ("argmax", 380, 382, 380), ("argmax", 388, 390, 388),
           ("prefill_slots", 530, 650, 525), ("sample", 650, 690, 545),
           ("decode_paged", 720, 800, 715), ("sample", 800, 840, 735)]

SPANS = [
    # a decode pump 100-400: one step, a collection after its emit
    ("bench.pump", 100, 400), ("engine.step", 110, 398),
    ("engine.admit", 110, 120), ("engine.decode", 120, 140),
    ("engine.sample", 140, 150), ("engine.readback", 150, 300),
    ("engine.emit", 300, 380), ("py.gc", 382, 388),
    # a prefill pump 500-900: admission's prefill, then the decode step
    ("bench.pump", 500, 900), ("engine.step", 510, 890),
    ("engine.admit", 510, 520), ("engine.prefill", 520, 540),
    ("engine.sample", 540, 550), ("engine.readback", 550, 700),
    ("engine.emit", 700, 710), ("engine.decode", 710, 730),
    ("engine.sample", 730, 740), ("engine.readback", 740, 850),
    ("engine.emit", 850, 880)]


def fake(modules=MODULES, spans=SPANS):
    """Window 0-1000 on the main thread, with the spans above; each
    program one op, run on the device at the times above."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 0, 1000)] + [ev(*s) for s in spans]
        + [ev(LAUNCH, t, t + 1) for *_, t in modules])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion", s + SKEW, e + SKEW)
                                   for _, s, e, _ in modules]),
        NS(name="XLA Modules", events=[
            ev(f"jit_{n}({i})", s + SKEW, e + SKEW)
            for i, (n, s, e, _) in enumerate(modules)])])
    return NS(planes=[host, dev])


def test_reduce_by_hand():
    t = E.reduce(fake(), ["decode", "prefill"])
    ns = 1e-9
    assert t.window_s == pytest.approx(1000 * ns)
    assert {k: v[0] for k, v in t.programs.items()} == {
        "decode_paged": 2, "sample": 3, "argmax": 2, "prefill_slots": 1}
    assert {k: v[1] for k, v in t.programs.items()} == pytest.approx({
        "decode_paged": 200 * ns, "sample": 110 * ns, "argmax": 4 * ns,
        "prefill_slots": 120 * ns})
    # busy 130-280, 380-382, 388-390, 530-690, 720-840
    assert [st[:2] for st in t.steps] == [
        pytest.approx((288 * ns, 134 * ns)),
        pytest.approx((380 * ns, 100 * ns))]
    assert [st[2:] for st in t.steps] == [(False, True), (True, True)]
    # count, total, self, device-idle in self: a step's self time is what
    # its children leave (380-382 and 388-398; 880-890)
    assert t.spans == {k: pytest.approx(v) for k, v in {
        "engine.step": [2, 668 * ns, 22 * ns, 18 * ns],
        "engine.admit": [2, 20 * ns, 20 * ns, 20 * ns],
        "engine.prefill": [1, 20 * ns, 20 * ns, 10 * ns],
        "engine.decode": [2, 40 * ns, 40 * ns, 20 * ns],
        "engine.sample": [3, 30 * ns, 30 * ns, 0.0],
        "engine.readback": [3, 410 * ns, 410 * ns, 40 * ns],
        "engine.emit": [3, 120 * ns, 120 * ns, 120 * ns],
        "py.gc": [1, 6 * ns, 6 * ns, 6 * ns]}.items()}
    # idle in pumps 146 + 120; of it inside the steps 134 + 100
    assert t.idle_in_pumps_s == pytest.approx(266 * ns)
    assert t.idle_in_spans_s == pytest.approx(234 * ns)
    assert E.span_share(t) == pytest.approx(100 * 234 / 266)
    # each gap by its midpoint: the pump's kind, then the innermost span
    assert [g for g, _ in t.gaps] == [
        "outside_pump", "outside_pump", "outside_pump",
        "pump.decode/engine.emit", "pump.prefill/engine.emit",
        "pump.decode/py.gc"]
    assert [s for _, s in t.gaps] == pytest.approx(
        [160 * ns, 140 * ns, 130 * ns, 100 * ns, 30 * ns, 6 * ns])
    # both decode programs start inside decode -> own readback
    assert t.aligned == 1.0


def test_metric_values():
    t = E.reduce(fake(), ["decode", "prefill"])
    assert E.program_ms(t, E.DECODE_PROGRAMS) == pytest.approx(100e-6)
    assert E.program_ms(t, E.PREFILL_PROGRAMS) == pytest.approx(120e-6)
    assert E.program_ms(t, E.SAMPLER_PROGRAMS) == pytest.approx(110e-6 / 3)
    assert E.program_ms(t, ("prefill_chunk",)) is None
    # one decode-only step, idle 134 ns
    assert E.host_gap_ms(t) == pytest.approx(134e-6)
    assert E.pad_share(14, 64) == pytest.approx(100 * 50 / 64)
    assert E.pad_share(0, 0) is None
    assert E.first_token_hold_ms([0.1, 0.3, 0.2]) == pytest.approx(200.0)
    assert E.first_token_hold_ms([]) is None
    assert "engine.readback" in E.table(t)


def test_alignment():
    dec = [("engine.decode", 0, 10), ("engine.readback", 20, 30),
           ("engine.decode", 40, 50), ("engine.readback", 60, 70)]
    # synchronous: a decode program belongs before its step's readback
    assert E._aligned([(5, 9, "decode_paged"), (45, 49, "decode_paged")],
                      dec) == 1.0
    assert E._aligned([(5, 9, "decode_paged"), (75, 79, "decode_paged")],
                      dec) == 0.5
    # fused: read back one step late, in the next decode's step
    assert E._aligned([(25, 35, "decode_sample_paged"),
                       (55, 65, "decode_sample_paged")], dec) == 1.0
    # programs and spans that cannot be paired give no reading
    assert E._aligned([(5, 9, "decode_paged")], dec) is None


def test_program_without_names():
    """An older program: anonymous modules, no engine spans.  Nothing to
    read, and no error."""
    mods = [("_lambda", s, e, t) for _, s, e, t in MODULES]
    t = E.reduce(fake(mods, [s for s in SPANS if s[0] == "bench.pump"]),
                 ["decode", "prefill"])
    assert set(t.programs) == {"_lambda"} and t.steps == [] and t.spans == {}
    assert t.aligned is None and E.host_gap_ms(t) is None
    assert E.program_ms(t, E.DECODE_PROGRAMS) is None
    assert {g.split("/")[0] for g, _ in t.gaps} <= {
        "outside_pump", "pump.decode", "pump.prefill"}
    assert E.span_share(t) == 0.0


def test_pump_count_must_match():
    with pytest.raises(ValueError):
        E.reduce(fake(), ["decode"])


def test_names_match_the_program():
    from repro.serving import spans
    assert E.SPANS == (spans.STEP, spans.ADMIT, spans.PREFILL, spans.DECODE,
                       spans.SAMPLE, spans.READBACK, spans.EMIT, spans.GC)
