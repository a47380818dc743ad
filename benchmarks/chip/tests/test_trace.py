"""The trace reduction: exact numbers on a hand-built trace, and sane ones
on a small trace recorded on one TPU v5e (``tests/record_trace.py``)."""

import os
from types import SimpleNamespace as NS

import pytest

import run

trace = run.load_file("trace.py", "bench_trace")
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data",
                    "small.xplane.pb")


def ev(name, start, end):
    return NS(name=name, start_ns=start, end_ns=end, duration_ns=end - start)


def fake(modules=(("jit_step", 15, 45), ("jit_step", 55, 95))):
    """Window 0-100; pumps 10-40 (decode) and 50-90 (prefill), each
    launching one program (at 14 and 54 on the host's clock, which the
    device's runs 1 ahead of); device ops at 15-25, 20-30 (overlapping),
    55-85 and 92-95 on the host's clock; times in ns."""
    host = NS(name="/host:CPU", lines=[NS(name="python3", events=[
        ev("bench.window", 0, 100), ev("bench.pump", 10, 40),
        ev("bench.pump", 50, 90), ev(trace.LAUNCH, 14, 16),
        ev(trace.LAUNCH, 54, 56)])])
    dev = NS(name="/device:TPU:0", lines=[
        NS(name="XLA Ops", events=[ev("fusion", 16, 26), ev("dot", 21, 31),
                                   ev("dot", 56, 86), ev("copy", 93, 96)]),
        NS(name="XLA Modules", events=[ev(n, s, e) for n, s, e in modules])])
    return NS(planes=[host, dev])


def test_union_and_overlap():
    assert trace.union([(5, 7), (0, 2), (1, 3), (7, 9)]) == [(0, 3), (5, 9)]
    merged = [(0, 3), (5, 9)]
    assert trace.overlap(merged, 2, 6) == 2
    assert trace.overlap(merged, 10, 20) == 0


def test_reduce_by_hand():
    r = trace.reduce(fake(), ["decode", "prefill"])
    ns = 1e-9
    assert r.window_s == pytest.approx(100 * ns)
    # busy: 15-30 (15) + 55-85 (30) + 92-95 (3) = 48
    assert r.busy_s == pytest.approx(48 * ns)
    assert r.busy_by_kind == pytest.approx({"decode": 15 * ns,
                                            "prefill": 30 * ns})
    assert r.busy_in_pumps == pytest.approx([15 * ns, 30 * ns])
    # idle inside pumps: (30 - 15) + (40 - 30) = 25
    assert r.idle_in_pumps_s == pytest.approx(25 * ns)
    # gaps: 0-15 outside (15), 30-55 (25, midpoint 42.5 outside pumps),
    # 85-92 (7, midpoint 88.5 in the prefill pump), 95-100 outside (5)
    assert [t for t, _ in r.idle_gaps] == [
        "outside_pump", "outside_pump", "pump.prefill", "outside_pump"]
    assert [s for _, s in r.idle_gaps] == pytest.approx(
        [25 * ns, 15 * ns, 7 * ns, 5 * ns])
    assert dict(r.top_ops) == pytest.approx({"dot": 40 * ns,
                                             "fusion": 10 * ns,
                                             "copy": 3 * ns})


def test_dropped_program_event_is_refused():
    """With one device program missing, launches and programs cannot be
    paired, so the clocks cannot be aligned: no numbers, an error."""
    with pytest.raises(ValueError, match="cannot be aligned"):
        trace.reduce(fake(modules=[("jit_step", 55, 95)]), ["decode",
                                                             "prefill"])


def test_pump_count_must_match():
    with pytest.raises(ValueError):
        trace.reduce(fake(), ["decode"])


@pytest.mark.skipif(not os.path.exists(DATA), reason="no recorded trace")
def test_recorded_tpu_trace():
    from jax.profiler import ProfileData
    kinds = ["decode", "prefill", "decode", "decode", "prefill", "decode"]
    r = trace.reduce(ProfileData.from_file(DATA), kinds)
    assert r.devices == 1 and r.pumps == 6
    assert 0 < r.busy_s < r.window_s
    assert r.count_by_kind == {"decode": 4, "prefill": 2}
    # a prefill pump runs the larger program: more busy time per pump
    assert (r.busy_by_kind["prefill"] / 2 > r.busy_by_kind["decode"] / 4)
    # every device op ran inside a pump span, so the host sleeps between
    # pumps show as idle outside them, and the one inside each pump as
    # idle in it
    assert sum(r.busy_in_pumps) == pytest.approx(r.busy_s, rel=1e-6)
    assert r.idle_in_pumps_s > 0
    assert {t for t, _ in r.idle_gaps} <= {"outside_pump", "pump.decode",
                                          "pump.prefill"}
    assert r.top_ops
