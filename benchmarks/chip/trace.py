"""Reduction of one profiler trace to the numbers the per-layer metrics
read: device busy time (the union of the intervals in which an operation
ran), idle gaps tagged by what the host was doing, busy time per pump kind,
and the operations that took the most time.

The host spans are the benchmark's own ``TraceAnnotation``s: one
``bench.window`` around the measured window and one ``bench.pump`` around
each ``client.pump()``.  The harness records each pump's kind (decode only,
or with a prefill) in order, and the n-th ``bench.pump`` span is the n-th
pump.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os

WINDOW = "bench.window"
PUMP = "bench.pump"
OPS_LINES = ("XLA Ops",)
MODULES_LINE = "XLA Modules"
LAUNCH = "PJRT_LoadedExecutable_Execute"
OUTSIDE = "outside_pump"
NAME_CHARS = 160  # an op's name is its HLO text; the head says what it is


def find_xplane(log_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return paths[-1]


def union(intervals) -> list[tuple[float, float]]:
    """Merge [start, end) intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def overlap(merged: list[tuple[float, float]], a: float, b: float) -> float:
    """Length of [a, b) covered by the disjoint sorted ``merged``."""
    i = max(bisect.bisect_right(merged, (a, float("inf"))) - 1, 0)
    total = 0.0
    while i < len(merged) and merged[i][0] < b:
        s, e = merged[i]
        total += max(0.0, min(e, b) - max(s, a))
        i += 1
    return total


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float              # mean over the devices that ran anything
    devices: int
    pumps: int
    busy_by_kind: dict         # kind -> device-busy seconds inside pumps
    count_by_kind: dict        # kind -> number of pumps
    busy_in_pumps: list        # per pump, device-busy seconds inside it
    idle_in_pumps_s: float     # mean over devices, idle inside pump spans
    top_ops: list              # [(name, seconds)] summed over devices
    idle_gaps: list            # [(tag, seconds)] longest first


def _host_spans(pd, name):
    spans = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == name:
                    spans.append((ev.start_ns, ev.end_ns))
    return sorted(spans)


def _clock_offset(plane, launches) -> float:
    """How far the device's clock runs ahead of the host's, in ns.

    The k-th program the device ran is the k-th the host launched, and no
    program starts before its launch; the smallest (device start - host
    launch) over all of them is taken as the clocks' offset, so the
    quickest start lags its launch by nothing.  Where the two counts
    differ (the profiler dropped events) the programs cannot be paired,
    and the trace is refused rather than read unaligned."""
    modules = sorted(ev.start_ns for line in plane.lines
                     if line.name == MODULES_LINE for ev in line.events)
    if not modules or len(modules) != len(launches):
        raise ValueError(f"{plane.name}: {len(modules)} programs ran on "
                         f"the device, the host launched {len(launches)}; "
                         f"device and host clocks cannot be aligned")
    return min(m - h for m, h in zip(modules, launches))


def _device_ops(pd):
    """{device plane name: [(op name, start_ns, end_ns)]} on the host's
    clock, for each device plane that ran operations."""
    launches = sorted(s for s, _ in _host_spans(pd, LAUNCH))
    out = {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        ops = [(ev.name, ev.start_ns, ev.end_ns)
               for line in plane.lines if line.name in OPS_LINES
               for ev in line.events]
        if ops:
            off = _clock_offset(plane, launches)
            out[plane.name] = [(n, s - off, e - off) for n, s, e in ops]
    return out


def reduce(pd, kinds: list[str], top: int = 10) -> Reduced:
    """``pd`` a ``jax.profiler.ProfileData``; ``kinds`` the harness's kind
    of each pump, in order."""
    windows = _host_spans(pd, WINDOW)
    if not windows:
        raise ValueError(f"trace has no {WINDOW!r} span")
    w0, w1 = windows[0]
    pumps = [(s, e) for s, e in _host_spans(pd, PUMP) if s >= w0 and e <= w1]
    if len(pumps) != len(kinds):
        raise ValueError(f"trace holds {len(pumps)} pump spans in the "
                         f"window, the harness ran {len(kinds)} pumps")
    devices = _device_ops(pd)
    if not devices:
        raise ValueError("trace has no device operations")
    busy_total, idle_in = 0.0, 0.0
    busy_by_kind = collections.Counter()
    busy_in = [0.0] * len(pumps)
    op_time = collections.Counter()
    gaps = []
    starts = [s for s, _ in pumps]
    for ops in devices.values():
        clipped = [(max(s, w0), min(e, w1)) for _, s, e in ops
                   if e > w0 and s < w1]
        merged = union(clipped)
        busy_total += sum(e - s for s, e in merged)
        for name, s, e in ops:
            if e > w0 and s < w1:
                op_time[name[:NAME_CHARS]] += min(e, w1) - max(s, w0)
        for i, ((s, e), kind) in enumerate(zip(pumps, kinds)):
            b = overlap(merged, s, e)
            busy_in[i] += b / len(devices)
            busy_by_kind[kind] += b / len(devices)
            idle_in += (e - s - b) / len(devices)
        edges = [w0] + [x for iv in merged for x in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                mid = (a + b) / 2
                i = bisect.bisect_right(starts, mid) - 1
                inside = i >= 0 and pumps[i][1] > mid
                tag = f"pump.{kinds[i]}" if inside else OUTSIDE
                gaps.append((tag, (b - a) / 1e9))
    ns = 1e9
    gaps.sort(key=lambda g: -g[1])
    return Reduced(
        window_s=(w1 - w0) / ns, busy_s=busy_total / len(devices) / ns,
        devices=len(devices), pumps=len(pumps),
        busy_by_kind={k: v / ns for k, v in busy_by_kind.items()},
        count_by_kind=dict(collections.Counter(kinds)),
        busy_in_pumps=[b / ns for b in busy_in],
        idle_in_pumps_s=idle_in / ns,
        top_ops=[(n, t / ns) for n, t in op_time.most_common(top)],
        idle_gaps=gaps[:top])


def read(log_dir: str, kinds: list[str]) -> Reduced:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(log_dir)), kinds)
