"""Reduction of one profiler trace by the serving program's own names: its
host spans (``repro/serving/spans.py``) and its programs' XLA module names
(``jit_<name>``, ``repro/serving/core.py``).

``trace.py`` reads the trace from outside the program: device busy time
inside the benchmark's ``bench.pump`` spans.  This module reads it from
inside: device time per named program, device-idle time per ``engine.step``
span, each idle gap's innermost program span, a self-time table of the
spans, and a check that the device's clock was aligned to the host's (the
share of decode programs that start inside their own ``engine.decode`` ->
``engine.readback`` interval).  Spans and programs are read inside the
``bench.window`` span, on the host thread that ran it, for one device.

A program without these names (an older checkout) yields empty tables and
``None`` metrics, never an error.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import statistics

import run as _run

_trace = _run.load_file("trace.py", "bench_trace")

# the program's span names (repro/serving/spans.py), and the module names
# of its programs (repro/serving/core.py), without the "jit_" prefix
STEP = "engine.step"
PREFILL = "engine.prefill"
DECODE = "engine.decode"
READBACK = "engine.readback"
SPANS = (STEP, "engine.admit", PREFILL, DECODE, "engine.sample", READBACK,
         "engine.emit", "py.gc")
DECODE_PROGRAMS = ("decode_paged", "decode_sample_paged", "decode",
                   "decode_sample")
FUSED = ("decode_sample_paged", "decode_sample")   # read back a step late
PREFILL_PROGRAMS = ("prefill_slots", "prefill")
SAMPLER_PROGRAMS = ("sample",)


@dataclasses.dataclass
class EngineTrace:
    window_s: float
    programs: dict         # name -> [calls, device seconds]
    steps: list            # per engine.step: (s, idle s, prefill?, decode?)
    spans: dict            # name -> [count, total s, self s, idle s in self]
    idle_in_pumps_s: float
    idle_in_spans_s: float  # the part of idle_in_pumps_s inside a span
    gaps: list             # [(tag, seconds)] every idle gap, longest first
    aligned: float | None  # share of decode programs inside their interval


def module_name(event_name: str) -> str:
    """``jit_decode_paged(123456)`` -> ``decode_paged``."""
    name = event_name.split("(", 1)[0]
    return name[4:] if name.startswith("jit_") else name


def _window_line(pd):
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == _trace.WINDOW:
                    return line, (ev.start_ns, ev.end_ns)
    raise ValueError(f"trace has no {_trace.WINDOW!r} span")


def _device(pd):
    """(ops, modules) of the one device plane that ran operations, on the
    host's clock, aligned as ``trace.py`` aligns them."""
    launches = sorted(s for s, _ in _trace._host_spans(pd, _trace.LAUNCH))
    found = []
    for plane in pd.planes:
        if not plane.name.startswith("/device:") or "CPU" in plane.name:
            continue
        ops = [(ev.start_ns, ev.end_ns) for line in plane.lines
               if line.name in _trace.OPS_LINES for ev in line.events]
        if ops:
            found.append((plane, ops))
    if len(found) != 1:
        raise ValueError(f"{len(found)} devices ran operations; the engine's "
                         f"spans are read against one")
    plane, ops = found[0]
    off = _trace._clock_offset(plane, launches)
    modules = sorted((ev.start_ns - off, ev.end_ns - off,
                      module_name(ev.name)) for line in plane.lines
                     if line.name == _trace.MODULES_LINE
                     for ev in line.events)
    return [(s - off, e - off) for s, e in ops], modules


def _parents(spans: list) -> list:
    """Index of each span's innermost enclosing span (-1 for none); spans
    sorted by (start, -end) nest, being one thread's."""
    parent, stack = [], []
    for i, (_, s, e) in enumerate(spans):
        while stack and spans[stack[-1]][2] <= s:
            stack.pop()
        parent.append(stack[-1] if stack else -1)
        stack.append(i)
    return parent


def _innermost(spans, starts, parent, t):
    i = bisect.bisect_right(starts, t) - 1
    while i >= 0 and spans[i][2] <= t:
        i = parent[i]
    return i


def _aligned(modules, spans):
    decode = [m for m in modules if m[2] in DECODE_PROGRAMS]
    dspans = [sp for sp in spans if sp[0] == DECODE]
    reads = [sp for sp in spans if sp[0] == READBACK]
    if not decode or len(decode) != len(dspans):
        return None
    rstarts = [sp[1] for sp in reads]
    hit = 0
    for k, ((m0, _, name), (_, d0, d1)) in enumerate(zip(decode, dspans)):
        if name in FUSED and k + 1 < len(dspans):
            d1 = dspans[k + 1][2]
        j = bisect.bisect_left(rstarts, d1)
        end = reads[j][2] if j < len(reads) else float("inf")
        hit += d0 <= m0 <= end
    return hit / len(decode)


def reduce(pd, kinds: list[str]) -> EngineTrace:
    """``pd`` a ``jax.profiler.ProfileData``; ``kinds`` the harness's kind
    of each pump, in order (as for ``trace.reduce``)."""
    line, (w0, w1) = _window_line(pd)
    events = [(ev.name, ev.start_ns, ev.end_ns) for ev in line.events
              if w0 <= ev.start_ns and ev.end_ns <= w1]
    pumps = sorted((s, e) for n, s, e in events if n == _trace.PUMP)
    if len(pumps) != len(kinds):
        raise ValueError(f"trace holds {len(pumps)} pump spans in the "
                         f"window, the harness ran {len(kinds)} pumps")
    spans = sorted((ev for ev in events if ev[0] in SPANS),
                   key=lambda sp: (sp[1], -sp[2]))
    ops, modules = _device(pd)
    busy = _trace.union((max(s, w0), min(e, w1)) for s, e in ops
                        if e > w0 and s < w1)
    modules = [m for m in modules if w0 <= m[0] < w1]

    programs = collections.defaultdict(lambda: [0, 0.0])
    for s, e, name in modules:
        programs[name][0] += 1
        programs[name][1] += (e - s) / 1e9

    parent = _parents(spans)
    dur = [e - s for _, s, e in spans]
    idle = [d - _trace.overlap(busy, s, e) for d, (_, s, e) in zip(dur, spans)]
    self_t, self_idle = list(dur), list(idle)
    names_below = [set() for _ in spans]
    for i in reversed(range(len(spans))):
        p = parent[i]
        if p >= 0:
            self_t[p] -= dur[i]
            self_idle[p] -= idle[i]
            names_below[p] |= names_below[i] | {spans[i][0]}
    table = collections.defaultdict(lambda: [0, 0.0, 0.0, 0.0])
    steps = []
    for i, (name, _, _) in enumerate(spans):
        row = table[name]
        row[0] += 1
        row[1] += dur[i] / 1e9
        row[2] += self_t[i] / 1e9
        row[3] += self_idle[i] / 1e9
        if name == STEP:
            steps.append((dur[i] / 1e9, idle[i] / 1e9,
                          PREFILL in names_below[i], DECODE in names_below[i]))

    covered = _trace.union((s, e) for _, s, e in spans)
    idle_pumps = idle_spans = 0.0
    for a, b in pumps:
        idle_pumps += b - a - _trace.overlap(busy, a, b)
        i = max(bisect.bisect_right(covered, (a, float("inf"))) - 1, 0)
        while i < len(covered) and covered[i][0] < b:
            s, e = max(covered[i][0], a), min(covered[i][1], b)
            if e > s:
                idle_spans += e - s - _trace.overlap(busy, s, e)
            i += 1

    gaps = []
    pstarts = [s for s, _ in pumps]
    sstarts = [s for _, s, _ in spans]
    edges = [w0] + [x for iv in busy for x in iv] + [w1]
    for a, b in zip(edges[0::2], edges[1::2]):
        if b <= a:
            continue
        mid = (a + b) / 2
        i = bisect.bisect_right(pstarts, mid) - 1
        tag = (f"pump.{kinds[i]}" if i >= 0 and pumps[i][1] > mid
               else _trace.OUTSIDE)
        j = _innermost(spans, sstarts, parent, mid)
        if j >= 0:
            tag += "/" + spans[j][0]
        gaps.append((tag, (b - a) / 1e9))
    gaps.sort(key=lambda g: -g[1])
    return EngineTrace(
        window_s=(w1 - w0) / 1e9, programs=dict(programs), steps=steps,
        spans=dict(table), idle_in_pumps_s=idle_pumps / 1e9,
        idle_in_spans_s=idle_spans / 1e9, gaps=gaps,
        aligned=_aligned(modules, spans))


# ---------------------------------------------------------------------
# the numbers a per-layer metric would read
# ---------------------------------------------------------------------
def program_ms(t: EngineTrace, names) -> float | None:
    """Mean device milliseconds per call of the programs ``names``."""
    calls = sum(t.programs.get(n, (0, 0.0))[0] for n in names)
    secs = sum(t.programs.get(n, (0, 0.0))[1] for n in names)
    return 1e3 * secs / calls if calls else None


def host_gap_ms(t: EngineTrace) -> float | None:
    """Median device-idle milliseconds inside the engine steps that ran a
    decode and no prefill."""
    idle = [i for _, i, prefill, decode in t.steps if decode and not prefill]
    return 1e3 * statistics.median(idle) if idle else None


def pad_share(tokens: int, computed: int) -> float | None:
    """Share of the prefilled positions that were padding, %."""
    return 100.0 * (computed - tokens) / computed if computed else None


def first_token_hold_ms(holds: list) -> float | None:
    return 1e3 * statistics.median(holds) if holds else None


def span_share(t: EngineTrace) -> float | None:
    """Share of the device-idle time inside pumps that lies inside a
    program span, %."""
    if t.idle_in_pumps_s <= 0:
        return None
    return 100.0 * t.idle_in_spans_s / t.idle_in_pumps_s


def table(t: EngineTrace) -> str:
    """Self time per span and device time per program, for standard
    error."""
    out = [f"{'span':<18}{'count':>8}{'total ms':>12}{'self ms':>12}"
           f"{'idle ms':>12}"]
    for name in SPANS:
        if name in t.spans:
            n, tot, own, idle = t.spans[name]
            out.append(f"{name:<18}{n:>8}{tot * 1e3:>12.1f}{own * 1e3:>12.1f}"
                       f"{idle * 1e3:>12.1f}")
    out.append(f"{'program':<26}{'calls':>8}{'device ms':>12}{'ms/call':>10}")
    for name, (n, secs) in sorted(t.programs.items(),
                                  key=lambda kv: -kv[1][1]):
        out.append(f"{name:<26}{n:>8}{secs * 1e3:>12.1f}"
                   f"{secs * 1e3 / n:>10.3f}")
    share = span_share(t)
    out.append(f"aligned {t.aligned}; idle in pumps {t.idle_in_pumps_s:.4f} s"
               f", {share if share is None else round(share, 2)}% inside a "
               f"program span")
    return "\n".join(out)
