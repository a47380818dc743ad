"""Names of the serving engine's host spans, and the garbage-collector span.

The engine marks its host work with ``jax.profiler.TraceAnnotation``: the
spans land on the profiler's host plane, on the same clock as the
executable launches the device trace is aligned to, so each span shares a
clock with the device programs it dispatches.  Outside a trace a span
costs about a microsecond and records nothing.

``STEP`` covers one ``EngineCore._advance``; the others are its children:

* ``ADMIT``    scheduler plan, page allocation, building the prompt arrays
* ``PREFILL``  the prefill program's dispatch (batched, or one chunk)
* ``DECODE``   the decode (or fused decode + sample) dispatch
* ``SAMPLE``   the sampler's dispatch
* ``READBACK`` the blocking host copy of the sampled tokens
* ``EMIT``     per-token bookkeeping after the readback: finish, release,
  the streamed events

``GC`` covers each garbage collection of the process (``gc.callbacks``,
installed once when this module is imported).
"""

from __future__ import annotations

import gc

from jax.profiler import TraceAnnotation as span

STEP = "engine.step"
ADMIT = "engine.admit"
PREFILL = "engine.prefill"
DECODE = "engine.decode"
SAMPLE = "engine.sample"
READBACK = "engine.readback"
EMIT = "engine.emit"
GC = "py.gc"

_open_gc: list = []


def _on_gc(phase: str, info: dict) -> None:
    if phase == "start":
        s = span(GC)
        s.__enter__()
        _open_gc.append(s)
    elif _open_gc:
        _open_gc.pop().__exit__(None, None, None)


gc.callbacks.append(_on_gc)
