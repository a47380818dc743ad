#!/usr/bin/env python3
"""Record the small TPU trace the trace-reduction test reads.

    python3 benchmarks/chip/tests/record_trace.py OUT_DIR

On one TPU: a window span holding six pump spans, each running a jitted
matmul (two of them a second, larger one, as a prefill would), with host
sleeps between and inside them, so the trace has device busy time, idle
gaps inside and outside pump spans, and two kinds of pump.  Prints each
plane and line with its event count, and the pump kinds in order.
"""

import os
import shutil
import sys
import time

import jax
import jax.numpy as jnp
from jax.profiler import ProfileData

out = sys.argv[1]
small = jax.jit(lambda x: jnp.tanh(x @ x))
big = jax.jit(lambda x: jnp.tanh(x @ x) @ x)
x = jnp.ones((2048, 2048), jnp.bfloat16)
small(x).block_until_ready()
big(x).block_until_ready()
tmp = os.path.join(out, "raw")
shutil.rmtree(tmp, ignore_errors=True)
kinds = ["decode", "prefill", "decode", "decode", "prefill", "decode"]
jax.profiler.start_trace(tmp)
with jax.profiler.TraceAnnotation("bench.window"):
    time.sleep(0.002)
    for kind in kinds:
        with jax.profiler.TraceAnnotation("bench.pump"):
            (big if kind == "prefill" else small)(x).block_until_ready()
            time.sleep(0.001)
            small(x).block_until_ready()
        time.sleep(0.003)
jax.profiler.stop_trace()
sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
import trace as trace_mod  # noqa: E402

path = trace_mod.find_xplane(tmp)
shutil.copy(path, os.path.join(out, "small.xplane.pb"))
pd = ProfileData.from_file(path)
for plane in pd.planes:
    print("plane", plane.name)
    for line in plane.lines:
        evs = list(line.events)
        print("  line", repr(line.name), len(evs),
              [(e.name, e.start_ns, e.duration_ns) for e in evs[:3]])
print("kinds", kinds)
print(trace_mod.reduce(pd, kinds))
