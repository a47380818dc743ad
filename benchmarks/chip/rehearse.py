#!/usr/bin/env python3
"""Compile rehearsal: each configuration's prefill and decode step
programs, compiled for a described TPU v5e (``v5e:2x2``, one chip of it)
without a chip attached, with their memory analysis.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py [config ...]

It compiles the serving program's own jitted steps (``serving/core.py``)
at the configuration's widths and capacity: the batched prefill at the
largest bucket the benchmark's mixes reach (``max_seq``), and the paged
decode step.  Nothing runs; this says whether the programs compile and fit,
not how fast they are.
"""

from __future__ import annotations

import os
import sys

os.environ.setdefault("TPU_LOG_DIR", "disabled")
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))
sys.path.insert(0, HERE)


def rehearse(name: str, topo) -> None:
    import importlib
    import json
    import time

    import jax
    import jax.numpy as jnp
    from jax.sharding import SingleDeviceSharding

    import weights as W
    from repro.models import model as model_lib
    from repro.quant.convert import quantize_params
    from repro.serving import core

    with open(os.path.join(HERE, "configs", f"{name}.json")) as f:
        c = json.load(f)
    e = c["engine"]
    model = importlib.import_module(f"models.{c['model_type']}")
    pcfg = model.program_config(c)
    one = SingleDeviceSharding(topo.devices[0])

    def on_chip(tree):
        return jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one),
            tree)

    tensors = model.tensors(c)
    params = on_chip(jax.eval_shape(
        lambda k: quantize_params(model.to_program(
            c, W.make_all(tensors, k)), mode=e["quant"]),
        jax.random.PRNGKey(0)))
    b, s, page = e["max_batch"], e["max_seq"], e["page_size"]
    n_pages = b * (s // page) + 1
    cache = jax.eval_shape(lambda: model_lib.init_paged_cache(
        pcfg, b, s, page_size=page, num_pages=n_pages,
        kv_dtype=e["kv_dtype"]))
    cache = on_chip(cache)
    i32 = jnp.int32
    progs = {
        "prefill": (core._jit_prefill_slots(pcfg), (
            params, jax.ShapeDtypeStruct((b, s), i32, sharding=one),
            jax.ShapeDtypeStruct((b,), i32, sharding=one), cache,
            jax.ShapeDtypeStruct((b,), i32, sharding=one))),
        "decode": (core._jit_decode_paged(pcfg), (
            params, jax.ShapeDtypeStruct((b,), i32, sharding=one), cache,
            jax.ShapeDtypeStruct((b,), jnp.bool_, sharding=one))),
    }
    for prog, (fn, args) in progs.items():
        t0 = time.perf_counter()
        compiled = fn.lower(*args).compile()
        m = compiled.memory_analysis()
        gb = 1e9
        print(f"{name} {prog}: compiled in {time.perf_counter() - t0:.1f} s;"
              f" arguments {m.argument_size_in_bytes / gb:.2f} GB, output "
              f"{m.output_size_in_bytes / gb:.2f} GB, temporaries "
              f"{m.temp_size_in_bytes / gb:.2f} GB, aliased "
              f"{m.alias_size_in_bytes / gb:.2f} GB", flush=True)


def main() -> None:
    import jax
    from jax.experimental import topologies

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    names = sys.argv[1:] or sorted(
        f[:-5] for f in os.listdir(os.path.join(HERE, "configs")))
    for name in names:
        rehearse(name, topo)


if __name__ == "__main__":
    main()
