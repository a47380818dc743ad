import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# a virtual-CPU-mesh analysis: never claim an accelerator, which belongs to
# one process at a time (dryrun_all runs many of these side by side)
os.environ["JAX_PLATFORMS"] = "cpu"

# NOTE: the lines above MUST precede every other import (jax locks the
# device count and platform on first init), which is why __future__ imports
# are omitted.

DOC = """Multi-pod dry-run: lower + compile every (arch × shape × mesh) cell.

For each cell:
  1. FULL compile on the production mesh — proves the sharding config is
     coherent and memory fits (compiled.memory_analysis()).  This is the
     pass/fail deliverable.
  2. Shallow UNROLLED cost variants (per-layer-exact; while-loop bodies are
     otherwise counted once by XLA cost analysis) — lowered, compiled, and
     linearly extrapolated to the full depth for §Roofline terms.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch smollm-360m \
      --shape decode_32k --mesh single --out experiments/dryrun
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES, InputShape, ModelConfig
from repro.configs.registry import get_arch
from repro.distributed import ctx as dctx
from repro.distributed import sharding as shd
from repro.launch import specs as specs_lib
from repro.launch.hlo_stats import collective_bytes, link_traffic_bytes
from repro.launch.mesh import make_production_mesh
from repro.models import model as model_lib
from repro.training.train_loop import make_train_step
from repro.training.optimizer import init_adamw

TRAIN_MICROBATCHES = 8


# ---------------------------------------------------------------------------
# step builders
# ---------------------------------------------------------------------------


def build_step(cfg: ModelConfig, shape: InputShape, mesh, microbatches: int,
               layout: str = "zero3"):
    """Returns (jitted_fn, arg_specs_tuple).  layout: "zero3" (paper-faithful
    streaming tier) or "tp" (ship-activations residency, §Perf)."""
    dp = shd.dp_axes(mesh)
    quant = shape.kind != "train"
    max_seq = shape.seq_len if shape.kind != "train" else shape.seq_len
    pspecs = specs_lib.param_specs(cfg, max_seq=max_seq, quant=quant,
                                   dtype=jnp.bfloat16)
    pshard = shd.params_shardings(pspecs, mesh, zero3=(layout == "zero3"))
    inputs = specs_lib.input_specs(cfg, shape)

    if shape.kind == "train":
        ospec = jax.eval_shape(init_adamw, pspecs)
        oshard = shd.params_shardings(ospec, mesh,
                                      zero3=(layout == "zero3"))

        # optimizer state: mu/nu follow param sharding; step replicated
        oshard = dataclasses.replace(
            oshard,
            step=shd.replicated(mesh)) if dataclasses.is_dataclass(oshard) \
            else oshard
        tok_shard = NamedSharding(
            mesh, shd.batch_pspec(mesh, shape.global_batch, 2))
        extras_shard = jax.tree.map(
            lambda s: NamedSharding(
                mesh, shd.batch_pspec(mesh, shape.global_batch, s.ndim)),
            inputs["extras"])
        step = make_train_step(cfg, microbatches=microbatches)

        def fn(params, opt_state, tokens, extras):
            return step(params, opt_state, tokens,
                        extras if extras else None)

        jf = jax.jit(fn,
                     in_shardings=(pshard, oshard, tok_shard, extras_shard),
                     donate_argnums=(0, 1))
        return jf, (pspecs, ospec, inputs["tokens"], inputs["extras"])

    cache_spec = specs_lib.cache_specs(cfg, shape.global_batch, shape.seq_len)
    cshard = shd.cache_shardings(cache_spec, mesh, shape.global_batch)

    if shape.kind == "prefill":
        tok_shard = NamedSharding(
            mesh, shd.batch_pspec(mesh, shape.global_batch, 2))
        extras_shard = jax.tree.map(
            lambda s: NamedSharding(
                mesh, shd.batch_pspec(mesh, shape.global_batch, s.ndim)),
            inputs["extras"])

        def fn(params, tokens, cache, extras):
            return model_lib.prefill(params, cfg, tokens, cache,
                                     extras if extras else None)

        jf = jax.jit(fn,
                     in_shardings=(pshard, tok_shard, cshard, extras_shard),
                     out_shardings=(NamedSharding(mesh, P()), cshard),
                     donate_argnums=(2,))
        return jf, (pspecs, inputs["tokens"], cache_spec, inputs["extras"])

    # decode
    tok_shard = NamedSharding(
        mesh, shd.batch_pspec(mesh, shape.global_batch, 1))

    def fn(params, token, cache):
        return model_lib.decode_step(params, cfg, token, cache)

    jf = jax.jit(fn,
                 in_shardings=(pshard, tok_shard, cshard),
                 out_shardings=(NamedSharding(mesh, P()), cshard),
                 donate_argnums=(2,))
    return jf, (pspecs, inputs["token"], cache_spec)


def act_constraint(mesh):
    """Residual stream: sequence-parallel over 'model'; logits: vocab-parallel
    over 'model' (prevents GSPMD replicating [B,S,V] f32 at the LM head)."""
    dp = shd.dp_axes(mesh)
    msize = mesh.shape.get("model", 1)

    def constrain(x, kind="resid"):
        if "model" not in mesh.shape:
            return x
        if kind == "embed":
            # embedding-table gradient: match the table's param sharding
            dims = [None] * x.ndim
            if x.shape[0] % msize == 0:
                dims[0] = "model"
            if x.ndim > 1 and "data" in mesh.shape and \
                    x.shape[-1] % mesh.shape["data"] == 0:
                dims[-1] = "data"
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(*dims)))
        if kind == "logits":
            if x.shape[-1] % msize == 0:
                dims = [None] * x.ndim
                dims[0] = dp
                dims[-1] = "model"
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(*dims)))
            return x
        if kind == "q_seq":
            # queries/outputs stay sequence-sharded: avoids the SP<->TP
            # reshard (an all-gather of the full residual per layer)
            if x.ndim == 4 and x.shape[1] % msize == 0:
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(dp, "model", None, None)))
            return x
        if kind == "kv_gather":
            # K/V gathered over model: GQA keys are n_heads/n_kv_heads
            # smaller than the residual, so shipping them is the cheap side
            if x.ndim == 4:
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(dp, None, None, None)))
            return x
        if kind == "heads":
            # [B, S, H, Dh]: heads -> model when divisible (TP attention),
            # else sequence -> model (keeps GSPMD from replicating the batch)
            if x.ndim == 4 and x.shape[2] % msize == 0:
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(dp, None, "model", None)))
            if x.ndim == 4 and x.shape[1] % msize == 0:
                return jax.lax.with_sharding_constraint(
                    x, NamedSharding(mesh, P(dp, "model", None, None)))
            return x
        if x.ndim == 3 and x.shape[1] % msize == 0:
            return jax.lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(dp, "model", None)))
        return x

    return constrain


# ---------------------------------------------------------------------------
# cost variants: shallow unrolled configs + linear extrapolation
# ---------------------------------------------------------------------------


def cost_variant_cfgs(cfg: ModelConfig) -> list[tuple[str, ModelConfig, dict]]:
    """[(name, variant_cfg, weights)] with weights {name: coefficient} such
    that full_cost = Σ coeff * variant_cost solves to the full depth."""
    f = cfg.family
    if f == "audio":
        a = dataclasses.replace(cfg, n_layers=1, n_encoder_layers=1)
        b = dataclasses.replace(cfg, n_layers=1, n_encoder_layers=2)
        c = dataclasses.replace(cfg, n_layers=2, n_encoder_layers=1)
        e, d = cfg.n_encoder_layers, cfg.n_layers
        # cost = base + E*enc + D*dec;  A = base+enc+dec, B = A+enc, C = A+dec
        return [("A", a, {}), ("B", b, {}), ("C", c, {})], \
            lambda fa, fb, fc: fa + (e - 1) * (fb - fa) + (d - 1) * (fc - fa)
    if f == "hybrid":
        a = dataclasses.replace(cfg, n_layers=2, shared_attn_every=2)
        b = dataclasses.replace(cfg, n_layers=4, shared_attn_every=2)
        c = dataclasses.replace(cfg, n_layers=4, shared_attn_every=4)
        m, s = cfg.n_layers, cfg.n_layers // cfg.shared_attn_every
        # A = base+2m+1s; B = base+4m+2s; C = base+4m+1s
        # m_cost=(C-A)/2; s_cost=B-C; base=A-2m-s
        return [("A", a, {}), ("B", b, {}), ("C", c, {})], \
            lambda fa, fb, fc: (fa - 2 * ((fc - fa) / 2) - (fb - fc)
                                + m * ((fc - fa) / 2) + s * (fb - fc))
    if f == "mla_moe":
        a = dataclasses.replace(cfg, n_layers=2)   # 1 dense + 1 moe
        b = dataclasses.replace(cfg, n_layers=3)   # 1 dense + 2 moe
        nm = cfg.n_layers - cfg.first_k_dense
        return [("A", a, {}), ("B", b, {})], \
            lambda fa, fb: fa + (nm - 1) * (fb - fa)
    a = dataclasses.replace(cfg, n_layers=1)
    b = dataclasses.replace(cfg, n_layers=2)
    return [("A", a, {}), ("B", b, {})], \
        lambda fa, fb: fa + (cfg.n_layers - 1) * (fb - fa)


def run_cost_variants(cfg: ModelConfig, shape: InputShape, mesh,
                      microbatches: int, layout: str = "zero3") -> dict:
    variants, combine = cost_variant_cfgs(cfg)
    results = []
    for name, vcfg, _ in variants:
        with dctx.lowering_ctx(constrain=act_constraint(mesh),
                               remat=(shape.kind == "train"),
                               unroll_scans=True, mesh=mesh):
            with mesh:
                jf, argspecs = build_step(vcfg, shape, mesh, microbatches=1,
                                          layout=layout)
                lowered = jf.lower(*argspecs)
                compiled = lowered.compile()
        ca = compiled.cost_analysis() or {}
        coll = collective_bytes(compiled.as_text())
        results.append({
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "link_bytes": link_traffic_bytes(coll),
            "collectives": coll,
        })

    def comb(key):
        vals = [r[key] for r in results]
        return float(combine(*vals))

    out = {"flops": comb("flops"), "bytes": comb("bytes"),
           "link_bytes": comb("link_bytes"),
           "variants": results}
    if shape.kind == "train" and microbatches > 1:
        # variants lowered at microbatches=1 over the full global batch;
        # grad-accumulation splits the same tokens, so per-step totals match
        # up to the (microbatches-1) extra optimizer-free accumulations —
        # negligible; totals kept as-is.
        pass
    return out


# ---------------------------------------------------------------------------


def run_cell(arch: str, shape_name: str, multi_pod: bool,
             skip_cost: bool = False, microbatches: int = TRAIN_MICROBATCHES,
             layout: str = "zero3") -> dict:
    cfg = get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    rec: dict = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "kind": shape.kind, "layout": layout,
        "microbatches": microbatches,
        "n_devices": int(len(mesh.devices.reshape(-1))),
    }
    t0 = time.time()
    try:
        with dctx.lowering_ctx(constrain=act_constraint(mesh),
                               remat=(shape.kind == "train"), mesh=mesh):
            with mesh:
                jf, argspecs = build_step(cfg, shape, mesh, microbatches,
                                          layout=layout)
                lowered = jf.lower(*argspecs)
                t_lower = time.time() - t0
                compiled = lowered.compile()
                t_compile = time.time() - t0 - t_lower
        mem = compiled.memory_analysis()
        rec.update(
            status="ok", lower_s=round(t_lower, 1),
            compile_s=round(t_compile, 1),
            memory={
                k: int(getattr(mem, k, 0)) for k in
                ("argument_size_in_bytes", "output_size_in_bytes",
                 "temp_size_in_bytes", "generated_code_size_in_bytes",
                 "alias_size_in_bytes")
            })
        ca = compiled.cost_analysis() or {}
        rec["cost_analysis_raw"] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
        }
        rec["collectives_raw"] = collective_bytes(compiled.as_text())
    except Exception as e:  # noqa: BLE001 — recorded, the driver aggregates
        rec.update(status="fail", error=f"{type(e).__name__}: {e}",
                   traceback=traceback.format_exc()[-4000:])
        return rec

    if not skip_cost and not multi_pod:
        try:
            rec["cost"] = run_cost_variants(cfg, shape, mesh, microbatches,
                                            layout)
        except Exception as e:  # noqa: BLE001
            rec["cost"] = {"error": f"{type(e).__name__}: {e}"}
    return rec


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--shape", required=True, choices=sorted(SHAPES))
    ap.add_argument("--mesh", default="single", choices=["single", "multi"])
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--skip-cost", action="store_true")
    ap.add_argument("--microbatches", type=int, default=TRAIN_MICROBATCHES)
    ap.add_argument("--layout", default="zero3", choices=["zero3", "tp"])
    ap.add_argument("--tag", default="")
    args = ap.parse_args()

    rec = run_cell(args.arch, args.shape, args.mesh == "multi",
                   skip_cost=args.skip_cost, microbatches=args.microbatches,
                   layout=args.layout)
    os.makedirs(args.out, exist_ok=True)
    suffix = f"__{args.tag}" if args.tag else ""
    path = os.path.join(
        args.out, f"{args.arch}__{args.shape}__{args.mesh}{suffix}.json")
    with open(path, "w") as f:
        json.dump(rec, f, indent=1)
    print(json.dumps({k: v for k, v in rec.items()
                      if k not in ("traceback", "cost")}, indent=1))
    if rec["status"] != "ok":
        raise SystemExit(1)


if __name__ == "__main__":
    main()
