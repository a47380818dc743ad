"""Multi-device distribution tests (run in subprocesses with forced device
counts so the rest of the suite keeps seeing 1 CPU device)."""

import os
import subprocess
import sys


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(script: str, devices: int = 8) -> subprocess.CompletedProcess:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    return subprocess.run([sys.executable, "-c", script], env=env,
                          capture_output=True, text=True, timeout=600)


def _check(r):
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"


def test_moe_expert_parallel_equivalence():
    _check(_run("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs.registry import ASSIGNED_ARCHS
from repro.distributed import ctx
from repro.models import moe as moe_mod
cfg = ASSIGNED_ARCHS['qwen2-moe-a2.7b'].reduced()
key = jax.random.PRNGKey(0)
p = moe_mod.init_moe(key, cfg, jnp.float32)
x = jax.random.normal(jax.random.fold_in(key,1), (4, 8, cfg.d_model), jnp.float32)
y_local = moe_mod.moe_ffn(p, x, cfg)
mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
with ctx.lowering_ctx(mesh=mesh):
    with mesh:
        y_s = jax.jit(lambda p, x: moe_mod.moe_ffn(p, x, cfg))(p, x)
rel = float(jnp.max(jnp.abs(y_local - y_s)) / (jnp.max(jnp.abs(y_local)) + 1e-9))
assert rel < 2e-2, rel
"""))


def test_hybrid_stream_primitives():
    _check(_run("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.distributed.hybrid_stream import streamed_matmul_chain, alpha_split_matmul
mesh = jax.make_mesh((8,), ('data',), axis_types=(AxisType.Auto,) * 1)
key = jax.random.PRNGKey(0)
x = jax.random.normal(key, (4, 64))
ws = [jax.random.normal(jax.random.fold_in(key, i), (64, 64)) * 0.1
      for i in range(3)]
with mesh:
    y = streamed_matmul_chain(x, ws, mesh, 'data')
ref = x
for w in ws:
    ref = ref @ w
assert float(jnp.max(jnp.abs(y - ref))) < 1e-4
with mesh:
    for alpha in (0.0, 0.25, 0.5, 1.0):
        y2 = alpha_split_matmul(x, ws[0], mesh, alpha)
        assert float(jnp.max(jnp.abs(y2 - x @ ws[0]))) < 1e-4, alpha
"""))


def test_pipeline_parallel_correctness():
    _check(_run("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.distributed.pipeline import pipelined_forward
mesh = jax.make_mesh((4,), ('pod',), axis_types=(AxisType.Auto,) * 1)
key = jax.random.PRNGKey(0)
n_stages, m, mb, d = 4, 6, 2, 16
ws = jax.random.normal(key, (n_stages, d, d)) * 0.3
xs = jax.random.normal(jax.random.fold_in(key, 1), (m, mb, d))
def layer_fn(w, x):
    return jnp.tanh(x @ w)
with mesh:
    out = pipelined_forward(layer_fn, ws, xs, mesh, 'pod')
ref = xs
for s in range(n_stages):
    ref = jnp.tanh(ref @ ws[s])
assert float(jnp.max(jnp.abs(out - ref))) < 1e-5
"""))


def test_elastic_reshard_across_device_counts():
    _check(_run("""
import jax, jax.numpy as jnp
from repro.configs.registry import ASSIGNED_ARCHS
from repro.distributed.elastic import make_elastic_mesh, reshard_params
from repro.models import model as M
cfg = ASSIGNED_ARCHS['smollm-360m'].reduced()
params = M.init_params(cfg, jax.random.PRNGKey(0), max_seq=32)
for n in (8, 6, 4):
    mesh = make_elastic_mesh(jax.devices()[:n], prefer_model=4)
    p2 = reshard_params(params, mesh)
    toks = jnp.zeros((2, 8), jnp.int32)
    with mesh:
        logits = M.forward(p2, cfg, toks, {})
    assert not bool(jnp.isnan(logits).any()), n
"""))


def test_sharding_rules_cover_all_archs():
    _check(_run("""
import jax, jax.numpy as jnp
from jax.sharding import AxisType
from repro.configs.registry import ASSIGNED_ARCHS
from repro.distributed import sharding as shd
from repro.launch import specs as specs_lib
mesh = jax.make_mesh((2, 4), ('data', 'model'), axis_types=(AxisType.Auto,) * 2)
for name, cfg in ASSIGNED_ARCHS.items():
    ps = specs_lib.param_specs(cfg.reduced(), max_seq=64, quant=False)
    tree = shd.params_shardings(ps, mesh)  # must not raise
    cs = specs_lib.cache_specs(cfg.reduced(), 8, 64)
    shd.cache_shardings(cs, mesh, 8)
print('ok')
""", devices=8))


def test_grad_compress_allreduce_traffic():
    _check(_run("""
import jax, jax.numpy as jnp
from jax import shard_map
from jax.sharding import AxisType, PartitionSpec as P
from repro.distributed.grad_compress import psum_compressed
mesh = jax.make_mesh((8,), ('data',), axis_types=(AxisType.Auto,) * 1)
g = jax.random.normal(jax.random.PRNGKey(0), (8, 128)) * 0.1
with mesh:
    out = shard_map(lambda g: psum_compressed(g, 'data'), mesh=mesh,
                    in_specs=P('data'), out_specs=P('data'),
                    check_vma=False)(g)
ref = g.mean(0)
rel = float(jnp.linalg.norm(out[0] - ref) / jnp.linalg.norm(ref))
assert rel < 0.05, rel
"""))


def test_router_replicas_each_on_their_own_device():
    # Router.build puts replica i's params and paged KV on local device i;
    # four replicas serve the same greedy streams as one replica
    _check(_run("""
import jax
from repro.configs.registry import get_arch
from repro.models import model as M
from repro.serving.client import ServingClient
from repro.serving.scheduler import SamplingParams
cfg = get_arch('smollm-360m').reduced()
params = M.init_params(cfg, jax.random.PRNGKey(0), max_seq=64)
prompts = [[3 + i, 5, 7 + 2 * i, 11][:2 + i % 3] for i in range(12)]
streams = {}
for n in (4, 1):
    client = ServingClient(cfg, params, replicas=n, route='least_loaded',
                           max_batch=2, max_seq=64, page_size=8, eos_id=-1)
    for i, core in enumerate(client.router.cores):
        held = {d for leaf in jax.tree.leaves(core.cache)
                for d in leaf.devices()}
        assert held == {jax.devices()[i]}, (i, held)
    hs = [client.submit(p, max_new_tokens=6,
                        sampling=SamplingParams(temperature=0.0))
          for p in prompts]
    client.run()
    streams[n] = [h.request.out_tokens for h in hs]
    assert all(len(s) == 6 for s in streams[n]), streams[n]
assert streams[4] == streams[1]
print('ok')
""", devices=4))
