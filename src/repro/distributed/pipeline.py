"""GPipe-style pipeline parallelism over the ``pod`` axis (multi-pod option).

The multi-pod mesh's ``pod`` axis defaults to data parallelism; for models
whose per-pod weight residency is the constraint, ``pipelined_forward`` runs
the layer stack split into ``pod`` stages with microbatch rotation via
``collective-permute`` (the canonical shard_map pipeline: all stages run the
same program; microbatch m enters stage s at step m+s).

This is a library primitive with a small-scale correctness test; the dry-run
exercises it through launch/dryrun.py --pipeline (optional mode).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipelined_forward(layer_fn, params_stages, x_microbatches, mesh: Mesh,
                      axis: str = "pod"):
    """Run ``x`` through layers split into ``n = |axis|`` stages.

    layer_fn(stage_params, x) -> x ; params_stages: pytree with leading dim n
    (stacked per-stage parameters, sharded P(axis)); x_microbatches:
    [m, mb, ...] microbatched inputs (replicated). Returns [m, mb, ...].
    """
    n = mesh.shape[axis]

    def body(stage_params, xs):
        stage = jax.lax.axis_index(axis)
        m = xs.shape[0]
        steps = m + n - 1
        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros_like(xs)

        def step(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any); others take the permuted
            # output of the previous stage from the previous step.
            take = jnp.clip(t, 0, m - 1)
            inp = jnp.where(stage == 0, xs[take], buf)
            out = layer_fn(jax.tree.map(lambda a: a[0], stage_params), inp)
            # rotate stage s -> s+1
            buf_next = jax.lax.ppermute(
                out, axis, [(i, (i + 1) % n) for i in range(n)])
            # last stage emits microbatch t-(n-1)
            emit_idx = jnp.clip(t - (n - 1), 0, m - 1)
            valid = (t >= n - 1) & (stage == n - 1)
            outs = jax.lax.cond(
                valid,
                lambda o: o.at[emit_idx].set(out),
                lambda o: o, outs)
            return buf_next, outs

        buf, outs = jax.lax.fori_loop(0, steps, step, (buf, outs))
        # broadcast the last stage's outputs to every stage for a replicated
        # return value (psum of masked contributions)
        outs = jax.lax.psum(
            jnp.where(stage == n - 1, outs, jnp.zeros_like(outs)), axis)
        return outs

    return shard_map(
        body, mesh=mesh,
        in_specs=(P(axis), P(*([None] * x_microbatches.ndim))),
        out_specs=P(*([None] * x_microbatches.ndim)),
        check_vma=False,
    )(params_stages, x_microbatches)
