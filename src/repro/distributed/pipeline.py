"""GPipe-style pipeline parallelism over an explicit mesh axis.

``pipeline_apply`` runs a homogeneous stage function over ``P`` pipeline
stages held on a ``pipe`` mesh axis, streaming ``M`` microbatches with the
classic (M + P − 1)-tick schedule; activations move between stages with
``ppermute`` (point-to-point, the TPU-native inter-stage transfer).  The
whole schedule is differentiable, so ``jax.grad`` through it yields correct
pipeline-parallel training (GPipe semantics: no weight staleness).

Stage parameters are stacked on a leading axis of size P and sharded
``P(axis)`` so each device holds exactly its stage's weights.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as PS


def pipeline_apply(stage_fn, stage_params, x, *, mesh: Mesh,
                   axis: str = "pipe", microbatches: int | None = None):
    """Run ``stage_fn(params_p, x) -> y`` over P pipeline stages.

    stage_params: pytree with leading stacked axis P (sharded over ``axis``).
    x: (M, mb, ...) microbatched input (replicated; stage 0 consumes it).
    Returns (M, mb, ...) outputs from the last stage.
    """
    p = mesh.shape[axis]
    m = x.shape[0] if microbatches is None else microbatches
    t_total = m + p - 1

    def per_stage(params_stacked, xs):
        # inside shard_map: params_stacked has leading dim 1 (this stage)
        params = jax.tree.map(lambda a: a[0], params_stacked)
        stage_id = jax.lax.axis_index(axis)
        mb_shape = xs.shape[1:]
        state = jnp.zeros(mb_shape, xs.dtype)      # current activation
        outputs = jnp.zeros_like(xs)

        def tick(carry, t):
            state, outputs = carry
            # stage 0 ingests microbatch t; others take the permuted input
            inject = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, m - 1), 0, keepdims=False)
            x_in = jnp.where(stage_id == 0, inject, state)
            active = (t - stage_id >= 0) & (t - stage_id < m)
            y = stage_fn(params, x_in)
            y = jnp.where(active, y, state)
            # last stage writes its result for microbatch (t - P + 1)
            out_idx = jnp.clip(t - p + 1, 0, m - 1)
            write = (stage_id == p - 1) & (t - (p - 1) >= 0)
            cur = jax.lax.dynamic_index_in_dim(outputs, out_idx, 0,
                                               keepdims=False)
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs, jnp.where(write, y, cur), out_idx, 0)
            # shift activations one stage down the ring
            nxt = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % p) for i in range(p)])
            return (nxt, outputs), None

        (_, outputs), _ = jax.lax.scan(tick, (state, outputs),
                                       jnp.arange(t_total))
        # every stage returns its buffer; only the last stage's slice is
        # meaningful.  Returning per-stage (out_specs PS(axis)) rather than
        # broadcasting keeps the backward pass exact: a replicated output
        # would scale parameter cotangents by 1/P.
        return outputs

    in_specs = (jax.tree.map(lambda _: PS(axis), stage_params), PS())
    out_specs = PS(axis)
    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    stacked = fn(stage_params, x)          # (P·M, mb, ...)
    return stacked[(p - 1) * m:]


def stack_stage_params(per_stage_params: list):
    """List of P per-stage pytrees → stacked pytree with leading P axis."""
    return jax.tree.map(lambda *xs: jnp.stack(xs), *per_stage_params)
