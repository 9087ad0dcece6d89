"""Pipeline parallelism over a mesh `pp` axis (SURVEY.md §2; reference
contrast: torch pipeline parallelism ships modules to different GPUs and
drives them with host threads — here the schedule is a compiled collective
program: every stage is the SAME traced computation, activations hop stages
via ppermute, and XLA overlaps the steady-state bubble).

GPipe schedule: M microbatches through S stages takes M+S-1 ticks. Stage
parameters are stacked on a leading S dim sharded over `pp`; inside
shard_map each device sees its own stage's slice.
"""

from functools import partial
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,
    stage_params,
    microbatches: jax.Array,
    mesh: Mesh,
    axis: str = "pp",
):
    """Run microbatches through S pipeline stages.

    stage_fn: (params_slice, x) -> y, same shapes for x and y (inter-stage
      activations must agree; project in/out in stages 0 / S-1).
    stage_params: pytree whose leaves have leading dim S (stacked stages).
    microbatches: [M, ...] array; every microbatch enters stage 0.
    Returns [M, ...] outputs of the last stage, replicated over `axis`.
    """
    S = mesh.shape[axis]
    M = microbatches.shape[0]

    def per_device(params, xs):
        # params leaves arrive as [1, ...] (this device's stage); drop the dim
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        is_first = stage == 0
        is_last = stage == S - 1
        perm = [(i, (i + 1) % S) for i in range(S)]

        buf = jnp.zeros_like(xs[0])
        outs = jnp.zeros((M,) + xs.shape[1:], xs.dtype)

        def tick(t, carry):
            buf, outs = carry
            mb = jax.lax.dynamic_index_in_dim(
                xs, jnp.minimum(t, M - 1), keepdims=False)
            inp = jnp.where(is_first, mb, buf)
            y = stage_fn(params, inp)
            out_idx = jnp.clip(t - (S - 1), 0, M - 1)
            take = jnp.logical_and(is_last, t >= S - 1)
            cur = jax.lax.dynamic_index_in_dim(outs, out_idx, keepdims=False)
            outs = jax.lax.dynamic_update_index_in_dim(
                outs, jnp.where(take, y, cur), out_idx, 0)
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, M + S - 1, tick, (buf, outs))
        # only the last stage wrote outs; psum replicates it to every stage
        outs = jnp.where(is_last, outs, jnp.zeros_like(outs))
        return jax.lax.psum(outs, axis)

    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(P(axis), P()),  # stages sharded; microbatches replicated
        out_specs=P(),
        check_vma=False,
    )(stage_params, microbatches)


def make_microbatches(batch: jax.Array, num_microbatches: int) -> jax.Array:
    """[B, ...] → [M, B/M, ...]."""
    if num_microbatches < 1:
        raise ValueError(f"num_microbatches must be >= 1, got "
                         f"{num_microbatches}")
    B = batch.shape[0]
    if B % num_microbatches:
        raise ValueError(
            f"batch size {B} (batch shape {tuple(batch.shape)}) is not "
            f"divisible by num_microbatches={num_microbatches}: "
            f"{B} % {num_microbatches} == {B % num_microbatches} rows "
            f"would be dropped — pad or resize the batch")
    return batch.reshape((num_microbatches, B // num_microbatches)
                         + batch.shape[1:])


def stack_stage_params(params_list):
    """List of per-stage pytrees (same structure) → stacked pytree with
    leading S dim, ready to shard over `pp`."""
    return jax.tree_util.tree_map(
        lambda *ps: jnp.stack(ps, axis=0), *params_list)


def shard_pipeline_params(stacked, mesh: Mesh, axis: str = "pp"):
    sharding = jax.tree_util.tree_map(
        lambda _: NamedSharding(mesh, P(axis)), stacked)
    return jax.device_put(stacked, sharding)
