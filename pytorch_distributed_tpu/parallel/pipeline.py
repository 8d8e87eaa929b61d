"""Pipeline parallelism: a GPipe schedule expressed the SPMD way.

The reference repo has no pipeline parallelism (SURVEY.md §2: DDP, ZeRO-1
and FSDP only) — this is a capability extension that falls out almost for
free on TPU: under single-controller SPMD a pipeline is just (a) the
stacked layer dimension of the params sharded over the ``pp`` mesh axis
and (b) a ``lax.scan`` over schedule ticks whose stage-to-stage handoff is
a ``ppermute`` riding the ICI torus. Backprop needs no hand-written
schedule: the transpose of ``ppermute`` is the reverse ``ppermute``, so
differentiating the scan yields the reverse (1F1B-shaped) pipeline
automatically.

Schedule shape (classic GPipe): with S stages and M microbatches the loop
runs ``M + S - 1`` ticks; stage s is busy on ticks ``s .. s+M-1``; the
bubble fraction is ``(S-1)/(M+S-1)`` — keep M >= 4*S for >80%% utilisation.

Layout contract: stage-stacked parameters have leading dim S (one slice
per stage), sharded ``P("pp")``; microbatched inputs/outputs have leading
dim M, replicated over ``pp``.
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from pytorch_distributed_tpu.runtime.mesh import current_mesh


def _pipeline_local(stage_params, xs, *, stage_fn, axis: str):
    """Runs per-shard inside shard_map: the GPipe tick loop for my stage.

    stage_params: this stage's slice of the stacked params (leading stage
    dim of size 1, kept so tree structure matches the global view).
    xs: [M, ...] all microbatches (replicated).
    """
    stage = lax.axis_index(axis)
    n_stages = lax.axis_size(axis)
    M = xs.shape[0]
    params = jax.tree_util.tree_map(lambda p: p[0], stage_params)

    shift = [(i, i + 1) for i in range(n_stages - 1)]  # stage i -> i+1

    def tick(carry, t):
        cur, outs = carry
        # stage 0 ingests microbatch t while they last; other stages (and
        # drain ticks) consume the activation handed over last tick
        mb = lax.dynamic_index_in_dim(
            xs, jnp.clip(t, 0, M - 1), axis=0, keepdims=False
        )
        inp = jnp.where(stage == 0, mb, cur)
        y = stage_fn(params, inp)
        # last stage: y at tick t completes microbatch t - (S-1)
        m = t - (n_stages - 1)
        is_ready = jnp.logical_and(stage == n_stages - 1, m >= 0)
        outs = lax.dynamic_update_index_in_dim(
            outs,
            jnp.where(is_ready, y, lax.dynamic_index_in_dim(
                outs, jnp.clip(m, 0, M - 1), axis=0, keepdims=False)),
            jnp.clip(m, 0, M - 1),
            axis=0,
        )
        nxt = lax.ppermute(y, axis, shift)  # stage 0 receives zeros: unused
        return (nxt, outs), None

    y0 = jax.eval_shape(stage_fn, params, xs[0])
    cur0 = jnp.zeros(y0.shape, y0.dtype)
    outs0 = jnp.zeros((M,) + y0.shape, y0.dtype)
    (_, outs), _ = lax.scan(
        tick, (cur0, outs0), jnp.arange(M + n_stages - 1)
    )
    # outputs are only real on the last stage; psum of the masked buffer
    # replicates them to every stage
    outs = jnp.where(stage == n_stages - 1, outs, jnp.zeros_like(outs))
    return lax.psum(outs, axis)


def pipeline_forward(
    stage_fn: Callable,
    stacked_params,
    microbatches,
    *,
    axis: str = "pp",
    mesh: Mesh | None = None,
):
    """Run stage-stacked params over microbatches with a GPipe schedule.

    ``stage_fn(params_one_stage, x) -> y`` applies ONE stage's layers; x
    and y must have identical shape/dtype (the activation handed between
    stages). ``stacked_params``: pytree whose leaves have leading dim =
    number of stages (= mesh ``axis`` size). ``microbatches``: [M, ...],
    M >= 1. Returns [M, ...] outputs, replicated over ``axis``.

    Differentiable end-to-end; grads of the stacked params come back with
    the same leading stage dim, still sharded over ``axis``.
    """
    mesh = mesh or current_mesh()
    n_stages = mesh.shape[axis]
    leaves = jax.tree_util.tree_leaves(stacked_params)
    for leaf in leaves:
        if leaf.shape[0] != n_stages:
            raise ValueError(
                f"stacked param leading dim {leaf.shape[0]} != pipeline "
                f"stages {n_stages} (mesh axis {axis!r})"
            )
    fn = jax.shard_map(
        functools.partial(_pipeline_local, stage_fn=stage_fn, axis=axis),
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    return fn(stacked_params, microbatches)


def stage_sharding(mesh: Mesh | None = None, axis: str = "pp"):
    """NamedSharding for stage-stacked params (leading dim over ``axis``)."""
    mesh = mesh or current_mesh()
    return NamedSharding(mesh, P(axis))


def split_microbatches(batch, num_microbatches: int):
    """[B, ...] -> [M, B/M, ...] for every leaf of a batch pytree."""

    def split(x):
        B = x.shape[0]
        if B % num_microbatches != 0:
            raise ValueError(
                f"batch dim {B} not divisible by {num_microbatches} "
                "microbatches"
            )
        return x.reshape((num_microbatches, B // num_microbatches) + x.shape[1:])

    return jax.tree_util.tree_map(split, batch)


def merge_microbatches(batch):
    """Inverse of :func:`split_microbatches`."""

    def merge(x):
        return x.reshape((x.shape[0] * x.shape[1],) + x.shape[2:])

    return jax.tree_util.tree_map(merge, batch)
