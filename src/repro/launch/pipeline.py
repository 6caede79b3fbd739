"""Pipeline parallelism: GPipe schedule over the ``pod`` axis.

The default multi-pod posture replicates parameters across pods (pure
DP; only gradient traffic crosses the inter-pod links). When a model's
parameters do NOT fit one pod even FSDP-sharded, the alternative is to
make the pod axis a *pipeline* axis: each pod owns a contiguous block of
layers, microbatches stream through, and only (B_micro, S, d_model)
activations cross pods — the smallest possible inter-pod payload.

This module implements the schedule as a pure shard_map program:

  * every stage holds its layer block's params (sharded however the
    intra-pod rules dictate — the stage function is arbitrary);
  * activations advance stage-to-stage with ``jax.lax.ppermute`` (a
    point-to-point collective: exactly one inter-pod hop per
    microbatch per boundary — the paper's static, deterministic
    dataflow at pod granularity);
  * the standard GPipe pipeline runs S + M − 1 ticks for S stages and
    M microbatches (bubble fraction (S−1)/(S+M−1)).

``pipeline_apply`` is forward-only (serving / eval); training composes
it with jax.grad exactly like any other jax function (ppermute has a
transpose rule), with the usual GPipe activation-stash memory cost.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def stage_index(axis: str = "pod"):
    return jax.lax.axis_index(axis)


def pipeline_apply(stage_fn: Callable, stage_params, x: jax.Array, *,
                   mesh, axis: str = "pod",
                   microbatches: int) -> jax.Array:
    """Run ``stage_fn`` as an ``n_stage``-deep GPipe pipeline.

    stage_fn: (params_for_stage, h) -> h          (one layer block)
    stage_params: pytree whose leaves have a leading ``n_stages`` dim,
        sharded over ``axis`` (each pod holds only its own block).
    x: (B, ...) global batch; B % microbatches == 0.
    Returns stage_fn applied n_stages times, identical to the sequential
    program (tested in tests/test_pipeline.py).
    """
    n_stages = mesh.shape[axis]
    B = x.shape[0]
    assert B % microbatches == 0
    mb = B // microbatches
    n_ticks = n_stages + microbatches - 1

    def per_stage(params, xs):
        # params: this stage's block (leading dim 1); xs: full batch,
        # replicated — every stage sees the schedule, computes only when
        # its slot holds a live microbatch.
        params = jax.tree.map(lambda p: p[0], params)
        sidx = jax.lax.axis_index(axis)
        mbs = xs.reshape(microbatches, mb, *xs.shape[1:])

        def tick(state, t):
            held, outs = state
            # stage 0 injects microbatch t (when t < M); everyone else
            # uses what arrived from the left neighbour
            inject = mbs[jnp.minimum(t, microbatches - 1)]
            h_in = jnp.where(sidx == 0,
                             jnp.where(t < microbatches, inject,
                                       jnp.zeros_like(inject)),
                             held)
            h_out = stage_fn(params, h_in)
            # pass rightward; the last stage's output is collected when
            # microbatch m = t - (n_stages-1) completes
            nxt = jax.lax.ppermute(
                h_out, axis,
                [(i, i + 1) for i in range(n_stages - 1)])
            m = t - (n_stages - 1)
            take = jnp.logical_and(m >= 0, sidx == n_stages - 1)
            upd = jax.lax.dynamic_update_index_in_dim(
                outs, h_out, jnp.clip(m, 0, microbatches - 1), 0)
            outs = jnp.where(take, upd, outs)
            return (nxt, outs), None

        # carries start pod-varying (+0*sidx) so the scan's carry type
        # is stable under shard_map's varying-axis tracking
        vary = (0.0 * sidx).astype(xs.dtype)
        outs0 = jnp.zeros((microbatches, mb) + xs.shape[1:],
                          xs.dtype) + vary
        held0 = jnp.zeros((mb,) + xs.shape[1:], xs.dtype) + vary
        (_, outs), _ = jax.lax.scan(tick, (held0, outs0),
                                    jnp.arange(n_ticks))
        # broadcast the last stage's collected outputs to every stage
        # (psum of one-hot-masked outs) so the result is replicated
        outs = jax.lax.psum(
            jnp.where(sidx == n_stages - 1, outs, jnp.zeros_like(outs)),
            axis)
        return outs.reshape(B, *xs.shape[1:])

    pspec = jax.tree.map(lambda _: P(axis), stage_params)
    # explicit-axis meshes (jax.make_mesh's default) type every traced
    # value with its sharding, which needs the mesh in context
    with jax.set_mesh(mesh):
        return jax.shard_map(per_stage, mesh=mesh,
                             in_specs=(pspec, P()), out_specs=P())(
            stage_params, x)


def bubble_fraction(n_stages: int, microbatches: int) -> float:
    """GPipe bubble overhead — the schedule's idle fraction."""
    return (n_stages - 1) / (n_stages + microbatches - 1)
