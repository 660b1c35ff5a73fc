"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The production meshes in this repo are (pod, data, model); pipelining is an
OPTIONAL axis for deployments that prefer PP over deeper FSDP (e.g. cross-pod
stages where ICI/DCN bandwidth is the binding constraint). The implementation
is deliberately self-contained: stages are laid out on a 1-D "pipe" mesh
axis, microbatches stream through with the classic GPipe schedule
(P + M - 1 ticks for M microbatches over P stages), and inter-stage hops are
jax.lax.ppermute sends of the activation block.

Each device holds its stage's parameters only => params sharded on the pipe
axis; within a stage, any inner sharding (tensor/fsdp over other mesh axes)
still applies because shard_map composes.
"""

from __future__ import annotations


import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipeline_apply(stage_fn, params_stacked, x_microbatches, mesh: Mesh,
                   axis: str = "pipe"):
    """Run M microbatches through S pipeline stages.

    stage_fn(stage_params, x) -> x  (the per-stage computation)
    params_stacked: pytree with leading axis S (stage-major).
    x_microbatches: (M, mb, ...) microbatched input.
    Returns (M, mb, ...) outputs (as produced by the LAST stage).
    """
    n_stages = mesh.shape[axis]
    M = x_microbatches.shape[0]
    assert M >= 1

    def per_device(params_local, xs):
        # params_local: this stage's params (leading axis 1) ; xs: (M, mb, ...)
        p = jax.tree_util.tree_map(lambda a: a[0], params_local)
        idx = jax.lax.axis_index(axis)
        n_ticks = n_stages + M - 1
        buf = jnp.zeros_like(xs[0])                  # current activation
        outs = jnp.zeros_like(xs)                    # last stage accumulates

        def tick(t, carry):
            buf, outs = carry
            # stage 0 ingests microbatch t (if any remain)
            mb_in = jax.lax.dynamic_index_in_dim(
                xs, jnp.clip(t, 0, M - 1), keepdims=False)
            buf = jnp.where(idx == 0,
                            jnp.where(t < M, mb_in, jnp.zeros_like(buf)),
                            buf)
            # every stage computes on its current buffer
            y = stage_fn(p, buf)
            # last stage emits microbatch t - (S - 1)
            out_slot = t - (n_stages - 1)
            emit = (idx == n_stages - 1) & (out_slot >= 0)
            outs = jax.lax.cond(
                emit,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_slot, 0, M - 1), 0),
                lambda o: o, outs)
            # shift activations downstream: stage i -> i+1
            perm = [(i, i + 1) for i in range(n_stages - 1)]
            buf = jax.lax.ppermute(y, axis, perm)
            return buf, outs

        _, outs = jax.lax.fori_loop(0, n_ticks, tick, (buf, outs))
        # only the last stage holds real outputs; broadcast via masked psum
        if n_stages > 1:
            outs = jax.lax.psum(
                jnp.where(idx == n_stages - 1, outs, jnp.zeros_like(outs)),
                axis)
        return outs

    spec_p = jax.tree_util.tree_map(lambda _: P(axis), params_stacked)
    return jax.shard_map(
        per_device, mesh=mesh,
        in_specs=(spec_p, P()), out_specs=P(),
        check_vma=False,
    )(params_stacked, x_microbatches)
