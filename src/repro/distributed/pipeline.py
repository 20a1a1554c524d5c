"""GPipe-style pipeline parallelism over a mesh axis (shard_map + ppermute).

Each device along the `pipe` axis owns one stage's params.  Microbatches
march through the ring: at every tick each stage computes on the activation
it holds and collective-permutes it to the next stage.  With M microbatches
and S stages the schedule runs S + M - 1 ticks (classic GPipe bubble
(S-1)/(S+M-1)); activations for in-flight microbatches live in a rolling
buffer.  Used to host pipeline stages on the `pod` axis (DCN-friendly:
point-to-point permutes only, no all-to-alls across pods).
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable,
    stacked_params,
    x: jax.Array,
    *,
    mesh: Mesh,
    axis: str = "pod",
    num_microbatches: int,
):
    """Run x through `n_stages` sequential applications of stage_fn.

    stage_fn(params_i, x) -> x, applied in stage order along `axis`.
    stacked_params: leading dim == mesh.shape[axis] (one slice per stage).
    x: (batch, ...) with batch % num_microbatches == 0.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % num_microbatches == 0
    mb = b // num_microbatches

    def per_stage(params_l, x_l):
        # params_l: one stage's params (leading stage dim stripped by specs)
        params_l = jax.tree.map(lambda a: a[0], params_l)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_stages + num_microbatches - 1
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        micro = x_l.reshape(num_microbatches, mb, *x_l.shape[1:])
        outputs = jnp.zeros_like(micro)
        carry = jnp.zeros((mb,) + x_l.shape[1:], x_l.dtype)

        def tick(t, state):
            carry, outputs = state
            # stage 0 ingests microbatch t (if any remain)
            feed = micro[jnp.clip(t, 0, num_microbatches - 1)]
            inp = jnp.where(stage == 0, feed, carry)
            out = stage_fn(params_l, inp)
            # last stage retires microbatch t - (n_stages - 1)
            done_idx = t - (n_stages - 1)
            write = jnp.logical_and(stage == n_stages - 1, done_idx >= 0)
            outputs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_slice_in_dim(
                    o, out[None], jnp.maximum(done_idx, 0), axis=0
                ),
                lambda o: o,
                outputs,
            )
            carry = jax.lax.ppermute(out, axis, perm)
            return carry, outputs

        _, outputs = jax.lax.fori_loop(
            0, n_ticks, tick, (carry, outputs)
        )
        # results live on the last stage; share them back to every stage so
        # the caller sees a replicated output (one more ring rotation)
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), axis
        )
        return outputs.reshape(b, *x_l.shape[1:])

    pspecs = jax.tree.map(lambda _: P(axis), stacked_params)
    return shard_map(
        per_stage,
        mesh=mesh,
        in_specs=(pspecs, P()),
        out_specs=P(),
        check_vma=False,
    )(stacked_params, x)
