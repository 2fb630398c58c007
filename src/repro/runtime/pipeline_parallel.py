"""GPipe-style pipeline parallelism over a mesh axis (the "pod" axis in
the multi-pod mesh): stage-sharded layer stacks, microbatched schedule,
activations forwarded with lax.ppermute.

This is the optional PP mode — the default distribution is DP x TP/FSDP
(launch/mesh.py); PP is exercised by its own test and available for
pipelining a layer stack across pods where cross-pod bandwidth (DCI) is
much lower than ICI: PP exchanges only (microbatch, seq, d_model)
activations per tick instead of full gradients.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jnp.ndarray


def pipeline_apply(stage_fn: Callable, stage_params, x: Array, *,
                   mesh: Mesh, axis: str, n_microbatches: int) -> Array:
    """Run ``y = stage_P-1( ... stage_0(x))`` with GPipe microbatching.

    stage_params: pytree whose leaves have leading dim P (stage-sharded
    over ``axis``); stage_fn(params_stage, x_mb) -> y_mb, same shape.
    x: (B, ...) with B % n_microbatches == 0. Output is replicated.
    """
    n_stages = mesh.shape[axis]
    b = x.shape[0]
    assert b % n_microbatches == 0, (b, n_microbatches)
    mb = b // n_microbatches

    def per_stage(params_stage, x_all):
        params_stage = jax.tree.map(lambda a: a[0], params_stage)
        stage = jax.lax.axis_index(axis)
        n_ticks = n_microbatches + n_stages - 1
        fwd_perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        mbs = x_all.reshape(n_microbatches, mb, *x_all.shape[1:])
        # mark the loop carries as pod-varying up front (each stage holds
        # different values), else the fori carry types mismatch
        carry_in = jax.lax.pcast(jnp.zeros_like(mbs[0]), (axis,),
                                 to="varying")
        outputs = jax.lax.pcast(jnp.zeros_like(mbs), (axis,),
                                to="varying")

        def tick(t, state):
            carry, outs = state
            # stage 0 injects microbatch t (if still available)
            inject = mbs[jnp.minimum(t, n_microbatches - 1)]
            inp = jnp.where(stage == 0, inject, carry)
            out = stage_fn(params_stage, inp)
            # last stage commits finished microbatch t - (P-1)
            done_idx = t - (n_stages - 1)
            commit = (stage == n_stages - 1) & (done_idx >= 0)
            updated = jax.lax.dynamic_update_index_in_dim(
                outs, out, jnp.maximum(done_idx, 0), 0)
            outs = jnp.where(commit, updated, outs)
            # forward activations to the next stage
            carry = jax.lax.ppermute(out, axis, fwd_perm)
            return carry, outs

        _, outputs = jax.lax.fori_loop(0, n_ticks, tick,
                                       (carry_in, outputs))
        # replicate final outputs from the last stage to all stages
        outputs = jax.lax.psum(
            jnp.where(stage == n_stages - 1, outputs, 0.0), axis)
        return outputs.reshape(b, *x_all.shape[1:])

    fn = jax.shard_map(
        per_stage, mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P())
    return fn(stage_params, x)


def stack_stages(layer_params_stacked, n_stages: int):
    """Reshape (L, ...) stacked layer params into (P, L/P, ...) stages."""
    def r(a):
        nl = a.shape[0]
        assert nl % n_stages == 0, (nl, n_stages)
        return a.reshape(n_stages, nl // n_stages, *a.shape[1:])
    return jax.tree.map(r, layer_params_stacked)
