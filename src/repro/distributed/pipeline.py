"""GPipe-style pipeline parallelism over a ``stage`` mesh axis.

The production meshes for this assignment are (data, model) / (pod, data,
model); PP is provided as an optional axis for deployments that prefer
pipeline over pure FSDPxTP (e.g. cross-pod stages).  Implementation:
``shard_map`` over ``stage`` — each stage holds a slice of the layer stack
(params sharded with P("stage") on the stacked-layer axis), microbatches
stream through stages with ``jax.lax.ppermute`` boundary transfers in a
classic GPipe schedule of ``n_micro + n_stages - 1`` ticks.

Numerically equivalent to running the full stack sequentially (tested on a
forced multi-device host in tests/test_distributed.py).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipelined_apply", "sequential_apply"]


def sequential_apply(layer_fn, stacked_params, x):
    """Reference: apply all L stacked layers in order.  x [B, ...]."""

    def body(h, p):
        return layer_fn(p, h), None

    h, _ = jax.lax.scan(body, x, stacked_params)
    return h


def pipelined_apply(layer_fn, stacked_params, x, *, mesh: Mesh,
                    n_micro: int, stage_axis: str = "stage",
                    layers_per_stage: int | None = None):
    """GPipe forward over the ``stage`` axis of ``mesh``.

    stacked_params: pytree with leading layer axis L = n_stages * per_stage.
    x: [B, ...] with B % n_micro == 0.
    """
    n_stages = mesh.shape[stage_axis]
    L = jax.tree.leaves(stacked_params)[0].shape[0]
    assert L % n_stages == 0, (L, n_stages)
    B = x.shape[0]
    assert B % n_micro == 0, (B, n_micro)
    mb = B // n_micro

    def stage_fn(params_stage, x_all):
        # params_stage: this stage's [L/n_stages, ...] slice (via shard_map)
        sid = jax.lax.axis_index(stage_axis)
        n_ticks = n_micro + n_stages - 1
        out = jnp.zeros_like(x_all)
        carry = jnp.zeros((mb,) + x_all.shape[1:], x_all.dtype)

        def tick(t, state):
            out, carry = state
            # stage 0 ingests microbatch t (if within range)
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            fresh = jax.lax.dynamic_slice_in_dim(x_all, mb_idx * mb, mb, 0)
            h = jnp.where(sid == 0, fresh, carry)

            def body(hh, p):
                return layer_fn(p, hh), None

            h, _ = jax.lax.scan(body, h, params_stage)
            # last stage emits microbatch (t - n_stages + 1)
            emit_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            emit = (sid == n_stages - 1) & (t >= n_stages - 1)
            cur = jax.lax.dynamic_slice_in_dim(out, emit_idx * mb, mb, 0)
            upd = jnp.where(emit, h, cur)
            out = jax.lax.dynamic_update_slice_in_dim(out, upd, emit_idx * mb, 0)
            # shift activations to the next stage
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            carry = jax.lax.ppermute(h, stage_axis, perm)
            return out, carry

        out, _ = jax.lax.fori_loop(0, n_ticks, tick, (out, carry))
        # only the last stage holds results; others contribute zeros
        return jax.lax.psum(out, stage_axis)

    pspec_params = jax.tree.map(lambda _: P(stage_axis), stacked_params)
    f = jax.shard_map(stage_fn, mesh=mesh,
                  in_specs=(pspec_params, P()),
                  out_specs=P(), check_vma=False)
    return f(stacked_params, x)
