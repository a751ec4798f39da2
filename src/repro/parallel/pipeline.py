"""Pipeline parallelism: gpipe microbatch schedule over a 'stage' mesh axis.

Implemented with shard_map + collective_permute — the jax-native mapping of
the paper-era NCCL send/recv pipelines.  The production dry-run mesh uses
FSDP x TP x pod (all 40 cells fit without PP), so this module is the
*capability* deliverable: it is exercised by tests on a host-device mesh and
is what a >2-pod deployment of the 405B would enable on the 'pod' axis.

Schedule: classic fill-drain gpipe.  For n_micro microbatches and n_stages
stages, the loop runs n_micro + n_stages - 1 ticks; at tick t, stage s
processes microbatch (t - s) when 0 <= t - s < n_micro.  Activations advance
one stage per tick via ppermute; outputs accumulate on the last stage and are
broadcast back at the end (psum over a one-hot mask).
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P



def pipeline_apply(stage_fn: Callable[[Any, jax.Array], jax.Array],
                   stage_params: Any, x: jax.Array, *, mesh: Mesh,
                   axis: str = "stage") -> jax.Array:
    """Run x through n_stages sequential stages with gpipe microbatching.

    stage_params: pytree whose leaves have leading dim n_stages (stage i's
      slice parameterizes stage_fn at stage i); sharded over `axis`.
    x: (n_micro, micro_batch, ...) microbatched input, replicated.
    Returns (n_micro, micro_batch, ...) outputs, replicated on every device.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    total = n_micro + n_stages - 1
    perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

    def per_device(params, xs):
        # params leaves: (1, ...) — this device's stage slice
        params = jax.tree_util.tree_map(lambda p: p[0], params)
        stage = jax.lax.axis_index(axis)
        state = jnp.zeros_like(xs[0])                    # inflight activation
        outputs = jnp.zeros_like(xs)

        def tick(t, carry):
            state, outputs = carry
            mb = t - stage                                # microbatch index
            valid = (mb >= 0) & (mb < n_micro)
            inject = xs[jnp.clip(t, 0, n_micro - 1)]
            x_in = jnp.where(stage == 0, inject, state)
            y = stage_fn(params, x_in)
            y = jnp.where(valid, y, state)
            out_t = t - (n_stages - 1)
            write = (stage == n_stages - 1) & (out_t >= 0) & (out_t < n_micro)
            outputs = jax.lax.cond(
                write,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.clip(out_t, 0, n_micro - 1), 0),
                lambda o: o, outputs)
            state = jax.lax.ppermute(y, axis, perm)
            return state, outputs

        _, outputs = jax.lax.fori_loop(0, total, tick, (state, outputs))
        # broadcast last stage's outputs to all stages
        mask = (stage == n_stages - 1).astype(outputs.dtype)
        return jax.lax.psum(outputs * mask, axis)

    spec_p = jax.tree_util.tree_map(
        lambda _: P(axis), stage_params)
    fn = jax.shard_map(per_device, mesh=mesh,
                   in_specs=(spec_p, P()), out_specs=P(),
                   check_vma=False)
    return fn(stage_params, x)


def stage_split(params: Any, n_stages: int) -> Any:
    """Reshape a stacked-layer tree (L, ...) into (n_stages, L//n_stages, ...)
    so each pipeline stage owns a contiguous block of layers."""
    def one(p):
        L = p.shape[0]
        assert L % n_stages == 0, (L, n_stages)
        return p.reshape(n_stages, L // n_stages, *p.shape[1:])
    return jax.tree_util.tree_map(one, params)
