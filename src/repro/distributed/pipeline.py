"""GPipe-style pipeline parallelism over a 'pipe' mesh axis via shard_map.

The assigned production mesh is (pod, data, model) — PP is the optional
fourth axis for depth-dominated models (deepseek-67b at 95 layers is the
natural customer).  Each pipeline stage owns one slice of the layer stack;
microbatches rotate through stages with ``jax.lax.ppermute`` on the classic
bubble schedule (S + M - 1 ticks for S stages / M microbatches; bubble
fraction (S-1)/(M+S-1)).

Microbatch m is processed by stage s at tick m + s and retires from the
last stage at tick m + S - 1.  Inputs are replicated to the pipe group
(stage 0 injects), outputs are psum-collected from the last stage.

Exercised by tests/test_distributed.py (single-stage identity inline + a
4-stage subprocess run on forced host devices) — the 40-cell dry-run mesh
has no pipe axis, by assignment.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

__all__ = ["pipeline_apply"]


def pipeline_apply(
    stage_fn: Callable,  # (stage_params, x) -> x (same shape)
    stage_params,  # leaves with leading dim n_stages (sharded over 'pipe')
    x: jnp.ndarray,  # (n_micro, micro_batch, ...) microbatched input
    mesh: Mesh,
    *,
    axis: str = "pipe",
) -> jnp.ndarray:
    n_stages = int(mesh.shape[axis])
    n_micro = x.shape[0]

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis), P()),
        out_specs=P(),
        check_vma=False,
    )
    def run(params, xs):
        params = jax.tree.map(lambda a: a[0], params)  # this stage's slice
        stage = jax.lax.axis_index(axis)
        cur = jnp.zeros_like(xs[0])
        buf = jnp.zeros_like(xs)
        ticks = n_micro + n_stages - 1
        fwd = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(t, state):
            cur, buf = state
            x_in = xs[jnp.minimum(t, n_micro - 1)]
            inject = (stage == 0) & (t < n_micro)
            cur = jnp.where(inject, x_in, cur)
            out = stage_fn(params, cur)
            retire_idx = t - (n_stages - 1)
            do_retire = (stage == n_stages - 1) & (retire_idx >= 0)
            upd = jax.lax.dynamic_update_index_in_dim(
                buf, out, jnp.clip(retire_idx, 0, n_micro - 1), 0)
            buf = jnp.where(do_retire, upd, buf)
            cur = jax.lax.ppermute(out, axis, fwd)
            return cur, buf

        cur, buf = jax.lax.fori_loop(0, ticks, tick, (cur, buf))
        mask = (stage == n_stages - 1).astype(buf.dtype)
        return jax.lax.psum(buf * mask, axis)

    return run(stage_params, x)
