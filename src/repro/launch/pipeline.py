"""Pipeline parallelism: GPipe-style microbatch schedule over a 'stage' axis.

The layer-stacked transformer maps naturally onto stages: each stage owns
L/S contiguous layers; activations hand off between neighbouring stages via
``ppermute`` inside ``shard_map``. The schedule runs M + S - 1 ticks; tick t
has stage s working on microbatch t - s (bubble fraction (S-1)/(M+S-1)).
Autodiff through the schedule gives the backward pipeline for free
(transpose of ppermute is the reverse permute); the stage body is remat'd
so saved activations stay O(ticks x microbatch), not O(ticks x layers).

This is the optional trainer flag promised in DESIGN.md §5; the assigned
256/512-chip dry-run meshes use DP x TP, which dominates PP at these model
sizes, so PP is exercised at test scale (tests/test_pipeline.py) and
available for deeper-than-HBM models.
"""
from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, params_staged, x_micro, mesh: Mesh,
                   axis: str = "stage"):
    """Run microbatches through the stage pipeline.

    stage_fn(params_slab, x) -> x        one stage's compute (L/S layers)
    params_staged: pytree, leaves (S, ...) — dim 0 sharded over ``axis``
    x_micro: (M, mb, ...) microbatched activations (replicated)
    Returns (M, mb, ...) outputs of the LAST stage (zeros elsewhere).
    """
    s = mesh.shape[axis]
    m = x_micro.shape[0]
    ticks = m + s - 1

    def body(params_slab, xm):
        # params_slab: (1, ...) local stage slab; xm: (M, mb, ...)
        slab = jax.tree.map(lambda a: a[0], params_slab)
        stage = jax.lax.axis_index(axis)
        fwd = [(i, i + 1) for i in range(s - 1)]

        def tick(carry, t):
            buf, out = carry  # buf: (mb, ...) current stage input
            # stage 0 injects microbatch t; others use what arrived
            inject = jnp.where(t < m, t, 0)
            x0 = xm[inject]
            x_in = jnp.where(stage == 0, x0, buf)
            active = (t - stage >= 0) & (t - stage < m)

            y = jax.checkpoint(stage_fn)(slab, x_in)
            y = jnp.where(active, y, jnp.zeros_like(y))
            # hand off to the next stage
            nxt = jax.lax.ppermute(y, axis, fwd)
            # last stage records its finished microbatch
            done_idx = jnp.clip(t - (s - 1), 0, m - 1)
            is_last = stage == s - 1
            rec = (active & is_last)
            out = out.at[done_idx].set(jnp.where(rec, y, out[done_idx]))
            return (nxt, out), None

        # carries become device-varying after the ppermute: mark them so
        buf0 = jax.lax.pcast(jnp.zeros_like(xm[0]), (axis,), to="varying")
        out0 = jax.lax.pcast(jnp.zeros_like(xm), (axis,), to="varying")
        (buf, out), _ = jax.lax.scan(tick, (buf0, out0),
                                     jnp.arange(ticks, dtype=jnp.int32))
        # every stage holds `out`; only the last stage's is real
        return jax.lax.psum(jnp.where(stage == s - 1, out, jnp.zeros_like(out)),
                            axis)

    spec_p = jax.tree.map(lambda a: P(axis, *(None,) * (a.ndim - 1)), params_staged)
    fn = jax.shard_map(body, mesh=mesh, in_specs=(spec_p, P()), out_specs=P())
    return fn(params_staged, x_micro)


def stage_params(params_stacked, n_stages: int):
    """Reshape (L, ...) layer-stacked params to (S, L/S, ...) stage slabs."""
    def one(a):
        l = a.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return a.reshape((n_stages, l // n_stages) + a.shape[1:])
    return jax.tree.map(one, params_stacked)
