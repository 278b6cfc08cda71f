"""Pipeline parallelism: an explicit GPipe schedule on a ``pipe`` mesh axis.

GSPMD alone cannot express cross-microbatch pipelining, so this module
builds the schedule explicitly with shard_map + lax.ppermute:

  * stage d owns layer-slice params (stacked dim sharded over ``pipe``);
  * at tick t, stage 0 injects microbatch t; every stage applies its slice
    to the activation it holds; activations rotate d -> d+1;
  * after n_mb + n_stages - 1 ticks the last stage has every microbatch's
    output (the (n_stages-1)-tick bubble is the usual GPipe cost).

Use `pipeline_apply` for inference/forward pipelining over pods (the `pod`
axis doubles as `pipe` when PP is enabled in the launcher).  Correctness is
tested against sequential layer application on a forced multi-device CPU
(tests/test_pipeline.py, subprocess).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_apply(stage_fn: Callable, stacked_params: Any,
                   microbatches: jax.Array, mesh: Mesh,
                   axis: str = "pipe") -> jax.Array:
    """Run ``y = stage_{D-1}(...stage_0(x))`` for each microbatch with the
    GPipe rotation schedule.

    stage_fn(params_slice, x) -> y        (same shape as x)
    stacked_params: leading dim = n_stages (will be sharded over ``axis``)
    microbatches: (n_mb, ...) — replicated input, sharded schedule
    returns: (n_mb, ...) outputs (gathered from the last stage)
    """
    n_stages = dict(zip(mesh.axis_names, mesh.devices.shape))[axis]
    n_mb = microbatches.shape[0]

    def per_device(p_slice, mbs):
        p = jax.tree_util.tree_map(lambda a: a[0], p_slice)
        d = lax.axis_index(axis)
        x0 = jnp.zeros_like(mbs[0])
        outs0 = jnp.zeros_like(mbs)
        perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]

        def tick(carry, t):
            x, outs = carry
            inject = mbs[jnp.clip(t, 0, n_mb - 1)]
            x = jnp.where(d == 0, inject, x)
            y = stage_fn(p, x)
            m = t - (n_stages - 1)
            take = jnp.logical_and(d == n_stages - 1,
                                   jnp.logical_and(m >= 0, m < n_mb))
            outs = jnp.where(
                take, outs.at[jnp.clip(m, 0, n_mb - 1)].set(y), outs)
            y = lax.ppermute(y, axis, perm)
            return (y, outs), None

        (x, outs), _ = lax.scan(tick, (x0, outs0),
                                jnp.arange(n_mb + n_stages - 1))
        return outs[None]   # (1, n_mb, ...) per stage

    pspec = jax.tree_util.tree_map(
        lambda a: P(*((axis,) + (None,) * (a.ndim - 1))), stacked_params)
    fn = jax.shard_map(per_device, mesh=mesh,
                       in_specs=(pspec, P(*((None,) * microbatches.ndim))),
                       out_specs=P(axis, *((None,) * microbatches.ndim)),
                       check_vma=False)
    outs = fn(stacked_params, microbatches)
    return outs[-1]   # the last stage's collected outputs


def bubble_fraction(n_stages: int, n_mb: int) -> float:
    """GPipe bubble overhead: (D-1)/(D-1+M)."""
    return (n_stages - 1) / (n_stages - 1 + n_mb)
