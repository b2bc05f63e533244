"""Optional pipeline parallelism: GPipe-style microbatch pipeline over a
`pipe` mesh axis using ``shard_map`` + ``jax.lax.ppermute``.

At the 512-chip production scale FSDP×TP suffices (and avoids bubbles), so
PP is off by default; this module exists for the >4k-chip regime where a
`pipe` axis bounds the FSDP all-gather ring. The schedule is the classic
GPipe fill-drain: with M microbatches and P stages, bubble fraction =
(P-1)/(M+P-1).

Activations hop stages with ``ppermute`` (collective-permute on the wire —
point-to-point, ICI/DCN friendly). Correctness is tested against a
sequential stage composition in tests/test_distributed.py on 4 host
devices.
"""

from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P


__all__ = ["pipeline_apply", "bubble_fraction"]


def bubble_fraction(n_stages: int, n_microbatches: int) -> float:
    return (n_stages - 1) / (n_microbatches + n_stages - 1)


def pipeline_apply(
    stage_fn: Callable,  # (stage_params, x_microbatch) -> x_microbatch
    stage_params,  # pytree stacked over stages (leading dim = P)
    x: jnp.ndarray,  # (M, mb, ...) microbatched input
    mesh: Mesh,
    *,
    axis: str = "pipe",
):
    """Run a P-stage pipeline over M microbatches; returns (M, mb, ...)."""
    n_stages = mesh.shape[axis]
    M = x.shape[0]
    steps = M + n_stages - 1

    def body(params, xs):
        params = jax.tree.map(lambda p: p[0], params)  # this device's stage
        stage = jax.lax.axis_index(axis)

        def step(carry, t):
            acc, inflight = carry
            mb_idx = jnp.clip(t, 0, M - 1)
            first_in = jax.lax.dynamic_index_in_dim(xs, mb_idx, axis=0, keepdims=False)
            inp = jnp.where(stage == 0, first_in, inflight)
            out = stage_fn(params, inp)
            active = jnp.logical_and(t - stage >= 0, t - stage < M)
            out = jnp.where(active, out, jnp.zeros_like(out))
            # the last stage emits microbatch t (its `active` window aligns)
            emit = jnp.logical_and(stage == n_stages - 1, active)
            out_idx = jnp.clip(t - (n_stages - 1), 0, M - 1)
            prev = jax.lax.dynamic_index_in_dim(acc, out_idx, axis=0, keepdims=False)
            acc = jax.lax.dynamic_update_index_in_dim(
                acc, jnp.where(emit, out, prev), out_idx, axis=0
            )
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(out, axis, perm)
            return (acc, nxt), None

        acc0 = jnp.zeros_like(xs)
        inflight0 = jnp.zeros_like(xs[0])
        (acc, _), _ = jax.lax.scan(step, (acc0, inflight0), jnp.arange(steps))
        # only the last stage's accumulator is populated → psum broadcasts it
        acc = jnp.where(stage == n_stages - 1, acc, jnp.zeros_like(acc))
        return jax.lax.psum(acc, axis)

    nd = x.ndim
    return jax.shard_map(
        body,
        mesh=mesh,
        in_specs=(P(axis), P(*([None] * nd))),
        out_specs=P(*([None] * nd)),
        check_vma=False,
    )(stage_params, x)
