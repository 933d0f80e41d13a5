"""Pipeline parallelism (GPipe-style) over a ``pipe`` mesh axis.

The assigned 40-cell baseline uses DP×TP (+pod); PP is provided for the
1000+-node regime where a model's layers exceed one pod's HBM even at full
TP — stages shard the layer stack, microbatches stream through
``jax.lax.ppermute`` boundaries inside ``shard_map``, and the bubble is the
usual (S−1)/(S−1+M).

Tested on small forced-host meshes in tests/test_pipeline.py; compose with
the planner by carving ``pipe`` out of the ``data`` axis:
    mesh = Mesh(devs.reshape(pipe, data, model), ("pipe", "data", "model")).
"""

from __future__ import annotations

import functools
from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


def pipeline_forward(stage_fn: Callable, mesh: Mesh, *, num_microbatches: int,
                     axis: str = "pipe"):
    """Build a pipelined forward: x -> stages applied in sequence.

    ``stage_fn(stage_params, x)`` applies ONE stage's layers. Stage params
    are sharded over ``axis`` (leading dim = num_stages); activations flow
    stage-to-stage with ppermute. Returns f(stage_params, x) with x
    microbatched on the leading dim.
    """
    n_stages = mesh.shape[axis]

    def pipelined(stage_params, x):
        # x: [M, mb, ...] microbatches, replicated across the pipe axis
        M = x.shape[0]
        steps = M + n_stages - 1

        def body(params_local, xs):
            # shard_map keeps the sharded stage dim as size 1 — squeeze it
            params_local = jax.tree.map(lambda p: p[0], params_local)
            idx = jax.lax.axis_index(axis)

            def step(carry, t):
                buf, outs = carry
                # stage 0 injects microbatch t; others take the permuted buf
                mb = jnp.where(t < M, t, M - 1)
                inject = xs[mb]
                cur = jnp.where(idx == 0, inject, buf)
                cur = stage_fn(params_local, cur)
                # push to the next stage
                nxt = jax.lax.ppermute(
                    cur, axis,
                    [(i, (i + 1) % n_stages) for i in range(n_stages)])
                # last stage records its output for microbatch t-(S-1)
                out_t = t - (n_stages - 1)
                valid = (idx == n_stages - 1) & (out_t >= 0) & (out_t < M)
                outs = jax.lax.cond(
                    valid,
                    lambda o: o.at[jnp.clip(out_t, 0, M - 1)].set(cur),
                    lambda o: o, outs)
                return (nxt, outs), None

            buf0 = jnp.zeros_like(xs[0])
            outs0 = jnp.zeros_like(xs)
            (_, outs), _ = jax.lax.scan(step, (buf0, outs0),
                                        jnp.arange(steps))
            # broadcast the last stage's outputs to every pipe rank
            # (psum of the masked buffer: only the last stage contributes)
            outs = jnp.where(idx == n_stages - 1, outs, 0.0)
            return jax.lax.psum(outs, axis)

        return jax.shard_map(
            body, mesh=mesh,
            in_specs=(P(axis), P()),          # stage params sharded, x replicated
            out_specs=P(),
            check_vma=False,
        )(stage_params, x)

    return pipelined


def stage_params_from_stack(stacked, n_stages: int):
    """Reshape layer-stacked params [L, ...] into [S, L/S, ...] stages."""
    return jax.tree.map(
        lambda p: p.reshape((n_stages, p.shape[0] // n_stages) + p.shape[1:]),
        stacked)
