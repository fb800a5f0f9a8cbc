"""Pipeline parallelism: GPipe-style microbatching over a 'pp' mesh axis.

New capability (the reference has no intra-model parallelism, SURVEY.md
§2.2).  TPU-idiomatic design: the layer stack is split into S contiguous
stages, each stage's parameters live on one slice of the 'pp' axis, and
activations flow stage-to-stage over ICI via ``lax.ppermute`` inside a
``shard_map``.  The schedule is a single ``lax.scan`` over M + S - 1 ticks
(fill + steady state + drain); every tick each device runs its own stage
on the microbatch it just received and forwards the result to its
neighbor.  Everything is differentiable — ppermute/scan/where all have
transpose rules — so ``jax.grad`` through ``pipeline_apply`` yields
pipeline-parallel backprop with no hand-written backward schedule.

Layout contract: stage parameters are any pytree whose leaves carry a
leading [S] axis sharded P('pp'); activations are replicated in and out
(the final psum broadcast makes every stage hold the outputs, which keeps
the loss/backward simple at small scale — revisit for giant batches).
"""

from __future__ import annotations

from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, PartitionSpec as P


def split_stages(layer_params: Any, num_stages: int) -> Any:
    """Reshape stacked per-layer params [L, ...] -> [S, L/S, ...]."""
    def leaf(x):
        l = x.shape[0]
        if l % num_stages:
            raise ValueError(f"num_layers={l} not divisible by "
                             f"pp={num_stages}")
        return x.reshape(num_stages, l // num_stages, *x.shape[1:])
    return jax.tree.map(leaf, layer_params)


def merge_stages(stage_params: Any) -> Any:
    """Inverse of split_stages: [S, L/S, ...] -> [L, ...]."""
    return jax.tree.map(
        lambda x: x.reshape(x.shape[0] * x.shape[1], *x.shape[2:]),
        stage_params)


def pipeline_apply(
    mesh: Mesh,
    stage_fn: Callable[..., jax.Array],
    stage_params: Any,
    microbatches: jax.Array,
    extras: Any = None,
    axis_name: str = "pp",
) -> jax.Array:
    """Run ``stage_fn`` (one stage's layers) as an S-stage GPipe pipeline.

    stage_params: pytree with leading [S] axis (see split_stages), sharded
    over ``axis_name``.  microbatches: [M, mb, ...] activations.
    ``extras``: replicated side inputs passed to every stage call
    (e.g. RoPE sin/cos).  Returns [M, mb, ...] outputs (replicated).
    """
    num_stages = mesh.shape[axis_name]
    num_micro = microbatches.shape[0]

    @partial(shard_map, mesh=mesh,
             in_specs=(P(axis_name), P(), P()), out_specs=P(),
             check_vma=False)
    def run(params_local, mb_all, extras_):
        # params_local: [1, L/S, ...] — this device's stage; squeeze it.
        params_stage = jax.tree.map(lambda x: x[0], params_local)
        idx = jax.lax.axis_index(axis_name)
        first, last = idx == 0, idx == num_stages - 1
        fwd = [(i, (i + 1) % num_stages) for i in range(num_stages)]

        zero = jnp.zeros_like(mb_all[0])
        out0 = jnp.zeros_like(mb_all)

        def tick(carry, t):
            recv, out = carry
            # Stage 0 injects microbatch t (clamped during the drain
            # phase — those outputs are never collected).
            inject = mb_all[jnp.clip(t, 0, num_micro - 1)]
            x_in = jnp.where(first, inject, recv)
            y = stage_fn(params_stage, x_in, extras_)
            # The last stage finishes microbatch t-(S-1) at tick t.
            m = t - (num_stages - 1)
            collect = last & (m >= 0)
            out = jax.lax.dynamic_update_slice(
                out, jnp.where(collect, y, out[jnp.clip(m, 0, num_micro - 1)]
                               )[None],
                (jnp.clip(m, 0, num_micro - 1),) + (0,) * (out.ndim - 1))
            recv = jax.lax.ppermute(y, axis_name, fwd)
            return (recv, out), None

        (recv, out), _ = jax.lax.scan(
            tick, (zero, out0), jnp.arange(num_micro + num_stages - 1))
        # Broadcast the last stage's collected outputs to every stage.
        return jax.lax.psum(jnp.where(last, out, jnp.zeros_like(out)),
                            axis_name)

    if extras is None:
        extras = ()
    return run(stage_params, microbatches, extras)
