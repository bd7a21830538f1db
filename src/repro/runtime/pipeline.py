"""GPipe-style pipeline parallelism over a "stage" mesh axis.

The framework's default distribution is 2-D FSDP x TP (sharding.py); this
module adds the third option for very deep archs (deepseek-67b: 95 layers)
or cross-pod scaling where DCN bandwidth makes FSDP all-gathers expensive:
split the layer stack into S stages, shard microbatches through them with
``jax.lax.ppermute`` inside a ``shard_map``, and overlap stage compute with
the point-to-point transfers (XLA's latency-hiding scheduler handles the
async pairs; the schedule below is the standard GPipe fill-drain with
B microbatches -> pipeline bubble S-1 / (B + S - 1)).

Layout contract: stage-stacked parameters [S, ...] sharded over "stage";
inputs [B_micro, ...] replicated along "stage" (each stage computes every
microbatch but only its own layer slice — activations flow, weights stay).

``pipelined_apply`` is deliberately model-agnostic: it takes
``stage_fn(stage_params, h) -> h`` (one stage's layer run, e.g. the scanned
transformer block group) and composes the schedule around it.
"""
from __future__ import annotations

from typing import Callable

import jax
import jax.numpy as jnp
from jax.sharding import Mesh
from jax.sharding import PartitionSpec as P


def pipelined_apply(
    stage_fn: Callable,
    stage_params,
    h: jax.Array,              # [n_micro, micro_batch, ...] microbatched input
    mesh: Mesh,
    stage_axis: str = "stage",
):
    """Run h through S pipeline stages with the GPipe fill-drain schedule.

    Returns the output of the LAST stage for every microbatch, in order.
    Inside shard_map each device holds stage s's params and, at tick t,
    works on microbatch (t - s); ppermute shifts activations s -> s+1.
    """
    n_stages = mesh.shape[stage_axis]
    n_micro = h.shape[0]
    n_ticks = n_micro + n_stages - 1

    def body(params_s, h_all):
        # params_s: this stage's slice [1, ...] (shard_map strips nothing —
        # leading stage dim becomes size 1); h_all: [n_micro, mb, ...]
        params_local = jax.tree.map(lambda x: x[0], params_s)
        sid = jax.lax.axis_index(stage_axis)

        def tick(carry, t):
            acc, inflight = carry
            # stage 0 injects microbatch t; others take the permuted input
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            injected = h_all[mb_idx]
            x_in = jnp.where(sid == 0, injected, inflight)
            y = stage_fn(params_local, x_in)
            # last stage banks its result for microbatch (t - (S-1))
            out_idx = t - (n_stages - 1)
            valid = (out_idx >= 0) & (sid == n_stages - 1)
            acc = jax.lax.cond(
                valid,
                lambda a: jax.lax.dynamic_update_index_in_dim(
                    a, y, jnp.maximum(out_idx, 0), 0),
                lambda a: a, acc)
            # shift activations to the next stage (ring; last->first unused)
            perm = [(i, (i + 1) % n_stages) for i in range(n_stages)]
            nxt = jax.lax.ppermute(y, stage_axis, perm)
            return (acc, nxt), None

        acc0 = jnp.zeros((n_micro,) + h_all.shape[1:], h_all.dtype)
        (acc, _), _ = jax.lax.scan(
            tick, (acc0, jnp.zeros_like(h_all[0])), jnp.arange(n_ticks))
        # every device returns the full acc; only the last stage's is real —
        # zero the others and psum to replicate it along the stage axis.
        acc = jnp.where(sid == n_stages - 1, acc, jnp.zeros_like(acc))
        return jax.lax.psum(acc, stage_axis)

    spec_params = jax.tree.map(lambda _: P(stage_axis), stage_params)
    fn = jax.shard_map(
        body, mesh=mesh,
        in_specs=(spec_params, P()),          # params stage-sharded, h replicated
        out_specs=P(),
        check_vma=False,
    )
    return fn(stage_params, h)


def bubble_fraction(n_stages: int, n_micro: int) -> float:
    """GPipe bubble overhead: idle ticks / total ticks."""
    return (n_stages - 1) / (n_micro + n_stages - 1)


def stage_split(defs_or_params, n_stages: int):
    """Split a layer-stacked pytree [L, ...] into [S, L/S, ...]."""
    def one(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape((n_stages, l // n_stages) + x.shape[1:])
    return jax.tree.map(one, defs_or_params)
