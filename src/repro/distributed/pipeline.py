"""GPipe-style pipeline parallelism over a mesh axis (default: "pod").

``pipeline_apply`` runs S stages over M microbatches inside shard_map with
``jax.lax.ppermute`` boundary transfers: the classic (M + S - 1)-tick
schedule. Stage parameters are sharded over the pipeline axis (stage s lives
on pipeline rank s), so per-chip parameter memory drops by S at the cost of
bubble fraction (S-1)/(M+S-1).

This is the ``--pipeline pod`` option of the launcher: with 2 pods the
cross-pod link carries only [B_micro, S, D] activations per tick instead of
a full gradient all-reduce. The trade-off is measured in EXPERIMENTS.md
§Perf.
"""

from __future__ import annotations

from typing import Any, Callable

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def pipeline_apply(
    stage_fn: Callable[[Any, jax.Array], jax.Array],
    stage_params: Any,  # leaves with leading [n_stages, ...]
    x: jax.Array,  # [n_micro, B_micro, ...] microbatched activations
    *,
    mesh,
    axis: str = "pod",
) -> jax.Array:
    """Run ``x`` through S pipeline stages; returns transformed microbatches.

    stage_fn(params_slice, x_micro) -> x_micro. Stage parameters enter
    sharded over ``axis`` (leading dim); activations are replicated across
    ``axis`` outside and stream through ranks inside.
    """
    n_stages = mesh.shape[axis]
    n_micro = x.shape[0]
    assert n_micro >= 1

    def inner(params_local, x_local):
        # params_local: [1, ...] this rank's stage. x_local: all microbatches.
        params_here = jax.tree.map(lambda l: l[0], params_local)
        rank = jax.lax.axis_index(axis)
        ticks = n_micro + n_stages - 1
        buf = jnp.zeros_like(x_local[0])
        outputs = jnp.zeros_like(x_local)

        def tick(t, carry):
            buf, outputs = carry
            # Stage 0 ingests microbatch t (if any); others use the received buffer.
            mb_idx = jnp.clip(t, 0, n_micro - 1)
            x_in = jnp.where(rank == 0, x_local[mb_idx], buf)
            y = stage_fn(params_here, x_in)
            # Mask ticks where this rank has no live microbatch.
            live = (t - rank >= 0) & (t - rank < n_micro)
            y = jnp.where(live, y, jnp.zeros_like(y))
            # Last stage writes its finished microbatch t - (S-1).
            out_idx = jnp.clip(t - (n_stages - 1), 0, n_micro - 1)
            write = (rank == n_stages - 1) & live
            outputs = jax.lax.dynamic_update_index_in_dim(
                outputs,
                jnp.where(write, y, outputs[out_idx]),
                out_idx,
                axis=0,
            )
            # Shift activations to the next rank.
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages) for i in range(n_stages)]
            )
            return buf, outputs

        _, outputs = jax.lax.fori_loop(0, ticks, tick, (buf, outputs))
        # Outputs are only valid on the last rank: mask + psum broadcasts.
        if n_stages > 1:
            outputs = jax.lax.psum(
                jnp.where(rank == n_stages - 1, outputs, jnp.zeros_like(outputs)), axis
            )
        return outputs

    in_specs = (
        jax.tree.map(lambda _: P(axis), stage_params),
        P(),
    )
    return jax.shard_map(
        inner, mesh=mesh, in_specs=in_specs, out_specs=P(), check_vma=False
    )(stage_params, x)
