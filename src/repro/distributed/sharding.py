"""Logical-axis sharding rules for the production mesh.

Every parameter and key activation carries a tuple of *logical* axis names;
``ShardingRules`` maps those to mesh axes. The production mesh is
``("data", "model")`` single-pod or ``("pod", "data", "model")`` multi-pod
(see launch/mesh.py); "pod" acts as an extra pure-DP axis by default.

Conventions (see DESIGN.md §6):
  * batch                  -> ("pod", "data")   (DP)
  * heads / kv_heads / ffn / vocab -> "model"   (TP, Megatron col->row)
  * experts                -> "data"            (EP; a2a stays intra-pod)
  * embed / model dims     -> replicated
  * optimizer states       -> additionally sharded over "data" (ZeRO-1)
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Optional, Sequence, Tuple, Union

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

MeshAxes = Union[None, str, Tuple[str, ...]]


@dataclasses.dataclass(frozen=True)
class ShardingRules:
    rules: Dict[str, MeshAxes]

    def spec(self, logical_axes: Optional[Sequence[Optional[str]]]) -> P:
        if logical_axes is None:
            return P()
        parts = []
        used: set = set()
        for ax in logical_axes:
            mesh_axes = self.rules.get(ax) if ax is not None else None
            if mesh_axes is None:
                parts.append(None)
                continue
            if isinstance(mesh_axes, str):
                mesh_axes = (mesh_axes,)
            # A mesh axis may appear at most once in a PartitionSpec.
            free = tuple(m for m in mesh_axes if m not in used)
            used.update(free)
            parts.append(free if len(free) > 1 else (free[0] if free else None))
        return P(*parts)

    def sharding(self, mesh: Mesh, logical_axes) -> NamedSharding:
        return NamedSharding(mesh, self.spec(logical_axes))

    def with_overrides(self, **overrides: MeshAxes) -> "ShardingRules":
        merged = dict(self.rules)
        merged.update(overrides)
        return ShardingRules(merged)


def default_rules(mesh: Mesh) -> ShardingRules:
    """Rules for both single-pod and multi-pod meshes."""
    has_pod = "pod" in mesh.axis_names
    batch_axes: MeshAxes = ("pod", "data") if has_pod else ("data",)
    return ShardingRules(
        {
            # activations
            "batch": batch_axes,
            "seq": None,
            "seq_shard": ("data",),  # sequence parallelism (long-context)
            "embed": None,
            # attention
            "heads": ("model",),
            "kv_heads": ("model",),
            "head_dim": None,
            "qk_lora": None,
            # mlp
            "ffn": ("model",),
            # embeddings / output
            "vocab": ("model",),
            # MoE
            "experts": ("data",),
            "expert_ffn": ("model",),
            # recurrent / ssm
            "ssm_inner": ("model",),
            "ssm_state": None,
            # conv frontends
            "conv_k": None,
        }
    )


def logical_sharding_tree(abstract_tree, logical_tree, mesh: Mesh, rules: ShardingRules):
    """Map a pytree of logical-axis tuples to NamedShardings."""
    return jax.tree.map(
        lambda _, la: rules.sharding(mesh, la),
        abstract_tree,
        logical_tree,
        is_leaf=lambda x: x is None,
    )


def constrain(x: jax.Array, rules: ShardingRules, *logical_axes: Optional[str]) -> jax.Array:
    """with_sharding_constraint via logical axes (no-op outside jit/mesh)."""
    try:
        return jax.lax.with_sharding_constraint(x, rules.spec(logical_axes))
    except (ValueError, RuntimeError):
        return x


def fit_spec(spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """Drop mesh axes whose size does not divide the dimension they shard.

    Explicit jit in_shardings require exact divisibility; dims that cannot
    shard evenly fall back to replication (e.g. qwen2.5's 40 heads on a
    16-wide model axis — a documented baseline cost, see EXPERIMENTS.md
    §Perf). Axis *prefixes* that divide are kept: ('pod','data') on a batch
    divisible by pod but not pod*data keeps 'pod'.
    """
    parts = list(spec) + [None] * (len(shape) - len(spec))
    out = []
    for dim, p in zip(shape, parts):
        if p is None:
            out.append(None)
            continue
        axes = p if isinstance(p, tuple) else (p,)
        kept = []
        size = 1
        for a in axes:
            nxt = size * mesh.shape[a]
            if dim % nxt == 0:
                kept.append(a)
                size = nxt
            else:
                break
        out.append(tuple(kept) if len(kept) > 1 else (kept[0] if kept else None))
    while out and out[-1] is None:
        out.pop()
    return P(*out)


def batch_partition(mesh: Mesh, n: int) -> P:
    """Largest prefix of DP axes that divides a batch of size n."""
    axes = [a for a in ("pod", "data") if a in mesh.axis_names]
    chosen = []
    size = 1
    for a in axes:
        if n % (size * mesh.shape[a]) == 0:
            chosen.append(a)
            size *= mesh.shape[a]
    if not chosen:
        return P()
    return P(tuple(chosen) if len(chosen) > 1 else chosen[0])


def zero1_spec(param_spec: P, shape: Tuple[int, ...], mesh: Mesh) -> P:
    """ZeRO-1: extend a parameter spec with 'data' sharding on the first
    free dimension divisible by the data-axis size (optimizer states only).

    Falls back to the unmodified spec when nothing divides — correctness
    first, memory second.
    """
    if "data" not in mesh.axis_names:
        return param_spec
    data_size = mesh.shape["data"]
    parts = list(param_spec) + [None] * (len(shape) - len(param_spec))
    used = set()
    for p in parts:
        for a in (p if isinstance(p, tuple) else (p,)):
            if a:
                used.add(a)
    if "data" in used:
        return param_spec
    # Account for existing sharding when checking divisibility.
    for i, (dim, p) in enumerate(zip(shape, parts)):
        denom = 1
        for a in (p if isinstance(p, tuple) else (p,)):
            if a:
                denom *= mesh.shape[a]
        local = dim // denom if denom and dim % denom == 0 else dim
        if p is None and dim % data_size == 0:
            parts[i] = "data"
            return P(*parts)
        if p is not None and dim % (denom * data_size) == 0:
            cur = p if isinstance(p, tuple) else (p,)
            parts[i] = tuple(a for a in cur if a) + ("data",)
            return P(*parts)
        del local
    return param_spec


def mesh_device_count(mesh: Mesh) -> int:
    return int(np.prod([mesh.shape[a] for a in mesh.axis_names]))
