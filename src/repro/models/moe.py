"""Mixture-of-Experts layer with explicit expert parallelism (DeepSeek-style).

Sharding design (DESIGN.md §6):
  * experts sharded over the ``data`` axis (EP) — all-to-all stays intra-pod;
  * token-slot pairs additionally split over the ``model`` axis, so dispatch
    activation volume per chip is T*k*D / (ep*tp);
  * expert weights are replicated across ``model`` within a data row (their
    optimizer states are ZeRO-sharded over ``model`` instead — see
    train/optimizer.py);
  * shared experts (DeepSeek's always-on experts) run as a plain TP MLP.

The dispatch is a shard_map region: top-k routing, capacity-bounded
scatter into per-destination send buffers, ``jax.lax.all_to_all`` over
``data``, a second capacity-bounded dispatch onto local experts, grouped
expert matmul, and the inverse path. Tokens over capacity are dropped
(GShard semantics, capacity_factor configurable); an auxiliary
load-balancing loss is returned to the trainer.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from .layers import ParamDef, act_fn


def moe_defs(
    n_layers: int,
    d_model: int,
    n_experts: int,
    d_ff_expert: int,
    n_shared: int,
) -> Dict[str, Any]:
    L = (n_layers,) if n_layers else ()
    pl = (None,) * len(L)
    defs: Dict[str, Any] = {
        "router": ParamDef(L + (d_model, n_experts), pl + ("embed", None), dtype=jnp.float32),
        "w_gate": ParamDef(L + (n_experts, d_model, d_ff_expert), pl + ("experts", "embed", None)),
        "w_up": ParamDef(L + (n_experts, d_model, d_ff_expert), pl + ("experts", "embed", None)),
        "w_down": ParamDef(L + (n_experts, d_ff_expert, d_model), pl + ("experts", None, "embed")),
    }
    if n_shared:
        d_sh = n_shared * d_ff_expert
        defs["shared"] = {
            "w_gate": ParamDef(L + (d_model, d_sh), pl + ("embed", "ffn")),
            "w_up": ParamDef(L + (d_model, d_sh), pl + ("embed", "ffn")),
            "w_down": ParamDef(L + (d_sh, d_model), pl + ("ffn", "embed")),
        }
    return defs


def _axis_size(name: str) -> int:
    try:
        return jax.lax.axis_size(name)
    except NameError:
        return 1


def _dispatch(flat_idx, values, n_dest, capacity, fill=0):
    """Scatter ``values`` [P, ...] into [n_dest, capacity, ...] buffers.

    flat_idx: [P] destination ids (−1 = invalid). Returns (buffers, slot,
    kept) where ``slot`` is each pair's row in its destination buffer
    (capacity overflow and invalid pairs land in a trash row that is sliced
    off — GShard-style token dropping). ``fill`` sets the empty-slot value
    (use −1 for id buffers so empty slots are not mistaken for expert 0).
    """
    onehot = jax.nn.one_hot(flat_idx, n_dest, dtype=jnp.int32)  # invalid -> 0s
    slot = jnp.cumsum(onehot, axis=0) - onehot
    slot = jnp.sum(slot * onehot, axis=1)  # [P]
    valid = (flat_idx >= 0) & (slot < capacity)
    dest = jnp.where(valid, flat_idx, n_dest - 1)
    row = jnp.where(valid, slot, capacity)  # trash row
    buf_shape = (n_dest, capacity + 1) + values.shape[1:]
    buffers = jnp.full(buf_shape, fill, values.dtype).at[dest, row].set(values)
    return buffers[:, :capacity], slot, valid


def moe_layer(
    params: Dict[str, Any],
    x: jax.Array,  # [B, S, D] (batch sharded over dp axes, replicated over model)
    *,
    mesh,
    top_k: int,
    capacity_factor: float = 1.25,
    activation: str = "silu",
    dp_axes: Tuple[str, ...] = ("data",),
    ep_axis: str = "data",
    tp_axis: str = "model",
) -> Tuple[jax.Array, jax.Array]:
    """Routed experts. Returns (y, aux_loss)."""
    n_experts = params["w_gate"].shape[0]
    d_model = x.shape[-1]
    ep = mesh.shape[ep_axis] if ep_axis in mesh.axis_names else 1
    tp = mesh.shape[tp_axis] if tp_axis in mesh.axis_names else 1
    assert n_experts % ep == 0, (n_experts, ep)
    e_local = n_experts // ep

    batch_spec = tuple(a for a in dp_axes if a in mesh.axis_names)
    x_spec = P(batch_spec if len(batch_spec) > 1 else (batch_spec[0] if batch_spec else None), None, None)
    w_spec = P(ep_axis, None, None)  # experts sharded over data
    r_spec = P(None, None)

    def inner(x_l, router_w, w_gate, w_up, w_down):
        B_l, S, D = x_l.shape
        T = B_l * S
        xf = x_l.reshape(T, D)

        # ---- routing (computed redundantly per model shard; cheap) -------
        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), router_w)
        probs = jax.nn.softmax(logits, axis=-1)
        w_topk, idx_topk = jax.lax.top_k(probs, top_k)  # [T, k]
        w_topk = w_topk / jnp.maximum(w_topk.sum(-1, keepdims=True), 1e-9)

        # load-balance aux loss (Switch/GShard form)
        me = probs.mean(axis=0)
        ce = jnp.zeros((n_experts,), jnp.float32).at[idx_topk.reshape(-1)].add(1.0)
        ce = ce / jnp.maximum(ce.sum(), 1.0)
        aux = n_experts * jnp.sum(me * ce)

        # ---- split token-slot pairs over the model axis -------------------
        mi = jax.lax.axis_index(tp_axis) if tp > 1 else jnp.int32(0)
        P_total = T * top_k
        pair_token = jnp.repeat(jnp.arange(T), top_k)
        pair_expert = idx_topk.reshape(-1)
        pair_w = w_topk.reshape(-1)
        P_pad = -(-P_total // tp) * tp
        pad = P_pad - P_total
        pair_token = jnp.pad(pair_token, (0, pad))
        pair_expert = jnp.pad(pair_expert, (0, pad), constant_values=-1)
        pair_w = jnp.pad(pair_w, (0, pad))
        P_l = P_pad // tp
        sl = mi * P_l
        my_token = jax.lax.dynamic_slice_in_dim(pair_token, sl, P_l)
        my_expert = jax.lax.dynamic_slice_in_dim(pair_expert, sl, P_l)
        my_w = jax.lax.dynamic_slice_in_dim(pair_w, sl, P_l)

        # ---- first dispatch: to expert-owning data shards ------------------
        cap1 = max(8, int(math.ceil(P_l / ep * capacity_factor)))
        dest = jnp.where(my_expert >= 0, my_expert // e_local, -1)
        x_pairs = xf[my_token]  # [P_l, D]
        send_x, slot1, valid1 = _dispatch(dest, x_pairs, ep, cap1)
        meta = jnp.where(valid1, my_expert % e_local, -1)
        send_m, _, _ = _dispatch(dest, meta, ep, cap1, fill=-1)
        if ep > 1:
            recv_x = jax.lax.all_to_all(send_x, ep_axis, 0, 0, tiled=False)
            recv_m = jax.lax.all_to_all(send_m, ep_axis, 0, 0, tiled=False)
        else:
            recv_x, recv_m = send_x[None], send_m[None]
        recv_x = recv_x.reshape(ep * cap1, D)
        recv_m = recv_m.reshape(ep * cap1)

        # ---- second dispatch: onto local experts ---------------------------
        cap2 = max(8, int(math.ceil(ep * cap1 / e_local * capacity_factor)))
        xe, slot2, valid2 = _dispatch(recv_m, recv_x, e_local, cap2)  # [E_l, C2, D]

        # ---- grouped expert MLP -------------------------------------------
        a = act_fn(activation)
        gate = jnp.einsum("ecd,edf->ecf", xe, w_gate)
        up = jnp.einsum("ecd,edf->ecf", xe, w_up)
        ye = jnp.einsum("ecf,efd->ecd", a(gate) * up, w_down)  # [E_l, C2, D]

        # ---- inverse path ---------------------------------------------------
        e_ids = jnp.where(recv_m >= 0, recv_m, 0)
        row2 = jnp.where(valid2, slot2, cap2 - 1)
        back = ye[e_ids, row2] * valid2[:, None].astype(ye.dtype)  # [ep*cap1, D]
        back = back.reshape(ep, cap1, D)
        if ep > 1:
            ret = jax.lax.all_to_all(back, ep_axis, 0, 0, tiled=False)
        else:
            ret = back[0][None]
        ret = ret.reshape(ep, cap1, D)
        d1 = jnp.where(valid1, dest, 0)
        r1 = jnp.where(valid1, slot1, 0)
        pair_out = ret[d1, jnp.minimum(r1, cap1 - 1)] * valid1[:, None].astype(ret.dtype)
        pair_out = pair_out * my_w[:, None].astype(pair_out.dtype)

        # combine pairs back onto local tokens, then sum over model shards
        y = jnp.zeros((T, D), pair_out.dtype).at[my_token].add(
            jnp.where(valid1[:, None], pair_out, 0)
        )
        if tp > 1:
            y = jax.lax.psum(y, tp_axis)
            aux = jax.lax.pmean(aux, tp_axis)
        for ax in batch_spec:
            aux = jax.lax.pmean(aux, ax)
        if ep > 1 and ep_axis not in batch_spec:
            aux = jax.lax.pmean(aux, ep_axis)
        return y.reshape(B_l, S, D).astype(x_l.dtype), aux

    y, aux = jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(x_spec, r_spec, w_spec, w_spec, w_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )(x, params["router"], params["w_gate"], params["w_up"], params["w_down"])
    return y, aux
