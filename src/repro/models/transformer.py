"""Decoder-only transformer assembly for dense / MoE / MLA / hybrid configs.

Layers are *stacked* ([L, ...] leading dim) and traversed with
``jax.lax.scan`` + configurable remat — the HLO stays one-block-sized, which
keeps 236B-parameter dry-run compiles tractable and is also what a real
deployment wants (faster compiles, better fusion reuse).

Three execution modes:
  * train   — no caches; chunked causal attention bounds memory.
  * prefill — emits per-layer cache tensors ([L, B, S, ...] via scan ys).
  * decode  — one token against caches (linear or ring for sliding window;
              MLA decodes in the absorbed compressed-cache form).
"""

from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from ..configs.base import ModelConfig
from ..distributed.sharding import ShardingRules, constrain
from .layers import (
    ParamDef,
    apply_rope,
    causal_attention,
    gated_mlp,
    gated_mlp_defs,
    gqa_defs,
    gqa_attention_block,
    init_kv_cache,
    rms_norm,
)
from .moe import moe_defs, moe_layer
from .ssm import init_ssm_state, selective_ssm, ssm_defs


@dataclasses.dataclass(frozen=True)
class ModelContext:
    mesh: Any
    rules: ShardingRules


# ---------------------------------------------------------------------------
# parameter definitions
# ---------------------------------------------------------------------------

def _stack(defs: Dict[str, Any], n: int) -> Dict[str, Any]:
    def add_dim(d: ParamDef) -> ParamDef:
        return ParamDef((n,) + d.shape, (None,) + d.logical, d.init, d.scale, d.dtype)

    return jax.tree.map(add_dim, defs, is_leaf=lambda x: isinstance(x, ParamDef))


def _attn_defs(cfg: ModelConfig) -> Dict[str, Any]:
    if cfg.use_mla:
        qk_dim = cfg.qk_nope_head_dim + cfg.qk_rope_head_dim
        return {
            "w_dq": ParamDef((cfg.d_model, cfg.q_lora_rank), ("embed", "qk_lora")),
            "q_norm": ParamDef((cfg.q_lora_rank,), ("qk_lora",), init="zeros"),
            "w_uq": ParamDef((cfg.q_lora_rank, cfg.n_heads, qk_dim), ("qk_lora", "heads", None)),
            "w_dkv": ParamDef(
                (cfg.d_model, cfg.kv_lora_rank + cfg.qk_rope_head_dim), ("embed", "qk_lora")
            ),
            "kv_norm": ParamDef((cfg.kv_lora_rank,), ("qk_lora",), init="zeros"),
            "w_uk": ParamDef(
                (cfg.kv_lora_rank, cfg.n_heads, cfg.qk_nope_head_dim), ("qk_lora", "heads", None)
            ),
            "w_uv": ParamDef(
                (cfg.kv_lora_rank, cfg.n_heads, cfg.v_head_dim), ("qk_lora", "heads", None)
            ),
            "wo": ParamDef((cfg.n_heads, cfg.v_head_dim, cfg.d_model), ("heads", None, "embed")),
        }
    return gqa_defs(
        cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim, qkv_bias=cfg.qkv_bias
    )


def decoder_defs(cfg: ModelConfig) -> Dict[str, Any]:
    D, V = cfg.d_model, cfg.vocab_size
    defs: Dict[str, Any] = {
        "embed": ParamDef((V, D), ("vocab", "embed"), scale=D ** -0.5),
        "final_norm": ParamDef((D,), ("embed",), init="zeros"),
    }
    if not cfg.tie_embeddings:
        defs["unembed"] = ParamDef((D, V), ("embed", "vocab"))

    def block_defs(moe: bool) -> Dict[str, Any]:
        blk: Dict[str, Any] = {
            "norm1": ParamDef((D,), ("embed",), init="zeros"),
            "norm2": ParamDef((D,), ("embed",), init="zeros"),
            "attn": _attn_defs(cfg),
        }
        if moe:
            # moe_defs stacks itself; handled below.
            pass
        else:
            blk["mlp"] = gated_mlp_defs(D, cfg.d_ff)
        if cfg.family == "hybrid":
            blk["ssm"] = ssm_defs(0, D, cfg.ssm_expand * D, cfg.ssm_state)
            blk["attn_scale"] = ParamDef((D,), ("embed",), init="zeros")
            blk["ssm_scale"] = ParamDef((D,), ("embed",), init="zeros")
        return blk

    def stacked_block(n: int, moe: bool) -> Dict[str, Any]:
        blk = _stack(block_defs(moe), n)
        if moe:
            blk["moe"] = moe_defs(n, D, cfg.n_experts, cfg.d_ff_expert, cfg.n_shared_experts)
        return blk

    if cfg.n_experts and cfg.first_dense_layers:
        defs["dense_layers"] = stacked_block(cfg.first_dense_layers, moe=False)
        defs["moe_layers"] = stacked_block(cfg.n_layers - cfg.first_dense_layers, moe=True)
    elif cfg.n_experts:
        defs["moe_layers"] = stacked_block(cfg.n_layers, moe=True)
    else:
        defs["layers"] = stacked_block(cfg.n_layers, moe=False)
    return defs


# ---------------------------------------------------------------------------
# MLA attention (DeepSeek-V2)
# ---------------------------------------------------------------------------

def _mla_attention(
    cfg: ModelConfig,
    p: Dict[str, jax.Array],
    x: jax.Array,
    positions: jax.Array,
    *,
    mode: str,
    cache: Optional[Dict[str, jax.Array]] = None,
    cache_pos: Optional[jax.Array] = None,
    q_chunk: Optional[int] = None,
) -> Tuple[jax.Array, Optional[Dict[str, jax.Array]]]:
    """Multi-head Latent Attention. Decode runs the *absorbed* form against
    the compressed cache [B, S, kv_lora] + [B, S, rope_d] — the MLA win."""
    B, S, _ = x.shape
    nope, rope_d = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    scale = float((nope + rope_d) ** -0.5)

    cq = rms_norm(jnp.einsum("bsd,dr->bsr", x, p["w_dq"]), p["q_norm"])
    q = jnp.einsum("bsr,rhk->bshk", cq, p["w_uq"])
    q_nope, q_rope = q[..., :nope], q[..., nope:]
    q_rope = apply_rope(q_rope, positions, cfg.rope_theta)

    ckv_full = jnp.einsum("bsd,dr->bsr", x, p["w_dkv"])
    c_kv = rms_norm(ckv_full[..., : cfg.kv_lora_rank], p["kv_norm"])
    k_rope = apply_rope(ckv_full[:, :, None, cfg.kv_lora_rank :], positions, cfg.rope_theta)[:, :, 0]

    if mode != "decode":
        k_nope = jnp.einsum("bsr,rhk->bshk", c_kv, p["w_uk"])
        v = jnp.einsum("bsr,rhv->bshv", c_kv, p["w_uv"])
        k = jnp.concatenate(
            [k_nope, jnp.broadcast_to(k_rope[:, :, None], (B, S, cfg.n_heads, rope_d))], axis=-1
        )
        qq = jnp.concatenate([q_nope, q_rope], axis=-1)
        out = causal_attention(qq, k, v, q_chunk=q_chunk, softmax_scale=scale)
        y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
        cache_out = {"c_kv": c_kv, "k_rope": k_rope} if mode == "prefill" else None
        return y, cache_out

    assert S == 1 and cache is not None and cache_pos is not None
    ckv_cache = jax.lax.dynamic_update_slice_in_dim(cache["c_kv"], c_kv, cache_pos, axis=1)
    kr_cache = jax.lax.dynamic_update_slice_in_dim(cache["k_rope"], k_rope, cache_pos, axis=1)
    q_c = jnp.einsum("bshk,rhk->bshr", q_nope, p["w_uk"])  # absorb W_uk
    scores = (
        jnp.einsum("bshr,btr->bhst", q_c, ckv_cache, preferred_element_type=jnp.float32)
        + jnp.einsum("bshk,btk->bhst", q_rope, kr_cache, preferred_element_type=jnp.float32)
    ) * scale
    t_pos = jnp.arange(ckv_cache.shape[1])
    scores = jnp.where((t_pos <= cache_pos)[None, None, None, :], scores, -1e30)
    probs = jax.nn.softmax(scores, axis=-1)
    ctx_c = jnp.einsum("bhst,btr->bshr", probs.astype(ckv_cache.dtype), ckv_cache)
    out = jnp.einsum("bshr,rhv->bshv", ctx_c, p["w_uv"])
    y = jnp.einsum("bshv,hvd->bsd", out, p["wo"])
    return y, {"c_kv": ckv_cache, "k_rope": kr_cache}


# ---------------------------------------------------------------------------
# blocks & stacks
# ---------------------------------------------------------------------------

def _block(
    cfg: ModelConfig,
    ctx: ModelContext,
    p: Dict[str, Any],
    x: jax.Array,
    positions: jax.Array,
    *,
    moe: bool,
    mode: str,
    cache: Optional[Dict[str, Any]] = None,
    cache_pos: Optional[jax.Array] = None,
    q_chunk: Optional[int] = None,
) -> Tuple[jax.Array, Optional[Dict[str, Any]], jax.Array]:
    rules = ctx.rules
    aux = jnp.zeros((), jnp.float32)
    h = rms_norm(x, p["norm1"])

    attn_cache = cache.get("attn") if cache else None
    if cfg.use_mla:
        attn_out, attn_cache_out = _mla_attention(
            cfg, p["attn"], h, positions, mode=mode,
            cache=attn_cache, cache_pos=cache_pos, q_chunk=q_chunk,
        )
    else:
        attn_out, attn_cache_out = gqa_attention_block(
            p["attn"], h, positions,
            rope_theta=cfg.rope_theta, mode=mode,
            cache=attn_cache, cache_pos=cache_pos,
            sliding_window=cfg.sliding_window or None, q_chunk=q_chunk,
        )
    cache_out: Dict[str, Any] = {}
    if attn_cache_out is not None:
        cache_out["attn"] = attn_cache_out

    if cfg.family == "hybrid":
        if mode == "train":
            ssm_state = None
        elif mode == "prefill":
            ssm_state = init_ssm_state(x.shape[0], cfg.ssm_expand * cfg.d_model, cfg.ssm_state)
        else:
            ssm_state = cache.get("ssm") if cache else None
        ssm_out, ssm_state_out = selective_ssm(p["ssm"], h, state=ssm_state, unroll=cfg.scan_unroll)
        if ssm_state_out is not None:
            cache_out["ssm"] = ssm_state_out
        fused = 0.5 * (rms_norm(attn_out, p["attn_scale"]) + rms_norm(ssm_out, p["ssm_scale"]))
        x = x + fused
    else:
        x = x + attn_out
    x = constrain(x, rules, "batch", None, None)

    h2 = rms_norm(x, p["norm2"])
    if moe:
        routed, aux = moe_layer(
            p["moe"], h2,
            mesh=ctx.mesh, top_k=cfg.top_k,
            capacity_factor=cfg.capacity_factor,
            activation=cfg.activation, dp_axes=("pod", "data"),
        )
        from jax.ad_checkpoint import checkpoint_name

        routed = checkpoint_name(routed, "moe_routed_out")
        mlp_out = routed
        if "shared" in p["moe"]:
            mlp_out = mlp_out + gated_mlp(p["moe"]["shared"], h2, cfg.activation)
    else:
        mlp_out = gated_mlp(p["mlp"], h2, cfg.activation)
    x = x + mlp_out
    x = constrain(x, rules, "batch", None, None)
    return x, (cache_out or None), aux


def _remat(fn, policy: str):
    if policy == "none":
        return fn
    if policy == "full":
        return jax.checkpoint(fn, policy=jax.checkpoint_policies.nothing_saveable)
    if policy == "dots_plus_collectives":
        # 'dots' recomputes the whole block in backward — including the MoE
        # all-to-all dispatch, doubling wire per step. Saving the named
        # routed-expert output keeps the recompute but not the collectives
        # (§Perf iteration 5).
        return jax.checkpoint(
            fn,
            policy=jax.checkpoint_policies.save_from_both_policies(
                jax.checkpoint_policies.dots_with_no_batch_dims_saveable,
                jax.checkpoint_policies.save_only_these_names("moe_routed_out"),
            ),
        )
    return jax.checkpoint(fn, policy=jax.checkpoint_policies.dots_with_no_batch_dims_saveable)


def _scan_stack(
    cfg: ModelConfig,
    ctx: ModelContext,
    stack_params: Dict[str, Any],
    x: jax.Array,
    positions: jax.Array,
    *,
    moe: bool,
    mode: str,
    caches: Optional[Dict[str, Any]] = None,
    cache_pos: Optional[jax.Array] = None,
    q_chunk: Optional[int] = None,
):
    def body(carry, layer_in):
        x_in, aux_in = carry
        p, cache = layer_in
        x_out, cache_out, aux = _block(
            cfg, ctx, p, x_in, positions,
            moe=moe, mode=mode, cache=cache, cache_pos=cache_pos, q_chunk=q_chunk,
        )
        return (x_out, aux_in + aux), cache_out

    body = _remat(body, cfg.remat_policy if mode == "train" else "none")
    (x, aux), caches_out = jax.lax.scan(
        body, (x, jnp.zeros((), jnp.float32)), (stack_params, caches),
        unroll=True if cfg.scan_unroll else 1,
    )
    return x, aux, caches_out


# ---------------------------------------------------------------------------
# public API
# ---------------------------------------------------------------------------

def embed_tokens(cfg: ModelConfig, ctx: ModelContext, params: Dict[str, Any], tokens: jax.Array) -> jax.Array:
    x = sharded_embed_lookup(ctx, params["embed"], tokens)
    if cfg.scale_embeddings:
        x = x * jnp.sqrt(jnp.float32(cfg.d_model)).astype(x.dtype)
    return x


def sharded_embed_lookup(ctx: ModelContext, table: jax.Array, tokens: jax.Array) -> jax.Array:
    """Vocab-sharded embedding lookup without gathering the table.

    ``jnp.take`` from a row-sharded table makes GSPMD all-gather the whole
    [V, D] table every step (§Perf iteration 2). The TP-native form looks up
    locally with masked ids and psums the [B, S, D] partials — wire cost
    B*S*D instead of V*D (plus it reverses in backward to a local
    scatter-add). Falls back to plain take when the mesh/vocab don't permit.
    """
    mesh = ctx.mesh
    try:
        tp = mesh.shape.get("model", 1)
    except AttributeError:
        tp = 1
    V = table.shape[0]
    B = tokens.shape[0]
    if tp <= 1 or V % tp != 0:
        return jnp.take(table, tokens, axis=0)

    from jax.sharding import PartitionSpec as P

    from ..distributed.sharding import batch_partition

    tok_spec = batch_partition(mesh, B)
    tok_parts = list(tok_spec) + [None] * (tokens.ndim - len(tok_spec))
    out_parts = tok_parts + [None]

    def inner(tab_l, tok_l):
        mi = jax.lax.axis_index("model")
        v_l = tab_l.shape[0]
        rel = tok_l - mi * v_l
        ok = (rel >= 0) & (rel < v_l)
        x = jnp.take(tab_l, jnp.clip(rel, 0, v_l - 1), axis=0)
        x = jnp.where(ok[..., None], x, jnp.zeros((), x.dtype))
        return jax.lax.psum(x, "model")

    return jax.shard_map(
        inner,
        mesh=mesh,
        in_specs=(P("model", None), P(*tok_parts)),
        out_specs=P(*out_parts),
        check_vma=False,
    )(table, tokens)


def unembed(cfg: ModelConfig, ctx: ModelContext, params: Dict[str, Any], x: jax.Array) -> jax.Array:
    if cfg.tie_embeddings:
        logits = jnp.einsum("bsd,vd->bsv", x, params["embed"])
    else:
        logits = jnp.einsum("bsd,dv->bsv", x, params["unembed"])
    if cfg.logit_softcap:
        logits = jnp.tanh(logits / cfg.logit_softcap) * cfg.logit_softcap
    return constrain(logits, ctx.rules, "batch", None, "vocab")


def forward(
    cfg: ModelConfig,
    ctx: ModelContext,
    params: Dict[str, Any],
    tokens: jax.Array,  # [B, S]
    *,
    mode: str = "train",
    prefix_embeds: Optional[jax.Array] = None,
    caches: Optional[Dict[str, Any]] = None,
    cache_pos: Optional[jax.Array] = None,
) -> Tuple[jax.Array, jax.Array, Optional[Dict[str, Any]]]:
    """Returns (logits, aux_loss, caches_out)."""
    x = embed_tokens(cfg, ctx, params, tokens)
    if prefix_embeds is not None:
        x = jnp.concatenate([prefix_embeds.astype(x.dtype), x], axis=1)
    B, S, _ = x.shape
    if mode == "decode":
        positions = (jnp.zeros((B, 1), jnp.int32) + cache_pos)
    else:
        positions = jnp.broadcast_to(jnp.arange(S)[None, :], (B, S))
    x = constrain(x, ctx.rules, "batch", None, None)
    q_chunk = cfg.attn_q_chunk if (mode != "decode" and S > cfg.attn_q_chunk) else None

    aux_total = jnp.zeros((), jnp.float32)
    caches_out: Dict[str, Any] = {}
    for stack_name, moe in (("layers", False), ("dense_layers", False), ("moe_layers", True)):
        if stack_name not in params:
            continue
        x, aux, nc = _scan_stack(
            cfg, ctx, params[stack_name], x, positions,
            moe=moe, mode=mode,
            caches=caches.get(stack_name) if caches else None,
            cache_pos=cache_pos, q_chunk=q_chunk,
        )
        aux_total += aux
        if nc is not None:
            caches_out[stack_name] = nc

    x = rms_norm(x, params["final_norm"])
    logits = unembed(cfg, ctx, params, x)
    return logits, aux_total, (caches_out or None)


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def init_caches(cfg: ModelConfig, batch: int, max_len: int) -> Dict[str, Any]:
    """Stacked decode caches. Sliding-window archs get ring buffers."""

    def one_layer_cache() -> Dict[str, Any]:
        c: Dict[str, Any] = {}
        if cfg.use_mla:
            c["attn"] = {
                "c_kv": jnp.zeros((batch, max_len, cfg.kv_lora_rank), cfg.dtype),
                "k_rope": jnp.zeros((batch, max_len, cfg.qk_rope_head_dim), cfg.dtype),
            }
        else:
            ring = bool(cfg.sliding_window) and cfg.sliding_window < max_len
            kv_len = cfg.sliding_window if ring else max_len
            c["attn"] = init_kv_cache(
                batch, kv_len, cfg.n_kv_heads, cfg.resolved_head_dim, cfg.dtype, ring=ring
            )
        if cfg.family == "hybrid":
            c["ssm"] = init_ssm_state(batch, cfg.ssm_expand * cfg.d_model, cfg.ssm_state)
        return c

    def stacked(n: int):
        return jax.tree.map(
            lambda leaf: jnp.broadcast_to(leaf[None], (n,) + leaf.shape).copy(),
            one_layer_cache(),
        )

    caches: Dict[str, Any] = {}
    if cfg.n_experts and cfg.first_dense_layers:
        caches["dense_layers"] = stacked(cfg.first_dense_layers)
        caches["moe_layers"] = stacked(cfg.n_layers - cfg.first_dense_layers)
    elif cfg.n_experts:
        caches["moe_layers"] = stacked(cfg.n_layers)
    else:
        caches["layers"] = stacked(cfg.n_layers)
    return caches
