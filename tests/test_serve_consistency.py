"""Decode-path correctness: prefill + token-by-token decode must reproduce
the logits of a single full forward pass (per architecture family)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import all_configs, smoke_config
from repro.distributed import default_rules
from repro.launch.mesh import make_mesh
from repro.models import ModelContext, build_model
from repro.serve import prefill_to_decode_caches


@pytest.fixture(scope="module")
def ctx():
    mesh = make_mesh((1, 1), ("data", "model"))
    return ModelContext(mesh, default_rules(mesh))


def _full_logits(model, cfg, params, tokens, ctx, batch_extra):
    """Teacher-forced logits for every position via the train-mode forward."""
    if cfg.family == "audio":
        from repro.models import encdec

        enc = encdec.encode(cfg, params, batch_extra["frames"])
        logits, _ = encdec.decode_stack(cfg, params, tokens, enc, mode="train")
        return logits
    if cfg.family == "ssm":
        # xlstm: reuse loss-path forward
        from repro.models.model import _xlstm_model  # noqa: SLF001

        # run() is closed over; emulate via prefill of successive prefixes
        raise pytest.skip("covered by test_xlstm_forms")
    from repro.models import transformer

    prefix = batch_extra.get("patches")
    logits, _, _ = transformer.forward(cfg, ctx, params, tokens, mode="train", prefix_embeds=prefix)
    if prefix is not None:
        logits = logits[:, prefix.shape[1] :]
    return logits


@pytest.mark.parametrize(
    "arch", ["granite-3-2b", "gemma-2b", "qwen2.5-32b", "deepseek-v2-236b", "whisper-tiny", "internvl2-76b"]
)
def test_decode_matches_forward(arch, ctx):
    cfg = smoke_config(all_configs()[arch])
    model = build_model(cfg)
    key = jax.random.PRNGKey(3)
    params = model.init(key)
    B, S_pre, S_total = 2, 24, 30
    tokens = jax.random.randint(key, (B, S_total), 0, cfg.vocab_size)
    extra = {}
    if cfg.family == "audio":
        extra["frames"] = jax.random.normal(key, (B, cfg.encoder_frames, cfg.d_model), jnp.bfloat16)
    if cfg.family == "vlm":
        extra["patches"] = jax.random.normal(key, (B, cfg.vision_tokens, cfg.d_model), jnp.bfloat16)

    full = _full_logits(model, cfg, params, tokens, ctx, extra)

    pre_batch = dict(extra)
    pre_batch["tokens"] = tokens[:, :S_pre]
    logits_pre, pc = model.prefill(params, pre_batch, ctx)
    # prefill's last-position logits == full forward at position S_pre-1
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, 0], np.float32),
        np.asarray(full[:, S_pre - 1], np.float32),
        rtol=2e-2, atol=2e-2,
    )

    prefix_len = cfg.vision_tokens if cfg.family == "vlm" else 0
    max_len = S_total + prefix_len + 4
    caches = prefill_to_decode_caches(cfg, model, pc, B, max_len, S_pre + prefix_len)
    # MLA's absorbed decode reassociates bf16 matmuls (q.W_uk).c_kv, which
    # carries larger-but-bounded rounding noise; the fp32 equivalence is
    # pinned exactly by test_mla_absorbed_exact_fp32 below.
    tol = 1.5e-1 if cfg.use_mla else 2e-2
    for t in range(S_pre, S_total):
        logits_d, caches = model.decode_step(
            params, tokens[:, t : t + 1], caches, jnp.int32(t + prefix_len), ctx
        )
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(full[:, t], np.float32),
            rtol=tol, atol=tol,
            err_msg=f"{arch} decode step {t}",
        )


def test_mla_absorbed_exact_fp32():
    """Absorbed-form MLA decode == expanded-form attention exactly (fp32)."""
    import dataclasses

    from repro.models.layers import init_tree
    from repro.models.transformer import _attn_defs, _mla_attention

    cfg = smoke_config(all_configs()["deepseek-v2-236b"])
    cfg = dataclasses.replace(cfg, dtype=jnp.float32)
    params = jax.tree.map(
        lambda p: p.astype(jnp.float32), init_tree(_attn_defs(cfg), jax.random.PRNGKey(0))
    )
    B, S = 2, 17
    x = jax.random.normal(jax.random.PRNGKey(1), (B, S, cfg.d_model), jnp.float32) * 0.3
    pos = jnp.broadcast_to(jnp.arange(S)[None], (B, S))
    y_full, cache = _mla_attention(cfg, params, x, pos, mode="prefill")
    cache_prefix = {
        k: jnp.pad(v[:, : S - 1], ((0, 0), (0, 4), (0, 0)))
        for k, v in cache.items()
    }
    y_dec, _ = _mla_attention(
        cfg, params, x[:, S - 1 : S], pos[:, S - 1 : S],
        mode="decode", cache=cache_prefix, cache_pos=jnp.int32(S - 1),
    )
    np.testing.assert_allclose(
        np.asarray(y_dec[:, 0]), np.asarray(y_full[:, S - 1]), rtol=1e-4, atol=1e-4
    )


def test_hymba_ring_cache_decode(ctx):
    """Sliding-window ring cache must match the full forward within window."""
    cfg = smoke_config(all_configs()["hymba-1.5b"])  # window = 64
    model = build_model(cfg)
    key = jax.random.PRNGKey(4)
    params = model.init(key)
    B, S_pre, S_total = 1, 80, 96  # prefill longer than the 64-token window
    tokens = jax.random.randint(key, (B, S_total), 0, cfg.vocab_size)

    from repro.models import transformer

    full, _, _ = transformer.forward(cfg, ctx, params, tokens, mode="train")

    pre_batch = {"tokens": tokens[:, :S_pre]}
    logits_pre, pc = model.prefill(params, pre_batch, ctx)
    np.testing.assert_allclose(
        np.asarray(logits_pre[:, 0], np.float32),
        np.asarray(full[:, S_pre - 1], np.float32),
        rtol=3e-2, atol=3e-2,
    )
    caches = prefill_to_decode_caches(cfg, model, pc, B, S_total + 4, S_pre)
    for t in range(S_pre, S_total):
        logits_d, caches = model.decode_step(params, tokens[:, t : t + 1], caches, jnp.int32(t), ctx)
        np.testing.assert_allclose(
            np.asarray(logits_d[:, 0], np.float32),
            np.asarray(full[:, t], np.float32),
            rtol=3e-2, atol=3e-2,
            err_msg=f"hymba ring decode step {t}",
        )


def test_xlstm_forms_consistent(ctx):
    """mLSTM parallel == chunkwise == recurrent; sLSTM prefill->decode."""
    from repro.models import xlstm
    from repro.models.layers import init_tree

    key = jax.random.PRNGKey(5)
    B, S, D, H = 2, 64, 64, 4
    defs = xlstm.mlstm_defs(0, D, H)
    params = init_tree(defs, key)
    x = jax.random.normal(key, (B, S, D), jnp.float32).astype(jnp.bfloat16) * 0.3

    out_par, _ = xlstm.mlstm_block(params, x, H)  # S<=256 -> parallel
    out_chunk, st = xlstm.mlstm_block(params, x, H, return_state=True)
    np.testing.assert_allclose(
        np.asarray(out_par, np.float32), np.asarray(out_chunk, np.float32), rtol=3e-2, atol=3e-2
    )
    # recurrent continuation from chunkwise state == parallel on S+1
    x1 = jax.random.normal(jax.random.PRNGKey(6), (B, 1, D), jnp.float32).astype(jnp.bfloat16) * 0.3
    out_rec, _ = xlstm.mlstm_block(params, x1, H, state=st)
    full2, _ = xlstm.mlstm_block(params, jnp.concatenate([x, x1], 1), H)
    np.testing.assert_allclose(
        np.asarray(out_rec[:, 0], np.float32), np.asarray(full2[:, -1], np.float32),
        rtol=4e-2, atol=4e-2,
    )

    # model-level: prefill then decode matches full forward next-token logits
    cfg = smoke_config(all_configs()["xlstm-350m"])
    model = build_model(cfg)
    params_m = model.init(key)
    tokens = jax.random.randint(key, (2, 40), 0, cfg.vocab_size)
    logits_pre, caches = model.prefill(params_m, {"tokens": tokens[:, :32]}, ctx)
    logits_d, _ = model.decode_step(params_m, tokens[:, 32:33], caches, jnp.int32(32), ctx)
    # teacher-forced reference: prefill of the longer prefix
    logits_ref, _ = model.prefill(params_m, {"tokens": tokens[:, :34]}, ctx)
    logits_ref33, _ = model.prefill(params_m, {"tokens": tokens[:, :33]}, ctx)
    np.testing.assert_allclose(
        np.asarray(logits_d[:, 0], np.float32), np.asarray(logits_ref33[:, -1], np.float32),
        rtol=4e-2, atol=4e-2,
    )
