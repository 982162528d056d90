"""Decoder-only LM covering the dense / moe / ssm / hybrid / vlm families.

One scan-over-layers implementation; the per-layer block is selected by
``cfg.family``.  All heavy activations carry logical sharding constraints via
the ``ParallelCtx`` so the same code runs on 1 CPU device and on the
(pod, data, model) production mesh.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.dist.sharding import ParallelCtx
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.common import layer_scan as _scan
from repro.models.common import (
    ParamDef, gated_mlp, rms_norm, stack_defs,
)

def _remat_policy(ctx):
    if getattr(ctx, "remat_policy", "nothing") == "dots":
        return jax.checkpoint_policies.checkpoint_dots
    return jax.checkpoint_policies.nothing_saveable


VLM_PATCH_DIM = 1024  # CLIP-style frontend stub output dim (llava projector in)


# ---------------------------------------------------------------------------
# Parameter structure
# ---------------------------------------------------------------------------


def _mlp_defs(d: int, ff: int) -> dict:
    return {
        "w_gate": ParamDef((d, ff), ("fsdp", "tp")),
        "w_up": ParamDef((d, ff), ("fsdp", "tp")),
        "w_down": ParamDef((ff, d), ("tp", "fsdp")),
    }


def _block_defs(cfg: ArchConfig, moe_mode: str = "gather") -> dict:
    d = cfg.d_model
    defs: dict[str, Any] = {"ln1": ParamDef((d,), (None,), init="ones")}
    if cfg.family in ("dense", "moe", "hybrid", "vlm"):
        defs["attn"] = attn.attn_param_defs(
            d, cfg.num_heads, cfg.num_kv_heads, cfg.resolved_head_dim,
            cfg.qk_norm)
        defs["ln2"] = ParamDef((d,), (None,), init="ones")
    if cfg.family == "moe":
        defs["moe"] = moe_mod.moe_param_defs(d, cfg.moe, moe_mode)
    elif cfg.family in ("dense", "vlm", "hybrid"):
        defs["mlp"] = _mlp_defs(d, cfg.d_ff)
    if cfg.family in ("ssm", "hybrid"):
        di = cfg.ssm.d_inner or cfg.ssm.expand * d
        defs["ssm"] = ssm_mod.ssm_param_defs(d, cfg.ssm, di)
    return defs


def param_defs(cfg: ArchConfig, moe_mode: str = "gather") -> dict:
    d, v = cfg.d_model, cfg.vocab_size
    defs: dict[str, Any] = {
        "embed": ParamDef((v, d), ("tp", "fsdp"), init="embed", scale=0.02),
        "out_norm": ParamDef((d,), (None,), init="ones"),
        "layers": stack_defs(_block_defs(cfg, moe_mode), cfg.num_layers),
    }
    if not cfg.tie_embeddings:
        defs["lm_head"] = ParamDef((d, v), ("fsdp", "tp"))
    if cfg.family == "vlm":
        defs["mm_proj"] = ParamDef((VLM_PATCH_DIM, d), (None, "fsdp"))
    return defs


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------


def _d_inner(cfg: ArchConfig) -> int:
    return cfg.ssm.d_inner or cfg.ssm.expand * cfg.d_model


def _block(cfg: ArchConfig, ctx: ParallelCtx, p: dict, x: jax.Array,
           positions: jax.Array, is_global: jax.Array) -> tuple[jax.Array, jax.Array]:
    """One layer. Returns (x, aux_loss)."""
    aux = jnp.float32(0.0)
    # Named scopes put "attention", "mlp" and "ssd_mixer" on the op names
    # of the compiled step, for reading device time per layer in a trace.
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    if cfg.family == "ssm":
        with jax.named_scope("ssd_mixer"):
            x = x + ssm_mod.ssm_forward(p["ssm"], h, cfg.ssm, _d_inner(cfg),
                                        cfg.norm_eps)
        return x, aux
    with jax.named_scope("attention"):
        q, k, v = attn.project_qkv(p["attn"], h, positions, cfg.rope_theta,
                                   cfg.qk_norm, cfg.norm_eps)
        q = ctx.cs(q, "batch", None, "tp", None)
        k = ctx.cs(k, "batch", None, "tp", None)
        a = attn.attend(q, k, v, causal=True, window=cfg.attn_window,
                        is_global=is_global)
        a = jnp.einsum("bshk,hkd->bsd", a, p["attn"]["wo"].astype(x.dtype))
    if cfg.family == "hybrid":
        with jax.named_scope("ssd_mixer"):
            s = ssm_mod.ssm_forward(p["ssm"], h, cfg.ssm, _d_inner(cfg),
                                    cfg.norm_eps)
        x = x + 0.5 * (a + s)  # hymba: mean-fused parallel heads
    else:
        x = x + a
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, aux = moe_mod.moe_ffn(p["moe"], h2, cfg.moe, ctx)
        x = x + y
    else:
        with jax.named_scope("mlp"):
            x = x + gated_mlp(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                              p["mlp"]["w_down"])
    x = ctx.cs(x, "batch", None, None)
    return x, aux


def global_layer_flags(cfg: ArchConfig) -> jax.Array:
    """Per-layer bool: True = full/global attention, False = sliding window.

    Dense archs: all True. Hymba: 3 global layers (first/middle/last) unless
    running the long-context serve config where all layers are SWA (the
    config sets attn_window and we mark globals only when window is set).
    """
    L = cfg.num_layers
    if cfg.attn_window is None:
        return jnp.ones((L,), bool)
    flags = [i in (0, L // 2, L - 1) for i in range(L)]
    return jnp.asarray(flags)


def _scan_layers(cfg: ArchConfig, ctx: ParallelCtx, params: dict,
                 x: jax.Array, positions: jax.Array,
                 flags: jax.Array | None = None) -> tuple[jax.Array, jax.Array]:
    if flags is None:
        flags = global_layer_flags(cfg)

    def body(carry, xs):
        x, aux = carry
        layer_p, flag = xs
        x, a = _block(cfg, ctx, layer_p, x, positions, flag)
        return (x, aux + a), None

    fn = body
    if ctx.remat:
        fn = jax.checkpoint(body, policy=_remat_policy(ctx))
    (x, aux), _ = _scan(fn, (x, jnp.float32(0.0)),
                               (params["layers"], flags))
    return x, aux


# ---------------------------------------------------------------------------
# Forward passes
# ---------------------------------------------------------------------------


def embed_inputs(cfg: ArchConfig, ctx: ParallelCtx, params: dict,
                 batch: dict) -> tuple[jax.Array, jax.Array]:
    """Returns (x (B,S,d), loss_mask (B,S))."""
    tokens = batch["tokens"]
    x = params["embed"].astype(_cdtype(params))[tokens]
    mask = batch.get("mask", jnp.ones(tokens.shape, bool))
    if cfg.family == "vlm" and "patch_embeds" in batch:
        pe = jnp.einsum("bpd,de->bpe", batch["patch_embeds"].astype(x.dtype),
                        params["mm_proj"].astype(x.dtype))
        x = jnp.concatenate([pe, x], axis=1)
        mask = jnp.concatenate(
            [jnp.zeros(pe.shape[:2], bool), mask], axis=1)
    return ctx.cs(x, "batch", None, None), mask


def _cdtype(params) -> jnp.dtype:
    dt = params["embed"].dtype
    # fp8 is a STORAGE dtype (quantized serving); compute stays bf16.
    if dt.itemsize == 1:
        return jnp.bfloat16
    return dt


def logits_fn(cfg: ArchConfig, params: dict, x: jax.Array) -> jax.Array:
    head = params["embed"].T if cfg.tie_embeddings else params["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head.astype(x.dtype))


def forward(cfg: ArchConfig, ctx: ParallelCtx, params: dict,
            batch: dict) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Full training forward. Returns (logits, loss_mask, moe_aux)."""
    x, mask = embed_inputs(cfg, ctx, params, batch)
    positions = jnp.arange(x.shape[1])[None, :]
    x, aux = _scan_layers(cfg, ctx, params, x, positions)
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    logits = logits_fn(cfg, params, x)
    return ctx.cs(logits, "batch", None, "tp"), mask, aux


def token_metrics(logits: jax.Array, labels: jax.Array):
    """Per-token (ce, correct, pmax) — the pure-jnp oracle the
    ``loss_confidence`` Pallas kernel reproduces."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    mx = jnp.max(lf, axis=-1)
    am = jnp.argmax(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    ce = lse - gold
    pmax = jnp.exp(mx - lse)
    return ce, am == labels, pmax


def per_sample_metrics(cfg: ArchConfig, logits: jax.Array, labels: jax.Array,
                       mask: jax.Array, pa_threshold: float = 0.5):
    """Sequence-level (loss, PA, PC) — KAKURENBO's importance signals.

    For LMs a "sample" is a sequence: loss = mean token CE, PC = mean max
    softmax prob, PA = token accuracy >= pa_threshold (DESIGN.md Sec. 3).
    ``labels``/``mask`` cover only the text positions (VLM prefixes masked).
    """
    ce, correct, pmax = token_metrics(logits, labels)
    m = mask.astype(jnp.float32)
    denom = jnp.maximum(jnp.sum(m, axis=-1), 1.0)
    loss = jnp.sum(ce * m, axis=-1) / denom
    acc = jnp.sum(correct.astype(jnp.float32) * m, axis=-1) / denom
    pc = jnp.sum(pmax * m, axis=-1) / denom
    return loss, acc >= pa_threshold, pc


# ---------------------------------------------------------------------------
# Serving: prefill + decode with stacked per-layer caches
# ---------------------------------------------------------------------------


def init_cache(cfg: ArchConfig, batch: int, max_len: int,
               dtype=jnp.bfloat16, ring: bool = False) -> dict:
    """Stacked (L, ...) caches.

    ``ring=True`` (long-context serve for SWA archs): the attention cache is a
    ring buffer of size ``attn_window`` and every layer attends SWA — the
    sub-quadratic mode that makes the 512K-ctx cells feasible.
    """
    L = cfg.num_layers
    cache: dict[str, Any] = {"len": jnp.zeros((), jnp.int32)}
    if cfg.family != "ssm" and cfg.num_heads:
        s_cache = max_len
        if ring:
            assert cfg.attn_window is not None, "ring cache needs a window"
            s_cache = min(max_len, cfg.attn_window)
        hkv, dh = cfg.num_kv_heads, cfg.resolved_head_dim
        cache["k"] = jnp.zeros((L, batch, s_cache, hkv, dh), dtype)
        cache["v"] = jnp.zeros((L, batch, s_cache, hkv, dh), dtype)
    if cfg.family in ("ssm", "hybrid"):
        di = _d_inner(cfg)
        n, nh, hd = cfg.ssm.state_dim, di // cfg.ssm.head_dim, cfg.ssm.head_dim
        conv_dim = di + 2 * n
        cache["ssm_state"] = jnp.zeros((L, batch, nh, n, hd), jnp.float32)
        cache["conv_buf"] = jnp.zeros(
            (L, batch, cfg.ssm.conv_width - 1, conv_dim), dtype)
    return cache


def _decode_block(cfg: ArchConfig, ctx: ParallelCtx, p: dict, x: jax.Array,
                  layer_cache: dict, cache_len: jax.Array,
                  is_global: jax.Array) -> tuple[jax.Array, dict]:
    new_cache = dict(layer_cache)
    h = rms_norm(x, p["ln1"], cfg.norm_eps)
    positions = cache_len[None, None] + jnp.zeros((x.shape[0], 1), jnp.int32)
    if cfg.family == "ssm":
        y, sc = ssm_mod.ssm_decode_step(
            p["ssm"], h, {"state": layer_cache["ssm_state"],
                          "conv_buf": layer_cache["conv_buf"]},
            cfg.ssm, _d_inner(cfg), cfg.norm_eps)
        new_cache["ssm_state"], new_cache["conv_buf"] = sc["state"], sc["conv_buf"]
        return x + y, new_cache
    q, k, v = attn.project_qkv(p["attn"], h, positions, cfg.rope_theta,
                               cfg.qk_norm, cfg.norm_eps)
    s_cache = layer_cache["k"].shape[1]
    ring = cfg.attn_window is not None and s_cache <= cfg.attn_window
    write_idx = cache_len % s_cache if ring else cache_len
    kc, vc = attn.update_cache(layer_cache["k"], layer_cache["v"],
                               k.astype(layer_cache["k"].dtype),
                               v.astype(layer_cache["v"].dtype), write_idx)
    new_cache["k"], new_cache["v"] = kc, vc
    if ctx.seq_parallel_kv and ctx.mesh is not None:
        a = _sp_decode_attend(ctx, q, kc, vc, cache_len + 1)
    elif ring:
        # Ring buffer: every resident slot is inside the window by
        # construction; only mask the not-yet-written slots.
        a = attn.decode_attend(q, kc, vc, jnp.minimum(cache_len + 1, s_cache))
    else:
        a = attn.decode_attend(q, kc, vc, cache_len + 1,
                               window=cfg.attn_window, is_global=is_global)
    a = jnp.einsum("bshk,hkd->bsd", a, p["attn"]["wo"].astype(x.dtype))
    if cfg.family == "hybrid":
        y, sc = ssm_mod.ssm_decode_step(
            p["ssm"], h, {"state": layer_cache["ssm_state"],
                          "conv_buf": layer_cache["conv_buf"]},
            cfg.ssm, _d_inner(cfg), cfg.norm_eps)
        new_cache["ssm_state"], new_cache["conv_buf"] = sc["state"], sc["conv_buf"]
        x = x + 0.5 * (a + y)
    else:
        x = x + a
    h2 = rms_norm(x, p["ln2"], cfg.norm_eps)
    if cfg.family == "moe":
        y, _ = moe_mod.moe_ffn(p["moe"], h2, cfg.moe, ctx)
        x = x + y
    else:
        x = x + gated_mlp(h2, p["mlp"]["w_gate"], p["mlp"]["w_up"],
                          p["mlp"]["w_down"])
    return x, new_cache


def _sp_decode_attend(ctx: ParallelCtx, q, kc, vc, cache_len):
    """Sequence-parallel flash-decode: KV sharded over 'model' on seq dim."""
    from jax.sharding import PartitionSpec as P
    dp = ctx.dp_axes

    def inner(q_l, k_l, v_l, n):
        return attn.decode_attend_sp(q_l, k_l, v_l, n, axis="model")

    return jax.shard_map(
        inner, mesh=ctx.mesh,
        in_specs=(P(dp, None, None, None), P(dp, "model", None, None),
                  P(dp, "model", None, None), P()),
        out_specs=P(dp, None, None, None), check_vma=False,
    )(q, kc, vc, cache_len)


def decode_step(cfg: ArchConfig, ctx: ParallelCtx, params: dict,
                token: jax.Array, cache: dict) -> tuple[jax.Array, dict]:
    """One decode step. token: (B, 1) int32. Returns (logits (B,1,V), cache)."""
    x = params["embed"].astype(_cdtype(params))[token]
    flags = global_layer_flags(cfg)
    cache_len = cache["len"]
    layer_caches = {k: v for k, v in cache.items() if k != "len"}

    def body(x, xs):
        layer_p, layer_c, flag = xs
        x, new_c = _decode_block(cfg, ctx, layer_p, x, layer_c, cache_len, flag)
        return x, new_c

    x, new_layer_caches = _scan(
        body, x, (params["layers"], layer_caches, flags))
    x = rms_norm(x, params["out_norm"], cfg.norm_eps)
    logits = logits_fn(cfg, params, x)
    new_cache = dict(new_layer_caches)
    new_cache["len"] = cache_len + 1
    return logits, new_cache


def prefill(cfg: ArchConfig, ctx: ParallelCtx, params: dict,
            batch: dict, max_len: int | None = None) -> tuple[jax.Array, dict]:
    """Prefill: run the full prompt, return last-position logits + cache."""
    x, _ = embed_inputs(cfg, ctx, params, batch)
    b, s = x.shape[0], x.shape[1]
    # s includes VLM patch positions; the cache must cover them too.
    max_len = max(max_len or s, s)
    positions = jnp.arange(s)[None, :]
    flags = global_layer_flags(cfg)
    cache = init_cache(cfg, b, max_len, dtype=x.dtype)

    def body(carry, xs):
        x, _aux = carry
        layer_p, flag = xs
        h = rms_norm(x, layer_p["ln1"], cfg.norm_eps)
        emit = {}
        if cfg.family == "ssm":
            y, st, cb = ssm_mod.ssm_forward(layer_p["ssm"], h, cfg.ssm,
                                            _d_inner(cfg), cfg.norm_eps,
                                            return_state=True)
            emit["ssm_state"], emit["conv_buf"] = st, cb
            return (x + y, _aux), emit
        q, k, v = attn.project_qkv(layer_p["attn"], h, positions,
                                   cfg.rope_theta, cfg.qk_norm, cfg.norm_eps)
        emit["k"], emit["v"] = k, v
        a = attn.attend(q, k, v, causal=True, window=cfg.attn_window,
                        is_global=flag)
        a = jnp.einsum("bshk,hkd->bsd", a,
                       layer_p["attn"]["wo"].astype(x.dtype))
        if cfg.family == "hybrid":
            y, st, cb = ssm_mod.ssm_forward(layer_p["ssm"], h, cfg.ssm,
                                            _d_inner(cfg), cfg.norm_eps,
                                            return_state=True)
            emit["ssm_state"], emit["conv_buf"] = st, cb
            x = x + 0.5 * (a + y)
        else:
            x = x + a
        h2 = rms_norm(x, layer_p["ln2"], cfg.norm_eps)
        if cfg.family == "moe":
            y, aux = moe_mod.moe_ffn(layer_p["moe"], h2, cfg.moe, ctx)
            x, _aux = x + y, _aux + aux
        else:
            x = x + gated_mlp(h2, layer_p["mlp"]["w_gate"],
                              layer_p["mlp"]["w_up"], layer_p["mlp"]["w_down"])
        return (x, _aux), emit

    fn = body
    if ctx.remat:
        fn = jax.checkpoint(body, policy=_remat_policy(ctx))
    (x, _), emitted = _scan(fn, (x, jnp.float32(0.0)),
                                   (params["layers"], flags))
    if "k" in emitted:
        kv_dt = cache["k"].dtype
        cache["k"] = cache["k"].at[:, :, :s].set(emitted["k"].astype(kv_dt))
        cache["v"] = cache["v"].at[:, :, :s].set(emitted["v"].astype(kv_dt))
    if "ssm_state" in emitted:
        cache["ssm_state"] = emitted["ssm_state"]
        cache["conv_buf"] = emitted["conv_buf"].astype(cache["conv_buf"].dtype)
    cache["len"] = jnp.full((), s, jnp.int32)
    logits = logits_fn(
        cfg, params, rms_norm(x[:, -1:], params["out_norm"], cfg.norm_eps))
    return logits, cache
