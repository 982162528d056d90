"""Unified model facade: one API over all assigned architecture families.

``Model`` wraps (family-dispatched) param construction, forward/loss,
prefill/decode, abstract input specs and logical shardings — everything the
trainer, server and dry-run need.
"""
from __future__ import annotations

from typing import Any

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig, ShapeSpec
from repro.dist.sharding import ParallelCtx, spec_tree_for
from repro.models import encdec, transformer
from repro.models.common import (
    abstract_params, init_params, logical_tree,
)
from repro.models.transformer import VLM_PATCH_DIM

ENC_FRAME_DIM = 1024       # stub audio frontend (w2v-BERT-style) output dim
DEC_FRACTION = 4           # encdec: S_dec = seq_len // DEC_FRACTION
VLM_NUM_PATCHES = 576      # stub vision frontend (24x24 patches, anyres base)


class Model:
    def __init__(self, cfg: ArchConfig, ctx: ParallelCtx | None = None):
        self.cfg = cfg
        self.ctx = ctx or ParallelCtx()
        self._mod = encdec if cfg.family == "encdec" else transformer

    # -- params ---------------------------------------------------------------

    def param_defs(self):
        if self.cfg.family == "encdec":
            return self._mod.param_defs(self.cfg)
        return self._mod.param_defs(
            self.cfg, getattr(self.ctx, "moe_fsdp_mode", "gather"))

    def abstract_params(self, dtype=jnp.bfloat16):
        return abstract_params(self.param_defs(), dtype)

    def param_specs(self, dtype=jnp.bfloat16):
        defs = self.param_defs()
        return spec_tree_for(logical_tree(defs), self.ctx,
                             abstract_params(defs, dtype))

    def init(self, rng: jax.Array, dtype=jnp.float32):
        return init_params(rng, self.param_defs(), dtype)

    # -- training -------------------------------------------------------------

    def loss_and_metrics(self, params, batch: dict):
        """Returns (scalar_loss, (per-sample loss, PA, PC))."""
        cfg = self.cfg
        logits, mask, aux = self._mod.forward(cfg, self.ctx, params, batch)
        labels = batch["labels"]
        if cfg.family == "vlm" and logits.shape[1] != labels.shape[1]:
            logits = logits[:, -labels.shape[1]:]          # drop patch positions
            mask = mask[:, -labels.shape[1]:]
        with jax.named_scope("scoring"):
            metrics = self._mod.per_sample_metrics(cfg, logits, labels, mask)
        loss_vec, pa, pc = metrics
        w = batch.get("weight")
        weighted = loss_vec * w if w is not None else loss_vec
        scalar = jnp.mean(weighted)
        if cfg.moe is not None:
            scalar = scalar + cfg.moe.router_aux_weight * aux
        return scalar, (loss_vec, pa, pc)

    # -- serving ----------------------------------------------------------------

    def prefill(self, params, batch: dict, max_len: int | None = None):
        return self._mod.prefill(self.cfg, self.ctx, params, batch, max_len)

    def decode_step(self, params, token, cache):
        return self._mod.decode_step(self.cfg, self.ctx, params, token, cache)

    def init_cache(self, batch: int, max_len: int, dtype=jnp.bfloat16,
                   ring: bool = False):
        cfg = self.cfg
        if cfg.family == "encdec":
            return encdec.init_cache(cfg, batch, max_len,
                                     enc_len=max_len // DEC_FRACTION, dtype=dtype)
        return transformer.init_cache(cfg, batch, max_len, dtype, ring=ring)

    # -- abstract inputs for the dry-run ---------------------------------------

    def input_specs(self, shape: ShapeSpec, dtype=jnp.bfloat16) -> dict:
        """ShapeDtypeStruct stand-ins for every model input (no allocation)."""
        cfg = self.cfg
        b, s = shape.global_batch, shape.seq_len
        i32, b8 = jnp.int32, jnp.bool_

        def tok(bb, ss):
            return jax.ShapeDtypeStruct((bb, ss), i32)

        if shape.kind in ("train", "prefill"):
            if cfg.family == "encdec":
                batch = {
                    "frames": jax.ShapeDtypeStruct((b, s, ENC_FRAME_DIM), dtype),
                    "tokens": tok(b, s // DEC_FRACTION),
                }
            elif cfg.family == "vlm":
                batch = {
                    "patch_embeds": jax.ShapeDtypeStruct(
                        (b, VLM_NUM_PATCHES, VLM_PATCH_DIM), dtype),
                    "tokens": tok(b, s),
                }
            else:
                batch = {"tokens": tok(b, s)}
            if shape.kind == "train":
                lab = batch["tokens"].shape
                batch["labels"] = jax.ShapeDtypeStruct(lab, i32)
                batch["mask"] = jax.ShapeDtypeStruct(lab, b8)
            return batch
        # decode: one new token against a cache of length s
        ring = (cfg.attn_window is not None and s > cfg.attn_window
                and cfg.sub_quadratic)
        cache = jax.eval_shape(
            lambda: self.init_cache(b, s, dtype=dtype, ring=ring))
        return {"token": tok(b, 1), "cache": cache}

    def input_logical(self, shape: ShapeSpec) -> dict:
        """Logical sharding axes matching input_specs' structure."""
        cfg = self.cfg
        if shape.kind in ("train", "prefill"):
            out: dict[str, Any] = {"tokens": ("batch", None)}
            if cfg.family == "encdec":
                out["frames"] = ("batch", None, None)
            if cfg.family == "vlm":
                out["patch_embeds"] = ("batch", None, None)
            if shape.kind == "train":
                out["labels"] = ("batch", None)
                out["mask"] = ("batch", None)
            return out
        seq_ax = "seq_tp" if self.ctx.seq_parallel_kv else None
        cache: dict[str, Any] = {"len": ()}
        if cfg.family != "ssm" and cfg.num_heads:
            cache["k"] = (None, "batch", seq_ax, None, None)
            cache["v"] = (None, "batch", seq_ax, None, None)
        if cfg.family == "encdec":
            cache["xk"] = (None, "batch", None, None, None)
            cache["xv"] = (None, "batch", None, None, None)
        if cfg.family in ("ssm", "hybrid"):
            cache["ssm_state"] = (None, "batch", None, None, None)
            cache["conv_buf"] = (None, "batch", None, None)
        return {"token": ("batch", None), "cache": cache}

    def input_shardings(self, shape: ShapeSpec, dtype=jnp.bfloat16):
        specs = self.input_specs(shape, dtype)
        logical = self.input_logical(shape)
        return jax.tree.map(
            lambda lg, sds: self.ctx.spec(*lg, dims=tuple(sds.shape)),
            logical, specs,
            is_leaf=lambda x: isinstance(x, tuple) and all(
                isinstance(e, (str, type(None))) for e in x))


def build_model(cfg: ArchConfig, ctx: ParallelCtx | None = None) -> Model:
    return Model(cfg, ctx)
