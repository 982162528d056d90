"""The main path's Pallas kernels at real widths, compiled for a TPU v5e chip.

Interpret mode (every other kernel test) runs the kernel body as jnp and so
accepts tilings that Mosaic refuses.  These tests hand each kernel to the
TPU compiler for a *described* v5e chip — no chip needed — and assert that
the compiled program really contains the kernel (``tpu_custom_call``), so a
silent fall back to a jnp twin cannot pass.

The topology is described inside a fixture, never at import time: only one
process may hold the TPU library, and test workers import every test file.
Keep every such compile in this file, so that one worker holds it.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

N_SCORES = 1 << 20          # per-sample plan state at N = 2^20 samples
SMOLLM_VOCAB = 49152        # smollm-135m's vocabulary


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # A compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it.
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _hlo(fn, *args) -> str:
    return jax.jit(fn).lower(*args).compile().as_text()


def test_loss_confidence_kernel_compiles(one_chip):
    from repro.kernels import loss_confidence, ops
    logits = jax.ShapeDtypeStruct((1024, SMOLLM_VOCAB), jnp.float32,
                                  sharding=one_chip)
    labels = jax.ShapeDtypeStruct((1024,), jnp.int32, sharding=one_chip)
    hlo = _hlo(lambda lg, lab: loss_confidence.loss_confidence_kernel(
        lg, lab, interpret=False), logits, labels)
    assert "tpu_custom_call" in hlo
    # The train step differentiates through fused_loss_metrics' custom vjp.
    hlo = _hlo(lambda lg, lab: jax.grad(lambda x: ops.fused_loss_metrics(
        x, lab, scoring="kernel", interpret=False)[0].sum())(lg),
        logits, labels)
    assert "tpu_custom_call" in hlo


def test_minmax_and_histogram_kernels_compile(one_chip):
    from repro.kernels import threshold_select
    loss = jax.ShapeDtypeStruct((N_SCORES,), jnp.float32, sharding=one_chip)
    valid = jax.ShapeDtypeStruct((N_SCORES,), jnp.bool_, sharding=one_chip)
    hlo = _hlo(lambda x, v: threshold_select.histogram_with_range(
        x, v, interpret=False), loss, valid)
    assert hlo.count("tpu_custom_call") >= 2


def test_rank_select_kernels_compile(one_chip):
    from repro.kernels import threshold_select
    scores = jax.ShapeDtypeStruct((N_SCORES,), jnp.float32, sharding=one_chip)
    k = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    hlo = _hlo(lambda s, kk: threshold_select.rank_select_mask(
        s, kk, use_kernel=True, interpret=False), scores, k)
    # four byte-histogram passes and the select-mask pass
    assert hlo.count("tpu_custom_call") >= 5


def test_flash_attention_compiles(one_chip):
    from repro.kernels import flash_attention
    q = jax.ShapeDtypeStruct((1, 2048, 9, 64), jnp.float32, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, 3, 64), jnp.float32,
                              sharding=one_chip)
    hlo = _hlo(lambda a, b, c: flash_attention.flash_attention(
        a, b, c, causal=True, interpret=False), q, kv, kv)
    assert "tpu_custom_call" in hlo


@pytest.mark.parametrize("b,s", [(8, 512), (1, 2048)])
def test_flash_attention_value_and_vjp_compile(one_chip, b, s):
    """smollm's step shape (one grid step per group) and a longer sequence
    (the causal tile grid): forward and both backward kernels."""
    from repro.kernels import flash_attention
    q = jax.ShapeDtypeStruct((b, s, 9, 64), jnp.float32, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, s, 3, 64), jnp.float32, sharding=one_chip)

    def value_and_vjp(q, k, v):
        out, pull = jax.vjp(lambda *a: flash_attention.flash_attention(
            *a, causal=True, interpret=False), q, k, v)
        return out, pull(out)

    hlo = _hlo(value_and_vjp, q, kv, kv)
    assert hlo.count("tpu_custom_call") >= 3
    for name in ("flash_attention_fwd", "flash_attention_bwd_dq",
                 "flash_attention_bwd_dkv"):
        assert name in hlo
