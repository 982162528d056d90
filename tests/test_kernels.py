"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps, assert_allclose."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import ops
from repro.kernels import flash_attention as fa
from repro.kernels.ref import (flash_attention_ref, histogram_ref,
                               loss_confidence_ref, minmax_ref)
from repro.kernels.threshold_select import BIG, histogram_with_range
from repro.models.ssm import ssd_scan_ref


@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (2, 128, 4, 2, 16), (1, 256, 8, 8, 32), (2, 128, 6, 3, 64),
    (1, 512, 2, 1, 128),
])
@pytest.mark.parametrize("causal", [True, False])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(b, s, hq, hkv, d, causal, dtype, rng):
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), dtype)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), dtype)
    # f32 operands at the MXU for f32 inputs: the algorithm against the f32
    # oracle, without the bf16 rounding of the kernel's operands.  The
    # forward kernel runs where the call is differentiated (``jax.vjp``); an
    # undifferentiated call at these one-tile shapes is XLA's attention.
    out = jax.vjp(lambda *a: fa._attention(
        *a, causal=causal, interpret=None,
        f32_operands=dtype == jnp.float32), q, k, v)[0]
    ref = flash_attention_ref(q, k, v, causal=causal)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("s,hq,hkv", [(512, 9, 3), (640, 4, 4)])
def test_flash_attention_forward_only_call(s, hq, hkv, rng):
    """Undifferentiated: XLA's attention up to ``XLA_FORWARD_MAX_S``
    positions (bit-identical to the oracle), the forward kernel past it (to
    bf16 rounding)."""
    b, d = 1, 64
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k, v = (jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
            for _ in range(2))
    out = jax.jit(fa.flash_attention)(q, k, v)
    ref = flash_attention_ref(q, k, v)
    if s <= fa.XLA_FORWARD_MAX_S:
        np.testing.assert_array_equal(out, jax.jit(flash_attention_ref)(
            q, k, v))
    else:
        assert _rel(out, ref) < BF16_OPERANDS_REL


def test_flash_attention_rejects_head_dim_over_128():
    q = jnp.zeros((1, 128, 2, 256), jnp.float32)
    with pytest.raises(ValueError, match="D=256"):
        fa.flash_attention(q, q, q)


def _vjp_all(fn, q, k, v, ct):
    """fn's output and its (dq, dk, dv) for cotangent ``ct``, one program."""
    def both(q, k, v):
        out, pull = jax.vjp(fn, q, k, v)
        return (out,) + pull(ct)
    return jax.jit(both)(q, k, v)


def _rel(a, b):
    return float(jnp.linalg.norm(a - b) / jnp.linalg.norm(b))


# Relative (Frobenius) error of bf16 MXU operands with f32 accumulation
# against f32 arithmetic: each rounding is 2^-9 relative, and an output or
# gradient sums a few of them (q k^T, P, P v; dO, dS in the backward).
BF16_OPERANDS_REL = 1.5e-2


# Every shape with the kernel's bf16 operands ("default"); the smollm
# grouping, causal and not, also with f32 operands ("highest"), where the
# algorithm must match to f32.  8 query heads on one kv head at S = 512
# exceed one tile's rows: the (q tile, kv tile) grid of long sequences.
VJP_CASES = [(s, hq, hkv, causal, "default", BF16_OPERANDS_REL)
             for s in (128, 256) for hq, hkv in ((9, 3), (4, 4))
             for causal in (True, False)] + [
    (256, 9, 3, causal, "highest", 1e-5) for causal in (True, False)] + [
    (512, 8, 1, causal, "highest", 1e-5) for causal in (True, False)]


@pytest.mark.parametrize("s,hq,hkv,causal,precision,tol", VJP_CASES)
def test_flash_attention_vjp_matches_oracles(s, hq, hkv, causal, precision,
                                             tol, rng):
    """The kernel's output and q, k, v gradients against ``jax.vjp`` of the
    f32 oracle and of ``attend``'s jnp path: within bf16 rounding with bf16
    operands, to f32 rounding with f32 operands."""
    from repro.models.attention import attend_chunked
    b, d = 2, 64
    q = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(b, s, hkv, d)), jnp.float32)
    ct = jnp.asarray(rng.normal(size=(b, s, hq, d)), jnp.float32)
    oracle = _vjp_all(lambda *a: flash_attention_ref(*a, causal=causal),
                      q, k, v, ct)
    jnp_path = _vjp_all(lambda *a: attend_chunked(*a, causal=causal),
                        q, k, v, ct)
    got = _vjp_all(lambda *a: fa._attention(
        *a, causal=causal, interpret=None,
        f32_operands=precision == "highest"),
        q, k, v, ct)
    for name, g, o, j in zip(("o", "dq", "dk", "dv"), got, oracle, jnp_path):
        assert _rel(g, o) < tol, (name, _rel(g, o))
        assert _rel(g, j) < tol, (name, _rel(g, j))


@pytest.mark.parametrize("b,s,nh,p,n,chunk", [
    (2, 64, 3, 16, 8, 16), (1, 128, 2, 32, 16, 32), (2, 96, 1, 8, 4, 16),
])
def test_ssd_scan(b, s, nh, p, n, chunk, rng):
    x = jnp.asarray(rng.normal(size=(b, s, nh, p)), jnp.float32)
    dt = jnp.asarray(rng.normal(size=(b, s, nh)), jnp.float32)
    a_log = jnp.asarray(rng.uniform(0, 1, (nh,)), jnp.float32)
    bm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    cm = jnp.asarray(rng.normal(size=(b, s, n)), jnp.float32)
    dsk = jnp.asarray(rng.normal(size=(nh,)), jnp.float32)
    y1, s1 = ssd_scan_ref(x, dt, a_log, bm, cm, dsk, chunk)
    y2, s2 = ops.ssd_scan(x, dt, a_log, bm, cm, dsk, chunk)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               rtol=1e-4, atol=1e-4)


def test_ssd_scan_matches_sequential_recurrence(rng):
    """Chunked SSD == naive per-step recurrence (independent ground truth)."""
    b, s, nh, p, n, chunk = 1, 32, 2, 8, 4, 8
    x = rng.normal(size=(b, s, nh, p)).astype(np.float32)
    dtr = rng.normal(size=(b, s, nh)).astype(np.float32)
    a_log = rng.uniform(0, 1, (nh,)).astype(np.float32)
    bm = rng.normal(size=(b, s, n)).astype(np.float32)
    cm = rng.normal(size=(b, s, n)).astype(np.float32)
    dsk = np.zeros((nh,), np.float32)
    y_chunked, state_chunked = ssd_scan_ref(
        jnp.asarray(x), jnp.asarray(dtr), jnp.asarray(a_log), jnp.asarray(bm),
        jnp.asarray(cm), jnp.asarray(dsk), chunk)
    # naive recurrence
    a = -np.exp(a_log)
    dt = np.logaddexp(0, dtr)  # softplus
    h = np.zeros((b, nh, n, p), np.float32)
    ys = np.zeros_like(x)
    for t in range(s):
        decay = np.exp(dt[:, t] * a[None, :])                 # (b, nh)
        upd = np.einsum("bh,bn,bhp->bhnp", dt[:, t], bm[:, t], x[:, t])
        h = h * decay[:, :, None, None] + upd
        ys[:, t] = np.einsum("bn,bhnp->bhp", cm[:, t], h)
    np.testing.assert_allclose(np.asarray(y_chunked), ys, rtol=2e-4, atol=2e-4)
    np.testing.assert_allclose(np.asarray(state_chunked), h, rtol=2e-4,
                               atol=2e-4)


@pytest.mark.parametrize("t,v", [(64, 512), (100, 1000), (256, 2048)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_loss_confidence(t, v, dtype, rng):
    lg = jnp.asarray(rng.normal(size=(t, v)) * 3, dtype)
    lab = jnp.asarray(rng.integers(0, v, (t,)), jnp.int32)
    ce1, c1, p1 = loss_confidence_ref(lg.astype(jnp.float32), lab)
    ce2, c2, p2 = ops.loss_confidence(lg, lab)
    np.testing.assert_allclose(np.asarray(ce1), np.asarray(ce2),
                               rtol=1e-3, atol=1e-3)
    assert bool((c1 == c2).all())
    np.testing.assert_allclose(np.asarray(p1), np.asarray(p2),
                               rtol=1e-3, atol=1e-3)


@pytest.mark.parametrize("n,bins", [(1000, 64), (4096, 512), (3000, 128)])
def test_histogram(n, bins, rng):
    loss = jnp.asarray(rng.normal(size=(n,)), jnp.float32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    lo, hi = jnp.float32(-3), jnp.float32(3)
    h1 = histogram_ref(loss, valid, lo, hi, bins)
    h2 = ops.loss_histogram(loss, valid, lo, hi, bins)
    assert bool((h1 == h2).all())
    assert int(h2.sum()) == int(valid.sum())


@pytest.mark.parametrize("n", [1000, 2048, 3000])
def test_minmax(n, rng):
    """The range pass matches the masked-reduction oracle exactly."""
    loss = jnp.asarray(rng.normal(size=(n,)) * 5, jnp.float32)
    valid = jnp.asarray(rng.random(n) < 0.8)
    lo_ref, hi_ref = minmax_ref(loss, valid)
    lo, hi = ops.loss_minmax(loss, valid)
    assert float(lo) == float(lo_ref)
    assert float(hi) == float(hi_ref)


def test_minmax_all_invalid(rng):
    """No valid samples -> the raw [BIG, -BIG] sentinels (callers fold)."""
    loss = jnp.asarray(rng.normal(size=(256,)), jnp.float32)
    lo, hi = ops.loss_minmax(loss, jnp.zeros(256, bool))
    assert float(lo) == pytest.approx(BIG, rel=1e-6)
    assert float(hi) == pytest.approx(-BIG, rel=1e-6)


def test_histogram_with_range_fused(rng):
    """Range pass + histogram pass chained on device == two-step oracle."""
    n, bins = 4096, 512
    loss = jnp.asarray(rng.exponential(1.0, n), jnp.float32)
    valid = jnp.asarray(rng.random(n) < 0.7)
    hist, lo_raw, hi_raw = histogram_with_range(loss, valid, bins=bins)
    lo_ref, hi_ref = minmax_ref(loss, valid)
    assert float(lo_raw) == float(lo_ref)
    assert float(hi_raw) == float(hi_ref)
    h_ref = histogram_ref(loss, valid, jnp.minimum(lo_raw, hi_raw), hi_raw,
                          bins)
    assert bool((hist == h_ref).all())
    assert int(hist.sum()) == int(valid.sum())


def test_model_metrics_match_kernel(rng):
    """transformer.token_metrics (used in training) == fused kernel output."""
    from repro.models.transformer import token_metrics
    t, v = 32, 257
    lg = jnp.asarray(rng.normal(size=(t, v)) * 2, jnp.float32)
    lab = jnp.asarray(rng.integers(0, v, (t,)), jnp.int32)
    ce_m, cor_m, p_m = token_metrics(lg, lab)
    ce_k, cor_k, p_k = ops.loss_confidence(lg, lab)
    np.testing.assert_allclose(np.asarray(ce_m), np.asarray(ce_k), rtol=1e-4,
                               atol=1e-4)
    assert bool((cor_m == cor_k).all())
    np.testing.assert_allclose(np.asarray(p_m), np.asarray(p_k), rtol=1e-4,
                               atol=1e-4)
