"""Jit'd public wrappers over the Pallas kernels (padding, layout, dispatch).

Backend selection is centralised in ``repro.kernels.backend``: every wrapper
takes ``interpret: bool | None = None`` and resolves ``None`` through the
probe (interpret mode off-TPU, compiled Mosaic on TPU, both overridable with
``REPRO_PALLAS_INTERPRET=0|1``) — resolution happens *outside* the jit
boundary and the flag is a static argument, so flipping the backend
retraces instead of silently reusing a stale compilation.

``fused_loss_metrics`` is the train-hot-path entry point: the per-sample
(ce, PA, PC) triple of paper Sec. 3.4 in one streaming pass, differentiable
(an analytic ``custom_vjp`` — ``pallas_call`` has no autodiff rule), with
the forward dispatched per ``backend.scoring_backend()``: the Pallas kernel
where it compiles, a fused one-pass jnp twin where the kernel would only
interpret.  ``rank_select`` is the count-then-select twin for the rank-based
plans (see ``threshold_select.rank_select_mask``).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import backend
from repro.kernels import flash_attention as _fa
from repro.kernels import loss_confidence as _lc
from repro.kernels import ssd_scan as _ssd
from repro.kernels import threshold_select as _ts

# Re-exported probe API (the documented entry points).
use_interpret = backend.use_interpret
backend_name = backend.backend_name
scoring_backend = backend.scoring_backend


@functools.partial(jax.jit, static_argnames=("causal", "interpret"))
def flash_attention(q, k, v, causal: bool = True,
                    interpret: bool | None = None):
    """(B, S, H, D) flash attention; differentiable, blocking from the
    shape (``kernels/flash_attention.py``)."""
    return _fa.flash_attention(q, k, v, causal=causal,
                               interpret=backend.resolve(interpret))


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def ssd_scan(x, dt, a_log, b, c, d_skip, chunk: int = 128,
             interpret: bool | None = None):
    """Same signature as models.ssm.ssd_scan_ref (the oracle).

    x: (B,S,NH,P); dt: (B,S,NH) raw (pre-softplus); b,c: (B,S,N).
    """
    interpret = backend.resolve(interpret)
    B, S, NH, P = x.shape
    n = b.shape[-1]
    s_orig = S
    if S % chunk:
        pad = chunk - S % chunk
        x = jnp.pad(x, ((0, 0), (0, pad), (0, 0), (0, 0)))
        b = jnp.pad(b, ((0, 0), (0, pad), (0, 0)))
        c = jnp.pad(c, ((0, 0), (0, pad), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad), (0, 0)), constant_values=-1e30)
        S += pad
    a = -jnp.exp(a_log.astype(jnp.float32))
    dtp = jax.nn.softplus(dt.astype(jnp.float32))            # (B,S,NH)
    dta = dtp * a[None, None, :]
    # (B*NH, ...) layout, b/c broadcast across heads
    xr = x.transpose(0, 2, 1, 3).reshape(B * NH, S, P)
    dtr = dtp.transpose(0, 2, 1).reshape(B * NH, S)
    dtar = dta.transpose(0, 2, 1).reshape(B * NH, S)
    br = jnp.broadcast_to(b[:, None], (B, NH, S, n)).reshape(B * NH, S, n)
    cr = jnp.broadcast_to(c[:, None], (B, NH, S, n)).reshape(B * NH, S, n)
    y, state = _ssd.ssd_scan_kernel(xr, dtr, dtar, br, cr, chunk=chunk,
                                    interpret=interpret)
    y = y.reshape(B, NH, S, P).transpose(0, 2, 1, 3)[:, :s_orig]
    y = y + d_skip[None, None, :, None].astype(jnp.float32) * x[:, :s_orig].astype(jnp.float32)
    state = state.reshape(B, NH, n, P)
    return y.astype(x.dtype), state


def _padded_kernel_metrics(lf, lab, interpret):
    """Pad (T, V) to the kernel's block grid and run loss_confidence_kernel."""
    t = lf.shape[0]
    v = lf.shape[1]
    blk_t = 256
    if t % blk_t:
        pad = blk_t - t % blk_t
        lf = jnp.pad(lf, ((0, pad), (0, 0)))
        lab = jnp.pad(lab, (0, pad))
    blk_v = 2048
    while v % blk_v:
        blk_v //= 2
    ce, cor, pmax = _lc.loss_confidence_kernel(
        lf, lab, blk_t=min(blk_t, lf.shape[0]), blk_v=max(blk_v, 1),
        interpret=interpret)
    return ce[:t], cor[:t], pmax[:t]


@functools.partial(jax.jit, static_argnames=("interpret",))
def loss_confidence(logits, labels, interpret: bool | None = None):
    """(..., V) logits + (...) labels -> per-element (ce, correct, pmax)."""
    interpret = backend.resolve(interpret)
    shape = labels.shape
    v = logits.shape[-1]
    lf = logits.reshape(-1, v)
    lab = labels.reshape(-1)
    t = lf.shape[0]
    ce, cor, pmax = _padded_kernel_metrics(lf, lab, interpret)
    return (ce.reshape(shape), cor.reshape(shape).astype(bool),
            pmax.reshape(shape))


@functools.partial(jax.jit, static_argnames=("bins", "interpret"))
def loss_histogram(loss, valid, lo, hi, bins: int = 512,
                   interpret: bool | None = None):
    return _ts.histogram_kernel(_ts.bin_index(loss, lo, hi, bins), valid,
                                bins=bins,
                                interpret=backend.resolve(interpret))


@functools.partial(jax.jit, static_argnames=("interpret",))
def loss_minmax(loss, valid, interpret: bool | None = None):
    """Raw (lo, hi) scalars of the valid losses (no degeneracy fold — see
    threshold_select.minmax_kernel)."""
    mm = _ts.minmax_kernel(loss, valid, interpret=backend.resolve(interpret))
    return mm[0], mm[1]


@functools.partial(jax.jit,
                   static_argnames=("high", "use_kernel", "interpret"))
def rank_select(scores, k, high: bool = False, use_kernel: bool | None = None,
                interpret: bool | None = None):
    """Exact k-smallest (or -largest) mask via count-then-select.

    Bit-identical to the stable-argsort rank masks (see
    threshold_select.rank_select_mask for the tie contract).  ``use_kernel``
    defaults per the probe, mirroring ``scoring_backend()``: the Pallas
    histogram/select kernels where they compile (TPU), the jnp radix twin
    under the interpreter — either way the plan stops materialising a full
    argsort.
    """
    if use_kernel is None:
        use_kernel = not backend.use_interpret()
    return _ts.rank_select_mask(scores, k, high=high, use_kernel=use_kernel,
                                interpret=backend.resolve(interpret))


# ---------------------------------------------------------------------------
# Fused in-step scoring: differentiable (ce, pa, pc) in one streaming pass
# ---------------------------------------------------------------------------


def _reference_metrics(lf, lab):
    """Fused one-pass jnp twin of loss_confidence_kernel (the hot-path
    backend where the kernel would only interpret): two reductions (max,
    sum-exp) + the gold gather — no separate argmax/logsumexp/softmax
    passes, and ``correct`` falls out of the same max (gold >= m, exactly
    the kernel's tie rule)."""
    m = jnp.max(lf, axis=-1)
    sumexp = jnp.sum(jnp.exp(lf - m[:, None]), axis=-1)
    lse = m + jnp.log(sumexp)
    gold = jnp.take_along_axis(lf, lab[:, None], axis=-1)[:, 0]
    ce = lse - gold
    correct = gold >= m
    pmax = 1.0 / sumexp
    return ce, correct, pmax


@functools.lru_cache(maxsize=None)
def _fused_metrics_vjp(which: str, interpret: bool):
    """The custom_vjp core, cached per (backend, interpret) pair.

    Forward runs the one-pass scoring (kernel or jnp reference); backward is
    the analytic softmax gradient — ``pallas_call`` has no autodiff rule,
    and even the jnp path profits: lse is reconstructed from the saved
    ``ce`` (lse = ce + gold) instead of re-reducing, so the backward is a
    single elementwise pass over the logits.  Only ``ce`` carries gradient;
    PA/PC are selection bookkeeping, not loss terms.
    """

    @jax.custom_vjp
    def fused(logits, labels):
        lf = logits.astype(jnp.float32)
        if which == "kernel":
            ce, cor, pmax = _padded_kernel_metrics(lf, labels, interpret)
            return ce, cor != 0, pmax
        return _reference_metrics(lf, labels)

    def fwd(logits, labels):
        out = fused(logits, labels)
        return out, (logits, labels, out[0])

    def bwd(res, cts):
        logits, labels, ce = res
        g = cts[0]            # d/d(ce); PA/PC cotangents are float0: ignored
        lf = logits.astype(jnp.float32)
        gold = jnp.take_along_axis(lf, labels[:, None], axis=-1)[:, 0]
        lse = ce + gold       # saved forward result: no second reduction
        probs = jnp.exp(lf - lse[:, None])
        onehot = labels[:, None] == jax.lax.broadcasted_iota(
            labels.dtype, lf.shape, 1)
        dlogits = ((probs - onehot) * g[:, None]).astype(logits.dtype)
        zeros = np.zeros(labels.shape, dtype=jax.dtypes.float0)
        return dlogits, zeros

    fused.defvjp(fwd, bwd)
    return fused


def fused_loss_metrics(logits, labels, scoring: str | None = None,
                       interpret: bool | None = None):
    """Per-sample ``(ce, pa, pc)`` from (B, V) logits in one fused pass.

    The train-step scoring behind ``TrainConfig.fused_scoring``: one
    streaming online-softmax pass instead of the three jnp reductions of
    ``models.cnn.per_sample_metrics``, differentiable through ``ce`` (the
    analytic vjp above).  ``scoring`` picks the forward backend — "kernel"
    (Pallas) or "reference" (fused jnp) — defaulting to
    ``backend.scoring_backend()``: the kernel wherever it compiles, the
    reference where the kernel would only interpret.
    """
    scoring = scoring or backend.scoring_backend()
    if scoring not in ("kernel", "reference"):
        raise ValueError(
            f"fused_loss_metrics scoring={scoring!r}: must be 'kernel' or "
            "'reference'")
    return _fused_metrics_vjp(scoring, backend.resolve(interpret))(
        logits, labels)
