"""Differentiable flash attention (Pallas TPU; validated in interpret mode).

No S x S tensor is written to HBM in either direction.  The forward is an
online softmax that returns ``o`` and the f32 log-sum-exp of every query;
the backward recomputes the probabilities from q, k and that lse (the
standard flash backward, with ``delta = rowsum(dO * O)`` computed once up
front), in one kernel for dQ and one for dK/dV.  So q, k, v, o and lse are
the only residuals the ``custom_vjp`` keeps.  A call that is not
differentiated keeps nothing, and up to ``XLA_FORWARD_MAX_S`` positions it
runs XLA's fused attention instead of the forward kernel, which is faster
there (smollm-135m's step-D refresh forward at 8 x 512).

Layout: feature-major, positions on the lanes.  The kernels read q, k, v and
write o and the gradients as ``(B, H * D, S)``, the physical layout XLA gives
the model's ``(B, S, H, D)`` projections on the TPU, so the transposes in
``_flash`` are bitcasts there and not copies.  Lse and delta are
``(B, Hkv, G, S)`` with G = Hq / Hkv.  Within a grid step the G query heads
of a kv head are stacked, ``G * 128`` queries per 128-position sub-block, so
dK and dV sum over the group inside the kernel and K/V are never repeated in
HBM.  The forward and dQ kernels turn each query sub-block into rows once
and stream them through the MXU against 128 keys at a time (per-query
values as lane-repeated columns); the dK/dV kernel computes the scores
transposed, 128 keys against the stacked queries on the lanes, where the
per-query lse and delta are rows.

Blocking follows the shape.  A grid step holds one (batch, kv-head) group
over a q tile and a kv tile of ``t`` positions (``_tile``: the whole
sequence while G * t fits ``ROW_BUDGET``, so at smollm's 9/3 heads one grid
step per group up to S = 1024).  Longer sequences take a (q tile, kv tile)
grid whose index maps clamp the tiles a causal mask skips, so those are
neither fetched nor computed.  Inside a step the kernels loop over
128-position sub-blocks up to the diagonal, unrolled, and mask only the
diagonal one.

Precision: the MXU gets bf16 operands with f32 accumulation (q, k, v, P, dO
and dS rounded at the dot), which is what a default-precision f32 ``einsum``
does on the TPU; max, exp, sums, lse and the output scaling stay f32, and
inputs and outputs keep their dtype in HBM.  ``_attention(...,
f32_operands=True)`` keeps f32 operands: the parity tests' hook, against an
f32 oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import backend

NEG_INF = -1e30
SUB = 128          # sub-block: 128 positions, one vreg of lanes
ROW_BUDGET = 3072  # queries (G heads x positions) one grid step holds
VMEM_LIMIT = 64 * 1024 * 1024
# Longest sequence whose undifferentiated call runs XLA's attention.  Per
# layer on a v5e (9/3 heads, D 64, causal): at 8 x 512 XLA 0.0745 ms against
# the forward kernel's 0.0901; at 1 x 1024 the kernel leads, 0.0316 against
# 0.0372, as its causal skipping grows with the sub-blocks.
XLA_FORWARD_MAX_S = 512

_NT = (((1,), (1,)), ((), ()))  # a @ b.T


def _tile(s: int, g: int) -> int:
    """Positions per q/kv tile: the largest multiple of 128 dividing S whose
    G * t query columns fit ``ROW_BUDGET`` (at least 128)."""
    n = s // SUB
    return SUB * max((m for m in range(1, n + 1)
                      if n % m == 0 and g * SUB * m <= ROW_BUDGET), default=1)


def _mxu_operand(f32_operands: bool, interpret: bool):
    """What a dot is handed: bf16 (under the interpreter rounded to bf16 and
    widened back, since the CPU's dots take no bf16 x bf16 -> f32; the
    products are exact in f32 either way), or f32 for the parity tests."""
    if f32_operands:
        return lambda x: x.astype(jnp.float32)
    if interpret:
        return lambda x: x.astype(jnp.bfloat16).astype(jnp.float32)
    return lambda x: x.astype(jnp.bfloat16)


class _Shape:
    """Static blocking of one call: batch, kv heads, group, head dim, tile,
    tiles per sequence, sub-blocks per tile and the grid."""

    def __init__(self, b, s, hq, hkv, d):
        self.b, self.s, self.hkv, self.g, self.d = b, s, hkv, hq // hkv, d
        self.t = _tile(s, self.g)
        self.n, self.nsub = s // self.t, self.t // SUB
        self.grid = (b, hkv, self.n, self.n)

    def specs(self, causal: bool, kv_inner: bool):
        """BlockSpecs of q-shaped, kv-shaped and per-query operands on the
        grid (b, kv head, outer tile, inner tile); tiles a causal mask skips
        map to the tile already resident, so they are not fetched again."""
        g, t, d = self.g, self.t, self.d

        def tiles(o, i):
            iq, ik = (o, i) if kv_inner else (i, o)
            if causal:
                iq, ik = ((iq, jnp.minimum(ik, iq)) if kv_inner
                          else (jnp.maximum(iq, ik), ik))
            return iq, ik

        return (pl.BlockSpec((1, g * d, t),
                             lambda b, h, o, i: (b, h, tiles(o, i)[0])),
                pl.BlockSpec((1, d, t),
                             lambda b, h, o, i: (b, h, tiles(o, i)[1])),
                pl.BlockSpec((1, 1, g, t),
                             lambda b, h, o, i: (b, h, 0, tiles(o, i)[0])))

    @staticmethod
    def out_spec(spec):
        """``spec``'s block indexed by the outer tile alone: an output stays
        resident across the inner axis it accumulates over."""
        if len(spec.block_shape) == 4:
            return pl.BlockSpec(spec.block_shape,
                                lambda b, h, o, i: (b, h, 0, o))
        return pl.BlockSpec(spec.block_shape, lambda b, h, o, i: (b, h, o))


def _queries(ref, start, g: int, d: int):
    """Positions ``start:start+128`` of the G query heads of a (1, G*D, t)
    block, side by side: a (D, G*128) f32 matrix."""
    return jnp.concatenate([ref[0, j * d:(j + 1) * d, pl.ds(start, SUB)]
                            for j in range(g)], axis=1).astype(jnp.float32)


def _put_queries(ref, start: int, g: int, d: int, x):
    """Write a (D, G*128) matrix back as ``_queries`` read it."""
    for j in range(g):
        ref[0, j * d:(j + 1) * d, start:start + SUB] = (
            x[:, j * SUB:(j + 1) * SUB].astype(ref.dtype))


def _per_query(ref, start, g: int):
    """The same positions of a (1, 1, G, t) per-query block: (1, G*128)."""
    rows = ref[0, 0, :, pl.ds(start, SUB)]                     # (G, 128)
    return jnp.concatenate([rows[j:j + 1] for j in range(g)], axis=1)


def _keys(ref, start, mxu):
    """Positions ``start:start+128`` of a (1, D, t) block, keys on sublanes:
    (128, D)."""
    return mxu(ref[0, :, pl.ds(start, SUB)].astype(jnp.float32).T)


def _rows(qt, mxu):
    """A (D, G*128) query block as the (G*128, D) rows the MXU streams."""
    return mxu(qt.T)


def _column(ref, start, g: int):
    """The positions of ``_per_query`` as a (G*128, 128) column, each
    query's value on every lane."""
    rows = ref[0, 0, :, pl.ds(start, SUB)]                     # (G, 128)
    return jnp.concatenate(
        [jnp.broadcast_to(rows[j:j + 1], (SUB, SUB)).T for j in range(g)],
        axis=0)


def _diag_mask(shape, key_axis: int):
    """Causal mask of a diagonal sub-block whose G*128 queries run along the
    other axis (position = index % 128): keys at or before the query."""
    key = jax.lax.broadcasted_iota(jnp.int32, shape, key_axis)
    qry = jax.lax.broadcasted_iota(jnp.int32, shape, 1 - key_axis) & (SUB - 1)
    return key <= qry


def _masked(x, key_axis: int, masked: bool):
    if not masked:
        return x
    return jnp.where(_diag_mask(x.shape, key_axis), x, NEG_INF)


def _causal_steps(run, causal: bool, iq, ik, n: int):
    """Run the tile pair (q tile iq, kv tile ik): the diagonal pair masked,
    pairs below it in full, pairs above it not at all."""
    if not causal:
        run(False)
        return
    pl.when(ik == iq)(lambda: run(True))
    if n > 1:
        pl.when(ik < iq)(lambda: run(False))


def _params():
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel", "parallel", "arbitrary"),
        vmem_limit_bytes=VMEM_LIMIT)


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *,
                sh, scale, causal, mxu):
    iq, ik = pl.program_id(2), pl.program_id(3)
    g, d = sh.g, sh.d

    @pl.when(ik == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(q, j, carry, masked):
        m, l, acc = carry              # (R, 128) lane-repeated twice, (R, D)
        start = pl.multiple_of(j * SUB, SUB)
        kt = mxu(k_ref[0, :, pl.ds(start, SUB)])                # (D, 128)
        s = _masked(jnp.dot(q, kt, preferred_element_type=jnp.float32), 1,
                    masked)
        m_new = jnp.maximum(m, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new)
        l = alpha * l + jnp.sum(p, axis=1, keepdims=True)
        vt = mxu(v_ref[0, :, pl.ds(start, SUB)])
        acc = acc * alpha[:, :d] + jax.lax.dot_general(
            mxu(p), vt, _NT, preferred_element_type=jnp.float32)
        return m_new, l, acc

    def run(diag):
        for a in range(sh.nsub):
            q = _rows(_queries(q_ref, a * SUB, g, d) * scale, mxu)
            carry = (m_ref[a], l_ref[a], acc_ref[a])
            n_full = a if diag else sh.nsub
            if n_full:
                carry = jax.lax.fori_loop(
                    0, n_full, lambda j, c, q=q: step(q, j, c, False), carry,
                    unroll=True)
            if diag:
                carry = step(q, a, carry, True)
            m_ref[a], l_ref[a], acc_ref[a] = carry

    _causal_steps(run, causal, iq, ik, sh.n)

    @pl.when(ik == (iq if causal else sh.n - 1))
    def _finish():
        for a in range(sh.nsub):
            l = l_ref[a]
            _put_queries(o_ref, a * SUB, g, d, (acc_ref[a] / l[:, :d]).T)
            lse = m_ref[a] + jnp.log(l)                        # (R, 128)
            for j in range(g):
                lse_ref[0, 0, j:j + 1, a * SUB:(a + 1) * SUB] = (
                    lse[j * SUB:(j + 1) * SUB].T[0:1])


def _forward(qt, kt, vt, sh, causal, f32_operands, interpret):
    qspec, kvspec, rowspec = sh.specs(causal, kv_inner=True)
    r = sh.g * SUB
    return pl.pallas_call(
        functools.partial(_fwd_kernel, sh=sh, scale=sh.d ** -0.5,
                          causal=causal,
                          mxu=_mxu_operand(f32_operands, interpret)),
        grid=sh.grid,
        in_specs=[qspec, kvspec, kvspec],
        out_specs=[sh.out_spec(qspec), sh.out_spec(rowspec)],
        out_shape=[jax.ShapeDtypeStruct(qt.shape, qt.dtype),
                   jax.ShapeDtypeStruct((sh.b, sh.hkv, sh.g, sh.s),
                                        jnp.float32)],
        scratch_shapes=[pltpu.VMEM((sh.nsub, r, SUB), jnp.float32),
                        pltpu.VMEM((sh.nsub, r, SUB), jnp.float32),
                        pltpu.VMEM((sh.nsub, r, sh.d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_attention_fwd",
    )(qt, kt, vt)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------


def _probs_and_ds(k, v, qt, dot, lse, delta, masked):
    """Transposed P and dS of one (128 keys, G*128 queries) sub-block."""
    st = _masked(jnp.dot(k, qt, preferred_element_type=jnp.float32), 0, masked)
    pt = jnp.exp(st - lse)
    dpt = jnp.dot(v, dot, preferred_element_type=jnp.float32)
    return pt, pt * (dpt - delta)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dq_ref, acc_ref,
               *, sh, scale, causal, mxu):
    iq, ik = pl.program_id(2), pl.program_id(3)
    g, d = sh.g, sh.d

    @pl.when(ik == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def step(q, do, lse, delta, j, dq, masked):
        start = pl.multiple_of(j * SUB, SUB)
        kt = mxu(k_ref[0, :, pl.ds(start, SUB)])                # (D, 128)
        vt = mxu(v_ref[0, :, pl.ds(start, SUB)])
        s = _masked(jnp.dot(q, kt, preferred_element_type=jnp.float32), 1,
                    masked)
        p = jnp.exp(s - lse)
        dp = jnp.dot(do, vt, preferred_element_type=jnp.float32)
        ds = p * (dp - delta)
        return dq + jax.lax.dot_general(mxu(ds), kt, _NT,
                                        preferred_element_type=jnp.float32)

    def run(diag):
        for a in range(sh.nsub):
            start = a * SUB
            body = functools.partial(
                step, _rows(_queries(q_ref, start, g, d) * scale, mxu),
                _rows(_queries(do_ref, start, g, d), mxu),
                _column(lse_ref, start, g), _column(dl_ref, start, g))
            dq = acc_ref[a]
            n_full = a if diag else sh.nsub
            if n_full:
                dq = jax.lax.fori_loop(
                    0, n_full, lambda j, c, f=body: f(j, c, False), dq,
                    unroll=True)
            if diag:
                dq = body(a, dq, True)
            acc_ref[a] = dq

    _causal_steps(run, causal, iq, ik, sh.n)

    @pl.when(ik == (iq if causal else sh.n - 1))
    def _finish():
        for a in range(sh.nsub):
            _put_queries(dq_ref, a * SUB, g, d, (acc_ref[a] * scale).T)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, dl_ref, dk_ref, dv_ref,
                dk_acc, dv_acc, *, sh, scale, causal, mxu):
    ik, iq = pl.program_id(2), pl.program_id(3)
    g, d = sh.g, sh.d

    @pl.when(iq == 0)
    def _init():
        dk_acc[...] = jnp.zeros_like(dk_acc)
        dv_acc[...] = jnp.zeros_like(dv_acc)

    def step(k, v, i, carry, masked):
        dkt, dvt = carry                  # (D, 128) each
        start = pl.multiple_of(i * SUB, SUB)
        qt = mxu(_queries(q_ref, start, g, d) * scale)
        dot = mxu(_queries(do_ref, start, g, d))
        pt, dst = _probs_and_ds(k, v, qt, dot, _per_query(lse_ref, start, g),
                                _per_query(dl_ref, start, g), masked)
        dvt = dvt + jax.lax.dot_general(dot, mxu(pt), _NT,
                                        preferred_element_type=jnp.float32)
        dkt = dkt + jax.lax.dot_general(qt, mxu(dst), _NT,
                                        preferred_element_type=jnp.float32)
        return dkt, dvt

    def run(diag):
        for b in range(sh.nsub):
            body = functools.partial(step, _keys(k_ref, b * SUB, mxu),
                                     _keys(v_ref, b * SUB, mxu))
            carry = (dk_acc[b], dv_acc[b])
            if diag:
                carry = body(b, carry, True)
            first = b + 1 if diag else 0
            if first < sh.nsub:
                carry = jax.lax.fori_loop(
                    first, sh.nsub, lambda i, c, f=body: f(i, c, False),
                    carry, unroll=True)
            dk_acc[b], dv_acc[b] = carry

    _causal_steps(run, causal, iq, ik, sh.n)

    @pl.when(iq == sh.n - 1)
    def _finish():
        for b in range(sh.nsub):
            lanes = slice(b * SUB, (b + 1) * SUB)
            dk_ref[0, :, lanes] = dk_acc[b].astype(dk_ref.dtype)
            dv_ref[0, :, lanes] = dv_acc[b].astype(dv_ref.dtype)


def _backward(qt, kt, vt, ot, lse, dot, sh, causal, f32_operands, interpret):
    kw = dict(sh=sh, scale=sh.d ** -0.5, causal=causal,
              mxu=_mxu_operand(f32_operands, interpret))
    r = sh.g * SUB
    delta = jnp.sum((dot.astype(jnp.float32) * ot.astype(jnp.float32))
                    .reshape(sh.b, sh.hkv, sh.g, sh.d, sh.s), axis=3)

    qspec, kvspec, rowspec = sh.specs(causal, kv_inner=True)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, **kw),
        grid=sh.grid,
        in_specs=[qspec, kvspec, kvspec, qspec, rowspec, rowspec],
        out_specs=sh.out_spec(qspec),
        out_shape=jax.ShapeDtypeStruct(qt.shape, qt.dtype),
        scratch_shapes=[pltpu.VMEM((sh.nsub, r, sh.d), jnp.float32)],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_attention_bwd_dq",
    )(qt, kt, vt, dot, lse, delta)

    qspec, kvspec, rowspec = sh.specs(causal, kv_inner=False)
    acc = pltpu.VMEM((sh.nsub, sh.d, SUB), jnp.float32)
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, **kw),
        grid=sh.grid,
        in_specs=[qspec, kvspec, kvspec, qspec, rowspec, rowspec],
        out_specs=[sh.out_spec(kvspec)] * 2,
        out_shape=[jax.ShapeDtypeStruct(kt.shape, kt.dtype),
                   jax.ShapeDtypeStruct(vt.shape, vt.dtype)],
        scratch_shapes=[acc, acc],
        compiler_params=_params(),
        interpret=interpret,
        name="flash_attention_bwd_dkv",
    )(qt, kt, vt, dot, lse, delta)
    return dq, dk, dv


def _xla_attention(q, k, v, causal: bool):
    """The undifferentiated call up to ``XLA_FORWARD_MAX_S`` positions:
    XLA's fused attention (the f32 oracle's program)."""
    from repro.kernels.ref import flash_attention_ref  # ref imports models
    return flash_attention_ref(q, k, v, causal=causal)


@functools.lru_cache(maxsize=None)
def _flash(shape: tuple, causal: bool, f32_operands: bool, interpret: bool):
    """The custom_vjp over (B, S, H, D) operands, one per static setting.
    A differentiated call runs the kernels; an undifferentiated one (a
    forward-only program) runs ``_xla_attention`` up to
    ``XLA_FORWARD_MAX_S`` positions and the forward kernel beyond."""
    sh = _Shape(*shape)

    def features(x):
        return x.reshape(sh.b, sh.s, -1).transpose(0, 2, 1)

    def positions(xt):
        return xt.transpose(0, 2, 1).reshape(sh.b, sh.s, -1, sh.d)

    def fwd(q, k, v):
        qt, kt, vt = features(q), features(k), features(v)
        ot, lse = _forward(qt, kt, vt, sh, causal, f32_operands, interpret)
        return positions(ot), (qt, kt, vt, ot, lse)

    @jax.custom_vjp
    def attn(q, k, v):
        if sh.s <= XLA_FORWARD_MAX_S:
            return _xla_attention(q, k, v, causal)
        return fwd(q, k, v)[0]

    def bwd(res, do):
        grads = _backward(*res, features(do), sh, causal, f32_operands,
                          interpret)
        return tuple(positions(g) for g in grads)

    attn.defvjp(fwd, bwd)
    return attn


def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                    causal: bool = True,
                    interpret: bool | None = None) -> jax.Array:
    """q: (B, S, Hq, D); k, v: (B, S, Hkv, D) -> (B, S, Hq, D).

    Differentiable; S a multiple of 128, Hq a multiple of Hkv, D at most
    128.
    """
    return _attention(q, k, v, causal=causal, interpret=interpret)


def _attention(q, k, v, *, causal: bool, interpret: bool | None,
               f32_operands: bool = False) -> jax.Array:
    """``flash_attention``, with f32 MXU operands on request (the tests)."""
    b, s, hq, d = q.shape
    hkv = k.shape[2]
    if s % SUB or hq % hkv or d > SUB:
        raise ValueError(f"flash_attention: S={s} must be a multiple of {SUB}, "
                         f"Hq={hq} of Hkv={hkv}, and D={d} at most {SUB}")
    return _flash((b, s, hq, hkv, d), causal, f32_operands,
                  backend.resolve(interpret))(q, k, v)
