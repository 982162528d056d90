"""Plain reference of mamba2-130m: Mamba-2 blocks (Dao and Gu 2024).

Each pre-norm block projects to (z, x, B, C, dt), runs a causal depthwise
convolution and SiLU over (x, B, C), and applies the selective state-space
map with a scalar decay per head, in its quadratic ("dual") form:

    y_t = sum_{s <= t} (C_t . B_s) exp(sum_{r=s+1..t} dt_r A) dt_s x_s + D x_t

with the segment sums built directly from masked cumulative sums (the
paper's ``segsum``), not as differences of one long cumulative sum.  Then
y * SiLU(z), a gated RMSNorm, and the output projection.  One B/C group
(ngroups = 1).  A final RMSNorm and the tied embedding give the logits.

The program computes the same map chunk by chunk with an inter-chunk state
recurrence; this file never chunks.  The weight layout is the program's,
because the benchmark makes one set of weights from the seed for both.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from bench.reference import rms_norm


def _dims(c):
    s = c["ssm_cfg"]
    d_inner = s["expand"] * c["d_model"]
    return d_inner, d_inner // s["headdim"], s["d_state"], s["headdim"]


def padded_vocab(c) -> int:
    """Rows of the embedding: ``vocab_size`` rounded up to a multiple of
    ``pad_vocab_size_multiple``, as the upstream language model pads it."""
    m = c.get("pad_vocab_size_multiple", 1)
    return -(-c["vocab_size"] // m) * m


def program_arch(c: dict) -> dict:
    """Keyword arguments of the program's ``ArchConfig`` for this file."""
    s = c["ssm_cfg"]
    return dict(
        name=c["name"], family="ssm", num_layers=c["n_layer"],
        d_model=c["d_model"], num_heads=0, num_kv_heads=0, d_ff=0,
        vocab_size=padded_vocab(c), tie_embeddings=c["tie_embeddings"],
        norm_eps=c["norm_epsilon"],
        ssm=dict(state_dim=s["d_state"], head_dim=s["headdim"],
                 expand=s["expand"], conv_width=s["d_conv"],
                 chunk=s["chunk_size"]))


def init_weights(c: dict, key, dtype=jnp.float32):
    """Random weights with the upstream Mamba-2 initialisation."""
    L, d, v = c["n_layer"], c["d_model"], padded_vocab(c)
    d_inner, nh, n, _ = _dims(c)
    width = c["ssm_cfg"]["d_conv"]
    conv_dim = d_inner + 2 * n
    k = iter(jax.random.split(key, 8))

    def uniform(kk, shape, bound):
        return jax.random.uniform(kk, shape, jnp.float32, -bound, bound)

    # dt log-uniform in [1e-3, 0.1], floored at 1e-4; dt_bias = softplus^-1(dt).
    dt = jnp.exp(jax.random.uniform(next(k), (L, nh), jnp.float32)
                 * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
    dt = jnp.maximum(dt, 1e-4)
    ssm = {
        "w_in": uniform(next(k), (L, d, 2 * d_inner + 2 * n + nh),
                        1 / math.sqrt(d)),
        "conv_w": uniform(next(k), (L, width, conv_dim), 1 / math.sqrt(width)),
        "conv_b": uniform(next(k), (L, conv_dim), 1 / math.sqrt(width)),
        "a_log": jnp.log(jax.random.uniform(next(k), (L, nh), jnp.float32,
                                            1.0, 16.0)),
        "dt_bias": dt + jnp.log(-jnp.expm1(-dt)),
        "d_skip": jnp.ones((L, nh), jnp.float32),
        "norm_w": jnp.ones((L, d_inner), jnp.float32),
        "w_out": uniform(next(k), (L, d_inner, d), 1 / math.sqrt(d_inner))
        / math.sqrt(L),
    }
    w = {
        "embed": 0.02 * jax.random.normal(next(k), (v, d), jnp.float32),
        "layers": {"ln1": jnp.ones((L, d), jnp.float32), "ssm": ssm},
        "out_norm": jnp.ones((d,), jnp.float32),
    }
    return jax.tree.map(lambda x: x.astype(dtype), w)


def _segsum(a):
    """(..., T) -> (..., T, T): out[t, s] = sum_{r=s+1..t} a_r for s <= t,
    -inf above the diagonal."""
    t = a.shape[-1]
    x = jnp.broadcast_to(a[..., :, None], a.shape + (t,))   # x[t, s] = a_t
    x = jnp.where(jnp.tril(jnp.ones((t, t), bool), -1), x, 0.0)
    seg = jnp.cumsum(x, axis=-2)
    return jnp.where(jnp.tril(jnp.ones((t, t), bool)), seg, -jnp.inf)


def _mixer(c, p, h):
    d_inner, nh, n, hp = _dims(c)
    eps = c["norm_epsilon"]
    width = c["ssm_cfg"]["d_conv"]
    b, s, _ = h.shape
    proj = jnp.einsum("bsd,de->bse", h, p["w_in"])
    z = proj[..., :d_inner]
    xbc = proj[..., d_inner:2 * d_inner + 2 * n]
    dt = proj[..., 2 * d_inner + 2 * n:]
    # Causal depthwise convolution: out_t = sum_i w_i * in_{t - (W-1) + i}.
    padded = jnp.pad(xbc, ((0, 0), (width - 1, 0), (0, 0)))
    conv = sum(padded[:, i:i + s] * p["conv_w"][i] for i in range(width))
    xbc = jax.nn.silu(conv + p["conv_b"])
    x = xbc[..., :d_inner].reshape(b, s, nh, hp)
    bm, cm = xbc[..., d_inner:d_inner + n], xbc[..., d_inner + n:]
    dt = jax.nn.softplus((dt + p["dt_bias"]).astype(jnp.float32))  # (b,s,h)
    a = -jnp.exp(p["a_log"].astype(jnp.float32))                   # (h,)
    decay = jnp.exp(_segsum(jnp.moveaxis(dt * a, -1, 1)))           # (b,h,t,s)
    cb = jnp.einsum("btn,bsn->bts", cm, bm).astype(jnp.float32)
    y = jnp.einsum("bts,bhts,bsh,bshp->bthp", cb, decay, dt,
                   x.astype(jnp.float32))
    y = y + p["d_skip"].astype(jnp.float32)[None, None, :, None] * x
    y = y.reshape(b, s, d_inner).astype(h.dtype) * jax.nn.silu(z)
    y = rms_norm(y, p["norm_w"], eps)
    return jnp.einsum("bse,ed->bsd", y, p["w_out"])


def forward(c: dict, w, tokens):
    """(b, s) token ids -> (b, s, vocab) logits."""
    eps = c["norm_epsilon"]
    x = w["embed"][tokens]

    def layer(x, p):
        return x + _mixer(c, p["ssm"], rms_norm(x, p["ln1"], eps)), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["layers"])
    x = rms_norm(x, w["out_norm"], eps)
    return jnp.einsum("bsd,vd->bsv", x, w["embed"])
