"""GQA attention: training/prefill (the Pallas flash kernel where eligible,
else chunked-q jnp) and decode paths.

Layouts:
  x       (B, S, d_model)
  q       (B, S, Hq, Dh)   — Hq sharded over "tp" when divisible
  k, v    (B, S, Hkv, Dh)  — replicated over "tp" when Hkv %% tp != 0 (GQA)
  cache   (B, S_max, Hkv, Dh) — batch-sharded; optionally seq-sharded over
          "model" (sequence-parallel flash-decode, see ``decode_attend_sp``).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro import tracing
from repro.kernels import backend
from repro.kernels.flash_attention import flash_attention
from repro.models.common import ParamDef, rms_norm, rope

NEG_INF = -1e30


def attn_param_defs(d_model: int, n_q: int, n_kv: int, dh: int,
                    qk_norm: bool) -> dict:
    defs = {
        "wq": ParamDef((d_model, n_q, dh), (("fsdp", "tp", None))),
        "wk": ParamDef((d_model, n_kv, dh), (("fsdp", "tp", None))),
        "wv": ParamDef((d_model, n_kv, dh), (("fsdp", "tp", None))),
        "wo": ParamDef((n_q, dh, d_model), (("tp", None, "fsdp"))),
    }
    if qk_norm:
        defs["q_norm"] = ParamDef((dh,), ((None,)), init="ones")
        defs["k_norm"] = ParamDef((dh,), ((None,)), init="ones")
    return defs


def project_qkv(p: dict, x: jax.Array, positions: jax.Array,
                theta: float, qk_norm: bool, norm_eps: float):
    dt = x.dtype
    q = jnp.einsum("bsd,dhk->bshk", x, p["wq"].astype(dt))
    k = jnp.einsum("bsd,dhk->bshk", x, p["wk"].astype(dt))
    v = jnp.einsum("bsd,dhk->bshk", x, p["wv"].astype(dt))
    if qk_norm:
        q = rms_norm(q, p["q_norm"], norm_eps)
        k = rms_norm(k, p["k_norm"], norm_eps)
    q = rope(q, positions, theta)
    k = rope(k, positions, theta)
    return q, k, v


def _grouped_scores(q5, k, scale):
    # q5: (B, Q, Hkv, G, Dh), k: (B, K, Hkv, Dh) -> (B, Hkv, G, Q, K) f32
    return jnp.einsum("bqhgd,bkhd->bhgqk", q5, k,
                      preferred_element_type=jnp.float32) * scale


def _outside_any_mesh(x: jax.Array) -> bool:
    """False when ``x`` lives on a mesh (a GSPMD jit or a shard_map body),
    whatever its size: a Mosaic kernel is not partitioned, and a (1,) mesh
    keeps the jnp path so that it computes what a larger mesh does."""
    return jax.typeof(x).sharding.mesh.empty


def _kernel_eligible(q: jax.Array, window: int | None) -> bool:
    """Whether ``attend`` takes the flash kernel for these inputs."""
    s, dh = q.shape[1], q.shape[3]
    return (window is None and s % 128 == 0 and dh in (64, 128)
            and backend.attention_backend() == "kernel"
            and _outside_any_mesh(q))


def attend(q: jax.Array, k: jax.Array, v: jax.Array, *,
           causal: bool = True, window: int | None = None,
           is_global: jax.Array | bool = True,
           q_chunk: int = 512) -> jax.Array:
    """Full-sequence attention.

    The differentiable flash kernel (``kernels/flash_attention.py``) where
    ``_kernel_eligible``; otherwise chunked-q jnp.  ``window``: sliding-window
    size; applied unless ``is_global`` (a traced bool works — hybrid archs mix
    global and SWA layers inside one scan).  Each trace counts
    ``attention.kernel`` or ``attention.jnp``.
    """
    if _kernel_eligible(q, window):
        tracing.count("attention.kernel")
        return flash_attention(q, k, v, causal=causal)
    tracing.count("attention.jnp")
    return attend_chunked(q, k, v, causal=causal, window=window,
                          is_global=is_global, q_chunk=q_chunk)


def attend_chunked(q: jax.Array, k: jax.Array, v: jax.Array, *,
                   causal: bool = True, window: int | None = None,
                   is_global: jax.Array | bool = True,
                   q_chunk: int = 512) -> jax.Array:
    """``attend``'s jnp path: chunked-q evaluation that materialises each
    chunk's (Cq, S) scores."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    g = hq // hkv
    scale = dh ** -0.5
    q5 = q.reshape(b, s, hkv, g, dh)
    kpos = jnp.arange(s)

    use_window = window is not None
    win = window if use_window else s

    def _block(qc, q0):
        # qc: (B, Cq, Hkv, G, Dh); q0: first global q position of the chunk.
        cq = qc.shape[1]
        scores = _grouped_scores(qc, k, scale)  # (B,Hkv,G,Cq,S) f32
        qpos = q0 + jnp.arange(cq)
        mask = jnp.ones((cq, s), bool)
        if causal:
            mask &= kpos[None, :] <= qpos[:, None]
        if use_window:
            wmask = mask & (qpos[:, None] - kpos[None, :] < win)
            mask = jnp.where(jnp.asarray(is_global), mask, wmask)
        scores = jnp.where(mask[None, None, None], scores, NEG_INF)
        probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
        return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v)

    if s > q_chunk and s % q_chunk == 0:
        nc = s // q_chunk
        qs = q5.reshape(b, nc, q_chunk, hkv, g, dh).transpose(1, 0, 2, 3, 4, 5)

        def body(_, xs):
            qc, idx = xs
            return None, _block(qc, idx * q_chunk)

        _, out = jax.lax.scan(body, None, (qs, jnp.arange(nc)))
        out = out.transpose(1, 0, 2, 3, 4, 5).reshape(b, s, hq, dh)
    else:
        out = _block(q5, 0).reshape(b, s, hq, dh)
    return out


def cross_attend(q: jax.Array, k: jax.Array, v: jax.Array) -> jax.Array:
    """Encoder-decoder cross attention (no mask, no rope on this path)."""
    b, s, hq, dh = q.shape
    hkv = k.shape[2]
    q5 = q.reshape(b, s, hkv, hq // hkv, dh)
    scores = _grouped_scores(q5, k, dh ** -0.5)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v).reshape(b, s, hq, dh)


# ---------------------------------------------------------------------------
# Decode path
# ---------------------------------------------------------------------------


def decode_attend(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                  cache_len: jax.Array, *, window: int | None = None,
                  is_global: jax.Array | bool = True) -> jax.Array:
    """One-token attention against a (B, S_max, Hkv, Dh) cache."""
    # fp8 caches are a storage format; compute in the query dtype.
    k_cache = k_cache.astype(q.dtype)
    v_cache = v_cache.astype(q.dtype)
    b, one, hq, dh = q.shape
    hkv = k_cache.shape[2]
    s = k_cache.shape[1]
    q5 = q.reshape(b, 1, hkv, hq // hkv, dh)
    scores = _grouped_scores(q5, k_cache, dh ** -0.5)  # (B,Hkv,G,1,S)
    kpos = jnp.arange(s)
    mask = kpos < cache_len
    if window is not None:
        wmask = mask & (cache_len - 1 - kpos < window)
        mask = jnp.where(jnp.asarray(is_global), mask, wmask)
    scores = jnp.where(mask[None, None, None, None], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1).astype(q.dtype)
    return jnp.einsum("bhgqk,bkhd->bqhgd", probs, v_cache).reshape(b, 1, hq, dh)


def decode_attend_sp(q: jax.Array, k_loc: jax.Array, v_loc: jax.Array,
                     cache_len: jax.Array, axis: str = "model") -> jax.Array:
    """Sequence-parallel flash-decode (runs under shard_map over ``axis``).

    The KV cache's sequence dim is sharded over the model axis; each shard
    computes local (max, exp-sum, weighted-V) and combines with one pmax +
    two psums of O(B*Hq*Dh) — instead of replicating an O(S) cache 16x.
    """
    k_loc = k_loc.astype(q.dtype)
    v_loc = v_loc.astype(q.dtype)
    b, one, hq, dh = q.shape
    hkv = k_loc.shape[2]
    s_loc = k_loc.shape[1]
    shard = jax.lax.axis_index(axis)
    kpos = shard * s_loc + jnp.arange(s_loc)
    q5 = q.reshape(b, 1, hkv, hq // hkv, dh)
    scores = _grouped_scores(q5, k_loc, dh ** -0.5)  # (B,Hkv,G,1,S_loc) f32
    scores = jnp.where((kpos < cache_len)[None, None, None, None], scores, NEG_INF)
    m_loc = jnp.max(scores, axis=-1, keepdims=True)
    m = jax.lax.pmax(m_loc, axis)
    p = jnp.exp(scores - m)
    den = jax.lax.psum(jnp.sum(p, axis=-1), axis)          # (B,Hkv,G,1)
    num = jax.lax.psum(
        jnp.einsum("bhgqk,bkhd->bhgqd", p.astype(q.dtype), v_loc), axis)
    out = num / den[..., None].astype(q.dtype)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, 1, hq, dh)


def update_cache(k_cache, v_cache, k_new, v_new, idx):
    """Write one token at position ``idx`` (ring-buffer for SWA handled by
    caller passing idx %% window)."""
    k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k_new, idx, axis=1)
    v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v_new, idx, axis=1)
    return k_cache, v_cache
