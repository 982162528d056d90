"""Plain reference of smollm-135m: a Llama decoder (Touvron et al. 2023).

Pre-norm blocks of grouped-query causal self-attention with rotary position
embedding (half-split rotation, as Hugging Face's Llama) and a SwiGLU MLP;
a final RMSNorm and a linear head.  Query head ``h`` reads key/value head
``h // (heads / kv_heads)``.  Everything is one einsum per product, in the
weights' dtype, with softmax and norms in float32.

The weight layout is the program's (``embed``, ``layers`` stacked over
depth, ``out_norm``, ``lm_head``), because the benchmark makes one set of
weights from the seed and hands it to both.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from bench.reference import rms_norm


def program_arch(c: dict) -> dict:
    """Keyword arguments of the program's ``ArchConfig`` for this file."""
    return dict(
        name=c["name"], family="dense", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=c["intermediate_size"],
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"])


def init_weights(c: dict, key, dtype=jnp.float32):
    """Random weights: every matrix N(0, initializer_range^2), norms 1."""
    L, d, ff = c["num_hidden_layers"], c["hidden_size"], c["intermediate_size"]
    h, kv, hd, v = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"], c["vocab_size"])
    std = c["initializer_range"]
    shapes = {
        "embed": (v, d),
        "layers": {
            "attn": {"wq": (L, d, h, hd), "wk": (L, d, kv, hd),
                     "wv": (L, d, kv, hd), "wo": (L, h, hd, d)},
            "mlp": {"w_gate": (L, d, ff), "w_up": (L, d, ff),
                    "w_down": (L, ff, d)},
        },
    }
    if not c["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v)
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    w = jax.tree.unflatten(tree, [
        (std * jax.random.normal(k, s, jnp.float32)).astype(dtype)
        for k, s in zip(keys, leaves)])
    w["layers"]["ln1"] = jnp.ones((L, d), dtype)
    w["layers"]["ln2"] = jnp.ones((L, d), dtype)
    w["out_norm"] = jnp.ones((d,), dtype)
    return w


def _rope(x, theta):
    """Rotary embedding on (b, s, heads, hd): pairs (i, i + hd/2) rotate by
    position * theta^(-2i/hd)."""
    s, hd = x.shape[1], x.shape[-1]
    half = hd // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[None, :, None, :], jnp.sin(ang)[None, :, None, :]
    xf = x.astype(jnp.float32)
    x1, x2 = xf[..., :half], xf[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def forward(c: dict, w, tokens):
    """(b, s) token ids -> (b, s, vocab) logits."""
    eps, theta = c["rms_norm_eps"], c["rope_theta"]
    group = c["num_attention_heads"] // c["num_key_value_heads"]
    x = w["embed"][tokens]
    s = tokens.shape[1]
    causal = jnp.tril(jnp.ones((s, s), bool))

    def layer(x, p):
        h = rms_norm(x, p["ln1"], eps)
        q = _rope(jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wq"]), theta)
        k = _rope(jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wk"]), theta)
        v = jnp.einsum("bsd,dhk->bshk", h, p["attn"]["wv"])
        k = jnp.repeat(k, group, axis=2)
        v = jnp.repeat(v, group, axis=2)
        scores = jnp.einsum("bqhk,bshk->bhqs", q, k).astype(jnp.float32)
        scores = scores / jnp.sqrt(jnp.float32(q.shape[-1]))
        scores = jnp.where(causal[None, None], scores, -jnp.inf)
        probs = jax.nn.softmax(scores, axis=-1).astype(x.dtype)
        a = jnp.einsum("bhqs,bshk->bqhk", probs, v)
        x = x + jnp.einsum("bqhk,hkd->bqd", a, p["attn"]["wo"])
        h = rms_norm(x, p["ln2"], eps)
        gate = jax.nn.silu(jnp.einsum("bsd,df->bsf", h, p["mlp"]["w_gate"]))
        up = jnp.einsum("bsd,df->bsf", h, p["mlp"]["w_up"])
        return x + jnp.einsum("bsf,fd->bsd", gate * up, p["mlp"]["w_down"]), None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, w["layers"])
    x = rms_norm(x, w["out_norm"], eps)
    head = w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head)
