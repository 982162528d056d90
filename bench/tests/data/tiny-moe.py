"""Plain reference of ``tiny-moe``, a test fixture: a configuration of a
family that ``bench/flops.py`` does not count, brought by its own files.

Pre-norm blocks of grouped-query causal self-attention with rotary position
embedding (half-split rotation) and a mixture of SwiGLU experts: a softmax
router over ``num_experts``, the ``num_experts_per_tok`` most probable
experts per token with their probabilities renormalised to sum to one, and
the token's output their weighted sum.  Every expert is evaluated densely
and the unchosen ones weighted by 0, so no token is ever dropped; the
program's capacity never binds at these sizes.  The router's balancing loss
is switched off (``router_aux_weight`` 0), so the loss is the language
model's alone.
"""
from __future__ import annotations

import json
import sys

import jax
import jax.numpy as jnp

from bench.reference import rms_norm


def program_arch(c: dict) -> dict:
    """Keyword arguments of the program's ``ArchConfig``; ``moe`` is given
    as a plain dict, as a JSON file would hold it."""
    e, k = c["num_experts"], c["num_experts_per_tok"]
    return dict(
        name=c["name"], family="moe", num_layers=c["num_hidden_layers"],
        d_model=c["hidden_size"], num_heads=c["num_attention_heads"],
        num_kv_heads=c["num_key_value_heads"], d_ff=0,
        vocab_size=c["vocab_size"], head_dim=c["head_dim"],
        rope_theta=c["rope_theta"], norm_eps=c["rms_norm_eps"],
        tie_embeddings=c["tie_word_embeddings"],
        moe={"num_experts": e, "top_k": k,
             "d_ff_expert": c["moe_intermediate_size"],
             # A capacity of every token for each expert: none is dropped.
             "capacity_factor": e / k, "router_aux_weight": 0.0})


def init_weights(c: dict, key, dtype=jnp.float32):
    """Random weights: every matrix N(0, initializer_range^2), norms 1."""
    L, d = c["num_hidden_layers"], c["hidden_size"]
    h, kv, hd, v = (c["num_attention_heads"], c["num_key_value_heads"],
                    c["head_dim"], c["vocab_size"])
    e, ff = c["num_experts"], c["moe_intermediate_size"]
    shapes = {
        "embed": (v, d),
        "layers": {
            "attn": {"wq": (L, d, h, hd), "wk": (L, d, kv, hd),
                     "wv": (L, d, kv, hd), "wo": (L, h, hd, d)},
            "moe": {"router": (L, d, e), "w_gate": (L, e, d, ff),
                    "w_up": (L, e, d, ff), "w_down": (L, e, ff, d)},
        },
    }
    if not c["tie_word_embeddings"]:
        shapes["lm_head"] = (d, v)
    leaves, tree = jax.tree.flatten(
        shapes, is_leaf=lambda x: isinstance(x, tuple))
    keys = jax.random.split(key, len(leaves))
    w = jax.tree.unflatten(tree, [
        (c["initializer_range"] * jax.random.normal(k, s, jnp.float32)
         ).astype(dtype) for k, s in zip(keys, leaves)])
    w["layers"]["ln1"] = jnp.ones((L, d), dtype)
    w["layers"]["ln2"] = jnp.ones((L, d), dtype)
    w["out_norm"] = jnp.ones((d,), dtype)
    return w


def _rope(x, theta):
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
    e, k_top = c["num_experts"], c["num_experts_per_tok"]
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
        m = p["moe"]
        router = jax.nn.softmax(jnp.einsum(
            "bsd,de->bse", h, m["router"]).astype(jnp.float32), axis=-1)
        top_p, top_e = jax.lax.top_k(router, k_top)
        top_p = top_p / jnp.sum(top_p, axis=-1, keepdims=True)
        gate = jnp.sum(jax.nn.one_hot(top_e, e, dtype=jnp.float32)
                       * top_p[..., None], axis=-2).astype(x.dtype)
        g = jax.nn.silu(jnp.einsum("bsd,edf->bsef", h, m["w_gate"]))
        u = jnp.einsum("bsd,edf->bsef", h, m["w_up"])
        y = jnp.einsum("bsef,efd->bsed", g * u, m["w_down"])
        return x + jnp.einsum("bse,bsed->bsd", gate, y), None

    x, _ = jax.lax.scan(layer, x, w["layers"])
    x = rms_norm(x, w["out_norm"], eps)
    head = w["embed"].T if c["tie_word_embeddings"] else w["lm_head"]
    return jnp.einsum("bsd,dv->bsv", x, head)


def forward_flops_per_token(c: dict, seq_len: int) -> int:
    """Multiply-adds x 2 of one token's forward: the attention weights and
    the full S x S score and value products, the router, the chosen
    experts' SwiGLU, and the head."""
    d, h, kv, hd = (c["hidden_size"], c["num_attention_heads"],
                    c["num_key_value_heads"], c["head_dim"])
    attn = d * h * hd + 2 * d * kv * hd + h * hd * d
    experts = c["num_experts_per_tok"] * 3 * d * c["moe_intermediate_size"]
    layer = 2 * (attn + d * c["num_experts"] + experts) + 4 * seq_len * h * hd
    return c["num_hidden_layers"] * layer + 2 * d * c["vocab_size"]


def window_flops(rec) -> tuple[int, int]:
    """(trained, refreshed) model FLOPs of the window: the backward pass is
    twice the forward.  Logged, so that a test can check what ``mfu``
    makes of it."""
    fwd = forward_flops_per_token(rec.cfg, rec.seq_len)
    trained = sum(s.bwd_samples for s in rec.epochs) * rec.seq_len * 3 * fwd
    refreshed = sum(s.fwd_samples - s.bwd_samples
                    for s in rec.epochs) * rec.seq_len * fwd
    print("window_flops " + json.dumps(
        {"trained": trained, "refreshed": refreshed,
         "window_s": rec.window_s, "chips": rec.chips}), file=sys.stderr)
    return trained, refreshed
