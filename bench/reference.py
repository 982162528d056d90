"""Plain reference of the training step: loss, per-sample signals, AdamW.

Straightforward ``jax.numpy`` with no kernels, caches or batching tricks,
written from the published descriptions and importing nothing of the
program.  Each configuration's forward pass lives beside its configuration
file (``bench/configs/<name>.py``); this module holds what every language
model shares:

- the per-sample signals KAKURENBO ranks by (paper Sec. 3.4, a sample being
  one sequence): loss = mean token cross-entropy, PA = token accuracy at
  least one half, PC = mean max-softmax probability;
- the mean loss of a batch and its gradient, taken in blocks of rows;
- AdamW (Loshchilov and Hutter) with bias correction and decoupled weight
  decay on every parameter.

The precision is the caller's: ``run_steps`` traces under
``jax.default_matmul_precision("highest")`` for the float32 reference, and
the lower-precision control casts the weights to bfloat16 first.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


def rms_norm(x, w, eps):
    """x / rms(x) * w, the mean square taken in float32."""
    xf = x.astype(jnp.float32)
    inv = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return (xf * inv).astype(x.dtype) * w.astype(x.dtype)


def per_sample(logits, labels):
    """Sequence-level (loss, PA, PC) from (b, s, V) logits, in float32."""
    lf = logits.astype(jnp.float32)
    lse = jax.nn.logsumexp(lf, axis=-1)
    gold = jnp.take_along_axis(lf, labels[..., None], axis=-1)[..., 0]
    ce = lse - gold
    acc = jnp.mean((jnp.argmax(lf, axis=-1) == labels).astype(jnp.float32),
                   axis=-1)
    pc = jnp.mean(jnp.exp(jnp.max(lf, axis=-1) - lse), axis=-1)
    return jnp.mean(ce, axis=-1), acc, pc


def batch_loss(forward, weights, tokens, labels):
    """Mean of the per-sample losses, and the per-sample signals."""
    loss, acc, pc = per_sample(forward(weights, tokens), labels)
    return jnp.mean(loss), (loss, acc, pc)


def loss_and_grad(forward, weights, tokens, labels, rows):
    """Batch mean loss, its gradient and the per-sample signals, computed
    ``rows`` rows at a time so that the reference fits beside the program's
    leftovers; the blocks' means are averaged (equal block sizes)."""
    b = tokens.shape[0]
    nb = b // rows
    vg = jax.value_and_grad(functools.partial(batch_loss, forward),
                            has_aux=True)
    total, grads, sig = None, None, []
    for i in range(nb):
        sl = slice(i * rows, (i + 1) * rows)
        (l, s), g = vg(weights, tokens[sl], labels[sl])
        total = l if total is None else total + l
        grads = g if grads is None else jax.tree.map(jnp.add, grads, g)
        sig.append(s)
    grads = jax.tree.map(lambda g: (g / nb).astype(jnp.float32), grads)
    sig = tuple(jnp.concatenate(parts) for parts in zip(*sig))
    return total / nb, grads, sig


def adamw(params, grads, m, v, t, lr, hp):
    """One AdamW step in float32 (t counts from 1)."""
    b1, b2, eps, wd = hp["b1"], hp["b2"], hp["eps"], hp["weight_decay"]
    m = jax.tree.map(lambda m_, g: b1 * m_ + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v_, g: b2 * v_ + (1 - b2) * g * g, v, grads)
    c1, c2 = 1 - b1 ** t, 1 - b2 ** t

    def upd(p, m_, v_):
        return p - lr * ((m_ / c1) / (jnp.sqrt(v_ / c2) + eps) + wd * p)

    return jax.tree.map(upd, params, m, v), m, v


@jax.jit
def leaf_norms(tree) -> jax.Array:
    """L2 norm of every leaf, in ``jax.tree.leaves`` order, in float32."""
    return jnp.stack([jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32))))
                      for x in jax.tree.leaves(tree)])


@jax.jit
def change_norms(after, before) -> jax.Array:
    """Per-leaf L2 norm of ``after - before``."""
    return leaf_norms(jax.tree.map(
        lambda a, b: a.astype(jnp.float32) - b.astype(jnp.float32),
        after, before))
