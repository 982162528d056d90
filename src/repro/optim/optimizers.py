"""Optimizers in pure JAX: SGD(+momentum/Nesterov), AdamW, RMSProp, Adafactor.

The paper trains with SGD-momentum (ResNet/WRN/DeepCAM), RMSProp
(EfficientNet) and AdamW (DeiT) — all provided.  Adafactor (factored second
moment, no momentum) is used for the 1T-param kimi-k2 config where full Adam
state would not fit HBM (DESIGN.md Sec. 5).

API: ``opt = make_optimizer(name, **hp); state = opt.init(params);
params, state = opt.update(grads, state, params, lr)``.  States are pytrees
mirroring params, so pjit shards them exactly like the parameters (ZeRO).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class Optimizer:
    init: Callable[[Any], Any]
    update: Callable[[Any, Any, Any, jax.Array], tuple[Any, Any]]
    name: str = ""


def _tmap(f, *trees):
    return jax.tree.map(f, *trees)


def sgd(momentum: float = 0.0, nesterov: bool = False,
        weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        if momentum == 0.0:
            return ()
        return _tmap(jnp.zeros_like, params)

    def update(grads, state, params, lr):
        if weight_decay:
            grads = _tmap(lambda g, p: g + weight_decay * p, grads, params)
        if momentum == 0.0:
            return _tmap(lambda p, g: p - lr * g, params, grads), state
        new_m = _tmap(lambda m, g: momentum * m + g, state, grads)
        if nesterov:
            step = _tmap(lambda m, g: momentum * m + g, new_m, grads)
        else:
            step = new_m
        return _tmap(lambda p, s: p - lr * s, params, step), new_m

    return Optimizer(init, update, "sgd")


def adamw(b1: float = 0.9, b2: float = 0.999, eps: float = 1e-8,
          weight_decay: float = 0.01, state_dtype=None) -> Optimizer:
    def init(params):
        z = (lambda p: jnp.zeros(p.shape, state_dtype or p.dtype))
        return {"m": _tmap(z, params), "v": _tmap(z, params),
                "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params, lr):
        t = state["t"] + 1
        bc1 = 1 - b1 ** t.astype(jnp.float32)
        bc2 = 1 - b2 ** t.astype(jnp.float32)
        m = _tmap(lambda m, g: b1 * m + (1 - b1) * g.astype(m.dtype),
                  state["m"], grads)
        v = _tmap(lambda v, g: b2 * v + (1 - b2) * jnp.square(g).astype(v.dtype),
                  state["v"], grads)

        def step(p, m_, v_):
            mh = m_.astype(jnp.float32) / bc1
            vh = v_.astype(jnp.float32) / bc2
            upd = mh / (jnp.sqrt(vh) + eps) + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype)

        return _tmap(step, params, m, v), {"m": m, "v": v, "t": t}

    return Optimizer(init, update, "adamw")


def rmsprop(decay: float = 0.9, momentum: float = 0.9, eps: float = 1e-8,
            weight_decay: float = 0.0) -> Optimizer:
    def init(params):
        return {"v": _tmap(jnp.zeros_like, params),
                "m": _tmap(jnp.zeros_like, params)}

    def update(grads, state, params, lr):
        if weight_decay:
            grads = _tmap(lambda g, p: g + weight_decay * p, grads, params)
        v = _tmap(lambda v, g: decay * v + (1 - decay) * jnp.square(g),
                  state["v"], grads)
        m = _tmap(lambda m, g, v_: momentum * m + g / (jnp.sqrt(v_) + eps),
                  state["m"], grads, v)
        return _tmap(lambda p, m_: p - lr * m_, params, m), {"v": v, "m": m}

    return Optimizer(init, update, "rmsprop")


def adafactor(eps: float = 1e-30, clip_threshold: float = 1.0,
              weight_decay: float = 0.0) -> Optimizer:
    """Factored second moment: O(rows+cols) state for matrices (1T-param HBM
    budget); vectors fall back to a full second moment."""

    def init(params):
        def one(p):
            if p.ndim >= 2:
                return {"r": jnp.zeros(p.shape[:-1], jnp.float32),
                        "c": jnp.zeros(p.shape[:-2] + p.shape[-1:], jnp.float32)}
            return {"v": jnp.zeros(p.shape, jnp.float32)}
        return {"s": _tmap(one, params), "t": jnp.zeros((), jnp.int32)}

    def update(grads, state, params, lr):
        t = state["t"] + 1
        beta = 1.0 - t.astype(jnp.float32) ** -0.8

        def one(p, g, s):
            gf = g.astype(jnp.float32)
            g2 = jnp.square(gf) + eps
            if p.ndim >= 2:
                r = beta * s["r"] + (1 - beta) * jnp.mean(g2, axis=-1)
                c = beta * s["c"] + (1 - beta) * jnp.mean(g2, axis=-2)
                denom = jnp.sqrt(
                    r[..., :, None] * c[..., None, :]
                    / jnp.maximum(jnp.mean(r, axis=-1, keepdims=True)[..., None],
                                  eps))
                upd = gf / jnp.maximum(denom, eps)
                new_s = {"r": r, "c": c}
            else:
                v = beta * s["v"] + (1 - beta) * g2
                upd = gf / (jnp.sqrt(v) + eps)
                new_s = {"v": v}
            rms = jnp.sqrt(jnp.mean(jnp.square(upd)) + eps)
            upd = upd / jnp.maximum(1.0, rms / clip_threshold)
            if weight_decay:
                upd = upd + weight_decay * p.astype(jnp.float32)
            return (p.astype(jnp.float32) - lr * upd).astype(p.dtype), new_s

        flat_p, td = jax.tree.flatten(params)
        flat_g = jax.tree.flatten(grads)[0]
        flat_s = jax.tree.flatten(
            state["s"], is_leaf=lambda x: isinstance(x, dict) and (
                "r" in x or "v" in x))[0]
        out = [one(p, g, s) for p, g, s in zip(flat_p, flat_g, flat_s)]
        new_params = jax.tree.unflatten(td, [o[0] for o in out])
        new_s = jax.tree.unflatten(
            jax.tree.structure(state["s"], is_leaf=lambda x: isinstance(x, dict)
                               and ("r" in x or "v" in x)),
            [o[1] for o in out])
        return new_params, {"s": new_s, "t": t}

    return Optimizer(init, update, "adafactor")


def make_optimizer(name: str, **hp) -> Optimizer:
    """The optimizer ``name``; its update runs under the named scope
    ``optimizer``, so a trace attributes the update's device time."""
    opt = {"sgd": sgd, "adamw": adamw, "rmsprop": rmsprop,
           "adafactor": adafactor}[name](**hp)

    def update(grads, state, params, lr):
        with jax.named_scope("optimizer"):
            return opt.update(grads, state, params, lr)

    return dataclasses.replace(opt, update=update)
