"""What decides ``correct``: the timed path against the plain reference.

Every number here compares something the program produced on the timed
path, at the timed sizes, with ``bench/reference.py`` and the
configuration's own forward pass (``bench/configs/<name>.py``), which
import nothing of the program and start from the weights the benchmark
made from the seed.

Model step (every cell).  ``StepTap`` wraps the trainer's compiled scan
block during set-up's epoch 0 — the same object, call and feed the window
then uses — and reads its first three steps: each step's loss, the first
gradient as AdamW receives it (its first moment after one step divided by
``1 - b1``), and the parameters' change after three steps (what step 4
receives).  The reference follows the same three steps on the same rows.
Norms are compared per leaf, by the gap between the program's norm and the
reference's, over the larger of the reference's norm of that leaf and of
the median leaf (``grad_norm_rel``, ``update_norm_rel``: the worst leaf).
Leaves whose reference gradient is under a thousandth of the median leaf's
move by rounding alone under Adam and are left out of the change.  The
first gradient is also compared by the norm of its difference from the
reference's, per leaf on the same scale (``grad_diff_rel``: the worst
leaf; ``grad_diff_med``: the median leaf): on this corpus every row pushes
the same few logits, so half a batch has nearly the gradient norms of the
whole one, and only the difference shows it.  Of the losses only the first
step's is read (``loss1_rel``): those of steps 2 and 3 follow Adam's
first, sign-like updates, which turn rounding in near-zero gradient
entries into whole steps (on mamba2-130m they spread from 0.1% to 8%
between seeds).

Which numbers a configuration compares, and their limits, is in
``bench/configs/<name>.limits.json``; PERF.md gives the readings each
limit was set from.  A number without a limit is printed as a reading.

KAKURENBO cells add four layer checks:

- ``plan_diff`` (exact): for every window epoch, the hidden and moved-back
  sets against a plain numpy plan computed from a copy of the sample state
  the plan read (stable sort by loss, the lowest floor(F N) are
  candidates, a candidate stays hidden only if correct with PC >= tau at
  its last observation);
- ``seen_diff`` (exact): for every window epoch, the rows of its visible
  and hidden lists whose last observation in the state after that epoch is
  not that epoch: a visible row the fused observe left out or wrote
  elsewhere, or a hidden row step D did not refresh;
- ``observe_rel`` and ``refresh_rel``: once the window has closed, the
  window's own train block runs one more step on a batch of visible rows,
  and step D once more over the hidden list of the last window epoch that
  hid samples, both at the reference's parameters after its three steps
  (weights the benchmark made, never the program's) and marked with an
  epoch the window did not reach.  The (loss, PC) that the fused observe and step D wrote into the
  sample state for those rows (all of the batch; a sample of the hidden
  list) are compared with the reference forward pass at the same
  parameters; a row whose last observation is not that epoch reads
  infinite.  ``refresh_mean_rel`` is ``refresh_rel`` with the rows' gaps
  averaged instead of their widest taken: the widest of a few rows swings
  with the seed's weights far more than an answer altered moves it
  (PERF.md), so a configuration may compare the mean.

The no-hiding baseline's plan is checked to cover every sample once.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference


class StepTap:
    """Reads the first steps of the trainer's compiled scan block.

    Installed on ``trainer.engine`` before set-up's first epoch and removed
    after it, so the window runs the block unwrapped.  The engine's block
    takes one step per call (``scan_steps=1``).
    """

    def __init__(self, engine, initial_weights, b1: float, steps: int = 3):
        if engine._block is None:
            engine._build_block()
        self.engine, self.inner = engine, engine._block
        self.initial_weights, self.b1, self.steps = initial_weights, b1, steps
        self.losses, self.rows, self.lrs = [], [], []
        self.grad_norms = self.change_norms = self.grad = None
        engine._block = self

    def __call__(self, carry, xs, epoch, lr):
        if xs["idx"].shape[0] != 1:
            raise ValueError("the step tap needs one step per scan block")
        carry, out = self.inner(carry, xs, epoch, lr)
        k = len(self.losses)
        if k < self.steps:
            self.losses.append(out[0][0])
            self.rows.append(xs["idx"][0])
            self.lrs.append(float(lr))
            if k == 0:
                g = first_gradient(carry.opt_state["m"], self.b1)
                self.grad_norms = reference.leaf_norms(g)
                self.grad = jax.device_get(g)   # on the host, off the chip
            if k == self.steps - 1:
                self.change_norms = reference.change_norms(
                    carry.params, self.initial_weights())
        return carry, out

    def remove(self):
        self.engine._block = self.inner

    def readings(self) -> dict:
        got = jax.device_get({"losses": self.losses, "rows": self.rows,
                              "grad": self.grad_norms,
                              "change": self.change_norms})
        return {"losses": np.asarray(got["losses"], np.float64),
                "rows": np.stack(got["rows"]), "lrs": list(self.lrs),
                "grad": np.asarray(got["grad"], np.float64),
                "grad_tree": self.grad,
                "change": np.asarray(got["change"], np.float64)}


@functools.partial(jax.jit, static_argnums=1)
def first_gradient(m, b1: float):
    """The first step's gradient from AdamW's first moment after one step
    (m_1 = (1 - b1) g_1)."""
    return jax.tree.map(lambda x: x / (1 - b1), m)


# ---------------------------------------------------------------------------
# The reference's three steps
# ---------------------------------------------------------------------------


def reference_steps(forward, weights_fn, batches, lrs, hp, *, rows: int,
                    dtype=jnp.float32) -> dict:
    """Three AdamW steps of the plain reference from ``weights_fn()``.

    ``batches`` are (tokens, labels) of the program's first steps; ``dtype``
    is the compute dtype of the forward and backward passes (float32 at
    ``highest`` for the reference, bfloat16 for the control); the update
    itself stays in float32.  Returns per-step losses, the first step's
    the first gradient (on the host) and its leaf norms, the change's leaf
    norms, and the parameters after the last step (on the device).
    """
    def step(params, m, v, t, lr, tokens, labels):
        w = jax.tree.map(lambda p: p.astype(dtype), params)
        loss, grads, _ = reference.loss_and_grad(
            forward, w, tokens, labels, rows)
        params, m, v = reference.adamw(params, grads, m, v, t, lr, hp)
        return params, m, v, loss, grads

    with jax.default_matmul_precision("highest"):
        step = jax.jit(step, donate_argnums=(0, 1, 2))
        params = weights_fn()
        m = jax.tree.map(jnp.zeros_like, params)
        v = jax.tree.map(jnp.zeros_like, params)
        losses, grad, first = [], None, None
        for k, ((tokens, labels), lr) in enumerate(zip(batches, lrs)):
            params, m, v, loss, g = step(
                params, m, v, jnp.float32(k + 1), jnp.float32(lr),
                jnp.asarray(tokens), jnp.asarray(labels))
            losses.append(loss)
            if k == 0:
                grad = reference.leaf_norms(g)
                first = jax.device_get(g)
            del g
        del m, v
        change = reference.change_norms(params, weights_fn())
    got = jax.device_get({"losses": losses, "grad": grad, "change": change})
    return {"losses": np.asarray(got["losses"], np.float64),
            "grad": np.asarray(got["grad"], np.float64),
            "grad_tree": first,
            "change": np.asarray(got["change"], np.float64),
            "params": params}


def worst_leaf_gap(prog: np.ndarray, ref: np.ndarray,
                   keep: np.ndarray | None = None) -> float:
    """max over leaves of |prog - ref| / max(ref, median(ref))."""
    if keep is None:
        keep = np.ones_like(ref, bool)
    scale = np.maximum(ref, np.median(ref))
    return float(np.max(np.abs(prog - ref)[keep] / scale[keep]))


STEP_NUMBERS = ("loss1_rel", "grad_norm_rel", "grad_diff_rel",
                "grad_diff_med", "update_norm_rel")


def step_numbers(tap: dict, ref: dict, names=STEP_NUMBERS) -> dict:
    """The model step's numbers ``names`` for readings ``tap`` against
    ``ref``.

    ``loss1_rel``: the first step's loss; ``grad_norm_rel``: the first
    gradient's norms; ``grad_diff_rel`` and ``grad_diff_med``: the worst and
    the median leaf of the norm of the first gradient's difference from the
    reference's, over the reference's norm of that leaf or the median leaf;
    ``update_norm_rel``: the norms of the change after the three steps, over
    the leaves whose reference gradient reaches a thousandth of the median
    leaf's.
    """
    out = {}
    if "loss1_rel" in names:
        out["loss1_rel"] = float(abs(tap["losses"][0] - ref["losses"][0])
                                 / abs(ref["losses"][0]))
    if "grad_norm_rel" in names:
        out["grad_norm_rel"] = worst_leaf_gap(tap["grad"], ref["grad"])
    if "update_norm_rel" in names:
        keep = ref["grad"] >= 1e-3 * np.median(ref["grad"])
        out["update_norm_rel"] = worst_leaf_gap(tap["change"], ref["change"],
                                                keep)
    if {"grad_diff_rel", "grad_diff_med"} & set(names):
        diffs = grad_diffs(tap, ref)
        for name, f in (("grad_diff_rel", np.max), ("grad_diff_med",
                                                     np.median)):
            if name in names:
                out[name] = float(f(diffs))
    return out


def grad_diffs(tap: dict, ref: dict) -> np.ndarray:
    """Per leaf, |first gradient - the reference's| / max(reference leaf
    norm, median leaf norm)."""
    diff = np.array([np.linalg.norm((np.asarray(a, np.float64)
                                     - np.asarray(b, np.float64)).ravel())
                     for a, b in zip(jax.tree.leaves(tap["grad_tree"]),
                                     jax.tree.leaves(ref["grad_tree"]))])
    return diff / np.maximum(ref["grad"], np.median(ref["grad"]))


def sample_gap(x, ref, reduce=np.max) -> float:
    """Worst (or, with ``reduce=np.mean``, mean) gap of per-sample values,
    each over the larger of its reference value and the median one (a loss
    near 0 reads no relative noise)."""
    x, ref = np.asarray(x, np.float64), np.abs(np.asarray(ref, np.float64))
    return float(reduce(np.abs(x - ref) / np.maximum(ref, np.median(ref))))


# ---------------------------------------------------------------------------
# Plan and refresh
# ---------------------------------------------------------------------------


def fraction_ceiling(mix: dict, epoch: int) -> np.float32:
    """F_e = max_fraction * alpha_i for the last milestone_i <= epoch."""
    alpha = 0.0
    for a, m in zip(mix["fraction_alphas"], mix["fraction_milestones"]):
        if epoch >= m:
            alpha = a
    return np.float32(mix["max_fraction"]) * np.float32(alpha)


def reference_plan(state: dict, f_max: np.float32, tau: float,
                   moveback: bool) -> tuple[np.ndarray, np.ndarray]:
    """(hidden mask, moved-back mask) from a copy of the sample state."""
    loss = np.asarray(state["loss"], np.float32)
    n = loss.shape[0]
    num_hide = int(np.floor(np.float32(f_max) * np.float32(n)))
    rank = np.empty(n, np.int64)
    rank[np.argsort(loss, kind="stable")] = np.arange(n)
    seen = np.asarray(state["seen"]) >= 0
    eligible = seen
    if moveback:
        eligible = (np.asarray(state["pa"], bool)
                    & (np.asarray(state["pc"], np.float32) >= np.float32(tau))
                    & seen)
    hidden = (rank < num_hide) & eligible
    return hidden, np.asarray(state["hidden"], bool) & ~hidden


def plan_diff(plans, states, mix: dict, n: int) -> int:
    """Samples whose hidden or moved-back membership differs from the
    reference plan, summed over the window's epochs (KAKURENBO)."""
    diff = 0
    for plan, state in zip(plans, states):
        hidden, moved = reference_plan(
            state, fraction_ceiling(mix, plan.epoch), mix["tau"],
            mix["moveback"])
        got_h = np.zeros(n, bool)
        got_h[plan.hidden_indices] = True
        got_m = np.zeros(n, bool)
        got_m[plan.moveback_indices] = True
        vis = np.zeros(n, np.int64)
        np.add.at(vis, plan.visible_indices, 1)
        diff += int(np.sum(got_h != hidden) + np.sum(got_m != moved)
                    + np.sum(vis != (~hidden).astype(np.int64)))
    return diff


def cover_diff(plans, n: int) -> int:
    """Samples not trained exactly once per epoch (no-hiding baseline)."""
    diff = 0
    for plan in plans:
        vis = np.zeros(n, np.int64)
        np.add.at(vis, plan.visible_indices, 1)
        diff += int(np.sum(vis != 1))
    return diff


def reference_signals(forward, weights, tokens, labels, rows: int):
    """Per-sample (loss, PC) of the reference forward, ``rows`` at a time."""
    @jax.jit
    def sig(w, t, l):
        loss, _, pc = reference.per_sample(forward(w, t), l)
        return loss, pc

    out = []
    with jax.default_matmul_precision("highest"):
        for i in range(0, len(tokens), rows):
            out.append(jax.device_get(sig(weights, jnp.asarray(
                tokens[i:i + rows]), jnp.asarray(labels[i:i + rows]))))
    return (np.concatenate([o[0] for o in out]),
            np.concatenate([o[1] for o in out]))


STRATEGY_NUMBERS = ("seen_diff", "observe_rel", "refresh_rel",
                    "refresh_mean_rel")


def seen_diff(plans, states_after) -> int:
    """Rows of each epoch's visible and hidden lists whose last observation
    in the state after that epoch (``states_after``, host dicts) is not that
    epoch, summed over the epochs (KAKURENBO)."""
    diff = 0
    for plan, state in zip(plans, states_after):
        rows = np.union1d(plan.visible_indices, plan.hidden_indices)
        diff += int(np.sum(np.asarray(state["seen"])[rows] != plan.epoch))
    return diff


def signals_gap(state: dict, rows: np.ndarray, epoch: int, loss, pc,
                reduce=np.max) -> float:
    """The (loss, PC) recorded in ``state`` for ``rows`` against the
    reference's ``loss`` and ``pc``, the larger of the two gaps; infinite
    when a row's last observation is not ``epoch``."""
    if np.any(np.asarray(state["seen"])[rows] != epoch):
        return math.inf
    return max(sample_gap(np.asarray(state["loss"])[rows], loss, reduce),
               sample_gap(np.asarray(state["pc"])[rows], pc, reduce))


def refresh_gap(state: dict, hidden: np.ndarray, sample: np.ndarray,
                epoch: int, loss, pc, reduce=np.max) -> float:
    """Step D's (loss, PC) for ``sample`` of the ``hidden`` list against the
    reference's; infinite when the list is empty or a row of it was not
    refreshed at ``epoch``."""
    if not len(hidden) or np.any(np.asarray(state["seen"])[hidden] != epoch):
        return math.inf
    return signals_gap(state, sample, epoch, loss, pc, reduce)


@jax.jit
def copy_state(state):
    """A device copy of the sample state (the plan's input)."""
    return jax.tree.map(jnp.copy, state)


def state_dict_of(state) -> dict:
    """Host copy of the fields the plan reads."""
    got = jax.device_get(state)
    return {k: np.asarray(getattr(got, k))
            for k in ("loss", "pa", "pc", "seen", "hidden")}
