"""Per-sample bookkeeping state for KAKURENBO and related methods.

The paper (Sec. 3.4) keeps, for every sample n in the dataset:
  - a (possibly lagging) loss  l_n,
  - prediction accuracy  PA_n  (was the sample predicted correctly?),
  - prediction confidence PC_n (max softmax probability),
all refreshed from the *training* forward pass for visible samples and from a
forward-only refresh pass for hidden samples.  Here that state is a pytree of
``(N,)`` device arrays; under the mesh-sharded trainer
(``TrainConfig.mesh_shape``) it lives row-sharded over the ``("data",)``
mesh axis for the whole run — the scatter below and the selection plan
(``core/selection.py``) both operate on the sharded layout, and the state
only crosses the host boundary at the per-epoch ``EpochPlan``
materialisation (see ``docs/architecture.md``).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class SampleState:
    """State for the N samples of a dataset.

    Attributes:
      loss:    (N,) f32 — lagging loss from the last epoch the sample was seen.
      pa:      (N,) bool — correctly predicted last time it was seen.
      pc:      (N,) f32 — prediction confidence (max softmax prob).
      hidden:  (N,) bool — hidden during the *current* epoch.
      seen:    (N,) i32 — epoch index at which loss/pa/pc were last refreshed
               (-1 = never; such samples are always treated as important).
      forget_events: (N,) i32 — count of correct->incorrect transitions
               (used by the FORGET baseline; free to maintain).
      prev_correct: (N,) bool — correctness at the previous observation
               (for forgetting-event detection).
    """

    loss: jax.Array
    pa: jax.Array
    pc: jax.Array
    hidden: jax.Array
    seen: jax.Array
    forget_events: jax.Array
    prev_correct: jax.Array

    @property
    def num_samples(self) -> int:
        return self.loss.shape[0]


def init_sample_state(num_samples: int, init_loss: float = 1e9) -> SampleState:
    """Fresh state: everything visible, infinitely-important losses.

    ``init_loss`` is large so that never-seen samples sort as maximally
    important and are never hidden (the paper hides *low*-loss samples).
    """
    n = num_samples
    return SampleState(
        loss=jnp.full((n,), init_loss, jnp.float32),
        pa=jnp.zeros((n,), bool),
        pc=jnp.zeros((n,), jnp.float32),
        hidden=jnp.zeros((n,), bool),
        seen=jnp.full((n,), -1, jnp.int32),
        forget_events=jnp.zeros((n,), jnp.int32),
        prev_correct=jnp.zeros((n,), bool),
    )


def scatter_observations(
    state: SampleState,
    indices: jax.Array,
    loss: jax.Array,
    pa: jax.Array,
    pc: jax.Array,
    epoch: jax.Array | int,
    valid: jax.Array | None = None,
) -> SampleState:
    """Record (loss, PA, PC) for the samples at ``indices``.

    This is the "lagging loss" update (paper Sec. 3.4): called once per
    training batch with metrics computed *during* the forward pass, and once
    per hidden-refresh batch at epoch end.  Duplicate indices are allowed
    (last write wins under XLA scatter semantics, matching the paper where a
    sample is observed at most once per epoch anyway).

    ``valid`` is the numeric guard's score-quarantine mask
    (``train/guard.py``): entries where it is False scatter the sample's
    *existing* values back — loss/PA/PC, the ``seen`` epoch, the
    forgetting-event state all hold — so a non-finite observation is a
    bit-exact no-op for that sample and the next epoch plan stays finite.
    ``None`` (the default) is the unguarded path, traced exactly as before.
    (With duplicate indices an invalid later duplicate restores the
    *pre-batch* value; irrelevant in practice, since a sample is observed
    at most once per epoch.)

    Sharding: the update is scatter-only (no cross-sample reductions) plus
    O(B) gathers, so it is GSPMD-safe — with ``state`` row-sharded over the
    data axes and ``indices`` arbitrary global ids, the partitioner lowers
    each scatter to an O(B) gather of the updates plus shard-local writes,
    which is exactly the schedule a hand-written shard_map version would
    use.  The mesh trainer relies on this to keep the fused observe inside
    its jitted step without a second, shard-offset state contract.
    """
    with jax.named_scope("observe"):
        # A forgetting event (FORGET baseline) is a correct -> incorrect flip.
        was_correct = state.prev_correct[indices]
        epoch = jnp.asarray(epoch, jnp.int32)
        if valid is None:
            forget_inc = (was_correct & ~pa).astype(jnp.int32)
            seen_val = jnp.broadcast_to(epoch, indices.shape)
        else:
            loss = jnp.where(valid, loss, state.loss[indices])
            pa = jnp.where(valid, pa, state.pa[indices])
            pc = jnp.where(valid, pc, state.pc[indices])
            forget_inc = jnp.where(valid, was_correct & ~pa,
                                   False).astype(jnp.int32)
            seen_val = jnp.where(valid, epoch, state.seen[indices])
            pa_prev = jnp.where(valid, pa, state.prev_correct[indices])
        return SampleState(
            loss=state.loss.at[indices].set(loss.astype(jnp.float32)),
            pa=state.pa.at[indices].set(pa),
            pc=state.pc.at[indices].set(pc.astype(jnp.float32)),
            hidden=state.hidden,
            seen=state.seen.at[indices].set(seen_val),
            forget_events=state.forget_events.at[indices].add(forget_inc),
            prev_correct=state.prev_correct.at[indices].set(
                pa if valid is None else pa_prev),
        )


@jax.tree_util.register_dataclass
@dataclasses.dataclass
class TrainCarry:
    """The full device-resident train state threaded through a scanned epoch.

    This is the ``lax.scan`` carry of the scanned epoch engine
    (``train/engines.py``): params, optimizer state, the error-feedback
    residual (None without compression) and the strategy's device state
    (``SampleState``, or a fused-select state pytree; None for stateless
    strategies) ride through K train steps per dispatch, and per-step
    (loss, backward-count) scalars come back as the scan's stacked outputs
    — so the whole block costs one dispatch and the losses one
    ``device_get`` per epoch.  The host-loop engine threads the same objects
    through its per-batch jitted step; sharing the structure is what keeps
    the two engines' donation/restart contracts identical (a crash between
    scan blocks leaves a fully live carry to hand back for
    checkpoint-on-fault).  ``gstate`` is the numeric guard's counter pytree
    (``train/guard.py::GuardState``; None with ``guard_policy="off"``, so
    the unguarded carry is structurally unchanged).
    """

    params: Any
    opt_state: Any
    ef: Any
    sstate: Any
    gstate: Any = None


def with_hidden(state: SampleState, hidden: jax.Array) -> SampleState:
    return dataclasses.replace(state, hidden=hidden)


def state_summary(state: SampleState) -> dict[str, Any]:
    """Host-side summary used for logging / checksum in checkpoints."""
    return {
        "num_samples": int(state.num_samples),
        "num_hidden": int(jnp.sum(state.hidden)),
        "mean_loss_seen": float(
            jnp.mean(jnp.where(state.seen >= 0, state.loss, 0.0))
        ),
        "num_seen": int(jnp.sum(state.seen >= 0)),
    }
