"""KAKURENBO epoch orchestration (paper Fig. 1).

Per epoch e:
  B.1/B.2  rank samples by lagging loss, hide lowest-loss fraction <= F_e
  B.3      move back candidates not (correct & PC >= tau) last epoch
  C        train on the visible set with uniform w/o-replacement sampling;
           LR multiplied by 1/(1-F*_e) (Eq. 8); per-sample (loss, PA, PC)
           recorded from the training forward pass ("lagging loss")
  D        forward-only refresh of the hidden set at epoch end

This module is model-agnostic: the trainer supplies
  train_step(batch_indices)  -> (per-sample loss, pa, pc) and
  eval_forward(batch_indices) -> (loss, pa, pc)
while this class owns the SampleState and the epoch plan.

Device residency: the whole epoch plan — selection, move-back and the
visible-index permutation — is ONE jitted step (``_plan_step``) driven by a
checkpointable jax PRNG key, and per-batch observation is fused into the
trainer's jitted train step (``KakurenboStrategy.fused_observe``).
``SampleState`` therefore crosses the host boundary exactly once per epoch:
the ``jax.device_get`` that materialises the EpochPlan's index lists.

Mesh sharding: given a ``ParallelCtx`` with a ``("data",)`` mesh
(``TrainConfig.mesh_shape``), ``SampleState`` is row-sharded over the data
axis and the plan step becomes a *cross-shard* plan: the histogram selection
methods run under shard_map — each shard histograms its own rows, the
histograms are psum'd (O(bins) communication) and every shard derives the
same global threshold — while ``"sort"`` falls back to a global GSPMD
argsort (the O(N) gather the paper's own method costs).  The epoch shuffle
uses the replicated device PRNG key, so the permutation — and with it the
hide/move-back masks and the batch order — is bit-identical across mesh
sizes (enforced by ``tests/test_mesh_trainer.py``).
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Iterator

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro import tracing
from repro.core import planops
from repro.core import selection as sel
from repro.core.schedule import FractionSchedule, kakurenbo_lr
from repro.core.state import SampleState, init_sample_state, scatter_observations, with_hidden
from repro.core.strategy import EpochPlan, SampleStrategy, register_strategy
from repro.dist.sharding import ParallelCtx


@dataclasses.dataclass
class KakurenboConfig:
    max_fraction: float = 0.3
    fraction_alphas: tuple[float, ...] = (1.0, 0.8, 0.6, 0.4)
    fraction_milestones: tuple[int, ...] = (0, 30, 60, 80)
    tau: float = 0.7
    # "sort" (paper) | "histogram" (optimized) | "histogram_pallas" (kernel)
    selection: str = "sort"
    drop_top_fraction: float = 0.0  # DropTop (App. D)
    adjust_lr: bool = True          # LR component (Eq. 8)
    moveback: bool = True           # MB component
    reduce_fraction: bool = True    # RF component
    # Component toggles above express Table 6's v1000..v1111 ablations.


@functools.partial(
    tracing.jit, name=tracing.PLAN_STEP,
    static_argnames=("method", "tau", "drop_top", "moveback", "adjust_lr",
                     "mesh"))
def _plan_step(state: SampleState, key: jax.Array, f_max: jax.Array, *,
               method: str, tau: float, drop_top: float, moveback: bool,
               adjust_lr: bool, mesh=None):
    """The entire epoch plan as one device-resident step.

    Selection + move-back + the visible/hidden split + the epoch shuffle all
    happen on device; returns (hidden mask, moved-back mask, permuted index
    order with the visible set first, hidden count, F*, Eq. 8 LR factor).

    With ``mesh`` (a ``("data",)`` mesh; ``state`` row-sharded over it) this
    is a *cross-shard* plan: the histogram methods run their selection under
    shard_map — per-shard histograms psum'd into a globally consistent
    threshold, O(bins) communication — while ``"sort"`` runs as a global
    GSPMD argsort (O(N) gather, the paper method's own cost).  The shuffle
    key is replicated, so masks and batch order are identical for every mesh
    size, ``(1,)`` included.
    """
    if mesh is not None and method in ("histogram", "histogram_pallas"):
        def local_select(st, fm):
            return sel.select_hidden_histogram(
                st, fm, tau=tau, axis_names=("data",),
                drop_top_fraction=drop_top, moveback=moveback,
                use_kernel=(method == "histogram_pallas"))

        hidden = jax.shard_map(
            local_select, mesh=mesh, in_specs=(P("data"), P()),
            out_specs=P("data"), check_vma=False)(state, f_max)
    else:
        hidden = sel.select_hidden(state, f_max, method=method, tau=tau,
                                   drop_top_fraction=drop_top,
                                   moveback=moveback)
    # Move-back set (Sec. 3.1): hidden last epoch, visible again this epoch.
    moved_back = state.hidden & ~hidden
    n = state.num_samples
    perm = jax.random.permutation(key, n)
    # Stable-sort the random permutation by hiddenness: visible indices come
    # first in uniformly-shuffled order (the epoch's batch order), hidden
    # indices follow — one fixed-shape array instead of two ragged ones.
    order = perm[jnp.argsort(hidden[perm], stable=True)]
    num_hidden = jnp.sum(hidden).astype(jnp.int32)
    f_star = num_hidden.astype(jnp.float32) / n
    if adjust_lr:
        lr_scale = kakurenbo_lr(jnp.float32(1.0), f_star)
    else:
        lr_scale = jnp.float32(1.0)
    return hidden, moved_back, order, num_hidden, f_star, lr_scale


class KakurenboSampler:
    """Owns SampleState + epoch planning. Host-side glue; math is jitted."""

    def __init__(self, num_samples: int, config: KakurenboConfig | None = None,
                 seed: int = 0, ctx: ParallelCtx | None = None):
        self.config = config or KakurenboConfig()
        self.ctx = ctx or ParallelCtx()
        self.ctx.check_rows(num_samples)
        # Row-sharded over the data axes under a mesh; plain device arrays
        # otherwise (shard_rows is the identity with no mesh).
        self.state: SampleState = self.ctx.shard_rows(
            init_sample_state(num_samples))
        # The unified planops seeding convention (one key per strategy name).
        self._key = self.ctx.replicate(planops.strategy_key(seed, "kakurenbo"))
        # Host round trips involving SampleState: host-dispatched observe
        # scatters + per-epoch plan materialisations. The fused trainer path
        # keeps this at 1/epoch; the legacy path pays 1/batch on top.
        self.host_round_trips = 0
        c = self.config
        self._fraction_schedule = FractionSchedule(
            max_fraction=c.max_fraction,
            alphas=c.fraction_alphas if c.reduce_fraction else (1.0,) * len(c.fraction_alphas),
            milestones=c.fraction_milestones,
        )
        self._observe = tracing.jit(scatter_observations,
                                    tracing.OBSERVE_SCATTER)

    # -- epoch boundary ------------------------------------------------------

    def begin_epoch(self, epoch: int) -> EpochPlan:
        c = self.config
        f_max = float(self._fraction_schedule(epoch))
        with tracing.span("plan.dispatch"):
            self._key, sub = jax.random.split(self._key)
            hidden, moved_back, order, num_hidden, f_star, lr_scale = (
                _plan_step(self.state, sub, jnp.float32(f_max),
                           method=c.selection, tau=c.tau,
                           drop_top=c.drop_top_fraction,
                           moveback=c.moveback, adjust_lr=c.adjust_lr,
                           mesh=self.ctx.mesh))
            self.state = with_hidden(self.state, hidden)
        # The single host sync of the epoch: materialise the plan (one
        # device_get for the order, the move-back mask and the scalars).
        with tracing.span("plan.fetch"):
            order_np, mb_np, nh, f_star, lr_scale = jax.device_get(
                (order, moved_back, num_hidden, f_star, lr_scale))
        self.host_round_trips += 1
        n = self.state.num_samples
        nh = int(nh)
        return EpochPlan(
            epoch=epoch,
            visible_indices=order_np[: n - nh],
            hidden_indices=np.sort(order_np[n - nh:]),
            max_fraction=f_max,
            hidden_fraction=float(f_star),
            lr_scale=float(lr_scale),
            needs_refresh=nh > 0,
            host_syncs=1,
            moveback_indices=np.flatnonzero(mb_np),
        )

    # -- per-batch bookkeeping ----------------------------------------------

    def observe(self, indices: np.ndarray | jax.Array, loss: jax.Array,
                pa: jax.Array, pc: jax.Array, epoch: int) -> None:
        """Record lagging loss/PA/PC from a training or refresh batch.

        Host-dispatched path; the fused trainer performs this scatter inside
        its jitted train step instead (see ``KakurenboStrategy.fused_observe``).
        """
        self.host_round_trips += 1
        self.state = self._observe(self.state, jnp.asarray(indices), loss, pa,
                                   pc, epoch)

    # -- epoch end: refresh hidden list (step D) ------------------------------

    def refresh_hidden(
        self,
        plan: EpochPlan,
        eval_forward: Callable[[np.ndarray], tuple[jax.Array, jax.Array, jax.Array]],
        batch_size: int,
    ) -> int:
        """Forward-only pass over the hidden list (paper step D.1).

        Returns the number of refreshed samples — padding excluded, so the
        count is exactly the useful forward-only extra work.
        """
        hidden = plan.hidden_indices
        for start in range(0, len(hidden), batch_size):
            idx = hidden[start : start + batch_size]
            # range() guarantees idx is non-empty; the trailing batch is
            # padded (repeating its last index) to keep a single jit
            # signature, and the padded tail is sliced off before observe.
            if len(idx) < batch_size:
                pad = np.full(batch_size - len(idx), idx[-1])
                loss, pa, pc = eval_forward(np.concatenate([idx, pad]))
                loss, pa, pc = loss[: len(idx)], pa[: len(idx)], pc[: len(idx)]
                tracing.count("refresh_padded_rows", len(pad))
            else:
                loss, pa, pc = eval_forward(idx)
            with tracing.span("refresh.observe"):
                self.observe(idx, loss, pa, pc, plan.epoch)
        tracing.count("refresh_rows", len(hidden))
        return int(len(hidden))

    def batches(self, plan: EpochPlan, batch_size: int) -> Iterator[np.ndarray]:
        """Uniform w/o-replacement batches over the visible set (step C).

        Drops the trailing partial batch, like the paper's DDP loaders.
        """
        v = plan.visible_indices
        for start in range(0, len(v) - batch_size + 1, batch_size):
            yield v[start : start + batch_size]

    # -- checkpointable device RNG -------------------------------------------

    def key_data(self) -> jax.Array:
        """Serializable uint32 view of the epoch-shuffle PRNG key."""
        return planops.key_data(self._key)

    def load_key_data(self, data) -> None:
        self._key = self.ctx.replicate(planops.load_key(data))


@register_strategy("kakurenbo")
class KakurenboStrategy(SampleStrategy):
    """The paper's method behind the unified strategy protocol."""

    config_cls, config_field = KakurenboConfig, "kakurenbo"
    fused_observe = staticmethod(scatter_observations)

    def __init__(self, num_samples: int, config: KakurenboConfig | None = None,
                 seed: int = 0, ctx: ParallelCtx | None = None):
        super().__init__(num_samples, config, seed)
        self._inner = KakurenboSampler(num_samples, config, seed, ctx=ctx)

    @property
    def state(self) -> SampleState:
        return self._inner.state

    @state.setter
    def state(self, value: SampleState) -> None:
        self._inner.state = value

    @property
    def host_round_trips(self) -> int:
        return self._inner.host_round_trips

    def get_device_state(self) -> SampleState:
        return self._inner.state

    def set_device_state(self, state: SampleState) -> None:
        self._inner.state = state

    def plan(self, epoch: int) -> EpochPlan:
        return self._inner.begin_epoch(epoch)

    def observe(self, indices, loss, pa, pc, epoch: int) -> None:
        self._inner.observe(indices, loss, pa, pc, epoch)

    def on_epoch_end(self, plan: EpochPlan, eval_forward, batch_size: int) -> int:
        return self._inner.refresh_hidden(plan, eval_forward, batch_size)

    def state_dict(self) -> dict:
        return {"arrays": {"state": self._inner.state,
                           "rng_key": self._inner.key_data()},
                "host": {"rng_impl": "threefry2x32"}}

    def load_state_dict(self, state: dict) -> None:
        self._inner.state = self._inner.ctx.shard_rows(
            jax.tree.map(jnp.asarray, state["arrays"]["state"]))
        self._inner.load_key_data(state["arrays"]["rng_key"])
