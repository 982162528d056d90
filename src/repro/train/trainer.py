"""Epoch-based trainer over the unified ``SampleStrategy`` protocol.

This is the training loop used by the paper-reproduction experiments and
the end-to-end examples.  It runs in two placement modes behind one config:

- **single-device** (``mesh_shape=None``, the default): the original jitted
  train/eval steps, unchanged and bit-for-bit compatible with every
  existing parity suite;
- **mesh-sharded data-parallel** (``mesh_shape=(D,)``): the train step runs
  under shard_map over a ``("data",)`` mesh (``launch/mesh.py``), with
  params/optimizer state replicated, batches and the strategy's
  ``SampleState`` row-sharded, the fused observe scatter kept sharded via
  GSPMD, and gradients combined with a *chunk-major deterministic fold*
  (see ``_jit_steps_mesh``; ``grad_allreduce="psum"`` swaps in the fast
  O(params) all-reduce) so losses and parameter trajectories are
  bit-identical for every mesh size dividing ``grad_chunks``.
  ``tests/test_mesh_trainer.py`` enforces ``(1,)`` vs ``(8,)`` equality.

Orthogonally, the per-epoch batch loop is dispatched by an *epoch engine*
(``train/engines.py``, selected per strategy capability in
``_make_engine``): the classic host loop (one jitted step per
host-assembled batch), or — for strategies whose per-batch work fits
entirely inside the jitted step — the scanned engine, which gathers batches
from device-resident data and rolls ``scan_steps`` train steps into each
``lax.scan`` dispatch.  The two engines share ``_step_core`` and are
bit-identical (``tests/test_scan_engine.py``).

(The pod-scale pjit step for the large model configs lives in
``repro.launch.train`` and shares the same Model API and ``EpochPlan``
contract.)

The trainer is strategy-agnostic: every selection method — KAKURENBO and
all baselines — arrives through ``repro.core.make_strategy`` and drives the
loop exclusively via the protocol (``plan`` / ``observe`` /
``batch_weights`` / ``fused_observe`` / ``fused_select`` /
``on_epoch_end`` / ``state_dict``).  Adding a strategy never touches this
file (``docs/adding_a_strategy.md``).

The trainer owns: jitted train/eval steps, LR scheduling (incl. Eq. 8 via
``plan.lr_scale``), work accounting (fwd/bwd sample counts — the quantity
the paper's speedup comes from), checkpoint/restart and failure injection.
"""
from __future__ import annotations

import dataclasses
import inspect
import logging
import math
import time
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import tracing
from repro.checkpoint import checkpoint as ckpt
from repro.core import (
    ForgetConfig, ISWRConfig, InfoBatchConfig, KakurenboConfig, LRSchedule,
    SBConfig, GradMatchConfig, SampleStrategy, make_strategy, planops,
)
from repro.data.pipeline import Pipeline, materialize
from repro.dist.compression import compress_grads, init_error_feedback
from repro.dist.sharding import ParallelCtx
from repro.optim.optimizers import Optimizer, make_optimizer
from repro.train import guard
from repro.train.engines import HostLoopEngine, ScanEpochEngine

logger = logging.getLogger("repro.train")


@dataclasses.dataclass
class TrainConfig:
    epochs: int = 10
    batch_size: int = 64
    strategy: str = "baseline"
    optimizer: str = "sgd"
    optimizer_hp: dict = dataclasses.field(
        default_factory=lambda: {"momentum": 0.9})
    lr: LRSchedule = dataclasses.field(
        default_factory=lambda: LRSchedule(base_lr=0.05, kind="cosine",
                                           total_epochs=10, warmup_epochs=1))
    kakurenbo: KakurenboConfig = dataclasses.field(default_factory=KakurenboConfig)
    iswr: ISWRConfig = dataclasses.field(default_factory=ISWRConfig)
    forget: ForgetConfig = dataclasses.field(default_factory=ForgetConfig)
    sb: SBConfig = dataclasses.field(default_factory=SBConfig)
    gradmatch: GradMatchConfig = dataclasses.field(default_factory=GradMatchConfig)
    infobatch: InfoBatchConfig = dataclasses.field(default_factory=InfoBatchConfig)
    grad_compression: bool = False
    checkpoint_dir: str | None = None
    checkpoint_every: int = 0          # epochs; 0 = only on demand
    seed: int = 0
    eval_every: int = 1
    # Fuse the strategy's per-batch observe scatter into the jitted train
    # step (device-resident SampleState, 1 host sync/epoch). False forces
    # the legacy per-batch host observe() path — kept for the differential
    # parity test; both paths are bit-identical.
    fused_observe: bool = True
    # Fused in-step scoring: derive the per-sample (loss, PA, PC) triple
    # from the model's logits in ONE streaming online-softmax pass
    # (``kernels/ops.fused_loss_metrics`` — the Pallas kernel on TPU, its
    # fused jnp twin elsewhere) instead of the model's separate
    # logsumexp/argmax/softmax reductions.  Requires the Trainer's
    # ``logits_fn(params, batch) -> (B, V) logits``; the trainer then builds
    # the ``loss_fn`` contract itself (weighted-mean CE scalar + the
    # metrics triple feeding the fused_observe scatter), so the 1-sync/epoch
    # engine contract and the guard/quarantine paths are untouched.
    fused_scoring: bool = False
    # Mesh-sharded data-parallel mode: e.g. (8,) trains over a ("data",)
    # mesh of 8 devices (host-simulated on CPU via
    # XLA_FLAGS=--xla_force_host_platform_device_count=8). None = the
    # single-device path, byte-for-byte the pre-mesh trainer.
    mesh_shape: tuple[int, ...] | None = None
    # Gradients are reduced as a fold over this many fixed-size batch chunks
    # regardless of mesh size (each device sums its own contiguous chunk
    # range in parallel, the fold order is global-chunk-major), which makes
    # losses/trajectories bit-identical across any mesh size dividing it.
    # Must divide batch_size.
    grad_chunks: int = 8
    # How mesh gradients are combined: "fold" (default) is the chunk-major
    # deterministic fold above — O(grad_chunks x params) all-gather bytes,
    # bit-identical across mesh sizes; "psum" is the fast O(params)
    # all-reduce (one pmean over the data axis) for deployments that prefer
    # speed over cross-mesh-size reproducibility.
    grad_allreduce: str = "fold"
    # Epoch engine: "auto" runs strategies whose per-batch work fits inside
    # the jitted step (SampleStrategy.supports_scan + active fused observe;
    # all 8 registered strategies qualify) through the scanned engine, and
    # everything else (fused_observe=False, host-planned external
    # strategies) through the host loop; "scan"/"host" force one (forcing
    # "scan" on an incapable strategy raises).
    engine: str = "auto"
    # Scanned engine: place the full dataset in device memory once and
    # assemble batches by on-device gather (False forces host assembly, i.e.
    # the host-loop engine under engine="auto").
    device_data: bool = True
    # Scanned engine: train steps rolled into one lax.scan dispatch (the
    # block is unrolled, so compile time grows with this; dispatch count
    # shrinks as 1/scan_steps).
    scan_steps: int = 8
    # Numeric guard (train/guard.py): "off" traces the byte-identical
    # unguarded step; "skip_update" detects non-finite loss/grads inside
    # the jitted step, holds params/opt/EF at their pre-step values, and
    # quarantines the batch's per-sample observations so poisoned losses
    # never enter SampleState or the next epoch's hiding plan.  Counters
    # ride the device carry — host syncs stay at 1/epoch.
    guard_policy: str = "off"
    # With the guard on, abort the run (raise guard.NonFiniteError, which
    # the supervisor classifies as restartable) once this many *consecutive*
    # train steps were non-finite.  0 disables the abort; the check runs at
    # the epoch boundary, the run's only host sync.
    guard_abort_after: int = 0
    # Save checkpoints on a background thread (checkpoint.save_async).  The
    # trainer keeps the pending handle and re-raises any save failure at
    # the next checkpoint boundary — and never GCs older checkpoints until
    # the newer save is confirmed on disk.
    async_checkpoint: bool = False
    # Wire train/fault.py's StragglerMonitor into the epoch loop: per-epoch
    # worker latencies (measured, or injected via Trainer.shard_latency_fn
    # for tests/chaos) feed the monitor, and flagged stragglers shed a
    # fraction of their next epoch's rows to the other workers via
    # fault.rescale_plan/rebalance.  Off by default: the default uniform
    # latencies never flag, so the epoch plan is bit-identical to the
    # unmonitored trainer.
    straggler_mitigation: bool = False
    # World size the straggler monitor models.  0 = the mesh's data-parallel
    # degree (1 off-mesh).  Setting it >1 off-mesh simulates a multi-worker
    # deployment in one process — how the chaos suite drives slow-shard
    # scenarios without a device mesh.
    straggler_workers: int = 0


@dataclasses.dataclass
class EpochStats:
    epoch: int
    train_loss: float
    test_acc: float
    hidden_fraction: float
    fwd_samples: int
    bwd_samples: int
    lr: float
    # The ``epoch`` span's seconds (repro/tracing.py).
    wall_time: float
    # SampleState host round trips in the epoch's plan + batch loop (the
    # quantity the device-resident selection engine minimises; step-D
    # refresh is epoch-boundary work accounted in fwd_samples instead).
    host_syncs: int = 0
    # Which epoch engine dispatched the batch loop ("host" | "scan").
    engine: str = "host"
    # Numeric guard accounting for this epoch (0 with guard_policy="off"):
    # train steps whose update was skipped for non-finite loss/grads, and
    # per-sample observations quarantined from the fused observe scatter.
    nonfinite_steps: int = 0
    quarantined_observations: int = 0
    # The epoch's spans and counters (repro/tracing.py): phase intervals
    # (name, parent, start, end, counts) on time.perf_counter, per-dispatch
    # aggregates (parent, count, total_s, longest_s), and the counters
    # (compiles, compile_s, refresh_rows, refresh_padded_rows).
    spans: dict = dataclasses.field(default_factory=dict)
    counters: dict = dataclasses.field(default_factory=dict)


def _fused_scoring_loss_fn(logits_fn: Callable) -> Callable:
    """Build the trainer's ``loss_fn`` contract from a raw logits function.

    The fused-scoring hot path (``TrainConfig.fused_scoring``): one
    streaming online-softmax pass over the (B, V) logits yields the full
    per-sample (ce, pa, pc) triple — Pallas kernel where it compiles, fused
    one-pass jnp twin elsewhere, analytic vjp either way (see
    ``kernels/ops.fused_loss_metrics``).  The scalar is the (optionally
    weighted) mean CE, matching the convention every hand-written loss_fn
    in the repo uses, so engines/guard/mesh code is agnostic to which
    scoring built the triple.
    """
    from repro.kernels import ops as kernel_ops

    def loss_fn(params, batch):
        logits = logits_fn(params, batch)
        with jax.named_scope("scoring"):
            ce, pa, pc = kernel_ops.fused_loss_metrics(logits,
                                                       batch["labels"])
        w = batch.get("weight")
        scalar = jnp.mean(ce * w) if w is not None else jnp.mean(ce)
        return scalar, (ce, pa, pc)

    return loss_fn


class Trainer:
    """``loss_fn(params, batch) -> (scalar, (loss_vec, pa, pc))``;
    ``batch`` = dataset.get(indices) arrays (+ optional 'weight')."""

    def __init__(self, cfg: TrainConfig,
                 init_params: Callable[[jax.Array], Any],
                 loss_fn: Callable[[Any, dict], tuple] | None,
                 dataset, test_dataset=None,
                 num_classes: int | None = None,
                 feats_fn: Callable | None = None,
                 strategy: SampleStrategy | None = None,
                 logits_fn: Callable[[Any, dict], jax.Array] | None = None):
        self.cfg = cfg
        self.dataset = dataset
        self.test_dataset = test_dataset
        self.logits_fn = logits_fn
        if cfg.fused_scoring:
            if logits_fn is None:
                raise ValueError(
                    "TrainConfig.fused_scoring=True requires the Trainer's "
                    "logits_fn argument (params, batch) -> (B, V) logits — "
                    "the fused scoring pass derives (loss, PA, PC) from raw "
                    "logits, not from a pre-built loss_fn")
            self.loss_fn = _fused_scoring_loss_fn(logits_fn)
        elif loss_fn is None:
            raise ValueError(
                "loss_fn is required unless fused_scoring=True builds it "
                "from logits_fn")
        else:
            self.loss_fn = loss_fn
        self._init_params = init_params
        self.opt: Optimizer = make_optimizer(cfg.optimizer, **cfg.optimizer_hp)
        self.pipeline = Pipeline(dataset.get, cfg.batch_size)
        self.num_samples = dataset.num_samples
        self.ctx = self._build_ctx()
        # impl pinned so the checkpointed key restores on any session
        # (planops.load_key hard-codes the same impl).
        self.rng = jax.random.key(cfg.seed, impl=planops.KEY_IMPL)
        self.params = init_params(self.rng)
        self.opt_state = self.opt.init(self.params)
        self.ef_state = (init_error_feedback(self.params)
                         if cfg.grad_compression else None)
        # Numeric guard counters (train/guard.py): device-resident, threaded
        # through the step like the strategy's state, never checkpointed
        # (they are run diagnostics, not trajectory).  _guard_seen tracks
        # the cumulative totals already reported, so EpochStats get deltas.
        self.guard_state = (guard.init_guard_state()
                            if cfg.guard_policy != "off" else None)
        self._guard_seen = (0, 0)
        self._guard_host_q = 0    # legacy host-observe path's quarantines
        self._pending_save = None  # async-checkpoint handle, see save_checkpoint
        self._place()
        self.epoch = 0
        self.history: list[EpochStats] = []
        # Straggler mitigation (train/fault.py): per-epoch worker latencies
        # feed the monitor; tests/chaos inject skew via shard_latency_fn.
        if cfg.straggler_mitigation:
            from repro.train import fault as _fault
            self._straggler = _fault.StragglerMonitor(
                world_size=cfg.straggler_workers
                or max(self.ctx.dp_size, 1))
        else:
            self._straggler = None
        self.shard_latency_fn: Callable[[int], list[float]] | None = None
        # ctx reaches strategies whose constructor declares it (kakurenbo,
        # random): their SampleState is row-sharded and their plan step runs
        # the cross-shard selection. Other strategies stay host/uncommitted
        # and are resharded on the fly by the jitted mesh step.
        self.strategy = strategy or make_strategy(
            cfg.strategy, self.num_samples, cfg=cfg, seed=cfg.seed,
            num_classes=num_classes, total_epochs=cfg.epochs, ctx=self.ctx)
        self.feats_fn = feats_fn
        self._device_data = None       # lazy cache, see device_data()
        self._jit_steps()

    def _build_ctx(self) -> ParallelCtx:
        c = self.cfg
        if c.engine not in ("auto", "scan", "host"):
            raise ValueError(
                f"TrainConfig.engine={c.engine!r}: must be 'auto', 'scan' or "
                "'host'")
        if c.grad_allreduce not in ("fold", "psum"):
            raise ValueError(
                f"TrainConfig.grad_allreduce={c.grad_allreduce!r}: must be "
                "'fold' (deterministic chunk-major fold) or 'psum' (fast "
                "O(params) all-reduce)")
        if c.guard_policy not in guard.GUARD_POLICIES:
            raise ValueError(
                f"TrainConfig.guard_policy={c.guard_policy!r}: must be one "
                f"of {guard.GUARD_POLICIES}")
        if c.guard_abort_after and c.guard_policy == "off":
            raise ValueError(
                "TrainConfig.guard_abort_after requires "
                "guard_policy='skip_update' — with the guard off no "
                "non-finite steps are ever counted")
        if not c.mesh_shape:
            return ParallelCtx()
        from repro.launch.mesh import make_data_mesh
        num_devices = math.prod(c.mesh_shape)
        if c.batch_size % c.grad_chunks:
            raise ValueError(
                f"batch_size={c.batch_size} must be a multiple of "
                f"grad_chunks={c.grad_chunks}")
        if c.grad_chunks % num_devices:
            raise ValueError(
                f"grad_chunks={c.grad_chunks} must be a multiple of the mesh "
                f"size {num_devices} — it is the fixed reduction layout that "
                "keeps losses bit-identical across mesh sizes")
        return ParallelCtx(mesh=make_data_mesh(num_devices))

    def _place(self) -> None:
        """Replicate the train state over the mesh (no-op off-mesh).

        Called whenever params/opt/ef are (re)built on the host default
        device: init, FORGET's reinit, checkpoint restore.
        """
        self.params = self.ctx.replicate(self.params)
        self.opt_state = self.ctx.replicate(self.opt_state)
        if self.ef_state is not None:
            self.ef_state = self.ctx.replicate(self.ef_state)
        if self.guard_state is not None:
            # Guard counters summarise the *global* step: replicated.
            self.guard_state = self.ctx.replicate(self.guard_state)

    # Legacy alias: tests and notebooks reach sampler state via tr.sampler.
    @property
    def sampler(self) -> SampleStrategy:
        return self.strategy

    # ------------------------------------------------------------------ setup

    def _jit_steps(self):
        # Fused hooks: the strategy's per-batch work runs inside the jitted
        # train step, so its device state never bounces to the host
        # mid-epoch.  ``fused_observe`` is the bookkeeping scatter (gated by
        # TrainConfig.fused_observe for the legacy-parity path);
        # ``fused_select`` is the in-step forward-then-mask selection (SB) —
        # always active, it has no host equivalent.  Either hook requires
        # the strategy to expose device state, which the engines then thread
        # through the epoch.
        has_dev = self.strategy.get_device_state() is not None
        fuse = (self.strategy.fused_observe
                if self.cfg.fused_observe and has_dev else None)
        fsel = self.strategy.fused_select if has_dev else None
        self._fuse, self._fsel = fuse, fsel
        self._thread_state = fuse is not None or fsel is not None
        if self.ctx.mesh is not None:
            self._jit_steps_mesh(fuse, fsel)
            self.engine = self._make_engine()
            return
        opt, loss_fn, compress = self.opt, self.loss_fn, self.cfg.grad_compression
        batch_size = self.cfg.batch_size
        guarded = self.cfg.guard_policy != "off"
        fuse_valid = (guarded and fuse is not None
                      and "valid" in inspect.signature(fuse).parameters)

        # The un-jitted step math, shared by both epoch engines: the host
        # loop jits it per batch, the scanned engine inlines it into its
        # lax.scan blocks — one compilation contract, so the engines are
        # bit-identical by construction.  The step reports its backward
        # sample count as a device scalar (the full batch, or the fused
        # select's surviving count) so work accounting never syncs mid-epoch.
        # ``guarded`` branches are trace-time: with guard_policy="off" the
        # compiled step is byte-identical to the unguarded trainer (gstate
        # is the empty None pytree then).
        def train_step(params, opt_state, ef, sstate, gstate, batch, indices,
                       epoch, lr):
            if fsel is not None:
                # Forward-only loss at the current params drives the in-step
                # selection; the chosen weights mask the backward pass.
                _, (lv0, _, _) = loss_fn(params, batch)
                if guarded:
                    # A non-finite selection loss would poison the select
                    # state's history: hold the state and fall back to
                    # training the full batch (where/select never propagate
                    # the discarded branch).
                    ok0 = jnp.all(jnp.isfinite(lv0))
                    w_new, s_new = fsel(sstate, lv0)
                    sstate = guard.select(ok0, s_new, sstate)
                    w_sel = jnp.where(ok0, w_new, jnp.ones_like(w_new))
                else:
                    w_sel, sstate = fsel(sstate, lv0)
                batch = dict(batch)
                batch["weight"] = (batch["weight"] * w_sel
                                   if "weight" in batch else w_sel)
                bwd = jnp.count_nonzero(w_sel).astype(jnp.int32)
            else:
                bwd = jnp.int32(batch_size)
            (scalar, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            if guarded:
                ok = guard.all_finite(scalar, grads)
                if compress:
                    # Zero *before* compression so a poisoned gradient never
                    # enters the error-feedback residual; the select below
                    # then restores the residual (and params/opt) bit-exactly.
                    # Without compression nothing stateful sees the raw
                    # grads before the select, so the O(params) zeroing pass
                    # is skipped (the select alone discards the bad update —
                    # ``where`` never propagates the dropped branch's NaNs).
                    grads = guard.zero_if(~ok, grads)
                prev = (params, opt_state, ef)
            if compress:
                grads, ef = compress_grads(grads, ef)
            params, opt_state = opt.update(grads, opt_state, params, lr)
            if guarded:
                params, opt_state, ef = guard.select(
                    ok, (params, opt_state, ef), prev)
            if fuse is not None:
                lv, pa, pc = metrics
                if fuse_valid:
                    # Score quarantine: per-sample observations with
                    # non-finite loss/confidence scatter their previous
                    # values back (core/state.py), keeping the next epoch's
                    # hiding plan finite.
                    valid = guard.observation_valid(lv, pc)
                    sstate = fuse(sstate, indices, lv, pa, pc, epoch,
                                  valid=valid)
                    quarantined = jnp.sum(~valid).astype(jnp.int32)
                elif guarded:
                    # External fused observe without a ``valid`` parameter:
                    # degrade to all-or-nothing — any bad observation skips
                    # the whole batch's scatter.
                    valid = guard.observation_valid(lv, pc)
                    s_new = fuse(sstate, indices, lv, pa, pc, epoch)
                    sstate = guard.select(jnp.all(valid), s_new, sstate)
                    quarantined = jnp.sum(~valid).astype(jnp.int32)
                else:
                    sstate = fuse(sstate, indices, lv, pa, pc, epoch)
            if guarded:
                if fuse is None:
                    quarantined = jnp.int32(0)
                gstate = guard.update_counters(gstate, ok, quarantined)
            return (params, opt_state, ef, sstate, gstate, scalar, bwd,
                    metrics)

        def eval_step(params, batch):
            _, metrics = loss_fn(params, batch)
            return metrics

        self._step_core = train_step
        self._train_step = jax.jit(train_step, donate_argnums=(0, 1, 2, 3, 4))
        self._eval_step = tracing.jit(eval_step, tracing.EVAL_FORWARD)
        self.engine = self._make_engine()

    def _make_engine(self):
        """Pick the epoch engine for this (strategy, config) pair.

        The scanned engine requires every per-batch hook to be expressible
        on device: ``SampleStrategy.supports_scan`` plus an *active* fused
        observe whenever the strategy observes at all
        (``TrainConfig.fused_observe=False`` forces the host loop, keeping
        the legacy differential-parity path intact).  There is no per-
        strategy branch here: all 8 registered strategies scan — loss-
        dependent selection rides the in-step ``fused_select`` hook.
        """
        s = self.strategy
        observes = type(s).observe is not SampleStrategy.observe
        scannable = s.supports_scan and (self._fuse is not None
                                         or not observes)
        mode = self.cfg.engine
        if mode == "scan" and not scannable:
            raise ValueError(
                f"engine='scan' but strategy {s.name!r} cannot run scanned "
                "epochs (host-side observe without an active fused_observe) "
                "— use engine='auto' or 'host'")
        if mode == "scan" and not self.cfg.device_data:
            raise ValueError(
                "engine='scan' requires device_data=True — the scanned "
                "engine assembles batches by gathering from the "
                "device-resident dataset")
        use_scan = (mode == "scan" or (mode == "auto" and scannable
                                       and self.cfg.device_data
                                       and self.cfg.scan_steps > 0))
        return ScanEpochEngine(self) if use_scan else HostLoopEngine(self)

    def device_data(self) -> dict:
        """The full dataset as device arrays, placed once and cached
        (row-sharded over the data axes under a mesh when N divides the
        data-parallel degree, replicated otherwise) — the gather source for
        the scanned engine's on-device batch assembly."""
        if self._device_data is None:
            arrays = (self.dataset.arrays() if hasattr(self.dataset, "arrays")
                      else materialize(self.dataset.get, self.num_samples))
            if (self.ctx.mesh is not None
                    and self.num_samples % self.ctx.dp_size == 0):
                self._device_data = self.ctx.shard_rows(
                    {k: jnp.asarray(v) for k, v in arrays.items()})
            else:
                self._device_data = self.ctx.replicate(
                    {k: jnp.asarray(v) for k, v in arrays.items()})
        return self._device_data

    def _jit_steps_mesh(self, fuse, fsel=None):
        """Mesh-sharded train/eval steps (``TrainConfig.mesh_shape``).

        The train step is a shard_map over the ``("data",)`` axis wrapped in
        one jit with the (GSPMD) fused observe scatter:

        - params / optimizer state / EF residuals are replicated; batches,
          per-sample metrics and ``SampleState`` are row-sharded.
        - ``grad_allreduce="fold"`` (default): the global batch is viewed as
          ``grad_chunks`` fixed-size chunks in batch order.  Each device
          computes per-chunk loss/grads for its contiguous chunk range *in
          parallel*, then partial results are all-gathered and folded
          left-to-right in global chunk order.  The reduction tree therefore
          depends only on ``grad_chunks`` — never on the mesh size — which
          is what makes losses and parameter trajectories bit-identical
          between ``(1,)`` and ``(8,)`` meshes
          (``tests/test_mesh_trainer.py``).  The all-gather costs
          O(grad_chunks × params) wire bytes versus a psum's O(params).
        - ``grad_allreduce="psum"``: the fast mode — each device takes one
          loss/grad over its whole batch shard and gradients are combined
          with a single ``pmean`` over the data axis.  O(params) wire bytes
          and no chunk loop, but the reduction tree now depends on the mesh
          size, so results are reproducible per mesh size rather than across
          mesh sizes.
        - Error-feedback compression (``grad_compression``) quantizes the
          folded (replicated) gradients before the optimizer update — the
          same contract as the single-device step, so it is deterministic
          and mesh-size-invariant too.
        - The fused observe runs as a *global* scatter on the row-sharded
          state after the shard_map core: XLA partitions it into an O(B)
          metrics gather + shard-local writes (see
          ``core/state.py::scatter_observations``), and a sharding
          constraint keeps the state from ever gathering to one device.
        - The fused select (SB) runs *before* the shard_map core: a
          forward-only GSPMD pass over the sharded batch yields the (B,)
          loss (per-sample, so bit-identical across mesh sizes — the
          ``_eval_step`` argument), which is constrained *replicated*
          together with the select state so the history/percentile/draw
          math is the single-device computation on every shard; the chosen
          weights are constrained back to rows and enter the batch.
        """
        ctx = self.ctx
        mesh = ctx.mesh
        opt, loss_fn, compress = self.opt, self.loss_fn, self.cfg.grad_compression
        guarded = self.cfg.guard_policy != "off"
        fuse_valid = (guarded and fuse is not None
                      and "valid" in inspect.signature(fuse).parameters)
        C = self.cfg.grad_chunks
        D = ctx.dp_size
        local_chunks = C // D
        chunk_rows = self.cfg.batch_size // C

        # Numeric guard inside the shard_map cores: the check runs on the
        # *reduced* gradients (post-fold / post-pmean), which are already
        # replicated — so ``ok`` is the same device bool on every shard and
        # the held/advanced select cannot diverge across the mesh.  The
        # zero-before-compress / select-after-update containment is the
        # single-device step's, verbatim.
        def _guard_update(params, opt_state, ef, scalar, grads, lr):
            ok = guard.all_finite(scalar, grads)
            if compress:
                # Only the EF residual sees the raw grads pre-select; zero
                # them first so it is never poisoned.  Uncompressed, the
                # post-update select alone contains the fault.
                grads = guard.zero_if(~ok, grads)
            prev = (params, opt_state, ef)
            if compress:
                grads, ef = compress_grads(grads, ef)
            params, opt_state = opt.update(grads, opt_state, params, lr)
            params, opt_state, ef = guard.select(
                ok, (params, opt_state, ef), prev)
            return params, opt_state, ef, ok

        def local_core_psum(params, opt_state, ef, batch, lr):
            # Fast mode: one loss/grad over the local rows, one O(params)
            # pmean.  Equal shard sizes make the mean-of-local-means the
            # exact global-batch mean in real arithmetic; in floats the
            # reduction tree depends on D, hence no cross-mesh-size
            # bit-identity promise (grad_allreduce="fold" has that).
            (scalar, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            grads = jax.lax.pmean(grads, "data")
            scalar = jax.lax.pmean(scalar, "data")
            if guarded:
                params, opt_state, ef, ok = _guard_update(
                    params, opt_state, ef, scalar, grads, lr)
                return params, opt_state, ef, scalar, metrics, ok
            if compress:
                grads, ef = compress_grads(grads, ef)
            params, opt_state = opt.update(grads, opt_state, params, lr)
            return params, opt_state, ef, scalar, metrics

        def chunk_loss(params, cb):
            # The global mean's 1/C is applied to each chunk's loss *before*
            # differentiation (exact for a power-of-two C), so the fold below
            # is a pure add chain.  A ``/ C`` after the fold would leave the
            # optimizer's ``b * m + g / C`` with two products, and XLA:CPU's
            # fusion emitters contract such a sum into an FMA differently
            # in a standalone step and in an unrolled scan block — one ulp,
            # enough to break the scan-vs-host bit-identity.
            scalar, metrics = loss_fn(params, cb)
            return scalar / C, metrics

        def local_core(params, opt_state, ef, batch, lr):
            # Local rows: (B/D, ...) = ``local_chunks`` contiguous global
            # chunks (chunk-major layout, so device order == chunk order).
            grads_c, loss_c, mets = [], [], []
            for i in range(local_chunks):
                cb = jax.tree.map(
                    lambda x: jax.lax.dynamic_slice_in_dim(
                        x, i * chunk_rows, chunk_rows, 0), batch)
                (s, m), g = jax.value_and_grad(
                    chunk_loss, has_aux=True)(params, cb)
                grads_c.append(g)
                loss_c.append(s)
                mets.append(m)
            # Stack local per-chunk partials, gather across devices, fold in
            # global chunk order. reshape((C,)+...) turns the gathered
            # (D, local_chunks, ...) into chunk-major (C, ...).
            stacked = jax.tree.map(lambda *xs: jnp.stack(xs), *grads_c)
            gathered = jax.lax.all_gather(
                (stacked, jnp.stack(loss_c)), "data")

            def fold(x):
                x = x.reshape((C,) + x.shape[2:])
                acc = x[0]
                for j in range(1, C):
                    acc = acc + x[j]
                return acc

            grads = jax.tree.map(fold, gathered[0])
            # Every chunk scalar is a chunk-mean of the user loss_fn over C,
            # so the fold is exactly the global-batch mean (equal chunks).
            scalar = fold(gathered[1])
            metrics = jax.tree.map(
                lambda *xs: jnp.concatenate(xs, axis=0), *mets)
            if guarded:
                params, opt_state, ef, ok = _guard_update(
                    params, opt_state, ef, scalar, grads, lr)
                return params, opt_state, ef, scalar, metrics, ok
            if compress:
                grads, ef = compress_grads(grads, ef)
            params, opt_state = opt.update(grads, opt_state, params, lr)
            return params, opt_state, ef, scalar, metrics

        core = jax.shard_map(
            local_core_psum if self.cfg.grad_allreduce == "psum"
            else local_core,
            mesh=mesh,
            in_specs=(P(), P(), P(), P("data"), P()),
            out_specs=(P(), P(), P(), P(), P("data"))
            + ((P(),) if guarded else ()),
            check_vma=False)

        batch_size = self.cfg.batch_size
        rep_sharding = NamedSharding(mesh, P())
        rows_sharding = NamedSharding(mesh, ctx.rows_spec)

        def train_step(params, opt_state, ef, sstate, gstate, batch, indices,
                       epoch, lr):
            if fsel is not None:
                _, (lv0, _, _) = loss_fn(params, batch)
                # Replicate the loss vector and the (global-history) select
                # state: the selection math is then the exact single-device
                # computation on every shard — mesh-size-invariant.
                lv0 = jax.lax.with_sharding_constraint(lv0, rep_sharding)
                sstate = jax.tree.map(
                    lambda x: jax.lax.with_sharding_constraint(
                        x, rep_sharding), sstate)
                if guarded:
                    # Same containment as the single-device step: a
                    # non-finite selection loss holds the select state and
                    # trains the full batch.  lv0 is replicated, so ok0 is
                    # too — no cross-shard divergence.
                    ok0 = jnp.all(jnp.isfinite(lv0))
                    w_new, s_new = fsel(sstate, lv0)
                    sstate = guard.select(ok0, s_new, sstate)
                    w_sel = jnp.where(ok0, w_new, jnp.ones_like(w_new))
                else:
                    w_sel, sstate = fsel(sstate, lv0)
                bwd = jnp.count_nonzero(w_sel).astype(jnp.int32)
                w_sel = jax.lax.with_sharding_constraint(w_sel, rows_sharding)
                batch = dict(batch)
                batch["weight"] = (batch["weight"] * w_sel
                                   if "weight" in batch else w_sel)
            else:
                bwd = jnp.int32(batch_size)
            if guarded:
                params, opt_state, ef, scalar, metrics, ok = core(
                    params, opt_state, ef, batch, lr)
            else:
                params, opt_state, ef, scalar, metrics = core(
                    params, opt_state, ef, batch, lr)
            if fuse is not None:
                lv, pa, pc = metrics
                if fuse_valid:
                    # Score quarantine over the row-sharded metrics: the
                    # masked scatter partitions exactly like the unguarded
                    # one (O(B) gathers + shard-local writes).
                    valid = guard.observation_valid(lv, pc)
                    sstate = fuse(sstate, indices, lv, pa, pc, epoch,
                                  valid=valid)
                    quarantined = jnp.sum(~valid).astype(jnp.int32)
                elif guarded:
                    valid = guard.observation_valid(lv, pc)
                    s_new = fuse(sstate, indices, lv, pa, pc, epoch)
                    sstate = guard.select(jnp.all(valid), s_new, sstate)
                    quarantined = jnp.sum(~valid).astype(jnp.int32)
                else:
                    sstate = fuse(sstate, indices, lv, pa, pc, epoch)
                sstate = ctx.constrain_rows(sstate)
            if guarded:
                if fuse is None:
                    quarantined = jnp.int32(0)
                gstate = guard.update_counters(gstate, ok, quarantined)
            return (params, opt_state, ef, sstate, gstate, scalar, bwd,
                    metrics)

        def eval_step(params, batch):
            _, metrics = loss_fn(params, batch)
            return metrics

        self._step_core = train_step
        self._train_step = jax.jit(train_step, donate_argnums=(0, 1, 2, 3, 4))
        # Forward-only metrics are per-sample (no cross-sample reductions in
        # the loss vector), so plain GSPMD over the sharded batch is already
        # bit-identical across mesh sizes; no chunking needed.
        self._eval_step = tracing.jit(
            eval_step, tracing.EVAL_FORWARD,
            in_shardings=(NamedSharding(mesh, P()),
                          NamedSharding(mesh, P("data"))))

    # ------------------------------------------------------------------ epochs

    def _collect_feats(self):
        feats, labels = [], []
        for idx, batch in self.pipeline.batches(np.arange(self.num_samples)):
            p = self.feats_fn(self.params, batch)
            feats.append(np.asarray(p))
            labels.append(batch["labels"])
        return np.concatenate(feats), np.concatenate(labels)

    def _epoch_indices(self, epoch: int):
        """Returns (visible indices, EpochPlan) for this epoch."""
        with tracing.span("prepare"):
            self.strategy.prepare(
                epoch,
                self._collect_feats if self.feats_fn is not None else None)
        with tracing.span("plan"):
            plan = self.strategy.plan(epoch)
        if plan.reinit_model:
            # e.g. FORGET: restart training from scratch on the pruned set.
            self.params = self._init_params(self.rng)
            self.opt_state = self.opt.init(self.params)
            self._place()
        return plan.visible_indices, plan

    def _rebalanced_order(self, indices: np.ndarray) -> np.ndarray:
        """Re-slice an epoch's visible order when stragglers are flagged.

        The plan's global order is deterministically split into per-worker
        views (``fault.rescale_plan``/``worker_slice``), flagged stragglers
        shed a fraction of their rows to the fastest workers
        (``StragglerMonitor.rebalance``), and the views are re-flattened —
        rebalanced workers first, then the slice-trimmed tail, so every
        visible sample still trains exactly once.  With no straggler
        flagged (the default: uniform latencies) this returns ``indices``
        unchanged — bit-identical plans.
        """
        from repro.train import fault as _fault
        mon = self._straggler
        if mon.world_size <= 1 or not mon.stragglers().any():
            return indices
        idx = np.asarray(indices)
        bs = self.cfg.batch_size
        chunk = mon.world_size * bs
        n_used = (len(idx) // chunk) * chunk
        rp = _fault.rescale_plan(idx[:n_used], mon.world_size, bs)
        per_worker = mon.rebalance(rp.per_worker)
        logger.warning(
            "straggler mitigation: stragglers %s — rebalanced worker rows "
            "%s", np.nonzero(mon.stragglers())[0].tolist(),
            [len(w) for w in per_worker])
        return np.concatenate([*per_worker, idx[n_used:]])

    def run_epoch(self, epoch: int) -> EpochStats:
        with tracing.epoch(epoch) as rec:
            stats = self._run_epoch(epoch, rec.start)
        stats.wall_time = rec.seconds
        stats.spans, stats.counters = rec.spans(), rec.counters
        if logger.isEnabledFor(logging.INFO):
            logger.info(tracing.summary(rec))
        return stats

    def _run_epoch(self, epoch: int, t0: float) -> EpochStats:
        c = self.cfg
        indices, plan = self._epoch_indices(epoch)
        lr = float(c.lr(epoch)) * plan.lr_scale
        # The batch loop is the engine's job (train/engines.py): the host
        # loop dispatches one jitted step per batch; the scanned engine
        # gathers batches on device and dispatches scan_steps-sized blocks.
        if self._straggler is not None:
            indices = self._rebalanced_order(indices)
        with tracing.span("train"):
            res = self.engine.run_epoch(epoch, indices, plan, lr)
        if self._straggler is not None:
            # Feed the monitor this epoch's per-worker latencies.  Measured
            # wall time is uniform across simulated workers (one process),
            # so the default never flags and the plan stays bit-identical;
            # tests/chaos inject skew through shard_latency_fn.
            w = self._straggler.world_size
            lat = (self.shard_latency_fn(epoch)
                   if self.shard_latency_fn is not None
                   else [(time.perf_counter() - t0) / w] * w)
            self._straggler.record_epoch(lat)
        fwd, bwd = res.fwd_samples, res.bwd_samples
        # Guard accounting: the engine reports the device counters'
        # cumulative totals (fetched inside its single epoch-end
        # device_get); diff against what was already reported so the stats
        # are per-epoch.  The abort policy also lives here — the epoch
        # boundary is the run's only host sync.
        nonfinite = quarantined = 0
        if self.guard_state is not None:
            nonfinite = res.nonfinite_steps - self._guard_seen[0]
            quarantined = res.quarantined - self._guard_seen[1]
            self._guard_seen = (res.nonfinite_steps, res.quarantined)
            if nonfinite:
                logger.warning(
                    "numeric guard: epoch %d skipped %d non-finite step(s), "
                    "quarantined %d observation(s) (consecutive=%d)",
                    epoch, nonfinite, quarantined, res.guard_consecutive)
            if (c.guard_abort_after
                    and res.guard_consecutive >= c.guard_abort_after):
                raise guard.NonFiniteError(
                    f"{res.guard_consecutive} consecutive non-finite train "
                    f"steps at epoch {epoch} (guard_abort_after="
                    f"{c.guard_abort_after}) — params are held at the last "
                    "finite update; restart from the latest checkpoint")
        with tracing.span("refresh"):
            if plan.needs_refresh:
                # KAKURENBO step D: forward-only refresh of the hidden list.
                def fwd_fn(idx):
                    with tracing.span("refresh.assemble"):
                        batch = self.dataset.get(idx)
                    with tracing.span("refresh.dispatch"):
                        return self._eval_step(self.params, batch)
                fwd += self.strategy.on_epoch_end(plan, fwd_fn, c.batch_size)
        with tracing.span("eval"):
            acc = (self.evaluate() if (self.test_dataset is not None
                                       and epoch % c.eval_every == 0)
                   else float("nan"))
        with tracing.span("finish"):
            stats = EpochStats(
                epoch=epoch,
                train_loss=(float(np.mean(res.losses)) if len(res.losses)
                            else float("nan")),
                test_acc=acc,
                hidden_fraction=plan.hidden_fraction,
                fwd_samples=fwd, bwd_samples=bwd, lr=lr,
                wall_time=0.0,   # set from the epoch span once it closes
                host_syncs=plan.host_syncs + res.host_syncs,
                engine=self.engine.name,
                nonfinite_steps=nonfinite,
                quarantined_observations=quarantined)
            self.history.append(stats)
            self.epoch = epoch + 1
            if (c.checkpoint_dir and c.checkpoint_every
                    and (epoch + 1) % c.checkpoint_every == 0):
                self.save_checkpoint()
        return stats

    def run(self, epochs: int | None = None,
            fail_at_epoch: int | None = None) -> list[EpochStats]:
        """Run remaining epochs; ``fail_at_epoch`` injects a simulated crash
        (raises) for the fault-tolerance tests."""
        total = epochs or self.cfg.epochs
        while self.epoch < total:
            if fail_at_epoch is not None and self.epoch == fail_at_epoch:
                raise RuntimeError(f"injected failure at epoch {self.epoch}")
            self.run_epoch(self.epoch)
        # Surface a failed trailing async save before reporting success.
        self.finish_checkpoints()
        return self.history

    # ------------------------------------------------------------------ eval

    def evaluate(self) -> float:
        ds = self.test_dataset
        correct = total = 0
        for idx, batch in Pipeline(ds.get, self.cfg.batch_size).batches(
                np.arange(ds.num_samples)):
            _, pa, _ = self._eval_step(self.params, batch)
            correct += int(np.sum(np.asarray(pa)))
            total += len(idx)
        return correct / max(total, 1)

    # ------------------------------------------------------------------ fault tolerance

    def _ckpt_tree(self, strategy_sd: dict | None = None):
        sd = strategy_sd or self.strategy.state_dict()
        # The trainer's init key rides the checkpoint: FORGET-style
        # reinit_model restarts must draw the same fresh params after a
        # restore even if the restoring process was configured with a
        # different seed (restore always wins over construction seeds).
        tree = {"params": self.params, "opt_state": self.opt_state,
                "strategy": sd["arrays"],
                "rng": planops.key_data(self.rng)}
        if self.ef_state is not None:
            # The error-feedback residual is part of the trajectory: without
            # it a compressed-gradient restart re-quantizes from zero carry
            # and silently diverges from the uninterrupted run.  Only added
            # when compression is on, so uncompressed checkpoints keep the
            # legacy leaf set.
            tree["ef"] = self.ef_state
        return tree

    def save_checkpoint(self) -> str | None:
        if not self.cfg.checkpoint_dir:
            return None
        # The strategy's host state (epoch-shuffle / with-replacement RNGs,
        # restart flags) must be checkpointed too — without it a restart
        # re-shuffles differently and the resumed trajectory silently
        # diverges from the uninterrupted one
        # (caught by test_checkpoint_restart_bit_exact).
        sd = self.strategy.state_dict()
        meta = {"epoch": self.epoch, "strategy": sd["host"]}
        if self.cfg.async_checkpoint:
            # Join the previous handle first: a failed background save must
            # surface *before* we start the next one — and older
            # checkpoints are only GC'd after the newer save is confirmed
            # on disk (keep=None disables save()'s own GC on the thread),
            # so a crash chain can always fall back to a real checkpoint.
            self.finish_checkpoints()
            self._pending_save = ckpt.save_async(
                self.cfg.checkpoint_dir, self.epoch, self._ckpt_tree(sd),
                metadata=meta, keep=None)
            return self._pending_save.path
        return ckpt.save(self.cfg.checkpoint_dir, self.epoch,
                         self._ckpt_tree(sd), metadata=meta)

    def finish_checkpoints(self) -> None:
        """Join any pending async save — re-raising its failure — then GC
        superseded checkpoints.  Called between async saves and at the end
        of ``run()``; safe to call any time."""
        if self._pending_save is None:
            return
        self._pending_save.join()
        self._pending_save = None
        ckpt.gc(self.cfg.checkpoint_dir)

    def restore_latest(self) -> bool:
        if not self.cfg.checkpoint_dir:
            return False
        try:
            res = ckpt.restore_latest(self.cfg.checkpoint_dir,
                                      self._ckpt_tree())
        except ValueError as e:
            # e.g. a pre-strategy-format checkpoint with a different leaf set
            raise ValueError(
                f"incompatible checkpoint in {self.cfg.checkpoint_dir!r} "
                f"(old format?): {e}") from e
        if res is None:
            return False
        tree, meta, step = res
        if "strategy" not in meta:
            raise ValueError(
                f"checkpoint step {step} predates the strategy state format "
                "(no 'strategy' metadata) — cannot restore RNG state")
        self.params = tree["params"]
        self.opt_state = tree["opt_state"]
        if self.ef_state is not None:
            self.ef_state = tree["ef"]
        self.rng = planops.load_key(tree["rng"])
        self._place()
        self.strategy.load_state_dict(
            {"arrays": tree["strategy"], "host": meta["strategy"]})
        self.epoch = meta["epoch"]
        return True
