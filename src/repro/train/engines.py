"""Epoch engines: how a planned epoch's batches become train steps.

The trainer owns *what* trains (the strategy's ``EpochPlan``) and the step
math (``Trainer._step_core``: loss/grads, optional compression, optimizer
update, optional fused observe scatter — single-device or mesh-sharded).
An epoch engine owns *how* the plan is dispatched:

- ``HostLoopEngine`` — the classic loop: one jitted step per batch, batches
  assembled on the host by the ``Pipeline`` and shipped host→device each
  step.  The only engine that can run per-batch host hooks (host
  ``observe()`` when the fused scatter is off), so it is also the
  legacy-parity reference.  Per-step loss scalars are collected as device
  arrays and converted to floats once at epoch end — the loop never blocks
  on a step.

- ``ScanEpochEngine`` — the device-resident epoch: the full dataset is
  placed in device memory once (``Trainer.device_data``), every epoch's
  batch layout is shipped as one ``(num_steps, B)`` index-plan array
  (row-sharded over the data axes under a mesh), and batches are assembled
  *inside* the jitted step by gathering rows from the plan.
  ``TrainConfig.scan_steps`` consecutive steps are rolled into a single
  ``jax.lax.scan`` block per dispatch, with the ``TrainCarry`` (params,
  optimizer state, EF residual, SampleState) threaded through and per-step
  loss scalars coming back as the scan's stacked outputs — fetched with one
  ``device_get`` per epoch.  Per-sample ``batch_weights`` are pre-gathered
  into the plan (they are plan-time lookups by protocol contract), so a
  scanned epoch does zero per-batch host work.

The scan block uses ``unroll=True``: the K step bodies are inlined into one
XLA computation instead of a while loop.  That is what makes the scanned
engine *bit-identical* to the host loop — XLA compiles a rolled loop body
with different layouts/fusions than a standalone step (measurably different
conv-grad reductions), while the unrolled block reproduces the per-step
compilation exactly.  One dispatch still covers K batches, which is where
the wall-clock win comes from (``benchmarks/step_throughput.py``).

Engine choice (``Trainer._make_engine``) is per strategy capability:
``SampleStrategy.supports_scan`` strategies — all 8 registered ones — run
scanned by default (``TrainConfig.engine="auto"``, ``device_data=True``);
only the legacy ``fused_observe=False`` parity path (and host-planned
external strategies without a fused observe) keep the host loop.
Loss-dependent selection (Selective-Backprop) is the in-step
``fused_select`` hook inside ``Trainer._step_core``, so it runs identically
under either engine; its surviving-sample count comes back as a per-step
device scalar next to the loss, fetched once per epoch.
Both engines honour the same crash contract: the latest live train state is
always handed back (the ``finally`` blocks), so checkpoint-on-fault works
mid-epoch — at batch granularity in the host loop, at scan-block
granularity in the scanned engine.
"""
from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import tracing
from repro.core.state import TrainCarry
from repro.core.strategy import SampleStrategy
from repro.data.pipeline import epoch_index_plan


@dataclasses.dataclass
class EpochRunResult:
    """What an engine hands back to ``Trainer.run_epoch``."""

    losses: np.ndarray        # (num_steps,) f64 per-step loss scalars
    fwd_samples: int
    bwd_samples: int
    host_syncs: int           # SampleState round trips spent in the loop
    # Numeric guard counters (train/guard.py) — *cumulative* run totals as
    # fetched from the device GuardState in the same epoch-end device_get
    # that materialises the losses (so guarding adds no host syncs); all 0
    # with the guard off.  The trainer diffs totals into per-epoch stats.
    nonfinite_steps: int = 0
    quarantined: int = 0
    guard_consecutive: int = 0


def _all_live(tree) -> bool:
    """True when no leaf is a donated-and-consumed (deleted) jax array.

    Crash-handback guard: a failure *between* dispatches leaves the carry
    fully live, but a failure *inside* a dispatch (device OOM, runtime
    error, interrupt) happens after donation — then neither the old carry
    nor the partial output is usable, and handing deleted buffers to the
    trainer would only turn the later checkpoint-on-fault into a confusing
    'Array has been deleted' error masking the original fault.
    """
    return not any(getattr(leaf, "is_deleted", lambda: False)()
                   for leaf in jax.tree.leaves(tree))


class HostLoopEngine:
    """Per-batch jitted dispatch with host-side batch assembly."""

    name = "host"

    def __init__(self, trainer):
        self.tr = trainer

    def run_epoch(self, epoch: int, indices: np.ndarray, plan,
                  lr: float) -> EpochRunResult:
        tr = self.tr
        fwd = 0
        losses, bwds = [], []
        # Fused paths: thread the strategy's device state through the jitted
        # step for the whole epoch; hand it back only at the epoch boundary.
        fuse = tr._fuse
        dev_state = (tr.strategy.get_device_state() if tr._thread_state
                     else None)
        gstate = tr.guard_state
        # Strategies that don't override observe() (e.g. baseline) keep no
        # per-sample state, so their no-op observe is not a host round trip.
        observes = type(tr.strategy).observe is not SampleStrategy.observe
        loop_syncs = 0
        host_quarantined = 0
        epoch_dev = jnp.int32(epoch)
        try:
            for idx, batch in tr.pipeline.batches(indices):
                fwd += len(idx)
                weight = tr.strategy.batch_weights(idx)
                b = dict(batch)
                if weight is not None:
                    b["weight"] = jnp.asarray(weight, jnp.float32)
                (tr.params, tr.opt_state, tr.ef_state, dev_state, gstate,
                 scalar, bwd, metrics) = tr._train_step(
                    tr.params, tr.opt_state, tr.ef_state, dev_state, gstate,
                    b, jnp.asarray(idx), epoch_dev, lr)
                # Device scalars only — converted to floats once at epoch
                # end, so the loop never blocks on a step's completion.  The
                # step reports its own backward count (fused-select
                # strategies train a loss-dependent subset of the batch).
                losses.append(scalar)
                bwds.append(bwd)
                if fuse is None:
                    lv, pa, pc = metrics
                    if gstate is not None and observes:
                        # Legacy host-observe path under the guard: filter
                        # the non-finite observations out before the
                        # strategy scatters them.  This path already syncs
                        # every batch, so the host-side mask is free.
                        lv = np.asarray(lv)
                        valid = np.isfinite(lv) & np.isfinite(np.asarray(pc))
                        if not valid.all():
                            host_quarantined += int((~valid).sum())
                            idx = np.asarray(idx)[valid]
                            lv, pa, pc = (lv[valid], np.asarray(pa)[valid],
                                          np.asarray(pc)[valid])
                    if len(np.asarray(idx)):
                        tr.strategy.observe(idx, lv, pa, pc, epoch)
                    loop_syncs += int(observes)
        finally:
            # The train step donates dev_state, so mid-epoch the strategy's
            # own reference may point at deleted buffers — always hand back
            # the latest live state, even on a crash (between dispatches;
            # see _all_live for the inside-a-dispatch case), so
            # checkpoint-on-fault (save_checkpoint -> strategy.state_dict)
            # stays valid.  The guard counters ride the same contract.
            if tr._thread_state and _all_live(dev_state):
                tr.strategy.set_device_state(dev_state)
            if gstate is not None and _all_live(gstate):
                tr.guard_state = gstate
            # Host-path quarantines join the cumulative totals the trainer
            # diffs (the device counters only see fused observations).
            tr._guard_host_q += host_quarantined
        nf = qr = consec = 0
        if losses:
            # The epoch's single loss/work materialisation (guard counters
            # included — no extra round trip).
            ls, bw, g = jax.device_get((losses, bwds, gstate))
            ls = np.asarray(ls, np.float64)
            bwd_total = int(np.sum(np.asarray(bw, np.int64)))
            if g is not None:
                nf, qr, consec = (int(g.nonfinite_steps), int(g.quarantined),
                                  int(g.consecutive))
        else:
            ls, bwd_total = np.zeros(0), 0
        return EpochRunResult(losses=ls, fwd_samples=fwd,
                              bwd_samples=bwd_total, host_syncs=loop_syncs,
                              nonfinite_steps=nf,
                              quarantined=qr + tr._guard_host_q,
                              guard_consecutive=consec)


def scan_block_sizes(num_steps: int, scan_steps: int) -> list[int]:
    """Partition an epoch's steps into scan-block lengths.

    As many full ``scan_steps`` blocks as fit, then the remainder as
    descending powers of two.  Any partition is bit-identical (blocks are
    unrolled, so splitting changes dispatch boundaries, not math); the
    point of the binary remainder is compile-cache stability: strategies
    like KAKURENBO change the visible count — and with it the remainder —
    every epoch, and naively compiling one block per distinct remainder
    length re-traces every epoch.  This way the engine only ever compiles
    block lengths from {scan_steps} ∪ {1, 2, 4, ...} — O(log scan_steps)
    shapes for the whole run.
    """
    sizes = [scan_steps] * (num_steps // scan_steps)
    rem = num_steps % scan_steps
    p = 1 << (scan_steps.bit_length())
    while rem:
        if rem >= p:
            sizes.append(p)
            rem -= p
        else:
            p >>= 1
    return sizes


class ScanEpochEngine:
    """Gather-based batch assembly + multi-step ``lax.scan`` dispatch."""

    name = "scan"

    def __init__(self, trainer):
        self.tr = trainer
        self.scan_steps = max(int(trainer.cfg.scan_steps), 1)
        self._block = None   # built lazily: see _build_block
        self._dispatched = 0  # train steps dispatched, for step annotations

    def _build_block(self):
        """Close the jitted scan block over the device-resident dataset.

        Deferred to the first ``run_epoch``/``warmup`` call so that merely
        constructing a Trainer (to restore a checkpoint, to evaluate, in a
        config-validation test) never pays dataset materialisation +
        device placement.
        """
        trainer = self.tr
        data = trainer.device_data()
        ctx = trainer.ctx
        step_core = trainer._step_core

        def block(carry, xs, epoch, lr):
            def body(c, x):
                batch = {k: jnp.take(v, x["idx"], axis=0)
                         for k, v in data.items()}
                if ctx.mesh is not None:
                    batch = ctx.constrain_rows(batch)
                if "w" in x:
                    batch["weight"] = x["w"]
                (params, opt_state, ef, sstate, gstate, scalar, bwd,
                 _) = step_core(
                    c.params, c.opt_state, c.ef, c.sstate, c.gstate, batch,
                    x["idx"], epoch, lr)
                return (TrainCarry(params, opt_state, ef, sstate, gstate),
                        (scalar, bwd))
            # unroll=True: the K bodies are inlined, reproducing the
            # standalone per-step compilation bit for bit (a rolled while
            # loop compiles the conv grads with different layouts); one
            # dispatch still covers the whole block.  A length-1 block
            # (scan_steps=1, or a remainder block when num_steps % K == 1)
            # is inlined by hand: XLA canonicalises a 1-trip scan through a
            # different graph whose conv grads are NOT bit-identical to the
            # standalone step.  Block length is static at trace time, so
            # this is a plain python branch.
            if jax.tree.leaves(xs)[0].shape[0] == 1:
                carry, out = body(carry, jax.tree.map(lambda a: a[0], xs))
                return carry, jax.tree.map(lambda a: a[None], out)
            return jax.lax.scan(body, carry, xs, unroll=True)

        self._block = tracing.jit(block, tracing.TRAIN_BLOCK,
                                  donate_argnums=(0,))

    def warmup(self) -> int:
        """Compile every scan-block shape this engine can ever dispatch.

        Runs one dummy block per shape ({scan_steps} plus the power-of-2
        remainder lengths, see ``scan_block_sizes``) on a *cloned* carry —
        the real train state is untouched — so the jit cache is fully
        populated before the first timed/production epoch instead of paying
        a compile whenever a strategy's moving visible count first produces
        a new remainder length.  Returns the number of block shapes warmed.
        """
        if self._block is None:
            self._build_block()
        tr = self.tr
        bs = tr.cfg.batch_size
        w = tr.strategy.batch_weights(np.zeros(bs, np.int64))
        dev_state = (tr.strategy.get_device_state() if tr._thread_state
                     else None)
        # Exactly the shapes run_epoch can dispatch: every block length
        # scan_block_sizes emits for any remainder, plus the full block.
        sizes = sorted({size
                        for rem in range(self.scan_steps + 1)
                        for size in scan_block_sizes(rem, self.scan_steps)}
                       | {self.scan_steps}, reverse=True)
        for size in sizes:
            xs = {"idx": self._place_plan(np.zeros((size, bs), np.int32))}
            if w is not None:
                xs["w"] = self._place_plan(np.ones((size, bs), np.float32))
            carry = TrainCarry(*jax.tree.map(
                jnp.copy, (tr.params, tr.opt_state, tr.ef_state, dev_state,
                           tr.guard_state)))
            jax.block_until_ready(
                self._block(carry, xs, jnp.int32(0), 0.0)[1])
        return len(sizes)

    def _place_plan(self, arr: np.ndarray) -> jax.Array:
        """Ship an epoch-plan array, dim 1 (the batch dim) row-sharded over
        the data axes under a mesh."""
        ctx = self.tr.ctx
        if ctx.mesh is None:
            return jnp.asarray(arr)
        spec = P(None, *tuple(ctx.rows_spec))
        return jax.device_put(arr, NamedSharding(ctx.mesh, spec))

    def run_epoch(self, epoch: int, indices: np.ndarray, plan,
                  lr: float) -> EpochRunResult:
        tr, c = self.tr, self.tr.cfg
        with tracing.span("train.layout"):
            plan_idx = epoch_index_plan(np.asarray(indices), c.batch_size)
            num_steps = plan_idx.shape[0]
            if num_steps == 0:
                return EpochRunResult(losses=np.zeros(0), fwd_samples=0,
                                      bwd_samples=0, host_syncs=0)
            if self._block is None:
                self._build_block()
            # Per-sample static weights are plan-time lookups (protocol
            # contract), pre-gathered here in the host loop's exact call
            # order.
            w_rows = [tr.strategy.batch_weights(row) for row in plan_idx]
            xs = {"idx": self._place_plan(plan_idx.astype(np.int32))}
            if any(w is not None for w in w_rows):
                # None rows mean uniform; weight 1.0 is exact (loss * 1.0).
                xs["w"] = self._place_plan(np.stack(
                    [np.ones(c.batch_size, np.float32) if w is None
                     else np.asarray(w, np.float32) for w in w_rows]))
        dev_state = (tr.strategy.get_device_state() if tr._thread_state
                     else None)
        carry = TrainCarry(tr.params, tr.opt_state, tr.ef_state, dev_state,
                           tr.guard_state)
        losses, bwds = [], []
        epoch_dev = jnp.int32(epoch)
        try:
            start = 0
            for size in scan_block_sizes(num_steps, self.scan_steps):
                xs_block = jax.tree.map(
                    lambda a: a[start : start + size], xs)
                # The step annotation numbers dispatches across epochs, so
                # a profiler's step view keeps each block apart.
                with tracing.span("train.dispatch", step=self._dispatched):
                    carry, (block_losses, block_bwds) = self._block(
                        carry, xs_block, epoch_dev, lr)
                losses.append(block_losses)
                bwds.append(block_bwds)
                start += size
                self._dispatched += size
        finally:
            # The scan block donates the whole carry: hand the latest live
            # buffers back even on a mid-epoch crash, so checkpoint-on-fault
            # stays valid at scan-block granularity.  A crash *inside* a
            # dispatch (after donation) leaves nothing recoverable — don't
            # overwrite the trainer's refs with deleted buffers then.
            if _all_live(carry):
                tr.params, tr.opt_state = carry.params, carry.opt_state
                tr.ef_state = carry.ef
                if tr._thread_state:
                    tr.strategy.set_device_state(carry.sstate)
                if carry.gstate is not None:
                    tr.guard_state = carry.gstate
        # The epoch's single loss/work materialisation: per-step scalars
        # (loss + the step's backward count) were accumulated on device
        # across the scan blocks; the guard counters ride the same fetch.
        with tracing.span("train.fetch"):
            got_ls, got_bw, g = jax.device_get((losses, bwds, carry.gstate))
        ls = np.concatenate([np.asarray(x, np.float64) for x in got_ls])
        bwd = int(np.sum(np.concatenate(
            [np.asarray(x, np.int64) for x in got_bw])))
        nf = qr = consec = 0
        if g is not None:
            nf, qr, consec = (int(g.nonfinite_steps), int(g.quarantined),
                              int(g.consecutive))
        n = num_steps * c.batch_size
        return EpochRunResult(losses=ls, fwd_samples=n, bwd_samples=bwd,
                              host_syncs=0, nonfinite_steps=nf,
                              quarantined=qr, guard_consecutive=consec)
