"""Start the KAKURENBO trainer on a TPU and check what comes out.

    python3 chip_smoke.py             # one chip: main path, then the kernels
    python3 chip_smoke.py --chips 4   # four chips: data-parallel (4,) vs (1,)

One process, phases in order, each printing its own lines:

1. device — platform, kind and count as JAX reports them.  Anything but a
   TPU with compiled (not interpreted) Pallas kernels fails: this script
   never falls back to the CPU.
2. main path — smollm-135m at its published width and depth, built with
   ``build_model`` and trained by ``Trainer.run`` under KAKURENBO on the
   scanned engine with AdamW, for 3 epochs on a synthetic corpus made from
   a seed.  Checks: finite losses, samples hidden from epoch 1 on, some of
   them moved back later, and as many steps as the plan's visible samples
   need.
3. kernels — the Pallas kernels the TPU dispatch reaches, at real widths,
   against their jnp oracles; each compiled program must hold the kernel
   (``tpu_custom_call``), so a silent fall back to the oracle cannot pass.

With ``--chips 4`` only the data-parallel path runs: the same trainer on a
``(4,)`` and a ``(1,)`` mesh with the chunk-major gradient fold, whose
hidden sets, move-back sets (both non-empty), losses and parameters must be
bit-identical.

Any failed phase exits non-zero.  On success the last line of stdout is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
"""
from __future__ import annotations

import argparse
import gc
import json
import math
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "smollm-135m"
SEQ_LEN = 512
# 8 x 512 tokens per step: the largest batch whose train step fits a 16 GB
# v5e (12.9 GB by memory_analysis).  One step per scan block: an unrolled
# block of two steps needs 15.5 GB.
BATCH = 8
SCAN_STEPS = 1
NUM_SAMPLES = 256
EPOCHS = 3
# The corpus draws its tokens from the first DATA_VOCAB ids (the model keeps
# its full 49152-way embedding and head), so a random-init model learns the
# bigram source within epoch 0: samples become confidently correct and the
# move-back rule lets them hide from epoch 1 on.  The shrinking fraction
# schedule then moves some of them back.
DATA_VOCAB = 4
# The data-parallel comparison: 4 chunks of one row, folded in chunk order.
# The (1,) mesh runs all 4 chunks in one program, whose activations XLA
# keeps live together: 11.7 GB at 4 x 512 tokens, 19.4 GB at 8 x 512.
MESH_BATCH = 4
MESH_CHUNKS = 4
MESH_EPOCHS = 3
# Kernel widths: smollm's vocabulary, and plan state for 2^20 samples.
SCORE_ROWS = 1024
N_SCORES = 1 << 20


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


def device_phase(chips: int) -> dict:
    import jax
    from repro.kernels import backend
    devs = jax.devices()
    d = devs[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)} pallas={backend.backend_name()}")
    check(d.platform == "tpu", f"no TPU: JAX found {d.platform}")
    check(backend.backend_name() == "pallas",
          "Pallas kernels would run interpreted "
          f"({backend.ENV_VAR}={os.environ.get(backend.ENV_VAR)!r})")
    check(len(devs) >= chips, f"need {chips} chips, JAX found {len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


def build_trainer(*, batch: int, seq_len: int, num_samples: int,
                  epochs: int, scan_steps: int, reduced: bool = False,
                  **cfg_kw):
    """smollm-135m under KAKURENBO, built as ``examples/lm_train.py`` does."""
    from repro.configs.registry import get_arch
    from repro.core import KakurenboConfig, LRSchedule
    from repro.data import SyntheticLM
    from repro.models import build_model
    from repro.train import Trainer, TrainConfig

    cfg = get_arch(ARCH)
    if reduced:
        cfg = cfg.reduced()
    model = build_model(cfg)
    ds = SyntheticLM(num_samples=num_samples, seq_len=seq_len,
                     vocab_size=DATA_VOCAB, order=1, easy_fraction=0.7,
                     seed=0)
    tc = TrainConfig(
        epochs=epochs, batch_size=batch, strategy="kakurenbo",
        optimizer="adamw", optimizer_hp={},
        lr=LRSchedule(1e-3, "cosine", epochs, 1),
        kakurenbo=KakurenboConfig(max_fraction=0.3,
                                  fraction_milestones=(0, 1, 2, 3)),
        engine="scan", scan_steps=scan_steps, seed=0, **cfg_kw)
    return Trainer(tc, model.init, model.loss_and_metrics, ds, None), cfg


def record_plans(tr) -> list:
    """Keep every epoch plan the trainer's strategy makes."""
    plans = []
    plan = tr.strategy.plan
    tr.strategy.plan = lambda e: (plans.append(plan(e)) or plans[-1])
    return plans


def main_path_phase(*, batch: int = BATCH, seq_len: int = SEQ_LEN,
                    num_samples: int = NUM_SAMPLES, epochs: int = EPOCHS,
                    scan_steps: int = SCAN_STEPS, reduced: bool = False
                    ) -> None:
    import jax
    t0 = time.perf_counter()
    tr, cfg = build_trainer(batch=batch, seq_len=seq_len,
                            num_samples=num_samples, epochs=epochs,
                            scan_steps=scan_steps, reduced=reduced)
    plans = record_plans(tr)
    jax.block_until_ready(tr.params)
    print(f"main: arch={cfg.name} layers={cfg.num_layers} d={cfg.d_model} "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads} d_ff={cfg.d_ff} "
          f"vocab={cfg.vocab_size} batch={batch}x{seq_len} "
          f"samples={num_samples} engine={tr.engine.name} "
          f"scan_steps={scan_steps} setup_s={time.perf_counter() - t0:.6f}")
    # No engine warmup: with one step per block there is one block shape,
    # compiled in epoch 0 and counted there, and warmup's cloned carry
    # would hold a second copy of the train state next to the step.
    for epoch in range(epochs):
        (stats,) = tr.run(epochs=epoch + 1)[epoch:]
        # The trainer's own record (repro/tracing.py): the epoch span's
        # seconds and the compiles it counted.
        compile_s = stats.counters.get("compile_s", 0.0)
        hidden = len(plans[epoch].hidden_indices)
        moved_back = len(plans[epoch].moveback_indices)
        steps = stats.bwd_samples // batch
        want_steps = math.ceil((num_samples - hidden) / batch)
        print(f"main: epoch={epoch} loss={stats.train_loss!r} "
              f"hidden={hidden} moved_back={moved_back} steps={steps} "
              f"compiles={stats.counters.get('compiles', 0)} "
              f"compile_s={compile_s:.6f} "
              f"s_per_step={(stats.wall_time - compile_s) / max(steps, 1):.6f}")
        check(math.isfinite(stats.train_loss),
              f"epoch {epoch}: loss {stats.train_loss}")
        check(steps == want_steps,
              f"epoch {epoch}: {steps} steps for {num_samples - hidden} "
              f"visible samples, plan needs {want_steps}")
        if epoch >= 1:
            check(hidden > 0, f"epoch {epoch}: the plan hid no sample")
    check(sum(len(p.moveback_indices) for p in plans) > 0,
          "no hidden sample was moved back")


def _compiled(fn, *args):
    """Compile ``fn`` for these arguments' shapes and insist that a Pallas
    kernel is in the program; returns the executable."""
    import jax
    exe = jax.jit(fn).lower(*args).compile()
    check("tpu_custom_call" in exe.as_text(),
          "no Pallas kernel in the compiled program")
    return exe


def kernels_phase(*, rows: int = SCORE_ROWS, vocab: int = 49152,
                  n: int = N_SCORES) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core import planops
    from repro.core.selection import select_hidden
    from repro.core.state import init_sample_state, scatter_observations
    from repro.kernels import ops, ref, threshold_select

    k1, k2, k3, k4 = jax.random.split(jax.random.key(0), 4)
    logits = 3.0 * jax.random.normal(k1, (rows, vocab), jnp.float32)
    labels = jax.random.randint(k2, (rows,), 0, vocab, jnp.int32)

    # Fused scoring: values and the analytic vjp against the three-pass
    # oracle, at the tolerances of tests/test_fused_scoring.py.
    ce, pa, pc = _compiled(
        lambda x, y: ops.fused_loss_metrics(x, y, scoring="kernel"),
        logits, labels)(logits, labels)
    ce_o, pa_o, pc_o = jax.jit(ref.loss_confidence_ref)(logits, labels)
    ce_err = float(jnp.max(jnp.abs(ce - ce_o) / (1e-5 + 1e-4 * jnp.abs(ce_o))))
    pc_err = float(jnp.max(jnp.abs(pc - pc_o) / (1e-6 + 1e-4 * jnp.abs(pc_o))))
    pa_eq = bool(jnp.all(pa == pa_o))
    w = jax.random.uniform(k3, (rows,), jnp.float32)
    g = _compiled(jax.grad(lambda x: jnp.mean(
        ops.fused_loss_metrics(x, labels, scoring="kernel")[0] * w)),
        logits)(logits)
    g_o = jax.jit(jax.grad(lambda x: jnp.mean(
        ref.loss_confidence_ref(x, labels)[0] * w)))(logits)
    g_err = float(jnp.max(jnp.abs(g - g_o) / (1e-6 + 1e-4 * jnp.abs(g_o))))
    print(f"kernels: fused_scoring {rows}x{vocab} ce_err={ce_err:.3f} "
          f"pc_err={pc_err:.3f} pa_equal={pa_eq} vjp_err={g_err:.3f} "
          "(errors in units of the tolerance)")
    check(ce_err <= 1 and pc_err <= 1 and pa_eq and g_err <= 1,
          "fused scoring disagrees with its oracle")

    # Radix rank-select against the stable argsort, bit for bit; scores
    # rounded to 0.1 so ties straddle the threshold.
    scores = jnp.round(jax.random.exponential(k4, (n,), jnp.float32), 1)
    order = jax.jit(planops.stable_rank_order)(scores)
    for high in (False, True):
        select = _compiled(lambda s, kk: threshold_select.rank_select_mask(
            s, kk, high=high, use_kernel=True), scores, jnp.int32(0))
        for k in (0, 1, n // 3, n):
            got = select(scores, jnp.int32(k))
            want = order >= n - k if high else order < k
            check(bool(jnp.all(got == want)),
                  f"rank_select high={high} k={k} differs from the argsort")
    print(f"kernels: rank_select n={n} equal to stable argsort "
          "(k in 0, 1, n/3, n; low and high tails)")

    # histogram_pallas masks against the jnp histogram selection.
    rng = np.random.default_rng(0)
    state = scatter_observations(
        init_sample_state(n), jnp.arange(n),
        jnp.asarray(rng.exponential(1.0, n), jnp.float32),
        jnp.asarray(rng.random(n) < 0.8),
        jnp.asarray(rng.random(n), jnp.float32), 0)
    got = _compiled(lambda s: select_hidden(
        s, 0.3, method="histogram_pallas"), state)(state)
    want = jax.jit(lambda s: select_hidden(s, 0.3, method="histogram"))(state)
    n_hid = int(jnp.sum(got))
    print(f"kernels: histogram_pallas n={n} hidden={n_hid} "
          f"equal_to_histogram={bool(jnp.all(got == want))}")
    check(bool(jnp.all(got == want)) and n_hid > 0,
          "histogram_pallas masks differ from histogram")


def _mesh_run(mesh_shape, *, batch: int, chunks: int, seq_len: int,
              num_samples: int, epochs: int, reduced: bool):
    import jax
    import numpy as np
    tr, _ = build_trainer(batch=batch, seq_len=seq_len,
                          num_samples=num_samples, epochs=epochs,
                          scan_steps=1, reduced=reduced,
                          mesh_shape=mesh_shape, grad_chunks=chunks,
                          grad_allreduce="fold")
    plans = record_plans(tr)
    t0 = time.perf_counter()
    hist = tr.run()
    params = [np.asarray(x) for x in jax.tree.leaves(jax.device_get(tr.params))]
    print(f"mesh: shape={mesh_shape} losses={[h.train_loss for h in hist]!r} "
          f"hidden={[len(p.hidden_indices) for p in plans]} "
          f"moved_back={[len(p.moveback_indices) for p in plans]} "
          f"wall_s={time.perf_counter() - t0:.6f}")
    rec = [(np.sort(p.hidden_indices), np.asarray(p.moveback_indices),
            h.train_loss) for p, h in zip(plans, hist)]
    del tr
    gc.collect()
    return rec, params


def mesh_phase(*, chips: int = 4, batch: int = MESH_BATCH,
               chunks: int = MESH_CHUNKS, seq_len: int = SEQ_LEN,
               num_samples: int = NUM_SAMPLES, epochs: int = MESH_EPOCHS,
               reduced: bool = False) -> None:
    import numpy as np
    kw = dict(batch=batch, chunks=chunks, seq_len=seq_len,
              num_samples=num_samples, epochs=epochs, reduced=reduced)
    many, p_many = _mesh_run((chips,), **kw)
    one, p_one = _mesh_run((1,), **kw)
    for e, (a, b) in enumerate(zip(many, one)):
        check(np.array_equal(a[0], b[0]), f"epoch {e}: hidden sets differ")
        check(np.array_equal(a[1], b[1]), f"epoch {e}: move-back sets differ")
        check(a[2] == b[2], f"epoch {e}: loss {a[2]!r} vs {b[2]!r}")
    check(len(many) == len(one) == epochs, "epoch counts differ")
    check(sum(len(r[0]) for r in many) > 0, "no sample was hidden")
    check(sum(len(r[1]) for r in many) > 0, "no hidden sample was moved back")
    same = all(np.array_equal(x, y) for x, y in zip(p_many, p_one))
    print(f"mesh: ({chips},) vs (1,) hidden, move-back, losses bit-identical; "
          f"params bit-identical={same}")
    check(same, "final params differ between the meshes")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the data-parallel (4,) vs (1,) check")
    args = ap.parse_args(argv)
    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    try:
        device = device_phase(args.chips)
        if args.chips == 4:
            mesh_phase(chips=4)
        else:
            main_path_phase()
            kernels_phase()
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
