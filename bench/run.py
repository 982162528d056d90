"""One benchmark run of one cell of ``BENCHMARK.json``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell names a configuration (``bench/configs/<name>.json`` with its plain
reference ``<name>.py`` beside it) and a traffic mix
(``bench/traffic/<name>.json``).  The run:

1. refuses to report anything without a TPU (or with interpreted Pallas
   kernels), and names the platform, device kind and count;
2. set-up: makes the weights on the device from the seed, the corpus from
   the seed, builds the program's model and ``Trainer`` (scan engine, one
   step per block, AdamW, float32 parameters; on a ``(chips,)`` data mesh
   where the cell asks for several chips, with the global batch and the
   gradient reduction the mix gives), and runs the mix's set-up
   epochs through the trainer's own ``run_epoch`` — epoch 0 with the step
   tap of ``bench/check.py`` — then compiles the remaining shapes the
   window can reach;
3. the window: whole epochs of ``Trainer.run_epoch`` until ``--seconds``
   have passed, closed by ``block_until_ready`` on the train state;
4. with ``--trace 1`` the window runs under the profiler and the per-layer
   readers of ``bench/metrics/`` reduce the trace and the spans;
5. once the window is closed and the peak memory read, the checks of
   ``bench/check.py`` decide ``correct``: the program's parameters and
   optimizer state are freed, the reference takes its three steps, and
   for KAKURENBO the window's train block and step D run once more at the
   reference's parameters.

The last line of standard output is one JSON object; the numbers compared
and their limits come last there and as the last lines of standard error.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import importlib.util
import json
import math
import os
import pathlib
import shutil
import sys
import tempfile
import time
import typing

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


class NoChip(Exception):
    """The run cannot report: no TPU, too few chips, or interpreted kernels."""


def process_start_wall() -> float:
    """Wall-clock time this process started (Linux ``/proc``), else now."""
    try:
        with open("/proc/self/stat") as f:
            start_ticks = int(f.read().rsplit(")", 1)[1].split()[19])
        with open("/proc/uptime") as f:
            uptime = float(f.read().split()[0])
        return time.time() - uptime + start_ticks / os.sysconf("SC_CLK_TCK")
    except (OSError, ValueError, IndexError):
        return time.time()


START_WALL = process_start_wall()


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


# ---------------------------------------------------------------------------
# The cell's files
# ---------------------------------------------------------------------------


def load_json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_module(path: pathlib.Path):
    spec = importlib.util.spec_from_file_location(
        "bench_cfg_" + path.stem.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


class Cell:
    """A workload of ``BENCHMARK.json`` with its configuration and mix."""

    def __init__(self, name: str):
        bench = load_json(ROOT / "BENCHMARK.json")
        found = [w for w in bench["workloads"] if w["name"] == name]
        if not found:
            raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
        w = found[0]
        (c,) = [c for c in bench["configs"] if c["name"] == w["config"]]
        self.name, self.chips = name, w["chips"]
        self.config_path = ROOT / c["file"]
        self.config = load_json(self.config_path)
        self.model_ref = load_module(self.config_path.with_suffix(".py"))
        self.limits = load_json(self.config_path.with_suffix(".limits.json"))
        self.traffic = load_json(BENCH / "traffic" / f"{w['traffic']}.json")
        self.end_to_end = [m for m in bench["end_to_end"]
                           if name in m.get("workloads", [name])]
        self.per_layer = [m for m in bench["per_layer"]
                          if name in m.get("workloads", [name])]

    @property
    def kakurenbo(self) -> bool:
        return self.traffic["strategy"] == "kakurenbo"


# ---------------------------------------------------------------------------
# Device, compile cache, compile counter
# ---------------------------------------------------------------------------


def enable_compile_cache() -> str:
    """JAX's persistent cache: ``JAX_COMPILATION_CACHE_DIR`` where it is
    set, else the fixed ``<checkout>/.jax_cache``; every program cached."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def device_info(chips: int) -> dict:
    import jax
    from repro.kernels import backend
    devs = jax.devices()
    d = devs[0]
    log(f"device: platform={d.platform} kind={d.device_kind} "
        f"count={len(devs)} pallas={backend.backend_name()}")
    if d.platform != "tpu":
        raise NoChip(f"no TPU: JAX found {d.platform}")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found {len(devs)}")
    if backend.backend_name() != "pallas":
        raise NoChip("Pallas kernels would run interpreted")
    return {"platform": d.platform, "kind": d.device_kind, "count": chips}


class CompileCounter:
    """Programs compiled or loaded from the persistent cache (JAX's backend
    compile event covers both), and of those the cache loads."""

    def __init__(self):
        import jax.monitoring
        self.compiles = self.cache_hits = 0

        def on_duration(event: str, duration: float, **_):
            if event == "/jax/core/compile/backend_compile_duration":
                self.compiles += 1

        def on_event(event: str, **_):
            if event == "/jax/compilation_cache/cache_hits":
                self.cache_hits += 1

        jax.monitoring.register_event_duration_secs_listener(on_duration)
        jax.monitoring.register_event_listener(on_event)


# ---------------------------------------------------------------------------
# Set-up
# ---------------------------------------------------------------------------


def build_config(cls, kw: dict):
    """``cls(**kw)`` from JSON-like values: a field whose type is a
    dataclass (``ArchConfig.ssm``, ``.moe``, or any added later) is built
    from its dict, found from ``cls``'s own field types, and lists become
    tuples, so that frozen configurations stay hashable."""
    hints = typing.get_type_hints(cls)
    return cls(**{k: _field_value(hints[k], v) for k, v in kw.items()})


def _field_value(hint, v):
    if isinstance(v, dict):
        for t in typing.get_args(hint) or (hint,):
            if dataclasses.is_dataclass(t):
                return build_config(t, v)
    if isinstance(v, list):
        return tuple(_field_value(None, x) for x in v)
    return v


def seed_key(seed: int, stream: int):
    """A threefry key from any non-negative seed (wider than 32 bits too)."""
    import jax
    import numpy as np
    words = np.random.SeedSequence([seed, stream]).generate_state(2)
    return jax.random.wrap_key_data(words.astype(np.uint32),
                                    impl="threefry2x32")


class Run:
    """The trainer of one cell and what the benchmark records around it."""

    def __init__(self, cell: Cell, seed: int, arch_override=None):
        import jax
        from repro.configs.base import ArchConfig
        from repro.core import KakurenboConfig, LRSchedule
        from repro.models import build_model
        from repro.train import Trainer, TrainConfig

        from bench.check import StepTap
        from bench.corpus import SyntheticLM
        from bench.spans import Spans

        self.cell, self.seed = cell, seed
        cfg, mix = cell.config, cell.traffic
        if arch_override is not None:
            cfg = arch_override(cfg)
        self.cfg, run = cfg, cfg["run"]
        self.arch = build_config(ArchConfig, cell.model_ref.program_arch(cfg))
        # A mix may set the global batch, and for a cell on several chips
        # how its gradients are reduced over the ("data",) mesh.
        self.batch = mix.get("batch_size", run["batch_size"])
        self.seq_len = run["seq_len"]
        mesh = {}
        if cell.chips > 1:
            mesh = {k: mix[k] for k in ("grad_chunks", "grad_allreduce")
                    if k in mix}
            mesh["mesh_shape"] = (cell.chips,)
        self.n = mix["batches_per_epoch"] * self.batch
        corpus = mix["corpus"]
        self.corpus = SyntheticLM(
            num_samples=self.n, seq_len=self.seq_len,
            vocab_size=corpus["data_vocab"], easy_fraction=corpus[
                "easy_fraction"], order=corpus["order"], seed=seed)
        self.forward = lambda w, t: cell.model_ref.forward(cfg, w, t)
        make = jax.jit(lambda k: cell.model_ref.init_weights(cfg, k))
        wkey = seed_key(seed, 1)
        self.initial_weights = lambda: make(wkey)
        model = build_model(self.arch)
        weights = self.initial_weights()
        want = jax.eval_shape(model.init, jax.random.key(0))
        if (jax.tree.structure(want) != jax.tree.structure(weights)
                or any(a.shape != b.shape for a, b in zip(
                    jax.tree.leaves(want), jax.tree.leaves(weights)))):
            raise ValueError("the reference's weight layout differs from "
                             "the program's parameters")
        sched = mix["lr_schedule"]
        kak = mix.get("kakurenbo", {})
        self.hp = run["optimizer_hp"]
        tc = TrainConfig(
            epochs=sched["total_epochs"], batch_size=self.batch,
            strategy=mix["strategy"], optimizer=run["optimizer"],
            optimizer_hp=dict(self.hp),
            lr=LRSchedule(run["base_lr"], sched["kind"],
                          sched["total_epochs"], sched["warmup_epochs"]),
            kakurenbo=build_config(KakurenboConfig, kak),
            engine=run["engine"], scan_steps=run["scan_steps"],
            seed=seed % (2**31 - 1), **mesh)
        self.tr = Trainer(tc, lambda _key: weights, model.loss_and_metrics,
                          self.corpus, None)
        del weights
        self.check_precision()
        self.spans = Spans()
        self.spans.wrap(self.tr)
        self.tap = StepTap(self.tr.engine, self.initial_weights,
                           self.hp["b1"])
        self.reference_rows = run.get("reference_rows", self.batch)
        self.epochs = []       # EpochStats of the window
        self.plans, self.states = [], []
        self.host = None       # HostClock of the window

    def check_precision(self) -> None:
        """The program trains as the configuration states: parameters in
        ``param_dtype``, matmuls at JAX's ``default`` precision."""
        import jax
        run = self.cfg["run"]
        dtypes = {str(x.dtype) for x in jax.tree.leaves(self.tr.params)}
        if dtypes != {run["param_dtype"]}:
            raise ValueError(f"parameters are {sorted(dtypes)}, the "
                             f"configuration states {run['param_dtype']}")
        precision = jax.config.jax_default_matmul_precision
        if (precision or "default") != run["matmul_precision"]:
            raise ValueError(f"matmul precision {precision!r}, the "
                             f"configuration states {run['matmul_precision']}")

    def setup(self) -> None:
        """The mix's set-up epochs (epoch 0 tapped), then the shapes the
        window can reach that those epochs did not compile."""
        import jax
        import numpy as np
        mix = self.cell.traffic
        # Past the mix's set-up epochs, go on until the last
        # ``setup_steady_epochs`` epochs each hid the mix's share of the
        # ceiling floor(F N), so that the window starts where every epoch
        # hides about as many (and the refresh has compiled), up to the
        # mix's most set-up epochs.
        want, steady = 0.0, 0
        if self.cell.kakurenbo:
            want = mix["setup_until_hidden"] * int(np.floor(
                np.float32(mix["kakurenbo"]["max_fraction"])
                * np.float32(self.n)))
            steady = mix["setup_steady_epochs"]
        t0 = time.perf_counter()
        epoch = 0
        self.setup_hidden = []
        while (epoch < mix["setup_epochs"]
               or (not all(h >= want for h in self.setup_hidden[-steady:])
                   and epoch < mix.get("setup_epochs_max", epoch))):
            last = self.tr.run_epoch(epoch)
            self.setup_hidden.append(round(last.hidden_fraction * self.n))
            if epoch == 0:
                self.tap.remove()
            epoch += 1
        self.setup_epochs = epoch
        jax.block_until_ready((self.tr.params, self.tr.opt_state))
        t1 = time.perf_counter()
        self.warm_shapes()
        self.epochs_s, self.warm_s = t1 - t0, time.perf_counter() - t1

    def warm_shapes(self) -> None:
        """Compile what a window epoch can reach with another visible or
        hidden count than set-up's epochs had, and the benchmark's own copy
        of the sample state before each plan: the engine's per-block slice
        of an epoch plan of each possible length, and the refresh's padded
        tail (its slice and its observe) of each possible remainder."""
        import numpy as np
        from bench.check import copy_state
        if not self.cell.kakurenbo:
            return
        copy_state(self.tr.strategy.get_device_state())   # the window's copy
        mix, b, n = self.cell.traffic["kakurenbo"], self.batch, self.n
        most_hidden = int(np.floor(np.float32(mix["max_fraction"])
                                   * np.float32(n)))
        place = self.tr.engine._place_plan
        for steps in range(-(-(n - most_hidden) // b), n // b + 1):
            place(np.zeros((steps, b), np.int32))[0:1]
        # The refresh's tail slices step D's forward outputs, which are
        # row-sharded on a mesh: slice real ones, so each slice compiles for
        # the layout the window gives it.
        strategy = self.tr.strategy
        saved = strategy.get_device_state()
        signals = self.tr._eval_step(self.tr.params,
                                     self.tr.dataset.get(np.arange(b)))
        for k in range(1, b):
            strategy.observe(np.arange(k), *(x[:k] for x in signals), 1)
        strategy.set_device_state(saved)

    def tap_batches(self, tap: dict) -> list:
        """(tokens, labels) of the tapped steps' rows."""
        return [(d["tokens"], d["labels"])
                for d in (self.corpus.get(rows) for rows in tap["rows"])]

    # -- the window ----------------------------------------------------------

    def window(self, seconds: float) -> float:
        """Whole epochs until ``seconds`` have passed; returns its length."""
        import jax
        from bench.check import copy_state
        from bench.spans import HostClock
        tr = self.tr
        plan = tr.strategy.plan

        def plan_and_keep(epoch):
            if self.cell.kakurenbo:
                self.states.append(copy_state(tr.strategy.get_device_state()))
            p = plan(epoch)
            self.plans.append(p)
            return p

        tr.strategy.plan = plan_and_keep
        epoch = tr.epoch
        self.host = HostClock(tr.engine)
        with self.spans.span("window"):
            self.t0 = time.perf_counter()
            while True:
                with self.spans.span("epoch"):
                    self.epochs.append(tr.run_epoch(epoch))
                epoch += 1
                if time.perf_counter() - self.t0 >= seconds:
                    break
            jax.block_until_ready((tr.params, tr.opt_state,
                                   tr.strategy.get_device_state()))
            self.t1 = time.perf_counter()
        self.host.remove()
        tr.strategy.plan = plan
        return self.t1 - self.t0


# ---------------------------------------------------------------------------
# Checks after the window
# ---------------------------------------------------------------------------


def window_numbers(r: Run) -> dict:
    """The window's plan numbers, from the host copies of its plans and of
    the sample state before each plan and after the last epoch."""
    from bench import check
    if not r.cell.kakurenbo:
        return {"plan_diff": check.cover_diff(r.plans, r.n)}
    states = [check.state_dict_of(s) for s in r.states]
    after = states[1:] + [check.state_dict_of(
        r.tr.strategy.get_device_state())]
    return {"plan_diff": check.plan_diff(r.plans, states,
                                         r.cell.traffic["kakurenbo"], r.n),
            "seen_diff": check.seen_diff(r.plans, after)}


def last_hiding_plan(r: Run):
    """The plan of the last window epoch that hid samples (else the last)."""
    return ([p for p in r.plans if len(p.hidden_indices)] or r.plans)[-1]


def probe_rows(r: Run) -> dict:
    """The rows the probe observes, drawn from the seed: a batch of the
    visible rows of the last window epoch that hid samples, and a sample of
    its hidden list."""
    import numpy as np
    last = last_hiding_plan(r)
    rng = np.random.default_rng(np.random.SeedSequence([r.seed, 2]))
    hidden = last.hidden_indices
    return {"train": np.sort(rng.choice(last.visible_indices, r.batch,
                                        replace=False)),
            "hidden": hidden,
            "sample": np.sort(rng.choice(hidden, min(len(hidden),
                                                     2 * r.batch),
                                         replace=False))}


def probe(r: Run, params, rows: dict, train: bool) -> tuple[dict, int]:
    """Step D over the hidden list of the last window epoch that hid
    samples, then, where ``train``, one step of the window's train block on
    ``rows["train"]``, both at ``params`` and marked with an epoch the
    window did not reach.  ``params`` are donated to the train block.
    Returns the sample state after both (on the host) and the marking
    epoch."""
    import jax.numpy as jnp
    import numpy as np
    from bench import check
    from repro.core.state import TrainCarry
    tr, last = r.tr, last_hiding_plan(r)
    epoch = tr.epoch
    tr.strategy.on_epoch_end(
        dataclasses.replace(last, epoch=epoch),
        lambda idx: tr._eval_step(params, tr.dataset.get(idx)), r.batch)
    if not train:
        return check.state_dict_of(tr.strategy.get_device_state()), epoch
    carry = TrainCarry(params, tr.opt.init(params), tr.ef_state,
                       tr.strategy.get_device_state(), tr.guard_state)
    carry, _ = tr.engine._block(
        carry, {"idx": jnp.asarray(rows["train"][None].astype(np.int32))},
        jnp.int32(epoch), r.epochs[-1].lr)
    return check.state_dict_of(carry.sstate), epoch


def checks(r: Run, names) -> tuple[dict, dict]:
    """The numbers ``names`` (all, where None) for ``r`` once its window has
    closed; frees the trainer.  Returns them with what ``bench/calibrate.py``
    reads its control on: the reference's steps, the probe's rows and the
    reference's (loss, PC) there."""
    import numpy as np
    from bench import check
    tap = r.tap.readings()
    r.tap = None   # it holds the engine, and so the trainer
    numbers = window_numbers(r)
    r.tr.params = r.tr.opt_state = None
    gc.collect()
    ref = check.reference_steps(r.forward, r.initial_weights, r.tap_batches(
        tap), tap["lrs"], r.hp, rows=r.reference_rows)
    numbers.update(check.step_numbers(
        tap, ref, check.STEP_NUMBERS if names is None else names))
    extra = {"ref": ref, "tap": tap}
    if r.cell.kakurenbo:
        rows = probe_rows(r)
        both = np.concatenate([rows["train"], rows["sample"]])
        data = r.corpus.get(both)
        loss, pc = check.reference_signals(r.forward, ref["params"],
                                           data["tokens"], data["labels"],
                                           r.reference_rows)
        observe = names is None or "observe_rel" in names
        state, epoch = probe(r, ref.pop("params"), rows, observe)
        b = len(rows["train"])
        if observe:
            numbers["observe_rel"] = check.signals_gap(
                state, rows["train"], epoch, loss[:b], pc[:b])
        numbers["refresh_rel"] = check.refresh_gap(
            state, rows["hidden"], rows["sample"], epoch, loss[b:], pc[b:])
        numbers["refresh_mean_rel"] = check.refresh_gap(
            state, rows["hidden"], rows["sample"], epoch, loss[b:], pc[b:],
            np.mean)
        got = {k: np.asarray(state[k])[rows["sample"]].tolist()
               for k in ("loss", "pc")}
        log(f"refresh rows: program={got} reference="
            f"{dict(loss=loss[b:].tolist(), pc=pc[b:].tolist())}")
        extra.update(rows=both, train=b, signals=(loss, pc), refreshed=got)
    r.tr = None
    gc.collect()
    if names is not None:
        numbers = {k: v for k, v in numbers.items() if k in names}
    return numbers, extra


def run_checks(r: Run) -> dict:
    """Every number compared for ``r``'s cell: {name: (value, limit)}."""
    from bench.check import STRATEGY_NUMBERS
    t0 = time.perf_counter()
    limits = r.cell.limits
    numbers, _ = checks(r, set(limits))
    log(f"checks_s={time.perf_counter() - t0!r}")
    want = set(limits) - (set() if r.cell.kakurenbo else set(STRATEGY_NUMBERS))
    missing = sorted(want - set(numbers))
    if missing:
        raise ValueError(f"no reading for {missing}")
    return {k: (numbers[k], limits[k]) for k in limits if k in want}


# ---------------------------------------------------------------------------
# Metrics
# ---------------------------------------------------------------------------


class Record:
    """What the per-layer readers of ``bench/metrics/`` read."""

    def __init__(self, r: Run, window_s: float, trace, peak: dict):
        self.arch, self.seq_len, self.batch = r.arch, r.seq_len, r.batch
        self.cfg, self.model_ref = r.cfg, r.cell.model_ref
        self.chips = r.cell.chips
        self.epochs, self.spans = r.epochs, r.spans
        self.window = (r.t0, r.t1)
        self.window_s = window_s
        self.trace = trace
        self.peak = peak


def read_per_layer(cell: Cell, rec: Record) -> dict:
    out = {}
    for m in cell.per_layer:
        mod = load_module(BENCH / "metrics" / f"{m['name']}.py")
        v = mod.read(rec)
        if v is not None:
            out[m["name"]] = {"value": v, "unit": m["unit"]}
    return out


def traced(r: Run, seconds: float):
    """The window under the profiler; returns (window_s, reduced trace)."""
    import jax
    from bench import trace as tr_mod
    log_dir = tempfile.mkdtemp(prefix="bench_trace_")
    try:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.enable_hlo_proto = False
        r.spans.annotate = r.spans.sync = True
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            window_s = r.window(seconds)
        finally:
            jax.profiler.stop_trace()
        return window_s, tr_mod.load(tr_mod.find_xplane(log_dir))
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


def memory_peak_bytes(devices) -> int:
    """The peak of the fullest of ``devices``.  The TPU runtime keeps the
    programs' temporaries in a reserved region that ``peak_bytes_in_use``
    leaves out, so a chip's peak is both together."""
    peaks = []
    for d in devices:
        mem = d.memory_stats() or {}
        log(f"memory_stats {d.id}: {mem}")
        peaks.append(int(mem.get("peak_bytes_in_use", 0)
                         + mem.get("peak_bytes_reserved", 0)))
    return max(peaks)


def main(argv=None, *, require_tpu: bool = True, arch_override=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a non-negative whole number")
    cell = Cell(args.workload)
    cache = enable_compile_cache()
    import jax
    from bench import peaks
    log(f"compile cache: {cache}")
    try:
        device = device_info(cell.chips)
    except NoChip as e:
        if require_tpu:
            log(f"FAILED: {e}")
            return 2
        d = jax.devices()[0]
        device = {"platform": d.platform, "kind": d.device_kind,
                  "count": cell.chips}
    peak = (peaks.peak(device["kind"]) if require_tpu
            else peaks.PEAKS.get(device["kind"]))
    counter = CompileCounter()
    t_build = time.time()
    r = Run(cell, args.seed, arch_override)
    t_setup = time.time()
    r.setup()
    setup_s = time.time() - START_WALL
    log(f"setup phases: start_to_build_s={t_build - START_WALL!r} "
        f"build_s={t_setup - t_build!r} epochs_s={r.epochs_s!r} "
        f"warm_s={r.warm_s!r}")
    log(f"setup: setup_s={setup_s!r} compiles={counter.compiles} "
        f"cache_loads={counter.cache_hits} epochs={r.setup_epochs} "
        f"hidden={r.setup_hidden} "
        f"samples={r.n} "
        f"batch={r.batch}x{r.seq_len}")
    before = counter.compiles
    if args.trace:
        window_s, trace = traced(r, args.seconds)
    else:
        window_s, trace = r.window(args.seconds), None
    in_window = counter.compiles - before
    stats = r.epochs
    peak_bytes = memory_peak_bytes(jax.devices()[:cell.chips])
    device["memory_peak_bytes"] = peak_bytes
    hidden = [len(p.hidden_indices) for p in r.plans]
    log(f"window: window_s={window_s!r} epochs={len(stats)} "
        f"compiles_in_window={in_window} hidden={hidden} "
        f"moved_back={[len(p.moveback_indices) for p in r.plans]} "
        f"steps={[s.bwd_samples // r.batch for s in stats]} "
        f"epoch_s={[s.seconds for s in r.spans.between('epoch', r.t0, r.t1)]} "
        f"plan_s={[s.seconds for s in r.spans.between('plan', r.t0, r.t1)]} "
        f"refresh_s={[s.seconds for s in r.spans.between('refresh', r.t0, r.t1)]} "
        f"losses={[s.train_loss for s in stats]}")
    log(f"host: {r.host.summary(r.spans.between('train', r.t0, r.t1))}")
    breakdown = None
    if args.trace:
        from bench.trace import breakdown as reduce_breakdown, busy_ns
        t_reduce = time.perf_counter()
        rec = Record(r, window_s, trace, peak)
        metrics = read_per_layer(cell, rec)
        lo, hi = trace.window()
        device["busy_s"] = busy_ns(trace, lo, hi) * 1e-9
        device["window_s"] = (hi - lo) * 1e-9
        breakdown = reduce_breakdown(trace)
        log(f"trace: ops={sum(len(v) for v in trace.ops.values())} "
            f"reduce_s={time.perf_counter() - t_reduce!r}")
    else:
        values = {"samples_per_s": r.n * len(stats) / window_s,
                  "peak_hbm_gb": peak_bytes / 1e9, "setup_s": setup_s}
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    failed = sum(not math.isfinite(s.train_loss) for s in stats)
    checks = run_checks(r)
    ok = all(v <= lim for v, lim in checks.values()) and failed == 0
    result = {"correct": ok, "attempted": len(stats), "failed": failed,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # A check with nothing to compare reads infinite; JSON has no number
    # for that, so it goes out as the string "inf".
    result["checks"] = {k: {"value": v if math.isfinite(v) else str(v),
                            "limit": lim} for k, (v, lim) in checks.items()}
    for k, (v, lim) in checks.items():
        log(f"check {k}: {v!r} limit {lim!r} "
            f"{'ok' if v <= lim else 'FAILED'}")
    print(json.dumps(result, default=float), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
