"""Which path ``models.attention.attend`` takes, and what it counts.

The flash kernel exactly where it is eligible (no window, S a multiple of
128, head_dim 64 or 128, the backend probe's "kernel", no mesh); the jnp
path for a window with a traced ``is_global``, an unaligned S, another head
size, the interpreter, and inputs on a mesh of any size, (1,) included.
Each trace counts ``attention.kernel`` or ``attention.jnp`` in the open
epoch, and the epoch's log line prints them.
"""
from __future__ import annotations

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import tracing
from repro.kernels import backend
from repro.kernels.flash_attention import flash_attention
from repro.models import attention as attn

from tests.test_tracing import _lm_trainer


def _qkv(s=128, dh=64, hq=4, hkv=2, b=1):
    r = np.random.default_rng(0)
    return [jnp.asarray(r.normal(size=(b, s, h, dh)), jnp.float32)
            for h in (hq, hkv, hkv)]


def _counted(fn, *args):
    with tracing.epoch(0) as rec:
        out = fn(*args)
    return out, {k: v for k, v in rec.counters.items()
                 if k.startswith("attention.")}


@pytest.fixture
def kernel_backend(monkeypatch):
    """The probe's answer on a TPU; the kernel itself runs interpreted."""
    monkeypatch.setattr(backend, "attention_backend", lambda: "kernel")


def test_eligible_inputs_take_the_kernel(kernel_backend):
    q, k, v = _qkv()
    out, counts = _counted(lambda: attn.attend(q, k, v, causal=True))
    assert counts == {"attention.kernel": 1}
    np.testing.assert_array_equal(out, flash_attention(q, k, v, causal=True))


CASES = {
    "window_traced_is_global": (dict(), dict(window=32), True),
    "unaligned_seq": (dict(s=96), dict(), False),
    "head_dim_32": (dict(dh=32), dict(), False),
}


@pytest.mark.parametrize("shape,kw,traced", CASES.values(), ids=CASES.keys())
def test_ineligible_inputs_take_the_jnp_path(kernel_backend, shape, kw,
                                             traced):
    q, k, v = _qkv(**shape)

    def call(fn, is_global):
        return fn(q, k, v, causal=True, is_global=is_global, **kw)

    wrap, arg = (jax.jit, jnp.bool_(False)) if traced else (lambda f: f, True)
    out, counts = _counted(wrap(lambda g: call(attn.attend, g)), arg)
    assert counts == {"attention.jnp": 1}
    np.testing.assert_array_equal(
        out, wrap(lambda g: call(attn.attend_chunked, g))(arg))


def test_interpreter_takes_the_jnp_path():
    assert backend.attention_backend() == "reference"   # this CPU process
    q, k, v = _qkv()
    out, counts = _counted(lambda: attn.attend(q, k, v))
    assert counts == {"attention.jnp": 1}
    np.testing.assert_array_equal(out, attn.attend_chunked(q, k, v))


_MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P
from repro import tracing
from repro.kernels import backend
from repro.launch.mesh import make_mesh
from repro.models import attention as attn

backend.attention_backend = lambda: "kernel"
x = jnp.ones((4, 128, 4, 64), jnp.float32)
f = lambda q: attn.attend(q, q[:, :, :2], q[:, :, :2])
runs = [("one_device", jax.jit(f), x)]
for n in (4, 1):
    mesh = make_mesh((n,), ("data",))
    xs = jax.device_put(x, NamedSharding(mesh, P("data")))
    sm = jax.shard_map(f, mesh=mesh, in_specs=P("data"), out_specs=P("data"),
                       check_vma=False)
    runs += [(f"gspmd{n}", jax.jit(f), xs), (f"shard_map{n}", jax.jit(sm), xs)]
for name, fn, arg in runs:
    with tracing.epoch(0) as rec:
        jax.block_until_ready(fn(arg))
    print(name, sorted(k for k in rec.counters if k.startswith("attention.")))
"""


def test_sharded_inputs_take_the_jnp_path():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.pathsep.join(["src", os.environ.get(
                   "PYTHONPATH", "")]))
    out = subprocess.run([sys.executable, "-c", _MESH_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    lines = out.stdout.strip().splitlines()
    assert lines == ["one_device ['attention.kernel']",
                     "gspmd4 ['attention.jnp']",
                     "shard_map4 ['attention.jnp']",
                     "gspmd1 ['attention.jnp']",
                     "shard_map1 ['attention.jnp']"], out.stdout


@pytest.mark.parametrize("arch,want", [
    ("smollm-135m", {"attention.jnp"}),     # the interpreter: jnp
    ("mamba2-130m", set()),                 # never reaches attend
])
def test_trainer_epoch_counts_the_attention_path(arch, want, caplog):
    tr = _lm_trainer(arch)
    with caplog.at_level("INFO", logger="repro.train"):
        first = tr.run_epoch(0)
    assert {k for k in first.counters if k.startswith("attention.")} == want
    line = next(r.getMessage() for r in caplog.records
                if r.getMessage().startswith("epoch 0:"))
    for name in tracing.ATTENTION_PATHS:
        assert (f" {name}=" in line) == (name in want), line
