"""The single Pallas backend probe: compiled Mosaic vs interpret mode.

Every kernel module used to hardcode ``interpret: bool = True`` per
function, which meant a real TPU deployment had to edit four files (or
monkeypatch ``ops.INTERPRET``) before anything compiled.  All kernel entry
points now default ``interpret=None`` and resolve through this one probe:

- ``REPRO_PALLAS_INTERPRET=0|1`` (env) overrides everything — a TPU run
  compiles without code edits, and a TPU *parity* run can still force the
  interpreter;
- otherwise interpret mode is chosen exactly when the default jax backend
  is not a TPU (this CPU container, CI) — the only platform where the
  Mosaic lowering exists.

The probe result is cached for the life of the process (jax's backend
choice is fixed once initialised).  Tests that monkeypatch the env var must
call ``probe_cache_clear()``.

``scoring_backend()`` is the hot-path variant of the same decision: the
fused (ce, pa, pc) scoring inside the train step should run the Pallas
kernel only where it compiles ("kernel"); under the interpreter it would be
orders of magnitude slower than XLA, so the hot path falls back to the
fused one-pass jnp reference ("reference") — the interpreted kernel stays
reachable explicitly, for the parity suites.  ``attention_backend()`` makes
the same decision for ``models.attention.attend``'s flash kernel.
"""
from __future__ import annotations

import functools
import os

import jax

#: Env override: "0"/"false" compiles the kernels, anything truthy forces
#: interpret mode. Unset = probe the jax backend.
ENV_VAR = "REPRO_PALLAS_INTERPRET"

_FALSY = ("0", "false", "no", "off", "")


@functools.lru_cache(maxsize=None)
def use_interpret() -> bool:
    """True when Pallas kernels should run in interpret mode."""
    env = os.environ.get(ENV_VAR)
    if env is not None:
        return env.strip().lower() not in _FALSY
    return jax.default_backend() != "tpu"


def resolve(interpret: bool | None) -> bool:
    """The per-call ``interpret=`` default: explicit wins, else the probe."""
    return use_interpret() if interpret is None else bool(interpret)


def backend_name() -> str:
    """"interpret" or "pallas" — the label BENCH records carry."""
    return "interpret" if use_interpret() else "pallas"


def scoring_backend() -> str:
    """Hot-path dispatch for the fused scoring: "kernel" where the Pallas
    kernel compiles, "reference" (fused one-pass jnp) under the interpreter."""
    return "reference" if use_interpret() else "kernel"


#: Dispatch for ``models.attention.attend``'s flash kernel: the same answer.
attention_backend = scoring_backend


def probe_cache_clear() -> None:
    """Forget the cached probe (tests that flip ``REPRO_PALLAS_INTERPRET``)."""
    use_interpret.cache_clear()
