"""Small sizes of the benchmark's configurations, for CPU tests only.

Each configuration's JSON gives them in its ``"cpu"`` block: ``"tiny"``,
the keys that ``tiny()`` overrides, and ``"depth_key"``, the key of its
layer count.  A benchmark run never reads the block.
"""
from __future__ import annotations

import copy


def shallow(cfg: dict) -> dict:
    """``cfg`` at its published widths, 2 layers deep, 64 tokens: bfloat16
    rounding of the wide vocabulary and state shows as at full size."""
    out = copy.deepcopy(cfg)
    out[cfg["cpu"]["depth_key"]] = 2
    out["run"]["seq_len"] = 64
    out["run"]["reference_rows"] = 2
    return out


def shallow_full_length(cfg: dict) -> dict:
    """``shallow`` at the configuration's own sequence length, where a
    bfloat16 error that 64 tokens hide shows: mamba2's control reads 4 to
    13 times its gradient limits at 512 tokens and under them at 64."""
    out = shallow(cfg)
    out["run"]["seq_len"] = cfg["run"]["seq_len"]
    return out


def tiny(cfg: dict) -> dict:
    """``cfg`` at a size a CPU test can hold: 2 layers, 64 wide, 32 tokens."""
    out = copy.deepcopy(cfg)
    out.update(cfg["cpu"]["tiny"])
    out["run"]["seq_len"] = 32
    out["run"]["reference_rows"] = 2
    return out
