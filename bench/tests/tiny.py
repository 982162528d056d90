"""Tiny widths of the benchmark's configurations, for CPU tests only."""
from __future__ import annotations

import copy

TINY = {
    "smollm-135m": {"hidden_size": 64, "intermediate_size": 128,
                    "num_hidden_layers": 2, "num_attention_heads": 4,
                    "num_key_value_heads": 2, "head_dim": 16,
                    "vocab_size": 257},
    "mamba2-130m": {"d_model": 64, "n_layer": 2, "vocab_size": 257,
                    "ssm_cfg": {"layer": "Mamba2", "d_state": 16, "d_conv": 4,
                                "expand": 2, "headdim": 16, "ngroups": 1,
                                "chunk_size": 16}},
}


def shallow(cfg: dict) -> dict:
    """``cfg`` at its published widths, 2 layers deep, 64 tokens: bfloat16
    rounding of the wide vocabulary and state shows as at full size."""
    out = copy.deepcopy(cfg)
    out["num_hidden_layers" if "num_hidden_layers" in cfg else "n_layer"] = 2
    out["run"]["seq_len"] = 64
    out["run"]["reference_rows"] = 2
    return out


def tiny(cfg: dict) -> dict:
    """``cfg`` at a size a CPU test can hold: 2 layers, 64 wide, 32 tokens."""
    out = copy.deepcopy(cfg)
    out.update(TINY[cfg["name"]])
    out["run"]["seq_len"] = 32
    out["run"]["reference_rows"] = 2
    return out
