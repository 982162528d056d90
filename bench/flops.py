"""Model FLOPs per token, computed from a configuration's shapes.

Counts the multiply-adds the model's mathematics needs (2 FLOPs each), with
no recomputation: every weight matmul, the attention score and value
products over the full (masked, not skipped) ``S x S`` square, and for SSD
mixers the chunked state-space-duality products at the configured chunk
length.  Elementwise work (norms, activations, softmax, the optimizer) is
not counted.  The backward pass is twice the forward.

Works from anything with the attributes of the program's ``ArchConfig``
(``family``, ``num_layers``, ``d_model``, ``num_heads``, ``num_kv_heads``,
``resolved_head_dim``, ``d_ff``, ``vocab_size``, ``tie_embeddings``,
``ssm``), so the count follows the configuration as it is run.
"""
from __future__ import annotations


def _attention_params(c) -> int:
    hd = c.resolved_head_dim
    return (c.d_model * c.num_heads * hd            # q
            + 2 * c.d_model * c.num_kv_heads * hd  # k, v
            + c.num_heads * hd * c.d_model)        # o


def _ssd_dims(c):
    s = c.ssm
    d_inner = s.d_inner or s.expand * c.d_model
    return d_inner, d_inner // s.head_dim, s.state_dim, s.head_dim, s.chunk


def matmul_params(c) -> int:
    """Weights that take part in a matmul per token (head included once)."""
    per_layer = 0
    if c.family == "dense":
        per_layer = _attention_params(c) + 3 * c.d_model * c.d_ff
    elif c.family == "ssm":
        d_inner, nh, n, _, _ = _ssd_dims(c)
        conv = c.ssm.conv_width * (d_inner + 2 * n)
        per_layer = (c.d_model * (2 * d_inner + 2 * n + nh) + conv
                     + d_inner * c.d_model)
    else:
        raise ValueError(f"no FLOP count for family {c.family!r}")
    return c.num_layers * per_layer + c.d_model * c.vocab_size


def mixer_flops_per_token(c, seq_len: int) -> int:
    """Forward FLOPs per token of the sequence mixer beyond its weights."""
    if c.family == "dense":
        # QK^T and PV over the full square: 2 products x 2 FLOPs x S x H x Dh.
        return c.num_layers * 4 * seq_len * c.num_heads * c.resolved_head_dim
    d_inner, nh, n, p, chunk = _ssd_dims(c)
    chunk = min(chunk, seq_len)
    # Per token and layer: C.B within the chunk (shared by the heads), the
    # intra-chunk y, the chunk state and the carried-in state's output.
    return c.num_layers * 2 * (chunk * n + chunk * nh * p + 2 * nh * n * p)


def forward_flops_per_token(c, seq_len: int) -> int:
    return 2 * matmul_params(c) + mixer_flops_per_token(c, seq_len)


def train_flops_per_token(c, seq_len: int) -> int:
    """Forward plus backward (twice the forward)."""
    return 3 * forward_flops_per_token(c, seq_len)
