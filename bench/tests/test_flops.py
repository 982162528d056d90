"""bench/flops.py against counts made by hand from the published shapes."""
import dataclasses

from bench import flops


@dataclasses.dataclass
class SSM:
    state_dim: int = 128
    head_dim: int = 64
    expand: int = 2
    conv_width: int = 4
    chunk: int = 256
    d_inner: int | None = None


@dataclasses.dataclass
class Arch:
    family: str
    num_layers: int
    d_model: int
    num_heads: int
    num_kv_heads: int
    d_ff: int
    vocab_size: int
    tie_embeddings: bool
    head_dim: int | None = None
    ssm: SSM | None = None

    @property
    def resolved_head_dim(self):
        return self.head_dim or self.d_model // self.num_heads


SMOLLM = Arch("dense", 30, 576, 9, 3, 1536, 49152, True, 64)
MAMBA2 = Arch("ssm", 24, 768, 0, 0, 0, 50288, True, ssm=SSM())


def test_smollm_135m_at_512():
    # Per layer: q 576*576, k and v 576*192 each, o 576*576, SwiGLU
    # 3*576*1536; 30 layers; the tied head 576*49152 (its matmul is made
    # all the same).
    layer = 331_776 + 2 * 110_592 + 331_776 + 2_654_208
    weights = 30 * layer + 28_311_552
    assert flops.matmul_params(SMOLLM) == weights == 134_479_872
    attention = 30 * 4 * 512 * 9 * 64
    assert flops.forward_flops_per_token(SMOLLM, 512) == 2 * weights + attention
    assert flops.train_flops_per_token(SMOLLM, 512) == 913_047_552  # 0.91 G


def test_mamba2_130m_at_512():
    # Per layer: in_proj 768*(2*1536 + 2*128 + 24 heads), conv 4*1792,
    # out_proj 1536*768; 24 layers; tied head 768*50288 (50277 padded to a
    # multiple of 16).
    layer = 768 * 3352 + 4 * 1792 + 1536 * 768
    weights = 24 * layer + 768 * 50288
    assert flops.matmul_params(MAMBA2) == weights == 128_888_832
    assert 3 * 2 * weights == 773_332_992  # 0.77 G before the SSD
    # SSD per token and layer at chunk 256: C.B 256*128, intra-chunk y
    # 256*24*64, chunk state and carried-in output 24*128*64 each.
    ssd = 24 * 2 * (256 * 128 + 256 * 24 * 64 + 2 * 24 * 128 * 64)
    assert flops.forward_flops_per_token(MAMBA2, 512) == 2 * weights + ssd
    assert flops.train_flops_per_token(MAMBA2, 512) == 891_297_792


def test_short_sequences_shrink_the_chunk():
    assert (flops.mixer_flops_per_token(MAMBA2, 64)
            == 24 * 2 * (64 * 128 + 64 * 24 * 64 + 2 * 24 * 128 * 64))
