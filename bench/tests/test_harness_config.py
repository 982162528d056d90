"""What the harness takes from a configuration's and a cell's own files:
nested configuration fields, the configuration's own FLOP count, the
cell's chips in ``mfu``, and the fullest chip's memory peak."""
import dataclasses
import pathlib
import types

import pytest

from bench import flops, run
from bench.metrics import mfu

DATA = pathlib.Path(__file__).resolve().parent / "data"


def test_nested_dicts_become_their_dataclasses():
    from repro.configs.base import ArchConfig, MoEConfig, SSMConfig
    arch = run.build_config(ArchConfig, dict(
        name="x", family="hybrid", num_layers=2, d_model=64, num_heads=4,
        num_kv_heads=2, d_ff=128, vocab_size=257,
        moe={"num_experts": 8, "top_k": 2, "d_ff_expert": 32},
        ssm={"state_dim": 16, "head_dim": 16}))
    assert arch.moe == MoEConfig(num_experts=8, top_k=2, d_ff_expert=32)
    assert arch.ssm == SSMConfig(state_dim=16, head_dim=16)
    hash(arch)


def test_json_lists_become_tuples():
    from repro.core import KakurenboConfig
    kak = run.build_config(KakurenboConfig, {
        "fraction_alphas": [1.0, 0.5], "fraction_milestones": [0, 10]})
    assert kak.fraction_alphas == (1.0, 0.5)
    assert kak.fraction_milestones == (0, 10)


def test_a_moe_programs_arch_from_its_own_module():
    from repro.configs.base import ArchConfig, MoEConfig
    ref = run.load_module(DATA / "tiny-moe.py")
    kw = ref.program_arch(run.load_json(DATA / "tiny-moe.json"))
    assert isinstance(kw["moe"], dict)
    arch = run.build_config(ArchConfig, kw)
    assert isinstance(arch.moe, MoEConfig) and arch.moe.top_k == 2
    with pytest.raises(ValueError, match="no FLOP count"):
        flops.matmul_params(arch)


@dataclasses.dataclass
class Epoch:
    bwd_samples: int
    fwd_samples: int


def record(chips, model_ref=None):
    from repro.configs.base import ArchConfig
    arch = ArchConfig(name="smollm", family="dense", num_layers=30,
                      d_model=576, num_heads=9, num_kv_heads=3, d_ff=1536,
                      vocab_size=49152, head_dim=64)
    return types.SimpleNamespace(
        arch=arch, seq_len=512, epochs=[Epoch(256, 330), Epoch(176, 256)],
        window_s=2.5, chips=chips, cfg={}, peak={"bf16_flops_per_s": 197e12},
        model_ref=model_ref or types.SimpleNamespace())


def test_mfu_divides_by_every_chip_of_the_cell():
    one, four = mfu.read(record(1)), mfu.read(record(4))
    want = 100.0 * (432 * 512 * flops.train_flops_per_token(
        record(1).arch, 512) + 154 * 512 * flops.forward_flops_per_token(
        record(1).arch, 512)) / (2.5 * 197e12)
    assert one == want
    assert four == pytest.approx(one / 4, rel=1e-15)


def test_mfu_takes_the_configurations_own_count():
    own = types.SimpleNamespace(window_flops=lambda rec: (3e14, 1e14))
    assert mfu.read(record(2, own)) == pytest.approx(
        100.0 * 4e14 / (2.5 * 2 * 197e12), rel=1e-15)


class Device:
    def __init__(self, i, stats):
        self.id, self.stats = i, stats

    def memory_stats(self):
        return self.stats


def test_memory_peak_is_the_fullest_devices():
    devices = [Device(0, {"peak_bytes_in_use": 5, "peak_bytes_reserved": 2}),
               Device(1, {"peak_bytes_in_use": 6, "peak_bytes_reserved": 3}),
               Device(2, None),
               Device(3, {"peak_bytes_in_use": 8})]
    assert run.memory_peak_bytes(devices) == 9
    assert run.memory_peak_bytes(devices[:1]) == 7
