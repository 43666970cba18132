"""The benchmark's model FLOP count, against numbers worked by hand."""

import dataclasses
import json

import pytest

from benchmarks.chip import flops, manifest


def model(name):
    with open(manifest.HERE / "configs" / f"{name}.json") as f:
        return json.load(f)["model"]


# stabilityai/stablelm-2-1_6b at its published widths
STABLELM = {"d_model": 2048, "num_heads": 32, "num_kv_heads": 32,
            "head_dim": 64, "d_ff": 5632, "vocab_size": 100352,
            "tie_embeddings": False, "act": "swiglu"}


def test_qwen2_six_layers_at_4096():
    m = dict(model("qwen2-1.5b-f32"), num_layers=6)
    # 6 x (6 layers x 46.79M + the tied 1536 x 151936 head) = 3.085 GFLOP;
    # attention 6 x 6 x 12 x 128 x 4096 = 0.226 GFLOP
    assert flops.matmul_params(m) == 514_129_920
    assert flops.attention_flops_per_token(m, 4096) == 226_492_416
    per_token = flops.train_flops_per_token(m, 4096)
    assert per_token == pytest.approx(3.31e9, abs=0.005e9)
    assert per_token * 32768 == pytest.approx(108.5e12, abs=0.05e12)


@pytest.mark.parametrize("depth", [1, 2, 4, 6])
def test_stablelm_per_step(depth):
    m = dict(STABLELM, num_layers=depth)
    # per layer 4 x 2048^2 + 3 x 2048 x 5632 = 51,380,224 matmul
    # parameters, the untied head 2048 x 100352 = 205,520,896, attention
    # 6 x 4096 x 32 x 64 = 50,331,648 per layer; the embedding is a gather
    step = 32768 * (6 * (51_380_224 * depth + 205_520_896)
                    + 50_331_648 * depth)
    assert 32768 * flops.train_flops_per_token(m, 4096) == step
    if depth == 4:
        assert step == pytest.approx(87.4e12, abs=0.05e12)


def _resolved(arch, model):
    from repro.models import registry
    from benchmarks.chip import harness
    cfg = harness.with_settings(registry.get_arch(arch).cfg, model)
    return dataclasses.asdict(cfg)


def test_qwen2_cell_as_built():
    """``step_mfu``'s count in the qwen2 cell, from the configuration as
    the harness builds it: the same integer as from the file's own
    numbers."""
    import jax
    from benchmarks.chip import harness
    cell = manifest.load_cell("qwen2-1.5b-f32.train4k-fill")
    b = harness.build(cell, jax.devices()[:1])
    assert harness.flops_per_token(b) == 3_311_271_936


def test_deepseek_moe_share_at_4096():
    # deepseek-moe-16b on one chip: 6 layers, 8 of 64 experts, 12800 of
    # the vocabulary, untied.  Dense layer 0: 16,777,216 attention +
    # 3 x 2048 x 10944 FFN; each expert layer: the same attention, the
    # router 2048 x 64, 2 shared experts 3 x 2048 x 2816, and of the
    # routed experts 6 x 8/64 x 3 x 2048 x 1408 = 6,488,064; the head
    # 2048 x 12800.  Attention 6 x 6 x 16 x 128 x 4096.
    m = _resolved("deepseek-moe-16b", {"num_layers": 6, "vocab_size": 12800,
                                       "moe": {"num_experts": 8}})
    published = {"num_layers": 28, "vocab_size": 102400,
                 "moe.num_experts": 64}
    assert flops.layer_matmul_params(m, 0, published) == 84_017_152
    assert flops.layer_matmul_params(m, 1, published) == 40_697_856
    assert flops.matmul_params(m, published) == 313_720_832
    assert flops.attention_flops_per_token(m, 4096) == 301_989_888
    assert flops.train_flops_per_token(m, 4096, published) == 2_184_314_880
    # with every expert held the router spans them all: 6 x 3 x 2048 x
    # 1408 routed per layer
    whole = _resolved("deepseek-moe-16b", {"num_layers": 6})
    assert flops.layer_matmul_params(whole, 1, {}) == \
        16_777_216 + 131_072 + 17_301_504 + 51_904_512


def test_sliding_window_by_hand():
    # 3 layers of which the last is global; window 16 of 64 positions: a
    # local query reads 16 (1 - 16/128) = 14 keys on average, a global
    # one 32.  Attention 12 x 4 x 16 x (14 + 14 + 32) = 46,080; per layer
    # 3 x 64 x 64 + 2 x 64 x 32 + 3 x 64 x 128 = 36,864 matmul
    # parameters, the head 64 x 100.
    m = {"num_layers": 3, "d_model": 64, "num_heads": 4, "num_kv_heads": 2,
         "head_dim": 16, "d_ff": 128, "vocab_size": 100, "act": "swiglu",
         "sliding_window": 16, "global_interval": 3}
    assert flops.mean_keys(16, 64) == 14 and flops.mean_keys(0, 64) == 32
    assert flops.attention_flops_per_token(m, 64) == 46_080
    assert flops.train_flops_per_token(m, 64) == 6 * (3 * 36_864 + 6_400) \
        + 46_080
    # a window as long as the sequence is full causal attention
    assert flops.attention_flops_per_token(
        dict(m, sliding_window=64), 64) == 12 * 4 * 16 * 32 * 3


@pytest.mark.parametrize("arch,what", [
    ("jamba-v0.1-52b", "Mamba"), ("xlstm-125m", "mLSTM/sLSTM"),
    ("whisper-base", "encoder-decoder"), ("phi-3-vision-4.2b", "vision"),
])
def test_uncovered_mixer_raises(arch, what):
    m = _resolved(arch, {})
    with pytest.raises(ValueError, match=what):
        flops.train_flops_per_token(m, 4096)
