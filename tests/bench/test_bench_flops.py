"""The benchmark's model FLOP count, against numbers worked by hand."""

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
