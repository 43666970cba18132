"""Shared set-up of the benchmark's CPU tests: the checkout root (for
``benchmarks.chip``) and ``src`` on the path, and tiny copies of the
benchmark's cells."""

import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
for p in (ROOT, ROOT / "src"):
    if str(p) not in sys.path:
        sys.path.insert(0, str(p))


def tiny_cell(name: str):
    """The cell ``name`` as BENCHMARK.json has it, with its limits, at a
    size the CPU holds: every width cut, the sequence cut to 64."""
    from benchmarks.chip import manifest
    cell = manifest.load_cell(name, ROOT)
    m = cell.config["model"]
    gqa = m["num_kv_heads"] < m["num_heads"]
    m.update(num_layers=2, d_model=64, num_heads=4,
             num_kv_heads=2 if gqa else 4, head_dim=16, d_ff=128,
             vocab_size=512)
    cell.traffic.update(seq_len=64)
    return cell


def tiny_moe_cell():
    """The registry's deepseek-moe-16b at the reduced widths the CPU holds,
    written as a configuration file writes a chip's share: a dense first
    layer and two expert layers, 8 experts held of the 64 the router spans,
    expert parallelism over the data axis; the traffic of the qwen2 cell at
    2 rows.  No plain reference covers experts yet, so it names none."""
    import dataclasses
    cell = tiny_cell("qwen2-1.5b-f32.train4k-fill")
    config = {
        "arch": "deepseek-moe-16b",
        "model": {"num_layers": 3, "d_model": 64, "num_heads": 4,
                  "num_kv_heads": 4, "head_dim": 16, "d_ff": 128,
                  "vocab_size": 512, "dtype": "float32",
                  "moe": {"num_experts": 8, "d_expert": 32,
                          "shared_d_expert": 32}},
        "parallel": {"zero": 1, "ep_axis": "data", "tp_enabled": False,
                     "comm_strategy": "mgwfbp", "pack_kernel": False},
        "published": {"moe.num_experts": 64}}
    return dataclasses.replace(
        cell, name="deepseek-moe-16b.tiny", config_name="deepseek-moe-16b",
        config=config, traffic=dict(cell.traffic, batch_per_chip=2))


@pytest.fixture
def tiny():
    return tiny_cell


@pytest.fixture
def tiny_moe():
    return tiny_moe_cell
