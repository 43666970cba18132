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


@pytest.fixture
def tiny():
    return tiny_cell
