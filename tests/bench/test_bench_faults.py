"""A run whose timed path is broken underneath comes out not correct.

Each test drives the whole of a run (``harness.run_cell``) at a size the
CPU holds, past the harness's look for a chip, with one fault of
``faults.py`` planted in the program, and with the cell's own limits.
The sound run comes out correct.
"""

import time

import jax
import pytest

from benchmarks.chip import faults, harness

CELL = "qwen2-1.5b-f32.train4k-fill"


def _run(cell, seed=2**31 + 7):
    return harness.run_cell(cell, seed, 0.3, False, jax.devices(),
                            time.perf_counter())


def test_sound_run_is_correct(tiny):
    r = _run(tiny(CELL))
    assert r["correct"], r["checks"]
    assert r["attempted"] >= 1 and r["failed"] == 0
    assert list(r)[-1] == "checks"


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "loss_altered"])
def test_fault_is_caught(tiny, fault):
    with faults.FAULTS[fault]():
        r = _run(tiny(CELL))
    assert not r["correct"], r["checks"]

