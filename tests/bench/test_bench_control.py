"""The check's control comes out not correct.

The control is the plain reference one precision below the
configuration's float32: its parameters stored in bfloat16, and every
matrix product's operands (and, in the backward pass, its cotangent)
rounded to bfloat16.  Put in the program's place and compared with the
reference in float32, it has to fail the cell's limits.  On the chip it
does so at the cell's own size (see PERF.md); here at a size the CPU holds.
"""

import jax
import pytest

from benchmarks.chip import check, harness
from benchmarks.chip.reference import dense_decoder

CELLS = ["qwen2-1.5b-f32.train4k-fill"]


@pytest.mark.parametrize("cell", CELLS)
def test_control_fails_the_limits(tiny, cell):
    c = tiny(cell)
    b = harness.build(c, jax.devices()[:1])
    seed = 2**32 + 11
    ref = harness.reference_readings(b, seed)
    ctl = harness.reference_readings(b, seed, control=True)
    values, _ = check.readings(ctl, ref, [e[0] for e in b.ents])
    ok, checks = check.judge(values, c.limits)
    assert not ok, checks


def test_control_is_one_precision_below():
    model, dot = dense_decoder.control({"dtype": "float32", "d_model": 8})
    assert model == {"dtype": "bfloat16", "d_model": 8}
    # bfloat16 keeps 8 bits of significand: 1 + 2^-8 rounds to even (1.0),
    # 1 + 3 x 2^-8 to 1 + 2^-6
    a = jax.numpy.asarray([[1.0 + 2**-8, 1.0 + 3 * 2**-8]])
    b = jax.numpy.asarray([[1.0], [0.0]])
    assert float(dot("ij,jk->ik", a, b)[0, 0]) == 1.0
    b = jax.numpy.asarray([[0.0], [1.0]])
    assert float(dot("ij,jk->ik", a, b)[0, 0]) == 1.0 + 2**-6
