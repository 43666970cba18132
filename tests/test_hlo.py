"""HLO cost parser: validated against XLA's own cost_analysis on loop-free
programs; trip-count scaling validated against manual unrolling."""

import jax
import jax.numpy as jnp
import pytest

from repro.utils import hlo


def _flops(fn, *specs):
    comp = jax.jit(fn).lower(*specs).compile()
    return comp, hlo.analyze(comp.as_text())


def test_matches_xla_on_loop_free():
    d = 128

    def f(x, w1, w2):
        return jnp.tanh(x @ w1) @ w2

    x = jax.ShapeDtypeStruct((64, d), jnp.float32)
    w = jax.ShapeDtypeStruct((d, d), jnp.float32)
    comp, ours = _flops(f, x, w, w)
    xla = comp.cost_analysis()["flops"]
    # dot flops dominate; ours counts only dots, XLA adds elementwise
    assert ours.dot_flops == pytest.approx(2 * 2 * 64 * d * d)
    assert abs(ours.dot_flops - xla) / xla < 0.01


def test_scan_trip_count_scaling():
    d, L = 64, 7

    def scanned(x, ws):
        def body(c, w):
            return jnp.tanh(c @ w), None
        c, _ = jax.lax.scan(body, x, ws)
        return c.sum()

    def unrolled(x, ws):
        for i in range(L):
            x = jnp.tanh(x @ ws[i])
        return x.sum()

    x = jax.ShapeDtypeStruct((32, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((L, d, d), jnp.float32)
    _, s = _flops(scanned, x, ws)
    _, u = _flops(unrolled, x, ws)
    assert s.flops == pytest.approx(u.flops, rel=0.01)
    assert s.flops == pytest.approx(2 * 32 * d * d * L, rel=0.01)


def test_nested_scan():
    d = 32

    def f(x, ws):
        def outer(c, w):
            def inner(c2, _):
                return jnp.tanh(c2 @ w), None
            c2, _ = jax.lax.scan(inner, c, None, length=3)
            return c2, None
        c, _ = jax.lax.scan(outer, x, ws)
        return c.sum()

    x = jax.ShapeDtypeStruct((8, d), jnp.float32)
    ws = jax.ShapeDtypeStruct((4, d, d), jnp.float32)
    _, c = _flops(f, x, ws)
    assert c.flops == pytest.approx(2 * 8 * d * d * 3 * 4, rel=0.02)


def test_tuple_typed_while_ops_parsed():
    """Regression: tuple output types contain spaces + /*index=N*/ comments
    which previously defeated the op regex."""
    line = ("  %while.319 = (s32[], f32[8,1,1,4096]{3,2,1,0}, "
            "/*index=5*/f32[8,4096]{1,0}) while(%tuple.1), "
            "condition=%cond.1, body=%body.1")
    m = hlo._OP_RE.match(line)
    assert m is not None
    assert m.group(3) == "while"


def test_shape_bytes():
    assert hlo._shape_bytes("f32[8,4]{1,0}") == 128
    assert hlo._shape_bytes("bf16[10]") == 20
    assert hlo._shape_bytes("(s32[], f32[4])") == 20
    assert hlo._shape_bytes("pred[3]") == 3


def test_collective_bytes_empty():
    assert hlo.collective_bytes("") == {}
