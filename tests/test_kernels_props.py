"""Kernel property sweeps (interpret=True on CPU); skipped without the
real hypothesis package."""

import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")

import hypothesis  # noqa: E402
import hypothesis.strategies as st  # noqa: E402
import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.kernels.flash_attention import ops as fa_ops  # noqa: E402
from repro.kernels.rmsnorm import ops as rn_ops  # noqa: E402
from repro.models import layers  # noqa: E402


@hypothesis.given(
    st.integers(1, 2), st.integers(3, 80), st.integers(1, 3),
    st.sampled_from([16, 32, 64]), st.booleans())
@hypothesis.settings(max_examples=12, deadline=None)
def test_flash_attention_property(b, s, g, d, causal):
    hkv = 2
    hq = hkv * g
    q = jax.random.normal(jax.random.PRNGKey(3), (b, s, hq, d))
    k = jax.random.normal(jax.random.PRNGKey(4), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(5), (b, s, hkv, d))
    o = fa_ops.flash_attention(q, k, v, causal=causal, block_q=32,
                               block_k=32, interpret=True)
    r = layers.attention_ref(q, k, v, causal=causal)
    np.testing.assert_allclose(np.asarray(o), np.asarray(r), rtol=3e-5,
                               atol=3e-5)


@hypothesis.given(st.integers(1, 50), st.sampled_from([8, 96, 128, 200]))
@hypothesis.settings(max_examples=10, deadline=None)
def test_rmsnorm_property(rows, d):
    x = jax.random.normal(jax.random.PRNGKey(rows), (rows, d))
    s = jnp.ones((d,))
    o = rn_ops.rmsnorm(x, s, block_rows=32, interpret=True)
    # unit-RMS property
    rms = np.sqrt(np.mean(np.asarray(o) ** 2, -1))
    np.testing.assert_allclose(rms, 1.0, rtol=2e-2)
