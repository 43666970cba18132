"""Per-kernel allclose vs pure-jnp oracles, shape/dtype sweeps
(interpret=True executes the kernel body on CPU).

The randomized shape sweeps live in tests/test_kernels_props.py
(hypothesis)."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bucket_pack import ops as bp_ops, ref as bp_ref
from repro.kernels.flash_attention import ops as fa_ops, ref as fa_ref
from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref


# ---------------------------------------------------------------------------
# flash attention
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("b,sq,skv,hq,hkv,d", [
    (2, 128, 128, 4, 2, 64),
    (1, 100, 100, 8, 8, 32),
    (2, 257, 257, 4, 1, 128),
    (1, 64, 64, 2, 2, 96),
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 37),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_matches_ref(b, sq, skv, hq, hkv, d, causal,
                                     window, dtype):
    q = jax.random.normal(jax.random.PRNGKey(0), (b, sq, hq, d), dtype)
    k = jax.random.normal(jax.random.PRNGKey(1), (b, skv, hkv, d), dtype)
    v = jax.random.normal(jax.random.PRNGKey(2), (b, skv, hkv, d), dtype)
    o = fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                               block_q=64, block_k=64, interpret=True)
    r = fa_ref.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)


def test_flash_attention_rejects_bad_gqa():
    q = jnp.zeros((1, 8, 3, 16))
    k = jnp.zeros((1, 8, 2, 16))
    with pytest.raises(ValueError):
        fa_ops.flash_attention(q, k, v=k, interpret=True)


# ---------------------------------------------------------------------------
# bucket pack
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shapes", [
    [(33,), (128, 7), (512,)],
    [(1,)],
    [(5, 5), (1000,), (3, 5, 7), (2048,), (17,)],
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_bucket_pack_roundtrip(shapes, dtype):
    leaves = [jax.random.normal(jax.random.PRNGKey(i), s).astype(dtype)
              for i, s in enumerate(shapes)]
    packed = bp_ops.pack(leaves, interpret=True)
    rref = bp_ref.pack_ref(leaves)
    np.testing.assert_array_equal(np.asarray(packed, np.float32),
                                  np.asarray(rref, np.float32))
    outs = bp_ops.unpack(packed, [l.shape for l in leaves],
                         [l.dtype for l in leaves], interpret=True)
    for o, l in zip(outs, leaves):
        np.testing.assert_array_equal(np.asarray(o, np.float32),
                                      np.asarray(l, np.float32))


def test_bucket_pack_many_leaves_chunked():
    """> MAX_SRCS_PER_CALL leaves exercises the chunked path."""
    leaves = [jnp.full((7,), float(i)) for i in range(40)]
    packed = bp_ops.pack(leaves, interpret=True)
    rref = bp_ref.pack_ref(leaves)
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(rref))


def test_bucket_pack_mixed_dtype_default_promotes():
    """ops.pack / pack_ref / core.bucketer.pack share ONE default dtype
    rule (result_type promotion) — mixed-dtype buckets used to diverge
    (ops followed leaves[0].dtype, bucketer promoted)."""
    leaves = [jnp.ones((33,), jnp.bfloat16),
              jnp.full((70,), 2.0, jnp.float32)]
    packed = bp_ops.pack(leaves, interpret=True)
    rref = bp_ref.pack_ref(leaves)
    assert packed.dtype == rref.dtype == jnp.float32
    np.testing.assert_array_equal(np.asarray(packed), np.asarray(rref))


def test_bucket_pack_fallback_layout_identical():
    """Kernel pack and unpack agree bit for bit with the jnp oracle in
    ref.py, on slots big enough for several row blocks and on offsets
    that force the smallest block."""
    from repro.kernels.bucket_pack.kernel import MAX_BLOCK_ROWS, TILE
    shapes = [(33,), (4 * MAX_BLOCK_ROWS, 128), (3, TILE), (512,)]
    leaves = [jax.random.normal(jax.random.PRNGKey(i), s)
              for i, s in enumerate(shapes)]
    packed = bp_ops.pack(leaves, jnp.bfloat16, interpret=True)
    ref = bp_ref.pack_ref(leaves, jnp.bfloat16)
    assert packed.dtype == ref.dtype == jnp.bfloat16
    np.testing.assert_array_equal(np.asarray(packed, np.float32),
                                  np.asarray(ref, np.float32))
    dtypes = [l.dtype for l in leaves]
    outs = bp_ops.unpack(packed, shapes, dtypes, interpret=True)
    refs = bp_ref.unpack_ref(ref, shapes, dtypes)
    for o, r in zip(outs, refs):
        assert o.dtype == r.dtype and o.shape == r.shape
        np.testing.assert_array_equal(np.asarray(o), np.asarray(r))


# ---------------------------------------------------------------------------
# rmsnorm
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("shape", [(4, 64, 128), (100, 300), (7, 13, 65),
                                   (1, 256)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_rmsnorm_matches_ref(shape, dtype):
    x = jax.random.normal(jax.random.PRNGKey(0), shape).astype(dtype)
    s = jax.random.normal(jax.random.PRNGKey(1), shape[-1:]).astype(dtype)
    o = rn_ops.rmsnorm(x, s, block_rows=64, interpret=True)
    r = rn_ref.rmsnorm_ref(x, s)
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=2e-2 if dtype == jnp.bfloat16 else 1e-5,
                               atol=1e-5)


