"""Per-kernel allclose vs pure-jnp oracles, shape/dtype sweeps
(interpret=True executes the kernel body on CPU).

The randomized shape sweeps live in tests/test_kernels_props.py
(hypothesis)."""

import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.bucket_pack import ops as bp_ops, ref as bp_ref
from repro.kernels.flash_attention import ops as fa_ops
from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref
from repro.models import layers


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
    r = layers.attention_ref(q, k, v, causal=causal, window=window)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(o, np.float32),
                               np.asarray(r, np.float32),
                               rtol=tol, atol=tol)


def _qkv(b, sq, skv, hq, hkv, d, dtype, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    return (jax.random.normal(ks[0], (b, sq, hq, d), dtype),
            jax.random.normal(ks[1], (b, skv, hkv, d), dtype),
            jax.random.normal(ks[2], (b, skv, hkv, d), dtype),
            jax.random.normal(ks[3], (b, sq, hq, d), dtype))


def _grads(attend, q, k, v, ct):
    """(o, dq, dk, dv) of ``attend`` against the cotangent ``ct``, in
    float32."""
    o, pull = jax.vjp(attend, q, k, v)
    return [np.asarray(x, np.float32) for x in (o, *pull(ct))]


@pytest.mark.parametrize("b,s,hq,hkv,d", [
    (1, 100, 2, 2, 64),      # G = 1
    (2, 130, 4, 2, 32),      # G = 2
    (1, 96, 6, 1, 128),      # G = 6
])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 37),
                                           (False, 0)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention_grads_match_ref(b, s, hq, hkv, d, causal, window,
                                         dtype):
    """dq, dk and dv of the kernels' custom VJP against autodiff of
    ``layers.attention_ref``; every length is a non-multiple of the 64-row
    blocks, so the padding is masked in all three kernels."""
    q, k, v, ct = _qkv(b, s, s, hq, hkv, d, dtype)
    got = _grads(lambda q, k, v: fa_ops.flash_attention(
        q, k, v, causal=causal, window=window, block_q=64, block_k=64,
        interpret=True), q, k, v, ct)
    ref = _grads(lambda q, k, v: layers.attention_ref(
        q, k, v, causal=causal, window=window), q, k, v, ct)
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    for name, a, r in zip(("o", "dq", "dk", "dv"), got, ref):
        scale = max(1.0, float(np.abs(r).max()))
        np.testing.assert_allclose(a, r, rtol=tol, atol=tol * scale,
                                   err_msg=name)


@pytest.mark.parametrize("causal,window,bad_kv,clean_q,bad_q,clean_kv", [
    # causal: only the last q block reads the last KV block, and KV blocks
    # 1-3 are read by no row of q block 0
    (True, 0, 3, [0, 1, 2], 0, [1, 2, 3]),
    # a window of one block: q blocks 2 and 3 read nothing of KV block 0,
    # KV blocks 0 and 1 nothing of q block 3
    (True, 64, 0, [2, 3], 3, [0, 1]),
])
def test_flash_attention_skips_masked_blocks(causal, window, bad_kv, clean_q,
                                             bad_q, clean_kv):
    """S is four blocks.  A block full of NaN poisons every block computed
    against it (0 * NaN is NaN, masked or not).  With a KV block poisoned,
    o and dq stay exact on the q blocks that must skip it (forward and dQ
    kernels); with a q block poisoned, dk and dv stay exact on the KV
    blocks that must skip it (dK/dV kernel)."""
    blk = 64
    q, k, v, ct = _qkv(1, 4 * blk, 4 * blk, 4, 2, 32, jnp.float32)

    def kernel(q, k, v):
        return fa_ops.flash_attention(q, k, v, causal=causal, window=window,
                                      block_q=blk, block_k=blk,
                                      interpret=True)

    ref = _grads(lambda q, k, v: layers.attention_ref(
        q, k, v, causal=causal, window=window), q, k, v, ct)

    def check(got, names, blocks):
        for name in names:
            i = ("o", "dq", "dk", "dv").index(name)
            assert np.isnan(got[i]).any(), name     # the poison is live
            for j in blocks:
                rows = slice(j * blk, (j + 1) * blk)
                np.testing.assert_allclose(got[i][:, rows], ref[i][:, rows],
                                           rtol=2e-5, atol=2e-5,
                                           err_msg=f"{name} block {j}")

    poison = lambda x, j: x.at[:, j * blk:(j + 1) * blk].set(jnp.nan)  # noqa
    check(_grads(kernel, q, k, poison(v, bad_kv), ct), ("o", "dq"), clean_q)
    check(_grads(kernel, poison(q, bad_q), k, v, ct), ("dk", "dv"), clean_kv)


@pytest.mark.parametrize("bq,bk,seq", [(64, 64, 256), (128, 64, 300),
                                       (64, 128, 256)])
@pytest.mark.parametrize("causal,window", [(True, 0), (True, 100),
                                           (False, 0), (False, 70)])
def test_flash_attention_block_ranges_are_exact(bq, bk, seq, causal, window):
    """The blocks the kernels compute (and fetch) are exactly those with a
    kept entry, and the mask is skipped exactly on the blocks with none
    masked: checked against the element mask, block by block."""
    from repro.kernels.flash_attention.kernel import Geometry
    g = Geometry(head_dim=128, scale=1.0, causal=causal, window=window,
                 block_q=bq, block_k=bk, seq_q=seq, seq_kv=seq,
                 interpret=True)
    pos = np.arange(g.nq * bq)[:, None], np.arange(g.nk * bk)[None, :]
    keep = np.broadcast_to(pos[1] < seq, (g.nq * bq, g.nk * bk))
    if causal:
        keep = keep & (pos[1] <= pos[0])
    if window:
        keep = keep & (pos[1] > pos[0] - window)
    blocks = keep.reshape(g.nq, bq, g.nk, bk)
    needed = blocks.any(axis=(1, 3))
    for i in range(g.nq):
        lo, hi = (int(x) for x in g.kv_blocks(i))
        assert [j for j in range(g.nk) if lo <= j <= hi] == \
            list(np.flatnonzero(needed[i])), i
    for j in range(g.nk):
        lo, hi = (int(x) for x in g.q_blocks(j))
        assert [i for i in range(g.nq) if lo <= i <= hi] == \
            list(np.flatnonzero(needed[:, j])), j
    for i, j in zip(*np.nonzero(needed)):
        assert bool(g.partial(i, j)) == (not blocks[i, :, j].all()), (i, j)


@pytest.mark.parametrize("case,kernel", [
    ("self", True),                      # training and prefill
    ("window", True),                    # gemma3's local layers
    ("decode", False),                   # one query against a cache
    ("kv_len", False),                   # cache positions masked
    ("q_offset", False),                 # queries not from position 0
    ("head_64", False),                  # stablelm's head size
    ("cross", False),                    # sq != skv
    ("ragged", False),                   # S not a multiple of 128
    ("off_tpu", False),                  # any other backend
    ("gspmd", False),                    # a mesh axis the compiler shards
])
def test_attention_dispatch(monkeypatch, case, kernel):
    """``layers.attention`` takes the kernels exactly where the backend is
    a TPU and the shapes qualify, and the chunked scan otherwise; in
    interpret mode both give the same values."""
    calls = []
    real = fa_ops.flash_attention
    monkeypatch.setattr(layers.flash_ops, "flash_attention",
                        lambda *a, **kw: calls.append(kw) or real(*a, **kw))
    monkeypatch.setattr(layers, "_on_tpu", lambda: case != "off_tpu")
    sq, skv, d = {"decode": (1, 256, 128), "head_64": (256, 256, 64),
                  "cross": (128, 256, 128),
                  "ragged": (200, 200, 128)}.get(case, (256, 256, 128))
    q, k, v, _ = _qkv(1, sq, skv, 4, 2, d, jnp.float32)
    kw = {"causal": case != "cross", "window": 100 if case == "window" else 0,
          "chunk": 64,
          "kv_len": jnp.array([200]) if case == "kv_len" else None,
          "q_offset": 16 if case == "q_offset" else 0}
    mesh = (jax.set_mesh(jax.make_mesh((1,), ("model",))) if case == "gspmd"
            else contextlib.nullcontext())
    with mesh:
        o = layers.attention(q, k, v, **kw)
    assert len(calls) == int(kernel)
    monkeypatch.setattr(layers, "_on_tpu", lambda: False)
    scan = layers.attention(q, k, v, **kw)
    np.testing.assert_allclose(np.asarray(o), np.asarray(scan), rtol=2e-5,
                               atol=2e-5)


@pytest.mark.parametrize("seq,head_dim,block", [
    (4096, 128, 1024),       # qwen2: the largest block
    (4096, 256, 512),        # gemma3's head: half the rows in VMEM
    (1536, 128, 512),        # the largest that divides
    (384, 128, 128),
    (100, 128, 128),         # none divides: padded to the smallest
])
def test_flash_attention_block_size(seq, head_dim, block):
    assert fa_ops.block_size(seq, head_dim) == block


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


