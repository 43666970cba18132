"""Bucket assembly: ordering, pack/unpack, bucketed apply."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bucketer
from repro.core.planner import MergePlan, TensorSpec, plan_fixed_size


def _tree():
    return {"a": {"w": jnp.arange(6.0).reshape(2, 3),
                  "b": jnp.ones((4,))},
            "z": jnp.full((2, 2), 3.0)}


def test_backward_order_is_reversed_flatten():
    tree = _tree()
    order = [p for p, _ in bucketer.leaves_in_backward_order(tree)]
    fwd = [jax.tree_util.keystr(p) for p, _ in
           jax.tree_util.tree_flatten_with_path(tree)[0]]
    assert order == list(reversed(fwd))


def test_leaf_metadata():
    metas = bucketer.leaf_metadata(_tree())
    assert [m.size for m in metas] == [4, 6, 4]
    assert metas[0].path == "['z']"
    assert metas[0].nbytes == 16


def test_pack_unpack_roundtrip():
    tree = _tree()
    metas = bucketer.leaf_metadata(tree)
    leaves = [v for _, v in bucketer.leaves_in_backward_order(tree)]
    buf = bucketer.pack(leaves)
    assert buf.shape == (14,)
    outs = bucketer.unpack(buf, metas)
    for o, l in zip(outs, leaves):
        np.testing.assert_allclose(np.asarray(o), np.asarray(l))


def test_unpack_size_mismatch():
    metas = bucketer.leaf_metadata(_tree())
    with pytest.raises(ValueError):
        bucketer.unpack(jnp.zeros(13), metas)


def test_apply_bucketed_identity():
    tree = _tree()
    metas = bucketer.leaf_metadata(tree)
    specs = [TensorSpec(m.path, m.nbytes, 1e-3) for m in metas]
    plan = plan_fixed_size(specs, 30)
    out = bucketer.apply_bucketed(tree, plan, lambda buf: buf * 2.0)
    for (_, a), (_, b) in zip(
            bucketer.leaves_in_backward_order(out),
            bucketer.leaves_in_backward_order(tree)):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b) * 2.0)


def test_apply_bucketed_plan_mismatch():
    tree = _tree()
    plan = MergePlan(((0,), (1,)))  # only 2 tensors, tree has 3
    with pytest.raises(ValueError):
        bucketer.apply_bucketed(tree, plan, lambda b: b)


def test_pack_unpack_kernel_layout_roundtrip():
    """use_kernel=True speaks the TILE-aligned slot layout end to end."""
    tree = _tree()
    metas = bucketer.leaf_metadata(tree)
    leaves = [v for _, v in bucketer.leaves_in_backward_order(tree)]
    buf = bucketer.pack(leaves, use_kernel=True)
    assert buf.shape == (bucketer.packed_elems(metas, aligned=True),)
    outs = bucketer.unpack(buf, metas, use_kernel=True)
    for o, l in zip(outs, leaves):
        np.testing.assert_allclose(np.asarray(o), np.asarray(l))
    # layout mismatch is loud: an aligned buffer fed to the plain unpack
    with pytest.raises(ValueError):
        bucketer.unpack(buf, metas, use_kernel=False)


@pytest.mark.parametrize("n", [1, 4])
@pytest.mark.parametrize("nelems", [1, 4096, 280173056])
def test_padded_elems_gives_whole_tiles_per_shard(nelems, n):
    """ZeRO-1 bucket buffers split into n shards of whole kernel TILEs
    (qwen2-1.5b's single bucket, 280173056 elements at one layer, left a
    shard ending inside a tile), padding by less than one TILE per shard."""
    from repro.core import comm
    from repro.kernels.bucket_pack.kernel import TILE
    padded = comm.padded_elems(nelems, n)
    assert padded % (n * TILE) == 0
    assert 0 <= padded - nelems < n * TILE


def test_slot_elems_and_packed_elems():
    from repro.kernels.bucket_pack.kernel import TILE
    assert bucketer.slot_elems(5) == 5
    assert bucketer.slot_elems(5, aligned=True) == TILE
    assert bucketer.slot_elems(TILE, aligned=True) == TILE
    metas = bucketer.leaf_metadata(_tree())
    assert bucketer.packed_elems(metas) == sum(m.size for m in metas)
    assert bucketer.packed_elems(metas, aligned=True) == \
        sum(bucketer.slot_elems(m.size, aligned=True) for m in metas)


def test_pack_mixed_dtype_matches_ops_default():
    """bucketer.pack and kernels ops.pack agree on the promoted dtype."""
    from repro.kernels.bucket_pack import ops as bp_ops
    leaves = [jnp.ones((3,), jnp.bfloat16), jnp.full((4,), 2.0, jnp.float32)]
    a = bucketer.pack(leaves)
    b = bp_ops.pack(leaves)
    assert a.dtype == b.dtype == jnp.float32


def test_apply_bucketed_kernel_matches_plain():
    tree = _tree()
    metas = bucketer.leaf_metadata(tree)
    specs = [TensorSpec(m.path, m.nbytes, 1e-3) for m in metas]
    plan = plan_fixed_size(specs, 30)
    plain = bucketer.apply_bucketed(tree, plan, lambda buf: buf * 2.0)
    kern = bucketer.apply_bucketed(tree, plan, lambda buf: buf * 2.0,
                                   use_kernel=True)
    for (_, a), (_, b) in zip(bucketer.leaves_in_backward_order(plain),
                              bucketer.leaves_in_backward_order(kern)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_tensor_specs_backward_order():
    specs = bucketer.tensor_specs(_tree(), lambda m: m.size * 1e-6)
    assert [s.name for s in specs] == ["['z']", "['a']['w']", "['a']['b']"]
    assert specs[0].t_b == pytest.approx(4e-6)
