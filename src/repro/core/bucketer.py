"""Bucket assembly: gradient pytrees <-> flat merged buffers.

The paper's §5.3 pre-allocates one contiguous buffer per merged-gradient
group and copies each member tensor into it so a single all-reduce covers
the whole group.  Here a bucket is materialized by flattening member arrays
and concatenating (optionally through the ``bucket_pack`` Pallas kernel);
after the collective the buffer is split back into the original shapes.

Ordering: gradients are communicated in *backward production order* — the
reverse of the forward parameter-creation order.  Models expose their
parameters as a pytree; ``backward_order`` derives a deterministic tensor
ordering from the tree paths, and model configs may override it with an
explicit ordering when the pytree layout does not match execution order
(e.g. scan-stacked layers, handled by ``expand_stacked``).

Profiling: every op that packs, reduces or unpacks bucket ``k`` runs under
the named scope ``bucket_<k>`` (:func:`bucket_scope`), ``k`` being the
bucket's index in ``plan.buckets``; the compiled program's op metadata then
says which bucket a device op belongs to.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.planner import MergePlan, TensorSpec


@dataclasses.dataclass(frozen=True)
class LeafMeta:
    """Metadata for one gradient leaf in backward order."""

    path: str
    shape: tuple[int, ...]
    dtype: Any
    size: int           # elements
    nbytes: int


def bucket_scope(k: int):
    """The named scope of bucket ``k``'s ops."""
    return jax.named_scope(f"bucket_{k}")


def _path_str(path) -> str:
    return jax.tree_util.keystr(path)


def leaves_in_backward_order(tree) -> list[tuple[str, Any]]:
    """(path, leaf) pairs, reversed forward order.

    ``jax.tree_util.tree_flatten_with_path`` is deterministic (sorted dict
    keys / tuple order); model param trees are built so that this order
    matches forward creation order, hence the reversal yields backward
    order.  Layer stacks built with ``lax.scan`` keep a leading layer axis;
    they are still one leaf here and are expanded by the planner via
    ``expand_stacked`` when per-layer granularity is wanted.
    """
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return [(_path_str(p), v) for p, v in reversed(flat)]


def leaf_metadata(tree) -> list[LeafMeta]:
    out = []
    for path, leaf in leaves_in_backward_order(tree):
        shape = tuple(leaf.shape)
        dtype = leaf.dtype
        size = int(np.prod(shape)) if shape else 1
        out.append(LeafMeta(path, shape, dtype, size,
                            size * jnp.dtype(dtype).itemsize))
    return out


def tensor_specs(tree, t_b_fn: Callable[[LeafMeta], float]) -> list[TensorSpec]:
    """Build planner inputs from a parameter pytree and a timing model."""
    return [TensorSpec(m.path, m.nbytes, t_b_fn(m)) for m in leaf_metadata(tree)]


# ---------------------------------------------------------------------------
# Pack / unpack.
#
# Two buffer layouts share one contract:
#   * plain (``use_kernel=False``): leaves concatenated back to back;
#   * slot-aligned (``use_kernel=True``): the bucket_pack kernel's slot
#     layout (``kernels/bucket_pack/kernel.py``).  Pack and unpack must
#     agree on ``use_kernel`` — the aligned total is
#     ``packed_elems(metas, aligned=True)``.
# ---------------------------------------------------------------------------

def slot_elems(size: int, aligned: bool = False) -> int:
    """Elements a leaf of ``size`` occupies in the packed buffer."""
    if not aligned:
        return size
    from repro.kernels.bucket_pack.kernel import slot_elems as kernel_slot
    return kernel_slot(size)


def packed_elems(metas: Sequence[LeafMeta], aligned: bool = False) -> int:
    """Total packed-buffer elements for a bucket under either layout."""
    return sum(slot_elems(m.size, aligned) for m in metas)


def pack(leaves: Sequence[jax.Array], dtype=None,
         use_kernel: bool = False) -> jax.Array:
    """Concatenate leaves into one flat buffer (paper §5.3 merged buffer)."""
    if not leaves:
        raise ValueError("empty bucket")
    dtype = dtype or jnp.result_type(*[l.dtype for l in leaves])
    if use_kernel:
        from repro.kernels.bucket_pack import ops as pack_ops
        return pack_ops.pack(list(leaves), dtype)
    flats = [l.reshape(-1).astype(dtype) for l in leaves]
    return jnp.concatenate(flats) if len(flats) > 1 else flats[0]


def unpack(buf: jax.Array, metas: Sequence[LeafMeta],
           use_kernel: bool = False) -> list[jax.Array]:
    """Split a flat buffer back into the bucket's member tensors."""
    expected = packed_elems(metas, aligned=use_kernel)
    if expected != buf.shape[0]:
        raise ValueError(f"buffer has {buf.shape[0]} elements, "
                         f"metas describe {expected}")
    if use_kernel:
        from repro.kernels.bucket_pack import ops as pack_ops
        return pack_ops.unpack(buf, [m.shape for m in metas],
                               [m.dtype for m in metas])
    out, off = [], 0
    for m in metas:
        out.append(jax.lax.dynamic_slice_in_dim(buf, off, m.size)
                   .reshape(m.shape).astype(m.dtype))
        off += m.size
    return out


def apply_bucketed(tree, plan: MergePlan,
                   collective: Callable[[jax.Array], jax.Array],
                   comm_dtype=None, use_kernel: bool = False):
    """Apply ``collective`` to each merged bucket of a gradient pytree.

    This is the generic engine used for all-reduce (psum), reduce-scatter,
    and compressed variants; the collective sees exactly one flat buffer per
    bucket, in plan order (backward order), mirroring the paper's pipeline.
    Returns a new pytree of the same structure.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    paths = [_path_str(p) for p, _ in flat]
    leaves = [v for _, v in flat]
    metas = leaf_metadata(tree)                      # backward order
    if plan.num_tensors != len(metas):
        raise ValueError(
            f"plan covers {plan.num_tensors} tensors but tree has {len(metas)}")
    # backward-order index -> forward flat index
    fwd_index = {path: i for i, path in enumerate(paths)}
    new_leaves: list[Any] = [None] * len(leaves)
    for k, bucket in enumerate(plan.buckets):
        bmetas = [metas[i] for i in bucket]
        arrs = [leaves[fwd_index[m.path]] for m in bmetas]
        orig_dtype = arrs[0].dtype
        with bucket_scope(k):
            buf = pack(arrs, dtype=comm_dtype or orig_dtype,
                       use_kernel=use_kernel)
            buf = collective(buf)
            wire_metas = [dataclasses.replace(mm, dtype=buf.dtype)
                          for mm in bmetas]
            for m, arr in zip(bmetas, unpack(buf, wire_metas,
                                             use_kernel=use_kernel)):
                new_leaves[fwd_index[m.path]] = arr.astype(m.dtype)
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


# ---------------------------------------------------------------------------
# Scan-stacked parameter expansion.
# ---------------------------------------------------------------------------

def expand_stacked(metas: Sequence[LeafMeta], stacked_axis_name: str = "layers",
                   num_layers: int | None = None) -> list[LeafMeta]:
    """Expand scan-stacked leaves (leading layer axis) into per-layer metas.

    For planning purposes a stacked leaf of shape (L, ...) is L logical
    tensors produced at different times during the backward scan.  The
    packed representation stays stacked at runtime; only the *planner* sees
    the expansion (granularity of the cost model), so plans computed on the
    expanded view are mapped back by ``contract_plan``.
    """
    out = []
    for m in metas:
        if num_layers and m.shape and m.shape[0] == num_layers and stacked_axis_name in m.path:
            per = m.size // m.shape[0]
            for l in range(m.shape[0]):
                out.append(LeafMeta(f"{m.path}[{l}]", m.shape[1:], m.dtype,
                                    per, per * jnp.dtype(m.dtype).itemsize))
        else:
            out.append(m)
    return out
