"""Bucketed collectives: the runtime half of MG-WFBP.

Inside a ``shard_map`` whose data-parallel axes are manual, gradients arrive
as *unreduced* per-shard values.  These helpers reduce them bucket-by-bucket
according to a :class:`MergePlan`:

* ``bucketed_allreduce``      — one ``lax.psum`` per bucket (paper semantics).
* ``bucketed_reduce_scatter`` / ``bucketed_allgather`` — ZeRO-1 variant: the
  plan drives merged reduce-scatters of gradients and merged all-gathers of
  updated parameters (beyond-paper).
* ``hierarchical_allreduce``  — two-level pod-aware reduction: RS intra-pod,
  AR across pods on the shard, AG intra-pod (beyond-paper; motivated by the
  paper's own observation that merging pays where the startup term is big —
  the DCN pod axis is exactly that).
* Compression hooks: cast-to-bf16-on-the-wire with fp32 accumulation
  (paper §8 lists gradient compression as future work).

All functions are pure and jit-safe; XLA's latency-hiding scheduler overlaps
the per-bucket collectives with any remaining compute they do not depend on,
which is the TPU-native realization of the paper's C++ comm thread.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Sequence

import jax
import jax.numpy as jnp

from repro.core import bucketer
from repro.core.planner import MergePlan

AxisNames = str | Sequence[str]


def replicated_shard(buf: jax.Array, axis_name: str) -> jax.Array:
    """This member's tile of a dim-0-even value replicated over ``axis_name``."""
    n = jax.lax.axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    sz = buf.shape[0] // n
    return jax.lax.dynamic_slice_in_dim(buf, idx * sz, sz)


def _mean_scale(axis_names: AxisNames) -> Callable[[jax.Array], jax.Array]:
    def scale(x):
        n = 1
        names = (axis_names,) if isinstance(axis_names, str) else tuple(axis_names)
        for a in names:
            n *= jax.lax.axis_size(a)
        return x / n
    return scale


def _wire_cast(buf: jax.Array, wire_dtype) -> tuple[jax.Array, Callable]:
    """Optionally compress the on-wire representation (e.g. bf16)."""
    if wire_dtype is None or buf.dtype == jnp.dtype(wire_dtype):
        return buf, lambda y: y
    orig = buf.dtype
    return buf.astype(wire_dtype), lambda y: y.astype(orig)


def _cpu_promotes(dtype) -> bool:
    """XLA:CPU's AllReducePromotion crashes on 16-bit reductions with
    partial replica groups; promote around the collective on CPU only
    (TPU, the target, reduces bf16 natively)."""
    dt = jnp.dtype(dtype)
    return (jax.default_backend() == "cpu" and dt.itemsize < 4
            and jnp.issubdtype(dt, jnp.floating))


def safe_psum(x, axis_names: AxisNames):
    """psum with the CPU 16-bit promotion workaround (pytree-ok)."""
    def one(v):
        if _cpu_promotes(v.dtype):
            return jax.lax.psum(v.astype(jnp.float32), axis_names
                                ).astype(v.dtype)
        return jax.lax.psum(v, axis_names)
    return jax.tree.map(one, x)


def safe_psum_scatter(buf: jax.Array, axis_name: str, **kw) -> jax.Array:
    if _cpu_promotes(buf.dtype):
        return jax.lax.psum_scatter(buf.astype(jnp.float32), axis_name,
                                    **kw).astype(buf.dtype)
    return jax.lax.psum_scatter(buf, axis_name, **kw)


def safe_all_gather(x: jax.Array, axis_name: str, *, axis: int) -> jax.Array:
    """Tiled all_gather whose VJP routes through the CPU-safe
    reduce-scatter (the FSDP gradient path: gather fwd, scatter bwd)."""

    @jax.custom_vjp
    def ag(v):
        return jax.lax.all_gather(v, axis_name, axis=axis, tiled=True)

    def fwd(v):
        return ag(v), None

    def bwd(_, g):
        return (safe_psum_scatter(g, axis_name, scatter_dimension=axis,
                                  tiled=True),)

    ag.defvjp(fwd, bwd)
    return ag(x)


def bucketed_allreduce(grads, plan: MergePlan, axis_names: AxisNames,
                       *, mean: bool = True, wire_dtype=None,
                       mode: str = "fused", use_kernel: bool = False):
    """All-reduce a gradient pytree bucket-by-bucket (MG-WFBP runtime).

    ``mode="fused"`` (default, TPU-native): each bucket is ONE variadic
    ``lax.psum`` — XLA emits a single all-reduce op with one operand per
    member tensor, so the startup cost is amortized exactly as the paper's
    merged buffer does on MPI, **without** the pack copy and without
    disturbing each leaf's tensor-parallel sharding.

    ``mode="packed"`` (paper-faithful §5.3): members are copied into one
    contiguous buffer (optionally via the bucket_pack Pallas kernel) and a
    single 1-D all-reduce runs.  Costs a pack/unpack round trip and a TP
    gather for model-sharded leaves — kept for baseline comparison and for
    interconnects that require contiguous buffers.
    """
    scale = _mean_scale(axis_names)

    if mode == "packed":
        def collective(buf):
            buf, restore = _wire_cast(buf, wire_dtype)
            buf = safe_psum(buf, axis_names)
            buf = restore(buf)
            return scale(buf) if mean else buf

        return bucketer.apply_bucketed(grads, plan, collective,
                                       use_kernel=use_kernel)

    # fused: one variadic psum per (bucket, dtype) — XLA requires uniform
    # operand element types per all-reduce
    metas = bucketer.leaf_metadata(grads)
    flat, treedef = jax.tree_util.tree_flatten_with_path(grads)
    paths = [bucketer._path_str(p) for p, _ in flat]
    fwd_index = {p: i for i, p in enumerate(paths)}
    leaves = [v for _, v in flat]
    new_leaves = list(leaves)
    for bucket in plan.buckets:
        idxs = [fwd_index[metas[i].path] for i in bucket]
        casted, restores = [], []
        for i in idxs:
            c, r = _wire_cast(leaves[i], wire_dtype)
            casted.append(c)
            restores.append(r)
        by_dtype: dict = {}
        for pos, c in enumerate(casted):
            by_dtype.setdefault(jnp.dtype(c.dtype), []).append(pos)
        for dt, poss in sorted(by_dtype.items(), key=lambda kv: str(kv[0])):
            ops = [casted[p] for p in poss]
            promote = _cpu_promotes(dt)
            if promote:
                ops = [o.astype(jnp.float32) for o in ops]
            reduced = jax.lax.psum(tuple(ops), axis_names)
            if promote:
                reduced = tuple(r.astype(dt) for r in reduced)
            for p, red in zip(poss, reduced):
                out = restores[p](red)
                new_leaves[idxs[p]] = scale(out) if mean else out
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def hierarchical_allreduce(grads, plan: MergePlan, *, intra_axis: str = "data",
                           inter_axis: str = "pod", mean: bool = True,
                           wire_dtype=None, mode: str = "fused",
                           use_kernel: bool = False):
    """Two-level pod-aware all-reduce per bucket.

    reduce-scatter over the intra-pod axis, all-reduce the 1/intra shard over
    the pod axis, all-gather intra-pod.  Moves 1/intra of the bytes over the
    slow inter-pod links compared to a flat all-reduce over (pod, data).

    ``mode="fused"``: psum over intra is variadic per bucket (sharding
    preserving); the pod-level reduce then runs on the intra-reduced values
    — a latency-optimal schedule when the pod axis dominates startup.
    """
    if mode == "fused":
        # intra-level merged psum, then pod-level merged psum per bucket.
        out = bucketed_allreduce(grads, plan, intra_axis, mean=mean,
                                 wire_dtype=wire_dtype, mode="fused")
        return bucketed_allreduce(out, plan, inter_axis, mean=mean,
                                  wire_dtype=wire_dtype, mode="fused")

    scale = _mean_scale((intra_axis, inter_axis))

    def collective(buf):
        buf, restore = _wire_cast(buf, wire_dtype)
        n = jax.lax.axis_size(intra_axis)
        pad = padded_elems(buf.shape[0], n) - buf.shape[0]
        if pad:
            buf = jnp.pad(buf, (0, pad))
        shard = safe_psum_scatter(buf, intra_axis, scatter_dimension=0,
                                  tiled=True)
        shard = safe_psum(shard, inter_axis)
        full = jax.lax.all_gather(shard, intra_axis, axis=0, tiled=True)
        if pad:
            full = full[: full.shape[0] - pad]
        full = restore(full)
        return scale(full) if mean else full

    return bucketer.apply_bucketed(grads, plan, collective,
                                   use_kernel=use_kernel)


# ---------------------------------------------------------------------------
# ZeRO-1: merged reduce-scatter of grads + merged all-gather of params.
# ---------------------------------------------------------------------------

def padded_elems(nelems: int, n: int) -> int:
    """Length of a bucket buffer padded for a tiled collective over ``n``
    shards: every shard is a whole number of kernel TILEs, which is a
    whole number of XLA:TPU's 1-D tiles in any dtype.  A shard that ends
    inside a tile sends the TPU compiler down a path whose time and host
    memory grow with the buffer."""
    from repro.kernels.bucket_pack.kernel import TILE
    q = n * TILE
    return -(-nelems // q) * q


def bucketed_reduce_scatter(grads, plan: MergePlan, axis_name: str,
                            *, mean: bool = True, wire_dtype=None,
                            use_kernel: bool = False):
    """Reduce-scatter each bucket over the DP axis; returns, per bucket, this
    shard's slice (list aligned with plan.buckets) plus unpack metadata.

    The caller runs the optimizer on the shard and then calls
    ``bucketed_allgather`` — both collectives enjoy the same merged-message
    startup saving that motivates MG-WFBP for plain all-reduce.
    ``use_kernel`` selects the bucket_pack Pallas layout (TILE-aligned
    slots); the caller's param repack and the all-gather must use the same
    flag or shard offsets disagree.
    """
    metas = bucketer.leaf_metadata(grads)
    flat, _ = jax.tree_util.tree_flatten_with_path(grads)
    by_path = {bucketer._path_str(p): v for p, v in flat}
    n = jax.lax.axis_size(axis_name)
    shards, bucket_metas = [], []
    for bucket in plan.buckets:
        bmetas = [metas[i] for i in bucket]
        buf = bucketer.pack([by_path[m.path] for m in bmetas],
                            use_kernel=use_kernel)
        buf, restore = _wire_cast(buf, wire_dtype)
        pad = padded_elems(buf.shape[0], n) - buf.shape[0]
        if pad:
            buf = jnp.pad(buf, (0, pad))
        shard = safe_psum_scatter(buf, axis_name, scatter_dimension=0,
                                  tiled=True)
        shard = restore(shard)
        if mean:
            shard = shard / n
        shards.append(shard)
        bucket_metas.append(bmetas)
    return shards, bucket_metas


def bucketed_allgather(shards: Sequence[jax.Array],
                       bucket_metas: Sequence[Sequence[bucketer.LeafMeta]],
                       treedef_like, axis_name: str,
                       *, use_kernel: bool = False):
    """Gather updated parameter shards back into the full pytree, each
    leaf in the dtype of its ``treedef_like`` counterpart (the metas may
    describe float32 gradients of bfloat16 parameters)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(treedef_like)
    paths = [bucketer._path_str(p) for p, _ in flat]
    fwd_index = {p: i for i, p in enumerate(paths)}
    new_leaves = [None] * len(flat)
    for shard, bmetas in zip(shards, bucket_metas):
        full = jax.lax.all_gather(shard, axis_name, axis=0, tiled=True)
        total = bucketer.packed_elems(bmetas, aligned=use_kernel)
        full = full[:total]
        bmetas = [dataclasses.replace(m, dtype=flat[fwd_index[m.path]][1].dtype)
                  for m in bmetas]
        for m, arr in zip(bmetas, bucketer.unpack(full, bmetas,
                                                  use_kernel=use_kernel)):
            new_leaves[fwd_index[m.path]] = arr
    return jax.tree_util.tree_unflatten(treedef, new_leaves)


def collective_bytes_of_plan(plan: MergePlan, specs_bytes: Sequence[int]) -> list[int]:
    """Per-bucket wire bytes (diagnostics for EXPERIMENTS.md)."""
    out = []
    for bucket in plan.buckets:
        out.append(sum(specs_bytes[i] for i in bucket))
    return out
