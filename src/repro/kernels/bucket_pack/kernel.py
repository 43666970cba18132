"""Merged-gradient pack/unpack Pallas kernels (paper §5.3).

The paper pre-allocates one contiguous buffer per merged-gradient group and
copies member tensors in before a single all-reduce.  On TPU this is a
bandwidth-bound tiled HBM→VMEM→HBM copy; the MXU plays no role — exactly
the kind of op where BlockSpec tiling *is* the whole kernel.

Slot layout (the one contract every pack/unpack path speaks): each member
tensor, flattened, occupies a slot of :func:`slot_elems` elements — its
size rounded up to a TILE multiple, zero-padded at the tail — and slots
follow each other in bucket order.  ``core.bucketer`` sizes buffers, ZeRO-1
shards and decay masks from the same function, and ``ref.py`` is the
pure-jnp oracle of the same layout.

Blocks are lane-dense 2-D ``(rows, LANES)`` views of the flat buffers, the
layout XLA gives a TPU array.  A TILE is ``SUBLANES`` rows: a multiple of
the sublane tiling of every dtype down to 8 bits (f32 tiles 8 rows, bf16
16, int8/fp8 32), so any slot boundary is a tile boundary in any dtype.
Each call picks the largest power-of-two block (up to ``MAX_BLOCK_ROWS``)
that divides every slot it touches, so a block never straddles two slots.
``pack`` is one pallas_call over all destination blocks; the source for
block *i* is chosen with static offset comparisons against ``program_id``
(the member count per call is bounded by ops.py).  ``unpack`` is one
tiled-copy call per member.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

LANES = 128
SUBLANES = 32
TILE = SUBLANES * LANES
# 256 rows x 128 lanes x 4 B = 128 KiB per f32 block; with ops.py's 32
# sources per call, double-buffered, a pack stays near 8 MiB of VMEM.
MAX_BLOCK_ROWS = 256


def slot_elems(size: int) -> int:
    """Elements a tensor of ``size`` elements occupies in a packed buffer."""
    return size + ((-size) % TILE)


def _block_rows(*row_counts: int) -> int:
    """Largest power-of-two row block <= MAX_BLOCK_ROWS dividing every count
    (all counts are SUBLANES multiples, so the answer is >= SUBLANES)."""
    g = 0
    for r in row_counts:
        g = math.gcd(g, r)
    b = MAX_BLOCK_ROWS
    while g % b:
        b //= 2
    return b


def _make_pack_kernel(ranges, rows):
    """Kernel factory: each grid step writes one destination block; every
    source's index_map pre-loads its (clamped) candidate block and the
    owner is selected by comparing ``program_id`` against the static slot
    ranges — fully resolved to vector selects, no gather."""
    def kern(*refs):
        o_ref = refs[-1]
        srcs = refs[:-1]
        i = pl.program_id(0)
        acc = jnp.zeros((rows, LANES), o_ref.dtype)
        for s_idx, s_ref in enumerate(srcs):
            lo, hi = ranges[s_idx]
            inside = (i >= lo) & (i < hi)
            acc = jnp.where(inside, s_ref[...].astype(o_ref.dtype), acc)
        o_ref[...] = acc
    return kern


def pack_kernel(srcs: list[jax.Array], dtype, interpret: bool = False
                ) -> jax.Array:
    """srcs: flat arrays, each ``slot_elems`` long.  Returns the packed
    [sum(sizes)] buffer in the slot layout."""
    sizes = [s.shape[0] for s in srcs]
    if any(sz % TILE for sz in sizes):
        raise ValueError(f"source sizes {sizes} are not TILE={TILE} multiples")
    slot_rows = [sz // LANES for sz in sizes]
    rows = _block_rows(*slot_rows)
    ranges, lo = [], 0
    for r in slot_rows:
        ranges.append((lo, lo + r // rows))
        lo += r // rows
    n_blocks = lo

    in_specs = [
        pl.BlockSpec((rows, LANES),
                     functools.partial(
                         lambda i, lo, n: (jnp.clip(i - lo, 0, n - 1), 0),
                         lo=b_lo, n=b_hi - b_lo))
        for b_lo, b_hi in ranges]
    out = pl.pallas_call(
        _make_pack_kernel(ranges, rows),
        grid=(n_blocks,),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((n_blocks * rows, LANES), dtype),
        interpret=interpret,
    )(*[s.reshape(r, LANES) for s, r in zip(srcs, slot_rows)])
    return out.reshape(-1)


def _copy_kernel(s_ref, o_ref):
    o_ref[...] = s_ref[...].astype(o_ref.dtype)


def unpack_one_kernel(buf: jax.Array, offset: int, size: int, dtype,
                      interpret: bool = False) -> jax.Array:
    """Copy the slot buf[offset : offset+size] out as its own flat array."""
    if offset % TILE or size % TILE:
        raise ValueError(f"slot ({offset}, {size}) is not TILE={TILE} aligned")
    off_rows, size_rows = offset // LANES, size // LANES
    rows = _block_rows(off_rows, size_rows)
    lo = off_rows // rows
    out = pl.pallas_call(
        _copy_kernel,
        grid=(size_rows // rows,),
        in_specs=[pl.BlockSpec((rows, LANES), lambda i: (i + lo, 0))],
        out_specs=pl.BlockSpec((rows, LANES), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((size_rows, LANES), dtype),
        interpret=interpret,
    )(buf.reshape(-1, LANES))
    return out.reshape(-1)
