"""Pure-jnp oracle for bucket pack/unpack (the slot layout of kernel.py)."""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bucket_pack.kernel import slot_elems


def pad_flat(x: jax.Array) -> jax.Array:
    flat = x.reshape(-1)
    pad = slot_elems(flat.shape[0]) - flat.shape[0]
    return jnp.pad(flat, (0, pad)) if pad else flat


def pack_ref(leaves, dtype=None) -> jax.Array:
    # same default as ops.pack / core.bucketer.pack: mixed dtypes promote
    dtype = dtype or jnp.result_type(*[l.dtype for l in leaves])
    return jnp.concatenate([pad_flat(l).astype(dtype) for l in leaves])


def unpack_ref(buf: jax.Array, shapes, dtypes):
    out, off = [], 0
    for shape, dt in zip(shapes, dtypes):
        size = 1
        for d in shape:
            size *= d
        out.append(buf[off:off + size].reshape(shape).astype(dt))
        off += slot_elems(size)
    return out
