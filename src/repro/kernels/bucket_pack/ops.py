"""jit'd wrappers for merged-gradient pack/unpack.

Both speak the slot layout defined in ``kernel.py``.  ``interpret=None``
(default) selects Pallas interpret mode on the CPU backend and the compiled
kernel everywhere else; a kernel that cannot compile raises.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.bucket_pack import kernel as K
from repro.kernels.bucket_pack.ref import pad_flat

MAX_SRCS_PER_CALL = 32   # chunk very large buckets to bound kernel fan-in


def _auto_interpret() -> bool:
    return jax.default_backend() == "cpu"


def _result_dtype(leaves, dtype):
    if dtype is not None:
        return jnp.dtype(dtype)
    # same default as core.bucketer.pack: mixed-dtype buckets promote
    return jnp.dtype(jnp.result_type(*[l.dtype for l in leaves]))


def pack(leaves, dtype=None, interpret: bool | None = None) -> jax.Array:
    """Pack arbitrary-shaped leaves into one slot-layout flat buffer."""
    dtype = _result_dtype(leaves, dtype)
    if interpret is None:
        interpret = _auto_interpret()
    flats = [pad_flat(l) for l in leaves]
    pieces = [K.pack_kernel(flats[i:i + MAX_SRCS_PER_CALL], dtype,
                            interpret=interpret)
              for i in range(0, len(flats), MAX_SRCS_PER_CALL)]
    return pieces[0] if len(pieces) == 1 else jnp.concatenate(pieces)


def unpack(buf: jax.Array, shapes, dtypes, interpret: bool | None = None):
    """Inverse of :func:`pack` (slot offsets recomputed from shapes)."""
    if interpret is None:
        interpret = _auto_interpret()
    out, off = [], 0
    for shape, dt in zip(shapes, dtypes):
        size = 1
        for d in shape:
            size *= d
        slot = K.slot_elems(size)
        piece = K.unpack_one_kernel(buf, off, slot, buf.dtype,
                                    interpret=interpret)
        out.append(piece[:size].reshape(shape).astype(dt))
        off += slot
    return out
