"""Differentiable flash attention: layout, padding, block sizes and the
custom VJP around the kernels in ``kernel.py``.

``interpret=None`` (default) selects Pallas interpret mode on the CPU
backend and the compiled kernels everywhere else.  The residuals the
backward pass keeps are q, k, v, o and the float32 log-sum-exp of each
row: nothing of size ``Sq x Skv``.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.kernels.flash_attention import kernel as K

LANES = 128
BLOCKS = (1024, 512, 256, 128)
# the largest block x head dimension: at 1024 x 128 the forward and both
# backward kernels fit the TPU's scoped VMEM in float32, at 1024 x 256 the
# forward does not (described-v5e compile)
MAX_TILE = 1024 * 128


def _auto_interpret() -> bool:
    return jax.default_backend() == "cpu"


def block_size(seq: int, head_dim: int) -> int:
    """The largest of ``BLOCKS`` with ``block * head_dim <= MAX_TILE`` that
    divides ``seq``, else the smallest such (the sequence is then padded
    to it).  On a v5e, at qwen2's 4096 x 12 heads of 128, blocks of 1024
    ran forward and backward 12% faster than 512 and 44% faster than 256."""
    fits = [b for b in BLOCKS if b * head_dim <= MAX_TILE] or [BLOCKS[-1]]
    return next((b for b in fits if seq % b == 0), fits[-1])


def _pad_to(x, axis, mult):
    pad = (-x.shape[axis]) % mult
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3,))
def _attend(q, k, v, g: K.Geometry):
    return K.forward(q, k, v, g)[0]


def _attend_fwd(q, k, v, g):
    o, lse = K.forward(q, k, v, g)
    return o, (q, k, v, o, lse)


def _attend_bwd(g, res, do):
    return K.backward(*res, do, g)


_attend.defvjp(_attend_fwd, _attend_bwd)


@functools.partial(jax.jit, static_argnames=("causal", "window", "block_q",
                                             "block_k", "interpret"))
def flash_attention(q, k, v, *, causal: bool = True, window: int = 0,
                    block_q: int | None = None, block_k: int | None = None,
                    interpret: bool | None = None) -> jax.Array:
    """q: [B, Sq, Hq, D]; k/v: [B, Skv, Hkv, D] -> [B, Sq, Hq, D].

    GQA by head grouping; ``window > 0`` keeps keys with q_pos - k_pos in
    [0, window).  Blocks default to :func:`block_size` of each length;
    sequences are padded to block multiples and the head dimension to a
    multiple of 128, and the padding is masked or sliced off.
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if hq % hkv:
        raise ValueError(f"Hq={hq} not a multiple of Hkv={hkv}")
    dp = d + (-d) % LANES
    g = K.Geometry(
        head_dim=dp, scale=1.0 / math.sqrt(d), causal=causal, window=window,
        block_q=block_q or block_size(sq, dp),
        block_k=block_k or block_size(skv, dp), seq_q=sq, seq_kv=skv,
        interpret=_auto_interpret() if interpret is None else interpret)

    def flat(x, block):
        x = _pad_to(_pad_to(x, 3, LANES), 1, block)
        return x.reshape(b, x.shape[1], -1)

    o = _attend(flat(q, g.block_q), flat(k, g.block_k), flat(v, g.block_k),
                g)
    return o[:, :sq].reshape(b, sq, hq, dp)[..., :d]
