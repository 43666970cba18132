"""Flash attention Pallas TPU kernel: blockwise online softmax.

TPU adaptation of the (GPU-origin) flash-attention algorithm: the MXU wants
128-aligned [block_q, head_dim] × [head_dim, block_k] tiles resident in
VMEM; the online-softmax running statistics (m, l) and the output
accumulator live in fp32 VMEM scratch that persists across the innermost
(KV) grid dimension.  Supports GQA (G query heads share one KV head via the
index map), causal masking, and sliding windows (gemma3's local layers).

Layouts:  q [BHq, Sq, D], k/v [BHkv, Skv, D] with BHq = BHkv * G and the
query-head-major flattening (b, kvh, g).  Grid: (BHq, Sq/bq, Skv/bk), KV
innermost with "arbitrary" semantics (sequential accumulation).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            scale: float, causal: bool, window: int, block_q: int,
            block_k: int, seq_q: int, seq_kv: int):
    ki = pl.program_id(2)
    nk = pl.num_programs(2)

    @pl.when(ki == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q = q_ref[0].astype(jnp.float32)              # [bq, D]
    k = k_ref[0].astype(jnp.float32)              # [bk, D]
    v = v_ref[0].astype(jnp.float32)              # [bk, D]
    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ()))) * scale

    qi = pl.program_id(1)
    q_pos = qi * block_q + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 0)
    k_pos = ki * block_k + jax.lax.broadcasted_iota(jnp.int32,
                                                    (block_q, block_k), 1)
    mask = (k_pos < seq_kv) & (q_pos < seq_q)
    if causal:
        mask &= k_pos <= q_pos
    if window:
        mask &= k_pos > q_pos - window
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_scr[...]
    m_new = jnp.maximum(m_prev, s.max(axis=1))
    p = jnp.exp(s - m_new[:, None]) * mask
    corr = jnp.exp(m_prev - m_new)
    l_scr[...] = l_scr[...] * corr + p.sum(axis=1)
    acc_scr[...] = acc_scr[...] * corr[:, None] + jax.lax.dot_general(
        p, v, (((1,), (0,)), ((), ())))
    m_scr[...] = m_new

    @pl.when(ki == nk - 1)
    def _finish():
        o_ref[0] = (acc_scr[...] /
                    jnp.maximum(l_scr[...], 1e-30)[:, None]
                    ).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, scale: float, causal: bool,
                           window: int, block_q: int = 128,
                           block_k: int = 128, seq_q: int, seq_kv: int,
                           interpret: bool = False) -> jax.Array:
    """q: [BHq, Sq_pad, D]; k/v: [BHkv, Skv_pad, D]; Sq_pad % block_q == 0,
    Skv_pad % block_k == 0.  ``seq_q``/``seq_kv`` are the unpadded lengths
    (padding is masked out)."""
    bhq, sq, d = q.shape
    bhkv, skv, _ = k.shape
    g = bhq // bhkv
    grid = (bhq, sq // block_q, skv // block_k)

    kern = functools.partial(
        _kernel, scale=scale, causal=causal, window=window,
        block_q=block_q, block_k=block_k, seq_q=seq_q, seq_kv=seq_kv)

    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
            pl.BlockSpec((1, block_k, d), lambda b, i, j: (b // g, j, 0)),
        ],
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((bhq, sq, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q,), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(q, k, v)
