"""Flash attention Pallas TPU kernels: forward and backward by blockwise
online softmax.

TPU adaptation of the (GPU-origin) flash-attention algorithm: the MXU takes
128-aligned ``[block, head_dim]`` tiles resident in VMEM; the softmax
statistics and the accumulators live in float32 VMEM scratch that persists
across the grid's innermost (reduction) dimension, so no score block ever
leaves VMEM.  GQA (G query heads share one KV head), causal masking and
sliding windows (gemma3's local layers).

Layouts.  q, o and dq are ``[B, Sq, Hq * D]``, k, v, dk and dv
``[B, Skv, Hkv * D]``: a reshape of ``[B, S, H, D]``, no transpose.  Head
``h`` is the ``(block, D)`` tile at column block ``h`` (``h // G`` for
K/V), which needs ``D % 128 == 0`` on the TPU.  The forward pass also
writes each row's log-sum-exp, and the backward pass takes
``delta = rowsum(dO * O)``, both float32 ``[B, Hq, 1, Sq]``: a row per
head, so that a q block's statistics are one ``(1, block_q)`` tile.

Kernels.  ``forward``: grid ``(B, Hq, nq, nk)``, KV innermost; it works on
the transposed block ``k q^T``, where a q row's statistics lie along the
lanes and reduce across sublanes, and keeps its accumulator transposed
too.  ``dq``: the same grid on ``q k^T``, recomputing each block's
probabilities from q, k and the log-sum-exp.  ``dkv``: grid
``(B, Hkv, nk, G, nq)`` on ``k q^T`` again; it sums dK and dV over the G
query heads of a KV head and over the q blocks in VMEM.

Blocks that the causal mask or the window rules out do no compute
(``pl.when``) and fetch nothing: their index map is clamped to the nearest
block that is needed, so the pipeline finds that tile already in VMEM.
Blocks wholly inside the mask skip the mask arithmetic too.

Precision.  Compiled for the TPU, the tile operands of every product are
rounded to bfloat16 and accumulated in float32: one MXU pass, what XLA's
``DEFAULT`` precision does to the same float32 products elsewhere in the
program.  In interpret mode (the CPU) they stay float32, the CPU's default.
Statistics, exponentials and accumulators are float32 either way.
"""

from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
NT = (((1,), (1,)), ((), ()))      # a @ b.T
NN = (((1,), (0,)), ((), ()))      # a @ b
TN = (((0,), (0,)), ((), ()))      # a.T @ b
F32 = jnp.float32


@dataclasses.dataclass(frozen=True)
class Geometry:
    """What the kernels know statically: the mask, the tiling and the
    unpadded lengths (padded positions are masked)."""
    head_dim: int
    scale: float
    causal: bool
    window: int
    block_q: int
    block_k: int
    seq_q: int
    seq_kv: int
    interpret: bool

    @property
    def nq(self) -> int:
        return pl.cdiv(self.seq_q, self.block_q)

    @property
    def nk(self) -> int:
        return pl.cdiv(self.seq_kv, self.block_k)

    def heads(self, q, k) -> tuple[int, int, int]:
        """(Hq, Hkv, G) of the flattened q and k."""
        hq, hkv = q.shape[2] // self.head_dim, k.shape[2] // self.head_dim
        return hq, hkv, hq // hkv

    @property
    def mxu_dtype(self):
        return F32 if self.interpret else jnp.bfloat16

    def kv_blocks(self, i):
        """First and last KV block that q block ``i`` needs."""
        bq, bk = self.block_q, self.block_k
        lo = 0
        if self.window:
            lo = jnp.maximum(i * bq - self.window + 1, 0) // bk
        hi = self.nk - 1
        if self.causal:
            hi = jnp.minimum(((i + 1) * bq - 1) // bk, hi)
        return lo, hi

    def q_blocks(self, j):
        """First and last q block that needs KV block ``j``."""
        bq, bk = self.block_q, self.block_k
        lo = (j * bk) // bq if self.causal else 0
        hi = self.nq - 1
        if self.window:
            hi = jnp.minimum(((j + 1) * bk + self.window - 2) // bq, hi)
        return lo, hi

    def partial(self, i, j):
        """Whether block (i, j) holds a masked entry (python False where
        no block can)."""
        bq, bk = self.block_q, self.block_k
        out = False
        if self.seq_kv % bk:
            out = (j + 1) * bk > self.seq_kv
        if self.causal:
            out = out | ((j + 1) * bk - 1 > i * bq)
        if self.window:
            out = out | (j * bk < (i + 1) * bq - self.window)
        return out

    def mask(self, i, j, transposed: bool = False):
        """Which entries of block (i, j) are kept: ``[block_q, block_k]``,
        or ``[block_k, block_q]`` when ``transposed``."""
        shape = ((self.block_k, self.block_q) if transposed
                 else (self.block_q, self.block_k))
        qa, ka = (1, 0) if transposed else (0, 1)
        q_pos = i * self.block_q + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                            qa)
        k_pos = j * self.block_k + jax.lax.broadcasted_iota(jnp.int32, shape,
                                                            ka)
        keep = k_pos < self.seq_kv
        if self.causal:
            keep &= k_pos <= q_pos
        if self.window:
            keep &= k_pos > q_pos - self.window
        return keep


def _blocks(g: Geometry, i, j, needed, step):
    """Run ``step(masked)`` on block (i, j) if it is ``needed``, with the
    mask only where the block holds a masked entry."""
    partial = g.partial(i, j)
    if partial is False:
        pl.when(needed)(lambda: step(False))
        return
    pl.when(needed & jnp.logical_not(partial))(lambda: step(False))
    pl.when(needed & partial)(lambda: step(True))


def _scaled_q(q_ref, g: Geometry):
    return (q_ref[0].astype(F32) * g.scale).astype(g.mxu_dtype)


def _tile(ref, g: Geometry):
    return ref[0].astype(g.mxu_dtype)


def _dot(a, b, dims, g: Geometry):
    return jax.lax.dot_general(a.astype(g.mxu_dtype), b, dims,
                               preferred_element_type=F32)


def _row(ref):
    """A ``(1, 1, 1, n)`` statistics block as a ``(1, n)`` row."""
    return ref[0, 0]


def _col(ref):
    """A ``(1, 1, 1, n)`` statistics block as a ``(n, 1)`` column."""
    return ref[0, 0, 0][:, None]


# ---------------------------------------------------------------------------
# Forward.
# ---------------------------------------------------------------------------

def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_scr, l_scr, acc_scr,
                *, g: Geometry):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        s_t = _dot(_tile(k_ref, g), _scaled_q(q_ref, g), NT, g)
        if masked:
            keep = g.mask(i, j, transposed=True)
            s_t = jnp.where(keep, s_t, NEG_INF)
        m_prev = m_scr[...]
        m_new = jnp.maximum(m_prev, s_t.max(axis=0, keepdims=True))
        p_t = jnp.exp(s_t - m_new)
        if masked:      # a row masked so far has m_new == NEG_INF
            p_t = jnp.where(keep, p_t, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = corr * l_scr[...] + p_t.sum(axis=0, keepdims=True)
        acc_scr[...] = corr * acc_scr[...] + _dot(_tile(v_ref, g), p_t, TN, g)
        m_scr[...] = m_new

    lo, hi = g.kv_blocks(i)
    _blocks(g, i, j, (j >= lo) & (j <= hi), step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        l = l_scr[...]
        o_ref[0] = (acc_scr[...] / jnp.maximum(l, 1e-30)).T.astype(o_ref.dtype)
        # a row with nothing to attend to gets +inf: its probabilities
        # recompute to exactly 0 in the backward pass
        lse_ref[0, 0] = jnp.where(l > 0, m_scr[...] + jnp.log(l), jnp.inf)


def _q_major_specs(g: Geometry, grp: int):
    """Block specs on the grid ``(B, Hq, nq, nk)`` of the forward and dQ
    kernels: q-shaped blocks, KV blocks (clamped to the blocks q block
    ``i`` needs) and statistics rows."""
    bq, bk, d = g.block_q, g.block_k, g.head_dim

    def kv_map(b, h, i, j):
        lo, hi = g.kv_blocks(i)
        return b, jnp.clip(j, lo, hi), h // grp

    return (pl.BlockSpec((1, bq, d), lambda b, h, i, j: (b, i, h)),
            pl.BlockSpec((1, bk, d), kv_map),
            pl.BlockSpec((1, 1, 1, bq), lambda b, h, i, j: (b, h, 0, i)))


def _params(parallel: int, arbitrary: int):
    return pltpu.CompilerParams(dimension_semantics=(
        ("parallel",) * parallel + ("arbitrary",) * arbitrary))


def forward(q, k, v, g: Geometry):
    """q [B, Sq_pad, Hq * D], k/v [B, Skv_pad, Hkv * D] -> (o like q,
    float32 log-sum-exp [B, Hq, 1, Sq_pad])."""
    b, sq, _ = q.shape
    hq, _, grp = g.heads(q, k)
    q_spec, kv_spec, row_spec = _q_major_specs(g, grp)
    return pl.pallas_call(
        functools.partial(_fwd_kernel, g=g),
        grid=(b, hq, g.nq, g.nk),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec, row_spec],
        out_shape=[jax.ShapeDtypeStruct(q.shape, q.dtype),
                   jax.ShapeDtypeStruct((b, hq, 1, sq), F32)],
        scratch_shapes=[pltpu.VMEM((1, g.block_q), F32),
                        pltpu.VMEM((1, g.block_q), F32),
                        pltpu.VMEM((g.head_dim, g.block_q), F32)],
        compiler_params=_params(3, 1),
        interpret=g.interpret,
        name="flash_attention_fwd",
    )(q, k, v)


# ---------------------------------------------------------------------------
# Backward.
# ---------------------------------------------------------------------------

def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dq_ref,
               acc_scr, *, g: Geometry):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        acc_scr[...] = jnp.zeros_like(acc_scr)

    def step(masked: bool):
        k = _tile(k_ref, g)
        s = _dot(_scaled_q(q_ref, g), k, NT, g)
        p = jnp.exp(s - _col(lse_ref))
        if masked:
            p = jnp.where(g.mask(i, j), p, 0.0)
        dp = _dot(_tile(do_ref, g), _tile(v_ref, g), NT, g)
        ds = p * (dp - _col(delta_ref))
        acc_scr[...] += _dot(ds, k, NN, g)

    lo, hi = g.kv_blocks(i)
    _blocks(g, i, j, (j >= lo) & (j <= hi), step)

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        dq_ref[0] = (acc_scr[...] * g.scale).astype(dq_ref.dtype)


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, dk_ref,
                dv_ref, dk_scr, dv_scr, *, g: Geometry):
    j, gi, i = pl.program_id(2), pl.program_id(3), pl.program_id(4)

    @pl.when((gi == 0) & (i == 0))
    def _init():
        dk_scr[...] = jnp.zeros_like(dk_scr)
        dv_scr[...] = jnp.zeros_like(dv_scr)

    def step(masked: bool):
        q = _scaled_q(q_ref, g)
        do = _tile(do_ref, g)
        p_t = jnp.exp(_dot(_tile(k_ref, g), q, NT, g) - _row(lse_ref))
        if masked:
            p_t = jnp.where(g.mask(i, j, transposed=True), p_t, 0.0)
        dv_scr[...] += _dot(p_t, do, NN, g)
        dp_t = _dot(_tile(v_ref, g), do, NT, g)
        ds_t = p_t * (dp_t - _row(delta_ref))
        dk_scr[...] += _dot(ds_t, q, NN, g)

    lo, hi = g.q_blocks(j)
    _blocks(g, i, j, (i >= lo) & (i <= hi), step)

    @pl.when((gi == pl.num_programs(3) - 1) & (i == pl.num_programs(4) - 1))
    def _finish():
        dk_ref[0] = dk_scr[...].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[...].astype(dv_ref.dtype)


def backward(q, k, v, o, lse, do, g: Geometry):
    """Gradients (dq, dk, dv) of the forward pass's output, in the layouts
    and dtypes of q, k and v."""
    b, sq, _ = q.shape
    hq, hkv, grp = g.heads(q, k)
    d = g.head_dim
    bq, bk = g.block_q, g.block_k
    f32 = lambda x: x.astype(F32).reshape(b, sq, hq, d)     # noqa: E731
    delta = jnp.sum(f32(do) * f32(o), axis=-1).transpose(0, 2, 1)[:, :, None]

    q_spec, kv_spec, row_spec = _q_major_specs(g, grp)
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, g=g),
        grid=(b, hq, g.nq, g.nk),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=q_spec,
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((bq, d), F32)],
        compiler_params=_params(3, 1),
        interpret=g.interpret,
        name="flash_attention_dq",
    )(q, k, v, do, lse, delta)

    def q_block(j, i):
        lo, hi = g.q_blocks(j)
        return jnp.clip(i, lo, hi)

    q_spec = pl.BlockSpec((1, bq, d), lambda b, h, j, gi, i: (
        b, q_block(j, i), h * grp + gi))
    kv_spec = pl.BlockSpec((1, bk, d), lambda b, h, j, gi, i: (b, j, h))
    row_spec = pl.BlockSpec((1, 1, 1, bq), lambda b, h, j, gi, i: (
        b, h * grp + gi, 0, q_block(j, i)))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, g=g),
        grid=(b, hkv, g.nk, grp, g.nq),
        in_specs=[q_spec, kv_spec, kv_spec, q_spec, row_spec, row_spec],
        out_specs=[kv_spec, kv_spec],
        out_shape=[jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(v.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((bk, d), F32), pltpu.VMEM((bk, d), F32)],
        compiler_params=_params(3, 2),
        interpret=g.interpret,
        name="flash_attention_dkv",
    )(q, k, v, do, lse, delta)
    return dq, dk, dv

