"""Neural-net building blocks shared by all assigned architectures.

Pure-functional JAX: params are plain pytrees of arrays; every ``apply``
function is jit/vjp-safe.  Tensor-parallel sharding is expressed with
``with_sharding_constraint`` on the GSPMD-auto ``model`` axis (safe no-op
when no mesh with that axis is active, so single-device smoke tests run the
identical code).

The attention core is a chunked online-softmax (flash-attention schedule in
pure ``lax.scan`` form) so 32k-524k sequence dry-runs lower without
materializing S×S score matrices; the Pallas kernel in
``repro/kernels/flash_attention`` implements the same schedule with explicit
VMEM tiling for the TPU target and is validated against
:func:`attention_ref`.
"""

from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

NEG_INF = -1e30


# ---------------------------------------------------------------------------
# Sharding helper.
# ---------------------------------------------------------------------------

def _active_mesh_axis_names():
    """Non-Manual axis names of the ambient mesh, or None when no mesh."""
    mesh = jax.sharding.get_abstract_mesh()
    if mesh is None or mesh.empty:
        return None
    return {n for n, t in zip(mesh.axis_names, mesh.axis_types)
            if t != jax.sharding.AxisType.Manual}


def pshard(x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint that degrades to identity off-mesh and
    ignores axes that are manual in the current (shard_map) context."""
    names = _active_mesh_axis_names()
    if names is None:
        return x
    clean = []
    for s in spec:
        if s is None:
            clean.append(None)
        elif isinstance(s, str):
            clean.append(s if s in names else None)
        else:  # tuple of names
            kept = tuple(n for n in s if n in names)
            clean.append(kept if kept else None)
    if all(c is None for c in clean):
        return x
    return jax.lax.with_sharding_constraint(
        x, jax.sharding.PartitionSpec(*clean))


# ---------------------------------------------------------------------------
# Initializers.
# ---------------------------------------------------------------------------

def dense_init(key, d_in: int, d_out: int, dtype) -> jax.Array:
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def embed_init(key, vocab: int, d: int, dtype) -> jax.Array:
    return (jax.random.normal(key, (vocab, d), jnp.float32) * 0.02).astype(dtype)


# ---------------------------------------------------------------------------
# Norms.
# ---------------------------------------------------------------------------

def rms_norm(x: jax.Array, scale: jax.Array, eps: float = 1e-6) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    x = x * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32)).astype(dtype)


def layer_norm(x: jax.Array, scale: jax.Array, bias: jax.Array,
               eps: float = 1e-5) -> jax.Array:
    dtype = x.dtype
    x = x.astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    x = (x - mu) * jax.lax.rsqrt(var + eps)
    return (x * scale.astype(jnp.float32) + bias.astype(jnp.float32)
            ).astype(dtype)


# ---------------------------------------------------------------------------
# Rotary position embeddings.
# ---------------------------------------------------------------------------

def rope_angles(positions: jax.Array, head_dim: int,
                theta: float) -> tuple[jax.Array, jax.Array]:
    """cos/sin tables for given integer positions [.. S]."""
    half = head_dim // 2
    freq = 1.0 / (theta ** (np.arange(0, half, dtype=np.float32) * 2 / head_dim))
    ang = positions.astype(jnp.float32)[..., None] * freq  # [..., S, half]
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(x: jax.Array, cos: jax.Array, sin: jax.Array) -> jax.Array:
    """x: [B, S, H, D]; cos/sin: [S, D/2] (or broadcastable)."""
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    c = cos[None, :, None, :]
    s = sin[None, :, None, :]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1
                           ).astype(x.dtype)


# ---------------------------------------------------------------------------
# Chunked online-softmax attention (flash schedule in lax.scan form).
# ---------------------------------------------------------------------------

def _gqa_expand(q: jax.Array, num_kv: int) -> jax.Array:
    """[B,S,Hq,D] -> [B,S,Hkv,G,D]."""
    b, s, hq, d = q.shape
    return q.reshape(b, s, num_kv, hq // num_kv, d)


def attention_ref(q, k, v, *, causal: bool = True, window: int = 0,
                  q_offset=0, kv_len: Optional[jax.Array] = None,
                  scale: Optional[float] = None) -> jax.Array:
    """Full-materialization reference attention (tests / tiny shapes).

    q: [B,Sq,Hq,D], k/v: [B,Skv,Hkv,D]; GQA via head grouping.
    ``window > 0`` keeps keys with q_pos - k_pos in [0, window).
    ``kv_len`` ([B] int) masks cache positions >= kv_len (decode).
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = _gqa_expand(q * scale, hkv)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg.astype(jnp.float32),
                        k.astype(jnp.float32))
    q_pos = q_offset + jnp.arange(sq)
    k_pos = jnp.arange(skv)
    mask = jnp.ones((sq, skv), bool)
    if causal:
        mask &= k_pos[None, :] <= q_pos[:, None]
    if window:
        mask &= k_pos[None, :] > q_pos[:, None] - window
    mask = jnp.broadcast_to(mask[None], (b, sq, skv))
    if kv_len is not None:
        mask &= k_pos[None, None, :] < kv_len[:, None, None]
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    m = logits.max(axis=-1, keepdims=True)
    p = jnp.exp(logits - m) * mask[:, None, None]   # 0 for fully-masked rows
    w = p / jnp.maximum(p.sum(axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgst,btkd->bskgd", w, v.astype(jnp.float32))
    return out.reshape(b, sq, hq, d).astype(q.dtype)


def attention(q, k, v, *, causal: bool = True, window: int = 0,
              q_offset=0, kv_len: Optional[jax.Array] = None,
              chunk: int = 1024, scale: Optional[float] = None) -> jax.Array:
    """Memory-efficient attention: online softmax over KV chunks.

    Never materializes more than [B, Sq, H, chunk] of scores; exact same
    result as :func:`attention_ref` (tested).  This is the form the Pallas
    flash kernel implements with VMEM tiles on the TPU target.
    """
    b, sq, hq, d = q.shape
    skv, hkv = k.shape[1], k.shape[2]
    if skv <= chunk:
        return attention_ref(q, k, v, causal=causal, window=window,
                             q_offset=q_offset, kv_len=kv_len, scale=scale)
    n_chunks = -(-skv // chunk)
    pad = n_chunks * chunk - skv
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = _gqa_expand(q.astype(jnp.float32) * scale, hkv)   # [B,Sq,K,G,D]
    q_pos = q_offset + jnp.arange(sq)

    kc = k.reshape(b, n_chunks, chunk, hkv, d)
    vc = v.reshape(b, n_chunks, chunk, hkv, d)
    starts = jnp.arange(n_chunks) * chunk

    def body(carry, inp):
        m, l, acc = carry
        kb, vb, start = inp                                # [B,C,K,D]
        logits = jnp.einsum("bskgd,btkd->bkgst", qg, kb.astype(jnp.float32))
        k_pos = start + jnp.arange(chunk)
        mask = jnp.ones((sq, chunk), bool)
        if causal:
            mask &= k_pos[None, :] <= q_pos[:, None]
        if window:
            mask &= k_pos[None, :] > q_pos[:, None] - window
        mask &= (k_pos < skv)[None, :]
        mask = jnp.broadcast_to(mask[None], (b, sq, chunk))
        if kv_len is not None:
            mask = mask & (k_pos[None, None, :] < kv_len[:, None, None])
        logits = jnp.where(mask[:, None, None], logits, NEG_INF)
        m_new = jnp.maximum(m, logits.max(axis=-1))
        p = jnp.exp(logits - m_new[..., None]) * mask[:, None, None]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(axis=-1)
        acc_new = acc * corr[..., None] + jnp.einsum(
            "bkgst,btkd->bkgsd", p, vb.astype(jnp.float32))
        return (m_new, l_new, acc_new), None

    g = hq // hkv
    m0 = jnp.full((b, hkv, g, sq), NEG_INF, jnp.float32)
    l0 = jnp.zeros((b, hkv, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hkv, g, sq, d), jnp.float32)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0),
        (kc.transpose(1, 0, 2, 3, 4), vc.transpose(1, 0, 2, 3, 4), starts))
    out = acc / jnp.maximum(l, 1e-30)[..., None]           # [B,K,G,Sq,D]
    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d)
    return out.astype(q.dtype)


def decode_attend_seqsharded(q, k_local, v_local, *, axis: str,
                             shard_idx, kv_len, scale=None) -> jax.Array:
    """Single-token attention against a sequence-sharded KV cache.

    Used for ``long_500k`` (batch=1): the cache's sequence dim is sharded
    over the manual ``data`` axis; each shard computes partial (max, sum,
    acc) over its local chunk and the exact softmax is reconstructed with
    two psums + one pmax (flash-decode).  q: [B,1,Hq,D];
    k/v_local: [B,S_local,Hkv,D]; kv_len: [B] global valid length.
    """
    b, sq, hq, d = q.shape
    s_local, hkv = k_local.shape[1], k_local.shape[2]
    scale = scale if scale is not None else 1.0 / math.sqrt(d)
    qg = _gqa_expand(q.astype(jnp.float32) * scale, hkv)
    logits = jnp.einsum("bskgd,btkd->bkgst", qg, k_local.astype(jnp.float32))
    k_pos = shard_idx * s_local + jnp.arange(s_local)
    mask = k_pos[None, :] < kv_len[:, None]                 # [B,S_local]
    logits = jnp.where(mask[:, None, None, None], logits, NEG_INF)
    m_loc = logits.max(axis=-1)
    m_glob = jax.lax.pmax(m_loc, axis)
    p = jnp.exp(logits - m_glob[..., None]) * mask[:, None, None, None]
    l = jax.lax.psum(p.sum(axis=-1), axis)
    acc = jax.lax.psum(
        jnp.einsum("bkgst,btkd->bkgsd", p, v_local.astype(jnp.float32)), axis)
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, hq, d).astype(q.dtype)


# ---------------------------------------------------------------------------
# MLP.
# ---------------------------------------------------------------------------

def mlp_apply(params: dict, x: jax.Array, act: str = "swiglu") -> jax.Array:
    """SwiGLU (w_gate/w_up/w_down) or GELU (w_up/w_down) MLP with TP
    constraints on the hidden dim."""
    if act == "swiglu":
        h = jax.nn.silu(x @ params["w_gate"]) * (x @ params["w_up"])
    else:
        h = jax.nn.gelu(x @ params["w_up"] +
                        params.get("b_up", jnp.zeros((), x.dtype)))
    h = pshard(h, *([None] * (h.ndim - 1) + ["model"]))
    out = h @ params["w_down"]
    if "b_down" in params:
        out = out + params["b_down"]
    return out


def mlp_init(key, d_model: int, d_ff: int, act: str, dtype,
             bias: bool = False) -> dict:
    ks = jax.random.split(key, 3)
    p = {}
    if act == "swiglu":
        p["w_gate"] = dense_init(ks[0], d_model, d_ff, dtype)
    p["w_up"] = dense_init(ks[1], d_model, d_ff, dtype)
    p["w_down"] = dense_init(ks[2], d_ff, d_model, dtype)
    if bias:
        p["b_up"] = jnp.zeros((d_ff,), dtype)
        p["b_down"] = jnp.zeros((d_model,), dtype)
    return p


# ---------------------------------------------------------------------------
# Attention block params + apply.
# ---------------------------------------------------------------------------

def attn_init(key, d_model: int, num_heads: int, num_kv_heads: int,
              head_dim: int, dtype, qkv_bias: bool = False) -> dict:
    ks = jax.random.split(key, 4)
    p = {
        "w_q": dense_init(ks[0], d_model, num_heads * head_dim, dtype),
        "w_k": dense_init(ks[1], d_model, num_kv_heads * head_dim, dtype),
        "w_v": dense_init(ks[2], d_model, num_kv_heads * head_dim, dtype),
        "w_o": dense_init(ks[3], num_heads * head_dim, d_model, dtype),
    }
    if qkv_bias:
        p["b_q"] = jnp.zeros((num_heads * head_dim,), dtype)
        p["b_k"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
        p["b_v"] = jnp.zeros((num_kv_heads * head_dim,), dtype)
    return p


def qkv_proj(params: dict, x: jax.Array, num_heads: int, num_kv_heads: int,
             head_dim: int):
    b, s, _ = x.shape
    q = x @ params["w_q"]
    k = x @ params["w_k"]
    v = x @ params["w_v"]
    if "b_q" in params:
        q, k, v = q + params["b_q"], k + params["b_k"], v + params["b_v"]
    q = pshard(q.reshape(b, s, num_heads, head_dim), None, None, "model", None)
    k = pshard(k.reshape(b, s, num_kv_heads, head_dim), None, None, "model", None)
    v = pshard(v.reshape(b, s, num_kv_heads, head_dim), None, None, "model", None)
    return q, k, v


def out_proj(params: dict, o: jax.Array) -> jax.Array:
    b, s, h, d = o.shape
    return o.reshape(b, s, h * d) @ params["w_o"]
