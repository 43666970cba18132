"""Mixture-of-Experts FFN: top-k routing, capacity dispatch, optional EP.

Covers the three assigned MoE flavours:

* deepseek-moe-16b — fine-grained: 64 routed experts top-6 **plus** 2
  always-on shared experts;
* arctic-480b      — 128 routed top-2 **plus** a dense FFN residual running
  in parallel;
* jamba-v0.1-52b   — 16 routed top-2 on every other layer.

Dispatch is sort-free capacity-style but built with a cumsum-free
*sort-position* trick to avoid T×E×C one-hot tensors: assignments are
argsorted by expert id and positions-within-group are recovered with a
cummax, so peak extra memory is O(T·k) integers.  Expert compute uses
stacked-weight einsums ([E, d, f]) so FLOPs scale with capacity·E =
tokens·top_k·capacity_factor, not with E.

Expert parallelism: when ``ep_axis`` names a *manual* shard_map axis, the
expert dim of the dispatch buffer is exchanged with ``lax.all_to_all`` so
each shard computes only its local experts (weights enter pre-sharded on
dim 0).  Expert gradients are then owned per-shard and excluded from the
MG-WFBP data-parallel reduction (see train/step.py).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import layers


def moe_init(key, cfg: ModelConfig, dtype) -> dict:
    m = cfg.moe
    d = cfg.d_model
    ks = jax.random.split(key, 6)
    p = {
        "router": layers.dense_init(ks[0], d, m.num_experts, jnp.float32),
        "w_gate_e": _experts_init(ks[1], m.num_experts, d, m.d_expert, dtype),
        "w_up_e": _experts_init(ks[2], m.num_experts, d, m.d_expert, dtype),
        "w_down_e": _experts_init(ks[3], m.num_experts, m.d_expert, d, dtype),
    }
    if m.num_shared_experts:
        ds = m.shared_d_expert * m.num_shared_experts
        p["shared"] = layers.mlp_init(ks[4], d, ds, "swiglu", dtype)
    if cfg.dense_residual and cfg.d_ff > 0:
        p["dense_residual"] = layers.mlp_init(ks[5], d, cfg.d_ff, "swiglu",
                                              dtype)
    return p


def _experts_init(key, e: int, d_in: int, d_out: int, dtype):
    scale = 1.0 / math.sqrt(d_in)
    return (jax.random.normal(key, (e, d_in, d_out), jnp.float32) * scale
            ).astype(dtype)


def _positions_in_expert(flat_e: jax.Array, num_experts: int) -> jax.Array:
    """Per-assignment arrival position within its expert, O(Tk log Tk).

    argsort by expert id; within the sorted order, positions are
    ``arange - group_start`` where group_start is recovered by a cummax
    over boundary markers; scatter back to unsorted order.
    """
    n = flat_e.shape[0]
    order = jnp.argsort(flat_e)
    sorted_e = flat_e[order]
    idx = jnp.arange(n)
    is_start = jnp.concatenate([jnp.array([True]),
                                sorted_e[1:] != sorted_e[:-1]])
    group_start = jax.lax.cummax(jnp.where(is_start, idx, 0))
    pos_sorted = idx - group_start
    return jnp.zeros((n,), jnp.int32).at[order].set(pos_sorted.astype(jnp.int32))


def moe_apply(params: dict, x: jax.Array, cfg: ModelConfig,
              ep_axis: str = "", parallel=None
              ) -> tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux_loss scalar).

    Perf knobs (ParallelConfig, see §Perf):
      * ``moe_token_shard``  — shard expert compute over the capacity dim
        (expert weights replicated across TP): the down-projection then
        partitions over rows with NO partial-sum all-reduce of the
        7.5x-capacity buffer;
      * ``moe_combine_dtype`` — combine/scatter arithmetic dtype (fp32
        default; bf16 halves the backward all-to-all bytes);
      * ``moe_capacity_factor`` — override the config's 1.25."""
    m = cfg.moe
    token_shard = bool(parallel and parallel.moe_token_shard)
    cdt = (jnp.dtype(parallel.moe_combine_dtype)
           if parallel and parallel.moe_combine_dtype else jnp.float32)
    cap_factor = (parallel.moe_capacity_factor
                  if parallel and parallel.moe_capacity_factor
                  else m.capacity_factor)
    b, s, d = x.shape
    t = b * s
    xf = x.reshape(t, d)

    # --- routing (fp32 for stability) ---
    logits = xf.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)                   # [T, E]
    gate, eidx = jax.lax.top_k(probs, m.top_k)                # [T, k]
    gate = gate / jnp.maximum(gate.sum(-1, keepdims=True), 1e-9)
    # load-balance auxiliary loss (Switch-style)
    me = probs.mean(axis=0)
    ce = jnp.zeros((m.num_experts,), jnp.float32).at[eidx.reshape(-1)].add(
        1.0 / (t * m.top_k))
    aux = m.num_experts * jnp.sum(me * ce)

    # --- capacity dispatch ---
    ep = jax.lax.axis_size(ep_axis) if ep_axis else 1
    cap = int(math.ceil(t * m.top_k / m.num_experts * cap_factor))
    cap = max(8, -(-cap // 8) * 8)
    if ep > 1:
        cap = -(-cap // ep) * ep  # divisible for all_to_all tiling
    flat_e = eidx.reshape(-1)                                  # [T*k]
    pos = _positions_in_expert(flat_e, m.num_experts)
    keep = pos < cap
    dst = flat_e * cap + jnp.minimum(pos, cap - 1)             # [T*k]
    src_token = jnp.repeat(jnp.arange(t), m.top_k)
    disp = jnp.zeros((m.num_experts * cap, d), x.dtype)
    disp = disp.at[dst].add(
        jnp.where(keep[:, None], xf[src_token], 0).astype(x.dtype))
    disp = disp.reshape(m.num_experts, cap, d)

    # --- expert parallelism: exchange expert dim over the manual axis ---
    if ep > 1:
        disp = jax.lax.all_to_all(disp, ep_axis, split_axis=0, concat_axis=1,
                                  tiled=True)                  # [E/ep, cap*ep, d]
    wg, wu, wd = params["w_gate_e"], params["w_up_e"], params["w_down_e"]
    if token_shard:
        disp = layers.pshard(disp, None, "model", None)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", disp, wg)) * jnp.einsum(
        "ecd,edf->ecf", disp, wu)
    if token_shard:
        h = layers.pshard(h, None, "model", None)
    else:
        h = layers.pshard(h, None, None, "model")
    eout = jnp.einsum("ecf,efd->ecd", h, wd)
    if token_shard:
        eout = layers.pshard(eout, None, "model", None)
    if ep > 1:
        eout = jax.lax.all_to_all(eout, ep_axis, split_axis=1, concat_axis=0,
                                  tiled=True)                  # [E, cap, d]
    eout = eout.reshape(m.num_experts * cap, d)

    # --- combine ---
    gathered = eout[dst]                                        # [T*k, d]
    w = jnp.where(keep, gate.reshape(-1), 0.0).astype(cdt)
    out = jnp.zeros((t, d), cdt).at[src_token].add(
        gathered.astype(cdt) * w[:, None])
    out = out.astype(x.dtype)

    # --- always-on paths ---
    if "shared" in params:
        out = out + layers.mlp_apply(params["shared"], xf, "swiglu")
    if "dense_residual" in params:
        out = out + layers.mlp_apply(params["dense_residual"], xf, "swiglu")
    return out.reshape(b, s, d), aux
