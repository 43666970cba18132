"""Unified LM: one model class covering all 10 assigned architectures.

A model is a stack of *blocks*; ``ModelConfig.block_kind(i)`` names each
block's sequence mixer (attn / mamba / mlstm / slstm), its FFN (dense / moe
/ none) and its attention window.  Blocks are grouped into *stages*: a stage
is either a single unrolled block or a scanned repeat-group (period P
pattern × R repeats, params stacked on a leading R axis) — the
compile-time-tractable layout for 95-layer × 512-device dry-runs.

Entry points (all pure):
  * ``loss(params, batch)``                       — training objective
  * ``prefill(params, batch)``  -> (logits, cache)
  * ``decode_step(params, cache, tokens, pos)``   — one token w/ KV cache
  * ``init / init_cache / param_pspecs / input_specs``

KV caches: full-attention layers cache [B, max_len, Hkv, Dh] (rope applied
at write time); sliding-window layers use a ring buffer of ``window`` slots
with a slot→position table, so gemma3's 5:1 local:global pattern caches
window×5/6 of the naive footprint.  Mamba/xLSTM blocks carry O(1) state.
"""

from __future__ import annotations

import dataclasses
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig, ParallelConfig, ShapeConfig
from repro.models import layers, mamba, moe, xlstm

AUX_LOSS_COEF = 0.01


@dataclasses.dataclass(frozen=True)
class StageDef:
    first_layer: int       # global index of the stage's first block
    period: int            # blocks per repeat group
    repeats: int           # scanned repeats (1 => unrolled single group)
    encoder: bool = False  # whisper encoder stage

    @property
    def scanned(self) -> bool:
        return self.repeats > 1


def _dtype(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


class LM:
    """Decoder-only (or encoder-decoder) language model."""

    def __init__(self, cfg: ModelConfig,
                 parallel: ParallelConfig = ParallelConfig()):
        self.cfg = cfg
        self.parallel = parallel

    # ------------------------------------------------------------------
    # Stage layout.
    # ------------------------------------------------------------------

    def stage_layout(self) -> list[StageDef]:
        cfg = self.cfg
        stages: list[StageDef] = []
        if cfg.enc_dec:
            if self.parallel.scan_layers and cfg.enc_layers > 1:
                stages.append(StageDef(0, 1, cfg.enc_layers, encoder=True))
            else:
                stages += [StageDef(i, 1, 1, encoder=True)
                           for i in range(cfg.enc_layers)]
        n, skip = cfg.num_layers, cfg.moe_skip_first
        stages += [StageDef(i, 1, 1) for i in range(skip)]
        if not self.parallel.scan_layers:
            stages += [StageDef(i, 1, 1) for i in range(skip, n)]
            return stages
        period = cfg.repeat_period()
        repeats = (n - skip) // period
        if repeats <= 1:
            stages += [StageDef(i, 1, 1) for i in range(skip, n)]
        else:
            stages.append(StageDef(skip, period, repeats))
        return stages

    def _block_kind(self, layer_idx: int, encoder: bool) -> dict:
        if encoder:
            return {"mixer": "attn", "ffn": "dense", "window": 0,
                    "causal": False, "cross": False}
        k = self.cfg.block_kind(layer_idx)
        k["causal"] = True
        k["cross"] = self.cfg.enc_dec
        return k

    # ------------------------------------------------------------------
    # Init.
    # ------------------------------------------------------------------

    def _block_init(self, key, kind: dict) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        d, hd = cfg.d_model, cfg.resolved_head_dim
        ks = jax.random.split(key, 6)
        p: dict[str, Any] = {}
        if kind["mixer"] == "attn":
            p["norm1"] = jnp.ones((d,), dt)
            p["attn"] = layers.attn_init(ks[0], d, cfg.num_heads,
                                         cfg.num_kv_heads, hd, dt,
                                         cfg.qkv_bias)
        elif kind["mixer"] == "mamba":
            p["norm1"] = jnp.ones((d,), dt)
            p["mamba"] = mamba.mamba_init(ks[0], cfg, dt)
        elif kind["mixer"] == "mlstm":
            p["norm1"] = jnp.ones((d,), dt)
            p["mlstm"] = xlstm.mlstm_init(ks[0], cfg, dt)
        elif kind["mixer"] == "slstm":
            p["norm1"] = jnp.ones((d,), dt)
            p["slstm"] = xlstm.slstm_init(ks[0], cfg, dt)
        if kind["cross"]:
            p["norm_x"] = jnp.ones((d,), dt)
            p["cross"] = layers.attn_init(ks[1], d, cfg.num_heads,
                                          cfg.num_kv_heads, hd, dt, False)
        if kind["ffn"] == "dense":
            p["norm2"] = jnp.ones((d,), dt)
            p["mlp"] = layers.mlp_init(ks[2], d, cfg.d_ff, cfg.act, dt)
        elif kind["ffn"] == "moe":
            p["norm2"] = jnp.ones((d,), dt)
            p["moe"] = moe.moe_init(ks[3], cfg, dt)
        return p

    def init(self, key) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        keys = iter(jax.random.split(key, 4 + len(self.stage_layout()) * 2))
        params: dict[str, Any] = {
            "embed": layers.embed_init(next(keys), cfg.vocab_size,
                                       cfg.d_model, dt),
        }
        stages = []
        for st in self.stage_layout():
            kinds = [self._block_kind(st.first_layer + j, st.encoder)
                     for j in range(st.period)]

            def group_init(k, kinds=kinds):
                gks = jax.random.split(k, len(kinds))
                return {f"blk{j:02d}": self._block_init(gks[j], kinds[j])
                        for j in range(len(kinds))}

            if st.scanned:
                stages.append(jax.vmap(group_init)(
                    jax.random.split(next(keys), st.repeats)))
            else:
                stages.append(group_init(next(keys)))
        params["stages"] = stages
        params["final_norm"] = jnp.ones((cfg.d_model,), dt)
        if not cfg.tie_embeddings:
            params["lm_head"] = layers.dense_init(next(keys), cfg.d_model,
                                                  cfg.vocab_size, dt)
        if cfg.enc_dec:
            params["enc_norm"] = jnp.ones((cfg.d_model,), dt)
        return params

    # ------------------------------------------------------------------
    # Block apply (shared by train / prefill / decode).
    # ------------------------------------------------------------------

    def _attn_train(self, bp, x, kind, rope, enc_out=None):
        cfg, par = self.cfg, self.parallel
        hd = cfg.resolved_head_dim
        h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
        q, k, v = layers.qkv_proj(bp["attn"], h, cfg.num_heads,
                                  cfg.num_kv_heads, hd)
        if kind["mixer"] == "attn" and not kind.get("no_rope"):
            cos, sin = rope
            q, k = layers.apply_rope(q, cos, sin), layers.apply_rope(k, cos, sin)
        o = layers.attention(q, k, v, causal=kind["causal"],
                             window=kind["window"], chunk=par.attn_chunk)
        x = x + layers.out_proj(bp["attn"], o)
        if kind["cross"] and enc_out is not None:
            h = layers.rms_norm(x, bp["norm_x"], cfg.norm_eps)
            q, _, _ = layers.qkv_proj(bp["cross"], h, cfg.num_heads,
                                      cfg.num_kv_heads, hd)
            ke, ve = self._enc_kv(bp["cross"], enc_out)
            o = layers.attention(q, ke, ve, causal=False,
                                 chunk=par.attn_chunk)
            x = x + layers.out_proj(bp["cross"], o)
        return x, (k, v)

    def _enc_kv(self, ap, enc_out):
        cfg = self.cfg
        hd = cfg.resolved_head_dim
        b, s, _ = enc_out.shape
        ke = (enc_out @ ap["w_k"]).reshape(b, s, cfg.num_kv_heads, hd)
        ve = (enc_out @ ap["w_v"]).reshape(b, s, cfg.num_kv_heads, hd)
        return ke, ve

    def _block_train(self, bp, x, kind, rope, enc_out=None, collect=False):
        """One block forward.  With ``collect`` also returns the decode
        state the block would leave behind (prefill priming)."""
        cfg = self.cfg
        state: dict = {}
        if kind["mixer"] == "attn":
            x, kv = self._attn_train(bp, x, kind, rope, enc_out)
            if collect:
                state["kv"] = kv    # k already rope'd at its position
        elif kind["mixer"] == "mamba":
            h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
            out = mamba.mamba_apply(bp["mamba"], h, return_state=collect)
            if collect:
                out, state["mamba"] = out
            x = x + out
        elif kind["mixer"] == "mlstm":
            h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
            out = xlstm.mlstm_apply(bp["mlstm"], h, cfg, return_state=collect)
            if collect:
                out, state["mlstm"] = out
            x = x + out
        elif kind["mixer"] == "slstm":
            h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
            out = xlstm.slstm_apply(bp["slstm"], h, return_state=collect)
            if collect:
                out, state["slstm"] = out
            x = x + out
        x, aux = self._ffn_half(bp, x, kind)
        return x, aux, state

    # ------------------------------------------------------------------
    # Forward over stages (train / prefill).
    # ------------------------------------------------------------------

    def _run_stages(self, params, x, rope, enc_out=None, encoder=False,
                    collect_states=False):
        """Run all (matching) stages; returns (x, aux, states).

        ``states`` is a list parallel to the stage layout; each entry is a
        dict ``blkNN -> block state`` (stacked on a leading repeat axis for
        scanned stages), or None when not collecting / stage mismatched."""
        cfg, par = self.cfg, self.parallel
        aux_total = jnp.zeros((), jnp.float32)
        states: list[Any] = []

        for st, sp in zip(self.stage_layout(), params["stages"]):
            if st.encoder != encoder:
                states.append(None)
                continue
            kinds = [self._block_kind(st.first_layer + j, st.encoder)
                     for j in range(st.period)]

            def group_apply(gp, x, kinds=kinds):
                aux = jnp.zeros((), jnp.float32)
                st_out = {}
                for j, kind in enumerate(kinds):
                    x, a, bstate = self._block_train(
                        gp[f"blk{j:02d}"], x, kind, rope, enc_out,
                        collect=collect_states)
                    aux = aux + a
                    st_out[f"blk{j:02d}"] = bstate
                return x, aux, st_out

            alternating = (par.remat == "alternating" and st.scanned
                           and st.repeats % 2 == 0 and not collect_states)
            if par.remat == "block" or (par.remat == "alternating"
                                        and not alternating):
                group_apply = jax.checkpoint(group_apply, static_argnums=())

            if alternating:
                # remat every 2nd repeat-group: halves recompute FLOPs for
                # one group's worth of live internals (§Perf iteration)
                rematted = jax.checkpoint(group_apply, static_argnums=())

                def scan_body2(carry, gp2):
                    x, aux = carry
                    gp_a = jax.tree.map(lambda l: l[0], gp2)
                    gp_b = jax.tree.map(lambda l: l[1], gp2)
                    x, a1, _ = rematted(gp_a, x)
                    x, a2, _ = group_apply(gp_b, x)
                    return (x, aux + a1 + a2), None
                sp2 = jax.tree.map(
                    lambda l: l.reshape((st.repeats // 2, 2) + l.shape[1:]),
                    sp)
                (x, aux_total), _ = jax.lax.scan(scan_body2, (x, aux_total),
                                                 sp2)
                states.append(None)
            elif st.scanned:
                def scan_body(carry, gp):
                    x, aux = carry
                    x, a, s = group_apply(gp, x)
                    return (x, aux + a), s
                (x, aux_total), st_states = jax.lax.scan(
                    scan_body, (x, aux_total), sp)
                states.append(st_states if collect_states else None)
            else:
                x, a, st_states = group_apply(sp, x)
                aux_total = aux_total + a
                states.append(st_states if collect_states else None)
        return x, aux_total, states

    def _ffn_half(self, bp, x, kind):
        cfg = self.cfg
        aux = jnp.zeros((), jnp.float32)
        if kind["ffn"] == "dense":
            h = layers.rms_norm(x, bp["norm2"], cfg.norm_eps)
            x = x + layers.mlp_apply(bp["mlp"], h, cfg.act)
        elif kind["ffn"] == "moe":
            h = layers.rms_norm(x, bp["norm2"], cfg.norm_eps)
            mo, aux = moe.moe_apply(bp["moe"], h, cfg,
                                    self.parallel.ep_axis,
                                    self.parallel)
            x = x + mo
        return x, aux

    # ------------------------------------------------------------------
    # Embedding / head.
    # ------------------------------------------------------------------

    def _embed(self, params, tokens, prefix_embeds=None):
        cfg = self.cfg
        x = params["embed"][tokens] * math.sqrt(cfg.d_model)
        x = x.astype(_dtype(cfg))
        if prefix_embeds is not None:
            p = prefix_embeds.shape[1]
            x = jnp.concatenate(
                [prefix_embeds.astype(x.dtype), x[:, p:]], axis=1)
        return x

    def _head(self, params, x):
        cfg = self.cfg
        x = layers.rms_norm(x, params["final_norm"], cfg.norm_eps)
        w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
        logits = x @ w
        return layers.pshard(logits, None, None, "model")

    def _rope(self, positions):
        return layers.rope_angles(positions, self.cfg.resolved_head_dim,
                                  self.cfg.rope_theta)

    def _sinusoid(self, positions):
        """Sinusoidal positions for the enc-dec decoder (whisper uses a
        learned table capped at 448; sinusoidal removes the cap so the
        assigned 32k structural shapes lower — DESIGN.md §5)."""
        d = self.cfg.d_model
        half = d // 2
        freq = jnp.exp(-jnp.arange(half) * (jnp.log(10000.0) / (half - 1)))
        ang = positions.astype(jnp.float32)[:, None] * freq[None]
        return jnp.concatenate([jnp.sin(ang), jnp.cos(ang)], -1
                               ).astype(_dtype(self.cfg))

    # ------------------------------------------------------------------
    # Training loss.
    # ------------------------------------------------------------------

    def loss(self, params, batch) -> tuple[jax.Array, dict]:
        """batch: tokens [B,S] int32, labels [B,S] int32 (-1 = masked);
        optional prefix_embeds [B,P,d]; enc-dec adds enc_embeds [B,Se,d]."""
        cfg = self.cfg
        tokens, labels = batch["tokens"], batch["labels"]
        b, s = tokens.shape
        enc_out = None
        if cfg.enc_dec:
            enc = batch["enc_embeds"].astype(_dtype(cfg))
            rope_e = self._rope(jnp.arange(enc.shape[1]))
            enc, aux_e, _ = self._run_stages(params, enc, rope_e,
                                             encoder=True)
            enc_out = layers.rms_norm(enc, params["enc_norm"], cfg.norm_eps)
            x = self._embed(params, tokens)
            x = x + self._sinusoid(jnp.arange(s))[None]
        else:
            x = self._embed(params, tokens,
                            batch.get("prefix_embeds"))
        rope = self._rope(jnp.arange(s))
        x, aux, _ = self._run_stages(params, x, rope, enc_out=enc_out)
        logits = self._head(params, x)

        logits = logits.astype(jnp.float32)
        mask = (labels >= 0)
        safe = jnp.maximum(labels, 0)
        logz = jax.nn.logsumexp(logits, axis=-1)
        ll = jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
        nll = (logz - ll) * mask
        denom = jnp.maximum(mask.sum(), 1)
        loss = nll.sum() / denom
        total = loss + AUX_LOSS_COEF * aux
        return total, {"ce_loss": loss, "aux_loss": aux,
                       "tokens": denom.astype(jnp.float32)}

    # ------------------------------------------------------------------
    # KV cache.
    # ------------------------------------------------------------------

    def _cache_len(self, kind, max_len: int) -> int:
        w = kind["window"]
        return min(w, max_len) if w else max_len

    def _block_cache(self, kind, batch: int, max_len: int, enc_len: int) -> dict:
        cfg = self.cfg
        dt = _dtype(cfg)
        hd = cfg.resolved_head_dim
        c: dict[str, Any] = {}
        if kind["mixer"] == "attn":
            cl = self._cache_len(kind, max_len)
            c["k"] = jnp.zeros((batch, cl, cfg.num_kv_heads, hd), dt)
            c["v"] = jnp.zeros((batch, cl, cfg.num_kv_heads, hd), dt)
            c["slot_pos"] = jnp.full((cl,), -1, jnp.int32)
        elif kind["mixer"] == "mamba":
            c["mamba"] = mamba.mamba_cache_init(cfg, batch, dt)
        elif kind["mixer"] == "mlstm":
            c["mlstm"] = xlstm.mlstm_cache_init(cfg, batch)
        elif kind["mixer"] == "slstm":
            c["slstm"] = xlstm.slstm_cache_init(cfg, batch)
        if kind["cross"]:
            c["xk"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, hd), dt)
            c["xv"] = jnp.zeros((batch, enc_len, cfg.num_kv_heads, hd), dt)
        return c

    def init_cache(self, batch: int, max_len: int, enc_len: int = 0):
        caches = []
        for st in self.stage_layout():
            if st.encoder:
                caches.append({})
                continue
            kinds = [self._block_kind(st.first_layer + j, False)
                     for j in range(st.period)]
            group = {f"blk{j:02d}": self._block_cache(k, batch, max_len,
                                                      enc_len)
                     for j, k in enumerate(kinds)}
            if st.scanned:
                group = jax.tree.map(
                    lambda l: jnp.broadcast_to(l, (st.repeats,) + l.shape),
                    group)
            caches.append(group)
        return caches

    # ------------------------------------------------------------------
    # Decode.
    # ------------------------------------------------------------------

    def _attn_decode(self, bp, cache, x, kind, pos):
        """x: [B,1,d].  Returns (x, new block cache)."""
        cfg, par = self.cfg, self.parallel
        hd = cfg.resolved_head_dim
        h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
        q, k, v = layers.qkv_proj(bp["attn"], h, cfg.num_heads,
                                  cfg.num_kv_heads, hd)
        cos, sin = self._rope(pos[None])
        q, k = layers.apply_rope(q, cos, sin), layers.apply_rope(k, cos, sin)
        cl = cache["k"].shape[1]
        slot = pos % cl if kind["window"] else jnp.minimum(pos, cl - 1)
        kc = jax.lax.dynamic_update_slice_in_dim(cache["k"], k, slot, axis=1)
        vc = jax.lax.dynamic_update_slice_in_dim(cache["v"], v, slot, axis=1)
        spos = cache["slot_pos"].at[slot].set(pos)
        # mask: valid slot, causal, window
        valid = (spos >= 0) & (spos <= pos)
        if kind["window"]:
            valid &= spos > pos - kind["window"]
        o = self._masked_decode_attend(q, kc, vc, valid)
        x = x + layers.out_proj(bp["attn"], o)
        newc = {"k": kc, "v": vc, "slot_pos": spos}
        if kind["cross"]:
            h = layers.rms_norm(x, bp["norm_x"], cfg.norm_eps)
            qx, _, _ = layers.qkv_proj(bp["cross"], h, cfg.num_heads,
                                       cfg.num_kv_heads, hd)
            o = layers.attention(qx, cache["xk"], cache["xv"], causal=False,
                                 chunk=par.attn_chunk)
            x = x + layers.out_proj(bp["cross"], o)
            newc["xk"], newc["xv"] = cache["xk"], cache["xv"]
        return x, newc

    @staticmethod
    def _masked_decode_attend(q, kc, vc, valid):
        """q: [B,1,Hq,D]; kc/vc: [B,CL,Hkv,D]; valid: [CL] bool."""
        b, _, hq, d = q.shape
        hkv = kc.shape[2]
        qg = q.reshape(b, 1, hkv, hq // hkv, d).astype(jnp.float32)
        qg = qg / math.sqrt(d)
        logits = jnp.einsum("bskgd,btkd->bkgst", qg, kc.astype(jnp.float32))
        logits = jnp.where(valid[None, None, None, None, :], logits,
                           layers.NEG_INF)
        m = logits.max(axis=-1, keepdims=True)
        p = jnp.exp(logits - m) * valid[None, None, None, None, :]
        w = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
        o = jnp.einsum("bkgst,btkd->bskgd", w, vc.astype(jnp.float32))
        return o.reshape(b, 1, hq, d).astype(q.dtype)

    def _block_decode(self, bp, cache, x, kind, pos):
        cfg = self.cfg
        if kind["mixer"] == "attn":
            x, newc = self._attn_decode(bp, cache, x, kind, pos)
        elif kind["mixer"] == "mamba":
            h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
            y, mc = mamba.mamba_decode_step(bp["mamba"], cache["mamba"], h)
            x, newc = x + y, {"mamba": mc}
        elif kind["mixer"] == "mlstm":
            h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
            y, mc = xlstm.mlstm_decode_step(bp["mlstm"], cache["mlstm"], h, cfg)
            x, newc = x + y, {"mlstm": mc}
        elif kind["mixer"] == "slstm":
            h = layers.rms_norm(x, bp["norm1"], cfg.norm_eps)
            y, mc = xlstm.slstm_decode_step(bp["slstm"], cache["slstm"], h)
            x, newc = x + y, {"slstm": mc}
        x, _ = self._ffn_half(bp, x, kind)
        return x, newc

    def decode_step(self, params, cache, tokens, pos):
        """tokens: [B,1] int32; pos: scalar int32 (next position index)."""
        cfg = self.cfg
        x = self._embed(params, tokens)
        if cfg.enc_dec:
            x = x + self._sinusoid(pos[None])[None]
        new_caches = []
        for st, sp, sc in zip(self.stage_layout(), params["stages"], cache):
            if st.encoder:
                new_caches.append(sc)
                continue
            kinds = [self._block_kind(st.first_layer + j, False)
                     for j in range(st.period)]

            def group_decode(gp, gc, x, kinds=kinds):
                newg = {}
                for j, kind in enumerate(kinds):
                    x, nc = self._block_decode(gp[f"blk{j:02d}"],
                                               gc[f"blk{j:02d}"], x, kind, pos)
                    newg[f"blk{j:02d}"] = nc
                return x, newg

            if st.scanned:
                def scan_body(x, gp_gc):
                    gp, gc = gp_gc
                    x, newg = group_decode(gp, gc, x)
                    return x, newg
                x, newg = jax.lax.scan(scan_body, x, (sp, sc))
                new_caches.append(newg)
            else:
                x, newg = group_decode(sp, sc, x)
                new_caches.append(newg)
        logits = self._head(params, x)
        return logits, new_caches

    # ------------------------------------------------------------------
    # Prefill: run the full forward and materialize the cache.
    # ------------------------------------------------------------------

    def prefill(self, params, batch, max_len: int = 0):
        """batch: tokens [B,S] (+ prefix/enc embeds).  Returns (last-token
        logits [B,V], cache primed with positions 0..S-1)."""
        cfg = self.cfg
        tokens = batch["tokens"]
        b, s = tokens.shape
        max_len = max_len or s
        enc_out = None
        enc_len = 0
        if cfg.enc_dec:
            enc = batch["enc_embeds"].astype(_dtype(cfg))
            rope_e = self._rope(jnp.arange(enc.shape[1]))
            enc, _, _ = self._run_stages(params, enc, rope_e, encoder=True)
            enc_out = layers.rms_norm(enc, params["enc_norm"], cfg.norm_eps)
            enc_len = enc_out.shape[1]
            x = self._embed(params, tokens) + self._sinusoid(
                jnp.arange(s))[None]
        else:
            x = self._embed(params, tokens, batch.get("prefix_embeds"))
        rope = self._rope(jnp.arange(s))
        x, _, states = self._run_stages(params, x, rope, enc_out=enc_out,
                                        collect_states=True)
        logits = self._head(params, x[:, -1:, :])[:, 0]

        # Build the decode cache from collected block states.
        cache = self.init_cache(b, max_len, enc_len)
        layout = self.stage_layout()
        out_cache = list(cache)
        for idx, (st, st_states) in enumerate(zip(layout, states)):
            if st.encoder or st_states is None:
                continue
            sc = cache[idx]
            kinds = [self._block_kind(st.first_layer + j, False)
                     for j in range(st.period)]
            for j, kind in enumerate(kinds):
                blk = sc[f"blk{j:02d}"]
                bstate = st_states[f"blk{j:02d}"]
                if kind["mixer"] == "attn":
                    k, v = bstate["kv"]   # [B,S,H,D] / scanned [R,B,S,H,D]
                    cl = blk["k"].shape[-3]
                    kk, vv, spos = self._prime_cache_arrays(k, v, cl, s)
                    blk["k"], blk["v"], blk["slot_pos"] = kk, vv, spos
                else:
                    for key in ("mamba", "mlstm", "slstm"):
                        if key in bstate:
                            blk[key] = bstate[key]
                if kind["cross"]:
                    cross_p = params["stages"][idx][f"blk{j:02d}"]["cross"]
                    if st.scanned:
                        ke, ve = jax.vmap(
                            lambda ap: self._enc_kv(ap, enc_out))(cross_p)
                    else:
                        ke, ve = self._enc_kv(cross_p, enc_out)
                    blk["xk"], blk["xv"] = ke, ve
            out_cache[idx] = sc
        return logits, out_cache

    def _prime_cache_arrays(self, k, v, cache_len, s):
        """Place the last ``cache_len`` positions into the (ring) cache.
        k is already rope'd at its absolute position (applied in
        ``_attn_train``).  Works for stacked [R,B,S,H,D] and [B,S,H,D]."""

        def one(kr, v):
            if s <= cache_len:
                pad = cache_len - s
                kk = jnp.pad(kr, ((0, 0), (0, pad), (0, 0), (0, 0)))
                vv = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
                spos = jnp.where(jnp.arange(cache_len) < s,
                                 jnp.arange(cache_len), -1)
            else:
                # ring buffer: keep last cache_len positions
                positions = np.arange(s - cache_len, s)
                slots = positions % cache_len
                kk = jnp.zeros((kr.shape[0], cache_len) + kr.shape[2:],
                               kr.dtype)
                vv = jnp.zeros_like(kk)
                kk = kk.at[:, slots].set(kr[:, -cache_len:])
                vv = vv.at[:, slots].set(v[:, -cache_len:])
                spos = jnp.zeros((cache_len,), jnp.int32).at[slots].set(
                    jnp.asarray(positions, jnp.int32))
            return kk, vv, spos

        if k.ndim == 5:
            kk, vv, spos = jax.vmap(one)(k, v)
            return kk, vv, spos
        return one(k, v)
