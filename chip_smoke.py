#!/usr/bin/env python3
"""Bring-up smoke run of the MG-WFBP train step on TPU chips.

Drives the path a user calls: ``build_train_step`` -> ``jax.jit`` of the
step -> bucketed gradient reduction under a merge plan, with qwen2-1.5b at
its published widths and a cut depth, random weights from a seed and
synthetic token batches from ``DataPipeline``.

    python3 chip_smoke.py             # one chip
    python3 chip_smoke.py --chips 4   # four chips, data parallel

One chip: a few ``mgwfbp`` steps (finite losses, step times, peak device
memory), then one step through the ``bucket_pack`` Pallas kernel from the
same seed and batch, which must agree with the fused path.

Four chips: the same model on a (4, 1) ("data", "model") mesh with the
config's ZeRO-1, run under the ``mgwfbp``, ``wfbp`` and ``single`` plans
from one seed.  Bucketing only schedules the reduction, so their losses,
gradient norms and parameters must agree; every chip must hold its share
of the state.

The script refuses to run anywhere but on a TPU: it exits non-zero and
prints no result line.  On success the last line of its output is
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``.
It starts no other process and uses the chips from this one.
"""

import argparse
import dataclasses
import json
import math
import os
import sys
import time

ARCH = "qwen2-1.5b"
SEQ_LEN = 4096
BATCH_PER_CHIP = 8
MICROBATCH = 1
LAYERS = 6                   # of 28: the compiler's peak is 13.2 GB of 16
STEPS_ONE_CHIP = 5
STEPS_FOUR_CHIPS = 3
LOSS_RTOL = 2.0 ** -8        # bf16 rounding of one forward pass
# Bucketing and the pack kernel only reorder float additions, so two runs
# from one seed must take the same parameter updates: the norm of their
# difference stays within this share of the update's own norm.  (Compared
# leaf by leaf, a bf16 parameter whose update rounds across one boundary
# in one run and not the other diverges by whole ulps within a few steps.)
UPDATE_RTOL = 2.0 ** -5


def log(msg: str) -> None:
    print(msg, flush=True)


def fail(msg: str) -> None:
    print(f"chip_smoke: FAIL: {msg}", file=sys.stderr, flush=True)
    raise SystemExit(1)


def build(chips: int, strategy: str, pack_kernel: bool):
    """qwen2-1.5b at published widths, depth cut to LAYERS, and its step
    on a (chips, 1) ("data", "model") mesh."""
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh_for
    from repro.models import registry
    from repro.models.transformer import LM
    from repro.train.step import build_train_step

    bundle = registry.get_arch(ARCH)
    cfg = dataclasses.replace(bundle.cfg, num_layers=LAYERS)
    par = dataclasses.replace(bundle.parallel, dp_axes=("data",),
                              comm_strategy=strategy,
                              pack_kernel=pack_kernel)
    shape = ShapeConfig("smoke_4k", "train", SEQ_LEN, BATCH_PER_CHIP * chips)
    # warmup_steps=0: the first step already moves the weights, so the
    # parameter comparisons below see a real update
    run = dataclasses.replace(bundle.run_config("train_4k", par), model=cfg,
                              shape=shape, microbatch=MICROBATCH,
                              warmup_steps=0)
    model = LM(cfg, par)
    mesh = make_mesh_for(chips, par.tp_enabled)
    step_fn, init_fn, art = build_train_step(model, run, mesh)
    return bundle, cfg, run, mesh, step_fn, init_fn, art


def describe(bundle, cfg, run, mesh, art, chips: int) -> None:
    pub, train = bundle.cfg, bundle.run_config("train_4k")
    log(f"config: {cfg.name} d_model={cfg.d_model} heads={cfg.num_heads} "
        f"kv_heads={cfg.num_kv_heads} head_dim={cfg.resolved_head_dim} "
        f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} "
        f"tied_embeddings={cfg.tie_embeddings} dtype={cfg.dtype}")
    log(f"cuts: num_layers {pub.num_layers} -> {cfg.num_layers}; "
        f"global_batch {train.shape.global_batch} -> "
        f"{run.shape.global_batch} ({BATCH_PER_CHIP} per chip x {chips}); "
        f"microbatch {train.microbatch} -> {run.microbatch}; "
        f"seq_len {run.shape.seq_len} (as published in train_4k)")
    log(f"mesh: {dict(zip(mesh.axis_names, mesh.devices.shape))} "
        f"zero={run.parallel.zero} dp_axes={art.dp_axes}")
    plan = art.plan
    mib = [round(b / 2 ** 20, 1) for b in plan.bucket_bytes(art.specs)]
    log(f"plan: strategy={plan.strategy} buckets={plan.num_buckets} "
        f"tensors={plan.num_tensors} bucket_MiB={mib}")
    for k, bucket in enumerate(plan.buckets):
        log(f"  bucket {k}: " + ", ".join(art.specs[i].name for i in bucket))


def shardings_of(mesh, art):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    state = jax.tree.map(lambda s: NamedSharding(mesh, s), art.state_pspecs,
                         is_leaf=lambda x: isinstance(x, P))
    return state, NamedSharding(mesh, art.batch_pspec)


def initial_params(mesh, art, init_fn, seed: int):
    """The parameters every run starts from, initialised sharded."""
    import jax
    state_sh, _ = shardings_of(mesh, art)
    with jax.set_mesh(mesh):
        return jax.jit(lambda key: init_fn(key).params,
                       out_shardings=state_sh.params)(jax.random.PRNGKey(seed))


def compile_step(mesh, art, step_fn, init_fn, seed: int, batch):
    """Sharded init from ``seed`` and the compiled step; prints the
    compile time and the compiler's memory analysis."""
    import jax
    state_sh, _ = shardings_of(mesh, art)
    with jax.set_mesh(mesh):
        state = jax.jit(init_fn, out_shardings=state_sh)(
            jax.random.PRNGKey(seed))
        t0 = time.perf_counter()
        compiled = jax.jit(step_fn, donate_argnums=0).lower(
            state, batch).compile()
        dt = time.perf_counter() - t0
    ma = compiled.memory_analysis()
    log(f"compile_s={dt:.3f} compiler_peak_GB="
        f"{ma.peak_memory_in_bytes / 1e9:.3f} "
        f"args_GB={ma.argument_size_in_bytes / 1e9:.3f} "
        f"temps_GB={ma.temp_size_in_bytes / 1e9:.3f}")
    return state, compiled


def run_steps(compiled, state, batches, tag: str, first: int = 0):
    """Step through ``batches``; returns (state, losses, grad norms)."""
    import jax
    losses, gnorms = [], []
    for i, batch in enumerate(batches, start=first):
        t0 = time.perf_counter()
        state, metrics = compiled(state, batch)
        jax.block_until_ready((state, metrics))
        dt = time.perf_counter() - t0
        loss, gnorm = float(metrics["loss"]), float(metrics["grad_norm"])
        log(f"{tag} step {i}: loss={loss!r} grad_norm={gnorm!r} "
            f"step_s={dt:.6f}")
        if not (math.isfinite(loss) and math.isfinite(gnorm)):
            fail(f"{tag} step {i}: non-finite loss {loss} / grad norm {gnorm}")
        losses.append(loss)
        gnorms.append(gnorm)
    return state, losses, gnorms


def update_disagreement(a, b, p0):
    """(||a - b|| / ||b - p0||, largest |a - b|) over all parameters, where
    ``a`` and ``b`` are two runs' parameters and ``p0`` where both began."""
    import jax
    import jax.numpy as jnp

    def leaf(x, y, z):
        x, y, z = (t.astype(jnp.float32) for t in (x, y, z))
        return (jnp.sum(jnp.square(x - y)), jnp.sum(jnp.square(y - z)),
                jnp.max(jnp.abs(x - y)))

    out = [tuple(float(v) for v in t) for t in jax.tree.leaves(
        jax.tree.map(leaf, a, b, p0), is_leaf=lambda t: isinstance(t, tuple))]
    diff = math.sqrt(sum(o[0] for o in out))
    update = math.sqrt(sum(o[1] for o in out))
    return diff / update, max(o[2] for o in out)


def close(xs, ys, rtol: float) -> bool:
    return all(abs(x - y) <= rtol * max(abs(x), abs(y)) for x, y in zip(xs, ys))


def peak_memory(devices) -> list[int]:
    """``peak_bytes_in_use`` per device, printed beside the in-use bytes
    and the limit the runtime reports."""
    peaks = []
    for d in devices:
        stats = d.memory_stats() or {}
        peaks.append(int(stats.get("peak_bytes_in_use", 0)))
        log(f"memory device {d.id}: peak_bytes_in_use={peaks[-1]} "
            f"bytes_in_use={stats.get('bytes_in_use')} "
            f"bytes_limit={stats.get('bytes_limit')}")
    return peaks


def make_batches(cfg, run, batch_sh, steps: int):
    import jax
    from repro.data.pipeline import DataPipeline
    pipe = DataPipeline(cfg, run.shape, seed=run.seed)
    return [jax.tree.map(lambda x: jax.device_put(x, batch_sh),
                         pipe.batch_at(i)) for i in range(steps)]


def one_chip() -> None:
    import jax
    import jax.numpy as jnp

    bundle, cfg, run, mesh, step_fn, init_fn, art = build(
        1, "mgwfbp", pack_kernel=False)
    describe(bundle, cfg, run, mesh, art, 1)
    _, batch_sh = shardings_of(mesh, art)
    batches = make_batches(cfg, run, batch_sh, STEPS_ONE_CHIP)
    state, compiled = compile_step(mesh, art, step_fn, init_fn, run.seed,
                                   batches[0])
    state, l0, g0 = run_steps(compiled, state, batches[:1], "fused")
    ref_params = jax.tree.map(jnp.copy, state.params)
    state, losses, _ = run_steps(compiled, state, batches[1:], "fused",
                                    first=1)
    log(f"fused losses: {l0 + losses}")
    peak_memory(jax.devices())
    del state, compiled

    log("pack_kernel=True: one step from the same seed and batch")
    *_, kstep_fn, kinit_fn, kart = build(1, "mgwfbp", pack_kernel=True)
    kstate, kcompiled = compile_step(mesh, kart, kstep_fn, kinit_fn,
                                     run.seed, batches[0])
    kstate, kl, kg = run_steps(kcompiled, kstate, batches[:1], "kernel")
    kparams = kstate.params
    del kstate, kcompiled    # room for the initial parameters on the chip
    rel, worst = update_disagreement(
        kparams, ref_params, initial_params(mesh, kart, kinit_fn, run.seed))
    log(f"kernel vs fused: loss {kl[0]!r} vs {l0[0]!r}, grad_norm "
        f"{kg[0]!r} vs {g0[0]!r}, |dparam|/|update|={rel!r}, "
        f"max |dparam|={worst!r}")
    if not close(kl + kg, l0 + g0, LOSS_RTOL):
        fail("pack_kernel=True loss / grad norm disagree with the fused path")
    if rel > UPDATE_RTOL:
        fail("pack_kernel=True parameters disagree with the fused path")
    peak_memory(jax.devices())


def four_chips() -> None:
    import jax
    import jax.numpy as jnp

    devices = jax.devices()
    ref = p0 = None
    for strategy in ("mgwfbp", "wfbp", "single"):
        bundle, cfg, run, mesh, step_fn, init_fn, art = build(
            len(devices), strategy, pack_kernel=False)
        describe(bundle, cfg, run, mesh, art, len(devices))
        if p0 is None:
            p0 = initial_params(mesh, art, init_fn, run.seed)
        _, batch_sh = shardings_of(mesh, art)
        batches = make_batches(cfg, run, batch_sh, STEPS_FOUR_CHIPS)
        state, compiled = compile_step(mesh, art, step_fn, init_fn,
                                       run.seed, batches[0])
        check_spread(state, devices)
        state, losses, gnorms = run_steps(compiled, state, batches, strategy)
        peaks = peak_memory(devices)
        if min(peaks) < 0.5 * max(peaks):
            fail(f"{strategy}: device peaks {peaks} are lopsided")
        if ref is None:
            ref = (strategy, losses, gnorms,
                   jax.tree.map(jnp.copy, state.params))
        else:
            rel, worst = update_disagreement(state.params, ref[3], p0)
            log(f"{strategy} vs {ref[0]}: losses {losses} vs {ref[1]}, "
                f"grad norms {gnorms} vs {ref[2]}, "
                f"|dparam|/|update|={rel!r}, max |dparam|={worst!r}")
            if not close(losses + gnorms, ref[1] + ref[2], LOSS_RTOL):
                fail(f"{strategy} and {ref[0]} disagree in loss / grad norm")
            if rel > UPDATE_RTOL:
                fail(f"{strategy} and {ref[0]} disagree in parameters")
        del state, compiled


def check_spread(state, devices) -> None:
    """Every state leaf spans every chip, and each chip holds the same
    number of state bytes (ZeRO-1 shards the moments over ``data``)."""
    import jax
    held = {d: 0 for d in devices}
    for leaf in jax.tree.leaves(state):
        if leaf.sharding.device_set != set(devices):
            fail(f"a state leaf {leaf.shape} lives on "
                 f"{sorted(d.id for d in leaf.sharding.device_set)} only")
        for shard in leaf.addressable_shards:
            held[shard.device] += shard.data.nbytes
    log("state bytes per device: " + ", ".join(
        f"{d.id}:{b}" for d, b in held.items()))
    if len(set(held.values())) != 1:
        fail(f"state is not spread evenly: {held}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args(argv)

    import jax
    devices = jax.devices()
    dev = devices[0]
    log(f"device: platform={dev.platform} kind={dev.device_kind} "
        f"count={len(devices)}")
    if dev.platform != "tpu":
        fail(f"needs a TPU; JAX found platform {dev.platform!r}")
    if len(devices) != args.chips:
        fail(f"--chips {args.chips} but JAX sees {len(devices)} devices")

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(
        __file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache
    log(f"compile cache: {enable_compile_cache()}")

    if args.chips == 1:
        one_chip()
    else:
        four_chips()
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(devices)}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
