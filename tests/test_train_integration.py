"""End-to-end training integration on a single device.

The strongest correctness checks in the suite:

* the loss **decreases** over a short run (real learning on the synthetic
  structured stream);
* all comm strategies (wfbp / single / mgwfbp / fixed) produce **identical
  losses** — gradient merging must be a pure scheduling change (the paper's
  'no side-effect on convergence' claim, §6.3.2);
* checkpoint-restore resumes to bit-identical parameters.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataPipeline
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.train import checkpoint
from repro.train.step import build_train_step


def _setup(arch="xlstm-125m", strategy=None, steps=1, zero=0, seed=0,
           lr=1e-2, **run_overrides):
    bundle = registry.reduced_arch(arch)
    par = dataclasses.replace(bundle.parallel, dp_axes=(), zero=zero,
                              ep_axis="", attn_chunk=32)
    shape = ShapeConfig("tiny", "train", 32, 4)
    run = dataclasses.replace(bundle.run_config("train_4k", par),
                              shape=shape, microbatch=0, learning_rate=lr,
                              **run_overrides)
    model = bundle.model(par)
    mesh = make_mesh((1,), ("data",))
    step_fn, init_fn, art = build_train_step(model, run, mesh,
                                             strategy=strategy)
    state = init_fn(jax.random.PRNGKey(seed))
    pipe = DataPipeline(bundle.cfg, shape, seed=seed)
    return jax.jit(step_fn), state, pipe, art


def test_loss_decreases():
    step_fn, state, pipe, _ = _setup("xlstm-125m", lr=3e-2)
    losses = []
    for s in range(40):
        state, metrics = step_fn(state, pipe.batch_at(s))
        losses.append(float(metrics["loss"]))
    assert np.isfinite(losses).all()
    assert np.mean(losses[-5:]) < np.mean(losses[:5]) - 0.2, losses


@pytest.mark.parametrize("arch", ["qwen2-1.5b", "deepseek-moe-16b"])
def test_strategies_identical_losses(arch):
    """Merging is pure scheduling: parameters after N steps are identical
    across comm strategies (single device: collectives are no-ops, but the
    bucketed code paths — pack/unpack, variadic psum grouping — differ)."""
    results = {}
    for strat in ("wfbp", "single", "mgwfbp", "fixed:65536"):
        step_fn, state, pipe, _ = _setup(arch, strategy=strat)
        for s in range(3):
            state, metrics = step_fn(state, pipe.batch_at(s))
        results[strat] = (float(metrics["loss"]),
                          np.asarray(jax.tree.leaves(state.params)[0],
                                     np.float32))
    base_loss, base_w = results["mgwfbp"]
    for strat, (loss, w) in results.items():
        assert loss == pytest.approx(base_loss, rel=1e-5), strat
        np.testing.assert_allclose(w, base_w, rtol=1e-5, atol=1e-6,
                                   err_msg=strat)


@pytest.mark.parametrize("hp", [
    {},
    # non-default AdamW hyperparameters: regression guard for the packed
    # ZeRO-1 update hardcoding b1/b2/eps instead of reading the config —
    # the sharded and replicated paths must agree for ANY betas.
    {"adam_b1": 0.85, "adam_b2": 0.999, "adam_eps": 1e-6},
])
def test_zero1_matches_zero0(hp):
    """ZeRO-1 sharded optimizer == replicated optimizer (1-device)."""
    sA, stA, pipeA, _ = _setup("qwen2-1.5b", zero=0, lr=1e-3, **hp)
    sB, stB, pipeB, _ = _setup("qwen2-1.5b", zero=1, lr=1e-3, **hp)
    for s in range(3):
        stA, mA = sA(stA, pipeA.batch_at(s))
        stB, mB = sB(stB, pipeB.batch_at(s))
    assert float(mA["loss"]) == pytest.approx(float(mB["loss"]), rel=1e-4)
    wA = np.asarray(jax.tree.leaves(stA.params)[0], np.float32)
    wB = np.asarray(jax.tree.leaves(stB.params)[0], np.float32)
    np.testing.assert_allclose(wA, wB, rtol=2e-3, atol=2e-3)


def test_zero1_step_keeps_state_types():
    """A ZeRO-1 step on a data mesh, accumulating float32 gradients over
    microbatches, returns the state in the shapes and dtypes it took:
    bfloat16 parameters stay bfloat16, so the jitted step never retraces."""
    bundle = registry.reduced_arch("qwen2-1.5b")
    par = dataclasses.replace(bundle.parallel, dp_axes=("data",), zero=1,
                              ep_axis="", attn_chunk=32)
    shape = ShapeConfig("tiny", "train", 32, 4)
    run = dataclasses.replace(bundle.run_config("train_4k", par),
                              shape=shape, microbatch=2)
    mesh = make_mesh((1,), ("data",))
    step_fn, init_fn, _ = build_train_step(bundle.model(par), run, mesh)
    state = init_fn(jax.random.PRNGKey(0))
    new_state, _ = jax.jit(step_fn)(
        state, DataPipeline(bundle.cfg, shape, seed=0).batch_at(0))

    def avals(tree):
        return jax.tree.map(lambda a: (a.shape, jnp.dtype(a.dtype)), tree)
    assert avals(new_state) == avals(state)


def test_lr_schedule_respects_run_config():
    """warmup_steps / total_steps flow from RunConfig into the step's LR
    schedule (previously hardcoded to 100 / 10000)."""
    step_fn, state, pipe, _ = _setup("xlstm-125m", lr=1e-2,
                                     warmup_steps=4, total_steps=50)
    lrs = []
    for s in range(6):
        state, metrics = step_fn(state, pipe.batch_at(s))
        lrs.append(float(metrics["lr"]))
    # linear warmup over 4 steps: lr(0)=0, rising to peak at step 4
    assert lrs[0] == pytest.approx(0.0, abs=1e-9)
    assert lrs[4] == pytest.approx(1e-2, rel=0.01)
    assert lrs[5] < lrs[4]              # cosine decay has begun (total=50)


def test_plan_override_identical_numerics():
    """plan_override swaps the bucketing but cannot change the math."""
    from repro.core import planner as planner_mod
    sA, stA, pipeA, art = _setup("xlstm-125m", strategy="wfbp")
    override = planner_mod.plan_single(art.specs)
    bundle = registry.reduced_arch("xlstm-125m")
    par = dataclasses.replace(bundle.parallel, dp_axes=(), zero=0,
                              ep_axis="", attn_chunk=32)
    shape = ShapeConfig("tiny", "train", 32, 4)
    run = dataclasses.replace(bundle.run_config("train_4k", par),
                              shape=shape, microbatch=0, learning_rate=1e-2)
    model = bundle.model(par)
    mesh = make_mesh((1,), ("data",))
    sB, initB, artB = build_train_step(model, run, mesh, strategy="wfbp",
                                       plan_override=override)
    assert artB.plan.buckets == override.buckets
    stB = initB(jax.random.PRNGKey(0))
    sB = jax.jit(sB)
    for s in range(3):
        stA, mA = sA(stA, pipeA.batch_at(s))
        stB, mB = sB(stB, pipeA.batch_at(s))
    for a, b in zip(jax.tree.leaves(stA.params),
                    jax.tree.leaves(stB.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_pack_kernel_step_matches_plain():
    """par.pack_kernel routes bucket collectives through the Pallas packed
    layout; on a (1,)-device data mesh the bucketed psums actually execute,
    and the kernel path must be update-for-update identical."""
    bundle = registry.reduced_arch("xlstm-125m")
    shape = ShapeConfig("tiny", "train", 32, 4)
    mesh = make_mesh((1,), ("data",))
    outs = {}
    for kernel in (False, True):
        par = dataclasses.replace(bundle.parallel, dp_axes=("data",), zero=0,
                                  ep_axis="", attn_chunk=32,
                                  pack_kernel=kernel)
        run = dataclasses.replace(bundle.run_config("train_4k", par),
                                  shape=shape, microbatch=0,
                                  learning_rate=1e-2)
        model = bundle.model(par)
        step_fn, init_fn, _ = build_train_step(model, run, mesh,
                                               strategy="mgwfbp")
        state = init_fn(jax.random.PRNGKey(0))
        pipe = DataPipeline(bundle.cfg, shape, seed=0)
        jstep = jax.jit(step_fn)
        for s in range(2):
            state, metrics = jstep(state, pipe.batch_at(s))
        outs[kernel] = jax.tree.leaves(state.params)
    for a, b in zip(outs[False], outs[True]):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))


def test_microbatch_accumulation_matches_full_batch():
    bundle = registry.reduced_arch("stablelm-1.6b")
    par = dataclasses.replace(bundle.parallel, dp_axes=(), zero=0,
                              ep_axis="", attn_chunk=32)
    shape = ShapeConfig("tiny", "train", 32, 4)
    mesh = make_mesh((1,), ("data",))
    model = bundle.model(par)
    outs = {}
    for micro in (0, 2):
        run = dataclasses.replace(bundle.run_config("train_4k", par),
                                  shape=shape, microbatch=micro,
                                  learning_rate=1e-3)
        step_fn, init_fn, _ = build_train_step(model, run, mesh)
        state = init_fn(jax.random.PRNGKey(0))
        pipe = DataPipeline(bundle.cfg, shape, seed=0)
        state, metrics = jax.jit(step_fn)(state, pipe.batch_at(0))
        outs[micro] = np.asarray(jax.tree.leaves(state.params)[0],
                                 np.float32)
    np.testing.assert_allclose(outs[0], outs[2], rtol=2e-3, atol=2e-3)


def test_checkpoint_resume_bitexact(tmp_path):
    step_fn, state, pipe, _ = _setup("xlstm-125m", seed=1)
    for s in range(3):
        state, _ = step_fn(state, pipe.batch_at(s))
    checkpoint.save(str(tmp_path), 3, state)
    # continue original
    cont = state
    for s in range(3, 6):
        cont, _ = step_fn(cont, pipe.batch_at(s))
    # restore + replay
    restored, start, _ = checkpoint.restore(str(tmp_path), state)
    assert start == 3
    for s in range(3, 6):
        restored, _ = step_fn(restored, pipe.batch_at(s))
    for a, b in zip(jax.tree.leaves(cont.params),
                    jax.tree.leaves(restored.params)):
        np.testing.assert_array_equal(np.asarray(a, np.float32),
                                      np.asarray(b, np.float32))
