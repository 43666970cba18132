"""The harness takes a configuration with experts: nested settings, the
expert group's first gradient, and check entries for every scanned
stage."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.chip import feed, harness, manifest, weights
from conftest import ROOT


def test_nested_setting_changes_the_group(tiny_moe):
    from repro.configs.base import reduced
    from repro.models import registry
    b = harness.build(tiny_moe(), jax.devices()[:1])
    cfg = registry.get_arch("deepseek-moe-16b").cfg
    small = reduced(cfg, num_layers=3)
    # the file names three fields of ``moe``; top_k, the shared experts
    # and the capacity stay the registry's
    assert b.cfg.moe == small.moe
    assert b.cfg.moe.top_k == cfg.moe.top_k == 6
    assert b.cfg.moe_skip_first == 1 and b.run.parallel.ep_axis == "data"
    # flat values behave as before
    assert harness.with_settings(cfg, {"num_layers": 6}) == \
        dataclasses.replace(cfg, num_layers=6)


def test_nested_setting_needs_a_group():
    from repro.models import registry
    cfg = registry.get_arch("qwen2-1.5b").cfg    # no experts
    with pytest.raises(ValueError, match="moe"):
        harness.with_settings(cfg, {"moe": {"num_experts": 8}})


def _program_grad_sq(b, params, batch):
    """Per entry, the squared norm of the gradient of the program's own
    loss, as the step hands it to the optimizer: the mean over the
    microbatches, clipped by the global norm.  On one device the expert
    exchange is the identity, so the model runs without it."""
    from repro.models.transformer import LM
    model = LM(b.cfg, dataclasses.replace(b.run.parallel, ep_axis=""))
    grad = jax.jit(jax.grad(lambda p, mb: model.loss(p, mb)[0]))
    micro = b.run.microbatch
    gs = [grad(params, {k: v[i:i + micro] for k, v in batch.items()})
          for i in range(0, b.rows, micro)]
    g = jax.tree.map(lambda *x: sum(x) / len(x), *gs)
    norm = float(jnp.sqrt(sum(jnp.sum(x * x) for x in jax.tree.leaves(g))))
    scale = min(1.0, b.cell.traffic["optimizer"]["grad_clip"] / norm)
    return np.asarray(weights.entry_sq_norms(g, b.ents)) * scale ** 2


def test_first_grad_under_expert_parallelism(tiny_moe):
    b = harness.build(tiny_moe(), jax.devices()[:1])
    # the expert group's state comes after the merge plan's buckets
    assert len(b.opt_shapes) == len(b.art.plan.buckets) + 1
    seed = 2**33 + 5
    host = feed.batch_at(b.cell.traffic, b.cfg.vocab_size, seed, 0, b.rows)
    one = jax.sharding.SingleDeviceSharding(jax.devices()[0])
    ref = _program_grad_sq(b, harness.make_params(b, seed, one),
                           jax.device_put(host))
    state = harness.make_state(b, seed)
    batch = jax.device_put(host, b.batch_sh)
    state, _ = harness.compile_step(b, state, batch)(state, batch)
    got = np.asarray(harness.first_grad_fn(b)(state.opt_state))
    names = [e[0] for e in b.ents]
    experts = [i for i, n in enumerate(names) if "_e']#" in n]
    assert len(experts) == 3 * 2        # three matrices in two layers
    assert np.all(ref > 0)
    np.testing.assert_allclose(np.sqrt(got), np.sqrt(ref), rtol=1e-5,
                               err_msg=str(names))


def test_first_grad_needs_every_parameter(tiny_moe):
    b = harness.build(tiny_moe(), jax.devices()[:1])
    # the expert group's state taken away: its leaves are in no bucket
    with pytest.raises(ValueError, match="cover every parameter"):
        harness.first_grad_fn(dataclasses.replace(
            b, opt_shapes=b.opt_shapes[:-1]))


def _entries(arch, model, parallel):
    from repro.models import registry
    from repro.models.transformer import LM
    bundle = registry.get_arch(arch)
    lm = LM(harness.with_settings(bundle.cfg, model),
            harness.with_settings(bundle.parallel, parallel))
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    return weights.entries(shapes, lm.stage_layout())


def test_entries_of_qwen2_stay():
    """The qwen2 cell's check entries, by name and in order, as its limits
    were set on them: one per leaf outside the layers, and one per layer
    of each leaf of the scanned stage."""
    cell = manifest.load_cell("qwen2-1.5b-f32.train4k-fill", ROOT)
    blk = "['stages'][0]['blk00']"
    leaves = ([f"['attn']['{w}']" for w in
               ("b_k", "b_q", "b_v", "w_k", "w_o", "w_q", "w_v")]
              + [f"['mlp']['{w}']" for w in ("w_down", "w_gate", "w_up")]
              + ["['norm1']", "['norm2']"])
    want = ["['embed']", "['final_norm']"] + [
        f"{blk}{leaf}#{layer}" for leaf in leaves for layer in range(6)]
    got = harness.build(cell, jax.devices()[:1]).ents
    assert [e[0] for e in got] == want
    assert [e[2] for e in got] == [None, None] + list(range(6)) * 12


TINY = {"num_layers": 4, "d_model": 64, "num_heads": 4, "num_kv_heads": 4,
        "head_dim": 16, "d_ff": 128, "vocab_size": 512}


@pytest.mark.parametrize("arch,model,want", [
    # a dense first layer, then a stage of three expert layers
    ("deepseek-moe-16b",
     dict(TINY, moe={"num_experts": 8, "d_expert": 32, "shared_d_expert": 32}),
     {"['stages'][0]['blk00']['mlp']['w_up']": [None],
      "['stages'][1]['blk00']['moe']['w_up_e']": [1, 2, 3],
      "['stages'][1]['blk00']['moe']['router']": [1, 2, 3]}),
    # a period of two (local, global) repeated twice
    ("gemma3-12b", dict(TINY, sliding_window=16, global_interval=2),
     {"['stages'][0]['blk00']['attn']['w_q']": [0, 2],
      "['stages'][0]['blk01']['attn']['w_q']": [1, 3]}),
])
def test_entries_of_every_scanned_stage(arch, model, want):
    got = _entries(arch, model, {"tp_enabled": False})
    for path, layers in want.items():
        mine = [e for e in got if e[1] == path]
        names = [path if l is None else f"{path}#{l}" for l in layers]
        assert [e[0] for e in mine] == names
        assert [e[2] for e in mine] == (
            [None] if layers == [None] else list(range(len(layers))))
    assert len({e[0] for e in got}) == len(got)
