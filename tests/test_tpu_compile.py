"""Compile the main path's kernels and the qwen2-1.5b step for a TPU v5e.

Nothing runs: each test compiles against a described ``v5e:2x2`` topology,
which raises what the chip's compiler would raise (tiling the interpreter
accepts, VMEM limits, programs that do not fit, kernels that cannot be
partitioned).  The topology is described inside a fixture, never at
import, and the tests skip where it cannot be described.  They stay in
this one file so that one test worker loads the TPU compiler.
"""

import dataclasses

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import NamedSharding, SingleDeviceSharding

from repro.kernels.bucket_pack import kernel as bp_kernel, ops as bp_ops
from repro.kernels.flash_attention import ops as fa_ops

V5E_HBM_BYTES = 16e9
# qwen2-1.5b gradient leaves: (d_ff, d_model), (d_model, d_model) and the
# stacked k/v projections (2 * kv_heads * head_dim, d_model)
QWEN2_BUCKET_SHAPES = [(1536, 8960), (1536, 1536), (512, 1536)]


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "not here"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compiles_kernel(fn, *args) -> str:
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", QWEN2_BUCKET_SHAPES)
def test_bucket_pack_compiles_at_qwen2_bucket_sizes(one_chip, shape, dtype):
    leaves = [jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip),
              jax.ShapeDtypeStruct((1536,), dtype, sharding=one_chip)]
    _compiles_kernel(lambda a, b: bp_ops.pack([a, b], interpret=False),
                     *leaves)
    total = sum(bp_kernel.slot_elems(l.size) for l in leaves)
    buf = jax.ShapeDtypeStruct((total,), dtype, sharding=one_chip)
    _compiles_kernel(
        lambda b: bp_ops.unpack(b, [l.shape for l in leaves],
                                [l.dtype for l in leaves], interpret=False),
        buf)


def test_flash_attention_compiles_at_4096_gqa(one_chip):
    q = jax.ShapeDtypeStruct((1, 4096, 12, 128), jnp.bfloat16,
                             sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 2, 128), jnp.bfloat16,
                              sharding=one_chip)
    _compiles_kernel(
        lambda q, k, v: fa_ops.flash_attention(q, k, v, interpret=False),
        q, kv, kv)


@pytest.mark.parametrize("pack_kernel", [False, True])
def test_qwen2_step_compiles_and_fits_one_chip(topo, monkeypatch,
                                               pack_kernel):
    """The one-chip step of chip_smoke.py at published widths, 2 layers."""
    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh
    from repro.models import registry
    from repro.models.transformer import LM
    from repro.train.step import build_train_step

    # the CPU backend would pick interpret mode; compile the real kernel
    monkeypatch.setattr(bp_ops, "_auto_interpret", lambda: False)
    bundle = registry.get_arch("qwen2-1.5b")
    cfg = dataclasses.replace(bundle.cfg, num_layers=2)
    par = dataclasses.replace(bundle.parallel, dp_axes=("data",),
                              pack_kernel=pack_kernel)
    run = dataclasses.replace(
        bundle.run_config("train_4k", par), model=cfg, microbatch=1,
        shape=ShapeConfig("smoke_4k", "train", 4096, 8))
    mesh = make_mesh((1, 1), ("data", "model"), devices=topo.devices[:1])
    step_fn, init_fn, art = build_train_step(LM(cfg, par), run, mesh)

    def sds(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))

    state = jax.tree.map(sds, jax.eval_shape(init_fn, jax.random.PRNGKey(0)),
                         art.state_pspecs,
                         is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    batch = jax.tree.map(lambda l: sds(l, art.batch_pspec),
                         registry.train_input_specs(cfg, run.shape))
    with jax.set_mesh(mesh):
        compiled = jax.jit(step_fn, donate_argnums=0).lower(
            state, batch).compile()
    assert compiled.memory_analysis().peak_memory_in_bytes < V5E_HBM_BYTES
    assert ("tpu_custom_call" in compiled.as_text()) == pack_kernel
