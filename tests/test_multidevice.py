"""Multi-device distributed correctness (subprocess: needs
XLA_FLAGS=--xla_force_host_platform_device_count set before jax import, and
the rest of the suite must see 1 device).

The key check: 8-way data-parallel training with MG-WFBP bucketed
collectives produces the SAME loss trajectory as single-device training on
the identical global batch — distribution is semantically invisible.
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import dataclasses, jax, jax.numpy as jnp, numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.configs.base import ShapeConfig
    from repro.data.pipeline import DataPipeline
    from repro.launch.mesh import make_mesh
    from repro.models import registry
    from repro.train.step import build_train_step

    def run(arch, mesh_shape, axes, dp_axes, zero, ep, steps=3):
        bundle = registry.reduced_arch(arch)
        par = dataclasses.replace(bundle.parallel, dp_axes=dp_axes,
                                  zero=zero, ep_axis=ep, attn_chunk=32,
                                  hierarchical=len(dp_axes) > 1)
        shape = ShapeConfig("tiny", "train", 32, 8)
        run_cfg = dataclasses.replace(bundle.run_config("train_4k", par),
                                      shape=shape, microbatch=0,
                                      learning_rate=1e-2)
        model = bundle.model(par)
        mesh = make_mesh(mesh_shape, axes)
        with jax.set_mesh(mesh):
            step_fn, init_fn, art = build_train_step(model, run_cfg, mesh)
            sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                              art.state_pspecs,
                              is_leaf=lambda x: isinstance(x, P))
            state = jax.device_put(init_fn(jax.random.PRNGKey(0)), sh)
            pipe = DataPipeline(bundle.cfg, shape, seed=0)
            jstep = jax.jit(step_fn)
            losses = []
            bsh = NamedSharding(mesh, art.batch_pspec)
            for s in range(steps):
                batch = jax.tree.map(lambda x: jax.device_put(x, bsh),
                                     pipe.batch_at(s))
                state, m = jstep(state, batch)
                losses.append(float(m["loss"]))
            return losses

    # 1) DP(8) == single device, identical global batch
    l_dp = run("qwen2-1.5b", (8,), ("data",), ("data",), 0, "")
    l_1 = run("qwen2-1.5b", (1,), ("data",), (), 0, "")
    for a, b in zip(l_dp, l_1):
        assert abs(a - b) < 5e-3, (l_dp, l_1)
    print("DP==single OK", l_dp)

    # 2) multi-pod mesh + zero1 + hierarchical runs and learns
    l_mp = run("qwen2-1.5b", (2, 2, 2), ("pod", "data", "model"),
               ("pod", "data"), 1, "", steps=4)
    assert all(np.isfinite(l_mp)), l_mp
    print("multipod zero1 OK", l_mp)

    # 3) EP MoE on multi-pod mesh
    l_ep = run("deepseek-moe-16b", (2, 2, 2), ("pod", "data", "model"),
               ("pod", "data"), 1, "data", steps=2)
    assert all(np.isfinite(l_ep)), l_ep
    print("EP moe OK", l_ep)
    print("ALL-MULTIDEVICE-PASS")
""")


@pytest.mark.slow
def test_multidevice_suite():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "ALL-MULTIDEVICE-PASS" in res.stdout, \
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"
