"""Real-loop smoke: measured-cost planning on an actual 4-device CPU mesh.

The closed loop the paper runs once offline — measure (a, b) and per-tensor
t_b, plan, execute — driven end to end on real jitted train steps with 4
forced host devices, plus the online half (refit + replan + step swap) via
:class:`repro.train.replan.ReplanController`.

Assertions (the acceptance gate):

* the DP plan built from MEASURED costs predicts a step time <= the wfbp
  plan under the same fitted model (DP optimality on real numbers — if the
  fit were degenerate or the simulate replay inconsistent, this breaks);
* the closed-loop controller refits from live IterationRecords and, seeded
  with the wfbp plan, swaps at least once toward a merged plan.

Wall-clock rows are informational (CPU psum timing is too noisy to gate).
Runs in a subprocess so ``XLA_FLAGS`` lands before jax imports and the
parent process keeps its single device.

This design is a CPU rehearsal only.  The parent (``benchmarks/run.py``)
has already imported JAX, and a chip belongs to one process: on a TPU the
parent holds it and this child cannot reach it.  A chip version runs the
loop in one process, as ``chip_smoke.py`` does.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys

_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json, time
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ShapeConfig
from repro.core import bucketer, planner as planner_mod, profiler
from repro.core.simulator import simulate
from repro.data.pipeline import DataPipeline
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.obs import recorder
from repro.train import replan
from repro.train.step import build_train_step

bundle = registry.reduced_arch("qwen2-1.5b")
par = dataclasses.replace(bundle.parallel, dp_axes=("data",), zero=0,
                          ep_axis="", attn_chunk=32)
shape = ShapeConfig("tiny", "train", 16, 8)
run_cfg = dataclasses.replace(bundle.run_config("train_4k", par),
                              shape=shape, microbatch=0)
model = bundle.model(par)
mesh = make_mesh((4,), ("data",))

# 1. MEASURE: fit (a, b) from real timed collectives, t_f / per-tensor t_b
#    from the real jitted loss + VJP.
mdl = replan.measure_comm_model(mesh, ("data",),
                                sizes_bytes=(1 << 14, 1 << 18, 1 << 21),
                                n_iters=2)
params = model.init(jax.random.PRNGKey(0))
pipe = DataPipeline(bundle.cfg, shape, seed=0)
batch = pipe.batch_at(0)
metas = bucketer.leaf_metadata(params)
t_f, tb_table = profiler.measure_loss_profile(
    lambda p, b: model.loss(p, b), (params, batch), metas, n_iters=2)

# 2. PLAN from the measured costs; wfbp is the baseline partition.
with jax.set_mesh(mesh):
    _, _, art = build_train_step(model, run_cfg, mesh, strategy="wfbp",
                                 tb_table=tb_table, comm_model=mdl)
specs = art.specs
plan_wfbp = art.plan
plan_dp = planner_mod.Planner(specs, mdl).plan()
pred_wfbp = simulate(specs, plan_wfbp, mdl, t_f)
pred_dp = simulate(specs, plan_dp, mdl, t_f)
assert pred_dp.t_iter <= pred_wfbp.t_iter + 1e-12, (
    f"DP plan predicts {pred_dp.t_iter} > wfbp {pred_wfbp.t_iter} "
    "under the measured model")

# 3. EXECUTE + REFIT + REPLAN: live controller seeded with wfbp.
rec = recorder.FlightRecorder()
steps = 6
with jax.set_mesh(mesh):
    ctl, init_fn, cart = replan.closed_loop(
        model, run_cfg, mesh, strategy="wfbp", tb_table=tb_table,
        comm_model=mdl, t_f=t_f, recorder=rec,
        warmup=1, interval=2, hysteresis=1e-9)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), cart.state_pspecs,
                      is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(init_fn(jax.random.PRNGKey(0)), sh)
    walls = []
    for s in range(steps):
        fn = ctl.step_fn
        t0 = time.perf_counter()
        state, m = fn(state, pipe.batch_at(s))
        jax.block_until_ready(m)
        walls.append(time.perf_counter() - t0)

assert ctl.swaps, "controller never swapped off the wfbp seed"
assert rec.events("planner_update"), "no planner_update events recorded"
wall_after = min(walls[-2:])      # best-of post-swap (compile excluded)

print(json.dumps({
    "a_us": mdl.a * 1e6, "b_ns_per_byte": mdl.b * 1e9,
    "t_f_ms": t_f * 1e3, "tb_total_ms": sum(tb_table.values()) * 1e3,
    "num_tensors": len(specs),
    "wfbp_buckets": plan_wfbp.num_buckets, "dp_buckets": plan_dp.num_buckets,
    "pred_wfbp_ms": pred_wfbp.t_iter * 1e3,
    "pred_dp_ms": pred_dp.t_iter * 1e3,
    "swaps": len(ctl.swaps), "refits": len(ctl.decisions),
    "wall_step_ms": wall_after * 1e3,
}))
print("REAL-LOOP-OK")
"""


def run() -> list[tuple[str, float, str]]:
    env = dict(os.environ)
    src = str(pathlib.Path(__file__).resolve().parent.parent / "src")
    env["PYTHONPATH"] = src + os.pathsep + env.get("PYTHONPATH", "")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=1800)
    if "REAL-LOOP-OK" not in res.stdout:
        raise RuntimeError(
            f"real_loop subprocess failed\nstdout:\n{res.stdout[-2000:]}\n"
            f"stderr:\n{res.stderr[-2000:]}")
    payload = json.loads(res.stdout.strip().splitlines()[-2])
    speedup = (payload["pred_wfbp_ms"] / payload["pred_dp_ms"]
               if payload["pred_dp_ms"] > 0 else 1.0)
    return [
        ("real_loop.measured_a", payload["a_us"],
         f"fitted startup us (b={payload['b_ns_per_byte']:.3f} ns/B)"),
        ("real_loop.t_f", payload["t_f_ms"] * 1e3,
         f"measured forward ms={payload['t_f_ms']:.2f} "
         f"tb_total_ms={payload['tb_total_ms']:.2f}"),
        ("real_loop.pred_wfbp", payload["pred_wfbp_ms"] * 1e3,
         f"predicted wfbp step ms={payload['pred_wfbp_ms']:.2f} "
         f"({payload['wfbp_buckets']} buckets)"),
        ("real_loop.pred_planned", payload["pred_dp_ms"] * 1e3,
         f"predicted planned step ms={payload['pred_dp_ms']:.2f} "
         f"({payload['dp_buckets']} buckets) <= wfbp "
         f"(x{speedup:.2f})"),
        ("real_loop.swaps", float(payload["swaps"]),
         f"live step swaps ({payload['refits']} refits, "
         f"{payload['num_tensors']} tensors)"),
        ("real_loop.wall_step", payload["wall_step_ms"] * 1e3,
         f"post-swap wall step ms={payload['wall_step_ms']:.2f} "
         "(informational: CPU mesh)"),
    ]


if __name__ == "__main__":
    for name, value, derived in run():
        print(f"{name},{value:.3f},{derived}")
