"""Online refit + replan: the controller that closes the sim->real loop.

Fast tests drive :class:`repro.train.replan.ReplanController` with
synthetic IterationRecords; the slow test runs the full loop on a real
4-device CPU mesh in a subprocess (instrument -> refit -> Planner.update
-> step swap) and pins that a swap never changes numerics.
"""

import pathlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import planner as planner_mod
from repro.core.cost_model import AllReduceModel
from repro.core.planner import TensorSpec
from repro.core.simulator import simulate
from repro.obs.recorder import FlightRecorder, IterationRecord
from repro.train import replan


def _specs(n=8, nbytes=4 << 20, t_b=1e-3):
    return [TensorSpec(f"t{i}", nbytes, t_b) for i in range(n)]


def _record(i, t_iter):
    return IterationRecord(source="train", job="train", iteration=i,
                           start=float(i), end=float(i) + t_iter,
                           backward_end=float(i))


def test_controller_refits_and_swaps():
    """Observed comm 3x slower than modeled -> model rescales, the DP
    replan beats wfbp, and the rebuild callback swaps the step."""
    specs = _specs()
    model = AllReduceModel(1e-4, 1e-9)
    plan = planner_mod.plan_wfbp(specs)
    rec = FlightRecorder()
    rebuilt = []

    def rebuild(new_plan):
        rebuilt.append(new_plan)
        return lambda s, b: (s, b)          # stand-in step

    ctl = replan.ReplanController(specs, plan, model, rebuild=rebuild,
                                  recorder=rec, warmup=1, interval=2,
                                  damping=1.0, hysteresis=1e-6)
    pred = simulate(specs, plan, model)
    slow = pred.t_b_total + 3.0 * pred.t_c_no   # stretched fabric
    decisions = [ctl.observe(_record(i, slow)) for i in range(4)]
    fired = [d for d in decisions if d is not None]
    assert len(fired) == 1
    d = fired[0]
    assert d.stretch == pytest.approx(3.0, rel=1e-6)
    assert ctl.model.a == pytest.approx(3e-4, rel=1e-6)
    assert d.swapped and rebuilt and ctl.step_fn is not None
    assert ctl.plan.num_buckets < plan.num_buckets   # merged under higher a
    assert d.predicted_new < d.predicted_old
    # the planner's decision landed in the flight recorder
    assert rec.events("planner_update")


def test_controller_stable_when_prediction_holds():
    """Observations matching the model -> stretch 1, same plan, no swap."""
    specs = _specs()
    model = AllReduceModel(1e-4, 1e-9)
    plan = planner_mod.Planner(specs, model).plan()   # already optimal
    ctl = replan.ReplanController(specs, plan, model, warmup=1, interval=2,
                                  damping=1.0, hysteresis=0.05)
    pred = simulate(specs, plan, model)
    for i in range(6):
        ctl.observe(_record(i, pred.t_iter))
    assert ctl.decisions and all(not d.swapped for d in ctl.decisions)
    for d in ctl.decisions:
        assert d.stretch == pytest.approx(1.0, rel=1e-6)
    assert ctl.plan.buckets == plan.buckets


def test_controller_warmup_and_window():
    """No decision before warmup + a full window of records."""
    specs = _specs(4)
    model = AllReduceModel(1e-4, 1e-9)
    ctl = replan.ReplanController(specs, planner_mod.plan_wfbp(specs),
                                  model, warmup=3, interval=4)
    for i in range(6):                      # 3 warmup + 3 < interval
        assert ctl.observe(_record(i, 1.0)) is None
    assert ctl.observe(_record(6, 1.0)) is not None


def test_stretch_clamped():
    specs = _specs(4)
    model = AllReduceModel(1e-4, 1e-9)
    ctl = replan.ReplanController(specs, planner_mod.plan_wfbp(specs),
                                  model, warmup=0, interval=1, damping=1.0,
                                  max_stretch=5.0)
    d = ctl.observe(_record(0, 1e6))        # absurd wall time
    assert d.stretch == 5.0


def test_update_backward_times_incremental():
    specs = _specs(6)
    model = AllReduceModel(1e-4, 1e-9)
    ctl = replan.ReplanController(specs, planner_mod.plan_wfbp(specs), model)
    before = ctl.planner.scratch_plans
    ctl.update_backward_times({"t3": 5e-3, "t4": 6e-3})
    assert ctl.planner.scratch_plans == before      # incremental, no rebuild
    assert ctl.specs[3].t_b == 5e-3 and ctl.specs[4].t_b == 6e-3
    assert ctl.planner.specs[3].t_b == 5e-3
    # unknown / non-positive entries are ignored
    ctl.update_backward_times({"nope": 1.0, "t0": 0.0})
    assert ctl.specs[0].t_b == 1e-3


def test_drift_alerts_flow_to_recorder():
    specs = _specs()
    model = AllReduceModel(1e-4, 1e-9)
    plan = planner_mod.Planner(specs, model).plan()
    rec = FlightRecorder()
    ctl = replan.ReplanController(specs, plan, model, recorder=rec,
                                  warmup=2, interval=100,    # never refit
                                  drift_threshold=0.10)
    pred = simulate(specs, plan, model)
    for i in range(5):
        ctl.observe(_record(i, pred.t_iter * 2.0))   # sustained 100% drift
    assert rec.events("drift_alert")


def test_measure_comm_model_single_device():
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((1,), ("data",))
    m = replan.measure_comm_model(mesh, ("data",),
                                  sizes_bytes=(1 << 12, 1 << 14),
                                  n_warmup=0, n_iters=1)
    assert m.a >= 0.0 and m.b >= 0.0
    assert m.time(1 << 20) > 0.0


# ---------------------------------------------------------------------------
# full loop on 4 real (forced-host) devices — subprocess so XLA_FLAGS land
# before jax import; the rest of the suite keeps seeing 1 device.
# ---------------------------------------------------------------------------

_LOOP_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataPipeline
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.obs import recorder
from repro.train import replan
from repro.train.step import build_train_step, instrument_step

bundle = registry.reduced_arch("qwen2-1.5b")
par = dataclasses.replace(bundle.parallel, dp_axes=("data",), zero=0,
                          ep_axis="", attn_chunk=32)
shape = ShapeConfig("tiny", "train", 16, 8)
run_cfg = dataclasses.replace(bundle.run_config("train_4k", par),
                              shape=shape, microbatch=0)
model = bundle.model(par)
mesh = make_mesh((4,), ("data",))

# 1. MEASURE: real timed collectives fit the effective (a, b)
mdl = replan.measure_comm_model(mesh, ("data",),
                                sizes_bytes=(1 << 14, 1 << 18, 1 << 21),
                                n_iters=2)
assert mdl.a >= 0.0 and mdl.time(1 << 20) > 0.0

def run(steps, use_replan):
    rec = recorder.FlightRecorder()
    with jax.set_mesh(mesh):
        if use_replan:
            ctl, init_fn, art = replan.closed_loop(
                model, run_cfg, mesh, strategy="wfbp", comm_model=mdl,
                recorder=rec, warmup=1, interval=2, hysteresis=1e-9,
                damping=0.5)
        else:
            step_fn, init_fn, art = build_train_step(
                model, run_cfg, mesh, strategy="wfbp", comm_model=mdl)
            ctl = None
        sh = jax.tree.map(lambda s: NamedSharding(mesh, s), art.state_pspecs,
                          is_leaf=lambda x: isinstance(x, P))
        state = jax.device_put(init_fn(jax.random.PRNGKey(0)), sh)
        pipe = DataPipeline(bundle.cfg, shape, seed=0)
        fn = ctl.step_fn if ctl is not None else jax.jit(step_fn)
        for s in range(steps):
            if ctl is not None:
                fn = ctl.step_fn          # may have been swapped off-path
            state, m = fn(state, pipe.batch_at(s))
    return state, rec, ctl

# 2/3. EXECUTE + REFIT + REPLAN vs a never-replanned reference run
state_ref, _, _ = run(8, use_replan=False)
state_ctl, rec, ctl = run(8, use_replan=True)

assert ctl.decisions, "controller never refit"
assert ctl.swaps, "controller never swapped despite wfbp start + DP optimum"
assert rec.events("planner_update"), "Planner.update left no event trail"
assert rec.iterations("train"), "instrument_step recorded nothing"
swap = ctl.swaps[0]
assert swap.new_plan.num_buckets < swap.old_plan.num_buckets
assert swap.predicted_new <= swap.predicted_old

# 4. NUMERICS: a swap changes scheduling, never math — bit-identical params
for a, b in zip(jax.tree.leaves(state_ref.params),
                jax.tree.leaves(state_ctl.params)):
    np.testing.assert_array_equal(np.asarray(a, np.float32),
                                  np.asarray(b, np.float32))

# 5. KERNEL PARITY inside all-manual shard_map: the Pallas packed path ==
#    the plain concatenate path, for allreduce and for RS+AG
from repro.core import bucketer, comm, planner as planner_mod
tree = {"w": jnp.arange(4 * 600, dtype=jnp.float32).reshape(4, 600),
        "b": jnp.arange(40, dtype=jnp.float32) * 0.5}
metas = bucketer.leaf_metadata(tree)
specs = [planner_mod.TensorSpec(m.path, m.nbytes, 1e-4) for m in metas]
plan = planner_mod.plan_single(specs)

def make_ar(use_kernel):
    def body(t):
        return comm.bucketed_allreduce(t, plan, "data", mode="packed",
                                       use_kernel=use_kernel)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(), axis_names={"data"},
                                 check_vma=False))

plain = make_ar(False)(tree)
kern = make_ar(True)(tree)
for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(kern)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

def make_rsag(use_kernel):
    def body(t):
        shards, bm = comm.bucketed_reduce_scatter(t, plan, "data",
                                                  use_kernel=use_kernel)
        return comm.bucketed_allgather(shards, bm, t, "data",
                                       use_kernel=use_kernel)
    return jax.jit(jax.shard_map(body, mesh=mesh, in_specs=(P(),),
                                 out_specs=P(), axis_names={"data"},
                                 check_vma=False))

plain = make_rsag(False)(tree)
kern = make_rsag(True)(tree)
for a, b in zip(jax.tree.leaves(plain), jax.tree.leaves(kern)):
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))

print("REPLAN-LOOP-PASS")
"""


@pytest.mark.slow
def test_closed_loop_multidevice():
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).parent.parent / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _LOOP_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "REPLAN-LOOP-PASS" in res.stdout, \
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"
