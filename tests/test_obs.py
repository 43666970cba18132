"""Observability spine: metrics, recorder, timeline, drift.

Covers the obs acceptance criteria end to end:

* registry semantics (labels, kinds, snapshot delta/merge exactness);
* flight-recorder ring eviction + lossless JSONL round-trip;
* engine/planner/co-planner emission into one recorder;
* drift monitor silent-when-calibrated, and the full
  degrade -> alert -> refit -> replan -> recovered loop;
* sim + real-step records merging into ONE golden-pinned Chrome trace
  (regen:  PYTHONPATH=src python tests/test_obs.py --regen).
"""

import json
import pathlib
import types

import pytest

from repro.core.cost_model import AllReduceModel
from repro.core.planner import Planner, SpecDelta, make_plan
from repro.obs import drift, metrics, recorder, timeline
from repro.sim import scenarios, trace
from repro.sim.engine import ClusterSim, JobSpec, Topology
from repro.sim.schedules import LocalSGD
from repro.sim.workers import make_workers

GOLDEN_DIR = pathlib.Path(__file__).parent / "golden"
MODEL = AllReduceModel(4e-4, 1.5e-9)


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_labels_and_kind_guard():
    reg = metrics.Registry()
    c = reg.counter("requests_total", "test")
    c.inc(job="a")
    c.inc(2.0, job="a")
    c.inc(job="b")
    assert c.value(job="a") == 3.0
    assert c.value(job="b") == 1.0
    assert c.value(job="missing") == 0.0
    with pytest.raises(ValueError):
        c.inc(-1.0)
    with pytest.raises(TypeError):
        reg.gauge("requests_total", "redeclared as another kind")


def test_gauge_set_add():
    reg = metrics.Registry()
    g = reg.gauge("depth", "test")
    g.set(5.0)
    g.add(-2.0)
    assert g.value() == 3.0


def test_histogram_buckets_are_exact_and_quantile_bounded():
    reg = metrics.Registry()
    h = reg.histogram("lat", "test")
    values = [0.001, 0.25, 0.5, 1.0, 3.0, 100.0]
    for v in values:
        h.observe(v)
    assert h.count() == len(values)
    q = h.quantile(0.5)
    assert min(values) <= q <= max(values)
    # fixed exponential buckets: same value always lands in the same
    # bucket, so merged histograms are exact integer sums
    assert metrics.bucket_index(0.75) == metrics.bucket_index(0.6)
    assert metrics.bucket_upper_edge(metrics.bucket_index(0.75)) == 1.0


def test_snapshot_delta_and_merge():
    reg = metrics.Registry()
    c = reg.counter("ops_total", "test")
    h = reg.histogram("t", "test")
    c.inc(3.0)
    h.observe(1.0)
    before = reg.snapshot()
    c.inc(2.0)
    h.observe(2.0)
    h.observe(4.0)
    after = reg.snapshot()

    d = after.delta(before)
    assert d.value("ops_total") == 2.0
    assert d.hist("t")["count"] == 2

    merged = before.merge(d)
    assert merged.value("ops_total") == after.value("ops_total")
    assert merged.hist("t") == after.hist("t")

    # registry-independent merge stays exact too
    other = metrics.Registry()
    other.counter("ops_total", "test").inc(10.0)
    assert after.merge(other.snapshot()).value("ops_total") == 15.0


def test_snapshot_dict_round_trip():
    reg = metrics.Registry()
    reg.counter("c", "t").inc(job="x")
    reg.gauge("g", "t").set(2.5)
    reg.histogram("h", "t").observe(0.125)
    snap = reg.snapshot()
    back = metrics.Snapshot.from_dict(
        json.loads(json.dumps(snap.to_dict())))
    assert back.to_dict() == snap.to_dict()


# ---------------------------------------------------------------------------
# flight recorder
# ---------------------------------------------------------------------------

def _iter_record(i, source="sim", job="train"):
    return recorder.IterationRecord(
        source=source, job=job, iteration=i, start=float(i),
        end=i + 0.75, backward_end=i + 0.5, staleness=i % 2,
        buckets=(recorder.BucketRecord(0, 1024, i + 0.1, i + 0.2,
                                       i + 0.6, comm_s=0.3),),
        worker_compute=(("w0", 0.4), ("w1", 0.5)),
        worker_start=(("w0", float(i)), ("w1", float(i))),
        worker_end=(("w0", i + 0.7), ("w1", i + 0.75)),
        link_bytes=(("net", 1024.0),), link_busy=(("net", 0.3),),
        args={"plan": "abc"})


def test_ring_eviction_is_counted():
    rec = recorder.FlightRecorder(capacity=4)
    for i in range(6):
        rec.record(_iter_record(i))
    assert len(rec) == 4
    assert rec.evicted == 2
    assert rec.recorded == 6
    assert [r.iteration for r in rec.iterations()] == [2, 3, 4, 5]


def test_jsonl_round_trip_is_lossless(tmp_path):
    rec = recorder.FlightRecorder()
    rec.record(_iter_record(0))
    rec.record(recorder.EventRecord(
        kind="planner_update", time=1.0, source="planner",
        args={"plan": "deadbeef", "model_a": 9.72e-4 / 14}))
    rec.record(_iter_record(1, source="train"))
    path = tmp_path / "rec.jsonl"
    rec.write(str(path))
    back = recorder.read_jsonl(str(path))
    assert tuple(back) == rec.records       # bit-for-bit, dataclass ==


def test_unknown_record_type_rejected():
    with pytest.raises(ValueError):
        recorder.record_from_obj({"type": "mystery"})
    with pytest.raises(TypeError):
        recorder.FlightRecorder().record("not a record")


def test_plan_fingerprint_tracks_structure():
    specs, _ = trace.synthetic_specs(12, seed=3)
    p1 = make_plan("mgwfbp", specs, MODEL)
    p2 = make_plan("wfbp", specs, MODEL)
    assert recorder.plan_fingerprint(p1) == recorder.plan_fingerprint(p1)
    assert recorder.plan_fingerprint(p1) != recorder.plan_fingerprint(p2)


# ---------------------------------------------------------------------------
# producers: engine, planner, co-planner
# ---------------------------------------------------------------------------

def _small_sim(recorder_=None, schedule=None, iters=3):
    specs, t_f = trace.synthetic_specs(10, seed=21)
    plan = make_plan("mgwfbp", specs, MODEL)
    job = JobSpec(name="train", specs=specs, plan=plan, t_f=t_f,
                  workers=make_workers(3), topology=Topology(MODEL, 3),
                  iters=iters, schedule=schedule)
    return ClusterSim([job], seed=7, recorder=recorder_)


def test_engine_emits_records_matching_job_result():
    rec = recorder.FlightRecorder()
    res = _small_sim(rec).run()
    its = rec.iterations("train")
    assert len(its) == 3
    for r, it in zip(its, res.job("train").iterations):
        assert r == recorder.from_iteration_result(it, job="train")
    # and the sim_iteration_seconds histogram saw every iteration
    assert metrics.REGISTRY.histogram(
        "sim_iteration_seconds", "").count(job="train") >= 3


def test_engine_without_recorder_emits_nothing():
    sim = _small_sim(None)
    assert sim.recorder is None
    sim.run()        # must not touch the registry's iteration histogram


def test_planner_emits_counters_and_decision_events():
    specs, _ = trace.synthetic_specs(16, seed=4)
    rec = recorder.FlightRecorder()
    before = metrics.REGISTRY.snapshot()
    pl = Planner(specs, MODEL, recorder=rec)
    pl.update(SpecDelta(model=AllReduceModel(MODEL.a * 2, MODEL.b)))
    pl.append(specs[0])
    d = metrics.REGISTRY.snapshot().delta(before)
    assert d.value("planner_scratch_plans_total") == 1.0
    assert d.value("planner_incremental_updates_total") == 2.0
    events = rec.events("planner_update")
    assert len(events) == 2
    assert events[0].args["plan"] == recorder.plan_fingerprint(pl.plan()) \
        or events[0].args["plan"]            # fingerprint present & stable


def test_coplanner_emits_round_events():
    from repro.core.planner import plan_contention_aware
    from repro.core.simulator import simulate

    specs, t_f = trace.synthetic_specs(12, seed=9)
    rec = recorder.FlightRecorder()
    before = metrics.REGISTRY.snapshot()

    def evaluate(plan):
        r = simulate(specs, plan, MODEL, t_f)
        return r.t_iter, [(sum(specs[i].nbytes for i in b),
                           MODEL.time(sum(specs[i].nbytes for i in b)))
                          for b in plan.buckets]

    plan_contention_aware(specs, MODEL, evaluate, t_f=t_f, max_rounds=2,
                          recorder=rec)
    rounds = rec.events("coplan_round")
    assert rounds, "co-planner recorded no rounds"
    kinds = {e.args["round_kind"] for e in rounds}
    assert "response" in kinds
    d = metrics.REGISTRY.snapshot().delta(before)
    assert d.value("coplanner_rounds_total", kind="response") >= 1.0


# ---------------------------------------------------------------------------
# timeline: counters + staleness/frontier tracks
# ---------------------------------------------------------------------------

def test_chrome_trace_with_counters_round_trips():
    spans = [timeline.Span("s", "step", "j", "w", 0.0, 1.0)]
    counters = [timeline.CounterSample("staleness", "j/counters", 0.5,
                                       {"staleness": 2})]
    obj = timeline.to_chrome_trace(spans, counters)
    assert [e["ph"] for e in obj["traceEvents"]] == ["X", "C"]
    assert timeline.from_chrome_trace(obj) == spans
    assert timeline.chrome_counters(obj) == counters
    # counters absent -> byte-identical to the historical format
    # (golden traces depend on this)
    assert timeline.to_chrome_trace(spans) == trace.to_chrome_trace(spans)


def test_staleness_and_frontier_drift_tracks():
    res = _small_sim(schedule=LocalSGD(2), iters=4).run()
    samples = timeline.counter_samples_from(res.job("train"))
    staleness = [c for c in samples if c.name == "staleness"]
    frontier = [c for c in samples if c.name == "frontier_drift"]
    assert len(staleness) == 4 and len(frontier) == 4
    # LocalSGD(2): odd iterations run locally -> staleness sawtooth
    assert [c.values["staleness"] for c in staleness] == [1, 0, 1, 0]
    # every worker appears as a series, drift is nonnegative, and at
    # least one worker sits exactly on the frontier
    for c in frontier:
        assert set(c.values) == {"w0", "w1", "w2"}
        assert min(c.values.values()) == 0.0
        assert all(v >= 0.0 for v in c.values.values())


# ---------------------------------------------------------------------------
# drift monitor
# ---------------------------------------------------------------------------

def test_drift_monitor_silent_then_alerts_then_resets():
    m = drift.DriftMonitor(threshold=0.2, alpha=1.0, warmup=1)
    assert m.observe(0, 1.0, 1.1) is None          # 10% < threshold
    alert = m.observe(1, 1.0, 1.5)
    assert alert is not None and alert.kind == "iteration"
    assert alert.ewma == pytest.approx(0.5)
    m.reset()
    assert m.observe(2, 1.0, 1.05) is None
    assert len(m.alerts) == 1


def test_drift_monitor_per_link():
    m = drift.DriftMonitor(threshold=0.2, alpha=1.0, warmup=1)
    model = {"net": AllReduceModel(1e-3, 1e-9)}
    good = [(1 << 20, 1e-3 + 1e-9 * (1 << 20))]
    bad = [(1 << 20, 5e-3)]
    assert m.observe_links(0, model, {"net": good}) == []
    alerts = m.observe_links(1, model, {"net": bad})
    assert alerts and alerts[0].link == "net"
    assert m.residual("link:net") > 0.2


def test_fit_link_models_skips_degenerate_links():
    model = AllReduceModel(2e-4, 3e-9)
    samples = {"good": [(1 << 18, model.time(1 << 18)),
                        (1 << 22, model.time(1 << 22))],
               "degenerate": [(1 << 20, 1.0), (1 << 20, 1.0)]}
    fitted = drift.fit_link_models(samples)
    assert set(fitted) == {"good"}
    assert fitted["good"].a == pytest.approx(model.a, rel=1e-6)
    assert fitted["good"].b == pytest.approx(model.b, rel=1e-6)


def test_drift_end_to_end_degrade_alert_replan_recover():
    """The obs acceptance criterion: mid-run bandwidth change -> drift
    alert -> refit + replan -> post-replan residual back under
    threshold."""
    specs, t_f = trace.synthetic_specs(24, seed=5)
    rec = recorder.FlightRecorder()
    sim, rep = scenarios.drift_monitored(specs, t_f, iters=8, degrade_at=2,
                                         degrade_factor=4.0, recorder=rec)
    sim.run()
    assert rep.alerts, "degradation never raised a drift alert"
    assert rep.replans >= 1
    assert rep.plans[-1].buckets != rep.plans[0].buckets, \
        "4x slower fabric should change the optimal bucketing"
    # the refit actually learned the degraded per-byte cost
    assert rep.models[-1].b > rep.models[0].b * 2
    post = [r for i, r in rep.residuals
            if i > rep.alerts[-1].iteration]
    assert post and max(post) <= rep.monitor.threshold, post
    # the whole episode is on the flight recorder
    assert rec.events("drift_alert")
    assert rec.events("planner_update")
    assert len(rec.iterations("train")) == 8


def test_drift_calibrated_control_stays_silent():
    specs, t_f = trace.synthetic_specs(24, seed=5)
    sim, rep = scenarios.drift_monitored(specs, t_f, iters=6,
                                         degrade_at=None)
    sim.run()
    assert not rep.alerts
    assert max(r for _, r in rep.residuals) < 1e-9


# ---------------------------------------------------------------------------
# unified sim + real-step trace (golden-pinned)
# ---------------------------------------------------------------------------

def _unified_trace() -> dict:
    """Deterministic sim records + deterministic fake-clock real-step
    records, exported into ONE Chrome trace: the real-step-parity
    acceptance artifact."""
    from repro.train.step import instrument_step

    rec = recorder.FlightRecorder()
    res = _small_sim(rec, schedule=LocalSGD(2), iters=4).run()

    specs, t_f = trace.synthetic_specs(10, seed=21)
    art = types.SimpleNamespace(specs=specs,
                                plan=make_plan("mgwfbp", specs, MODEL),
                                comm_model=MODEL)
    ticks = iter(0.031 * k for k in range(8))
    wrapped = instrument_step(lambda s, b: (s, {}), art, t_f=t_f,
                              job="train", recorder=rec,
                              clock=lambda: next(ticks), sync=False)
    for step in range(3):
        wrapped(None, None)

    spans = list(res.spans) + recorder.record_spans(rec.records)
    counters = timeline.counter_samples_from(res.job("train"))
    return timeline.to_chrome_trace(spans, counters)


def test_sim_and_real_step_records_share_schema():
    obj = _unified_trace()
    # schema parity is a consequence of one dataclass, but pin it
    # explicitly: group spans by source and compare the lane structure
    pids = {e["pid"] for e in obj["traceEvents"] if e["ph"] == "X"}
    assert "sim:train" in pids and "train:train" in pids
    for group in ("sim:train", "train:train"):
        lanes = {e["tid"] for e in obj["traceEvents"]
                 if e["ph"] == "X" and e["pid"] == group}
        assert {"step", "comm"} <= lanes, (group, lanes)
    assert all(e["dur"] >= 0 for e in obj["traceEvents"]
               if e["ph"] == "X")


def test_golden_unified_trace_exact():
    path = GOLDEN_DIR / "obs_unified.trace.json"
    assert path.exists(), \
        f"{path} missing — run `python tests/test_obs.py --regen`"
    with open(path) as f:
        golden = json.load(f)
    current = _unified_trace()
    if current != golden:
        cur, gold = current["traceEvents"], golden["traceEvents"]
        assert len(cur) == len(gold), \
            f"{len(cur)} events vs golden {len(gold)}"
        for i, (a, b) in enumerate(zip(cur, gold)):
            assert a == b, f"event {i} drifted:\n  now: {a}\n  was: {b}"
        raise AssertionError("trace metadata drifted")


# ---------------------------------------------------------------------------
# real multi-device run -> same record schema (subprocess: needs
# XLA_FLAGS set before jax imports; the rest of the suite sees 1 device)
# ---------------------------------------------------------------------------

_MD_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import dataclasses, json, tempfile
import jax
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.configs.base import ShapeConfig
from repro.data.pipeline import DataPipeline
from repro.launch.mesh import make_mesh
from repro.models import registry
from repro.obs import recorder, timeline
from repro.sim import trace
from repro.sim.engine import ClusterSim, JobSpec, Topology
from repro.sim.workers import make_workers
from repro.core.planner import make_plan
from repro.core.cost_model import AllReduceModel
from repro.train.step import build_train_step, instrument_step

bundle = registry.reduced_arch("qwen2-1.5b")
par = dataclasses.replace(bundle.parallel, dp_axes=("data",), zero=0,
                          ep_axis="", attn_chunk=32)
shape = ShapeConfig("tiny", "train", 16, 8)
run_cfg = dataclasses.replace(bundle.run_config("train_4k", par),
                              shape=shape, microbatch=0)
model = bundle.model(par)
mesh = make_mesh((4,), ("data",))
rec = recorder.FlightRecorder()
with jax.set_mesh(mesh):
    step_fn, init_fn, art = build_train_step(model, run_cfg, mesh)
    sh = jax.tree.map(lambda s: NamedSharding(mesh, s), art.state_pspecs,
                      is_leaf=lambda x: isinstance(x, P))
    state = jax.device_put(init_fn(jax.random.PRNGKey(0)), sh)
    pipe = DataPipeline(bundle.cfg, shape, seed=0)
    jstep = jax.jit(step_fn)
    batch = pipe.batch_at(0)
    hlo = jstep.lower(state, batch).compile().as_text()
    wrapped = instrument_step(jstep, art, recorder=rec, hlo_text=hlo)
    for s in range(2):
        state, m = wrapped(state, pipe.batch_at(s))

train = rec.iterations("train")
assert len(train) == 2, train
assert all(r.source == "train" and r.t_iter > 0 for r in train)
assert train[0].buckets, "no per-bucket estimates on the record"
assert train[0].args["estimated_buckets"] is True
assert train[0].args["hlo_cost"]["collective_bytes"] > 0, \\
    "hlo cost analysis saw no collectives in a 4-way DP step"

# same schema as a simulator record, field for field
sim_rec = recorder.FlightRecorder()
specs, t_f = trace.synthetic_specs(8, seed=3)
mdl = AllReduceModel(4e-4, 1.5e-9)
job = JobSpec(name="train", specs=specs,
              plan=make_plan("mgwfbp", specs, mdl), t_f=t_f,
              workers=make_workers(2), topology=Topology(mdl, 2), iters=1)
ClusterSim([job], recorder=sim_rec).run()
fields = lambda r: sorted(dataclasses.asdict(r))
assert fields(train[0]) == fields(sim_rec.iterations()[0])

# ... and both sources export into ONE valid chrome trace
spans = recorder.record_spans(tuple(sim_rec.records) + rec.records)
obj = timeline.to_chrome_trace(spans)
pids = {e["pid"] for e in obj["traceEvents"]}
assert pids == {"sim:train", "train:train"}, pids
assert all(e["dur"] >= 0 for e in obj["traceEvents"])
fd, path = tempfile.mkstemp(suffix=".json"); os.close(fd)
timeline.write_chrome_trace(path, spans)
assert timeline.read_chrome_trace(path) == spans
os.unlink(path)
print("OBS-MULTIDEVICE-PASS")
"""


@pytest.mark.slow
def test_real_step_records_match_sim_schema():
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["PYTHONPATH"] = str(pathlib.Path(__file__).parent.parent / "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _MD_SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "OBS-MULTIDEVICE-PASS" in res.stdout, \
        f"stdout:\n{res.stdout[-3000:]}\nstderr:\n{res.stderr[-3000:]}"


def _regen() -> None:
    GOLDEN_DIR.mkdir(exist_ok=True)
    path = GOLDEN_DIR / "obs_unified.trace.json"
    with open(path, "w") as f:
        json.dump(_unified_trace(), f, indent=1)
    print(f"wrote {path}")


if __name__ == "__main__":
    import sys

    if "--regen" in sys.argv:
        _regen()
    else:
        print(__doc__)
