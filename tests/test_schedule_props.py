"""Frontier-invariant property tests for repro.sim.schedules; skipped
without the real hypothesis package.

Three families:

* random acyclic :class:`DAGSchedule` graphs always complete — no
  deadlock, whatever the precedence/resource mix;
* per-worker clocks are non-decreasing under every schedule, on random
  profiles with random jitter;
* total communicated bytes is schedule-invariant across the synchronous
  schedules (BSP, pipelined split collectives, 1F1B accumulation) — no
  schedule silently drops or duplicates gradient traffic.
"""

import pytest

pytest.importorskip("hypothesis", reason="property tests need hypothesis")

import hypothesis  # noqa: E402
import hypothesis.strategies as st  # noqa: E402
from prop_strategies import mk_specs, specs_strategy  # noqa: E402

from repro.core.cost_model import AllReduceModel  # noqa: E402
from repro.core.planner import make_plan  # noqa: E402
from repro.sim.engine import ClusterSim, JobSpec, Topology  # noqa: E402
from repro.sim.schedules import (BSP, DAGSchedule, DAGTask, LocalSGD,  # noqa: E402
                                 OneFoneB, PipelinedAllReduce)
from repro.sim.workers import make_workers  # noqa: E402

from schedule_harness import assert_frontier_monotone  # noqa: E402

MODEL = AllReduceModel(5e-4, 2e-9)


# -- random DAGs never deadlock ---------------------------------------------

@st.composite
def dag_tasks(draw):
    """Random acyclic task graphs: deps only point at earlier tasks."""
    n = draw(st.integers(1, 12))
    n_workers = draw(st.integers(1, 3))
    n_links = draw(st.integers(0, 2))
    tasks = []
    for i in range(n):
        n_deps = draw(st.integers(0, min(i, 3)))
        deps = tuple(sorted({f"t{d}" for d in draw(st.lists(
            st.integers(0, i - 1), min_size=n_deps, max_size=n_deps))})) \
            if i else ()
        kind = draw(st.integers(0, 2 if n_links else 1))
        worker = f"w{draw(st.integers(0, n_workers - 1))}" \
            if kind == 0 else None
        link = f"l{draw(st.integers(0, n_links - 1))}" \
            if kind == 2 else None
        tasks.append(DAGTask(f"t{i}", duration=draw(st.floats(0.0, 1e-2)),
                             worker=worker, link=link, deps=deps))
    return tuple(tasks)


@hypothesis.given(dag_tasks())
# zero-length link tasks finish inside their dispatch: one readied a task
# twice in the first dispatch loop, one completed the graph twice
@hypothesis.example(tasks=(
    DAGTask("t0", 0.0, None, "l0", ()), DAGTask("t1", 0.0, "w0", None, ()),
    DAGTask("t2", 0.0078125, "w0", None, ("t0",)),
    DAGTask("t3", 0.0, None, None, ("t2",))))
@hypothesis.example(tasks=(
    DAGTask("t0", 0.0078125, None, None, ()),
    DAGTask("t1", 0.0, "w0", None, ()),
    DAGTask("t2", 0.0, None, "l0", ("t0",))))
@hypothesis.settings(max_examples=60, deadline=None)
def test_random_dag_schedules_never_deadlock(tasks):
    job = JobSpec(name="dag", specs=[], plan=make_plan("wfbp", []),
                  t_f=0.0, workers=make_workers(1),
                  topology=Topology(MODEL),
                  schedule=DAGSchedule(tasks))
    res = ClusterSim([job]).run()
    jr = res.job("dag")
    assert len(jr.iterations) == 1                 # the graph completed
    ran = {s.name for s in res.spans if s.pid == "dag"}
    assert ran == {t.name for t in tasks}          # every task executed
    # completion respects every dependency edge
    ends = {s.name: s.end for s in res.spans if s.pid == "dag"}
    starts = {s.name: s.start for s in res.spans if s.pid == "dag"}
    for t in tasks:
        for d in t.deps:
            assert starts[t.name] >= ends[d] - 1e-12


# -- per-worker clocks never go backwards -----------------------------------

SCHEDULES = st.sampled_from([
    BSP(), PipelinedAllReduce(0.5), PipelinedAllReduce(0.25),
    OneFoneB(2), OneFoneB(4), LocalSGD(2), LocalSGD(4),
])


@hypothesis.given(SCHEDULES, specs_strategy(min_n=1, max_n=10),
                  st.floats(0.0, 0.4), st.integers(0, 1000),
                  st.sampled_from(["events", "analytic"]))
@hypothesis.settings(max_examples=60, deadline=None)
def test_worker_clocks_non_decreasing(schedule, sizes_times, jitter, seed,
                                      compute_mode):
    specs = mk_specs(*sizes_times)
    plan = make_plan("mgwfbp", specs, MODEL)
    job = JobSpec(name="j", specs=specs, plan=plan, t_f=1e-3,
                  workers=make_workers(3, jitter_sigma=jitter),
                  topology=Topology(MODEL), iters=5,
                  compute_mode=compute_mode, schedule=schedule)
    jr = ClusterSim([job], seed=seed).run().job("j")
    assert len(jr.iterations) == 5
    assert_frontier_monotone(jr)


# -- bytes are schedule-invariant for synchronous schedules -----------------

@hypothesis.given(specs_strategy(min_n=1, max_n=10),
                  st.sampled_from(["wfbp", "single", "mgwfbp"]),
                  st.integers(1, 4))
@hypothesis.settings(max_examples=40, deadline=None)
def test_bytes_schedule_invariant_for_synchronous(sizes_times, strategy,
                                                  iters):
    specs = mk_specs(*sizes_times)
    plan = make_plan(strategy, specs, MODEL)
    expected = sum(s.nbytes for s in specs) * iters

    def bytes_under(schedule):
        job = JobSpec(name="j", specs=specs, plan=plan, t_f=1e-3,
                      workers=make_workers(2), topology=Topology(MODEL),
                      iters=iters, compute_mode="analytic",
                      schedule=schedule)
        return ClusterSim([job]).run().job("j").bytes_communicated

    for schedule in (BSP(), PipelinedAllReduce(0.5),
                     PipelinedAllReduce(0.25), OneFoneB(3)):
        assert schedule.synchronous
        assert bytes_under(schedule) == pytest.approx(expected, rel=1e-12)
