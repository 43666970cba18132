"""Online refit + replan: close the sim->real loop on the live train step.

MG-WFBP's pipeline is measure -> plan -> execute (paper §5.1, Alg. 2) —
but the paper measures once, before training.  On a real fabric the
effective (a, b) drifts (contention, thermal throttling, elastic
membership), and a plan computed from a stale model silently stops being
optimal.  This module keeps the loop closed *during* training:

* :func:`measure_comm_model` — time real jitted collectives over the data
  axes at several message sizes and least-squares fit (a, b)
  (``cost_model.fit``): the measured analogue of
  ``cost_model.production_comm_model``.
* :class:`ReplanController` — a host-side policy that consumes the
  :class:`~repro.obs.recorder.IterationRecord` stream emitted by
  ``train.step.instrument_step`` (via its ``on_record`` hook), refits the
  effective comm model from the observed non-overlapped communication,
  drives the incremental :class:`~repro.core.planner.Planner` (which emits
  ``planner_update`` events), and — when the predicted win of the new plan
  beats a hysteresis threshold — rebuilds the jitted step with
  ``build_train_step(plan_override=...)`` OFF the hot path and swaps it in
  between iterations.  Bucketing is pure communication scheduling, so a
  swap can change step *timing* but never step *numerics* (pinned by
  tests/test_replan.py).
* :func:`closed_loop` — convenience assembly of the whole pipeline:
  measure costs, build the step from them, wrap it with instrumentation,
  and attach a controller whose rebuild callback re-derives the step.

Everything here runs on the host between dispatches; nothing lands inside
jit (same discipline as ``instrument_step``).
"""

from __future__ import annotations

import dataclasses
import time
from typing import Callable, Sequence

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core import cost_model
from repro.core import planner as planner_mod
from repro.core.cost_model import AllReduceModel
from repro.core.planner import MergePlan, SpecDelta, TensorSpec
from repro.core.simulator import simulate
from repro.obs.drift import DriftMonitor
from repro.obs.recorder import IterationRecord, plan_fingerprint

_EPS = 1e-12


# ---------------------------------------------------------------------------
# Measured communication model.
# ---------------------------------------------------------------------------

def measure_comm_model(mesh, dp_axes: Sequence[str],
                       sizes_bytes: Sequence[int] = (1 << 16, 1 << 19,
                                                     1 << 22),
                       *, n_warmup: int = 1, n_iters: int = 5,
                       name: str = "measured") -> AllReduceModel:
    """Fit (a, b) from real timed all-reduces on the mesh's data axes.

    Times ``jax.jit(shard_map(psum))`` per message size (compile + warmup
    excluded, wall clock around ``block_until_ready``) and least-squares
    fits ``T(M) = a + b*M`` — the measured counterpart of the analytic
    ``production_comm_model``.  With no data axes on the mesh the psum is
    an identity; the fit then captures dispatch overhead only, which is
    still the correct effective model for that (degenerate) topology.
    """
    axes = tuple(a for a in dp_axes if a in mesh.axis_names)
    samples_n: list[float] = []
    samples_t: list[float] = []
    for nbytes in sizes_bytes:
        n_elems = max(1, int(nbytes) // 4)
        x = jnp.zeros((n_elems,), jnp.float32)
        if axes:
            body = jax.shard_map(lambda v: jax.lax.psum(v, axes), mesh=mesh,
                                 in_specs=(P(),), out_specs=P(),
                                 axis_names=set(axes), check_vma=False)
        else:
            def body(v):
                return v + 0.0
        fn = jax.jit(body)
        jax.block_until_ready(fn(x))            # compile
        for _ in range(n_warmup):
            jax.block_until_ready(fn(x))
        t0 = time.perf_counter()
        for _ in range(n_iters):
            jax.block_until_ready(fn(x))
        samples_n.append(float(n_elems * 4))
        samples_t.append((time.perf_counter() - t0) / n_iters)
    if len(set(samples_n)) >= 2:
        return cost_model.fit(samples_n, samples_t, name)
    # single size: degenerate fit -> all latency, zero slope
    return AllReduceModel(max(samples_t[0], _EPS), 0.0, name)


# ---------------------------------------------------------------------------
# The controller.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class ReplanDecision:
    """One refit round: what the controller saw and what it did."""

    iteration: int
    observed_t_iter: float       # window-median wall iteration time
    stretch: float               # observed / predicted non-overlapped comm
    model: AllReduceModel        # effective model AFTER this refit
    old_plan: MergePlan
    new_plan: MergePlan
    predicted_old: float         # t_iter of old plan under the new model
    predicted_new: float         # t_iter of new plan under the new model
    swapped: bool

    @property
    def predicted_win(self) -> float:
        """Relative improvement the swap was judged on."""
        if self.predicted_old <= 0:
            return 0.0
        return (self.predicted_old - self.predicted_new) / self.predicted_old


class ReplanController:
    """Consume live IterationRecords; refit, replan, and swap the step.

    Policy knobs:

    * ``warmup``      — records ignored for refitting (compile jitter);
    * ``interval``    — records per refit window (median over the window
                        rejects stragglers);
    * ``damping``     — weight of the fresh fit against the previous
                        effective model (``cost_model.blend``; 0.5 kills
                        the two-cycle oscillation a full-step update can
                        enter, same rationale as ``plan_contention_aware``);
    * ``hysteresis``  — minimum predicted relative win before a swap is
                        worth a recompile (swaps are off-hot-path but not
                        free);
    * ``min_stretch`` / ``max_stretch`` — clamp on the per-round refit
                        ratio so one pathological window cannot catapult
                        the model.

    The controller plugs into ``instrument_step(..., on_record=ctl.observe)``.
    ``rebuild`` is called with the winning :class:`MergePlan` and must
    return the new (jitted, instrumented) step callable — typically a
    closure over ``build_train_step(..., plan_override=plan)``.  The
    freshly built step is exposed as :attr:`step_fn`; the driving loop
    reads it each iteration (see :func:`closed_loop`).

    Drift alerts: every record also feeds a :class:`DriftMonitor`
    comparing the current plan's closed-form prediction against the wall
    time, so sustained mismatch lands as ``drift_alert`` events in the
    recorder ring alongside the planner's ``planner_update`` events.
    """

    def __init__(self, specs: Sequence[TensorSpec], plan: MergePlan,
                 model: AllReduceModel, *,
                 t_f: float = 0.0,
                 rebuild: Callable[[MergePlan], Callable] | None = None,
                 recorder=None,
                 warmup: int = 2, interval: int = 4,
                 damping: float = 0.5, hysteresis: float = 0.05,
                 drift_threshold: float = 0.15,
                 min_stretch: float = 0.1, max_stretch: float = 10.0):
        if interval < 1:
            raise ValueError(f"interval must be >= 1, got {interval}")
        if not 0.0 <= damping <= 1.0:
            raise ValueError(f"damping must be in [0, 1], got {damping}")
        self.specs = list(specs)
        self.plan = plan
        self.model = cost_model.as_linear(model)
        self.t_f = float(t_f)
        self.rebuild = rebuild
        self.recorder = recorder
        self.warmup = int(warmup)
        self.interval = int(interval)
        self.damping = float(damping)
        self.hysteresis = float(hysteresis)
        self.min_stretch = float(min_stretch)
        self.max_stretch = float(max_stretch)
        self.planner = planner_mod.Planner(self.specs, self.model,
                                           recorder=recorder)
        self.monitor = DriftMonitor(threshold=drift_threshold,
                                    warmup=max(1, warmup),
                                    recorder=recorder,
                                    source="train", job="replan")
        self.step_fn: Callable | None = None   # set by rebuild / closed_loop
        self.decisions: list[ReplanDecision] = []
        self._window: list[float] = []
        self._n = 0

    # -- ingestion -------------------------------------------------------

    def observe(self, rec: IterationRecord) -> ReplanDecision | None:
        """Feed one live record; returns the decision if a refit ran."""
        observed = rec.end - rec.start
        self._n += 1
        pred = simulate(self.specs, self.plan, self.model, self.t_f)
        self.monitor.observe(rec.iteration, pred.t_iter, observed)
        if self._n <= self.warmup:
            return None
        self._window.append(observed)
        if len(self._window) < self.interval:
            return None
        return self._refit(rec.iteration)

    def update_backward_times(self, tb_table: dict[str, float]) -> MergePlan:
        """Point-refit per-tensor backward times (``path -> seconds``),
        e.g. from a fresh ``profiler.measure_loss_profile`` pass.  Routes
        through ``Planner.update`` so only the suffix from the first
        changed tensor is recomputed."""
        updates = {}
        for i, s in enumerate(self.specs):
            t_b = tb_table.get(s.name)
            if t_b is not None and t_b > 0 and t_b != s.t_b:
                updates[i] = dataclasses.replace(s, t_b=float(t_b))
        if not updates:
            return self.planner.plan()
        for i, s in updates.items():
            self.specs[i] = s
        return self.planner.update(SpecDelta(updates=updates))

    # -- the refit round -------------------------------------------------

    def _refit(self, iteration: int) -> ReplanDecision:
        window = sorted(self._window)
        self._window.clear()
        observed = window[len(window) // 2]              # median
        pred = simulate(self.specs, self.plan, self.model, self.t_f)
        # Observed non-overlapped communication: everything the wall
        # clock spent beyond forward + backward compute.  The stretch of
        # that bottleneck against its prediction is the refit signal —
        # uniform rescaling of (a, b), since the host-side records hold
        # the step's wall window alone and no per-bucket durations.
        obs_t_c_no = max(observed - (self.t_f + pred.t_b_total), 0.0)
        if pred.t_c_no > _EPS:
            stretch = obs_t_c_no / pred.t_c_no
        else:
            stretch = 1.0
        stretch = min(max(stretch, self.min_stretch), self.max_stretch)
        new_model = cost_model.blend(self.model,
                                     self.model.scaled(stretch),
                                     self.damping)
        new_plan = self.planner.replan(new_model)   # planner_update event
        self.model = new_model
        old_plan = self.plan
        t_old = simulate(self.specs, old_plan, new_model, self.t_f).t_iter
        t_new = simulate(self.specs, new_plan, new_model, self.t_f).t_iter
        win = (t_old - t_new) / t_old if t_old > 0 else 0.0
        swapped = False
        if new_plan.buckets != old_plan.buckets and win > self.hysteresis:
            if self.rebuild is not None:
                self.step_fn = self.rebuild(new_plan)
            self.plan = new_plan
            swapped = True
            self.monitor.reset()
        decision = ReplanDecision(
            iteration=iteration, observed_t_iter=observed, stretch=stretch,
            model=new_model, old_plan=old_plan,
            new_plan=new_plan, predicted_old=t_old, predicted_new=t_new,
            swapped=swapped)
        self.decisions.append(decision)
        return decision

    @property
    def swaps(self) -> list[ReplanDecision]:
        return [d for d in self.decisions if d.swapped]


# ---------------------------------------------------------------------------
# End-to-end assembly: measure -> plan -> execute -> refit -> replan.
# ---------------------------------------------------------------------------

def closed_loop(model, run, mesh, *,
                strategy: str | None = None,
                tb_table: dict | None = None,
                comm_model: AllReduceModel | None = None,
                t_f: float = 0.0,
                recorder=None,
                instrument: bool = True,
                donate: bool = True,
                **controller_kwargs):
    """Build a measured-cost train step with a live replan loop attached.

    Returns ``(controller, init_fn, art)``.  ``controller.step_fn`` is
    the instrumented step to drive; after each call the controller may
    have swapped in a rebuilt step (read the attribute fresh every
    iteration — that is the entire swap protocol):

        ctl, init_fn, art = closed_loop(model, run, mesh, ...)
        state = init_fn(jax.random.PRNGKey(0))
        for batch in batches:
            state, metrics = ctl.step_fn(state, batch)

    ``comm_model`` / ``tb_table`` are the measured costs (from
    :func:`measure_comm_model` / ``profiler.measure_loss_profile``);
    omitted, the step falls back to the analytic models and the loop
    simply starts from a worse prior.  The rebuild callback re-invokes
    ``build_train_step`` with ``plan_override`` and re-wraps with
    ``instrument_step`` feeding this same controller, so instrumentation
    and policy survive the swap.
    """
    from repro.train.step import build_train_step, instrument_step

    step_fn, init_fn, art = build_train_step(
        model, run, mesh, strategy=strategy, donate=donate,
        tb_table=tb_table, comm_model=comm_model)

    ctl = ReplanController(art.specs, art.plan, art.comm_model,
                           t_f=t_f, recorder=recorder,
                           **controller_kwargs)

    def _wrap(fn, artifacts):
        fn = jax.jit(fn)
        if not instrument:
            return fn
        return instrument_step(fn, artifacts, t_f=t_f, recorder=recorder,
                               on_record=ctl.observe)

    def rebuild(plan: MergePlan):
        new_fn, _, new_art = build_train_step(
            model, run, mesh, strategy=strategy, donate=donate,
            tb_table=tb_table, comm_model=ctl.model, plan_override=plan)
        art.plan = new_art.plan
        art.comm_model = new_art.comm_model
        return _wrap(new_fn, new_art)

    ctl.rebuild = rebuild
    ctl.step_fn = _wrap(step_fn, art)
    return ctl, init_fn, art
