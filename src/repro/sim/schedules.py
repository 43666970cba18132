"""Iteration schedules: per-worker frontiers beyond the BSP barrier.

The paper's pipelining model (§4) — and the engine as originally built —
assumes BSP: a global barrier at the last all-reduce of every iteration,
which is exactly the regime where MG-WFBP's merged-gradient plan is
provably optimal.  This module makes the iteration discipline a pluggable
**schedule**: a :class:`Schedule` names the dependency edges between
compute segments, bucket collectives and optimizer updates
(:meth:`Schedule.dependencies`), owns the engine-side driver that advances
each worker's *iteration frontier*, and carries its own homogeneous
closed form (:meth:`Schedule.predict_t_iter`) so the planner's fixpoint
can optimize bucketing under the schedule actually being run.

Concrete schedules
------------------
* :class:`BSP` — the paper's semantics, bit-identical to the engine's
  original loop (cross-validated against ``core.simulator.simulate``).
* :class:`PipelinedAllReduce` — DeAR-style (arXiv:2302.12445) split
  collectives: the reduce-scatter ``1 - ag_fraction`` of each bucket runs
  eagerly during backward, the all-gather remainder is deferred and
  overlaps the *next* iteration's forward; a worker's next forward starts
  at ``max(own backward end, last reduce-scatter end)`` and its next
  backward additionally waits for all deferred all-gathers (updated
  parameters).  ``ag_fraction=0`` degenerates to BSP exactly.
* :class:`OneFoneB` — ``micro_batches`` 1F1B micro-batch pairs per
  iteration with gradient accumulation: compute totals are unchanged but
  every gradient's final value lands during the *last* micro-batch's
  backward, compressing the WFBP overlap window to a ``1/M`` tail (the
  DP-visible timing of an 1F1B pipeline schedule, where bucket sync happens
  under the final backward).  ``micro_batches=1`` degenerates to BSP.
* :class:`LocalSGD` — communicate every ``h`` steps: between syncs each
  worker's frontier is its own compute stream (clocks drift), the sync
  step bucket-all-reduces like BSP, and ``IterationResult.staleness``
  counts unsynced local steps.  ``h=1`` degenerates to BSP.
* :class:`DAGSchedule` — an explicit task graph (compute streams, link
  occupancies, precedence edges) executed directly; the generic extension
  point, and the substrate for the never-deadlocks property tests.

Every driver speaks to the engine only through ``_JobRun``'s primitives
(``scales`` / ``launch_collective`` / ``finish_iteration``), so schedules
compose with everything the engine already does: heterogeneous + jittery
workers, link contention, bursts, multi-job runs, per-iteration hooks.
"""

from __future__ import annotations

import collections
import dataclasses
from typing import ClassVar, Sequence

import numpy as np

from repro.core.planner import MergePlan, TensorSpec
from repro.core.simulator import simulate
from repro.sim.engine import BucketTiming, IterationResult
from repro.sim.events import Latch
from repro.sim.trace import Span


@dataclasses.dataclass(frozen=True)
class FleetForm:
    """How a schedule's closed form maps onto the data-parallel kernels.

    The sweep fast path and the jitted fleet backend
    (``repro.sim.fleet``) evaluate three kernel shapes, selected by
    ``kind``:

    * ``"barrier"`` — the Eq. 7/8 recurrence with nominal ready times in
      the last micro-batch's ``1/micro_batches`` tail (BSP is
      ``micro_batches == 1``).  Exact under heterogeneity/jitter: the
      per-worker timeline is linear in the compute scale, so the
      synchronous ready time is the nominal one times the fleet max.
    * ``"pipelined"`` — the DeAR cross-iteration recurrence with the
      reduce-scatter fraction ``1 - ag_fraction`` eager and the rest
      deferred past the boundary.  Homogeneous fleets only.
    * ``"localsgd"`` — ``h - 1`` communication-free steps per round plus
      one barrier sync.  Homogeneous fleets only.

    ``heterogeneous_ok`` gates the jitter/straggler domain; schedules the
    kernels cannot express (``DAGSchedule``, custom subclasses) return
    ``None`` from :meth:`Schedule.fleet_form` and always take the engine.
    """

    kind: str                        # "barrier" | "pipelined" | "localsgd"
    micro_batches: int = 1           # barrier: 1F1B tail compression
    ag_fraction: float = 0.0         # pipelined: deferred share
    h: int = 1                       # localsgd: steps per round
    heterogeneous_ok: bool = True

    def __post_init__(self):
        if self.kind not in ("barrier", "pipelined", "localsgd"):
            raise ValueError(f"unknown fleet-form kind {self.kind!r}")


class Schedule:
    """How a job's iterations advance.  Subclasses are frozen dataclasses
    (hashable, usable as test fixtures) providing:

    * :meth:`driver` — the engine-side state machine;
    * :meth:`degenerate` — the parameter point at which the schedule
      provably reduces to BSP (the conformance harness runs both and
      asserts exact equality);
    * :meth:`dependencies` — the per-iteration dependency edges between
      compute segments (``fwd``/``bwd``), bucket collectives
      (``ar{k}``/``rs{k}``/``ag{k}``) and the optimizer update (``opt``);
      a trailing ``'`` marks a node of the next iteration;
    * :meth:`predict_t_iter` — the homogeneous, uncontended closed form
      for the steady-state per-iteration time (the schedule-aware analogue
      of ``core.simulator.simulate``; its validity domain is documented in
      docs/simulator.md);
    * :meth:`fleet_form` — the :class:`FleetForm` descriptor placing the
      closed form on the batched kernels (``None`` = engine only).
    """

    name: ClassVar[str] = "abstract"
    # True iff every iteration's gradients are fully synchronized — for
    # these schedules total communicated bytes is schedule-invariant
    # (property-tested in tests/test_schedule_props.py).
    synchronous: ClassVar[bool] = True

    def driver(self, run) -> "object":
        raise NotImplementedError

    def degenerate(self) -> "Schedule":
        raise NotImplementedError(f"{self.name} has no BSP-degenerate form")

    def validate_spec(self, spec) -> None:
        """Reject JobSpec combinations the driver cannot honour."""

    def dependencies(self, num_buckets: int) -> tuple[tuple[str, str], ...]:
        raise NotImplementedError

    def predict_t_iter(self, specs: Sequence[TensorSpec], plan: MergePlan,
                       model, t_f: float = 0.0) -> float:
        raise NotImplementedError

    def fleet_form(self) -> FleetForm | None:
        """Batched-kernel descriptor, or ``None`` if only the engine can
        run this schedule (the conservative default for subclasses)."""
        return None

    @property
    def label(self) -> str:
        return self.name


def _chain(edges: list[tuple[str, str]], nodes: list[str]) -> None:
    edges.extend(zip(nodes, nodes[1:]))


def _stepwise_dependencies(n_steps: int,
                           num_buckets: int) -> tuple[tuple[str, str], ...]:
    """The shared DAG shape of step-chained schedules (OneFoneB's
    micro-batches, LocalSGD's local steps): fwd/bwd pairs in sequence,
    collectives off the last backward, optimizer, next iteration."""
    edges: list[tuple[str, str]] = []
    for s in range(n_steps):
        edges.append((f"fwd{s}", f"bwd{s}"))
        if s + 1 < n_steps:
            edges.append((f"bwd{s}", f"fwd{s + 1}"))
    ars = [f"ar{k}" for k in range(num_buckets)]
    for ar in ars:
        edges.append((f"bwd{n_steps - 1}", ar))
    _chain(edges, ars)
    edges.append(((ars[-1] if ars else f"bwd{n_steps - 1}"), "opt"))
    edges.append(("opt", "fwd0'"))
    return tuple(edges)


def _schedule_ready_events(run, base: np.ndarray, eff_prefix: np.ndarray,
                           scales: np.ndarray, on_ready) -> None:
    """Schedule each bucket's "all workers produced the last gradient"
    event.  ``base[w]`` is worker w's backward origin; tensor j lands at
    ``base[w] + eff_prefix[j] * scales[w]``.  Analytic mode computes the
    fleet max directly; events mode schedules one arrival per worker per
    bucket-closing tensor through a :class:`Latch` (the faithful stream).
    Shared by the barrier and pipelined drivers so the two stay
    arithmetically identical on their common path."""
    eng = run.sim.engine
    buckets = run.plan.buckets
    if run.spec.compute_mode == "analytic":
        for k, bucket in enumerate(buckets):
            r = float((base + eff_prefix[bucket[-1]] * scales).max())
            eng.at(r, lambda k=k: on_ready(k))
    else:
        last_of = {b[-1]: k for k, b in enumerate(buckets)}
        n = len(run.workers)
        latches = [Latch(n, lambda k=k: on_ready(k))
                   for k in range(len(buckets))]
        for wi in range(n):
            for j, k in last_of.items():
                t = float(base[wi] + eff_prefix[j] * scales[wi])
                eng.at(t, latches[k].arrive)


# ---------------------------------------------------------------------------
# BSP (and the shared synchronous driver).
# ---------------------------------------------------------------------------

class _SyncDriver:
    """Barrier-synchronized iterations: the engine's original BSP state
    machine, generalized to per-worker start vectors (LocalSGD sync steps
    start workers at drifted clocks) and an overridable compute timeline
    (OneFoneB compresses gradient production into the last micro-batch).

    On the BSP path the arithmetic is expression-for-expression the
    pre-schedule engine's — the golden-trace tests and the closed-form
    cross-validation hold bit-identically.
    """

    def __init__(self, schedule: "Schedule", run) -> None:
        self.schedule = schedule
        self.run = run
        # per-iteration transient state
        self._it = 0
        self._ready: dict[int, float] = {}
        self._issued = 0
        self._in_flight = 0
        self._done_buckets: list[BucketTiming] = []
        self._bwd_end = 0.0
        self._iter_start = 0.0
        self._worker_compute: tuple[tuple[str, float], ...] = ()
        self._worker_start: tuple[tuple[str, float], ...] = ()
        self._worker_end: tuple[tuple[str, float], ...] = ()

    def start(self) -> None:
        self.start_iteration()

    def start_iteration(self) -> None:
        self._begin_sync(self.run.it, self.run.sim.engine.now)

    # -- compute-timeline hooks (overridden by OneFoneB) -----------------

    def _timeline(self, starts: np.ndarray, scales: np.ndarray,
                  prefix: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
        """(fwd_end, eff_prefix): tensor j's gradient is final on worker w
        at ``fwd_end[w] + eff_prefix[j] * scales[w]``."""
        return starts + self.run.spec.t_f * scales, prefix

    def _record_compute_spans(self, starts: np.ndarray, scales: np.ndarray,
                              fwd_end: np.ndarray, bwd_end: np.ndarray,
                              it: int) -> None:
        run = self.run
        for wi, w in enumerate(run.workers):
            run.sim.record(Span(
                name="forward", cat="compute", pid=run.name, tid=w.name,
                start=float(starts[wi]), end=float(fwd_end[wi]),
                args={"iter": it}))
            run.sim.record(Span(
                name="backward", cat="compute", pid=run.name, tid=w.name,
                start=float(fwd_end[wi]), end=float(bwd_end[wi]),
                args={"iter": it}))

    # -- one barrier-synchronized iteration ------------------------------

    def _begin_sync(self, it: int, start) -> None:
        run = self.run
        eng = run.sim.engine
        self._it = it
        starts = np.broadcast_to(np.asarray(start, dtype=np.float64),
                                 (len(run.workers),))
        self._iter_start = float(starts.min())
        self._ready = {}
        self._issued = 0
        self._in_flight = 0
        self._done_buckets = []

        prefix = run.backward_prefix()
        scales = run.scales(it)
        fwd_end, eff_prefix = self._timeline(starts, scales, prefix)
        bwd_end = fwd_end + \
            (eff_prefix[-1] if len(eff_prefix) else 0.0) * scales
        self._bwd_end = float(bwd_end.max())
        self._worker_compute = tuple(
            (w.name, float(bwd_end[wi] - starts[wi]))
            for wi, w in enumerate(run.workers))
        self._worker_start = tuple(
            (w.name, float(starts[wi])) for wi, w in enumerate(run.workers))
        self._worker_end = tuple(
            (w.name, float(bwd_end[wi]))
            for wi, w in enumerate(run.workers))
        self._record_compute_spans(starts, scales, fwd_end, bwd_end, it)

        if not run.plan.buckets:
            eng.at(self._bwd_end, self._finish_iteration)
            return
        _schedule_ready_events(run, fwd_end, eff_prefix, scales,
                               self._bucket_ready)

    def _bucket_ready(self, k: int) -> None:
        self._ready[k] = self.run.sim.engine.now
        if self.run.spec.comm_mode == "concurrent":
            self._launch(k)
        else:
            self._try_issue()

    def _try_issue(self) -> None:
        if self._in_flight or self._issued >= self.run.plan.num_buckets:
            return
        if self._issued in self._ready:
            self._launch(self._issued)

    def _launch(self, k: int) -> None:
        run = self.run
        self._in_flight += 1
        self._issued = max(self._issued, k + 1)
        nbytes = run.bucket_nbytes(k)
        run.launch_collective(
            k, nbytes, it=self._it,
            on_done=lambda start, k=k, nbytes=nbytes:
                self._collective_done(k, nbytes, start))

    def _collective_done(self, k: int, nbytes: int, start: float) -> None:
        run = self.run
        self._in_flight -= 1
        self._done_buckets.append(BucketTiming(
            iteration=self._it, bucket=k, nbytes=nbytes,
            ready=self._ready[k], start=start, end=run.sim.engine.now))
        if run.spec.comm_mode == "sequential":
            self._try_issue()
        if len(self._done_buckets) == run.plan.num_buckets:
            end = max(run.sim.engine.now, self._bwd_end)
            run.sim.engine.at(end, self._finish_iteration)

    def _make_result(self, staleness: int = 0) -> IterationResult:
        buckets = tuple(sorted(self._done_buckets, key=lambda b: b.bucket))
        return IterationResult(
            index=self._it, start=self._iter_start,
            end=self.run.sim.engine.now, backward_end=self._bwd_end,
            buckets=buckets, worker_compute=self._worker_compute,
            worker_start=self._worker_start, worker_end=self._worker_end,
            staleness=staleness)

    def _finish_iteration(self) -> None:
        if self.run.finish_iteration(self._make_result()):
            self.run.next_iteration(self.start_iteration)


@dataclasses.dataclass(frozen=True)
class BSP(Schedule):
    """The paper's bulk-synchronous discipline: every worker's frontier is
    the global barrier at max(last all-reduce end, slowest backward)."""

    name: ClassVar[str] = "bsp"
    synchronous: ClassVar[bool] = True

    def driver(self, run):
        return _SyncDriver(self, run)

    def degenerate(self) -> "BSP":
        return self

    def dependencies(self, num_buckets: int) -> tuple[tuple[str, str], ...]:
        edges: list[tuple[str, str]] = [("fwd", "bwd")]
        ars = [f"ar{k}" for k in range(num_buckets)]
        for ar in ars:
            edges.append(("bwd", ar))
        _chain(edges, ars)
        edges.append(((ars[-1] if ars else "bwd"), "opt"))
        edges.append(("opt", "fwd'"))
        return tuple(edges)

    def predict_t_iter(self, specs, plan, model, t_f=0.0) -> float:
        return simulate(specs, plan, model, t_f).t_iter

    def fleet_form(self) -> FleetForm:
        return FleetForm(kind="barrier")


# ---------------------------------------------------------------------------
# OneFoneB: micro-batched 1F1B with gradient accumulation.
# ---------------------------------------------------------------------------

class _OneFoneBDriver(_SyncDriver):
    """Same barrier discipline as BSP; the compute timeline interleaves
    ``micro_batches`` forward/backward pairs, so gradients only finalize
    during the last micro-batch's backward (a ``1/M``-scaled tail)."""

    def _timeline(self, starts, scales, prefix):
        m = self.schedule.micro_batches
        t_f = self.run.spec.t_f
        t_b_total = prefix[-1] if len(prefix) else 0.0
        pair = (t_f + t_b_total) / m
        warm = starts + ((m - 1) * pair) * scales
        return warm + (t_f / m) * scales, prefix / m

    def _record_compute_spans(self, starts, scales, fwd_end, bwd_end, it):
        run = self.run
        m = self.schedule.micro_batches
        t_f = run.spec.t_f
        prefix = run.backward_prefix()
        t_b_total = prefix[-1] if len(prefix) else 0.0
        cur = np.array(starts, dtype=np.float64)
        for mb in range(m):
            f1 = cur + (t_f / m) * scales
            b1 = f1 + (t_b_total / m) * scales
            for wi, w in enumerate(run.workers):
                run.sim.record(Span(
                    name="forward", cat="compute", pid=run.name, tid=w.name,
                    start=float(cur[wi]), end=float(f1[wi]),
                    args={"iter": it, "micro": mb}))
                run.sim.record(Span(
                    name="backward", cat="compute", pid=run.name,
                    tid=w.name, start=float(f1[wi]), end=float(b1[wi]),
                    args={"iter": it, "micro": mb}))
            cur = b1


@dataclasses.dataclass(frozen=True)
class OneFoneB(Schedule):
    """Micro-batched 1F1B with per-worker frontiers and end-of-iteration
    gradient sync (Megatron-style DP x PP interaction): each iteration is
    ``micro_batches`` forward/backward chunk pairs; total compute time is
    unchanged but the bucket-overlap window shrinks to the last backward
    chunk.  ``micro_batches=1`` is exactly BSP."""

    micro_batches: int = 4

    name: ClassVar[str] = "1f1b"
    synchronous: ClassVar[bool] = True

    def __post_init__(self):
        if self.micro_batches < 1:
            raise ValueError(
                f"need >= 1 micro batch, got {self.micro_batches}")

    @property
    def label(self) -> str:
        return f"1f1b{self.micro_batches}"

    def driver(self, run):
        return _OneFoneBDriver(self, run)

    def degenerate(self) -> "OneFoneB":
        return dataclasses.replace(self, micro_batches=1)

    def dependencies(self, num_buckets: int) -> tuple[tuple[str, str], ...]:
        return _stepwise_dependencies(self.micro_batches, num_buckets)

    def predict_t_iter(self, specs, plan, model, t_f=0.0) -> float:
        m = self.micro_batches
        prefix = np.cumsum([s.t_b for s in specs]) if specs \
            else np.zeros(0)
        t_b_total = float(prefix[-1]) if len(prefix) else 0.0
        pair = (t_f + t_b_total) / m
        base = (m - 1) * pair + t_f / m
        end = 0.0
        for bucket, nbytes in zip(plan.buckets,
                                  plan.bucket_bytes(specs)):
            ready = base + float(prefix[bucket[-1]]) / m
            end = max(end, ready) + model.time(nbytes)
        return max(end, t_f + t_b_total)

    def fleet_form(self) -> FleetForm:
        return FleetForm(kind="barrier", micro_batches=self.micro_batches)


# ---------------------------------------------------------------------------
# LocalSGD: communicate every H steps; frontiers drift between syncs.
# ---------------------------------------------------------------------------

class _LocalSGDDriver(_SyncDriver):
    """Rounds of ``h`` steps: the first ``h - 1`` are communication-free
    (each worker's frontier is its own compute stream), the last is a
    BSP-style bucket sync started from the drifted per-worker clocks.
    Iteration results (and hooks) for the local steps are flushed in order
    at the round barrier, where membership changes are safe."""

    def __init__(self, schedule, run):
        super().__init__(schedule, run)
        self._round_results: list[IterationResult] = []

    def start_iteration(self) -> None:
        run = self.run
        spec = run.spec
        first = run.it
        steps = min(self.schedule.h, spec.iters - first)
        T = run.sim.engine.now
        starts = np.full(len(run.workers), T, dtype=np.float64)
        prefix = run.backward_prefix()
        tail = prefix[-1] if len(prefix) else 0.0
        self._round_results = []
        for s in range(steps - 1):
            it = first + s
            scales = run.scales(it)
            fwd_end = starts + spec.t_f * scales
            bwd_end = fwd_end + tail * scales
            for wi, w in enumerate(run.workers):
                run.sim.record(Span(
                    name="forward", cat="compute", pid=run.name,
                    tid=w.name, start=float(starts[wi]),
                    end=float(fwd_end[wi]),
                    args={"iter": it, "local_step": s + 1}))
                run.sim.record(Span(
                    name="backward", cat="compute", pid=run.name,
                    tid=w.name, start=float(fwd_end[wi]),
                    end=float(bwd_end[wi]),
                    args={"iter": it, "local_step": s + 1}))
            self._round_results.append(IterationResult(
                index=it, start=float(starts.min()),
                end=float(bwd_end.max()),
                backward_end=float(bwd_end.max()), buckets=(),
                worker_compute=tuple(
                    (w.name, float(bwd_end[wi] - starts[wi]))
                    for wi, w in enumerate(run.workers)),
                worker_start=tuple(
                    (w.name, float(starts[wi]))
                    for wi, w in enumerate(run.workers)),
                worker_end=tuple(
                    (w.name, float(bwd_end[wi]))
                    for wi, w in enumerate(run.workers)),
                staleness=s + 1))
            starts = bwd_end
        self._begin_sync(first + steps - 1, starts)

    def _finish_iteration(self) -> None:
        run = self.run
        sync_result = self._make_result()
        for r in self._round_results:    # flush local steps, in order
            run.finish_iteration(r)
        self._round_results = []
        # only the sync step closes the round: its index is the round's
        # last, so its return value alone decides continuation
        if run.finish_iteration(sync_result):
            run.next_iteration(self.start_iteration)


@dataclasses.dataclass(frozen=True)
class LocalSGD(Schedule):
    """Communicate every ``h`` steps.  Between syncs workers run free —
    per-worker frontiers drift by heterogeneity and jitter — and the sync
    step all-reduces the accumulated update with the usual bucket overlap.
    ``IterationResult.staleness`` records unsynced steps; total bytes per
    round is one plan's worth (``1/h`` of BSP's per-iteration traffic).
    ``h=1`` is exactly BSP."""

    h: int = 4

    name: ClassVar[str] = "localsgd"
    synchronous: ClassVar[bool] = False

    def __post_init__(self):
        if self.h < 1:
            raise ValueError(f"need h >= 1, got {self.h}")

    @property
    def label(self) -> str:
        return f"localsgd{self.h}"

    def driver(self, run):
        return _LocalSGDDriver(self, run)

    def degenerate(self) -> "LocalSGD":
        return dataclasses.replace(self, h=1)

    def dependencies(self, num_buckets: int) -> tuple[tuple[str, str], ...]:
        return _stepwise_dependencies(self.h, num_buckets)

    def predict_t_iter(self, specs, plan, model, t_f=0.0) -> float:
        """Per-iteration average over one steady round: ``h - 1`` pure
        compute steps plus one BSP-like sync step."""
        t_b_total = sum(s.t_b for s in specs)
        sync = simulate(specs, plan, model, t_f).t_iter
        return ((self.h - 1) * (t_f + t_b_total) + sync) / self.h

    def fleet_form(self) -> FleetForm:
        if self.h == 1:                       # exactly BSP, jitter included
            return FleetForm(kind="barrier")
        return FleetForm(kind="localsgd", h=self.h, heterogeneous_ok=False)


# ---------------------------------------------------------------------------
# PipelinedAllReduce: DeAR-style split collectives across the boundary.
# ---------------------------------------------------------------------------

class _PipelinedDriver:
    """Per-worker frontiers with split collectives.

    Iteration ``it``: each worker forwards from its own frontier, backward
    additionally waits for the previous iteration's deferred all-gathers
    (updated parameters); reduce-scatters (``1 - ag_fraction`` of each
    bucket's cost) issue in order as buckets become ready; after the last
    reduce-scatter the all-gathers stream out in reverse bucket order —
    the order the next forward consumes parameters — overlapping that
    forward.  Worker w's next frontier is
    ``max(bwd_end[w], last reduce-scatter end)``.

    With ``ag_fraction == 0`` the reduce-scatter is the whole collective
    and the all-gathers are free, which reproduces BSP timing (and its
    trace) exactly — the conformance harness asserts this.
    """

    def __init__(self, schedule: "PipelinedAllReduce", run) -> None:
        self.schedule = schedule
        self.run = run
        self._state: dict = {}

    def start(self) -> None:
        run = self.run
        T = run.sim.engine.now
        starts = np.full(len(run.workers), T, dtype=np.float64)
        self._start_iteration(starts, ag_done=T)

    def _start_iteration(self, starts: np.ndarray, ag_done: float) -> None:
        run = self.run
        eng = run.sim.engine
        spec = run.spec
        it = run.it
        scales = run.scales(it)
        prefix = run.backward_prefix()
        tail = prefix[-1] if len(prefix) else 0.0
        fwd_end = starts + spec.t_f * scales
        bwd_start = np.maximum(fwd_end, ag_done)
        bwd_end = bwd_start + tail * scales
        for wi, w in enumerate(run.workers):
            run.sim.record(Span(
                name="forward", cat="compute", pid=run.name, tid=w.name,
                start=float(starts[wi]), end=float(fwd_end[wi]),
                args={"iter": it}))
            if bwd_start[wi] > fwd_end[wi]:
                run.sim.record(Span(
                    name="ag_wait", cat="compute", pid=run.name,
                    tid=w.name, start=float(fwd_end[wi]),
                    end=float(bwd_start[wi]), args={"iter": it}))
            run.sim.record(Span(
                name="backward", cat="compute", pid=run.name, tid=w.name,
                start=float(bwd_start[wi]), end=float(bwd_end[wi]),
                args={"iter": it}))

        self._state = {
            "it": it, "starts": starts, "bwd_end": bwd_end,
            # pure compute, excluding the ag_wait stall: equals BSP's
            # bwd_end - starts bitwise when the wait is zero (x - 0.0 == x)
            "compute": (bwd_end - starts) - (bwd_start - fwd_end),
            "ready": {}, "issued": 0, "in_flight": 0,
            "rs": {}, "ag": {}, "rs_done": 0.0,
        }
        if not run.plan.buckets:
            eng.at(float(bwd_end.max()), self._finalize)
            return
        _schedule_ready_events(run, bwd_start, prefix, scales,
                               self._bucket_ready)

    # -- eager reduce-scatter stream (in-order, one in flight) -----------

    def _bucket_ready(self, k: int) -> None:
        st = self._state
        st["ready"][k] = self.run.sim.engine.now
        self._try_issue()

    def _try_issue(self) -> None:
        st = self._state
        if st["in_flight"] or st["issued"] >= self.run.plan.num_buckets:
            return
        if st["issued"] in st["ready"]:
            self._launch_rs(st["issued"])

    def _launch_rs(self, k: int) -> None:
        st = self._state
        st["in_flight"] += 1
        st["issued"] = max(st["issued"], k + 1)
        nbytes = self.run.bucket_nbytes(k)
        f = self.schedule.ag_fraction
        self.run.launch_collective(
            k, nbytes, it=st["it"], fraction=1.0 - f,
            tag="reduce_scatter" if f > 0 else "allreduce",
            on_done=lambda start, k=k, nbytes=nbytes:
                self._rs_done(k, nbytes, start))

    def _rs_done(self, k: int, nbytes: int, start: float) -> None:
        st = self._state
        now = self.run.sim.engine.now
        st["in_flight"] -= 1
        st["rs"][k] = (nbytes, st["ready"][k], start, now)
        self._try_issue()
        if len(st["rs"]) == self.run.plan.num_buckets:
            st["rs_done"] = now
            self._issue_ags()

    # -- deferred all-gather stream (reverse order, overlaps next fwd) ---

    def _issue_ags(self) -> None:
        st = self._state
        order = list(range(self.run.plan.num_buckets - 1, -1, -1))

        def next_ag(i: int) -> None:
            if i == len(order):
                self._finalize()
                return
            k = order[i]
            nbytes = st["rs"][k][0]

            def done(start: float, k: int = k) -> None:
                st["ag"][k] = (start, self.run.sim.engine.now)
                next_ag(i + 1)

            self.run.launch_collective(
                k, nbytes, it=st["it"],
                fraction=self.schedule.ag_fraction, tag="all_gather",
                on_done=done)

        next_ag(0)

    def _finalize(self) -> None:
        st = self._state
        run = self.run
        now = run.sim.engine.now
        starts, bwd_end = st["starts"], st["bwd_end"]
        timings = []
        for k in range(run.plan.num_buckets):
            nbytes, ready, rs_start, rs_end = st["rs"][k]
            ag_start, ag_end = st["ag"][k]
            timings.append(BucketTiming(
                iteration=st["it"], bucket=k, nbytes=nbytes, ready=ready,
                start=rs_start, end=ag_end,
                comm_s=(rs_end - rs_start) + (ag_end - ag_start)))
        bwd_max = float(bwd_end.max())
        rs_done = st["rs_done"] if timings else bwd_max
        compute = st["compute"]
        result = IterationResult(
            index=st["it"], start=float(starts.min()),
            end=max(now, bwd_max), backward_end=bwd_max,
            buckets=tuple(timings),
            worker_compute=tuple(
                (w.name, float(compute[wi]))
                for wi, w in enumerate(run.workers)),
            worker_start=tuple(
                (w.name, float(starts[wi]))
                for wi, w in enumerate(run.workers)),
            worker_end=tuple(
                (w.name, float(bwd_end[wi]))
                for wi, w in enumerate(run.workers)),
            staleness=0)
        if run.finish_iteration(result):
            if run.resume_at > run.sim.engine.now:
                # a fault hook paused the job: the pipelined overlap is
                # broken anyway, so resynchronize the whole fleet at the
                # resume point (starts and the all-gather frontier alike)
                t = run.resume_at

                def resume(t: float = t) -> None:
                    self._start_iteration(
                        np.full(len(run.workers), t, dtype=np.float64),
                        ag_done=t)

                run.sim.engine.at(t, resume)
            elif len(run.workers) != len(bwd_end):
                # membership changed by a hook: resynchronize the fleet
                nxt = np.full(len(run.workers), max(bwd_max, rs_done),
                              dtype=np.float64)
                self._start_iteration(nxt, ag_done=now)
            else:
                nxt = np.maximum(bwd_end, rs_done)
                self._start_iteration(nxt, ag_done=now)


@dataclasses.dataclass(frozen=True)
class PipelinedAllReduce(Schedule):
    """DeAR-style decoupled all-reduce (arXiv:2302.12445): reduce-scatter
    eagerly during backward, all-gather lazily under the next iteration's
    forward.  ``ag_fraction`` is the share of each collective deferred
    (0.5 models the ring all-reduce's equal halves); 0 degenerates to
    BSP exactly."""

    ag_fraction: float = 0.5

    name: ClassVar[str] = "pipelined"
    synchronous: ClassVar[bool] = True

    def __post_init__(self):
        if not 0.0 <= self.ag_fraction < 1.0:
            raise ValueError(
                f"ag_fraction must be in [0, 1), got {self.ag_fraction}")

    @property
    def label(self) -> str:
        return f"pipelined{self.ag_fraction:g}"

    def validate_spec(self, spec) -> None:
        if spec.comm_mode != "sequential":
            raise ValueError(
                "PipelinedAllReduce defines its own issue order; "
                "comm_mode must be 'sequential'")

    def driver(self, run):
        return _PipelinedDriver(self, run)

    def degenerate(self) -> "PipelinedAllReduce":
        return dataclasses.replace(self, ag_fraction=0.0)

    def dependencies(self, num_buckets: int) -> tuple[tuple[str, str], ...]:
        edges: list[tuple[str, str]] = [("fwd", "bwd")]
        rss = [f"rs{k}" for k in range(num_buckets)]
        ags = [f"ag{k}" for k in range(num_buckets)]
        for rs in rss:
            edges.append(("bwd", rs))
        _chain(edges, rss)
        if rss:
            edges.append((rss[-1], "opt"))       # shard update after RS
            edges.append((rss[-1], ags[-1]))     # AGs follow the last RS
            _chain(edges, list(reversed(ags)))   # reverse: fwd-need order
            edges.append(("opt", "fwd'"))
            edges.append(("bwd", "fwd'"))
            edges.append((ags[0], "bwd'"))       # full params before bwd'
        else:
            edges.extend([("bwd", "opt"), ("opt", "fwd'")])
        return tuple(edges)

    def predict_t_iter(self, specs, plan, model, t_f=0.0,
                       iters: int = 8) -> float:
        """Steady-state period of the cross-iteration recurrence
        (homogeneous, uncontended)."""
        f = self.ag_fraction
        prefix = np.cumsum([s.t_b for s in specs]) if specs \
            else np.zeros(0)
        t_b_total = float(prefix[-1]) if len(prefix) else 0.0
        nbytes = plan.bucket_bytes(specs)
        S, ag_done, period = 0.0, 0.0, 0.0
        for _ in range(max(iters, 2)):
            fwd_end = S + t_f
            bwd_start = max(fwd_end, ag_done)
            bwd_end = bwd_start + t_b_total
            end = 0.0
            for bucket, nb in zip(plan.buckets, nbytes):
                ready = bwd_start + float(prefix[bucket[-1]])
                end = max(end, ready) + (1.0 - f) * model.time(nb)
            rs_done = end if plan.buckets else bwd_end
            ag_done = rs_done + sum(f * model.time(nb) for nb in nbytes)
            s_next = max(bwd_end, rs_done)
            period = s_next - S
            S = s_next
        return period

    def fleet_form(self) -> FleetForm:
        if self.ag_fraction == 0.0:           # exactly BSP, jitter included
            return FleetForm(kind="barrier")
        return FleetForm(kind="pipelined", ag_fraction=self.ag_fraction,
                         heterogeneous_ok=False)


# ---------------------------------------------------------------------------
# DAGSchedule: explicit task graphs (the generic extension point).
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class DAGTask:
    """One node of an explicit schedule DAG.

    ``worker`` names a compute stream (tasks on one stream serialize,
    FIFO in readiness order); ``link`` names a network resource (the task
    occupies it as a processor-sharing flow of ``duration`` seconds at
    full rate, contending with everything else on that link); neither
    means a pure dependency/delay node."""

    name: str
    duration: float = 0.0
    worker: str | None = None
    link: str | None = None
    deps: tuple[str, ...] = ()

    def __post_init__(self):
        if self.duration < 0:
            raise ValueError(f"negative duration: {self}")
        if self.worker is not None and self.link is not None:
            raise ValueError(
                f"task {self.name!r} cannot occupy a worker and a link")


class _DAGDriver:
    def __init__(self, schedule: "DAGSchedule", run) -> None:
        self.schedule = schedule
        self.run = run

    def start(self) -> None:
        run = self.run
        tasks = self.schedule.tasks
        self._t0 = run.sim.engine.now
        self._by_name = {t.name: t for t in tasks}
        self._dependents: dict[str, list[DAGTask]] = \
            collections.defaultdict(list)
        self._missing = {t.name: len(set(t.deps)) for t in tasks}
        for t in tasks:
            for d in set(t.deps):
                self._dependents[d].append(t)
        self._busy: dict[str, bool] = {}
        self._queues: dict[str, collections.deque] = {}
        self._windows: dict[str, list[float]] = {}   # stream -> [min, max]
        self._done = 0
        if not tasks:
            self._complete()
            return
        # deterministic: declaration order.  Taken before any dispatch: a
        # zero-length link flow finishes inside its dispatch and readies
        # its dependents, which it dispatches itself.
        for t in [t for t in tasks if self._missing[t.name] == 0]:
            self._dispatch(t)

    def _dispatch(self, t: DAGTask) -> None:
        if t.worker is None:
            self._execute(t)
            return
        if self._busy.get(t.worker):
            self._queues.setdefault(t.worker, collections.deque()).append(t)
        else:
            self._busy[t.worker] = True
            self._execute(t)

    def _execute(self, t: DAGTask) -> None:
        run = self.run
        eng = run.sim.engine
        start = eng.now

        def done() -> None:
            now = eng.now
            tid = t.worker or (f"link:{t.link}" if t.link else "ctrl")
            cat = "compute" if t.worker else ("comm" if t.link else "task")
            run.sim.record(Span(name=t.name, cat=cat, pid=run.name,
                                tid=tid, start=start, end=now,
                                args={"task": t.name}))
            if t.worker is not None:
                w = self._windows.setdefault(t.worker, [start, now])
                w[0], w[1] = min(w[0], start), max(w[1], now)
                q = self._queues.get(t.worker)
                if q:
                    self._execute(q.popleft())
                else:
                    self._busy[t.worker] = False
            self._done += 1
            if self._done == len(self.schedule.tasks):
                self._complete()
            # a dependent may finish inside its dispatch (a zero-length
            # link flow), and complete the graph there
            for dep in self._dependents.get(t.name, ()):
                self._missing[dep.name] -= 1
                if self._missing[dep.name] == 0:
                    self._dispatch(dep)

        if t.link is not None:
            run.sim.ensure_link(t.link)
            run.sim.links[t.link].add_flow(t.duration, done,
                                           owner=run.name)
        else:
            eng.after(t.duration, done)

    def _complete(self) -> None:
        run = self.run
        now = run.sim.engine.now
        streams = sorted(self._windows) if self._windows else []
        run.finish_iteration(IterationResult(
            index=run.it, start=self._t0, end=now, backward_end=now,
            buckets=(),
            worker_compute=tuple(
                (s, self._windows[s][1] - self._windows[s][0])
                for s in streams),
            worker_start=tuple((s, self._windows[s][0]) for s in streams),
            worker_end=tuple((s, self._windows[s][1]) for s in streams),
            staleness=0))


@dataclasses.dataclass(frozen=True)
class DAGSchedule(Schedule):
    """Execute an explicit acyclic task graph once.

    The generic escape hatch for schedules the named classes don't cover —
    and the substrate of the frontier property tests: any acyclic task set
    completes (no deadlock), streams serialize deterministically, and link
    tasks contend like every other flow.  Cycles and dangling dependencies
    are rejected at :class:`~repro.sim.engine.JobSpec` construction."""

    tasks: tuple[DAGTask, ...] = ()

    name: ClassVar[str] = "dag"
    synchronous: ClassVar[bool] = False

    def __post_init__(self):
        names = [t.name for t in self.tasks]
        if len(set(names)) != len(names):
            raise ValueError(f"duplicate task names: {names}")
        known = set(names)
        for t in self.tasks:
            missing = [d for d in t.deps if d not in known]
            if missing:
                raise ValueError(
                    f"task {t.name!r} depends on unknown {missing}")
        # Kahn's algorithm: anything left over sits on a cycle.
        indeg = {t.name: len(set(t.deps)) for t in self.tasks}
        dependents = collections.defaultdict(list)
        for t in self.tasks:
            for d in set(t.deps):
                dependents[d].append(t.name)
        queue = collections.deque(
            t.name for t in self.tasks if indeg[t.name] == 0)
        seen = 0
        while queue:
            n = queue.popleft()
            seen += 1
            for m in dependents[n]:
                indeg[m] -= 1
                if indeg[m] == 0:
                    queue.append(m)
        if seen != len(self.tasks):
            stuck = sorted(n for n, d in indeg.items() if d > 0)
            raise ValueError(f"dependency cycle through {stuck}")

    def validate_spec(self, spec) -> None:
        if spec.iters != 1:
            raise ValueError("DAGSchedule runs its graph once; iters must "
                             "be 1 (replicate tasks for more iterations)")

    def driver(self, run):
        return _DAGDriver(self, run)

    def dependencies(self, num_buckets: int) -> tuple[tuple[str, str], ...]:
        return tuple((d, t.name) for t in self.tasks for d in t.deps)


SCHEDULES = {
    "bsp": BSP,
    "pipelined": PipelinedAllReduce,
    "1f1b": OneFoneB,
    "localsgd": LocalSGD,
    "dag": DAGSchedule,
}
