"""Fleet-scale vectorized simulation backend: one jitted call per grid.

The closed forms in ``core/simulator`` and ``sim/schedules`` are exact on
their validity domain but were evaluated one (job, N, seed, bandwidth)
point at a time in Python.  This module turns them into data-parallel
kernels: every scenario becomes a **case** — a padded-and-masked bucket
column plus a handful of scalars — and a whole batch of cases (a scaling
grid, a placement-search scoring pass, a CoPlanner round's candidate
assignments) is evaluated by ONE jitted jax.numpy kernel:

* axes: bucket arrays are ``[K, C]`` with the scan (bucket) axis
  **leading** — XLA then fuses each recurrence step into one elementwise
  op over contiguous ``[C, S, I]`` blocks, which is where the >=10x win
  over the per-point Python loop comes from; jitter scales are
  ``[C, S, I]`` (case × seed × iteration) fleet-max values computed on
  the host (``WorkerProfile.scale`` is seeded per (seed, job, worker,
  iteration) — irreproducible with device RNG, and shared by every
  backend anyway);
* padding: ``K`` is the batch-max bucket count rounded up to a power of
  two (stable jit cache across nearby plans); masked steps are bitwise
  no-ops, and a *masked-off* row is distinct from a *real zero-byte
  bucket* (mask on, duration zero — its ready time still gates the
  recurrence, exactly like ``AllReduceModel.time(0) == 0``);
* schedules: the kernel computes all three closed-form shapes —
  barrier (BSP / OneFoneB tail compression), the DeAR pipelined
  cross-iteration recurrence, LocalSGD rounds — and selects per case by
  ``FleetForm.kind``, so heterogeneous batches (a mixed-schedule fleet)
  still take one device call;
* precision: everything runs under ``jax.enable_x64`` so
  the recurrence arithmetic is float64 like the numpy fast path; the
  scan recurrence itself is operation-for-operation the numpy one
  (agreement to well under 1e-9 — only sum *reductions* may
  re-associate, at ~1 ulp);
* models: any cost model goes through ``cost_model.as_linear`` — a
  hierarchical ``PathModel``'s per-link phases flatten to the one (a, b)
  the closed forms consume (a sum of affine phases is affine), so
  hierarchical ICI+DCN topologies ride the same kernel.

Validity is the sweep's ``closed_form_valid`` domain: single job on its
link, sequential issue, no bursts; heterogeneity/jitter only for
schedules whose :class:`~repro.sim.schedules.FleetForm` says
``heterogeneous_ok``.  ``run_sweep(backend="fleet")`` dispatches here;
the numpy path stays as the portable fallback and the event engine as
the oracle (``tests/test_fleet*.py`` pin all three together at 1e-9).

:class:`FleetEvaluator` is the co-planner face: it scores every
candidate assignment of a round in one device call, each job under its
OWN cost model (no cross-job contention — use the engine-backed
evaluator when contention is the question; this one is for fleet-scale
seed scoring and placement search, where the model already embeds the
contention via refit).

**Planning is batched too** (:func:`plan_cases` / :func:`plan_batched`):
the optimal-bucketing DP of ``core.planner.plan_dp_optimal`` runs as a
jitted ``lax.scan`` over layers with a leading case axis, so a whole
batch of (spec prefix-sums, flattened (a, b) model) planning problems —
a placement search, a co-plan round's responses, a what-if query burst —
is planned in ONE device call.  The recurrence is the O(L²)-masked
batched form (each scan step reduces over all L candidate split points),
which loses to the O(L) incremental ``Planner`` per point but wins on
throughput from a few dozen cases up (see docs/planner.md "Batched
planning" for the measured crossover); ``plan_dp_optimal`` and
``Planner`` stay the per-point oracles.
"""

from __future__ import annotations

import dataclasses
import hashlib
from typing import Mapping, MutableMapping, Sequence

import numpy as np

from repro.core import cost_model
from repro.core.coplanner import CoJob, CoObservation, JobObservation
from repro.core.cost_model import as_linear
from repro.core.planner import MergePlan, TensorSpec
from repro.core.simulator import bucket_arrays, spec_arrays
from repro.obs.metrics import REGISTRY
from repro.sim.schedules import FleetForm, Schedule

_KIND = {"barrier": 0, "pipelined": 1, "localsgd": 2}
_BARRIER, _PIPELINED, _LOCALSGD = 0, 1, 2

# the DP's improvement hysteresis — must match plan_dp_optimal's, so a
# candidate that is smaller only by accumulated-rounding dust does not
# steal the parent slot from an earlier (bigger-merge) candidate
_DP_EPS = 1e-15


def _kernel_call(kernel: str) -> None:
    REGISTRY.counter(
        "fleet_kernel_calls_total",
        "jitted fleet-kernel invocations, by kernel "
        "(evaluate = evaluate_cases, plan = plan_cases)").inc(kernel=kernel)


def fleet_available() -> bool:
    """True iff jax is importable (the kernel compiles lazily)."""
    try:
        import jax  # noqa: F401
    except Exception:  # pragma: no cover - environment-dependent
        return False
    return True


def profile_fingerprint(prefix_bytes: np.ndarray,
                        prefix_t: np.ndarray) -> str:
    """Content hash of one tensor profile's prefix arrays.

    This is the cache-scoping half of the geometry memo key: two
    profiles with identical bytes/ready structure may safely share
    geometry, two that differ never collide — so one cache can span
    jobs and grids (the old ``plan.buckets``-only key silently returned
    the wrong geometry if a caller reused a cache across profiles)."""
    h = hashlib.blake2b(digest_size=16)
    pb = np.ascontiguousarray(prefix_bytes, dtype=np.float64)
    pt = np.ascontiguousarray(prefix_t, dtype=np.float64)
    h.update(len(pb).to_bytes(8, "little"))
    h.update(pb.tobytes())
    h.update(pt.tobytes())
    return h.hexdigest()


class GeomCache(MutableMapping):
    """LRU-bounded geometry memo for :func:`make_case`.

    Keys are ``(profile_fingerprint, plan.buckets)`` so one instance can
    safely span tensor profiles (jobs, grids, snapshots).  Hits and
    evictions surface as ``fleet_geom_cache_hits_total`` /
    ``fleet_geom_cache_evictions_total``."""

    def __init__(self, maxsize: int = 4096):
        if maxsize < 1:
            raise ValueError("maxsize must be >= 1")
        self.maxsize = int(maxsize)
        self._d: "dict" = {}

    def get(self, key, default=None):
        try:
            return self[key]
        except KeyError:
            return default

    def __getitem__(self, key):
        val = self._d.pop(key)         # KeyError propagates on miss
        self._d[key] = val             # re-insert = move to MRU end
        REGISTRY.counter(
            "fleet_geom_cache_hits_total",
            "make_case geometry-memo hits").inc()
        return val

    def __setitem__(self, key, val):
        self._d.pop(key, None)
        self._d[key] = val
        while len(self._d) > self.maxsize:
            self._d.pop(next(iter(self._d)))
            REGISTRY.counter(
                "fleet_geom_cache_evictions_total",
                "make_case geometry-memo LRU evictions").inc()

    def __delitem__(self, key):
        del self._d[key]

    def __iter__(self):
        return iter(self._d)

    def __len__(self):
        return len(self._d)


@dataclasses.dataclass(frozen=True, eq=False)
class FleetCase:
    """One scenario column of the batch: a (specs, plan, model, schedule,
    scales) point, reduced to the arrays the kernel consumes."""

    bucket_bytes: np.ndarray        # [K_c] float64, per-bucket bytes
    ready_off: np.ndarray           # [K_c] nominal ready offsets (s)
    t_f: float                      # forward compute (s)
    t_b_total: float                # total backward compute (s)
    a: float                        # flat startup term (s)
    b: float                        # flat per-byte term (s/B)
    kind: int = _BARRIER            # _KIND[FleetForm.kind]
    micro_batches: int = 1          # barrier: OneFoneB tail compression
    ag_fraction: float = 0.0        # pipelined: deferred share
    h: int = 1                      # localsgd: steps per round
    s_max: np.ndarray | None = None  # [S, I] fleet-max scales (None = 1.0)


@dataclasses.dataclass(frozen=True, eq=False)
class FleetResult:
    """Kernel output: per-iteration times and total span per case."""

    t_iter: np.ndarray              # [C, S, iters] seconds
    span: np.ndarray                # [C, S] run wall time


def make_case(specs: Sequence[TensorSpec], plan: MergePlan, model, *,
              schedule: Schedule | None = None, t_f: float = 0.0,
              s_max: np.ndarray | None = None,
              prefix_bytes: np.ndarray | None = None,
              prefix_t: np.ndarray | None = None,
              cache: MutableMapping | None = None,
              profile_key: str | None = None) -> FleetCase:
    """Reduce one scenario to a :class:`FleetCase`.

    ``prefix_bytes`` / ``prefix_t`` (``core.simulator.spec_arrays``) can
    be passed in when many cases share one profile — the sweep computes
    them once per grid.  ``s_max`` is the fleet-max compute scale per
    (seed, iteration); rejected when the schedule's closed form is
    homogeneous-only (``FleetForm.heterogeneous_ok``).

    ``cache`` memoizes the per-plan bucket geometry keyed on
    ``(profile fingerprint, plan.buckets)`` — a grid re-scoring the same
    few plan structures under many models (every WFBP/single sweep, most
    DP sweeps) pays the O(num_buckets) Python walk once instead of per
    point.  One cache may safely span tensor profiles (use
    :class:`GeomCache` for an LRU-bounded one with hit/eviction
    counters); ``profile_key`` short-circuits the fingerprint hash when
    the caller already computed :func:`profile_fingerprint` for this
    profile — hot loops should.
    """
    form = schedule.fleet_form() if schedule is not None \
        else FleetForm(kind="barrier")
    if form is None:
        raise ValueError(
            f"schedule {schedule!r} has no fleet form — engine only")
    geom = None
    if cache is not None:
        if profile_key is None:
            if prefix_bytes is None or prefix_t is None:
                prefix_bytes, prefix_t = spec_arrays(specs)
            profile_key = profile_fingerprint(prefix_bytes, prefix_t)
        geom = cache.get((profile_key, plan.buckets))
    if geom is None:
        if plan.num_tensors != len(specs):
            raise ValueError(
                f"plan covers {plan.num_tensors} tensors, "
                f"specs has {len(specs)}")
        if prefix_bytes is None or prefix_t is None:
            prefix_bytes, prefix_t = spec_arrays(specs)
        geom = bucket_arrays(prefix_bytes, prefix_t, plan)
        if cache is not None:
            cache[(profile_key, plan.buckets)] = geom
    elif prefix_t is None:
        _, prefix_t = spec_arrays(specs)
    bucket_bytes, ready_off = geom
    sm = None
    if s_max is not None:
        sm = np.asarray(s_max, dtype=np.float64)
        if sm.ndim != 2:
            raise ValueError(
                f"s_max must be (seeds, iters)-shaped, got {sm.shape}")
        if not form.heterogeneous_ok and np.any(sm != 1.0):
            raise ValueError(
                f"{schedule.label} closed form is homogeneous-only; "
                "heterogeneous fleets need the event engine")
    lin = as_linear(model)
    return FleetCase(
        bucket_bytes=bucket_bytes, ready_off=ready_off, t_f=float(t_f),
        t_b_total=float(prefix_t[-1]) if len(prefix_t) else 0.0,
        a=float(lin.a), b=float(lin.b), kind=_KIND[form.kind],
        micro_batches=form.micro_batches, ag_fraction=form.ag_fraction,
        h=form.h, s_max=sm)


# ---------------------------------------------------------------------------
# The kernel (built lazily so importing this module never needs jax).
# ---------------------------------------------------------------------------

_KERNEL = None


def _get_kernel():
    global _KERNEL
    if _KERNEL is not None:
        return _KERNEL
    import jax
    import jax.numpy as jnp
    from jax import lax

    def kernel(bucket_bytes, ready_off, mask, a, b, t_f, t_b,
               m, ag_f, h, kind, s_max, has_pipelined, has_localsgd):
        # bucket arrays [K, C] (scan axis leading), scalars [C],
        # s_max [C, S, I].  All float64 under enable_x64.
        # has_pipelined / has_localsgd are STATIC: a barrier-only batch
        # (every pure scaling grid) compiles without the pipelined
        # cross-iteration scan — iters x K extra steps it never reads.
        iters = s_max.shape[2]
        dur = a[None, :] + b[None, :] * bucket_bytes
        # real zero-byte buckets cost 0 (AllReduceModel.time semantics)
        # but their mask stays on: the ready-time max still applies
        bt = jnp.where(mask & (bucket_bytes > 0.0), dur, 0.0)

        # -- barrier: Eq. 7/8 with ready times in the last micro-batch's
        #    1/m tail, scaled by the fleet-max compute scale ------------
        pair = (t_f + t_b) / m
        base = (m - 1.0) * pair + t_f / m
        nominal = base[None, :] + ready_off / m[None, :]    # [K, C]
        nominal_bwd = base + t_b / m                        # [C]

        def barrier_step(end, xs):
            bt_k, nom_k, mk = xs
            upd = jnp.maximum(end, nom_k[:, None, None] * s_max) \
                + bt_k[:, None, None]
            return jnp.where(mk[:, None, None], upd, end), None

        end, _ = lax.scan(barrier_step, jnp.zeros_like(s_max),
                          (bt, nominal, mask))
        barrier_t = jnp.maximum(end, nominal_bwd[:, None, None] * s_max)

        t_iter = barrier_t

        # -- localsgd: h-1 free steps per round, barrier on sync steps --
        # (localsgd cases carry m == 1 and s_max == 1, so barrier_t IS
        # the BSP sync time; truncated final rounds sync at iters-1)
        if has_localsgd:
            i_idx = jnp.arange(iters)
            is_sync = (((i_idx[None, :] + 1) % h[:, None]) == 0) \
                | (i_idx[None, :] == iters - 1)             # [C, I]
            local_t = (t_f + t_b)[:, None, None]
            localsgd_t = jnp.where(is_sync[:, None, :], barrier_t,
                                   jnp.broadcast_to(local_t,
                                                    barrier_t.shape))
            t_iter = jnp.where(kind[:, None, None] == _LOCALSGD,
                               localsgd_t, t_iter)

        # -- pipelined: DeAR cross-iteration recurrence (homogeneous) ---
        if has_pipelined:
            has = mask.any(axis=0)                          # [C]
            ag_total = ag_f * bt.sum(axis=0)                # [C]

            def pipe_iter(carry, _):
                S_, ag_done = carry                         # [C] each
                fwd_end = S_ + t_f
                bwd_start = jnp.maximum(fwd_end, ag_done)
                bwd_end = bwd_start + t_b

                def rs_step(end, xs):
                    bt_k, ro_k, mk = xs
                    upd = jnp.maximum(end, bwd_start + ro_k) \
                        + (1.0 - ag_f) * bt_k
                    return jnp.where(mk, upd, end), None

                rs_end, _ = lax.scan(rs_step, jnp.zeros_like(S_),
                                     (bt, ready_off, mask))
                rs_done = jnp.where(has, rs_end, bwd_end)
                ag_done_n = jnp.where(has, rs_done + ag_total, bwd_end)
                iter_end = jnp.maximum(ag_done_n, bwd_end)
                s_next = jnp.maximum(bwd_end, rs_done)
                return (s_next, ag_done_n), (iter_end - S_, iter_end)

            zero_c = jnp.zeros_like(a)
            _, (pipe_t, pipe_end) = lax.scan(pipe_iter, (zero_c, zero_c),
                                             None, length=iters)
            pipe_tb = jnp.broadcast_to(pipe_t.T[:, None, :],
                                       barrier_t.shape)
            t_iter = jnp.where(kind[:, None, None] == _PIPELINED,
                               pipe_tb, t_iter)
            # barrier/localsgd iterations abut (span = sum); pipelined
            # iterations overlap — span is the recurrence's absolute end
            span = jnp.where(kind[:, None] == _PIPELINED,
                             pipe_end[-1][:, None], t_iter.sum(axis=-1))
        else:
            span = t_iter.sum(axis=-1)
        return t_iter, span

    _KERNEL = jax.jit(kernel, static_argnums=(12, 13))
    return _KERNEL


def evaluate_cases(cases: Sequence[FleetCase],
                   iters: int = 1) -> FleetResult:
    """Evaluate a whole batch of cases in one jitted device call.

    Cases may mix schedules, models and bucket counts; bucket axes are
    padded to the batch max (next power of two, for jit-cache stability)
    and masked.  The case axis is padded the same way — fully-masked
    benign columns, sliced off the result — so batch sizes that differ
    only within a power-of-two bracket reuse one compiled kernel (a
    CoPlanner round whose candidate count drifts as the cache fills
    would otherwise recompile every round).  All cases carrying an
    ``s_max`` must agree on the seed count; cases without one broadcast
    a scale of 1.0.
    """
    if not cases:
        raise ValueError("need >= 1 case")
    if iters < 1:
        raise ValueError("need >= 1 iteration")
    if not fleet_available():
        raise RuntimeError(
            "fleet backend needs jax; use run_sweep(backend='numpy')")
    C = len(cases)
    S = 1
    for c in cases:
        if c.s_max is not None:
            if c.s_max.shape[1] != iters:
                raise ValueError(
                    f"s_max covers {c.s_max.shape[1]} iterations, "
                    f"sweep runs {iters}")
            if S == 1:
                S = c.s_max.shape[0]
            elif c.s_max.shape[0] not in (1, S):
                raise ValueError(
                    f"inconsistent seed counts across cases: "
                    f"{c.s_max.shape[0]} vs {S}")
    k_max = max((len(c.bucket_bytes) for c in cases), default=0)
    # at least 2: XLA inlines a one-step scan, and the inlined body rounds
    # differently from the loop, so a lone one-bucket case would not equal
    # the same case in a padded batch
    k_pad = 1 << (max(k_max, 2) - 1).bit_length()
    c_pad = 1 << (C - 1).bit_length()

    bb = np.zeros((k_pad, c_pad), dtype=np.float64)
    ro = np.zeros((k_pad, c_pad), dtype=np.float64)
    mk = np.zeros((k_pad, c_pad), dtype=bool)
    # padding columns are benign barrier cases: m = h = 1, all else 0
    scal = {n: np.zeros(c_pad, dtype=np.float64)
            for n in ("a", "b", "t_f", "t_b", "ag_f")}
    scal["m"] = np.ones(c_pad, dtype=np.float64)
    h = np.ones(c_pad, dtype=np.int32)
    kind = np.zeros(c_pad, dtype=np.int32)
    sm = np.ones((c_pad, S, iters), dtype=np.float64)
    for ci, c in enumerate(cases):
        nk = len(c.bucket_bytes)
        bb[:nk, ci] = c.bucket_bytes
        ro[:nk, ci] = c.ready_off
        mk[:nk, ci] = True
        scal["a"][ci] = c.a
        scal["b"][ci] = c.b
        scal["t_f"][ci] = c.t_f
        scal["t_b"][ci] = c.t_b_total
        scal["m"][ci] = c.micro_batches
        scal["ag_f"][ci] = c.ag_fraction
        h[ci] = c.h
        kind[ci] = c.kind
        if c.s_max is not None:
            sm[ci] = c.s_max
    import jax
    import jax.numpy as jnp
    kern = _get_kernel()
    _kernel_call("evaluate")
    with jax.enable_x64(True):
        t_iter, span = kern(
            jnp.asarray(bb), jnp.asarray(ro), jnp.asarray(mk),
            jnp.asarray(scal["a"]), jnp.asarray(scal["b"]),
            jnp.asarray(scal["t_f"]), jnp.asarray(scal["t_b"]),
            jnp.asarray(scal["m"]), jnp.asarray(scal["ag_f"]),
            jnp.asarray(h), jnp.asarray(kind), jnp.asarray(sm),
            bool((kind == _PIPELINED).any()),
            bool((kind == _LOCALSGD).any()))
        return FleetResult(t_iter=np.asarray(t_iter)[:C],
                           span=np.asarray(span)[:C])


# ---------------------------------------------------------------------------
# Batched planning: the optimal-bucketing DP with a leading case axis.
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True, eq=False)
class PlanCase:
    """One planning problem of the batch: a (spec prefix-sums, flat
    (a, b) model) pair, reduced to the arrays the plan kernel consumes."""

    pre: np.ndarray                 # [L+1] float64 prefix bytes (exact)
    ready: np.ndarray               # [L] gradient-ready times (s)
    a: float                        # flat startup term (s)
    b: float                        # flat per-byte term (s/B)

    @property
    def num_tensors(self) -> int:
        return len(self.ready)


def make_plan_case(specs: Sequence[TensorSpec], model, *,
                   prefix_bytes: np.ndarray | None = None,
                   prefix_t: np.ndarray | None = None) -> PlanCase:
    """Reduce one planning problem to a :class:`PlanCase`.

    Any cost model goes through :func:`~repro.core.cost_model.as_linear`
    (a ``PathModel`` flattens to the (a, b) the DP consumes, exactly like
    ``plan_dp_optimal``).  ``prefix_bytes`` / ``prefix_t`` from
    ``core.simulator.spec_arrays`` can be passed when many cases share
    one tensor profile.
    """
    if prefix_bytes is None or prefix_t is None:
        prefix_bytes, prefix_t = spec_arrays(specs)
    lin = as_linear(model)
    return PlanCase(pre=np.asarray(prefix_bytes, dtype=np.float64),
                    ready=np.asarray(prefix_t, dtype=np.float64),
                    a=float(lin.a), b=float(lin.b))


_PLAN_KERNEL = None


def _get_plan_kernel():
    global _PLAN_KERNEL
    if _PLAN_KERNEL is not None:
        return _PLAN_KERNEL
    import jax
    import jax.numpy as jnp
    from jax import lax

    def kernel(pre, ready, a, b):
        # pre [Lp+1, C], ready [Lp, C], a/b [C].  One scan step per layer
        # i; each step reduces over every candidate split point m <= i —
        # the O(L^2) recurrence of plan_dp_optimal, all cases at once:
        #
        #   cand[m] = max(F[m], ready[i]) + T(pre[i+1] - pre[m])
        #   f[i]    = "first candidate within _DP_EPS of the minimum"
        #
        # The winner rule reproduces the host DP's incumbent hysteresis
        # (`cand < f[i] - 1e-15` keeps the earlier, bigger-merge parent):
        # mathematically-tied candidates that round differently — the
        # only near-ties real profiles produce — land inside the window
        # together, and the earliest index wins on host and device alike.
        # That also absorbs XLA's fma contraction of a + b*d (~1 ulp vs
        # the host's separate mul/add), so bucket structure is bit-equal
        # to plan_dp_optimal even though f may differ in the last ulp.
        Lp = ready.shape[0]
        m_idx = jnp.arange(Lp + 1)[:, None]                 # [Lp+1, 1]

        def step(F, xs):
            r_i, p_i1, i = xs                               # [C], [C], []
            d = p_i1[None, :] - pre                         # [Lp+1, C]
            t = jnp.where(d > 0.0, a[None, :] + b[None, :] * d, 0.0)
            cand = jnp.maximum(F, r_i[None, :]) + t
            cand = jnp.where(m_idx <= i, cand, jnp.inf)
            cmin = cand.min(axis=0)                         # [C]
            win = jnp.argmax(cand < (cmin + _DP_EPS)[None, :], axis=0)
            f_i = jnp.take_along_axis(cand, win[None, :], axis=0)[0]
            F = lax.dynamic_update_index_in_dim(F, f_i, i + 1, 0)
            return F, (f_i, win.astype(jnp.int32))

        F0 = jnp.zeros_like(pre)                            # F[m] = f[m-1]
        _, (f, win) = lax.scan(step, F0,
                               (ready, pre[1:], jnp.arange(Lp)))
        return f, win

    _PLAN_KERNEL = jax.jit(kernel)
    return _PLAN_KERNEL


def _plan_recurrence_numpy(pre: np.ndarray, ready: np.ndarray,
                           a: np.ndarray, b: np.ndarray):
    """Portable fallback: the same recurrence, numpy per layer.

    No fma contraction here, so f is bit-identical to the host oracle's
    arithmetic; the winner rule is the same first-within-eps window."""
    Lp = ready.shape[0]
    C = a.shape[0]
    F = np.zeros((Lp + 1, C), dtype=np.float64)
    f = np.zeros((Lp, C), dtype=np.float64)
    win = np.zeros((Lp, C), dtype=np.int32)
    m_idx = np.arange(Lp + 1)[:, None]
    for i in range(Lp):
        d = pre[i + 1][None, :] - pre
        t = np.where(d > 0.0, a[None, :] + b[None, :] * d, 0.0)
        cand = np.maximum(F, ready[i][None, :]) + t
        cand = np.where(m_idx <= i, cand, np.inf)
        cmin = cand.min(axis=0)
        w = np.argmax(cand < (cmin + _DP_EPS)[None, :], axis=0)
        f[i] = cand[w, np.arange(C)]
        win[i] = w
        F[i + 1] = f[i]
    return f, win


def plan_cases(cases: Sequence[PlanCase], *,
               backend: str = "auto") -> list[MergePlan]:
    """Plan a whole batch of problems in one device call.

    Returns one ``MergePlan`` (strategy ``"dp_batched"``) per case,
    bucket-for-bucket equal to ``plan_dp_optimal`` on each.  ``L`` and
    ``C`` are padded to powers of two like :func:`evaluate_cases`
    (masked candidate rows and benign padding columns, sliced off), so
    nearby batch shapes reuse one compiled kernel.  ``backend`` is
    ``"auto"`` (jax when importable), ``"fleet"`` (require jax) or
    ``"numpy"`` (portable fallback, same recurrence per layer — the
    right choice for a handful of cases; the device call wins from a
    few dozen cases up, see docs/planner.md for the crossover).
    """
    if backend not in ("auto", "fleet", "numpy"):
        raise ValueError(f"unknown backend {backend!r}")
    if backend == "auto":
        backend = "fleet" if fleet_available() else "numpy"
    elif backend == "fleet" and not fleet_available():
        raise RuntimeError(
            "fleet backend needs jax; use plan_cases(backend='numpy')")
    cases = list(cases)
    REGISTRY.counter(
        "fleet_plan_cases_total",
        "planning problems solved by the batched DP, by backend").inc(
            len(cases), backend=backend)
    live = [(ci, c) for ci, c in enumerate(cases) if c.num_tensors > 0]
    out: list[MergePlan | None] = [
        None if c.num_tensors else MergePlan((), "dp_batched")
        for c in cases]
    if not live:
        return [p for p in out if p is not None] if cases else []
    l_max = max(c.num_tensors for _, c in live)
    C = len(live)
    if backend == "fleet":
        l_pad = 1 << (l_max - 1).bit_length()
        c_pad = 1 << (C - 1).bit_length()
    else:
        l_pad, c_pad = l_max, C
    pre = np.zeros((l_pad + 1, c_pad), dtype=np.float64)
    ready = np.zeros((l_pad, c_pad), dtype=np.float64)
    ab = np.zeros((2, c_pad), dtype=np.float64)
    for k, (_, c) in enumerate(live):
        n = c.num_tensors
        pre[:n + 1, k] = c.pre
        pre[n + 1:, k] = c.pre[-1]      # padded layers add zero bytes
        ready[:n, k] = c.ready
        ab[0, k], ab[1, k] = c.a, c.b
    if backend == "fleet":
        import jax
        import jax.numpy as jnp
        kern = _get_plan_kernel()
        _kernel_call("plan")
        with jax.enable_x64(True):
            _, win = kern(jnp.asarray(pre), jnp.asarray(ready),
                          jnp.asarray(ab[0]), jnp.asarray(ab[1]))
        win = np.asarray(win)
    else:
        _, win = _plan_recurrence_numpy(pre, ready, ab[0], ab[1])
    # host-side chain reconstruction: parent[i] = win[i] - 1, NEG = -1
    for k, (ci, c) in enumerate(live):
        last, i = [], c.num_tensors - 1
        while i != -1:
            last.append(i)
            i = int(win[i, k]) - 1
        out[ci] = MergePlan.from_boundaries(c.num_tensors, sorted(last),
                                            "dp_batched")
    return out  # type: ignore[return-value]


def plan_batched(problems: Sequence[tuple[Sequence[TensorSpec], object]],
                 *, backend: str = "auto") -> list[MergePlan]:
    """Convenience face over :func:`plan_cases`: a list of
    (specs, model) pairs in, one optimal ``MergePlan`` each out, all
    planned in one device call."""
    return plan_cases([make_plan_case(s, m) for s, m in problems],
                      backend=backend)


# ---------------------------------------------------------------------------
# Co-planner face: score a whole round of assignments in one call.
# ---------------------------------------------------------------------------

class FleetEvaluator:
    """Batched ``CoEvaluate``: one device call per *round* of candidate
    assignments instead of one Python simulation per assignment.

    Each job is scored under its own cost model on its schedule's closed
    form — no cross-job link contention is modelled, which is exactly the
    seed-scoring / placement-search regime (the engine-backed evaluator
    stays the oracle when contention itself is the question; a refit
    contended model slots in transparently since only ``job.model`` is
    read).  ``CoPlanner`` discovers :meth:`batch` via ``getattr`` and
    routes every round's uncached candidates through it.

    Observed iteration time is ``span / iters`` (for barrier schedules,
    exactly the closed form; for pipelined, the average realized window
    including warmup — raise ``iters`` to sharpen the steady state).
    Samples are the exact per-bucket (nbytes, model time) pairs the
    closed form charged, with per-link decomposition for ``PathModel``
    jobs, so a downstream refit reproduces the scoring model.
    """

    def __init__(self, jobs: Sequence[CoJob], *, iters: int = 8):
        if iters < 1:
            raise ValueError("need >= 1 iteration")
        self.jobs = tuple(jobs)
        self.iters = int(iters)
        self._static = {}
        # one profile-fingerprint-keyed LRU spans every job safely
        self._geom = GeomCache()
        for j in self.jobs:
            pb, pt = spec_arrays(j.specs)
            self._static[j.name] = (pb, pt, as_linear(j.model),
                                    profile_fingerprint(pb, pt))
        self._sample_cache: dict = {}

    def _job_samples(self, job: CoJob, plan: MergePlan):
        key = (job.name, plan.buckets)
        cached = self._sample_cache.get(key)
        if cached is None:
            pb, pt, lin, fp = self._static[job.name]
            geom = self._geom._d.get((fp, plan.buckets))
            nbytes = geom[0] if geom is not None \
                else bucket_arrays(pb, pt, plan)
            samples = tuple((int(n), lin.time(n)) for n in nbytes)
            links: tuple = ()
            if isinstance(job.model, cost_model.PathModel):
                per: dict[str, list] = {l: [] for l in job.model.links}
                for n in nbytes:
                    for p in job.model.phases:
                        per[p.link].append((int(n), p.time(n)))
                links = tuple((l, tuple(v)) for l, v in per.items())
            cached = (samples, links)
            self._sample_cache[key] = cached
        return cached

    def batch(self, assignments: Sequence[Mapping[str, MergePlan]]
              ) -> list[CoObservation]:
        cases = []
        for a in assignments:
            for j in self.jobs:
                pb, pt, _, fp = self._static[j.name]
                cases.append(make_case(
                    j.specs, a[j.name], j.model, schedule=j.schedule,
                    t_f=j.t_f, prefix_bytes=pb, prefix_t=pt,
                    cache=self._geom, profile_key=fp))
        res = evaluate_cases(cases, iters=self.iters)
        out: list[CoObservation] = []
        nj = len(self.jobs)
        for ai, a in enumerate(assignments):
            jobs_obs: dict[str, JobObservation] = {}
            makespan = 0.0
            for ji, j in enumerate(self.jobs):
                sp = float(res.span[ai * nj + ji, 0])
                makespan = max(makespan, sp)
                samples, link_samples = self._job_samples(j, a[j.name])
                jobs_obs[j.name] = JobObservation(
                    t_iter=sp / self.iters, samples=samples,
                    link_samples=link_samples)
            out.append(CoObservation(makespan=makespan, jobs=jobs_obs))
        return out

    def __call__(self, plans: Mapping[str, MergePlan]) -> CoObservation:
        return self.batch([plans])[0]
