"""Distributed train step: MG-WFBP-scheduled gradient communication.

Construction (``build_train_step``) happens once, outside jit:

  1. ``jax.eval_shape`` the parameter tree; split leaves into the
     *DP-replicated* group (attention, norms, dense FFN, shared experts —
     reduced over every data axis) and the *EP-owned* group (``*_e`` expert
     tensors when expert parallelism is on — owned along ``data``,
     replicated only over ``pod``).
  2. Build :class:`TensorSpec`s for the replicated group from the analytic
     per-tensor backward-time model (core/profiler.py) and ask the planner
     for the merge plan (``mgwfbp`` / ``wfbp`` / ``single`` / ``fixed:N`` /
     ``dp_optimal``) against the mesh's all-reduce cost model.
  3. Emit the step: ``shard_map`` with the DP axes *manual* (bucketed psum
     / reduce-scatter collectives placed explicitly, per plan — the paper's
     contribution) and the TP axis *auto* (GSPMD handles head/ffn sharding
     incl. non-divisible head counts).  Without TP every axis is manual.

ZeRO-1 (``parallel.zero == 1``): per-plan-bucket reduce-scatter of grads
over ``data`` (after a pod psum), optimizer on this shard's slice of the
packed bucket, merged all-gather of updated params — the same startup-cost
amortization argument the paper makes for all-reduce, applied to RS+AG.

Named scopes: the step's phases run under ``jax.named_scope`` so that a
profile of the compiled step attributes each device op to one of them —
``forward`` (opened inside the differentiated function, so that JAX names
the backward pass ``transpose(jvp(forward))`` and the ops ``jax.checkpoint``
recomputes ``.../rematted_computation``), ``accumulate`` (the microbatch
sum), ``grad_sync`` (the gradient reduction and the parameter repack,
``bucket_<k>`` per bucket of the plan, ``ep`` for the expert group) and
``optimizer`` (global norm, clipping, update).  Scopes only add metadata
to the compiled program.

Note on pytrees: group splitting inserts ``None`` at excluded leaves; JAX
treats ``None`` as an empty subtree, so the pruned trees flow through
bucketer/comm/optim untouched.
"""

from __future__ import annotations

import dataclasses
import re
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import RunConfig
from repro.core import bucketer, comm, cost_model, planner, profiler
from repro.models import sharding as shd
from repro.models.transformer import LM
from repro.optim import clip as oclip
from repro.optim.optimizers import Optimizer, make_optimizer
from repro.optim.schedule import warmup_cosine
from repro.train.train_state import TrainState

EP_LEAF_RE = re.compile(r"w_(gate|up|down)_e")


@dataclasses.dataclass
class StepArtifacts:
    """Everything the launcher needs besides the step function itself."""
    plan: planner.MergePlan
    ep_plan: planner.MergePlan | None
    specs: list
    comm_model: cost_model.AllReduceModel
    param_pspecs: Any
    state_pspecs: Any
    batch_pspec: P
    dp_axes: tuple
    manual_axes: frozenset


def _keystr(path) -> str:
    return jax.tree_util.keystr(path)


def _split_groups(tree, ep_on: bool):
    """(replicated, ep_owned) trees with None at excluded leaves."""
    def rep(path, leaf):
        return None if (ep_on and EP_LEAF_RE.search(_keystr(path))) else leaf

    def ep(path, leaf):
        return leaf if (ep_on and EP_LEAF_RE.search(_keystr(path))) else None

    return (jax.tree_util.tree_map_with_path(rep, tree),
            jax.tree_util.tree_map_with_path(ep, tree))


def _merge_groups(template, rep, ep):
    """Inverse of _split_groups: fill template positions from rep/ep."""
    rep_by = {_keystr(p): v for p, v in
              jax.tree_util.tree_flatten_with_path(rep)[0]}
    ep_by = {_keystr(p): v for p, v in
             jax.tree_util.tree_flatten_with_path(ep)[0]} if ep is not None \
        else {}
    flat_t, treedef = jax.tree_util.tree_flatten_with_path(template)
    out = [rep_by.get(_keystr(p), ep_by.get(_keystr(p), v))
           for p, v in flat_t]
    return jax.tree_util.tree_unflatten(treedef, out)


def _axes_size(axes) -> int:
    n = 1
    for a in axes:
        n *= jax.lax.axis_size(a)
    return n


# ---------------------------------------------------------------------------
# Planning.
# ---------------------------------------------------------------------------

def build_plan(params_shape, run: RunConfig, mesh_shape, mesh_axes,
               strategy: str | None = None,
               exclude: set | None = None,
               ep_on: bool | None = None,
               tb_table: dict | None = None,
               comm_model=None):
    """Merge plan(s) + tensor specs + cost model for this run.

    ``exclude``: leaf paths whose DP reduction happens elsewhere (ZeRO-3
    leaves reduce inside autodiff via the gather transpose).
    ``ep_on``: expert-parallel split as decided by the caller — must match
    the step body's _split_groups or the plan's bucket indices point at
    the wrong leaves; defaults to the mesh-derived value.
    ``tb_table``: measured per-tensor backward times (``{path: seconds}``,
    e.g. from ``profiler.measure_loss_profile`` or a refit from real
    ``IterationRecord`` timings) — used where present, with the analytic
    roofline as the fallback prior (paper §5.1 measure-then-plan).
    ``comm_model``: override the mesh-derived all-reduce model with a
    measured/refit one (``train.replan`` feeds the effective model here)."""
    par = run.parallel
    if ep_on is None:
        ep_on = bool(par.ep_axis) and par.ep_axis in mesh_axes
    rep_shape, ep_shape = _split_groups(params_shape, ep_on)
    if exclude:
        rep_shape = jax.tree_util.tree_map_with_path(
            lambda p, l: None if _keystr(p) in exclude else l, rep_shape)
    dims = dict(zip(mesh_axes, mesh_shape))
    dp_total = 1
    for a in par.dp_axes:
        dp_total *= dims.get(a, 1)
    local_batch = max(run.shape.global_batch // max(dp_total, 1), 1)
    micro = min(run.microbatch or local_batch, local_batch)
    t_b = profiler.analytic_tb(micro * run.shape.seq_len)
    if tb_table:
        t_b = profiler.measured_tb(tb_table, t_b)
    specs = [s for s in bucketer.tensor_specs(rep_shape, t_b) if s.nbytes]
    model = comm_model if comm_model is not None else \
        cost_model.production_comm_model(mesh_shape, mesh_axes, par.dp_axes)
    plan = planner.make_plan(strategy or par.comm_strategy, specs, model)
    ep_plan, ep_specs = None, []
    if ep_on:
        ep_specs = [s for s in bucketer.tensor_specs(ep_shape, t_b)
                    if s.nbytes]
        pods = dims.get("pod", 1)
        if ep_specs and pods > 1:
            pod_model = cost_model.production_comm_model(
                mesh_shape, mesh_axes, ("pod",))
            ep_plan = planner.make_plan(strategy or par.comm_strategy,
                                        ep_specs, pod_model)
    return plan, ep_plan, specs, model


# ---------------------------------------------------------------------------
# FSDP (ZeRO-3): parameters sharded over the data axis.
# ---------------------------------------------------------------------------

FSDP_MIN_BYTES = 1 << 20


def fsdp_augment(pspecs, params_shape, zero_axis: str, zero_n: int,
                 ep_on: bool):
    """Add a ``zero_axis`` entry to every large replicated leaf's spec.

    Returns (new_pspecs, {path: gathered_dim}).  Leaves already EP-owned,
    small leaves, and dims not divisible by the axis size are left alone.
    The training step all-gathers marked leaves before the forward pass;
    autodiff's transpose (psum_scatter) then delivers *sharded* gradients —
    ZeRO-3 semantics with the optimizer running entirely on shards.
    """
    fsdp_dims: dict[str, int] = {}

    def one(path, spec, leaf):
        k = _keystr(path)
        if ep_on and EP_LEAF_RE.search(k):
            return spec
        nbytes = 1
        for d in leaf.shape:
            nbytes *= d
        nbytes *= jnp.dtype(leaf.dtype).itemsize
        if nbytes < FSDP_MIN_BYTES:
            return spec
        entries = list(spec) + [None] * (len(leaf.shape) - len(spec))
        # prefer the largest free divisible dim
        order = sorted(range(len(leaf.shape)),
                       key=lambda d: -leaf.shape[d])
        for d in order:
            if entries[d] is None and leaf.shape[d] % zero_n == 0:
                entries[d] = zero_axis
                fsdp_dims[k] = d
                return P(*entries)
        return spec

    new = jax.tree_util.tree_map_with_path(one, pspecs, params_shape)
    return new, fsdp_dims


def gather_fsdp(params, fsdp_dims: dict, zero_axis: str):
    """all_gather marked leaves (inside the manual shard_map region).
    Uses the safe gather so the gradient reduce-scatter survives the
    XLA:CPU 16-bit promotion bug (comm.safe_all_gather)."""
    def one(path, leaf):
        d = fsdp_dims.get(_keystr(path))
        if d is None:
            return leaf
        return comm.safe_all_gather(leaf, zero_axis, axis=d)
    return jax.tree_util.tree_map_with_path(one, params)


# ---------------------------------------------------------------------------
# State init + shardings.
# ---------------------------------------------------------------------------

def init_state(model: LM, opt: Optimizer, run: RunConfig,
               plan: planner.MergePlan, ep_on: bool, zero_n: int, key,
               eff_zero: int | None = None, aligned: bool = False):
    """Global TrainState (ZeRO-1 moment buffers are full-size; the data-axis
    sharding distributes them).  ``aligned`` sizes the packed buffers for
    the bucket_pack kernel's TILE-aligned slot layout."""
    params = model.init(key)
    zero = run.parallel.zero if eff_zero is None else eff_zero
    if zero != 1:
        return TrainState.create(params, opt.init(params))
    rep_p, ep_p = _split_groups(params, ep_on)
    metas = bucketer.leaf_metadata(rep_p)
    opt_shards = []
    for bucket in plan.buckets:
        total = bucketer.packed_elems([metas[i] for i in bucket],
                                      aligned=aligned)
        padded = comm.padded_elems(total, zero_n)
        opt_shards.append(opt.init_leaf(jnp.zeros((padded,), jnp.float32)))
    if ep_on:
        opt_shards.append(opt.init(ep_p))
    return TrainState.create(params, opt_shards)


def _opt_pspecs_like(params_spec, opt_shape):
    """Moments inherit their parameter's spec ({'m','v','mu'} per leaf)."""
    spec_by = {_keystr(p): v for p, v in
               jax.tree_util.tree_flatten_with_path(
                   params_spec, is_leaf=lambda x: isinstance(x, P))[0]}

    def one(path, leaf):
        k = _keystr(path)
        # strip trailing ['m'] / ['v'] / ['mu']
        base = re.sub(r"\['(m|v|mu)'\]$", "", k)
        return spec_by.get(base, P())
    return jax.tree_util.tree_map_with_path(one, opt_shape)


def state_pspecs(state_shape, params_spec, run: RunConfig, zero_axis: str,
                 ep_on: bool, eff_zero: int | None = None):
    zero = run.parallel.zero if eff_zero is None else eff_zero
    if zero != 1:
        opt_spec = _opt_pspecs_like(params_spec, state_shape.opt_state)
    else:
        opt_spec = []
        n_buckets = len(state_shape.opt_state) - (1 if ep_on else 0)
        for k in range(n_buckets):
            opt_spec.append(jax.tree.map(lambda _: P(zero_axis),
                                         state_shape.opt_state[k]))
        if ep_on:
            opt_spec.append(_opt_pspecs_like(params_spec,
                                             state_shape.opt_state[-1]))
    return TrainState(step=P(), params=params_spec, opt_state=opt_spec)


# ---------------------------------------------------------------------------
# Step builder.
# ---------------------------------------------------------------------------

def build_train_step(model: LM, run: RunConfig, mesh,
                     strategy: str | None = None, donate: bool = True,
                     tb_table: dict | None = None, comm_model=None,
                     plan_override: planner.MergePlan | None = None):
    """Returns (jit-ready step_fn, init_fn, StepArtifacts).

    ``tb_table`` / ``comm_model`` thread measured costs into the plan
    (see :func:`build_plan`); ``plan_override`` installs a specific merge
    plan — the :class:`repro.train.replan.ReplanController` swap path —
    bypassing the strategy planner (bucketing is pure scheduling, so the
    override changes step timing, never numerics)."""
    par = run.parallel
    mesh_axes = tuple(mesh.axis_names)
    mesh_shape = tuple(mesh.devices.shape)
    dims = dict(zip(mesh_axes, mesh_shape))
    dp_axes = tuple(a for a in par.dp_axes if a in mesh_axes)
    tp_axis = par.tp_axis if (par.tp_enabled and par.tp_axis in mesh_axes
                              and par.tp_axis not in dp_axes) else ""
    # The DP axes are manual: the plan's collectives are placed by hand.
    # The TP axis stays Auto for GSPMD; without TP no axis is left for
    # GSPMD, so every axis goes manual (a Pallas kernel such as bucket_pack
    # compiles for the TPU only where no axis is Auto).
    manual = frozenset(dp_axes) if tp_axis else frozenset(mesh_axes)
    ep_on = bool(par.ep_axis) and par.ep_axis in mesh_axes
    zero_axis = "data" if "data" in dp_axes else (dp_axes[0] if dp_axes
                                                  else "")
    pod_axes = tuple(a for a in dp_axes if a != zero_axis)
    zero_n = _static_size(dims, (zero_axis,)) if zero_axis else 1
    # effective ZeRO mode: sharded-state modes need a real data axis
    eff_zero = par.zero if (zero_axis and dp_axes) else 0

    opt = make_optimizer(run.optimizer, weight_decay=run.weight_decay,
                         state_dtype=run.optimizer_state_dtype,
                         b1=run.adam_b1, b2=run.adam_b2, eps=run.adam_eps,
                         momentum=run.sgd_momentum)
    lr_fn = warmup_cosine(run.learning_rate, run.warmup_steps,
                          run.total_steps)
    # paper §5.3 contiguous-buffer execution through the bucket_pack Pallas
    # kernel (slot-aligned buffer layout)
    use_kernel = bool(par.pack_kernel)

    params_shape = jax.eval_shape(lambda: model.init(jax.random.PRNGKey(0)))
    pspecs = shd.param_pspecs(params_shape,
                              ep_axis=par.ep_axis if ep_on else "",
                              tp_axis=tp_axis,
                              moe_token_shard=par.moe_token_shard)
    pspecs = shd.filter_uneven(pspecs, params_shape, dims)
    fsdp_dims: dict[str, int] = {}
    if eff_zero == 3:
        pspecs, fsdp_dims = fsdp_augment(pspecs, params_shape, zero_axis,
                                         zero_n, ep_on)
    plan, ep_plan, specs, cmodel = build_plan(params_shape, run, mesh_shape,
                                              mesh_axes, strategy,
                                              exclude=set(fsdp_dims),
                                              ep_on=ep_on,
                                              tb_table=tb_table,
                                              comm_model=comm_model)
    if plan_override is not None:
        if plan_override.num_tensors != len(specs):
            raise ValueError(
                f"plan_override covers {plan_override.num_tensors} tensors "
                f"but the step has {len(specs)}")
        plan = plan_override

    # static per-bucket weight-decay masks (packed ZeRO-1 path only), as
    # (length, value) runs.  The step builds each mask from broadcasts: a
    # bucket-sized constant (2 GB for qwen2-1.5b's single bucket) makes the
    # TPU compile of a sharded step take tens of GB of host memory.  The
    # kernel layout pads each leaf's slot with zeros — padding never decays.
    decay_runs = []
    if eff_zero == 1:
        rep_shape, _ = _split_groups(params_shape, ep_on)
        rep_metas = bucketer.leaf_metadata(rep_shape)
        for bucket in plan.buckets:
            runs: list[list] = []
            for i in bucket:
                m = rep_metas[i]
                slot = bucketer.slot_elems(m.size, aligned=use_kernel)
                for n, v in ((m.size, float(opt.weight_decay_mask(m.path))),
                             (slot - m.size, 0.0)):
                    if runs and runs[-1][1] == v:
                        runs[-1][0] += n
                    elif n:
                        runs.append([n, v])
            decay_runs.append(runs)

    dp_size = _static_size(dims, dp_axes)
    local_batch = max(run.shape.global_batch // max(dp_size, 1), 1)
    micro = min(run.microbatch or local_batch, local_batch)
    n_micro = max(local_batch // micro, 1)

    # ------------------------------------------------------------------

    def model_loss(params, mb):
        with jax.named_scope("forward"):
            return model.loss(params, mb)

    def compute_grads(loss_fn, params, batch):
        """Loss, metrics and gradients of ``loss_fn`` over the local batch,
        accumulated over ``n_micro`` microbatches."""
        if n_micro == 1:
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, batch)
            return loss, metrics, grads
        resh = jax.tree.map(
            lambda x: x.reshape((n_micro, micro) + x.shape[1:]), batch)

        def mb_body(carry, mb):
            acc, loss_acc = carry
            (loss, metrics), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, mb)
            with jax.named_scope("accumulate"):
                acc = jax.tree.map(lambda a, g: a + g.astype(a.dtype), acc,
                                   grads)
                return (acc, loss_acc + loss), metrics

        with jax.named_scope("accumulate"):
            zeros = jax.tree.map(lambda x: jnp.zeros(x.shape, jnp.float32),
                                 params)
        (gacc, loss_sum), metrics = jax.lax.scan(
            mb_body, (zeros, jnp.zeros((), jnp.float32)), resh)
        with jax.named_scope("accumulate"):
            grads = jax.tree.map(lambda g: g / n_micro, gacc)
            metrics = jax.tree.map(lambda m: m[-1], metrics)
            return loss_sum / n_micro, metrics, grads

    def reduce_replicated(rep_g):
        kwargs = dict(mean=True, wire_dtype=par.wire_dtype or None)
        if use_kernel:
            # contiguous merged buffers via the pack kernel require the
            # packed collective mode (fused variadic psum never packs)
            kwargs.update(mode="packed", use_kernel=True)
        if par.hierarchical and pod_axes:
            return comm.hierarchical_allreduce(
                rep_g, plan, intra_axis=zero_axis, inter_axis=pod_axes[0],
                **kwargs)
        if dp_axes:
            return comm.bucketed_allreduce(rep_g, plan, dp_axes, **kwargs)
        return rep_g

    def reduce_ep(ep_g):
        if ep_g is None:
            return None
        if pod_axes and ep_plan is not None:
            with jax.named_scope("ep"):
                return comm.bucketed_allreduce(ep_g, ep_plan, pod_axes,
                                               mean=True)
        return ep_g

    def finish(state, new_params, new_opt, metrics, loss, gnorm, lr):
        """The next state, and the step's metrics averaged over the data
        axes; in the ``optimizer`` scope, beside the update."""
        with jax.named_scope("optimizer"):
            metrics = dict(metrics, loss=loss, grad_norm=gnorm, lr=lr)
            if dp_axes:
                metrics = jax.tree.map(lambda m: jax.lax.pmean(m, dp_axes),
                                       metrics)
            return TrainState(state.step + 1, new_params, new_opt), metrics

    # ------------------------------------------------------------------

    def step_zero0(state: TrainState, batch):
        loss, metrics, grads = compute_grads(model_loss, state.params, batch)
        with jax.named_scope("grad_sync"):
            rep_g, ep_g = _split_groups(grads, ep_on)
            rep_g = reduce_replicated(rep_g)
            ep_g = reduce_ep(ep_g) if ep_on else None
            grads = _merge_groups(grads, rep_g, ep_g)
        with jax.named_scope("optimizer"):
            sq = oclip.global_norm(rep_g) ** 2
            if ep_on and zero_axis:
                sq = sq + jax.lax.psum(oclip.global_norm(ep_g) ** 2,
                                       zero_axis)
            gnorm = jnp.sqrt(sq)
            grads, _ = oclip.clip_by_global_norm(grads, run.grad_clip, gnorm)
            lr = lr_fn(state.step)
            new_params, new_opt = opt.update(grads, state.params,
                                             state.opt_state, state.step, lr)
        return finish(state, new_params, new_opt, metrics, loss, gnorm, lr)

    def step_zero1(state: TrainState, batch):
        loss, metrics, grads = compute_grads(model_loss, state.params, batch)
        with jax.named_scope("grad_sync"):
            rep_g, ep_g = _split_groups(grads, ep_on)
            if pod_axes:
                npod = _static_size(dims, pod_axes)
                rep_g = jax.tree.map(lambda g: g / npod,
                                     comm.safe_psum(rep_g, pod_axes))
            shards, bucket_metas = comm.bucketed_reduce_scatter(
                rep_g, plan, zero_axis, mean=True,
                wire_dtype=par.wire_dtype or None, use_kernel=use_kernel)
            ep_g = reduce_ep(ep_g) if ep_on else None
        with jax.named_scope("optimizer"):
            sq = sum(jnp.sum(jnp.square(s.astype(jnp.float32)))
                     for s in shards)
            sq = jax.lax.psum(sq, zero_axis)
            if ep_on:
                sq = sq + jax.lax.psum(oclip.global_norm(ep_g) ** 2,
                                       zero_axis)
            gnorm = jnp.sqrt(sq)
            scale = (jnp.minimum(1.0,
                                 run.grad_clip / jnp.maximum(gnorm, 1e-12))
                     if run.grad_clip > 0 else jnp.ones(()))
            lr = lr_fn(state.step)

        n = _axes_size((zero_axis,))
        rep_p, ep_p = _split_groups(state.params, ep_on)
        flatp, _ = jax.tree_util.tree_flatten_with_path(rep_p)
        by_path = {_keystr(p): v for p, v in flatp}
        new_shards, new_opt = [], []
        for k, (bmetas, gshard) in enumerate(zip(bucket_metas, shards)):
            # this shard's slice of bucket k's parameters, packed as its
            # gradients were
            with jax.named_scope("grad_sync"), bucketer.bucket_scope(k):
                pbuf = bucketer.pack([by_path[m.path] for m in bmetas],
                                     use_kernel=use_kernel)
                pad = comm.padded_elems(pbuf.shape[0], n) - pbuf.shape[0]
                if pad:
                    pbuf = jnp.pad(pbuf, (0, pad))
                pshard = comm.replicated_shard(pbuf, zero_axis)
            with jax.named_scope("optimizer"):
                mask = jnp.concatenate([jnp.full((n,), v, jnp.float32)
                                        for n, v in decay_runs[k]])
                if pad:
                    mask = jnp.pad(mask, (0, pad))
                mshard = comm.replicated_shard(mask, zero_axis)
                g = gshard.astype(jnp.float32) * scale
                new_p, new_s = opt.flat_update(g, pshard, state.opt_state[k],
                                               state.step, lr, mshard)
            new_shards.append(new_p)
            new_opt.append(new_s)
        with jax.named_scope("grad_sync"):
            new_rep = comm.bucketed_allgather(new_shards, bucket_metas, rep_p,
                                              zero_axis, use_kernel=use_kernel)
        new_ep = None
        if ep_on:
            with jax.named_scope("optimizer"):
                ep_gc = jax.tree.map(lambda g: g * scale, ep_g)
                new_ep, new_ep_opt = opt.update(ep_gc, ep_p,
                                                state.opt_state[-1],
                                                state.step, lr)
            new_opt.append(new_ep_opt)
        new_params = _merge_groups(state.params, new_rep, new_ep)
        return finish(state, new_params, new_opt, metrics, loss, gnorm, lr)

    # ------------------------------------------------------------------
    # ZeRO-3 / FSDP: params + optimizer fully sharded over `data`; the
    # forward all-gathers, autodiff reduce-scatters, optimizer is local.
    # ------------------------------------------------------------------

    def step_zero3(state: TrainState, batch):
        def loss_of_sharded(sharded_params, mb):
            # the gather's transpose, the gradients' reduce-scatter, is
            # named transpose(jvp(grad_sync))
            with jax.named_scope("grad_sync"):
                full = gather_fsdp(sharded_params, fsdp_dims, zero_axis)
            return model_loss(full, mb)

        loss, metrics, grads = compute_grads(loss_of_sharded, state.params,
                                             batch)

        # fsdp leaves arrive as per-shard sums over `data` (gather
        # transpose); non-fsdp leaves are local and need the plan's
        # bucketed reduction.  EP leaves are owned.
        def split3(tree):
            fs, rest = {}, {}
            flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
            f_leaves, r_leaves = [], []
            for p, v in flat:
                if _keystr(p) in fsdp_dims:
                    f_leaves.append(v)
                    r_leaves.append(None)
                else:
                    f_leaves.append(None)
                    r_leaves.append(v)
            return (jax.tree_util.tree_unflatten(treedef, f_leaves),
                    jax.tree_util.tree_unflatten(treedef, r_leaves))

        with jax.named_scope("grad_sync"):
            fsdp_g, rest_g = split3(grads)
            rep_g, ep_g = _split_groups(rest_g, ep_on)
            rep_g = reduce_replicated(rep_g)
            ep_g = reduce_ep(ep_g) if ep_on else None
            if pod_axes:
                npod = _static_size(dims, pod_axes)
                fsdp_g = jax.tree.map(lambda g: g / npod,
                                      comm.safe_psum(fsdp_g, pod_axes))
            fsdp_g = jax.tree.map(lambda g: g / _axes_size((zero_axis,)),
                                  fsdp_g)
            grads = _merge_groups(grads, _merge_groups(rest_g, rep_g, ep_g),
                                  fsdp_g)

        with jax.named_scope("optimizer"):
            sq = oclip.global_norm(rep_g) ** 2
            sq = sq + jax.lax.psum(oclip.global_norm(fsdp_g) ** 2, zero_axis)
            if ep_on:
                sq = sq + jax.lax.psum(oclip.global_norm(ep_g) ** 2,
                                       zero_axis)
            gnorm = jnp.sqrt(sq)
            grads, _ = oclip.clip_by_global_norm(grads, run.grad_clip, gnorm)
            lr = lr_fn(state.step)
            new_params, new_opt = opt.update(grads, state.params,
                                             state.opt_state, state.step, lr)
        return finish(state, new_params, new_opt, metrics, loss, gnorm, lr)

    if eff_zero == 3:
        body = step_zero3
    elif eff_zero == 1:
        body = step_zero1
    else:
        body = step_zero0

    # ------------------------------------------------------------------
    # Shardings + shard_map wiring.
    # ------------------------------------------------------------------

    def init_fn(key):
        return init_state(model, opt, run, plan, ep_on, zero_n, key,
                          eff_zero=eff_zero, aligned=use_kernel)

    state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    st_pspecs = state_pspecs(state_shape, pspecs, run, zero_axis, ep_on,
                             eff_zero=eff_zero)
    batch_pspec = P(dp_axes) if dp_axes else P()

    if dp_axes:
        manual_state = jax.tree.map(
            lambda s: shd.manual_only(s, manual), st_pspecs,
            is_leaf=lambda x: isinstance(x, P))
        step_fn = jax.shard_map(
            body, mesh=mesh,
            in_specs=(manual_state, batch_pspec),
            out_specs=(manual_state, P()),
            axis_names=set(manual), check_vma=False)
    else:
        step_fn = body

    art = StepArtifacts(plan=plan, ep_plan=ep_plan, specs=specs,
                        comm_model=cmodel, param_pspecs=pspecs,
                        state_pspecs=st_pspecs, batch_pspec=batch_pspec,
                        dp_axes=dp_axes, manual_axes=manual)
    return step_fn, init_fn, art


def _static_size(dims, axes) -> int:
    n = 1
    for a in axes:
        n *= dims.get(a, 1)
    return n


# ---------------------------------------------------------------------------
# Host-side observability: the measurement half of the sim->real loop.
# ---------------------------------------------------------------------------

def instrument_step(step_fn, art: StepArtifacts, *, job: str = "train",
                    t_f: float = 0.0, recorder=None, source: str = "train",
                    clock=None, hlo_text: str | None = None,
                    sync: bool = True, on_record=None):
    """Wrap a (jitted) step function with host-side flight recording.

    Timing happens strictly OUTSIDE the jitted region — wall clock before
    dispatch and after ``jax.block_until_ready`` — so nothing lands on the
    device hot path (no Python callbacks inside jit, acceptance criterion
    of the obs subsystem).  Each record holds the measured wall window
    (``start``, ``end``) in the simulator's record schema
    (:class:`repro.obs.recorder.IterationRecord`), and both export into
    one Chrome trace (``repro.obs.recorder.record_spans``).  The host sees neither when the
    backward pass ends nor when a bucket's collective runs, so a record
    carries no buckets and ``backward_end`` is its ``end``; per-bucket
    device time comes from a profile of the step, read through its
    ``bucket_<k>`` named scopes.  ``args["predicted_t_iter"]`` is the
    closed-form prediction (``core.simulator.simulate`` over the step's
    own plan, specs and comm model, with forward time ``t_f``).

    ``hlo_text`` (the compiled step's HLO, e.g. ``jax.jit(step).lower(...)
    .compile().as_text()``) attaches ``utils.hlo.analyze`` cost counters
    to the first record.  ``clock`` injects a time source (deterministic
    golden tests); ``sync=False`` skips the block-until-ready (callers
    that already synchronize, or tests without real devices).

    ``on_record`` receives each :class:`IterationRecord` after it is (op-
    tionally) recorded — the hook a :class:`repro.train.replan.ReplanController`
    uses to consume live measurements without owning the recorder.
    """
    import time

    from repro.core.simulator import simulate
    from repro.obs.metrics import REGISTRY
    from repro.obs.recorder import IterationRecord, plan_fingerprint

    predicted = simulate(art.specs, art.plan, art.comm_model, t_f).t_iter
    fingerprint = plan_fingerprint(art.plan)
    hlo_cost = None
    if hlo_text is not None:
        from repro.utils import hlo as hlo_mod
        hlo_cost = hlo_mod.analyze(hlo_text).as_dict()
    now = clock if clock is not None else time.perf_counter
    hist = REGISTRY.histogram("train_step_seconds",
                              "real train-step wall time")
    step_idx = 0

    def wrapped(state, batch):
        nonlocal step_idx
        t0 = now()
        out = step_fn(state, batch)
        if sync:
            out = jax.block_until_ready(out)
        t1 = now()
        hist.observe(t1 - t0, job=job)
        if recorder is not None or on_record is not None:
            args = {"plan": fingerprint, "predicted_t_iter": predicted}
            if step_idx == 0 and hlo_cost is not None:
                args["hlo_cost"] = hlo_cost
            rec = IterationRecord(
                source=source, job=job, iteration=step_idx,
                start=t0, end=t1, backward_end=t1, args=args)
            if recorder is not None:
                recorder.record(rec)
            if on_record is not None:
                on_record(rec)
        step_idx += 1
        return out

    return wrapped
