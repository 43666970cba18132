"""One training cell: build the program's step, set it up from the seed,
drive the measured window, and check the first steps against the plain
reference.

The entry the window drives is the program's own: the model from
``repro.models.registry`` / ``LM`` cut by the configuration file, the mesh
from ``repro.launch.mesh.make_mesh_for``, the configuration's parallel
settings, and ``jax.jit(step, donate_argnums=0)`` of the step that
``repro.train.step.build_train_step`` returns.  Set-up builds that one
compiled step and its state, and drives it through the first
``checked_steps`` steps with the window's own call and feed; the window
then goes on with the same object.

Host spans (``data``: take the next batch and put it on the device;
``dispatch``; ``readback``: wait for the step's loss) are kept on the host
clock and written into the profiler's trace as well.
"""

from __future__ import annotations

import dataclasses
import glob
import math
import shutil
import sys
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.chip import check, feed, flops, manifest, trace, weights

SPANS = ("window", "data", "dispatch", "readback")


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


@dataclasses.dataclass
class Built:
    cell: manifest.Cell
    cfg: object            # the program's ModelConfig
    run: object            # the program's RunConfig
    mesh: object
    step_fn: object
    init_fn: object
    art: object
    state_sh: object
    batch_sh: object
    shapes: object         # the parameter tree's ShapeDtypeStructs
    opt_shapes: list       # the optimizer state's, one item per group
    ents: list             # weights.entries of the parameter tree
    rows: int              # sequences in the global batch

    @property
    def tokens_per_step(self) -> int:
        return self.rows * self.cell.traffic["seq_len"]


def with_settings(obj, settings: dict):
    """The dataclass ``obj`` with the fields that ``settings`` names
    replaced.  An object under a field that holds a dataclass (``moe``,
    ``mamba``) replaces that dataclass's own fields, so that a file can set
    ``{"moe": {"num_experts": 8}}`` and keep the rest of the group."""
    changes = {}
    for key, value in settings.items():
        if isinstance(value, dict):
            group = getattr(obj, key)
            if not dataclasses.is_dataclass(group):
                raise ValueError(f"{key}: {type(obj).__name__} holds no "
                                 f"group of settings there to change")
            value = with_settings(group, value)
        changes[key] = value
    return dataclasses.replace(obj, **changes)


def build(cell: manifest.Cell, devices) -> Built:
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import ShapeConfig
    from repro.launch.mesh import make_mesh_for
    from repro.models import registry
    from repro.models.transformer import LM
    from repro.train import step as train_step

    conf, traffic = cell.config, cell.traffic
    opt = traffic["optimizer"]
    bundle = registry.get_arch(conf["arch"])
    cfg = with_settings(bundle.cfg, conf["model"])
    par = with_settings(bundle.parallel, conf["parallel"])
    rows = len(devices) * traffic["batch_per_chip"]
    shape = ShapeConfig(cell.traffic_name, "train", traffic["seq_len"], rows)
    run = dataclasses.replace(
        bundle.run_config("train_4k", par), model=cfg, shape=shape,
        microbatch=traffic["microbatch"], optimizer=opt["name"],
        learning_rate=opt["learning_rate"], warmup_steps=opt["warmup_steps"],
        total_steps=opt["total_steps"], weight_decay=opt["weight_decay"],
        adam_b1=opt["b1"], adam_b2=opt["b2"], adam_eps=opt["eps"],
        grad_clip=opt["grad_clip"])
    mesh = make_mesh_for(len(devices), par.tp_enabled)
    model = LM(cfg, par)
    step_fn, init_fn, art = train_step.build_train_step(model, run, mesh)
    is_p = lambda x: isinstance(x, P)
    state_sh = jax.tree.map(lambda s: NamedSharding(mesh, s),
                            art.state_pspecs, is_leaf=is_p)
    state = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    return Built(cell, cfg, run, mesh, step_fn, init_fn, art, state_sh,
                 NamedSharding(mesh, art.batch_pspec), state.params,
                 state.opt_state,
                 weights.entries(state.params, model.stage_layout()), rows)


def describe(b: Built) -> None:
    c, p = b.cfg, b.run.parallel
    log(f"cell {b.cell.name}: {c.name} layers={c.num_layers} "
        f"d_model={c.d_model} heads={c.num_heads}/{c.num_kv_heads}x"
        f"{c.resolved_head_dim} d_ff={c.d_ff} vocab={c.vocab_size} "
        f"tied={c.tie_embeddings} qkv_bias={c.qkv_bias} dtype={c.dtype}"
        + (f" moe={c.moe}" if c.moe else ""))
    log(f"job: {b.rows} x {b.cell.traffic['seq_len']} tokens, microbatch "
        f"{b.run.microbatch}, mesh {dict(b.mesh.shape)}, zero={p.zero} "
        f"strategy={p.comm_strategy} pack_kernel={p.pack_kernel}")
    log(f"plan: {b.art.plan.num_buckets} buckets over "
        f"{b.art.plan.num_tensors} tensors")


# ---------------------------------------------------------------------------
# Set-up.
# ---------------------------------------------------------------------------

def make_state(b: Built, seed: int):
    """The program's initial state, with the benchmark's weights from the
    seed, made in one jitted call on the device."""
    def init(key):
        state = b.init_fn(key)
        return dataclasses.replace(state,
                                   params=weights.make_params(b.shapes, key))
    with jax.set_mesh(b.mesh):
        return jax.jit(init, out_shardings=b.state_sh)(weights.key_of(seed, 0))


def make_params(b: Built, seed: int, sharding):
    """The same weights alone, placed as ``sharding`` says."""
    make = jax.jit(lambda k: weights.make_params(b.shapes, k),
                   out_shardings=sharding)
    return make(weights.key_of(seed, 0))


def compile_step(b: Built, state, batch):
    with jax.set_mesh(b.mesh):
        compiled = jax.jit(b.step_fn, donate_argnums=0).lower(
            state, batch).compile()
    ma = compiled.memory_analysis()
    if ma is not None:
        log(f"compiler peak {ma.peak_memory_in_bytes / 1e9:.3f} GB "
            f"(arguments {ma.argument_size_in_bytes / 1e9:.3f}, "
            f"temporaries {ma.temp_size_in_bytes / 1e9:.3f})")
    return compiled


def first_grad_fn(b: Built):
    """Per entry, the squared norm of the first gradient as the optimizer
    got it, worked out from its state after one step: AdamW's first moment
    is then (1 - b1) g.  Under ZeRO-1 the moments of each bucket of the
    merge plan lie back to back in one flat buffer, leaves in plan order.
    Under expert parallelism the expert leaves are in no bucket: their
    group's state comes last, a tree shaped like the parameters with
    ``{"m", "v"}`` at each expert leaf."""
    if b.run.parallel.zero != 1:
        raise ValueError("the first-gradient reading is written for ZeRO-1")
    flat = {jax.tree_util.keystr(p): s for p, s in
            jax.tree_util.tree_flatten_with_path(b.shapes)[0]}
    where = {}
    for k, bucket in enumerate(b.art.plan.buckets):
        off = 0
        for i in bucket:
            name = b.art.specs[i].name
            size = math.prod(flat[name].shape)
            where[name] = (k, off, size)
            off += size
    has_experts = len(b.opt_shapes) > len(b.art.plan.buckets)
    experts = set(_first_moments(b.opt_shapes[-1])) if has_experts else set()
    if where.keys() & experts or where.keys() | experts != flat.keys():
        raise ValueError("the merge plan and the expert group do not cover "
                         "every parameter once")
    b1 = b.cell.traffic["optimizer"]["b1"]

    def sq(opt_state):
        ep_m = _first_moments(opt_state[-1]) if experts else {}
        out = []
        for _, name, rep in b.ents:
            if name in ep_m:
                seg = ep_m[name] if rep is None else ep_m[name][rep]
            else:
                k, off, size = where[name]
                if rep is not None:
                    per = size // flat[name].shape[0]
                    off, size = off + rep * per, per
                seg = opt_state[k]["m"][off:off + size]
            out.append(jnp.sum(jnp.square(seg.astype(jnp.float32))))
        return jnp.stack(out) / (1 - b1) ** 2

    return jax.jit(sq)


def _first_moments(group_state) -> dict:
    """{parameter path: its first moment} of a state tree that holds
    ``{"m", "v"}`` at each leaf of the parameters it covers."""
    out = {}
    for path, x in jax.tree_util.tree_flatten_with_path(group_state)[0]:
        if getattr(path[-1], "key", None) == "m":
            out[jax.tree_util.keystr(path[:-1])] = x
    return out


class Stepper:
    """The window's own call and feed: take the next batch, put it on the
    device, dispatch the compiled step, read its loss back."""

    def __init__(self, compiled, batches: feed.Feed, batch_sh):
        self.compiled, self.feed, self.batch_sh = compiled, batches, batch_sh
        self.spans: list = []     # (name, start, end) on the host clock

    def __call__(self, state):
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("data"):
            batch = jax.device_put(self.feed.next(), self.batch_sh)
        t1 = time.perf_counter()
        with jax.profiler.TraceAnnotation("dispatch"):
            state, metrics = self.compiled(state, batch)
        t2 = time.perf_counter()
        with jax.profiler.TraceAnnotation("readback"):
            loss = float(metrics["loss"])
            gnorm = float(metrics["grad_norm"])
        t3 = time.perf_counter()
        self.spans += [("data", t0, t1), ("dispatch", t1, t2),
                       ("readback", t2, t3)]
        return state, loss, gnorm


def checked_steps(b: Built, stepper: Stepper, state, seed: int,
                  grad_sq) -> tuple:
    """The first steps, through the window's own call and feed, with the
    readings the check compares."""
    losses, ok = [], True
    for i in range(b.cell.traffic["checked_steps"]):
        state, loss, gnorm = stepper(state)
        losses.append(loss)
        ok &= math.isfinite(loss) and math.isfinite(gnorm)
        log(f"step {i}: loss={loss!r} grad_norm={gnorm!r}")
        if i == 0:
            g = np.asarray(grad_sq(state.opt_state))
    # the initial weights are made again inside the program that compares
    # them, so that no copy of them outlives it
    change = jax.jit(lambda p, key: weights.diff_sq_norms(
        p, weights.make_params(b.shapes, key), b.ents))
    u = np.asarray(change(state.params, weights.key_of(seed, 0)))
    return state, {"loss": losses, "grad_sq": g, "update_sq": u}, ok


# ---------------------------------------------------------------------------
# The window.
# ---------------------------------------------------------------------------

def window(stepper: Stepper, state, seconds: float) -> tuple:
    """Steps one after another until ``seconds`` have passed since the
    first dispatch.  Returns (state, attempted, failed, elapsed, step
    times)."""
    n = failed = 0
    times, t_start = [], None
    while t_start is None or time.perf_counter() - t_start < seconds:
        mark = len(stepper.spans)
        try:
            state, loss, gnorm = stepper(state)
        except Exception as e:          # a step that raises has failed
            log(f"window step {n} raised: {e!r}")
            return state, n + 1, failed + 1, None, times
        n += 1
        failed += not (math.isfinite(loss) and math.isfinite(gnorm))
        _, d0, _ = stepper.spans[mark + 1]
        _, _, r1 = stepper.spans[mark + 2]
        t_start = d0 if t_start is None else t_start
        times.append(r1 - d0)
    return state, n, failed, stepper.spans[-1][2] - t_start, times


def traced_window(stepper: Stepper, state, steps: int, out_dir: str):
    """``steps`` steps under the profiler, which keeps the device's ops and
    the host's annotations only (no Python tracing, no HLO protos).
    Returns (state, attempted, failed, elapsed, trace)."""
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    jax.profiler.start_trace(out_dir, profiler_options=opts)
    failed = 0
    try:
        with jax.profiler.TraceAnnotation("window"):
            for _ in range(steps):
                state, loss, gnorm = stepper(state)
                failed += not (math.isfinite(loss) and math.isfinite(gnorm))
    finally:
        jax.profiler.stop_trace()
    first = len(stepper.spans) - 3 * steps
    elapsed = stepper.spans[-1][2] - stepper.spans[first + 1][1]
    path = sorted(glob.glob(f"{out_dir}/**/*.xplane.pb", recursive=True))[-1]
    return state, steps, failed, elapsed, trace.load(path, SPANS)


def peak_bytes(devices) -> int:
    return max(int((d.memory_stats() or {}).get("peak_bytes_in_use", 0))
               for d in devices)


# ---------------------------------------------------------------------------
# The reference.
# ---------------------------------------------------------------------------

def reference_readings(b: Built, seed: int, control: bool = False) -> dict:
    """The reference's readings for the first steps of ``seed``, on the
    first device; with ``control``, those of the reference one precision
    below the configuration's (the check's control)."""
    cell = b.cell
    ref = manifest.reference(cell.config)
    model, kw = cell.config["model"], {}
    if control:
        model, kw["dot"] = ref.control(model)
    dev = jax.devices()[0]
    tr = cell.traffic
    batches = [jax.device_put(feed.batch_at(tr, b.cfg.vocab_size, seed, s,
                                            b.rows), dev)
               for s in range(tr["checked_steps"])]
    ents = tuple(b.ents)
    sq = jax.jit(weights.entry_sq_norms, static_argnums=1)
    out = {}

    def on_first_grad(g, scale):
        out["grad_sq"] = np.asarray(sq(g, ents)) * float(scale) ** 2

    one = jax.sharding.SingleDeviceSharding(dev)
    losses, p = ref.train(model, tr["optimizer"],
                          make_params(b, seed, one), batches,
                          on_first_grad=on_first_grad, **kw)
    p0 = make_params(b, seed, one)
    out["update_sq"] = np.asarray(jax.jit(
        weights.diff_sq_norms, static_argnums=2)(p, p0, ents))
    out["loss"] = losses
    return out


def compare(b: Built, prog: dict, ref: dict, limits: dict | None):
    names = [e[0] for e in b.ents]
    values, where = check.readings(prog, ref, names)
    for k in check.NUMBERS:
        log(f"{k} = {values[k]!r} at {where[k]}"
            + (f" (limit {limits[k]!r})" if limits else ""))
    return values


# ---------------------------------------------------------------------------
# One run.
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Context:
    """What a per-layer metric's reader gets from a traced run."""
    cell: manifest.Cell
    trace: trace.Trace
    lo: float              # the traced window on the trace's clock (ns)
    hi: float
    spans: list            # the traced steps' host spans (name, start, end)
    steps: int
    seconds: float         # the traced steps' host-clock length
    tokens_per_step: int
    flops_per_token: float
    chips: int
    peak: dict             # peaks.json entry of the device


def device_info(devices) -> dict:
    d = devices[0]
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devices)}


def peaks_of(kind: str) -> dict:
    import json
    with open(manifest.HERE / "peaks.json") as f:
        table = json.load(f)
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


def flops_per_token(b: Built) -> int | float:
    """Model FLOPs per training token of the configuration as the program
    resolved it, the router spanning the published experts."""
    return flops.train_flops_per_token(dataclasses.asdict(b.cfg),
                                       b.cell.traffic["seq_len"],
                                       b.cell.config.get("published", {}))


def per_layer(b: Built, tz: trace.Trace, spans, steps: int, elapsed: float,
              peak: dict, chips: int):
    """(per-layer metrics, device busy/window seconds, breakdown) of a
    traced window."""
    w = tz.span("window")
    lo, hi = w.start, w.end
    ctx = Context(b.cell, tz, lo, hi, spans, steps, elapsed,
                  b.tokens_per_step, flops_per_token(b), chips, peak)
    metrics = {}
    for m in b.cell.per_layer:
        v = manifest.metric_reader(m["name"])(ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    if not tz.ops:
        raise ValueError("the trace holds no device operations")
    devs = sorted(tz.ops)
    busy = sum(trace.busy(tz.ops[d], lo, hi) for d in devs) / len(devs)
    tot = {}
    for d in devs:
        for name, ns in trace.top_ops(tz.ops[d], lo, hi, top=None):
            tot[name] = tot.get(name, 0.0) + ns
    top = sorted(tot.items(), key=lambda kv: -kv[1])[:10]
    breakdown = {
        "device_ops": [[k, v / 1e9 / len(devs)] for k, v in top],
        "idle_gaps": [[k, v / 1e9] for k, v in
                      trace.idle_gaps(tz.ops[devs[0]], tz.spans, lo, hi)]}
    return metrics, {"busy_s": busy / 1e9, "window_s": (hi - lo) / 1e9}, \
        breakdown


def run_cell(cell: manifest.Cell, seed: int, seconds: float, traced: bool,
             devices, t_process: float) -> dict:
    """One run of ``cell``; returns the result line's object."""
    if cell.limits is None:
        raise ValueError(f"no limits for {cell.name}")
    peak = peaks_of(devices[0].device_kind) if traced else None
    b = build(cell, devices)
    describe(b)
    tr = cell.traffic
    batches = feed.Feed(tr, b.cfg.vocab_size, seed, b.rows)
    out_dir = tempfile.mkdtemp(prefix="bench_trace_") if traced else None
    try:
        state = make_state(b, seed)
        t = time.perf_counter()
        first = jax.device_put(feed.batch_at(tr, b.cfg.vocab_size, seed, 0,
                                             b.rows), b.batch_sh)
        compiled = compile_step(b, state, first)
        del first
        log(f"compile_s={time.perf_counter() - t:.3f}")
        stepper = Stepper(compiled, batches, b.batch_sh)
        state, prog, sound = checked_steps(b, stepper, state, seed,
                                           first_grad_fn(b))
        setup_s = time.perf_counter() - t_process
        log(f"setup_s={setup_s!r}")
        mark = len(stepper.spans)
        if traced:
            state, n, failed, elapsed, tz = traced_window(
                stepper, state, tr["trace_steps"], out_dir)
        else:
            state, n, failed, elapsed, times = window(stepper, state, seconds)
            if times:
                q = np.percentile(times, [0, 25, 50, 75, 100])
                log(f"window: {n} steps, step_s min/q1/median/q3/max "
                    f"{' '.join(f'{x:.6f}' for x in q)}")
        mem = peak_bytes(devices)
        spans = stepper.spans[mark:]
        del state, compiled, stepper
    finally:
        batches.close()
    ref = reference_readings(b, seed)
    values = compare(b, prog, ref, cell.limits)
    ok, checks = check.judge(values, cell.limits)
    result = {"correct": ok and sound and failed == 0 and elapsed is not None,
              "attempted": n, "failed": failed}
    dev = device_info(devices) | {"memory_peak_bytes": mem}
    if traced:
        try:
            metrics, dev_extra, breakdown = per_layer(b, tz, spans, n, elapsed,
                                                      peak, len(devices))
        finally:
            shutil.rmtree(out_dir, ignore_errors=True)
        result |= {"metrics": metrics, "device": dev | dev_extra,
                   "breakdown": breakdown}
    else:
        e2e = {"tokens_per_s": n * b.tokens_per_step / elapsed
               if elapsed else 0.0, "setup_s": setup_s}
        result |= {"metrics": {m["name"]: {"value": e2e[m["name"]],
                                           "unit": m["unit"]}
                               for m in cell.end_to_end},
                   "device": dev}
    for k, c in checks.items():       # the last lines on standard error
        log(f"check {k}: {c['value']!r} limit {c['limit']!r}")
    result["checks"] = checks
    return result
