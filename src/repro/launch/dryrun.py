import os
# 512 placeholder devices for the multi-pod mesh; single-pod cells may set
# DRYRUN_DEVICES=256 to halve compiler host memory (35 GB container limit).
_N_DEV = os.environ.get("DRYRUN_DEVICES", "512")
os.environ["XLA_FLAGS"] = (os.environ.get("DRYRUN_XLA_EXTRA", "") +
                           f" --xla_force_host_platform_device_count={_N_DEV}"
                           ).strip()

"""Multi-pod dry-run: prove every (arch × shape × mesh) cell lowers,
compiles, and fits — no real hardware, 512 placeholder CPU devices.

For each cell this script:
  1. builds the production mesh (single-pod (16,16) or multi-pod
     (2,16,16)),
  2. builds the real step function — ``train_step`` for train shapes,
     ``serve`` prefill/decode for inference shapes — with the arch's
     production parallelism config (ZeRO, EP, MG-WFBP plan),
  3. ``jit(...).lower(**input_specs).compile()`` against ShapeDtypeStruct
     stand-ins (no allocation),
  4. records ``memory_analysis()`` (fits-on-chip proof),
     ``cost_analysis()`` (XLA's once-per-scan-body costs) and the
     trip-count-corrected HLO costs + collective bytes (utils/hlo.py),
     plus the MG-WFBP plan actually baked into the step,
  5. writes one JSON artifact per cell to ``artifacts/dryrun/``.

Usage::

    PYTHONPATH=src python -m repro.launch.dryrun --arch qwen2-1.5b \
        --shape train_4k --mesh both
    PYTHONPATH=src python -m repro.launch.dryrun --all
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import SHAPES
from repro.launch.mesh import make_production_mesh
from repro.models import registry, sharding as shd
from repro.models.transformer import LM
from repro.serve.engine import build_serve_step
from repro.train import step as step_mod
from repro.utils import flops as uflops, hlo as uhlo

ARTIFACT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..",
                            "artifacts", "dryrun")


def _sds(tree, spec_tree, mesh):
    """ShapeDtypeStructs with NamedShardings attached."""
    def one(leaf, spec):
        return jax.ShapeDtypeStruct(leaf.shape, leaf.dtype,
                                    sharding=NamedSharding(mesh, spec))
    return jax.tree.map(one, tree, spec_tree,
                        is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


def _mem_dict(compiled):
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    out = {}
    for k in ("argument_size_in_bytes", "output_size_in_bytes",
              "temp_size_in_bytes", "alias_size_in_bytes",
              "generated_code_size_in_bytes"):
        v = getattr(ma, k, None)
        if v is not None:
            out[k] = int(v)
    if out:
        out["total_hbm_bytes"] = (out.get("argument_size_in_bytes", 0)
                                  + out.get("temp_size_in_bytes", 0)
                                  + out.get("output_size_in_bytes", 0)
                                  - out.get("alias_size_in_bytes", 0))
    return out


def run_cell(arch: str, shape_name: str, mesh_name: str,
             strategy: str | None = None, verbose: bool = True,
             par_overrides: dict | None = None,
             run_overrides: dict | None = None, tag: str = "") -> dict:
    """Lower + compile one cell; returns the artifact dict.

    ``par_overrides`` / ``run_overrides``: perf-loop knobs (remat policy,
    wire dtype, microbatch, ...) applied on top of the arch defaults."""
    bundle = registry.get_arch(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=(mesh_name == "multi"))
    dims = dict(zip(mesh.axis_names, mesh.devices.shape))
    par = bundle.parallel
    dp = (("pod",) if mesh_name == "multi" else ()) + ("data",) + \
        (() if par.tp_enabled else ("model",))
    # the global batch must divide the DP extent; if folding the idle
    # model axis into DP over-shards (e.g. batch 256 on the 512-chip
    # multi-pod mesh), leave the model axis out (replicated compute).
    dp_total = 1
    for a in dp:
        dp_total *= dims.get(a, 1)
    if shape.kind == "train" and shape.global_batch % dp_total and \
            "model" in dp:
        dp = tuple(a for a in dp if a != "model")
    par = dataclasses.replace(par, dp_axes=dp, **(par_overrides or {}))
    model = LM(bundle.cfg, par)
    run = bundle.run_config(shape_name, par)
    if run_overrides:
        run = dataclasses.replace(run, **run_overrides)
    kind = shape.kind
    rec = {"arch": arch, "shape": shape_name, "mesh": mesh_name,
           "kind": kind, "devices": int(mesh.devices.size),
           "strategy": strategy or par.comm_strategy, "ok": False,
           "tag": tag, "par_overrides": par_overrides or {},
           "run_overrides": run_overrides or {}}
    t0 = time.time()
    try:
        with jax.set_mesh(mesh):
            if kind == "train":
                step_fn, init_fn, art = step_mod.build_train_step(
                    model, run, mesh, strategy=strategy)
                state_shape = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
                state_in = _sds(state_shape, art.state_pspecs, mesh)
                batch_shape = registry.train_input_specs(bundle.cfg, shape)
                batch_in = jax.tree.map(
                    lambda l: jax.ShapeDtypeStruct(
                        l.shape, l.dtype,
                        sharding=NamedSharding(mesh, art.batch_pspec)),
                    batch_shape)
                rec["plan"] = {
                    "strategy": art.plan.strategy,
                    "num_buckets": art.plan.num_buckets,
                    "num_tensors": art.plan.num_tensors,
                    "bucket_bytes": art.plan.bucket_bytes(art.specs),
                }
                lowered = jax.jit(step_fn).lower(state_in, batch_in)
            else:
                decode_fn, prefill_fn, sh = build_serve_step(model, shape,
                                                             mesh)
                params_shape = jax.eval_shape(
                    lambda: model.init(jax.random.PRNGKey(0)))
                params_in = jax.tree.map(
                    lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                      sharding=s),
                    params_shape, sh["params"])
                if kind == "prefill":
                    batch_shape = registry.train_input_specs(bundle.cfg,
                                                             shape)
                    batch_shape.pop("labels")
                    batch_in = jax.tree.map(
                        lambda l: jax.ShapeDtypeStruct(
                            l.shape, l.dtype, sharding=sh["tokens"]
                            if l.shape[0] == shape.global_batch and
                            len(l.shape) == 2 else NamedSharding(mesh, P())),
                        batch_shape)
                    lowered = jax.jit(prefill_fn).lower(params_in, batch_in)
                else:  # decode
                    enc_len = shape.seq_len if bundle.cfg.enc_dec else 0
                    cache_shape = jax.eval_shape(
                        lambda: model.init_cache(shape.global_batch,
                                                 shape.seq_len, enc_len))
                    cache_in = jax.tree.map(
                        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype,
                                                          sharding=s),
                        cache_shape, sh["cache"])
                    tok_in = jax.ShapeDtypeStruct(
                        (shape.global_batch, 1), jnp.int32,
                        sharding=sh["tokens"])
                    pos_in = jax.ShapeDtypeStruct((), jnp.int32)
                    lowered = jax.jit(decode_fn).lower(params_in, cache_in,
                                                       tok_in, pos_in)
            rec["lower_s"] = round(time.time() - t0, 2)
            t1 = time.time()
            compiled = lowered.compile()
            rec["compile_s"] = round(time.time() - t1, 2)
            rec["memory"] = _mem_dict(compiled)
            ca = compiled.cost_analysis() or {}
            rec["xla_cost"] = {"flops": float(ca.get("flops", 0.0)),
                               "bytes": float(ca.get("bytes accessed", 0.0))}
            txt = compiled.as_text()
            h = uhlo.analyze(txt)
            rec["hlo"] = h.as_dict()
            params_shape = jax.eval_shape(
                lambda: model.init(jax.random.PRNGKey(0)))
            rec["model_flops"] = uflops.model_flops(bundle.cfg, params_shape,
                                                    shape, kind)
            rec["ok"] = True
            if verbose:
                mem = rec["memory"].get("total_hbm_bytes", 0)
                print(f"  [OK] {arch} × {shape_name} × {mesh_name}: "
                      f"lower {rec['lower_s']}s compile {rec['compile_s']}s "
                      f"hlo_flops={rec['hlo']['flops']:.3e} "
                      f"coll_bytes={rec['hlo']['collective_bytes']:.3e} "
                      f"mem={mem/1e9:.2f}GB(prog)", flush=True)
    except Exception as e:  # noqa: BLE001
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-2000:]
        if verbose:
            print(f"  [FAIL] {arch} × {shape_name} × {mesh_name}: "
                  f"{rec['error'][:200]}", flush=True)
    return rec


def save_artifact(rec: dict, out_dir: str = ARTIFACT_DIR):
    os.makedirs(out_dir, exist_ok=True)
    suffix = f"__{rec['strategy']}" if rec.get("strategy") not in (
        None, "mgwfbp") else ""
    if rec.get("tag"):
        suffix += f"__{rec['tag']}"
    name = f"{rec['arch']}__{rec['shape']}__{rec['mesh']}{suffix}.json"
    with open(os.path.join(out_dir, name), "w") as f:
        json.dump(rec, f, indent=1)
    return name


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="")
    ap.add_argument("--shape", default="")
    ap.add_argument("--mesh", default="both", choices=["single", "multi",
                                                       "both"])
    ap.add_argument("--strategy", default=None,
                    help="override comm strategy (wfbp|single|mgwfbp|...)")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--skip-existing", action="store_true")
    ap.add_argument("--out", default=ARTIFACT_DIR)
    args = ap.parse_args()

    archs = registry.list_archs() if (args.all or not args.arch) \
        else [args.arch]
    meshes = ["single", "multi"] if args.mesh == "both" else [args.mesh]
    n_ok = n_fail = n_skip = 0
    for arch in archs:
        bundle = registry.get_arch(arch)
        shapes = [args.shape] if args.shape else list(SHAPES)
        for shape_name in shapes:
            if shape_name in bundle.skip_shapes:
                print(f"  [SKIP] {arch} × {shape_name}: "
                      f"{bundle.skip_shapes[shape_name]}", flush=True)
                n_skip += 1
                continue
            for mesh_name in meshes:
                suffix = f"__{args.strategy}" if args.strategy not in (
                    None, "mgwfbp") else ""
                fname = os.path.join(
                    args.out,
                    f"{arch}__{shape_name}__{mesh_name}{suffix}.json")
                if args.skip_existing and os.path.exists(fname):
                    try:
                        if json.load(open(fname)).get("ok"):
                            n_skip += 1
                            continue
                    except Exception:
                        pass
                rec = run_cell(arch, shape_name, mesh_name, args.strategy)
                save_artifact(rec, args.out)
                n_ok += rec["ok"]
                n_fail += not rec["ok"]
    print(f"dry-run done: {n_ok} ok, {n_fail} failed, {n_skip} skipped",
          flush=True)
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
