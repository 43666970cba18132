"""Training launcher: end-to-end driver over the real substrate.

Runs on every device JAX finds.  A reduced config for a quick run::

    PYTHONPATH=src python -m repro.launch.train --arch qwen2-1.5b \
        --reduced --steps 50

Production launch is the same entry point with ``--shape train_4k`` and no
``--reduced`` on a real slice (the dry-run proves those configs compile;
see launch/dryrun.py).  ``chip_smoke.py`` at the repository root drives
the same path at full width on one or four TPU chips.
"""

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--shape", default="train_4k")
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-scale)")
    ap.add_argument("--global-batch", type=int, default=0)
    ap.add_argument("--seq-len", type=int, default=0)
    ap.add_argument("--strategy", default=None)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--log-every", type=int, default=10)
    args = ap.parse_args()

    import dataclasses
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs.base import SHAPES, ShapeConfig
    from repro.data.pipeline import DataPipeline
    from repro.launch.compile_cache import enable_compile_cache
    from repro.launch.mesh import make_mesh_for
    from repro.models import registry
    from repro.train import checkpoint, fault
    from repro.train.step import build_train_step

    enable_compile_cache()
    bundle = registry.reduced_arch(args.arch) if args.reduced \
        else registry.get_arch(args.arch)
    shape = SHAPES[args.shape]
    if args.global_batch or args.seq_len:
        shape = ShapeConfig(shape.name, shape.kind,
                            args.seq_len or shape.seq_len,
                            args.global_batch or shape.global_batch)
    n_dev = len(jax.devices())
    mesh = make_mesh_for(n_dev, bundle.parallel.tp_enabled)
    dims = dict(zip(mesh.axis_names, mesh.devices.shape))
    par = dataclasses.replace(bundle.parallel, dp_axes=("data",),
                              attn_chunk=min(bundle.parallel.attn_chunk,
                                             shape.seq_len))
    run = dataclasses.replace(bundle.run_config(args.shape, par),
                              shape=shape)
    model = bundle.model(par)

    step_fn, init_fn, art = build_train_step(model, run, mesh,
                                             strategy=args.strategy)
    with jax.set_mesh(mesh):
        print(f"arch={bundle.cfg.name} devices={n_dev} mesh={dims} "
              f"plan={art.plan.strategy}:{art.plan.num_buckets} buckets "
              f"over {art.plan.num_tensors} tensors")
        shardings = jax.tree.map(lambda s: NamedSharding(mesh, s),
                                 art.state_pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
        state = jax.jit(init_fn, out_shardings=shardings)(
            jax.random.PRNGKey(run.seed))
        bsh = NamedSharding(mesh, art.batch_pspec)
        jstep = jax.jit(step_fn, donate_argnums=0)

        ckpt = checkpoint.AsyncCheckpointer(args.ckpt_dir)
        start = 0
        if args.resume:
            latest = checkpoint.latest_step(args.ckpt_dir)
            if latest is not None:
                state, start, _ = checkpoint.restore(args.ckpt_dir, state)
                print(f"resumed from step {start}")

        pipe = DataPipeline(bundle.cfg, shape, seed=run.seed)

        def wrapped_step(state, batch):
            batch = jax.tree.map(lambda x: jax.device_put(x, bsh), batch)
            return jstep(state, batch)

        def on_metrics(step, metrics, dt):
            if step % args.log_every == 0:
                print(f"step {step:5d} loss={float(metrics['loss']):.4f} "
                      f"gnorm={float(metrics['grad_norm']):.3f} "
                      f"{dt*1e3:.0f}ms", flush=True)

        state, final = fault.run_with_recovery(
            wrapped_step, state, pipe, ckpt, start, args.steps,
            ckpt_every=args.ckpt_every, on_metrics=on_metrics)
        print(f"done at step {final}; checkpoints in {args.ckpt_dir}")


if __name__ == "__main__":
    main()
