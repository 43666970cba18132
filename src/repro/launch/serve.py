"""Serving launcher: batched generation with a KV cache.

CPU-runnable example::

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2-1.5b \
        --reduced --requests 4 --max-new 16
"""

import argparse


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    import time
    import jax
    import jax.numpy as jnp
    from repro.launch.compile_cache import enable_compile_cache
    from repro.models import registry
    from repro.serve.engine import ServeEngine

    enable_compile_cache()
    bundle = registry.reduced_arch(args.arch) if args.reduced \
        else registry.get_arch(args.arch)
    model = bundle.model()
    params = model.init(jax.random.PRNGKey(0))
    engine = ServeEngine(model, params,
                         max_len=args.prompt_len + args.max_new + 8,
                         temperature=args.temperature)

    key = jax.random.PRNGKey(7)
    prompts = [jax.random.randint(jax.random.fold_in(key, i),
                                  (args.prompt_len,), 0,
                                  bundle.cfg.vocab_size)
               for i in range(args.requests)]
    extra = {}
    if bundle.cfg.enc_dec:
        extra["enc_embeds"] = jnp.zeros(
            (args.requests, 32, bundle.cfg.d_model), jnp.bfloat16)
    t0 = time.perf_counter()
    outs = engine.generate(prompts, max_new_tokens=args.max_new,
                           extra_batch=extra)
    dt = time.perf_counter() - t0
    total = sum(len(o) for o in outs)
    print(f"arch={bundle.cfg.name}: generated {total} tokens for "
          f"{args.requests} requests in {dt:.2f}s "
          f"({total/dt:.1f} tok/s incl. prefill+compile)")
    for i, o in enumerate(outs[:2]):
        print(f"  req{i}: {o[:12]}...")


if __name__ == "__main__":
    main()
