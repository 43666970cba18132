#!/usr/bin/env python3
"""Record the small trace that ``test_bench_trace.py`` reads (on a TPU).

    python3 tests/bench/record_trace.py <out.xplane.pb>

Four steps of a tiny program on every chip of the host: a matrix product,
then an all-reduce of its result over the chips, inside the benchmark's
``window`` / ``data`` / ``dispatch`` / ``readback`` host spans.  Only user
annotations are kept on the host side, so the file stays small.
"""

import glob
import shutil
import sys
import tempfile

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P


def main(out: str) -> int:
    devs = jax.devices()
    mesh = jax.make_mesh((len(devs),), ("data",), devices=devs)

    def body(a):
        y = a @ a.T
        return jax.lax.psum(y, "data") + jnp.tanh(y)

    f = jax.jit(jax.shard_map(body, mesh=mesh, in_specs=P("data"),
                              out_specs=P("data")))
    sh = NamedSharding(mesh, P("data"))
    x = jax.device_put(jnp.ones((256 * len(devs), 1024), jnp.bfloat16), sh)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    d = tempfile.mkdtemp()
    jax.profiler.start_trace(d, profiler_options=opts)
    with jax.profiler.TraceAnnotation("window"):
        for _ in range(4):
            with jax.profiler.TraceAnnotation("data"):
                x = jax.device_put(x, sh)
            with jax.profiler.TraceAnnotation("dispatch"):
                y = f(x)
            with jax.profiler.TraceAnnotation("readback"):
                float(y.addressable_shards[0].data[0, 0])
    jax.profiler.stop_trace()
    shutil.copy(glob.glob(f"{d}/**/*.xplane.pb", recursive=True)[0], out)
    shutil.rmtree(d)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
