#!/usr/bin/env python3
"""The on-chip benchmark of the MG-WFBP train step: one run of one cell.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <s> --trace <0|1>

The cell (``workloads`` in ``BENCHMARK.json``) names a configuration and a
traffic mix; their files, the cell's limits and the per-layer metrics'
readers are found by name (``manifest.py``).  With ``--trace 0`` the run
measures the end-to-end metrics over a window of ``--seconds``; with
``--trace 1`` it traces a few steps and reports the per-layer metrics.
Either way it checks the first steps against the plain reference.

The last line of standard output is one JSON object: ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and with ``--trace 1``
``breakdown``), and last ``checks``, each number compared beside its
limit.  The run refuses anything but a TPU with as many chips as the cell
asks for: it exits non-zero and prints no result line.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def fail(msg: str) -> int:
    print(f"benchmark: FAIL: {msg}", file=sys.stderr, flush=True)
    return 1


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import manifest
    cell = manifest.load_cell(args.workload, ROOT)
    if cell.limits is None:
        return fail(f"no limits file for {cell.name}")
    if not (ROOT / "src" / "repro").is_dir():
        return fail(f"no program under {ROOT / 'src'}")
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    devices = jax.devices()
    d = devices[0]
    print(f"device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devices)}", file=sys.stderr, flush=True)
    if d.platform != "tpu":
        return fail(f"needs a TPU; JAX found platform {d.platform!r}")
    if len(devices) != cell.chips:
        return fail(f"{cell.name} needs {cell.chips} chips; JAX sees "
                    f"{len(devices)}")

    from repro.launch.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}", file=sys.stderr)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)

    from benchmarks.chip import harness
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), devices, T_PROCESS)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
