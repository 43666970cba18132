#!/usr/bin/env python3
"""Readings from which a cell's limits are set, on the chip, in one process.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 1 2 ... \\
        [--control-seeds ...] [--faults half_batch ...] [--fault-seeds ...] \\
        [--out calib.jsonl]

For every seed: the program's first steps through the timed entry (the
step is compiled once), then the plain reference, and the numbers that
``check.py`` compares.  For each control seed the reference again with
its parameters and every matrix product one precision below the
configuration's (the reference's ``control``); for each planted fault
(``faults.py``) the program rebuilt with the fault, on the fault seeds.
Each reading is one JSON line.  Runs on the chip only: the limits are set
from these readings at the cell's own size.
"""

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[2]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--faults", nargs="*", default=[])
    ap.add_argument("--fault-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--out", default="")
    args = ap.parse_args(argv)
    sys.path.insert(0, str(ROOT))
    sys.path.insert(0, str(ROOT / "src"))

    import jax
    from benchmarks.chip import faults, feed, harness, manifest
    from repro.launch.compile_cache import enable_compile_cache

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print("calibrate: needs a TPU", file=sys.stderr)
        return 1
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = manifest.load_cell(args.workload, ROOT)
    out = open(args.out, "a") if args.out else None

    def emit(rec):
        line = json.dumps(rec)
        print(line, flush=True)
        if out:
            out.write(line + "\n")
            out.flush()

    def program(b, compiled, seed):
        tr = cell.traffic
        batches = feed.Feed(tr, b.cfg.vocab_size, seed, b.rows)
        try:
            state = harness.make_state(b, seed)
            stepper = harness.Stepper(compiled, batches, b.batch_sh)
            t = time.perf_counter()
            state, prog, sound = harness.checked_steps(
                b, stepper, state, seed, harness.first_grad_fn(b))
            dt = time.perf_counter() - t
            del state, stepper
        finally:
            batches.close()
        return prog, sound, dt

    def built():
        b = harness.build(cell, devices)
        state = harness.make_state(b, 0)
        batch = jax.device_put(feed.batch_at(cell.traffic, b.cfg.vocab_size,
                                             0, 0, b.rows), b.batch_sh)
        compiled = harness.compile_step(b, state, batch)
        del state, batch
        return b, compiled

    b, compiled = built()
    harness.describe(b)
    names = [e[0] for e in b.ents]
    refs = {}
    for seed in args.seeds:
        prog, sound, dt = program(b, compiled, seed)
        peak = harness.peak_bytes(devices)
        t = time.perf_counter()
        refs[seed] = harness.reference_readings(b, seed)
        values, where = harness.check.readings(prog, refs[seed], names)
        emit({"cell": cell.name, "kind": "program", "seed": seed,
              "values": values, "where": where, "sound": sound,
              "loss": prog["loss"], "ref_loss": refs[seed]["loss"],
              "steps_s": dt, "reference_s": time.perf_counter() - t,
              "peak_bytes": peak})
    low = manifest.reference(cell.config).LOWER[
        cell.config["model"]["dtype"]]
    for seed in args.control_seeds:
        t = time.perf_counter()
        ctl = harness.reference_readings(b, seed, control=True)
        values, where = harness.check.readings(ctl, refs[seed], names)
        emit({"cell": cell.name, "kind": f"control_{low}", "seed": seed,
              "values": values, "where": where, "loss": ctl["loss"],
              "control_s": time.perf_counter() - t})
    del compiled
    for name in args.faults:
        with faults.FAULTS[name]():
            fb, fcompiled = built()
            for seed in args.fault_seeds:
                prog, _, _ = program(fb, fcompiled, seed)
                values, where = harness.check.readings(prog, refs[seed],
                                                       names)
                emit({"cell": cell.name, "kind": f"fault_{name}",
                      "seed": seed, "values": values, "where": where,
                      "loss": prog["loss"]})
            del fcompiled
    emit({"cell": cell.name, "kind": "done",
          "process_s": time.perf_counter() - T_PROCESS})
    if out:
        out.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
