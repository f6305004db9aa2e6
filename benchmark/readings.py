"""Limit-setting: the numbers `correct` compares, read over several
seeds in ONE process (a run's set-up is long and a limit wants a dozen
seeds).

    python3 benchmark/readings.py --workload <cell> --seeds 1,2,3
        [--control lower] [--fault <name>[.<site>]] [--out <file>]

For each seed the cell's own driver makes one dataset, drives ONE pass
or call of the timed path through its own `window()`, frees the
program's state and runs its own `check()`: the same objects and the
same code as a run of `run.py`, without the warm-up and without a
measured window. With `--control` the same pass is then checked again
with the lower-precision reference in the program's place; with
`--fault` the fault is planted before the first pass (one fault a
process: it patches the program's classes). One JSON line a reading:
`{"seed", "mode": "sound"|"control"|<fault>, "compared": {name: value}}`.
No number read here is a metric.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import shutil
import sys
import tempfile
import time

import run as harness


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--control", default=None)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    ap.add_argument("--out", default=None, help="append the lines here too")
    args = ap.parse_args(argv)
    manifest = harness.load_json(os.path.join(harness.ROOT, "BENCHMARK.json"))
    cell, config, traffic = harness.find_cell(manifest, args.workload)
    traffic = dict(traffic, warmup_passes=0, datasets=1)
    store_dir = tempfile.mkdtemp(prefix="bench-store-")
    try:
        harness.pin_state(store_dir, args.workload)
        import jax
        from transmogrifai_tpu.utils.compile_cache import (
            enable_compile_cache)
        enable_compile_cache(min_compile_s=0.0)
        try:
            device = harness.device_report(int(cell["chips"]),
                                           args.rehearsal)
        except harness.NoResult as e:
            print(f"benchmark/readings.py: {e}", file=sys.stderr)
            return 3
        harness.say(f"[readings] {args.workload} on {device}")
        driver = harness.load_module("drivers", traffic["driver"])
        for seed in (int(s) for s in args.seeds.split(",")):
            t0 = time.perf_counter()
            one = driver.Run(cell=cell, config=config, traffic=traffic,
                             seed=seed, rehearsal=args.rehearsal,
                             fault=args.fault, control=None,
                             say=harness.say)
            one.setup()
            window = one.window(0.001, harness.Tracing(False))
            one.release()
            modes = [(args.fault or "sound", None)]
            if args.control:
                modes.append(("control", args.control))
            for mode, control in modes:
                one.control = control
                line = json.dumps({
                    "seed": seed, "mode": mode, "compared": {
                        c["name"]: c["value"] for c in one.check(window)}})
                harness.say(f"[readings] {line}")
                if args.out:
                    with open(args.out, "a") as fh:
                        fh.write(line + "\n")
            del one, window
            gc.collect()
            jax.clear_caches()      # executables carry a dataset each
            used = (jax.devices()[0].memory_stats() or {}).get(
                "bytes_in_use", 0)
            harness.say(f"[readings] seed {seed}: "
                        f"{time.perf_counter() - t0:.1f}s, device bytes "
                        f"in use after it {used}")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
