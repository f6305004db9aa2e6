"""Run one cell of BENCHMARK.json once, in one process.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last stdout line is the contract's one JSON object (`correct`,
`attempted`, `failed`, `metrics`, `device`, `breakdown` when traced, and
`compared` last); everything else goes on earlier lines. Without a TPU
the run exits non-zero and prints no result; `--rehearsal` runs the same
code at the configuration's `rehearsal` sizes on whatever JAX finds and
reports that platform — such a run is never a metric.

Nothing here knows a cell, a configuration, a traffic mix or a per-layer
metric by name: the cell names its configuration and traffic files, the
traffic file names its driver (`drivers/<driver>.py`), and each per-layer
metric is read by `layer_metrics/<name>.py`.
"""

from __future__ import annotations

import argparse
import contextlib
import importlib.util
import json
import os
import shutil
import sys
import tempfile
import time

T_START = time.perf_counter()
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
CACHE_ROOT = os.path.join(HERE, ".xla-cache")  # fixed: the path is in the key
# The program's sweep closes over each dataset, so its big programs carry
# the data as constants (hundreds of MB each) and can never be found
# again under another seed. An entry over this size is not written, and
# older entries go first once a cell's cache passes it: a run then writes
# megabytes, not gigabytes.
CACHE_MAX_BYTES = 256 << 20
TRACE_DIR = os.path.join(HERE, ".trace")


class NoResult(Exception):
    """The run cannot produce a result line (no chip, no program)."""


def say(msg: str) -> None:
    print(msg, flush=True)


def load_json(path: str):
    with open(path) as fh:
        return json.load(fh)


def load_module(kind: str, name: str):
    """`benchmark/<kind>/<name>.py` as a module, found by name."""
    path = os.path.join(HERE, kind, name + ".py")
    if not os.path.exists(path):
        raise FileNotFoundError(f"no {kind} file {path}")
    spec = importlib.util.spec_from_file_location(
        f"benchmark_{kind}_{name.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def find_cell(manifest: dict, workload: str):
    cells = {c["name"]: c for c in manifest["workloads"]}
    if workload not in cells:
        raise NoResult(f"no cell {workload!r} in BENCHMARK.json "
                       f"(cells: {sorted(cells)})")
    cell = cells[workload]
    cfg_entry = {c["name"]: c for c in manifest["configs"]}[cell["config"]]
    config = load_json(os.path.join(ROOT, cfg_entry["file"]))
    traffic = load_json(os.path.join(HERE, "traffic",
                                     cell["traffic"] + ".json"))
    return cell, config, traffic


def metrics_of(manifest: dict, group: str, workload: str):
    """The `group` metrics this cell reports (no `workloads` key = all)."""
    return [m for m in manifest[group]
            if "workloads" not in m or workload in m["workloads"]]


def pin_state(store_dir: str, workload: str) -> None:
    """Learned state cold, compile cache at its fixed place (one
    directory a cell, so one cell's entries never push out another's) —
    set before the program (and JAX) is imported, because both read
    these once."""
    os.environ["TRANSMOGRIFAI_STORE_DIR"] = store_dir
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          os.path.join(CACHE_ROOT, workload))
    os.environ.setdefault("JAX_COMPILATION_CACHE_MAX_SIZE",
                          str(CACHE_MAX_BYTES))
    os.makedirs(os.environ["JAX_COMPILATION_CACHE_DIR"], exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    if ROOT not in sys.path:
        sys.path.insert(0, ROOT)
    if HERE not in sys.path:
        sys.path.insert(0, HERE)


def device_report(chips: int, rehearsal: bool) -> dict:
    import jax
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    if rehearsal:
        return dev
    if dev["platform"] != "tpu":
        raise NoResult(f"JAX found no TPU (platform {dev['platform']!r}); "
                       "the benchmark has no CPU fallback — use "
                       "--rehearsal to debug the command")
    if dev["count"] < chips:
        raise NoResult(f"the cell asks for {chips} chips, JAX found "
                       f"{dev['count']}")
    return dev


def compile_counter() -> dict:
    """Live counts from JAX's own monitoring events (as
    `chip_smoke._count_xla_compiles`): compile requests that consulted
    the persistent cache, how many it answered, backend compile seconds."""
    from jax import monitoring
    counts = {"requests": 0, "cache_hits": 0, "backend_compile_s": 0.0}

    def on_event(event, **_):
        if event == "/jax/compilation_cache/compile_requests_use_cache":
            counts["requests"] += 1
        elif event == "/jax/compilation_cache/cache_hits":
            counts["cache_hits"] += 1

    def on_duration(event, duration_secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            counts["backend_compile_s"] += duration_secs

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)
    return counts


class Tracing:
    """`with tracing.span(name):` marks a pass or a call. With
    `--trace 1` the FIRST marked span is also recorded by the JAX
    profiler (device ops and the host annotation on one clock) and
    reduced after the window; later spans run untraced, so the trace
    stays small. With `--trace 0` a span costs two clock reads."""

    def __init__(self, enabled: bool):
        self.enabled = enabled
        self.traced = False       # a span has been recorded

    @contextlib.contextmanager
    def span(self, name: str):
        if not self.enabled or self.traced:
            yield
            return
        import jax
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        os.makedirs(TRACE_DIR, exist_ok=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(TRACE_DIR, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(f"bench:{name}"):
                yield
        finally:
            jax.profiler.stop_trace()
            self.traced = True

    def reduce(self, n_devices: int):
        """Reduced trace of the traced span, or None."""
        if not self.traced:
            return None
        import trace_reduce
        path = trace_reduce.find_xplane(TRACE_DIR)
        red = trace_reduce.reduce_xplane(path, n_devices) if path else None
        shutil.rmtree(TRACE_DIR, ignore_errors=True)
        return red


def memory_peak(devs) -> int:
    peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in devs]
    return int(max(peaks)) if peaks else 0


def run_cell(workload: str, seed: int, seconds: float, trace: bool,
             rehearsal: bool = False, fault: str | None = None,
             control: str | None = None) -> dict:
    """One run; returns the result object (also printed by `main`).
    `fault` and `control` are for the tests under `benchmark/tests` and
    the limit-setting runs: a driver plants the named fault under the
    timed path, or puts the lower-precision reference in the program's
    place, and `correct` has to come out false."""
    manifest = load_json(os.path.join(ROOT, "BENCHMARK.json"))
    cell, config, traffic = find_cell(manifest, workload)
    store_dir = tempfile.mkdtemp(prefix="bench-store-")
    try:
        pin_state(store_dir, workload)
        try:
            import jax
            import transmogrifai_tpu  # noqa: F401  (the system under test)
        except ImportError as e:
            raise NoResult(f"the program is not here: {e}")
        from transmogrifai_tpu.utils.compile_cache import (
            enable_compile_cache)
        enable_compile_cache(min_compile_s=0.0)
        device = device_report(int(cell["chips"]), rehearsal)
        say(f"[bench] cell {workload} seed {seed} seconds {seconds} "
            f"trace {int(trace)} device {device} "
            f"cache {os.environ['JAX_COMPILATION_CACHE_DIR']}")
        compiles = compile_counter()
        driver = load_module("drivers", traffic["driver"])
        tracing = Tracing(trace)
        run = driver.Run(cell=cell, config=config, traffic=traffic,
                         seed=int(seed), rehearsal=rehearsal, fault=fault,
                         control=control, say=say)
        run.setup()
        setup_compiles = dict(compiles)
        setup_s = time.perf_counter() - T_START
        say(f"[bench] set-up {setup_s:.1f}s; compile requests "
            f"{compiles['requests']}, cache hits {compiles['cache_hits']}, "
            f"backend compile {compiles['backend_compile_s']:.1f}s")
        window = run.window(float(seconds), tracing)
        window["compiles"] = {
            k: compiles[k] - setup_compiles[k] for k in compiles}
        say(f"[bench] window {window['window_s']:.1f}s; compile requests "
            f"{window['compiles']['requests']}, cache hits "
            f"{window['compiles']['cache_hits']}, backend compile "
            f"{window['compiles']['backend_compile_s']:.1f}s (summed over "
            "threads)")
        device["memory_peak_bytes"] = memory_peak(jax.devices())
        t0 = time.perf_counter()
        reduced = tracing.reduce(int(cell["chips"]))
        t1 = time.perf_counter()
        run.release()
        compared = run.check(window)
        say(f"[bench] trace reduction {t1 - t0:.1f}s, check "
            f"{time.perf_counter() - t1:.1f}s, run so far "
            f"{time.perf_counter() - T_START:.1f}s")
    finally:
        shutil.rmtree(store_dir, ignore_errors=True)

    values = dict(window["metrics"])
    values["setup_s"] = setup_s
    out_metrics = {}
    if trace:
        import work
        obs = {"window": window, "trace": reduced, "cell": cell,
               "config": config, "traffic": traffic, "device": device,
               "peaks": None if rehearsal else work.peaks_for(
                   device["kind"])}
        for m in metrics_of(manifest, "per_layer", workload):
            val = load_module("layer_metrics", m["name"]).read(obs)
            if val is not None:
                out_metrics[m["name"]] = {"value": val, "unit": m["unit"]}
        if reduced is not None:
            device["busy_s"] = reduced["busy_s"]
            device["window_s"] = reduced["window_s"]
    else:
        for m in metrics_of(manifest, "end_to_end", workload):
            out_metrics[m["name"]] = {"value": values[m["name"]],
                                      "unit": m["unit"]}
    correct = bool(compared) and all(
        c["value"] is not None and c["value"] <= c["limit"]
        for c in compared)
    result = {"correct": correct, "attempted": window["attempted"],
              "failed": window["failed"], "metrics": out_metrics,
              "device": device}
    if trace and reduced is not None:
        result["breakdown"] = {"device_ops": reduced["device_ops"][:10],
                               "idle_gaps": reduced["idle_gaps"][:10]}
    result["compared"] = {c["name"]: {"value": c["value"],
                                      "limit": c["limit"]}
                          for c in compared}
    for line in window.get("notes", []):
        say(f"[bench] {line}")
    say(f"[bench] all values: {json.dumps(values)}")
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearsal", action="store_true",
                    help="the configuration's rehearsal sizes, any platform")
    ap.add_argument("--control", default=None,
                    help="limit-setting only: put the lower-precision "
                         "reference in the program's place")
    ap.add_argument("--fault", default=None,
                    help="limit-setting only: plant the named fault")
    args = ap.parse_args(argv)
    try:
        result = run_cell(args.workload, args.seed, args.seconds,
                          bool(args.trace), args.rehearsal, args.fault,
                          args.control)
    except NoResult as e:
        print(f"benchmark/run.py: {e}", file=sys.stderr)
        return 3
    sys.stdout.flush()
    for name, c in result["compared"].items():
        print(f"compared {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
