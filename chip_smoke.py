"""The quickest proof that the system still starts on the chip.

    python chip_smoke.py                  # on a TPU; exit 0, last line `ok`
    python chip_smoke.py --cpu-rehearsal  # debug the command here, tiny size

Drives the main path once through the entry points a user calls, at the
full width of the one configuration every record of this repo uses
(`bench.make_data(100_000)` → transmogrify → SanityChecker → the
selector's OWN default sweep, 84 fits → `Workflow.train` → `save`):

1. train   — one child process. Backend must be `tpu`; all three model
             families must report finite CV metrics (the selector drops a
             family whose sweep raises and carries on, so "RF no longer
             compiles" would otherwise pass as an LR-only train); zero
             `retry`/`oom_redo` events; holdout AuPR >= 0.80.
2. score   — same process: warm `score_compiled` is ONE dispatch with no
             retrace and agrees with the unfused `score` within the
             printed tolerance; three `score_stream` batches (ragged
             tail) over a parquet the run wrote, zero retraces.
3. serve   — `python -m transmogrifai_tpu.cli serve` as its own process,
             driven over HTTP: mixed-size requests on both wires equal
             the phase-2 scores, no scoring program traced after ladder
             warm-up (`runtime_jit_traces_total` on `/metrics`; eager
             helper ops outside the instrumented programs are not
             counted — each request's latency is printed instead),
             SIGINT exits 0. Then one `--quantize int8-calibrated` boot.
4. mesh    — with >= 4 devices: the same train under
             `make_mesh(4, sweep=2)` must agree with the one-chip train
             and leave non-zero peak bytes on every device.

A chip belongs to ONE process at a time, so this parent never imports
jax (nor anything that does): each phase is exactly one child, run
strictly in sequence, and every child is stopped on the way out. Without
a TPU — and without `--cpu-rehearsal` — it exits non-zero and prints no
result. A passing run prints one JSON summary line (device, per-phase
facts, `"claim": null`; also written to `chiprun_out/chip_smoke/
summary.json`), and a passing CHIP run then ends its stdout with exactly
`{"ok": true, "device": {"platform": "tpu", "kind": "...", "count": n}}`
— the device as JAX reported it to the train child. The rehearsal prints
no `ok` line, because nothing was established about the chip. Walls
printed here are bring-up facts, not benchmark numbers.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import subprocess
import sys
import tempfile
import time
import urllib.request

HERE = os.path.dirname(os.path.abspath(__file__))
DEADLINE_S = 1150.0          # the chip check allows 1200 s, compile included
FAMILIES = ("OpLogisticRegression", "OpRandomForestClassifier",
            "OpXGBoostClassifier")
MIN_HOLDOUT_AUPR = 0.80      # planted-signal data; garbage numerics fail
# fused-vs-unfused and batch-vs-served probability tolerance. Bit-equality
# does not carry to the MXU's default f32 precision across batch shapes:
# the v5e gave max |dp| 0 fused-vs-unfused and 5.7e-4 served-vs-batch
# (PR 21 chip runs), so the bound sits ~3.5x above that — tighter than
# the CPU tests' 5e-3 fused-vs-eager bound, which a lower-precision path
# could pass. Tree winners may flip a bin edge on an ulp, so it must hold
# on all but 0.1 % of rows.
PROB_ATOL = 2e-3
PROB_OUTLIER_FRAC = 1e-3
QUANT_AGREE_MIN = 0.95       # documented int8 bound (tests/test_roofline.py)
# mesh-vs-one-chip drift `__graft_entry__.dryrun_multichip` tolerates
MESH_DRIFT_MAX = 2e-3
MESH_EXACT_TOL = 2e-4
MESH_INEXACT_MAX = 2
REQUEST_SIZES = (1, 7, 64, 1000)
SERVE_MAX_BATCH = 1024


class PhaseFailed(Exception):
    pass


def _say(msg: str) -> None:
    print(msg, flush=True)


# --------------------------------------------------------------------- #
# children (the only code here that touches jax)                        #
# --------------------------------------------------------------------- #

def _rehearsal_models():
    """All three families, shrunk so the CPU rehearsal takes a minute."""
    from transmogrifai_tpu.models import (
        OpLogisticRegression, OpRandomForestClassifier, OpXGBoostClassifier)
    return [(OpLogisticRegression(max_iter=20),
             [{"reg_param": r} for r in (0.01, 0.1)]),
            (OpRandomForestClassifier(n_trees=4, max_bins=16),
             [{"max_depth": d} for d in (3, 6)]),
            (OpXGBoostClassifier(n_estimators=8, max_bins=16,
                                 early_stopping_rounds=3),
             [{"eta": 0.3, "max_depth": 3}])]


def _train(rehearsal: bool, mesh=None):
    """(model, ds, pf, fitted selector, n_fits, wall_s) — bench.py's
    full-mode pipeline through `Workflow.train`."""
    sys.path.insert(0, HERE)
    from bench import make_data
    from transmogrifai_tpu.automl import transmogrify
    from transmogrifai_tpu.automl.sanity_checker import SanityChecker
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, DataSplitter)
    from transmogrifai_tpu.selector.model_selector import (
        _default_binary_models)
    from transmogrifai_tpu.workflow import Workflow

    ds = make_data(2_000 if rehearsal else 100_000)
    preds, label = FeatureBuilder.from_dataset(ds, response="label")
    checked = SanityChecker().set_input(
        label, transmogrify(preds)).get_output()
    models = _rehearsal_models() if rehearsal else _default_binary_models()
    selector = BinaryClassificationModelSelector.with_cross_validation(
        models=models, n_folds=3,
        splitter=DataSplitter(reserve_test_fraction=0.1))
    pf = selector.set_input(label, checked).get_output()
    t0 = time.perf_counter()
    model = Workflow().set_result_features(pf, label) \
        .set_input_dataset(ds).train(mesh=mesh)
    wall = time.perf_counter() - t0
    fitted = model.fitted[pf.origin_stage.uid]
    return (model, ds, pf, fitted,
            3 * sum(len(g) for _, g in models), wall)


def _check_summary(summary) -> dict:
    """Every family present with finite CV metrics; returns the
    per-config means keyed for the mesh comparison."""
    import math
    by_family: dict = {}
    for r in summary.validation_results:
        by_family.setdefault(r.model, []).append(r.mean_metric)
    for fam in FAMILIES:
        vals = by_family.get(fam)
        if not vals:
            raise PhaseFailed(
                f"family {fam} is missing from validation_results "
                f"(present: {sorted(by_family)}) — its sweep raised and "
                "the selector dropped it")
        if not all(math.isfinite(v) for v in vals):
            raise PhaseFailed(f"family {fam} has non-finite CV metrics")
    return {json.dumps([r.model, sorted(r.grid.items())]): r.mean_metric
            for r in summary.validation_results}


def _best(summary) -> list:
    """[family, [[param, value], ...]] — lists, so it survives JSON."""
    return [summary.best_model,
            [list(kv) for kv in sorted(summary.best_grid.items())]]


def _device_report(rehearsal: bool) -> dict:
    import jax
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if device["platform"] != "tpu" and not rehearsal:
        raise PhaseFailed(
            f"JAX found no TPU (backend is {device['platform']!r}); this "
            "check runs on the chip — use --cpu-rehearsal to debug the "
            "command here")
    return device


def _train_breakdown() -> dict:
    """Where the train wall went, from the spans and counters the
    program already keeps: per-stage fit walls, per-family sweep walls
    (families overlap on a thread pool, so they do not sum to the
    selector's wall), the sweep's dispatch accounting, and the XLA
    compiles the sweep's dispatches asked for (`compile:*` spans,
    measured thread-seconds)."""
    from transmogrifai_tpu.obs.trace import TRACER
    from transmogrifai_tpu.parallel.sweep import SWEEP_STATS
    walls: dict = {}
    sweep_compiles = []
    for sp in TRACER.spans():
        if sp.name.startswith(("stage:fit:", "sweep:family:")):
            walls[sp.name] = round(walls.get(sp.name, 0.0)
                                   + sp.duration_s, 1)
        elif sp.name.startswith("compile:sweep:dispatch:"):
            sweep_compiles.append(sp.duration_s)
    return {"span_wall_s": walls,
            "sweep_dispatches": SWEEP_STATS.dispatches,
            "sweep_dispatch_s": round(SWEEP_STATS.dispatch_s, 1),
            "sweep_compiles": len(sweep_compiles),
            "sweep_compile_s": round(sum(sweep_compiles), 1)}


def _prob1(tree):
    import numpy as np
    return np.asarray(tree["probability"], np.float64)[:, 1]


def _compare_probs(what: str, got, want) -> dict:
    """PROB_ATOL on all but PROB_OUTLIER_FRAC of rows; prints what the
    device gives."""
    import numpy as np
    got, want = np.asarray(got, np.float64), np.asarray(want, np.float64)
    if got.shape != want.shape or not np.isfinite(got).all():
        raise PhaseFailed(f"{what}: shape {got.shape} vs {want.shape}, "
                          f"finite={bool(np.isfinite(got).all())}")
    diff = np.abs(got - want)
    over = float((diff > PROB_ATOL).mean())
    out = {"max_abs_diff": float(diff.max()), "frac_over_atol": over,
           "exact_frac": float((diff == 0).mean())}
    _say(f"[score] {what}: max |dp| {out['max_abs_diff']:.3e}, "
         f"{out['exact_frac']:.4f} of rows bit-equal, {over:.2e} over the "
         f"stated tolerance {PROB_ATOL} (allowed {PROB_OUTLIER_FRAC})")
    if over > PROB_OUTLIER_FRAC:
        raise PhaseFailed(f"{what}: {over:.2e} of rows differ by more "
                          f"than {PROB_ATOL}")
    return out


def child_train_score(work: str, rehearsal: bool) -> dict:
    import numpy as np

    import jax
    from transmogrifai_tpu.analysis.retrace import DISPATCHES, MONITOR
    from transmogrifai_tpu.native.build import native_active
    from transmogrifai_tpu.obs.export import EventLog, install_event_log
    from transmogrifai_tpu.readers import DataReaders
    from transmogrifai_tpu.utils.compile_cache import (
        COMPILE_STATS, compile_cache_entries, enable_compile_cache)

    res: dict = {"device": _device_report(rehearsal),
                 "compile_cache": enable_compile_cache()}
    stats = jax.devices()[0].memory_stats() or {}
    res["bytes_limit"] = stats.get("bytes_limit")
    _say(f"[train] platform={res['device']['platform']} "
         f"device_kind={res['device']['kind']} "
         f"count={res['device']['count']} "
         f"bytes_limit={res['bytes_limit']} "
         f"compile_cache={res['compile_cache']}")
    # state a run keeps (cached programs, the perf corpus) lives under
    # the store root only; none of it decides what gets compiled
    from transmogrifai_tpu.store.config import cache_root
    res["store_at_start"] = {
        "root": cache_root(),
        "compile_cache_entries": compile_cache_entries()}
    _say(f"[train] kept state at start: {res['store_at_start']}")
    res["native"] = native_active()
    _say(f"[train] native host kernels active: {res['native']}")
    if shutil.which("cc") and not all(res["native"].values()):
        raise PhaseFailed("cc exists but a native kernel did not build")

    xla0 = dict(COMPILE_STATS)  # fed by the program's own listeners
    events_path = os.path.join(work, "events.jsonl")
    install_event_log(EventLog(events_path, run_id="chip-smoke"))
    model, ds, pf, fitted, n_fits, wall = _train(rehearsal)
    summary = fitted.summary
    res["train_wall_s"] = round(wall, 1)
    res["train_breakdown"] = _train_breakdown()
    xla = {k: COMPILE_STATS[k] - xla0[k] for k in xla0}
    res["train_xla"] = {**xla, "backend_compile_s":
                        round(xla["backend_compile_s"], 1)}
    _say(f"[train] breakdown: {res['train_breakdown']}; XLA compile "
         f"requests {xla['requests']}, persistent-cache hits "
         f"{xla['cache_hits']}, backend compile "
         f"{xla['backend_compile_s']:.1f}s (summed over threads)")
    res["n_fits"] = n_fits
    res["cv_means"] = _check_summary(summary)
    res["best"] = _best(summary)
    res["holdout_aupr"] = float(summary.holdout_metrics.get("AuPR", 0.0))
    with open(events_path) as fh:
        kinds = [json.loads(line)["kind"] for line in fh if line.strip()]
    res["retry_events"] = sum(k in ("retry", "oom_redo") for k in kinds)
    res["peak_bytes_in_use"] = (jax.devices()[0].memory_stats() or {}) \
        .get("peak_bytes_in_use")
    _say(f"[train] {n_fits} fits in {wall:.1f}s (compile included); "
         f"winner {res['best']}; holdout AuPR {res['holdout_aupr']:.4f}; "
         f"retry/oom_redo events {res['retry_events']}; "
         f"peak_bytes_in_use {res['peak_bytes_in_use']}")
    if res["retry_events"]:
        raise PhaseFailed(f"{res['retry_events']} retry/oom_redo events")
    if not rehearsal and res["holdout_aupr"] < MIN_HOLDOUT_AUPR:
        raise PhaseFailed(f"holdout AuPR {res['holdout_aupr']:.4f} < "
                          f"{MIN_HOLDOUT_AUPR}")
    model.save(os.path.join(work, "model"))

    # -- score: fused == one dispatch, no retrace, parity vs unfused ---- #
    n = len(ds)
    t0 = time.perf_counter()
    jax.block_until_ready(model.score_compiled(ds)[pf.name])
    res["score_cold_s"] = round(time.perf_counter() - t0, 2)
    traces, dispatches = MONITOR.snapshot(), DISPATCHES.snapshot()
    t0 = time.perf_counter()
    fused = jax.block_until_ready(model.score_compiled(ds)[pf.name])
    res["score_warm_s"] = round(time.perf_counter() - t0, 3)
    n_disp = sum(DISPATCHES.delta(dispatches).values())
    retraced = MONITOR.delta(traces)
    _say(f"[score] score_compiled on {n} rows: cold {res['score_cold_s']}s"
         f", warm {res['score_warm_s']}s, {n_disp} dispatch(es), "
         f"retraces {retraced}")
    if n_disp != 1 or retraced:
        raise PhaseFailed(f"fused scoring took {n_disp} dispatches, "
                          f"retraces {retraced}")
    fused_p = _prob1(fused)
    fused_pred = np.asarray(fused["prediction"], np.float64)
    if fused_p.shape != (n,):
        raise PhaseFailed(f"fused probability shape {fused_p.shape}")
    eager = model.score(ds)[pf.name]
    res["fused_vs_unfused"] = _compare_probs(
        "fused vs unfused", fused_p, _prob1(eager.data))

    # -- score_stream: 3 batches, ragged tail, zero retraces ------------ #
    pq = os.path.join(work, "data.parquet")
    ds.to_parquet(pq)
    batch = -(-n * 2 // 5)          # 0.4·n, 0.4·n, ragged 0.2·n
    reader = DataReaders.stream(parquet_path=pq, batch_size=batch,
                                schema=dict(ds.schema))
    list(model.score_stream(iter([next(iter(reader.stream()))])))  # warm
    traces = MONITOR.snapshot()
    outs = [_prob1(o[pf.name]) for o in model.score_stream(reader.stream())]
    retraced = MONITOR.delta(traces)
    sizes = [len(o) for o in outs]
    _say(f"[score] score_stream batches {sizes}, retraces {retraced}")
    if len(outs) != 3 or sizes[-1] >= sizes[0] or sum(sizes) != n \
            or retraced:
        raise PhaseFailed(f"score_stream: batches {sizes}, "
                          f"retraces {retraced}")
    res["stream_vs_fused"] = _compare_probs(
        "score_stream vs fused", np.concatenate(outs), fused_p)

    # -- requests for the serve phase, with the batch scores they must
    #    reproduce (a user sends features, not the label) --------------- #
    features = [k for k in ds.names() if k != "label"]
    requests, off = [], 0
    for i, size in enumerate(REQUEST_SIZES + REQUEST_SIZES + (1, 64)):
        idx = np.arange(off, off + size) % n
        off += size
        rows = ds.take(idx).to_rows()
        if i % 2 == 0:
            body = {"rows": [{k: r[k] for k in features} for r in rows]}
        else:
            body = {"columns": {k: [r[k] for r in rows] for k in features}}
        requests.append({"body": body, "prob1": fused_p[idx].tolist(),
                         "prediction": fused_pred[idx].tolist()})
    with open(os.path.join(work, "requests.json"), "w") as fh:
        json.dump({"result_feature": pf.name, "requests": requests}, fh,
                  default=lambda v: v.item())
    return res


def child_mesh(work: str, rehearsal: bool) -> dict:
    """The same train on a {sweep: 2, data: 2} mesh of four devices."""
    import jax
    from transmogrifai_tpu.parallel.mesh import make_mesh
    from transmogrifai_tpu.utils.compile_cache import enable_compile_cache

    res: dict = {"device": _device_report(rehearsal)}
    enable_compile_cache()
    with open(os.path.join(work, "train_score.json")) as fh:
        one_chip = json.load(fh)
    mesh = make_mesh(4, sweep=2)
    res["mesh"] = {k: int(v) for k, v in dict(mesh.shape).items()}
    _, _, _, fitted, n_fits, wall = _train(rehearsal, mesh=mesh)
    summary = fitted.summary
    res["train_wall_s"] = round(wall, 1)
    got = _check_summary(summary)
    want = one_chip["cv_means"]
    if got.keys() != want.keys():
        raise PhaseFailed("mesh sweep evaluated different configs")
    drifts = {k: abs(got[k] - want[k]) for k in want}
    res["max_drift"] = max(drifts.values())
    res["exact"] = sum(d <= MESH_EXACT_TOL for d in drifts.values())
    res["configs"] = len(drifts)
    res["best"] = _best(summary)
    res["winner_same"] = res["best"] == one_chip["best"]
    res["peak_bytes_in_use"] = [
        (d.memory_stats() or {}).get("peak_bytes_in_use")
        for d in jax.devices()[:4]]
    _say(f"[mesh] mesh {res['mesh']}: {n_fits} fits in {wall:.1f}s; "
         f"drift vs one chip max {res['max_drift']:.2e}, "
         f"{res['exact']}/{res['configs']} within {MESH_EXACT_TOL}; "
         f"winner {res['best']} (same: {res['winner_same']}); "
         f"peak_bytes_in_use {res['peak_bytes_in_use']}")
    if res["max_drift"] > MESH_DRIFT_MAX or \
            res["exact"] < res["configs"] - MESH_INEXACT_MAX:
        raise PhaseFailed(f"mesh-vs-one-chip drift {res['max_drift']:.2e}"
                          f", {res['exact']}/{res['configs']} exact")
    if not res["winner_same"]:
        best_key = json.dumps(one_chip["best"])
        if abs(got[best_key] - max(got.values())) > MESH_DRIFT_MAX:
            raise PhaseFailed(f"mesh winner {res['best']} != one-chip "
                              f"{one_chip['best']} beyond the drift")
    if res["device"]["platform"] == "tpu" and \
            not all(res["peak_bytes_in_use"]):
        raise PhaseFailed("a device shows zero peak_bytes_in_use: "
                          f"{res['peak_bytes_in_use']}")
    return res


CHILDREN = {"train_score": child_train_score, "mesh": child_mesh}


def run_child(name: str, work: str, rehearsal: bool) -> int:
    import logging
    logging.basicConfig(level=logging.INFO)  # stderr: the phase's log file
    try:
        res = CHILDREN[name](work, rehearsal)
    except PhaseFailed as e:
        _say(f"[{name}] FAILED: {e}")
        return 1
    with open(os.path.join(work, f"{name}.json"), "w") as fh:
        json.dump(res, fh)
    return 0


# --------------------------------------------------------------------- #
# parent: never imports jax                                             #
# --------------------------------------------------------------------- #

class Parent:
    def __init__(self, rehearsal: bool):
        self.rehearsal = rehearsal
        self.t0 = time.perf_counter()
        self.work = tempfile.mkdtemp(prefix="chip-smoke-")
        self.logs = os.path.join(HERE, "chiprun_out", "chip_smoke")
        os.makedirs(self.logs, exist_ok=True)
        self.procs: list = []
        self.env = dict(os.environ)
        if rehearsal:
            self.env["JAX_PLATFORMS"] = "cpu"
            self.env["XLA_FLAGS"] = (
                self.env.get("XLA_FLAGS", "")
                + " --xla_force_host_platform_device_count=4").strip()

    def remaining(self) -> float:
        return DEADLINE_S - (time.perf_counter() - self.t0)

    def spawn(self, argv: list, log_name: str, stdout=None):
        log = open(os.path.join(self.logs, log_name), "w")
        proc = subprocess.Popen(
            argv, cwd=HERE, env=self.env, stdout=stdout or log,
            stderr=log, start_new_session=True)
        proc.log_path = log.name
        log.close()
        self.procs.append(proc)
        return proc

    def stop_all(self) -> None:
        for proc in self.procs:
            if proc.poll() is None:
                try:
                    os.killpg(proc.pid, signal.SIGKILL)
                except ProcessLookupError:
                    pass
                proc.wait()
        shutil.rmtree(self.work, ignore_errors=True)

    def _log_tail(self, proc, n: int = 30) -> str:
        with open(proc.log_path, errors="replace") as fh:
            return "".join(fh.readlines()[-n:])

    # -- phases 1+2 and 4: one jax child each ----------------------------- #

    def jax_child(self, name: str) -> dict:
        argv = [sys.executable, os.path.abspath(__file__), "--child", name,
                "--work", self.work]
        if self.rehearsal:
            argv.append("--cpu-rehearsal")
        proc = self.spawn(argv, f"{name}.log", stdout=sys.stdout)
        try:
            rc = proc.wait(timeout=max(self.remaining(), 1.0))
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"{name}: still running at the deadline")
        if rc != 0:
            raise PhaseFailed(f"{name}: child exited {rc}\n"
                              + self._log_tail(proc))
        shutil.copy(os.path.join(self.work, f"{name}.json"), self.logs)
        with open(os.path.join(self.work, f"{name}.json")) as fh:
            return json.load(fh)

    # -- phase 3: cli serve, driven over HTTP ------------------------------ #

    def _http(self, url: str, body=None):
        data = None if body is None else json.dumps(body).encode()
        req = urllib.request.Request(
            url, data=data, method="GET" if body is None else "POST",
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=120) as resp:
            return json.loads(resp.read())

    def _boot_serve(self, tag: str, extra: list):
        platform = "cpu" if self.rehearsal else "tpu"
        argv = [sys.executable, "-m", "transmogrifai_tpu.cli", "serve",
                "--model-location", os.path.join(self.work, "model"),
                "--port", "0", "--platform", platform,
                "--max-batch", str(SERVE_MAX_BATCH)] + extra
        t0 = time.perf_counter()
        proc = self.spawn(argv, f"serve_{tag}.log")
        while True:  # the boot line carries the port the OS picked
            text = self._log_tail(proc, 200)
            if "on http://" in text:
                port = int(text.split("on http://", 1)[1]
                           .split(":", 1)[1].split()[0])
                break
            if proc.poll() is not None:
                raise PhaseFailed(f"serve[{tag}] exited {proc.returncode} "
                                  f"before binding\n{text}")
            if self.remaining() <= 0:
                raise PhaseFailed(f"serve[{tag}] not up at the deadline")
            time.sleep(0.5)
        return proc, f"http://127.0.0.1:{port}", time.perf_counter() - t0

    def _sigint(self, proc, tag: str) -> None:
        proc.send_signal(signal.SIGINT)
        try:
            rc = proc.wait(timeout=60)
        except subprocess.TimeoutExpired:
            raise PhaseFailed(f"serve[{tag}] ignored SIGINT for 60s")
        if rc != 0:
            raise PhaseFailed(f"serve[{tag}] exited {rc} on SIGINT\n"
                              + self._log_tail(proc))

    def _traces(self, base: str) -> float:
        series = self._http(f"{base}/metrics?format=json") \
            .get("runtime_jit_traces_total", {}).get("series", [])
        return sum(s["value"] for s in series)

    def _score(self, base: str, spec: dict, name: str):
        resp = self._http(f"{base}/score",
                          {**spec["body"], "deadline_ms": 60_000})
        scores = [row[name] for row in resp["scores"]]
        return ([s["probability_1"] for s in scores],
                [s["prediction"] for s in scores], resp["latency_ms"])

    def serve(self) -> dict:
        with open(os.path.join(self.work, "requests.json")) as fh:
            plan = json.load(fh)
        name, requests = plan["result_feature"], plan["requests"]
        res: dict = {}

        proc, base, boot_s = self._boot_serve("f32", [])
        health = self._http(f"{base}/healthz")
        if health["status"] != "ok":
            raise PhaseFailed(f"/healthz says {health['status']}")
        res["boot_s"] = round(boot_s, 1)
        res["buckets"] = health["buckets"]
        warm_traces = self._traces(base)
        worst, lat = 0.0, []
        for spec in requests:
            prob1, pred, latency_ms = self._score(base, spec, name)
            lat.append(round(latency_ms, 1))
            if len(prob1) != len(spec["prob1"]):
                raise PhaseFailed("served row count differs from request")
            diffs = [abs(a - b) for a, b in zip(prob1, spec["prob1"])]
            over = sum(d > PROB_ATOL for d in diffs)
            if over > int(PROB_OUTLIER_FRAC * len(diffs)):
                raise PhaseFailed(
                    f"served scores differ from the batch scores on "
                    f"{over}/{len(diffs)} rows (max {max(diffs):.3e})")
            worst = max(worst, max(diffs))
        recompiles = self._traces(base) - warm_traces
        res.update(requests=len(requests), max_abs_diff=worst,
                   latency_ms=lat, traces_at_warm=warm_traces,
                   traces_after_warm=recompiles)
        _say(f"[serve] f32: boot {boot_s:.1f}s, buckets {res['buckets']}, "
             f"{len(requests)} requests (sizes x wires) max |dp| vs batch "
             f"{worst:.3e}, latency_ms {lat}, scoring-program traces "
             f"after warm-up {recompiles}")
        if recompiles:
            raise PhaseFailed(f"{recompiles} scoring-program traces after "
                              "ladder warm-up")
        self._sigint(proc, "f32")

        proc, base, boot_s = self._boot_serve(
            "int8", ["--quantize", "int8-calibrated"])
        spec = next(s for s in requests if len(s["prob1"]) == 64)
        prob1, pred, _ = self._score(base, spec, name)
        agree = sum(a == b for a, b in zip(pred, spec["prediction"])) \
            / len(pred)
        qdiff = max(abs(a - b) for a, b in zip(prob1, spec["prob1"]))
        res["int8"] = {"boot_s": round(boot_s, 1), "rows": len(pred),
                       "prediction_agreement": agree,
                       "max_abs_diff": qdiff}
        _say(f"[serve] int8-calibrated: boot {boot_s:.1f}s, {len(pred)} "
             f"rows, prediction agreement {agree:.3f} (documented bound "
             f">= {QUANT_AGREE_MIN}), max |dp| {qdiff:.3e}")
        if agree < QUANT_AGREE_MIN:
            raise PhaseFailed(f"int8-calibrated agreement {agree:.3f}")
        self._sigint(proc, "int8")
        return res

    # -- the run ----------------------------------------------------------- #

    def run(self) -> int:
        phases: dict = {}
        try:
            t = time.perf_counter()
            ts = self.jax_child("train_score")
            phases["train_score"] = {
                **{k: ts[k] for k in (
                    "train_wall_s", "train_breakdown", "train_xla",
                    "n_fits", "best", "holdout_aupr", "score_cold_s",
                    "score_warm_s", "fused_vs_unfused", "stream_vs_fused",
                    "bytes_limit", "native", "compile_cache",
                    "store_at_start")},
                "wall_s": round(time.perf_counter() - t, 1)}
            t = time.perf_counter()
            phases["serve"] = {**self.serve(),
                               "wall_s": round(time.perf_counter() - t, 1)}
            if ts["device"]["count"] >= 4:
                t = time.perf_counter()
                mesh = self.jax_child("mesh")
                mesh.pop("device")
                phases["mesh"] = {
                    **mesh, "wall_s": round(time.perf_counter() - t, 1)}
            else:
                phases["mesh"] = f"skipped: {ts['device']['count']} device"
                _say(f"[mesh] {phases['mesh']}")
        except (PhaseFailed, OSError, KeyError, ValueError) as e:
            # OSError/KeyError/ValueError: a refused connection, an HTTP
            # error status or a malformed reply from the served process
            _say(f"chip_smoke FAILED: {type(e).__name__}: {e}")
            return 1
        finally:
            self.stop_all()
        summary = {"device": ts["device"], "phases": phases,
                   "wall_s": round(time.perf_counter() - self.t0, 1),
                   "claim": None}
        if self.rehearsal:
            summary = {"rehearsal": "cpu", **summary}
        with open(os.path.join(self.logs, "summary.json"), "w") as fh:
            json.dump(summary, fh)
        print(json.dumps(summary), flush=True)
        if not self.rehearsal:
            # the chip check reads this last line: exactly these keys
            dev = ts["device"]
            print(json.dumps({"ok": True, "device": {
                "platform": dev["platform"], "kind": dev["kind"],
                "count": dev["count"]}}), flush=True)
        return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--cpu-rehearsal", action="store_true",
                    help="tiny size on the CPU backend; prints no `ok`")
    ap.add_argument("--child", choices=sorted(CHILDREN),
                    help=argparse.SUPPRESS)
    ap.add_argument("--work", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if not os.path.isdir(os.path.join(HERE, "transmogrifai_tpu")):
        _say("chip_smoke FAILED: transmogrifai_tpu/ is not beside this "
             "script — it drives the repo it sits in")
        return 2
    if args.child:
        return run_child(args.child, args.work, args.cpu_rehearsal)
    return Parent(args.cpu_rehearsal).run()


if __name__ == "__main__":
    sys.exit(main())
