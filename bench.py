"""Benchmark: DEFAULT ModelSelector CV sweep wall-clock + scored rows/sec.

Workload (BASELINE.md config 1/4 shape, scaled to one chip): synthetic
tabular binary classification — rows × (20 numeric + 3 categorical)
features → transmogrify → SanityChecker → the DEFAULT
BinaryClassificationModelSelector sweep (LR + RandomForest + XGBoost grids,
`BinaryClassificationModelSelector.scala:62-137` parity — the full
reference grid: LR 8 elastic-net configs + RF 18 + XGB 2 (numRound 200,
early stopping 20) = 28 configs × 3-fold CV = 84 fits, batched into
vmapped XLA programs per family) → fused compiled scoring over the full
dataset.

Progressive emission: the main payload is printed the moment `run()`
completes, and every subsequent big-phase sub-result re-prints the MERGED
payload as a fresh JSON line — a reader of the LAST complete JSON line
keeps the already-measured sweep numbers if a later phase dies. A global
time budget (`BENCH_TIME_BUDGET` seconds, default 1140) gates each big
phase: phases that don't fit are skipped with an explicit `*_skipped`
reason. A mode that raises prints one diagnostic JSON line
(`"metric": "bench_error"`) and EXITS NON-ZERO.

`value` is scored rows/sec through the fused scorer (higher is better).
`vs_baseline` divides by BASELINE_ROWS_PER_SEC — an estimate of the
reference's Spark local[*] scoring throughput for an equivalent fitted
pipeline (the reference publishes no numbers; see BASELINE.md).

Modes: full (100k rows; needs an accelerator and fails without one) or
smoke (`BENCH_SMOKE=1` — 10k rows and lighter tree grids so the bench
finishes in minutes on the CPU; the JSON is tagged "mode": "smoke" and
still covers all three families). The size is chosen by that switch,
never inferred from the platform, and every payload names the platform
it ran on. Roofline fields exist only where the device's peak is known
(`PEAK_HBM_GBPS`): a CPU run carries none.
"""

import json
import os
import sys
import time
import traceback
import uuid

import numpy as np

BASELINE_ROWS_PER_SEC = 50_000.0  # documented estimate, BASELINE.md
# Spark local[*] estimate for the REFERENCE-SHAPED default sweep (84 fits:
# 24 LR elastic-net ~4s each + 54 RandomForest 50-tree ~60s each + 6
# XGBoost 200-round depth-10 ~90s each ≈ 3900s sequential, ÷2 for the
# parallelism-8 thread pool sharing local cores) — conservative, favors
# Spark; see BASELINE.md "Documented estimates". This is an ESTIMATE, not
# a measured Spark run (the image has no Spark/JVM); absolute wall-clock
# is the primary figure, the multiplier is secondary.
BASELINE_SWEEP_S = 1800.0

_T0 = time.perf_counter()


def _budget_s() -> float:
    return float(os.environ.get("BENCH_TIME_BUDGET", 1140.0))


def _remaining() -> float:
    """Seconds left in the global bench budget."""
    return _budget_s() - (time.perf_counter() - _T0)


_BENCH_ROOT = None     # bench-wide obs root span, opened by main()
_BENCH_ROOT_CM = None  # its context manager — MUST stay referenced: a
#                        dropped generator-CM is GC'd, which closes the
#                        span immediately and kills the whole rollup


def _emit(payload: dict) -> None:
    payload = dict(payload)
    payload["elapsed_s"] = round(time.perf_counter() - _T0, 1)
    if _BENCH_ROOT is not None:
        # goodput rollup over everything traced so far (recompile time,
        # retry backoff, ingest upload-wait): every re-emit carries the
        # newest decomposition, same contract as the other payload keys
        try:
            from transmogrifai_tpu.obs import goodput as _obs_goodput
            from transmogrifai_tpu.obs.trace import TRACER as _TRACER
            payload["goodput"] = _obs_goodput.build_report(
                _BENCH_ROOT,
                _TRACER.trace_spans(_BENCH_ROOT.trace_id)).to_json()
        except Exception as e:
            payload["goodput_error"] = f"{type(e).__name__}: {e}"
    print(json.dumps(payload))
    sys.stdout.flush()


# Peak HBM bandwidth by `jax.devices()[0].device_kind`, GB/s. Source:
# Google Cloud documentation, "TPU v5e" (16 GB HBM2E at 819 GB/s per
# chip). A kind that is not here is an error, not a default.
PEAK_HBM_GBPS = {"TPU v5 lite": 819.0}  # the kind a v5e reports


def _peak_hbm_bytes_per_s():
    """Roofline denominator for the device this process runs on: None on
    the CPU (no roofline fields are computed against a TPU's peak); an
    accelerator the table does not know raises."""
    import jax
    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    if dev.device_kind not in PEAK_HBM_GBPS:
        raise RuntimeError(
            f"no peak HBM bandwidth recorded for device_kind "
            f"{dev.device_kind!r}; add it to PEAK_HBM_GBPS with its source")
    return PEAK_HBM_GBPS[dev.device_kind] * 1e9


def _measure_fused(scorer, encs, raw_dev, repeats: int = 3) -> dict:
    """Shared measurement protocol for the fused scoring program at one
    input shape: XLA "bytes accessed" + flops from cost analysis, warm
    device execution averaged over `repeats`, derived bytes/s and —
    where the device's peak is known — `hbm_frac` against it."""
    import jax
    jfn = scorer.fused_jitted()
    ca = jfn.lower(scorer._consts, encs, raw_dev).compile() \
        .cost_analysis()
    jax.block_until_ready(jfn(scorer._consts, encs, raw_dev))  # warm
    t0 = time.perf_counter()
    for _ in range(repeats):
        jax.block_until_ready(jfn(scorer._consts, encs, raw_dev))
    dev_s = (time.perf_counter() - t0) / repeats
    out = {"dev_s": dev_s, "flops": float(ca.get("flops", 0.0)),
           "bytes_accessed": float(ca.get("bytes accessed", 0.0))}
    if out["bytes_accessed"] > 0 and dev_s > 0:
        out["bytes_per_sec"] = out["bytes_accessed"] / dev_s
        peak = _peak_hbm_bytes_per_s()
        if peak is not None:
            out["hbm_frac"] = out["bytes_per_sec"] / peak
    return out


def score_roofline(model, ds, repeats: int = 3) -> dict:
    """Measured HBM-roofline numbers for the fused scoring program on
    `ds`'s batch shape: XLA's "bytes accessed" (the bytes the compiled
    program actually touches, device dtype widths post-quantization)
    over the measured warm device execution; `scoring_hbm_frac` is that
    as a fraction of the device's peak bandwidth and is absent on the
    CPU. A failed compile or cost analysis raises."""
    scorer = model._compiled or model._ensure_compiled()
    encs, raw_dev, _ = scorer.host_phase(ds)
    m = _measure_fused(scorer, encs, raw_dev, repeats)
    out = {"score_device_s": m["dev_s"], "scoring_flops": m["flops"]}
    if "bytes_per_sec" in m:
        out["scoring_bytes_accessed"] = m["bytes_accessed"]
        out["scoring_bytes_per_sec"] = round(m["bytes_per_sec"], 1)
    if "hbm_frac" in m:
        out["scoring_hbm_frac"] = round(m["hbm_frac"], 6)
    return out


def smoke_mode() -> bool:
    """The workload size is the caller's choice, never the platform's."""
    return os.environ.get("BENCH_SMOKE") == "1"


def probe_backend() -> str:
    """Initialize the JAX backend up front and name its platform. No
    retry and no fallback: a backend that fails to initialize raises,
    and a full-size run that finds only the CPU fails here instead of
    quietly measuring something else."""
    import jax
    from transmogrifai_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    platform = jax.devices()[0].platform
    if platform == "cpu" and not smoke_mode():
        raise RuntimeError(
            "full-size bench needs an accelerator and JAX found only the "
            "CPU; BENCH_SMOKE=1 runs the smoke size here")
    return platform


def make_data(n, n_numeric=20, seed=7):
    from transmogrifai_tpu.data import Dataset
    import transmogrifai_tpu.types as t
    rng = np.random.default_rng(seed)
    cols, schema = {}, {}
    # strong planted signal (best real model AuPR ≈ 0.85+): a weak-signal
    # dataset lets zero-split min_info_gain=0.1 grid configs win on the
    # Spark-parity constant-scorer AuPR artifact ((1+prevalence)/2)
    w = 2.5 * rng.normal(size=n_numeric) / np.sqrt(n_numeric)
    Xn = rng.normal(size=(n, n_numeric))
    logits = Xn @ w + 0.9 * Xn[:, 0] * Xn[:, 1]
    for j in range(n_numeric):
        vals = Xn[:, j].astype(np.float64).copy()
        vals[rng.uniform(size=n) < 0.05] = np.nan  # typed numeric storage
        cols[f"num{j}"] = vals
        schema[f"num{j}"] = t.Real
    for name, levels, effect in (("cat_a", ["u", "v", "w"], 0.8),
                                 ("cat_b", ["x", "y"], -0.5),
                                 ("cat_c", ["p", "q", "r", "s"], 0.3)):
        ids = rng.integers(len(levels), size=n)
        logits = logits + effect * (ids == 0)
        cols[name] = np.array(levels, dtype=object)[ids]
        schema[name] = t.PickList
    y = (rng.uniform(size=n) < 1.0 / (1.0 + np.exp(-logits))).astype(int)
    cols["label"] = y.astype(np.float64)
    schema["label"] = t.Integral
    return Dataset(cols, schema)


def default_models(smoke: bool):
    """Full mode = the selector's OWN defaults (LR + RF + XGB,
    BinaryClassificationModelSelector.scala:62-64 parity — one source of
    truth in selector/model_selector.py). Smoke mode keeps all three
    families but shrinks forests/depths so a CPU run finishes within the
    driver's budget."""
    if not smoke:
        from transmogrifai_tpu.selector.model_selector import (
            _default_binary_models)
        return _default_binary_models()
    from transmogrifai_tpu.models import (
        OpLogisticRegression, OpRandomForestClassifier, OpXGBoostClassifier)
    lr_grid = [{"reg_param": r} for r in (0.001, 0.01, 0.1, 0.2)]
    rf_grid = [{"max_depth": d, "min_child_weight": m}
               for d in (3, 6) for m in (1.0, 10.0)]
    xgb_grid = [{"eta": e, "max_depth": d}
                for e in (0.1, 0.3) for d in (3,)]
    return [(OpLogisticRegression(max_iter=30), lr_grid),
            (OpRandomForestClassifier(n_trees=5, max_bins=32), rf_grid),
            (OpXGBoostClassifier(n_estimators=10, max_bins=32), xgb_grid)]


def run(platform: str) -> dict:
    import jax
    from transmogrifai_tpu.automl import transmogrify
    from transmogrifai_tpu.automl.sanity_checker import SanityChecker
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.selector import (
        BinaryClassificationModelSelector, DataSplitter)
    from transmogrifai_tpu.workflow import Workflow

    smoke = smoke_mode()
    n_rows = 10_000 if smoke else 100_000

    t0 = time.perf_counter()
    ds = make_data(n_rows)
    t_data = time.perf_counter() - t0

    preds, label = FeatureBuilder.from_dataset(ds, response="label")
    vector = transmogrify(preds)
    checked = SanityChecker().set_input(label, vector).get_output()
    models = default_models(smoke)
    n_fits = 3 * sum(len(g) for _, g in models)
    selector = BinaryClassificationModelSelector.with_cross_validation(
        models=models, n_folds=3,
        splitter=DataSplitter(reserve_test_fraction=0.1))
    pf = selector.set_input(label, checked).get_output()

    t0 = time.perf_counter()
    model = Workflow().set_result_features(pf, label).set_input_dataset(ds).train()
    t_train = time.perf_counter() - t0  # cold: includes every XLA compile

    fitted = model.fitted[pf.origin_stage.uid]
    holdout = fitted.summary.holdout_metrics

    # warm sweep-only: refit the selector on the already-materialized
    # columns — the steady-state default-sweep cost, which is what
    # BASELINE_SWEEP_S estimates for the reference. The full default sweep
    # is exec-bound (42 real fits incl. 20-tree depth-12 forests), so the
    # warm pass nearly doubles bench wall-clock — opt-in (BENCH_WARM=1) in
    # full mode to keep the driver run inside its budget; always on in
    # smoke mode where it is cheap.
    # adaptive: a fast cold train means the persistent compile cache was
    # warm, so the warm-sweep pass fits comfortably inside the budget —
    # and the global budget must still cover streaming + the big phase
    t_sweep_warm = None
    sweep_dispatch_fraction = None
    sweep_compile_s = None
    if smoke or os.environ.get("BENCH_WARM") == "1" or (
            t_train < 300 and _remaining() > t_train + 600):
        from transmogrifai_tpu.obs.trace import TRACER
        from transmogrifai_tpu.parallel.sweep import SWEEP_STATS
        from transmogrifai_tpu.stages.base import FitContext
        sel_stage = pf.origin_stage
        sel_est = getattr(sel_stage, "_estimator", sel_stage)
        sel_inputs = [model.train_columns[f.uid]
                      for f in sel_stage.input_features]
        SWEEP_STATS.reset()
        mark = max((sp.span_id for sp in TRACER.spans()), default=0)
        t0 = time.perf_counter()
        sel_est.fit(sel_inputs, FitContext(n_rows=n_rows, seed=43))
        t_sweep_warm = time.perf_counter() - t0
        # device-dispatch occupancy of the sweep wall-clock + the XLA
        # compile seconds its dispatches asked for, measured (the
        # `compile:*` spans of utils/compile_cache.py; thread-seconds)
        # can exceed 1.0: dispatch seconds SUM across the family thread
        # pool while t_sweep_warm is wall-clock, so >1 simply means
        # families overlapped (the reference's Parallelism=8 analogue)
        sweep_dispatch_fraction = SWEEP_STATS.dispatch_s / t_sweep_warm
        sweep_compile_s = sum(
            sp.duration_s for sp in TRACER.spans() if sp.span_id > mark
            and sp.name.startswith("compile:sweep:dispatch:"))

    # fused scoring: warm up (compile), then measure
    t0 = time.perf_counter()
    out = model.score_compiled(ds)
    jax.block_until_ready(out[pf.name])
    t_compile_score = time.perf_counter() - t0
    t0 = time.perf_counter()
    out = model.score_compiled(ds)
    jax.block_until_ready(out[pf.name])
    t_score = time.perf_counter() - t0
    rows_per_sec = n_rows / t_score

    # HBM roofline of the fused scoring program (arxiv 2008.01040):
    # tabular scoring is memory-bound, so the honest
    # utilization number is achieved bytes/s against peak HBM bandwidth
    # — not MFU, which reads ~1e-6 on a workload whose arithmetic
    # intensity is a few FLOPs/byte. Bytes are XLA's own "bytes
    # accessed" estimate of the compiled program (device dtype widths
    # post-quantization included); time is the measured warm device
    # execution (host phase excluded). FLOPs stay as a secondary field.
    roofline = score_roofline(model, ds)
    score_device_s = roofline.get("score_device_s")

    # streaming micro-batch scoring: parquet batches, host encode of batch
    # i+1 overlapped with device compute of batch i (score_stream)
    import tempfile
    from transmogrifai_tpu.readers import DataReaders
    pq_path = os.path.join(tempfile.mkdtemp(), "bench.parquet")
    ds.to_parquet(pq_path)
    # Full-size micro-batches: the batch IS the whole 100k-row file per
    # pass (a size chosen before PR 21's first direct chip run; not
    # re-measured). SUSTAINED run: a feeder thread keeps re-reading the parquet into a bounded queue (so file
    # reads overlap scoring) and passes keep flowing until a wall-clock
    # target is hit (BENCH_STREAM_S, default 90s full mode, budget
    # permitting) — steady-state rows/s, not a 2-pass burst.
    import queue as _queue
    import threading as _threading
    batch = n_rows
    reader = DataReaders.stream(parquet_path=pq_path, batch_size=batch,
                                schema=dict(ds.schema))
    # coalesce default 0: the async dispatch pipeline (device_depth +
    # grouped fetch) already overlaps per-dispatch latency, and the
    # host-side concat lands on the critical path.
    coalesce = int(os.environ.get("BENCH_COALESCE_ROWS", 0))

    def _warm_batches():
        for _ in range(max(1, -(-max(coalesce, 1) // batch))):
            yield from reader.stream()

    # warm the measured dispatch shape (coalesced when enabled)
    for sout in model.score_stream(_warm_batches(), coalesce_rows=coalesce):
        np.asarray(sout[pf.name]["prediction"])
        break
    if smoke:
        stream_target_s = 0.0
    elif _remaining() < 60.0:
        # budget already blown: shortest honest measurement, so the phase
        # still reports a number instead of pushing past the driver kill
        stream_target_s = 0.0
    else:
        stream_target_s = min(float(os.environ.get("BENCH_STREAM_S", 90.0)),
                              max(30.0, _remaining() - 520.0))
    stop = _threading.Event()
    feed_q: "_queue.Queue" = _queue.Queue(maxsize=6)
    # one parquet pass decodes in ~0.76s on this host — with grouped
    # result fetches the reader became the streaming bottleneck, so
    # several feeder threads each run independent passes
    n_feeders = 3

    def _feeder():
        while not stop.is_set():
            for b in reader.stream():
                # bounded put that re-checks stop: a feeder must never
                # block forever on a full queue after the deadline (it
                # would pin batches and contend with later host timing)
                while not stop.is_set():
                    try:
                        feed_q.put(b, timeout=0.2)
                        break
                    except _queue.Full:
                        continue
                if stop.is_set():
                    break
        try:
            feed_q.put_nowait(None)
        except _queue.Full:
            pass

    for _ in range(n_feeders):
        _threading.Thread(target=_feeder, daemon=True).start()

    def _batches():
        min_batches = 2 if smoke else 1
        got = 0
        while True:
            b = feed_q.get()
            if b is None:
                return
            yield b
            got += 1
            if got >= min_batches and time.perf_counter() - t0 >= stream_target_s:
                stop.set()
                # drain so the feeder's blocking put can see the stop
                while True:
                    try:
                        if feed_q.get_nowait() is None:
                            return
                    except _queue.Empty:
                        return

    t0 = time.perf_counter()
    streamed = 0
    n_passes = 0
    # fetch_group=8: one packed-buffer materialization per 8 batches
    # (see score_stream); the value is not re-measured since PR 21
    for sout in model.score_stream(_batches(), host_workers=3,
                                   device_depth=3, fetch_group=8,
                                   coalesce_rows=coalesce):
        streamed += int(np.asarray(sout[pf.name]["prediction"]).shape[0])
        n_passes += 1
    t_stream = time.perf_counter() - t0
    stream_rows_per_sec = streamed / t_stream
    # host-encode fraction of streaming wall-clock (pipelined encode runs
    # in worker threads; <0.5 means the device path, not host string
    # work, bounds throughput)
    bds = next(iter(reader.stream()))
    model._compiled.host_phase(bds)
    t0 = time.perf_counter()
    for _ in range(4):
        model._compiled.host_phase(bds)
    host_s_per_batch = (time.perf_counter() - t0) / 4
    stream_host_fraction = (host_s_per_batch * (streamed / batch)) / t_stream

    return {
        "metric": "fused_scoring_rows_per_sec",
        "value": round(rows_per_sec, 1),
        "unit": "rows/sec",
        "vs_baseline": round(rows_per_sec / BASELINE_ROWS_PER_SEC, 3),
        "mode": "smoke" if smoke else "full",
        "train_wall_s": round(t_train, 2),
        "sweep_warm_s": (round(t_sweep_warm, 2)
                         if t_sweep_warm is not None else None),
        # the baseline estimates the FULL default sweep; a smoke-sized
        # sweep is not comparable, so don't report a fake speedup
        "sweep_vs_baseline": (round(BASELINE_SWEEP_S / t_sweep_warm, 3)
                              if (not smoke and t_sweep_warm is not None)
                              else None),
        "sweep_fits": n_fits,
        "sweep_families": "LR+RF+XGB (default)",
        "n_rows": n_rows,
        "stream_rows_per_sec": round(stream_rows_per_sec, 1),
        "stream_sustained_s": round(t_stream, 1),
        "stream_passes": n_passes,
        "stream_host_fraction": round(stream_host_fraction, 3),
        # the sweep baseline is a documented ESTIMATE (no Spark in image);
        # absolute sweep_warm_s is primary, the multiplier secondary
        "sweep_baseline_estimate_s": BASELINE_SWEEP_S,
        "sweep_dispatch_fraction": (round(sweep_dispatch_fraction, 3)
                                    if sweep_dispatch_fraction is not None
                                    else None),
        "sweep_compile_s": (round(sweep_compile_s, 1)
                            if sweep_compile_s is not None else None),
        # headline roofline fields; scoring_flops is secondary context
        # absent (not null) where the device's peak is unknown: the CPU
        **({"scoring_hbm_frac": roofline["scoring_hbm_frac"]}
           if "scoring_hbm_frac" in roofline else {}),
        "scoring_bytes_per_sec": roofline.get("scoring_bytes_per_sec"),
        "scoring_bytes_accessed": roofline.get("scoring_bytes_accessed"),
        "scoring_flops": roofline.get("scoring_flops"),
        "score_device_s": (round(score_device_s, 4)
                           if score_device_s is not None else None),
        "holdout_aupr": round(holdout.get("AuPR", 0.0), 4),
        "holdout_auroc": round(holdout.get("AuROC", 0.0), 4),
        # clamp: on a fully warm cache the two timings differ by clock
        # noise and the subtraction can land slightly negative
        "score_compile_s": round(max(t_compile_score - t_score, 0.0), 2),
        "datagen_s": round(t_data, 2),
        "platform": platform,
    }


def _host_binned_aupr(y: np.ndarray, scores: np.ndarray,
                      mask: np.ndarray, n_bins: int = 4096) -> float:
    """Tie-grouped PR trapezoid over `n_bins` score buckets (host numpy;
    matches `aupr_binned_dev`)."""
    b = np.minimum((np.clip(scores, 0, 1) * n_bins).astype(np.int64),
                   n_bins - 1)
    hp = np.bincount(b, weights=mask * y, minlength=n_bins)
    ha = np.bincount(b, weights=mask, minlength=n_bins)
    tp = np.cumsum(hp[::-1])
    n_at = np.cumsum(ha[::-1])
    n_pos = tp[-1]
    if n_pos <= 0:
        return 0.0
    prec = np.where(n_at > 0, tp / np.maximum(n_at, 1e-30), 1.0)
    rec = tp / n_pos
    r = np.concatenate([[0.0], rec])
    p = np.concatenate([[1.0], prec])
    return float(((r[1:] - r[:-1]) * (p[1:] + p[:-1]) * 0.5).sum())


def run_big(platform: str, payload: dict) -> None:
    """BASELINE target 4 proof (10M rows × 500 features):
    out-of-core columnar ingestion (memmapped f16 store, never
    materialized on host) → device-resident bf16 / int8-binned buffers →
    the default-selector workload at 10M: the FULL 24-fit elastic-net LR
    sweep (grids stacked into one matmul output dim, X read once per
    FISTA step) runs live; tree families run a measured slice (depth-6
    forest trees + boosting rounds) and the full reference-shaped 84-fit
    sweep cost is extrapolated from the measured per-unit costs with the
    level-cost model documented in BASELINE.md. Scoring = one pass of
    the stacked-grid predict. Memory plan: parallel/bigdata.py header.

    Driver-survivable: merges each completed sub-phase into `payload`
    and RE-EMITS the merged line, so a timeout loses at most the phase
    in flight. Phases that don't fit `_remaining()` are skipped with an
    explicit `big_*_skipped` reason."""
    import gc

    import jax
    import jax.numpy as jnp
    from transmogrifai_tpu.data.columnar_store import (
        MANIFEST, synth_binary_store)
    from transmogrifai_tpu.parallel import bigdata as bd

    n_rows = int(os.environ.get("BENCH_BIG_ROWS", 10_000_000))
    d = int(os.environ.get("BENCH_BIG_D", 500))
    from transmogrifai_tpu.store.config import cache_root
    path = os.path.join(cache_root(), f"bigbench/{n_rows}x{d}")

    def note(msg):
        print(f"[big] {msg}", file=sys.stderr, flush=True)

    # ---- phase gates ------------------------------------------------- #
    # mirror synth_binary_store's reuse predicate exactly: a manifest
    # without matching generation params will REGENERATE (~300s), so it
    # must budget like a cache miss
    store_cached = False
    try:
        with open(os.path.join(path, MANIFEST)) as fh:
            m = json.load(fh)
        store_cached = (m.get("n_rows") == n_rows
                        and m.get("n_features") == d
                        and m.get("synth_seed") == 11
                        and m.get("synth_informative") == 20)
    except Exception:
        pass
    need = 360.0 if store_cached else 700.0  # fresh 10 GB gen ~300s extra
    if _remaining() < need:
        payload["big_skipped"] = (
            f"{_remaining():.0f}s budget left < {need:.0f}s needed "
            f"(store_cached={store_cached})")
        _emit(payload)
        return

    t0 = time.perf_counter()
    store = synth_binary_store(path, n_rows, d, seed=11)
    t_gen = time.perf_counter() - t0
    payload["big_rows"] = n_rows
    payload["big_d"] = d
    payload["big_datagen_s"] = round(t_gen, 1)

    note(f"store ready ({t_gen:.0f}s)")
    n_pad = -(-n_rows // bd.UPLOAD_CHUNK_ROWS) * bd.UPLOAD_CHUNK_ROWS
    y = np.zeros(n_pad, np.float32)
    y[:n_rows] = np.asarray(store.y, np.float32)
    y_dev = jnp.asarray(y)
    # 3-fold masks over the real rows; pad rows carry zero weight. Masks
    # stay on HOST — one (n,) f32 pair moves to device per fold, keeping
    # HBM for the 10 GB X buffer.
    fold_of = np.arange(n_pad) % 3
    fold_of[n_rows:] = -1
    W_np = [(fold_of != f) & (fold_of >= 0) for f in range(3)]
    V_np = [fold_of == f for f in range(3)]

    # ---- tree families FIRST: the lockstep tree measurements run before
    # the LR phase so a slow LR phase cannot eat the budget before they
    # are captured ----------------------------------------------------- #
    def _emit_extrapolation(lr3_s: float, rf_s: float, xgb_s: float,
                            estimated_lr: bool,
                            estimated_xgb: bool = False) -> None:
        payload["big_lr_estimated"] = estimated_lr
        if estimated_xgb:
            payload["big_xgb_estimated"] = True
        total = lr3_s + rf_s + xgb_s
        payload["big_sweep84_extrapolated_s"] = round(total, 1)
        # the sweep axis (grids × folds × trees) is embarrassingly
        # parallel, so the scaled figures divide the single-chip
        # extrapolation by the chip count — a perfect-packing MODEL.
        # `python bench.py multichip` MEASURES the same-chip-count
        # figure with the real work-stealing scheduler
        # (big_sweep_mesh<N>_measured_s + mesh_utilization_frac), so
        # r06+ rounds carry a measured-vs-modeled pair at ONE chip
        # count instead of extrapolation alone.
        n_mesh = int(os.environ.get("BENCH_MESH_DEVICES", 8))
        payload[f"big_sweep84_mesh{n_mesh}_extrapolated_s"] = round(
            total / n_mesh, 1)
        payload["big_sweep84_pod256_extrapolated_s"] = round(total / 256.0, 1)
        # honesty layer: the learned cost model's prediction for the
        # same 84-fit sweep, WITH residual-quantile error bars — when
        # the corpus is warm this replaces the bare scale() level-cost
        # model as the quoted figure (value/lo/hi + training support)
        try:
            from transmogrifai_tpu.perf.model import predict_sweep_seconds
            from transmogrifai_tpu.selector.model_selector import (
                _default_binary_models)
            predicted = predict_sweep_seconds(
                _default_binary_models(), n_rows=n_pad, n_cols=d,
                n_folds=3, dtype_bytes=2)
            if predicted is not None:
                payload["big_sweep84_model_s"] = predicted
        except Exception as e:
            payload["big_sweep84_model_err"] = f"{type(e).__name__}: {e}"[:200]

    t0 = time.perf_counter()
    edges = store.quantile_edges(32)
    rf_s = xgb_s = None
    # pipelined ingest (data/pipeline.py): worker threads read+cast
    # chunks while up to `depth` donated writes are in flight.
    # env knobs pin the pipeline shape; unset, the learned cost model
    # picks workers/depth from the predicted read-vs-upload balance
    # (cold corpus -> the UPLOAD_WORKERS/UPLOAD_DEPTH defaults exactly)
    _w = os.environ.get("BENCH_UPLOAD_WORKERS")
    _d = os.environ.get("BENCH_UPLOAD_DEPTH")
    up_workers = int(_w) if _w else None
    up_depth = int(_d) if _d else None
    from transmogrifai_tpu.utils.profiling import RunProfile
    ingest_prof = RunProfile(run_type="bench-big-ingest")
    # persistent device-matrix cache (data/feature_cache.py):
    # BENCH_FEATURE_CACHE=read|readwrite replays the content-addressed
    # wire artifact on repeat runs — the warm path skips the store
    # sweep entirely (big_upload_warm_s vs big_upload_cold_s below);
    # BENCH_FEATURE_CACHE_WIRE=int8|int4 compresses the cold wire too
    cache_env = os.environ.get("BENCH_FEATURE_CACHE", "off").lower()
    bench_cache = "off"
    if cache_env in ("read", "readwrite"):
        from transmogrifai_tpu.data.feature_cache import FeatureCacheParams
        bench_cache = FeatureCacheParams(
            # None falls through to resolved_dir(): the shared
            # TRANSMOGRIFAI_FEATURE_CACHE_DIR env / default path
            dir=os.environ.get("BENCH_FEATURE_CACHE_DIR"),
            policy=cache_env,
            wire=os.environ.get("BENCH_FEATURE_CACHE_WIRE", "auto"),
            # size-only artifact verify: a full sha256 pass re-reads the
            # multi-GB artifact before every warm replay
            verify="size")

    def _note_upload_cache(stats, prefix="big_upload"):
        payload[f"{prefix}_cache"] = stats.cache or "off"
        if stats.wire:
            payload[f"{prefix}_wire"] = stats.wire
        key = f"{prefix}_warm_s" if stats.cache_hit else f"{prefix}_cold_s"
        payload[key] = round(stats.wall_s, 1)
        if stats.bytes_saved_wire:
            payload[f"{prefix}_wire_compression"] = round(
                (stats.bytes_wire + stats.bytes_saved_wire)
                / max(stats.bytes_wire, 1), 2)
    # one-pass dual-representation build: bf16 + int8 from a SINGLE
    # store sweep (one memmap read, one f16 wire pass) — but both
    # buffers resident is 3 bytes/elem, plus the tree phase's ~2.5 GB
    # of one-hot working set, so gate on the HBM plan actually fitting
    # (10M×500 on a 16 GB v5e does NOT fit: 15 GB + 2.5 GB working set;
    # BENCH_BIG_DUAL=1/0 forces, BENCH_HBM_GB overrides the budget)
    hbm_gb = float(os.environ.get("BENCH_HBM_GB", 16.0))
    dual_env = os.environ.get("BENCH_BIG_DUAL", "auto")
    dual_fits = n_pad * d * 3 + 3.0e9 < hbm_gb * 1e9
    use_dual = dual_env == "1" or (dual_env == "auto" and dual_fits)
    payload["big_ingest_dual"] = use_dual
    X16 = None
    try:
        # leave ≥180s of budget for the lockstep measurements themselves
        deadline = max(_remaining() - 180.0, 60.0)
        if use_dual:
            X16, Xb, up_stats = bd.dual_device_matrices(
                store, edges, deadline_s=deadline, workers=up_workers,
                depth=up_depth, profile=ingest_prof, return_stats=True,
                cache=bench_cache)
        else:
            Xb, up_stats = bd.device_binned(
                store, edges, deadline_s=deadline, workers=up_workers,
                depth=up_depth, profile=ingest_prof, return_stats=True,
                cache=bench_cache)
    except TimeoutError as e:
        payload["big_trees_skipped"] = f"bin upload too slow: {e}"
        _emit(payload)
        X16 = None
        Xb = None  # fall through: the LR phase may still fit the budget
    if Xb is not None:
        payload["big_upload_gbps"] = round(up_stats.gbps, 4)
        payload["big_upload_overlap_frac"] = round(up_stats.overlap_frac, 3)
        payload["big_upload_workers"] = up_stats.workers
        payload["big_upload_depth"] = up_stats.depth
        if up_stats.plan:
            payload["big_upload_plan"] = up_stats.plan
            payload["big_upload_predicted_s"] = round(
                up_stats.predicted_wall_s, 1)
        _note_upload_cache(up_stats)
        payload["big_ingest_phases"] = [p.to_json()
                                        for p in ingest_prof.phases]
    if Xb is not None and _remaining() < 120:
        # the upload consumed the phase budget: skip the lockstep fits
        # (warmup + timed batches need ~2 min) instead of overrunning
        payload["big_trees_skipped"] = (
            f"{_remaining():.0f}s left after bin upload (<120s)")
        _emit(payload)
        del Xb
        gc.collect()
        Xb = None
    if Xb is not None:
        jax.block_until_ready(Xb)
        t_binned = time.perf_counter() - t0
        payload["big_bin_upload_s"] = round(t_binned, 1)
        Y1 = jax.nn.one_hot(y_dev.astype(jnp.int32), 2)
        w_full = jnp.asarray(W_np[0], jnp.float32)

        # LOCKSTEP measurement: trees/pairs grow level-synchronized
        # sharing each chunk's bin one-hot — the dominant out-of-core
        # cost — so the honest per-tree figure is the amortized batch
        # cost. Warm each program shape once so the measured per-unit
        # costs are steady-state execution, not XLA compile time;
        # the K-tree batch is ONE compiled shape reused by the timed run.
        RF_K = 16
        np.asarray(bd.fit_forest_big(
            Xb, Y1, w_full, RF_K, 6, 32, 2, seed=3,
            trees_per_dispatch=RF_K)["leaf"])
        t0 = time.perf_counter()
        trees = bd.fit_forest_big(Xb, Y1, w_full, RF_K, 6, 32, 2, seed=3,
                                  trees_per_dispatch=RF_K)
        np.asarray(trees["leaf"])  # host materialization closes timing
        per_tree_d6 = (time.perf_counter() - t0) / RF_K
        payload["big_rf_tree_d6_s"] = round(per_tree_d6, 2)
        payload["big_rf_lockstep_k"] = RF_K
        _emit(payload)  # RF lockstep number driver-captured from here on

        # level-cost model: a depth-D learner costs ≈ per_d6 · ΣD/Σ6
        # where Σℓ = 2^ℓ − 1 node-levels (histogram work doubles per
        # level); scale() feeds the 84-fit extrapolation below
        def scale(depth):
            return (2.0 ** depth - 1) / (2.0 ** 6 - 1)
        rf_s = 18 * (scale(3) + scale(6) + scale(12)) * 50 * per_tree_d6

        # GBT: the big-sweep shape is 2 XGB configs × 3 folds = 6 pairs;
        # one lockstep round grows all 6 pair-trees vs shared one-hots
        if _remaining() < 90:
            payload["big_gbt_skipped"] = (
                f"{_remaining():.0f}s left after RF lockstep (<90s)")
            # estimate the XGB term from the MEASURED RF per-tree cost:
            # the chunk one-hot stream cost is FLAT in K, so a 6-pair
            # round costs about the full K-batch (per_tree·RF_K) plus
            # ~50% margin/gradient overhead — flagged big_xgb_estimated
            xgb_est = 200 * scale(10) * (per_tree_d6 * RF_K * 1.5)
            _emit_extrapolation(75.0, rf_s, xgb_est, estimated_lr=True,
                                estimated_xgb=True)
            payload["big_lr_skipped"] = "budget exhausted with GBT"
            del Xb, trees
            gc.collect()
            _emit(payload)
            note("tree families freed (GBT skipped)")
            return
        w6 = jnp.tile(w_full[None], (6, 1))
        np.asarray(bd.fit_gbt_big_lockstep(
            Xb, y_dev, w6, 1, 6, 32, 0.1, 1.0, "logistic")[1])
        t0 = time.perf_counter()
        _, margin = bd.fit_gbt_big_lockstep(
            Xb, y_dev, w6, 2, 6, 32, 0.1, 1.0, "logistic")
        np.asarray(margin)
        round6_d6 = (time.perf_counter() - t0) / 2.0  # one 6-pair round
        payload["big_gbt_round6p_d6_s"] = round(round6_d6, 2)
        payload["big_gbt_round_d6_s"] = round(round6_d6 / 6.0, 2)

        # The full reference-shaped 84-fit sweep at 10M×500:
        #   RF 54 fits × 50 trees, depth {3,6,12} — lockstep-amortized
        #     per-tree cost (lockstep_width shrinks K for deep trees,
        #     roughly offset by the flat-cost regime shallow levels
        #     stay in)
        #   XGB 6 fits × 200 rounds, depth 10 — ONE 6-pair lockstep
        #     round per boosting round covers all 6 fits
        #   LR 24 fits — measured below when the budget allows; until
        #     then 75s enters (an earlier round's figure, never
        #     re-measured on a directly attached chip), flagged estimated
        xgb_s = 200 * scale(10) * round6_d6
        _emit_extrapolation(75.0, rf_s, xgb_s, estimated_lr=True)
        _emit(payload)

        # the XGB term dominates the extrapolation and the scale() model
        # OVERSTATES it: lockstep level cost is flat until the histogram
        # output rows (K·p·2^ℓ) leave the MXU tile regime, so a depth-10
        # round costs far less than 16.2× the depth-6 round. Measure ONE
        # real depth-10 6-pair round when the budget allows and replace
        # the modeled term with 200 × the measurement.
        if _remaining() > 300:
            note("depth-10 GBT round (compile+warm) ...")
            try:
                np.asarray(bd.fit_gbt_big_lockstep(
                    Xb, y_dev, w6, 1, 10, 32, 0.1, 1.0, "logistic")[1])
                t0 = time.perf_counter()
                _, m10 = bd.fit_gbt_big_lockstep(
                    Xb, y_dev, w6, 1, 10, 32, 0.1, 1.0, "logistic")
                np.asarray(m10)
                round6_d10 = time.perf_counter() - t0
                payload["big_gbt_round6p_d10_s"] = round(round6_d10, 2)
                xgb_s = 200 * round6_d10
                _emit_extrapolation(75.0, rf_s, xgb_s, estimated_lr=True)
                del m10
            except Exception as e:  # OOM/compile failure degrades to model
                payload["big_gbt_d10_error"] = f"{type(e).__name__}: {e}"[:300]
        else:
            payload["big_gbt_d10_skipped"] = (
                f"{_remaining():.0f}s left (<300s); xgb term uses the "
                "scale() model")

        # RF depth-12 — the LAST modeled extrapolation term (the 18
        # depth-12 configs dominate the RF sum at scale(12)=63.5×).
        # fit_forest_big picks K=1 at depth 12 (lockstep_width's
        # dispatch-time bound), so one real single-tree fit IS the cost
        # the sweep would pay per depth-12 tree.
        if _remaining() > 300:
            note("depth-12 RF tree (compile+warm) ...")
            try:
                np.asarray(bd.fit_forest_big(
                    Xb, Y1, w_full, 1, 12, 32, 2, seed=5)["leaf"])
                t0 = time.perf_counter()
                t12 = bd.fit_forest_big(Xb, Y1, w_full, 1, 12, 32, 2,
                                        seed=5)
                np.asarray(t12["leaf"])
                per_tree_d12 = time.perf_counter() - t0
                payload["big_rf_tree_d12_s"] = round(per_tree_d12, 2)
                rf_s = 18 * 50 * ((scale(3) + 1.0) * per_tree_d6
                                  + per_tree_d12)
                _emit_extrapolation(75.0, rf_s, xgb_s, estimated_lr=True)
                del t12
            except Exception as e:
                payload["big_rf_d12_error"] = f"{type(e).__name__}: {e}"[:300]
        else:
            payload["big_rf_d12_skipped"] = (
                f"{_remaining():.0f}s left (<300s); rf term uses the "
                "scale() model")
        del Xb, trees, margin
        gc.collect()
        _emit(payload)
        note("tree families freed; uploading bf16")

    # ---- linear family: full default 8-grid × 3-fold elastic-net sweep #
    if _remaining() < 200:
        payload["big_lr_skipped"] = f"{_remaining():.0f}s left (<200s)"
        _emit(payload)
        return
    t0 = time.perf_counter()
    if X16 is None:
        try:
            X16, bf_stats = bd.device_matrix(
                store, deadline_s=max(_remaining() - 150.0, 60.0),
                workers=up_workers, depth=up_depth, profile=ingest_prof,
                return_stats=True, cache=bench_cache)
        except TimeoutError as e:
            payload["big_lr_skipped"] = f"bf16 upload too slow: {e}"
            _emit(payload)
            return
        jax.block_until_ready(X16)
        payload["big_upload_bf16_s"] = round(time.perf_counter() - t0, 1)
        payload["big_upload_bf16_gbps"] = round(bf_stats.gbps, 4)
        _note_upload_cache(bf_stats, prefix="big_upload_bf16")
        payload["big_ingest_phases"] = [p.to_json()
                                        for p in ingest_prof.phases]
    # dual path: the bf16 matrix came out of the one-pass build, so
    # there is no separate bf16 upload to time — big_ingest_dual marks
    # it and big_bin_upload_s carries the (combined) pass; emitting a
    # 0.0 here would read as a bogus upload-time-vanished improvement
    # against rounds that timed a real second pass
    l1v, l2v = [], []
    for a in (0.1, 0.5):
        for r in (0.001, 0.01, 0.1, 0.2):
            l1v.append(r * a)
            l2v.append(r * (1 - a))
    l1v = jnp.asarray(l1v, jnp.float32)
    l2v = jnp.asarray(l2v, jnp.float32)
    # compile warm-up (fold shapes are identical across folds)
    w0 = jnp.asarray(W_np[0], jnp.float32)
    t0 = time.perf_counter()
    jax.block_until_ready(bd.fit_logreg_enet_grids_big(
        X16, y_dev, w0, l1v, l2v, 2, 200)["W"])
    note(f"LR fit compiled+run in {time.perf_counter() - t0:.1f}s")
    t0 = time.perf_counter()
    lr_metrics = np.zeros((8, 3))
    winner = None
    folds_done = 0
    for f in range(3):
        if f > 0 and _remaining() < 90:
            note(f"LR fold {f} skipped ({_remaining():.0f}s left)")
            break
        wf = jnp.asarray(W_np[f], jnp.float32)
        t1 = time.perf_counter()
        params = bd.fit_logreg_enet_grids_big(
            X16, y_dev, wf, l1v, l2v, 2, 200)
        jax.block_until_ready(params["W"])
        note(f"LR fold {f} fit {time.perf_counter() - t1:.1f}s")
        t1 = time.perf_counter()
        probs = bd.predict_logreg_grids_big(params["W"], params["b"], X16)
        jax.block_until_ready(probs)
        note(f"LR fold {f} predict {time.perf_counter() - t1:.1f}s")
        # per-grid binned AuPR on HOST from the materialized score
        # column (~330 MB/fold): exact sorts serialize on TPU at 10M
        # rows — np.bincount over 4096 score buckets gives the same
        # tie-grouped trapezoid with NO new device program.
        t1 = time.perf_counter()
        scores_np = np.asarray(probs[:, :, 1], np.float32)  # (8, n)
        vmask = np.asarray(V_np[f])
        lr_metrics[:, f] = [
            _host_binned_aupr(y, scores_np[gi], vmask.astype(np.float64))
            for gi in range(8)]
        note(f"LR fold {f} metric+materialize {time.perf_counter() - t1:.1f}s")
        del probs, wf
        folds_done += 1
        if f == 0:
            winner = params
    t_lr_sweep = time.perf_counter() - t0
    best_lr_aupr = float(
        lr_metrics[:, :folds_done].mean(axis=1).max()) if folds_done else 0.0
    payload["big_lr_sweep24_s"] = round(t_lr_sweep, 1)
    payload["big_lr_folds"] = folds_done
    payload["big_lr_best_aupr"] = round(best_lr_aupr, 4)

    # scoring throughput: stacked-grid predict = 1 X pass for 8 models;
    # report single-model rows/sec through one (g=1) predict
    W1 = winner["W"][:1]
    b1 = winner["b"][:1]
    jax.block_until_ready(bd.predict_logreg_grids_big(W1, b1, X16))
    t0 = time.perf_counter()
    scores1 = bd.predict_logreg_grids_big(W1, b1, X16)
    jax.block_until_ready(scores1)
    np.asarray(scores1[:, :1, 1])  # host materialization ends the timing
    t_score = time.perf_counter() - t0
    payload["big_score_rows_per_sec"] = round(n_rows / t_score, 1)

    # replace the estimated LR leg of the extrapolation with the
    # measured one (scaled to 3 folds if the budget truncated; only
    # when the tree phase ran — rf_s/xgb_s are None otherwise)
    if folds_done and rf_s is not None:
        _emit_extrapolation(t_lr_sweep * (3.0 / folds_done), rf_s, xgb_s,
                            estimated_lr=False)

    del X16, winner, params, scores1
    gc.collect()
    _emit(payload)


def run_multichip() -> None:
    """Measured multichip sweep (`python bench.py multichip`).

    A CPU host-mesh tool: pod-scale figures elsewhere in this file are
    extrapolations (single-chip terms ÷ chip count), and this mode
    MEASURES a distributed schedule instead — on a forced 8-device host
    mesh (`--xla_force_host_platform_device_count`, the reference's
    `local[2]` trick), the real work-stealing scheduler
    (parallel/scheduler.py) packing a multi-block 2-family grid across
    the lanes, exact-winner parity asserted, and the goodput mesh
    rollup reporting how well the lanes were actually packed — the
    measured counterpart of the ÷N perfect-packing model. MUST run in a
    fresh process (device-count flags precede backend init), which is
    why it is an argv mode and not a phase of the main run."""
    n_dev = int(os.environ.get("BENCH_MESH_DEVICES", 8))
    n_rows = int(os.environ.get("BENCH_MESH_ROWS", 2048))
    from transmogrifai_tpu.parallel.smoke import run_measured
    # 6 LR max_iter groups + 1 SVC group = 7 blocks over n_dev lanes:
    # enough blocks that packing (not block granularity) dominates
    measured = run_measured(n_devices=n_dev, n_rows=n_rows,
                            max_iters=(24, 20, 16, 12, 8, 4))
    key = f"sweep_mesh{n_dev}_measured_s"
    _emit({
        "metric": "mesh_sweep_measured",
        "value": measured["mesh_speedup"],
        "unit": f"x vs single device ({n_dev}-device host mesh)",
        "vs_baseline": measured["mesh_speedup"],
        "platform": "cpu-hostmesh",
        "n_rows": n_rows,
        "winner_exact": measured["winner_exact"],
        "big_sweep_single_measured_s": measured["sweep_single_measured_s"],
        f"big_sweep_mesh{n_dev}_measured_s": measured[key],
        "mesh_utilization_frac": measured["mesh_utilization_frac"],
        # measured speedup ÷ device count: what the ÷N extrapolation
        # assumes is 1.0 — the honesty gap, in one number
        "mesh_scaling_efficiency": measured["mesh_scaling_efficiency"],
        "mesh": measured["mesh"],
    })


def run_pod() -> None:
    """Measured multi-HOST sweep (`python bench.py pod`).

    The multichip mode measures lanes inside ONE process; this mode
    measures the pod tier: 2+ real scheduler processes (one per
    "host"), each on its own forced host mesh, claim-racing one sweep's
    blocks through the shared `store/` lease table, with the
    host-qualified journal shards as the cross-host completion log.
    Reports the measured single-host vs pod wall pair, the fleet-wide
    mesh-utilization rollup (per-host `GoodputReport.mesh` sections
    merged by `obs.goodput.fleet_mesh_rollup`), and asserts every
    host's winner is bit-identical to the single-host run. The parent
    never initializes JAX, so unlike multichip this mode needs no
    fresh-subprocess trampoline for itself — the host processes ARE the
    fresh subprocesses."""
    n_hosts = int(os.environ.get("BENCH_POD_HOSTS", 2))
    workers = int(os.environ.get("BENCH_POD_WORKERS", 2))
    n_rows = int(os.environ.get("BENCH_MESH_ROWS", 2048))
    from transmogrifai_tpu.parallel.pod_smoke import run_pod as _run_pod
    # 8 LR max_iter groups + 1 SVC = 9 blocks over n_hosts×workers
    # lanes: enough rounds that claim racing (not startup skew) sets
    # the packing
    measured = _run_pod(n_hosts=n_hosts, workers=workers, n_rows=n_rows,
                        max_iters=(24, 20, 16, 12, 8, 4, 6, 3))
    key = f"sweep_pod{n_hosts}_measured_s"
    _emit({
        "metric": "pod_sweep_measured",
        "value": measured["pod_speedup"],
        "unit": f"x vs single host ({n_hosts} host processes × "
                f"{workers} lanes, shared store)",
        "vs_baseline": measured["pod_speedup"],
        "platform": "cpu-hostmesh-pod",
        "n_rows": n_rows,
        "winner_exact": measured["winner_exact"],
        "sweep_single_host_measured_s":
            measured["sweep_single_host_measured_s"],
        key: measured[key],
        "pod_scaling_efficiency": round(
            measured["pod_speedup"] / n_hosts, 4),
        # a pod of n_hosts interpreters sharing fewer cores than hosts
        # is core-starved: the measured speedup tops out near
        # host_cpus/n_hosts there, so record the denominator
        "host_cpus": measured["host_cpus"],
        "core_starved": measured["host_cpus"] < n_hosts,
        "mesh_utilization_frac":
            measured["fleet_mesh_utilization_frac"],
        "fleet_mesh": measured["fleet_mesh"],
        "blocks": measured["blocks"],
    })


def run_costmodel() -> None:
    """Learned-cost-model bench (`python bench.py costmodel`): the
    model's production scorecard. Reports holdout MAPE per target on
    the synthetic smoke corpus (can the fit learn the structure at
    all?) and on the REAL block-runtime rows the measured schedules
    just recorded, plus the packing improvement: mesh_utilization_frac
    with predicted-LPT vs count-LPT on the forced 8-device host mesh,
    winners asserted bit-identical either way. MUST run in a fresh
    process (device-count flags precede backend init), hence an argv
    mode."""
    n_dev = int(os.environ.get("BENCH_MESH_DEVICES", 8))
    n_rows = int(os.environ.get("BENCH_MESH_ROWS", 2048))
    from transmogrifai_tpu.perf.smoke import run_costmodel_bench
    payload = run_costmodel_bench(n_devices=n_dev, n_rows=n_rows)
    _emit({
        "metric": "costmodel_packing_improvement",
        "value": payload.get("packing_improvement", 0.0),
        "unit": "mesh_utilization_frac (predicted-LPT minus count-LPT)",
        "vs_baseline": payload.get("packing_improvement", 0.0),
        "platform": "cpu-hostmesh",
        "n_rows": n_rows,
        **payload,
    })


def _bucket_roofline(svc, rows) -> dict:
    """Per-bucket achieved-bandwidth roofline on a warm service: for
    each ladder rung, XLA 'bytes accessed' of the fused program at that
    shape over the measured warm device execution, plus the per-call
    dispatch count (1 = whole-pipeline fusion held)."""
    from transmogrifai_tpu.analysis.retrace import DISPATCHES
    from transmogrifai_tpu.data.dataset import Dataset
    from transmogrifai_tpu.workflow.compiled import pad_dataset

    out: dict = {}
    version = svc._active
    scorer = version.scorer
    if not scorer.fusable:
        return out
    schema = {k: v for k, v in svc._schema.items() if k in rows[0]}
    for bucket in svc.ladder:
        base = Dataset.from_rows(
            [rows[i % len(rows)] for i in range(min(bucket, len(rows)))],
            schema=schema)
        ds = pad_dataset(base, bucket)
        encs, raw_dev, _ = scorer.host_phase(ds)
        m = _measure_fused(scorer, encs, raw_dev, repeats=5)
        before = DISPATCHES.snapshot()
        scorer.score_padded(base, bucket)
        entry = {
            "device_ms": round(m["dev_s"] * 1e3, 4),
            "dispatches_per_call": sum(
                DISPATCHES.delta(before).values()),
        }
        if "bytes_per_sec" in m:
            entry.update(
                bytes_accessed=int(m["bytes_accessed"]),
                gbps=round(m["bytes_per_sec"] / 1e9, 3))
        if "hbm_frac" in m:
            entry["hbm_frac"] = round(m["hbm_frac"], 6)
        out[str(bucket)] = entry
    return out


def run_serving() -> None:
    """Serving-mode bench (`python bench.py serve`): throughput/latency of
    the online scoring service vs. batch-ladder config. Trains one small
    model, then for each ladder drives concurrent single/multi-row
    clients through the micro-batcher and emits one JSON line per
    config: rows/s, request p50/p99, batches, padding fraction, sheds —
    plus the per-bucket HBM roofline (`bucket_roofline`: achieved
    bytes/s and `hbm_frac` per rung, with the dispatch count proving
    one fused program per score call) and a quantized-serving config
    beside the f32 ones."""
    import tempfile
    import threading

    from transmogrifai_tpu.automl import transmogrify
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models import OpLogisticRegression
    from transmogrifai_tpu.serving.service import (
        ScoringService, ServingConfig)
    from transmogrifai_tpu.workflow import Workflow
    from transmogrifai_tpu.workflow.serialization import model_fingerprint

    platform = probe_backend()
    ds = make_data(4000, n_numeric=8, seed=11)
    preds, label = FeatureBuilder.from_dataset(ds, response="label")
    vec = transmogrify(preds)
    pred = OpLogisticRegression(max_iter=40).set_input(
        label, vec).get_output()
    t0 = time.perf_counter()
    model = Workflow().set_result_features(pred, label) \
        .set_input_dataset(ds).train()
    rows = ds.to_rows()
    duration_s = float(os.environ.get("BENCH_SERVE_SECONDS", 3.0))
    n_clients = int(os.environ.get("BENCH_SERVE_CLIENTS", 8))
    with tempfile.TemporaryDirectory(prefix="bench-serve-") as tmp:
        model.save(tmp)
        version = model_fingerprint(tmp)
        _emit({"metric": "serve_setup_s", "platform": platform,
               "value": round(time.perf_counter() - t0, 2), "unit": "s",
               "vs_baseline": 0.0, "model_version": version})
        # (max_batch, quantize, tracing, wire): the extra
        # (128, None, False, rows) config is the tail-sampled-tracing
        # overhead control — same ladder, tracing off — for the
        # `serve_trace_overhead` emission; the (128, None, True,
        # columns) config drives the COLUMNAR request wire (callers
        # that already hold columns skip the row pivot — its parse
        # phase should read ~0 beside the row-wire configs')
        p99_by_config: dict = {}
        for max_batch, quantize, tracing, wire in (
                (8, None, True, "rows"), (32, None, True, "rows"),
                (128, None, True, "rows"), (128, None, False, "rows"),
                (128, "int8", True, "rows"),
                (128, None, True, "columns")):
            if _remaining() < duration_s + 30.0:
                _emit({"metric": "serve_skipped", "value": float(max_batch),
                       "unit": "config", "vs_baseline": 0.0,
                       "reason": "budget"})
                break
            svc = ScoringService.from_path(tmp, config=ServingConfig(
                max_batch=max_batch, batch_wait_ms=1.0, max_queue=1024,
                quantize=quantize, tracing={"enabled": tracing}))
            svc.start()
            stop_at = time.perf_counter() + duration_s
            sent = [0] * n_clients
            errors = [0] * n_clients

            def client(i: int) -> None:
                rng = np.random.default_rng(i)
                while time.perf_counter() < stop_at:
                    k = int(rng.integers(1, 5))  # mixed request sizes
                    batch = [rows[int(j)] for j in
                             rng.integers(0, len(rows), size=k)]
                    try:
                        if wire == "columns":
                            cols = {name: [r.get(name) for r in batch]
                                    for name in batch[0]}
                            svc.score_columns(cols, deadline_ms=10_000)
                        else:
                            svc.score(batch, deadline_ms=10_000)
                        sent[i] += k
                    except Exception:
                        errors[i] += 1

            threads = [threading.Thread(target=client, args=(i,))
                       for i in range(n_clients)]
            t1 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            wall = time.perf_counter() - t1
            reg = svc.registry.to_json()
            lat = reg["serving_request_latency_seconds"]["series"][0]
            pad = reg.get("serving_padded_rows_total",
                          {"series": [{"value": 0}]})["series"][0]["value"]
            scored = sum(sent)
            # per-bucket HBM roofline, MEASURED on the warm fused
            # programs the clients just exercised: bytes the compiled
            # program touches (XLA cost analysis — narrow dtypes when
            # quantized) over warm score_padded wall, beside the
            # dispatch count that proves whole-pipeline fusion held
            buckets = _bucket_roofline(svc, rows)
            # per-phase breakdown (parse called out by ROADMAP as the
            # serving-p50 dominator): p50/p99 of every
            # serving_phase_seconds series this config populated
            phases = {}
            for entry in reg.get("serving_phase_seconds",
                                 {"series": []})["series"]:
                name = entry["labels"].get("phase", "?")
                phases[name] = {
                    "p50_ms": (round(entry["p50"] * 1e3, 4)
                               if entry.get("p50") is not None else None),
                    "p99_ms": (round(entry["p99"] * 1e3, 4)
                               if entry.get("p99") is not None else None),
                }
            svc.stop()
            p99_by_config[(max_batch, quantize, tracing, wire)] = \
                lat["p99"]
            _emit({
                "metric": "serve_rows_per_sec", "platform": platform,
                "value": round(scored / max(wall, 1e-9), 1),
                "unit": "rows/s", "vs_baseline": 0.0,
                "max_batch": max_batch, "clients": n_clients,
                "quantize": quantize, "tracing": tracing, "wire": wire,
                "rows": scored, "errors": sum(errors),
                "latency_p50_ms": (round(lat["p50"] * 1e3, 3)
                                   if lat["p50"] is not None else None),
                "latency_p99_ms": (round(lat["p99"] * 1e3, 3)
                                   if lat["p99"] is not None else None),
                "pad_fraction": round(pad / max(pad + scored, 1), 4),
                "bucket_roofline": buckets,
            })
            if phases:
                _emit({"metric": "serve_phase_breakdown",
                       "platform": platform,
                       "value": float(len(phases)), "unit": "phases",
                       "vs_baseline": 0.0, "max_batch": max_batch,
                       "quantize": quantize, "wire": wire,
                       "phases": phases})
        on = p99_by_config.get((128, None, True, "rows"))
        off = p99_by_config.get((128, None, False, "rows"))
        if on is not None and off is not None and off > 0:
            # acceptance gate: tail-sampled tracing must cost < 5% p99
            # at the 128-ladder config
            _emit({"metric": "serve_trace_overhead", "platform": platform,
                   "value": round(on / off - 1.0, 4), "unit": "frac",
                   "vs_baseline": 0.0,
                   "p99_tracing_on_ms": round(on * 1e3, 3),
                   "p99_tracing_off_ms": round(off * 1e3, 3),
                   "budget_frac": 0.05,
                   "within_budget": bool(on / off - 1.0 < 0.05)})


def run_continual() -> None:
    """Continual-mode bench (`python bench.py continual`): the always-on
    freshness SLO numbers. Trains a store-backed model, serves it, then
    appends drifted records and runs one full drift→warm-refit→gated-
    swap cycle while client threads keep scoring. Emits:

    - ``continual_staleness_s``: append → fresh-model-serving seconds
      (the headline freshness metric of the closed loop);
    - ``continual_refit_p99_ms`` / ``p50``: serving latency percentiles
      measured DURING the refit window (the refit runs off the serving
      path — the batcher should barely notice), plus dropped-request
      and shed counts (must be 0 for the loop to claim 'under
      traffic')."""
    import tempfile
    import threading

    from transmogrifai_tpu.continual import ContinualLoop, ContinualParams
    from transmogrifai_tpu.data.columnar_store import ColumnarStore
    from transmogrifai_tpu.serving.service import (
        ScoringService, ServingConfig)

    platform = probe_backend()
    n_rows = int(os.environ.get("BENCH_CONTINUAL_ROWS", 20_000))
    n_feats = int(os.environ.get("BENCH_CONTINUAL_FEATS", 16))
    n_append = int(os.environ.get("BENCH_CONTINUAL_APPEND", 4096))
    n_clients = int(os.environ.get("BENCH_CONTINUAL_CLIENTS", 4))
    rng = np.random.default_rng(13)
    beta = rng.normal(size=n_feats)
    with tempfile.TemporaryDirectory(prefix="bench-continual-") as tmp:
        X = rng.standard_normal((n_rows, n_feats)).astype(np.float32)
        y = (X @ beta > 0).astype(np.float32)
        w = ColumnarStore.create(f"{tmp}/store", n_rows, n_feats,
                                 dtype="float32")
        w.write_chunk(0, X, y)
        store = w.close()
        t0 = time.perf_counter()
        loop = ContinualLoop(
            store, f"{tmp}/model",
            params=ContinualParams(window_rows=n_append,
                                   min_window_rows=256,
                                   journal_dir=f"{tmp}/journal"),
            seed=13)
        loop.train_initial()
        svc = ScoringService.from_path(
            f"{tmp}/model", config=ServingConfig(max_batch=32,
                                                 max_queue=1024))
        svc.start()
        loop.attach(svc)
        setup_s = time.perf_counter() - t0
        _emit({"metric": "continual_setup_s", "platform": platform,
               "value": round(setup_s, 2), "unit": "s",
               "vs_baseline": 0.0, "rows": n_rows, "features": n_feats})

        row = {f"f{j}": 0.1 for j in range(n_feats)}
        latencies: list = []
        errors = [0]
        halt = threading.Event()

        def client(i: int) -> None:
            while not halt.is_set():
                t = time.perf_counter()
                try:
                    svc.score([row], deadline_ms=10_000)
                    latencies.append(time.perf_counter() - t)
                except Exception:
                    errors[0] += 1
                time.sleep(0.002)

        threads = [threading.Thread(target=client, args=(i,), daemon=True)
                   for i in range(n_clients)]
        for th in threads:
            th.start()
        try:
            Xn = (rng.standard_normal((n_append, n_feats))
                  + 2.0).astype(np.float32)
            yn = (Xn @ beta > 0).astype(np.float32)
            loop.append(Xn, yn)
            t1 = time.perf_counter()
            result = loop.run_cycle()
            cycle_wall = time.perf_counter() - t1
        finally:
            halt.set()
            for th in threads:
                th.join(timeout=5)
            svc.stop()
        lat = np.array(latencies) if latencies else np.zeros(1)
        _emit({
            "metric": "continual_staleness_s", "platform": platform,
            "value": round(float(result.get("staleness_s") or cycle_wall),
                           3),
            "unit": "s", "vs_baseline": 0.0,
            "status": result.get("status"),
            "cycle_wall_s": round(cycle_wall, 3),
            "holdout_metric": (round(result["metric"], 4)
                               if result.get("metric") is not None
                               else None),
            "append_rows": n_append,
        })
        _emit({
            "metric": "continual_refit_p99_ms", "platform": platform,
            "value": round(float(np.percentile(lat, 99)) * 1e3, 3),
            "unit": "ms", "vs_baseline": 0.0,
            "p50_ms": round(float(np.percentile(lat, 50)) * 1e3, 3),
            "requests": len(latencies), "errors": errors[0],
            "clients": n_clients,
        })


def run_fleet() -> None:
    """Fleet-mode bench (`python bench.py fleet`): the multi-model
    tenancy numbers the ROADMAP fleet item asks for. Trains three small
    models (two same-shaped forests + one logistic), then emits:

    - ``fleet_first_start_s`` / ``fleet_warm_start_s``: construction to
      first-score for the whole fleet on its first boot (no warmup
      manifests yet) and on the next one (manifests + whatever the
      persistent compile cache holds) — plus the shared-program report
      (the same-shaped pair compiles ONCE). The compile cache is one
      fixed directory, so the first boot is XLA-cold only in a checkout
      whose cache was empty when the bench started: `xla_cold` says
      which, and `cold_s`/`speedup` appear on the warm line only then;
    - ``fleet_p99_ms`` per tenant under a mixed multi-tenant open-loop
      load (paced senders, mixed request sizes, three models), with
      per-tenant 429 counts — the over-quota tenant's sheds must not
      leak into the in-quota tenant's latency;
    - ``fleet_swap_goodput``: a rolling swap of one model DURING the
      load window — swap wall, requests served fleet-wide during the
      swap, and errors on the untouched models (must be 0)."""
    import tempfile
    import threading

    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models import (
        OpLogisticRegression, OpRandomForestClassifier)
    from transmogrifai_tpu.ops.numeric import RealVectorizer
    from transmogrifai_tpu.serving.fleet import FleetConfig, FleetService
    from transmogrifai_tpu.workflow import Workflow

    from transmogrifai_tpu.utils.compile_cache import (
        compile_cache_entries)
    platform = probe_backend()
    xla_cold = compile_cache_entries() == 0
    n = int(os.environ.get("BENCH_FLEET_ROWS", 2000))
    duration_s = float(os.environ.get("BENCH_FLEET_SECONDS", 4.0))
    rng = np.random.default_rng(17)
    feats = {f"x{j}": rng.normal(size=n) for j in range(6)}

    def fit(path: str, y: np.ndarray, forest: bool) -> None:
        ds = Dataset({**feats, "y": y},
                     {**{k: t.Real for k in feats}, "y": t.Integral})
        preds, label = FeatureBuilder.from_dataset(ds, response="y")
        vec = RealVectorizer(track_nulls=False).set_input(
            *preds).get_output()
        est = (OpRandomForestClassifier(n_trees=8, max_depth=4) if forest
               else OpLogisticRegression(max_iter=40))
        pred = est.set_input(label, vec).get_output()
        Workflow().set_result_features(pred, label) \
            .set_input_dataset(ds).train().save(path)

    with tempfile.TemporaryDirectory(prefix="bench-fleet-") as tmp:
        # isolate the cost-model corpus (multichip-smoke precedent):
        # against a dev machine's accumulated corpus, the serving-bucket
        # refit cadence fires REPEATEDLY on the members' scoring threads
        # during the measured window and books cost-model bookkeeping
        # into the fleet p99 (measured here: p50 4ms -> 250ms+). A fresh
        # corpus keeps the recording path (rows still accumulate, the
        # designed default) while the min_rows floor keeps mid-window
        # refits out of the latency. An explicit env pin wins.
        if "TRANSMOGRIFAI_PERF_CORPUS_DIR" not in os.environ:
            os.environ["TRANSMOGRIFAI_PERF_CORPUS_DIR"] = \
                f"{tmp}/perf-corpus"
        x = np.column_stack(list(feats.values()))
        beta = rng.normal(size=x.shape[1])
        t0 = time.perf_counter()
        fit(f"{tmp}/a", (x @ beta > 0).astype(np.float64), True)
        fit(f"{tmp}/b", (x @ -beta > 0).astype(np.float64), True)
        fit(f"{tmp}/a2", (x @ beta > 0.2).astype(np.float64), True)
        fit(f"{tmp}/c", (x @ beta > 0).astype(np.float64), False)
        _emit({"metric": "fleet_setup_s", "platform": platform,
               "value": round(time.perf_counter() - t0, 2), "unit": "s",
               "vs_baseline": 0.0, "rows": n})

        def config() -> FleetConfig:
            return FleetConfig(
                models={"a": f"{tmp}/a", "b": f"{tmp}/b",
                        "c": f"{tmp}/c"},
                tenants={"gold": {"rate": 1e6, "priority": 1},
                         "trial": {"rate": 60, "burst": 60,
                                   "priority": 0}},
                serving={"max_batch": 32, "batch_wait_ms": 1.0,
                         "max_queue": 1024},
                compile_cache=True)

        row = {k: 0.1 for k in feats}

        def first_score_s() -> "tuple":
            t1 = time.perf_counter()
            fleet = FleetService(config())
            fleet.start()
            for m in ("a", "b", "c"):
                fleet.score(m, [row], tenant="gold")
            return time.perf_counter() - t1, fleet

        first_s, fleet = first_score_s()
        shared = fleet.pool.report()
        warms = {name: h["versions"][-1]["warm_s"]
                 for name, h in fleet.models().items()}
        _emit({"metric": "fleet_first_start_s", "platform": platform,
               "value": round(first_s, 3), "unit": "s",
               "vs_baseline": 0.0, "models": 3, "xla_cold": xla_cold,
               "shared_program_sets": len(shared),
               "warm_s_per_model": {k: round(v, 3)
                                    for k, v in warms.items()}})
        fleet.stop()

        warm_s, fleet = first_score_s()
        saved = 0.0
        for name in ("a", "b", "c"):
            reg = fleet._services[name].registry.to_json()
            series = reg.get("serving_compile_cache_saved_s",
                             {"series": []})["series"]
            saved += sum(s.get("value", 0.0) for s in series)
        _emit({"metric": "fleet_warm_start_s", "platform": platform,
               "value": round(warm_s, 3), "unit": "s",
               "vs_baseline": 0.0, "first_s": round(first_s, 3),
               "compile_cache_saved_s": round(saved, 3),
               **({"cold_s": round(first_s, 3),
                   "speedup": round(first_s / max(warm_s, 1e-9), 2)}
                  if xla_cold else {})})

        # -- mixed multi-tenant open-loop load + rolling swap ----------- #
        lat: dict = {"gold": [], "trial": []}
        shed: dict = {"gold": 0, "trial": 0}
        errors: dict = {"gold": 0, "trial": 0}
        late: dict = {"gold": 0, "trial": 0}
        halt = threading.Event()
        lock = threading.Lock()

        def client(i: int, tenant: str, model: str, rate_hz: float
                   ) -> None:
            """TRUE open loop (wrk2-style): the send clock dispatches
            each request on its own worker thread and latency is
            measured from the SCHEDULED send tick — a slow completion
            (e.g. inside the rolling-swap window) delays nothing and
            its queueing time IS sampled, so the p99 cannot hide
            coordinated omission. In-flight is capped; an overrun send
            counts as an error instead of silently stalling the clock."""
            crng = np.random.default_rng(i)
            period = 1.0 / rate_hz
            inflight = threading.Semaphore(64)
            nxt = time.perf_counter()
            behind = 4 * period  # sender-lag re-anchor threshold

            def fire(scheduled: float, k: int) -> None:
                try:
                    fleet.score(model, [row] * k, tenant=tenant,
                                deadline_ms=10_000)
                    with lock:
                        lat[tenant].append(time.perf_counter() - scheduled)
                except Exception as e:
                    code = getattr(e, "code", "")
                    with lock:
                        if code in ("quota_exceeded",
                                    "shed_low_priority"):
                            shed[tenant] += 1
                        else:
                            errors[tenant] += 1
                finally:
                    inflight.release()

            while not halt.is_set():
                nxt += period * float(crng.uniform(0.5, 1.5))
                delay = nxt - time.perf_counter()
                if delay > 0:
                    time.sleep(delay)
                elif -delay > behind:
                    # the SENDER fell behind (GIL/scheduler lag in this
                    # in-process generator) — re-anchor and count the
                    # dropped ticks instead of booking sender lag as
                    # server queueing latency
                    with lock:
                        late[tenant] += 1
                    nxt = time.perf_counter()
                k = int(crng.integers(1, 5))
                if inflight.acquire(blocking=False):
                    threading.Thread(target=fire, args=(nxt, k),
                                     daemon=True).start()
                else:
                    with lock:
                        errors[tenant] += 1  # load-generator overrun

        # default rates target partial utilization on a CPU host; crank
        # BENCH_FLEET_RATE_HZ up to study saturation (open-loop senders
        # keep firing regardless, so overload shows up as honest p99
        # growth + overrun errors, not a slowed send clock)
        rate = float(os.environ.get("BENCH_FLEET_RATE_HZ", 8.0))
        spec = [("gold", "a", rate), ("gold", "b", rate),
                ("gold", "c", rate), ("trial", "a", 2 * rate),
                ("trial", "c", 2 * rate)]
        threads = [threading.Thread(target=client, args=(i, *s),
                                    daemon=True)
                   for i, s in enumerate(spec)]
        for th in threads:
            th.start()
        time.sleep(duration_s / 2)
        snap = fleet.router.snapshot()
        t1 = time.perf_counter()
        swap = fleet.reload_model("a", f"{tmp}/a2")
        swap_wall = time.perf_counter() - t1
        during = fleet.router.delta(snap)
        time.sleep(duration_s / 2)
        halt.set()
        for th in threads:
            th.join(timeout=5)
        time.sleep(0.5)  # drain dispatched in-flight requests before stop
        fleet.stop()
        for tenant in ("gold", "trial"):
            arr = np.array(lat[tenant]) if lat[tenant] else np.zeros(1)
            _emit({"metric": "fleet_p99_ms", "platform": platform,
                   "value": round(float(np.percentile(arr, 99)) * 1e3, 3),
                   "unit": "ms", "vs_baseline": 0.0, "tenant": tenant,
                   "p50_ms": round(float(np.percentile(arr, 50)) * 1e3, 3),
                   "requests": len(lat[tenant]), "shed_429": shed[tenant],
                   "errors": errors[tenant],
                   "sender_reanchors": late[tenant]})
        _emit({"metric": "fleet_swap_goodput", "platform": platform,
               "value": round(swap_wall, 3), "unit": "s",
               "vs_baseline": 0.0, "status": swap.get("status"),
               "requests_during_swap": sum(
                   d.get("requests", 0) for d in during.values()),
               "shed_during_swap": sum(
                   d.get("shed", 0) for d in during.values()),
               "errors_during_load": dict(errors)})


def run_router() -> None:
    """Router-mode bench (`python bench.py router`): the shared-state-
    plane + warmth-routing numbers the PR-17 acceptance asks for.
    Two fleet replicas over ONE shared artifact store, then emits:

    - ``router_cold_replay_s``: replica-2's cold-start-to-first-score
      when its warmup manifest comes out of the SHARED store (no local
      sidecar) and its programs out of the persistent compile cache —
      beside a warm restart (the 1.5x acceptance ratio) and the first
      boot, which is a true cold boot (`cold_s`) only when the compile
      cache was empty as the bench started, and `first_boot_s`
      otherwise. Replicas here share one process, hence one compile
      cache; separate replica processes under one
      `TRANSMOGRIFAI_STORE_DIR` share it through `<store>/xla-cache`;
    - ``router_quota_rows_s``: admitted rows/s for one metered tenant
      hammered open-loop THROUGH BOTH replicas with `shared_quota` —
      the 2-replica sum must stay within 10% of the single-replica
      quota (CAS-guarded shared balance, no per-request round trips);
    - ``router_wire_p99_ms``: client-observed p99 through the frontend
      HTTP server for the SAME columnar payload on the binary framing
      vs the JSON wire (binary must not be slower)."""
    import shutil
    import tempfile
    import threading
    import urllib.request

    from transmogrifai_tpu.utils.compile_cache import (
        compile_cache_entries)
    platform = probe_backend()
    xla_cold = compile_cache_entries() == 0
    n_rows = int(os.environ.get("BENCH_ROUTER_ROWS", 256))
    quota_s = float(os.environ.get("BENCH_ROUTER_QUOTA_SECONDS", 3.0))
    per_wire = int(os.environ.get("BENCH_ROUTER_REQUESTS", 80))
    rate = 400.0  # metered tenant: rows/s, burst = 1s of rate

    from transmogrifai_tpu.serving.binwire import (
        CONTENT_TYPE, encode_frame)
    from transmogrifai_tpu.serving.fleet import FleetConfig, FleetService
    from transmogrifai_tpu.serving.frontend import (
        Frontend, serve_frontend)
    from transmogrifai_tpu.workflow.serialization import WARMUP

    rng = np.random.default_rng(23)

    def fit(path: str) -> None:
        import transmogrifai_tpu.types as t
        from transmogrifai_tpu.data import Dataset
        from transmogrifai_tpu.features import FeatureBuilder
        from transmogrifai_tpu.models import OpLogisticRegression
        from transmogrifai_tpu.ops.numeric import RealVectorizer
        from transmogrifai_tpu.workflow import Workflow

        n = 200
        feats = {f"x{j}": rng.normal(size=n) for j in range(6)}
        x = np.column_stack(list(feats.values()))
        y = ((x @ rng.normal(size=6)) > 0).astype(np.float64)
        ds = Dataset({**feats, "y": y},
                     {**{k: t.Real for k in feats}, "y": t.Integral})
        preds, label = FeatureBuilder.from_dataset(ds, response="y")
        vec = RealVectorizer(track_nulls=False).set_input(
            *preds).get_output()
        pred = OpLogisticRegression(max_iter=40).set_input(
            label, vec).get_output()
        Workflow().set_result_features(pred, label) \
            .set_input_dataset(ds).train().save(path)

    with tempfile.TemporaryDirectory(prefix="bench-router-") as tmp:
        os.environ["TRANSMOGRIFAI_STORE_DIR"] = f"{tmp}/store"
        if "TRANSMOGRIFAI_PERF_CORPUS_DIR" not in os.environ:
            os.environ["TRANSMOGRIFAI_PERF_CORPUS_DIR"] = \
                f"{tmp}/perf-corpus"
        fit(f"{tmp}/model-a")

        def config(name: str, model_dir: str) -> FleetConfig:
            return FleetConfig(
                models={"m": model_dir},
                tenants={"gold": {"rate": 1e6, "priority": 1},
                         "meter": {"rate": rate, "burst": rate,
                                   "priority": 0}},
                serving={"max_batch": max(32, n_rows),
                         "batch_wait_ms": 1.0, "max_queue": 1024},
                compile_cache=True,
                store_dir=f"{tmp}/store", replica=name,
                shared_quota=True)

        cols = {f"x{j}": rng.normal(size=n_rows).tolist()
                for j in range(6)}

        def first_score_s(name: str, model_dir: str):
            t0 = time.perf_counter()
            fleet = FleetService(config(name, model_dir))
            fleet.start()
            fleet.score_columns("m", cols, tenant="gold")
            return time.perf_counter() - t0, fleet

        # -- cold boot / warm restart / replica-2 artifact replay ------- #
        first_s, boot = first_score_s("r0", f"{tmp}/model-a")
        boot.stop()
        warm_s, r1 = first_score_s("r1", f"{tmp}/model-a")
        shutil.copytree(f"{tmp}/model-a", f"{tmp}/model-b")
        os.remove(f"{tmp}/model-b/{WARMUP}")  # force the store fallback
        r2_s, r2 = first_score_s("r2", f"{tmp}/model-b")
        _emit({"metric": "router_cold_replay_s", "platform": platform,
               "value": round(r2_s, 3), "unit": "s", "vs_baseline": 0.0,
               ("cold_s" if xla_cold else "first_boot_s"):
                   round(first_s, 3),
               "warm_s": round(warm_s, 3),
               "ratio_vs_warm": round(r2_s / max(warm_s, 1e-9), 2),
               "acceptance_max_ratio": 1.5})

        try:
            # -- shared-quota invariant across both replicas ------------ #
            chunk = {k: v[:8] for k, v in cols.items()}
            admitted = [0]
            denied = [0]
            lock = threading.Lock()
            stop_at = time.perf_counter() + quota_s

            def hammer(rep) -> None:
                while time.perf_counter() < stop_at:
                    try:
                        rep.score_columns("m", chunk, tenant="meter")
                        with lock:
                            admitted[0] += 8
                    except Exception:
                        with lock:
                            denied[0] += 1
                        time.sleep(0.002)

            threads = [threading.Thread(target=hammer, args=(rep,),
                                        name=f"router-bench-{i}")
                       for i, rep in enumerate((r1, r2, r1, r2))]
            t0 = time.perf_counter()
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            window_s = time.perf_counter() - t0
            # hard ceiling: burst + rate*window is every token that
            # EXISTED fleet-wide during the window
            allowed = rate + rate * window_s
            measured = admitted[0] / window_s
            assert admitted[0] <= allowed * 1.001, \
                (f"2-replica tenant sum {admitted[0]} rows broke the "
                 f"shared balance (allowed {allowed:.0f})")
            assert admitted[0] >= 0.9 * rate * window_s, \
                (f"shared metering starved the tenant: {admitted[0]} "
                 f"rows admitted of {rate * window_s:.0f} earned")
            _emit({"metric": "router_quota_rows_s", "platform": platform,
                   "value": round(measured, 1), "unit": "rows/s",
                   "vs_baseline": 0.0, "quota_rows_s": rate,
                   "admitted_rows": admitted[0], "denials": denied[0],
                   "window_s": round(window_s, 2),
                   "overshoot_frac": round(
                       admitted[0] / allowed - 1.0, 4)})

            # -- binary vs JSON wire p99 through the frontend ----------- #
            fe = Frontend({"r1": r1, "r2": r2})
            server, _ = serve_frontend(fe, port=0, block=False)
            base = f"http://127.0.0.1:{server.port}"
            frame = encode_frame(cols, model="m", tenant="gold")
            jbody = json.dumps({"model": "m", "columns": cols,
                                "tenant": "gold"}).encode()
            lat = {"json": [], "binary": []}

            def shoot(wire: str) -> None:
                data, ctype = ((frame, CONTENT_TYPE) if wire == "binary"
                               else (jbody, "application/json"))
                for _ in range(per_wire // 2):
                    req = urllib.request.Request(
                        f"{base}/score", data=data,
                        headers={"Content-Type": ctype}, method="POST")
                    t1 = time.perf_counter()
                    with urllib.request.urlopen(req, timeout=30) as resp:
                        resp.read()
                    with lock:
                        lat[wire].append(
                            (time.perf_counter() - t1) * 1000.0)

            try:
                shoot("json")      # interleaved warm pass per wire,
                shoot("binary")    # then the measured concurrent pass
                for wire in lat:
                    lat[wire].clear()
                threads = [threading.Thread(target=shoot, args=(w,),
                                            name=f"router-wire-{w}-{i}")
                           for i in range(2) for w in ("json", "binary")]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()

                def pctl(xs, q):
                    xs = sorted(xs)
                    return xs[min(len(xs) - 1, int(q * len(xs)))]

                j99 = pctl(lat["json"], 0.99)
                b99 = pctl(lat["binary"], 0.99)
                assert b99 <= j99 * 1.1, \
                    (f"binary wire p99 {b99:.2f}ms regressed past JSON "
                     f"{j99:.2f}ms")
                _emit({"metric": "router_wire_p99_ms",
                       "platform": platform, "value": round(b99, 2),
                       "unit": "ms", "vs_baseline": 0.0,
                       "json_p99_ms": round(j99, 2),
                       "json_p50_ms": round(pctl(lat["json"], 0.5), 2),
                       "binary_p50_ms": round(
                           pctl(lat["binary"], 0.5), 2),
                       "rows_per_request": n_rows,
                       "requests_per_wire": len(lat["json"])})
            finally:
                server.shutdown()
                server.server_close()
        finally:
            r1.stop()
            r2.stop()


def run_fleetobs() -> None:
    """Fleet-observability bench (`python bench.py fleetobs`): the
    PR-20 acceptance numbers. Emits:

    - ``fleetobs_overhead``: serving p99 through one replica at the
      128-ladder config WITH trace-shard + metrics publishing on vs
      off — the observability plane must cost < 5% p99;
    - ``fleetobs_stitch_coverage``: fraction of sampled cross-hop
      requests (frontend process → replica process over HTTP) whose
      fleet-merged trace validates clean with both legs present —
      must be 100%."""
    import tempfile
    import threading

    from transmogrifai_tpu.serving import fleetobs_smoke
    from transmogrifai_tpu.serving.fleet import FleetConfig, FleetService
    from transmogrifai_tpu.serving.frontend import Frontend, HTTPReplica

    platform = probe_backend()
    duration_s = float(os.environ.get("BENCH_FLEETOBS_SECONDS", 3.0))
    n_clients = int(os.environ.get("BENCH_FLEETOBS_CLIENTS", 4))
    n_sampled = int(os.environ.get("BENCH_FLEETOBS_SAMPLED", 10))

    with tempfile.TemporaryDirectory(prefix="bench-fleetobs-") as tmp:
        store = f"{tmp}/store"
        os.makedirs(store, exist_ok=True)
        os.environ["TRANSMOGRIFAI_STORE_DIR"] = store
        if "TRANSMOGRIFAI_PERF_CORPUS_DIR" not in os.environ:
            os.environ["TRANSMOGRIFAI_PERF_CORPUS_DIR"] = \
                f"{tmp}/perf-corpus"
        fleetobs_smoke._fit_model(f"{tmp}/model")
        cols = fleetobs_smoke._cols(4)

        # -- publishing overhead at the 128-ladder config --------------- #
        # p99 on a multi-tenant CPU box is noisy run-to-run: tail
        # events are bursty (one scheduler stall poisons every client
        # in flight), so even pooled p99s swing +-15% between arms
        # measured at different moments. Estimate the overhead from
        # PAIRED reps instead — each rep runs both arms back to back
        # (alternating order, so allocator/GC growth doesn't fold into
        # the delta), the rep's p99 ratio cancels the slow drift, and
        # the median ratio across reps drops outlier reps entirely.
        n_reps = int(os.environ.get("BENCH_FLEETOBS_REPS", 6))
        lat_by_arm: dict = {"off": [], "on": []}
        rep_p99: dict = {"off": [], "on": []}

        def one_arm(arm: str, rep: int) -> None:
            config = FleetConfig(
                models={"m": f"{tmp}/model"},
                tenants={"gold": {"priority": 1}},
                serving={"max_batch": 128, "batch_wait_ms": 1.0,
                         "max_queue": 1024},
                compile_cache=True,
                store_dir=store, replica=f"bench-{arm}",
                obs={"enabled": arm == "on"})
            fleet = FleetService(config).start()
            try:
                lat: list = []
                lock = threading.Lock()
                # measure_from > now gives an unmeasured under-load
                # warmup so the XLA compiles for every batch bucket the
                # client mix produces land OUTSIDE the p99 window
                measure_from = time.perf_counter() + 1.0
                stop_at = measure_from + duration_s

                def client(i: int) -> None:
                    k = 0
                    while time.perf_counter() < stop_at:
                        # every 16th request rides a sampled trace so
                        # the "on" arm actually pays shard publishing
                        trace = (fleetobs_smoke._sampled_ctx(
                            uuid.uuid4().hex) if k % 16 == 0 else None)
                        k += 1
                        t1 = time.perf_counter()
                        try:
                            fleet.score_columns("m", cols,
                                                tenant="gold",
                                                trace=trace)
                        except Exception:
                            continue
                        if t1 < measure_from:
                            continue
                        with lock:
                            lat.append(time.perf_counter() - t1)

                threads = [threading.Thread(target=client, args=(i,),
                                            name=f"fleetobs-{arm}-{i}")
                           for i in range(n_clients)]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                lat_by_arm[arm].extend(lat)
                lat.sort()
                if lat:
                    rep_p99[arm].append(
                        lat[min(len(lat) - 1, int(0.99 * len(lat)))])
            finally:
                fleet.stop()

        for rep in range(n_reps):
            order = ("off", "on") if rep % 2 == 0 else ("on", "off")
            for arm in order:
                one_arm(arm, rep)

        def pooled_p99(arm: str):
            lat = sorted(lat_by_arm[arm])
            if not lat:
                return None
            return lat[min(len(lat) - 1, int(0.99 * len(lat)))]

        on, off = pooled_p99("on"), pooled_p99("off")
        ratios = sorted(a / b for a, b in
                        zip(rep_p99["on"], rep_p99["off"]) if b)
        if ratios and on is not None and off:
            overhead = ratios[len(ratios) // 2] - 1.0
            _emit({"metric": "fleetobs_overhead", "platform": platform,
                   "value": round(overhead, 4), "unit": "frac",
                   "vs_baseline": 0.0,
                   "p99_publish_on_ms": round(on * 1e3, 3),
                   "p99_publish_off_ms": round(off * 1e3, 3),
                   "rep_ratios": [round(r, 3) for r in ratios],
                   "max_batch": 128, "clients": n_clients,
                   "reps": n_reps, "budget_frac": 0.05,
                   "within_budget": bool(overhead < 0.05)})

        # -- cross-process stitched-trace coverage ---------------------- #
        if _remaining() < 120.0:
            _emit({"metric": "fleetobs_skipped", "value": 1.0,
                   "unit": "arm", "vs_baseline": 0.0,
                   "reason": "budget"})
            return
        procs = {}
        frontend = None
        try:
            urls = {}
            for name in ("r1", "r2"):
                procs[name], urls[name] = fleetobs_smoke.spawn_replica(
                    tmp, store, name, f"{tmp}/model")
            frontend = Frontend(
                {n: HTTPReplica(u) for n, u in urls.items()},
                store_dir=store)
            cov = fleetobs_smoke._stitched(frontend, store, n_sampled)
            _emit({"metric": "fleetobs_stitch_coverage",
                   "platform": platform,
                   "value": round(cov["stitched"] / max(1, cov["requests"]),
                                  4),
                   "unit": "frac", "vs_baseline": 0.0,
                   "requests": cov["requests"],
                   "stitched": cov["stitched"],
                   "hosts": cov["sample"]["hosts"],
                   "skew_s": cov["sample"]["skew_s"],
                   "acceptance_min": 1.0})
        finally:
            if frontend is not None:
                frontend.close()
            for proc in procs.values():
                fleetobs_smoke.stop_replica(proc)


def run_chaos_bench() -> None:
    """Chaos-mode bench (`python bench.py chaos`): the numbers that make
    "graceful degradation" falsifiable. Drives the 3-model/2-tenant
    fleet through the deterministic fault storms of
    `serving/chaos.run_chaos` (device-error storm -> breaker + degraded
    fallback, killed scoring thread -> watchdog restart, stalled
    dispatch -> in-budget recovery, corrupt reload under traffic) plus
    `run_continual_crash` (a killed continual cycle -> supervisor
    restart), and emits:

    - ``chaos_mttr_s``: measured HEALTHY->QUARANTINED->HEALTHY recovery
      of the stormed member, with breaker open/close transition counts
      and degraded-fallback request counts;
    - ``chaos_availability`` per tenant:model stream (non-error
      fraction) + p50/p99 under the storm — the stormed member degrades,
      the untouched members must hold availability 1.0;
    - ``chaos_recovery_s``: time-to-structured-answer for the killed
      and stalled scoring threads vs the configured stall budget;
    - ``chaos_slo_alert_s``: storm start → availability burn-rate alert
      firing (and the measured clear after recovery), plus the
      breaker-open flight-dump proof;
    - ``chaos_supervisor_restart``: the continual supervisor surviving
      a killed cycle."""
    import tempfile

    from transmogrifai_tpu.serving.chaos import (
        _train_models, run_chaos, run_continual_crash)

    platform = probe_backend()
    load_s = float(os.environ.get("BENCH_CHAOS_SECONDS", 4.0))
    with tempfile.TemporaryDirectory(prefix="bench-chaos-") as tmp:
        if "TRANSMOGRIFAI_PERF_CORPUS_DIR" not in os.environ:
            # fleet-bench precedent: a dev machine's accumulated corpus
            # fires serving-bucket refits mid-window and pollutes p99
            os.environ["TRANSMOGRIFAI_PERF_CORPUS_DIR"] = \
                f"{tmp}/perf-corpus"
        report = run_chaos(_train_models(tmp), seed=0, load_s=load_s,
                           flight_dir=f"{tmp}/flight")
        storm = report["storm"]
        slo = report.get("slo") or {}
        fl = report.get("flight") or {}
        _emit({"metric": "chaos_slo_alert_s", "platform": platform,
               "value": slo.get("alert_s") or 0.0, "unit": "s",
               "vs_baseline": 0.0, "fired": slo.get("fired"),
               "cleared": slo.get("cleared"),
               "clear_s": slo.get("clear_s"),
               "goodput_slo": report.get("goodput_slo"),
               "flight_breaker_dump": fl.get("breaker_dump"),
               "flight_valid_chrome_trace": fl.get("valid_chrome_trace"),
               "flight_failing_dispatch_spans":
                   fl.get("failing_dispatch_spans")})
        _emit({"metric": "chaos_mttr_s", "platform": platform,
               "value": storm.get("mttr_s") or 0.0, "unit": "s",
               "vs_baseline": 0.0, "member": storm["member"],
               "breaker_opens": storm["breaker_opens"],
               "breaker_closes": storm["breaker_closes"],
               "quarantined": storm["quarantined"],
               "recovered": storm["recovered"],
               "fallback_requests": storm["fallback_requests"],
               "fallback_version_responses":
                   storm["fallback_version_responses"],
               "faults_fired": storm["fired"],
               "goodput_resilience": report["goodput_resilience"]})
        for stream, stats in report["tenants"].items():
            _emit({"metric": "chaos_availability", "platform": platform,
                   "value": stats["availability"], "unit": "frac",
                   "vs_baseline": 0.0, "stream": stream,
                   "requests": stats["requests"],
                   "errors": stats["errors"],
                   "p50_ms": stats["p50_ms"], "p99_ms": stats["p99_ms"]})
        for scenario in ("kill", "stall"):
            s = report[scenario]
            _emit({"metric": "chaos_recovery_s", "platform": platform,
                   "value": s.get("answered_in_s") or 0.0, "unit": "s",
                   "vs_baseline": 0.0, "scenario": scenario,
                   "member": s["member"], "answer": s.get("answer"),
                   "watchdog_restarts": s["restarts"],
                   "recovered": s["recovered"],
                   **({"stall_budget_s": s["stall_budget_s"],
                       "within_budget": s["within_budget"]}
                      if "stall_budget_s" in s else {})})
        rel = report["reload"]
        _emit({"metric": "chaos_reload_rejected", "platform": platform,
               "value": 1.0 if rel["rejected"] else 0.0, "unit": "bool",
               "vs_baseline": 0.0,
               "resident_version_kept": rel["resident_version_kept"],
               "traffic_errors": rel["traffic"]["errors"],
               "traffic_requests": rel["traffic"]["requests"]})
        crash = run_continual_crash(tmp)
        _emit({"metric": "chaos_supervisor_restart",
               "platform": platform,
               "value": float(crash["supervisor_restarts"]),
               "unit": "count", "vs_baseline": 0.0, **crash})


def run_autopilot_bench() -> None:
    """Autopilot-mode bench (`python bench.py autopilot`, also reached
    as `python bench.py chaos --storm`): the numbers that make
    "self-driving serving" falsifiable. Drives the SAME seeded overload
    storm (`serving/chaos.run_storm` — delayed member + low-priority
    flood, gold deadline tighter than the degraded queue drain) at a
    static-config fleet and an autopilot fleet, and emits:

    - ``autopilot_storm_availability``: late-storm gold availability
      and p50/p99 per arm — the controller's damping is the static
      minus autopilot gap, on the same storm;
    - ``autopilot_actuations``: engage/release counts per ladder action
      from the flight-recorder events (each embeds the burn window that
      justified it), plus healthy-phase actuations (must be 0) and
      whether every actuation was released after the storm;
    - ``autopilot_shed``: shed counts by reason per arm (the
      predictive-admission rung sheds on PREDICTED drain, the static
      arm only on observed queue depth)."""
    import tempfile

    from transmogrifai_tpu.perf import model as perf_model
    from transmogrifai_tpu.serving.chaos import (
        _storm_cost_model, _train_models, run_storm)

    platform = probe_backend()
    flood_s = float(os.environ.get("BENCH_STORM_SECONDS", 2.0))
    # predictive admission needs the perf model ON; the pinned
    # deterministic cost model keeps the numbers host-independent
    os.environ["TRANSMOGRIFAI_PERF_MODEL"] = "1"
    with tempfile.TemporaryDirectory(prefix="bench-autopilot-") as tmp:
        if "TRANSMOGRIFAI_PERF_CORPUS_DIR" not in os.environ:
            os.environ["TRANSMOGRIFAI_PERF_CORPUS_DIR"] = \
                f"{tmp}/perf-corpus"
        dirs = _train_models(tmp)
        _storm_cost_model()
        try:
            arms = {
                "static": run_storm(dirs, autopilot=False, seed=0,
                                    flood_s=flood_s,
                                    flight_dir=f"{tmp}/flight"),
                "autopilot": run_storm(dirs, autopilot=True, seed=0,
                                       flood_s=flood_s,
                                       flight_dir=f"{tmp}/flight"),
            }
        finally:
            perf_model.set_model(None)
        for arm, report in arms.items():
            gold = report["storm"]["gold_a"]
            _emit({"metric": "autopilot_storm_availability",
                   "platform": platform, "value": gold["availability"],
                   "unit": "frac", "vs_baseline": 0.0, "arm": arm,
                   "slo_fired": report["storm"]["slo_fired"],
                   "slo_cleared": report["slo_cleared"],
                   "requests": gold["requests"],
                   "errors": gold["errors"],
                   "p50_ms": gold["p50_ms"], "p99_ms": gold["p99_ms"]})
            _emit({"metric": "autopilot_shed", "platform": platform,
                   "value": float(sum(report["shed"].values())),
                   "unit": "count", "vs_baseline": 0.0, "arm": arm,
                   **{f"shed_{k}": v
                      for k, v in sorted(report["shed"].items())}})
        auto = arms["autopilot"]
        acts: dict = {}
        for e in auto["events"]:
            k = f"{e.get('transition')}:{e.get('action')}"
            acts[k] = acts.get(k, 0) + 1
        rel = auto["release"]
        _emit({"metric": "autopilot_actuations", "platform": platform,
               "value": float(sum(acts.values())), "unit": "count",
               "vs_baseline": 0.0, "by_kind": acts,
               "healthy_actuations": auto["healthy"]["actuations"],
               "released": bool(rel["rung0"]
                                and not rel["fidelity_routes"]
                                and rel["pressure_a"] == 0.0
                                and not rel["spare_hosted"]),
               "flight_dumps": len(auto["flight_dumps"])})


# argv modes that never touch the main run: the forced-host-mesh ones
# (costmodel/multichip/pod) must set their device count BEFORE any JAX
# backend initializes, so they dispatch ahead of `probe_backend`
MODES = {
    "costmodel": run_costmodel,
    "multichip": run_multichip,
    "pod": run_pod,
    "serve": run_serving,
    "autopilot": run_autopilot_bench,
    "fleet": run_fleet,
    "router": run_router,
    "fleetobs": run_fleetobs,
    "continual": run_continual,
}


def _run_main() -> None:
    platform = probe_backend()
    payload = run(platform)
    payload["budget_s"] = _budget_s()
    # main payload goes out IMMEDIATELY — the big phase re-emits the
    # merged line after each completed sub-phase, so a last-line parse
    # always sees the newest complete result
    _emit(payload)
    # the 10M×500 out-of-core phase (BASELINE target 4): full mode only
    if payload["mode"] != "full":
        return
    if os.environ.get("BENCH_BIG") == "0":
        payload["big_skipped"] = "BENCH_BIG=0"
        _emit(payload)
        return
    run_big(platform, payload)


def main() -> int:
    global _BENCH_ROOT, _BENCH_ROOT_CM
    # root span for the whole bench: main-thread phase spans (train,
    # ingest pipelines, sweeps) nest under it via the context var and the
    # goodput rollup in _emit reads its subtree. Deliberately never
    # exited — the report treats "now" as the end of a live root.
    from transmogrifai_tpu.obs.trace import TRACER as _TRACER
    _BENCH_ROOT_CM = _TRACER.span("run:bench", category="run",
                                  new_trace=True)
    _BENCH_ROOT = _BENCH_ROOT_CM.__enter__()
    argv = sys.argv[1:]
    if "chaos" in argv:
        # the overload storm is a distinct scenario (load, not faults):
        # `bench.py chaos --storm` == `bench.py autopilot`
        mode, fn = "chaos", (run_autopilot_bench if "--storm" in argv
                             else run_chaos_bench)
    else:
        mode, fn = next(((m, f) for m, f in MODES.items() if m in argv),
                        ("main", _run_main))
    try:
        fn()
    except Exception as e:
        # one diagnostic line for whoever parses the last JSON line —
        # and a non-zero exit, so a failed phase is never a pass
        _emit({"metric": "bench_error", "value": 0.0, "unit": "error",
               "vs_baseline": 0.0, "mode": mode,
               "error": f"{type(e).__name__}: {e}",
               "trace_tail":
                   traceback.format_exc().strip().splitlines()[-3:]})
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
