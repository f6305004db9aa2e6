"""costmodel-smoke: the learned cost model's CI gate (`make costmodel-smoke`)
and the measured half of ``python bench.py costmodel``.

1. **synthetic corpus → fit → holdout MAPE.** A corpus generated from a
   known multiplicative law (with seeded lognormal noise) must fit to a
   holdout MAPE under the gate threshold per target — the log-linear
   ridge can actually learn the structure it claims to.
2. **predicted-LPT vs count-LPT on the forced 8-device host mesh.** A
   real multi-block sweep schedules twice: once with an explicitly COLD
   model (count-LPT — today's heuristic, and its block rows feed the
   corpus), once after refitting on the corpus those runs just wrote
   (predicted-LPT). Winners and every fold metric must be BIT-IDENTICAL
   either way — the model reorders and resizes work, never changes it —
   and both packings are measured via the goodput mesh rollup so the
   bench reports the improvement honestly.

Run: ``python -m transmogrifai_tpu.perf.smoke`` (fresh process: the
forced host-device count must precede JAX backend init).
"""

from __future__ import annotations

import json
import math
import os
import tempfile
import time
from typing import Any, Dict

MAPE_GATE = 0.35


def synth_corpus(corpus, seed: int = 11) -> None:
    """Deterministic synthetic training rows from known cost laws, one
    per target, with seeded multiplicative noise — the fit must recover
    structure, not memorize points."""
    import numpy as np
    rng = np.random.default_rng(seed)

    def noise(sigma=0.12):
        return float(np.exp(rng.normal(0.0, sigma)))

    for n_configs in (1, 2, 4, 8):
        for iters in (4, 8, 16, 32, 64):
            for n_rows in (10_000, 50_000, 200_000):
                secs = 3e-8 * n_configs * iters * n_rows * noise()
                corpus.append("block_runtime", {
                    "n_configs": n_configs, "n_rows": n_rows,
                    "n_cols": 50, "n_folds": 3, "dtype_bytes": 4,
                    "fam_logistic": 1.0, "iters": iters}, secs)
    for workers in (1, 2, 4):
        for depth in (1, 2, 4, 8):
            for gb in (0.5, 2.0, 8.0):
                bytes_wire = gb * 1e9
                wall = (bytes_wire / (40e6 * math.sqrt(workers))
                        + 64 * 0.05 / math.sqrt(depth)) * noise()
                corpus.append("ingest", {
                    "bytes_wire": bytes_wire, "workers": workers,
                    "depth": depth, "chunks": 64, "cache_hit": 0.0}, wall)
    for bucket in (1, 2, 4, 8, 16, 32, 64, 128):
        for _ in range(4):
            lat = (0.002 + 2e-5 * bucket) * noise(0.08)
            corpus.append("serving_bucket", {"bucket": bucket}, lat)


def _measured_schedule(selector_fn, cols, n_rows, mesh, label: str
                       ) -> Dict[str, Any]:
    from transmogrifai_tpu.obs import goodput as obs_goodput
    from transmogrifai_tpu.obs.trace import TRACER
    from transmogrifai_tpu.parallel.smoke import _fit, _rows
    with TRACER.span(f"run:costmodel-{label}", category="run",
                     new_trace=True) as root:
        t0 = time.perf_counter()
        rows = _rows(_fit(selector_fn(), cols, n_rows, mesh=mesh))
        wall = time.perf_counter() - t0
    report = obs_goodput.build_report(root, TRACER.trace_spans(root.trace_id))
    return {"rows": rows, "wall_s": round(wall, 3),
            "util": float(report.mesh.get("utilization_frac", 0.0)),
            "perf": report.perf}


def run_costmodel_bench(n_devices: int = 8,
                        n_rows: int = 240) -> Dict[str, Any]:
    """Shared by the smoke gate and ``bench.py costmodel``: synthetic-
    corpus MAPE per target + the measured count-LPT vs predicted-LPT
    schedule pair on the forced host mesh."""
    from transmogrifai_tpu.parallel.smoke import (
        _cols, _selector, ensure_host_devices)
    ensure_host_devices(n_devices)
    from transmogrifai_tpu import perf
    from transmogrifai_tpu.parallel.mesh import make_mesh
    from transmogrifai_tpu.parallel.smoke import _fit

    payload: Dict[str, Any] = {}

    # 1 — synthetic corpus: fit must beat the MAPE gate per target
    with tempfile.TemporaryDirectory(prefix="costmodel-synth-") as tmp:
        synth = perf.CostCorpus(tmp)
        synth_corpus(synth)
        for target in ("block_runtime", "ingest", "serving_bucket"):
            mape = perf.holdout_mape(synth, target)
            payload[f"holdout_mape_{target}"] = (
                round(mape, 4) if mape is not None else None)

    # 2 — measured packing: count-LPT (cold) vs predicted-LPT (warm)
    # on one multi-block sweep. The count run's tie-break orders the
    # LR groups ascending by max_iter — the longest blocks START LAST,
    # the pessimal packing predicted-LPT exists to fix.
    import shutil
    corpus_dir = tempfile.mkdtemp(prefix="costmodel-corpus-")
    os.environ.pop("TRANSMOGRIFAI_PERF_MODEL", None)
    perf.set_params(perf.PerfModelParams(corpus_dir=corpus_dir, min_rows=4))
    max_iters = (96, 80, 64, 48, 40, 32, 24, 16, 8, 4)
    mesh = make_mesh(n_devices, sweep=n_devices)
    cols = _cols(n_rows)

    def sel():
        return _selector(max_iters=max_iters)

    try:
        # warm compiles off the measurement (blocks record corpus rows)
        from transmogrifai_tpu.obs.trace import TRACER
        perf.set_model(perf.CostModel())  # explicitly cold decisions
        with TRACER.span("run:costmodel-warmup", category="run",
                         new_trace=True):
            _fit(sel(), cols, n_rows)
            _fit(sel(), cols, n_rows, mesh=mesh)

        count = _measured_schedule(sel, cols, n_rows, mesh, "count")

        # refit from the corpus those runs just wrote → predicted-LPT
        model = perf.refresh()
        warm = (model is not None
                and model.predict("block_runtime", perf.block_features(
                    "logistic", (96, False), 2, n_rows, 6, 2)) is not None)
        payload["model_warm"] = bool(warm)
        predicted = _measured_schedule(sel, cols, n_rows, mesh, "predicted")
        real_mape = perf.holdout_mape(perf.get_corpus(), "block_runtime")
        payload["holdout_mape_block_runtime_measured"] = (
            round(real_mape, 4) if real_mape is not None else None)
    finally:
        perf.set_model(None)
        perf.set_params(None)
        shutil.rmtree(corpus_dir, ignore_errors=True)

    exact = (count["rows"]["best_grid"] == predicted["rows"]["best_grid"]
             and set(count["rows"]["rows"]) == set(predicted["rows"]["rows"])
             and all(json.dumps(count["rows"]["rows"][k])
                     == json.dumps(predicted["rows"]["rows"][k])
                     for k in count["rows"]["rows"]))
    payload.update({
        "winner_exact": exact,
        "mesh_utilization_frac_count_lpt": round(count["util"], 4),
        "mesh_utilization_frac_predicted_lpt": round(predicted["util"], 4),
        "packing_improvement": round(
            predicted["util"] - count["util"], 4),
        "wall_s_count_lpt": count["wall_s"],
        "wall_s_predicted_lpt": predicted["wall_s"],
        "perf_residuals": predicted["perf"],
        "n_devices": n_devices,
    })
    return payload


def _smoke() -> int:
    payload = run_costmodel_bench()
    mape = payload.get("holdout_mape_block_runtime")
    assert mape is not None and mape < MAPE_GATE, (
        f"block-runtime holdout MAPE {mape} over the {MAPE_GATE} gate")
    for target in ("ingest", "serving_bucket"):
        m = payload.get(f"holdout_mape_{target}")
        assert m is not None and m < MAPE_GATE, (
            f"{target} holdout MAPE {m} over the {MAPE_GATE} gate")
    assert payload["winner_exact"], (
        "predicted-LPT schedule is not bit-identical to count-LPT")
    assert payload["model_warm"], (
        "measured schedule runs did not warm the model from the corpus")
    # predicted residuals were recorded (the honesty layer is live)
    assert payload["perf_residuals"].get("predictions", 0) > 0, (
        f"no perf_residual events recorded: {payload['perf_residuals']}")
    # packing: predicted-LPT must not be meaningfully WORSE than
    # count-LPT (host-CPU timing noise gets a small tolerance; bench.py
    # costmodel reports the raw pair as the headline)
    assert (payload["mesh_utilization_frac_predicted_lpt"]
            >= payload["mesh_utilization_frac_count_lpt"] - 0.1), payload
    print(json.dumps({"costmodel_smoke": "ok", **payload}))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(_smoke())
