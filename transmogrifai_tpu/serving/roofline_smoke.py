"""Roofline-scoring smoke: fused dispatch, quantized parity, lifted
parameters — end to end, in one process.

`make roofline-smoke` runs this module. Under a minute on CPU it must
prove the acceptance surface of the roofline scoring work
(`workflow/compiled.py` + the lifted model families + `serving/`):

1. whole-pipeline fusion: a warm `ScoringService` executes exactly ONE
   device dispatch per bucket per score call
   (`analysis.retrace.DISPATCHES`-asserted per rung);
2. quantized inference: int8 scoring agrees with the f32 path within
   the stated per-feature wire tolerance (the linear-path error bound
   sum(|w_d|·scale_d/2) computed from the model's own weights), and
   the quantized build's signature never adopts the f32 programs;
3. parameter lifting: TWO different same-shaped linear fits in one
   fleet share ONE compiled program set — the second member warms with
   ZERO new traces and scores bit-identically to a solo load;
4. honest accounting: `scoring_bytes_per_sec` is present and nonzero
   in the smoke payload (XLA's program bytes over the measured warm
   device execution); `scoring_hbm_frac` divides it by the device's
   recorded peak bandwidth and is therefore ABSENT on the CPU.

Run: ``JAX_PLATFORMS=cpu python -m transmogrifai_tpu.serving.roofline_smoke``
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import time


def _train_models(tmp: str):
    """a + b: logistic pipelines over IDENTICAL features with different
    labels — identical scoring signatures (weights are LIFTED jit
    arguments), different fitted coefficients."""
    import numpy as np

    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models import OpLogisticRegression
    from transmogrifai_tpu.ops.numeric import RealVectorizer
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(7)
    n = 160
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)

    def fit(name: str, y) -> None:
        ds = Dataset({"x1": x1, "x2": x2, "y": y},
                     {"x1": t.Real, "x2": t.Real, "y": t.Integral})
        preds, label = FeatureBuilder.from_dataset(ds, response="y")
        vec = RealVectorizer(track_nulls=False) \
            .set_input(*preds).get_output()
        pred = OpLogisticRegression(max_iter=30) \
            .set_input(label, vec).get_output()
        Workflow().set_result_features(pred, label) \
            .set_input_dataset(ds).train().save(os.path.join(tmp, name))

    y_a = ((x1 + 0.5 * x2 + rng.normal(0, 0.3, n)) > 0).astype(np.float64)
    y_b = ((x1 - 0.5 * x2 + rng.normal(0, 0.3, n)) > 0).astype(np.float64)
    fit("a", y_a)
    fit("b", y_b)


def main() -> int:
    t_start = time.perf_counter()
    import numpy as np

    from transmogrifai_tpu.analysis.retrace import DISPATCHES, MONITOR
    from transmogrifai_tpu.serving.fleet import (
        FleetConfig, FleetService, scoring_signature)
    from transmogrifai_tpu.serving.service import (
        ScoringService, ServingConfig)
    from transmogrifai_tpu.workflow.serialization import load_model

    payload = {"smoke": "roofline"}
    rows = [{"x1": 0.3, "x2": -1.2}, {"x1": -0.5, "x2": 0.8},
            {"x1": 2.0, "x2": 0.1}, {"x1": -1.4, "x2": -0.9}]

    with tempfile.TemporaryDirectory(prefix="roofline-smoke-") as tmp:
        _train_models(tmp)
        dir_a, dir_b = os.path.join(tmp, "a"), os.path.join(tmp, "b")

        # -- 1. one device dispatch per bucket per score call ---------- #
        svc = ScoringService.from_path(dir_a, config=ServingConfig(
            max_batch=8, batch_wait_ms=0.5))
        svc.start()
        dispatches = {}
        for k in (1, 2, 3, 4):  # buckets 1, 2, 4, 4
            svc.score(rows[:k])  # warm the request path
        for k in (1, 2, 4):
            before = DISPATCHES.snapshot()
            svc.score(rows[:k])
            dispatches[k] = sum(DISPATCHES.delta(before).values())
        payload["dispatches_per_call"] = dispatches
        assert all(v == 1 for v in dispatches.values()), \
            f"fused plan must dispatch ONE program per score call: " \
            f"{dispatches}"

        # f32 reference scores for the parity checks below
        f32_probs = np.asarray([r[next(k for k in r if "Logistic" in k)]
                                ["probability_1"]
                                for r in (svc.score(rows).rows())])
        svc.stop()

        # -- 2. quantized parity within the stated wire tolerance ------ #
        model_a = load_model(dir_a)
        qsvc = ScoringService(model=model_a, version_id="q0",
                              config=ServingConfig(max_batch=8,
                                                   batch_wait_ms=0.5,
                                                   quantize="int8"))
        qsvc.start()
        q_probs = np.asarray([r[next(k for k in r if "Logistic" in k)]
                              ["probability_1"]
                              for r in (qsvc.score(rows).rows())])
        qsvc.stop()
        # linear-path error bound: |Δlogit| <= sum_d |W_d|·scale_d/2
        # with scale_d = (hi_d − lo_d)/255 over this batch's own range,
        # plus the bf16 weight-table rounding (2^-8 relative);
        # sigmoid is 1-Lipschitz·1/4 so the prob tolerance follows
        pred_stage = [s for s in model_a.fitted.values()
                      if type(s).__name__ == "LogisticRegressionModel"][0]
        W = np.abs(np.asarray(pred_stage.W)).sum()
        X = np.asarray([[r["x1"], r["x2"]] for r in rows], np.float32)
        span = (X.max(0) - X.min(0)).max()
        tol_logit = float(W * (span / 255.0) / 2.0 + W * 2.0 ** -8 * 4.0)
        tol_prob = max(0.25 * tol_logit, 1e-4)
        q_err = float(np.abs(q_probs - f32_probs).max())
        payload["quant_prob_err"] = round(q_err, 6)
        payload["quant_prob_tol"] = round(tol_prob, 6)
        assert q_err <= tol_prob, \
            f"int8 parity {q_err} exceeds stated tolerance {tol_prob}"

        # quantized and f32 builds must NEVER share programs
        assert scoring_signature(model_a) != \
            scoring_signature(model_a, quant="int8"), \
            "quant config must fold into the compile-group key"

        # -- 3. two same-shaped linear tenants share ONE program ------- #
        solo = ScoringService.from_path(dir_b, config=ServingConfig(
            max_batch=8, batch_wait_ms=0.5))
        solo.start()
        solo_rows = solo.score(rows).rows()
        solo.stop()

        fleet = FleetService(FleetConfig(
            models={"a": dir_a},
            serving={"max_batch": 8, "batch_wait_ms": 0.5}))
        before = MONITOR.snapshot()
        fleet.add_model("b", dir_b)
        new_traces = MONITOR.delta(before)
        shared = fleet.pool.report()
        payload["shared_signatures"] = len(shared)
        payload["second_tenant_traces"] = sum(new_traces.values())
        assert len(shared) == 1 and \
            sorted(len(e["members"]) for e in shared.values()) == [2], \
            f"same-shaped linear tenants must share one program set: " \
            f"{shared}"
        assert not new_traces, \
            f"second linear tenant must warm with ZERO traces: {new_traces}"
        fleet.start()
        fleet_rows = fleet.score("b", rows).rows()
        fleet.stop()
        for s_row, f_row in zip(solo_rows, fleet_rows):
            for key in s_row:
                sv, fv = s_row[key], f_row[key]
                if isinstance(sv, dict):
                    for kk in sv:
                        assert sv[kk] == fv[kk], \
                            f"adopted tenant must score bit-identically " \
                            f"({key}.{kk}: {sv[kk]} != {fv[kk]})"

        # -- 4. achieved bytes/s present; hbm_frac only off the CPU ---- #
        import bench
        from transmogrifai_tpu.data.dataset import Dataset
        import transmogrifai_tpu.types as t
        big = Dataset({"x1": np.random.default_rng(1).normal(size=4096),
                       "x2": np.random.default_rng(2).normal(size=4096)},
                      {"x1": t.Real, "x2": t.Real})
        roof = bench.score_roofline(load_model(dir_a), big)
        payload["scoring_bytes_per_sec"] = roof.get("scoring_bytes_per_sec")
        assert payload["scoring_bytes_per_sec"] and \
            payload["scoring_bytes_per_sec"] > 0, \
            f"scoring_bytes_per_sec must be present and nonzero: {roof}"
        # a fraction of peak exists only where the device's peak is
        # known: never computed against a TPU's bandwidth on the CPU
        import jax
        on_cpu = jax.devices()[0].platform == "cpu"
        assert ("scoring_hbm_frac" in roof) != on_cpu, roof

    payload["wall_s"] = round(time.perf_counter() - t_start, 2)
    print(json.dumps(payload))
    print("ROOFLINE SMOKE OK")
    return 0


if __name__ == "__main__":
    sys.exit(main())
