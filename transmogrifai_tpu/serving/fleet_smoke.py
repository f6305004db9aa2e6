"""Fleet-serving smoke: multi-model tenancy end to end, in one process.

`make fleet-smoke` runs this module. Under a minute on CPU it must
prove the acceptance surface of the fleet subsystem
(`serving/fleet.py` + `serving/router.py`):

1. THREE models in one FleetService across TWO tenants — two models
   same-shaped (forest pipelines differing only in fitted tree values)
   and one differently-shaped;
2. shared bucket programs: the second same-shaped model's warmup
   performs ZERO new traces (`RetraceMonitor.delta()`-asserted) while
   the differently-shaped model compiles its own ladder;
3. per-tenant quota enforcement under mixed HTTP load: the over-quota
   tenant collects 429s, the in-quota tenant collects NONE;
4. a rolling swap of one model under live traffic drops ZERO in-flight
   requests on the untouched models — and the same-shaped replacement
   itself warms with zero new compiles;
5. first boot over fresh artifacts writes the warmup manifest; a
   second service instance over the same artifacts hits the manifest
   and the persistent compile cache and records
   `serving_compile_cache_saved_s`. Both boots' time-to-first-score is
   printed; the compile cache sits at its one fixed directory
   (utils/compile_cache.py), so the first boot is XLA-cold only on a
   fresh checkout.

Run: ``JAX_PLATFORMS=cpu python -m transmogrifai_tpu.serving.fleet_smoke``
"""

from __future__ import annotations

import json
import os
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request


def _train_models(tmp: str) -> None:
    """a + b: forest pipelines over IDENTICAL features with different
    labels — identical scoring signatures, different fitted trees.
    c: a logistic pipeline — its own signature."""
    import numpy as np

    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models import (
        OpLogisticRegression, OpRandomForestClassifier)
    from transmogrifai_tpu.ops.numeric import RealVectorizer
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(7)
    n = 160
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)

    def fit(name: str, y, forest: bool) -> None:
        ds = Dataset({"x1": x1, "x2": x2, "y": y},
                     {"x1": t.Real, "x2": t.Real, "y": t.Integral})
        preds, label = FeatureBuilder.from_dataset(ds, response="y")
        vec = RealVectorizer(track_nulls=False).set_input(
            *preds).get_output()
        est = (OpRandomForestClassifier(n_trees=4, max_depth=3) if forest
               else OpLogisticRegression(max_iter=40))
        pred = est.set_input(label, vec).get_output()
        model = Workflow().set_result_features(pred, label) \
            .set_input_dataset(ds).train()
        model.save(f"{tmp}/{name}")

    lrng = np.random.default_rng(3)
    ya = ((x1 + 0.5 * x2 + lrng.normal(0, 0.3, n)) > 0).astype(np.float64)
    yb = ((x1 - 0.5 * x2 + lrng.normal(0, 0.3, n)) > 0).astype(np.float64)
    fit("a", ya, forest=True)
    fit("b", yb, forest=True)
    fit("a_v2", yb, forest=True)   # same-shaped swap candidate for `a`
    fit("c", ya, forest=False)


def _post(url: str, payload: dict) -> dict:
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"}, method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


ROWS = [{"x1": 0.3, "x2": -1.2}, {"x1": -0.5, "x2": 0.8}]


def main() -> int:  # noqa: C901 (one linear acceptance script)
    os.environ.setdefault("TRANSMOGRIFAI_PERF_MODEL", "0")
    from transmogrifai_tpu.analysis.retrace import MONITOR
    from transmogrifai_tpu.serving.fleet import FleetConfig, FleetService
    from transmogrifai_tpu.serving.http import serve_fleet
    from transmogrifai_tpu.workflow.serialization import (
        load_warmup_manifest)

    with tempfile.TemporaryDirectory(prefix="fleet-smoke-") as tmp:
        _train_models(tmp)

        def config() -> FleetConfig:
            return FleetConfig(
                tenants={"gold": {"rate": 100_000, "priority": 1},
                         "trial": {"rate": 40, "burst": 40,
                                   "priority": 0}},
                serving={"max_batch": 8, "batch_wait_ms": 1.0,
                         "max_queue": 256},
                compile_cache=True)

        # -- 1+2: three models, shared programs, COLD start ------------- #
        t0 = time.perf_counter()
        fleet = FleetService(config())
        fleet.add_model("a", f"{tmp}/a")
        before = MONITOR.snapshot()
        fleet.add_model("b", f"{tmp}/b")
        delta_b = MONITOR.delta(before)
        before = MONITOR.snapshot()
        fleet.add_model("c", f"{tmp}/c")
        delta_c = sum(MONITOR.delta(before).values())
        fleet.start()
        fleet.score("a", ROWS, tenant="gold")
        cold_s = time.perf_counter() - t0
        try:
            assert delta_b == {}, \
                f"same-shaped model b re-traced: {delta_b}"
            assert delta_c > 0, "differently-shaped model c compiled 0"
            shared = fleet.pool.report()
            groups = [e for e in shared.values() if len(e["members"]) > 1]
            assert len(shared) == 2 and groups and \
                len(groups[0]["members"]) == 2, shared
            for m in ("a", "b", "c"):
                fleet.score(m, ROWS, tenant="gold")

            # -- 3: mixed HTTP load, quota sheds only the offender ------ #
            server, _ = serve_fleet(fleet, port=0, block=False)
            base = f"http://127.0.0.1:{server.port}"
            health = json.loads(urllib.request.urlopen(
                f"{base}/healthz", timeout=30).read())
            assert health["status"] == "ok", health
            assert health["shared_programs"], health
            counts = {"gold_429": 0, "trial_429": 0, "gold_ok": 0,
                      "trial_ok": 0, "other": 0}
            lock = threading.Lock()

            def client(tenant: str, model: str, stop_at: float) -> None:
                while time.perf_counter() < stop_at:
                    try:
                        _post(f"{base}/score",
                              {"model": model, "rows": ROWS,
                               "tenant": tenant, "deadline_ms": 10_000})
                        key = f"{tenant}_ok"
                    except urllib.error.HTTPError as e:
                        key = (f"{tenant}_429" if e.code == 429
                               else "other")
                    except Exception:
                        key = "other"
                    with lock:
                        counts[key] += 1

            stop_at = time.perf_counter() + 2.0
            specs = (("gold", "a", stop_at), ("gold", "b", stop_at),
                     ("gold", "c", stop_at), ("trial", "c", stop_at),
                     ("trial", "c", stop_at))
            threads = [threading.Thread(target=client, args=args,
                                        name=f"smoke-client-{i}")
                       for i, args in enumerate(specs)]
            for th in threads:
                th.start()
            for th in threads:
                th.join()
            assert counts["trial_429"] > 0, \
                f"over-quota tenant never shed: {counts}"
            assert counts["gold_429"] == 0, \
                f"in-quota tenant was shed: {counts}"
            assert counts["other"] == 0, counts
            assert counts["gold_ok"] > 0 and counts["trial_ok"] > 0, counts

            # -- 4: rolling swap, zero drops on untouched models -------- #
            errors = {"b": 0, "c": 0}
            served = {"b": 0, "c": 0}
            halt = threading.Event()

            def steady(model: str) -> None:
                while not halt.is_set():
                    try:
                        fleet.score(model, ROWS, tenant="gold",
                                    deadline_ms=10_000)
                        served[model] += 1
                    except Exception:
                        errors[model] += 1

            steady_threads = [threading.Thread(target=steady, args=(m,),
                                               name=f"smoke-steady-{m}")
                              for m in ("b", "c")]
            for th in steady_threads:
                th.start()
            before = MONITOR.snapshot()
            swap = _post(f"{base}/reload",
                         {"model": "a", "model_location": f"{tmp}/a_v2"})
            swap_traces = MONITOR.delta(before)
            time.sleep(0.3)
            halt.set()
            for th in steady_threads:
                th.join()
            assert swap["status"] == "swapped", swap
            assert errors == {"b": 0, "c": 0}, \
                f"rolling swap dropped in-flight requests: {errors}"
            assert served["b"] > 0 and served["c"] > 0, served
            assert swap_traces == {}, \
                f"same-shaped swap candidate re-traced: {swap_traces}"
            new_version = fleet.models()["a"]["model_version"]
            assert new_version == swap["version"], (swap, new_version)
            server.shutdown()
            server.server_close()
        finally:
            fleet.stop()

        # -- 5: warm start over the same artifacts ---------------------- #
        manifest = load_warmup_manifest(f"{tmp}/a")
        assert manifest and manifest.get("warm_s", 0) > 0, manifest
        t0 = time.perf_counter()
        fleet2 = FleetService(config())
        fleet2.add_model("a", f"{tmp}/a")
        fleet2.add_model("b", f"{tmp}/b")
        fleet2.add_model("c", f"{tmp}/c")
        fleet2.start()
        fleet2.score("a", ROWS, tenant="gold")
        warm_s = time.perf_counter() - t0
        try:
            info = fleet2.models()["a"]["versions"][-1]
            assert "compile_cache_saved_s" in info, info
            reg = fleet2._services["a"].registry.to_json()
            assert "serving_compile_cache_saved_s" in reg, sorted(reg)
        finally:
            fleet2.stop()

    print(f"fleet-smoke OK: 3 models / 2 tenants in one process; "
          f"same-shaped pair shares programs (0 new traces, "
          f"{delta_c} own compiles for the odd one); quota shed "
          f"{counts['trial_429']} trial vs 0 gold under load; rolling "
          f"swap dropped 0 in-flight (b={served['b']}, c={served['c']} "
          f"served); time-to-first-score {cold_s:.2f}s first boot "
          f"vs {warm_s:.2f}s with manifest + persistent cache")
    return 0


if __name__ == "__main__":
    sys.exit(main())
