"""Fleet-router smoke: two replicas over ONE shared store, end to end.

`make router-smoke` runs this module. Under a minute on CPU it must
prove the acceptance surface of the shared state plane + router tier
(`store/` + `serving/frontend.py`):

1. replica-1 boots over a fresh store — and a fresh compile cache
   under it (`<store>/xla-cache`), unless `JAX_COMPILATION_CACHE_DIR`
   places the cache somewhere an earlier run already warmed — and
   publishes its warmup manifest into the artifact store; a second
   service over the same local artifacts measures the WARM restart;
2. replica-2 boots from a model directory that has NO local warmup
   sidecar — its cold start is ARTIFACT REPLAY (store-keyed manifest by
   model fingerprint + shared persistent compile cache) and its
   cold-start-to-first-score lands within 1.5x the warm replica;
3. with `shared_quota` both replicas meter the same CAS-guarded
   fleet-wide balance: after one replica drains a tenant's burst, the
   over-quota tenant gets its 429 from EITHER replica (and over the
   frontend);
4. under concurrent mixed-wire load through the frontend HTTP server,
   binary-framed requests score BIT-IDENTICALLY to the JSON columnar
   wire;
5. split overload across replicas: each replica's burn stays under the
   multi-window threshold locally (errors never sit in both of its
   windows at once), but the fleet-folded burn crosses in BOTH windows
   — the fleet alert fires, exactly once, via the CAS latch.

Run: ``JAX_PLATFORMS=cpu python -m transmogrifai_tpu.serving.router_smoke``
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import threading
import time
import urllib.error
import urllib.request

COLS = {f"x{j}": [0.3 * j, -0.5, 2.0 - j, 0.25] for j in range(6)}


def _train_model(path: str) -> None:
    import numpy as np

    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models import OpLogisticRegression
    from transmogrifai_tpu.ops.numeric import RealVectorizer
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(13)
    n = 160
    feats = {f"x{j}": rng.normal(size=n) for j in range(6)}
    x = np.column_stack(list(feats.values()))
    y = ((x @ rng.normal(size=6)) > 0).astype(np.float64)
    ds = Dataset({**feats, "y": y},
                 {**{k: t.Real for k in feats}, "y": t.Integral})
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    vec = RealVectorizer(track_nulls=False).set_input(*preds).get_output()
    pred = OpLogisticRegression(max_iter=40).set_input(
        label, vec).get_output()
    Workflow().set_result_features(pred, label) \
        .set_input_dataset(ds).train().save(path)


def _post(url: str, data: bytes, content_type: str) -> dict:
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": content_type},
        method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def main() -> int:  # noqa: C901 (one linear acceptance script)
    os.environ.setdefault("TRANSMOGRIFAI_PERF_MODEL", "0")
    from transmogrifai_tpu.serving.binwire import (
        CONTENT_TYPE, encode_frame)
    from transmogrifai_tpu.serving.fleet import FleetConfig, FleetService
    from transmogrifai_tpu.serving.frontend import (
        Frontend, serve_frontend)
    from transmogrifai_tpu.utils.compile_cache import (
        compile_cache_entries)
    from transmogrifai_tpu.workflow.serialization import (
        WARMUP, load_warmup_manifest)

    with tempfile.TemporaryDirectory(prefix="router-smoke-") as tmp:
        store_dir = f"{tmp}/store"
        # the ONE resolution point: consumers (warmup publish, caches)
        # follow the store root without their own env knobs
        os.environ["TRANSMOGRIFAI_STORE_DIR"] = store_dir
        os.environ.setdefault("TRANSMOGRIFAI_PERF_CORPUS_DIR",
                              f"{tmp}/perf-corpus")
        # the first boot is XLA-cold only over an empty compile cache
        xla_cold = compile_cache_entries() == 0
        _train_model(f"{tmp}/model-a")

        def config(name: str, model_dir: str) -> FleetConfig:
            return FleetConfig(
                models={"m": model_dir},
                tenants={"gold": {"rate": 1e6, "priority": 1},
                         "meter": {"rate": 0.001, "burst": 30,
                                   "priority": 0}},
                serving={"max_batch": 8, "batch_wait_ms": 1.0,
                         "max_queue": 256},
                compile_cache=True,
                store_dir=store_dir, replica=name, shared_quota=True)

        def first_score_s(name: str, model_dir: str):
            t0 = time.perf_counter()
            fleet = FleetService(config(name, model_dir))
            fleet.start()
            fleet.score_columns("m", {k: list(v) for k, v in COLS.items()},
                                tenant="gold")
            return time.perf_counter() - t0, fleet

        # -- 1: cold boot populates the shared artifacts ---------------- #
        cold_s, boot = first_score_s("r0", f"{tmp}/model-a")
        boot.stop()
        assert os.path.exists(f"{tmp}/model-a/{WARMUP}"), \
            "cold warmup never wrote its local manifest"
        warm_s, r1 = first_score_s("r1", f"{tmp}/model-a")

        # -- 2: replica-2 cold start == artifact replay ----------------- #
        # same model, different host checkout: NO local warmup sidecar,
        # so the manifest must come back out of the shared store (keyed
        # by model fingerprint) and the XLA programs out of the shared
        # persistent compile cache
        shutil.copytree(f"{tmp}/model-a", f"{tmp}/model-b")
        os.remove(f"{tmp}/model-b/{WARMUP}")
        assert load_warmup_manifest(f"{tmp}/model-b"), \
            "store-backed warmup manifest fallback found nothing"
        r2_s, r2 = first_score_s("r2", f"{tmp}/model-b")
        try:
            ratio = r2_s / max(warm_s, 1e-9)
            # the acceptance bar (+0.25s absorbing scheduler noise on a
            # sub-second measurement)
            assert r2_s <= 1.5 * warm_s + 0.25, \
                (f"replica-2 cold start {r2_s:.2f}s vs warm replica "
                 f"{warm_s:.2f}s ({ratio:.2f}x > 1.5x): artifact replay "
                 f"did not carry")
            if xla_cold:
                assert r2_s < cold_s, (r2_s, cold_s)

            # -- 3: over-quota tenant 429s from EITHER replica ---------- #
            meter_cols = {k: list(v) for k, v in COLS.items()}
            admitted = 0
            denied = {"r1": 0, "r2": 0}
            for _ in range(30):  # 4-row requests drain the 30-row burst
                try:
                    r1.score_columns("m", meter_cols, tenant="meter")
                    admitted += 4
                except Exception:
                    denied["r1"] += 1
                    break
            assert admitted <= 32, \
                f"replica-1 alone admitted {admitted} rows past burst=30"
            for name, rep in (("r2", r2), ("r1", r1)):
                try:
                    rep.score_columns("m", meter_cols, tenant="meter")
                    raise AssertionError(
                        f"replica {name} admitted an over-quota tenant "
                        "(shared balance not consulted)")
                except Exception as e:
                    code = getattr(e, "code", None)
                    assert code == "quota_exceeded", (name, e)
                    denied[name] += 1
            assert denied["r1"] >= 1 and denied["r2"] >= 1, denied

            # -- 4: frontend — 429 over HTTP + wire bit-parity ---------- #
            fe = Frontend({"r1": r1, "r2": r2})
            server, _ = serve_frontend(fe, port=0, block=False)
            base = f"http://127.0.0.1:{server.port}"
            try:
                body = json.dumps({"model": "m", "columns": meter_cols,
                                   "tenant": "meter"}).encode()
                try:
                    _post(f"{base}/score", body, "application/json")
                    raise AssertionError(
                        "frontend admitted the over-quota tenant")
                except urllib.error.HTTPError as e:
                    assert e.code == 429, e.code

                frame = encode_frame(meter_cols, model="m",
                                     tenant="gold")
                jbody = json.dumps({"model": "m", "columns": meter_cols,
                                    "tenant": "gold"}).encode()
                results = {"json": [], "binary": []}
                errors = []
                lock = threading.Lock()

                def client(wire: str, n: int) -> None:
                    for _ in range(n):
                        try:
                            if wire == "binary":
                                out = _post(f"{base}/score", frame,
                                            CONTENT_TYPE)
                            else:
                                out = _post(f"{base}/score", jbody,
                                            "application/json")
                            with lock:
                                results[wire].append(out["scores"])
                        except Exception as e:
                            with lock:
                                errors.append(f"{wire}: {e}")

                threads = [threading.Thread(
                    target=client, args=(wire, 10),
                    name=f"router-smoke-{wire}-{i}")
                    for i in range(2) for wire in ("json", "binary")]
                for th in threads:
                    th.start()
                for th in threads:
                    th.join()
                assert not errors, errors[:3]
                assert len(results["json"]) == 20 and \
                    len(results["binary"]) == 20, {
                        k: len(v) for k, v in results.items()}
                ref = results["json"][0]
                for wire, outs in results.items():
                    for out in outs:
                        assert out == ref, \
                            (f"{wire} wire diverged from the JSON "
                             f"reference under concurrent load")
                health = json.loads(urllib.request.urlopen(
                    f"{base}/healthz", timeout=30).read())
                assert health["status"] == "ok", health
            finally:
                server.shutdown()
                server.server_close()
        finally:
            r1.stop()
            r2.stop()

        # -- 5: split overload — fleet burn fires ONCE, locals never -- #
        # rA's errors all land early: while they sit in its short
        # window its long window is diluted by warm-up traffic, and by
        # the time the long window slides past the warm-up the short
        # window has drained — never both at once. rB errors late and
        # little: its short window spikes but its long window stays
        # diluted. The fleet fold SUMS the counters: once the clean
        # warm-up slides out of the long window, fleet burn crosses the
        # threshold in BOTH windows and exactly one replica's engine
        # wins the CAS latch.
        from transmogrifai_tpu.obs.federate import FleetAlertLatch
        from transmogrifai_tpu.obs.slo import SLOEngine, SLOParams

        slo_store = f"{tmp}/slo-store"
        params = SLOParams.from_json({
            "slos": [{"name": "fleet-avail", "kind": "availability",
                      "objective": 0.9}],
            "windows": [[8.0, 2.0, 2.0, "page"]],
            "eval_period_s": 0.25})
        counters = {"rA": [0.0, 0.0], "rB": [0.0, 0.0]}  # [good, total]

        def source(nm: str):
            return lambda: tuple(counters[nm])

        engines = {}
        for nm in ("rA", "rB"):
            eng = SLOEngine(params)
            eng.set_source("fleet-avail", source(nm))
            eng.attach_fleet(slo_store, nm, name="router-split")
            engines[nm] = eng

        def add(nm: str, good: int, bad: int) -> None:
            counters[nm][0] += good
            counters[nm][1] += good + bad

        local_fired = []
        dt = 0.25
        for k in range(1, 43):  # t = 0.25 .. 10.5
            t = k * dt
            if t <= 2.0:        # warm-up: both clean
                add("rA", 25, 0)
                add("rB", 25, 0)
            elif t <= 4.0:      # rA's overload burst
                add("rA", 0, 5)
                add("rB", 10, 0)
            elif t <= 8.0:      # quiet middle: rA trickles, rB serves
                add("rA", 1, 0)
                add("rB", 10, 0)
            elif t <= 10.0:     # rB's (small) overload burst
                add("rA", 1, 0)
                add("rB", 0, 5)
            # else: two settle ticks, no traffic, so BOTH engines see
            # the final counters after the other's last publish
            for nm, eng in engines.items():
                st = eng.evaluate(now=t)["slos"]["fleet-avail"]
                if st["state"] == "firing":
                    local_fired.append((nm, t))

        assert not local_fired, \
            (f"local burn crossed the multi-window threshold on "
             f"{local_fired[:4]} — the split overload should only be "
             f"visible fleet-wide")
        for nm, eng in engines.items():
            st = eng.evaluate(now=10.5)["slos"]["fleet-avail"]
            assert st["alerts"] == 0, (nm, st["alerts"])
            fleet_view = st.get("fleet") or {}
            assert fleet_view.get("state") == "firing", (nm, fleet_view)
            assert fleet_view.get("replicas") == 2, (nm, fleet_view)
        latch_counts = FleetAlertLatch(
            slo_store, name="router-split").counts()
        row = latch_counts.get("fleet-avail") or {}
        assert row.get("state") == "firing" and row.get("fired") == 1, \
            (f"fleet alert must fire exactly once across both "
             f"replicas: {latch_counts}")

    print(f"router-smoke OK: replica-2 artifact replay "
          f"{r2_s:.2f}s vs warm {warm_s:.2f}s ({ratio:.2f}x, bar 1.5x; "
          f"{'cold' if xla_cold else 'first boot'} was {cold_s:.2f}s); "
          f"over-quota tenant denied by BOTH "
          f"replicas ({denied}) and 429'd by the frontend; 40 "
          f"concurrent mixed-wire requests bit-identical across "
          f"binary/JSON; split overload fired the FLEET alert exactly "
          f"once (owner={row.get('owner')}) while both local engines "
          f"stayed quiet")
    return 0


if __name__ == "__main__":
    sys.exit(main())
