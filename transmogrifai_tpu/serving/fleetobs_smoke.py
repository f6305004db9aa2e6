"""Fleet observability smoke: the PR-20 acceptance script.

Boots TWO replica PROCESSES (real `FleetService`s behind `serve_fleet`
HTTP, each publishing trace shards / metrics snapshots / SLO burn
samples to one shared store) plus an in-orchestrator `Frontend` over
`HTTPReplica` handles, and proves the fleet observability plane
end-to-end:

1. **Cross-process trace stitching** — sampled requests through the
   frontend come back as ONE validated Chrome trace per trace id
   (`merge_fleet_trace`), containing the frontend leg AND the serving
   leg from whichever replica process scored it (distinct pids,
   skew-normalized clocks).
2. **Federated metrics** — the frontend's `/metrics/fleet` view folds
   both replicas' PUBLISHED snapshots (no in-process registry reach).
3. **Fleet SLO burn, one alert** — a seeded deadline-error storm
   through BOTH replicas trips the fleet availability alert EXACTLY
   once (CAS latch: fired == 1, not K), with both replicas' traffic in
   the firing burn window, and the alert clears after recovery.
4. **One incident, one artifact** — the alert's flight dump opens a
   fleet incident; both replica processes contribute their rings
   within the capture window and `merge_incident` returns one
   validated cross-host Chrome trace.

Run: ``python -m transmogrifai_tpu.serving.fleetobs_smoke`` (the
``--replica`` flag is the internal worker entry). Also wired as
``make fleetobs-smoke`` and ``python -m transmogrifai_tpu.serving.chaos
--fleet``.

This is a CPU test tool: the orchestrator trains with JAX and then
starts two replica processes beside itself, which is only possible on
the CPU backend the replicas default to (``JAX_PLATFORMS=cpu``). A chip
belongs to one process at a time; the chip check is
``python chip_smoke.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import tempfile
import time
import uuid
from typing import Any, Dict, Optional, Tuple

from transmogrifai_tpu.serving.batcher import ScoreError

D = 4  # features per model

# time-scaled availability SLO shared by both replicas: a seconds-long
# error storm burns the 0.1% budget orders of magnitude too fast, so
# both burn windows trip; eval ticks fast enough that fleet folds stay
# fresh across the 2-process fleet
SLO = {
    "slos": [{"name": "gold-availability", "kind": "availability",
              "objective": 0.999, "tenant": "gold"}],
    "windows": [[2.4, 1.2, 2.0, "page"]],
    "time_scale": 1.0, "eval_period_s": 0.05,
}


def _fit_model(path: str, seed: int = 23) -> None:
    import numpy as np

    import transmogrifai_tpu.types as t
    from transmogrifai_tpu.data import Dataset
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.models import OpLogisticRegression
    from transmogrifai_tpu.ops.numeric import RealVectorizer
    from transmogrifai_tpu.workflow import Workflow

    rng = np.random.default_rng(seed)
    n = 160
    X = rng.normal(size=(n, D))
    beta = rng.normal(size=D)
    y = (X @ beta > 0).astype(np.float64)
    ds = Dataset({**{f"x{j}": X[:, j] for j in range(D)}, "y": y},
                 {**{f"x{j}": t.Real for j in range(D)},
                  "y": t.Integral})
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    vec = RealVectorizer(track_nulls=False).set_input(*preds).get_output()
    pred = OpLogisticRegression(max_iter=40).set_input(
        label, vec).get_output()
    Workflow().set_result_features(pred, label) \
        .set_input_dataset(ds).train().save(path)


def _cols(n_rows: int = 4) -> Dict[str, Any]:
    return {f"x{j}": [0.2 * (j + 1) - 0.1 * i for i in range(n_rows)]
            for j in range(D)}


# --------------------------------------------------------------------------- #
# Replica worker process                                                      #
# --------------------------------------------------------------------------- #

def replica_main(argv) -> int:
    """Internal worker: one fleet replica process. Serves until stdin
    closes (the orchestrator holds the pipe), then stops cleanly so
    final metrics/shard flushes land in the store."""
    p = argparse.ArgumentParser(prog="fleetobs_smoke --replica")
    p.add_argument("--name", required=True)
    p.add_argument("--store", required=True)
    p.add_argument("--model-dir", required=True)
    p.add_argument("--port-file", required=True)
    args = p.parse_args(argv)

    from transmogrifai_tpu.serving.fleet import FleetConfig, FleetService
    from transmogrifai_tpu.serving.http import serve_fleet

    config = FleetConfig(
        models={"m": args.model_dir},
        tenants={"gold": {"priority": 1}},
        serving={"max_batch": 8, "batch_wait_ms": 1.0, "max_queue": 256,
                 # zero-debounce black box: the fleet alert dump must
                 # never be debounced away, it opens the incident
                 "flight": {"dir": os.path.join(args.store, "..",
                                                f"flight-{args.name}"),
                            "min_interval_s": 0.0}},
        store_dir=args.store, replica=args.name, slo=SLO,
        obs={"metrics_period_s": 0.2, "capture_window_s": 10.0})
    fleet = FleetService(config).start()
    server, _ = serve_fleet(fleet, port=0, block=False)
    tmp = args.port_file + ".tmp"
    with open(tmp, "w", encoding="utf-8") as fh:
        fh.write(str(server.port))
    os.replace(tmp, args.port_file)
    try:
        sys.stdin.read()  # parent closes the pipe to stop us
    except KeyboardInterrupt:
        pass
    server.shutdown()
    fleet.stop()
    return 0


def spawn_replica(tmp: str, store: str, name: str, model_dir: str,
                  timeout_s: float = 240.0
                  ) -> Tuple[subprocess.Popen, str]:
    """Boot one replica worker; returns (process, base_url). The
    worker's stdout/stderr go to ``<tmp>/<name>.log``."""
    port_file = os.path.join(tmp, f"{name}.port")
    logf = open(os.path.join(tmp, f"{name}.log"), "w", encoding="utf-8")
    env = dict(os.environ)
    env.setdefault("JAX_PLATFORMS", "cpu")
    env.setdefault("TRANSMOGRIFAI_PERF_MODEL", "0")
    proc = subprocess.Popen(
        [sys.executable, "-m",
         "transmogrifai_tpu.serving.fleetobs_smoke", "--replica",
         "--name", name, "--store", store, "--model-dir", model_dir,
         "--port-file", port_file],
        stdin=subprocess.PIPE, stdout=logf, stderr=subprocess.STDOUT,
        env=env)
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        if os.path.exists(port_file):
            with open(port_file, encoding="utf-8") as fh:
                return proc, f"http://127.0.0.1:{int(fh.read())}"
        if proc.poll() is not None:
            raise RuntimeError(
                f"replica {name} died during boot "
                f"(see {tmp}/{name}.log)")
        time.sleep(0.1)
    proc.kill()
    raise RuntimeError(f"replica {name} never published its port")


def stop_replica(proc: subprocess.Popen) -> None:
    try:
        if proc.stdin is not None:
            proc.stdin.close()
        proc.wait(timeout=20)
    except Exception:
        proc.kill()


def _get_json(url: str, timeout_s: float = 10.0) -> Dict[str, Any]:
    import urllib.request
    with urllib.request.urlopen(url, timeout=timeout_s) as resp:
        return json.loads(resp.read())


# --------------------------------------------------------------------------- #
# Orchestrator                                                                #
# --------------------------------------------------------------------------- #

def _sampled_ctx(tid: str):
    from transmogrifai_tpu.obs.trace import TraceContext
    return TraceContext(trace_id=tid, parent_hex="0123456789abcdef",
                        sampled=True)


def _stitched(frontend, store: str, n: int) -> Dict[str, Any]:
    """Fire `n` sampled requests through the frontend and merge each
    trace id fleet-wide. Returns coverage counts."""
    from transmogrifai_tpu.obs.federate import merge_fleet_trace

    tids = []
    for _ in range(n):
        tid = uuid.uuid4().hex
        frontend.score_columns("m", _cols(), tenant="gold",
                               trace=_sampled_ctx(tid))
        tids.append(tid)
    time.sleep(0.3)  # replica shard appends are flush-per-record
    stitched = 0
    sample = None
    for tid in tids:
        merged = merge_fleet_trace(tid, store)
        ok = (not merged["problems"] and len(merged["hosts"]) >= 2
              and "frontend" in merged["hosts"]
              and merged["spans"] >= 3)
        stitched += int(ok)
        if sample is None:
            sample = {k: merged[k] for k in
                      ("hosts", "spans", "skew_s", "problems",
                       "missing_shards", "torn_shards")}
    return {"requests": n, "stitched": stitched, "sample": sample}


def _storm(replicas, duration_s: float = 2.5) -> int:
    """Seeded overload: deadline-doomed gold requests through BOTH
    replicas (deadline_exceeded is a counted error, not a shed), with
    good traffic interleaved so total counts keep flowing."""
    errors = 0
    stop_at = time.perf_counter() + duration_s
    while time.perf_counter() < stop_at:
        for rep in replicas:
            try:
                rep.score_columns("m", _cols(), tenant="gold",
                                  deadline_ms=0.005)
            except ScoreError:
                errors += 1
            try:
                rep.score_columns("m", _cols(), tenant="gold")
            except ScoreError:
                pass  # storm collateral: only the latch matters here
    return errors


def _good_traffic(replicas, duration_s: float) -> None:
    stop_at = time.perf_counter() + duration_s
    while time.perf_counter() < stop_at:
        for rep in replicas:
            try:
                rep.score_columns("m", _cols(), tenant="gold")
            except ScoreError:
                pass  # recovery traffic: best-effort by design
        time.sleep(0.02)


def _wait_latch(latch, slo: str, state: str,
                timeout_s: float = 20.0) -> Optional[Dict[str, Any]]:
    deadline = time.perf_counter() + timeout_s
    while time.perf_counter() < deadline:
        rec = latch.counts().get(slo)
        if rec and rec.get("state") == state:
            return rec
        time.sleep(0.05)
    return None


def main() -> int:  # noqa: C901 (one linear acceptance script)
    os.environ.setdefault("TRANSMOGRIFAI_PERF_MODEL", "0")
    from transmogrifai_tpu.obs.federate import (
        FleetAlertLatch, merge_incident)
    from transmogrifai_tpu.serving.frontend import Frontend, HTTPReplica
    from transmogrifai_tpu.store.state import StateCell

    report: Dict[str, Any] = {}
    with tempfile.TemporaryDirectory(prefix="fleetobs-smoke-") as tmp:
        store = os.path.join(tmp, "store")
        os.makedirs(store, exist_ok=True)
        os.environ["TRANSMOGRIFAI_STORE_DIR"] = store
        os.environ.setdefault("TRANSMOGRIFAI_PERF_CORPUS_DIR",
                              os.path.join(tmp, "perf-corpus"))
        model_dir = os.path.join(tmp, "model")
        _fit_model(model_dir)
        procs: Dict[str, subprocess.Popen] = {}
        frontend = None
        try:
            urls: Dict[str, str] = {}
            for name in ("r1", "r2"):
                procs[name], urls[name] = spawn_replica(
                    tmp, store, name, model_dir)
            print(f"[fleetobs] replicas up: {urls}")
            replicas = {name: HTTPReplica(url)
                        for name, url in urls.items()}
            frontend = Frontend(replicas, store_dir=store)

            # -- 1: cross-process trace stitching ----------------------- #
            cov = _stitched(frontend, store, n=5)
            report["stitching"] = cov
            assert cov["stitched"] == cov["requests"], \
                f"stitched {cov['stitched']}/{cov['requests']}: {cov}"
            print(f"[fleetobs] stitched {cov['stitched']}/"
                  f"{cov['requests']} sampled traces: "
                  f"hosts={cov['sample']['hosts']} "
                  f"spans={cov['sample']['spans']}")

            # -- 2: federated metrics ----------------------------------- #
            time.sleep(0.5)  # ≥1 publish period on both replicas
            fm = frontend.fleet_metrics_json()
            report["metrics_replicas"] = sorted(fm["replicas"])
            assert {"r1", "r2"} <= set(fm["replicas"]), fm["replicas"]
            fam = fm["fleet"].get("fleet_requests_total")
            assert fam, "federated view lost fleet_requests_total"
            print(f"[fleetobs] /metrics/fleet folds "
                  f"{sorted(fm['replicas'])}")

            # -- 3: fleet burn, exactly one alert ----------------------- #
            latch = FleetAlertLatch(store)
            errors = _storm(list(replicas.values()))
            rec = _wait_latch(latch, "gold-availability", "firing")
            assert rec is not None, \
                f"fleet alert never fired ({errors} seeded errors)"
            assert int(rec.get("fired", 0)) == 1, \
                f"fleet alert fired {rec.get('fired')} times, want 1"
            slo_view = _get_json(urls["r1"] + "/slo")
            fleet_view = (slo_view.get("slos", {})
                          .get("gold-availability", {})
                          .get("fleet") or {})
            report["alert"] = {"fired": int(rec["fired"]),
                               "owner": rec.get("owner"),
                               "replicas_in_window":
                                   fleet_view.get("replicas")}
            assert int(fleet_view.get("replicas") or 0) >= 2, \
                f"fleet burn window missing a replica: {fleet_view}"
            print(f"[fleetobs] fleet alert fired exactly once "
                  f"(owner={rec.get('owner')}, "
                  f"replicas={fleet_view.get('replicas')})")

            _good_traffic(list(replicas.values()), 4.0)
            cleared = _wait_latch(latch, "gold-availability", "ok")
            assert cleared is not None, "fleet alert never cleared"
            assert int(cleared.get("fired", 0)) == 1, cleared
            report["alert"]["cleared"] = True
            print("[fleetobs] fleet alert cleared (fired stayed 1)")

            # -- 4: one incident, one artifact -------------------------- #
            _, inc_val = StateCell(store, "obs-incident").read()
            inc = (inc_val or {}).get("incident") or {}
            incident_id = inc.get("id")
            assert incident_id, "alert dump opened no fleet incident"
            inc_dir = os.path.join(store, "obs", "incidents",
                                   str(incident_id))
            deadline = time.perf_counter() + 15.0
            while time.perf_counter() < deadline:
                hosts = sorted(os.listdir(inc_dir)) \
                    if os.path.isdir(inc_dir) else []
                if {"r1", "r2"} <= set(hosts):
                    break
                time.sleep(0.2)
            merged = merge_incident(str(incident_id), store)
            report["incident"] = {
                "id": incident_id, "hosts": merged["hosts"],
                "dumps": merged["dumps"],
                "problems": merged["problems"][:3]}
            assert {"r1", "r2"} <= set(merged["hosts"]), \
                f"incident missing a host ring: {merged['hosts']}"
            assert not merged["problems"], merged["problems"][:3]
            assert merged["trace"].get("traceEvents"), \
                "merged incident trace is empty"
            print(f"[fleetobs] incident {incident_id}: one artifact, "
                  f"hosts={merged['hosts']}, "
                  f"{len(merged['trace']['traceEvents'])} events")
        finally:
            if frontend is not None:
                frontend.close()
            for proc in procs.values():
                stop_replica(proc)
    print("fleetobs smoke OK: " + json.dumps(report))
    return 0


if __name__ == "__main__":
    if "--replica" in sys.argv[1:]:
        argv = [a for a in sys.argv[1:] if a != "--replica"]
        sys.exit(replica_main(argv))
    sys.exit(main())
