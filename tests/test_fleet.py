"""serving/fleet.py + serving/router.py: multi-model tenancy — scoring
signatures, shared bucket programs (RetraceMonitor-asserted dedup with
bit-identical numerics), per-tenant token-bucket quotas + priority
shedding, warmup manifests / persistent-compile cold-start accounting,
the fleet HTTP frontend, rolling swaps under traffic, and the goodput
fleet section."""

import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import transmogrifai_tpu.types as t
from transmogrifai_tpu.analysis.retrace import MONITOR
from transmogrifai_tpu.data import Dataset
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.models import (
    OpLogisticRegression, OpRandomForestClassifier)
from transmogrifai_tpu.ops.numeric import RealVectorizer
from transmogrifai_tpu.serving import ScoreError
from transmogrifai_tpu.serving.fleet import (
    FleetConfig, FleetService, scoring_signature)
from transmogrifai_tpu.serving.router import Router, TenantPolicy, TokenBucket
from transmogrifai_tpu.serving.service import ScoringService, ServingConfig
from transmogrifai_tpu.workflow import Workflow
from transmogrifai_tpu.workflow.serialization import (
    load_model, load_warmup_manifest, save_warmup_manifest)

ROWS = [{"x1": 0.3, "x2": -1.2}, {"x1": -0.5, "x2": 0.8},
        {"x1": 2.0, "x2": 0.1}]


def _train(y_sign=1.0, forest=True, depth=2, n=120, max_iter=30):
    """Forest pipelines over IDENTICAL features (seed pinned) so only
    the LABELS — and therefore only the fitted tree values — differ
    between same-shaped models."""
    rng = np.random.default_rng(7)
    x1 = rng.normal(size=n)
    x2 = rng.normal(size=n)
    lrng = np.random.default_rng(int(abs(y_sign * 10)) + (3 if forest
                                                          else 5))
    y = ((x1 + y_sign * 0.5 * x2 + lrng.normal(0, 0.3, n)) > 0) \
        .astype(np.float64)
    ds = Dataset({"x1": x1, "x2": x2, "y": y},
                 {"x1": t.Real, "x2": t.Real, "y": t.Integral})
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    vec = RealVectorizer(track_nulls=False).set_input(*preds).get_output()
    est = (OpRandomForestClassifier(n_trees=3, max_depth=depth)
           if forest else OpLogisticRegression(max_iter=max_iter))
    pred = est.set_input(label, vec).get_output()
    return Workflow().set_result_features(pred, label) \
        .set_input_dataset(ds).train()


@pytest.fixture(scope="module")
def model_dirs(tmp_path_factory):
    """m1/m2: same-shaped forests (different fitted trees); m3: a
    deeper forest (different tree-table shapes); plus an m1-shaped swap
    candidate."""
    base = tmp_path_factory.mktemp("fleet-models")
    dirs = {}
    for name, kw in (("m1", dict(y_sign=1.0)),
                     ("m2", dict(y_sign=-1.0)),
                     ("m1_v2", dict(y_sign=-1.0)),
                     ("m3", dict(y_sign=1.0, depth=4))):
        _train(**kw).save(str(base / name))
        dirs[name] = str(base / name)
    return dirs


def _fleet_config(model_dirs, **kw):
    cfg = dict(serving={"max_batch": 4, "batch_wait_ms": 1.0})
    cfg.update(kw)
    return FleetConfig(models={k: model_dirs[k]
                               for k in kw.pop("names", ())}, **cfg)


# --------------------------------------------------------------------- #
# scoring signature                                                     #
# --------------------------------------------------------------------- #

def test_scoring_signature_groups_tree_models(model_dirs):
    m1 = load_model(model_dirs["m1"])
    m2 = load_model(model_dirs["m2"])
    m3 = load_model(model_dirs["m3"])
    # same pipeline, different fitted TREE VALUES: tree tables flow as
    # device_constants jit arguments, so only their shapes key the
    # signature — m1 and m2 share
    assert scoring_signature(m1) == scoring_signature(m2)
    # deeper trees = different table shapes = different programs
    assert scoring_signature(m1) != scoring_signature(m3)
    # deterministic across loads of one artifact
    assert scoring_signature(m1) == scoring_signature(
        load_model(model_dirs["m1"]))


def test_scoring_signature_groups_lifted_linear_tenants():
    """PR 13 parameter lifting: linear-family weights flow as traced
    jit arguments (`LogisticRegressionModel.device_constants`), so two
    different same-shaped LR fits SHARE one compiled program — the
    zero-trace-onboarding contract for K-replica and warm-refit
    tenants."""
    a = _train(y_sign=1.0, forest=False)
    b = _train(y_sign=-1.0, forest=False)
    assert scoring_signature(a) == scoring_signature(b)
    assert scoring_signature(a) == scoring_signature(a)


def test_scoring_signature_is_value_sensitive_for_closure_constants():
    """Honesty check for state that still BAKES into the trace: a
    different max_iter changes nothing traced (both fits share), but
    hyperparams steering static control flow — a GBT learning rate, a
    GLM link — are value-digested via `signature_params`, and the
    quantization mode is folded into the key so a quantized and an f32
    build of ONE model can never adopt each other's programs."""
    a = _train(y_sign=1.0, forest=False)
    assert scoring_signature(a) != scoring_signature(a, quant="int8")
    assert scoring_signature(a, quant="int8") == \
        scoring_signature(a, quant="int8")
    assert scoring_signature(a, quant="int8") != \
        scoring_signature(a, quant="int4")


# --------------------------------------------------------------------- #
# shared bucket programs (the tentpole dedup contract)                  #
# --------------------------------------------------------------------- #

def test_shared_program_dedup_retrace_asserted(model_dirs):
    """Satellite acceptance: loading a second same-shaped model into a
    FleetService compiles ZERO new bucket programs
    (RetraceMonitor-asserted); a differently-shaped third compiles its
    own ladder."""
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"]},
        serving={"max_batch": 4, "batch_wait_ms": 1.0}))
    try:
        before = MONITOR.snapshot()
        fleet.add_model("m2", model_dirs["m2"])
        assert MONITOR.delta(before) == {}  # zero new traces, anywhere
        m2_info = fleet.models()["m2"]
        assert all(v == 0 for v in
                   m2_info["versions"][-1]["compile_counts"].values())
        assert m2_info["shared_from"] is not None

        before = MONITOR.snapshot()
        fleet.add_model("m3", model_dirs["m3"])
        assert sum(MONITOR.delta(before).values()) > 0
        assert fleet.models()["m3"]["shared_from"] is None

        report = fleet.pool.report()
        assert len(report) == 2
        sizes = sorted(len(e["members"]) for e in report.values())
        assert sizes == [1, 2]
    finally:
        fleet.stop()


def test_adopted_model_scores_bit_identical(model_dirs):
    """The adopted model executes the REFERENCE model's compiled
    program with its OWN tree tables as arguments — outputs must be
    bit-identical to an unshared solo load."""
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"], "m2": model_dirs["m2"]},
        serving={"max_batch": 4, "batch_wait_ms": 1.0}))
    fleet.start()
    try:
        res = fleet.score("m2", ROWS)
        solo = load_model(model_dirs["m2"])
        ds = Dataset.from_rows(ROWS, schema={"x1": t.Real, "x2": t.Real})
        direct = solo.score_compiled(ds)
        (name,) = [k for k in direct
                   if isinstance(direct[k], dict)
                   and "prediction" in direct[k]]
        np.testing.assert_array_equal(
            np.asarray(res.outputs[name]["prediction"]),
            np.asarray(direct[name]["prediction"]))
        np.testing.assert_array_equal(
            np.asarray(res.outputs[name]["probability"]),
            np.asarray(direct[name]["probability"]))
        # and the two members genuinely answer differently (different
        # fitted trees through one program); result names embed uids,
        # so resolve each model's own prediction key
        r1 = fleet.score("m1", ROWS)
        (name1,) = [k for k in r1.outputs
                    if isinstance(r1.outputs[k], dict)
                    and "prediction" in r1.outputs[k]]
        assert not np.array_equal(
            np.asarray(r1.outputs[name1]["probability"]),
            np.asarray(res.outputs[name]["probability"]))
    finally:
        fleet.stop()


def test_same_shaped_hot_swap_warms_with_zero_traces(model_dirs):
    """A rolling swap to a same-shaped candidate adopts the resident
    programs: the whole reload performs zero new traces."""
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"]},
        serving={"max_batch": 4, "batch_wait_ms": 1.0}))
    fleet.start()
    try:
        before = MONITOR.snapshot()
        out = fleet.reload_model("m1", model_dirs["m1_v2"])
        assert out["status"] == "swapped"
        assert MONITOR.delta(before) == {}
        # rollback stays instant/warm as ever
        back = fleet.rollback_model("m1")
        assert back["status"] == "rolled_back"
    finally:
        fleet.stop()


# --------------------------------------------------------------------- #
# router: quotas + priorities                                           #
# --------------------------------------------------------------------- #

def test_token_bucket_rate_and_burst():
    b = TokenBucket(rate=100.0, burst=10.0)
    assert b.try_take(10)          # full burst available
    assert not b.try_take(5)       # drained
    time.sleep(0.06)               # ~6 tokens refill at 100/s
    assert b.try_take(4)
    assert TokenBucket(rate=float("inf"), burst=1.0).try_take(10 ** 9)


def test_router_quota_sheds_only_the_offender():
    r = Router(tenants={"gold": TenantPolicy(rate=1e9, priority=1),
                        "trial": TenantPolicy(rate=10, burst=10,
                                              priority=0)})
    for _ in range(40):
        assert r.admit("gold", 8, queue_frac=0.0) == "gold"
    with pytest.raises(ScoreError) as ei:
        for _ in range(10):  # 10-row burst drains after one request
            r.admit("trial", 8, queue_frac=0.0)
    assert ei.value.code == "quota_exceeded"
    # gold keeps flowing after trial is shed
    assert r.admit("gold", 8, queue_frac=0.0) == "gold"
    snap = r.snapshot()
    assert snap["trial"]["shed"] >= 1
    assert snap["gold"]["shed"] == 0


def test_router_priority_shedding_is_graded():
    r = Router(tenants={"low": TenantPolicy(priority=0),
                        "mid": TenantPolicy(priority=1),
                        "high": TenantPolicy(priority=2)},
               shed_watermark=0.5)
    # below the watermark everyone is admitted
    for name in ("low", "mid", "high"):
        r.admit(name, 1, queue_frac=0.4)
    # just past the watermark: only the lowest class sheds
    with pytest.raises(ScoreError) as ei:
        r.admit("low", 1, queue_frac=0.55)
    assert ei.value.code == "shed_low_priority"
    r.admit("mid", 1, queue_frac=0.55)
    r.admit("high", 1, queue_frac=0.55)
    # near capacity: everything below the TOP class sheds...
    with pytest.raises(ScoreError):
        r.admit("mid", 1, queue_frac=0.99)
    # ...but the top class is never priority-shed (the bounded queue's
    # own queue_full backstop handles true saturation)
    r.admit("high", 1, queue_frac=0.99)


def test_router_unknown_tenant_gets_default_policy():
    r = Router(tenants={"gold": TenantPolicy(rate=1e9, priority=2)})
    # anonymous traffic is admitted unmetered but at the LOWEST
    # configured priority: it sheds first under pressure
    assert r.admit(None, 5, queue_frac=0.0) == "default"
    with pytest.raises(ScoreError):
        r.admit("anon", 1, queue_frac=0.9)
    r.admit("gold", 1, queue_frac=0.9)
    # explicit default policy is honored
    r2 = Router(tenants={"gold": TenantPolicy(priority=1)},
                default=TenantPolicy(rate=5, burst=5, priority=0))
    with pytest.raises(ScoreError) as ei:
        for _ in range(5):
            r2.admit("anon", 4, queue_frac=0.0)
    assert ei.value.code == "quota_exceeded"


def test_router_caps_wire_supplied_tenant_cardinality():
    """Unknown tenant names come off the wire: past `max_tenants` they
    fold into the shared default bucket instead of minting unbounded
    per-tenant state + labeled metric series."""
    r = Router(tenants={"gold": TenantPolicy(priority=1)}, max_tenants=3)
    assert r.admit("scan-1", 1, 0.0) == "scan-1"
    assert r.admit("scan-2", 1, 0.0) == "scan-2"
    for i in range(3, 50):  # cap reached: all fold into "default"
        assert r.admit(f"scan-{i}", 1, 0.0) == "default"
    snap = r.snapshot()
    assert len(snap) <= 4 + 1  # gold + 2 scans + default (+1 slack)
    assert "scan-49" not in snap


def test_router_snapshot_delta():
    r = Router(tenants={"a": TenantPolicy(), "b": TenantPolicy()})
    r.admit("a", 2, 0.0)
    r.note_success("a", "m", 2, 0.01)
    before = r.snapshot()
    r.note_success("b", "m", 7, 0.01)
    delta = r.delta(before)
    assert delta == {"b": {"requests": 1, "rows": 7, "shed": 0,
                           "errors": 0}}


# --------------------------------------------------------------------- #
# fleet service surface                                                 #
# --------------------------------------------------------------------- #

def test_fleet_unknown_model_and_duplicate_name(model_dirs):
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"]},
        serving={"max_batch": 4, "batch_wait_ms": 1.0}))
    fleet.start()
    try:
        with pytest.raises(ScoreError) as ei:
            fleet.score("nope", ROWS)
        assert ei.value.code == "not_found"
        with pytest.raises(ScoreError) as ei:
            fleet.add_model("m1", model_dirs["m2"])
        assert ei.value.code == "bad_request"
        with pytest.raises(ScoreError) as ei:
            fleet.remove_model("nope")
        assert ei.value.code == "not_found"
    finally:
        fleet.stop()


def test_fleet_config_validates_serving_keys(model_dirs):
    with pytest.raises(ValueError, match="unknown serving config"):
        FleetService(FleetConfig(models={"m1": model_dirs["m1"]},
                                 serving={"max_batchs": 8}))
    with pytest.raises(ValueError, match="model spec"):
        FleetService(FleetConfig(models={"m1": {"dir": "x"}}))


def test_fleet_rolling_swap_zero_drops_for_other_models(model_dirs):
    """In-process version of the smoke assertion: traffic on m2/m3
    sees zero errors while m1 is swapped."""
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"], "m2": model_dirs["m2"],
                "m3": model_dirs["m3"]},
        serving={"max_batch": 4, "batch_wait_ms": 1.0,
                 "max_queue": 512}))
    fleet.start()
    errors = {"m2": 0, "m3": 0}
    served = {"m2": 0, "m3": 0}
    halt = threading.Event()

    def client(model):
        while not halt.is_set():
            try:
                fleet.score(model, ROWS, deadline_ms=10_000)
                served[model] += 1
            except Exception:
                errors[model] += 1

    threads = [threading.Thread(target=client, args=(m,))
               for m in ("m2", "m3")]
    try:
        for th in threads:
            th.start()
        out = fleet.reload_model("m1", model_dirs["m1_v2"])
        time.sleep(0.2)
    finally:
        halt.set()
        for th in threads:
            th.join(timeout=5)
        fleet.stop()
    assert out["status"] == "swapped"
    assert errors == {"m2": 0, "m3": 0}
    assert served["m2"] > 0 and served["m3"] > 0


def test_fleet_goodput_section_from_rolling_swap(model_dirs):
    from transmogrifai_tpu.obs.goodput import build_report
    from transmogrifai_tpu.obs.trace import TRACER
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"]},
        tenants={"acme": {"rate": 1e9, "priority": 1}},
        serving={"max_batch": 4, "batch_wait_ms": 1.0}))
    fleet.start()
    try:
        with TRACER.span("run:test-fleet", category="run",
                         new_trace=True) as root:
            fleet.score("m1", ROWS, tenant="acme")
            fleet.reload_model("m1", model_dirs["m1_v2"])
            fleet.score("m1", ROWS, tenant="acme")
        report = build_report(root, TRACER.trace_spans(root.trace_id))
    finally:
        fleet.stop()
    assert report.fleet["swaps"] == 1
    assert report.fleet["swapped"] == 1
    assert report.fleet["swap_wall_s"] > 0
    assert report.to_json()["fleet"]["swaps"] == 1


# --------------------------------------------------------------------- #
# warmup manifest + persistent-compile accounting                       #
# --------------------------------------------------------------------- #

def test_warmup_manifest_roundtrip(tmp_path):
    d = str(tmp_path)
    assert load_warmup_manifest(d) is None
    assert save_warmup_manifest(d, {"fingerprint": "abc", "warm_s": 1.5})
    m = load_warmup_manifest(d)
    assert m["fingerprint"] == "abc" and m["warm_s"] == 1.5
    # garbage/foreign-version sidecars read as cold, never raise
    (tmp_path / "warmup.json").write_text("{torn")
    assert load_warmup_manifest(d) is None
    (tmp_path / "warmup.json").write_text(
        json.dumps({"warmup_version": 99, "fingerprint": "abc"}))
    assert load_warmup_manifest(d) is None


def test_cold_warmup_writes_manifest_and_warm_start_claims_savings(
        model_dirs, monkeypatch, tmp_path):
    """First service over an artifact records its cold warmup in the
    sidecar; a second service with the persistent compile cache enabled
    matches the manifest and records `serving_compile_cache_saved_s`."""
    import transmogrifai_tpu.utils.compile_cache as cc
    # pretend-enable the cache: touching the real process-global jax
    # compilation-cache config from a unit test would leak into every
    # later compile in the suite
    monkeypatch.setattr(cc, "enable_compile_cache",
                        lambda min_compile_s=0.5:
                        str(tmp_path / "cache"))
    svc = ScoringService.from_path(
        model_dirs["m3"], config=ServingConfig(max_batch=4))
    svc.stop()
    manifest = load_warmup_manifest(model_dirs["m3"])
    assert manifest is not None
    assert manifest["warm_s"] > 0 and manifest["compiles"] > 0
    assert manifest["ladder"] == [1, 2, 4]

    svc2 = ScoringService.from_path(
        model_dirs["m3"],
        config=ServingConfig(max_batch=4, compile_cache=True))
    svc2.stop()
    info = svc2.health()["versions"][-1]
    assert "compile_cache_saved_s" in info
    assert "serving_compile_cache_saved_s" in svc2.registry.to_json()


def test_manifest_ladder_mismatch_reads_as_cold(model_dirs, monkeypatch,
                                                tmp_path):
    import transmogrifai_tpu.utils.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache",
                        lambda min_compile_s=0.5:
                        str(tmp_path / "cache"))
    save_warmup_manifest(model_dirs["m3"], {
        "fingerprint": "not-the-fingerprint", "ladder": [1, 2, 4],
        "warm_s": 99.0, "compiles": 3})
    svc = ScoringService.from_path(
        model_dirs["m3"],
        config=ServingConfig(max_batch=4, compile_cache=True))
    svc.stop()
    info = svc.health()["versions"][-1]
    # mismatched fingerprint: no savings claim; the genuine cold warmup
    # does NOT overwrite someone else's sidecar blindly either — it
    # writes its own record (fingerprint now current)
    assert "compile_cache_saved_s" not in info
    m = load_warmup_manifest(model_dirs["m3"])
    assert m["fingerprint"] == info["version"]


def test_adoption_warmed_member_claims_no_compile_cache_savings(
        model_dirs, monkeypatch, tmp_path):
    """A member warmed through SHARED programs (zero traces) must not
    book the manifest's cold baseline as compile-cache savings — that
    recovery belongs to program sharing, not the persistent cache."""
    import transmogrifai_tpu.utils.compile_cache as cc
    monkeypatch.setattr(cc, "enable_compile_cache",
                        lambda min_compile_s=0.5:
                        str(tmp_path / "cache"))
    # give m2 a plausible manifest matching its fingerprint + ladder
    from transmogrifai_tpu.workflow.serialization import model_fingerprint
    save_warmup_manifest(model_dirs["m2"], {
        "fingerprint": model_fingerprint(model_dirs["m2"]),
        "ladder": [1, 2, 4], "warm_s": 9.9, "compiles": 3})
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"], "m2": model_dirs["m2"]},
        serving={"max_batch": 4, "batch_wait_ms": 1.0},
        compile_cache=True))
    try:
        info = fleet.models()["m2"]["versions"][-1]
        assert sum(int(v) for v in info["compile_counts"].values()) == 0
        assert "compile_cache_saved_s" not in info
    finally:
        fleet.stop()


def test_add_model_reservation_blocks_duplicates_and_lookups(model_dirs):
    """The name is reserved under the lock before the slow load/warm: a
    concurrent duplicate add fails fast and scoring against the
    still-loading name is a structured not_found, never a half-built
    member."""
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"]},
        serving={"max_batch": 4, "batch_wait_ms": 1.0}))
    try:
        with fleet._lock:
            fleet._services["loading"] = None  # in-flight reservation
        with pytest.raises(ScoreError) as ei:
            fleet.add_model("loading", model_dirs["m2"])
        assert ei.value.code == "bad_request"
        with pytest.raises(ScoreError) as ei:
            fleet.score("loading", ROWS)
        assert ei.value.code == "not_found"
        assert "loading" not in fleet.models()
        # a failed load releases its reservation
        with pytest.raises(Exception):
            fleet.add_model("bad", "/nonexistent/model/dir")
        fleet.add_model("bad", model_dirs["m2"])  # name reusable
    finally:
        fleet.stop()


def test_shared_warmup_never_becomes_cold_baseline(model_dirs):
    """An adoption-warmed member (zero compiles) must not write a
    near-zero 'cold' manifest that would poison future savings math."""
    import os
    wpath = os.path.join(model_dirs["m2"], "warmup.json")
    if os.path.exists(wpath):
        os.unlink(wpath)
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"], "m2": model_dirs["m2"]},
        serving={"max_batch": 4, "batch_wait_ms": 1.0}))
    fleet.stop()
    m1_manifest = load_warmup_manifest(model_dirs["m1"])
    assert m1_manifest is not None and m1_manifest["compiles"] > 0
    assert load_warmup_manifest(model_dirs["m2"]) is None


# --------------------------------------------------------------------- #
# HTTP frontend                                                         #
# --------------------------------------------------------------------- #

@pytest.fixture()
def fleet_http(model_dirs):
    from transmogrifai_tpu.serving.http import serve_fleet
    fleet = FleetService(FleetConfig(
        models={"m1": model_dirs["m1"], "m3": model_dirs["m3"]},
        tenants={"gold": {"rate": 1e9, "priority": 1},
                 "trial": {"rate": 3, "burst": 3, "priority": 0}},
        serving={"max_batch": 4, "batch_wait_ms": 1.0}))
    fleet.start()
    server, _ = serve_fleet(fleet, port=0, block=False)
    yield fleet, f"http://127.0.0.1:{server.port}"
    server.shutdown()
    server.server_close()
    fleet.stop()


def _post(url, payload, headers=None):
    req = urllib.request.Request(
        url, data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST")
    with urllib.request.urlopen(req, timeout=30) as resp:
        return json.loads(resp.read())


def test_fleet_http_score_models_health_metrics(fleet_http):
    fleet, base = fleet_http
    out = _post(f"{base}/score", {"model": "m1", "rows": ROWS},
                headers={"X-Tenant": "gold"})
    assert out["model"] == "m1" and len(out["scores"]) == 3
    health = json.loads(urllib.request.urlopen(
        f"{base}/healthz", timeout=30).read())
    assert health["status"] == "ok"
    assert set(health["models"]) == {"m1", "m3"}
    assert health["tenants"]["gold"]["requests"] >= 1
    models = json.loads(urllib.request.urlopen(
        f"{base}/models", timeout=30).read())["models"]
    assert set(models) == {"m1", "m3"}
    prom = urllib.request.urlopen(f"{base}/metrics", timeout=30) \
        .read().decode()
    assert 'fleet_requests_total{model="m1",tenant="gold"}' in prom
    mjson = json.loads(urllib.request.urlopen(
        f"{base}/metrics?format=json", timeout=30).read())
    assert "fleet" in mjson and set(mjson["models"]) == {"m1", "m3"}


def test_fleet_http_error_mapping(fleet_http):
    fleet, base = fleet_http
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(f"{base}/score", {"model": "nope", "rows": ROWS})
    assert ei.value.code == 404
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(f"{base}/score", {"rows": ROWS})
    assert ei.value.code == 400
    # over-quota tenant -> 429 with the structured code; gold untouched
    codes = []
    for _ in range(4):
        try:
            _post(f"{base}/score", {"model": "m1", "rows": ROWS,
                                    "tenant": "trial"})
            codes.append(200)
        except urllib.error.HTTPError as e:
            codes.append(e.code)
            body = json.loads(e.read())
            assert body["error"] == "quota_exceeded"
    assert 429 in codes
    _post(f"{base}/score", {"model": "m1", "rows": ROWS,
                            "tenant": "gold"})


def test_fleet_http_reload_and_rollback(fleet_http, model_dirs):
    fleet, base = fleet_http
    out = _post(f"{base}/reload", {"model": "m1",
                                   "model_location": model_dirs["m1_v2"]})
    assert out["status"] == "swapped"
    out = _post(f"{base}/reload", {"model": "m1", "rollback": True})
    assert out["status"] == "rolled_back"
    with pytest.raises(urllib.error.HTTPError) as ei:
        _post(f"{base}/reload", {"model": "nope",
                                 "model_location": model_dirs["m2"]})
    assert ei.value.code == 404


# --------------------------------------------------------------------- #
# params / CLI threading                                                #
# --------------------------------------------------------------------- #

def test_serving_params_fleet_and_compile_cache_roundtrip():
    from transmogrifai_tpu.workflow.params import ServingParams
    sp = ServingParams.from_json({
        "max_batch": 16, "compile_cache": True,
        "warmup_manifest": False,
        "fleet": {"models": {"a": "dir_a"},
                  "tenants": {"t": {"rate": 5, "priority": 1}}}})
    assert sp.to_json()["compile_cache"] is True
    cfg = sp.to_config()
    assert cfg.compile_cache is True
    assert cfg.warmup_manifest is False
    fc = sp.to_fleet_config()
    assert isinstance(fc, FleetConfig)
    assert fc.models == {"a": "dir_a"}
    assert fc.compile_cache is True
    # service-level knobs become the members' shared serving defaults
    assert fc.serving["max_batch"] == 16
    assert fc.serving["warmup_manifest"] is False
    with pytest.raises(ValueError):
        ServingParams().to_fleet_config()
