"""FleetService: multi-model tenancy with shared bucket programs.

The reference's production story is many models served to many tenants
(TF-Serving's multi-model servables, arxiv 1605.08695); until this
module one process served exactly ONE `WorkflowModel` and every cold
start re-traced and re-compiled the whole bucket ladder. A
`FleetService` hosts N named models in one process, each member keeping
the full `ScoringService` contract (own micro-batcher and scoring
thread, versioned hot-swap with resident rollback, per-request error
quarantine), and adds the two fleet-scale mechanisms:

**Shared bucket programs.** Two models whose scoring-segment static
signature agrees compile ONE set of bucket programs — keyed the same
way `parallel/sweep.static_signature` keys compile groups: everything
that shapes the traced program goes into the key, everything that flows
as a traced ARGUMENT stays out. Concretely (`scoring_signature`): the
canonical device/host segment wiring with uids replaced by traversal
indices, each stage's class + fitted params — where a stage that routes
its fitted arrays through `device_constants()` (the tree families, the
megabyte tables that dominate compile time) contributes only their
SHAPES/dtypes, because those arrays are jit arguments, while fitted
state a `device_apply` reads off `self` is a closure constant baked
into the XLA program and is therefore value-digested. The upshot: K
replicas of one artifact, and K tree-family models that differ only in
tree-table values (e.g. a continual warm-refit candidate next to its
parent), all execute the FIRST member's compiled programs — the
`ProgramPool` rewires an adopting scorer's segment functions onto the
reference scorer's jitted callables through a uid-bijection adapter, so
the second model's warmup performs ZERO new traces
(`RetraceMonitor.delta()`-asserted in tests and `make fleet-smoke`).

**Persistent-compile cold starts.** `ServingConfig.compile_cache`
(threaded from `ServingParams`/CLI) turns on JAX's persistent
compilation cache with a 0-second persistence threshold at service
construction, and each cold warmup writes an AOT warmup manifest
(`workflow/serialization.save_warmup_manifest`) beside the model
artifact recording the ladder, scoring signature, and cold warm wall
seconds. A replica (or a same-shaped swap) that finds a matching
manifest reaches first-score on cache hits instead of fresh XLA
compiles and reports the recovered seconds as
`serving_compile_cache_saved_s` (+ a `compile_cache_saved` goodput
event).

Admission and routing (per-tenant token-bucket quotas, priority
shedding, per-tenant metrics) live in `serving/router.py`; the fleet
HTTP frontend in `serving/http.py` (`serve_fleet`).

Thread-safety note: adopted members call the reference member's jitted
callables from their own scoring threads — `jax.jit` executables are
safe for concurrent invocation; mutation of the member table itself is
lock-guarded.
"""

from __future__ import annotations

import contextlib
import hashlib
import json
import logging
import threading
import time
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Tuple

import numpy as np

from transmogrifai_tpu.obs.metrics import MetricsRegistry
from transmogrifai_tpu.obs.trace import (
    TRACER, RequestTrace, TailSampler, TraceContext, TracingParams)
from transmogrifai_tpu.serving.batcher import ScoreError
from transmogrifai_tpu.serving.router import Router, TenantPolicy
from transmogrifai_tpu.serving.service import ScoringService, ServingConfig

log = logging.getLogger(__name__)

__all__ = ["FleetConfig", "FleetService", "ProgramPool",
           "scoring_signature"]


# --------------------------------------------------------------------------- #
# Scoring-segment static signature                                            #
# --------------------------------------------------------------------------- #

def _canonical_graph(model) -> Tuple[List[Any], List[Any]]:
    """Deterministic (features, fitted stages) walk of a model graph —
    the SAME traversal `save_model` serializes with, so two loads of one
    pipeline shape enumerate in the same order. Returns (feature list,
    fitted-stage list); uids map to positions in these lists."""
    feats: Dict[str, Any] = {}
    order: List[Any] = []
    for rf in model.result_features:
        for f in rf.traverse():
            if f.uid not in feats:
                feats[f.uid] = f
                order.append(f)
    stages: List[Any] = []
    seen: set = set()
    for f in order:
        st = f.origin_stage
        if st is not None and st.uid not in seen:
            seen.add(st.uid)
            stages.append(model.fitted.get(st.uid, st))
    return order, stages


def canonical_uids(model) -> List[str]:
    """Feature uids then stage uids in canonical order: two models with
    equal `scoring_signature` zip these lists into the uid bijection the
    program-sharing adapter remaps argument pytrees with."""
    order, stages = _canonical_graph(model)
    return [f.uid for f in order] + [s.uid for s in stages]


def _digest_value(v: Any, shape_only: bool) -> Any:
    """Canonical JSON-able form of one fitted-param value. Arrays under
    `shape_only` (the stage ships them as `device_constants()` jit
    arguments) contribute shape+dtype; otherwise their BYTES are hashed
    — they are closure constants of the traced program, so their values
    are part of the compile key."""
    if isinstance(v, dict):
        return {str(k): _digest_value(x, shape_only)
                for k, x in sorted(v.items(), key=lambda kv: str(kv[0]))}
    if isinstance(v, (list, tuple, np.ndarray)) or (
            hasattr(v, "shape") and hasattr(v, "dtype")):  # jax arrays too
        try:
            arr = np.asarray(v)
        except Exception:
            arr = None
        if arr is not None and arr.dtype != object:
            if shape_only:
                return ["#array", list(arr.shape), str(arr.dtype)]
            h = hashlib.sha256(np.ascontiguousarray(arr).tobytes())
            return ["#array", list(arr.shape), str(arr.dtype),
                    h.hexdigest()[:16]]
        return [_digest_value(x, shape_only) for x in v]
    if isinstance(v, (np.integer,)):
        return int(v)
    if isinstance(v, (np.floating,)):
        return float(v)
    if v is None or isinstance(v, (bool, int, float, str)):
        return v
    if callable(v):
        # stable identity only — never the repr (memory addresses drift)
        return ["#fn", getattr(v, "__module__", "?"),
                getattr(v, "__qualname__", getattr(v, "__name__", "?"))]
    return ["#repr", type(v).__name__, str(v)]


def _stage_signature(stage) -> Dict[str, Any]:
    from transmogrifai_tpu.stages.base import (
        FeatureGeneratorStage, is_host_stage)
    if isinstance(stage, FeatureGeneratorStage):
        # generators run on host per batch; only the produced ftype
        # shapes the device program (raw column NAMES stay out of the
        # key — renamed tenants still share)
        return {"kind": "raw"}
    entry: Dict[str, Any] = {
        "kind": "host" if is_host_stage(stage) else "device"}
    consts = None
    try:
        consts = stage.device_constants()
    except Exception:  # unfitted/host stages may not support it
        consts = None
    shape_only = consts is not None
    # signature_params (stages/base.py) is the stage's own statement of
    # which fitted facts shape the TRACE: lifted families (linear/GLM/
    # trees…) exclude the weight values they route through
    # device_constants() — two same-shaped fits then share — while
    # trace-steering hyperparams (GLM link, GBT learning rate) stay
    # value-digested
    try:
        params = stage.signature_params()
    except Exception:
        params = stage.get_params()
    entry["params"] = _digest_value(params, shape_only)
    if shape_only:
        # the consts pytree structure is part of the jit argument
        # structure even when its values are not
        entry["consts"] = _digest_value(consts, True)
    return entry


def scoring_signature(model, quant: Any = None) -> str:
    """The compile-group key of a model's bucket programs (the serving
    analogue of `parallel/sweep.static_signature`): a sha256 digest of
    the canonical scoring graph — segment wiring with uids replaced by
    traversal indices, stage classes, and fitted state partitioned into
    traced-argument facts (shape/dtype for `device_constants()` arrays)
    vs closure-constant facts (value digests for everything a
    `device_apply` reads off `self`). Two models with equal signatures
    trace byte-identical XLA programs per bucket and may share one
    compiled set through the `ProgramPool`.

    `quant` (a `workflow.compiled.ScoringQuant`, its mode string, or
    None) folds the quantized-inference config into the key: a
    quantized and an unquantized build of one model trace DIFFERENT
    programs (narrow wire structure, narrowed table dtypes) and must
    never adopt each other's bucket programs."""
    from transmogrifai_tpu.workflow.compiled import ScoringQuant
    q = ScoringQuant.resolve(quant)
    order, stages = _canonical_graph(model)
    fidx = {f.uid: i for i, f in enumerate(order)}
    sidx = {s.uid: i for i, s in enumerate(stages)}
    doc = {
        "quant": q.mode if q is not None else None,
        "features": [{
            "ftype": f.ftype.__name__,
            "is_response": bool(f.is_response),
            "origin": (sidx.get(f.origin_stage.uid)
                       if f.origin_stage is not None else None),
            "parents": [fidx[p.uid] for p in f.parents],
        } for f in order],
        "stages": [{
            "class": type(s).__name__,
            "op": s.operation_name,
            "inputs": [fidx[f.uid] for f in s.input_features],
            **_stage_signature(s),
        } for s in stages],
        "results": [fidx[f.uid] for f in model.result_features],
    }
    blob = json.dumps(doc, sort_keys=True, separators=(",", ":"))
    return hashlib.sha256(blob.encode()).hexdigest()[:16]


# --------------------------------------------------------------------------- #
# Program pool                                                                #
# --------------------------------------------------------------------------- #

@dataclass
class _PoolEntry:
    signature: str
    owner: str                      # "<model>:<version>" of the reference
    scorer: Any                     # the reference CompiledScorer (alive!)
    uids: List[str]                 # its canonical uid list
    members: List[str] = field(default_factory=list)


class ProgramPool:
    """signature -> reference compiled scorer. The first model to
    register a signature keeps its own jitted segment functions and
    becomes the REFERENCE; later models with the same signature are
    ADOPTED: their scorer's segment functions are replaced by adapters
    that remap every uid-keyed argument pytree (consts / encs /
    dev_vals) onto the reference's uids, invoke the reference's
    already-compiled program, and remap the outputs back. Values that
    differ between members (device_constants arrays, host_prepare
    encodings, raw batch columns) are exactly the values that flow as
    jit ARGUMENTS, so adoption is numerics-preserving by construction;
    everything baked into the trace is signature-equal.

    The entry holds the reference scorer, so its programs outlive the
    reference model's own serving lifecycle (unloading the reference
    member never invalidates its adoptees)."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entries: Dict[str, _PoolEntry] = {}

    def adopt_or_register(self, member: str, model,
                          scorer) -> Optional[str]:
        """Register `scorer` as the reference for its signature, or
        adopt it onto an existing reference. Returns the reference
        owner's member id when adopted, None when this scorer IS the
        reference."""
        # the scorer's quantization config is part of the compile-group
        # key: a quantized member can never adopt an f32 member's
        # programs (different wire structure and table dtypes)
        sig = scoring_signature(model, quant=getattr(scorer, "quant", None))
        uids = canonical_uids(model)
        with self._lock:
            entry = self._entries.get(sig)
            if entry is None:
                self._entries[sig] = _PoolEntry(
                    signature=sig, owner=member, scorer=scorer,
                    uids=uids, members=[member])
                scorer.program_signature = sig
                return None
            entry.members.append(member)
        self._adopt(scorer, uids, entry)
        scorer.program_signature = sig
        scorer.shared_from = entry.owner
        log.info("fleet: %s adopts bucket programs of %s (signature %s)",
                 member, entry.owner, sig)
        return entry.owner

    @staticmethod
    def _adopt(scorer, uids: List[str], entry: _PoolEntry) -> None:
        if len(uids) != len(entry.uids) or \
                len(scorer.segments) != len(entry.scorer.segments):
            # signatures collided but graphs disagree structurally —
            # impossible short of a hash collision; serve solo
            log.warning("fleet: signature %s structural mismatch; "
                        "member keeps its own programs", entry.signature)
            return
        b2a = dict(zip(uids, entry.uids))
        a2b = {a: b for b, a in b2a.items()}
        fns: List[Any] = []
        for (kind, _), ref_fn in zip(scorer.segments,
                                     entry.scorer._seg_fns):
            if kind != "device":
                fns.append(None)
                continue

            def adapter(consts, encs, dev_vals, _ref=ref_fn):
                out = _ref({b2a[k]: v for k, v in consts.items()},
                           {b2a[k]: v for k, v in encs.items()},
                           {b2a[k]: v for k, v in dev_vals.items()})
                return {a2b[k]: v for k, v in out.items()}

            fns.append(adapter)
        scorer._seg_fns = fns

    def report(self) -> Dict[str, Dict[str, Any]]:
        """signature -> {owner, members}: the dedup proof surface the
        fleet exposes on /healthz."""
        with self._lock:
            return {sig: {"owner": e.owner, "members": list(e.members)}
                    for sig, e in self._entries.items()}


# --------------------------------------------------------------------------- #
# Fleet service                                                               #
# --------------------------------------------------------------------------- #

class FleetMemberService(ScoringService):
    """One named model inside a fleet: a full ScoringService whose every
    installed version (initial load, hot-swap candidates) first offers
    its compiled scorer to the fleet's ProgramPool — so a same-shaped
    swap candidate adopts the resident programs and warms with zero new
    traces."""

    def __init__(self, fleet_name: str, pool: ProgramPool, **kw):
        self._fleet_name = fleet_name
        self._pool = pool
        self.shared_from: Optional[str] = None
        super().__init__(**kw)
        # the FleetService-level watchdog supervises every member; a
        # per-member watchdog thread would be N redundant supervisors
        self._own_watchdog = False
        # chaos plans target one member's fault sites by name
        # (serving.device_dispatch#<member>) and health events carry it
        self.fault_scope = fleet_name
        if self._health is not None:
            self._health.member = fleet_name

    def _install(self, model, version_id: str, path: Optional[str] = None):
        scorer = model._ensure_compiled(quant=self.config.quantize)
        self.shared_from = self._pool.adopt_or_register(
            f"{self._fleet_name}:{version_id}", model, scorer)
        return super()._install(model, version_id, path=path)


@dataclass
class FleetConfig:
    """JSON-loadable fleet layout: named models, tenant policies, shared
    serving defaults. Example::

        {"models": {"churn": "models/churn",
                    "churn-eu": {"path": "models/churn_eu",
                                 "serving": {"max_batch": 32}}},
         "tenants": {"acme": {"rate": 200, "burst": 400, "priority": 1},
                     "trial": {"rate": 20, "priority": 0}},
         "serving": {"max_batch": 16},
         "compile_cache": true}
    """

    models: Dict[str, Any] = field(default_factory=dict)
    tenants: Dict[str, Any] = field(default_factory=dict)
    # policy for tenants not named above (None = admit unmetered at the
    # lowest priority, so configured tenants always outrank anonymous
    # traffic under pressure)
    default_tenant: Optional[Dict[str, Any]] = None
    shed_watermark: float = 0.5
    serving: Dict[str, Any] = field(default_factory=dict)
    compile_cache: Optional[bool] = None
    # serving/resilience.ResilienceParams JSON: shared default for every
    # member's health machine / breaker / watchdog (a member spec's
    # serving overrides may still pin its own `resilience` block)
    resilience: Optional[Dict[str, Any]] = None
    # obs/slo.SLOParams JSON evaluated over the FLEET registry: per-
    # tenant/per-model availability + latency objectives judged from
    # the labeled fleet_* series (member-level SLOs go in a member's
    # own serving config instead)
    slo: Optional[Dict[str, Any]] = None
    # shared state plane (store/): the artifact-store root this replica
    # shares with its peers, this replica's name, and whether tenant
    # quotas meter against the CAS-guarded fleet-wide balance instead of
    # a private per-replica bucket (the K-replica tenant invariant)
    store_dir: Optional[str] = None
    replica: str = "r0"
    shared_quota: bool = False
    # serving/autopilot.AutopilotParams JSON: the SLO-burn-driven
    # supervisor (rebucket re-arm, fidelity route flips, predictive
    # admission, warm-spare activation). None = no controller; requires
    # an `slo` block (the burn signal it closes the loop on)
    autopilot: Optional[Dict[str, Any]] = None
    # obs/federate.FleetObs knobs (requires store_dir): trace-shard
    # publishing, metrics snapshots, incident correlation. Keys:
    # enabled (default True when store_dir is set), metrics_period_s,
    # capture_window_s
    obs: Optional[Dict[str, Any]] = None

    _FIELDS = ("models", "tenants", "default_tenant", "shed_watermark",
               "serving", "compile_cache", "resilience", "slo", "store_dir", "replica",
               "shared_quota", "autopilot", "obs")

    @staticmethod
    def from_json(d: Dict[str, Any]) -> "FleetConfig":
        return FleetConfig(**{k: d[k] for k in FleetConfig._FIELDS
                              if k in d})

    def to_json(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self._FIELDS}

    @staticmethod
    def load(path: str) -> "FleetConfig":
        with open(path) as fh:
            return FleetConfig.from_json(json.load(fh))


def _model_spec(spec: Any) -> Tuple[str, Dict[str, Any]]:
    if isinstance(spec, str):
        return spec, {}
    if isinstance(spec, dict) and spec.get("path"):
        return str(spec["path"]), dict(spec.get("serving") or {})
    raise ValueError(f"fleet model spec must be a path or "
                     f'{{"path": ...}}: {spec!r}')


class FleetService:
    """N named models, one process. See module docstring.

    Usage::

        fleet = FleetService(FleetConfig(
            models={"a": "dir_a", "b": "dir_b"},
            tenants={"acme": {"rate": 100, "priority": 1}}))
        fleet.start()
        fleet.score("a", rows, tenant="acme")
        fleet.reload_model("b", "dir_b_v2")   # others undisturbed
        fleet.stop()
    """

    def __init__(self, config: Optional[FleetConfig] = None,
                 registry: Optional[MetricsRegistry] = None):
        self.config = config or FleetConfig()
        self.registry = registry or MetricsRegistry()
        self.pool = ProgramPool()
        self.shared_quota = None
        if self.config.shared_quota and self.config.store_dir:
            from transmogrifai_tpu.store import SharedQuota
            self.shared_quota = SharedQuota(
                self.config.store_dir, replica=self.config.replica,
                registry=self.registry)
        self.router = Router(
            tenants={name: TenantPolicy.from_json(p)
                     for name, p in (self.config.tenants or {}).items()},
            default=(TenantPolicy.from_json(self.config.default_tenant)
                     if self.config.default_tenant else None),
            shed_watermark=self.config.shed_watermark,
            registry=self.registry,
            shared=self.shared_quota)
        self._lock = threading.Lock()
        self._services: Dict[str, FleetMemberService] = {}
        self._started = False
        self.started_at = time.time()
        # fleet-level hang watchdog: ONE supervisor heartbeats every
        # member's scoring loop (serving/resilience.Watchdog); members
        # skip their own per-service watchdog threads
        from transmogrifai_tpu.serving.resilience import (
            ResilienceParams, Watchdog)
        self._resilience = ResilienceParams.from_json(
            self.config.resilience
            or (self.config.serving or {}).get("resilience"))
        self.watchdog: Optional[Watchdog] = None
        if self._resilience.enabled:
            self.watchdog = Watchdog(
                self._live_services,
                period_s=self._resilience.watchdog_period_s,
                name="fleet-watchdog")
        self._m_models = self.registry.gauge(
            "fleet_models", "models currently hosted by this process")
        self._m_shared = self.registry.gauge(
            "fleet_shared_signatures",
            "distinct compiled bucket-program sets across all models")
        # fleet-level request tracing: admission (router) spans + the
        # sampler that judges admission-shed traces (requests that never
        # reach a member service); members sample their own
        self.tracing = TracingParams.from_json(
            (self.config.serving or {}).get("tracing"))
        self.sampler: Optional[TailSampler] = (
            TailSampler(self.tracing, registry=self.registry)
            if self.tracing.enabled else None)
        # fleet-level SLO engine over the labeled fleet_* series
        self.slo_engine = None
        if self.config.slo and dict(self.config.slo).get("enabled", True):
            self._build_slo_engine()
        # fidelity route flips (autopilot-owned): requests for a key
        # model resolve to the mapped resident sibling (e.g. its
        # int8-calibrated build) until cleared — a table write, no
        # compile, no drop (the quant sibling is a separate member
        # whose programs never adopt the f32 member's)
        self._fidelity_routes: Dict[str, str] = {}  # guarded-by: self._lock
        # SLO-burn autopilot (opt-in via config.autopilot; needs slo)
        self.autopilot = None
        if self.config.autopilot and \
                dict(self.config.autopilot).get("enabled", True):
            from transmogrifai_tpu.serving.autopilot import (
                Autopilot, AutopilotParams)
            self.autopilot = Autopilot(
                self, AutopilotParams.from_json(self.config.autopilot))
        # fleet observability federation (trace shards + metrics
        # snapshots + incident correlation) over the shared store
        self.fleetobs = None
        obs_cfg = dict(self.config.obs or {})
        if self.config.store_dir and obs_cfg.get("enabled", True):
            try:
                from transmogrifai_tpu.obs.federate import FleetObs
                self.fleetobs = FleetObs(
                    self.config.store_dir, self.config.replica,
                    snapshot_fn=self._obs_snapshot,
                    metrics_period_s=float(
                        obs_cfg.get("metrics_period_s", 1.0)),
                    capture_window_s=float(
                        obs_cfg.get("capture_window_s", 10.0)))
            except Exception:
                log.warning("fleet: observability federation disabled "
                            "(setup failed)", exc_info=True)
        for name, spec in (self.config.models or {}).items():
            path, overrides = _model_spec(spec)
            self.add_model(name, path, overrides)

    def _build_slo_engine(self) -> None:
        """Per-tenant/per-model SLOs judged from the fleet registry's
        labeled series: availability from fleet_requests/errors/shed,
        latency from the per-tenant latency histogram, staleness from
        the continual freshness gauge on the process registry."""
        from transmogrifai_tpu.obs.metrics import get_registry
        from transmogrifai_tpu.obs.slo import (
            SLOEngine, SLOParams, availability_source, latency_source,
            staleness_source)
        params = SLOParams.from_json(self.config.slo)
        engine = SLOEngine(params, registry=self.registry)
        for slo in engine.slos():
            if slo.kind == "availability":
                # the error/shed families carry a tenant label but no
                # model label, so availability SLOs scope by TENANT
                # (a model-scoped availability needs per-member SLOs
                # on that member's own serving config).
                # fleet_requests_total ticks in Router.note_success —
                # SUCCESSES only — so the source must build the
                # denominator as successes+errors+sheds, or a total
                # outage (no successes) would zero the window and
                # never fire
                scope = {"tenant": slo.tenant} if slo.tenant else {}
                engine.set_source(slo.name, availability_source(
                    self.registry, "fleet_requests_total",
                    error_families=("fleet_errors_total",),
                    shed_families=("fleet_shed_total",),
                    requests_count="successes", **scope))
            elif slo.kind == "latency":
                engine.set_source(slo.name, latency_source(
                    self.registry, "fleet_request_latency_seconds",
                    slo.threshold_s,
                    **({"tenant": slo.tenant} if slo.tenant else {})))
            elif slo.kind == "staleness":
                engine.set_source(slo.name, staleness_source(
                    get_registry(), "continual_staleness_current_seconds",
                    slo.threshold_s))
        if self.config.store_dir:
            # a configured store IS the fleet: share burn state (and the
            # fleet alert latch) through it directly — the env-var path
            # stays for processes without a FleetConfig
            try:
                engine.attach_fleet(self.config.store_dir,
                                    self.config.replica)
            except Exception:
                log.debug("fleet: slo fleet attach failed", exc_info=True)
        else:
            from transmogrifai_tpu.obs.slo import maybe_attach_fleet
            maybe_attach_fleet(engine)
        self.slo_engine = engine

    # -- membership -------------------------------------------------------- #

    def _live_services(self) -> Dict[str, FleetMemberService]:
        with self._lock:
            return {k: v for k, v in self._services.items()
                    if v is not None}

    def _serving_config(self, overrides: Dict[str, Any]) -> ServingConfig:
        base = dict(self.config.serving or {})
        base.update(overrides or {})
        if self.config.resilience is not None:
            base.setdefault("resilience", self.config.resilience)
        if self.config.compile_cache is not None:
            base.setdefault("compile_cache", self.config.compile_cache)
        known = {f for f in ServingConfig.__dataclass_fields__}
        unknown = set(base) - known
        if unknown:
            raise ValueError(
                f"unknown serving config keys: {sorted(unknown)}")
        return ServingConfig(**base)

    def add_model(self, name: str, path: str,
                  overrides: Optional[Dict[str, Any]] = None
                  ) -> FleetMemberService:
        """Load + warm a model under `name` (programs shared through the
        pool where signatures agree) and start serving it if the fleet
        is running. Rejects duplicate names."""
        from transmogrifai_tpu.workflow.serialization import (
            load_model, model_fingerprint)
        cfg = self._serving_config(overrides or {})
        # reserve the name UNDER the lock before the slow load/warm: a
        # concurrent duplicate add_model must fail fast, not overwrite a
        # member whose scoring thread would then leak for the process
        # lifetime
        with self._lock:
            if name in self._services:
                raise ScoreError("bad_request",
                                 f"model {name!r} already hosted")
            self._services[name] = None  # reservation
        try:
            model = load_model(path)
            svc = FleetMemberService(
                name, self.pool, model=model,
                version_id=model_fingerprint(path), config=cfg)
        except BaseException:
            with self._lock:
                if self._services.get(name) is None:
                    self._services.pop(name, None)
            raise
        with self._lock:
            if name not in self._services:
                # removed (or the whole fleet reconfigured) mid-load:
                # don't resurrect a member nobody tracks
                removed = True
            else:
                removed = False
                self._services[name] = svc
                if self._started:
                    svc.start()
        if removed:
            svc.stop()
            raise ScoreError("bad_request",
                             f"model {name!r} was removed while loading")
        self._note_membership()
        return svc

    def remove_model(self, name: str) -> None:
        with self._lock:
            if name not in self._services:
                raise ScoreError("not_found", f"no model named {name!r}")
            svc = self._services.pop(name)
        if svc is not None:  # None = reservation of an in-flight add
            svc.stop()
        self._note_membership()

    def _note_membership(self) -> None:
        with self._lock:
            n = sum(1 for s in self._services.values() if s is not None)
        self._m_models.set(n)
        self._m_shared.set(len(self.pool.report()))

    def _service(self, name: str) -> FleetMemberService:
        with self._lock:
            svc = self._services.get(name)
        if svc is None:
            # absent, or a reservation whose load/warm is still running
            raise ScoreError("not_found",
                             f"no model named {name!r} (or still loading)")
        return svc

    # -- lifecycle --------------------------------------------------------- #

    def start(self) -> "FleetService":
        with self._lock:
            self._started = True
            services = [s for s in self._services.values()
                        if s is not None]
        for svc in services:
            svc.start()
        if self.watchdog is not None:
            self.watchdog.start()
        if self.slo_engine is not None:
            # alert events attach to the caller's span (chaos/bench run
            # roots): the engine thread has no ambient span of its own
            self.slo_engine.span = TRACER.current()
            self.slo_engine.start()
        if self.autopilot is not None:
            self.autopilot.start()
        if self.fleetobs is not None:
            self.fleetobs.start()
        return self

    def stop(self, timeout: float = 5.0) -> None:
        if self.autopilot is not None:
            self.autopilot.stop()
        if self.slo_engine is not None:
            self.slo_engine.stop()
        if self.fleetobs is not None:
            self.fleetobs.stop()
        if self.watchdog is not None:
            self.watchdog.stop()
        with self._lock:
            self._started = False
            services = [s for s in self._services.values()
                        if s is not None]
        for svc in services:
            svc.stop(timeout=timeout)

    def __enter__(self) -> "FleetService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- scoring ----------------------------------------------------------- #

    def score(self, model: str, rows: List[Dict[str, Any]],
              tenant: Optional[str] = None,
              deadline_ms: Optional[float] = None,
              trace: Optional[TraceContext] = None):
        """Route one row-wire request: resolve the model, pass tenant
        admission (token-bucket quota + priority shedding against the
        target model's queue pressure), then score through that model's
        own micro-batcher. Per-tenant accounting happens here so every
        member's latency lands in the tenant's labeled series.

        The request trace OPENS here (not in the member), so router
        admission is its first phase child and an admission-shed
        request still leaves a kept trace (sheds are errors to the
        tail sampler)."""
        return self._score_routed(
            model, len(rows or ()), tenant, trace,
            lambda svc, tr: svc.score(rows, deadline_ms=deadline_ms,
                                      trace=tr))

    def score_columns(self, model: str, columns: Dict[str, List[Any]],
                      tenant: Optional[str] = None,
                      deadline_ms: Optional[float] = None,
                      trace: Optional[TraceContext] = None):
        """Columnar request wire through the same admission path as
        `score` (quota metering in rows, identical shedding/tracing):
        the member converts columns with no row pivot and its outputs
        are bit-identical to the row wire for the same data."""
        n_rows = 0
        if isinstance(columns, dict):
            for v in columns.values():
                n_rows = len(v) if hasattr(v, "__len__") else 0
                break
        return self._score_routed(
            model, n_rows, tenant, trace,
            lambda svc, tr: svc.score_columns(
                columns, deadline_ms=deadline_ms, trace=tr))

    def score_frame(self, frame: bytes,
                    trace: Optional[TraceContext] = None):
        """Binary columnar wire: decode one length-prefixed frame
        (serving/binwire.py) and route it exactly like `score_columns`.
        Any malformation raises bad_request BEFORE admission, so a
        client framing bug never charges a tenant's quota, the breaker,
        or the health window."""
        from transmogrifai_tpu.serving.binwire import decode_frame
        columns, meta = decode_frame(frame)
        model = meta.get("model")
        if not isinstance(model, str) or not model:
            raise ScoreError("bad_request",
                             "binary frame: missing model name")
        return self.score_columns(
            model, columns, tenant=meta.get("tenant"),
            deadline_ms=meta.get("deadline_ms"), trace=trace)

    def set_fidelity_route(self, model: str,
                           target: Optional[str] = None) -> Optional[str]:
        """Install (target given) or clear (target=None) the fidelity
        route flip for `model`. Autopilot-owned: callers must emit the
        actuation event that justified the flip (lint L022). Returns
        the previous target, None if there was none."""
        with self._lock:
            if target is None:
                return self._fidelity_routes.pop(model, None)
            if target not in self._services:
                raise ScoreError(
                    "not_found",
                    f"fidelity target {target!r} is not a hosted member")
            prev = self._fidelity_routes.get(model)
            self._fidelity_routes[model] = str(target)
            return prev

    def resolve_model(self, model: str) -> str:
        """The member name requests for `model` actually score on."""
        with self._lock:
            return self._fidelity_routes.get(model, model)

    def _score_routed(self, model: str, n_rows: int,
                      tenant: Optional[str],
                      trace: Optional[TraceContext], member_call):
        # predictive pressure is keyed by the REQUESTED model (the
        # logical route key the autopilot writes against); the fidelity
        # flip only changes which member's queue serves that traffic
        requested = model
        model = self.resolve_model(model)
        svc = self._service(model)
        rt: Optional[RequestTrace] = None
        if self.sampler is not None and svc.sampler is not None:
            rt = RequestTrace(ctx=trace, rows=n_rows,
                              tenant=tenant or "default", model=model)
        t0 = time.monotonic()
        try:
            admission = (rt.child("serving:admission", model=model)
                         if rt is not None else contextlib.nullcontext())
            with admission:
                queue_frac = svc._batcher.depth() / max(
                    1, svc.config.max_queue)
                drain_s = None
                if max(queue_frac, self.router.pressure(requested)) >= \
                        self.router.shed_watermark:
                    # only when a shed is plausible: the model predict
                    # is cheap but not free, and the happy path pays
                    # nothing for the proportional backoff hint
                    drain_s = svc.predicted_drain_s()
                tname = self.router.admit(tenant, n_rows,
                                          queue_frac, model=requested,
                                          drain_s=drain_s)
        except ScoreError as e:
            # admission shed: the member never saw this request, so the
            # fleet finishes + samples the trace itself (always kept)
            if rt is not None:
                rt.finish(e.code)
                self.sampler.observe(rt, time.monotonic() - t0,
                                     error=True)
            raise
        with TRACER.span("fleet:score", category="serving",
                         tenant=tname, model=model):
            try:
                # the member's scoring owns the trace from here: phase
                # children, finish, tail sampling, exemplars
                result = member_call(svc, rt if rt is not None else trace)
            except ScoreError as e:
                self.router.note_error(tname, model, e.code)
                raise
        self.router.note_success(tname, model, n_rows,
                                 time.monotonic() - t0)
        return result

    # -- rolling swap ------------------------------------------------------ #

    def reload_model(self, name: str, model_location: str
                     ) -> Dict[str, Any]:
        """Rolling swap of ONE member under traffic: the candidate is
        loaded, pool-adopted (a same-shaped candidate warms with zero
        new compiles), warmed OFF the serving path, then atomically
        flipped — in-flight requests on every OTHER model never touch
        this path at all, and this model's in-flight batches finish on
        the version they were dispatched with. Emits a `fleet_swap`
        goodput event carrying the per-tenant traffic served DURING the
        swap window (the goodput report's rolling-swap accounting)."""
        svc = self._service(name)
        before = self.router.snapshot()
        t0 = time.monotonic()
        status = "failed"
        try:
            result = svc.reload(model_location)
            status = result.get("status", "swapped")
        finally:
            wall = time.monotonic() - t0
            during = self.router.delta(before)
            try:
                from transmogrifai_tpu.obs.export import record_event
                record_event(
                    "fleet_swap", model=name, wall_s=round(wall, 6),
                    status=status,
                    requests_during_swap=sum(
                        d.get("requests", 0) for d in during.values()),
                    shed_during_swap=sum(
                        d.get("shed", 0) for d in during.values()),
                    per_tenant=during)
            except Exception:
                log.debug("fleet_swap event emission failed",
                          exc_info=True)
        if status == "swapped":
            self.registry.counter(
                "fleet_swaps_total", "rolling model swaps applied",
                model=name).inc()
        self._note_membership()
        return result

    def rollback_model(self, name: str) -> Dict[str, Any]:
        return self._service(name).rollback()

    # -- introspection ----------------------------------------------------- #

    def models(self) -> Dict[str, Dict[str, Any]]:
        with self._lock:
            services = {k: v for k, v in self._services.items()
                        if v is not None}
        out: Dict[str, Dict[str, Any]] = {}
        for name, svc in services.items():
            health = svc.health()
            health["shared_from"] = svc.shared_from
            out[name] = health
        return out

    def health(self) -> Dict[str, Any]:
        models = self.models()
        statuses = [m["status"] for m in models.values()]
        if not self._started or not models or \
                not any(s == "ok" for s in statuses):
            status = "down"
        elif all(s == "ok" for s in statuses):
            status = "ok"
        else:
            # one member quarantined/down must NOT 503 the whole fleet:
            # the healthy members keep taking traffic, balancers see
            # "degraded" with the per-member breakdown
            status = "degraded"
        out = {
            "status": status,
            "replica": self.config.replica,
            "models": models,
            "tenants": self.router.snapshot(),
            "shared_programs": self.pool.report(),
        }
        with self._lock:
            if self._fidelity_routes:
                out["fidelity_routes"] = dict(self._fidelity_routes)
        if self.autopilot is not None:
            out["autopilot"] = self.autopilot.status()
        if status == "down":
            hints = [float(m.get("retry_after_s") or 0.0)
                     for m in models.values()]
            hints.append(self._resilience.watchdog_period_s)
            out["retry_after_s"] = round(max(hints), 3)
        return out

    def metrics_json(self) -> Dict[str, Any]:
        with self._lock:
            services = {k: v for k, v in self._services.items()
                        if v is not None}
        return {"fleet": self.registry.to_json(),
                "models": {name: svc.registry.to_json()
                           for name, svc in services.items()}}

    def _obs_snapshot(self):
        """What this replica publishes to the metrics federation: the
        fleet registry (tenant/model-labeled series) plus every
        member's serving_* registry labeled by model. Runs on the
        publisher thread — reads only, never blocks a scoring path."""
        snap = MetricsRegistry()
        snap.merge(self.registry)
        for name, svc in self._live_services().items():
            snap.merge(svc.registry, model=name)
        return snap

    def fleet_metrics_json(self) -> Dict[str, Any]:
        """The aggregated `/metrics/fleet` payload: every replica's
        last-published snapshot merged (counters summed, histograms
        bucket-merged, gauges replica-labeled), with per-replica
        publish timestamps as provenance. Requires a store_dir."""
        if not self.config.store_dir:
            raise ScoreError(
                "not_found",
                "no store_dir configured: metrics federation is off")
        from transmogrifai_tpu.obs.federate import aggregate_fleet_metrics
        merged, info = aggregate_fleet_metrics(self.config.store_dir)
        return {"replica": self.config.replica,
                "replicas": info,
                "fleet": merged.to_json()}
