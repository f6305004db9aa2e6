"""ScoringService: the compiled scorer as a servable, observable endpoint.

This is the productization layer the reference stops short of (SURVEY
§2.13 ends at cluster-free batch scoring): an in-process service that

- admits row-dict requests through a BOUNDED queue (load-shedding with
  structured errors at capacity),
- coalesces concurrent requests into one device batch padded to a
  power-of-two shape bucket (``serving/batcher.py``) so the jit cache
  stays warm — the retrace counters prove zero recompiles after warmup,
- AOT-warms every bucket at model load (one compile per bucket, per
  segment, before the first request arrives),
- hot-swaps model versions under traffic: load a new serialization dir,
  warm it OFF the serving path, then atomically swap; the previous
  version is retained for one-call rollback,
- quarantines per-request errors: a failing batch is re-scored request
  by request so one bad record fails one request, not its batchmates,
- exports latency/throughput/queue/shed/compile metrics through a
  ``MetricsRegistry`` (JSON + Prometheus text).

Threading model: callers (any thread) do host-side row→Dataset parsing
and block on a per-request future; ONE scoring thread owns batch
assembly and every device dispatch, so jit caches are touched without
cross-thread interleaving. Model swap flips one attribute under a lock.
"""

from __future__ import annotations

import logging
import os
import threading
import time
from collections import deque
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.data.dataset import Dataset
from transmogrifai_tpu.data.rowcodec import columns_dataset, encode_rows
from transmogrifai_tpu.obs.metrics import (
    MICRO_LATENCY_BUCKETS, MetricsRegistry)
from transmogrifai_tpu.obs.trace import (
    TRACER, RequestTrace, TailSampler, TraceContext, TracingParams, now_s)
from transmogrifai_tpu.runtime.faults import (
    SITE_BATCH_ASSEMBLE, SITE_DEVICE_DISPATCH, SITE_RELOAD_LOAD,
    fault_point)
from transmogrifai_tpu.serving.batcher import (
    MicroBatcher, Request, ScoreError, bucket_for, bucket_ladder,
    derive_ladder)
from transmogrifai_tpu.serving.resilience import (
    QUARANTINED, MemberHealth, ResilienceParams, Watchdog)
from transmogrifai_tpu.serving.staging import StagingPool
from transmogrifai_tpu.workflow.compiled import slice_result_tree

log = logging.getLogger(__name__)


@dataclass
class ServingConfig:
    """Knobs for the scoring service (see also ServingParams in
    workflow/params.py, its JSON-loadable mirror)."""

    max_batch: int = 64            # top bucket = largest device batch
    min_bucket: int = 1            # bottom rung of the ladder
    buckets: Optional[Sequence[int]] = None  # explicit ladder override
    max_queue: int = 256           # bounded admission queue
    batch_wait_ms: float = 2.0     # linger to coalesce concurrent requests
    default_deadline_ms: float = 2000.0  # per-request deadline
    warm_on_load: bool = True      # AOT-compile every bucket at load
    keep_versions: int = 2         # live + rollback
    # derive the ladder from observed request sizes + the cost model's
    # predicted per-bucket latency once enough traffic has been seen
    # (serving/batcher.derive_ladder; a cold model keeps the power-of-
    # two ladder exactly). Ignored when `buckets` is explicit.
    auto_ladder: bool = False
    # optional data/feature_cache.py policy (a FeatureCacheParams JSON
    # dict) installed as the process default at service construction:
    # any store-backed scoring this process runs through the
    # parallel/bigdata.py builders then reuses cached — and, with
    # resident=True, HBM-resident — device matrices across model
    # hot-swaps instead of re-uploading after every /reload (the row
    # /score path itself builds no device matrices)
    feature_cache: Optional[Dict[str, Any]] = None
    # persistent XLA compilation cache (utils/compile_cache.py) enabled
    # at service construction with a 0s persistence threshold (a bucket
    # ladder is MANY small programs; a replica's cold start is their
    # compile-time sum). None = read TRANSMOGRIFAI_SERVING_COMPILE_CACHE
    # (off when unset — tests and embedded callers stay hermetic);
    # `cli serve` defaults it ON. The directory is not a knob here:
    # JAX_COMPILATION_CACHE_DIR places it, else <store root>/xla-cache
    # (utils/compile_cache.py).
    compile_cache: Optional[bool] = None
    # write/read the AOT warmup manifest beside each model artifact
    # (workflow/serialization.save_warmup_manifest): a cold warmup
    # records its wall seconds + ladder; a later replica (or same-shaped
    # swap) that matches the manifest reports the recovered compile
    # seconds as `serving_compile_cache_saved_s`
    warmup_manifest: bool = True
    # serving resilience knobs (serving/resilience.ResilienceParams as a
    # JSON dict): per-member health state machine, circuit breaker +
    # degraded fallback, hang watchdog. None = defaults (enabled);
    # {"enabled": false} turns the layer off
    resilience: Optional[Dict[str, Any]] = None
    # quantized inference ("int8"/"int4", workflow.compiled.ScoringQuant):
    # the request matrix ships on an affine narrow wire and fitted
    # tables compute in narrowed dtypes inside the fused bucket
    # programs. Stated per-feature tolerance scale/2 =
    # (hi − lo)/(2·(2^bits − 1)); "-calibrated" variants quantize
    # against fit-time fleet-wide ranges persisted with the model
    # (repeat scores bit-stable across batch compositions, quantization
    # is a constant-scale vectorized pass during batch staging), bare
    # modes against each batch's own range; None = exact f32 scoring.
    # Folded into the fleet's program-sharing signature, so quantized
    # and f32 members never adopt each other's programs (calibrated and
    # batch-relative builds of one mode DO share — scale/lo are traced
    # arguments).
    quantize: Optional[str] = None
    # request-scoped tracing + tail sampling (obs/trace.TracingParams
    # JSON): every /score request gets a span tree (W3C traceparent
    # honored + echoed, parse/queue-wait/pad/dispatch/demux phase
    # children, serving_phase_seconds histograms with trace-id
    # exemplars); the tail sampler keeps errors + the slow tail and
    # head-samples the healthy majority. None = defaults (ON);
    # {"enabled": false} turns request tracing off.
    tracing: Optional[Dict[str, Any]] = None
    # SLO burn-rate engine (obs/slo.SLOParams JSON): declarative
    # availability/latency/staleness objectives evaluated over this
    # service's registry with multi-window multi-burn-rate alerting,
    # surfaced on /slo + slo_* gauges + slo_alert events. None = off
    # (opt-in: an SLO without an operator reading it is noise).
    slo: Optional[Dict[str, Any]] = None
    # crash flight recorder (obs/flight.py): {"enabled": bool, "dir":
    # str, "capacity": int, "min_interval_s": float}. None = enabled
    # with defaults — serving processes should always have a black box.
    flight: Optional[Dict[str, Any]] = None

    def ladder(self) -> Tuple[int, ...]:
        if self.buckets:
            ladder = tuple(sorted(set(int(b) for b in self.buckets)))
            if ladder[0] < 1:
                raise ValueError(f"bucket sizes must be >= 1: {ladder}")
            return ladder
        return bucket_ladder(self.max_batch, self.min_bucket)


def raw_schema(model) -> Dict[str, type]:
    """Raw input column name -> feature type, from the model's own graph
    (the reader-schema derivation the runner uses, DataReader.scala:221)."""
    schema: Dict[str, type] = {}
    for rf in model.result_features:
        for f in rf.raw_features():
            schema[f.name] = f.ftype
    return schema


def _synthetic_rows(schema: Dict[str, type], n: int,
                    response_names: Sequence[str] = ()) -> List[Dict[str, Any]]:
    """Type-appropriate warmup rows: numerics 0, text-kinds None (the
    missing-value path every fitted stage already handles). Only SHAPES
    matter for warmup — the scores are discarded."""
    row: Dict[str, Any] = {}
    for name, ftype in schema.items():
        if name in response_names:
            continue
        if issubclass(ftype, T.Binary):
            row[name] = False
        elif issubclass(ftype, T.OPNumeric):
            row[name] = 0.0
        else:
            row[name] = None
    return [dict(row) for _ in range(n)]


class ModelVersion:
    """One loaded + warmed model: the unit of hot-swap."""

    def __init__(self, model, version_id: str,
                 path: Optional[str] = None, quant: Optional[str] = None):
        self.model = model
        self.version_id = version_id
        self.path = path or getattr(model, "loaded_from", None)
        self.loaded_at = time.time()
        self.scorer = model._ensure_compiled(quant=quant)
        self.compile_counts: Dict[int, int] = {}  # bucket -> traces seen
        self.warm_s: float = 0.0                  # measured warmup wall
        self.cache_saved_s: Optional[float] = None  # vs manifest cold warm

    def warm(self, ladder: Tuple[int, ...],
             warm_rows: Optional[List[Dict[str, Any]]] = None) -> None:
        """AOT-compile every bucket shape BEFORE serving traffic from this
        version. Warm data is synthesized from the model's raw schema
        (or caller-provided rows); per-bucket trace deltas are kept so
        the metrics surface can report compile counts per bucket."""
        from transmogrifai_tpu.analysis.retrace import MONITOR
        schema = raw_schema(self.model)
        responses = [f.name for rf in self.model.result_features
                     for f in rf.raw_features() if f.is_response]
        rows = warm_rows or _synthetic_rows(schema, 1, responses)
        base = Dataset.from_rows(
            rows, schema={k: v for k, v in schema.items()
                          if k in rows[0]})
        for bucket in ladder:
            before = MONITOR.snapshot()
            # score_padded only pads UP: truncate warm data for buckets
            # smaller than the provided warm rows
            sample = base if len(base) <= bucket \
                else base.take(np.arange(bucket))
            self.scorer.score_padded(sample, bucket)
            new = sum(MONITOR.delta(before).values())
            self.compile_counts[bucket] = \
                self.compile_counts.get(bucket, 0) + new

    def info(self) -> Dict[str, Any]:
        out = {"version": self.version_id, "path": self.path,
               "loaded_at": self.loaded_at,
               "warm_s": round(self.warm_s, 6),
               "compile_counts": {str(k): v
                                  for k, v in self.compile_counts.items()}}
        if self.cache_saved_s is not None:
            out["compile_cache_saved_s"] = round(self.cache_saved_s, 6)
        return out


@dataclass
class ScoreResult:
    """Per-request outcome: result feature name -> host arrays (sliced to
    this request's rows) + the serving version that produced it."""

    outputs: Dict[str, Any]
    model_version: str
    n_rows: int = 0
    latency_s: float = 0.0
    # request-scoped trace correlation (set when tracing is on): the
    # trace id this request's spans carry and the W3C traceparent echo
    # the HTTP layer returns as a response header
    trace_id: Optional[str] = None
    traceparent: Optional[str] = None

    def rows(self) -> List[Dict[str, Any]]:
        """Row-dict view of the outputs (the `/score` JSON shape),
        matching `score_function`'s per-row conversion."""
        out: List[Dict[str, Any]] = []
        for i in range(self.n_rows):
            row: Dict[str, Any] = {}
            for name, v in self.outputs.items():
                if isinstance(v, dict) and "prediction" in v:
                    m: Dict[str, float] = {"prediction": float(
                        np.asarray(v["prediction"])[i])}
                    prob = np.asarray(v["probability"])[i]
                    for j, x in enumerate(np.ravel(prob)):
                        m[f"probability_{j}"] = float(x)
                    row[name] = m
                elif isinstance(v, dict) and "value" in v:
                    present = bool(np.asarray(v["mask"])[i])
                    row[name] = (float(np.asarray(v["value"])[i])
                                 if present else None)
                else:
                    arr = np.asarray(v)
                    first = arr[i]
                    if arr.dtype == object:
                        row[name] = first
                    else:
                        row[name] = (first.tolist() if arr.ndim > 1
                                     else first.item())
            out.append(row)
        return out


class ScoringService:
    """Online scoring over a loaded WorkflowModel. See module docstring.

    Usage::

        svc = ScoringService.from_path("model_dir")
        svc.start()
        result = svc.score([{"age": 31.0, "sex": "male", ...}])
        svc.reload("model_dir_v2")   # warm, then atomic swap
        svc.rollback()               # back to the prior version
        svc.stop()
    """

    def __init__(self, model=None, version_id: Optional[str] = None,
                 config: Optional[ServingConfig] = None,
                 registry: Optional[MetricsRegistry] = None,
                 warm_rows: Optional[List[Dict[str, Any]]] = None):
        self.config = config or ServingConfig()
        self.ladder = self.config.ladder()
        self.registry = registry or MetricsRegistry()
        self.warm_rows = warm_rows
        self._swap_lock = threading.Lock()
        self._versions: List[ModelVersion] = []   # newest-last history
        self._active: Optional[ModelVersion] = None
        self._batcher = MicroBatcher(
            self.config.max_queue, self.ladder[-1],
            batch_wait_s=self.config.batch_wait_ms / 1000.0)
        # resident per-bucket batch staging (serving/staging.py): the
        # scoring thread writes each batch into preallocated buffers —
        # coalesce + pad are writes, not fresh concat/pad allocations.
        # Hot-swaps/rollbacks/rebuckets invalidate (generation fence).
        self._staging = StagingPool()
        # (rows, seconds) of batch-run row decodes, drained into the
        # perf corpus by the scoring thread AFTER each pad wall closes
        self._parse_notes: List[Tuple[int, float]] = []
        self._thread: Optional[threading.Thread] = None
        self._running = False
        # resilience layer: health state machine + breaker + watchdog
        # bookkeeping (serving/resilience.py). `_generation` fences the
        # scoring thread: a watchdog restart bumps it, and a stale
        # (formerly wedged) loop that wakes later sees the mismatch and
        # exits without touching shared state.
        self.resilience = ResilienceParams.from_json(
            self.config.resilience)
        self.fault_scope: Optional[str] = None  # fleet member name
        self._health: Optional[MemberHealth] = None
        if self.resilience.enabled:
            self._health = MemberHealth(self.resilience, registry=self.registry)
        self._generation = 0
        self._inflight_lock = threading.Lock()
        self._inflight: List[Request] = []
        self._busy_since: Optional[float] = None
        self._watchdog: Optional[Watchdog] = None
        self._own_watchdog = True   # fleet members are fleet-supervised
        self._m_fallback = None     # created lazily with member label
        self.started_at = time.time()          # epoch timestamp (display)
        self._started_mono = time.monotonic()  # uptime arithmetic (L009)
        self._trace_parent = None  # span the batcher thread nests under
        self._schema: Dict[str, type] = {}
        # request-scoped tracing: per-request span trees + tail sampling
        # (obs/trace.py). ON by default — the cost is a few Span objects
        # per request and the sampler keeps the process ring bounded.
        self.tracing = TracingParams.from_json(self.config.tracing)
        self.sampler: Optional[TailSampler] = (
            TailSampler(self.tracing, registry=self.registry)
            if self.tracing.enabled else None)
        # crash flight recorder: ring always armed for serving processes
        # (the serving plane is exactly where a post-mortem matters);
        # {"enabled": false} opts out, dir/capacity/debounce overridable
        flight_cfg = dict(self.config.flight or {})
        if flight_cfg.get("enabled", True):
            from transmogrifai_tpu.obs import flight
            flight.enable(
                dump_dir=flight_cfg.get("dir"),
                capacity=flight_cfg.get("capacity"),
                min_interval_s=flight_cfg.get("min_interval_s"))
        # SLO burn-rate engine (opt-in via config.slo)
        self.slo_engine = None
        if self.config.slo and dict(self.config.slo).get("enabled", True):
            self._build_slo_engine()
        # observed request-size distribution (rows per request): the
        # sample `derive_ladder` shapes the bucket ladder from
        self._sizes: deque = deque(maxlen=4096)
        self._auto_done = False   # an auto rebucket landed
        self._auto_seen = 0       # batches processed (auto trigger)
        self._auto_next = 256     # next attempt threshold
        # serializes ladder derivation+warm+swap: a slow warm must not
        # overlap a second derivation computed from the stale ladder
        self._rebucket_lock = threading.Lock()
        # persistent XLA compile cache: resolved BEFORE the first model
        # install so its warmup compiles land in (or hit) the cache
        cc = self.config.compile_cache
        if cc is None:
            cc = os.environ.get("TRANSMOGRIFAI_SERVING_COMPILE_CACHE",
                                "").lower() in ("1", "on", "true")
        self._compile_cache_path: Optional[str] = None
        if cc:
            from transmogrifai_tpu.utils.compile_cache import (
                enable_compile_cache)
            self._compile_cache_path = enable_compile_cache(
                min_compile_s=0.0)
        self._init_metrics()
        if self.config.feature_cache:
            # device-matrix cache policy for this serving process: warm
            # scoring over a ColumnarStore replays the wire artifact,
            # and resident=True keeps the built matrices in HBM across
            # hot-swaps (a /reload swaps the MODEL, not the data)
            from transmogrifai_tpu.data.feature_cache import (
                FeatureCacheParams, set_default_cache_params)
            set_default_cache_params(
                FeatureCacheParams.from_json(dict(self.config.feature_cache)))
        if model is not None:
            self._install(model, version_id or "v0")

    # -- construction ------------------------------------------------------ #

    @classmethod
    def from_path(cls, model_location: str, **kwargs) -> "ScoringService":
        from transmogrifai_tpu.workflow.serialization import (
            load_model, model_fingerprint)
        model = load_model(model_location)
        return cls(model=model,
                   version_id=model_fingerprint(model_location), **kwargs)

    def _init_metrics(self) -> None:
        r = self.registry
        self._m_requests = r.counter(
            "serving_requests_total", "scoring requests admitted")
        self._m_rows = r.counter(
            "serving_rows_total", "rows scored (valid rows, not padding)")
        self._m_pad_rows = r.counter(
            "serving_padded_rows_total", "pad rows added for shape buckets")
        self._m_batches = r.counter(
            "serving_batches_total", "device batches dispatched")
        self._m_swaps = r.counter(
            "serving_model_swaps_total", "successful model hot-swaps")
        self._m_errors = r.counter(
            "serving_errors_total", "requests failed with internal errors")
        self._m_queue = r.gauge(
            "serving_queue_depth", "requests waiting in the bounded queue")
        self._m_latency = r.histogram(
            "serving_request_latency_seconds",
            "enqueue-to-resolve latency per request")
        self._m_batch_lat = r.histogram(
            "serving_batch_latency_seconds",
            "device batch execution latency")
        # µs-resolution buckets: host phases (parse, pad, demux) run in
        # tens of µs — on the default 100µs-floor ladder they all land
        # in the first bucket and the interpolated p50 is meaningless
        self._phase_hists = {
            phase: r.histogram(
                "serving_phase_seconds",
                "per-request time spent in each serving phase",
                bounds=MICRO_LATENCY_BUCKETS, phase=phase)
            for phase in self._PHASES}
        self._m_staging_alloc = r.counter(
            "serving_staging_allocations_total",
            "resident batch staging buffer sets (re)allocated")
        self._m_staging_fallback = r.counter(
            "serving_staging_fallback_total",
            "batches the staging pool refused (legacy concat path)")
        self._m_staging_gen = r.gauge(
            "serving_staging_generation",
            "staging-pool generation (bumps on hot-swap/rebucket)")

    def _shed(self, reason: str):
        return self.registry.counter(
            "serving_shed_total", "requests shed under overload",
            reason=reason)

    def _build_slo_engine(self) -> None:
        """Wire the declarative SLOs (obs/slo.py) onto this service's
        own registry: availability from the request/error/shed
        counters, latency from the request-latency histogram,
        staleness from the continual loop's freshness gauge on the
        process registry."""
        from transmogrifai_tpu.obs.metrics import get_registry
        from transmogrifai_tpu.obs.slo import (
            SLOEngine, SLOParams, availability_source, latency_source,
            staleness_source)
        params = SLOParams.from_json(self.config.slo)
        engine = SLOEngine(params, registry=self.registry)
        for slo in engine.slos():
            if slo.kind == "availability":
                engine.set_source(slo.name, availability_source(
                    self.registry, "serving_requests_total",
                    error_families=("serving_errors_total",),
                    shed_families=("serving_shed_total",)))
            elif slo.kind == "latency":
                engine.set_source(slo.name, latency_source(
                    self.registry, "serving_request_latency_seconds",
                    slo.threshold_s))
            elif slo.kind == "staleness":
                engine.set_source(slo.name, staleness_source(
                    get_registry(), "continual_staleness_current_seconds",
                    slo.threshold_s))
        from transmogrifai_tpu.obs.slo import maybe_attach_fleet
        maybe_attach_fleet(engine)
        self.slo_engine = engine

    # the closed phase-label set (span names are `serving:<phase>`);
    # request-derived values never become labels
    _PHASES = ("parse", "assemble", "queue_wait", "pad",
               "device_dispatch", "demux", "admission")

    def _phase_hist(self, phase: str):
        """The labeled per-phase latency family (`serving_phase_seconds
        {phase=...}`). The fixed set is pre-bound at init (the
        `_init_metrics` convention) so the per-request finish path
        never takes the registry lock; an unexpected phase still
        resolves through the registry rather than dropping data."""
        hist = self._phase_hists.get(phase)
        if hist is None:
            hist = self.registry.histogram(
                "serving_phase_seconds",
                "per-request time spent in each serving phase",
                bounds=MICRO_LATENCY_BUCKETS, phase=phase)
            self._phase_hists[phase] = hist
        return hist

    def _finish_request_trace(self, rt: Optional[RequestTrace],
                              latency_s: float,
                              error: Optional[str] = None) -> None:
        """Request-trace epilogue on EVERY exit path (success, shed,
        deadline, scoring error): end the root, run the tail-sampling
        decision, and on keep record the phase histograms with this
        trace's id pinned as the bucket exemplar (exemplars must point
        at traces that EXIST — a dropped trace id would 404)."""
        if rt is None:
            if error is None:
                self._m_latency.observe(latency_s)
            return
        rt.finish(error)
        kept = False
        if self.sampler is not None:
            kept = self.sampler.observe(rt, latency_s,
                                        error=error is not None)
        exemplar = rt.trace_id if kept else None
        for phase, dur in rt.phase_durations().items():
            self._phase_hist(phase).observe(dur, exemplar=exemplar)
        if error is None:
            # the request-latency family has always counted SUCCESSFUL
            # resolves only; the kept trace's id rides along as the
            # exemplar on whichever bucket this latency landed in
            self._m_latency.observe(latency_s, exemplar=exemplar)

    def _install(self, model, version_id: str,
                 path: Optional[str] = None) -> ModelVersion:
        """Load-side half of a swap: compile + warm OFF the serving path,
        then atomically flip `_active`."""
        version = ModelVersion(model, version_id, path=path,
                               quant=self.config.quantize)
        path = version.path  # falls back to the model's loaded_from
        if self.config.warm_on_load:
            manifest = None
            if path and self.config.warmup_manifest:
                from transmogrifai_tpu.workflow.serialization import (
                    load_warmup_manifest)
                manifest = load_warmup_manifest(path)
                if manifest is not None and (
                        manifest.get("fingerprint") != version_id
                        or manifest.get("ladder") != list(self.ladder)):
                    manifest = None  # stale sidecar: treat as cold
            t0 = time.perf_counter()
            version.warm(self.ladder, self.warm_rows)
            version.warm_s = time.perf_counter() - t0
            # bucket label only (no version label): label cardinality must
            # stay bounded by the ladder width, not grow per reload — the
            # per-version breakdown lives in health()['versions'] instead
            for bucket, n in version.compile_counts.items():
                self.registry.counter(
                    "serving_bucket_compiles_total",
                    "XLA traces attributed to each shape bucket at warmup",
                    bucket=bucket).inc(n)
            self._note_warmup(version, manifest)
        with self._swap_lock:
            self._versions.append(version)
            keep = max(2, self.config.keep_versions)
            del self._versions[:-keep]
            self._active = version
            self._schema = raw_schema(model)
        # the new model may stage a different column layout: fence the
        # resident batch buffers (scoring thread reallocates lazily)
        self._staging.invalidate()
        self.registry.gauge(
            "serving_model_versions", "versions held (active + rollback)"
        ).set(len(self._versions))
        return version

    def _note_warmup(self, version: ModelVersion,
                     manifest: Optional[Dict[str, Any]]) -> None:
        """Cold-start accounting around one warmup: with a matching
        manifest AND the persistent compile cache enabled, the delta to
        the manifest's recorded cold warmup is the measured recovery
        (`serving_compile_cache_saved_s` + a `compile_cache_saved`
        goodput event); a warmup that actually compiled programs with
        no prior manifest IS the cold baseline and writes one. A warmup
        absorbed by shared programs (zero traces, no manifest claim)
        records neither — its near-zero wall must not become a 'cold'
        baseline that poisons future savings."""
        n_compiles = sum(version.compile_counts.values())
        if manifest is not None and self._compile_cache_path \
                and n_compiles > 0:
            # n_compiles gate: a warmup absorbed by the fleet's SHARED
            # programs traces nothing — its near-zero wall against the
            # manifest's cold baseline is program-sharing's win, not the
            # compile cache's, and must not be booked here
            saved = max(0.0, float(manifest.get("warm_s") or 0.0)
                        - version.warm_s)
            version.cache_saved_s = saved
            self.registry.counter(
                "serving_compile_cache_saved_s",
                "warmup seconds recovered by the persistent compile "
                "cache vs the recorded cold warmup").inc(saved)
            try:
                from transmogrifai_tpu.obs.export import record_event
                record_event("compile_cache_saved",
                             saved_s=round(saved, 6),
                             warm_s=round(version.warm_s, 6),
                             model_version=version.version_id)
            except Exception:
                log.debug("compile_cache_saved event failed",
                          exc_info=True)
        elif (version.path and self.config.warmup_manifest
                and manifest is None and n_compiles > 0):
            from transmogrifai_tpu.workflow.serialization import (
                save_warmup_manifest)
            save_warmup_manifest(version.path, {
                "fingerprint": version.version_id,
                "ladder": list(self.ladder),
                "warm_s": round(version.warm_s, 6),
                "compiles": n_compiles,
                "compile_counts": {str(k): v for k, v
                                   in version.compile_counts.items()},
                "signature": getattr(version.scorer,
                                     "program_signature", None),
                "compile_cache": bool(self._compile_cache_path),
                "warmed_at": time.time(),
            })

    # -- lifecycle --------------------------------------------------------- #

    def start(self) -> "ScoringService":
        if self._active is None:
            raise RuntimeError("no model installed — pass one or reload()")
        if self._running:
            return self
        if self._batcher.closed:  # restart after stop(): fresh admissions
            self._batcher = MicroBatcher(
                self.config.max_queue, self.ladder[-1],
                batch_wait_s=self.config.batch_wait_ms / 1000.0)
        # the scoring thread does not inherit this context: capture the
        # caller's current span so batch spans nest under the run that
        # started the service (e.g. the runner's serve phase)
        self._trace_parent = TRACER.current()
        self._running = True
        self._start_scoring_thread()
        if self._health is not None and self._own_watchdog:
            # single-service mode supervises itself; fleet members are
            # covered by the FleetService-level watchdog instead
            self._watchdog = Watchdog(
                lambda: {"service": self},
                period_s=self.resilience.watchdog_period_s)
            self._watchdog.start()
        if self.slo_engine is not None:
            # alert events attach to the span that started the service
            # (the engine thread has no ambient span of its own)
            self.slo_engine.span = self._trace_parent
            self.slo_engine.start()
        return self

    def _start_scoring_thread(self) -> None:
        gen = self._generation
        self._thread = threading.Thread(
            target=self._serve_loop, args=(gen,),
            name=f"scoring-batcher-{gen}", daemon=True)
        self._thread.start()

    def stop(self, timeout: float = 5.0) -> None:
        self._running = False
        if self.slo_engine is not None:
            self.slo_engine.stop()
        if self._watchdog is not None:
            self._watchdog.stop()
            self._watchdog = None
        for req in self._batcher.close():
            req.fail(ScoreError("shutdown", "service stopped"))
        if self._thread is not None:
            self._thread.join(timeout=timeout)
            if self._thread is not None and self._thread.is_alive():
                # the scoring thread is wedged (e.g. a hung dispatch):
                # its in-flight batch must still be ANSWERED, not left
                # blocking clients forever on a dead service
                self._fail_inflight(ScoreError(
                    "shutdown",
                    "service stopped with the batch still in flight"))
            self._thread = None

    # -- resilience: liveness + recovery ------------------------------------ #

    def _fault_site(self, base: str) -> str:
        """Fleet members scope injection sites by member name
        (`serving.device_dispatch#a`) so a chaos plan can storm ONE
        member while its peers run clean."""
        return f"{base}#{self.fault_scope}" if self.fault_scope else base

    def _has_fallback(self) -> bool:
        with self._swap_lock:
            return len(self._versions) >= 2

    def _fail_inflight(self, error: ScoreError) -> List[Request]:
        """Quarantine the in-flight batch per-request: every client
        blocked on it gets a structured error NOW (never a hang)."""
        with self._inflight_lock:
            batch, self._inflight = self._inflight, []
            self._busy_since = None
        for req in batch:
            if not req._event.is_set():
                self._m_errors.inc()
                if self._health is not None:
                    self._health.note_request(False)
                req.fail(error)
        return batch

    def check_liveness(self) -> Optional[str]:
        """Watchdog probe: ``"dead"`` when the scoring thread exited
        (killed by a BaseException), ``"stalled"`` when its current
        batch has been in flight past ``watchdog_stall_s`` (a wedged
        jit dispatch), else None."""
        if not self._running:
            return None
        th = self._thread
        if th is None:
            return None
        if not th.is_alive():
            return "dead"
        busy = self._busy_since
        if busy is not None and (
                time.monotonic() - busy) > self.resilience.watchdog_stall_s:
            return "stalled"
        return None

    def recover_scoring_thread(self, reason: str) -> None:
        """Watchdog recovery: fence off the wedged/dead loop (generation
        bump), answer its in-flight batch with structured errors, and
        start a fresh scoring thread over the SAME batcher (queued
        requests keep their place). Recorded as
        `serving_watchdog_restarts_total` + a ``watchdog_restart``
        event; the health machine quarantines until recovery is
        re-proven (or the window washes clean)."""
        with self._inflight_lock:
            stalled_since = self._busy_since
        self._generation += 1
        # a stale (formerly wedged) loop that wakes mid-batch may still
        # WRITE the staging buffers it fetched; orphan them so the
        # restarted loop allocates a fresh set it alone owns
        self._staging.invalidate()
        # the recovery gets its own span under the service's trace so
        # the watchdog_restart + health_transition events it emits land
        # in the goodput rollup (the watchdog thread has no ambient span)
        with TRACER.span("serving:watchdog_restart", category="serving",
                         parent=self._trace_parent, reason=reason,
                         member=self.fault_scope or "service"):
            if self._health is not None:
                self._health.note_stall(since=stalled_since)
            self._fail_inflight(ScoreError(
                "watchdog_restart",
                f"scoring loop {reason}; thread restarted — retry",
                retry_after_s=self.resilience.watchdog_period_s))
            self.registry.counter(
                "serving_watchdog_restarts_total",
                "scoring threads restarted by the hang watchdog",
                reason=reason).inc()
            try:
                from transmogrifai_tpu.obs.export import record_event
                record_event("watchdog_restart", reason=reason,
                             member=self.fault_scope or "service")
            except Exception:
                log.debug("watchdog_restart event failed", exc_info=True)
            # black box: the ring holds the batches that led up to the
            # wedge/death — dump it before the evidence scrolls away
            try:
                from transmogrifai_tpu.obs import flight
                flight.request_dump(f"watchdog_{reason}")
            except Exception:
                log.debug("flight dump on watchdog restart failed",
                          exc_info=True)
            log.warning("serving%s: scoring loop %s; restarting thread "
                        "(generation %d)",
                        f"[{self.fault_scope}]" if self.fault_scope
                        else "", reason, self._generation)
            if self._running:
                self._start_scoring_thread()
            if self._health is not None:
                self._health.clear_stall()

    def __enter__(self) -> "ScoringService":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()

    # -- client API -------------------------------------------------------- #

    def _begin_request_trace(self, trace: Any,
                             n_rows: int) -> Optional[RequestTrace]:
        """The request's span buffer: an incoming `RequestTrace` (the
        fleet router already opened it around admission) passes
        through; a `TraceContext` (W3C wire context or an in-process
        parent span, e.g. a continual cycle) roots the request under
        the caller's trace; None mints a fresh trace id."""
        if isinstance(trace, RequestTrace):
            return trace
        if self.sampler is None:
            return None
        ctx = trace if isinstance(trace, TraceContext) else None
        return RequestTrace(ctx=ctx, rows=n_rows,
                            member=self.fault_scope or "service")

    def score(self, rows: List[Dict[str, Any]],
              deadline_ms: Optional[float] = None,
              timeout_s: Optional[float] = None,
              trace: Any = None) -> ScoreResult:
        """Score `rows` (list of raw-column dicts). Blocks until the
        micro-batcher resolves this request or its deadline passes.
        Raises ScoreError with a structured code on shed/expiry/bad
        input — the service keeps serving others regardless.

        `trace` carries request-scoped trace context (an
        `obs.trace.TraceContext` from a ``traceparent`` header or an
        in-process parent span, or a pre-opened `RequestTrace`); every
        exit path — success, shed, deadline, error — finishes the
        request's span tree and runs it through the tail sampler."""
        return self._traced_score(
            trace, len(rows or ()),
            lambda rt: self._score_inner(rows, deadline_ms, timeout_s,
                                         rt))

    def _traced_score(self, trace: Any, n_rows: int,
                      inner) -> ScoreResult:
        """The request-trace envelope shared by BOTH wires: open the
        span buffer, run `inner(rt)`, and on every exit path — success,
        shed, deadline, error — finish the trace, run tail sampling,
        and stamp the trace id onto the result or the raised
        ScoreError (a failed request must be as correlatable as a slow
        one)."""
        rt = self._begin_request_trace(trace, n_rows)
        t0 = time.monotonic()
        try:
            result = inner(rt)
        except ScoreError as e:
            self._finish_request_trace(rt, time.monotonic() - t0,
                                       error=e.code)
            if rt is not None:
                # error traces are the ones tail sampling ALWAYS keeps:
                # the failed response must carry the id a client needs
                # to find them (HTTP echoes it as headers + body field)
                e.trace_id = rt.trace_id
                e.traceparent = rt.traceparent()
            raise
        except BaseException as e:
            self._finish_request_trace(rt, time.monotonic() - t0,
                                       error=type(e).__name__)
            raise
        self._finish_request_trace(rt, result.latency_s)
        if rt is not None:
            result.trace_id = rt.trace_id
            result.traceparent = rt.traceparent()
        return result

    def _admit(self) -> None:
        """Shared admission preamble for BOTH wires: reject when the
        service is down, and FAST-FAIL a quarantined member with no
        resident fallback (structured error + retry-after) instead of
        queueing into a dead (or known-broken) batcher."""
        if not self._running:
            raise ScoreError("shutdown", "service is not running")
        if self._health is not None:
            retry_after = self._health.admit(self._has_fallback())
            if retry_after is not None:
                self._shed("circuit_open").inc()
                raise ScoreError(
                    "circuit_open",
                    f"member quarantined (breaker open / scoring loop "
                    f"down); retry in {retry_after:.2f}s",
                    retry_after_s=retry_after)

    def _score_inner(self, rows: List[Dict[str, Any]],
                     deadline_ms: Optional[float],
                     timeout_s: Optional[float],
                     rt: Optional[RequestTrace]) -> ScoreResult:
        self._admit()
        if not rows:
            raise ScoreError("bad_request", "empty rows")
        # the row PIVOT is deferred: admission validates shape only
        # (per-request wire checks, bucket fit) and the scoring thread
        # encodes the whole batch's rows through ONE compiled-codec
        # pass during staging (data/rowcodec.py; amortized host work
        # replacing the per-request Dataset.from_rows loop ROADMAP
        # called out as the serving p50 dominator). The parse child
        # therefore times request-side wire validation; the amortized
        # batch encode lands in the `pad` (staging) phase.
        if rt is not None:
            with rt.child("serving:assemble") as asm:
                with rt.child("serving:parse", parent=asm,
                              rows=len(rows)):
                    self._validate_rows(rows)
                bucket_for(len(rows), self.ladder)  # must fit a bucket
        else:
            self._validate_rows(rows)
            bucket_for(len(rows), self.ladder)  # admission: must fit
        return self._enqueue(None, deadline_ms, timeout_s, rt,
                             rows=rows)

    def _validate_rows(self, rows: List[Dict[str, Any]]) -> None:
        for r in rows:
            if not isinstance(r, dict):
                raise ScoreError(
                    "bad_request",
                    f"rows must be objects, got {type(r).__name__}")

    def _enqueue(self, ds: Optional[Dataset],
                 deadline_ms: Optional[float],
                 timeout_s: Optional[float],
                 rt: Optional[RequestTrace],
                 rows: Optional[List[Dict[str, Any]]] = None
                 ) -> ScoreResult:
        """Shared post-parse half of row and columnar scoring: deadline
        resolution, admission into the micro-batcher, and the blocking
        wait on the request future. Both wires land here, so mixed
        row/columnar traffic coalesces into the same batches and shares
        one bucket ladder."""
        if deadline_ms is None:
            ddl_ms = self.config.default_deadline_ms
        else:
            try:
                ddl_ms = float(deadline_ms)
            except (TypeError, ValueError):
                raise ScoreError(
                    "bad_request",
                    f"deadline_ms must be a number, got {deadline_ms!r}")
        deadline = (time.monotonic() + ddl_ms / 1000.0) if ddl_ms > 0 \
            else None
        n_rows = len(ds) if ds is not None else len(rows)
        self._sizes.append(n_rows)
        req = Request(ds, deadline, trace=rt, rows=rows,
                      schema=self._schema)
        if rt is not None:
            rt.enqueued_s = now_s()  # queue-wait span starts here
        try:
            self._batcher.put(req)
        except ScoreError as e:
            self._shed(e.code).inc()
            if e.code == "queue_full" and e.retry_after_s is None:
                # proportional backoff: predicted time to drain the
                # backlog through top-rung batches. None while the
                # model is cold — the HTTP layer then answers its
                # constant default, exactly the pre-model behavior.
                e.retry_after_s = self.predicted_drain_s()
            raise
        self._m_requests.inc()
        self._m_queue.set(self._batcher.depth())
        wait_s = timeout_s if timeout_s is not None else (
            ddl_ms / 1000.0 + 30.0 if ddl_ms > 0 else None)
        outputs, version = req.wait(wait_s)
        latency = time.monotonic() - req.enqueued_at
        return ScoreResult(outputs=outputs, model_version=version,
                           n_rows=req.n_rows, latency_s=latency)

    def _parse_rows(self, rows: List[Dict[str, Any]]) -> Dataset:
        """Row wire → Dataset through the compiled codec cache (kept
        for embedded callers and tests — the serving path itself now
        defers the pivot to batch staging). The FULL raw schema is
        passed (not a rows[0]-filtered subset): a column absent from
        the first row but present in a later one must still be
        schema-typed, never value-inferred — the old filter produced
        dtype-inconsistent batches on ragged first rows."""
        try:
            return encode_rows(rows, self._schema)
        except Exception as e:
            raise ScoreError("bad_request", f"unparseable rows: {e}")

    def _note_parse(self, n_rows: int, seconds: float) -> None:
        """Sampled host-parse cost into the perf corpus
        (`serving_parse` target): the ladder derivation and any other
        host-cost consumer can then PREDICT parse seconds per request
        size instead of assuming host work is free. Never raises."""
        try:
            from transmogrifai_tpu import perf
            perf.note_parse(n_rows, len(self._schema), seconds)
        except Exception:
            log.debug("perf parse recording failed", exc_info=True)

    def score_columns(self, columns: Dict[str, List[Any]],
                      deadline_ms: Optional[float] = None,
                      timeout_s: Optional[float] = None,
                      trace: Any = None) -> ScoreResult:
        """Columnar request wire: score ``{name: [values...]}`` with NO
        row pivot — callers that already hold columns (feature stores,
        batch scorers, the HTTP ``{"columns": ...}`` body) skip the
        per-row parse entirely; outputs are bit-identical to the row
        wire for the same data. Ragged lengths, unknown columns, and
        undeclarable cell types are structured ``bad_request``s.
        Columnar and row traffic coalesce into the same device batches
        (one bucket ladder)."""
        if not isinstance(columns, dict) or not columns:
            raise ScoreError("bad_request",
                             'expected {"columns": {name: [values...]}}')
        n_rows = 0
        for v in columns.values():
            n_rows = len(v) if hasattr(v, "__len__") else 0
            break
        return self._traced_score(
            trace, n_rows,
            lambda rt: self._score_columns_inner(columns, deadline_ms,
                                                 timeout_s, rt))

    def _score_columns_inner(self, columns: Dict[str, List[Any]],
                             deadline_ms: Optional[float],
                             timeout_s: Optional[float],
                             rt: Optional[RequestTrace]) -> ScoreResult:
        self._admit()
        t0 = time.perf_counter()
        if rt is not None:
            with rt.child("serving:assemble") as asm:
                with rt.child("serving:parse", parent=asm,
                              columnar=True):
                    ds = self._parse_columns(columns)
                bucket_for(len(ds), self.ladder)
        else:
            ds = self._parse_columns(columns)
            bucket_for(len(ds), self.ladder)
        # perf-corpus note AFTER the span: corpus appends are sampled
        # file IO and must never pollute the parse timing they record
        self._note_parse(len(ds), time.perf_counter() - t0)
        return self._enqueue(ds, deadline_ms, timeout_s, rt)

    def _parse_columns(self, columns: Dict[str, List[Any]]) -> Dataset:
        try:
            ds = columns_dataset(columns, self._schema,
                                 strict_schema=True)
        except ValueError as e:
            raise ScoreError("bad_request", f"bad columnar payload: {e}")
        except Exception as e:
            raise ScoreError("bad_request",
                             f"unparseable columnar payload: {e}")
        if len(ds) == 0:
            raise ScoreError("bad_request", "empty columns")
        return ds

    def score_row(self, row: Dict[str, Any], **kw) -> Dict[str, Any]:
        """Single-row convenience: returns the one result row dict."""
        return self.score([row], **kw).rows()[0]

    # -- hot swap ---------------------------------------------------------- #

    def reload(self, model_location: str) -> Dict[str, Any]:
        """Load + warm a new serialized model, then atomically swap it
        under traffic. The displaced version stays resident for
        `rollback()`. In-flight batches finish on the version they were
        dispatched with — no request is ever mis-versioned.

        The candidate dir is integrity-verified BEFORE anything is
        loaded: a torn/corrupt artifact is rejected with a structured
        error (and a `serving_reload_rejected_total` tick) while the
        resident version keeps serving untouched."""
        from transmogrifai_tpu.workflow.serialization import (
            ModelIntegrityError, load_model, model_fingerprint,
            verify_model_dir)
        try:
            verify_model_dir(model_location)
            vid = model_fingerprint(model_location)
        except (ModelIntegrityError, OSError) as e:
            self.registry.counter(
                "serving_reload_rejected_total",
                "reloads rejected by artifact integrity verification").inc()
            log.warning("serving: reload of %s rejected (%s); resident "
                        "version keeps serving", model_location, e)
            raise ScoreError(
                "bad_request",
                f"reload rejected by integrity check, resident version "
                f"keeps serving: {e}")
        active = self._active
        if active is not None and active.version_id == vid:
            return {"status": "unchanged", "version": vid}
        # injectable load failure (chaos: serving.reload_load) — an
        # error here propagates to the caller while the resident
        # version keeps serving untouched
        fault_point(self._fault_site(SITE_RELOAD_LOAD))
        model = load_model(model_location, verify=False)  # verified above
        version = self._install(model, vid, path=model_location)
        self._m_swaps.inc()
        log.info("serving: swapped to model %s from %s", vid,
                 model_location)
        return {"status": "swapped", "version": version.version_id,
                "previous": active.version_id if active else None}

    def rollback(self) -> Dict[str, Any]:
        """Re-activate the previous resident version (already warm —
        rollback is instant, no compile)."""
        with self._swap_lock:
            if len(self._versions) < 2:
                raise ScoreError("bad_request",
                                 "no previous version to roll back to")
            demoted = self._versions.pop()
            restored = self._versions[-1]
            self._active = restored
            self._schema = raw_schema(restored.model)
            n_versions = len(self._versions)
        self._staging.invalidate()  # restored model's layout may differ
        self.registry.gauge(
            "serving_model_versions", "versions held (active + rollback)"
        ).set(n_versions)
        self._m_swaps.inc()
        self.registry.counter(
            "serving_rollbacks_total",
            "model versions rolled back (manual + automatic)").inc()
        log.info("serving: rolled back %s -> %s", demoted.version_id,
                 restored.version_id)
        return {"status": "rolled_back", "version": restored.version_id,
                "previous": demoted.version_id}

    # -- learned bucket ladder ---------------------------------------------- #

    def suggest_ladder(self) -> Tuple[int, ...]:
        """The ladder the cost model + observed request sizes would
        pick right now (`serving/batcher.derive_ladder`). With an
        explicit `buckets` config, a cold model, or no traffic yet,
        this is the current ladder unchanged."""
        if self.config.buckets:
            return self.ladder
        try:
            from transmogrifai_tpu import perf
            model = perf.get_model()
        except Exception:
            model = None
        return derive_ladder(self.config.max_batch, self.config.min_bucket,
                             list(self._sizes), model,
                             n_cols=len(self._schema))

    def rebucket(self) -> Dict[str, Any]:
        """Re-derive the bucket ladder from observed traffic + predicted
        per-bucket latency and swap it in under traffic: new rungs are
        AOT-warmed on the active version OFF the serving path first, so
        the scoring thread never compiles mid-request. The top rung
        (max_batch) never changes, so admission capacity is stable.
        Serialized: concurrent rebuckets (auto + manual) would each
        derive from the same stale ladder and double-swap."""
        with self._rebucket_lock:
            return self._rebucket_locked()

    def _rebucket_locked(self) -> Dict[str, Any]:
        new = tuple(self.suggest_ladder())
        if new == tuple(self.ladder):
            return {"status": "unchanged", "ladder": list(self.ladder)}
        fresh = tuple(b for b in new if b not in self.ladder)
        if self.config.warm_on_load and fresh:
            with self._swap_lock:
                versions = list(self._versions)
            for version in versions:
                # EVERY resident version, not just the active one: a
                # post-rebucket rollback() must stay 'already warm — no
                # compile', so the demoted version needs the new rungs
                # compiled too
                version.warm(fresh, self.warm_rows)
        old = self.ladder
        with self._swap_lock:
            self.ladder = new
        self._staging.invalidate()  # per-bucket buffers keyed off rungs
        self.registry.counter(
            "serving_rebuckets_total",
            "bucket-ladder re-derivations applied").inc()
        log.info("serving: bucket ladder rebucketed %s -> %s",
                 list(old), list(new))
        try:
            from transmogrifai_tpu.obs.export import record_event
            record_event("ladder_rebucket", previous=list(old),
                         ladder=list(new))
        except Exception:
            log.debug("rebucket event emission failed", exc_info=True)
        return {"status": "rebucketed", "ladder": list(new),
                "previous": list(old)}

    def _auto_rebucket(self) -> None:
        if not self._rebucket_lock.acquire(blocking=False):
            return  # a previous attempt is still deriving/warming
        try:
            # refit from the corpus first: the serving_bucket rows this
            # process has been recording are younger than the cached
            # model's refit cadence, and a stale fit derives the cold
            # (unchanged) ladder
            from transmogrifai_tpu import perf
            perf.refresh()
            if self._rebucket_locked()["status"] == "rebucketed":
                self._auto_done = True
        except Exception:
            log.warning("serving: auto rebucket failed; ladder unchanged",
                        exc_info=True)
        finally:
            self._rebucket_lock.release()

    def rearm_auto_rebucket(self) -> bool:
        """Re-arm the auto-rebucket trigger after its one shot landed.
        The shot stays one-shot ORGANICALLY (a derived ladder should not
        churn under stable traffic); a controller that watched the
        traffic mix shift (SLO burn) re-arms it under its own cooldown.
        The next scored batch re-derives from the freshest size sample.
        Returns False when there was nothing to re-arm (still armed, or
        the auto path is off for this config)."""
        if not self.config.auto_ladder or self.config.buckets:
            return False
        if not self._auto_done:
            return False
        self._auto_done = False
        self._auto_next = self._auto_seen + 1
        return True

    def predicted_drain_s(self) -> Optional[float]:
        """Predicted seconds to drain the CURRENT queue backlog through
        top-rung batches (perf.predict_drain_seconds), clamped to
        [0.1, 30] so a runaway fit can never tell clients to go away
        for an hour. None while the cost model is cold."""
        try:
            from transmogrifai_tpu import perf
            depth = self._batcher.depth()
            top = max(self.ladder) if self.ladder else \
                self.config.max_batch
            pred = perf.predict_drain_seconds(max(1, depth), top)
            if pred is None:
                return None
            return round(max(0.1, min(30.0, pred.value)), 3)
        except Exception:
            log.debug("drain-time prediction failed", exc_info=True)
            return None

    # -- introspection ----------------------------------------------------- #

    def health(self) -> Dict[str, Any]:
        active = self._active
        if not (self._running and active):
            status = "down"
        elif self._health is not None and \
                self._health.state == QUARANTINED:
            # still "serving" when a fallback version exists, but the
            # primary path is dark — /healthz reports it as unhealthy
            # (503 + Retry-After) so balancers drain this member
            status = "quarantined"
        else:
            status = "ok"
        out = {
            "status": status,
            "model_version": active.version_id if active else None,
            "uptime_s": round(time.monotonic() - self._started_mono, 3),
            "queue_depth": self._batcher.depth(),
            "buckets": list(self.ladder),
            "compile_cache": self._compile_cache_path,
            "versions": [v.info() for v in self._versions],
            "staging": {
                "generation": self._staging.generation,
                "allocations": self._staging.allocations,
                "assembled": self._staging.assembled,
                "fallbacks": self._staging.fallbacks,
            },
        }
        if self._health is not None:
            out["health"] = self._health.snapshot()
            if status == "quarantined":
                out["retry_after_s"] = round(
                    max(self._health.retry_after_s(),
                        self.resilience.watchdog_period_s), 3)
        return out

    # -- scoring thread ---------------------------------------------------- #

    def _serve_loop(self, gen: int = 0) -> None:
        while self._running and self._generation == gen:
            batch, expired = self._batcher.next_batch()
            if self._generation != gen:
                # fenced off by a watchdog restart while we were blocked:
                # hand anything we popped back to the live loop's clients
                # as structured errors (they were already answered if
                # they were in flight when the restart fired)
                for req in [*batch, *expired]:
                    if not req._event.is_set():
                        req.fail(ScoreError(
                            "watchdog_restart",
                            "scoring loop restarted; retry"))
                return
            self._m_queue.set(self._batcher.depth())
            for req in expired:
                self._shed("deadline_exceeded").inc()
                req.fail(ScoreError(
                    "deadline_exceeded",
                    "request deadline passed while queued"))
            if not batch:
                continue
            self._auto_seen += 1
            if (self.config.auto_ladder and not self._auto_done
                    and not self.config.buckets
                    and self._auto_seen >= self._auto_next):
                # deferred rebucket once the size sample is dense enough;
                # off-thread — warming new rungs must not stall the
                # scoring loop. RETRIED every ~512 batches until one
                # lands: at the first attempt the cached model is often
                # still cold on the serving target (its fit predates the
                # bucket rows this very traffic recorded), and a one-shot
                # flag would silently disable the feature forever.
                self._auto_next = self._auto_seen + 512
                threading.Thread(target=self._auto_rebucket,
                                 name="serving-rebucket",
                                 daemon=True).start()
            with self._inflight_lock:
                if self._generation != gen:
                    continue  # fenced: top of loop exits
                self._inflight = list(batch)
                self._busy_since = time.monotonic()
            # NO `finally` around the in-flight clear: a BaseException
            # (InjectedKill / fatal runtime error) must leave the batch
            # REGISTERED as in flight while it kills this thread, so the
            # watchdog's recovery can answer those clients — a finally
            # would wipe the list on the way out and orphan them
            try:
                self._process(batch, gen)
            except Exception as e:  # the scoring thread must NEVER die
                log.exception("serving: unexpected batch failure")
                for req in batch:
                    if not req._event.is_set():
                        req.fail(ScoreError(
                            "internal",
                            f"unexpected serving failure: "
                            f"{type(e).__name__}: {e}"))
            with self._inflight_lock:
                if self._generation == gen:
                    self._inflight = []
                    self._busy_since = None

    def _dispatch_plan(self) -> Tuple[ModelVersion, str]:
        """(version, mode) for this batch. Modes:

        - ``primary``: the active version, breaker closed (normal path);
        - ``probe``: breaker open, half-open slot claimed — dispatch the
          active version to test recovery;
        - ``fallback``: breaker open, resident previous version exists —
          degraded mode, serve known-good answers instead of going dark;
        - ``reject``: breaker open, no fallback, probe not due — fail
          the batch fast with ``circuit_open``."""
        version = self._active
        h = self._health
        if h is None or not h.breaker_open:
            return version, "primary"
        if h.probe_due():
            return version, "probe"
        prev = None
        with self._swap_lock:
            if len(self._versions) >= 2:
                prev = self._versions[-2]
        if prev is not None:
            return prev, "fallback"
        return version, "reject"

    def _live(self, gen: Optional[int]) -> bool:
        """True while `gen` is still the current scoring generation. A
        stale (watchdog-fenced) thread that wakes mid-batch may still
        RESOLVE its requests (harmless — they were already answered)
        but must not note health/breaker state or account metrics for
        a generation it no longer belongs to."""
        return gen is None or self._generation == gen

    def _queue_wait_spans(self, batch: List[Request],
                          t_end: float) -> List[Request]:
        """Backdate one ``serving:queue_wait`` child per traced request
        (enqueue tick → batch pickup) and return the traced subset."""
        traced = [r for r in batch if r.trace is not None]
        for r in traced:
            if r.trace.enqueued_s is not None:
                r.trace.child_at("serving:queue_wait",
                                 r.trace.enqueued_s, t_end)
        return traced

    def _process(self, batch: List[Request],
                 gen: Optional[int] = None) -> None:
        version, mode = self._dispatch_plan()
        assert version is not None
        t_pickup = now_s()
        traced = self._queue_wait_spans(batch, t_pickup)
        if mode == "reject":
            retry_after = self._health.retry_after_s() if self._health \
                else None
            for req in batch:
                self._m_errors.inc()
                req.fail(ScoreError(
                    "circuit_open",
                    "breaker open and no resident fallback version",
                    retry_after_s=retry_after))
            return
        t0 = time.monotonic()
        with TRACER.span("serving:batch", category="serving",
                         parent=self._trace_parent,
                         requests=len(batch), mode=mode,
                         version=version.version_id) as sp:
            try:
                # batch ASSEMBLY quarantines too: two requests with
                # mismatched column sets/ftypes fail staging AND the
                # concat fallback, and that must degrade to per-request
                # scoring, not kill the batch — and it is NOT a device
                # failure, so it feeds the health window but never the
                # breaker
                fault_point(self._fault_site(SITE_BATCH_ASSEMBLE))
                t_pad0 = now_s()
                ds, n_valid, bucket = self._assemble_batch(batch)
                t_pad1 = now_s()
                sp.set(bucket=bucket, rows=n_valid)
            except Exception as e:
                log.warning("serving: batch assembly of %d requests "
                            "failed (%s); quarantining per-request",
                            len(batch), e)
                for req in batch:
                    self._score_single(req, version, mode, gen)
                return
            for r in traced:
                r.trace.child_at("serving:pad", t_pad0, t_pad1,
                                 bucket=bucket, batch_rows=n_valid)
            if self._parse_notes:
                # pad wall is closed: the sampled corpus appends can
                # no longer pollute the timing they record
                for n_rows, secs in self._parse_notes:
                    self._note_parse(n_rows, secs)
                self._parse_notes = []
            t_d0 = now_s()
            try:
                if mode != "fallback":
                    # degraded fallback skips the site: the injected
                    # fault models a broken ACTIVE version, and the
                    # resident previous version is the recovery path
                    fault_point(self._fault_site(SITE_DEVICE_DISPATCH))
                out = version.scorer.score_padded(ds, bucket)
            except Exception as e:
                t_d1 = now_s()
                for r in traced:
                    # the failing dispatch is part of this request's
                    # story (and the flight recorder's): the quarantine
                    # re-score appends its own dispatch span after it
                    r.trace.child_at(
                        "serving:device_dispatch", t_d0, t_d1,
                        error=f"{type(e).__name__}: {e}"[:200],
                        bucket=bucket, mode=mode,
                        version=version.version_id)
                if self._live(gen):
                    self._note_dispatch(False, mode)
                # error quarantine: one bad record must fail ONE
                # request. Re-score each request alone so its
                # batchmates still get answers; only the offender sees
                # the error.
                log.warning("serving: batch of %d requests failed (%s); "
                            "quarantining per-request", len(batch), e)
                for req in batch:
                    self._score_single(req, version, mode, gen)
                return
            t_d1 = now_s()
            for r in traced:
                r.trace.child_at("serving:device_dispatch", t_d0, t_d1,
                                 bucket=bucket, mode=mode,
                                 version=version.version_id)
            # success-path health notes stay INSIDE the batch span:
            # their events (breaker_close on a probe win, degraded_
            # fallback, health_transition) attach to this trace —
            # outside the span they would vanish from the goodput rollup
            latency = time.monotonic() - t0
            live = self._live(gen)
            if live:
                self._note_dispatch(True, mode)
                if mode == "fallback":
                    self._note_fallback(len(batch), version)
                if self._health is not None:
                    for _ in batch:
                        self._health.note_request(True, latency)
        if live:
            self._account_batch(len(batch), n_valid, bucket, latency)
        off = 0
        for req in batch:
            t_x0 = now_s()
            sliced = {name: slice_result_tree(v, off, off + req.n_rows)
                      for name, v in out.items()}
            if req.trace is not None:
                req.trace.child_at("serving:demux", t_x0, now_s())
            req.resolve(sliced, version.version_id)
            off += req.n_rows

    def _assemble_batch(self, batch: List[Request]
                        ) -> Tuple[Dataset, int, int]:
        """Coalesce + pad through the resident staging pool: the
        batch's ROW-WIRE requests are decoded by ONE compiled-codec
        pass per aligned run (amortized host parse — the scoring
        thread pays one pivot per batch, not the callers one per
        request), every part's columns are WRITTEN into slices of the
        per-bucket staging block, and the pad tail repeats the last
        valid row — zero fresh staging allocations in steady state
        (the parse-smoke assert). The staged dataset is already
        bucket-sized, so `score_padded`'s own concat+pad path no-ops
        and the device write reads straight off the staging buffers.
        Batches the pool refuses (mixed column layouts, exact-int
        object columns) take the legacy concat path — correctness
        never depends on staging."""
        pool = self._staging
        n_valid = sum(r.n_rows for r in batch)
        bucket = bucket_for(n_valid, self.ladder)
        parts = self._encode_parts(batch)
        alloc0, fb0 = pool.allocations, pool.fallbacks
        staged = pool.assemble(parts, n_valid, bucket)
        self._m_staging_alloc.inc(pool.allocations - alloc0)
        self._m_staging_gen.set(pool.generation)
        if staged is None:
            self._m_staging_fallback.inc(pool.fallbacks - fb0)
            ds = Dataset.concat(parts) if len(parts) > 1 else parts[0]
            return ds, n_valid, bucket
        return staged, n_valid, bucket

    def _encode_parts(self, batch: List[Request]) -> List[Dataset]:
        """Order-preserving Dataset parts for one batch: already-
        columnar requests pass through; consecutive row-wire requests
        whose rows all share one key order decode through a SINGLE
        `RowCodec.encode_aligned` call (one pivot + one bulk cast for
        the whole run). A run with mixed key orders degrades to
        per-request encodes (each request keeps its own column-union
        semantics — two requests with different column sets must fail
        assembly exactly like the eager path did). Runs group by each
        request's ENQUEUE-TIME schema object, never the live
        `self._schema`: a hot-swap between enqueue and assembly must
        not re-type queued requests against the new model."""
        from transmogrifai_tpu.data.rowcodec import codec_for
        parts: List[Dataset] = []
        run: List[Request] = []
        run_schema: Optional[Dict[str, type]] = None

        def flush() -> None:
            if not run:
                return
            t0 = time.perf_counter()
            k0 = None
            vals: List[Any] = []
            aligned = True
            for req in run:
                for r in req.rows:
                    kt = tuple(r)
                    if k0 is None:
                        k0 = kt
                    elif kt != k0:
                        aligned = False
                        break
                    vals.append(r.values())
                if not aligned:
                    break
            if aligned:
                parts.append(codec_for(k0, run_schema)
                             .encode_aligned(vals, len(vals)))
            else:
                parts.extend(req.dataset for req in run)
            # deferred to _process AFTER the pad wall closes: the
            # sampled corpus append is file IO and must not ride the
            # pad-phase timing it helps explain
            self._parse_notes.append(
                (sum(req.n_rows for req in run),
                 time.perf_counter() - t0))
            run.clear()

        for req in batch:
            if req._dataset is not None:
                flush()
                parts.append(req._dataset)
            else:
                if run and req._schema is not run_schema:
                    flush()
                run_schema = req._schema
                run.append(req)
        flush()
        return parts

    def _note_dispatch(self, ok: bool, mode: str) -> None:
        """Primary-path dispatch outcomes feed the breaker; fallback
        dispatches prove nothing about the broken primary and stay out."""
        if self._health is not None and mode in ("primary", "probe"):
            self._health.note_dispatch(ok, probe=(mode == "probe"))

    def _note_fallback(self, n_requests: int, version: ModelVersion) -> None:
        if self._m_fallback is None:
            self._m_fallback = self.registry.counter(
                "serving_degraded_fallback_total",
                "requests served by the resident previous version while "
                "the breaker was open")
        self._m_fallback.inc(n_requests)
        try:
            from transmogrifai_tpu.obs.export import record_event
            record_event("degraded_fallback", requests=n_requests,
                         member=self.fault_scope or "service",
                         version=version.version_id)
        except Exception:
            log.debug("degraded_fallback event failed", exc_info=True)

    def _score_single(self, req: Request, version: ModelVersion,
                      mode: str = "primary",
                      gen: Optional[int] = None) -> None:
        t0 = time.monotonic()
        # materialize the (possibly deferred) row decode BEFORE the
        # dispatch site: a client-malformed payload is a bad_request —
        # an INPUT problem, never a member outcome — so it must feed
        # neither the circuit breaker nor the health error-rate window
        # (either would let sustained malformed traffic from one client
        # quarantine a healthy member for every tenant)
        try:
            ds = req.dataset
        except Exception as e:
            if self._live(gen):
                self._m_errors.inc()
            req.fail(ScoreError(
                "bad_request",
                f"unparseable rows: {type(e).__name__}: {e}"))
            return
        t_d0 = now_s()
        try:
            bucket = bucket_for(req.n_rows, self.ladder)
            if mode != "fallback":
                fault_point(self._fault_site(SITE_DEVICE_DISPATCH))
            out = version.scorer.score_padded(ds, bucket)
            if req.trace is not None:
                req.trace.child_at("serving:device_dispatch", t_d0,
                                   now_s(), bucket=bucket, mode=mode,
                                   quarantined=True,
                                   version=version.version_id)
            latency = time.monotonic() - t0
            if self._live(gen):
                self._note_dispatch(True, mode)
                self._account_batch(1, req.n_rows, bucket, latency)
                if mode == "fallback":
                    self._note_fallback(1, version)
                if self._health is not None:
                    self._health.note_request(True, latency)
            req.resolve(out, version.version_id)
        except ScoreError as e:
            # admission-shaped failure (oversized request): not a
            # dispatch failure — never feeds the breaker
            if self._live(gen):
                self._m_errors.inc()
                if self._health is not None:
                    self._health.note_request(False,
                                              time.monotonic() - t0)
            req.fail(e)
        except Exception as e:
            if req.trace is not None:
                req.trace.child_at(
                    "serving:device_dispatch", t_d0, now_s(),
                    error=f"{type(e).__name__}: {e}"[:200], mode=mode,
                    quarantined=True, version=version.version_id)
            if self._live(gen):
                self._note_dispatch(False, mode)
                self._m_errors.inc()
                if self._health is not None:
                    self._health.note_request(False,
                                              time.monotonic() - t0)
            req.fail(ScoreError(
                "record_error",
                f"request failed scoring in isolation: "
                f"{type(e).__name__}: {e}"))

    def _account_batch(self, n_requests: int, n_valid: int, bucket: int,
                       latency_s: float) -> None:
        # cost-model corpus row (sampled) + predicted-vs-measured
        # residual for this bucket's compiled shape; never raises
        try:
            from transmogrifai_tpu import perf
            perf.note_serving(bucket, latency_s)
        except Exception:
            log.debug("perf serving recording failed", exc_info=True)
        self._m_batches.inc()
        self._m_rows.inc(n_valid)
        self._m_pad_rows.inc(bucket - n_valid)
        self._m_batch_lat.observe(latency_s)
        self.registry.counter(
            "serving_bucket_batches_total",
            "device batches dispatched per shape bucket",
            bucket=bucket).inc()
        self.registry.counter(
            "serving_bucket_requests_total",
            "requests coalesced per shape bucket",
            bucket=bucket).inc(n_requests)
