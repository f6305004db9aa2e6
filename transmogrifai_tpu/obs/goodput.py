"""Goodput accounting: roll a span tree into useful-work vs badput buckets.

"ML Productivity Goodput" (arxiv 2502.06982, PAPERS.md) argues the
metric that matters for accelerator fleets is not FLOPs but the fraction
of wall-clock spent on USEFUL work — everything else (recompiles,
retries, redone work, input stalls) is badput that per-op profilers
never attribute. This module computes that rollup from the spans and
events the rest of the codebase already emits:

- ``retry_backoff_s``  — time slept between retry attempts
  (`runtime/retry.py` opens a ``retry:<site>`` span around each
  backoff);
- ``recompile_s``      — time spent re-tracing jitted programs
  (`analysis/retrace.py` emits a ``recompile`` event with the measured
  trace duration on every jit cache miss) plus the XLA backend compiles
  that followed (the ``compile`` spans `utils/compile_cache.py` records
  from `jax.monitoring`, thread-seconds). JAX times a backend compile
  around its look into the persistent cache, so the bucket holds both
  real compiles and loads of a cached program: the spans with
  ``cache_hit`` set are counted under ``compile_cache_loads``;
- ``ingest_wait_s``    — main-thread time blocked on device completion
  tokens during pipelined ingest (the `IngestStats.upload_wait_s`
  attribute on each ingest span);
- ``oom_redo_s``       — wall time wasted on sweep blocks that died of
  device OOM before the halved retry succeeded (``oom_redo`` events
  from `parallel/sweep.py`);
- ``fault_redo_s``     — wall time of failed attempts that a
  `RetryPolicy` subsequently retried (``fault_redo`` events: the work
  is redone, distinct from the backoff sleep);
- ``productive_s``     — the remainder. Buckets sum to the root span's
  wall time BY CONSTRUCTION, so "what fraction was useful" is always
  answerable.

Savings are tracked separately (they are not part of the wall-time
decomposition): ``resume_saved_s`` sums the journaled durations of
sweep blocks a resumed run skipped (``journal_resume`` events), and
``cache_saved_s`` sums the upload seconds feature-cache hits avoided —
each artifact records its cold build's wall time, so a warm replay
reports cold-minus-warm as recovered ingest badput (``cache_hit``
events from `parallel/bigdata.py`); ``compile_cache_saved_s`` sums the
warmup seconds serving's persistent XLA compile cache recovered vs each
model's recorded cold warmup (``compile_cache_saved`` events from
`serving/service.py`).

A ``mesh`` section (present when a distributed sweep ran) rolls up the
scheduler's ``mesh_utilization`` events: the fraction of workers × wall
the mesh lanes spent executing grid blocks, plus steal/requeue/idle
counters — the measured packing efficiency behind any pod-scale
extrapolation (`parallel/scheduler.py`).

The report lands in `RunProfile.to_json()["goodput"]`, bench payloads,
and beside the CLI's ``--trace-out`` trace.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Dict, Iterable, Optional

from transmogrifai_tpu.obs.trace import Span

__all__ = ["GoodputReport", "build_report", "fleet_mesh_rollup",
           "BADPUT_BUCKETS"]

BADPUT_BUCKETS = ("retry_backoff_s", "recompile_s", "ingest_wait_s",
                  "oom_redo_s", "fault_redo_s")


@dataclass
class GoodputReport:
    """Wall-time decomposition of one trace (one run)."""

    wall_s: float = 0.0
    trace_id: Optional[str] = None
    buckets: Dict[str, float] = field(default_factory=dict)
    savings: Dict[str, float] = field(default_factory=dict)
    counts: Dict[str, int] = field(default_factory=dict)
    # distributed-sweep packing: rolled up from the scheduler's
    # ``mesh_utilization`` events (parallel/scheduler.py) — how much of
    # workers × wall the mesh lanes spent executing blocks, plus
    # steal/requeue/straggler counters. Empty when no schedule ran.
    mesh: Dict[str, Any] = field(default_factory=dict)
    # continual-training accounting: rolled up from the loop's
    # ``continual_cycle`` summary events (continual/loop.py) — cycles by
    # outcome, refit wall time, and append-to-fresh-model staleness.
    # Empty when no continual loop ran in this trace.
    continual: Dict[str, Any] = field(default_factory=dict)
    # learned-cost-model scorecard: rolled up from ``perf_residual``
    # events (one per consumer decision a prediction backed) — how many
    # predictions this run made and how far off they were. Empty when
    # the model was cold/disabled.
    perf: Dict[str, Any] = field(default_factory=dict)
    # fleet-serving accounting: rolled up from ``fleet_swap`` events
    # (one per rolling model swap — wall time plus the PER-TENANT
    # traffic served/shed during the swap window, the goodput-under-
    # rolling-swaps number of serving/fleet.py) and ``tenant_shed``
    # admission events. Empty when no fleet ran in this trace.
    fleet: Dict[str, Any] = field(default_factory=dict)
    # fleet-frontend routing accounting: rolled up from
    # ``router_route`` events (serving/frontend.py emits one per routed
    # request) — requests per replica, warm vs cold routing decisions,
    # wire split (json vs binary), and error outcomes. Empty when no
    # frontend routed in this trace.
    router: Dict[str, Any] = field(default_factory=dict)
    # compiled-scoring accounting: rolled up from ``device_dispatch``
    # events (CompiledScorer._dispatch emits one per XLA program launch
    # with the bytes shipped in and returned) — dispatch counts prove
    # whole-pipeline fusion held (one per score call on fused plans) and
    # the byte totals are the numerator of the achieved-bandwidth
    # roofline bench reports as `scoring_hbm_frac`. Empty when no
    # compiled scoring ran inside a span.
    scoring: Dict[str, Any] = field(default_factory=dict)
    # serving-resilience accounting (serving/resilience.py): breaker
    # open/close transitions, quarantine entries and recoveries with
    # the measured MTTR (mean/max seconds from outage start to the
    # HEALTHY transition), degraded-fallback traffic served by the
    # resident previous version, and watchdog thread restarts — the
    # availability story of a run that survived injected (or real)
    # serving faults. Empty when nothing tripped.
    resilience: Dict[str, Any] = field(default_factory=dict)
    # SLO accounting (obs/slo.py): rolled from ``slo_alert`` events —
    # alerts fired/resolved per SLO name with total measured
    # time-in-alert seconds. A run whose chaos storm fired and cleared
    # an availability alert reports it here. Empty when no SLO engine
    # ran (or nothing fired).
    slo: Dict[str, Any] = field(default_factory=dict)

    @property
    def badput_s(self) -> float:
        return sum(v for k, v in self.buckets.items()
                   if k != "productive_s")

    @property
    def goodput_frac(self) -> float:
        if self.wall_s <= 0.0:
            return 1.0
        return max(0.0, min(1.0, self.buckets.get("productive_s", 0.0)
                            / self.wall_s))

    def to_json(self) -> Dict[str, Any]:
        out = {
            "wall_s": round(self.wall_s, 6),
            "trace_id": self.trace_id,
            "goodput_frac": round(self.goodput_frac, 4),
            "buckets": {k: round(v, 6)
                        for k, v in sorted(self.buckets.items())},
            "savings": {k: round(v, 6)
                        for k, v in sorted(self.savings.items())},
            "counts": dict(sorted(self.counts.items())),
        }
        if self.mesh:
            out["mesh"] = dict(sorted(self.mesh.items()))
        if self.continual:
            out["continual"] = dict(sorted(self.continual.items()))
        if self.perf:
            out["perf"] = dict(sorted(self.perf.items()))
        if self.fleet:
            out["fleet"] = dict(sorted(self.fleet.items()))
        if self.router:
            out["router"] = dict(sorted(self.router.items()))
        if self.scoring:
            out["scoring"] = dict(sorted(self.scoring.items()))
        if self.resilience:
            out["resilience"] = dict(sorted(self.resilience.items()))
        if self.slo:
            out["slo"] = dict(sorted(self.slo.items()))
        return out

    def pretty(self) -> str:
        lines = [f"goodput: {self.goodput_frac:.1%} of "
                 f"{self.wall_s:.2f}s wall"]
        for k, v in sorted(self.buckets.items()):
            lines.append(f"  {k}: {v:.3f}s")
        for k, v in sorted(self.savings.items()):
            lines.append(f"  (saved) {k}: {v:.3f}s")
        return "\n".join(lines)


def fleet_mesh_rollup(
        host_meshes: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Roll per-host ``GoodputReport.mesh`` sections into one fleet
    view.

    ``mesh_utilization_frac`` is weighted by each host's workers × wall
    (the ``worker_wall_s`` accumulator `build_report` stamps), so a
    host with 8 busy lanes counts 8× a host with one — the same math
    `build_report` uses within a host, lifted across the pod. Worker
    counts and block/steal/requeue counters sum; hosts without a mesh
    section (no distributed sweep ran there) are skipped. Hosts that
    only report ``utilization_frac`` (pre-accumulator payloads) fall
    back to an unweighted wall of 1.0 so old reports still merge.
    """
    out: Dict[str, Any] = {"hosts": 0}
    wall = busy = 0.0
    for m in host_meshes:
        if not m:
            continue
        out["hosts"] += 1
        w = float(m.get("worker_wall_s", 0.0) or 0.0)
        if w <= 0.0:
            w = 1.0
            b = float(m.get("utilization_frac", 0.0) or 0.0)
        else:
            b = float(m.get("busy_s", 0.0) or 0.0)
        wall += w
        busy += b
        out["workers"] = out.get("workers", 0) + int(
            m.get("workers", 0) or 0)
        for key in ("schedules", "steals", "requeues", "blocks"):
            out[key] = out.get(key, 0) + int(m.get(key, 0) or 0)
        out["idle_s"] = round(out.get("idle_s", 0.0)
                              + float(m.get("idle_s", 0.0) or 0.0), 6)
    out["worker_wall_s"] = round(wall, 6)
    out["busy_s"] = round(busy, 6)
    out["mesh_utilization_frac"] = round(
        busy / wall, 4) if wall > 0 else 0.0
    return out


def build_report(root: Span, spans: Iterable[Span]) -> GoodputReport:
    """Classify `spans` (one trace, root included or not) into goodput
    buckets against `root`'s wall clock.

    Badput assignment is exclusive by source: a retry span counts its
    own duration once even when nested inside an ingest worker span
    (the ingest bucket reads the stats attribute, not span wall time),
    so buckets never double-count one second of badput."""
    report = GoodputReport(wall_s=root.duration_s, trace_id=root.trace_id)
    b = {k: 0.0 for k in BADPUT_BUCKETS}
    counts = {"retries": 0, "recompiles": 0, "oom_redos": 0,
              "resumed_blocks": 0, "faults_injected": 0,
              "cache_hits": 0, "cache_misses": 0,
              "steals": 0, "workers_retired": 0,
              "block_resizes": 0, "compile_cache_loads": 0}
    saved = 0.0
    cache_saved = 0.0
    compile_saved = 0.0
    compile_hits = 0
    fleet: Dict[str, Any] = {}
    router: Dict[str, Any] = {}
    resilience: Dict[str, Any] = {}
    scoring: Dict[str, Any] = {}
    slo: Dict[str, Any] = {}
    mttrs: list = []
    # mesh rollup accumulators: several schedules (one per selector fit)
    # can land in one trace — utilization averages weighted by each
    # schedule's wall, counters sum
    mesh_wall = 0.0
    mesh_busy = 0.0
    mesh: Dict[str, Any] = {}
    continual: Dict[str, Any] = {}
    perf_n = 0
    perf_err_sum = 0.0
    perf_by_target: Dict[str, int] = {}
    seen: set = set()
    for sp in [root, *spans]:
        if sp.span_id in seen or sp.trace_id != root.trace_id:
            continue
        seen.add(sp.span_id)
        if sp is not root:  # the root's wall IS the denominator
            if sp.category == "retry":
                b["retry_backoff_s"] += sp.duration_s
                counts["retries"] += 1
            elif sp.category == "ingest":
                b["ingest_wait_s"] += float(
                    sp.attributes.get("upload_wait_s", 0.0) or 0.0)
            elif sp.category == "compile":
                b["recompile_s"] += sp.duration_s
                counts["compile_cache_loads"] += bool(
                    sp.attributes.get("cache_hit"))
        # events count wherever they landed — INCLUDING the root (a
        # sweep invoked directly under the root attaches its
        # journal_resume / oom_redo events there)
        for name, _, attrs in sp.events:
            if name == "recompile":
                b["recompile_s"] += float(attrs.get("trace_s", 0.0) or 0.0)
                counts["recompiles"] += 1
            elif name == "oom_redo":
                b["oom_redo_s"] += float(attrs.get("wasted_s", 0.0) or 0.0)
                counts["oom_redos"] += 1
            elif name == "fault_redo":
                b["fault_redo_s"] += float(attrs.get("wasted_s", 0.0) or 0.0)
            elif name == "journal_resume":
                saved += float(attrs.get("saved_s", 0.0) or 0.0)
                counts["resumed_blocks"] += int(attrs.get("blocks", 0) or 0)
            elif name == "cache_hit":
                cache_saved += float(attrs.get("saved_s", 0.0) or 0.0)
                counts["cache_hits"] += 1
            elif name == "cache_miss":
                counts["cache_misses"] += 1
            elif name == "compile_cache_saved":
                compile_saved += float(attrs.get("saved_s", 0.0) or 0.0)
                compile_hits += 1
            elif name == "fleet_swap":
                fleet["swaps"] = fleet.get("swaps", 0) + 1
                st = str(attrs.get("status") or "unknown")
                fleet[st] = fleet.get(st, 0) + 1
                fleet["swap_wall_s"] = round(
                    fleet.get("swap_wall_s", 0.0)
                    + float(attrs.get("wall_s", 0.0) or 0.0), 6)
                fleet["requests_during_swaps"] = \
                    fleet.get("requests_during_swaps", 0) + int(
                        attrs.get("requests_during_swap", 0) or 0)
                fleet["shed_during_swaps"] = \
                    fleet.get("shed_during_swaps", 0) + int(
                        attrs.get("shed_during_swap", 0) or 0)
                per_tenant = attrs.get("per_tenant") or {}
                if isinstance(per_tenant, dict):
                    tenants = fleet.setdefault("tenants", {})
                    for tname, d in per_tenant.items():
                        cur = tenants.setdefault(
                            str(tname), {"requests_during_swaps": 0,
                                         "shed_during_swaps": 0})
                        cur["requests_during_swaps"] += int(
                            (d or {}).get("requests", 0) or 0)
                        cur["shed_during_swaps"] += int(
                            (d or {}).get("shed", 0) or 0)
            elif name == "tenant_shed":
                fleet["sheds"] = fleet.get("sheds", 0) + 1
            elif name == "router_route":
                router["requests"] = router.get("requests", 0) + 1
                router["rows"] = router.get("rows", 0) + int(
                    attrs.get("rows", 0) or 0)
                if attrs.get("warm"):
                    router["warm_routes"] = router.get("warm_routes", 0) + 1
                else:
                    router["cold_routes"] = router.get("cold_routes", 0) + 1
                by_rep = router.setdefault("by_replica", {})
                rep = str(attrs.get("replica") or "unknown")
                by_rep[rep] = by_rep.get(rep, 0) + 1
                by_wire = router.setdefault("by_wire", {})
                wire = str(attrs.get("wire") or "json")
                by_wire[wire] = by_wire.get(wire, 0) + 1
                outcome = str(attrs.get("outcome") or "ok")
                if outcome != "ok":
                    errs = router.setdefault("errors", {})
                    errs[outcome] = errs.get(outcome, 0) + 1
            elif name == "device_dispatch":
                scoring["dispatches"] = scoring.get("dispatches", 0) + 1
                scoring["bytes_in"] = scoring.get("bytes_in", 0) + int(
                    attrs.get("bytes_in", 0) or 0)
                scoring["bytes_out"] = scoring.get("bytes_out", 0) + int(
                    attrs.get("bytes_out", 0) or 0)
                scoring["dispatch_s"] = round(
                    scoring.get("dispatch_s", 0.0)
                    + float(attrs.get("dispatch_s", 0.0) or 0.0), 6)
                if attrs.get("quant"):
                    scoring["quant_dispatches"] = \
                        scoring.get("quant_dispatches", 0) + 1
            elif name == "breaker_open":
                resilience["breaker_opens"] = \
                    resilience.get("breaker_opens", 0) + 1
            elif name == "breaker_close":
                resilience["breaker_closes"] = \
                    resilience.get("breaker_closes", 0) + 1
            elif name == "health_transition":
                to = str(attrs.get("to") or "")
                if to == "quarantined":
                    resilience["quarantines"] = \
                        resilience.get("quarantines", 0) + 1
                rec = attrs.get("recovery_s")
                if rec is not None:
                    resilience["recoveries"] = \
                        resilience.get("recoveries", 0) + 1
                    mttrs.append(float(rec))
            elif name == "degraded_fallback":
                resilience["fallback_batches"] = \
                    resilience.get("fallback_batches", 0) + 1
                resilience["fallback_requests"] = \
                    resilience.get("fallback_requests", 0) + int(
                        attrs.get("requests", 0) or 0)
            elif name == "watchdog_restart":
                resilience["watchdog_restarts"] = \
                    resilience.get("watchdog_restarts", 0) + 1
            elif name == "slo_alert":
                sname = str(attrs.get("slo") or "unknown")
                per = slo.setdefault("by_slo", {}).setdefault(
                    sname, {"fired": 0, "resolved": 0,
                            "alert_s": 0.0})
                state = str(attrs.get("state") or "")
                if state == "firing":
                    slo["alerts_fired"] = slo.get("alerts_fired", 0) + 1
                    per["fired"] += 1
                elif state == "resolved":
                    slo["alerts_resolved"] = \
                        slo.get("alerts_resolved", 0) + 1
                    per["resolved"] += 1
                    per["alert_s"] = round(
                        per["alert_s"]
                        + float(attrs.get("alert_s", 0.0) or 0.0), 6)
            elif name == "supervisor_restart":
                continual["supervisor_restarts"] = \
                    continual.get("supervisor_restarts", 0) + 1
            elif name == "fault":
                counts["faults_injected"] += 1
            elif name == "steal":
                counts["steals"] += 1
            elif name == "worker_retired":
                counts["workers_retired"] += 1
            elif name == "block_resize":
                counts["block_resizes"] += 1
            elif name == "perf_residual":
                perf_n += 1
                perf_err_sum += float(attrs.get("abs_rel_err", 0.0) or 0.0)
                t = str(attrs.get("target") or "unknown")
                perf_by_target[t] = perf_by_target.get(t, 0) + 1
            elif name == "continual_cycle":
                continual["cycles"] = continual.get("cycles", 0) + 1
                st = attrs.get("status") or "unknown"
                continual[st] = continual.get(st, 0) + 1
                continual["cycle_wall_s"] = round(
                    continual.get("cycle_wall_s", 0.0)
                    + float(attrs.get("wall_s", 0.0) or 0.0), 6)
                stale = attrs.get("staleness_s")
                if stale is not None:
                    continual["last_staleness_s"] = round(float(stale), 6)
            elif name == "drift_detected":
                continual["drift_detected"] = \
                    continual.get("drift_detected", 0) + 1
            elif name == "mesh_utilization":
                wall = float(attrs.get("wall_s", 0.0) or 0.0)
                workers = int(attrs.get("workers", 0) or 0)
                mesh_wall += wall * max(workers, 1)
                mesh_busy += (float(attrs.get("utilization_frac", 0.0)
                                    or 0.0) * wall * max(workers, 1))
                mesh["workers"] = max(mesh.get("workers", 0), workers)
                mesh["schedules"] = mesh.get("schedules", 0) + 1
                for key in ("steals", "requeues", "blocks"):
                    mesh[key] = mesh.get(key, 0) + int(
                        attrs.get(key, 0) or 0)
                mesh["idle_s"] = round(mesh.get("idle_s", 0.0) + float(
                    attrs.get("idle_s", 0.0) or 0.0), 6)
    # badput cannot exceed wall (overlapped worker backoffs can): clamp
    # proportionally so the decomposition stays a decomposition
    total_bad = sum(b.values())
    if total_bad > report.wall_s > 0.0:
        scale = report.wall_s / total_bad
        b = {k: v * scale for k, v in b.items()}
        total_bad = report.wall_s
    b["productive_s"] = max(0.0, report.wall_s - total_bad)
    report.buckets = b
    if saved > 0.0 or counts["resumed_blocks"]:
        report.savings["resume_saved_s"] = saved
    if cache_saved > 0.0 or counts["cache_hits"]:
        report.savings["cache_saved_s"] = cache_saved
    if compile_hits:
        report.savings["compile_cache_saved_s"] = compile_saved
        counts["compile_cache_hits"] = compile_hits
    if fleet:
        report.fleet = fleet
    if router:
        report.router = router
    if scoring:
        report.scoring = scoring
    if resilience:
        if mttrs:
            resilience["mean_mttr_s"] = round(sum(mttrs) / len(mttrs), 6)
            resilience["max_mttr_s"] = round(max(mttrs), 6)
        report.resilience = resilience
    if slo:
        report.slo = slo
    if mesh:
        mesh["utilization_frac"] = round(
            mesh_busy / mesh_wall, 4) if mesh_wall > 0 else 0.0
        # raw accumulators so fleet_mesh_rollup can re-weight across
        # hosts without re-walking each host's trace
        mesh["worker_wall_s"] = round(mesh_wall, 6)
        mesh["busy_s"] = round(mesh_busy, 6)
        report.mesh = mesh
    if continual:
        report.continual = continual
    if perf_n:
        report.perf = {
            "predictions": perf_n,
            "mean_abs_rel_err": round(perf_err_sum / perf_n, 4),
            "by_target": dict(sorted(perf_by_target.items()))}
    report.counts = {k: v for k, v in counts.items() if v}
    return report
