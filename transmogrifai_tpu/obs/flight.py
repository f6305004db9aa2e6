"""Crash flight recorder: a bounded ring of recent spans/events/metric
deltas, dumped atomically to a post-mortem artifact when something dies.

The serving plane's incidents (breaker trips, watchdog restarts, shed
storms — PR 12) and the fleet goodput methodology's demand that badput
be ATTRIBUTED (arxiv 2502.06982) both need the same thing at 3am: "what
happened in the 30 seconds before the incident", as one file, written
by the process that was there. Post-hoc log scraping can't answer that
— the interesting spans were tail-sampled into the process ring and the
process may be about to die. So this module keeps a fixed-size,
lock-free ring (CPython ``deque(maxlen=...)`` appends are atomic — no
lock on the hot path) of compact records fed by:

- every FINISHED span on the global tracer (a `Tracer` sink installed
  by `enable()`), which includes every kept request trace and every
  serving batch span;
- every `record_event` emission (retries, faults, breaker transitions,
  SLO alerts) whether or not a span/log was open;
- optional metric-delta notes (`note_metric`) from subsystems that want
  a counter movement in the post-mortem timeline.

`dump(reason)` stages the artifact in a temp sibling and commits it via
`runtime/integrity.commit_staged_dir` — the same crash-consistency
protocol model saves use — so a dump racing a SIGKILL never leaves a
torn half-artifact. The artifact is three files:

- ``trace.json``  — a VALID Chrome/Perfetto trace (own pid +
  process_name metadata, so it merges with other processes' traces and
  passes `validate_chrome_trace`);
- ``events.jsonl`` — the ring's event/metric tail, one JSON per line;
- ``meta.json``   — reason, timestamps, ring occupancy, drop counts.

Dump triggers (wired in `serving/`): watchdog restart, breaker open,
quarantine entry, unhandled scoring-thread death (the watchdog's
``dead`` verdict), SIGTERM (cli `serve`), and on demand via the HTTP
``/debug/dump`` route. Dumps are debounced (`min_interval_s`) so an
error storm produces ONE artifact per window, not one per failure.
"""

from __future__ import annotations

import json
import logging
import os
import shutil
import tempfile
import threading
import time
from collections import deque
from typing import Any, Dict, List, Optional

from transmogrifai_tpu.obs.trace import TRACER, Span, now_s

log = logging.getLogger(__name__)

__all__ = ["FlightRecorder", "RECORDER", "get_recorder", "enable",
           "disable", "note_event", "note_metric", "request_dump"]

DEFAULT_CAPACITY = 4096
DEFAULT_MIN_INTERVAL_S = 5.0


def default_dump_dir() -> str:
    from transmogrifai_tpu.store.config import resolve_dir
    return resolve_dir("flight", env="TRANSMOGRIFAI_FLIGHT_DIR")


class FlightRecorder:
    """See module docstring. One per process (`RECORDER`); tests build
    their own. `enabled` gates the ring feed so an idle (non-serving)
    process pays a single attribute check per span."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY,
                 dump_dir: Optional[str] = None,
                 min_interval_s: float = DEFAULT_MIN_INTERVAL_S):
        # deque(maxlen) appends/iteration are atomic under the GIL: the
        # scoring thread, HTTP workers, and the watchdog all feed this
        # ring without a lock on the record path
        self._ring: deque = deque(maxlen=int(capacity))
        self.capacity = int(capacity)
        self.dump_dir = dump_dir
        self.min_interval_s = float(min_interval_s)
        self.enabled = False
        self.records_seen = 0          # monotonic; seen - len(ring) = dropped
        self.dumps: List[str] = []     # committed artifact paths
        self.dump_failures = 0
        self._last_dump_s: Optional[float] = None
        self._dump_lock = threading.Lock()  # dumps only — never the feed
        self._seq = 0
        # called (reason, committed_path) after every successful dump;
        # the fleet incident coordinator hooks here. Exceptions eaten —
        # a bad hook must not fail the artifact that already committed.
        self.on_dump: List[Any] = []

    def configure(self, dump_dir: Optional[str] = None,
                  capacity: Optional[int] = None,
                  min_interval_s: Optional[float] = None
                  ) -> "FlightRecorder":
        if dump_dir is not None:
            self.dump_dir = dump_dir
        if min_interval_s is not None:
            self.min_interval_s = float(min_interval_s)
        if capacity is not None and int(capacity) != self.capacity:
            self.capacity = int(capacity)
            self._ring = deque(self._ring, maxlen=self.capacity)
        return self

    # -- feed (hot path: no locks) ------------------------------------------ #

    def note_span(self, sp: Span) -> None:
        if not self.enabled:
            return
        self.records_seen += 1
        self._ring.append(("span", sp))

    def note_event(self, name: str,
                   attrs: Optional[Dict[str, Any]] = None) -> None:
        if not self.enabled:
            return
        self.records_seen += 1
        self._ring.append(("event", (name, now_s(), dict(attrs or {}))))

    def note_metric(self, name: str, value: float,
                    **labels: Any) -> None:
        if not self.enabled:
            return
        self.records_seen += 1
        self._ring.append(
            ("metric", (name, now_s(), float(value), dict(labels))))

    # -- dump ---------------------------------------------------------------- #

    def snapshot(self) -> List[Any]:
        """A consistent-enough copy of the ring (atomic list() under the
        GIL), oldest first."""
        return list(self._ring)

    def dump(self, reason: str, out_dir: Optional[str] = None,
             force: bool = False) -> Optional[str]:
        """Write one post-mortem artifact; returns its committed path,
        or None when debounced/disabled/failed (a flight recorder must
        never take down the thing it is recording). `force` skips the
        debounce (the on-demand /debug/dump route)."""
        if not self.enabled and not force:
            return None
        with self._dump_lock:
            now = time.perf_counter()
            if not force and self._last_dump_s is not None and \
                    now - self._last_dump_s < self.min_interval_s:
                return None
            self._last_dump_s = now
            self._seq += 1
            seq = self._seq
        records = self.snapshot()
        base = out_dir or self.dump_dir or default_dump_dir()
        try:
            return self._write(records, reason, base, seq)
        except Exception:
            self.dump_failures += 1
            log.warning("flight: dump (%s) failed", reason, exc_info=True)
            return None

    def _write(self, records: List[Any], reason: str, base: str,
               seq: int) -> str:
        from transmogrifai_tpu.obs.export import chrome_trace
        from transmogrifai_tpu.runtime.integrity import commit_staged_dir
        os.makedirs(base, exist_ok=True)
        stamp = time.strftime("%Y%m%d-%H%M%S")
        final = os.path.join(base, f"flight-{stamp}-{seq:03d}-{reason}")
        staged = tempfile.mkdtemp(prefix=".flight-staging-", dir=base)
        try:
            spans = [rec for kind, rec in records if kind == "span"]
            # the ring's loose events render as instants on a synthetic
            # recorder span so the Chrome trace stays fully parented
            carrier = Span("flight:events", category="flight")
            for kind, rec in records:
                if kind == "event":
                    name, t_s, attrs = rec
                    carrier.events.append((name, t_s, attrs))
                elif kind == "metric":
                    name, t_s, value, labels = rec
                    carrier.events.append(
                        (name, t_s, {"value": value, **labels}))
            if carrier.events:
                carrier.start_s = min(t for _, t, _ in carrier.events)
                carrier.end()
                carrier.end_s = max(
                    carrier.end_s or 0.0,
                    max(t for _, t, _ in carrier.events))
                spans = spans + [carrier]
            trace = chrome_trace(
                spans, process_name=f"flight:{reason}", pid=os.getpid())
            # a ring SNAPSHOT is not a full trace: a span's parent may
            # still be open (never finished -> never in the ring) or
            # already scrolled out. Orphaned parent references are
            # detached (original id kept as `orphaned_parent`) so the
            # dump stays a VALID Chrome trace per validate_chrome_trace
            present = {ev["args"]["span_id"]
                       for ev in trace["traceEvents"]
                       if ev.get("ph") == "X"
                       and isinstance(ev.get("args", {}).get("span_id"),
                                      int)}
            for ev in trace["traceEvents"]:
                if ev.get("ph") != "X":
                    continue
                parent = ev.get("args", {}).get("parent_id")
                if parent is not None and parent not in present:
                    ev["args"]["orphaned_parent"] = parent
                    ev["args"]["parent_id"] = None
            with open(os.path.join(staged, "trace.json"), "w",
                      encoding="utf-8") as fh:
                json.dump(trace, fh)
            with open(os.path.join(staged, "events.jsonl"), "w",
                      encoding="utf-8") as fh:
                for kind, rec in records:
                    if kind == "span":
                        fh.write(json.dumps(
                            {"kind": "span", **rec.to_json()},
                            default=repr) + "\n")
                    elif kind == "event":
                        name, t_s, attrs = rec
                        fh.write(json.dumps(
                            {"kind": "event", "name": name,
                             "ts_s": round(t_s, 6), **attrs},
                            default=repr) + "\n")
                    else:
                        name, t_s, value, labels = rec
                        fh.write(json.dumps(
                            {"kind": "metric", "name": name,
                             "ts_s": round(t_s, 6), "value": value,
                             **labels}, default=repr) + "\n")
            with open(os.path.join(staged, "meta.json"), "w",
                      encoding="utf-8") as fh:
                from transmogrifai_tpu.obs import trace as _trace_mod
                json.dump({
                    "reason": reason, "at": time.time(), "pid": os.getpid(),
                    "records": len(records),
                    "capacity": self.capacity,
                    "records_seen": self.records_seen,
                    "dropped": max(0, self.records_seen - len(records)),
                    # clock anchors: this process's wall epoch and the
                    # perf-clock zero all ts_s offsets count from — the
                    # cross-host incident merge shifts every dump onto
                    # one fleet timeline with these
                    "epoch_time": _trace_mod._EPOCH_TIME,
                    "epoch_perf": _trace_mod._EPOCH_PERF,
                }, fh)
            commit_staged_dir(staged, final)
        except BaseException:
            shutil.rmtree(staged, ignore_errors=True)
            raise
        self.dumps.append(final)
        log.warning("flight: dumped %d record(s) to %s (reason: %s)",
                    len(records), final, reason)
        for hook in list(self.on_dump):
            try:
                hook(reason, final)
            except Exception:
                log.debug("flight: on_dump hook failed", exc_info=True)
        try:
            from transmogrifai_tpu.obs.export import emit_event
            emit_event("flight_dump", reason=reason, path=final,
                       records=len(records))
        except Exception:  # best-effort breadcrumb
            log.debug("flight_dump event emission failed", exc_info=True)
        return final

    def reset(self) -> None:
        self._ring.clear()
        self.records_seen = 0
        self.dumps = []
        self.dump_failures = 0
        # the debounce anchor is read twice under _dump_lock in dump()
        # (`is not None`, then the subtraction) — nulling it bare from
        # another thread can land between the two reads and crash the
        # dump path; every _last_dump_s write takes the lock
        with self._dump_lock:
            self._last_dump_s = None


RECORDER = FlightRecorder()


def get_recorder() -> FlightRecorder:
    return RECORDER


def enable(dump_dir: Optional[str] = None,
           capacity: Optional[int] = None,
           min_interval_s: Optional[float] = None) -> FlightRecorder:
    """Turn the process recorder on and hook it to the global tracer
    (idempotent — serving services call this at construction)."""
    RECORDER.configure(dump_dir=dump_dir, capacity=capacity,
                       min_interval_s=min_interval_s)
    RECORDER.enabled = True
    TRACER.add_sink(RECORDER.note_span)
    return RECORDER


def disable() -> None:
    RECORDER.enabled = False
    TRACER.remove_sink(RECORDER.note_span)


def note_event(name: str, attrs: Optional[Dict[str, Any]] = None) -> None:
    """Module-level feed used by `obs.export.record_event` (cheap no-op
    while the recorder is disabled)."""
    RECORDER.note_event(name, attrs)


def note_metric(name: str, value: float, **labels: Any) -> None:
    RECORDER.note_metric(name, value, **labels)


def request_dump(reason: str, out_dir: Optional[str] = None,
                 force: bool = False) -> Optional[str]:
    """Best-effort dump trigger for incident paths (breaker open,
    quarantine, watchdog restart, SIGTERM): never raises."""
    try:
        return RECORDER.dump(reason, out_dir=out_dir, force=force)
    except Exception:
        log.debug("flight: request_dump(%s) failed", reason, exc_info=True)
        return None
