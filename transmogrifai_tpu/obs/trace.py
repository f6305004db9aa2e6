"""Thread-safe hierarchical span tracer (the unified-timeline half of the
observability layer).

Every subsystem in this codebase already times itself — `RunProfile`
phases, `IngestStats` stage timers, serving latency histograms,
`RetraceMonitor` compile counts — but each island keeps its own clock
and none can be correlated into one timeline. A `Span` is the shared
currency: a named, attributed interval with a parent, so a retry
backoff inside an ingest worker inside a training run renders as ONE
nested tree (exported to Perfetto by `obs/export.py`, rolled into
goodput/badput buckets by `obs/goodput.py`).

Design constraints this module answers:

- **contextvar propagation**: the current span lives in a
  `contextvars.ContextVar`, so nesting works without threading a span
  handle through every call signature. Worker threads (ingest pool,
  serving batcher, selector families) do NOT inherit the caller's
  context — cross-thread parents are passed EXPLICITLY via
  ``tracer.span(..., parent=span)``, which also sets the contextvar in
  the worker for anything it calls (e.g. a `RetryPolicy` backoff span
  opened inside a worker chunk span).
- **two clocks**: span durations come from `time.perf_counter()`
  (monotonic — wall-clock steps must not corrupt durations; satellite
  of the same PR fixes `RunProfile` the same way), while each span also
  carries an epoch `start_at` for humans. Export timestamps derive from
  the perf clock against one process epoch, so they are monotonic and
  non-negative by construction.
- **the profiler's clock**: `Tracer.span` also holds a
  `jax.profiler.TraceAnnotation` of the same name open for its
  lifetime, on the thread that opened it. While the JAX profiler
  records, every program span therefore sits in the trace's host plane
  beside the device's operations, and an idle gap on the device can be
  attributed to the program phase open when it began; while it does
  not record, the annotation costs well under a microsecond.
- **bounded memory**: finished spans collect in a ring (default 64k);
  a long-lived serving process drops the oldest and counts the drops
  instead of growing without bound.
- **the host-device boundary**: a read of a device array goes through
  `pull(site, x)` and an upload of a host array through `upload(site,
  x)` (or, where the copy happens inside a `jnp` call, under
  `uploading(site, operands)`): spans `pull:<site>` / `upload:<site>`
  of category `transfer` with the `bytes` that crossed, a pull also
  with the seconds it waited for the producer (`wait_s`) and the
  seconds of the read itself (`copy_s`). `train_passes()` hands a
  reader the finished spans by training pass, attributes included.

The tracer is always on: an un-exported span costs one object and two
clock reads, which is noise next to anything worth tracing here (file
IO, XLA dispatch, model fits).
"""

from __future__ import annotations

import contextlib
import contextvars
import itertools
import logging
import re
import threading
import time
import uuid
from collections import deque
from dataclasses import dataclass
from typing import (Any, Callable, Dict, Iterable, Iterator, List, Optional,
                    Tuple)

__all__ = ["Span", "Tracer", "TRACER", "get_tracer", "current_span",
           "add_event", "ambient_traceparent", "new_run_id", "now_s",
           "pull", "upload", "uploading", "train_passes",
           "new_trace_id", "span_id_hex", "parse_traceparent",
           "format_traceparent", "TraceContext", "RequestTrace",
           "TracingParams", "TailSampler"]

# one process epoch for both clocks: export timestamps are
# perf_counter-relative to this origin, mapped onto the epoch origin
_EPOCH_PERF = time.perf_counter()
_EPOCH_TIME = time.time()

_span_ids = itertools.count(1)

# `jax.profiler.TraceAnnotation`, imported on the first span (this
# module stays importable without JAX); `contextlib.nullcontext` where
# the profiler cannot be imported
_annotation: Optional[Callable[[str], Any]] = None


def _profiler_annotation(name: str):
    global _annotation
    if _annotation is None:
        try:
            from jax.profiler import TraceAnnotation
            _annotation = TraceAnnotation
        except Exception:
            _annotation = contextlib.nullcontext
    return _annotation(name)


def new_run_id() -> str:
    """Run-level correlation id: unique across processes, short enough
    to grep in a JSONL event log."""
    return uuid.uuid4().hex[:12]


def now_s() -> float:
    """Perf-clock offset from the process trace epoch — the timebase
    every span's start_s/end_s lives in. Exposed so code that measures
    a phase boundary OUTSIDE a span (e.g. the micro-batcher's enqueue
    tick) can later backdate a span to it."""
    return time.perf_counter() - _EPOCH_PERF


# -- W3C trace context (traceparent) ----------------------------------------- #

_TRACEPARENT_RE = re.compile(
    r"^([0-9a-f]{2})-([0-9a-f]{32})-([0-9a-f]{16})-([0-9a-f]{2})$")


def new_trace_id() -> str:
    """A W3C-shaped 32-hex trace id (uuid4 bytes)."""
    return uuid.uuid4().hex


def span_id_hex(span_id: int) -> str:
    """A span id as the 16-hex W3C parent-id field."""
    return format(span_id & 0xFFFFFFFFFFFFFFFF, "016x")


def parse_traceparent(header: Optional[str]
                      ) -> Optional[Tuple[str, str, bool]]:
    """Parse a W3C ``traceparent`` header into ``(trace_id,
    parent_span_id, sampled)``; None for a missing/malformed header or
    the all-zero ids the spec forbids. Unknown versions are accepted
    per spec (fields we understand are read; ff is invalid)."""
    if not header:
        return None
    m = _TRACEPARENT_RE.match(header.strip().lower())
    if m is None:
        return None
    version, trace_id, parent_id, flags = m.groups()
    if version == "ff" or trace_id == "0" * 32 or parent_id == "0" * 16:
        return None
    return trace_id, parent_id, bool(int(flags, 16) & 0x01)


def format_traceparent(trace_id: str, span_id: Any,
                       sampled: bool = True) -> str:
    """Render a version-00 ``traceparent``. `trace_id` shorter than 32
    hex chars (internal run ids are 12) is left-padded with zeros so
    the header stays spec-shaped; `span_id` may be an int (internal
    span ids) or a 16-hex string."""
    tid = str(trace_id).lower()
    tid = ("0" * 32 + re.sub(r"[^0-9a-f]", "", tid))[-32:]
    sid = span_id_hex(span_id) if isinstance(span_id, int) \
        else str(span_id).lower()
    return f"00-{tid}-{sid}-{'01' if sampled else '00'}"


class Span:
    """One named interval in a trace tree.

    `attributes` are set at open (`tracer.span(name, key=val)`) or later
    via `set()`; `events` are point-in-time markers inside the span
    (recompiles, journal resumes, injected faults). `end()` is
    idempotent; an un-ended span exports with "now" as its end so a
    live process can still dump a coherent trace.
    """

    __slots__ = ("name", "category", "span_id", "parent_id", "trace_id",
                 "start_s", "end_s", "start_at", "attributes", "events",
                 "thread_id", "thread_name", "error")

    def __init__(self, name: str, category: str = "span",
                 parent: Optional["Span"] = None,
                 trace_id: Optional[str] = None,
                 attributes: Optional[Dict[str, Any]] = None):
        self.name = name
        self.category = category
        self.span_id = next(_span_ids)
        self.parent_id = parent.span_id if parent is not None else None
        self.trace_id = trace_id or (
            parent.trace_id if parent is not None else new_run_id())
        self.start_s = time.perf_counter() - _EPOCH_PERF
        self.end_s: Optional[float] = None
        self.start_at = _EPOCH_TIME + self.start_s
        self.attributes: Dict[str, Any] = dict(attributes or {})
        self.events: List[Tuple[str, float, Dict[str, Any]]] = []
        t = threading.current_thread()
        self.thread_id = t.ident or 0
        self.thread_name = t.name
        self.error: Optional[str] = None

    # -- mutation ---------------------------------------------------------- #

    def set(self, **attributes: Any) -> "Span":
        self.attributes.update(attributes)
        return self

    def event(self, name: str, **attributes: Any) -> None:
        """Point-in-time marker inside this span (exported as a Perfetto
        instant event)."""
        self.events.append(
            (name, time.perf_counter() - _EPOCH_PERF, dict(attributes)))

    def end(self) -> None:
        if self.end_s is None:
            self.end_s = time.perf_counter() - _EPOCH_PERF

    # -- views ------------------------------------------------------------- #

    @property
    def duration_s(self) -> float:
        end = self.end_s if self.end_s is not None \
            else time.perf_counter() - _EPOCH_PERF
        return max(0.0, end - self.start_s)

    def to_json(self) -> Dict[str, Any]:
        return {
            "name": self.name, "category": self.category,
            "span_id": self.span_id, "parent_id": self.parent_id,
            "trace_id": self.trace_id,
            "start_at": round(self.start_at, 6),
            "duration_s": round(self.duration_s, 6),
            "thread": self.thread_name,
            "attributes": self.attributes,
            "events": [{"name": n, "offset_s": round(t - self.start_s, 6),
                        **a} for n, t, a in self.events],
            "error": self.error,
        }

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        return (f"Span({self.name!r}, id={self.span_id}, "
                f"parent={self.parent_id}, {self.duration_s:.4f}s)")


def _span_at(name: str, start_s: float, end_s: float, category: str,
             parent: Optional[Span], trace_id: Optional[str] = None,
             error: Optional[str] = None,
             attributes: Optional[Dict[str, Any]] = None) -> Span:
    """A backdated, already finished span from measured boundaries
    (perf offsets from `now_s()`), on the calling thread."""
    sp = Span(name, category=category, parent=parent, trace_id=trace_id,
              attributes=attributes)
    sp.start_s = float(start_s)
    sp.start_at = _EPOCH_TIME + sp.start_s
    sp.end_s = max(float(end_s), sp.start_s)
    sp.error = error
    return sp


class Tracer:
    """Process span collector + contextvar-based current-span tracking.

    One global instance (`TRACER`) serves the whole process; tests that
    need isolation construct their own or call `reset()`.
    """

    def __init__(self, max_spans: int = 65536):
        self._lock = threading.Lock()
        self._finished: deque = deque(maxlen=max_spans)
        self._live: Dict[int, Span] = {}
        self.dropped = 0
        # finished-span sinks (the flight recorder's feed): called once
        # per finished span, outside the tracer lock, exceptions eaten —
        # a broken sink must never take down a scoring thread
        self._sinks: List[Callable[[Span], None]] = []
        # NOTE: a per-Tracer ContextVar would leak on tracer churn;
        # module scope is fine because tests always reset the global.
        self._current: contextvars.ContextVar[Optional[Span]] = \
            contextvars.ContextVar(f"obs_span_{id(self)}", default=None)

    # -- span lifecycle ---------------------------------------------------- #

    @contextlib.contextmanager
    def span(self, name: str, category: str = "span",
             parent: Optional[Span] = None, new_trace: bool = False,
             trace_id: Optional[str] = None,
             **attributes: Any) -> Iterator[Span]:
        """Open a child of `parent` (explicit, for cross-thread nesting)
        or of the calling context's current span. `new_trace=True` roots
        a fresh trace — under `trace_id` when given (the runner passes
        its run correlation id, so the trace, the profile, and the JSONL
        event log all share ONE id), else a fresh one. Exceptions —
        including BaseExceptions like an injected kill — are recorded on
        the span and re-raised."""
        if parent is None and not new_trace:
            parent = self._current.get()
        sp = Span(name, category=category,
                  parent=None if new_trace else parent,
                  trace_id=(trace_id or new_run_id()) if new_trace
                  else trace_id,
                  attributes=attributes)
        with self._lock:
            self._live[sp.span_id] = sp
        token = self._current.set(sp)
        try:
            with _profiler_annotation(name):
                yield sp
        except BaseException as e:
            sp.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            self._current.reset(token)
            sp.end()
            with self._lock:
                self._live.pop(sp.span_id, None)
                if len(self._finished) == self._finished.maxlen:
                    self.dropped += 1
                self._finished.append(sp)
            self._notify(sp)

    def span_at(self, name: str, start_s: float, end_s: float,
                parent: Optional[Span] = None, category: str = "span",
                **attributes: Any) -> Span:
        """Record a backdated, already finished span (boundaries are
        perf offsets from `now_s()`) under `parent`, or under the
        calling context's current span: how a duration that is only
        known once it is over — an XLA compile reported by
        `jax.monitoring` — lands in the timeline where it happened."""
        if parent is None:
            parent = self._current.get()
        sp = _span_at(name, start_s, end_s, category, parent,
                      attributes=attributes)
        self.collect([sp])
        return sp

    def current(self) -> Optional[Span]:
        return self._current.get()

    # -- sinks + out-of-band collection ------------------------------------- #

    def add_sink(self, fn: Callable[[Span], None]) -> None:
        with self._lock:
            if fn not in self._sinks:
                self._sinks.append(fn)

    def remove_sink(self, fn: Callable[[Span], None]) -> None:
        with self._lock:
            if fn in self._sinks:
                self._sinks.remove(fn)

    def _notify(self, sp: Span) -> None:
        for fn in list(self._sinks):
            try:
                fn(sp)
            except Exception:  # a broken sink must not break tracing
                logging.getLogger(__name__).debug(
                    "span sink %r failed", fn, exc_info=True)

    def collect(self, spans: Iterable[Span]) -> None:
        """Admit externally-constructed FINISHED spans into the ring
        (the tail sampler's kept request traces come through here:
        their spans are buffered per request and only land in the
        process timeline once the keep decision is made)."""
        spans = list(spans)
        with self._lock:
            for sp in spans:
                if sp.end_s is None:
                    sp.end()
                if len(self._finished) == self._finished.maxlen:
                    self.dropped += 1
                self._finished.append(sp)
        for sp in spans:
            self._notify(sp)

    # -- collection views --------------------------------------------------- #

    def spans(self) -> List[Span]:
        """Finished spans, oldest first (live spans excluded)."""
        with self._lock:
            return list(self._finished)

    def trace_spans(self, trace_id: str,
                    include_live: bool = True) -> List[Span]:
        """Every span of one trace (one runner invocation), finished and
        — by default — still-open, sorted by start time."""
        with self._lock:
            out = [s for s in self._finished if s.trace_id == trace_id]
            if include_live:
                out += [s for s in self._live.values()
                        if s.trace_id == trace_id]
        return sorted(out, key=lambda s: (s.start_s, s.span_id))

    def reset(self) -> None:
        with self._lock:
            self._finished.clear()
            self._live.clear()
            self.dropped = 0


TRACER = Tracer()


def get_tracer() -> Tracer:
    return TRACER


def current_span() -> Optional[Span]:
    """The calling context's innermost open span on the global tracer."""
    return TRACER.current()


def add_event(name: str, **attributes: Any) -> bool:
    """Attach an instant event to the current span, if any. The no-span
    case is a cheap no-op so library code can emit unconditionally."""
    sp = TRACER.current()
    if sp is None:
        return False
    sp.event(name, **attributes)
    return True


# -- the host-device boundary ------------------------------------------------ #

def _host_nbytes(x: Any) -> int:
    """Bytes of the numpy leaves of `x` (an array or a pytree)."""
    import jax
    import numpy as np
    return sum(int(leaf.nbytes) for leaf in jax.tree_util.tree_leaves(x)
               if isinstance(leaf, np.ndarray))


def pull(site: str, x: Any) -> Any:
    """The host value of `x` (an array or a pytree of arrays): what
    `np.asarray` gave at the site, leaf by leaf, under a span
    `pull:<site>` of category `transfer` that carries `bytes` (of the
    leaves that were device arrays), `wait_s` (until the producer had
    finished: the chip was computing, or uploading what the producer
    needs) and `copy_s` (the read itself: the chip idle unless another
    thread holds it). Blocking first changes nothing: `np.asarray`
    blocked anyway. A value already on the host is returned as it is,
    with no span."""
    import jax
    import numpy as np
    if isinstance(x, np.ndarray):
        return x
    leaves, treedef = jax.tree_util.tree_flatten(x)
    dev = [leaf for leaf in leaves if isinstance(leaf, jax.Array)]
    if not dev:
        return x
    with TRACER.span(f"pull:{site}", category="transfer",
                     bytes=sum(int(leaf.nbytes) for leaf in dev)) as sp:
        t0 = time.perf_counter()
        jax.block_until_ready(dev)
        t1 = time.perf_counter()
        out = treedef.unflatten([np.asarray(leaf) for leaf in leaves])
        sp.set(wait_s=t1 - t0, copy_s=time.perf_counter() - t1)
    return out


@contextlib.contextmanager
def uploading(site: str, operands: Any) -> Iterator[Optional[Span]]:
    """A span `upload:<site>` (category `transfer`) around a `jnp` call
    inside which the numpy leaves of `operands` become device arrays;
    `bytes` counts those leaves. Nothing is blocked on: the span's wall
    is the host's seconds in the call, the bytes are the counter, and
    the transfer's tail shows as the `wait_s` of the next pull. No host
    leaf, no span."""
    n = _host_nbytes(operands)
    if not n:
        yield None
        return
    with TRACER.span(f"upload:{site}", category="transfer", bytes=n) as sp:
        yield sp


def upload(site: str, x: Any, dtype: Any = None) -> Any:
    """`jnp.asarray(x, dtype)` under a span `upload:<site>` (see
    `uploading`); a device array passes through with no span."""
    import jax.numpy as jnp
    with uploading(site, x):
        return jnp.asarray(x, dtype)


def train_passes(tracer: Optional[Tracer] = None) -> List[Dict[str, Any]]:
    """The ring's finished spans by training pass: one ``{"root": Span,
    "spans": [Span, ...]}`` for every finished `workflow:train` span,
    oldest pass first, `spans` being everything that descends from the
    root (walking `parent_id`; the family threads' spans have explicit
    parents), in the order they were opened. A span under no
    `workflow:train` is left out. This is a reader's way to a span's
    attributes (`bytes`, `wait_s`, whatever a layer sets): the names and
    seconds alone never carried them."""
    # by span id: the order the spans were opened in (the ring holds
    # them in the order they finished, children before parents)
    spans = sorted((tracer or TRACER).spans(), key=lambda sp: sp.span_id)
    by_id = {sp.span_id: sp for sp in spans}
    roots: Dict[int, Optional[Span]] = {}

    def root_of(sp: Span) -> Optional[Span]:
        if sp.span_id not in roots:
            parent = by_id.get(sp.parent_id)  # type: ignore[arg-type]
            roots[sp.span_id] = (
                sp if sp.name == "workflow:train"
                else root_of(parent) if parent is not None else None)
        return roots[sp.span_id]

    passes: Dict[int, Dict[str, Any]] = {}
    for sp in spans:
        root = root_of(sp)
        if root is sp:
            passes[sp.span_id] = {"root": sp, "spans": []}
        elif root is not None:
            passes[root.span_id]["spans"].append(sp)
    return list(passes.values())


def ambient_traceparent() -> Optional[str]:
    """The calling context's current span as a W3C ``traceparent``
    header value, or None with no span open — how out-of-band state
    (pod lease claims, published records) stamps the trace it belongs
    to without threading a span handle through every signature."""
    sp = TRACER.current()
    if sp is None:
        return None
    return format_traceparent(sp.trace_id, sp.span_id, sampled=True)


# -- request-scoped tracing --------------------------------------------------- #

@dataclass
class TraceContext:
    """Incoming trace context for one request: a W3C wire context
    (`trace_id` + `parent_hex`, from a ``traceparent`` header) or an
    in-process parent span (the continual loop parents its live
    holdout requests under the cycle span). ``sampled`` carries the
    caller's sampling decision: a sampled=01 wire context (or any
    in-process parent) is force-kept past tail sampling, so a
    distributed trace never loses its serving leg."""

    trace_id: Optional[str] = None
    parent_hex: Optional[str] = None
    parent: Optional[Span] = None
    sampled: bool = False

    @staticmethod
    def from_traceparent(header: Optional[str]) -> Optional["TraceContext"]:
        parsed = parse_traceparent(header)
        if parsed is None:
            return None
        trace_id, parent_hex, sampled = parsed
        return TraceContext(trace_id=trace_id, parent_hex=parent_hex,
                            sampled=sampled)

    @staticmethod
    def from_span(sp: Optional[Span]) -> Optional["TraceContext"]:
        if sp is None:
            return None
        return TraceContext(trace_id=sp.trace_id, parent=sp, sampled=True)


class RequestTrace:
    """One request's span buffer: a root ``serving:request`` span plus
    phase children, held OUT of the process ring until the tail sampler
    decides to keep it (`Tracer.collect`). Children may be opened live
    (`child(...)` context manager, caller thread) or BACKDATED from
    measured phase boundaries (`child_at(...)`, the scoring thread's
    per-batch timestamps replicated onto every member request)."""

    __slots__ = ("root", "spans", "forced", "enqueued_s", "_done")

    def __init__(self, name: str = "serving:request",
                 ctx: Optional[TraceContext] = None,
                 **attributes: Any):
        ctx = ctx or TraceContext()
        self.root = Span(name, category="serving",
                         parent=ctx.parent,
                         trace_id=ctx.trace_id or new_trace_id(),
                         attributes=attributes)
        if ctx.parent is None and ctx.parent_hex:
            # wire-context parent: not an in-process span, carried as an
            # attribute so the exported trace still links to the caller
            self.root.attributes["parent_traceparent"] = ctx.parent_hex
        self.forced = ctx.sampled or ctx.parent is not None
        self.spans: List[Span] = [self.root]
        self.enqueued_s: Optional[float] = None
        self._done = False

    @property
    def trace_id(self) -> str:
        return self.root.trace_id

    def traceparent(self, sampled: bool = True) -> str:
        """The response-header echo: same trace id, the request root as
        the span id."""
        return format_traceparent(self.root.trace_id, self.root.span_id,
                                  sampled=sampled)

    @contextlib.contextmanager
    def child(self, name: str, parent: Optional[Span] = None,
              **attributes: Any) -> Iterator[Span]:
        sp = Span(name, category="serving", parent=parent or self.root,
                  trace_id=self.root.trace_id, attributes=attributes)
        self.spans.append(sp)
        try:
            yield sp
        except BaseException as e:
            sp.error = f"{type(e).__name__}: {e}"
            raise
        finally:
            sp.end()

    def child_at(self, name: str, start_s: float, end_s: float,
                 error: Optional[str] = None, **attributes: Any) -> Span:
        """Backdated phase child from measured boundaries (perf offsets
        from `now_s()`): how the scoring thread attributes one batch's
        pad/dispatch/demux wall to every request it carried."""
        sp = _span_at(name, start_s, end_s, "serving", self.root,
                      trace_id=self.root.trace_id, error=error,
                      attributes=attributes)
        self.spans.append(sp)
        return sp

    def phase_durations(self) -> Dict[str, float]:
        """phase suffix -> seconds, for the ``serving_phase_seconds``
        histograms (span names are ``serving:<phase>``)."""
        out: Dict[str, float] = {}
        for sp in self.spans[1:]:
            phase = sp.name.rsplit(":", 1)[-1]
            out[phase] = out.get(phase, 0.0) + sp.duration_s
        return out

    def finish(self, error: Optional[str] = None) -> Span:
        """Idempotently end the root (phase children were ended by
        their own scopes)."""
        if not self._done:
            self._done = True
            if error:
                self.root.error = error
            self.root.end()
        return self.root


@dataclass
class TracingParams:
    """Knobs for request-scoped tracing + tail sampling (JSON-loadable
    via ``ServingConfig.tracing`` / ``ServingParams.tracing``). On by
    default: the per-request cost is a handful of Span objects and
    clock reads, and the tail sampler keeps the ring bounded at fleet
    QPS."""

    enabled: bool = True
    # tail sampling: always keep error/deadline/shed/fallback traces
    # and anything at or above the rolling `slow_quantile` of request
    # latency; head-sample 1-in-`head_sample_every` of the rest
    slow_quantile: float = 0.95
    head_sample_every: int = 64
    # latency-quantile estimator: rolling sample buffer + the floor of
    # observations before "slow" judgments start (cold = head sampling
    # only, so a warmup burst can't define "slow" forever)
    latency_window: int = 2048
    min_latency_samples: int = 64

    _FIELDS = ("enabled", "slow_quantile", "head_sample_every",
               "latency_window", "min_latency_samples")

    def __post_init__(self):
        if not (0.0 < self.slow_quantile < 1.0):
            raise ValueError(
                f"slow_quantile must be in (0,1): {self.slow_quantile}")
        if self.head_sample_every < 1 or self.latency_window < 1 \
                or self.min_latency_samples < 1:
            raise ValueError("tracing windows/rates must be >= 1")

    @staticmethod
    def from_json(d: Optional[Dict[str, Any]]) -> "TracingParams":
        d = d or {}
        return TracingParams(**{k: d[k] for k in TracingParams._FIELDS
                                if k in d})

    def to_json(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self._FIELDS}


class TailSampler:
    """Tail-based sampling decision for finished request traces.

    Head sampling decides at request START and throws the interesting
    traces away with the boring ones; tail sampling decides at the END,
    when the outcome is known: errors, sheds, deadline misses, degraded
    fallbacks and force-sampled contexts are ALWAYS kept, the slowest
    `slow_quantile` tail of latencies is kept, and a deterministic
    1-in-N head sample of the healthy fast majority survives as the
    baseline. Everything else is dropped BEFORE it reaches the process
    span ring, which is what makes always-on tracing affordable at
    fleet QPS. Thread-safe; counters land in the service registry as
    ``serving_trace_kept_total{reason=...}`` /
    ``serving_trace_dropped_total``."""

    def __init__(self, params: Optional[TracingParams] = None,
                 registry=None):
        self.params = params or TracingParams()
        self.registry = registry
        self._lock = threading.Lock()
        self._latencies: deque = deque(maxlen=self.params.latency_window)
        self._seen = 0
        self.kept = 0
        self.dropped = 0

    def _threshold(self) -> Optional[float]:
        vals = sorted(self._latencies)
        if len(vals) < self.params.min_latency_samples:
            return None
        return vals[min(len(vals) - 1,
                        int(self.params.slow_quantile * len(vals)))]

    def decide(self, latency_s: float, error: bool = False,
               forced: bool = False) -> Tuple[bool, str]:
        """(keep, reason) for one finished request. `error` covers every
        non-success outcome (scoring error, shed, deadline, fallback);
        `forced` is a caller-sampled wire context or in-process parent."""
        with self._lock:
            self._latencies.append(float(latency_s))
            self._seen += 1
            if error:
                keep, reason = True, "error"
            elif forced:
                keep, reason = True, "forced"
            else:
                thr = self._threshold()
                if thr is not None and latency_s >= thr:
                    keep, reason = True, "slow"
                elif self._seen % self.params.head_sample_every == 1 \
                        or self.params.head_sample_every == 1:
                    keep, reason = True, "head"
                else:
                    keep, reason = False, "dropped"
            if keep:
                self.kept += 1
            else:
                self.dropped += 1
        if self.registry is not None:
            if keep:
                self.registry.counter(
                    "serving_trace_kept_total",
                    "request traces kept by the tail sampler",
                    reason=reason).inc()
            else:
                self.registry.counter(
                    "serving_trace_dropped_total",
                    "request traces dropped by the tail sampler").inc()
        return keep, reason

    def observe(self, rt: RequestTrace, latency_s: float,
                error: bool = False,
                tracer: Optional[Tracer] = None) -> bool:
        """Finish-side entry point: decide, and on keep admit the
        request's span buffer into the process ring."""
        keep, reason = self.decide(latency_s, error=error,
                                   forced=rt.forced)
        if keep:
            rt.root.set(sampled=reason)
            (tracer or TRACER).collect(rt.spans)
        return keep
