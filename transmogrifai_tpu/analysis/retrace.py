"""Runtime retracing detector: count jit cache misses per labeled program.

Silent retracing is the canonical JAX perf bug: a jitted function whose
inputs change shape/dtype/static-arg value per call re-traces and
re-compiles every time, turning a microseconds dispatch into seconds of
XLA work — invisible except as mysterious slowness (per the TPU
performance-model line of work, graph-level analysis BEFORE compilation
is where TPU stacks win or lose; this is the dynamic complement to
`analysis/opcheck.py`'s static pass).

The trick: `jax.jit(f)` executes `f`'s *Python body* exactly once per
trace (cache miss). Wrapping the body with a counter therefore counts
traces, not calls:

    fn = instrumented_jit(seg_fn, label="compiled:segment0[OpLogReg]")
    fn(x)   # trace #1 (compile)
    fn(x)   # cached — no count
    fn(y)   # new shape -> trace #2

`workflow/compiled.py` labels each fused segment with its stage names and
`parallel/sweep.py` labels each sweep program with its family + static
group, so `MONITOR.counts()` attributes recompile churn to a specific
stage/program. When one label exceeds `warn_after` traces a warning is
logged once, naming the label — the usual culprits are per-batch shape
drift (pad batches to stable shapes) and unstable static args.
"""

from __future__ import annotations

import functools
import itertools
import logging
import threading
from typing import Any, Callable, Dict, Optional

log = logging.getLogger(__name__)

# monotonically unique wrapper ids (id(object()) would be reused after GC,
# silently merging two programs' per-instance trace counts)
_instance_ids = itertools.count(1)


class RetraceMonitor:
    """Process-wide trace accounting keyed by program label.

    `counts()` aggregates across every wrapper instance sharing a label
    (useful inventory of what compiled), but CHURN is judged per wrapper
    INSTANCE: seven workflows each compiling their own 'compiled:seg0[...]'
    once is seven healthy one-trace programs, not churn — only a single
    jitted program re-tracing past `warn_after` (per-call shape drift,
    unstable statics) trips the warning."""

    def __init__(self, warn_after: int = 6):
        self._lock = threading.Lock()
        self._counts: Dict[str, int] = {}
        self._instance: Dict[tuple, int] = {}  # (label, instance) -> traces
        self.warn_after = warn_after

    def record(self, label: str, instance: Optional[int] = None) -> int:
        with self._lock:
            n = self._counts.get(label, 0) + 1
            self._counts[label] = n
            key = (label, instance)
            n_inst = self._instance.get(key, 0) + 1
            self._instance[key] = n_inst
        if n_inst == self.warn_after + 1:
            log.warning(
                "retrace churn: %r traced %d times — each trace is a fresh "
                "XLA compile; check for per-call shape drift or unstable "
                "static args (pad batches to a fixed shape)", label, n_inst)
        return n

    def counts(self) -> Dict[str, int]:
        with self._lock:
            return dict(self._counts)

    def count(self, label: str) -> int:
        with self._lock:
            return self._counts.get(label, 0)

    def total(self) -> int:
        with self._lock:
            return sum(self._counts.values())

    def churning(self) -> Dict[str, int]:
        """label -> worst per-instance trace count, for labels where any
        single program instance re-traced past the warn threshold."""
        with self._lock:
            out: Dict[str, int] = {}
            for (label, _), n in self._instance.items():
                if n > self.warn_after:
                    out[label] = max(out.get(label, 0), n)
            return out

    def snapshot(self) -> Dict[str, int]:
        """Point-in-time copy of per-label trace counts, for differential
        accounting around a scoped operation (e.g. the serving warmup
        attributes compiles to each shape bucket by diffing snapshots)."""
        return self.counts()

    def delta(self, before: Dict[str, int]) -> Dict[str, int]:
        """Traces recorded since `before` (a `snapshot()`), per label —
        labels with no new traces are omitted, so an empty dict means the
        jit cache fully absorbed the interval (zero recompiles)."""
        now = self.counts()
        out = {label: n - before.get(label, 0) for label, n in now.items()
               if n - before.get(label, 0) > 0}
        return out

    def reset(self) -> None:
        with self._lock:
            self._counts.clear()
            self._instance.clear()

    def report(self) -> str:
        counts = self.counts()
        if not counts:
            return "retrace: no instrumented programs traced"
        churn = self.churning()
        lines = ["retrace: traces per program (1 = compiled once, ideal)"]
        for label, n in sorted(counts.items(), key=lambda kv: -kv[1]):
            flag = "  <-- CHURN" if label in churn else ""
            lines.append(f"  {n:4d}  {label}{flag}")
        return "\n".join(lines)


MONITOR = RetraceMonitor()

# Device-dispatch accounting, same snapshot()/delta() contract as the
# trace monitor but counting EXECUTIONS of compiled scoring segments
# (CompiledScorer._dispatch), not traces: `DISPATCHES.delta(before)`
# around one score call proves how many XLA programs it launched — the
# fused-plan invariant ("exactly ONE device dispatch per bucket per
# score call") that `make roofline-smoke` and tests assert. warn_after
# is effectively disabled: thousands of dispatches of one program are
# the healthy steady state, not churn.
DISPATCHES = RetraceMonitor(warn_after=1 << 62)


def instrumented_jit(fn: Callable, label: Optional[str] = None,
                     monitor: Optional[RetraceMonitor] = None,
                     **jit_kwargs: Any) -> Callable:
    """`jax.jit(fn, **jit_kwargs)` with trace counting under `label`.

    Drop-in for the jit entry points in workflow/compiled.py and
    parallel/sweep.py; `jit_kwargs` pass through (static_argnames, ...).
    """
    import jax

    mon = monitor or MONITOR
    lbl = label or getattr(fn, "__qualname__", repr(fn))
    inst = next(_instance_ids)  # churn is judged per wrapper, not per label

    @functools.wraps(fn)
    def traced(*args: Any, **kwargs: Any) -> Any:
        # this body runs ONLY on a jit cache miss, so everything here is
        # recompile accounting: count the trace, time the body
        # re-execution, and drop a `recompile` event on the current obs
        # span so the unified timeline and the goodput report both see
        # where compile churn happened (the XLA compile that follows is
        # a `compile:*` span of its own: utils/compile_cache.py)
        import time as _time

        from transmogrifai_tpu.obs import trace as _obs_trace
        from transmogrifai_tpu.obs.metrics import get_registry

        n = mon.record(lbl, inst)
        # process-wide series on the `/metrics` scrape surface: a serving
        # process whose count moves after ladder warmup is recompiling
        get_registry().counter(
            "runtime_jit_traces_total",
            "instrumented-jit traces (each one is an XLA compile)").inc()
        t0 = _time.perf_counter()
        try:
            return fn(*args, **kwargs)
        finally:
            dt = _time.perf_counter() - t0
            _obs_trace.add_event("recompile", label=lbl, n=n,
                                 trace_s=round(dt, 6))

    return jax.jit(traced, **jit_kwargs)
