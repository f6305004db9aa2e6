"""Bounded-depth host→device chunk pipeline for bulk ingest.

Every out-of-core upload in this codebase used to be a serial loop:
memmap read → host dtype cast → donated `dynamic_update_slice`, one
chunk at a time, with the host idle during each transfer and the device
idle during each read/cast — the textbook input-bound pattern tf.data
solves with pipelined prefetch.

This module is the reusable fix: `run_chunk_pipeline` drives any
host→device bulk transfer as a two-stage pipeline,

- stage 1 (thread pool, `workers`): ``prepare(item)`` reads the chunk
  and casts it to the wire dtype — numpy memmap reads and dtype casts
  release the GIL, so workers genuinely overlap;
- stage 2 (main thread, `depth` in flight): ``upload(prepared)``
  dispatches the donated device write and returns a completion TOKEN (a
  tiny device array that depends on the write). JAX async dispatch
  keeps up to `depth` writes in flight; the pipeline blocks on the
  oldest token once the bound is exceeded, which is also what makes the
  per-chunk deadline check track REAL transfer progress instead of
  enqueue time (the r5 loops could never fire their deadline because
  every write enqueued instantly).

All tokens are drained before returning, so the caller's buffer is
ready (`block_until_ready` semantics are built in) and the recorded
wall time is honest transfer time, not dispatch time.

Per-stage timers land in `IngestStats` (read/cast seconds summed over
workers, main-thread device-wait seconds, wall clock, bytes, max
in-flight depth) with derived `overlap_frac` (fraction of host prep
hidden behind transfers) and `gbps` (wire bytes / wall). Stats attach
to a `RunProfile` via `RunProfile.record_ingest`.

Smoke: ``python -m transmogrifai_tpu.data.pipeline`` runs a small
synthetic store through the pipelined dual-representation build and
asserts the overlap metrics are emitted (wired into `make check`).
"""

from __future__ import annotations

import threading
import time
from collections import deque
from concurrent.futures import ThreadPoolExecutor
from dataclasses import dataclass, field
from typing import Any, Callable, Dict, Iterable, Optional

from transmogrifai_tpu.obs.metrics import get_registry
from transmogrifai_tpu.obs.trace import TRACER
from transmogrifai_tpu.runtime.faults import SITE_READ_CHUNK, fault_point

__all__ = ["IngestStats", "run_chunk_pipeline"]


@dataclass
class IngestStats:
    """Per-stage timers for one pipelined ingest.

    `read_s`/`cast_s` sum across worker threads; `upload_wait_s` is
    main-thread time blocked on device completion tokens (depth
    backpressure + final drain); `wall_s` covers the whole pipeline
    including the drain, so the buffer is ready when it is recorded.
    """

    label: str = "ingest"
    workers: int = 0
    depth: int = 0
    chunks: int = 0
    bytes_read: int = 0
    bytes_wire: int = 0
    read_s: float = 0.0
    cast_s: float = 0.0
    dispatch_s: float = 0.0
    upload_wait_s: float = 0.0
    wall_s: float = 0.0
    max_in_flight: int = 0
    retries: int = 0          # transient prepare failures retried
    retry_wait_s: float = 0.0  # backoff slept across all retries
    # feature-cache accounting (data/feature_cache.py): `read_s` /
    # `bytes_read` always mean STORE memmap reads, so a warm cache hit
    # shows 0 there and its artifact IO lands in `cache_read_s` /
    # `cache_bytes` instead — the warm-path proof tests assert exactly
    # that split
    # learned-cost-model plan accounting (perf/): when the upload shape
    # (workers/depth) was model-chosen, the predicted wall rides along
    # so the pipeline can score predicted-vs-measured at drain time
    plan: str = ""             # "" (heuristic/explicit) or "model"
    predicted_wall_s: float = 0.0
    wire: str = ""             # wire mode label (f16/int8/int4/...)
    cache: str = ""            # "", "off", "miss", "hit", "resident"
    cache_key: str = ""        # content address of this build
    cache_read_s: float = 0.0  # artifact (warm) read seconds
    cache_bytes: int = 0       # artifact bytes read on a hit
    cache_write_s: float = 0.0  # artifact tee seconds on a readwrite miss
    bytes_saved_wire: int = 0  # f16-equivalent bytes NOT shipped (quant)
    _lock: threading.Lock = field(default_factory=threading.Lock,
                                  repr=False, compare=False)

    # worker-side accounting (thread-safe) ------------------------------ #

    def note_read(self, seconds: float, nbytes: int) -> None:
        with self._lock:
            self.read_s += seconds
            self.bytes_read += nbytes

    def note_cast(self, seconds: float, wire_nbytes: int) -> None:
        with self._lock:
            self.cast_s += seconds
            self.bytes_wire += wire_nbytes
            self.chunks += 1

    def note_retry(self, delay_s: float) -> None:
        with self._lock:
            self.retries += 1
            self.retry_wait_s += delay_s

    def note_cache_read(self, seconds: float, nbytes: int) -> None:
        with self._lock:
            self.cache_read_s += seconds
            self.cache_bytes += nbytes

    # derived ----------------------------------------------------------- #

    @property
    def cache_hit(self) -> bool:
        """This build replayed a cached artifact (disk or resident)
        instead of sweeping the store."""
        return self.cache in ("hit", "resident")

    @property
    def host_s(self) -> float:
        # cache_read_s counts: warm replays do their (artifact) IO on
        # the same worker threads, so overlap_frac stays meaningful
        return self.read_s + self.cast_s + self.cache_read_s

    @property
    def overlap_frac(self) -> float:
        """Fraction of host prep time hidden behind the device side
        (dispatch incl. first-call compile + transfer waits, or other
        workers): 0 = fully serial (wall = host + dispatch + wait),
        1 = host work fully overlapped (wall ≈ dispatch + wait).
        Counting `dispatch_s` matters: on a compile-dominated first run
        the workers prefetch behind the jit trace, and a formula that
        ignored main-thread dispatch time reported that real overlap
        as 0."""
        if self.host_s <= 0.0:
            return 0.0
        hidden = (self.host_s + self.dispatch_s + self.upload_wait_s
                  - self.wall_s)
        return max(0.0, min(1.0, hidden / self.host_s))

    @property
    def gbps(self) -> float:
        """Wire GB/s over the full pipeline wall clock."""
        if self.wall_s <= 0.0:
            return 0.0
        return self.bytes_wire / self.wall_s / 1e9

    def to_extra(self) -> Dict[str, Any]:
        """Phase-extra dict for `RunProfile` / bench payloads."""
        return {
            "chunks": self.chunks,
            "bytes_wire": self.bytes_wire,
            "read_s": round(self.read_s, 4),
            "cast_s": round(self.cast_s, 4),
            "dispatch_s": round(self.dispatch_s, 4),
            "upload_wait_s": round(self.upload_wait_s, 4),
            "wall_s": round(self.wall_s, 4),
            "overlap_frac": round(self.overlap_frac, 4),
            "gbps": round(self.gbps, 4),
            "workers": self.workers,
            "depth": self.depth,
            "max_in_flight": self.max_in_flight,
            "retries": self.retries,
            "retry_wait_s": round(self.retry_wait_s, 4),
            **({"wire": self.wire} if self.wire else {}),
            **({"plan": self.plan,
                "predicted_wall_s": round(self.predicted_wall_s, 4),
                } if self.plan else {}),
            **({"cache": self.cache,
                "cache_key": self.cache_key,
                "cache_read_s": round(self.cache_read_s, 4),
                "cache_bytes": self.cache_bytes,
                "cache_write_s": round(self.cache_write_s, 4),
                "bytes_saved_wire": self.bytes_saved_wire,
                } if self.cache else {}),
        }


def run_chunk_pipeline(items: Iterable[Any],
                       prepare: Callable[[Any], Any],
                       upload: Callable[[Any], Any],
                       *, workers: int = 2, depth: int = 2,
                       deadline_s: Optional[float] = None,
                       label: str = "ingest",
                       stats: Optional[IngestStats] = None,
                       retry: Optional[Any] = None) -> IngestStats:
    """Drive `items` through prepare (worker threads) → upload (main
    thread, bounded async depth). Returns the filled `IngestStats`.

    `prepare(item)` runs on the pool and should call
    `stats.note_read`/`stats.note_cast` around its IO/cast phases.
    `upload(prepared)` runs on the caller thread in ITEM ORDER (donated
    carries stay race-free) and returns a completion token — any jax
    array whose readiness implies the write finished — or None to skip
    depth accounting for that item.

    `retry`: optional `runtime.retry.RetryPolicy` — each chunk's prepare
    is retried under it on TRANSIENT failures (IO errors classified by
    the policy), with attempts and backoff recorded in
    `IngestStats.retries`/`retry_wait_s`. prepare is a pure read+cast,
    so a retried chunk produces byte-identical output and the pipeline
    result is bitwise-equal to a fault-free run. Fatal errors, and
    transient ones past the budget, propagate on the failing item's
    turn (futures re-raise in submission order); nothing hangs.

    `deadline_s` is checked against real elapsed time before each
    upload; because the depth bound back-pressures dispatch, elapsed
    tracks actual transfer progress to within `depth` chunks — the
    serial loops this replaces measured enqueue time and could never
    fire mid-transfer. The deadline is NOT re-checked after the final
    drain: a finished buffer is returned, not discarded. (Deadline
    expiry is deliberately OUTSIDE the retry policy: a blown time
    budget is not transient.)
    """
    st = stats if stats is not None else IngestStats(label=label)
    st.workers = workers
    st.depth = depth

    def prepare_once(item):
        fault_point(SITE_READ_CHUNK)
        return prepare(item)

    if retry is None:
        prepare_task = prepare_once
    else:
        prepare_task = retry.wrap(
            prepare_once, label=f"{label}.read_chunk",
            on_attempt=lambda ev: st.note_retry(ev.delay_s))

    # worker threads do not inherit the caller's span context: each
    # chunk prepare opens its own span EXPLICITLY parented under the
    # pipeline's ingest span, so worker rows nest in the run timeline
    # (and any retry backoff spans opened inside nest under the chunk)
    with TRACER.span(f"ingest:{label}", category="ingest",
                     workers=workers, depth=depth) as ingest_span:
        def worker_task(item):
            with TRACER.span("ingest:chunk", category="ingest_chunk",
                             parent=ingest_span):
                return prepare_task(item)

        t_start = time.perf_counter()
        it = iter(items)
        pending: deque = deque()      # prepare futures, submission order
        in_flight: deque = deque()    # upload completion tokens
        lookahead = max(1, workers) + max(1, depth)

        def elapsed() -> float:
            return time.perf_counter() - t_start

        pool = ThreadPoolExecutor(max_workers=max(1, workers))
        try:
            def fill() -> None:
                while len(pending) < lookahead:
                    try:
                        item = next(it)
                    except StopIteration:
                        return
                    pending.append(pool.submit(worker_task, item))

            fill()
            i = 0
            while pending:
                prepared = pending.popleft().result()  # re-raises worker errors
                fill()
                if deadline_s is not None and elapsed() > deadline_s:
                    raise TimeoutError(
                        f"{label} past {deadline_s:.0f}s deadline at chunk "
                        f"{i} ({elapsed():.1f}s elapsed)")
                t0 = time.perf_counter()
                token = upload(prepared)
                st.dispatch_s += time.perf_counter() - t0
                i += 1
                if token is not None:
                    in_flight.append(token)
                    while len(in_flight) > max(1, depth):
                        t0 = time.perf_counter()
                        _block(in_flight.popleft())
                        st.upload_wait_s += time.perf_counter() - t0
                    st.max_in_flight = max(st.max_in_flight, len(in_flight))
            # drain: the last token's readiness implies the final write
            # landed, so the recorded wall time is true transfer time and
            # the caller's buffer needs no separate block_until_ready
            while in_flight:
                t0 = time.perf_counter()
                _block(in_flight.popleft())
                st.upload_wait_s += time.perf_counter() - t0
        except BaseException:
            # a deadline/worker error must surface NOW: without
            # cancel_futures the pool shutdown would sit through up to
            # `lookahead` queued multi-hundred-MB reads — eating exactly the
            # budget reserve the deadline protects
            pool.shutdown(wait=False, cancel_futures=True)
            raise
        finally:
            pool.shutdown(wait=True)
            st.wall_s = elapsed()
            # the span carries the stats the goodput rollup reads
            # (upload_wait_s → ingest-wait badput) and the process-wide
            # registry gets the cumulative ingest counters the serving
            # /metrics surface exposes
            ingest_span.set(**st.to_extra())
            reg = get_registry()
            reg.counter("ingest_chunks_total",
                        "chunks driven through run_chunk_pipeline"
                        ).inc(st.chunks)
            reg.counter("ingest_bytes_wire_total",
                        "bytes shipped host->device by pipelined ingest"
                        ).inc(st.bytes_wire)
            reg.counter("ingest_upload_wait_seconds_total",
                        "main-thread seconds blocked on device tokens"
                        ).inc(st.upload_wait_s)
            if st.retries:
                reg.counter("ingest_retries_total",
                            "transient chunk-read retries"
                            ).inc(st.retries)
            if st.chunks > 0 and st.wall_s > 0:
                # cost-model corpus row for this upload (+ residual when
                # the plan was model-predicted); recording never raises
                try:
                    from transmogrifai_tpu import perf
                    # the upload plan was predicted BEFORE the cache
                    # decision, for a cold store read — scoring it
                    # against a cache-hit replay (10x faster, different
                    # bytes) would pollute the residual histogram with
                    # a feature mismatch, so hits record the training
                    # row but skip the residual
                    predicted = ((st.predicted_wall_s or None)
                                 if not st.cache_hit else None)
                    perf.note(
                        "ingest",
                        perf.ingest_features(st.bytes_wire, st.workers,
                                             st.depth, st.chunks,
                                             st.cache_hit),
                        predicted, st.wall_s)
                except Exception:
                    import logging as _logging
                    _logging.getLogger(__name__).debug(
                        "perf ingest recording failed", exc_info=True)
    return st


def _block(token: Any) -> None:
    if hasattr(token, "block_until_ready"):
        token.block_until_ready()


# -- smoke (make ingest-smoke) ---------------------------------------------- #

def _smoke() -> int:
    """Small synthetic ColumnarStore through the pipelined one-pass
    dual-representation build; asserts results match the serial
    reference and that overlap metrics are emitted."""
    import json
    import tempfile

    import jax.numpy as jnp
    import numpy as np

    from transmogrifai_tpu.data.columnar_store import synth_binary_store
    from transmogrifai_tpu.models.trees import bin_features
    from transmogrifai_tpu.parallel import bigdata as bd
    from transmogrifai_tpu.utils.profiling import RunProfile

    with tempfile.TemporaryDirectory(prefix="ingest-smoke-") as tmp:
        store = synth_binary_store(f"{tmp}/store", 20_000, 16, seed=5,
                                   chunk_rows=4096)
        edges = store.quantile_edges(16, sample=8000)
        prof = RunProfile(run_type="ingest-smoke")
        X16, Xb, stats = bd.dual_device_matrices(
            store, edges, chunk_rows=4096, workers=2, depth=2,
            profile=prof, return_stats=True)
        n = store.n_rows
        ref = np.asarray(store.chunk(0, n))
        want16 = np.asarray(jnp.asarray(ref, jnp.bfloat16))
        got16 = np.asarray(X16[:n])
        assert got16.tobytes() == want16.tobytes(), "bf16 matrix mismatch"
        wantb = np.asarray(bin_features(
            jnp.asarray(ref, jnp.float32), jnp.asarray(edges))
            .astype(jnp.int8))
        np.testing.assert_array_equal(np.asarray(Xb[:n]), wantb)
        assert stats.chunks == -(-n // 4096)
        assert stats.wall_s > 0 and stats.gbps > 0
        assert 0.0 <= stats.overlap_frac <= 1.0
        ingest_phases = [p for p in prof.phases
                         if "overlap_frac" in p.extra]
        assert ingest_phases, "RunProfile missing ingest phase"
        print(json.dumps({"ingest_smoke": "ok", **stats.to_extra()}))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(_smoke())
