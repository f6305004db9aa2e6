"""Out-of-core model fitting: device-resident compressed matrices fed by
row-chunk streaming from a `ColumnarStore`.

Reference parity: BASELINE target 4 (10M×500 CV sweep) — the workload the
reference runs as a Spark cluster job (`OpValidator.scala:299-358`
dispatching fits over executors). One TPU chip can't hold 10M×500 f32
(20 GB) plus working set, so this module:

- streams the memmapped store to the device ONCE per representation,
  through donated `dynamic_update_slice` writes into a persistent HBM
  buffer (no 2× copies);
- keeps TWO device representations, built per model family and freed
  after: bf16 (10 GB at 10M×500) for linear-family fits/scoring, and
  int8 quantile-binned (5 GB) for every tree family;
- grows trees with CHUNKED histogram matmuls: the (n, d·bins) bin
  one-hot — 320 GB at 10M×500×32, impossible to materialize — is built
  per row-chunk inside a `lax.scan` and contracted immediately, with the
  per-chunk A-side stacking ALL histogram values ([G·, H]) so each chunk
  is read once; gain/split selection reuses the in-core logic
  (`models/trees.py:split_from_histograms`).
- leaf sums use the same chunked matmul (TPU scatter-add serializes at
  10M rows);
- feeds every upload through the persistent content-addressed feature
  cache (`data/feature_cache.py`, ``cache=`` on the builders): repeat
  sweeps / resumed runs / serving warmups replay the wire tape from a
  verified artifact with ZERO store reads (bit-identical buffers), and
  cold misses can ship an int8/int4 quantized wire with dequant fused
  into the donated write (2–4× fewer bytes than f16).

Memory plan at 10M×500×32 bins (v5e 16 GB HBM):
    linear family : X bf16 10 GB + y/masks/logits ≈ 0.2 GB     → 10.2 GB
    tree families : Xb int8 5 GB + per-chunk one-hots ≈ 2.2 GB → 7.2 GB
    (families run sequentially; buffers freed between families)
"""

from __future__ import annotations

import logging
import os
import time
from functools import partial
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax

from transmogrifai_tpu.data import feature_cache as fc
from transmogrifai_tpu.data.columnar_store import ColumnarStore
from transmogrifai_tpu.data.pipeline import IngestStats, run_chunk_pipeline
from transmogrifai_tpu.models.trees import split_from_histograms
from transmogrifai_tpu.obs.export import record_event

log = logging.getLogger(__name__)

UPLOAD_CHUNK_ROWS = 262_144   # ~256 MB f16 per upload dispatch at d=500
HIST_CHUNK_ROWS = 65_536      # bounds per-chunk one-hot to ~2 GB at d=500
UPLOAD_WORKERS = 2            # memmap read + cast threads (GIL-releasing)
UPLOAD_DEPTH = 4              # donated writes in flight (hides upload latency)


def _pad_rows(n: int, chunk: int) -> int:
    return -(-n // chunk) * chunk


@partial(jax.jit, donate_argnums=(0,))
def _write_cast_rows(buf, chunk, r0):
    """Donated row write; widens/narrows the wire chunk to the buffer
    dtype ON DEVICE (fused into the update), so the host ships the
    narrowest representation."""
    return jax.lax.dynamic_update_slice(
        buf, chunk.astype(buf.dtype), (r0, 0))


@partial(jax.jit, donate_argnums=(0,))
def _bin_write_rows(buf, chunk_f16, edges, r0):
    from transmogrifai_tpu.models.trees import bin_features
    binned = bin_features(chunk_f16.astype(jnp.float32), edges) \
        .astype(jnp.int8)
    return jax.lax.dynamic_update_slice(buf, binned, (r0, 0))


@partial(jax.jit, donate_argnums=(0, 1))
def _dual_write_rows(buf16, bufb, chunk_f16, edges, r0):
    """ONE wire chunk → BOTH device representations: widen to the
    linear-family dtype and quantile-bin to int8, each fused into its
    donated row write. The store is read once and the bytes cross the
    host→device link once."""
    from transmogrifai_tpu.models.trees import bin_features
    binned = bin_features(chunk_f16.astype(jnp.float32), edges) \
        .astype(jnp.int8)
    return (jax.lax.dynamic_update_slice(
                buf16, chunk_f16.astype(buf16.dtype), (r0, 0)),
            jax.lax.dynamic_update_slice(bufb, binned, (r0, 0)))


@jax.jit
def _probe(buf):
    """Tiny array depending on `buf`: its readiness is the completion
    token for the write that produced `buf` — blocking on it instead of
    the (donated, multi-GB) buffer itself lets later writes stay in
    flight."""
    return buf[(0,) * buf.ndim]


# -- quantized (compressed) wire: dequant fused into the donated write ------ #

def _unpack_dequant(chunk, scale, lo, bits: int, d: int):
    """Wire uint8 → f32 features ON DEVICE: unpack int4 nibbles when
    packed (feature 2j low, 2j+1 high — mirrors
    `feature_cache._pack4`), then the per-feature affine dequant
    x = q·scale + lo. Runs inside the donated write, so the host ships
    1 (int8) or 0.5 (int4) bytes/elem instead of the 2-byte f16 wire."""
    if bits == 4:
        lo_nib = chunk & jnp.uint8(0x0F)
        hi_nib = (chunk >> 4).astype(jnp.uint8)
        chunk = jnp.stack([lo_nib, hi_nib], axis=-1) \
            .reshape(chunk.shape[0], -1)[:, :d]
    return chunk.astype(jnp.float32) * scale + lo


@partial(jax.jit, donate_argnums=(0,), static_argnames=("bits",))
def _dequant_write_rows(buf, chunk_q, scale, lo, r0, *, bits):
    x = _unpack_dequant(chunk_q, scale, lo, bits, buf.shape[1])
    return jax.lax.dynamic_update_slice(buf, x.astype(buf.dtype), (r0, 0))


@partial(jax.jit, donate_argnums=(0,), static_argnames=("bits",))
def _dequant_bin_write_rows(buf, chunk_q, scale, lo, edges, r0, *, bits):
    from transmogrifai_tpu.models.trees import bin_features
    x = _unpack_dequant(chunk_q, scale, lo, bits, buf.shape[1])
    binned = bin_features(x, edges).astype(jnp.int8)
    return jax.lax.dynamic_update_slice(buf, binned, (r0, 0))


@partial(jax.jit, donate_argnums=(0, 1), static_argnames=("bits",))
def _dequant_dual_write_rows(buf16, bufb, chunk_q, scale, lo, edges, r0, *,
                             bits):
    from transmogrifai_tpu.models.trees import bin_features
    x = _unpack_dequant(chunk_q, scale, lo, bits, buf16.shape[1])
    binned = bin_features(x, edges).astype(jnp.int8)
    return (jax.lax.dynamic_update_slice(
                buf16, x.astype(buf16.dtype), (r0, 0)),
            jax.lax.dynamic_update_slice(bufb, binned, (r0, 0)))


def _zeros(shape, dtype, sharding):
    if sharding is None:
        return jnp.zeros(shape, dtype)
    # allocate ON the mesh (out_shardings) — a host-side zeros +
    # device_put would ship shape-many zero bytes through the link
    return jax.jit(lambda: jnp.zeros(shape, dtype),
                   out_shardings=sharding)()


def _put(chunk_np, sharding):
    return (jnp.asarray(chunk_np) if sharding is None
            else jax.device_put(chunk_np, sharding))


def _resolve_upload_plan(store, chunk_rows: int, workers, depth,
                         stats, bytes_per_elem: float = 2.0
                         ) -> Tuple[int, int]:
    """Pick the upload pipeline shape. Explicit `workers`/`depth` win
    unchanged (bench env knobs, tests); a None axis is filled by the
    learned cost model's predicted read-vs-upload balance
    (`perf.choose_upload_plan`) when the ingest target is warm, else by
    the hand-tuned `UPLOAD_WORKERS`/`UPLOAD_DEPTH` defaults — a cold
    corpus reproduces today's plan exactly. The chosen plan's predicted
    wall lands in `stats.predicted_wall_s` so the pipeline can score
    the prediction against the measured wall."""
    if workers is not None and depth is not None:
        return workers, depth
    try:
        from transmogrifai_tpu import perf
        # bytes_per_elem comes from the RESOLVED wire (f16=2, int8=1,
        # int4=0.5): training rows carry measured wire bytes, so the
        # plan query must use the same scale or the model is read off
        # its training distribution
        bytes_wire = float(store.n_rows) * store.n_features * bytes_per_elem
        chunks = -(-store.n_rows // max(chunk_rows, 1))
        w, d, pred = perf.choose_upload_plan(
            bytes_wire, chunks, UPLOAD_WORKERS, UPLOAD_DEPTH,
            fixed_workers=workers, fixed_depth=depth)
        if pred is not None:
            stats.predicted_wall_s = pred.value
            stats.plan = "model"
        return w, d
    except Exception:
        log.debug("upload plan resolution failed; using defaults",
                  exc_info=True)
        return (workers if workers is not None else UPLOAD_WORKERS,
                depth if depth is not None else UPLOAD_DEPTH)


def _default_ingest_retry():
    """Bounded-retry policy for transient IO during bulk ingest
    (tf.data-style bounded retry instead of fail-fast: a single flaky
    NFS read must not burn a 600 s upload). `TRANSMOGRIFAI_INGEST_RETRIES`
    sets total attempts (1 disables retrying)."""
    from transmogrifai_tpu.runtime.retry import RetryPolicy
    attempts = int(os.environ.get("TRANSMOGRIFAI_INGEST_RETRIES", "3"))
    return RetryPolicy(max_attempts=max(1, attempts),
                       base_delay_s=0.1, max_delay_s=5.0)


def _pipelined_upload(items, chunk_rows: int, prepare, label: str,
                      bufs: dict, write, *, n_rows: int,
                      workers: int, depth: int,
                      deadline_s: Optional[float], sharding,
                      profile, retry=None, stats: IngestStats = None,
                      tee=None) -> IngestStats:
    """Shared scaffold for the upload builders: timed prepare, bounded
    pipeline, progress/summary logging, profile record. `write(bufs,
    chunk_dev, r0)` dispatches the donated write(s), rebinding `bufs`
    entries, and returns the completion token. `prepare` is the
    worker-side chunk producer (store sweep, quantizing sweep, or
    cache-artifact replay). `tee(chunk)` — the feature-cache artifact
    append — runs on the main thread in item order BEFORE the device
    dispatch, so a readwrite miss persists exactly the bytes it ships.
    Chunk reads retry transient IO under `retry` (default
    `_default_ingest_retry`); attempts land in the returned stats."""
    st = stats if stats is not None else IngestStats(label=label)
    st.label = label

    def upload(prep):
        r0, c = prep
        if tee is not None:
            tee(c)
        token = write(bufs, _put(c, sharding), r0)
        if r0 and (r0 // chunk_rows) % 8 == 0:
            log.info("%s: %d/%d rows", label, r0, n_rows)
        return token

    run_chunk_pipeline(items, prepare, upload, workers=workers,
                       depth=depth, deadline_s=deadline_s,
                       label=f"{label} upload", stats=st,
                       retry=retry if retry is not None
                       else _default_ingest_retry())
    log.info("%s: %d rows in %.1fs (%.2f GB/s, overlap %.2f, retries %d"
             "%s)", label, n_rows, st.wall_s, st.gbps, st.overlap_frac,
             st.retries, f", cache {st.cache}" if st.cache else "")
    if profile is not None:
        profile.record_ingest(f"{label}_upload", st)
    return st


def _chunk_prepare(store: ColumnarStore, chunk_rows: int, wire: np.dtype,
                   stats: IngestStats):
    """prepare(r0) for the upload pipelines: memmap read → wire-dtype
    cast → tail pad, timed into `stats` (runs on worker threads; numpy
    releases the GIL for the copy and the cast)."""
    d = store.n_features

    def prepare(r0: int):
        t0 = time.perf_counter()
        # copy=True: a memmap slice is a lazy VIEW — without the copy the
        # page faults (the actual disk read) would happen on the MAIN
        # thread inside the device transfer, silently re-serializing the
        # pipeline and zeroing read_s
        c = np.array(store.chunk(r0, r0 + chunk_rows), copy=True)
        stats.note_read(time.perf_counter() - t0, c.nbytes)
        t0 = time.perf_counter()
        if c.dtype != wire:
            c = c.astype(wire)
        if len(c) < chunk_rows:  # pad the tail chunk to the static shape
            c = np.concatenate(
                [c, np.zeros((chunk_rows - len(c), d), wire)])
        stats.note_cast(time.perf_counter() - t0, c.nbytes)
        return r0, c

    return prepare


def _quant_prepare(store: ColumnarStore, chunk_rows: int,
                   plan: "fc.QuantPlan", stats: IngestStats):
    """prepare(r0) for the compressed wire path: memmap read →
    per-feature affine quantize (+ int4 nibble pack) → tail pad with the
    quantized-zero row. Ships 2–4× fewer bytes than the f16 wire; the
    device side dequantizes inside the donated write."""
    def prepare(r0: int):
        t0 = time.perf_counter()
        c = np.array(store.chunk(r0, r0 + chunk_rows), copy=True)
        stats.note_read(time.perf_counter() - t0, c.nbytes)
        t0 = time.perf_counter()
        q = plan.quantize(c)
        if len(q) < chunk_rows:
            q = np.concatenate(
                [q, np.tile(plan.pad_row, (chunk_rows - len(q), 1))])
        stats.note_cast(time.perf_counter() - t0, q.nbytes)
        return r0, q

    return prepare


def _artifact_prepare(art: "fc.CacheArtifact", chunk_rows: int,
                      stats: IngestStats):
    """prepare(r0) for a cache HIT: replay the artifact's wire tape.
    The bytes are already wire-ready (cast/quantized/padded at cold
    build time), so there is no store read and no cast — artifact IO
    lands in `stats.cache_read_s`, and `stats.read_s`/`bytes_read` stay
    0 (the warm-path proof the tests assert)."""
    mm = art.wire

    def prepare(r0: int):
        t0 = time.perf_counter()
        c = np.array(mm[r0:r0 + chunk_rows], copy=True)
        stats.note_cache_read(time.perf_counter() - t0, c.nbytes)
        stats.note_cast(0.0, c.nbytes)  # wire-ready: nothing to cast
        return r0, c

    return prepare


class _CacheSession:
    """Per-build feature-cache orchestration shared by the three
    builders: resolves the `cache=` policy, computes the content
    address, consults the resident registry and the on-disk cache,
    picks warm-replay vs cold-sweep prepare, tees the wire stream into
    a staged artifact on a readwrite miss, and emits the hit/miss/
    corrupt events + counters the goodput report and serving /metrics
    read. Corrupt or torn artifacts are REJECTED (structured
    `FeatureCacheError`, counted) and fall back to a cold rebuild —
    never a crash, never stale data."""

    def __init__(self, kind: str, store: ColumnarStore, chunk_rows: int, *,
                 legacy_wire, target_name: str, edges=None, sharding=None,
                 cache=None):
        self.kind = kind
        self.store = store
        self.chunk_rows = int(chunk_rows)
        self.edges = edges
        self.sharding = sharding
        self.d = store.n_features
        self.n_pad = _pad_rows(store.n_rows, chunk_rows)
        self.params = fc.resolve_cache_params(cache)
        self.legacy_wire = np.dtype(legacy_wire)
        mode = self.params.wire if self.params is not None else "auto"
        if mode in ("int8", "int4"):
            self.wire_mode = mode
            self.bits: Optional[int] = 8 if mode == "int8" else 4
        else:
            if mode == "f16":
                # explicit f16 wire: force 2-byte chunks even when the
                # narrowest-dtype rule would keep a wider store dtype
                # (an f32 store rounds through f16 on the wire — the
                # same contract the binned/dual builders document)
                self.legacy_wire = np.dtype(np.float16)
            self.wire_mode = self.legacy_wire.name
            self.bits = None
        self.quant: Optional[fc.QuantPlan] = None
        self.cache_obj = None
        self.key = ""
        if self.params is not None:
            self.cache_obj = fc.FeatureCache(self.params)
            self.key = fc.cache_key(
                kind, store, target_dtype=target_name, wire=self.wire_mode,
                chunk_rows=self.chunk_rows, edges=edges, sharding=sharding,
                quant_sample=self.params.quant_sample,
                quant_seed=self.params.quant_seed)
        self.artifact: Optional[fc.CacheArtifact] = None
        self.writer: Optional[fc.ArtifactWriter] = None
        self._stats: Optional[IngestStats] = None

    # -- resident layer -------------------------------------------------- #

    def resident(self) -> Optional[Tuple[Tuple, IngestStats]]:
        """HBM-resident arrays for this exact key, when the policy opts
        in — a sweep resume or serving warm re-requesting the same build
        gets the live device buffers with zero IO."""
        if self.params is None or not self.params.resident or not self.key:
            return None
        entry = fc.resident_get(self.key)
        if entry is None:
            return None
        stats = IngestStats(label=f"{self.kind}_resident")
        stats.cache = "resident"
        stats.cache_key = self.key
        stats.wire = self.wire_mode
        saved = float(entry["extra"].get("cold_wall_s", 0.0))
        fc.count_hit(self.store.nbytes(), saved)
        record_event("cache_hit", key=self.key, build=self.kind,
                     resident=True, saved_s=round(saved, 6))
        return entry["arrays"], stats

    # -- build-time hooks ------------------------------------------------ #

    def _expected_wire_cols(self) -> int:
        return (self.d + 1) // 2 if self.bits == 4 else self.d

    def _check_meta(self, art: "fc.CacheArtifact") -> None:
        meta = art.meta
        expect = {"kind": self.kind, "n_pad": self.n_pad,
                  "n_features": self.d, "wire": self.wire_mode,
                  "wire_cols": self._expected_wire_cols(),
                  "chunk_rows": self.chunk_rows}
        for field_, want in expect.items():
            if meta.get(field_) != want:
                raise fc.FeatureCacheError(
                    art.path, f"meta {field_}={meta.get(field_)!r} does "
                              f"not match the requested build ({want!r})",
                    self.key)
        if self.bits is not None and art.quant is None:
            raise fc.FeatureCacheError(
                art.path, "quantized wire artifact lacks quant.npz",
                self.key)

    def _meta(self) -> dict:
        return {
            "kind": self.kind,
            "store_fingerprint": fc.store_fingerprint(self.store),
            "n_rows": int(self.store.n_rows),
            "n_pad": int(self.n_pad),
            "n_features": int(self.d),
            "store_dtype": self.store.dtype.name,
            "wire": self.wire_mode,
            "wire_dtype": ("uint8" if self.bits is not None
                           else self.legacy_wire.name),
            "wire_cols": self._expected_wire_cols(),
            "chunk_rows": self.chunk_rows,
            "edges_sha": fc._edges_digest(self.edges),
            "sharding": (None if self.sharding is None
                         else str(self.sharding)),
        }

    def begin(self, stats: IngestStats):
        """Resolve warm vs cold. Returns (prepare, items) for
        `_pipelined_upload`."""
        self._stats = stats
        stats.wire = self.wire_mode
        stats.cache_key = self.key
        if self.cache_obj is not None:
            try:
                art = self.cache_obj.load(self.key)
                if art is not None:
                    self._check_meta(art)
                self.artifact = art
            except fc.FeatureCacheError as e:
                fc.count_corrupt()
                record_event("cache_corrupt", key=self.key,
                             build=self.kind, reason=e.reason)
                log.warning("feature cache: %s — rebuilding", e)
                self.artifact = None
        if self.artifact is not None:
            self.quant = self.artifact.quant
            stats.cache = "hit"
            return (_artifact_prepare(self.artifact, self.chunk_rows,
                                      stats),
                    range(0, self.n_pad, self.chunk_rows))
        if self.bits is not None:
            self.quant = fc.compute_quant_plan(
                self.store, self.bits, sample=self.params.quant_sample,
                seed=self.params.quant_seed)
        if self.cache_obj is not None:
            stats.cache = "miss"
            if self.params.writable:
                try:
                    self.writer = self.cache_obj.writer(self.key,
                                                        self._meta())
                except OSError:
                    log.warning("feature cache: cannot stage artifact "
                                "under %s; building uncached",
                                self.params.resolved_dir(), exc_info=True)
                    self.writer = None
        if self.quant is not None:
            prepare = _quant_prepare(self.store, self.chunk_rows,
                                     self.quant, stats)
        else:
            prepare = _chunk_prepare(self.store, self.chunk_rows,
                                     self.legacy_wire, stats)
        return prepare, range(0, self.store.n_rows, self.chunk_rows)

    def quant_device(self):
        """(scale, lo) as device arrays for the fused-dequant writes."""
        return jnp.asarray(self.quant.scale), jnp.asarray(self.quant.lo)

    def tee(self, chunk: np.ndarray) -> None:
        """Artifact append off the upload stream (main thread, item
        order). A failing disk degrades to an uncached build — it must
        not kill a multi-hundred-second upload."""
        if self.writer is None:
            return
        t0 = time.perf_counter()
        try:
            self.writer.append(chunk)
        except OSError:
            log.warning("feature cache: artifact append failed; "
                        "continuing uncached", exc_info=True)
            self.writer.abort()
            self.writer = None
            return
        if self._stats is not None:
            self._stats.cache_write_s += time.perf_counter() - t0

    def finish(self, stats: IngestStats, arrays: Tuple) -> None:
        """Post-pipeline bookkeeping: finalize the staged artifact
        (integrity manifest LAST → crash-consistent rename), emit
        hit/miss events + counters, stamp wire savings, and publish
        resident arrays when the policy keeps them."""
        if self.bits is not None:
            f16_equiv = self.n_pad * self.d * 2
            stats.bytes_saved_wire = max(0, f16_equiv - stats.bytes_wire)
        if self.params is None:
            return
        if stats.cache == "hit":
            saved = max(0.0, self.artifact.cold_wall_s - stats.wall_s)
            fc.count_hit(self.store.nbytes(), saved)
            record_event("cache_hit", key=self.key, build=self.kind,
                         saved_s=round(saved, 6), bytes=stats.cache_bytes)
        else:
            fc.count_miss()
            record_event("cache_miss", key=self.key, build=self.kind)
            if self.writer is not None:
                try:
                    self.writer.finalize(
                        quant=self.quant,
                        cold={"wall_s": round(stats.wall_s, 6),
                              "gbps": round(stats.gbps, 6),
                              "bytes_wire": stats.bytes_wire})
                except OSError:
                    log.warning("feature cache: artifact finalize failed; "
                                "next run rebuilds", exc_info=True)
                finally:
                    self.writer = None
        if self.params.resident and self.key:
            cold_wall = (self.artifact.cold_wall_s
                         if self.artifact is not None else stats.wall_s)
            fc.resident_put(self.key, arrays,
                            cold_wall_s=cold_wall or stats.wall_s)

    def abort(self) -> None:
        """Build died (deadline, worker error): remove the staged
        artifact so a torn tape can never be mistaken for a cache
        entry."""
        if self.writer is not None:
            self.writer.abort()
            self.writer = None


def device_matrix(store: ColumnarStore, dtype=jnp.bfloat16,
                  chunk_rows: int = UPLOAD_CHUNK_ROWS,
                  deadline_s: Optional[float] = None, *,
                  workers: Optional[int] = None,
                  depth: Optional[int] = None,
                  sharding=None, profile=None, return_stats: bool = False,
                  retry=None, cache=None):
    """Stream the store into one (n_pad, d) device buffer through the
    bounded-depth chunk pipeline (`data/pipeline.py`): worker threads
    read+cast upcoming chunks while up to `depth` donated writes are in
    flight. Rows pad to a chunk multiple with zeros (weight-masked
    everywhere downstream); donation keeps peak HBM = buffer + in-flight
    chunks. The returned buffer is READY (the pipeline drains all
    writes), so recorded timings are transfer time, not enqueue time.

    The wire dtype is the narrower of (store dtype, `dtype`); widening
    happens on device inside the donated write — an f16 store headed for
    a bf16 buffer ships 2 bytes/elem and casts on the VPU, bit-identical
    to a host-side cast (both round-to-nearest-even).

    `sharding`: optional NamedSharding for the buffer — each chunk is
    `jax.device_put` with the same spec, so multichip uploads spread
    across the mesh (a feature-axis spec like P(None, "data") splits
    every chunk's bytes across chips).

    `deadline_s`: optional wall-clock budget. Depth backpressure makes
    the per-chunk check track real transfer progress, so TimeoutError
    fires mid-upload for the caller to turn into an explicit skip
    marker.

    `cache`: feature-cache policy (None → process default/env;
    "off"/"read"/"readwrite"; or a `FeatureCacheParams`). On a hit the
    build replays the content-addressed wire artifact — zero store
    reads — and is bit-identical to the cold build that wrote it; on a
    readwrite miss the wire stream tees into a crash-consistent
    artifact for free. `FeatureCacheParams(wire="int8"/"int4")` ships a
    quantized wire with dequant fused into the donated write (2–4×
    fewer bytes; max abs error scale/2 per feature — see
    data/feature_cache.py)."""
    target = np.dtype(dtype)
    legacy_wire = (target if target.itemsize < store.dtype.itemsize
                   else store.dtype)
    sess = _CacheSession("matrix", store, chunk_rows,
                         legacy_wire=legacy_wire, target_name=target.name,
                         sharding=sharding, cache=cache)
    res = sess.resident()
    if res is not None:
        (x,), stats = res
        if profile is not None:
            profile.record_ingest("device_matrix_upload", stats)
        return (x, stats) if return_stats else x
    stats = IngestStats(label="device_matrix")
    workers, depth = _resolve_upload_plan(
        store, chunk_rows, workers, depth, stats,
        bytes_per_elem=(sess.bits / 8.0 if sess.bits
                        else float(sess.legacy_wire.itemsize)))
    prepare, items = sess.begin(stats)
    n_pad = sess.n_pad
    bufs = {"x": _zeros((n_pad, store.n_features), dtype, sharding)}

    if sess.quant is None:
        def write(bufs, cdev, r0):
            bufs["x"] = _write_cast_rows(bufs["x"], cdev, r0)
            return _probe(bufs["x"])
    else:
        scale_dev, lo_dev = sess.quant_device()
        bits = sess.quant.bits

        def write(bufs, cdev, r0):
            bufs["x"] = _dequant_write_rows(bufs["x"], cdev, scale_dev,
                                            lo_dev, r0, bits=bits)
            return _probe(bufs["x"])

    try:
        _pipelined_upload(items, chunk_rows, prepare, "device_matrix",
                          bufs, write, n_rows=store.n_rows,
                          workers=workers, depth=depth,
                          deadline_s=deadline_s, sharding=sharding,
                          profile=profile, retry=retry, stats=stats,
                          tee=sess.tee)
    except BaseException:
        sess.abort()
        raise
    sess.finish(stats, (bufs["x"],))
    return (bufs["x"], stats) if return_stats else bufs["x"]


def device_binned(store: ColumnarStore, edges: np.ndarray,
                  chunk_rows: int = UPLOAD_CHUNK_ROWS,
                  deadline_s: Optional[float] = None, *,
                  workers: Optional[int] = None,
                  depth: Optional[int] = None,
                  sharding=None, profile=None, return_stats: bool = False,
                  retry=None, cache=None):
    """(n_pad, d) int8 quantile-binned device buffer through the same
    chunk pipeline as `device_matrix`. Chunks ship as f16 and bin ON
    DEVICE (broadcast-compare, VPU): a host `searchsorted` loop is a
    second full pass over the matrix on one core, while f16 wire +
    device-side binning costs one pipelined upload pass. `deadline_s`/`sharding`/`profile`/
    `cache` as in `device_matrix`; a cache hit replays the f16 wire
    tape, so the binned matrix is BIT-IDENTICAL to the direct build
    (same wire bytes through the same device binning)."""
    sess = _CacheSession("binned", store, chunk_rows,
                         legacy_wire=np.dtype(np.float16),
                         target_name="int8", edges=edges,
                         sharding=sharding, cache=cache)
    res = sess.resident()
    if res is not None:
        (b,), stats = res
        if profile is not None:
            profile.record_ingest("device_binned_upload", stats)
        return (b, stats) if return_stats else b
    stats = IngestStats(label="device_binned")
    workers, depth = _resolve_upload_plan(
        store, chunk_rows, workers, depth, stats,
        bytes_per_elem=(sess.bits / 8.0 if sess.bits
                        else float(sess.legacy_wire.itemsize)))
    prepare, items = sess.begin(stats)
    n_pad = sess.n_pad
    edges_dev = jnp.asarray(edges)
    bufs = {"b": _zeros((n_pad, store.n_features), jnp.int8, sharding)}

    if sess.quant is None:
        def write(bufs, cdev, r0):
            bufs["b"] = _bin_write_rows(bufs["b"], cdev, edges_dev, r0)
            return _probe(bufs["b"])
    else:
        scale_dev, lo_dev = sess.quant_device()
        bits = sess.quant.bits

        def write(bufs, cdev, r0):
            bufs["b"] = _dequant_bin_write_rows(
                bufs["b"], cdev, scale_dev, lo_dev, edges_dev, r0,
                bits=bits)
            return _probe(bufs["b"])

    try:
        _pipelined_upload(items, chunk_rows, prepare, "device_binned",
                          bufs, write, n_rows=store.n_rows,
                          workers=workers, depth=depth,
                          deadline_s=deadline_s, sharding=sharding,
                          profile=profile, retry=retry, stats=stats,
                          tee=sess.tee)
    except BaseException:
        sess.abort()
        raise
    sess.finish(stats, (bufs["b"],))
    return (bufs["b"], stats) if return_stats else bufs["b"]


def dual_device_matrices(store: ColumnarStore, edges: np.ndarray,
                         dtype=jnp.bfloat16,
                         chunk_rows: int = UPLOAD_CHUNK_ROWS,
                         deadline_s: Optional[float] = None, *,
                         workers: Optional[int] = None,
                         depth: Optional[int] = None, sharding=None,
                         profile=None, return_stats: bool = False,
                         retry=None, cache=None):
    """ONE pass over the store → BOTH device representations: the
    (n_pad, d) `dtype` (bf16) linear-family matrix AND the (n_pad, d)
    int8 quantile-binned matrix. Halves host IO versus running
    `device_matrix` + `device_binned` back to back (the memmap is read
    once) and halves wire traffic too: chunks ship once as f16 and each
    donated write fans out device-side into the widen AND the bin.

    For an f16 store the bf16 matrix is bit-identical to
    `device_matrix`'s and the binned matrix to `device_binned`'s (same
    f16 wire, same device ops). For wider stores the wire is still f16
    — matching `device_binned`'s contract — so the bf16 matrix rounds
    through f16 first; use the separate builders when that matters.

    Both buffers must be HBM-resident simultaneously (3 bytes/elem
    total) — at 10M×500 that is ~15 GB before tree working set, so the
    bench gates this path on the memory plan fitting.

    `cache` as in `device_matrix`: the artifact is the SINGLE wire tape
    (the one f16 — or quantized — stream that fans out device-side into
    both representations), so caching the dual build costs one compact
    file, and a hit reproduces BOTH matrices bit-identically with zero
    store reads."""
    d = store.n_features
    target = np.dtype(dtype)
    sess = _CacheSession("dual", store, chunk_rows,
                         legacy_wire=np.dtype(np.float16),
                         target_name=target.name, edges=edges,
                         sharding=sharding, cache=cache)
    res = sess.resident()
    if res is not None:
        (x, b), stats = res
        if profile is not None:
            profile.record_ingest("dual_upload", stats)
        return (x, b, stats) if return_stats else (x, b)
    stats = IngestStats(label="dual")
    workers, depth = _resolve_upload_plan(
        store, chunk_rows, workers, depth, stats,
        bytes_per_elem=(sess.bits / 8.0 if sess.bits
                        else float(sess.legacy_wire.itemsize)))
    prepare, items = sess.begin(stats)
    n_pad = sess.n_pad
    edges_dev = jnp.asarray(edges)
    bufs = {"x": _zeros((n_pad, d), dtype, sharding),
            "b": _zeros((n_pad, d), jnp.int8, sharding)}

    if sess.quant is None:
        def write(bufs, cdev, r0):
            bufs["x"], bufs["b"] = _dual_write_rows(bufs["x"], bufs["b"],
                                                    cdev, edges_dev, r0)
            # one executable produces both buffers: either probe tokens
            # the completion of the pair
            return _probe(bufs["b"])
    else:
        scale_dev, lo_dev = sess.quant_device()
        bits = sess.quant.bits

        def write(bufs, cdev, r0):
            bufs["x"], bufs["b"] = _dequant_dual_write_rows(
                bufs["x"], bufs["b"], cdev, scale_dev, lo_dev, edges_dev,
                r0, bits=bits)
            return _probe(bufs["b"])

    try:
        _pipelined_upload(items, chunk_rows, prepare, "dual", bufs, write,
                          n_rows=store.n_rows, workers=workers,
                          depth=depth, deadline_s=deadline_s,
                          sharding=sharding, profile=profile, retry=retry,
                          stats=stats, tee=sess.tee)
    except BaseException:
        sess.abort()
        raise
    sess.finish(stats, (bufs["x"], bufs["b"]))
    if return_stats:
        return bufs["x"], bufs["b"], stats
    return bufs["x"], bufs["b"]


# --------------------------------------------------------------------------- #
# linear family                                                               #
# --------------------------------------------------------------------------- #

@partial(jax.jit, static_argnames=("n_classes", "max_iter"))
def fit_logreg_big(X16: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                   l2, n_classes: int, max_iter: int = 50) -> Dict:
    """`fit_logreg` against a bf16 device-resident X: the X·W / Xᵀ·R
    matmuls run with bf16 operands at full MXU rate and f32 accumulation
    instead of promoting X to f32 (which would materialize a 20 GB copy).
    Same L-BFGS loop, vmappable over (l2, w)."""
    d = X16.shape[1]
    y1 = jax.nn.one_hot(y.astype(jnp.int32), n_classes, dtype=jnp.float32)
    wsum = jnp.maximum(w.sum(), 1.0)

    def loss_fn(p):
        logits = jnp.matmul(X16, p["W"].astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32) + p["b"]
        ll = optax.softmax_cross_entropy(logits, y1)
        return (ll * w).sum() / wsum + 0.5 * l2 * (p["W"] ** 2).sum()

    params = {"W": jnp.zeros((d, n_classes), jnp.float32),
              "b": jnp.zeros((n_classes,), jnp.float32)}
    opt = optax.lbfgs()
    state = opt.init(params)
    vg = optax.value_and_grad_from_state(loss_fn)

    def step(carry, _):
        p, s = carry
        v, g = vg(p, state=s)
        updates, s = opt.update(g, s, p, value=v, grad=g, value_fn=loss_fn)
        return (optax.apply_updates(p, updates), s), v

    (params, _), _ = jax.lax.scan(step, (params, state), None,
                                  length=max_iter)
    return params


@partial(jax.jit, static_argnames=("n_classes", "max_iter"))
def fit_logreg_enet_big(X16: jnp.ndarray, y: jnp.ndarray, w: jnp.ndarray,
                        l1, l2, n_classes: int, max_iter: int = 200) -> Dict:
    """`fit_logreg_enet` (FISTA) against bf16 device-resident X — the
    default LR grid is elastic-net, so the 10M-row sweep needs this
    path. All X-touching products are bf16×bf16 → f32."""
    d = X16.shape[1]
    y1 = jax.nn.one_hot(y.astype(jnp.int32), n_classes, dtype=jnp.float32)
    wsum = jnp.maximum(w.sum(), 1.0)

    def mv(v):  # Xᵀ diag(w) X v with bf16 X
        xv = jnp.matmul(X16, v.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        return jnp.matmul(X16.T, (w * xv).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    v0 = jnp.full((d,), 1.0 / jnp.sqrt(jnp.float32(d)), jnp.float32)

    def pw(v, _):
        u = mv(v)
        nrm = jnp.linalg.norm(u)
        return u / jnp.maximum(nrm, 1e-12), nrm

    _, norms = jax.lax.scan(pw, v0, None, length=16)
    L = 0.5 * 1.05 * norms[-1] / wsum + l2 + 1e-8  # softmax Hessian bound 1/2
    step = 1.0 / L

    def smooth_grads(W, b):
        logits = jnp.matmul(X16, W.astype(jnp.bfloat16),
                            preferred_element_type=jnp.float32) + b
        R = (jax.nn.softmax(logits) - y1) * w[:, None]
        gW = jnp.matmul(X16.T, R.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32) / wsum + l2 * W
        return gW, R.sum(0) / wsum

    def fista_step(carry, _):
        W, b, Wm, bm, t = carry
        gW, gb = smooth_grads(Wm, bm)
        W1 = Wm - step * gW
        W1 = jnp.sign(W1) * jnp.maximum(jnp.abs(W1) - step * l1, 0.0)
        b1 = bm - step * gb
        t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        beta = (t - 1.0) / t1
        return (W1, b1, W1 + beta * (W1 - W), b1 + beta * (b1 - b), t1), None

    W0 = jnp.zeros((d, n_classes), jnp.float32)
    b0 = jnp.zeros((n_classes,), jnp.float32)
    (W, b, _, _, _), _ = jax.lax.scan(
        fista_step, (W0, b0, W0, b0, jnp.float32(1.0)), None,
        length=max_iter)
    return {"W": W, "b": b}


@partial(jax.jit, static_argnames=("n_classes", "max_iter"))
def fit_logreg_enet_grids_big(X16: jnp.ndarray, y: jnp.ndarray,
                              w: jnp.ndarray, l1v: jnp.ndarray,
                              l2v: jnp.ndarray, n_classes: int,
                              max_iter: int = 200) -> Dict:
    """The WHOLE elastic-net grid in one program with X read once per
    FISTA step: weights live as (d, g·k) so the forward/adjoint products
    are single wide matmuls — at 10M×500 bf16 (10 GB) the fit is HBM-
    bandwidth bound, and a vmap over grids would re-stream X per grid
    (g× the traffic, 60s+ dispatches); stacking grids into the matmul
    output dim costs one X pass for all of them. Returns
    {"W": (g, d, k), "b": (g, k)}."""
    d = X16.shape[1]
    g = l1v.shape[0]
    k = n_classes
    y1 = jax.nn.one_hot(y.astype(jnp.int32), k, dtype=jnp.float32)
    wsum = jnp.maximum(w.sum(), 1.0)

    def mv(v):
        xv = jnp.matmul(X16, v.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32)
        return jnp.matmul(X16.T, (w * xv).astype(jnp.bfloat16),
                          preferred_element_type=jnp.float32)

    v0 = jnp.full((d,), 1.0 / jnp.sqrt(jnp.float32(d)), jnp.float32)

    def pw(v, _):
        u = mv(v)
        nrm = jnp.linalg.norm(u)
        return u / jnp.maximum(nrm, 1e-12), nrm

    _, norms = jax.lax.scan(pw, v0, None, length=16)
    lam = norms[-1] / wsum                       # shared λmax(XᵀWX)/wsum
    L = 0.5 * 1.05 * lam + l2v + 1e-8            # (g,) softmax bound 1/2
    step = (1.0 / L)[None, :, None]              # (1, g, 1) for W
    step_b = (1.0 / L)[:, None]                  # (g, 1) for b
    l1 = l1v[None, :, None]
    l2 = l2v[None, :, None]

    def smooth_grads(W, b):                      # W (d, g, k), b (g, k)
        logits = jnp.matmul(
            X16, W.reshape(d, g * k).astype(jnp.bfloat16),
            preferred_element_type=jnp.float32).reshape(-1, g, k) + b
        R = (jax.nn.softmax(logits, axis=-1) - y1[:, None, :]) \
            * w[:, None, None]                   # (n, g, k)
        gW = jnp.matmul(X16.T, R.reshape(-1, g * k).astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32
                        ).reshape(d, g, k) / wsum + l2 * W
        return gW, R.sum(0) / wsum

    def fista_step(carry, _):
        W, b, Wm, bm, t = carry
        gW, gb = smooth_grads(Wm, bm)
        W1 = Wm - step * gW
        W1 = jnp.sign(W1) * jnp.maximum(jnp.abs(W1) - step * l1, 0.0)
        b1 = bm - step_b * gb
        t1 = 0.5 * (1.0 + jnp.sqrt(1.0 + 4.0 * t * t))
        beta = (t - 1.0) / t1
        return (W1, b1, W1 + beta * (W1 - W), b1 + beta * (b1 - b), t1), None

    W0 = jnp.zeros((d, g, k), jnp.float32)
    b0 = jnp.zeros((g, k), jnp.float32)
    (W, b, _, _, _), _ = jax.lax.scan(
        fista_step, (W0, b0, W0, b0, jnp.float32(1.0)), None,
        length=max_iter)
    return {"W": jnp.transpose(W, (1, 0, 2)), "b": b}


@partial(jax.jit, static_argnames=())
def predict_logreg_grids_big(W, b, X16):
    """(g, n, k) probabilities for stacked grid weights — one X pass."""
    d, (g, _, k) = X16.shape[1], W.shape
    logits = jnp.matmul(
        X16, jnp.transpose(W, (1, 0, 2)).reshape(d, g * k).astype(
            jnp.bfloat16),
        preferred_element_type=jnp.float32).reshape(-1, g, k) + b
    return jnp.transpose(jax.nn.softmax(logits, axis=-1), (1, 0, 2))


@partial(jax.jit, static_argnames=())
def predict_logreg_big(W, b, X16):
    logits = jnp.matmul(X16, W.astype(jnp.bfloat16),
                        preferred_element_type=jnp.float32) + b
    prob = jax.nn.softmax(logits, axis=-1)
    return {"prediction": jnp.argmax(logits, -1).astype(jnp.float32),
            "rawPrediction": logits, "probability": prob}


# --------------------------------------------------------------------------- #
# tree families: chunked-histogram growth                                     #
# --------------------------------------------------------------------------- #

def _chunked_histograms(Xb, node_idx, V, n_nodes: int, n_bins: int,
                        chunk: int):
    """(V_cols, nodes, d, bins) f32 histograms without materializing the
    full bin one-hot: scan over row chunks, per chunk ONE matmul
    (V·nodes, c) @ (c, d·bins) — the A side stacks every histogram value
    column (gradients + weights) so the 1-2 GB per-chunk one-hot B is
    read exactly once."""
    n, d = Xb.shape
    m = V.shape[1]
    n_chunks = n // chunk

    # scan over chunk INDICES and dynamic-slice each operand: passing the
    # reshaped (n_chunks, chunk, d) array as scan xs makes XLA materialize
    # a re-laid-out copy of the whole multi-GB buffer (two such copies
    # resident do not fit beside a 10M×500 matrix); aligned dynamic
    # slices read the argument buffer in place
    def body(acc, i):
        r0 = i * chunk
        xb_c = jax.lax.dynamic_slice(Xb, (r0, 0), (chunk, d))
        ni_c = jax.lax.dynamic_slice(node_idx, (r0,), (chunk,))
        v_c = jax.lax.dynamic_slice(V, (r0, 0), (chunk, m))
        B = jax.nn.one_hot(xb_c, n_bins,
                           dtype=jnp.bfloat16).reshape(chunk, d * n_bins)
        A = jax.nn.one_hot(ni_c, n_nodes, dtype=jnp.bfloat16)  # (c, nodes)
        # (c, m·nodes): value v times node indicator, all columns at once
        Av = (A[:, None, :] * v_c.astype(jnp.bfloat16)[:, :, None]
              ).reshape(chunk, m * n_nodes)
        h = jnp.matmul(Av.T, B, preferred_element_type=jnp.float32)
        return acc + h.reshape(m, n_nodes, d, n_bins), None

    acc0 = jnp.zeros((m, n_nodes, d, n_bins), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, jnp.arange(n_chunks, dtype=jnp.int32))
    return acc


def _chunked_leaf_sums(node_idx, V, n_nodes: int, chunk: int):
    """(nodes, m) Σ per-node values via chunked matmul (scatter-add
    serializes at 10M rows)."""
    n, m = V.shape
    n_chunks = n // chunk

    def body(acc, i):
        r0 = i * chunk
        ni_c = jax.lax.dynamic_slice(node_idx, (r0,), (chunk,))
        v_c = jax.lax.dynamic_slice(V, (r0, 0), (chunk, m))
        A = jax.nn.one_hot(ni_c, n_nodes, dtype=jnp.bfloat16)
        return acc + jnp.matmul(A.T, v_c.astype(jnp.bfloat16),
                                preferred_element_type=jnp.float32), None

    acc, _ = jax.lax.scan(body, jnp.zeros((n_nodes, m), jnp.float32),
                          jnp.arange(n_chunks, dtype=jnp.int32))
    return acc


def _chunked_histograms_multi(Xb, node_K, V_K, n_nodes: int, n_bins: int,
                              chunk: int):
    """(K, p, nodes, d, bins) f32 histograms for K LOCKSTEP learners from
    ONE bin one-hot build per row chunk.

    The per-chunk cost of the histogram matmul is modelled as FLAT in the
    number of histogram rows up to several hundred (the MXU pads the
    output M axis to the 128-row tile; streaming the (chunk, d·bins)
    one-hot operand is the floor — see `grow_trees_big_lockstep`). Growing K learners level-synchronized therefore amortizes
    the dominant one-hot cost K-fold: the A side stacks every learner's
    node-indicator × value columns into one (chunk, K·p·nodes) operand.

    node_K: (K, n) int32 per-learner node assignment; V_K: (K, n, p)
    value columns (gradient cols + weight col — bf16 is enough: the
    matmul quantizes operands to bf16 anyway, matching `_histograms`'s
    documented precision contract)."""
    n, d = Xb.shape
    K, _, p = V_K.shape
    n_chunks = n // chunk

    # index-scan + dynamic slices, NOT reshaped/transposed scan xs: the
    # (n_chunks, chunk, d) view chose a transposed layout and XLA kept a
    # second full copy of the 4.9 GB Xb — 9.7 GB of HLO temps that do
    # not fit beside it at 10M×500; slices read the buffers in place
    def body(acc, i):
        r0 = i * chunk
        xb_c = jax.lax.dynamic_slice(Xb, (r0, 0), (chunk, d))
        ni_c = jax.lax.dynamic_slice(node_K, (0, r0), (K, chunk))
        v_c = jax.lax.dynamic_slice(V_K, (0, r0, 0), (K, chunk, p))
        B = jax.nn.one_hot(xb_c, n_bins,
                           dtype=jnp.bfloat16).reshape(chunk, d * n_bins)
        # joint A operand (c, K·p·nodes): per-row, K·p nonzeros
        oh = (jnp.transpose(ni_c)[:, :, None]
              == jnp.arange(n_nodes, dtype=jnp.int32)[None, None, :]
              )                                        # (c, K, nodes)
        vt = jnp.transpose(v_c, (1, 0, 2)).astype(jnp.bfloat16)  # (c, K, p)
        Av = (oh[:, :, None, :].astype(jnp.bfloat16)
              * vt[:, :, :, None]).reshape(chunk, K * p * n_nodes)
        h = jnp.matmul(Av.T, B, preferred_element_type=jnp.float32)
        return acc + h.reshape(K, p, n_nodes, d, n_bins), None

    acc0 = jnp.zeros((K, p, n_nodes, d, n_bins), jnp.float32)
    acc, _ = jax.lax.scan(body, acc0, jnp.arange(n_chunks, dtype=jnp.int32))
    return acc


def _chunked_leaf_sums_multi(node_K, V_K, n_nodes: int, chunk: int):
    """(K, nodes, p) per-learner leaf sums, one pass over the rows."""
    K, n, p = V_K.shape
    n_chunks = n // chunk

    def body(acc, i):
        r0 = i * chunk
        ni_c = jax.lax.dynamic_slice(node_K, (0, r0), (K, chunk))
        v_c = jax.lax.dynamic_slice(V_K, (0, r0, 0), (K, chunk, p))
        A = jax.nn.one_hot(ni_c, n_nodes, dtype=jnp.bfloat16)  # (K, c, nodes)
        h = jnp.einsum("kcn,kcp->knp", A, v_c.astype(jnp.bfloat16),
                       preferred_element_type=jnp.float32)
        return acc + h, None

    acc, _ = jax.lax.scan(body, jnp.zeros((K, n_nodes, p), jnp.float32),
                          jnp.arange(n_chunks, dtype=jnp.int32))
    return acc


def _select_bin_big(Xb: jnp.ndarray, feat_idx: jnp.ndarray) -> jnp.ndarray:
    """Xb[r, feat_idx[r]] as a fused compare+reduce (elementwise over the
    int8 matrix; XLA fuses the one-hot into the reduction, nothing
    (n, d)-sized materializes)."""
    d = Xb.shape[1]
    onehot = jnp.arange(d, dtype=jnp.int32)[None, :] == feat_idx[:, None]
    return jnp.where(onehot, Xb.astype(jnp.int32), 0).sum(axis=1)


@partial(jax.jit, static_argnames=("max_depth", "n_bins", "chunk"))
def grow_tree_big(Xb: jnp.ndarray, G: jnp.ndarray, H: jnp.ndarray,
                  max_depth: int, n_bins: int, reg_lambda=1.0,
                  min_child_weight=1.0, min_gain=0.0, min_gain_norm=0.0,
                  feature_mask: Optional[jnp.ndarray] = None,
                  chunk: int = HIST_CHUNK_ROWS) -> Dict:
    """`grow_tree` for device-resident int8 bins at out-of-core row
    counts. Same dense-array tree encoding, same split rule
    (`split_from_histograms`), chunked reductions."""
    n, d = Xb.shape
    m = G.shape[1]
    max_nodes = 2 ** max_depth
    node_idx = jnp.zeros(n, dtype=jnp.int32)
    feats = jnp.zeros((max_depth, max_nodes), jnp.int32)
    bins = jnp.full((max_depth, max_nodes), n_bins, jnp.int32)
    GH = jnp.concatenate([G, H[:, None]], axis=1)  # (n, m+1)

    for level in range(max_depth):
        n_nodes = 2 ** level
        hist = _chunked_histograms(Xb, node_idx, GH, n_nodes, n_bins, chunk)
        hg, hh = hist[:m], hist[m]
        bf, bb = split_from_histograms(
            hg, hh, n_bins, reg_lambda, min_child_weight, min_gain,
            min_gain_norm, feature_mask, level, None)
        feats = feats.at[level, :n_nodes].set(bf)
        bins = bins.at[level, :n_nodes].set(bb)
        from transmogrifai_tpu.models.trees import (
            _ONEHOT_LOOKUP_MAX, _table_lookup2)
        if n_nodes <= _ONEHOT_LOOKUP_MAX:
            sample_feat, split_bin = _table_lookup2(bf, bb, node_idx)
        else:
            sample_feat, split_bin = bf[node_idx], bb[node_idx]
        sample_bin = _select_bin_big(Xb, sample_feat)
        node_idx = node_idx * 2 + (sample_bin > split_bin).astype(jnp.int32)

    sums = _chunked_leaf_sums(node_idx, GH, max_nodes, chunk)
    leaf_g, leaf_h = sums[:, :m], sums[:, m]
    leaf = leaf_g / (leaf_h + reg_lambda)[:, None]
    return {"feat": feats, "bin": bins, "leaf": leaf}


@partial(jax.jit, static_argnames=("max_depth", "n_bins", "chunk"))
def grow_trees_big_lockstep(Xb, V_K, max_depth: int, n_bins: int,
                            reg_lambda=1.0, min_child_weight=1.0,
                            min_gain=0.0, min_gain_norm=0.0,
                            feature_mask_K: Optional[jnp.ndarray] = None,
                            chunk: int = HIST_CHUNK_ROWS) -> Dict:
    """Grow K trees LEVEL-SYNCHRONIZED, sharing each chunk's bin one-hot.

    One histogram matmul streams the whole (chunk, d·bins) one-hot
    operand whether it produces 2 histogram rows or 514, so a single
    tree leaves most of the MXU's M axis idle. Growing the whole
    lockstep batch against one B build shares that stream K-fold.
    (Per-chunk and per-tree times: not measured on a directly attached
    chip — PERF.md §7; one K=16 depth-6 batch at 1M×500 is the only
    run on record.) The per-learner value
    columns V_K (K, n, m+1) carry [G·, H] (gradients/labels × bootstrap
    weights, then the weight column); trees may differ in bootstrap
    weights (RF), gradients (GBT fold pairs), and feature masks.

    Returns {"feat": (K, depth, 2^depth), "bin": ..., "leaf":
    (K, 2^depth, m)} — `fit_forest`-shaped stacked arrays."""
    from transmogrifai_tpu.models.trees import (
        _ONEHOT_LOOKUP_MAX, _table_lookup2)
    n, d = Xb.shape
    K, _, p = V_K.shape
    m = p - 1
    max_nodes = 2 ** max_depth
    node_K = jnp.zeros((K, n), dtype=jnp.int32)
    feats = jnp.zeros((K, max_depth, max_nodes), jnp.int32)
    bins = jnp.full((K, max_depth, max_nodes), n_bins, jnp.int32)

    def split_k(hg, hh, fmask, level):
        return split_from_histograms(
            hg, hh, n_bins, reg_lambda, min_child_weight, min_gain,
            min_gain_norm, fmask, level, None)

    for level in range(max_depth):
        n_nodes = 2 ** level
        hist = _chunked_histograms_multi(Xb, node_K, V_K, n_nodes,
                                         n_bins, chunk)
        hg_K, hh_K = hist[:, :m], hist[:, m]
        if feature_mask_K is None:
            bf_K, bb_K = jax.vmap(split_k, in_axes=(0, 0, None, None))(
                hg_K, hh_K, None, level)
        else:
            bf_K, bb_K = jax.vmap(split_k, in_axes=(0, 0, 0, None))(
                hg_K, hh_K, feature_mask_K, level)
        feats = feats.at[:, level, :n_nodes].set(bf_K)
        bins = bins.at[:, level, :n_nodes].set(bb_K)

        def route(args):
            bf, bb, node = args
            if n_nodes <= _ONEHOT_LOOKUP_MAX:
                sf, sb_ = _table_lookup2(bf, bb, node)
            else:
                sf, sb_ = bf[node], bb[node]
            sample_bin = _select_bin_big(Xb, sf)
            return node * 2 + (sample_bin > sb_).astype(jnp.int32)

        # lax.map (not vmap): a vmapped (K, n, d) one-hot select would
        # gamble on full fusion of a 40 GB intermediate at 10M rows; the
        # sequential per-learner pass is a bounded (n, d) VPU stream
        node_K = jax.lax.map(route, (bf_K, bb_K, node_K))

    sums = _chunked_leaf_sums_multi(node_K, V_K, max_nodes, chunk)
    leaf_g, leaf_h = sums[:, :, :m], sums[:, :, m]
    leaf = leaf_g / (leaf_h + reg_lambda)[:, :, None]
    return {"feat": feats, "bin": bins, "leaf": leaf}


# Per-chunk histogram-matmul floor for one (65536, 500·32) one-hot
# operand stream, scaling with the operand width; cost is modelled flat
# until the matmul's output M axis (K·p·nodes rows) exceeds ~512, then
# roughly linear in M tiles. Both constants predate PR 21 and have not
# been re-measured on a directly attached chip (ROADMAP.md, Speed 1).
_CHUNK_FLOOR_S = 0.008
_FLAT_M_ROWS = 512.0


def lockstep_dispatch_estimate_s(n: int, d: int, n_bins: int,
                                 max_depth: int, K: int, p: int,
                                 chunk: int = HIST_CHUNK_ROWS) -> float:
    """Wall-clock model for one lockstep batch dispatch: per level, every
    row chunk pays the one-hot stream floor times the M-tile factor."""
    n_chunks = -(-n // chunk)
    per_chunk = _CHUNK_FLOOR_S * (d * n_bins) / 16000.0
    total = sum(max(1.0, K * p * (2.0 ** level) / _FLAT_M_ROWS)
                for level in range(max_depth)) * n_chunks * per_chunk
    return total * 1.2  # routing + leaf passes ride on top (~20%)


def lockstep_width(max_depth: int, d: int, n_bins: int, m: int,
                   requested: int, n: Optional[int] = None,
                   target_s: float = 20.0) -> int:
    """How many lockstep learners per dispatch: bound the deepest level's
    carried histogram (K·(m+1)·2^(depth-1)·d·bins f32) to ~800 MB AND —
    when the row count is known — bound the modeled dispatch wall-clock
    to `target_s` (deep levels leave the flat-cost regime, so K must
    shrink with depth). A deep-enough single tree can exceed the target by itself;
    K=1 then matches the pre-lockstep behavior."""
    budget_elems = 2e8  # ~800 MB f32 carried histogram
    per_learner = (m + 1) * (2 ** (max_depth - 1)) * d * n_bins
    k_mem = max(1, int(budget_elems // max(per_learner, 1)))
    k = max(1, min(requested, k_mem, 16))
    if n is not None:
        while k > 1 and lockstep_dispatch_estimate_s(
                n, d, n_bins, max_depth, k, m + 1) > target_s:
            k -= 1
    return k


@partial(jax.jit, static_argnames=("max_depth", "n_bins", "chunk",
                                   "bootstrap", "n_sub"))
def _forest_lockstep_batch(Xb, Y, w, keys, max_depth: int, n_bins: int,
                           min_child_weight, min_gain,
                           n_sub: Optional[int], bootstrap: bool,
                           chunk: int):
    """One lockstep batch of keys.shape[0] bootstrap trees: per-tree
    Poisson weights and feature masks drawn in-program, value columns
    [Y·boot, boot] stacked bf16 (the histogram matmul quantizes to bf16
    regardless — see `_histograms`'s precision contract)."""
    n, d = Xb.shape

    def inputs(key):
        k1, k2 = jax.random.split(key)
        if bootstrap:
            boot = jax.random.poisson(k1, 1.0, (n,)).astype(jnp.float32) * w
        else:
            boot = w
        V = jnp.concatenate([Y * boot[:, None], boot[:, None]],
                            axis=1).astype(jnp.bfloat16)
        if n_sub is not None and n_sub < d:
            scores = jax.random.uniform(k2, (d,))
            fmask = scores <= jnp.sort(scores)[n_sub - 1]
        else:
            fmask = jnp.ones((d,), bool)
        return V, fmask

    V_K, fm_K = jax.vmap(inputs)(keys)
    return grow_trees_big_lockstep(
        Xb, V_K, max_depth, n_bins, reg_lambda=1e-6,
        min_child_weight=min_child_weight, min_gain_norm=min_gain,
        feature_mask_K=fm_K, chunk=chunk)


def fit_forest_big(Xb, Y, w, n_trees: int, max_depth: int, n_bins: int,
                   n_outputs: int, seed: int = 0,
                   subsample_features: bool = True,
                   min_child_weight: float = 1.0, min_gain: float = 0.0,
                   bootstrap: bool = True,
                   chunk: int = HIST_CHUNK_ROWS,
                   trees_per_dispatch: Optional[int] = None) -> Dict:
    """Host loop dispatching LOCKSTEP tree batches: each dispatch
    grows `trees_per_dispatch` trees level-synchronized against shared
    per-chunk bin one-hots — the dominant out-of-core histogram cost
    is shared across the batch (see `grow_trees_big_lockstep`). Returns stacked (T, ...) tree arrays like
    `fit_forest`. (`n_outputs` is accepted for `fit_forest` signature
    parity; the output width comes from Y's trailing dim.)"""
    n, d = int(Xb.shape[0]), int(Xb.shape[1])
    n_sub = max(int(np.sqrt(d)), 1) if subsample_features else None
    m = int(Y.shape[1])
    K = lockstep_width(max_depth, d, n_bins, m,
                       trees_per_dispatch or 16, n=n)
    K = min(K, n_trees)
    # pad the tree count up to a batch multiple (extra trees are grown
    # and sliced off) so every dispatch reuses ONE compiled batch shape
    n_batches = -(-n_trees // K)
    keys = jax.random.split(jax.random.PRNGKey(seed), n_batches * K)
    parts = []
    for b in range(n_batches):
        ks = keys[b * K:(b + 1) * K]
        parts.append(_forest_lockstep_batch(
            Xb, Y, w, ks, max_depth, n_bins,
            min_child_weight, min_gain, n_sub, bootstrap, chunk))
    trees = jax.tree.map(lambda *a: jnp.concatenate(a), *parts)
    return jax.tree.map(lambda a: a[:n_trees], trees)


@partial(jax.jit, static_argnames=("max_depth", "n_bins", "objective",
                                   "chunk"))
def _gbt_round_big(Xb, y, w, margin, max_depth: int, n_bins: int,
                   learning_rate, reg_lambda, objective: str,
                   min_child_weight=1.0, gamma=0.0,
                   chunk: int = HIST_CHUNK_ROWS):
    """One deterministic boosting round (the big path has no row/column
    subsampling, so no PRNG plumbing)."""
    if objective == "logistic":
        p = jax.nn.sigmoid(margin)
        g, h = (p - y) * w, jnp.maximum(p * (1 - p), 1e-6) * w
    else:
        g, h = (margin - y) * w, w
    tree = grow_tree_big(Xb, (-g)[:, None], h, max_depth, n_bins,
                         reg_lambda=reg_lambda,
                         min_child_weight=min_child_weight, min_gain=gamma,
                         chunk=chunk)
    upd = predict_tree_big(tree, Xb)[:, 0]
    return margin + learning_rate * upd, tree


def fit_gbt_big(Xb, y, w, n_estimators: int, max_depth: int, n_bins: int,
                learning_rate, reg_lambda, objective: str = "logistic",
                min_child_weight: float = 1.0, gamma: float = 0.0,
                seed: int = 0, chunk: int = HIST_CHUNK_ROWS
                ) -> Tuple[Dict, jnp.ndarray]:
    """Host loop over boosting rounds carrying the device margin.
    `seed` is accepted for signature parity with `fit_gbt` but currently
    unused — the big path has no row/column subsampling (deterministic
    rounds)."""
    n = Xb.shape[0]
    margin = jnp.zeros(n, jnp.float32)
    trees = []
    for r in range(n_estimators):
        margin, tree = _gbt_round_big(
            Xb, y, w, margin, max_depth, n_bins,
            jnp.float32(learning_rate), jnp.float32(reg_lambda), objective,
            min_child_weight, jnp.float32(gamma), chunk)
        trees.append(tree)
    return jax.tree.map(lambda *a: jnp.stack(a), *trees), margin


@partial(jax.jit, static_argnames=("max_depth", "n_bins", "objective",
                                   "chunk"))
def _gbt_round_big_lockstep(Xb, y, w_K, margin_K, max_depth: int,
                            n_bins: int, learning_rate, reg_lambda,
                            objective: str, min_child_weight=1.0,
                            gamma=0.0, chunk: int = HIST_CHUNK_ROWS):
    """One boosting round for K LOCKSTEP grid×fold pairs: each pair has
    its own margin and row weights (fold masks), but every pair's
    gradient histograms contract against the SAME per-chunk bin one-hot
    (`grow_trees_big_lockstep`), so a 6-pair CV sweep streams the
    one-hot operand once per round instead of six times."""
    if objective == "logistic":
        p = jax.nn.sigmoid(margin_K)
        g = (p - y[None, :]) * w_K
        h = jnp.maximum(p * (1 - p), 1e-6) * w_K
    else:
        g = (margin_K - y[None, :]) * w_K
        h = w_K
    V_K = jnp.stack([-g, h], axis=-1).astype(jnp.bfloat16)  # (K, n, 2)
    trees = grow_trees_big_lockstep(
        Xb, V_K, max_depth, n_bins, reg_lambda=reg_lambda,
        min_child_weight=min_child_weight, min_gain=gamma, chunk=chunk)

    def upd(t):  # sequential per pair: bounded (n, d) routing streams
        return predict_tree_big(t, Xb)[:, 0]

    upd_K = jax.lax.map(upd, trees)
    return margin_K + learning_rate * upd_K, trees


def fit_gbt_big_lockstep(Xb, y, w_K, n_estimators: int, max_depth: int,
                         n_bins: int, learning_rate, reg_lambda,
                         objective: str = "logistic",
                         min_child_weight: float = 1.0, gamma: float = 0.0,
                         chunk: int = HIST_CHUNK_ROWS
                         ) -> Tuple[Dict, jnp.ndarray]:
    """Host loop over rounds for K lockstep pairs; returns
    ({"feat": (T, K, ...), ...}, margins (K, n)). The caller picks K:
    `lockstep_dispatch_estimate_s(n, d, n_bins, max_depth, K, 2)`
    models one dispatch's wall (deep rounds at 10M rows may need the
    pair set split across two host loops)."""
    n = Xb.shape[0]
    K = int(w_K.shape[0])
    margin_K = jnp.zeros((K, n), jnp.float32)
    trees = []
    for r in range(n_estimators):
        margin_K, tree = _gbt_round_big_lockstep(
            Xb, y, w_K, margin_K, max_depth, n_bins,
            jnp.float32(learning_rate), jnp.float32(reg_lambda), objective,
            min_child_weight, jnp.float32(gamma), chunk)
        trees.append(tree)
    return jax.tree.map(lambda *a: jnp.stack(a), *trees), margin_K


def predict_tree_big(tree: Dict, Xb: jnp.ndarray) -> jnp.ndarray:
    """`predict_tree` with the big-n fused compare-select — the shared
    walk + gather-free leaf reads, just a different per-row selector."""
    from transmogrifai_tpu.models.trees import predict_tree
    return predict_tree(tree, Xb, select_fn=_select_bin_big)


@partial(jax.jit, static_argnames=())
def predict_forest_big(trees: Dict, Xb: jnp.ndarray) -> jnp.ndarray:
    preds = jax.lax.map(lambda t: predict_tree_big(t, Xb), trees)
    return preds.mean(axis=0)
