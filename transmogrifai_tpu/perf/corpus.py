"""CostCorpus: the append-only JSONL profile corpus the cost model fits on.

Every run already times its work — sweep blocks journal `duration_s`,
ingest pipelines fill `IngestStats`, serving batches observe latency
histograms. This module persists those measurements as training rows:

    {"target": "block_runtime", "features": {...}, "value": 12.3,
     "predicted": 11.8, "ts": 1690000000}

one JSON object per line, appended with flush (no fsync — the corpus is
an optimization; losing the tail costs training rows, not correctness)
and read torn-tail-tolerantly. Rows accumulate across runs in one
directory (`perf.params.resolved_corpus_dir`), so the model a process
fits reflects every run before it — the tf.data-autotuning-style
closed loop (arxiv 2101.12127) over the repo's own history.

`note()` is the single recording entry point every consumer calls: it
appends the training row AND, when a prediction was made, scores it —
the absolute relative error lands in the process-wide
``perf_model_abs_rel_err`` histogram (exposed on serving /metrics) and
as a ``perf_residual`` event in the run's trace/event log (rolled into
the goodput payload), so the model is continuously scored in
production. Recording NEVER raises: a full disk degrades the model,
not the sweep.

`harvest_journal` lifts block rows out of `SweepJournal` files whose
records carry the static-signature ``facts`` stamp (runtime/journal.py)
— resumed runs contribute training rows even when this process never
executed their blocks.
"""

from __future__ import annotations

import json
import logging
import os
import threading
import time
from typing import Any, Dict, Iterable, List, Optional

from transmogrifai_tpu.perf import params as perf_params

__all__ = ["CostCorpus", "get_corpus", "note", "note_serving",
           "note_parse", "harvest_journal", "device_generation",
           "CORPUS_FILE"]

log = logging.getLogger(__name__)

CORPUS_FILE = "corpus.jsonl"

# a replica name (FleetConfig.replica, exported by the operator) moves
# this process's appends into its own shard — corpus-<replica>.jsonl —
# so K replicas on one shared corpus dir never interleave writes into
# one file; readers merge every shard
ENV_REPLICA = "TRANSMOGRIFAI_PERF_REPLICA"

# device-generation namespace override (tests / heterogeneous-pod ops);
# default is derived from the local accelerator's device_kind
ENV_DEVGEN = "TRANSMOGRIFAI_PERF_DEVGEN"

# targets the model learns; anything else is ignored at fit time
TARGETS = ("block_runtime", "ingest", "serving_bucket", "serving_parse")

_DEVGEN_LOCK = threading.Lock()
_DEVGEN: Optional[str] = None  # guarded-by: _DEVGEN_LOCK


def device_generation() -> str:
    """The accelerator generation this process measures on, as a slug
    (``cpu``, ``tpu_v4``, ...). A fleet corpus on shared storage mixes
    hosts of different generations; rows are stamped with this so each
    host fits only the timings its own hardware produced — a v4 block
    time is training noise to a v5 scheduler. Env-overridable; falls
    back to ``unknown`` before the backend is importable."""
    global _DEVGEN
    with _DEVGEN_LOCK:
        if _DEVGEN is not None:
            return _DEVGEN
    env = os.environ.get(ENV_DEVGEN)
    if env:
        gen = env
    else:
        try:
            import jax
            import re as _re
            kind = jax.devices()[0].device_kind
            gen = _re.sub(r"[^a-z0-9]+", "_", str(kind).lower()).strip("_") \
                or "unknown"
        except Exception:
            return "unknown"  # backend not up yet: do NOT cache
    with _DEVGEN_LOCK:
        _DEVGEN = gen
    return gen


class CostCorpus:
    """Append-only JSONL training corpus: this process writes ONE shard
    (`corpus.jsonl`, or `corpus-<replica>.jsonl` when a replica name is
    set), readers merge every shard in the directory."""

    def __init__(self, dir_path: str, replica: Optional[str] = None):
        self.dir = dir_path
        if replica is None:
            replica = os.environ.get(ENV_REPLICA) or None
        self.replica = replica
        name = f"corpus-{replica}.jsonl" if replica else CORPUS_FILE
        self.path = os.path.join(dir_path, name)
        self._lock = threading.Lock()
        self._appended = 0  # rows this process added (fit invalidation)
        self._appended_bytes = 0  # bytes of those rows (foreign-delta calc)
        self._seq = 0  # per-process append sequence (merge tie-break)

    def _shard_paths(self) -> List[str]:
        """Every corpus shard in the directory, own shard included —
        the unsharded `corpus.jsonl` plus each `corpus-<replica>.jsonl`."""
        try:
            names = os.listdir(self.dir)
        except OSError:
            return [self.path]
        shards = sorted(
            os.path.join(self.dir, n) for n in names
            if n == CORPUS_FILE
            or (n.startswith("corpus-") and n.endswith(".jsonl")))
        return shards or [self.path]

    def append(self, target: str, features: Dict[str, float], value: float,
               predicted: Optional[float] = None, **extra: Any) -> bool:
        """Append one training row; returns False (and logs at debug) on
        any failure instead of raising."""
        rec: Dict[str, Any] = {
            "target": target,
            "features": {k: float(v) for k, v in features.items()},
            "value": float(value),
            "ts": int(time.time()),
            # merge identity: (ts, replica, seq) totally orders the
            # fleet-merged view — ts alone ties constantly at int-second
            # resolution across K replica shards
            "replica": self.replica or "",
            # device-generation namespace: fits filter on this
            "devgen": device_generation(),
        }
        if predicted is not None:
            rec["predicted"] = float(predicted)
        if extra:
            rec.update(extra)
        try:
            with self._lock:
                rec["seq"] = self._seq
                self._seq += 1
                line = json.dumps(rec)
                # the corpus IS an append-only log: the lock exists to
                # serialize the disk appends (torn-tail repair + write
                # must be atomic per row), so I/O under it is the design
                # conc-ok: C003 (append-log serializer)
                os.makedirs(self.dir, exist_ok=True)
                # conc-ok: C003 (append-log serializer)
                with open(self.path, "a+b") as fh:
                    # a torn tail from a killed writer has no newline:
                    # appending straight onto it would corrupt THIS row
                    # too — terminate the torn line first (the reader
                    # skips it, this row survives)
                    fh.seek(0, os.SEEK_END)
                    if fh.tell() > 0:
                        fh.seek(-1, os.SEEK_END)
                        if fh.read(1) != b"\n":
                            fh.write(b"\n")
                    fh.write(line.encode("utf-8") + b"\n")
                    fh.flush()
                self._appended += 1
                self._appended_bytes += len(line) + 1
            return True
        except (OSError, ValueError, TypeError):
            log.debug("perf corpus append failed", exc_info=True)
            return False

    def rows(self, target: Optional[str] = None,
             max_rows: int = 200_000,
             devgen: Optional[str] = None) -> List[Dict[str, Any]]:
        """Parsed corpus rows (newest-last), skipping torn/garbage lines.
        `max_rows` keeps a years-old corpus from ballooning fit time —
        the NEWEST rows are kept (they reflect the current hardware).
        `devgen` filters to one device-generation namespace (rows
        without a stamp — pre-namespacing corpora — are kept, they came
        from the same machine as today's unsharded readers).

        The merge is totally ordered by (ts, replica, seq): replica
        shards on a fleet store carry identical int-second `ts` values
        constantly, and a ts-only sort leaves same-second interleaving
        to incidental shard listing order — the max_rows trim would
        then drop one replica's rows wholesale and dedupe keys (e.g.
        harvest block_keys) could vanish from the kept window. Rows
        predating the stamps tie-break on (shard name, line number),
        which is the same order the old stable sort preserved."""
        keyed: List[tuple] = []
        for path in self._shard_paths():
            shard_name = os.path.basename(path)
            try:
                with open(path, encoding="utf-8", errors="replace") as fh:
                    for lineno, line in enumerate(fh):
                        line = line.strip()
                        if not line:
                            continue
                        try:
                            rec = json.loads(line)
                        except ValueError:
                            continue  # torn tail / garbage line
                        if not isinstance(rec, dict):
                            continue
                        if target is not None and \
                                rec.get("target") != target:
                            continue
                        if devgen is not None and \
                                rec.get("devgen") not in (None, devgen):
                            continue
                        if isinstance(rec.get("features"), dict) and \
                                isinstance(rec.get("value"), (int, float)):
                            ts = rec.get("ts", 0)
                            if not isinstance(ts, (int, float)):
                                ts = 0
                            replica = rec.get("replica")
                            if not isinstance(replica, str):
                                replica = shard_name
                            seq = rec.get("seq")
                            if not isinstance(seq, int):
                                seq = lineno
                            keyed.append((ts, replica, seq, len(keyed),
                                          rec))
            except OSError:
                continue
        keyed.sort(key=lambda t: t[:4])
        return [t[4] for t in keyed[-max_rows:]]

    def version(self) -> tuple:
        """Cheap change token for fit caching: (total shard bytes, rows
        appended by this process, bytes this process appended — the
        foreign-growth delta is total minus own)."""
        size = 0
        for path in self._shard_paths():
            try:
                size += os.path.getsize(path)
            except OSError:
                pass
        return (self.path, size, self._appended, self._appended_bytes)

    def __len__(self) -> int:
        return len(self.rows())


_CORPUS_LOCK = threading.Lock()
_CORPUS: Dict[str, CostCorpus] = {}


def get_corpus() -> Optional[CostCorpus]:
    """The active corpus (per resolved directory), or None when the
    perf model is disabled."""
    if not perf_params.enabled():
        return None
    d = perf_params.resolved_corpus_dir()
    key = f"{d}\x00{os.environ.get(ENV_REPLICA, '')}"
    with _CORPUS_LOCK:
        c = _CORPUS.get(key)
        if c is None:
            c = CostCorpus(d)
            _CORPUS[key] = c
        return c


def note(target: str, features: Dict[str, float], predicted,
         measured: float, example: bool = True, **extra: Any) -> None:
    """Record one consumer decision: the measured value as a training
    row (when `example`), and — when a prediction was made — the
    predicted-vs-measured residual into the process metrics registry
    (``perf_model_abs_rel_err`` histogram) and the run's event log
    (``perf_residual``). `predicted` is a `model.Prediction`, a float,
    or None (cold). Never raises."""
    try:
        pred_v: Optional[float] = None
        if predicted is not None:
            pred_v = float(getattr(predicted, "value", predicted))
        if example:
            corpus = get_corpus()
            if corpus is not None:
                corpus.append(target, features, measured,
                              predicted=pred_v, **extra)
                # online per-decision Bayesian update: the process
                # model absorbs this measurement NOW (sufficient-
                # statistics update, perf/model.py) instead of waiting
                # for a periodic batch refit
                from transmogrifai_tpu.perf.model import observe
                observe(target, features, measured)
        if pred_v is not None and measured > 0:
            err = abs(pred_v - measured) / max(abs(measured), 1e-9)
            from transmogrifai_tpu.obs.metrics import get_registry
            get_registry().histogram(
                "perf_model_abs_rel_err",
                "cost-model |predicted-measured|/measured per decision",
                target=target).observe(err)
            from transmogrifai_tpu.obs.export import record_event
            record_event("perf_residual", target=target,
                         abs_rel_err=round(err, 4),
                         predicted=round(pred_v, 6),
                         measured=round(measured, 6))
    except Exception:
        log.debug("perf residual recording failed", exc_info=True)


# serving batches arrive at request rate: record the first few per
# bucket densely (cold corpus needs rows fast), then sample — the
# corpus must not grow one line per scored batch forever
_SERVING_COUNTS: Dict[int, int] = {}
_SERVING_LOCK = threading.Lock()
_SERVING_DENSE = 64
_SERVING_SAMPLE = 16


def note_serving(bucket: int, latency_s: float, predicted=None) -> None:
    """Sampled recording of one serving device batch (bucket, latency).
    When no prediction is passed, the active model's own per-bucket
    estimate is scored — the honesty layer must see serving residuals
    whenever the ladder decision was model-driven (the predict is a
    dot product, and only on sampled batches)."""
    with _SERVING_LOCK:
        n = _SERVING_COUNTS.get(bucket, 0)
        _SERVING_COUNTS[bucket] = n + 1
    if n >= _SERVING_DENSE and n % _SERVING_SAMPLE != 0:
        return
    from transmogrifai_tpu.perf.features import serving_features
    feats = serving_features(bucket)
    if predicted is None:
        try:
            from transmogrifai_tpu.perf.model import get_model
            model = get_model()
            if model is not None:
                predicted = model.predict("serving_bucket", feats)
        except Exception:
            predicted = None
    note("serving_bucket", feats, predicted, latency_s)


# host-parse recordings arrive once per REQUEST — denser than batches;
# same dense-then-sampled cadence as serving batches, keyed by rows
_PARSE_COUNTS: Dict[int, int] = {}
_PARSE_DENSE = 64
_PARSE_SAMPLE = 64


def note_parse(n_rows: int, n_cols: int, seconds: float) -> None:
    """Sampled recording of one host-side request parse (the row codec
    / columnar convert): rows+cols → measured seconds becomes a
    ``serving_parse`` training row, so ladder derivation and other
    host-cost consumers can PREDICT what a b-row request costs on host
    instead of treating parse as free. Residuals are not scored here —
    parse predictions are consumed inside derive_ladder, which has no
    per-decision measurement to compare against."""
    with _SERVING_LOCK:
        n = _PARSE_COUNTS.get(n_rows, 0)
        _PARSE_COUNTS[n_rows] = n + 1
    if n >= _PARSE_DENSE and n % _PARSE_SAMPLE != 0:
        return
    from transmogrifai_tpu.perf.features import parse_features
    note("serving_parse", parse_features(n_rows, n_cols), None, seconds)


def harvest_journal(paths: Iterable[str],
                    corpus: Optional[CostCorpus] = None) -> int:
    """Lift block-runtime training rows out of sweep-journal files whose
    records carry the ``facts`` stamp (one row per unique block, not per
    config — the block ran as ONE program). Appends into `corpus` (or
    the active one) and returns how many rows were added. Unreadable
    files and fact-less records (pre-PR-9 journals) are skipped.

    Idempotent against the corpus: blocks whose ``block_key`` is
    already recorded — by a previous harvest, or LIVE by the run that
    wrote the journal (the sweep stamps its corpus rows with the same
    key) — are skipped, so re-running the harvest CLI never duplicates
    training rows. (A block with identical grids re-measured in a
    LATER run records live under the same key; its journal harvest is
    skipped as redundant — harvesting is a backfill for runs whose
    live rows were lost, not a second measurement channel.)"""
    corpus = corpus if corpus is not None else get_corpus()
    if corpus is None:
        return 0
    added = 0
    seen: set = {r.get("block_key")
                 for r in corpus.rows("block_runtime")} - {None}
    for path in paths:
        try:
            with open(path, encoding="utf-8", errors="replace") as fh:
                lines = fh.readlines()
        except OSError:
            continue
        for line in lines:
            line = line.strip()
            if not line:
                continue
            try:
                rec = json.loads(line)
            except ValueError:
                continue
            facts = rec.get("facts") if isinstance(rec, dict) else None
            if not isinstance(facts, dict):
                continue
            block_key = facts.get("block_key")
            block_s = facts.get("block_s")
            if block_key in seen or not isinstance(block_s, (int, float)):
                continue
            seen.add(block_key)
            feats = {k: float(v) for k, v in facts.items()
                     if k not in ("block_key", "block_s")
                     and isinstance(v, (int, float))}
            if corpus.append("block_runtime", feats, float(block_s),
                             source="journal", block_key=block_key):
                added += 1
    return added


def main(argv=None) -> int:
    """``python -m transmogrifai_tpu.perf.corpus <journal files/dirs>`` —
    harvest journal records into the active corpus and print a summary."""
    import argparse
    import glob as _glob
    parser = argparse.ArgumentParser(
        prog="python -m transmogrifai_tpu.perf.corpus",
        description="harvest sweep-journal records into the perf corpus")
    parser.add_argument("paths", nargs="+",
                        help="journal files, or directories to scan for "
                             "*.journal* files")
    args = parser.parse_args(argv)
    files: List[str] = []
    for p in args.paths:
        if os.path.isdir(p):
            files.extend(sorted(_glob.glob(os.path.join(
                _glob.escape(p), "*.journal*"))))
        else:
            files.append(p)
    corpus = get_corpus()
    if corpus is None:
        print(json.dumps({"error": "perf model disabled "
                                   "(TRANSMOGRIFAI_PERF_MODEL=0)"}))
        return 1
    added = harvest_journal(files, corpus)
    print(json.dumps({"harvested_rows": added, "corpus": corpus.path,
                      "total_rows": len(corpus)}))
    return 0


if __name__ == "__main__":
    import sys
    sys.exit(main())
