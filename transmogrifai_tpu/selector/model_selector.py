"""ModelSelector: cross-validated model + hyperparameter selection.

Reference parity: `core/.../selector/ModelSelector.scala:72-211` (prep data
→ findBestEstimator → refit best on full prepared train → evaluate → wrap
SelectedModel + ModelSelectorSummary), factories
`BinaryClassificationModelSelector.scala:49-224`,
`MultiClassificationModelSelector`, `RegressionModelSelector.scala`,
defaults `DefaultSelectorParams.scala:35-90`.

The sweep (folds × models × grids) runs through
`transmogrifai_tpu.parallel.sweep.run_sweep` — vmapped/batched XLA programs
instead of the reference's Future-per-fit thread pool.
"""

from __future__ import annotations

import logging
from dataclasses import dataclass, field
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.data.columns import Column
from transmogrifai_tpu.evaluators import (
    BinaryClassificationEvaluator, MultiClassificationEvaluator,
    RegressionEvaluator)
from transmogrifai_tpu.models import OpLinearRegression, OpLogisticRegression
from transmogrifai_tpu.obs.trace import TRACER, pull, upload
from transmogrifai_tpu.parallel.sweep import run_sweep
from transmogrifai_tpu.selector.splitters import DataBalancer, DataCutter, DataSplitter
from transmogrifai_tpu.selector.validators import OpCrossValidation
from transmogrifai_tpu.stages.base import Estimator, FitContext, Transformer

log = logging.getLogger(__name__)

# Families sweep on a thread pool of the reference's width (Parallelism=8,
# the Future-per-fit pool of OpValidator.scala:374).
_FAMILY_THREADS = 8


@dataclass
class ValidationResult:
    model: str
    grid: Dict[str, Any]
    fold_metrics: List[float]
    model_index: int = 0  # index into ModelSelector.models (class names can repeat)

    @property
    def mean_metric(self) -> float:
        return float(np.mean(self.fold_metrics)) if self.fold_metrics else float("nan")

    def to_json(self) -> Dict:
        return {"model": self.model, "model_index": self.model_index,
                "grid": self.grid, "fold_metrics": self.fold_metrics,
                "mean": self.mean_metric}


@dataclass
class ModelSelectorSummary:
    """ModelSelectorSummary.scala analogue, persisted on the fitted model."""

    problem_type: str
    metric_name: str
    validation_results: List[ValidationResult] = field(default_factory=list)
    best_model: str = ""
    best_grid: Dict[str, Any] = field(default_factory=dict)
    train_metrics: Dict[str, Any] = field(default_factory=dict)
    holdout_metrics: Dict[str, Any] = field(default_factory=dict)
    splitter_summary: Dict[str, Any] = field(default_factory=dict)
    larger_is_better: bool = True

    def to_json(self) -> Dict:
        return {
            "problem_type": self.problem_type, "metric": self.metric_name,
            "validation_results": [r.to_json() for r in self.validation_results],
            "best_model": self.best_model, "best_grid": self.best_grid,
            "train_metrics": self.train_metrics,
            "holdout_metrics": self.holdout_metrics,
            "splitter": self.splitter_summary,
        }

    def pretty(self) -> str:
        sign = -1.0 if self.larger_is_better else 1.0  # best first
        lines = [f"Evaluated {len(self.validation_results)} model configs "
                 f"({self.metric_name}):"]
        for r in sorted(self.validation_results, key=lambda r: sign * r.mean_metric):
            lines.append(f"  {r.model} {r.grid} -> {r.mean_metric:.4f}")
        lines.append(f"Best: {self.best_model} {self.best_grid}")
        return "\n".join(lines)


class ModelSelector(Estimator):
    """Estimator2(RealNN, OPVector) → Prediction. Fits the sweep, refits the
    winner on the full prepared training data, evaluates train + holdout."""

    in_types = (T.RealNN, T.OPVector)
    out_type = T.Prediction
    response_aware = True  # slot 0 is the label

    def __init__(self, models: Sequence[Tuple[Estimator, List[Dict]]],
                 validator=None, splitter=None, evaluator=None,
                 problem_type: str = "binary", uid: Optional[str] = None,
                 checkpoint_dir: Optional[str] = None,
                 n_classes: Optional[int] = None):
        super().__init__(uid=uid)
        self.models = list(models)
        # a classifier's number of classes is a compiled shape of every
        # sweep program: stated here (a configuration knows its label) it
        # stays put when a table's rarest label does not fall; left None
        # it is read off the label once a fit (`fit_model`)
        self.n_classes = n_classes
        self.validator = validator or OpCrossValidation()
        self.splitter = splitter
        self.evaluator = evaluator or BinaryClassificationEvaluator()
        self.problem_type = problem_type
        # sweep checkpointing (SURVEY.md §5.4 — the reference has no
        # mid-sweep resume; long TPU sweeps need one): per-family metric
        # matrices persist as JSON after each family completes, and a
        # per-block SweepJournal (runtime/journal.py) persists each grid
        # config's fold metrics AS THE SWEEP RUNS — both keyed by a
        # signature of the family + grids + data content + folds + seed,
        # so a killed sweep resumes at the first un-journaled block
        self.checkpoint_dir = checkpoint_dir
        self.checkpoint_fsync = True  # journal durability (tests may relax)

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        label_col, vec_col = cols
        # the fit's four phases are sibling spans under the caller's
        # `stage:fit:*`: prepare, sweep, refit, evaluate
        with TRACER.span("selector:prepare", category="selector"):
            y_np = np.asarray(pull("selector:label", label_col.data["value"]),
                              dtype=np.float64)
            X_full = upload("selector:matrix", vec_col.device_value())

            # -- data preparation (Splitter.split + preValidationPrepare) #
            split_summary: Dict[str, Any] = {}
            if self.splitter is not None:
                train_idx, test_idx, ssum = self.splitter.split(y_np)
                train_idx, prep_details = self.splitter.prepare(
                    y_np, train_idx)
                split_summary = ssum.to_json()
                split_summary["details"].update(prep_details)
            else:
                train_idx = np.arange(len(y_np))
                test_idx = np.array([], dtype=np.int64)
            if self.problem_type != "regression":
                # the one place the number of classes is read off the
                # label (the host column the split just used)
                ctx.n_classes = int(self.n_classes or max(
                    int(y_np.max(initial=0)) + 1, 2))

            X = X_full[upload("selector:rows", train_idx)]
            y_train = y_np[train_idx]
            y_dev = upload("selector:label", y_train.astype(np.float32))
            folds = self.validator.splits(y_train)
            data_digest = (self._data_digest(X, y_dev)
                           if ctx.cv_refit is None
                           and self.checkpoint_dir is not None else None)

        with TRACER.span("selector:sweep", category="selector",
                         classes=ctx.n_classes or 0,
                         problem=self.problem_type):
            results, failures = self._sweep(
                ctx, X, y_dev, folds, train_idx, data_digest)
        # the sweep's padded/sharded data and binned matrices die with it:
        # the refit bins for itself, and on a wide table they are gigabytes
        ctx._sweep_data_cache = ctx._sweep_bin_cache = None
        if not results:
            raise RuntimeError(
                f"All {failures} model families failed during validation")

        sign = 1.0 if self.evaluator.is_larger_better else -1.0
        finite = [r for r in results if np.isfinite(r.mean_metric)]
        return self._finish(ctx, results, finite, sign, X, X_full, y_np,
                            y_dev, train_idx, test_idx, split_summary)

    def _sweep(self, ctx, X, y_dev, folds, train_idx, data_digest):
        """Validate every family's grid over the folds; returns
        (results, number of families that failed)."""
        # -- the sweep --------------------------------------------------- #
        sharding = None
        use_scheduler = False
        if ctx.mesh is not None:
            import os as _os
            from transmogrifai_tpu.parallel.mesh import (
                SWEEP_AXIS, sweep_sharding)
            # a >1-wide sweep axis runs the distributed work-stealing
            # scheduler (parallel/scheduler.py): grid blocks partition
            # across the mesh's sweep rows, per-worker journal shards
            # form the shared completion log, and each worker's blocks
            # execute the exact single-device programs (bit-identical
            # winner). TRANSMOGRIFAI_DISTRIBUTED_SWEEP=0 falls back to
            # the grid-axis vmap sharding path.
            use_scheduler = (
                ctx.cv_refit is None
                and dict(ctx.mesh.shape).get(SWEEP_AXIS, 1) > 1
                and _os.environ.get(
                    "TRANSMOGRIFAI_DISTRIBUTED_SWEEP", "1") != "0")
            if not use_scheduler:  # spread the grid axis across the mesh
                sharding = sweep_sharding(ctx.mesh)
        results: List[ValidationResult] = []
        failures = 0
        if ctx.cv_refit is None:
            # family jobs run on pool threads with no inherited span
            # context: parent each family span explicitly so sweep-block
            # spans nest under the caller's run/stage span
            _sweep_parent = TRACER.current()

            def run_family(mi_est_grids):
                mi, (est, grids) = mi_est_grids
                with TRACER.span(f"sweep:family:{type(est).__name__}",
                                 category="sweep_family",
                                 parent=_sweep_parent, grids=len(grids)):
                    sig, ckpt, cached = self._checkpoint_lookup(
                        mi, est, grids, X, data_digest, folds, ctx)
                    if cached is not None:
                        return cached
                    # block-granular journal: completed grid blocks
                    # persist as the sweep runs, so a kill ANYWHERE
                    # inside the family resumes at the first
                    # un-journaled block instead of re-running the
                    # family from scratch
                    journal = self._journal_for(mi, est, sig)
                    grid_fold = self._run_sweep_with_retry(
                        est, grids, X, y_dev, folds, ctx, sharding,
                        journal=journal)
                    self._save_checkpoint(ckpt, grid_fold)
                    return grid_fold

            # Families run on a thread pool (`_FAMILY_THREADS`): device
            # executions serialize on the chip anyway, but one family's
            # XLA compiles overlap another's compiles AND executions —
            # the dominant cold-process cost. Threads only help a fresh
            # process; a warm compile cache degrades gracefully to
            # interleaved execution.
            from concurrent.futures import ThreadPoolExecutor
            par = min(len(self.models), _FAMILY_THREADS)
            if use_scheduler:
                outcomes = self._sweep_scheduled(
                    ctx, X, y_dev, folds, data_digest)
            elif par > 1 and sharding is None and len(self.models) > 1:
                with ThreadPoolExecutor(max_workers=par) as pool:
                    futs = [pool.submit(run_family, (mi, mg))
                            for mi, mg in enumerate(self.models)]
                    outcomes = []
                    for f in futs:
                        try:
                            outcomes.append(f.result())
                        except Exception as e:
                            outcomes.append(e)
            else:
                outcomes = []
                for mi, mg in enumerate(self.models):
                    try:
                        outcomes.append(run_family((mi, mg)))
                    except Exception as e:
                        outcomes.append(e)
            for mi, ((est, grids), out) in enumerate(
                    zip(self.models, outcomes)):
                if isinstance(out, Exception):
                    # drop failing family (OpValidator.scala:344-347)
                    failures += 1
                    log.error("Model family %s failed; dropping from sweep",
                              type(est).__name__, exc_info=out)
                    continue
                for grid, fm in zip(grids, out):
                    results.append(ValidationResult(
                        model=type(est).__name__, grid=grid,
                        fold_metrics=[float(m) for m in fm], model_index=mi))
        else:
            results, failures = self._sweep_with_workflow_cv(
                ctx, folds, train_idx, y_dev, sharding)
        return results, failures

    def _sweep_scheduled(self, ctx, X, y_dev, folds, data_digest):
        """Distributed sweep: ALL families' grid blocks go into ONE
        work-stealing schedule over the mesh (parallel/scheduler.py) —
        one queue packs the mesh better than per-family fan-out, and a
        straggling tree family's blocks spread over lanes that finished
        their linear families. Per-family checkpoints still short-
        circuit whole families; per-worker journal shards
        (``<family>.journal-w<k>.jsonl``) are the shared completion log
        for steal/resume decisions. Returns one outcome per family
        (metric matrix, or the Exception that failed it)."""
        from transmogrifai_tpu.parallel.scheduler import (
            GridScheduler, SweepJob)

        outcomes: List[Any] = [None] * len(self.models)
        jobs, meta = [], []
        for mi, (est, grids) in enumerate(self.models):
            sig, ckpt, cached = self._checkpoint_lookup(
                mi, est, grids, X, data_digest, folds, ctx)
            if cached is not None:
                outcomes[mi] = cached
                continue
            jobs.append(SweepJob(
                index=mi, est=est, grids=grids,
                journal=self._journal_for(mi, est, sig, sharded=True),
                name=type(est).__name__,
                # per-block transient-error retry: distribution must not be
                # LESS fault-tolerant than the single-device family path
                run=self._block_runner(type(est).__name__)))
            meta.append((mi, ckpt))
        if jobs:
            import os as _os
            pod_store = _os.environ.get("TRANSMOGRIFAI_POD_STORE")
            if pod_store:
                # pod tier (parallel/pod.py): this process is ONE HOST of
                # a multi-host sweep — every host env-points at the same
                # store dir + sweep id and races block claims through the
                # shared lease table. Requires journals (checkpoint_dir),
                # which double as the cross-host completion log.
                from transmogrifai_tpu.parallel.scheduler import (
                    HostScheduler)
                workers = _os.environ.get("TRANSMOGRIFAI_POD_WORKERS")
                sched = HostScheduler(
                    pod_store,
                    _os.environ.get("TRANSMOGRIFAI_POD_HOST",
                                    f"h{_os.getpid()}"),
                    sweep_id=_os.environ.get(
                        "TRANSMOGRIFAI_POD_SWEEP", "pod"),
                    mesh=ctx.mesh,
                    n_workers=int(workers) if workers else None,
                    lease_ttl_s=float(_os.environ.get(
                        "TRANSMOGRIFAI_POD_TTL_S", "30") or 30))
            else:
                sched = GridScheduler(mesh=ctx.mesh)
            for (mi, ckpt), out in zip(meta, sched.run(
                    jobs, X, y_dev, folds, self.evaluator, ctx)):
                outcomes[mi] = out
                if not isinstance(out, Exception):
                    self._save_checkpoint(ckpt, out)
        return outcomes

    def _block_runner(self, family: str):
        """run_sweep wrapped in the transient-error RetryPolicy, one policy
        per family job (attempt budgets must not pool across blocks of
        different families). Used as `SweepJob.run` by the scheduler;
        completed grids inside a retried block skip via the journal."""
        policy = self._sweep_retry_policy()

        def run_block(*args, **kwargs):
            return policy.call(run_sweep, *args,
                               label=f"sweep.{family}", **kwargs)
        return run_block

    @staticmethod
    def _sweep_retry_policy(retries: int = 2):
        """Retries ONLY errors that declare themselves `transient=True`
        (injected faults, transport layers that know). A compile error
        or a device runtime fault on a directly attached chip is
        deterministic: it surfaces on the first attempt and the
        family-drop policy sees it, instead of being re-run three times
        with back-off. Shared by the single-device family path AND the
        distributed scheduler's per-block runner — the persistent
        compile cache plus the block journal make a retry cheap
        (journaled blocks are skipped)."""
        from transmogrifai_tpu.runtime.retry import RetryPolicy
        return RetryPolicy(max_attempts=retries + 1, base_delay_s=3.0,
                           max_delay_s=10.0, backoff=1.5,
                           transient_types=())

    def _run_sweep_with_retry(self, est, grids, X, y_dev, folds, ctx,
                              sharding, retries: int = 2, journal=None):
        """Family sweep behind the transient-error RetryPolicy; only after
        exhaustion does the family-drop fault tolerance
        (OpValidator.scala:344-347 parity) take over."""
        return self._sweep_retry_policy(retries).call(
            run_sweep, est, grids, X, y_dev, folds, self.evaluator, ctx,
            sharding=sharding, journal=journal,
            label=f"sweep.{type(est).__name__}")

    # -- sweep checkpointing ------------------------------------------- #

    def _checkpoint_lookup(self, mi, est, grids, X, data_digest, folds, ctx):
        """(sig, ckpt_path, cached matrix-or-None) for one family — the
        ONE source of checkpoint-hit semantics for both the
        single-device family path and the distributed scheduler."""
        sig = self._sweep_signature(
            mi, est, grids, X, data_digest, folds, ctx)
        ckpt = self._checkpoint_path(mi, est, sig)
        cached = self._load_checkpoint(ckpt)
        if cached is not None:
            log.info("sweep checkpoint hit: %s (%d grids)",
                     type(est).__name__, len(cached))
        return sig, ckpt, cached

    @staticmethod
    def _data_digest(X, y) -> Optional[str]:
        """sha256 of the training data bytes, computed ONCE per fit (the
        device→host materialization is shared by every family's key)."""
        import hashlib
        try:
            hasher = hashlib.sha256()
            hasher.update(np.ascontiguousarray(np.asarray(X)).tobytes())
            hasher.update(np.ascontiguousarray(np.asarray(y)).tobytes())
            return hasher.hexdigest()
        except Exception:
            return None

    def _sweep_signature(self, mi, est, grids, X, data_digest, folds,
                         ctx) -> Optional[str]:
        """Hash of everything that determines the metric matrix: family +
        params + grids, the TRAINING DATA CONTENT (the digest of X and y
        bytes — same-shaped different data must miss), the fold
        structure, the evaluator class + metric, and the fit seed. Keys
        both the per-family checkpoint file and the per-block journal.
        Never raises: checkpointing is an optimization, so any failure
        degrades to 'no checkpoint'."""
        if self.checkpoint_dir is None or data_digest is None:
            return None
        import hashlib
        import json as _json
        try:
            val = self.validator
            sig = _json.dumps({
                "family": type(est).__name__, "index": mi,
                "params": {k: repr(v) for k, v in sorted(est.params.items())
                           if k != "uid"},
                "grids": grids, "shape": list(map(int, X.shape)),
                "data": data_digest,
                "folds": len(folds),
                "validator": [type(val).__name__,
                              getattr(val, "n_folds", None),
                              getattr(val, "train_ratio", None),
                              getattr(val, "seed", None)],
                "seed": getattr(ctx, "seed", None),
                "evaluator": [type(self.evaluator).__name__,
                              getattr(self.evaluator, "metric", None)],
            }, sort_keys=True, default=repr)
            return hashlib.sha256(sig.encode()).hexdigest()[:16]
        except Exception:
            log.warning("sweep checkpointing disabled for this fit "
                        "(signature failed)", exc_info=True)
            return None

    def _checkpoint_path(self, mi, est, sig) -> Optional[str]:
        if self.checkpoint_dir is None or sig is None:
            return None
        import os
        try:
            os.makedirs(self.checkpoint_dir, exist_ok=True)
        except OSError:
            log.warning("sweep checkpointing disabled for this fit "
                        "(checkpoint_dir unusable)", exc_info=True)
            return None
        return os.path.join(self.checkpoint_dir,
                            f"sweep_{mi}_{type(est).__name__}_{sig}.json")

    def _journal_for(self, mi, est, sig, sharded: bool = False):
        """Open (or resume) the family's block journal beside the family
        checkpoint. `sharded=True` (the distributed scheduler) returns a
        `ShardedSweepJournal`: per-worker ``-w<k>.jsonl`` shard files
        merged on read, so concurrent workers never share an append fd
        (and a pre-existing single-file journal at the base path still
        merges in read-only). Never raises — an unusable journal
        degrades to family-level resume granularity."""
        if self.checkpoint_dir is None or sig is None:
            return None
        import os

        from transmogrifai_tpu.runtime.journal import (
            ShardedSweepJournal, SweepJournal)
        try:
            os.makedirs(self.checkpoint_dir, exist_ok=True)
            path = os.path.join(
                self.checkpoint_dir,
                f"sweep_{mi}_{type(est).__name__}_{sig}.journal")
            # resume symmetry: a single-device resume of a MESH-journaled
            # sweep must read the shard files too, or every block the
            # mesh completed re-runs (appends then go to shard 0)
            cls = (ShardedSweepJournal
                   if sharded or ShardedSweepJournal.has_shards(path)
                   else SweepJournal)
            return cls(path, meta={"sig": sig},
                       fsync=getattr(self, "checkpoint_fsync", True))
        except Exception:
            log.warning("sweep journal unusable; family-level resume only",
                        exc_info=True)
            return None

    @staticmethod
    def _load_checkpoint(path: Optional[str]):
        import json as _json
        import os
        if path is None or not os.path.exists(path):
            return None
        try:
            with open(path) as f:
                return _json.load(f)["grid_fold"]
        except Exception:
            log.warning("unreadable sweep checkpoint %s; re-running", path)
            return None

    @staticmethod
    def _save_checkpoint(path: Optional[str], grid_fold) -> None:
        if path is None:
            return
        import json as _json
        import os
        try:
            tmp = path + ".tmp"
            with open(tmp, "w") as f:
                _json.dump({"grid_fold": [[float(m) for m in row]
                                          for row in grid_fold]}, f)
            os.replace(tmp, path)  # atomic: a killed write never half-loads
        except OSError:
            log.warning("could not write sweep checkpoint %s", path,
                        exc_info=True)

    def _sweep_with_workflow_cv(self, ctx, folds, train_idx, y_dev, sharding):
        """Workflow-level CV (OpWorkflowCore.withWorkflowCV → cutDAG,
        FitStagesUtil.scala:302-367; in-fold applyDAG OpValidator.scala:250):
        re-fit the pre-selector feature-engineering DAG on each fold's
        training rows via `ctx.cv_refit`, then sweep each family fold by
        fold on the fold-specific matrix — fold-global statistics cannot
        leak into validation metrics."""
        import jax.numpy as jnp

        per_family: Dict[int, List[List[float]]] = {}
        dead: set = set()
        # fold-outer so all families in one fold share the sweep data cache;
        # the fold matrix is built AND consumed inside the loop — only one
        # fold's refit matrix is alive at a time (bounds device memory to
        # the plain sweep's footprint)
        for fi, (tr, va) in enumerate(folds):
            fold_rows = train_idx[np.asarray(tr) > 0.5]
            X_fold = jnp.asarray(
                np.asarray(ctx.cv_refit(fold_rows))[train_idx])
            for mi, (est, grids) in enumerate(self.models):
                if mi in dead:
                    continue
                try:
                    gm = run_sweep(est, grids, X_fold, y_dev, [(tr, va)],
                                   self.evaluator, ctx, sharding=sharding)
                except Exception:
                    dead.add(mi)
                    per_family.pop(mi, None)
                    log.exception(
                        "Model family %s failed in fold %d; dropping",
                        type(est).__name__, fi)
                    continue
                rows = per_family.setdefault(
                    mi, [[] for _ in range(len(grids))])
                for gi, row in enumerate(gm):
                    rows[gi].append(float(row[0]))
        results: List[ValidationResult] = []
        for mi, (est, grids) in enumerate(self.models):
            if mi in per_family:
                for grid, fm in zip(grids, per_family[mi]):
                    results.append(ValidationResult(
                        model=type(est).__name__, grid=grid,
                        fold_metrics=fm, model_index=mi))
        return results, len(dead)

    def _finish(self, ctx, results, finite, sign, X, X_full, y_np, y_dev,
                train_idx, test_idx, split_summary):
        if not finite:
            raise RuntimeError(
                "Every validated config produced a non-finite metric")
        best = max(finite, key=lambda r: sign * r.mean_metric)

        # -- refit winner on full prepared train ------------------------- #
        best_est_proto = self.models[best.model_index][0]
        kwargs = {k: v for k, v in best_est_proto.params.items() if k != "uid"}
        kwargs.update(best.grid)
        best_est = type(best_est_proto)(**kwargs)
        with TRACER.span("selector:refit", category="selector",
                         model=best.model):
            model = best_est.fit_arrays(
                X, y_dev, jnp.ones_like(y_dev), ctx)

        # -- evaluate train + holdout ------------------------------------ #
        # on the device where the evaluator can and counts stay exact in
        # float32: only a (K, K) table (a regressor's few sums) crosses
        # to the host, not (n, K) probabilities or an (n,) prediction
        on_device = getattr(self.evaluator, "evaluate_device", None)
        if max(len(train_idx), len(test_idx)) >= (1 << 24):
            on_device = None

        def _eval(idx: np.ndarray, rows=None, y=None) -> Dict[str, Any]:
            if len(idx) == 0:
                return {}
            if rows is None:
                rows = X_full[upload("evaluate:rows", idx)]
            pred = model.predict_arrays(rows)
            if on_device is not None:
                if y is None:
                    y = upload("evaluate:label", y_np[idx], jnp.float32)
                m = on_device(y, pred, ctx.n_classes)
            else:
                pcol = Column(T.Prediction,
                              {k: np.asarray(pull(f"evaluate:{k}", v))
                               for k, v in pred.items()})
                lcol = Column(T.RealNN, {
                    "value": y_np[idx],
                    "mask": np.ones(len(idx), dtype=bool)})
                m = self.evaluator.evaluate(lcol, pcol)
            # scalars, and a multiclass evaluator's (K, K) table of
            # counts: the summary keeps the table its metrics came from
            return {k: v for k, v in m.to_json().items()
                    if k == "Confusion" or not isinstance(v, list)}

        with TRACER.span("selector:evaluate", category="selector",
                         on_device=on_device is not None):
            # the prepared train rows are at hand: a second gather of them
            # is a table-sized buffer (and its scratch) for nothing
            train_metrics = _eval(train_idx, X, y_dev)
            holdout_metrics = _eval(test_idx)
        summary = ModelSelectorSummary(
            problem_type=self.problem_type,
            metric_name=self.evaluator.default_metric,
            validation_results=results, best_model=best.model,
            best_grid=best.grid, train_metrics=train_metrics,
            holdout_metrics=holdout_metrics, splitter_summary=split_summary,
            larger_is_better=self.evaluator.is_larger_better)
        model.summary = summary
        return model


# --------------------------------------------------------------------------- #
# Factories (ModelSelectorFactory + per-problem selectors)                    #
# --------------------------------------------------------------------------- #

# the reference's shared grid axes (DefaultSelectorParams.scala:35-76)
_REGULARIZATION = (0.001, 0.01, 0.1, 0.2)
_ELASTIC_NET = (0.1, 0.5)
_MAX_DEPTH = (3, 6, 12)
_MIN_INFO_GAIN = (0.001, 0.01, 0.1)
_MIN_INSTANCES = (10.0, 100.0)


def _lr_grid() -> List[Dict]:
    """LR/linear: ElasticNet {0.1, 0.5} × Regularization {0.001..0.2} = 8."""
    return [{"reg_param": r, "elastic_net_param": a}
            for a in _ELASTIC_NET for r in _REGULARIZATION]


def _rf_grid() -> List[Dict]:
    """RF/DT: MaxDepth × MinInfoGain × MinInstancesPerNode = 18."""
    return [{"max_depth": d, "min_info_gain": g, "min_instances_per_node": m}
            for d in _MAX_DEPTH for g in _MIN_INFO_GAIN
            for m in _MIN_INSTANCES]


def _default_binary_models() -> List[Tuple[Estimator, List[Dict]]]:
    """Reference defaults: LR + RF + XGB
    (BinaryClassificationModelSelector.scala:62-64, grids :70-137): LR 8
    elastic-net configs at maxIter 50, RF 18 tree-shape configs at
    numTrees 50, XGB numRound 200 / eta 0.02 / depth 10 / gamma 0.8 /
    early stopping 20 × minChildWeight {1, 10} — 28 configs total."""
    from transmogrifai_tpu.models import (
        OpRandomForestClassifier, OpXGBoostClassifier)
    xgb_grid = [{"min_child_weight": m} for m in (1.0, 10.0)]
    return [(OpLogisticRegression(max_iter=50), _lr_grid()),
            (OpRandomForestClassifier(n_trees=50), _rf_grid()),
            (OpXGBoostClassifier(n_estimators=200, eta=0.02, max_depth=10,
                                 gamma=0.8, early_stopping_rounds=20),
             xgb_grid)]


def _default_multiclass_models() -> List[Tuple[Estimator, List[Dict]]]:
    """LR + RF (MultiClassificationModelSelector.scala:61-88) — 26 configs."""
    from transmogrifai_tpu.models import OpRandomForestClassifier
    return [(OpLogisticRegression(max_iter=50), _lr_grid()),
            (OpRandomForestClassifier(n_trees=50), _rf_grid())]


def _default_regression_models() -> List[Tuple[Estimator, List[Dict]]]:
    """Linear + RF + GBT (RegressionModelSelector.scala:61-99): linear 8
    elastic-net configs, RF 18, Spark-GBT 18 at maxIter 20 / stepSize 0.1
    — 44 configs total."""
    from transmogrifai_tpu.models import (
        OpGBTRegressor, OpRandomForestRegressor)
    return [(OpLinearRegression(), _lr_grid()),
            (OpRandomForestRegressor(n_trees=50), _rf_grid()),
            (OpGBTRegressor(n_estimators=20, learning_rate=0.1), _rf_grid())]


class BinaryClassificationModelSelector:
    """`BinaryClassificationModelSelector.with_cross_validation()` factory
    (BinaryClassificationModelSelector.scala:170)."""

    @staticmethod
    def with_cross_validation(
            models: Optional[Sequence[Tuple[Estimator, List[Dict]]]] = None,
            n_folds: int = 3, validation_metric: str = "AuPR",
            splitter=None, seed: int = 42,
            checkpoint_dir: Optional[str] = None) -> ModelSelector:
        return ModelSelector(
            models=models or _default_binary_models(),
            validator=OpCrossValidation(n_folds=n_folds, seed=seed),
            splitter=splitter if splitter is not None else DataBalancer(seed=seed),
            evaluator=BinaryClassificationEvaluator(metric=validation_metric),
            problem_type="binary", checkpoint_dir=checkpoint_dir)

    @staticmethod
    def with_train_validation_split(
            models: Optional[Sequence[Tuple[Estimator, List[Dict]]]] = None,
            train_ratio: float = 0.75, validation_metric: str = "AuPR",
            splitter=None, seed: int = 42,
            checkpoint_dir: Optional[str] = None) -> ModelSelector:
        from transmogrifai_tpu.selector.validators import OpTrainValidationSplit
        return ModelSelector(
            models=models or _default_binary_models(),
            validator=OpTrainValidationSplit(train_ratio=train_ratio, seed=seed),
            splitter=splitter if splitter is not None else DataBalancer(seed=seed),
            evaluator=BinaryClassificationEvaluator(metric=validation_metric),
            problem_type="binary", checkpoint_dir=checkpoint_dir)


class MultiClassificationModelSelector:
    @staticmethod
    def with_cross_validation(
            models: Optional[Sequence[Tuple[Estimator, List[Dict]]]] = None,
            n_folds: int = 3, validation_metric: str = "F1",
            splitter=None, seed: int = 42,
            checkpoint_dir: Optional[str] = None,
            n_classes: Optional[int] = None) -> ModelSelector:
        return ModelSelector(
            models=models or _default_multiclass_models(),
            validator=OpCrossValidation(n_folds=n_folds, seed=seed),
            splitter=splitter if splitter is not None else DataCutter(seed=seed),
            evaluator=MultiClassificationEvaluator(metric=validation_metric),
            problem_type="multiclass", checkpoint_dir=checkpoint_dir,
            n_classes=n_classes)

    @staticmethod
    def with_train_validation_split(
            models: Optional[Sequence[Tuple[Estimator, List[Dict]]]] = None,
            train_ratio: float = 0.75, validation_metric: str = "F1",
            splitter=None, seed: int = 42,
            checkpoint_dir: Optional[str] = None,
            n_classes: Optional[int] = None) -> ModelSelector:
        from transmogrifai_tpu.selector.validators import OpTrainValidationSplit
        return ModelSelector(
            models=models or _default_multiclass_models(),
            validator=OpTrainValidationSplit(train_ratio=train_ratio, seed=seed),
            splitter=splitter if splitter is not None else DataCutter(seed=seed),
            evaluator=MultiClassificationEvaluator(metric=validation_metric),
            problem_type="multiclass", checkpoint_dir=checkpoint_dir,
            n_classes=n_classes)


class RegressionModelSelector:
    @staticmethod
    def with_cross_validation(
            models: Optional[Sequence[Tuple[Estimator, List[Dict]]]] = None,
            n_folds: int = 3, validation_metric: str = "RMSE",
            splitter=None, seed: int = 42,
            checkpoint_dir: Optional[str] = None) -> ModelSelector:
        return ModelSelector(
            models=models or _default_regression_models(),
            validator=OpCrossValidation(n_folds=n_folds, seed=seed),
            splitter=splitter if splitter is not None else DataSplitter(seed=seed),
            evaluator=RegressionEvaluator(metric=validation_metric),
            problem_type="regression", checkpoint_dir=checkpoint_dir)

    @staticmethod
    def with_train_validation_split(
            models: Optional[Sequence[Tuple[Estimator, List[Dict]]]] = None,
            train_ratio: float = 0.75, validation_metric: str = "RMSE",
            splitter=None, seed: int = 42,
            checkpoint_dir: Optional[str] = None) -> ModelSelector:
        from transmogrifai_tpu.selector.validators import OpTrainValidationSplit
        return ModelSelector(
            models=models or _default_regression_models(),
            validator=OpTrainValidationSplit(train_ratio=train_ratio, seed=seed),
            splitter=splitter if splitter is not None else DataSplitter(seed=seed),
            evaluator=RegressionEvaluator(metric=validation_metric),
            problem_type="regression", checkpoint_dir=checkpoint_dir)
