"""Workflow engine: fit the feature DAG layer-by-layer, score, save/load.

Reference parity: `core/.../OpWorkflow.scala:61-588` (train),
`OpWorkflowModel.scala:60-455` (score/evaluate/save),
`FitStagesUtil.scala:51-369` (layered DAG fit + fused layer transforms).

TPU-first: fitting walks the layered DAG on host, dispatching estimator fits
(which internally run jitted reductions/optimizers); transforms execute
eagerly during fit so estimators see materialized inputs. Scoring uses the
same walk (`_execute`) or the fused `CompiledScorer` (workflow/compiled.py)
that runs every jittable stage in ONE XLA program.
"""

from __future__ import annotations

import json
import logging
import os
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

_log = logging.getLogger(__name__)

from transmogrifai_tpu.data.columns import TEXT, Column
from transmogrifai_tpu.data.dataset import Dataset
from transmogrifai_tpu.features.dag import clone_graph, topological_layers
from transmogrifai_tpu.obs.metrics import get_registry
from transmogrifai_tpu.obs.trace import TRACER
from transmogrifai_tpu.stages.base import (
    Estimator, FeatureGeneratorStage, FitContext, Stage, Transformer)


def _validate_or_raise(result_features, strict: bool, where: str) -> None:
    """Run the static opcheck pass; raise on errors under strict, else log.
    Warnings are always logged (they never block)."""
    import logging

    from transmogrifai_tpu.analysis.opcheck import validate_graph

    log = logging.getLogger(__name__)
    report = validate_graph(result_features)
    if report.errors and strict:
        report.raise_if_errors()
    for issue in report.errors:
        log.warning("opcheck (%s, strict=False): %s", where, issue)
    for issue in report.warnings:
        log.info("opcheck (%s): %s", where, issue)


class Workflow:
    """Declarative workflow: wire result features, then `train()`."""

    def __init__(self):
        self.result_features: Tuple = ()
        self._dataset: Optional[Dataset] = None
        self._reader = None
        self.parameters: Dict[str, Any] = {}
        self._rff = None
        self._rff_score_source = None
        self.blocklist: List[str] = []
        self._workflow_cv = False
        self._warm_models: Dict[str, Transformer] = {}

    def set_result_features(self, *features) -> "Workflow":
        self.result_features = tuple(features)
        return self

    def set_input_dataset(self, dataset: Dataset) -> "Workflow":
        self._dataset = dataset
        return self

    def set_reader(self, reader) -> "Workflow":
        self._reader = reader
        return self

    def set_parameters(self, params) -> "Workflow":
        """Accepts an OpParams or a plain dict; stage_params overrides are
        applied to the DAG's stages at train time
        (OpWorkflow.setParameters, OpWorkflow.scala:179-201)."""
        from transmogrifai_tpu.workflow.params import OpParams
        if isinstance(params, OpParams):
            self.parameters = params.to_json()
        else:
            self.parameters = dict(params)
        return self

    def with_model_stages(self, model: "WorkflowModel",
                          exclude: Sequence[str] = ()) -> "Workflow":
        """Warm start (OpWorkflow.withModelStages, OpWorkflow.scala:468-472):
        estimators whose uid matches a fitted stage in `model` reuse that
        fitted transformer instead of refitting — only new estimators
        train. `exclude` uids REFIT even when a fitted stage exists —
        the continual-refit path reuses every feature-engineering fit
        but re-trains the predictor (warm-started from its weights)."""
        skip = set(exclude)
        self._warm_models.update({uid: m for uid, m in model.fitted.items()
                                  if uid not in skip})
        return self

    def with_workflow_cv(self) -> "Workflow":
        """Move the pre-ModelSelector feature-engineering DAG inside the CV
        folds (OpWorkflowCore.withWorkflowCV, OpWorkflowCore.scala:105 →
        FitStagesUtil.cutDAG:302-367): estimators feeding the selector are
        re-fit on each fold's training rows, so fold-global statistics
        (target encodings, supervised buckets, sanity-check selections)
        cannot leak into validation metrics."""
        self._workflow_cv = True
        return self

    def with_raw_feature_filter(self, score_dataset=None, score_reader=None,
                                **rff_params) -> "Workflow":
        """Enable RawFeatureFilter before training
        (OpWorkflow.withRawFeatureFilter, OpWorkflow.scala:544-586):
        train/score distribution comparison drops unhealthy raw features and
        rewires the DAG around them."""
        from transmogrifai_tpu.automl.raw_feature_filter import RawFeatureFilter
        self._rff = RawFeatureFilter(**rff_params)
        self._rff_score_source = (score_dataset, score_reader)
        return self

    # ------------------------------------------------------------------ #

    def _raw_features(self) -> List:
        seen: Dict[str, Any] = {}
        for f in self.result_features:
            for r in f.raw_features():
                seen.setdefault(r.uid, r)
        return list(seen.values())

    def _resolve_dataset(self, dataset: Optional[Dataset]) -> Dataset:
        ds = dataset if dataset is not None else self._dataset
        if ds is None and self._reader is not None:
            # aggregating readers fold per-key event streams through each raw
            # feature's monoid (readers/readers.py; DataReader.scala:216-330)
            ds = self._reader.read(self._raw_features())
        if ds is None:
            raise RuntimeError(
                "No input data: call set_input_dataset / set_reader or pass "
                "a dataset to train()/score()")
        return ds

    def train(self, dataset: Optional[Dataset] = None, seed: int = 42,
              mesh=None, strict: bool = True) -> "WorkflowModel":
        """Materialize raw features, then fit the DAG layer by layer
        (OpWorkflow.train → fitStages → fitAndTransformLayer).

        `mesh`: optional jax.sharding.Mesh — estimator fits that support it
        (the ModelSelector sweep) shard their work across it.

        A static opcheck pass (`analysis.opcheck.validate_graph`) runs
        FIRST — before any data materialization, fit, or XLA compile — and
        raises `GraphValidationError` on a miswired DAG (type mismatches,
        response leakage, cycles, host/device contract violations).
        `strict=False` downgrades validation errors to logged warnings.

        An OpParams ``feature_cache`` config is installed as the
        process-default device-matrix cache policy for the train's
        extent (`data/feature_cache.py`), so any big-data matrix built
        under this train — selector sweeps, out-of-core fits — resolves
        the run's cache policy without per-call plumbing. An OpParams
        ``perf_model`` config installs the same way (`perf/params.py`):
        the learned cost model's corpus location and tuning knobs apply
        to every scheduler/sweep/ingest decision under this train."""
        from transmogrifai_tpu.data.feature_cache import cache_scope
        from transmogrifai_tpu.perf.params import params_scope
        from transmogrifai_tpu.utils.compile_cache import (
            register_compile_listeners)
        register_compile_listeners()  # every XLA compile below is a span
        # a child of the caller's current span, so a runner's `run:train`
        # still roots the trace
        with cache_scope(self.parameters.get("feature_cache")), \
                params_scope(self.parameters.get("perf_model")), \
                TRACER.span("workflow:train", category="workflow"):
            return self._train_impl(dataset, seed, mesh, strict)

    def _train_impl(self, dataset: Optional[Dataset], seed: int,
                    mesh, strict: bool) -> "WorkflowModel":
        if not self.result_features:
            raise RuntimeError("set_result_features before train()")
        _validate_or_raise(self.result_features, strict, where="train")
        ds = self._resolve_dataset(dataset)
        rff_results = None
        source_features = self.result_features
        if self._rff is not None:
            ds, source_features, rff_results = self._apply_rff(ds)
        # fit a private clone: the estimator→model swap must not mutate the
        # user's graph or previously returned models (see dag.clone_graph)
        result_features = clone_graph(source_features)
        layers = topological_layers(result_features)
        stage_params = self.parameters.get("stage_params") or {}
        if stage_params:
            from transmogrifai_tpu.workflow.params import apply_stage_params
            import logging
            apply_stage_params(
                [s for layer in layers[1:] for s in layer], stage_params,
                log=logging.getLogger(__name__))
        # resumable sweeps: thread the sweep-checkpoint config onto every
        # ModelSelector in the (cloned) DAG that has no checkpoint_dir of
        # its own — a re-invoked train() with the same params then skips
        # journaled grid blocks (runtime/journal.py)
        sweep_ckpt = self.parameters.get("sweep_checkpoint") or {}
        if sweep_ckpt.get("checkpoint_dir"):
            for layer in layers[1:]:
                for stage in layer:
                    est = getattr(stage, "_estimator", None) or stage
                    if self._is_selector(est) and est.checkpoint_dir is None:
                        est.checkpoint_dir = sweep_ckpt["checkpoint_dir"]
                        est.checkpoint_fsync = bool(
                            sweep_ckpt.get("fsync", True))
        ctx = FitContext(n_rows=len(ds), seed=seed, mesh=mesh)
        columns: Dict[str, Column] = {}
        fitted: Dict[str, Transformer] = {}

        with TRACER.span("workflow:materialize", category="workflow") as sp:
            for gen in layers[0] if layers else []:
                if not isinstance(gen, FeatureGeneratorStage):
                    raise TypeError(
                        f"Layer-0 stage {gen!r} is not a feature generator")
                columns[gen.get_output().uid] = gen.materialize(ds)
            # `text_factorized` short of `text_columns`: a column took
            # `Column.from_values`' per-cell path (not str|None storage)
            text = [c for c in columns.values() if c.kind == TEXT]
            sp.set(text_columns=len(text),
                   text_cells=sum(len(c) for c in text),
                   text_factorized=sum(c.factorized for c in text))

        n_fits = 0
        for li, layer in enumerate(layers[1:], start=1):
            for stage in layer:
                inputs = [columns[f.uid] for f in stage.input_features]
                # a re-train sees fitted models in the DAG; refit via their
                # original estimator (copyWithNewStages swap, stages/base.py)
                est = getattr(stage, "_estimator", None) or stage
                if isinstance(est, Estimator):
                    warm = self._warm_models.get(est.uid)
                    if warm is not None and not isinstance(warm, Estimator):
                        # warm start: reuse the previously fitted model
                        fitted[est.uid] = warm
                        columns[stage.get_output().uid] = warm.transform(
                            inputs, ctx)
                        continue
                    stage_ctx = ctx.child(li)
                    if self._workflow_cv and self._is_selector(est):
                        stage_ctx.cv_refit = self._make_cv_refit(
                            stage, layers, columns, ctx)
                    # per-stage spans: every fit and transform lands in
                    # the run's unified timeline keyed by stage uid, so
                    # a slow estimator is attributable from the trace
                    # alone (the OpSparkListener per-stage analogue)
                    with TRACER.span(
                            f"stage:fit:{stage.operation_name}",
                            category="stage", uid=est.uid, layer=li):
                        model = est.fit(inputs, stage_ctx)
                    n_fits += 1
                    fitted[est.uid] = model
                    with TRACER.span(
                            f"stage:transform:{stage.operation_name}",
                            category="stage", uid=est.uid, layer=li):
                        out = model.transform(inputs, ctx)
                elif isinstance(stage, Transformer):
                    fitted[stage.uid] = stage
                    with TRACER.span(
                            f"stage:transform:{stage.operation_name}",
                            category="stage", uid=stage.uid, layer=li):
                        out = stage.transform(inputs, ctx)
                else:
                    raise TypeError(f"Cannot execute stage {stage!r}")
                columns[stage.get_output().uid] = out

        reg = get_registry()
        reg.counter("train_runs_total",
                    "Workflow.train invocations").inc()
        reg.counter("train_stages_fitted_total",
                    "estimators fitted during train").inc(n_fits)
        model = WorkflowModel(
            result_features=result_features, fitted=fitted,
            train_columns=columns)
        model.rff_results = rff_results
        model.blocklist = list(self.blocklist)
        # fingerprint capture is opt-in via a "continual" parameters
        # block (even an empty one): the sampled device gather + per-
        # column quantile pass is real work on wide matrices, and batch
        # workflows that never attach a DriftMonitor shouldn't pay it
        if "continual" in self.parameters:
            cont_params = self.parameters.get("continual") or {}
            model.training_fingerprint = self._capture_fingerprint(
                result_features, columns, seed,
                n_bins=int(cont_params.get("n_bins", 10)))
        # per-column quantization calibration is captured on EVERY
        # train (a strided min/max over the host-origin columns — far
        # cheaper than the opt-in histogram fingerprint): quantized
        # serving with "-calibrated" mode then ships fleet-wide
        # fit-time ranges and repeat scores are bit-stable across batch
        # compositions (workflow/compiled.ScoringQuant)
        model.quant_calibration = self._capture_quant_calibration(
            result_features, fitted, columns)
        return model

    @staticmethod
    def _capture_quant_calibration(result_features, fitted, columns):
        """Fit-time per-column [lo, hi] ranges for the quantized
        serving wire: captured for every HOST-ORIGIN device-input
        column (raw generator outputs + host-stage outputs — exactly
        the leaves `quantize_wire` sees as numpy arrays at serving
        time). Scalar ranges are extended to include 0.0 because
        masked slots ride the wire as exact 0.0 fills. Rows are
        strided-sampled past 256k (a quant range needs coverage, not
        exactness). Best-effort: failure means no calibration, never a
        failed train."""
        from transmogrifai_tpu.data.columns import SCALAR, VECTOR
        from transmogrifai_tpu.stages.base import is_host_stage
        try:
            host_uids = {f.uid for rf in result_features
                         for f in rf.raw_features()}
            for s in fitted.values():
                if is_host_stage(s):
                    host_uids.add(s.get_output().uid)
            cal = {}
            for uid in host_uids:
                col = columns.get(uid)
                if col is None:
                    continue
                kind = col.kind
                if kind == SCALAR:
                    v = np.asarray(col.data["value"], np.float64)
                    m = np.asarray(col.data["mask"]).astype(bool)
                    v = v[m]
                    if v.size > 262_144:
                        v = v[::v.size // 262_144]
                    if v.size == 0:
                        continue
                    with np.errstate(invalid="ignore"):
                        fin = v[np.isfinite(v)]
                    if fin.size == 0:
                        continue
                    lo = min(float(fin.min()), 0.0)
                    hi = max(float(fin.max()), 0.0)
                    cal[uid] = {"lo": [lo], "hi": [hi]}
                elif kind == VECTOR:
                    a = np.asarray(col.data)
                    if a.ndim != 2 or a.size == 0:
                        continue
                    if a.shape[0] > 65_536:
                        a = a[::a.shape[0] // 65_536]
                    import warnings
                    with np.errstate(invalid="ignore"), \
                            warnings.catch_warnings():
                        warnings.simplefilter("ignore", RuntimeWarning)
                        fin = np.where(np.isfinite(a), a, np.nan)
                        lo = np.nanmin(fin, axis=0)
                        hi = np.nanmax(fin, axis=0)
                    lo = np.where(np.isfinite(lo), lo, 0.0)
                    hi = np.where(np.isfinite(hi), hi, lo)
                    cal[uid] = {"lo": [float(x) for x in lo],
                                "hi": [float(x) for x in hi]}
            return cal or None
        except Exception as e:
            _log.warning("quant calibration capture failed (%s: %s) — "
                         "quantized serving will use batch-relative "
                         "ranges", type(e).__name__, e)
            return None

    @staticmethod
    def _capture_fingerprint(result_features, columns, seed: int,
                             n_bins: int = 10):
        """Training-data fingerprint for drift detection (continual/):
        per-feature histograms + moments of the PREDICTOR'S input matrix
        plus the label rate, taken from the already-materialized train
        columns (no second data pass). The row sample is gathered ON
        DEVICE, so only sample-many rows ever transfer to host — a
        multi-GB big-data matrix must not round-trip through host RAM
        for a 100k-row histogram. Persisted into ModelInsights, so a
        later DriftMonitor compares appended records against what this
        model actually trained on. Best-effort: workflows without a
        (label, vector) predictor simply have no fingerprint."""
        from transmogrifai_tpu import types as T
        from transmogrifai_tpu.continual.drift import (
            _FP_SAMPLE, TrainingFingerprint)
        try:
            pred = next((f for f in result_features
                         if issubclass(f.ftype, T.Prediction)), None)
            if pred is None or pred.origin_stage is None:
                return None
            label_f = next((p for p in pred.parents if p.is_response), None)
            vec_f = next((p for p in pred.parents
                          if issubclass(p.ftype, T.OPVector)), None)
            if label_f is None or vec_f is None:
                return None
            vec_col = columns.get(vec_f.uid)
            label_col = columns.get(label_f.uid)
            if vec_col is None or label_col is None:
                return None
            dv = vec_col.device_value()
            total = int(dv.shape[0])
            if total > _FP_SAMPLE:
                rng = np.random.default_rng(seed)
                idx = np.sort(rng.choice(total, size=_FP_SAMPLE,
                                         replace=False))
                X = np.asarray(dv[idx])  # device gather, sample-sized copy
            else:
                X = np.asarray(dv)
            y = np.asarray(label_col.data["value"], dtype=np.float64)
            meta = vec_col.meta
            names = meta.column_names() if meta is not None else None
            return TrainingFingerprint.from_arrays(
                X, y, n_bins=n_bins, seed=seed, feature_names=names,
                total_rows=total)
        except Exception as e:
            _log.warning("training fingerprint capture failed (%s: %s) — "
                         "model will have no drift fingerprint",
                         type(e).__name__, e)
            _log.debug("fingerprint capture traceback", exc_info=True)
            return None

    @staticmethod
    def _is_selector(est) -> bool:
        from transmogrifai_tpu.selector.model_selector import ModelSelector
        return isinstance(est, ModelSelector)

    def _make_cv_refit(self, selector_stage, layers, columns, ctx):
        """The cutDAG "during" partition (FitStagesUtil.scala:302-367) as a
        closure: re-fit every estimator feeding the selector's feature
        vector on `fold_rows` only, re-run the transformers, and return the
        fold-specific feature matrix for ALL rows. The label subtree is
        excluded (reused from the global pass) so fold masks stay aligned.
        """
        label_f, vec_f = selector_stage.input_features
        label_uids = {f.uid for f in label_f.traverse()}
        during_stage_uids = {
            f.origin_stage.uid for f in vec_f.traverse()
            if not f.is_raw and f.uid not in label_uids}
        base = dict(columns)  # global columns materialized so far

        def refit(fold_rows: np.ndarray) -> np.ndarray:
            cols = dict(base)
            salt = 0
            for layer in layers[1:]:
                for stage in layer:
                    if (stage is selector_stage
                            or stage.uid not in during_stage_uids):
                        continue
                    salt += 1
                    ins_full = [cols[f.uid] for f in stage.input_features]
                    est = getattr(stage, "_estimator", None) or stage
                    if isinstance(est, Estimator):
                        fold_ctx = FitContext(
                            n_rows=len(fold_rows),
                            seed=ctx.seed * 1000003 + salt, mesh=ctx.mesh)
                        # fit_model (NOT fit): fold models are throwaway and
                        # must not graph-swap origin_stage away from the
                        # globally fitted model
                        m = est.fit_model(
                            [c.take(fold_rows) for c in ins_full], fold_ctx)
                        m.uid = est.uid
                        m.input_features = est.input_features
                        out = m.transform(ins_full)
                    else:
                        out = stage.transform(ins_full)
                    cols[stage.get_output().uid] = out
            return np.asarray(cols[vec_f.uid].data)

        return refit

    def _apply_rff(self, ds: Dataset):
        """Run RawFeatureFilter and rewire the DAG around dropped raw
        features (OpWorkflow.scala:235-258 generateRawData with RFF +
        setBlocklist). Result features that become unproducible raise —
        the reference's default retention policy."""
        from transmogrifai_tpu.features.dag import rewire_without

        raws = self._raw_features()
        label = next((f for f in raws if f.is_response), None)
        score_ds = None
        if self._rff_score_source is not None:
            score_ds, score_reader = self._rff_score_source
            if score_ds is None and score_reader is not None:
                score_ds = score_reader.read(raws)
        filtered = self._rff.generate_filtered_raw(
            ds, raws, score_dataset=score_ds, label_feature=label)
        self.blocklist = list(filtered.features_to_drop)
        if not filtered.features_to_drop:
            return filtered.clean_dataset, self.result_features, filtered.results
        survived, dropped = rewire_without(
            self.result_features, filtered.features_to_drop)
        if dropped:
            raise RuntimeError(
                f"RawFeatureFilter removed raw features "
                f"{filtered.features_to_drop} making result features "
                f"{dropped} unproducible; protect them via "
                f"protected_features or relax thresholds")
        return filtered.clean_dataset, tuple(survived), filtered.results


class WorkflowModel:
    """A fitted workflow (OpWorkflowModel): scoring, evaluation, persistence."""

    def __init__(self, result_features: Sequence, fitted: Dict[str, Transformer],
                 train_columns: Optional[Dict[str, Column]] = None):
        self.result_features = tuple(result_features)
        self.fitted = dict(fitted)
        self.train_columns = train_columns or {}
        self._compiled = None
        self.rff_results = None   # RawFeatureFilterResults when RFF ran
        self.blocklist: List[str] = []
        self._check_finite = False
        self.loaded_from: Optional[str] = None  # set by load_model
        # drift-detection fingerprint of the predictor's training matrix
        # (continual/drift.TrainingFingerprint), set by Workflow.train()
        self.training_fingerprint = None
        # fit-time per-column [lo, hi] ranges for calibrated quantized
        # serving (uid -> {"lo": [...], "hi": [...]}); set by
        # Workflow.train(), persisted in the model manifest
        self.quant_calibration = None

    def with_finite_checks(self, enabled: bool = True) -> "WorkflowModel":
        """Numeric-sanitizer discipline (SURVEY §5.2 — the build's
        analogue of the reference's serializability validation): when
        enabled, every fitted transform's numeric output is checked for
        NaN/Inf on PRESENT values during eager scoring, raising with the
        producing stage's name instead of letting a poisoned column
        propagate into a silent bad model score."""
        self._check_finite = enabled
        return self

    @staticmethod
    def _assert_finite(stage, col: Column) -> None:
        data = col.data
        leaves = (data.values() if isinstance(data, dict) else [data])
        for leaf in leaves:
            arr = np.asarray(leaf)
            if arr.dtype.kind != "f":
                continue
            if isinstance(data, dict) and "mask" in data:
                mask = np.asarray(data["mask"]).astype(bool)
                if arr.shape[:1] == mask.shape[:1]:
                    arr = arr[mask]
            if arr.size and not np.isfinite(arr).all():
                raise FloatingPointError(
                    f"Stage {stage.operation_name} ({stage.uid}) produced "
                    f"non-finite values (NaN/Inf) in its output — enable "
                    f"upstream imputation or inspect the fitted params")

    # ------------------------------------------------------------------ #
    # execution                                                          #
    # ------------------------------------------------------------------ #

    def _execute(self, ds: Dataset) -> Dict[str, Column]:
        """Eager layer-by-layer transform walk (estimators must be fitted)."""
        layers = topological_layers(self.result_features)
        columns: Dict[str, Column] = {}
        for gen in layers[0] if layers else []:
            columns[gen.get_output().uid] = gen.materialize(
                ds, allow_missing_response=True)
        for layer in layers[1:]:
            for stage in layer:
                model = self.fitted.get(stage.uid)
                if model is None:
                    raise RuntimeError(
                        f"Stage {stage.operation_name} ({stage.uid}) has no "
                        "fitted model — did train() run?")
                inputs = [columns[f.uid] for f in stage.input_features]
                out_col = model.transform(inputs)
                if self._check_finite:
                    self._assert_finite(stage, out_col)
                columns[stage.get_output().uid] = out_col
        return columns

    def score(self, dataset: Dataset,
              keep_intermediate: bool = False) -> Dict[str, Column]:
        """Batch scoring: returns {feature_name: Column} for result features
        (OpWorkflowModel.score; drops raw/intermediate like saveScores)."""
        columns = self._execute(dataset)
        if keep_intermediate:
            return columns
        return {f.name: columns[f.uid] for f in self.result_features}

    def _ensure_compiled(self, sharding=None, strict: bool = True,
                         quant=None):
        """Shared gate for EVERY compiled entry point (score_compiled,
        score_stream, score_function): opcheck-validate the fitted graph
        before building a new CompiledScorer. Post-train the graph's
        origin stages ARE the fitted transformers (the estimator→model
        swap in stages/base.py mutates the feature nodes in place), so
        the device-contract checks see exactly what the planner traces.

        `quant` ("int8"/"int4"/ScoringQuant/None) selects the quantized
        inference mode — a different compiled program set, so the cached
        scorer is rebuilt when it changes."""
        from transmogrifai_tpu.workflow.compiled import (
            CompiledScorer, ScoringQuant)
        q = ScoringQuant.resolve(quant)
        if self._compiled is None or \
                getattr(self._compiled, "sharding", None) != sharding or \
                getattr(self._compiled, "quant", None) != q:
            _validate_or_raise(self.result_features, strict,
                               where="compile")
            self._compiled = CompiledScorer(self, sharding=sharding,
                                            quant=q)
        return self._compiled

    def score_compiled(self, dataset: Dataset, sharding=None,
                       strict: bool = True) -> Dict[str, Any]:
        """Fused-XLA scoring path (the `local/` + MLeap equivalent).

        `sharding`: optional row-axis NamedSharding (e.g.
        `parallel.data_sharding(mesh)`) — batch inputs are placed with it
        so the fused program's work spreads across the mesh.

        The fitted graph is opcheck-validated before the first compile
        (`strict=False` downgrades errors to logged warnings)."""
        return self._ensure_compiled(sharding, strict)(dataset)

    def score_stream(self, batches, prefetch: int = 2, sharding=None,
                     host_workers: int = 2, device_depth: int = 2,
                     fetch_group: int = 1, coalesce_rows: int = 0,
                     strict: bool = True, pad_tail: bool = True):
        """Streaming micro-batch scoring as a TWO-stage pipeline
        (OpWorkflowRunner streaming loop, OpWorkflowRunner.scala:233-262):

        - stage 1 (thread pool, `host_workers`): host encode of upcoming
          batches — string→id tables, raw column extraction (numpy/C
          murmur3, mostly GIL-releasing);
        - stage 2 (`device_depth` in flight): the fused device program is
          DISPATCHED for batch i+1..i+depth before batch i's results are
          yielded — JAX's async dispatch means the device execution of
          later batches overlaps the consumer's reads of earlier ones
          (a depth-1 loop serializes host→dispatch→fetch per batch).

        `fetch_group` > 1 amortizes the device→host RESULT fetch, a
        blocking sync with a fixed cost per materialization: grouped
        mode packs `fetch_group` batches' result arrays into ONE flat
        device buffer (one concat dispatch) and fetches it once, then
        yields the batches as host-materialized numpy results. What a
        fetch costs on a directly attached chip has not been measured
        (ROADMAP Speed 4).

        `coalesce_rows` > 0 merges incoming batches into super-batches of
        at least that many rows before dispatch, then splits each result
        back to the ORIGINAL batch boundaries — the output contract (one
        result per input batch, in order) is unchanged. Every dispatch
        pays a fixed overhead on top of the device compute, so bigger
        dispatches raise throughput roughly until compute dominates;
        stable input batch sizes keep the coalesced shape stable (one
        compiled program).

        `pad_tail` (default on) pads a RAGGED FINAL micro-batch up to the
        largest batch shape already seen instead of tracing a fresh XLA
        program for it: a 10M-row stream at batch 1024 ends with one
        partial batch, and before this fix that one batch paid a full
        recompile (seconds) to score a sliver of rows. Pad rows repeat
        the last real row and are sliced back off before the yield, so
        the output contract is unchanged (`analysis/retrace` counters
        assert the no-churn property in tests).

        `batches`: iterable of Datasets (e.g. `StreamingReader.stream()`).
        Yields {feature_name: result} per batch like `score_compiled`.
        """
        from collections import deque
        from concurrent.futures import ThreadPoolExecutor

        from transmogrifai_tpu.workflow.compiled import (
            pad_dataset, slice_result_tree)

        if coalesce_rows and coalesce_rows > 0:
            split_sizes: deque = deque()

            def _coalesced():
                buf, rows = [], 0
                for ds in batches:
                    buf.append(ds)
                    rows += ds.n_rows
                    if rows >= coalesce_rows:
                        split_sizes.append([b.n_rows for b in buf])
                        yield Dataset.concat(buf)
                        buf, rows = [], 0
                if buf:
                    split_sizes.append([b.n_rows for b in buf])
                    yield Dataset.concat(buf)

            # results come back in dispatch order, so the FIFO of split
            # sizes stays aligned with the inner generator's yields
            for host in self.score_stream(
                    _coalesced(), prefetch=prefetch, sharding=sharding,
                    host_workers=host_workers, device_depth=device_depth,
                    fetch_group=fetch_group, strict=strict,
                    pad_tail=pad_tail):
                off = 0
                for s in split_sizes.popleft():
                    yield {f: slice_result_tree(v, off, off + s)
                           for f, v in host.items()}
                    off += s
            return
        if pad_tail:
            # ragged-tail fix: the FINAL partial batch re-pads to the
            # largest shape already compiled (then slices the pad rows
            # back off) instead of tracing a fresh program for one batch.
            # Only the final batch: a mid-stream smaller batch is a real
            # workload shape (variable-size sources), and padding every
            # one of them to the max would silently multiply device work.
            # One-item lookahead tells us which batch is last; an empty
            # final batch passes through unpadded (nothing to repeat).
            tail_sizes: deque = deque()

            def _pad_tails():
                it = iter(batches)
                try:
                    cur = next(it)
                except StopIteration:
                    return
                prev = 0
                for nxt in it:
                    tail_sizes.append((cur.n_rows, cur.n_rows))
                    yield cur
                    prev = max(prev, cur.n_rows)
                    cur = nxt
                n = cur.n_rows
                target = prev if (prev and 0 < n < prev) else n
                tail_sizes.append((n, target))
                yield pad_dataset(cur, target) if target > n else cur

            for host in self.score_stream(
                    _pad_tails(), prefetch=prefetch, sharding=sharding,
                    host_workers=host_workers, device_depth=device_depth,
                    fetch_group=fetch_group, strict=strict,
                    pad_tail=False):
                n, target = tail_sizes.popleft()
                if target > n:
                    yield {f: slice_result_tree(v, 0, n)
                           for f, v in host.items()}
                else:
                    yield host
            return
        scorer = self._ensure_compiled(sharding, strict)
        try:
            device_fn = scorer.fused_jitted()  # shared compile cache
        except RuntimeError:
            # multi-segment plan (host stage consumes device output):
            # sequential per-batch scoring, no host/device overlap
            for ds in batches:
                yield scorer(ds)
            return

        group_n = max(1, int(fetch_group))

        def dispatch(host_out):
            encs, raw_dev, columns = host_out
            out = device_fn(scorer._consts, encs, raw_dev)  # async dispatch
            result: Dict[str, Any] = {}
            for f in self.result_features:
                result[f.name] = (out[f.uid] if f.uid in out
                                  else columns[f.uid].data)
            # per-batch-fetch mode: start the device→host result copy NOW
            # (it queues behind the execution), so the consumer's
            # np.asarray finds the bytes already on host instead of
            # blocking per batch. Grouped mode fetches one packed buffer
            # instead of per-leaf async copies.
            if group_n == 1:
                try:
                    for leaf in _jax.tree_util.tree_leaves(result):
                        if hasattr(leaf, "copy_to_host_async"):
                            leaf.copy_to_host_async()
                except Exception:
                    _log.debug("async host copy unavailable; consumer "
                               "will fetch synchronously", exc_info=True)
            return result

        import jax as _jax

        def encode(ds):
            encs, raw_dev, columns = scorer.host_phase(ds)
            # pre-stage the bulk input transfer from the WORKER thread so
            # uploads of batch i+1 overlap the device execution of batch
            # i (the transfer otherwise serializes inside dispatch)
            try:
                raw_dev = _jax.device_put(raw_dev)
            except Exception:
                # non-array leaves: let dispatch transfer lazily
                _log.debug("worker-side device_put skipped", exc_info=True)
            return encs, raw_dev, columns

        # ONE jitted pack fn: jax.jit itself caches per input pytree
        # structure/shape, so distinct group shapes retrace automatically
        _pack = _jax.jit(lambda ls: _jax.numpy.concatenate(
            [x.reshape(-1) for x in ls]))

        def _packable(v) -> bool:
            # float32 only: the flat buffer is f32, and round-tripping
            # wider/integer dtypes through it would silently lose bits.
            # Non-f32 device leaves (none exist today) fall back to a
            # per-leaf fetch below.
            return (isinstance(v, _jax.Array)
                    and v.dtype == _jax.numpy.float32)

        def materialize_group(group):
            """One flat-buffer fetch for a whole group of results.
            Packs every f32 device leaf — inside result dicts AND bare
            array result features — into one buffer; anything else is
            materialized per leaf."""
            if not group:
                return []
            flats = []   # per-result f32 leaves in deterministic order
            metas = []   # (fname, key-or-None, shape) per leaf
            for result in group:
                leaves = []
                meta = []
                for fname in sorted(result):
                    val = result[fname]
                    if isinstance(val, dict):
                        for k in sorted(val):
                            if _packable(val[k]):
                                meta.append((fname, k, val[k].shape))
                                leaves.append(val[k])
                    elif _packable(val):
                        meta.append((fname, None, val.shape))
                        leaves.append(val)
                flats.append(leaves)
                metas.append(meta)
            if sum(len(ls) for ls in flats) == 0:
                return list(group)
            flat_all = [x for ls in flats for x in ls]
            buf = np.asarray(_pack(flat_all))  # ONE fetch
            out = []
            off = 0
            for result, meta in zip(group, metas):
                host: Dict[str, Any] = {}
                for f, v in result.items():
                    if isinstance(v, dict):
                        host[f] = {k: (np.asarray(x)
                                       if isinstance(x, _jax.Array)
                                       and not _packable(x) else x)
                                   for k, x in v.items()}
                    elif isinstance(v, _jax.Array) and not _packable(v):
                        host[f] = np.asarray(v)
                    else:
                        host[f] = v
                for fname, k, shape in meta:
                    size = int(np.prod(shape))
                    # copy: a view would pin the WHOLE group buffer for
                    # as long as any one batch's array is retained
                    piece = buf[off:off + size].reshape(shape).copy()
                    if k is None:
                        host[fname] = piece
                    else:
                        host[fname][k] = piece
                    off += size
                out.append(host)
            return out

        with ThreadPoolExecutor(max_workers=max(1, host_workers)) as pool:
            encoded = deque()    # host-encode futures
            in_flight = deque()  # dispatched (async) device results

            def pump():  # encode-done or backlog → dispatch to device
                while encoded and (encoded[0].done()
                                   or len(encoded) > max(1, prefetch)):
                    in_flight.append(dispatch(encoded.popleft().result()))

            if group_n == 1:
                for ds in batches:
                    encoded.append(pool.submit(encode, ds))
                    pump()
                    while len(in_flight) > max(1, device_depth):
                        yield in_flight.popleft()
                while encoded:
                    in_flight.append(dispatch(encoded.popleft().result()))
                while in_flight:
                    yield in_flight.popleft()
                return
            # grouped-fetch mode: hold up to group_n dispatched batches,
            # then pack + materialize them with one fetch. The fetch
            # runs on its OWN single worker so it overlaps continued
            # encode+dispatch instead of idling the device.
            depth = max(group_n, device_depth)
            with ThreadPoolExecutor(max_workers=1) as fetch_pool:
                fetched = deque()  # materialize futures, arrival order

                def drain_ready(max_pending: int):
                    while fetched and (fetched[0].done()
                                       or len(fetched) > max_pending):
                        yield from fetched.popleft().result()

                for ds in batches:
                    encoded.append(pool.submit(encode, ds))
                    pump()
                    while len(in_flight) >= depth + group_n:
                        grp = [in_flight.popleft()
                               for _ in range(group_n)]
                        fetched.append(
                            fetch_pool.submit(materialize_group, grp))
                    yield from drain_ready(2)
                while encoded:
                    in_flight.append(dispatch(encoded.popleft().result()))
                while in_flight:
                    grp = [in_flight.popleft()
                           for _ in range(min(group_n, len(in_flight)))]
                    fetched.append(
                        fetch_pool.submit(materialize_group, grp))
                while fetched:
                    yield from fetched.popleft().result()

    def score_function(self, strict: bool = True):
        """Row-level scoring closure: Map[str, Any] → Map[str, Any]
        (local/.../OpWorkflowModelLocal.scala:79-122). Shares the cached
        validated scorer with score_compiled/score_stream."""
        scorer = self._ensure_compiled(strict=strict)

        def score_row(row: Dict[str, Any]) -> Dict[str, Any]:
            ds = Dataset.from_rows([row])
            out = scorer(ds)
            result: Dict[str, Any] = {}
            for f in self.result_features:
                v = out.get(f.name)
                if isinstance(v, dict) and "prediction" in v:  # Prediction pytree
                    m: Dict[str, float] = {
                        "prediction": float(np.asarray(v["prediction"])[0])}
                    prob = np.asarray(v["probability"])[0]
                    raw = np.asarray(v["rawPrediction"])[0]
                    for i, x in enumerate(prob):
                        m[f"probability_{i}"] = float(x)
                    for i, x in enumerate(raw):
                        m[f"rawPrediction_{i}"] = float(x)
                    result[f.name] = m
                elif isinstance(v, dict):  # scalar {value, mask} pytree
                    present = bool(np.asarray(v["mask"])[0])
                    result[f.name] = (
                        float(np.asarray(v["value"])[0]) if present else None)
                else:
                    arr = np.asarray(v)
                    first = arr[0]
                    if arr.dtype == object:  # host kinds: str/list/dict
                        result[f.name] = first
                    else:
                        result[f.name] = (first.tolist() if arr.ndim > 1
                                          else first.item())
            return result

        return score_row

    def evaluate(self, dataset: Dataset, evaluator, label_feature,
                 prediction_feature):
        cols = self._execute(dataset)
        return evaluator.evaluate(
            cols[label_feature.uid], cols[prediction_feature.uid])

    # ------------------------------------------------------------------ #
    # persistence                                                        #
    # ------------------------------------------------------------------ #

    def save(self, path: str, overwrite: bool = True,
             strict_fns: bool = False, extra_json=None) -> None:
        """`strict_fns=True` refuses to persist cloudpickled closures —
        callable params must be `@extract_fn`-registered or module-level
        (see `workflow/serialization.py`). `extra_json` stages sidecar
        JSON files (e.g. insights with the training fingerprint) under
        the same integrity manifest."""
        from transmogrifai_tpu.workflow.serialization import save_model
        save_model(self, path, overwrite=overwrite, strict_fns=strict_fns,
                   extra_json=extra_json)

    @staticmethod
    def load(path: str, verify: bool = True) -> "WorkflowModel":
        """`verify=False` skips the integrity-manifest check — the
        escape hatch for artifacts saved before integrity.json existed
        (see workflow/serialization.py)."""
        from transmogrifai_tpu.workflow.serialization import load_model
        return load_model(path, verify=verify)

    def model_insights(self):
        """Merged explanation artifact (ModelInsights.scala:74)."""
        from transmogrifai_tpu.insights import ModelInsights
        return ModelInsights.extract(self)

    def summary(self) -> Dict[str, Any]:
        """Stage inventory + params (OpWorkflowModel.summary analogue)."""
        return {
            "result_features": [f.name for f in self.result_features],
            "stages": [
                {"uid": uid, "class": type(s).__name__}
                for uid, s in sorted(self.fitted.items())
            ],
        }
