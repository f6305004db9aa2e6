"""Stage abstraction: typed, lazily-wired transformers and estimators.

Reference parity: `features/.../stages/OpPipelineStages.scala:55-553` (arity
traits, `OpTransformer` row contract) and `features/.../stages/base/*`
(Unary/Binary/.../Sequence Transformer+Estimator pairs).

TPU-first redesign: a stage is a pair of pure functions instead of a Spark
pipeline node —

- `Estimator.fit(columns, ctx) -> Transformer`  (host-driven; may run jitted
  stats reductions over sharded batches)
- `Transformer` splits into `host_prepare(columns) -> enc` (string/object
  work, numpy) and `device_apply(enc, device_inputs) -> arrays` (pure jnp,
  jittable). The fitted DAG's device_apply chain fuses into ONE XLA program
  at scoring time (replacing both `FitStagesUtil.applyOpTransformations`
  row-fusion and the MLeap local path).

Contract for `host_prepare`: it may only read host-kind input columns
(text/list/map); device-kind inputs (scalar/vector/prediction) may be None
when running inside the compiled scorer.
"""

from __future__ import annotations

from dataclasses import dataclass, field
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Type

import numpy as np

from transmogrifai_tpu import types as T
from transmogrifai_tpu.data.columns import Column, kind_of, SCALAR, VECTOR, PREDICTION
from transmogrifai_tpu.data.metadata import VectorMetadata
from transmogrifai_tpu.obs.trace import pull
from transmogrifai_tpu.utils.uid import UID


@dataclass
class FitContext:
    """Per-fit environment: row count, rng seed, optional device mesh.

    `cv_refit` is set by the workflow ONLY on the ModelSelector's context
    when workflow-level CV is enabled (`Workflow.with_workflow_cv()`): a
    callable `fold_rows -> (n_total, d) feature matrix` that re-fits the
    pre-selector feature-engineering DAG on the given rows (the cutDAG
    equivalent, FitStagesUtil.scala:302-367)."""

    n_rows: int
    seed: int = 42
    mesh: Any = None  # jax.sharding.Mesh when running sharded
    data_axis: str = "data"
    cv_refit: Any = None
    # a classification selector reads the number of classes off the label
    # ONCE and leaves it here for every family's sweep, the refit and the
    # metrics (`models/base.n_classes_of`)
    n_classes: Any = None

    def child(self, salt: int) -> "FitContext":
        return FitContext(self.n_rows, self.seed * 1000003 + salt, self.mesh, self.data_axis)


class StageRegistry:
    """Class registry for stage (de)serialization
    (OpPipelineStageReaderWriter analogue)."""

    _classes: Dict[str, type] = {}

    @classmethod
    def register(cls, stage_cls: type) -> None:
        cls._classes[stage_cls.__name__] = stage_cls

    @classmethod
    def get(cls, name: str) -> type:
        try:
            return cls._classes[name]
        except KeyError:
            raise KeyError(f"Stage class {name!r} is not registered") from None


class Stage:
    """Base: typed inputs, one output feature, serializable params.

    Subclasses declare `in_types`: a tuple of FeatureType classes for fixed
    arity, or (`elem_type`, Ellipsis) for variadic same-type inputs
    (SequenceEstimator analogue). `None` disables checking.
    """

    in_types: Optional[Tuple] = None
    out_type: type = T.OPVector  # default output feature type

    # Stages that may legitimately combine the response with predictors
    # (models, SanityChecker, supervised bucketizers) set this True; by
    # convention their slot 0 is the label slot. `analysis.opcheck` treats
    # any other stage mixing response-derived features with predictors as
    # response leakage, and outputs of response-aware stages (e.g. a
    # Prediction) as sanctioned rather than tainted.
    response_aware: bool = False

    def __init__(self, uid: Optional[str] = None, **params):
        self.uid = uid or UID(type(self))
        self.params: Dict[str, Any] = params
        self.input_features: Tuple = ()
        self._output = None

    def __init_subclass__(cls, **kw):
        super().__init_subclass__(**kw)
        StageRegistry.register(cls)

    # -- wiring --------------------------------------------------------- #

    @property
    def operation_name(self) -> str:
        return type(self).__name__

    def set_input(self, *features) -> "Stage":
        self._check_inputs(features)
        self.input_features = tuple(features)
        self._output = None
        return self

    def _check_inputs(self, features: Sequence) -> None:
        spec = self.in_types
        if spec is None:
            return
        if len(spec) == 2 and spec[1] is Ellipsis:
            elem = spec[0]
            if elem is not None:
                for f in features:
                    if not issubclass(f.ftype, elem):
                        raise TypeError(
                            f"{self.operation_name} requires inputs of type "
                            f"{elem.__name__}; got {f.ftype.__name__} ({f.name})")
            return
        if len(features) != len(spec):
            raise TypeError(
                f"{self.operation_name} requires {len(spec)} inputs, got {len(features)}")
        for f, t in zip(features, spec):
            if t is not None and not issubclass(f.ftype, t):
                raise TypeError(
                    f"{self.operation_name} input {f.name!r}: expected "
                    f"{t.__name__}, got {f.ftype.__name__}")

    def output_ftype(self) -> type:
        return self.out_type

    def output_name(self) -> str:
        base = "-".join(f.name for f in self.input_features) or "raw"
        return f"{base}_{self.operation_name}_{self.uid}"

    def get_output(self):
        from transmogrifai_tpu.features import Feature
        if self._output is None:
            if not self.input_features and not isinstance(self, FeatureGeneratorStage):
                raise RuntimeError(f"{self.operation_name}: set_input before get_output")
            is_resp = bool(self.input_features) and all(
                f.is_response for f in self.input_features)
            self._output = Feature(
                name=self.output_name(), ftype=self.output_ftype(),
                origin_stage=self, parents=self.input_features,
                is_response=is_resp)
        return self._output

    # -- persistence ----------------------------------------------------- #

    def get_params(self) -> Dict[str, Any]:
        """JSON-serializable constructor params (override to extend)."""
        return dict(self.params)

    def __repr__(self) -> str:
        return f"{type(self).__name__}(uid={self.uid!r})"


class Transformer(Stage):
    """A fitted/stateless row-parallel operation (OpTransformer analogue)."""

    jittable = True  # device_apply is pure jnp and may be traced under jit

    def host_prepare(self, cols: Sequence[Optional[Column]]) -> Any:
        """Host-side encode of object-kind inputs → pytree of np arrays."""
        return None

    def device_apply(self, enc: Any, dev: Sequence[Any]) -> Any:
        """Pure-jnp compute over encoded + parent device values."""
        raise NotImplementedError(type(self).__name__)

    def device_constants(self) -> Any:
        """Large fitted arrays the compiled scorer should pass as jit
        ARGUMENTS instead of letting device_apply close over them:
        closure-captured arrays are value-baked into the XLA executable
        (bigger programs, and no two models can share one), so
        megabyte-scale model parameters (tree tables) must flow as
        arguments. None (default) = nothing big; device_apply reads self.
        """
        return None

    def device_apply_with(self, consts: Any, enc: Any,
                          dev: Sequence[Any]) -> Any:
        """device_apply with `device_constants()` threaded back in as a
        traced argument. Default ignores consts."""
        return self.device_apply(enc, dev)

    def signature_params(self) -> Dict[str, Any]:
        """Fitted params that shape the TRACED program — the facts
        `serving/fleet.scoring_signature` folds into the compile-group
        key. Defaults to `get_params()` (every fitted value is a closure
        constant baked into the XLA program). Stages that lift their
        fitted arrays through `device_constants()` override this to
        exclude the lifted VALUES — they flow as jit arguments, so only
        their shapes/dtypes key the program (via the consts digest) and
        same-shaped tenants share one compiled program — while keeping
        any hyperparams that still steer the trace (static control flow,
        baked scalars like a GBT learning rate)."""
        return self.get_params()

    def narrow_device_constants(self, consts: Any) -> Any:
        """Quantized-inference view of `device_constants()`: the same
        pytree with HBM-heavy tables re-typed to narrower dtypes, used
        by the compiled scorer's int8/int4 scoring mode. The narrowing
        rule must depend only on STATIC shape facts (never array
        values), so every model sharing a scoring signature narrows to
        identical traced dtypes and program adoption stays zero-trace.
        Default: unchanged (nothing to narrow)."""
        return consts

    def output_meta(self) -> Optional[VectorMetadata]:
        """Static vector metadata (set at fit time for fitted models)."""
        return None

    def transform(self, cols: Sequence[Column], ctx: Optional[FitContext] = None) -> Column:
        enc = self.host_prepare(cols)
        dev = self.device_apply(enc, [c.device_value() for c in cols])
        return self._wrap(dev)

    def _wrap(self, dev: Any) -> Column:
        out_t = self.output_ftype()
        k = kind_of(out_t)
        if k == VECTOR:
            return Column.vector(dev, self.output_meta())
        if k == SCALAR:
            # normalize back to the host columnar contract (f64 value, bool mask)
            dev = pull(f"stage:{self.operation_name}",
                       {"value": dev["value"], "mask": dev["mask"]})
            return Column(out_t, {
                "value": np.asarray(dev["value"], dtype=np.float64),
                "mask": np.asarray(dev["mask"]).astype(bool)})
        if k == PREDICTION:
            # a fitted model's prediction over the rows it was given
            dev = pull(f"stage:{self.operation_name}", dict(dev))
            return Column(out_t, {key: np.asarray(a) for key, a in dev.items()})
        raise TypeError(
            f"{self.operation_name}: device output cannot have host kind {k}; "
            "override transform() as a HostTransformer")


class HostTransformer(Transformer):
    """Transformer producing host-kind output (text/list/map) — runs eagerly
    on host in both fit and compiled-scoring paths."""

    jittable = False

    def transform(self, cols: Sequence[Column], ctx: Optional[FitContext] = None) -> Column:
        raise NotImplementedError(type(self).__name__)


# Column kinds that never cross to device (see data/columns.py kind table).
HOST_KINDS = ("text", "list", "map")


def is_host_stage(stage) -> bool:
    """THE host/device segmentation rule — single source of truth shared by
    the compiled scorer's planner (workflow/compiled.py) and the static
    validator (analysis/opcheck.py): a Transformer runs on host when it
    subclasses HostTransformer OR sets jittable=False (plain Transformers
    like DateListVectorizer override transform() and must never be traced
    into a device segment)."""
    return isinstance(stage, Transformer) and (
        isinstance(stage, HostTransformer) or not stage.jittable)


class Estimator(Stage):
    """Unfitted stage: `fit` learns params and returns the fitted
    Transformer (which keeps this estimator's uid, mirroring the reference's
    estimator→model swap in `Feature.copyWithNewStages`)."""

    def fit(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        model = self.fit_model(cols, ctx)
        model.uid = self.uid
        model.input_features = self.input_features
        # The fitted model takes over the estimator's output feature node AND
        # becomes its origin stage, so post-fit DAG traversal sees fitted
        # transformers — the reference's `copyWithNewStages` estimator→model
        # swap. `_estimator` is kept so a re-train can find the unfitted stage.
        out = self.get_output()
        out.origin_stage = model
        model._output = out
        model._estimator = self
        return model

    def fit_model(self, cols: Sequence[Column], ctx: FitContext) -> Transformer:
        raise NotImplementedError(type(self).__name__)


class FeatureGeneratorStage(Stage):
    """Arity-0 origin of every raw feature
    (`features/.../stages/FeatureGeneratorStage.scala:67-125`).

    Extracts one typed column from a Dataset: either a named column (fast
    vectorized path) or a per-record python extract function (the reference's
    macro-captured extractFn)."""

    def __init__(self, name: str, ftype: type,
                 extract: Optional[Callable[[Dict[str, Any]], Any]] = None,
                 column: Optional[str] = None, is_response: bool = False,
                 null_fill: Any = None, uid: Optional[str] = None):
        super().__init__(uid=uid)
        from transmogrifai_tpu.utils.fnser import decode_fn
        self.feature_name = name
        self.ftype = ftype
        self.extract = decode_fn(extract)
        self.column = column if column is not None else (name if extract is None else None)
        self.is_response = is_response
        self.null_fill = null_fill  # vectorized null replacement (fast path)

    def output_ftype(self) -> type:
        return self.ftype

    def output_name(self) -> str:
        return self.feature_name

    def get_output(self):
        from transmogrifai_tpu.features import Feature
        if self._output is None:
            self._output = Feature(
                name=self.feature_name, ftype=self.ftype, origin_stage=self,
                parents=(), is_response=self.is_response)
        return self._output

    def materialize(self, dataset, allow_missing_response: bool = False) -> Column:
        if self.feature_name in getattr(dataset, "pre_extracted", ()) and \
                self.feature_name in dataset.columns:
            # aggregating readers already folded events to final typed values
            # keyed by feature name — bypass extract fns (readers/readers.py)
            return Column.from_values(
                self.ftype, dataset.column(self.feature_name))
        if self.extract is not None:
            values = [self.extract(row) for row in dataset.to_rows()]
            return Column.from_values(self.ftype, values)
        if self.column not in dataset.columns:
            if self.is_response and allow_missing_response:
                # scoring data without the label column: a type-appropriate
                # placeholder (zeros for numerics, empties otherwise).
                # Training always raises (allow_missing_response=False).
                fill = 0.0 if issubclass(self.ftype, T.OPNumeric) else None
                return Column.from_values(self.ftype, [fill] * len(dataset))
            raise KeyError(
                f"Raw feature {self.feature_name!r}: column {self.column!r} "
                f"not in dataset {dataset.names()}")
        values = dataset.column(self.column)
        if self.null_fill is not None:
            if values.dtype != object:  # typed numeric storage: NaN = missing
                values = np.where(np.isnan(values.astype(np.float64)),
                                  float(self.null_fill), values)
            else:
                values = np.array(
                    [self.null_fill if v is None else v for v in values],
                    dtype=object)
        return Column.from_values(self.ftype, values)

    def get_params(self) -> Dict[str, Any]:
        from transmogrifai_tpu.utils.fnser import encode_fn
        return {
            "name": self.feature_name, "ftype": self.ftype.__name__,
            "extract": encode_fn(self.extract),
            "column": self.column, "is_response": self.is_response,
            "null_fill": self.null_fill,
        }
