"""Command-line entry points: run a workflow app, generate a starter app.

Reference parity: `cli/src/main/scala/com/salesforce/op/cli/CliExec.scala`
(`transmogrifai gen` project generator driven by data schema,
`cli/.../gen/Ops.scala:49-54`) and the runner config CLI
(`OpWorkflowRunner.scala:379-440`, scopt-parsed OpWorkflowRunnerConfig).

Usage:
  python -m transmogrifai_tpu.cli run --app pkg.module:factory \
      --run-type train --params params.json
  python -m transmogrifai_tpu.cli gen --input data.csv --response label \
      --output my_app.py [--problem binary|multiclass|regression]
  (gen also accepts .parquet / .avro data files, or a bare .avsc Avro
   schema for schema-only generation)
"""

from __future__ import annotations

import argparse
import importlib
import json
import sys
from typing import Optional


def _load_factory(spec: str):
    """'pkg.module:attr' → the runner factory callable/instance."""
    if ":" not in spec:
        raise SystemExit(f"--app must be 'module:factory', got {spec!r}")
    mod_name, attr = spec.split(":", 1)
    mod = importlib.import_module(mod_name)
    obj = getattr(mod, attr)
    return obj() if callable(obj) else obj


def cmd_run(args) -> int:
    from transmogrifai_tpu.utils.compile_cache import enable_compile_cache
    enable_compile_cache()
    if args.platform:  # must happen before any backend init
        import jax
        jax.config.update("jax_platforms", args.platform)
    from transmogrifai_tpu.workflow.params import OpParams
    runner = _load_factory(args.app)
    params = OpParams.load(args.params) if args.params else OpParams()
    if args.model_location:
        params.model_location = args.model_location
    if args.write_location:
        params.write_location = args.write_location
    if args.metrics_location:
        params.metrics_location = args.metrics_location
    if getattr(args, "trace_out", None):
        # unified observability: write the run's span timeline as
        # Perfetto/Chrome-trace JSON (+ a sibling .events.jsonl with the
        # correlation-id-stamped structured event log) and fold the
        # goodput report into the printed profile
        params.trace_location = args.trace_out
    if getattr(args, "sweep_checkpoint_dir", None):
        # resumable sweeps: re-running this exact command after a
        # preemption resumes at the first un-journaled grid block
        from transmogrifai_tpu.workflow.params import SweepCheckpointParams
        params.sweep_checkpoint = SweepCheckpointParams(
            checkpoint_dir=args.sweep_checkpoint_dir)
    if getattr(args, "mesh_devices", None) or \
            getattr(args, "mesh_sweep", None) or \
            getattr(args, "mesh_slices", None):
        # distributed sweeps: train over a (sweep, data) device mesh —
        # the selector's grid blocks schedule across the sweep axis via
        # the work-stealing scheduler (parallel/scheduler.py)
        from transmogrifai_tpu.workflow.params import MeshParams
        base_mesh = params.mesh or MeshParams()
        if getattr(args, "mesh_devices", None):
            base_mesh.n_devices = args.mesh_devices
        if getattr(args, "mesh_sweep", None):
            base_mesh.sweep = args.mesh_sweep
        if getattr(args, "mesh_slices", None):
            base_mesh.n_slices = args.mesh_slices
        params.mesh = base_mesh
    if getattr(args, "feature_cache", None) or \
            getattr(args, "feature_cache_dir", None) or \
            getattr(args, "feature_cache_wire", None):
        # persistent device-matrix cache: repeat runs over the same
        # store replay the wire artifact instead of re-uploading
        from transmogrifai_tpu.workflow.params import FeatureCacheParams
        base = params.feature_cache
        if base is None:
            # seed from the env-resolved default so `--feature-cache-wire`
            # alone LAYERS onto a TRANSMOGRIFAI_FEATURE_CACHE enable
            # instead of masking it with policy="off" params
            from transmogrifai_tpu.data.feature_cache import (
                get_default_cache_params)
            base = get_default_cache_params() or FeatureCacheParams()
        if getattr(args, "feature_cache", None):
            base.policy = args.feature_cache
        elif getattr(args, "feature_cache_dir", None) and not base.enabled:
            base.policy = "readwrite"  # --feature-cache-dir alone enables
        if getattr(args, "feature_cache_dir", None):
            base.dir = args.feature_cache_dir
        if getattr(args, "feature_cache_wire", None):
            base.wire = args.feature_cache_wire
        params.feature_cache = base
    if getattr(args, "perf_model", None) or \
            getattr(args, "perf_corpus_dir", None) or \
            getattr(args, "perf_model_path", None):
        # learned cost model: corpus/model locations + the kill switch
        # (a cold corpus degrades every consumer to today's heuristics,
        # so enabling is always safe)
        from transmogrifai_tpu.perf.params import PerfModelParams
        pm = params.perf_model or PerfModelParams()
        if getattr(args, "perf_model", None):
            pm.enabled = args.perf_model != "off"
        if getattr(args, "perf_corpus_dir", None):
            pm.corpus_dir = args.perf_corpus_dir
        if getattr(args, "perf_model_path", None):
            pm.model_path = args.perf_model_path
        params.perf_model = pm
    result = runner.run(args.run_type, params)
    print(json.dumps(result.to_json(), indent=2, default=str))
    return 0


# --------------------------------------------------------------------------- #
# gen: starter app from a data file (ProblemSchema/AvroField analogue)        #
# --------------------------------------------------------------------------- #

# pipeline + runner body shared by the single-module and package
# templates so the generated workflow can never diverge between them
_PIPELINE_BODY = '''features = transmogrify(predictors)
checked = label.sanity_check(features, remove_bad_features=True)
prediction = {selector_expr}.set_input(
    label, checked).get_output()

workflow = Workflow().set_result_features(prediction, label)


def runner() -> WorkflowRunner:
    return WorkflowRunner(
        workflow,
        train_reader=DataReaders.{reader_fn}("{data_path}"),
        score_reader=DataReaders.{reader_fn}("{data_path}"),
        {evaluator_wiring}
        prediction_feature=prediction)
'''

_APP_TEMPLATE = '''"""Generated by `transmogrifai_tpu gen` from {input_path}.

Run:
  python -m transmogrifai_tpu.cli run --app {module_name}:runner \\
      --run-type train --params params.json
"""

import transmogrifai_tpu.types as t
from transmogrifai_tpu.automl import transmogrify
from transmogrifai_tpu.automl.sanity_checker import SanityChecker
from transmogrifai_tpu.features import FeatureBuilder
from transmogrifai_tpu.readers import DataReaders
from transmogrifai_tpu.selector import {selector}
from transmogrifai_tpu.evaluators import {evaluator}{extra_imports}
from transmogrifai_tpu.workflow import Workflow
from transmogrifai_tpu.workflow.runner import WorkflowRunner

# -- raw features (one per column of {input_path}) -------------------------- #
{feature_lines}

predictors = [{predictor_names}]

# -- pipeline --------------------------------------------------------------- #
label = {label_expr}
{pipeline_body}'''

_FEATURES_TEMPLATE = '''"""Feature definitions generated from {input_path}.

The Features.scala analogue of the reference project template
(templates/simple/src/main/scala/com/salesforce/app/Features.scala):
raw typed features in one module, workflow wiring in app.py.
"""

import transmogrifai_tpu.types as t  # noqa: F401
from transmogrifai_tpu.features import FeatureBuilder

{feature_lines}

predictors = [{predictor_names}]
raw_label = {label_var}
'''

_PKG_APP_TEMPLATE = '''"""Workflow wiring generated from {input_path}.

Run from the project root:
  python -m transmogrifai_tpu.cli run --app {pkg}.app:runner \\
      --run-type train --params params.json
"""

from transmogrifai_tpu.automl import transmogrify
from transmogrifai_tpu.readers import DataReaders
from transmogrifai_tpu.selector import {selector}
from transmogrifai_tpu.evaluators import {evaluator}{extra_imports}
from transmogrifai_tpu.workflow import Workflow
from transmogrifai_tpu.workflow.runner import WorkflowRunner

from {pkg}.features import predictors, raw_label

label = {pkg_label_expr}
{pipeline_body}'''

_PKG_INIT_TEMPLATE = '''"""Generated {problem} AutoML app ({pkg})."""

from {pkg}.app import runner, workflow  # noqa: F401
'''

_PYPROJECT_TEMPLATE = """[project]
name = "{pkg}"
version = "0.1.0"
description = "Generated {problem} AutoML app (transmogrifai_tpu)"
requires-python = ">=3.10"
dependencies = ["transmogrifai_tpu", "jax", "numpy"]

[build-system]
requires = ["setuptools>=61"]
build-backend = "setuptools.build_meta"

[tool.setuptools]
packages = ["{pkg}"]
"""

_GITIGNORE = """__pycache__/
*.pyc
/model/
/metrics/
/scores/
"""

_PKG_TEST_TEMPLATE = '''"""Smoke test for the generated app: the pipeline
graph wires, the runner builds, and the DAG has the expected stages.

Run from the project root: python -m pytest tests/ -q
"""

import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def test_workflow_wires():
    from transmogrifai_tpu.features.dag import topological_layers

    from {pkg}.app import prediction, runner, workflow

    layers = topological_layers(list(workflow.result_features))
    assert len(layers) >= 3  # raw -> vectorize -> check -> select
    r = runner()
    assert r.prediction_feature is prediction
'''


_EVALUATOR_WIRING = """evaluator={evaluator}(),
        label_feature={raw_label_var},"""
# a text label trains against label.indexed(); raw values would not match
# the predicted indices, so runner-side evaluation is left unwired
_NO_EVALUATOR_WIRING = """# evaluator omitted: the label is index-encoded at
        # train time; evaluate against the indexed label in-process instead
        label_feature={raw_label_var},"""


def _pyname(name: str) -> str:
    """Column name → valid python identifier (gen templates)."""
    import re as _re
    var = _re.sub(r"\W", "_", name)
    if not var or var[0].isdigit():
        var = "f_" + var
    return var


def _gen_feature_line(name: str, ftype_name: str, is_response: bool) -> str:
    role = "as_response" if is_response else "as_predictor"
    return (f'{_pyname(name)} = FeatureBuilder.{ftype_name}("{name}")'
            f'.from_column("{name}").{role}()')


_DATE_RE = None


def _refine_schema(ds, schema, response=None):
    """Data-driven type refinement for generated feature lines (the
    reference CLI's AutomaticSchema does the same from sample data,
    cli/.../gen/AvroField.scala): low-cardinality Text → PickList,
    ISO-dated Text → Date. Only the GENERATED CODE is refined — runtime
    inference stays untouched. The response column is never refined:
    rewriting a low-cardinality Text label to PickList/Date would change
    the downstream problem-kind inference and label wiring (r4 advisor)."""
    import re as _re
    global _DATE_RE
    if _DATE_RE is None:
        _DATE_RE = _re.compile(
            r"^\d{4}-\d{2}-\d{2}([T ]\d{2}:\d{2}(:\d{2})?)?$")
    from transmogrifai_tpu import types as T
    out = dict(schema)
    for name, ftype in schema.items():
        if ftype is not T.Text or ds is None or name == response:
            continue
        col = ds.column(name)
        vals = [v for v in col if v is not None and str(v) != ""]
        if not vals:
            continue
        svals = [str(v) for v in vals]
        if sum(1 for v in svals if _DATE_RE.match(v)) >= 0.95 * len(svals):
            out[name] = T.Date
            continue
        distinct = len(set(svals))
        if distinct <= 100 and distinct <= 0.3 * len(svals):
            out[name] = T.PickList
    return out


def cmd_gen(args) -> int:
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.data import Dataset

    ds = None
    data_path = args.input
    if args.input.endswith(".avsc"):
        # schema-only generation — the reference CLI's primary mode
        # (`transmogrifai gen --schemaFile`, cli/.../gen/Ops.scala:49-54,
        # AvroField.scala): no data to inspect, so the problem kind comes
        # from the response FIELD TYPE (or --problem)
        import json as _json
        from transmogrifai_tpu.data.avro import _Names, avro_ftype
        with open(args.input) as f:
            avsc = _json.load(f)
        if not (isinstance(avsc, dict) and avsc.get("type") == "record"):
            raise SystemExit(f"{args.input}: not an Avro record schema")
        names_ = _Names()
        schema = {fld["name"]: avro_ftype(fld["type"], names_)
                  for fld in avsc["fields"]}
        reader_fn = "avro"
        # readers must point at DATA, not the schema file — default to a
        # sibling .avro path the user fills in
        data_path = args.input[: -len(".avsc")] + ".avro"
    elif args.input.endswith(".avro"):
        ds = Dataset.from_avro(args.input)
        schema = ds.schema
        reader_fn = "avro"
    elif args.input.endswith(".parquet"):
        ds = Dataset.from_parquet(args.input)
        schema = ds.schema
        reader_fn = "parquet"
    else:
        ds = Dataset.from_csv(args.input)
        schema = ds.schema
        reader_fn = "csv"
    if args.response not in schema:
        raise SystemExit(
            f"response {args.response!r} not in columns {list(schema)}")
    if ds is not None:
        schema = _refine_schema(ds, schema, response=args.response)

    # infer problem kind (ProblemKind.scala): binary / multiclass /
    # regression from the response column (or its declared type when
    # generating from a bare schema)
    problem = args.problem
    if problem is None and ds is not None:
        resp = ds.column(args.response)
        vals = resp[~_missing_mask(resp)]
        distinct = len(set(np.round(vals.astype(float), 9).tolist())) \
            if vals.dtype != object else len(set(vals.tolist()))
        if distinct <= 2:
            problem = "binary"
        elif distinct <= 30:
            problem = "multiclass"
        else:
            problem = "regression"
    elif problem is None:
        rt = schema[args.response]
        if issubclass(rt, T.Binary):
            problem = "binary"
        elif issubclass(rt, (T.Text, T.PickList, T.ComboBox, T.ID)):
            problem = "multiclass"
        else:
            problem = "regression"
    selector, evaluator = {
        "binary": ("BinaryClassificationModelSelector",
                   "BinaryClassificationEvaluator"),
        "multiclass": ("MultiClassificationModelSelector",
                       "MultiClassificationEvaluator"),
        "regression": ("RegressionModelSelector", "RegressionEvaluator"),
    }[problem]

    lines, names = [], []
    label_var = _pyname(args.response)
    for name, ftype in schema.items():
        is_resp = name == args.response
        tname = "RealNN" if (is_resp and problem != "multiclass") \
            else ftype.__name__
        lines.append(_gen_feature_line(name, tname, is_resp))
        if not is_resp:
            names.append(_pyname(name))

    module_name = args.output.rsplit("/", 1)[-1].removesuffix(".py")
    indexed = (problem == "multiclass"
               and schema[args.response].__name__ in ("Text", "PickList"))
    label_expr = f"{label_var}.indexed()" if indexed else label_var
    wiring = (_NO_EVALUATOR_WIRING if indexed
              else _EVALUATOR_WIRING).format(
        evaluator=evaluator, raw_label_var=label_var)
    # --light: a small explicit, user-editable grid for quick first
    # iterations (swap back to the full reference-shaped defaults by
    # dropping the models= argument)
    extra_imports = ""
    if getattr(args, "light", False):
        if problem == "regression":
            extra_imports = ("\nfrom transmogrifai_tpu.models import "
                             "OpLinearRegression")
            selector_expr = (
                f"{selector}.with_cross_validation(n_folds=2, models=[\n"
                "    (OpLinearRegression(),\n"
                '     [{"reg_param": 0.001}, {"reg_param": 0.1}])])')
        else:
            extra_imports = ("\nfrom transmogrifai_tpu.models import "
                             "OpLogisticRegression")
            selector_expr = (
                f"{selector}.with_cross_validation(n_folds=2, models=[\n"
                "    (OpLogisticRegression(max_iter=40),\n"
                '     [{"reg_param": 0.01}, {"reg_param": 0.1}])])')
    else:
        selector_expr = f"{selector}.with_cross_validation()"
    code = _APP_TEMPLATE.format(
        input_path=args.input, module_name=module_name,
        feature_lines="\n".join(lines), extra_imports=extra_imports,
        predictor_names=", ".join(names), label_expr=label_expr,
        selector=selector, evaluator=evaluator,
        pipeline_body=_PIPELINE_BODY.format(
            selector_expr=selector_expr, evaluator_wiring=wiring,
            reader_fn=reader_fn, data_path=data_path))
    with open(args.output, "w") as f:
        f.write(code)
    print(f"Generated {args.output} ({problem} problem, "
          f"{len(names)} predictors)")

    if getattr(args, "project_dir", None):
        # full BUILDABLE project skeleton (templates/simple/ analogue):
        # package (features.py + app.py), pyproject, test, params, README
        import os
        pd = os.path.abspath(args.project_dir)  # cwd-independent config
        pkg = _pyname(module_name)
        pkg_dir = os.path.join(pd, pkg)
        tests_dir = os.path.join(pd, "tests")
        os.makedirs(pkg_dir, exist_ok=True)
        os.makedirs(tests_dir, exist_ok=True)

        # data path must resolve from the project root, not gen's cwd
        abs_data = (data_path if os.path.isabs(data_path)
                    else os.path.abspath(data_path))
        pkg_label_expr = "raw_label.indexed()" if indexed else "raw_label"
        pkg_wiring = (_NO_EVALUATOR_WIRING if indexed
                      else _EVALUATOR_WIRING).format(
            evaluator=evaluator, raw_label_var="raw_label")
        with open(os.path.join(pkg_dir, "features.py"), "w") as f:
            f.write(_FEATURES_TEMPLATE.format(
                input_path=args.input, feature_lines="\n".join(lines),
                predictor_names=", ".join(names), label_var=label_var))
        with open(os.path.join(pkg_dir, "app.py"), "w") as f:
            f.write(_PKG_APP_TEMPLATE.format(
                input_path=args.input, pkg=pkg, selector=selector,
                evaluator=evaluator, extra_imports=extra_imports,
                pkg_label_expr=pkg_label_expr,
                pipeline_body=_PIPELINE_BODY.format(
                    selector_expr=selector_expr, evaluator_wiring=pkg_wiring,
                    reader_fn=reader_fn, data_path=abs_data)))
        with open(os.path.join(pkg_dir, "__init__.py"), "w") as f:
            f.write(_PKG_INIT_TEMPLATE.format(pkg=pkg, problem=problem))
        with open(os.path.join(pd, "pyproject.toml"), "w") as f:
            f.write(_PYPROJECT_TEMPLATE.format(pkg=pkg, problem=problem))
        with open(os.path.join(pd, ".gitignore"), "w") as f:
            f.write(_GITIGNORE)
        with open(os.path.join(tests_dir, "test_app.py"), "w") as f:
            f.write(_PKG_TEST_TEMPLATE.format(pkg=pkg))
        params = {
            "model_location": os.path.join(pd, "model"),
            "metrics_location": os.path.join(pd, "metrics"),
            "write_location": os.path.join(pd, "scores"),
            "stage_params": {},
            "custom_tag_name": "app",
            "custom_tag_value": pkg,
        }
        with open(os.path.join(pd, "params.json"), "w") as f:
            json.dump(params, f, indent=2)
        with open(os.path.join(pd, "README.md"), "w") as f:
            f.write(_PROJECT_README.format(
                module_name=module_name, pkg=pkg,
                app_path=os.path.abspath(args.output),
                app_dir=os.path.dirname(os.path.abspath(args.output)) or ".",
                problem=problem, data_path=data_path))
        print(f"Project skeleton in {pd}/ ({pkg}/features.py, {pkg}/app.py, "
              f"pyproject.toml, tests/, params.json, README.md)")
    return 0


_PROJECT_README = """# {module_name}

Generated {problem} AutoML app over `{data_path}`.

Layout (the reference's templates/simple project skeleton, Python-world):

    {pkg}/features.py   raw typed feature definitions (edit types here)
    {pkg}/app.py        pipeline wiring + runner (edit the model grid here)
    pyproject.toml      installable package config (`pip install -e .`)
    tests/test_app.py   wiring smoke test (`python -m pytest tests/ -q`)
    params.json         run config (model/metrics/score locations,
                        per-stage `stage_params` overrides)

Train (writes the fitted model + metrics per params.json; run from this
directory):

    python -m transmogrifai_tpu.cli run --app {pkg}.app:runner \\
        --run-type train --params params.json

Score / evaluate / streaming-score with the same config:

    python -m transmogrifai_tpu.cli run --app {pkg}.app:runner \\
        --run-type score --params params.json
    python -m transmogrifai_tpu.cli run --app {pkg}.app:runner \\
        --run-type evaluate --params params.json

A single-module copy of the app was also written to `{app_path}`
(`run --app {module_name}:runner` with `{app_dir}` on PYTHONPATH).
"""


def _missing_mask(arr):
    import numpy as _np
    if arr.dtype == object:
        return _np.fromiter((v is None for v in arr), bool, len(arr))
    return _np.isnan(arr.astype(float))


import numpy as np  # noqa: E402  (used by cmd_gen)


def cmd_warmup(args) -> int:
    """Pre-warm the persistent compile cache (a cold full train pays
    minutes of XLA compiles, dominated by the sweep programs). Runs the
    DEFAULT selector sweep once on synthetic data of the target shape so
    those programs land in the cache directory
    (utils/compile_cache.py). The winner's refit and the
    fused scorer still compile on the first real train (their shapes
    depend on the winning config and the real pipeline), and a real
    train's sweep runs on the post-splitter row count — pass `--rows`
    matching that count for exact cache hits."""
    import time as _time

    import numpy as np

    from transmogrifai_tpu.evaluators import (
        BinaryClassificationEvaluator, MultiClassificationEvaluator,
        RegressionEvaluator)
    from transmogrifai_tpu.parallel.sweep import run_sweep
    from transmogrifai_tpu.selector.model_selector import (
        _default_binary_models, _default_multiclass_models,
        _default_regression_models)
    from transmogrifai_tpu.stages.base import FitContext
    from transmogrifai_tpu.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax.numpy as jnp
    n, d = int(args.rows), int(args.features)
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(n, d)).astype(np.float32))
    if args.problem == "regression":
        y = rng.normal(size=n).astype(np.float32)
        models = _default_regression_models()
        ev = RegressionEvaluator()
    else:
        k = 3 if args.problem == "multiclass" else 2
        y = rng.integers(k, size=n).astype(np.float32)
        models = (_default_multiclass_models()
                  if args.problem == "multiclass"
                  else _default_binary_models())
        ev = (MultiClassificationEvaluator()
              if args.problem == "multiclass"
              else BinaryClassificationEvaluator())
    folds = [((np.arange(n) % 3 != f).astype(np.float32),
              (np.arange(n) % 3 == f).astype(np.float32)) for f in range(3)]
    # the selector states the number of classes of every sweep program it
    # compiles: the warm-up states the same
    ctx = FitContext(n_rows=n, seed=42,
                     n_classes=None if args.problem == "regression" else k)
    t0 = _time.perf_counter()
    for est, grids in models:
        t1 = _time.perf_counter()
        try:
            run_sweep(est, grids, X, jnp.asarray(y), folds, ev, ctx)
            print(f"warmed {type(est).__name__} "
                  f"({len(grids)} grids) in "
                  f"{_time.perf_counter() - t1:.1f}s")
        except Exception as e:  # NaiveBayes non-negativity etc.
            print(f"skipped {type(est).__name__}: {e}")
    print(f"warmup done in {_time.perf_counter() - t0:.1f}s "
          f"(shapes: {n}x{d}, {args.problem})")
    return 0


def cmd_serve(args) -> int:
    """Boot the online scoring service over a saved model dir (no app
    factory needed — serving is model-only), or a multi-model
    FleetService when `--fleet-config` (or a params `serving.fleet`
    block) names one. Blocks until Ctrl-C.

    `--params` may carry a `serving` section (ServingParams JSON:
    buckets/queue/deadline knobs); flags override its host/port. The
    persistent XLA compile cache defaults ON here (cold replica starts
    are the production path this command exists for); `--compile-cache
    off` pins it off."""
    if args.platform:  # must happen before any backend init
        import jax
        jax.config.update("jax_platforms", args.platform)
    from transmogrifai_tpu.serving.http import (
        serve as http_serve, serve_fleet)
    from transmogrifai_tpu.serving.service import ScoringService
    from transmogrifai_tpu.workflow.params import OpParams, ServingParams

    params = OpParams.load(args.params) if args.params else OpParams()
    sp = params.serving or ServingParams()
    if args.host:
        sp.host = args.host
    if args.port is not None:
        sp.port = args.port
    if args.max_batch is not None:
        sp.max_batch = args.max_batch
    if args.compile_cache:
        sp.compile_cache = args.compile_cache == "on"
    elif sp.compile_cache is None:
        sp.compile_cache = True
    if args.resilience:
        sp.resilience = {**(sp.resilience or {}),
                         "enabled": args.resilience == "on"}
    if args.watchdog_stall_s is not None:
        sp.resilience = {**(sp.resilience or {}),
                         "watchdog_stall_s": args.watchdog_stall_s}
    if args.quantize:
        sp.quantize = None if args.quantize == "off" else args.quantize
    if args.tracing:
        sp.tracing = {**(sp.tracing or {}),
                      "enabled": args.tracing == "on"}
    if args.slo_config:
        import json as _json
        with open(args.slo_config) as fh:
            sp.slo = _json.load(fh)
    if args.flight_dir:
        sp.flight = {**(sp.flight or {}), "dir": args.flight_dir}

    # SIGTERM black box: an orchestrator tearing this replica down gets
    # a flight dump of its last seconds before the default handler runs
    import signal

    def _sigterm_dump(signum, frame):  # pragma: no cover - signal path
        from transmogrifai_tpu.obs import flight
        flight.request_dump("sigterm", force=True)
        signal.signal(signal.SIGTERM, signal.SIG_DFL)
        signal.raise_signal(signal.SIGTERM)

    try:
        signal.signal(signal.SIGTERM, _sigterm_dump)
    except (ValueError, OSError):  # non-main thread / exotic platform
        pass

    fleet_cfg = None
    if args.fleet_config:
        from transmogrifai_tpu.serving.fleet import FleetConfig
        fleet_cfg = FleetConfig.load(args.fleet_config)
        if fleet_cfg.compile_cache is None:
            fleet_cfg.compile_cache = sp.compile_cache
    elif sp.fleet:
        fleet_cfg = sp.to_fleet_config()

    if fleet_cfg is not None:
        from transmogrifai_tpu.serving.fleet import FleetService
        fleet = FleetService(fleet_cfg).start()
        server, thread = serve_fleet(fleet, host=sp.host, port=sp.port,
                                     block=False)
        shared = fleet.pool.report()
        print(f"fleet serving {len(fleet.models())} model(s) "
              f"({len(shared)} compiled program set(s)) on "
              f"http://{sp.host}:{server.port} — Ctrl-C to stop")
        try:
            while thread.is_alive():
                thread.join(1.0)
        except KeyboardInterrupt:
            print("shutting down")
        finally:
            server.shutdown()
            server.server_close()
            fleet.stop()
        return 0

    model_location = args.model_location or params.model_location
    if not model_location:
        raise SystemExit("serve: --model-location (or params."
                         "model_location) is required")
    service = ScoringService.from_path(model_location,
                                       config=sp.to_config())
    service.start()
    server, thread = http_serve(service, host=sp.host, port=sp.port,
                                block=False)
    print(f"serving {model_location} "
          f"(version {service.health()['model_version']}) on "
          f"http://{sp.host}:{server.port} — "
          f"buckets {list(service.ladder)}; Ctrl-C to stop")
    try:
        while thread.is_alive():
            thread.join(1.0)
    except KeyboardInterrupt:
        print("shutting down")
    finally:
        server.shutdown()
        server.server_close()
        service.stop()
    return 0


def cmd_lint(args) -> int:
    """Static JAX-pitfall lint (see analysis/lint.py); exit 1 on findings.
    Also exposed as `python -m transmogrifai_tpu.lint <paths>`."""
    from transmogrifai_tpu.analysis.lint import main as lint_main
    return lint_main(args.paths)


def main(argv: Optional[list] = None) -> int:
    parser = argparse.ArgumentParser(prog="transmogrifai_tpu")
    sub = parser.add_subparsers(dest="command", required=True)

    run_p = sub.add_parser("run", help="run a workflow application")
    run_p.add_argument("--app", required=True,
                       help="module:factory returning a WorkflowRunner")
    run_p.add_argument("--run-type", required=True,
                       choices=["train", "score", "streaming-score",
                                "features", "evaluate", "serve"])
    run_p.add_argument("--params", help="OpParams JSON path")
    run_p.add_argument("--platform", choices=["cpu", "tpu"],
                       help="force a JAX backend (before initialization)")
    run_p.add_argument("--model-location")
    run_p.add_argument("--write-location")
    run_p.add_argument("--metrics-location")
    run_p.add_argument(
        "--trace-out",
        help="write the run's span timeline as Perfetto/Chrome-trace "
             "JSON here (open in ui.perfetto.dev); a sibling "
             "<path>.events.jsonl carries the structured event log and "
             "the printed profile gains a goodput report")
    run_p.add_argument(
        "--sweep-checkpoint-dir",
        help="persist per-family sweep checkpoints + per-block journals "
             "here; a killed train re-run with the same command resumes "
             "at the first incomplete grid block")
    run_p.add_argument(
        "--feature-cache", choices=["off", "read", "readwrite"],
        help="persistent content-addressed cache for built device "
             "feature matrices: repeat runs over the same store skip the "
             "host memmap sweep and replay the wire artifact")
    run_p.add_argument(
        "--feature-cache-dir",
        help="artifact directory for --feature-cache (default "
             "<store root>/feature_cache); implies "
             "readwrite when --feature-cache is not given")
    run_p.add_argument(
        "--perf-model", choices=["on", "off"],
        help="learned cost model (perf/): on fits from the profile "
             "corpus and drives scheduler packing, the HBM gate, upload "
             "workers/depth, and the serving ladder; off pins every "
             "knob to the hand-tuned heuristics (same as "
             "TRANSMOGRIFAI_PERF_MODEL=0)")
    run_p.add_argument(
        "--perf-corpus-dir",
        help="profile-corpus directory for --perf-model (default "
             "TRANSMOGRIFAI_PERF_CORPUS_DIR or "
             "<store root>/perf)")
    run_p.add_argument(
        "--perf-model-path",
        help="fitted cost-model JSON (perf.model.CostModel.save) to "
             "load instead of fitting from the corpus — ships a tuned "
             "predictor with a saved workflow")
    run_p.add_argument(
        "--feature-cache-wire", choices=["auto", "f16", "int8", "int4"],
        help="cold-miss wire compression: int8/int4 ship a quantized "
             "wire with dequant fused into the donated device write "
             "(2-4x fewer bytes)")
    run_p.add_argument(
        "--mesh-devices", type=int,
        help="train over a device mesh of this many devices: selector "
             "sweeps distribute their grid blocks across the mesh's "
             "sweep axis via the work-stealing scheduler")
    run_p.add_argument(
        "--mesh-sweep", type=int,
        help="sweep-axis width of the mesh (default: all devices on "
             "sweep); remaining devices shard each worker's row data")
    run_p.add_argument(
        "--mesh-slices", type=int,
        help="lay the mesh out for a multi-slice pod (slice boundaries "
             "on the sweep axis; see make_multislice_mesh)")
    run_p.set_defaults(fn=cmd_run)

    gen_p = sub.add_parser("gen", help="generate a starter app from data")
    gen_p.add_argument(
        "--input", required=True,
        help="CSV, parquet, or avro data file — or a bare .avsc Avro "
             "schema (schema-only generation; readers point at the "
             "sibling .avro data path)")
    gen_p.add_argument("--response", required=True)
    gen_p.add_argument("--output", required=True, help="output .py path")
    gen_p.add_argument("--project-dir",
                       help="also write a project skeleton (params.json + "
                            "README) to this directory")
    gen_p.add_argument("--problem",
                       choices=["binary", "multiclass", "regression"])
    gen_p.add_argument("--light", action="store_true",
                       help="emit a small explicit model grid (quick first "
                            "iteration) instead of the full default sweep")
    gen_p.set_defaults(fn=cmd_gen)

    warm_p = sub.add_parser(
        "warmup",
        help="pre-compile the default SWEEP program shapes into the "
             "persistent XLA cache (the dominant cold-train cost; winner "
             "refit + fused scoring still compile on first train)")
    warm_p.add_argument("--rows", type=int, default=100_000,
                        help="training-matrix row count to warm for")
    warm_p.add_argument("--features", type=int, default=55,
                        help="post-transmogrify feature count to warm for")
    warm_p.add_argument("--problem",
                        choices=["binary", "multiclass", "regression"],
                        default="binary")
    warm_p.set_defaults(fn=cmd_warmup)

    serve_p = sub.add_parser(
        "serve",
        help="online scoring service over a saved model dir: "
             "shape-bucketed micro-batching, /score /healthz /metrics "
             "/reload, model hot-swap")
    serve_p.add_argument("--model-location",
                         help="serialized model dir (or set "
                              "model_location in --params)")
    serve_p.add_argument("--params", help="OpParams JSON path (optional "
                                          "`serving` section)")
    serve_p.add_argument("--host", default=None)
    serve_p.add_argument("--port", type=int, default=None,
                         help="0 binds an OS-assigned free port")
    serve_p.add_argument("--max-batch", type=int, default=None,
                         help="largest device batch (top shape bucket)")
    serve_p.add_argument("--platform", choices=["cpu", "tpu"],
                         help="force a JAX backend (before initialization)")
    serve_p.add_argument(
        "--fleet-config",
        help="FleetConfig JSON (serving/fleet.py): host N named models "
             "in this process with per-tenant quotas/priorities; "
             "same-shaped models share compiled bucket programs")
    serve_p.add_argument(
        "--compile-cache", choices=["on", "off"],
        help="persistent XLA compilation cache at startup (default on "
             "for this command): a replica or same-shaped swap warms "
             "on cache hits instead of recompiling the bucket ladder. "
             "JAX_COMPILATION_CACHE_DIR places the directory; unset, "
             "it is <store root>/xla-cache (default store root: "
             "<checkout>/.transmogrifai_store)")
    serve_p.add_argument(
        "--resilience", choices=["on", "off"],
        help="serving resilience layer (health state machine, circuit "
             "breaker + degraded fallback, hang watchdog; default on — "
             "fine knobs via the params `serving.resilience` block)")
    serve_p.add_argument(
        "--watchdog-stall-s", type=float, default=None,
        help="per-batch stall budget before the watchdog quarantines "
             "the in-flight batch and restarts the scoring thread")
    serve_p.add_argument(
        "--quantize", choices=["int8", "int4", "int8-calibrated",
                               "int4-calibrated", "off"],
        help="quantized inference: requests ship on an affine narrow "
             "wire and fitted tables compute in narrowed dtypes inside "
             "the fused bucket programs (per-feature tolerance "
             "(hi-lo)/(2*(2^bits-1)); '-calibrated' uses fit-time "
             "fleet-wide ranges persisted with the model — repeat "
             "scores bit-stable across batch compositions; default "
             "off = exact f32)")
    serve_p.add_argument(
        "--tracing", choices=["on", "off"],
        help="request-scoped tracing + tail sampling (default on): "
             "W3C traceparent honored/echoed, per-request phase spans, "
             "serving_phase_seconds histograms with trace-id exemplars")
    serve_p.add_argument(
        "--slo-config",
        help="SLOParams JSON path (obs/slo.py): declarative per-tenant "
             "availability/latency/staleness objectives with "
             "multi-window burn-rate alerting on /slo + slo_* gauges")
    serve_p.add_argument(
        "--flight-dir",
        help="crash-flight-recorder dump directory (default "
             "TRANSMOGRIFAI_FLIGHT_DIR or "
             "<store root>/flight)")
    serve_p.set_defaults(fn=cmd_serve)

    lint_p = sub.add_parser(
        "lint",
        help="AST-based JAX-pitfall lint over stage/kernel source "
             "(numpy-in-device, traced branches, unhashable statics, "
             "fit-path nondeterminism, host_prepare contract)")
    lint_p.add_argument("paths", nargs="+",
                        help=".py files or directories to lint")
    lint_p.set_defaults(fn=cmd_lint)

    args = parser.parse_args(argv)
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
