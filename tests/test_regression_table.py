"""A typed table with a numeric target through the regression selector,
against the plain references the benchmark's regression cell uses
(`benchmark/reference/regression.py`, `sanity_regression.py` and
`benchmark/train_check_regression.py`, which import nothing of the
program but what they hold it to), and the pieces that path brings: a
boosted chain that starts at the target's mean, the regression
evaluator's device form, the checker's branch for a label that is not
categorical, the spans and counters of a regression pass.
"""

import copy
import json
import os
import sys

import jax.numpy as jnp
import numpy as np
import pytest

from transmogrifai_tpu.evaluators import RegressionEvaluator
from transmogrifai_tpu.evaluators import device_metrics as dm
from transmogrifai_tpu.models import (
    OpGBTClassifier, OpGBTRegressor, OpXGBoostRegressor)
from transmogrifai_tpu.models import trees
from transmogrifai_tpu.models.linear import fit_linreg_enet, predict_linreg
from transmogrifai_tpu.obs.trace import TRACER
from transmogrifai_tpu.parallel import sweep as S
from transmogrifai_tpu.selector.validators import OpCrossValidation
from transmogrifai_tpu.stages.base import FitContext

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
QUIET = lambda _: None  # noqa: E731


@pytest.fixture(scope="module")
def bench():
    """The benchmark's regression modules (its directories on the path
    for this module's tests only)."""
    sys.path[:0] = [BENCH, os.path.join(BENCH, "drivers")]
    try:
        import datagen_airlines
        import train_check_regression
        import train_passes_regression
        from reference import encode_typed, regression, sanity_regression
        from reference import trees as ref_trees
        yield {"check": train_check_regression, "gen": datagen_airlines,
               "driver": train_passes_regression, "reg": regression,
               "sanity": sanity_regression, "encode": encode_typed,
               "trees": ref_trees}
    finally:
        sys.path.remove(BENCH)
        sys.path.remove(os.path.join(BENCH, "drivers"))


def _config(depth: int = 5) -> dict:
    """The regression configuration at a size the CPU trains in seconds:
    the forest's tree cut to `depth`, everything else as the cell has it
    (the boosted chain keeps the cell's rounds)."""
    with open(os.path.join(BENCH, "configs", "airlines.json")) as fh:
        config = copy.deepcopy(json.load(fh))
    forest = config["selector"]["families"][1]
    forest["grid"] = [dict(forest["grid"][0], max_depth=depth)]
    return config


def _encoded(bench, n=1500, seed=5, stream=1):
    """(kept float32 matrix, target) of a small table of the schema, by
    the references' encoder and checker."""
    config = _config()
    schema = config["schema"]
    cols, y = bench["gen"].make_table(schema, n, seed, stream)
    X, _, _, _ = bench["encode"].encode(
        cols, bench["gen"].column_names(schema))
    kept, _ = bench["sanity"].check(X, y)
    return X[:, kept], y


# --------------------------------------------------------------------- #
# the whole pass                                                        #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("seed,rows", [(31, 1500), (4000000007, 2500)])
def test_whole_pass_against_the_references(bench, seed, rows):
    config = _config()
    schema = config["schema"]
    driver = bench["driver"]
    ds, cols, y = driver.make_dataset(schema, rows, seed=seed, stream=1)
    with TRACER.span("run:train-regression", new_trace=True) as root:
        model, pf, checked = driver.train_once(
            ds, schema["label"], config["selector"])
    spans = TRACER.trace_spans(root.trace_id)
    last = bench["check"].extract(
        {"stream": 1, "model": model, "pf": pf, "checked": checked,
         "cols": cols, "y": y, "boost_folds": driver.boost_folds_of(spans)})
    compared = {c["name"]: c for c in bench["check"].compare(
        last, config, seed, say=QUIET)}
    for name in ("encode_err", "levels_mismatch", "kept_mismatch",
                 "holdout_rows_diff", "winner_mismatch", "edges_err",
                 "boost_train_rows_diff"):
        assert compared[name]["value"] == 0, (name, compared[name])
    # float32 on both sides off the chip: every gap is rounding
    for name in ("cv_metric_gap", "tree_cv_metric_gap",
                 "boost_cv_metric_gap", "boost_train_metric_gap",
                 "holdout_metric_gap", "label_corr_gap"):
        assert compared[name]["value"] < 2e-5, (name, compared[name])
    # a program that says nothing of its chains' training rows (one from
    # before the fetch span carried them) is not correct, and the check
    # does not raise
    silent = {c["name"]: c["value"] for c in bench["check"].compare(
        dict(last, boost_folds={}), config, seed, say=QUIET)}
    assert silent["boost_train_rows_diff"] == 1e30
    assert silent["boost_train_metric_gap"] == 1e30
    assert compared["split_gain_gap"]["value"] < 5e-3
    assert compared["leaf_gap"]["value"] < 1e-4
    fitted = model.fitted[pf.origin_stage.uid]
    summ = fitted.summary
    assert summ.problem_type == "regression"
    assert len(summ.validation_results) == 5
    # scalars only in the summary: the device form keeps no histogram
    assert set(summ.holdout_metrics) == {"RMSE", "MSE", "MAE", "R2"}
    assert set(summ.train_metrics) == {"RMSE", "MSE", "MAE", "R2"}
    # the spans of a regression pass say what ran
    sweep, = [s for s in spans if s.name == "selector:sweep"]
    assert sweep.attributes["problem"] == "regression"
    assert sweep.attributes["classes"] == 0
    evaluate, = [s for s in spans if s.name == "selector:evaluate"]
    assert evaluate.attributes["on_device"] is True
    cont, = [s for s in spans if s.name == "sanity:contingency"]
    assert cont.attributes["categorical_label"] is False
    boosted = [s for s in spans if s.name == "sweep:dispatch:gbt"]
    assert boosted and all(
        s.attributes["objective"] == "squared"
        and s.attributes["pad_depth"] == 6 for s in boosted)
    rounds = config["selector"]["families"][2]["params"]["n_estimators"]
    assert sum(s.attributes["rounds"] * s.attributes["pairs"]
               for s in boosted) == 2 * 3 * rounds
    counters = driver.counters_of(spans)
    assert counters["boost_rounds"] == 6 * rounds
    assert counters["hist_reads"] == 2 and counters["value_columns"] == 1
    assert counters["evaluate_on_device"] is True
    assert counters["categorical_label"] is False
    assert counters["encoded_width"] == 78
    assert counters["selected_width"] == last["X"].shape[1]


def test_the_selectors_metrics_never_take_the_host_evaluator(
        bench, monkeypatch):
    """`selector:evaluate` of a regression pass reduces on the device:
    the host `evaluate` (which wants the whole prediction) is not
    called."""
    def refuse(self, label, prediction):
        raise AssertionError("the host evaluator was called")
    monkeypatch.setattr(RegressionEvaluator, "evaluate", refuse)
    config = _config(depth=3)
    schema = config["schema"]
    ds, _, _ = bench["driver"].make_dataset(schema, 600, seed=3, stream=0)
    model, pf, _ = bench["driver"].train_once(
        ds, schema["label"], config["selector"])
    held = model.fitted[pf.origin_stage.uid].summary.holdout_metrics
    assert held["RMSE"] > 0 and np.isfinite(held["R2"])


# --------------------------------------------------------------------- #
# the tree families against the reference's trees                       #
# --------------------------------------------------------------------- #

def _binned(bench, X, n_bins=32):
    with bench["check"].train_check_typed._typed_reference():
        edges = bench["trees"].quantile_edges(X, n_bins)
    return bench["trees"].bin_matrix(X, edges), edges


def _same_splits(a_feat, a_bin, b_feat, b_bin, n_bins) -> float:
    """Share of the nodes of two stacks of trees that split alike (a
    node that does not split compares by that alone)."""
    a_feat, a_bin = np.asarray(a_feat), np.asarray(a_bin)
    b_feat, b_bin = np.asarray(b_feat), np.asarray(b_bin)
    whole = (a_bin >= n_bins) & (b_bin >= n_bins)
    same = whole | ((a_feat == b_feat) & (a_bin == b_bin))
    return float(same.mean())


@pytest.mark.parametrize("depth,mcw", [(3, 10.0), (6, 10.0), (6, 100.0)])
def test_regression_tree_is_the_references_tree(bench, depth, mcw):
    X, y = _encoded(bench, n=3000)
    Xb, _ = _binned(bench, X)
    w = (np.arange(len(y)) % 3 != 0).astype(np.float32)    # a fold's mask
    layout = trees.hist_layout(trees.indicator_columns(jnp.asarray(X)))
    got = trees.fit_forest(
        Xb, jnp.asarray(y, jnp.float32)[:, None], jnp.asarray(w), 1, depth,
        32, 1, 42, False, mcw, min_gain=jnp.float32(0.001), layout=layout)
    args = {"lam": 1e-6, "mcw": mcw, "min_gain": 0.0, "alpha": 0.0,
            "min_gain_norm": 0.001}
    with bench["check"].train_check_typed._typed_reference():
        want = bench["reg"].forest_fold(Xb, y, w, 1, depth, 32, 42, False,
                                        args, quant="bf16")
    assert _same_splits(got["feat"], got["bin"], want["feat"], want["bin"],
                        32) == 1.0
    np.testing.assert_allclose(np.asarray(got["leaf"]), want["leaf"],
                               rtol=2e-5, atol=1e-4)
    pred = trees.forest_regression_pred(got, Xb)["prediction"]
    ref = bench["reg"].forest_predict(want, Xb)
    np.testing.assert_allclose(np.asarray(pred), np.asarray(ref),
                               rtol=2e-5, atol=1e-4)


@pytest.mark.parametrize("mcw", [10.0, 100.0])
def test_twenty_round_squared_chain_is_the_references_chain(bench, mcw):
    X, y = _encoded(bench, n=3000)
    Xb, _ = _binned(bench, X)
    on = np.arange(len(y)) % 3 == 0
    w = (~on).astype(np.float32)
    layout = trees.hist_layout(trees.indicator_columns(jnp.asarray(X)))
    got, margin = trees.fit_gbt(
        Xb, jnp.asarray(y, jnp.float32), jnp.asarray(w), 20, 6, 32,
        jnp.float32(0.1), jnp.float32(1.0), "squared", mcw,
        min_gain_norm=jnp.float32(0.001), layout=layout)
    args = {"lam": 1.0, "mcw": mcw, "min_gain": 0.0, "alpha": 0.0,
            "min_gain_norm": 0.001}
    with bench["check"].train_check_typed._typed_reference():
        want, ref_margin, base = bench["reg"].boosted_fold(
            Xb, y, w, 20, 6, 32, 0.1, args, quant="bf16")
    assert base == pytest.approx(float(y[~on].mean()), rel=1e-6)
    assert got["feat"].shape == (20, 6, 64)
    # equal up to exact ties: a near-tie may fall either way on the last
    # bit of a float32 sum, and the rounds after it then differ
    assert _same_splits(got["feat"], got["bin"], want["feat"], want["bin"],
                        32) > 0.98
    rmse = bench["reg"].validation_metric("RMSE", y[on],
                                          np.asarray(margin)[on])
    ref_rmse = bench["reg"].validation_metric("RMSE", y[on],
                                              np.asarray(ref_margin)[on])
    assert rmse == pytest.approx(ref_rmse, rel=1e-4)


def test_the_sweeps_boosted_fold_metrics_are_the_references(bench):
    """`run_sweep` over the cell's boosted grid (the round-chunked host
    dispatch): every (configuration, fold) RMSE against the reference's
    own chain under the same fold mask."""
    X, y = _encoded(bench, n=2400)
    folds = OpCrossValidation(n_folds=3, seed=42).splits(y)
    grids = [{"max_depth": 6, "min_info_gain": 0.001,
              "min_instances_per_node": m} for m in (10.0, 100.0)]
    got = np.asarray(S.run_sweep(
        OpGBTRegressor(n_estimators=20, learning_rate=0.1, max_bins=32),
        grids, jnp.asarray(X), jnp.asarray(y, jnp.float32), folds,
        RegressionEvaluator(), FitContext(n_rows=len(y), seed=7)))
    Xb, _ = _binned(bench, X)
    for gi, grid in enumerate(grids):
        args = {"lam": 1.0, "mcw": grid["min_instances_per_node"],
                "min_gain": 0.0, "alpha": 0.0, "min_gain_norm": 0.001}
        for fi, (tr, va) in enumerate(folds):
            with bench["check"].train_check_typed._typed_reference():
                _, margin, _ = bench["reg"].boosted_fold(
                    Xb, y, tr, 20, 6, 32, 0.1, args, quant="bf16")
            on = np.asarray(va) > 0
            want = bench["reg"].validation_metric(
                "RMSE", y[on], np.asarray(margin)[on])
            assert got[gi, fi] == pytest.approx(want, rel=1e-4), (gi, fi)


# --------------------------------------------------------------------- #
# a chain starts at the mean                                            #
# --------------------------------------------------------------------- #

def test_base_score_is_the_weighted_mean_for_squared_loss_only():
    y = jnp.asarray([1.0, 2.0, 3.0, 10.0])
    w = jnp.asarray([1.0, 1.0, 0.0, 2.0])
    assert float(trees.gbt_base_score(y, w, "squared")) \
        == pytest.approx(23.0 / 4.0)
    assert float(trees.gbt_base_score(y, w, "logistic")) == 0.0
    assert float(trees.gbt_base_score(y, w * 0, "squared")) == 0.0


@pytest.mark.parametrize("est", [OpGBTRegressor, OpXGBoostRegressor])
def test_a_boosted_regressor_with_no_round_left_predicts_the_mean(est):
    """A chain whose trees cannot split (a child-weight floor above the
    table) still predicts the target's mean, not 0."""
    rng = np.random.default_rng(0)
    X = jnp.asarray(rng.normal(size=(200, 3)), jnp.float32)
    y = jnp.asarray(rng.normal(size=200) + 50.0, jnp.float32)
    model = est(n_estimators=3, max_depth=2, max_bins=8,
                min_child_weight=1e6).fit_arrays(
        X, y, jnp.ones(200), FitContext(n_rows=200, seed=1))
    assert model.base_score == pytest.approx(float(y.mean()), rel=1e-6)
    pred = np.asarray(model.predict_arrays(X)["prediction"])
    np.testing.assert_allclose(pred, float(y.mean()), rtol=1e-6)


def test_base_score_round_trips_and_an_older_model_loads_at_zero():
    rng = np.random.default_rng(1)
    X = jnp.asarray(rng.normal(size=(300, 4)), jnp.float32)
    y = jnp.asarray(3 * np.asarray(X[:, 0]) + 20.0, jnp.float32)
    model = OpGBTRegressor(n_estimators=5, max_depth=3, max_bins=8) \
        .fit_arrays(X, y, jnp.ones(300), FitContext(n_rows=300, seed=1))
    params = model.get_params()
    assert params["base_score"] == pytest.approx(float(y.mean()), rel=1e-6)
    again = trees.GBTRegressionModel(**params)
    np.testing.assert_array_equal(
        np.asarray(again.predict_arrays(X)["prediction"]),
        np.asarray(model.predict_arrays(X)["prediction"]))
    older = dict(params)
    del older["base_score"]             # saved before the chain had a start
    shifted = trees.GBTRegressionModel(**older)
    np.testing.assert_allclose(
        np.asarray(shifted.predict_arrays(X)["prediction"])
        + params["base_score"],
        np.asarray(model.predict_arrays(X)["prediction"]), rtol=1e-5)
    # starting at the mean, 5 rounds at 0.1 reach nearer than from 0
    err = np.asarray(model.predict_arrays(X)["prediction"]) - np.asarray(y)
    assert np.sqrt(np.mean(err ** 2)) < 3.0


def test_a_boosted_classifier_has_no_base_score():
    rng = np.random.default_rng(2)
    X = jnp.asarray(rng.normal(size=(200, 3)), jnp.float32)
    y = jnp.asarray(np.asarray(X[:, 0]) > 0, jnp.float32)
    model = OpGBTClassifier(n_estimators=2, max_depth=2, max_bins=8) \
        .fit_arrays(X, y, jnp.ones(200), FitContext(n_rows=200, seed=1))
    assert "base_score" not in model.get_params()


def test_a_warm_refit_continues_from_the_residents_start():
    rng = np.random.default_rng(3)
    X = jnp.asarray(rng.normal(size=(300, 4)), jnp.float32)
    y = jnp.asarray(2 * np.asarray(X[:, 1]) + 30.0, jnp.float32)
    ctx = FitContext(n_rows=300, seed=1)
    est = OpGBTRegressor(n_estimators=4, max_depth=2, max_bins=8)
    cold = est.fit_arrays(X, y, jnp.ones(300), ctx)
    est.init_params = cold.get_params()
    warm = est.fit_arrays(X, y, jnp.ones(300), ctx)
    assert warm.base_score == cold.base_score
    assert warm.trees["feat"].shape[0] == 5         # one round appended

    def rmse(m):
        e = np.asarray(m.predict_arrays(X)["prediction"]) - np.asarray(y)
        return float(np.sqrt(np.mean(e ** 2)))
    assert rmse(warm) < rmse(cold)


# --------------------------------------------------------------------- #
# the linear fit                                                        #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("reg,alpha", [(0.001, 0.1), (0.1, 0.5)])
def test_fit_linreg_enet_is_the_references_fit(bench, reg, alpha):
    X, y = _encoded(bench, n=2000)
    w = (np.arange(len(y)) % 3 != 1).astype(np.float32)
    got = fit_linreg_enet(jnp.asarray(X), jnp.asarray(y, jnp.float32),
                          jnp.asarray(w), jnp.float32(reg * alpha),
                          jnp.float32(reg * (1 - alpha)))
    want = bench["reg"].fit_enet(X, y, w, reg, alpha)
    # the raw columns' Lipschitz step is tiny: what 300 steps reach is
    # the same on both sides, and small
    scale = float(np.abs(np.asarray(want["beta"])).max())
    assert scale > 0
    np.testing.assert_allclose(np.asarray(got["beta"]),
                               np.asarray(want["beta"]), atol=1e-4 * scale)
    assert float(got["intercept"]) == pytest.approx(
        float(want["intercept"]), rel=1e-5)
    pred = predict_linreg(got, jnp.asarray(X))["prediction"]
    ref = bench["reg"].predict_linear(want, X)
    on = w == 0
    assert bench["reg"].validation_metric("RMSE", y[on],
                                          np.asarray(pred)[on]) \
        == pytest.approx(bench["reg"].validation_metric(
            "RMSE", y[on], np.asarray(ref)[on]), rel=1e-6)


# --------------------------------------------------------------------- #
# regression metrics on the device                                      #
# --------------------------------------------------------------------- #

def _metric_case(case):
    rng = np.random.default_rng(8)
    n = 5000
    y = np.round(rng.standard_t(3, size=n) * 30 + 8)
    pred = (y * 0.6 + rng.normal(size=n) * 20).astype(np.float32)
    mask = np.ones(n, np.float32)
    if case == "fold-mask":
        mask = (rng.uniform(size=n) < 0.33).astype(np.float32)
    elif case == "constant-target":
        y = np.full(n, 7.0)
    elif case == "far-tail":
        y[:5] = [1437.0, 1800.0, -25.0, 1210.0, 999.0]
    return y, pred, mask


@pytest.mark.parametrize("case", ["all-rows", "fold-mask",
                                  "constant-target", "far-tail"])
def test_regression_dev_against_float64(case, bench):
    y, pred, mask = _metric_case(case)
    got = {k: float(v) for k, v in dm.regression_dev(
        jnp.asarray(y, jnp.float32), jnp.asarray(pred),
        jnp.asarray(mask)).items()}
    on = mask > 0
    want = bench["reg"].metrics(y[on], pred[on])
    for name in ("RMSE", "MSE", "MAE", "R2"):
        assert got[name] == pytest.approx(want[name], rel=2e-5, abs=1e-6)


@pytest.mark.parametrize("metric", ["RMSE", "MAE", "R2"])
def test_evaluate_device_is_the_host_evaluators_metrics(metric):
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.data.columns import Column
    y, pred, _ = _metric_case("far-tail")
    ev = RegressionEvaluator(metric)
    host = ev.evaluate(
        Column(T.RealNN, {"value": y, "mask": np.ones(len(y), bool)}),
        Column(T.Prediction, {"prediction": pred})).to_json()
    dev = ev.evaluate_device(jnp.asarray(y, jnp.float32),
                             {"prediction": jnp.asarray(pred)},
                             None).to_json()
    for name in ("RMSE", "MSE", "MAE", "R2"):
        assert dev[name] == pytest.approx(host[name], rel=2e-5)
    assert host["SignedPercentageErrorHistogram"]       # the host's alone
    assert dev["SignedPercentageErrorHistogram"] == []
    # the metrics are python floats: nothing of the device is kept
    assert all(isinstance(dev[name], float) for name in
               ("RMSE", "MSE", "MAE", "R2"))


def test_the_held_metric_program_carries_the_kernels_name():
    text = dm.regression_metrics_dev("RMSE").lower(
        jnp.ones(16), jnp.ones(16)).as_text(debug_info=True)
    assert "metric:rmse" in text
    assert dm.regression_metrics_dev("RMSE") \
        is dm.regression_metrics_dev("RMSE")


# --------------------------------------------------------------------- #
# the checker, a label that is not categorical                          #
# --------------------------------------------------------------------- #

@pytest.mark.parametrize("rows,seed", [(1200, 1), (3000, 4000000021)])
def test_checker_without_contingency_against_its_reference(
        bench, rows, seed):
    from transmogrifai_tpu.automl import transmogrify
    from transmogrifai_tpu.automl.sanity_checker import SanityChecker
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.workflow import Workflow
    schema = _config()["schema"]
    ds, cols, y = bench["driver"].make_dataset(schema, rows, seed, 0)
    preds, label = FeatureBuilder.from_dataset(ds, response=schema["label"])
    checked = SanityChecker().set_input(
        label, transmogrify(preds)).get_output()
    with TRACER.span("run:check-regression", new_trace=True) as root:
        model = Workflow().set_result_features(checked, label) \
            .set_input_dataset(ds).train()
    fitted = model.fitted[checked.origin_stage.uid]
    X_ref, _, _, _ = bench["encode"].encode(
        cols, bench["gen"].column_names(schema))
    kept, corr = bench["sanity"].check(X_ref, y)
    assert [int(i) for i in fitted.indices] == kept
    # six all-zero null indicators and the three pivots' null columns go
    assert len(kept) == X_ref.shape[1] - 9
    got = np.nan_to_num([s["corrLabel"] for s in fitted.summary["stats"]])
    np.testing.assert_allclose(got, corr, atol=5e-6)
    assert fitted.summary["categoricalStats"] == []
    cont, = [s for s in TRACER.trace_spans(root.trace_id)
             if s.name == "sanity:contingency"]
    assert cont.attributes == {"categorical_label": False,
                               "tables": "none"}


def test_a_categorical_label_says_so_on_the_contingency_span():
    from transmogrifai_tpu import types as T
    from transmogrifai_tpu.automl import transmogrify
    from transmogrifai_tpu.automl.sanity_checker import SanityChecker
    from transmogrifai_tpu.data import Dataset
    from transmogrifai_tpu.features import FeatureBuilder
    from transmogrifai_tpu.workflow import Workflow
    rng = np.random.default_rng(0)
    n = 200
    ds = Dataset({"level": np.asarray(["a", "b", "c"], object)[
        rng.integers(0, 3, n)], "x": rng.normal(size=n),
        "y": rng.integers(0, 2, n).astype(np.float64)},
        {"level": T.PickList, "x": T.Real, "y": T.RealNN})
    preds, label = FeatureBuilder.from_dataset(ds, response="y")
    checked = SanityChecker().set_input(
        label, transmogrify(preds)).get_output()
    with TRACER.span("run:check-binary", new_trace=True) as root:
        Workflow().set_result_features(checked, label) \
            .set_input_dataset(ds).train()
    cont, = [s for s in TRACER.trace_spans(root.trace_id)
             if s.name == "sanity:contingency"]
    assert cont.attributes["categorical_label"] is True
    assert cont.attributes["groups"] >= 1


# --------------------------------------------------------------------- #
# the generator and the driver's record                                 #
# --------------------------------------------------------------------- #

def test_level_codes_are_distinct_capital_letters(bench):
    for card, letters in ((29, 2), (320, 3), (676, 2)):
        codes = bench["gen"].level_codes(card, letters)
        assert len(set(codes.tolist())) == card
        assert all(len(c) == letters and c.isalpha() and c.isupper()
                   for c in codes)
    with pytest.raises(ValueError):
        bench["gen"].level_codes(677, 2)


def test_the_table_is_the_seeds_and_has_the_sources_shape(bench):
    schema = _config()["schema"]
    gen = bench["gen"]
    cols, y = gen.make_table(schema, 20000, 4000000009, 2)
    again, y2 = gen.make_table(schema, 20000, 4000000009, 2)
    other, y3 = gen.make_table(schema, 20000, 4000000009, 3)
    assert all(np.array_equal(cols[k], again[k]) for k in cols)
    assert np.array_equal(y, y2) and not np.array_equal(y, y3)
    assert list(cols) == [name for name, _ in gen.column_names(schema)]
    assert cols["Month"].min() == 1 and cols["Month"].max() == 12
    assert cols["DayofMonth"].min() == 1 and cols["DayofMonth"].max() == 31
    feb = cols["Month"] == 2
    assert cols["DayofMonth"][feb].max() == 28
    assert set(np.unique(cols["DayOfWeek"])) == set(range(1, 8))
    for name in ("CRSDepTime", "CRSArrTime"):
        v = cols[name]
        assert v.min() >= 0 and v.max() <= 2359 and (v % 100).max() <= 59
    assert cols["Distance"].min() >= 30 and cols["Distance"].max() <= 4962
    assert len(np.unique(cols["UniqueCarrier"])) <= 29
    assert 150 < len(np.unique(cols["Origin"])) <= 320
    # whole minutes, median near 0, mean near 8, a long right tail and a
    # short negative side
    assert np.array_equal(y, np.round(y))
    assert abs(np.median(y)) <= 2 and 5 < y.mean() < 12
    assert np.mean(np.abs(y) <= 15) > 0.75
    assert y.max() > 500 and y.min() >= -25
    assert len(np.unique(y)) > 30           # not a categorical label


def test_the_driver_runs_whatever_program_is_there(bench, monkeypatch):
    """The driver asks the program for no private name when its `Run` is
    made: a program that cannot give what the configuration guarantees
    runs to its end and the check says so."""
    args = dict(cell={}, config=_config(), traffic={"rows_key": "train"},
                seed=1, rehearsal=True, fault=None, control=None, say=QUIET)
    monkeypatch.delattr(trees, "gbt_base_score")
    assert bench["driver"].Run(**args).rows == 8000


def test_boost_folds_of_reads_the_fetch_spans(bench):
    with TRACER.span("run:fetches", new_trace=True) as root:
        with TRACER.span("sweep:fetch:gbt") as sp:
            sp.set(grids=[0, 0], folds=[0, 1], train_loss=[4.0, 9.0],
                   train_weight=[10.0, 12.0])
        with TRACER.span("sweep:fetch:gbt") as sp:
            sp.set(grids=[1], folds=[2], train_loss=[1.0],
                   train_weight=[11.0])
        with TRACER.span("sweep:fetch:gbt"):
            pass                        # a program that says nothing
        with TRACER.span("sweep:fetch:forest") as sp:
            sp.set(grids=[5], folds=[0], train_loss=[0.0],
                   train_weight=[0.0])
    got = bench["driver"].boost_folds_of(TRACER.trace_spans(root.trace_id))
    assert got == {(0, 0): {"train_loss": 4.0, "train_weight": 10.0},
                   (0, 1): {"train_loss": 9.0, "train_weight": 12.0},
                   (1, 2): {"train_loss": 1.0, "train_weight": 11.0}}


def _reader(name):
    import importlib.util
    spec = importlib.util.spec_from_file_location(
        name, os.path.join(BENCH, "layer_metrics", name + ".py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


PASS_R = {"wall_s": 16.0, "spans": [
    ("sweep:dispatch:gbt", 2.0), ("compile:sweep:dispatch:gbt/prog", 0.5),
    ("sweep:dispatch:gbt", 1.0), ("sweep:dispatch:forest", 3.0),
    ("sweep:dispatch:linreg", 1.0)],
    "counters": {"boost_rounds": 120, "hist_reads": 2}}
PASS_S = {"wall_s": 14.0, "spans": [
    ("sweep:dispatch:gbt", 1.5), ("sweep:dispatch:forest", 3.0)],
    "counters": {"boost_rounds": 100, "hist_reads": 2}}
PASS_BARE = {"wall_s": 9.0, "spans": [("sweep:dispatch:forest", 3.0)],
             "counters": {"hist_reads": 1}}
REG_READINGS = {"train_boost_s": (2.5, 2.0),
                "train_boost_rounds": (120, 110)}


@pytest.mark.parametrize("name", sorted(REG_READINGS))
def test_regression_layer_metric_reader_on_a_hand_made_window(name):
    read = _reader(name)
    one, two = REG_READINGS[name]
    assert read({"window": {"passes": [PASS_R]}}) == pytest.approx(one)
    assert read({"window": {"passes": [PASS_R, PASS_S]}}) \
        == pytest.approx(two)
    assert read({"window": {"passes": []}}) is None
    assert read({"window": {}}) is None
    # a pass with no boosted dispatch, or a program without the span's
    # attributes, gives nothing and does not raise
    assert read({"window": {"passes": [PASS_BARE]}}) is None
    assert read({"window": {"passes": [
        {"wall_s": 1.0, "spans": [("sweep:dispatch:forest", 1.0)]}]}}) \
        is None


@pytest.mark.parametrize("name", ["train_reg_mfu_pct",
                                  "train_reg_busy_mfu_pct"])
def test_regression_share_of_the_peak_reads_on_the_chip_only(
        name, monkeypatch):
    monkeypatch.syspath_prepend(BENCH)
    for mod in ("work_regression", "work_multi", "work"):
        sys.modules.pop(mod, None)
    read = _reader(name)
    with open(os.path.join(BENCH, "configs", "airlines.json")) as fh:
        config = json.load(fh)
    with open(os.path.join(BENCH, "peaks.json")) as fh:
        peaks = json.load(fh)["TPU v5 lite"]
    obs = {"window": {"passes": [PASS_R], "rows": 5_000_000},
           "config": config, "peaks": None,
           "trace": {"n_ops": 5, "busy_s": 8.0}}
    assert read(obs) is None                         # off the chip
    share = read(dict(obs, peaks=peaks))
    import work_regression
    work = work_regression.train_pass(config, 5_000_000)
    least = max(work["ops"] / 197e12, work["bytes"] / 819e9)
    assert share == pytest.approx(
        100 * least / (16.0 if name == "train_reg_mfu_pct" else 8.0))
    assert 0 < share < 100
    assert read(dict(obs, peaks=peaks, trace=None)) is None \
        or name == "train_reg_mfu_pct"


def _boosted_sweep(est, grids, y, folds, X=None):
    """(fold metrics, the pass's `sweep:fetch:gbt` spans) of the
    round-chunked boosted sweep."""
    n = len(y)
    if X is None:
        X = np.arange(n, dtype=np.float32)[:, None]
    with TRACER.span("run:boosted-sweep", new_trace=True) as root:
        got = np.asarray(S.run_sweep(
            est, grids, jnp.asarray(X), jnp.asarray(y, jnp.float32), folds,
            RegressionEvaluator(), FitContext(n_rows=n, seed=7)))
    return got, [s for s in TRACER.trace_spans(root.trace_id)
                 if s.name == "sweep:fetch:gbt"]


def test_the_boosted_sweep_starts_every_pair_at_its_own_folds_mean():
    """With a learning rate of 0 a chain stays where it started: each
    fold's validation RMSE and training loss are those of its own
    training rows' mean."""
    y = np.asarray([0.0, 10.0, 20.0, 30.0], np.float32)
    folds = [(jnp.asarray([1.0, 1.0, 0.0, 0.0]),
              jnp.asarray([0.0, 0.0, 1.0, 1.0])),
             (jnp.asarray([0.0, 0.0, 1.0, 1.0]),
              jnp.asarray([1.0, 1.0, 0.0, 0.0]))]
    got, fetches = _boosted_sweep(
        OpGBTRegressor(n_estimators=2, learning_rate=0.0, max_bins=4),
        [{"max_depth": 2}], y, folds)
    # fold 0 starts at 5: validation rows 20 and 30; fold 1 at 25
    np.testing.assert_allclose(
        got, [[np.sqrt((15.0 ** 2 + 25.0 ** 2) / 2)] * 2], rtol=1e-6)
    said = {(g, f): (loss, weight) for sp in fetches for g, f, loss, weight
            in zip(*(sp.attributes[k] for k in (
                "grids", "folds", "train_loss", "train_weight")))}
    assert said == {(0, 0): (25.0, 2.0), (0, 1): (25.0, 2.0)}


@pytest.mark.parametrize("rounds", [1, 5])
def test_a_swept_chain_says_what_it_fitted(bench, rounds):
    """`train_loss` and `train_weight` on `sweep:fetch:gbt`: each real
    (configuration, fold) pair once, against the reference's own chain
    over the fold's training rows."""
    X, y = _encoded(bench, n=1800)
    folds = OpCrossValidation(n_folds=3, seed=42).splits(y)
    grids = [{"max_depth": 3, "min_info_gain": 0.001,
              "min_instances_per_node": m} for m in (10.0, 100.0)]
    _, fetches = _boosted_sweep(
        OpGBTRegressor(n_estimators=rounds, learning_rate=0.1, max_bins=32),
        grids, y, folds, X)
    said = bench["driver"].boost_folds_of(fetches)
    assert sorted(said) == [(g, f) for g in range(2) for f in range(3)]
    Xb, _ = _binned(bench, X)
    for (gi, fi), fit in said.items():
        tr = np.asarray(folds[fi][0])
        assert fit["train_weight"] == tr.sum()
        args = {"lam": 1.0, "mcw": grids[gi]["min_instances_per_node"],
                "min_gain": 0.0, "alpha": 0.0, "min_gain_norm": 0.001}
        with bench["check"].train_check_typed._typed_reference():
            _, margin, _ = bench["reg"].boosted_fold(
                Xb, y, tr, rounds, 3, 32, 0.1, args, quant="bf16")
        want = bench["reg"].metrics(y[tr > 0], np.asarray(margin)[tr > 0])
        assert fit["train_loss"] == pytest.approx(want["MSE"], rel=1e-4)


def test_train_summary_is_the_objectives_own_loss():
    y = jnp.asarray([0.0, 1.0, 1.0, 0.0])
    w = jnp.asarray([1.0, 2.0, 0.0, 1.0])
    margin = jnp.asarray([0.5, 2.0, -7.0, -1.0])
    sq = trees.gbt_train_summary(margin, y, w, "squared")
    assert float(sq["train_weight"]) == 4.0
    assert float(sq["train_loss"]) == pytest.approx(
        (0.25 + 2 * 1.0 + 1.0) / 4)
    lg = trees.gbt_train_summary(margin, y, w, "logistic")
    p = 1 / (1 + np.exp(-np.asarray(margin, np.float64)))
    ll = -(np.asarray(y) * np.log(p) + (1 - np.asarray(y)) * np.log(1 - p))
    assert float(lg["train_loss"]) == pytest.approx(
        float((ll * np.asarray(w)).sum() / 4), rel=1e-6)


def test_narrowed_label_correlations_tell_the_precision(bench):
    """The control's leg under the checker: at float32 operands the
    narrowed Gram product gives the float64 correlations; at fp8,
    saturating at 448, it does not."""
    X, y = _encoded(bench, n=3000)
    _, want = bench["sanity"].check(X, y)
    narrowed = bench["sanity"].narrowed_label_correlations
    same = narrowed(X, y, None, np.inf)
    assert np.abs(same - want).max() < 1e-5
    lower = narrowed(X, y, jnp.float8_e4m3fn, 448.0)
    assert np.abs(lower - want).max() > 1e-3
