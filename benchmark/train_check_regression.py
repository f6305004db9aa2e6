"""What decides `correct` for a training pass over a typed table with a
NUMERIC target (the regression selector: an elastic-net least-squares
fit, a regression forest and a squared-loss boosted chain, RMSE).

The last timed pass, at the timed size, against the plain references
(`reference/`, which import nothing of the program). Every gap on an
RMSE, MAE or R2 is RELATIVE (the target's scale is tens of minutes):

- `encode_err`, `levels_mismatch`, `kept_mismatch`: the matrix
  `transmogrify()` made, the pivot vocabularies and the columns the
  checker kept, as `train_check_multi.py` holds them, with
  `reference/encode_typed.py` and `reference/sanity_regression.py` (no
  contingency table: the label is not categorical) over the checker's
  documented row sample;
- `label_corr_gap`: widest |program - reference| correlation of an
  encoded column with the label (the checker's float32 Gram product
  against float64);
- `holdout_rows_diff`, `winner_mismatch`: the holdout's size; the
  reference's rule (the smallest mean RMSE) over the fold metrics the
  program reported;
- `cv_metric_gap`: one linear (configuration, fold) drawn from the seed,
  refitted by `reference/regression.py` `fit_enet` under the reference's
  fold mask AT THE STATED PRECISION (`precision.linear_products`), its
  RMSE on the fold's validation rows against the fold metric the
  program reported;
- `tree_cv_metric_gap`: one forest (configuration, fold) GROWN by the
  reference at the stated histogram precision under its own fold mask
  and the documented bootstrap, scored by the reference;
- `boost_cv_metric_gap`: one boosted (configuration, fold), the
  winner's configuration where a boosted one won: all its rounds grown
  by the reference from the weighted mean, scored by the reference;
- `boost_train_rows_diff`, `boost_train_metric_gap`: the same chain by
  the rows it was FITTED on, which a validation RMSE of a shallow chain
  on a noisy target cannot see: the sum of its training weights and the
  RMSE of its final margin over them, as the program's sweep put them
  on the chain's `sweep:fetch:gbt` span (`train_weight`, the square
  root of `train_loss`), against the reference fold's training rows
  and the reference chain's RMSE over them. A program that says
  neither reads 1e30 on both;
- the winner's parameters: every tree against exact float32 histograms
  and 64 seeded leaves against float64 sums (`split_gain_gap`,
  `leaf_gap`, `edges_err`); a boosted winner's base score is held with
  its leaves. The configuration requires that a tree family wins: a
  linear winner reads 1 on both, since nothing of it is held here;
- `holdout_metric_gap`: RMSE, MAE and R2 that the TIMED pass took on the
  device (`selector:evaluate`, in the selector's summary) against
  float64 numpy, twice: over the program's own holdout predictions,
  and over the reference's own prediction from the winner's parameters
  on its own holdout rows; the widest relative gap of the six.

`control` puts the reference one precision step down in the program's
place (fp8 products in the linear fold and in the checker's Gram
product; fp8 histogram values and bfloat16 leaf sums in the trees;
every fp8 narrowing saturates at fp8's largest finite number, 448,
since a cast past it gives NaN): it has to fail.
"""

from __future__ import annotations

import numpy as np

import datagen_airlines
import train_check
import train_check_multi
import train_check_typed
from reference import encode_typed as ref_encode
from reference import regression as ref_reg
from reference import sanity_regression as ref_sanity
from reference import selector as ref_selector
from reference import trees as ref_trees

FP8_MAX = 448.0     # float8_e4m3fn's largest finite number
METRICS = ("RMSE", "MAE", "R2")


def extract(last: dict) -> dict:
    """`train_check_typed.extract` (its Cramér's V table has to be
    empty: the checker took none), plus the checker's label
    correlations, a boosted winner's base score, what the sweep's
    boosted chains said of their training rows, and the holdout as the
    program split it with the program's own predictions there."""
    import jax.numpy as jnp
    model, pf, checked = last["model"], last["pf"], last["checked"]
    out = train_check_typed.extract(last)
    out["contingency_groups"] = len(out.pop("cramers_v"))
    out["boost_folds"] = last.get("boost_folds", {})
    out["label_corr"] = np.asarray(
        [float(s["corrLabel"]) for s in
         model.fitted[checked.origin_stage.uid].summary["stats"]])
    winner = model.fitted[pf.origin_stage.uid]
    if "trees" in out["winner"]:
        out["winner"]["base_score"] = float(
            getattr(winner, "base_score", 0.0))
    y = np.asarray(last["y"], np.float64)
    _, test_idx, _ = pf.origin_stage.splitter.split(y)
    rows = model.train_columns[checked.uid].device_value()[
        jnp.asarray(test_idx)]
    out["holdout"] = {
        "idx": np.asarray(test_idx),
        "pred": np.asarray(winner.predict_arrays(rows)["prediction"],
                           np.float64)}
    return out


def _rel(a: float, b: float) -> float:
    return abs(a - b) / max(abs(b), 1e-12)


def _steps(stated: str, control) -> dict:
    """`quant`, `leaf_quant` and `clip` of a reference tree: the stated
    histogram precision, or the control's one step down (its fp8 values
    saturate: `reference/regression.py`)."""
    if control:
        return {"quant": train_check.TREE_CONTROL,
                "leaf_quant": train_check.LEAF_CONTROL, "clip": FP8_MAX}
    return {"quant": stated, "leaf_quant": None, "clip": None}


def _linear_fold_metric(grid, X, y, w, on, iters, metric, dtype,
                        clip=None) -> float:
    import jax.numpy as jnp
    params = ref_reg.fit_enet(X, y, w, grid["reg_param"],
                              grid["elastic_net_param"], iters, dtype=dtype,
                              clip=clip)
    pred = ref_reg.predict_linear(params, jnp.asarray(X[on], jnp.float32),
                                  dtype, clip)
    return ref_reg.validation_metric(metric, y[on], np.asarray(pred))


def _forest_fold_metric(fam, grid, Xb, y, w, on, n_bins, metric, fit_seed,
                        steps) -> float:
    import jax.numpy as jnp
    trees = ref_reg.forest_fold(
        Xb, y, w, int(train_check._param(fam, grid, "n_trees", 1)),
        int(train_check._param(fam, grid, "max_depth", 5)), n_bins,
        fit_seed, bool(fam["params"].get("subsample_features", True)),
        train_check._tree_args(fam, grid), **steps)
    pred = ref_reg.forest_predict(
        trees, Xb[jnp.asarray(np.flatnonzero(on))])
    return ref_reg.validation_metric(metric, y[on], np.asarray(pred))


def _boosted_fold_metrics(fam, grid, Xb, y, w, on, n_bins, metric,
                          steps) -> tuple:
    """(the validation metric, the RMSE over the training rows) of the
    reference's chain under the fold's training weights `w`."""
    _, margin, _ = ref_reg.boosted_fold(
        Xb, y, w, int(train_check._param(fam, grid, "n_estimators", 1)),
        int(train_check._param(fam, grid, "max_depth", 5)), n_bins,
        float(train_check._param(fam, grid, "learning_rate", 0.1)),
        train_check._tree_args(fam, grid), **steps)
    margin = np.asarray(margin)
    fitted = np.asarray(w) > 0
    return (ref_reg.validation_metric(metric, y[on], margin[on]),
            ref_reg.metrics(y[fitted], margin[fitted])["RMSE"])


def _check_trees(win, fam, grid, X, y, n_bins, rng, fit_seed, control):
    """(split gap, leaf gap, edges err, trees used, base score used).
    With `control` the reference grows its own trees one precision step
    down first and they stand in for the program's."""
    import jax.numpy as jnp
    args = train_check._tree_args(fam, grid)
    edges = ref_trees.quantile_edges(X, n_bins)
    edges_err = float(np.abs(edges - win["edges"]).max()) \
        if edges.shape == win["edges"].shape else float("inf")
    Xb = ref_trees.bin_matrix(X, edges)
    n, d = X.shape
    forest = "Forest" in fam["estimator"]
    depth = int(train_check._param(fam, grid, "max_depth", 5))
    n_trees = int(train_check._param(
        fam, grid, "n_trees" if forest else "n_estimators", 1))
    trees = {key: np.asarray(v) for key, v in win["trees"].items()}
    if not control and trees["feat"].shape[:2] != (n_trees, depth):
        return 1.0, 1.0, edges_err, trees, 0.0    # not the stated ensemble
    lr = float(train_check._param(fam, grid, "learning_rate",
                                  win["learning_rate"]))
    yj = jnp.asarray(y, jnp.float32)
    ones = jnp.ones(n, jnp.float32)
    base = float(ref_reg.base_score(yj, ones)) if not forest else 0.0
    leaf_gap = 0.0
    if not forest and not control:
        # the chain's start is a leaf of its own: held like one
        leaf_gap = _rel(float(win.get("base_score", 0.0)),
                        float(np.mean(np.asarray(y, np.float64))))
    margin = jnp.full(n, base, jnp.float32)
    grown = {"feat": [], "bin": [], "leaf": []}
    split_gap = 0.0
    for t in range(n_trees):
        if forest:
            boot, fmask = ref_trees.forest_bootstrap(
                fit_seed, n_trees, t, n, d,
                bool(fam["params"].get("subsample_features", True)))
            G, H = ref_reg.forest_targets(yj, boot)
        else:
            fmask = None
            G, H = ref_reg.boosted_targets(margin, yj, ones)
        if control:
            tree = ref_trees.grow(
                Xb, jnp.clip(G, -FP8_MAX, FP8_MAX), H, depth, n_bins,
                fmask=fmask, quant=train_check.TREE_CONTROL,
                leaf_quant=train_check.LEAF_CONTROL, **args)
            for key in grown:
                grown[key].append(tree[key])
        else:
            tree = {key: v[t] for key, v in trees.items()}
        sg, lg, leaf_idx = ref_trees.verify(
            tree, Xb, G, H, n_bins, fmask=fmask, rng=rng, **args)
        split_gap, leaf_gap = max(split_gap, sg), max(leaf_gap, lg)
        if not forest:
            margin = margin + jnp.float32(lr) * ref_trees.leaf_values(
                tree, leaf_idx)[:, 0]
    if control:
        trees = {key: np.stack(v) for key, v in grown.items()}
    return split_gap, leaf_gap, edges_err, trees, base


def compare(last: dict, config: dict, seed: int, control=None,
            say=print) -> list:
    spec = config["selector"]
    limits = config["limits"]["train"]
    schema = config["schema"]
    stated = _steps(config["precision"]["histogram_values"], None)
    lower = _steps(None, control)
    metric = spec["metric"]
    enc_rules = schema["encoding"]
    names_types = datagen_airlines.column_names(schema)
    rng = np.random.default_rng([int(seed) % (1 << 63), 77])
    summ = last["summary"]
    y = np.asarray(last["y"], np.float64)
    numbers = {}

    # host_encode: the encoded matrix, the vocabularies, the checker
    X_ref, _, vocabs, _ = ref_encode.encode(
        last["cols"], names_types, enc_rules["top_k"],
        enc_rules["min_support"])
    enc = last["encoded"]
    numbers["encode_err"] = float(np.abs(enc - X_ref).max()) \
        if enc.shape == X_ref.shape else float("inf")
    numbers["levels_mismatch"] = float(sum(
        last["vocabs"].get(name) != vocab for name, vocab in vocabs.items()))
    rows = train_check_multi.checker_rows(len(y), enc_rules["checker_sample"])
    kept_ref, corr_ref = ref_sanity.check(
        X_ref if rows is None else X_ref[rows],
        y if rows is None else y[rows])
    numbers["kept_mismatch"] = float(len(set(kept_ref) ^ set(last["kept"]))
                                     + last["contingency_groups"])
    if control:
        got_corr = ref_sanity.narrowed_label_correlations(
            X_ref if rows is None else X_ref[rows],
            y if rows is None else y[rows],
            ref_trees.QUANT[train_check.LINEAR_CONTROL], FP8_MAX)
    else:
        got_corr = np.nan_to_num(last["label_corr"])
    numbers["label_corr_gap"] = float(np.abs(got_corr - corr_ref).max()) \
        if got_corr.shape == corr_ref.shape else float("inf")
    say(f"[check] encoded {enc.shape[1]} columns, reference "
        f"{X_ref.shape[1]}; kept {len(last['kept'])}, reference "
        f"{len(kept_ref)}; largest |label correlation| "
        f"{np.abs(corr_ref).max():.4f}")

    def out():
        return [{"name": name,
                 "value": value if np.isfinite(value) else 1e30,
                 "limit": float(limits[name])}
                for name, value in numbers.items()]

    if numbers["kept_mismatch"]:
        return out()        # the fits saw another matrix: nothing to hold
    X_ref = X_ref[:, kept_ref]
    del enc
    X = last["X"]
    numbers["encode_err"] = max(
        numbers["encode_err"], float(np.abs(X - X_ref).max())
        if X.shape == X_ref.shape else float("inf"))

    # selector_sweep: holdout, folds, the winner
    sp = spec["splitter"]
    train_idx, test_idx = ref_selector.holdout_split(
        len(y), sp["reserve_test_fraction"], sp["seed"])
    numbers["holdout_rows_diff"] = float(
        abs(len(train_idx) - summ["split"].get("n_train", -1))
        + abs(len(test_idx) - summ["split"].get("n_test", -1)))
    means = [float(np.mean(fm)) for _, _, fm in summ["results"]]
    want = ref_selector.winner(means, larger_is_better=metric == "R2")
    got = next((i for i, (m, g, _) in enumerate(summ["results"])
                if m == summ["best_model"] and g == summ["best_grid"]), -1)
    numbers["winner_mismatch"] = float(want != got)
    say(f"[check] mean validation {metric}: " + ", ".join(
        f"{m[2:8]}{i}={v:.4f}" for i, ((m, _, _), v) in enumerate(
            zip(summ["results"], means))))
    Xtr, ytr = X_ref[train_idx], y[train_idx]
    val = spec["validator"]
    folds = ref_selector.cv_masks(len(ytr), val["folds"], val["seed"])
    fs = spec["fit_seed"]
    fit_seed = fs["train_seed"] * 1000003 + fs["selector_layer"]

    # selector_sweep, linear model_kernels: one (config, fold)
    linear = [(i, r) for i, r in enumerate(summ["results"])
              if r[0] == "OpLinearRegression"]
    if linear:
        i, (_, grid, fold_metrics) = linear[int(rng.integers(len(linear)))]
        j = int(rng.integers(len(folds)))
        iters = int(config["precision"]["linear_iterations"])
        products = train_check_multi._linear_products(config)

        def fold_metric(dtype, clip=None):
            return _linear_fold_metric(
                grid, Xtr, ytr, folds[j][0], folds[j][1] > 0, iters, metric,
                dtype, clip)

        ref_m = fold_metric(ref_trees.QUANT.get(products))
        got_m = (fold_metric(ref_trees.QUANT[train_check.LINEAR_CONTROL],
                             FP8_MAX)
                 if control else float(fold_metrics[j]))
        numbers["cv_metric_gap"] = _rel(got_m, ref_m)
        say(f"[check] linear config {i} fold {j}: reference {ref_m:.6f}, "
            f"program {got_m:.6f}")

    with train_check_typed._typed_reference():
        rng_t = np.random.default_rng([int(seed) % (1 << 63), 78])
        Xb = None

        def binned(n_bins):
            return ref_trees.bin_matrix(
                Xtr, ref_trees.quantile_edges(Xtr, n_bins))

        # selector_sweep, tree model_kernels: one forest (config, fold)
        forests = [(i, r) for i, r in enumerate(summ["results"])
                   if "Forest" in r[0]]
        if forests:
            i, (name, grid, fold_metrics) = forests[
                int(rng_t.integers(len(forests)))]
            j = int(rng_t.integers(len(folds)))
            fam = train_check._family(config, name)
            n_bins = int(train_check._param(fam, grid, "max_bins", 32))
            Xb = binned(n_bins)
            on = folds[j][1] > 0
            ref_m = _forest_fold_metric(fam, grid, Xb, ytr, folds[j][0], on,
                                        n_bins, metric, fit_seed, stated)
            got_m = (_forest_fold_metric(fam, grid, Xb, ytr, folds[j][0],
                                         on, n_bins, metric, fit_seed, lower)
                     if control else float(fold_metrics[j]))
            numbers["tree_cv_metric_gap"] = _rel(got_m, ref_m)
            say(f"[check] forest config {i} fold {j}: reference "
                f"{ref_m:.6f}, program {got_m:.6f}")

        # one boosted (config, fold): the winner's where one won
        boosted = [(i, r) for i, r in enumerate(summ["results"])
                   if train_check._boosted(train_check._family(config, r[0]))]
        if boosted:
            won = [(i, r) for i, r in boosted if r[0] == summ["best_model"]
                   and r[1] == summ["best_grid"]]
            i, (name, grid, fold_metrics) = (won or boosted)[
                int(rng_t.integers(len(won or boosted)))]
            j = int(rng_t.integers(len(folds)))
            fam = train_check._family(config, name)
            n_bins = int(train_check._param(fam, grid, "max_bins", 32))
            if Xb is None:
                Xb = binned(n_bins)
            on = folds[j][1] > 0
            ref_m, ref_fit = _boosted_fold_metrics(
                fam, grid, Xb, ytr, folds[j][0], on, n_bins, metric, stated)
            fit_rows = float(folds[j][0].sum())
            if control:
                got_m, got_fit = _boosted_fold_metrics(
                    fam, grid, Xb, ytr, folds[j][0], on, n_bins, metric,
                    lower)
                got_rows = fit_rows
            else:
                # the chain's place among its family's configurations
                at = [r[1] for _, r in boosted if r[0] == name].index(grid)
                said = last["boost_folds"].get((at, j), {})
                got_m = float(fold_metrics[j])
                got_fit = float(np.sqrt(said.get("train_loss", np.inf)))
                got_rows = float(said.get("train_weight", np.inf))
            numbers["boost_cv_metric_gap"] = _rel(got_m, ref_m)
            numbers["boost_train_rows_diff"] = abs(got_rows - fit_rows)
            numbers["boost_train_metric_gap"] = _rel(got_fit, ref_fit)
            say(f"[check] boosted config {i} fold {j}: reference "
                f"{ref_m:.6f}, program {got_m:.6f}; over its {fit_rows:.0f}"
                f" training rows reference {ref_fit:.6f}, program "
                f"{got_fit:.6f} over {got_rows:.0f}")
        del Xb

        # model_kernels: the winner's parameters
        fam = train_check._family(config, summ["best_model"])
        grid = summ["best_grid"]
        win = last["winner"]
        if "trees" not in win:
            # a linear winner: the configuration requires a tree family
            numbers.update(split_gain_gap=1.0, leaf_gap=1.0)
            ref_pred = np.full(len(test_idx), np.nan)
        else:
            n_bins = int(train_check._param(fam, grid, "max_bins", 32))
            sg, lg, ee, trees, base = _check_trees(
                win, fam, grid, Xtr, ytr, n_bins, rng, fit_seed, control)
            numbers.update(split_gain_gap=sg, leaf_gap=lg, edges_err=ee)
            Xb_te = ref_trees.bin_matrix(X_ref[test_idx], win["edges"])
            if "Forest" in fam["estimator"]:
                ref_pred = np.asarray(ref_reg.forest_predict(trees, Xb_te))
            else:
                ref_pred = np.asarray(ref_reg.boosted_predict(
                    trees, Xb_te, float(train_check._param(
                        fam, grid, "learning_rate", win["learning_rate"])),
                    base if control else win.get("base_score", 0.0)))

    # evaluators: the holdout's metrics as the timed pass took them on
    # the device, against float64 numpy over the program's own
    # predictions and over the reference's
    hold = last["holdout"]
    own = ref_reg.metrics(y[hold["idx"]], hold["pred"])
    ref = ref_reg.metrics(y[test_idx], ref_pred)
    got = {m: float(summ["holdout"].get(m, np.inf)) for m in METRICS}
    gaps = [_rel(got[m], side[m]) for m in METRICS
            for side in ((ref,) if control else (own, ref))]
    numbers["holdout_metric_gap"] = float(np.max(np.nan_to_num(
        gaps, nan=np.inf)))
    say(f"[check] winner {summ['best_model']} {grid}; holdout RMSE "
        f"reference {ref['RMSE']:.6f}, own predictions {own['RMSE']:.6f}, "
        f"program {got['RMSE']}; R2 {ref['R2']:.6f}, program {got['R2']}")
    return out()
