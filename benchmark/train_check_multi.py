"""What decides `correct` for a training pass over a typed table with a
label of MANY classes (the multiclass selector: multinomial logistic
regression and a classifier forest, `DataCutter`, weighted F1).

The last timed pass, at the timed size, against the plain references
(`reference/`, which import nothing of the program):

- `encode_err`, `levels_mismatch`, `kept_mismatch`, `cramers_v_gap`:
  the matrix `transmogrify()` made, the pivot vocabularies, the columns
  the checker kept and Cramér's V over the K labels, as
  `train_check_typed.py` holds them, with `reference/encode_typed.py`
  (Real, Integral, Binary, PickList) and the checker's documented row
  sample (above 1,000,000 rows a seeded sample of that many:
  `numpy.random.default_rng(seed).choice(n, size, replace=False)`,
  sorted);
- `holdout_rows_diff`, `labels_kept_mismatch`: the holdout's size; the
  labels `reference/cutter.py` keeps against the ones the program's
  cutter kept (a label on one side only counts 1, another order 1);
- `winner_mismatch`: the reference's rule over the fold metrics the
  program reported;
- `cv_metric_gap`: EVERY multinomial (configuration, fold) refitted by
  `reference/linear.py` under the reference's fold mask AT THE STATED
  PRECISION (`precision.linear_products`: the operands of every matrix
  product of the fit and of the prediction narrowed to it, exact
  products, float32 sums, as the chip's default precision multiplies
  float32 matrices), its weighted F1 by `reference/multiclass.py`
  against the fold metric the program reported; the number is the
  SMALLEST of these gaps. Why the smallest: the fits are one lockstep
  program, so what goes wrong in it (a fit that saw half its rows) moves
  every one of them, by 1e-3 and more; and on this table's raw columns
  (a step of 1e-10) the weighted F1 of one fit is a step function of
  rounding noise: groups of identical rows sit on a tie between two
  labels, the largest worth 2.4e-3, and flip between two sound
  arithmetics in about one pair of four, while the other pairs agree to
  the metric's last float32 bits;
- `tree_cv_metric_gap`: one forest (configuration, fold) GROWN by
  `reference/trees.py` at the stated histogram precision under the
  reference's fold mask and the forest's documented bootstrap
  (`reference/trees.py` `forest_bootstrap`: per-tree keys split from
  PRNGKey(fit seed), Poisson(1) row counts, the feature mask), scored
  by the reference, against the fold metric the program reported. The
  reference reads the operand K + 1 times a level (a matmul a value
  column): the program's one composite read has to grow the same tree;
- the winner's parameters: the forest's every tree against exact
  float32 K-class histograms and 64 seeded leaves against float64 sums
  (`split_gain_gap`, `leaf_gap`, `edges_err`: `train_check._check_trees`).
  The configuration requires that the forest wins: any other winner
  reads 1 on both, since nothing of it is held here;
- `confusion_diff`: the holdout's (K, K) confusion matrix that the TIMED
  pass counted on the device (`selector:evaluate`, kept in the
  selector's summary as `Confusion`) against numpy's count of the
  program's own holdout predictions: widest absolute difference of a
  cell, 0 when sound;
- `holdout_metric_gap`: the reference's own prediction from the
  winner's parameters on its own holdout rows and its own weighted F1,
  against the holdout metric the program reported.

`control` puts the reference one precision step down in the program's
place (fp8 products in the logistic fold, fp8 histogram values and
bfloat16 leaf sums in the trees): it has to fail.
"""

from __future__ import annotations

import numpy as np

import datagen_kdd
import train_check
import train_check_typed
from reference import cutter as ref_cutter
from reference import encode_typed as ref_encode
from reference import linear as ref_linear
from reference import multiclass as ref_multi
from reference import pivot as ref_pivot
from reference import sanity as ref_sanity
from reference import selector as ref_selector
from reference import trees as ref_trees


def extract(last: dict) -> dict:
    """`train_check_typed.extract` plus the holdout as the program split
    it and the program's own predictions there (the table the timed pass
    counted from them is in the summary)."""
    import jax.numpy as jnp
    model, pf, checked = last["model"], last["pf"], last["checked"]
    out = train_check_typed.extract(last)
    y = np.asarray(last["y"], np.float64)
    _, test_idx, _ = pf.origin_stage.splitter.split(y)
    fitted = model.fitted[pf.origin_stage.uid]
    rows = model.train_columns[checked.uid].device_value()[
        jnp.asarray(test_idx)]
    pred = fitted.predict_arrays(rows)["prediction"]
    out["holdout"] = {"idx": np.asarray(test_idx),
                      "pred": np.asarray(pred, np.float64)}
    return out


def checker_rows(n: int, sample: dict):
    """The rows the checker reads: all, or its documented sample."""
    if n <= int(sample["upper_limit"]):
        return None
    rng = np.random.default_rng(int(sample["seed"]))
    return np.sort(rng.choice(n, size=int(sample["upper_limit"]),
                              replace=False))


def _linear_products(config: dict):
    """The operands of the program's float32 matrix products, as the
    configuration states them for the chip (`precision.linear_products`).
    The program leaves them to the backend's default: a rehearsal off
    the chip multiplies float32 exactly, and so does its reference."""
    import jax
    return (config["precision"]["linear_products"]
            if jax.default_backend() == "tpu" else None)


def _logistic_fold_metric(fam, grid, X, y, w, on, k, metric,
                          dtype) -> float:
    """The reference's own multinomial fit under the row weights `w` (a
    fold's training mask) with the operands of every matrix product at
    `dtype`, its prediction by the same product, and its own metric on
    the rows `on`."""
    import jax.numpy as jnp
    params = ref_linear.fit_enet(
        X, y, w, grid["reg_param"], grid["elastic_net_param"], k,
        fam["params"]["max_iter"], dtype=dtype)
    logits = ref_linear._mm(jnp.asarray(X[on], jnp.float32), params["W"],
                            dtype) + params["b"]
    return ref_multi.validation_metric(
        metric, y[on], {"prediction": np.asarray(jnp.argmax(logits, -1))},
        k)


def _forest_fold_metric(fam, grid, Xb, y, w, on, k, n_bins, metric,
                        fit_seed, quant, leaf_quant=None) -> float:
    """The reference's own forest under the row weights `w` (a fold's
    training mask) and the documented bootstrap, and its own metric on
    the rows `on`."""
    import jax
    import jax.numpy as jnp
    args = train_check._tree_args(fam, grid)
    depth = int(train_check._param(fam, grid, "max_depth", 5))
    n_trees = int(train_check._param(fam, grid, "n_trees", 1))
    n, d = Xb.shape
    Y1 = jax.nn.one_hot(jnp.asarray(y).astype(jnp.int32), k,
                        dtype=jnp.float32)
    wj = jnp.asarray(w, jnp.float32)
    grown = {"feat": [], "bin": [], "leaf": []}
    for t in range(n_trees):
        boot, fmask = ref_trees.forest_bootstrap(
            fit_seed, n_trees, t, n, d,
            bool(fam["params"].get("subsample_features", True)))
        boot = boot * wj
        tree = ref_trees.grow(Xb, Y1 * boot[:, None], boot, depth, n_bins,
                              fmask=fmask, quant=quant,
                              leaf_quant=leaf_quant, **args)
        for key in grown:
            grown[key].append(tree[key])
    trees = {key: np.stack(v) for key, v in grown.items()}
    pred = ref_trees.forest_predict(trees, Xb[jnp.asarray(np.flatnonzero(on))])
    return ref_multi.validation_metric(
        metric, y[on], {"prediction": np.asarray(pred["prediction"])}, k)


def compare(last: dict, config: dict, seed: int, control=None,
            say=print) -> list:
    spec = config["selector"]
    limits = config["limits"]["train"]
    schema = config["schema"]
    stated_hist = config["precision"]["histogram_values"]
    k = int(schema["classes"])
    metric = spec["metric"]
    enc_rules = schema["encoding"]
    names_types = datagen_kdd.column_names(schema)
    rng = np.random.default_rng([int(seed) % (1 << 63), 77])
    summ = last["summary"]
    y = np.asarray(last["y"], np.float64)
    numbers = {}

    # host_encode: the encoded matrix, the vocabularies, the checker
    X_ref, _, vocabs, groups = ref_encode.encode(
        last["cols"], names_types, enc_rules["top_k"],
        enc_rules["min_support"])
    enc = last["encoded"]
    numbers["encode_err"] = float(np.abs(enc - X_ref).max()) \
        if enc.shape == X_ref.shape else float("inf")
    numbers["levels_mismatch"] = float(sum(
        last["vocabs"].get(name) != vocab for name, vocab in vocabs.items()))
    rows = checker_rows(len(y), enc_rules["checker_sample"])
    kept_ref, v_ref = ref_sanity.check(
        X_ref if rows is None else X_ref[rows],
        y if rows is None else y[rows], groups)
    numbers["kept_mismatch"] = float(len(set(kept_ref) ^ set(last["kept"])))
    pivoted = [name for name, ty in names_types
               if ty in ref_pivot.PIVOT_TYPES]
    numbers["cramers_v_gap"] = max(
        abs(v_ref[name] - last["cramers_v"].get(f"{name}_{name}", np.inf))
        for name in pivoted)
    say(f"[check] encoded {enc.shape[1]} columns, reference "
        f"{X_ref.shape[1]}; kept {len(last['kept'])}, reference "
        f"{len(kept_ref)}; largest Cramér's V "
        f"{max(v_ref[name] for name in pivoted):.4f}")

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
    if X.shape == X_ref.shape:
        numbers["encode_err"] = max(numbers["encode_err"],
                                    float(np.abs(X - X_ref).max()))
    else:
        numbers["encode_err"] = float("inf")

    # selector_sweep: holdout, the cutter, folds, the winner
    sp = spec["splitter"]
    train_idx, test_idx = ref_selector.holdout_split(
        len(y), sp["reserve_test_fraction"], sp["seed"])
    numbers["holdout_rows_diff"] = float(
        abs(len(train_idx) - summ["split"].get("n_train", -1))
        + abs(len(test_idx) - summ["split"].get("n_test", -1)))
    keep, kept_labels = ref_cutter.cut(
        y[train_idx], sp["max_label_categories"], sp["min_label_fraction"])
    got_labels = [float(v) for v in summ["split"].get(
        "details", {}).get("labels_kept", [])]
    numbers["labels_kept_mismatch"] = float(
        len(set(kept_labels) ^ set(got_labels))
        + (kept_labels != got_labels))
    train_idx = train_idx[keep]
    means = [float(np.mean(fm)) for _, _, fm in summ["results"]]
    want = ref_selector.winner(means)
    got = next((i for i, (m, g, _) in enumerate(summ["results"])
                if m == summ["best_model"] and g == summ["best_grid"]), -1)
    numbers["winner_mismatch"] = float(want != got)
    say("[check] mean validation metrics: " + ", ".join(
        f"{m[:6]}{i}={v:.4f}" for i, ((m, _, _), v) in enumerate(
            zip(summ["results"], means)))
        + f"; labels kept {len(kept_labels)}, rows cut "
        f"{int((~keep).sum())}")
    Xtr, ytr = X_ref[train_idx], y[train_idx]
    val = spec["validator"]
    folds = ref_selector.cv_masks(len(ytr), val["folds"], val["seed"])
    fs = spec["fit_seed"]
    fit_seed = fs["train_seed"] * 1000003 + fs["selector_layer"]

    # selector_sweep, linear model_kernels: every multinomial (config,
    # fold), the best-reproduced of them compared
    linear = [(i, r) for i, r in enumerate(summ["results"])
              if r[0] == "OpLogisticRegression"]
    if linear:
        fam = train_check._family(config, "OpLogisticRegression")

        def fold_metric(grid, j, products):
            return _logistic_fold_metric(
                fam, grid, Xtr, ytr, folds[j][0], folds[j][1] > 0, k,
                metric, ref_trees.QUANT.get(products))

        gaps = []
        for i, (_, grid, fold_metrics) in linear:
            for j in range(len(folds)):
                ref_m = fold_metric(grid, j, _linear_products(config))
                got_m = (fold_metric(grid, j, train_check.LINEAR_CONTROL)
                         if control else float(fold_metrics[j]))
                gaps.append((abs(ref_m - got_m), i, j, ref_m, got_m))
        gap, i, j, ref_m, got_m = min(gaps)
        numbers["cv_metric_gap"] = gap
        say(f"[check] logistic (config, fold) gaps "
            + ", ".join(f"{g[0]:.2e}" for g in gaps)
            + f"; the smallest, config {i} fold {j}: reference "
            f"{ref_m:.6f}, program {got_m:.6f}")

    # the 0/1-column edge rule and a row block sized for a hundred
    # columns of 32 bins, as the typed cell sets them
    with train_check_typed._typed_reference():
        # selector_sweep, tree model_kernels: one forest (config, fold)
        forests = [(i, r) for i, r in enumerate(summ["results"])
                   if "Forest" in r[0]]
        if forests:
            rng_t = np.random.default_rng([int(seed) % (1 << 63), 78])
            i, (name, grid, fold_metrics) = forests[
                int(rng_t.integers(len(forests)))]
            j = int(rng_t.integers(len(folds)))
            fam = train_check._family(config, name)
            n_bins = int(train_check._param(fam, grid, "max_bins", 32))
            Xb = ref_trees.bin_matrix(
                Xtr, ref_trees.quantile_edges(Xtr, n_bins))
            on = folds[j][1] > 0
            ref_m = _forest_fold_metric(
                fam, grid, Xb, ytr, folds[j][0], on, k, n_bins, metric,
                fit_seed, stated_hist)
            got_m = (_forest_fold_metric(
                fam, grid, Xb, ytr, folds[j][0], on, k, n_bins, metric,
                fit_seed, train_check.TREE_CONTROL,
                train_check.LEAF_CONTROL)
                if control else float(fold_metrics[j]))
            numbers["tree_cv_metric_gap"] = abs(ref_m - got_m)
            say(f"[check] forest config {i} fold {j}: reference "
                f"{ref_m:.6f}, program {got_m:.6f}")
            del Xb

        # model_kernels: the winner's parameters
        fam = train_check._family(config, summ["best_model"])
        grid = summ["best_grid"]
        win = last["winner"]
        if "trees" not in win or win["trees"]["leaf"].shape[-1] != k:
            # not the K-class forest the configuration requires to win:
            # nothing of it can be held
            numbers.update(split_gain_gap=1.0, leaf_gap=1.0)
            pred = {"prediction": np.full(len(test_idx), -1.0)}
        else:
            n_bins = int(train_check._param(fam, grid, "max_bins", 32))
            sg, lg, ee, trees = train_check._check_trees(
                win, fam, grid, Xtr, ytr, k, n_bins, rng, fit_seed,
                train_check.TREE_CONTROL if control else None)
            numbers.update(split_gain_gap=sg, leaf_gap=lg, edges_err=ee)
            pred = ref_trees.forest_predict(
                trees, ref_trees.bin_matrix(X_ref[test_idx], win["edges"]))

    # evaluators: the holdout's table as the timed pass counted it on the
    # device, against numpy's count of the program's own predictions
    hold = last["holdout"]
    conf_np = ref_multi.confusion(y[hold["idx"]], hold["pred"], k)
    conf = np.asarray(summ["holdout"].get("Confusion", ()), np.float64)
    numbers["confusion_diff"] = float(np.abs(conf - conf_np).max()) \
        if conf.shape == conf_np.shape else float("inf")
    ref_hold = ref_multi.validation_metric(
        metric, y[test_idx], {"prediction": np.asarray(pred["prediction"])},
        k)
    numbers["holdout_metric_gap"] = abs(
        ref_hold - float(summ["holdout"].get(metric, np.inf)))
    say(f"[check] winner {summ['best_model']} {grid}; holdout {metric} "
        f"reference {ref_hold:.6f}, program {summ['holdout'].get(metric)}; "
        f"holdout confusion diagonal {int(np.trace(conf_np))} of "
        f"{int(conf_np.sum())}")
    return out()
