"""What decides `correct` for a training pass over a table of numeric
columns with a label of HUNDREDS of classes (the multiclass selector:
multinomial logistic regression and a softmax boosted chain,
`DataCutter`, weighted F1).

The last timed pass, at the timed size, against the plain references
(`reference/`, which import nothing of the program):

- `encode_err`, `kept_mismatch`: the matrix `transmogrify()` made and
  the columns the checker kept, with `reference/encode_typed.py` and
  `reference/sanity_regression.py` (a label of more whole values than
  the checker's `categorical_label_max_card` takes no contingency
  table: a group the program's checker priced counts 1);
- `holdout_rows_diff`, `labels_kept_mismatch`, `winner_mismatch`: the
  holdout's size, the labels `reference/cutter.py` keeps (none is cut
  at `max_label_categories` = K), the reference's rule over the fold
  metrics the program reported;
- `cv_metric_gap`: every multinomial (configuration, fold) refitted by
  `reference/linear.py` at the stated products, its weighted F1 against
  the fold metric the program reported; for each configuration the
  MEDIAN over its folds (a row near a tie between two of 355 labels
  falls either way between two sound arithmetics, in one fold, while a
  fault in the lockstep program moves every fold), and the largest of
  those medians;
- `boost_cv_metric_gap`: one boosted (configuration, fold) drawn from
  the seed, the winner's configuration where a boosted one won, every
  round of the chain grown by `reference/softmax.py` at the stated
  histogram precision from the fold's training rows, its weighted F1 on
  the fold's validation rows against the fold metric the program
  reported;
- `boost_train_rows_diff`, `boost_train_metric_gap`: the same chain by
  the rows it was FITTED on, as the program's sweep put them on the
  chain's `sweep:fetch:gbt` span: the sum of its training weights
  against the reference fold's, and its cross-entropy over them against
  the reference chain's (relative). A program that says neither reads
  1e30 on both;
- the winner's parameters, which the configuration requires to be the
  boosted chain's (any other winner reads 1 on the three): its first
  round's K trees against exact histograms of the refit's rows
  (`split_gain_gap`, `leaf_gap`: `reference/softmax.py`
  `verify_round`), its bin edges (`edges_err`), and `class_margin_gap`,
  its (n, K) margin after every round at 4,096 seeded training rows
  against the reference's chain that keeps the program's splits and
  takes its own leaves from its own gradients (`teacher_margin`), as a
  share of the largest reference margin there;
- `holdout_metric_gap`: the weighted F1 the TIMED pass took on the
  device (`selector:evaluate`) against float64 numpy, twice: over the
  program's own holdout predictions, and over the reference's own
  prediction from the winner's parameters on its own holdout rows.

`control` puts the reference one precision step down in the program's
place (fp8 products in the logistic folds; fp8 histogram values and
bfloat16 leaf sums in the boosted chain, the first round's trees and the
chain's leaves): it has to fail.
"""

from __future__ import annotations

import numpy as np

import datagen_dionis
import train_check
import train_check_multi
import train_check_typed
from reference import bins as ref_bins
from reference import cutter as ref_cutter
from reference import encode_typed as ref_encode
from reference import multiclass as ref_multi
from reference import sanity_regression as ref_sanity
from reference import selector as ref_selector
from reference import softmax as ref_softmax
from reference import trees as ref_trees

MARGIN_ROWS = 4096


def extract(last: dict) -> dict:
    """`train_check_typed.extract` (its Cramér's V table has to be
    empty: the checker took none), what the sweep's boosted chains said
    of their training rows, and the holdout as the program split it with
    the program's own predictions there."""
    import jax.numpy as jnp
    model, pf, checked = last["model"], last["pf"], last["checked"]
    out = train_check_typed.extract(last)
    out["contingency_groups"] = len(out.pop("cramers_v"))
    out["boost_folds"] = last.get("boost_folds", {})
    y = np.asarray(last["y"], np.float64)
    _, test_idx, _ = pf.origin_stage.splitter.split(y)
    rows = model.train_columns[checked.uid].device_value()[
        jnp.asarray(test_idx)]
    winner = model.fitted[pf.origin_stage.uid]
    out["holdout"] = {
        "idx": np.asarray(test_idx),
        "pred": np.asarray(winner.predict_arrays(rows)["prediction"],
                           np.float64)}
    return out


def _chain_args(fam: dict, grid: dict) -> dict:
    """The chain's constants as `reference/softmax.py` names them."""
    a = train_check._tree_args(fam, grid)
    return {"depth": int(train_check._param(fam, grid, "max_depth", 6)),
            "eta": float(train_check._param(fam, grid, "eta", 0.3)),
            "lam": a["lam"], "mcw": a["mcw"], "min_gain": a["min_gain"],
            "min_gain_norm": a["min_gain_norm"], "alpha": a["alpha"]}


def _steps(stated: str, control) -> dict:
    if control:
        return {"quant": train_check.TREE_CONTROL,
                "leaf_quant": train_check.LEAF_CONTROL}
    return {"quant": stated, "leaf_quant": None}


def _f1(metric: str, y, margin, k: int) -> float:
    return ref_multi.validation_metric(
        metric, y, {"prediction": np.asarray(margin).argmax(1)}, k)


def _boosted_fold(Xb, y, fit, on, k, rounds, n_bins, args, steps, metric):
    """(the validation metric, the cross-entropy over the training rows)
    of the reference's chain grown from the rows `fit`."""
    fit_rows, val_rows = np.flatnonzero(fit), np.flatnonzero(on)
    a = dict(args)
    depth, eta = a.pop("depth"), a.pop("eta")
    trees, margin = ref_softmax.boost(
        Xb[fit_rows], y[fit_rows], np.ones(len(fit_rows), np.float32), k,
        rounds, depth, n_bins, eta, **a, **steps)
    ce = ref_softmax.mlogloss(margin, y[fit_rows],
                              np.ones(len(fit_rows)))
    val = ref_softmax.predict_margin(trees, Xb[val_rows], eta)
    return _f1(metric, y[val_rows], val, k), ce


def compare(last: dict, config: dict, seed: int, control=None,
            say=print) -> list:
    import jax.numpy as jnp
    spec = config["selector"]
    limits = config["limits"]["train"]
    schema = config["schema"]
    stated = _steps(config["precision"]["histogram_values"], None)
    steps = _steps(None, control) if control else stated
    k = int(schema["classes"])
    metric = spec["metric"]
    enc_rules = schema["encoding"]
    names_types = datagen_dionis.column_names(schema)
    rng = np.random.default_rng([int(seed) % (1 << 63), 77])
    summ = last["summary"]
    y = np.asarray(last["y"], np.float64)
    numbers = {}

    def out():
        return [{"name": name,
                 "value": value if np.isfinite(value) else 1e30,
                 "limit": float(limits[name])}
                for name, value in numbers.items()]

    # host_encode: the encoded matrix, the checker
    X_ref, _, _, _ = ref_encode.encode(
        last["cols"], names_types, enc_rules["top_k"],
        enc_rules["min_support"])
    enc = last["encoded"]
    numbers["encode_err"] = float(np.abs(enc - X_ref).max()) \
        if enc.shape == X_ref.shape else float("inf")
    rows = train_check_multi.checker_rows(len(y), enc_rules["checker_sample"])
    kept_ref, _ = ref_sanity.check(X_ref if rows is None else X_ref[rows],
                                   y if rows is None else y[rows])
    numbers["kept_mismatch"] = float(len(set(kept_ref) ^ set(last["kept"]))
                                     + last["contingency_groups"])
    say(f"[check] encoded {enc.shape[1]} columns, reference "
        f"{X_ref.shape[1]}; kept {len(last['kept'])}, reference "
        f"{len(kept_ref)}")
    if numbers["kept_mismatch"]:
        return out()        # the fits saw another matrix: nothing to hold
    X_ref = X_ref[:, kept_ref]
    del enc
    X = last["X"]
    numbers["encode_err"] = max(
        numbers["encode_err"], float(np.abs(X - X_ref).max())
        if X.shape == X_ref.shape else float("inf"))

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
        f"{m[2:8]}{i}={v:.4f}" for i, ((m, _, _), v) in enumerate(
            zip(summ["results"], means)))
        + f"; labels kept {len(kept_labels)}, rows cut "
        f"{int((~keep).sum())}")
    Xtr, ytr = X_ref[train_idx], y[train_idx]
    val = spec["validator"]
    folds = ref_selector.cv_masks(len(ytr), val["folds"], val["seed"])

    # selector_sweep, linear model_kernels: every multinomial (config,
    # fold), the largest median over folds compared
    linear = [(i, r) for i, r in enumerate(summ["results"])
              if r[0] == "OpLogisticRegression"]
    if linear:
        fam = train_check._family(config, "OpLogisticRegression")

        def fold_metric(grid, j, products):
            return train_check_multi._logistic_fold_metric(
                fam, grid, Xtr, ytr, folds[j][0], folds[j][1] > 0, k,
                metric, ref_trees.QUANT.get(products))

        gaps = []
        for i, (_, grid, fold_metrics) in linear:
            for j in range(len(folds)):
                ref_m = fold_metric(
                    grid, j, train_check_multi._linear_products(config))
                got_m = (fold_metric(grid, j, train_check.LINEAR_CONTROL)
                         if control else float(fold_metrics[j]))
                gaps.append((abs(ref_m - got_m), i, j, ref_m, got_m))
        medians = {i: float(np.median([g[0] for g in gaps if g[1] == i]))
                   for i, _ in linear}
        numbers["cv_metric_gap"] = max(medians.values())
        say("[check] logistic (config, fold) gaps "
            + ", ".join(f"{g[0]:.2e}" for g in gaps)
            + "; metrics reference/program "
            + ", ".join(f"{g[3]:.6f}/{g[4]:.6f}" for g in gaps)
            + "; the medians by config "
            + ", ".join(f"{i}: {m:.2e}" for i, m in medians.items()))

    # selector_sweep, tree model_kernels: one boosted (config, fold)
    boosted = [(i, r) for i, r in enumerate(summ["results"])
               if train_check._boosted(train_check._family(config, r[0]))]
    rng_t = np.random.default_rng([int(seed) % (1 << 63), 78])
    if boosted:
        won = [(i, r) for i, r in boosted if r[0] == summ["best_model"]
               and r[1] == summ["best_grid"]]
        i, (name, grid, fold_metrics) = (won or boosted)[
            int(rng_t.integers(len(won or boosted)))]
        j = int(rng_t.integers(len(folds)))
        fam = train_check._family(config, name)
        n_bins = int(train_check._param(fam, grid, "max_bins", 32))
        rounds = int(train_check._param(fam, grid, "n_estimators", 1))
        Xb = np.asarray(ref_trees.bin_matrix(
            Xtr, ref_bins.typed_edges(Xtr, n_bins)))
        fit, on = folds[j][0] > 0, folds[j][1] > 0
        args = _chain_args(fam, grid)
        ref_m, ref_fit = _boosted_fold(Xb, ytr, fit, on, k, rounds, n_bins,
                                       args, stated, metric)
        fit_rows = float(fit.sum())
        if control:
            got_m, got_fit = _boosted_fold(Xb, ytr, fit, on, k, rounds,
                                           n_bins, args, steps, metric)
            got_rows = fit_rows
        else:
            # the chain's place among its family's configurations
            at = [r[1] for _, r in boosted if r[0] == name].index(grid)
            said = last["boost_folds"].get((at, j), {})
            got_m = float(fold_metrics[j])
            got_fit = float(said.get("train_loss", np.inf))
            got_rows = float(said.get("train_weight", np.inf))
        numbers["boost_cv_metric_gap"] = abs(got_m - ref_m)
        numbers["boost_train_rows_diff"] = abs(got_rows - fit_rows)
        numbers["boost_train_metric_gap"] = abs(got_fit - ref_fit) \
            / max(abs(ref_fit), 1e-12)
        say(f"[check] boosted config {i} fold {j}: reference {ref_m:.6f}, "
            f"program {got_m:.6f}; over its {fit_rows:.0f} training rows "
            f"cross-entropy reference {ref_fit:.6f}, program {got_fit:.6f} "
            f"over {got_rows:.0f}")
        del Xb

    # model_kernels: the winner's parameters
    fam = train_check._family(config, summ["best_model"])
    grid = summ["best_grid"]
    win = last["winner"]
    n_est = int(train_check._param(fam, grid, "n_estimators", 0))
    depth = int(train_check._param(fam, grid, "max_depth", 0))
    shape = np.shape(win.get("trees", {}).get("feat"))
    if not train_check._boosted(fam) or shape != (n_est, k, depth,
                                                   2 ** depth):
        # not the K-class chain the configuration requires to win (or
        # not of its rounds): nothing of it can be held
        numbers.update(split_gain_gap=1.0, leaf_gap=1.0,
                       class_margin_gap=1.0)
        ref_pred = np.full(len(test_idx), -1.0)
    else:
        n_bins = int(train_check._param(fam, grid, "max_bins", 32))
        args = _chain_args(fam, grid)
        edges = ref_bins.typed_edges(Xtr, n_bins)
        numbers["edges_err"] = float(np.abs(edges - win["edges"]).max()) \
            if edges.shape == win["edges"].shape else float("inf")
        Xb = np.asarray(ref_trees.bin_matrix(Xtr, edges))
        trees = {"feat": win["trees"]["feat"], "bin": win["trees"]["bin"],
                 "leaf": np.asarray(win["trees"]["leaf"])[..., 0]}
        ones = np.ones(len(ytr), np.float32)
        G, H = ref_softmax.grad_hess(jnp.zeros((len(ytr), k), jnp.float32),
                                     jnp.asarray(ytr, jnp.float32),
                                     jnp.asarray(ones))
        first = {key: v[0] for key, v in trees.items()}
        if control:
            first, _ = ref_softmax.grow_round(
                Xb, G, H, args["depth"], n_bins, args["lam"], args["mcw"],
                args["min_gain"], args["min_gain_norm"], args["alpha"],
                **steps)
        sg, lg = ref_softmax.verify_round(
            first, Xb, G, H, n_bins, args["lam"], args["mcw"],
            args["min_gain"], args["min_gain_norm"], args["alpha"], rng)
        numbers.update(split_gain_gap=sg, leaf_gap=lg)
        del G, H
        _, teacher = ref_softmax.teacher_margin(
            trees, Xb, ytr, ones, args["eta"], args["lam"], args["alpha"])
        at = np.sort(rng.choice(len(ytr), min(MARGIN_ROWS, len(ytr)),
                                replace=False))
        ref_at = np.asarray(teacher, np.float64)[at]
        del teacher
        got_trees = trees
        if control:
            leaves, _ = ref_softmax.teacher_margin(
                trees, Xb, ytr, ones, args["eta"], args["lam"],
                args["alpha"], leaf_quant=steps["leaf_quant"])
            got_trees = dict(trees, leaf=leaves)
        got_at = np.asarray(ref_softmax.predict_margin(
            got_trees, Xb[at], args["eta"]), np.float64)
        numbers["class_margin_gap"] = float(
            np.abs(got_at - ref_at).max() / max(np.abs(ref_at).max(), 1e-12))
        del Xb
        Xb_te = ref_trees.bin_matrix(X_ref[test_idx], win["edges"])
        ref_pred = np.asarray(ref_softmax.predict_margin(
            trees, Xb_te, args["eta"])).argmax(1)
        if control:
            hold = {"idx": test_idx, "pred": np.asarray(
                ref_softmax.predict_margin(got_trees, Xb_te, args["eta"])
            ).argmax(1)}
        say(f"[check] winner's first round: split gap {sg:.3e}, leaf gap "
            f"{lg:.3e}; class margins at {len(at)} rows: widest "
            f"{np.abs(ref_at).max():.3f}, gap "
            f"{numbers['class_margin_gap']:.3e}")

    # evaluators: the holdout's metric as the timed pass took it on the
    # device, against float64 numpy over the program's own predictions
    # and over the reference's
    if not control:
        hold = last["holdout"]
    own = ref_multi.validation_metric(
        metric, y[hold["idx"]], {"prediction": hold["pred"]}, k)
    ref = ref_multi.validation_metric(
        metric, y[test_idx], {"prediction": ref_pred}, k)
    got_h = own if control else float(summ["holdout"].get(metric, np.inf))
    numbers["holdout_metric_gap"] = max(abs(got_h - ref), abs(got_h - own))
    say(f"[check] winner {summ['best_model']} {grid}; holdout {metric} "
        f"reference {ref:.6f}, own predictions {own:.6f}, program {got_h}")
    return out()
