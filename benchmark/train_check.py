"""What decides `correct` for a training pass.

`extract()` takes what the last timed pass produced to the host: the
feature matrix it trained on, the selector's summary (holdout sizes,
every configuration's fold metrics, the winner, the holdout metric) and
the winner's fitted parameters. `compare()` holds each against the plain
references, which see only the raw columns and the configuration file:

- `encode_err`: widest |program - reference| over the feature matrix
  (layer `host_encode`);
- `holdout_rows_diff`, `winner_mismatch`: the holdout's size, and the
  winner's index as the reference's rule picks it from the fold metrics
  the program reported (`selector_sweep`); whether those metrics are
  right is for the two numbers that follow, and the boosted one checks
  the winner's own configuration;
- `cv_metric_gap`: one logistic (configuration, fold) drawn from the
  seed, refitted by the reference under the reference's own fold mask
  and scored by the reference's own metric, against the fold metric the
  program reported (`selector_sweep`, linear `model_kernels`);
- `tree_cv_metric_gap`: one boosted (configuration, fold) drawn from the
  seed (the winner's configuration where a boosted one won), GROWN by
  the reference at the stated histogram precision under its own fold
  mask and scored by the reference's own metric, against the fold
  metric the program reported (`selector_sweep`, tree `model_kernels`);
  a forest's fold fits draw their bootstrap from the program's own
  random stream, which a reference that takes nothing of the program
  cannot grow again;
- the winner's parameters (`model_kernels`): a tree winner's every tree
  is held against exact histograms (`split_gain_gap`, `leaf_gap`,
  `edges_err`), a logistic winner against the reference's refit
  (`weights_gap`);
- `holdout_metric_gap`: the reference's own prediction and metric on its
  own holdout rows from the winner's parameters, against the holdout
  metric the program reported.

`control` puts the reference's own fold fits and its own winner,
computed one precision step under what the configuration states (fp8 for the bf16 histogram values
and bfloat16 for the float32 leaf sums of a tree; fp8 for the bfloat16
operands of a logistic fit's products), in the program's place: it has
to fail.
"""

from __future__ import annotations

import numpy as np

import datagen
from reference import encode as ref_encode
from reference import linear as ref_linear
from reference import metrics as ref_metrics
from reference import selector as ref_selector
from reference import trees as ref_trees

TREE_CONTROL, LINEAR_CONTROL = "fp8", "fp8"
# the control is the WHOLE reference one step down: fp8 for the bf16
# histogram values, bfloat16 for the float32 leaf sums
LEAF_CONTROL = "bf16"


def extract(last: dict) -> dict:
    model, pf, checked = last["model"], last["pf"], last["checked"]
    fitted = model.fitted[pf.origin_stage.uid]
    s = fitted.summary
    out = {"stream": last["stream"], "cols": last["cols"], "y": last["y"],
           "X": np.asarray(model.train_columns[checked.uid].device_value(),
                           np.float32),
           "summary": {
               "results": [(r.model, dict(r.grid), list(r.fold_metrics))
                           for r in s.validation_results],
               "best_model": s.best_model, "best_grid": dict(s.best_grid),
               "holdout": dict(s.holdout_metrics),
               "split": dict(s.splitter_summary)}}
    if hasattr(fitted, "trees"):
        out["winner"] = {
            "edges": np.asarray(fitted.edges, np.float32),
            "trees": {k: np.asarray(v) for k, v in fitted.trees.items()},
            "learning_rate": float(getattr(fitted, "learning_rate", 1.0))}
    else:
        out["winner"] = {"W": np.asarray(fitted.W, np.float32),
                         "b": np.asarray(fitted.b, np.float32)}
    return out


def _family(config: dict, estimator: str) -> dict:
    return next(f for f in config["selector"]["families"]
                if f["estimator"] == estimator)


def _param(fam: dict, grid: dict, name: str, default):
    return grid.get(name, fam["params"].get(name, default))


def _tree_args(fam: dict, grid: dict) -> dict:
    """The gain's constants for a family, as the estimators document
    them: a forest grows pure trees (lambda 1e-6, gain threshold on the
    normalised scale), boosting uses the XGBoost constants."""
    mcw = max(float(_param(fam, grid, "min_child_weight", 1.0)),
              float(_param(fam, grid, "min_instances_per_node", 1.0)))
    if "Forest" in fam["estimator"]:
        return {"lam": 1e-6, "mcw": mcw, "min_gain": 0.0, "alpha": 0.0,
                "min_gain_norm": float(_param(fam, grid, "min_info_gain", 0))}
    return {"lam": float(_param(fam, grid, "reg_lambda", 1.0)), "mcw": mcw,
            "min_gain": float(_param(fam, grid, "gamma", 0.0)),
            "alpha": float(_param(fam, grid, "alpha", 0.0)),
            "min_gain_norm": float(_param(fam, grid, "min_info_gain", 0))}


def _boosted(fam: dict) -> bool:
    return "n_estimators" in fam["params"]


def _boosted_fold_metric(fam, grid, Xb, y, w, on, k, n_bins, metric,
                         quant, leaf_quant=None) -> float:
    """The reference's own boosted fit under the row weights `w` (a
    fold's training mask), and its own metric on the rows `on`. `quant`
    is the precision of the histogram values: the one the configuration
    states for the reference (its tree is then the program's but for
    the order of the float32 sums), one step lower for the control."""
    import jax
    import jax.numpy as jnp
    args = _tree_args(fam, grid)
    depth = int(_param(fam, grid, "max_depth", 5))
    rounds = int(_param(fam, grid, "n_estimators", 1))
    lr = float(_param(fam, grid, "eta", _param(fam, grid, "learning_rate",
                                               0.1)))
    yj, wj = jnp.asarray(y, jnp.float32), jnp.asarray(w, jnp.float32)
    margin = jnp.zeros(len(y), jnp.float32)
    for _ in range(rounds):
        G, H = ref_trees.gbt_grad_hess(margin, yj, wj)
        tree = ref_trees.grow(Xb, G, H, depth, n_bins, quant=quant,
                              leaf_quant=leaf_quant, **args)
        margin = margin + lr * ref_trees.leaf_values(
            tree, ref_trees.walk(tree, Xb))[:, 0]
    p1 = np.asarray(jax.nn.sigmoid(margin))[on]
    pred = {"probability": np.stack([1 - p1, p1], 1),
            "prediction": (p1 >= 0.5).astype(np.int32)}
    return ref_metrics.validation_metric(metric, y[on], pred, k)


def _check_trees(win, fam, grid, X, y, k, n_bins, rng, fit_seed, quant):
    """(split gap, leaf gap, edges err, trees used). With `quant` the
    reference grows its own trees at that precision first and they stand
    in for the program's."""
    import jax
    import jax.numpy as jnp
    args = _tree_args(fam, grid)
    edges = ref_trees.quantile_edges(X, n_bins)
    edges_err = float(np.abs(edges - win["edges"]).max()) \
        if edges.shape == win["edges"].shape else float("inf")
    Xb = ref_trees.bin_matrix(X, edges)
    yj = jnp.asarray(y, jnp.float32)
    n, d = X.shape
    forest = "Forest" in fam["estimator"]
    depth = int(_param(fam, grid, "max_depth", 5))
    n_trees = int(_param(fam, grid, "n_trees" if forest else "n_estimators",
                         1))
    trees = {key: np.asarray(v) for key, v in win["trees"].items()}
    if not quant and trees["feat"].shape[:2] != (n_trees, depth):
        return 1.0, 1.0, edges_err, trees     # not the stated ensemble
    lr = float(_param(fam, grid, "eta", _param(fam, grid, "learning_rate",
                                               win["learning_rate"])))
    grown = {"feat": [], "bin": [], "leaf": []}
    split_gap = leaf_gap = 0.0
    margin = jnp.zeros(n, jnp.float32)
    Y1 = jax.nn.one_hot(yj.astype(jnp.int32), k, dtype=jnp.float32)
    for t in range(n_trees):
        if forest:
            boot, fmask = ref_trees.forest_bootstrap(
                fit_seed, n_trees, t, n, d,
                bool(fam["params"].get("subsample_features", True)))
            G, H = Y1 * boot[:, None], boot
        else:
            fmask = None
            G, H = ref_trees.gbt_grad_hess(margin, yj,
                                           jnp.ones(n, jnp.float32))
        if quant:
            tree = ref_trees.grow(Xb, G, H, depth, n_bins, fmask=fmask,
                                  quant=quant, leaf_quant=LEAF_CONTROL,
                                  **args)
            for key in grown:
                grown[key].append(tree[key])
        else:
            tree = {key: v[t] for key, v in trees.items()}
        sg, lg, leaf_idx = ref_trees.verify(
            tree, Xb, G, H, n_bins, fmask=fmask, rng=rng, **args)
        split_gap, leaf_gap = max(split_gap, sg), max(leaf_gap, lg)
        if not forest:
            margin = margin + lr * ref_trees.leaf_values(
                tree, leaf_idx)[:, 0]
    if quant:
        trees = {key: np.stack(v) for key, v in grown.items()}
    return split_gap, leaf_gap, edges_err, trees


def compare(last: dict, config: dict, seed: int, control=None,
            say=print) -> list:
    import jax.numpy as jnp
    spec = config["selector"]
    limits = config["limits"]["train"]
    # the reference grows its own trees at the histogram precision the
    # configuration states (its logistic fits stay float32: bf16
    # operands brought them no nearer to the chip's, PERF.md section 6)
    stated_hist = config["precision"]["histogram_values"]
    schema = config["schema"]
    k = int(schema["classes"])
    rng = np.random.default_rng([int(seed) % (1 << 63), 77])
    summ = last["summary"]
    numbers = {}

    # host_encode: the matrix the pass trained on
    X_ref, _ = ref_encode.encode(last["cols"], datagen.column_names(schema))
    X = last["X"]
    numbers["encode_err"] = float(np.abs(X - X_ref).max()) \
        if X.shape == X_ref.shape else float("inf")
    y = np.asarray(last["y"], np.float64)

    # selector_sweep: holdout, folds, the winner
    sp = spec["splitter"]
    train_idx, test_idx = ref_selector.holdout_split(
        len(y), sp["reserve_test_fraction"], sp["seed"])
    numbers["holdout_rows_diff"] = float(
        abs(len(train_idx) - summ["split"].get("n_train", -1))
        + abs(len(test_idx) - summ["split"].get("n_test", -1)))
    # the winner by the reference's rule over the fold metrics as the
    # program reported them: whether THEY are right is for the two gap
    # numbers below (a table with the checked entries replaced by the
    # reference's would flip a near-tie between two boosted
    # configurations on a sound run, and say nothing more)
    means = [float(np.mean(fm)) for _, _, fm in summ["results"]]
    want = ref_selector.winner(means)
    got = next((i for i, (m, g, _) in enumerate(summ["results"])
                if m == summ["best_model"] and g == summ["best_grid"]), -1)
    numbers["winner_mismatch"] = float(want != got)
    say("[check] mean validation metrics: " + ", ".join(
        f"{m[:6]}{i}={v:.4f}" for i, ((m, _, _), v) in enumerate(
            zip(summ["results"], means))))
    Xtr, ytr = X_ref[train_idx], y[train_idx]
    val = spec["validator"]
    folds = ref_selector.cv_masks(len(ytr), val["folds"], val["seed"])
    linear = [(i, r) for i, r in enumerate(summ["results"])
              if r[0] == "OpLogisticRegression"]
    if linear:
        i, (_, grid, fold_metrics) = linear[int(rng.integers(len(linear)))]
        j = int(rng.integers(len(folds)))
        fam = _family(config, "OpLogisticRegression")
        on = folds[j][1] > 0

        def fold_metric(dtype=None):
            params = ref_linear.fit_enet(
                Xtr, ytr, folds[j][0], grid["reg_param"],
                grid["elastic_net_param"], k, fam["params"]["max_iter"],
                dtype=dtype)
            pred = {key: np.asarray(v)[on] for key, v in
                    ref_linear.predict(params, Xtr).items()}
            return ref_metrics.validation_metric(
                spec["metric"], ytr[on], pred, k)

        ref_m = fold_metric()
        got_m = (fold_metric(ref_trees.QUANT[LINEAR_CONTROL]) if control
                 else float(fold_metrics[j]))
        numbers["cv_metric_gap"] = abs(ref_m - got_m)
        say(f"[check] logistic config {i} fold {j}: reference "
            f"{ref_m:.6f}, program {got_m:.6f}")

    # selector_sweep, tree model_kernels: one boosted (config, fold)
    boosted = [(i, r) for i, r in enumerate(summ["results"])
               if r[0] != "OpLogisticRegression"
               and _boosted(_family(config, r[0]))]
    if boosted:
        rng_t = np.random.default_rng([int(seed) % (1 << 63), 78])
        won = [(i, r) for i, r in boosted if r[0] == summ["best_model"]
               and r[1] == summ["best_grid"]]
        i, (name, grid, fold_metrics) = (won or boosted)[
            int(rng_t.integers(len(won or boosted)))]
        j = int(rng_t.integers(len(folds)))
        fam = _family(config, name)
        n_bins = int(_param(fam, grid, "max_bins", 32))
        Xb = ref_trees.bin_matrix(Xtr, ref_trees.quantile_edges(Xtr, n_bins))
        on = folds[j][1] > 0
        ref_m = _boosted_fold_metric(fam, grid, Xb, ytr, folds[j][0], on, k,
                                     n_bins, spec["metric"], stated_hist)
        got_m = (_boosted_fold_metric(fam, grid, Xb, ytr, folds[j][0], on,
                                      k, n_bins, spec["metric"],
                                      TREE_CONTROL, LEAF_CONTROL)
                 if control else float(fold_metrics[j]))
        numbers["tree_cv_metric_gap"] = abs(ref_m - got_m)
        say(f"[check] boosted config {i} fold {j}: reference "
            f"{ref_m:.6f}, program {got_m:.6f}")
        del Xb

    # model_kernels: the winner's parameters
    fam = _family(config, summ["best_model"])
    grid = summ["best_grid"]
    win = last["winner"]
    fs = spec["fit_seed"]
    fit_seed = fs["train_seed"] * 1000003 + fs["selector_layer"]
    if "trees" in win:
        n_bins = int(_param(fam, grid, "max_bins", 32))
        sg, lg, ee, trees = _check_trees(
            win, fam, grid, Xtr, ytr, k, n_bins, rng, fit_seed,
            TREE_CONTROL if control else None)
        numbers.update(split_gain_gap=sg, leaf_gap=lg, edges_err=ee)
        Xb_te = ref_trees.bin_matrix(X_ref[test_idx], win["edges"])
        pred = (ref_trees.forest_predict(trees, Xb_te)
                if "Forest" in fam["estimator"] else
                ref_trees.gbt_predict(trees, Xb_te, float(_param(
                    fam, grid, "eta", win["learning_rate"]))))
    else:
        ref = ref_linear.fit_enet(
            Xtr, ytr, np.ones(len(ytr), np.float32), grid["reg_param"],
            grid["elastic_net_param"], k, fam["params"]["max_iter"])
        ref_v = np.concatenate([np.asarray(ref["W"]).ravel(),
                                np.asarray(ref["b"]).ravel()])
        if control:
            low = ref_linear.fit_enet(
                Xtr, ytr, np.ones(len(ytr), np.float32), grid["reg_param"],
                grid["elastic_net_param"], k, fam["params"]["max_iter"],
                dtype=ref_trees.QUANT[LINEAR_CONTROL])
            win = {"W": np.asarray(low["W"]), "b": np.asarray(low["b"])}
        got_v = np.concatenate([win["W"].ravel(), win["b"].ravel()])
        numbers["weights_gap"] = float(
            np.linalg.norm(got_v - ref_v) / max(np.linalg.norm(ref_v), 1e-12)
        ) if got_v.shape == ref_v.shape else float("inf")
        pred = ref_linear.predict(
            {"W": jnp.asarray(win["W"]), "b": jnp.asarray(win["b"])},
            X_ref[test_idx])
    pred = {key: np.asarray(v) for key, v in pred.items()}
    ref_hold = ref_metrics.validation_metric(
        spec["metric"], y[test_idx], pred, k)
    numbers["holdout_metric_gap"] = abs(
        ref_hold - float(summ["holdout"].get(spec["metric"], np.inf)))
    say(f"[check] winner {summ['best_model']} {grid}; holdout "
        f"{spec['metric']} reference {ref_hold:.6f}, program "
        f"{summ['holdout'].get(spec['metric'])}")
    return [{"name": name, "value": value if np.isfinite(value) else 1e30,
             "limit": float(limits[name])}
            for name, value in numbers.items()]
