"""Faults planted under the timed path, for the tests in
`benchmark/tests` and the limit-setting runs: `correct` has to come out
false with each. They patch the PROGRAM's classes in this process.

A fault is `<name>` or `<name>.<site>`. The sites are the places of a
pass where a fit happens: `refit` (the winner's refit: `fit_arrays` of
the three estimator classes), `sweep_linear` (the sweep's logistic fold
fits: `parallel/sweep.py`'s `fit_logreg_enet`) and `sweep_trees` (the
sweep's forest and boosted fold fits: `_sweep_forest`, `_sweep_gbt`).
Without a site the fault is planted at every site it has; a site's
prefix names all that begin so (`half_batch.sweep`: both sweep sites).

- `state_unchanged` (`refit`, `sweep_linear`): the fit returns its
  starting state (unsplit trees with zero leaves, zero weights).
- `half_batch` (all three sites): the fit sees the first half of its
  rows only.
- `answer_altered` (`refit`, `sweep_linear`): one produced number is
  changed where it is produced (a tree's root threshold, the largest
  weight).
"""

from __future__ import annotations

import numpy as np

TRAIN = ("state_unchanged", "half_batch", "answer_altered")
_PLANTED = []       # one fault a process: the patches do not come off
SITES = {"state_unchanged": ("refit", "sweep_linear"),
         "half_batch": ("refit", "sweep_linear", "sweep_trees"),
         "answer_altered": ("refit", "sweep_linear")}


def _first_half(w):
    import jax.numpy as jnp
    n = w.shape[-1]
    return w * (jnp.arange(n) < n // 2).astype(w.dtype)


def plant_train(fault: str) -> None:
    name, _, site = fault.partition(".")
    if name not in TRAIN or (site and not any(
            s.startswith(site) for s in SITES[name])):
        raise ValueError(f"no training fault {fault!r} (have "
                         + ", ".join(f"{n}[.{'|'.join(s)}]"
                                     for n, s in SITES.items()) + ")")
    if _PLANTED:
        if _PLANTED != [fault]:
            raise RuntimeError(f"{_PLANTED[0]!r} is planted already")
        return
    _PLANTED.append(fault)
    sites = (SITES[name] if not site else
             tuple(s for s in SITES[name] if s.startswith(site)))
    import jax.numpy as jnp
    from transmogrifai_tpu.models import logistic, trees
    from transmogrifai_tpu.parallel import sweep

    def wrap(cls):
        real = cls.fit_arrays

        def fit_arrays(self, X, y, w, ctx, *a, **kw):
            if name == "half_batch":
                w = _first_half(w)
            model = real(self, X, y, w, ctx, *a, **kw)
            if hasattr(model, "trees"):
                t = {k: np.array(v) for k, v in model.trees.items()}
                n_bins = int(self.max_bins)
                if name == "state_unchanged":
                    t["bin"][:] = n_bins
                    t["leaf"][:] = 0.0
                elif name == "answer_altered":
                    root = t["bin"][0, 0, 0]
                    t["bin"][0, 0, 0] = (root + n_bins // 2) % (n_bins - 1)
                model.trees = t
            elif name == "state_unchanged":
                model.W, model.b = model.W * 0, model.b * 0
            elif name == "answer_altered":
                model.W = model.W.copy()
                model.W.flat[int(np.abs(model.W).argmax())] *= 1.5
            return model
        cls.fit_arrays = fit_arrays

    if "refit" in sites:
        for cls in (logistic.OpLogisticRegression,
                    trees.OpRandomForestClassifier, trees.OpGBTClassifier):
            wrap(cls)

    if "sweep_linear" in sites:
        real_enet = sweep.fit_logreg_enet

        def fit_logreg_enet(X, y, w, *a, **kw):     # traced under vmap
            if name == "half_batch":
                w = _first_half(w)
            out = real_enet(X, y, w, *a, **kw)
            if name == "state_unchanged":
                out = {k: v * 0 for k, v in out.items()}
            elif name == "answer_altered":
                W = out["W"]
                top = jnp.abs(W) == jnp.abs(W).max()
                out = dict(out, W=jnp.where(top, W * 1.5, W))
            return out
        sweep.fit_logreg_enet = fit_logreg_enet

    if "sweep_trees" in sites:
        # the family sweeps take the folds' training masks W (folds, n):
        # every fold fit of the family then sees half of its rows, and
        # the validation masks V stay whole
        def halved(real):
            def family_sweep(est, grids, X, y, W, V, *a, **kw):
                return real(est, grids, X, y, _first_half(jnp.asarray(W)),
                            V, *a, **kw)
            return family_sweep
        sweep._sweep_forest = halved(sweep._sweep_forest)
        sweep._sweep_gbt = halved(sweep._sweep_gbt)
