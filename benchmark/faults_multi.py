"""Faults planted under a many-label pass, beside `faults.py` and
`faults_typed.py`: `correct` has to come out false with each. They patch
the PROGRAM's modules in this process.

- `half_rows`: every fit of the sweep and the refit (logistic and
  forest) sees the first half of its rows only (`faults.py`'s
  `half_batch` at all three sites);
- `class_column_dropped`: the forest's histograms leave one class's
  column out (class 1: its rows count in no class histogram, at any
  node), in the sweep and in the refit;
- `labels_merged`: label 2 is folded into label 1 before any fit sees
  the label (the selector's training label), so two of the three large
  classes are one;
- `classes_one_short`: the selector takes K one short of the
  configuration's, so the top label has no column in any fit or metric;
- `confusion_cell_off`: the device confusion matrix counts one row too
  many in the cell (label 0, prediction 1), wherever it is counted (the
  sweep's fold metrics, the selector's train and holdout metrics).
"""

from __future__ import annotations

MULTI = ("half_rows", "class_column_dropped", "labels_merged",
         "classes_one_short", "confusion_cell_off")
_PLANTED = []       # one fault a process: the patches do not come off


def plant(fault: str) -> None:
    if fault not in MULTI:
        raise ValueError(f"no many-label fault {fault!r} (have "
                         + ", ".join(MULTI) + ")")
    if _PLANTED:
        if _PLANTED != [fault]:
            raise RuntimeError(f"{_PLANTED[0]!r} is planted already")
        return
    _PLANTED.append(fault)

    if fault == "half_rows":
        import faults
        faults.plant_train("half_batch")
        return

    import jax.numpy as jnp
    from transmogrifai_tpu.models import trees
    from transmogrifai_tpu.selector import model_selector

    if fault == "class_column_dropped":
        real = trees._class_histograms

        def class_histograms(B, node_idx, cls, H, *a, **kw):
            return real(B, node_idx, cls, jnp.where(cls == 1, 0.0, H),
                        *a, **kw)
        trees._class_histograms = class_histograms
        return

    if fault == "confusion_cell_off":
        from transmogrifai_tpu.evaluators import device_metrics
        real_conf = device_metrics.confusion_dev

        def confusion_dev(*a, **kw):
            return real_conf(*a, **kw).at[0, 1].add(1.0)
        device_metrics.confusion_dev = confusion_dev
        return

    real_fit = model_selector.ModelSelector.fit_model

    if fault == "labels_merged":
        def fit_model(self, cols, ctx):
            import numpy as np
            label, vec = cols
            y = np.asarray(label.data["value"], np.float64)
            merged = type(label)(label.ftype, dict(
                label.data, value=np.where(y == 2.0, 1.0, y)))
            return real_fit(self, [merged, vec], ctx)
    else:
        def fit_model(self, cols, ctx):
            self.n_classes = int(self.n_classes) - 1
            try:
                return real_fit(self, cols, ctx)
            finally:
                self.n_classes = int(self.n_classes) + 1
    model_selector.ModelSelector.fit_model = fit_model
