"""Evaluator stages: score a Prediction column against a label column.

Reference parity: `core/.../evaluators/OpEvaluatorBase.scala`,
`Evaluators.scala:40-316` thin factories. An Evaluator is not a DAG stage;
it consumes (label Column, prediction Column) and returns a metrics
dataclass. `default_metric` names the value used for model selection
(larger-is-better handled via `is_larger_better`).
"""

from __future__ import annotations

from typing import Optional

import numpy as np

from transmogrifai_tpu.data.columns import Column
from transmogrifai_tpu.evaluators.metrics import (
    binary_metrics, multiclass_metrics, regression_metrics)
from transmogrifai_tpu.obs.trace import pull, upload


class Evaluator:
    name: str = "evaluator"
    default_metric: str = ""
    is_larger_better: bool = True

    def evaluate(self, label: Column, prediction: Column):
        raise NotImplementedError

    def metric_value(self, label: Column, prediction: Column) -> float:
        m = self.evaluate(label, prediction).to_json()
        return float(m[self.default_metric])


def _label_array(label: Column) -> np.ndarray:
    return np.asarray(pull("evaluate:label", label.data["value"]),
                      dtype=np.float64)


def _prediction_field(prediction: Column, field: str,
                      dtype=None) -> np.ndarray:
    """One field of a prediction column on the host: a span
    `pull:evaluate:<field>` where it was still a device array."""
    return np.asarray(pull(f"evaluate:{field}", prediction.data[field]),
                      dtype=dtype)


class BinaryClassificationEvaluator(Evaluator):
    """AuPR default, matching BinaryClassificationModelSelector's default."""

    name = "binEval"
    default_metric = "AuPR"

    def __init__(self, metric: str = "AuPR", threshold: float = 0.5):
        self.default_metric = metric
        self.threshold = threshold
        self.is_larger_better = metric not in ("Error",)

    def evaluate(self, label: Column, prediction: Column):
        y = _label_array(label)
        prob = _prediction_field(prediction, "probability")
        if prob.ndim == 2 and prob.shape[1] >= 2:
            scores = prob[:, 1]
        else:
            scores = _prediction_field(prediction, "prediction",
                                       np.float64)
        return binary_metrics(y, scores, self.threshold)


class MultiClassificationEvaluator(Evaluator):
    """F1 default (OpMultiClassificationEvaluator)."""

    name = "multiEval"
    default_metric = "F1"

    def __init__(self, metric: str = "F1"):
        self.default_metric = metric
        self.is_larger_better = metric not in ("Error",)

    def evaluate(self, label: Column, prediction: Column):
        y = _label_array(label)
        pred = _prediction_field(prediction, "prediction", np.float64)
        return multiclass_metrics(y, pred)

    def evaluate_device(self, y, pred: dict, n_classes: int):
        """`evaluate` of device arrays: the (K, K) confusion table is
        counted on the device (`confusion_dev`: exact while a cell stays
        under 2^24) and only it crosses to the host, where the metrics
        are taken in float64 as `evaluate` takes them."""
        import jax.numpy as jnp

        from transmogrifai_tpu.evaluators.device_metrics import (
            confusion_dev)
        from transmogrifai_tpu.evaluators.metrics import (
            multiclass_from_confusion)
        p = pred["prediction"]
        return multiclass_from_confusion(pull(
            "evaluate:confusion", confusion_dev(
                y, p, jnp.ones(p.shape[0], jnp.float32), int(n_classes))))


class RegressionEvaluator(Evaluator):
    """RMSE default, smaller is better (OpRegressionEvaluator)."""

    name = "regEval"
    default_metric = "RMSE"
    is_larger_better = False

    def __init__(self, metric: str = "RMSE"):
        self.default_metric = metric
        self.is_larger_better = metric in ("R2",)

    def evaluate(self, label: Column, prediction: Column):
        y = _label_array(label)
        pred = _prediction_field(prediction, "prediction", np.float64)
        return regression_metrics(y, pred)

    def evaluate_device(self, y, pred: dict, n_classes=None):
        """`evaluate` of device arrays: the four metrics are reduced on
        the device (`regression_dev`, float32 sums under the scope
        `metric:<default metric>`) and only those scalars cross to the
        host; no (n,) prediction does. The signed-percentage-error
        histogram is the host form's alone (a selector's summary keeps
        scalars). `n_classes` is the classifiers' argument and is not
        read."""
        import jax.numpy as jnp

        from transmogrifai_tpu.evaluators.device_metrics import (
            regression_metrics_dev)
        from transmogrifai_tpu.evaluators.metrics import RegressionMetrics
        m = pull("evaluate:metrics", regression_metrics_dev(
            self.default_metric)(upload("evaluate:label", y, jnp.float32),
                                 pred["prediction"]))
        return RegressionMetrics(
            rmse=float(m["RMSE"]), mse=float(m["MSE"]), mae=float(m["MAE"]),
            r2=float(m["R2"]))


class BinScoreEvaluator(Evaluator):
    """Score-decile calibration (`OpBinScoreEvaluator.scala:53`)."""

    name = "binScoreEval"
    default_metric = "BrierScore"
    is_larger_better = False

    def __init__(self, num_bins: int = 10):
        self.num_bins = num_bins

    def evaluate(self, label: Column, prediction: Column):
        from transmogrifai_tpu.evaluators.metrics import bin_score_metrics
        y = _label_array(label)
        prob = _prediction_field(prediction, "probability")
        scores = (prob[:, 1] if prob.ndim == 2 and prob.shape[1] >= 2
                  else _prediction_field(prediction, "prediction",
                                         np.float64))
        return bin_score_metrics(y, scores, self.num_bins)


class ForecastEvaluator(Evaluator):
    """SMAPE/seasonal-error metrics (`OpForecastEvaluator.scala:59`)."""

    name = "forecastEval"
    default_metric = "SMAPE"
    is_larger_better = False

    def __init__(self, seasonal_window: int = 1):
        self.seasonal_window = seasonal_window

    def evaluate(self, label: Column, prediction: Column):
        from transmogrifai_tpu.evaluators.metrics import forecast_metrics
        y = _label_array(label)
        pred = _prediction_field(prediction, "prediction", np.float64)
        return forecast_metrics(y, pred, self.seasonal_window)


class LambdaEvaluator(Evaluator):
    """Custom-metric evaluator (`Evaluators.scala` custom lambda factories)."""

    def __init__(self, name: str, fn, is_larger_better: bool = True):
        self.name = name
        self.default_metric = name
        self.fn = fn
        self.is_larger_better = is_larger_better

    def evaluate(self, label: Column, prediction: Column):
        value = float(self.fn(label, prediction))
        metric_name = self.default_metric

        class _M:
            def to_json(self) -> dict:
                return {metric_name: value}

        return _M()


class Evaluators:
    """Thin factories mirroring `Evaluators.scala:40-316`:
    `Evaluators.BinaryClassification.au_pr()` etc."""

    class BinaryClassification:
        @staticmethod
        def au_pr():
            return BinaryClassificationEvaluator(metric="AuPR")

        @staticmethod
        def au_roc():
            return BinaryClassificationEvaluator(metric="AuROC")

        @staticmethod
        def precision():
            return BinaryClassificationEvaluator(metric="Precision")

        @staticmethod
        def recall():
            return BinaryClassificationEvaluator(metric="Recall")

        @staticmethod
        def f1():
            return BinaryClassificationEvaluator(metric="F1")

        @staticmethod
        def error():
            return BinaryClassificationEvaluator(metric="Error")

        @staticmethod
        def brier_score():
            return BinScoreEvaluator()

        @staticmethod
        def custom(metric_name: str, fn, is_larger_better: bool = True):
            return LambdaEvaluator(metric_name, fn, is_larger_better)

    class MultiClassification:
        @staticmethod
        def f1():
            return MultiClassificationEvaluator(metric="F1")

        @staticmethod
        def precision():
            return MultiClassificationEvaluator(metric="Precision")

        @staticmethod
        def recall():
            return MultiClassificationEvaluator(metric="Recall")

        @staticmethod
        def error():
            return MultiClassificationEvaluator(metric="Error")

        @staticmethod
        def custom(metric_name: str, fn, is_larger_better: bool = True):
            return LambdaEvaluator(metric_name, fn, is_larger_better)

    class Regression:
        @staticmethod
        def rmse():
            return RegressionEvaluator(metric="RMSE")

        @staticmethod
        def mse():
            return RegressionEvaluator(metric="MSE")

        @staticmethod
        def mae():
            return RegressionEvaluator(metric="MAE")

        @staticmethod
        def r2():
            return RegressionEvaluator(metric="R2")

        @staticmethod
        def custom(metric_name: str, fn, is_larger_better: bool = True):
            return LambdaEvaluator(metric_name, fn, is_larger_better)

    class Forecast:
        @staticmethod
        def smape(seasonal_window: int = 1):
            return ForecastEvaluator(seasonal_window)
