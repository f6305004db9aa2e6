"""`work.py` at a tiny shape against counts written out by hand, and the
table of peaks."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
import work  # noqa: E402


def test_tree_fit_by_hand():
    # n=10 rows, d=3 features, m=2 targets, depth 2, 1 tree: 2 levels,
    # each 10*3*(2+1) = 90 accumulations and 10*3 + 10*3*4 + 10*4 = 190 B
    assert work.tree_fit(10, 3, 2, 2, 1) == {"ops": 180.0, "bytes": 380.0}


def test_logistic_fit_by_hand():
    # 5 iterations, n=10, d=3, k=2: 4*10*3*2 = 240 ops and 2*10*3*4 = 240 B
    assert work.logistic_fit(10, 3, 2, 5) == {"ops": 1200.0, "bytes": 1200.0}


def test_train_pass_by_hand():
    config = {
        "schema": {"classes": 2, "columns": [{"count": 3}]},
        "selector": {
            "splitter": {"reserve_test_fraction": 0.5},
            "validator": {"folds": 2},
            "families": [
                {"estimator": "OpLogisticRegression",
                 "params": {"max_iter": 10}, "grid": [{}]},
                {"estimator": "OpXGBoostClassifier",
                 "params": {"n_estimators": 2, "max_depth": 2},
                 "grid": [{}, {}]}]}}
    # 20 rows -> 10 training rows. LR: 200 iterations (the floor) of
    # 240 ops / 240 B = 48000 / 48000 a fit. XGB: m=1, 4 levels of
    # 10*3*2 = 60 ops and 30+80+40 = 150 B = 240 / 600 a fit.
    # Folds 2: 2*48000 + 2*2*240 = 96960 ops, 2*48000 + 4*600 = 98400 B;
    # the refit is the costliest configuration by bytes (LR): + 48000.
    got = work.train_pass(config, 20)
    assert got == {"ops": 144960.0, "bytes": 146400.0}


def test_least_seconds_names_the_bound():
    peaks = {"flops_per_s": 100.0, "hbm_bytes_per_s": 10.0}
    assert work.least_seconds({"ops": 50.0, "bytes": 50.0}, peaks) == (
        5.0, "bytes")
    assert work.least_seconds({"ops": 500.0, "bytes": 10.0}, peaks) == (
        5.0, "ops")


def test_peaks_table():
    v5e = work.peaks_for("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        work.peaks_for("TPU v99")
