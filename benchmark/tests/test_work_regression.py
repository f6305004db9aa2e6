"""`work_regression.py` against the schema and the shapes by hand."""
import copy
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import work  # noqa: E402
import work_multi  # noqa: E402
import work_regression  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "airlines.json")) as fh:
        return json.load(fh)


def test_widths_from_the_schema(config):
    """Six integer columns give a value and a null indicator each, the
    three level columns 20 + 2 each (29, 320 and 320 levels, top K 20).
    No cell is missing, so every null indicator goes; OTHER stays where
    the cardinality is over top K (all three)."""
    encoded, kept = work_multi.widths(config["schema"])
    assert encoded == 2 * 6 + 3 * 22 == 78
    assert kept == 6 + 3 * 21 == 69


def test_a_pass_at_the_cells_shape(config):
    n, d, rows = 4_500_000, 69, 5_000_000
    got = work_regression.train_pass(config, rows)
    ls = work_regression.ls_fit(n, d)
    assert ls == {"ops": 300.0 * 4 * n * d, "bytes": 300.0 * 2 * n * d * 4}
    tree = work.tree_fit(n, d, 1, 12, 1)
    chain = work.tree_fit(n, d, 1, 6, 10)
    # a level: two accumulations a cell; one read of the int8 matrix, of
    # the gradient and the weight (float32) and of the node index
    assert tree == {"ops": 12.0 * n * d * 2, "bytes": 12.0 * n * (d + 12)}
    assert chain == {"ops": 60.0 * n * d * 2, "bytes": 60.0 * n * (d + 12)}
    scored = work_regression.metric(n)
    assert scored == {"ops": 6.0 * n, "bytes": 12.0 * n}
    # 3 folds x (2 least-squares + 1 tree + 2 chains), a metric each; the
    # lockstep linear sweep's reads once; the refit as the costliest
    # single fit by bytes (a least-squares fit's 300 x 2 reads of the
    # float32 matrix); the train and holdout metrics; the encoded write
    assert ls["bytes"] > chain["bytes"] > tree["bytes"]
    assert got["ops"] == pytest.approx(
        6 * ls["ops"] + 3 * tree["ops"] + 6 * chain["ops"]
        + 15 * scored["ops"] + ls["ops"] + 6.0 * rows)
    assert got["bytes"] == pytest.approx(
        rows * 78 * 4 + ls["bytes"] + 3 * tree["bytes"] + 6 * chain["bytes"]
        + 15 * scored["bytes"] + ls["bytes"] + 12.0 * rows)
    seconds, bound = work_regression.least_seconds(
        config, rows, work.peaks_for("TPU v5 lite"))
    assert bound == "bytes" and 0.1 < seconds < 5.0
    assert work_regression.least_seconds(config, rows, None) is None


def test_a_pass_at_a_second_shape_by_hand(config):
    """1,000 rows, 2 folds, one chain of 3 rounds at depth 2 and nothing
    else: 900 training rows of 69 columns."""
    small = copy.deepcopy(config)
    spec = small["selector"]
    spec["validator"]["folds"] = 2
    chain = spec["families"][2]
    chain["params"]["n_estimators"] = 3
    chain["grid"] = [dict(chain["grid"][0], max_depth=2)]
    spec["families"] = [chain]
    got = work_regression.train_pass(small, 1000)
    n, d = 900, 69
    one = {"ops": 6.0 * n * d * 2, "bytes": 6.0 * n * (d + 12)}
    assert got["ops"] == pytest.approx(
        2 * one["ops"] + 2 * 6.0 * n + one["ops"] + 6.0 * 1000)
    assert got["bytes"] == pytest.approx(
        1000 * 78 * 4 + 2 * one["bytes"] + 2 * 12.0 * n + one["bytes"]
        + 12.0 * 1000)
