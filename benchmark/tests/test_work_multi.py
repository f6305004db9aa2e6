"""`work_multi.py` against the schema and the shapes by hand."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

import work  # noqa: E402
import work_multi  # noqa: E402


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(os.path.dirname(HERE), "configs",
                           "kddcup99.json")) as fh:
        return json.load(fh)


def test_widths_from_the_schema(config):
    """38 numeric columns give a value and a null indicator each; the
    three level columns 3 + 2, 20 + 2, 11 + 2. No cell is missing, so
    every null indicator goes, with the constant column and the flag
    under the variance floor; OTHER stays only where the cardinality is
    over top K (service)."""
    encoded, kept = work_multi.widths(config["schema"])
    assert encoded == 2 * 38 + 5 + 22 + 13 == 116
    assert kept == (38 - 2) + 3 + 21 + 11 == 71


def test_a_pass_counts_every_fit_once_and_the_sweeps_reads_once(config):
    n, d, k = 1_800_000, 71, 23
    got = work_multi.train_pass(config, 2_000_000)
    logistic = work.logistic_fit(n, d, k, 200)
    tree = work.tree_fit(n, d, k, 12, 1)
    # 2 configs x 3 folds of FISTA operations, one run of reads; 3 fold
    # trees; the refit as the costliest single fit by bytes (a logistic
    # fit's 200 x 2 reads of the float32 matrix)
    assert logistic["bytes"] > tree["bytes"]
    assert got["ops"] == pytest.approx(
        6 * logistic["ops"] + 3 * tree["ops"] + logistic["ops"])
    assert got["bytes"] == pytest.approx(
        2_000_000 * 116 * 4 + logistic["bytes"] + 3 * tree["bytes"]
        + logistic["bytes"])
    seconds, bound = work_multi.least_seconds(
        config, 2_000_000, work.peaks_for("TPU v5 lite"))
    assert bound == "bytes" and 0.1 < seconds < 5.0
    assert work_multi.least_seconds(config, 2_000_000, None) is None


def test_kernel_counts():
    h = work_multi.class_histograms(1_800_000, 2048, 23, 1042)
    assert h["ops"] == 2.0 * 1_800_000 * 23 * 2048 * 1042
    assert h["bytes"] == 1_800_000 * (1042 * 2 + 12) + 4 * 23 * 2048 * 1042
    c = work_multi.confusion(600_000, 23)
    assert c == {"ops": 2.0 * 600_000 * 529, "bytes": 12.0 * 600_000}
