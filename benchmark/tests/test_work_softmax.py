"""`work_softmax.py` against the shapes by hand, and the three softmax
readers on a recorded traced run of `dionis.train`
(`testdata/trace_dionis.json`)."""
import importlib.util
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import work  # noqa: E402
import work_softmax  # noqa: E402

READERS = ("train_softmax_mfu_pct", "train_softmax_busy_mfu_pct",
           "train_softmax_hist_dispatch_pct")


@pytest.fixture(scope="module")
def config():
    with open(os.path.join(BENCH, "configs", "dionis.json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "testdata", "trace_dionis.json")) as fh:
        return json.load(fh)


def _reader(name):
    path = os.path.join(BENCH, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location(f"softmax_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_a_round_and_the_products_at_the_cells_shape():
    n, m, d, k = 374_569, 249_712, 60, 355
    one = work_softmax.softmax_round(n, m, d, k, 6)
    assert one["ops"] == pytest.approx(
        2.0 * 6 * m * d * k + 8.0 * n * k + 2.0 * m * k + 6.0 * n * k)
    assert one["bytes"] == pytest.approx(
        6.0 * (m * d + 12 * m * k) + 16.0 * n * k + 12.0 * m * k
        + 24.0 * n * k)
    # 64 TFLOP of one-hot products a round at this shape
    prod = work_softmax.class_hist_round(n, k, 6, d * 32)
    assert prod["ops"] == pytest.approx(4.0 * n * 63 * 1920 * k)
    assert 6.4e13 < prod["ops"] < 6.5e13


def test_a_pass_at_the_cells_shape(config):
    rows = 416_188
    n, d, k = 374_569, 60, 355
    m = n * 2 // 3
    got = work_softmax.train_pass(config, rows)
    lr = work.logistic_fit(n, d, k, work.enet_iters(50))
    fold = work_softmax.softmax_round(n, m, d, k, 6)
    refit = work_softmax.softmax_round(n, n, d, k, 6)
    # 3 folds x (2 logistic fits + 2 chains of 4 rounds); the logistic
    # sweep's reads once; the refit as the costliest single fit by bytes
    # (a chain of 4 rounds over all n rows)
    assert 4 * refit["bytes"] > lr["bytes"]
    assert got["ops"] == pytest.approx(
        6 * lr["ops"] + 6 * 4 * fold["ops"] + 4 * refit["ops"])
    assert got["bytes"] == pytest.approx(
        rows * 120 * 4 + lr["bytes"] + 6 * 4 * fold["bytes"]
        + 4 * refit["bytes"])
    seconds, bound = work_softmax.least_seconds(
        config, rows, work.peaks_for("TPU v5 lite"))
    assert bound == "bytes" and 0.1 < seconds < 5.0


@pytest.mark.parametrize("name", READERS)
def test_reader_on_the_recorded_run(name, config, recorded):
    read = _reader(name)
    obs = {"window": recorded["window"], "trace": recorded["trace"],
           "config": config,
           "peaks": work.peaks_for(recorded["device_kind"])}
    got = read(obs)
    assert got == pytest.approx(recorded["read"][name], rel=1e-12)
    assert 0 < got < 100
    # off the chip, or without the pass's spans, nothing
    assert read(dict(obs, peaks=None)) is None
    empty = {"window": {"rows": 416188, "passes": [
        {"wall_s": 60.0, "spans": []}]}, "trace": {}, "config": config,
        "peaks": obs["peaks"]}
    assert read(empty) is None or name == "train_softmax_mfu_pct"
