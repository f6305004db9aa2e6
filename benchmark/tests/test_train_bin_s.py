"""`layer_metrics/train_bin_s.py` on hand-made windows."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def read():
    path = os.path.join(HERE, "layer_metrics", "train_bin_s.py")
    spec = importlib.util.spec_from_file_location("train_bin_s", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


TWO = {"wall_s": 17.0, "spans": [
    ("selector:sweep", 8.0), ("sweep:bin", 4.0), ("sweep:dispatch:gbt", 0.5),
    ("sweep:bin", 0.5), ("selector:refit", 4.0), ("tree:edges", 3.5)]}
ONE = {"wall_s": 10.0, "spans": [
    ("selector:sweep", 4.0), ("sweep:bin", 0.5), ("selector:refit", 0.5)]}
NONE = {"wall_s": 9.0, "spans": [
    ("selector:sweep", 4.0), ("sweep:dispatch:logistic", 0.5)]}


def test_spans_are_summed_within_a_pass_and_averaged_over_passes(read):
    assert read({"window": {"passes": [TWO]}}) == pytest.approx(4.5)
    assert read({"window": {"passes": [TWO, ONE]}}) == pytest.approx(2.5)
    # a pass that binned nothing (no tree family) counts as a pass
    assert read({"window": {"passes": [TWO, ONE, NONE]}}) \
        == pytest.approx(5.0 / 3)


def test_nothing_to_read_without_the_span(read):
    assert read({"window": {"passes": [NONE]}}) is None
    assert read({"window": {"passes": []}}) is None
    assert read({"window": {}}) is None
