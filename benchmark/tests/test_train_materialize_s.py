"""`layer_metrics/train_materialize_s.py` on hand-made windows."""

import importlib.util
import os

import pytest

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def read():
    path = os.path.join(HERE, "layer_metrics", "train_materialize_s.py")
    spec = importlib.util.spec_from_file_location("train_materialize_s", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


SLOW = {"wall_s": 17.0, "spans": [
    ("workflow:materialize", 3.0), ("stage:fit:OneHotVectorizer", 2.5),
    ("pivot:fit", 2.5), ("pivot:encode", 3.5), ("selector:sweep", 4.0)]}
FAST = {"wall_s": 10.0, "spans": [
    ("workflow:materialize", 1.5), ("pivot:fit", 0.1),
    ("selector:sweep", 4.0)]}
NONE = {"wall_s": 9.0, "spans": [
    ("selector:sweep", 4.0), ("sweep:dispatch:logistic", 0.5)]}


def test_the_span_is_averaged_over_the_passes(read):
    assert read({"window": {"passes": [SLOW]}}) == pytest.approx(3.0)
    assert read({"window": {"passes": [SLOW, FAST]}}) == pytest.approx(2.25)


def test_nothing_to_read_without_the_span(read):
    # every pass materializes: one without the span is another program
    assert read({"window": {"passes": [SLOW, NONE]}}) is None
    assert read({"window": {"passes": [NONE]}}) is None
    assert read({"window": {"passes": []}}) is None
    assert read({"window": {}}) is None
