"""The tree winner's check, on the program's own tree fits at a size a
test run can hold: a sound fit passes the configuration's limits; the
control (the reference's trees from fp8 histogram values) and each
fault planted in the fit fail at least one of them.

(The rehearsal-size harness runs of `test_correct.py` pick a logistic
winner, so the tree branch of `train_check` is driven here directly.)
"""
import json
import os
import sys
import tempfile

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
os.environ.setdefault("TRANSMOGRIFAI_STORE_DIR", tempfile.mkdtemp())
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)

import datagen  # noqa: E402
import train_check  # noqa: E402
from reference import encode as ref_encode  # noqa: E402

N_ROWS = 20000


@pytest.fixture(scope="module")
def higgs():
    with open(os.path.join(BENCH, "configs", "higgs.json")) as fh:
        config = json.load(fh)
    cols, y = datagen.make_table(config["schema"], N_ROWS, 7, 0)
    X, _ = ref_encode.encode(cols, datagen.column_names(config["schema"]))
    return config, X, y


def _fit(config, X, y, family: str, grid: dict, fault=None):
    import jax.numpy as jnp
    import transmogrifai_tpu.models as models
    from transmogrifai_tpu.stages.base import FitContext
    fam = train_check._family(config, family)
    est = getattr(models, family)(**{**fam["params"], **grid})
    w = jnp.ones(len(y), jnp.float32)
    if fault == "half_batch":
        w = w * (jnp.arange(len(y)) < len(y) // 2)
    m = est.fit_arrays(jnp.asarray(X), jnp.asarray(y, jnp.float32), w,
                       FitContext(len(y), 42))
    trees = {k: np.array(v) for k, v in m.trees.items()}
    if fault == "state_unchanged":
        trees["bin"][:] = est.max_bins
        trees["leaf"][:] = 0.0
    elif fault == "answer_altered":
        trees["bin"][0, 0, 0] = (trees["bin"][0, 0, 0] + 16) % 31
    return fam, {"edges": np.asarray(m.edges, np.float32), "trees": trees,
                 "learning_rate": float(getattr(m, "learning_rate", 1.0)),
                 "model": m}


FAMILIES = [("OpXGBoostClassifier", {"min_child_weight": 1.0}),
            ("OpRandomForestClassifier",
             {"max_depth": 6, "min_info_gain": 0.001,
              "min_instances_per_node": 10.0})]


def _numbers(config, X, y, family, grid, fault=None, quant=None):
    fam, win = _fit(config, X, y, family, grid, fault)
    rng = np.random.default_rng(3)
    sg, lg, ee, _ = train_check._check_trees(
        win, fam, grid, X, y, 2, 32, rng, 42, quant)
    lim = config["limits"]["train"]
    return {"split_gain_gap": sg, "leaf_gap": lg, "edges_err": ee}, lim


@pytest.mark.parametrize("family,grid", FAMILIES,
                         ids=[f for f, _ in FAMILIES])
def test_sound_fit_passes(higgs, family, grid):
    config, X, y = higgs
    nums, lim = _numbers(config, X, y, family, grid)
    assert all(nums[k] <= lim[k] for k in nums), nums


@pytest.mark.parametrize("fault", ["state_unchanged", "half_batch",
                                   "answer_altered"])
@pytest.mark.parametrize("family,grid", FAMILIES,
                         ids=[f for f, _ in FAMILIES])
def test_fault_fails(higgs, family, grid, fault):
    config, X, y = higgs
    nums, lim = _numbers(config, X, y, family, grid, fault=fault)
    assert any(nums[k] > lim[k] for k in nums), nums


def test_control_fails(higgs):
    """fp8 histogram values in place of bf16: boosting's second tree
    grows from real-valued gradients, which fp8 cannot hold."""
    config, X, y = higgs
    family, grid = FAMILIES[0]
    nums, lim = _numbers(config, X, y, family, grid,
                         quant=train_check.TREE_CONTROL)
    assert any(nums[k] > lim[k] for k in nums), nums


@pytest.mark.parametrize("family,grid", FAMILIES,
                         ids=[f for f, _ in FAMILIES])
def test_reference_predicts_as_the_program(higgs, family, grid):
    """The holdout metric rests on the reference's own walk of the
    winner's trees: it has to give the program's probabilities."""
    import jax.numpy as jnp
    from reference import trees as ref_trees
    config, X, y = higgs
    fam, win = _fit(config, X, y, family, grid)
    Xb = ref_trees.bin_matrix(X, win["edges"])
    ref = (ref_trees.forest_predict(win["trees"], Xb)
           if "Forest" in family else
           ref_trees.gbt_predict(win["trees"], Xb, win["learning_rate"]))
    got = win["model"].predict_arrays(jnp.asarray(X))
    assert np.abs(np.asarray(ref["probability"])
                  - np.asarray(got["probability"])).max() < 1e-6
