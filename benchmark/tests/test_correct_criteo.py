"""`correct` for the typed cell has to come out false when it should.

As `test_correct.py`: each case is one whole run of the harness at the
configuration's rehearsal size on the CPU, in its own process. The
control and one fault under the fits (`faults.py`) show that what
`train_check.py` compares still holds on the kept matrix; the three
faults under the feature stages (`faults_typed.py`) each have to trip
the numbers named beside them, so every number this cell adds fails on
at least one fault.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

CASES = [
    ([], True, []),
    (["--control", "lower"], False, []),
    (["--fault", "half_batch.sweep_trees"], False, ["tree_cv_metric_gap"]),
    (["--fault", "level_folded"], False, ["levels_mismatch", "encode_err"]),
    (["--fault", "null_as_level"], False, ["cramers_v_gap", "encode_err"]),
    (["--fault", "dropped_kept"], False, ["kept_mismatch"]),
]


@pytest.mark.parametrize("extra,want,tripped", CASES,
                         ids=["-".join(e) or "sound" for e, _, _ in CASES])
def test_correct(extra, want, tripped):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "criteo.train", "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearsal", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is want, result["compared"]
    for name in tripped:
        c = result["compared"][name]
        assert c["value"] > c["limit"], (name, c)
    assert list(result)[-1] == "compared"


def test_widths_from_the_schema():
    """`work_typed.widths` against the schema by hand: 13 integer
    columns, one without holes; 26 categorical, 5 of them with at most
    20 levels (3, 4, 10, 18, 15), 14 without holes."""
    import work_typed
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "criteo.json")) as fh:
        schema = json.load(fh)["schema"]
    encoded = 13 * 2 + 21 * 22 + (5 + 6 + 12 + 20 + 17)
    kept = encoded - 1 - 14 - 5
    assert work_typed.widths(schema) == (encoded, kept) == (548, 528)


def test_typed_pass_by_hand():
    import work_typed
    config = {
        "schema": {"classes": 2, "encoding": {"top_k": 20}, "columns": [
            {"count": 1, "type": "Integral", "missing": [0.1]},
            {"count": 1, "type": "PickList", "missing": [0.0],
             "cardinality": [3]}]},
        "selector": {
            "splitter": {"reserve_test_fraction": 0.5},
            "validator": {"folds": 2},
            "families": [
                {"estimator": "OpLogisticRegression",
                 "params": {"max_iter": 10}, "grid": [{}, {}]},
                {"estimator": "OpXGBoostClassifier",
                 "params": {"n_estimators": 2, "max_depth": 2},
                 "grid": [{}, {}]}]}}
    # encoded 2 + (3 + OTHER + null) = 7, kept 2 + 3 = 5; 20 rows -> 10
    # training rows. LR: 200 iterations (the floor) of 4*10*5*2 = 400 ops
    # and 2*10*5*4 = 400 B: 80000 / 80000 a fit; the sweep's 2 x 2 fits
    # count their operations each and the matrix's reads ONCE. XGB: m=1,
    # 4 levels of 10*5*2 = 100 ops and 50+80+40 = 170 B: 400 / 680 a
    # fit, 2 x 2 fits. The refit is the costliest fit by bytes (LR). The
    # encoded matrix's write: 20*7*4 = 560 B.
    assert work_typed.widths(config["schema"]) == (7, 5)
    assert work_typed.train_pass(config, 20) == {
        "ops": 4 * 80000.0 + 4 * 400.0 + 80000.0,
        "bytes": 560.0 + 80000.0 + 4 * 680.0 + 80000.0}
    assert work_typed.least_seconds(config, 20, None) is None
