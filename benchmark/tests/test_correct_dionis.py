"""`correct` for the softmax-boosting cell has to come out false when it
should.

As `test_correct_airlines.py`: each case is one whole run of the harness
at the configuration's rehearsal size (8,000 rows, all 355 classes) on
the CPU, in its own process. The sound run has to pass with every exact
number at 0; the control (the reference one precision step down) and
each fault of `faults_softmax.py` have to fail, by the numbers named
beside them.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

EXACT = ["encode_err", "kept_mismatch", "labels_kept_mismatch",
         "holdout_rows_diff", "winner_mismatch", "edges_err",
         "boost_train_rows_diff"]
CASES = [
    ([], True, []),
    (["--control", "lower"], False, ["cv_metric_gap",
                                     "boost_train_metric_gap"]),
    (["--fault", "classes_one_short"], False, ["class_margin_gap"]),
    (["--fault", "hessian_constant"], False, ["class_margin_gap",
                                              "boost_train_metric_gap"]),
    (["--fault", "round_short"], False, ["boost_train_metric_gap"]),
    (["--fault", "half_rows_boost"], False, ["boost_train_rows_diff"]),
    (["--fault", "class_trees_swapped"], False, ["class_margin_gap"]),
]


@pytest.mark.parametrize("extra,want,tripped", CASES,
                         ids=["-".join(e) or "sound" for e, _, _ in CASES])
def test_correct(extra, want, tripped):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "dionis.train", "--seed", "4000000011",
         "--seconds", "1", "--trace", "0", "--rehearsal", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=3000)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is want, result["compared"]
    if want:
        for name in EXACT:
            assert result["compared"][name]["value"] == 0, name
    for name in tripped:
        c = result["compared"][name]
        assert c["value"] > c["limit"], (name, c)
    assert list(result)[-1] == "compared"
