"""`correct` for the many-label cell has to come out false when it should.

As `test_correct_criteo.py`: each case is one whole run of the harness at
the configuration's rehearsal size on the CPU, in its own process. The
sound run has to pass with every exact number at 0; the control (the
reference one precision step down) and each fault of `faults_multi.py`
have to fail, by the numbers named beside them.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

EXACT = ["encode_err", "levels_mismatch", "kept_mismatch",
         "holdout_rows_diff", "labels_kept_mismatch", "winner_mismatch",
         "edges_err", "confusion_diff"]
CASES = [
    ([], True, []),
    (["--control", "lower"], False, ["cv_metric_gap"]),
    (["--fault", "half_rows"], False, ["cv_metric_gap",
                                       "tree_cv_metric_gap"]),
    (["--fault", "class_column_dropped"], False, ["tree_cv_metric_gap"]),
    (["--fault", "labels_merged"], False, ["tree_cv_metric_gap",
                                           "holdout_metric_gap"]),
    (["--fault", "classes_one_short"], False, ["leaf_gap"]),
    (["--fault", "confusion_cell_off"], False, ["confusion_diff"]),
]


@pytest.mark.parametrize("extra,want,tripped", CASES,
                         ids=["-".join(e) or "sound" for e, _, _ in CASES])
def test_correct(extra, want, tripped):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "kddcup99.train", "--seed", "11", "--seconds", "1",
         "--trace", "0", "--rehearsal", *extra],
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
