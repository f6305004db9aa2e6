"""`correct` has to come out false when it should.

Each case is one whole run of the harness at the configuration's
rehearsal size (the look for a chip skipped, nothing else), in its own
process because a planted fault patches the program's classes:

- the control: the reference one precision step down in the program's
  place (fp8 histogram values and bfloat16 leaf sums for a tree winner,
  bfloat16 products for a logistic winner);
- each fault a cell can have, planted under the timed path: a fit
  returns its starting state, sees half of its rows (at every site, and
  in the sweep's tree fits alone), or one produced number is altered.

A sound run of each cell has to come out true.
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

CASES = [
    ("higgs.train", [], True),
    ("higgs.train", ["--control", "lower"], False),
    ("higgs.train", ["--fault", "state_unchanged"], False),
    ("higgs.train", ["--fault", "half_batch"], False),
    ("higgs.train", ["--fault", "half_batch.sweep_trees"], False),
    ("higgs.train", ["--fault", "answer_altered"], False),
]


@pytest.mark.parametrize("cell,extra,want", CASES,
                         ids=[f"{c}{'-'.join(e)}" for c, e, _ in CASES])
def test_correct(cell, extra, want):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "11", "--seconds", "1", "--trace",
         "0", "--rehearsal", *extra],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=1500)
    assert proc.returncode == 0, proc.stderr[-2000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    assert result["correct"] is want, result["compared"]
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"


def test_no_chip_no_result():
    """Without --rehearsal and without a TPU: non-zero, no result line."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", "higgs.train", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=600)
    assert proc.returncode != 0
    assert "correct" not in proc.stdout
