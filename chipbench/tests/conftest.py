"""Helpers: one rehearsal run of a cell in a process of its own (CPU, rows and
entities shrunk, widths kept), and its last line parsed."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
CELLS = ["glm_hashed_sparse.solve", "game_fe_re.cd", "game_fe_re.serve_steady"]


def rehearse(workload, *extra, seed=2**31 + 11, seconds=2, trace=0):
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", workload,
         "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace),
         "--rehearse", *extra],
        cwd=ROOT, capture_output=True, text=True, timeout=900,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-3000:]
    return json.loads(proc.stdout.strip().splitlines()[-1]), proc


@pytest.fixture(scope="session")
def rehearsal():
    return rehearse
