"""Each cell's ``--rehearse`` run on the CPU ends in a last line with the
contract's keys and ``platform: cpu``, reports no device metric, and a run
without ``--rehearse`` off the chip prints no result at all."""

import os
import subprocess
import sys

import pytest

from conftest import CELLS, ROOT

KEYS = {"correct", "attempted", "failed", "metrics", "device"}


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_rehearsal_last_line(rehearsal, cell, trace):
    result, proc = rehearsal(cell, trace=trace)
    assert KEYS <= set(result)
    assert list(result)[-1] == "compared"
    assert result["device"]["platform"] == "cpu"
    assert result["correct"] is True and result["attempted"] > 0
    assert result["failed"] == 0
    # counts only: no time, rate, share or memory reading from a CPU
    for name in result["metrics"]:
        assert name.split(".")[0] in ("solver", "dispatch", "compile",
                                      "batcher"), name
    assert "busy_s" not in result["device"]
    # every number compared is printed beside its limit, last on stderr
    tail = proc.stderr.strip().splitlines()[-len(result["compared"]):]
    assert all(line.startswith("compared ") for line in tail)


def test_off_the_chip_there_is_no_result():
    proc = subprocess.run(
        [sys.executable, "-m", "chipbench.run", "--workload", CELLS[1],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, capture_output=True, text=True, timeout=300,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode != 0
    assert proc.stdout.strip() == ""
