"""A run with the timed path broken underneath comes out not correct.

The harness runs a cell at a tiny size on the CPU (its look for a chip is
the command's, which these tests skip) with each planted fault of
``faults.py``, and the comparison with the reference must fail.
"""

import time

import pytest

import faults
import harness
from tinycfg import tiny

CELLS = ["city2k-converge", "lab54-converge"]


def _run(cell):
    spec, cfg = tiny(cell)
    return harness.run(cell, 2**33 + 5, 1.0, False, time.perf_counter(), spec=spec, cfg=cfg)


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    out = _run(cell)
    assert out["correct"], out["checks"]
    assert out["failed"] == 0


@pytest.mark.parametrize("fault", sorted(faults.FAULTS))
@pytest.mark.parametrize("cell", CELLS)
def test_planted_fault_is_not_correct(cell, fault, monkeypatch):
    _, cfg = tiny(cell)
    faults.FAULTS[fault](monkeypatch, cfg["fields"])
    out = _run(cell)
    assert not out["correct"], out["checks"]
