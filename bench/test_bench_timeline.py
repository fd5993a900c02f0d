"""Trace reduction on a hand-made trace whose union and gaps are known.

fixtures/trace_small.json, one device, window [0, 8] s:

    ops    [0,1] fusion.1, [0.5,2] fusion.2, [3,4] copy, [6,7] fusion.1
    busy   [0,2] + [3,4] + [6,7] = 4 s            idle 4 s = 50%
    spans  tick [1.5,5], pump [5,6.5]
    gaps   [2,3] tick; [4,5] tick; [5,6] pump; [7,8] outside every span
"""

from pathlib import Path

import pytest

import timeline

FIX = Path(__file__).resolve().parent / "fixtures" / "trace_small.json"


@pytest.fixture
def tr():
    return timeline.Trace.from_json(FIX)


def test_union_merges_overlaps():
    assert timeline.union([(3, 4), (0, 1), (0.5, 2), (2, 2.5)]) == [(0, 2.5), (3, 4)]


def test_busy_and_idle(tr):
    assert timeline.busy(tr, 0.0, 8.0) == pytest.approx(4.0)
    assert timeline.busy(tr, 1.0, 3.5) == pytest.approx(1.5)
    # only device time inside the given host spans counts
    assert timeline.busy(tr, 0.0, 8.0, within=[(1.5, 5.0)]) == pytest.approx(1.5)


def test_busy_averages_over_devices(tr):
    tr.ops.append(("/device:TPU:1", "all", 0.0, 8.0))
    tr.devices = 2
    assert timeline.busy(tr, 0.0, 8.0) == pytest.approx(6.0)


def test_top_ops(tr):
    ops = dict(timeline.top_ops(tr, 0.0, 8.0))
    assert ops == pytest.approx({"fusion.1": 2.0, "fusion.2": 1.5, "copy": 1.0})


def test_idle_gaps_by_span(tr):
    gaps = dict(timeline.idle_gaps(tr, 0.0, 8.0))
    assert gaps == pytest.approx({"tick": 2.0, "pump": 1.0, "driver": 1.0})


def test_align_finds_clock_offset(tr):
    recorded = [("tick", 101.5, 105.0), ("pump", 105.0, 106.5)]
    assert timeline.align(tr, recorded) == pytest.approx(-100.0)
