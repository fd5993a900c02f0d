"""Work counts and the peak table, against numbers worked by hand."""

import numpy as np
import pytest

import work
from reference import adjacency


def test_sweep_counts_two_sensors():
    # sensor 0 holds 3 lanes, sensor 1 holds 2; one field, 2 message slots
    flops, nbytes = work.sweep(np.array([[3, 2]]), slots=2)
    # 2 L^2 + 4 L: (18 + 12) + (8 + 8)
    assert flops == 46
    # factor triangles 6 + 3, coefficients in/out 2 * 5, messages 2 * 2
    assert nbytes == 4 * (9 + 10 + 4)


def test_serve_counts_take_the_lesser_table_read():
    lanes = np.array([[3, 2], [3, 2]])  # two fields
    flops, nbytes = work.serve(rows=4, fields=2, k=1, lanes=lanes, dim=2, sensors=2)
    # tables once: positions 2*2 + coefficients 10 = 14 floats, against
    # 4 queries x 1 table x 2 lanes x (2 + 2) = 32: 14, then 8 in, 8 out
    assert nbytes == 4 * (14 + 8 + 8)
    # 4 rows x 1 x 2 lanes x ((3*2 - 1) + 2*2)
    assert flops == 72
    flops, nbytes = work.serve(rows=1, fields=2, k=1, lanes=lanes, dim=2, sensors=2)
    assert nbytes == 4 * (8 + 2 + 2)  # 1 x 1 x 2 x (2 + 2) = 8 < 14


def test_lanes_of_a_tiny_network():
    pos = np.array([[0.0, 0.0], [0.1, 0.0], [1.0, 1.0]], np.float32)
    assert adjacency(pos, 0.2).sum(1).tolist() == [2, 2, 1]


def test_peak_table():
    p = work.peak("TPU v5 lite")
    assert p["flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    assert work.least_seconds(197e12, 0.0, "TPU v5 lite") == pytest.approx(1.0)
    assert work.least_seconds(1.0, 819e9, "TPU v5 lite") == pytest.approx(1.0)
    with pytest.raises(KeyError):
        work.peak("TPU v99")
