"""The work formulas against counts made by hand."""
from __future__ import annotations

import pytest

from chipbench import work
from chipbench.peaks import chip_peaks


def test_scd_call_by_hand():
    # m = 1,024 rows, H = 3 steps, n_local = 5: per step a dot and an
    # axpy over 1,024 rows = 4,096 FLOPs; bytes: 3 columns of 1,024
    # floats, 3 x 4 per-step scalars, alpha in and out (2 x 5 floats),
    # w in and the residual out (2 x 1,024 floats)
    flops, nbytes = work.scd_call(1024, 3, 5)
    assert flops == 3 * 4096
    assert nbytes == 3 * 1024 * 4 + 3 * 16 + 2 * 5 * 4 + 2 * 1024 * 4


def test_round_work_by_hand():
    # K = 2 workers of the call above, then the exchange and apply:
    # read 2 updates and w, write w, read w for the metric = 5 vectors
    f1, b1 = work.scd_call(1024, 3, 5)
    flops, nbytes = work.round_work(1024, 3, 5, 2)
    assert nbytes == 2 * b1 + 5 * 1024 * 4
    assert flops == 2 * f1 + 2 * 1024 + 2 * 1024


def test_allreduce_bytes_by_hand():
    # a ring all-reduce over 4 chips sends 2 x 3/4 of the vector
    assert work.allreduce_ici_bytes(1000, 4) == pytest.approx(6000.0)


def test_roofline_takes_the_binding_bound():
    p = chip_peaks("TPU v5 lite")
    assert work.roofline_seconds(197e12, 0, p) == pytest.approx(1.0)
    assert work.roofline_seconds(1, 819e9, p) == pytest.approx(1.0)
    assert work.roofline_seconds(1, 819e9, p, chips=4) == pytest.approx(0.25)
    assert work.roofline_seconds(1, 1, p, ici_bytes=200e9) == \
        pytest.approx(1.0)


def test_unknown_device_is_an_error():
    with pytest.raises(KeyError):
        chip_peaks("cpu")
