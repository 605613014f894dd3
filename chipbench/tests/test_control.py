"""The control, at a size a test run holds: the reference solve in
bfloat16, put in the program's place, is refused by the comparison on
every seed, while the same reference in float32 passes it. On the chip
the control runs at each cell's own size through ``control.py``."""
from __future__ import annotations

import pytest

from chipbench import control

SEEDS = [1, 2, 2**31 + 5]


@pytest.mark.parametrize("workload", ["tiny.h-local", "tinyx4.h-local",
                                      "tiny.h16"])
def test_bfloat16_control_is_refused(tiny_root, workload):
    for seed in SEEDS:
        r = control.control(workload, seed, "bfloat16", root=tiny_root)
        assert not r["correct"], r
        gap = r["checks"]["primal_gap"]
        assert gap["value"] > 3 * gap["limit"], r


@pytest.mark.parametrize("workload", ["tiny.h-local", "tinyx4.h-local",
                                      "tiny.h16"])
def test_float32_reference_passes(tiny_root, workload):
    for seed in SEEDS:
        r = control.control(workload, seed, "float32", root=tiny_root)
        assert r["correct"], r
