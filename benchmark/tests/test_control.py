"""Step 3 of "How correct is decided", at a size a test run holds: the
program reads 0 on every number compared; the control (the reference
in int32 / float32) fails one of them."""

import pytest

from benchmark import control

CELLS = [
    ("sigscale-5k.plan", 0.01),
    ("envelope-5k.spread", 0.05),
    ("envelope-5k.anti", 0.05),
    ("envelope-5k.basic", 0.02),
]


@pytest.mark.parametrize("cell,scale", CELLS)
def test_program_passes_and_control_fails(cell, scale):
    for seed, program, low in control.readings(cell, [7, 3_000_000_019], 2.0, scale):
        assert all(v == 0 for v in program.values()), (seed, program)
        assert any(v > 0 for v in low.values()), (seed, low)
