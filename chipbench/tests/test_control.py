"""The comparison has to fail what it is there to catch.  The control,
the plain reference in the program's place at 32-bit precision, and
each fault a cell can have, planted in the round body under a run that
skips only the look for a chip, must all come out not correct."""

import pytest

from chipbench import probes
from chipbench.tests.small import CELLS, run_small


@pytest.mark.parametrize("name", CELLS)
def test_control_at_32_bits_is_not_correct(name):
    res = run_small(name, control_bits=32)
    assert res["correct"] is False
    assert res["checks"]["wrong_replies"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(probes.FAULTS))
@pytest.mark.parametrize("name", CELLS)
def test_fault_is_not_correct(name, fault):
    res = run_small(name, fault=fault)
    assert res["correct"] is False, res["checks"]
