"""The control: the program's own lower-precision path (FxP4 GEMMs and
activations, policy flexpe-fxp4) in place of the FxP8 one the
configuration states, at a size a test run holds, comes out not correct
against the limit (the on-chip readings at the cells' own sizes are in
PERF.md)."""
import time

import pytest

from onchip import harness

pytestmark = pytest.mark.usefixtures("fresh_program")


def test_fxp4_path_is_not_correct(tiny_cell):
    res = harness.run_cell(tiny_cell("reason"), 2 ** 33 + 5, 3.0, False,
                           t_start=time.perf_counter(),
                           policy_name="flexpe-fxp4")
    assert not res["correct"], res["compared"]
    cmp = res["compared"]["logit_gap_max"]
    assert cmp["value"] > cmp["limit"]
