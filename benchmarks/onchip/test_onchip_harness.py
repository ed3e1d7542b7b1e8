"""The harness loop at a reduced size on the CPU, kernels in interpret
mode, driven from the test: both loops run a window with nothing compiled
in it, report their metrics, and come out correct against the plain
reference."""
import time

import pytest

from onchip import harness

pytestmark = pytest.mark.usefixtures("fresh_program")


@pytest.mark.parametrize("mix", ["reason", "code_shared"])
def test_a_sound_run_is_correct(tiny_cell, mix):
    cell = tiny_cell(mix)
    res = harness.run_cell(cell, 2 ** 33 + 5, 3.0, False,
                           t_start=time.perf_counter())
    cmp = res["compared"]
    assert res["correct"], cmp
    assert cmp["compilations_in_window"]["value"] == 0
    assert cmp["logit_gap_max"]["value"] <= cmp["logit_gap_max"]["limit"]
    m = res["metrics"]
    assert m["setup_s"]["value"] > 0
    if mix == "reason":
        assert m["output_tok_s"]["value"] > 0 and m["itl_p95_ms"]["value"] > 0
        assert "ttft_p90_ms" not in m
    else:
        assert m["ttft_p90_ms"]["value"] > 0
        assert "output_tok_s" not in m and "itl_p95_ms" not in m
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res) == ["correct", "attempted", "failed", "metrics",
                         "device", "compared"]

