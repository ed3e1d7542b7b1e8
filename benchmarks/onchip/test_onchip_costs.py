"""costs.py against operations and bytes worked out by hand at MiniCPM-2B
widths (d_model 2304, 36 heads of 64, d_ff 5760, vocab 122,753)."""
import json
from pathlib import Path

from onchip import costs, peaks

SPEC = json.loads((Path(__file__).parent / "configs" /
                   "minicpm_2b.json").read_text())


def test_gemm_q_projection_at_16_rows():
    ops, nbytes = costs.gemm(16, 2304, 2304)
    assert ops == 2 * 16 * 2304 * 2304 == 169_869_312
    # int8 x codes + int8 w codes + f32 scale + f32 result
    assert nbytes == 36_864 + 5_308_416 + 9_216 + 147_456 == 5_501_952
    # decode GEMMs are bound by bytes on a v5e
    v5e = peaks.chip_peaks("TPU v5 lite")
    assert peaks.bound_s(ops, nbytes, v5e) == nbytes / 819e9


def test_decode_attention_one_row_of_1000_positions():
    ops, nbytes = costs.attention_decode(SPEC, [1000])
    assert ops == 4 * 36 * 64 * 1000 == 9_216_000
    # K and V int8 codes plus an f32 scale per position and head; bf16 q
    # and output
    assert nbytes == 1000 * 36 * 2 * (64 + 4) + 36 * 64 * 2 * 2 == 4_905_216


def test_step_gemms_cover_every_projection_and_the_padded_head():
    calls = costs.step_gemms(SPEC, 16, 16)
    assert len(calls) == 7 * 40 + 1
    assert calls[-1] == (16, 2304, 122_880)
    weights = sum(k * n for _, k, n in calls[:-1])
    assert weights == 40 * (4 * 2304 * 2304 + 3 * 2304 * 5760)
    assert costs.layer_params(SPEC) * 40 == weights


def test_token_ops():
    per_layer = costs.layer_params(SPEC)
    want = (2 * per_layer * 40 + 4 * 36 * 64 * 10 * 40
            + 2 * 2304 * 122_753)
    assert costs.token_ops(SPEC, 9) == want
    assert costs.token_ops(SPEC, 9, head=False) == want - 2 * 2304 * 122_753


def test_unknown_device_kind_is_an_error():
    import pytest
    with pytest.raises(ValueError):
        peaks.chip_peaks("TPU v9 imaginary")
