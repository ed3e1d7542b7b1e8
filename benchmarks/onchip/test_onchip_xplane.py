"""The trace reduction, on a small trace recorded on a v5e chip
(`testdata/small.xplane.pb`): a traced run of the harness on a 2-layer
configuration of hidden size 256 (4 heads of 64, d_ff 512) through the
compiled Pallas path, cut to the first 0.2 s of its window and to the
device's program and operation lines and the engine thread's host line
(about 0.9 MB)."""
from pathlib import Path

import pytest

from onchip import names, xplane

TRACE = Path(__file__).parent / "testdata" / "small.xplane.pb"
LAYERS = 2


@pytest.fixture(scope="module")
def red():
    return xplane.reduce(TRACE)


@pytest.fixture(scope="module")
def raw():
    """Device operations and the window, read straight from the file."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(TRACE))
    ops, window = [], None
    for plane in pd.planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name == xplane.WINDOW_SPAN:
                    window = (int(ev.start_ns), int(ev.end_ns))
                if plane.name == "/device:TPU:0" and line.name == "XLA Ops":
                    ops.append((int(ev.start_ns), int(ev.end_ns)))
    return ops, window


def test_busy_is_the_union_of_device_ops_in_the_window(red, raw):
    ops, (w0, w1) = raw
    covered = set()
    for s, e in ops:
        covered.update(range(max(s, w0) // 1000, e // 1000))   # per us
    assert red.busy_s == pytest.approx(len(covered) * 1e-6, rel=0.02)
    last = max(e for _, e in ops)
    assert red.window_s >= (max(last, w1) - w0) * 1e-9 - 1e-9
    assert red.window_s < (max(last, w1) - w0) * 1e-9 + 1e-3
    assert 0 < red.busy_s <= red.window_s


def test_programs_and_kernels_per_step(red):
    decodes = red.program_seconds(names.DECODE_PROGRAM)
    prefills = red.program_seconds(names.PREFILL_PROGRAM)
    assert decodes and all(t > 0 for t in decodes)
    gemms, _ = red.kernel_seconds(names.GEMM_KERNEL, names.DECODE_PROGRAM)
    attn, _ = red.kernel_seconds(names.PAGED_ATTENTION_KERNEL,
                                 names.DECODE_PROGRAM)
    # 7 projections a layer and the head; one attention call a layer
    assert gemms == len(decodes) * (7 * LAYERS + 1)
    assert attn == len(decodes) * LAYERS
    if prefills:
        pg, _ = red.kernel_seconds(names.GEMM_KERNEL, names.PREFILL_PROGRAM)
        pa, _ = red.kernel_seconds(names.PAGED_ATTENTION_KERNEL,
                                   names.PREFILL_PROGRAM)
        assert pg == len(prefills) * (7 * LAYERS + 1) and pa == 0
    # kernel time sits inside its programs' time
    _, kt = red.kernel_seconds(".", names.DECODE_PROGRAM)
    assert 0 < kt < sum(decodes)


def test_gaps_and_breakdown(red):
    gaps = red.gaps
    assert gaps and all(s > 0 for _, s in gaps)
    assert sum(s for _, s in gaps) == pytest.approx(
        red.window_s - red.busy_s, rel=1e-6, abs=1e-9)
    labels = {label for label, _ in gaps}
    assert all(label and not label.startswith("$") for label in labels)
    bd = red.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    top = dict(bd["device_ops"])
    assert "fxp_gemm" in top and "paged_attention" in top
    assert not any(k.startswith("while") for k in top)
