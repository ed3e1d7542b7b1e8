"""Fixtures of the benchmark's CPU tests: a configuration and mixes cut to
a size a test run holds, driven through the same harness as on the
chip, with the Pallas kernels in interpret mode."""
import json
from pathlib import Path

import pytest

from onchip import harness, loadgen, sut

HERE = Path(__file__).resolve().parent


def tiny_spec(backend="pallas-interpret"):
    spec = json.loads((HERE / "configs" / "minicpm_2b.json").read_text())
    spec.update(hidden_size=128, intermediate_size=256, num_hidden_layers=2,
                num_attention_heads=4, num_key_value_heads=2, head_dim=32,
                vocab_size=500)
    spec["engine"].update(slots=4, max_len=192, kv_blocks=64,
                          prefill_chunk=32, backend=backend)
    return spec


def tiny_mix(name):
    mix = loadgen.load_mix(name)
    if mix["loop"] == "closed":
        mix.update(prompt_len={"median": 20, "sigma": 0.5, "min": 8,
                               "max": 40},
                   output_len={"median": 24, "sigma": 0.4, "min": 8,
                               "max": 48})
    else:
        mix.update(rate_per_s=4.0,
                   prefixes={"count": 3, "len": 32, "zipf_s": 1.1},
                   suffix_len={"median": 16, "sigma": 0.5, "min": 4,
                               "max": 32},
                   output_len={"median": 6, "sigma": 0.5, "min": 2,
                               "max": 12})
    return mix


@pytest.fixture
def tiny_cell():
    def make(mix_name="reason", limit=0.25, sample=4):
        bench = json.loads(harness.BENCHMARK.read_text())
        return harness.Cell(
            f"tiny.{mix_name}", tiny_spec(), tiny_mix(mix_name), 1,
            harness.metrics_for(bench["end_to_end"], f"minicpm_2b.{mix_name}"),
            [],
            {"logit_gap_max": limit, "sample_requests": sample})
    return make


@pytest.fixture
def fresh_program():
    """Forget the program's compiled steps before and after a test that
    patches the program, so no other test runs a patched step."""
    sut.import_program()
    from repro.serving import executor
    import jax
    executor._STEP_CACHE.clear()
    jax.clear_caches()
    yield
    executor._STEP_CACHE.clear()
    jax.clear_caches()
