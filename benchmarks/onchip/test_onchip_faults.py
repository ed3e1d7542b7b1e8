"""A run whose timed path is broken underneath comes out not correct:
once for each fault a one-chip serving cell can have. (The exchange
between chips is not on a one-chip cell's path.)"""
import time

import numpy as np
import pytest

from onchip import harness

pytestmark = pytest.mark.usefixtures("fresh_program")


def _token_altered(monkeypatch):
    """Every sampled token moved to its neighbour where it is produced."""
    from repro.serving import executor
    core = executor._sample_core

    def altered(vocab, logits, keys, temps, topks):
        return (core(vocab, logits, keys, temps, topks) + 1) % vocab
    monkeypatch.setattr(executor, "_sample_core", altered)


def _state_unchanged(monkeypatch):
    """The step returns the KV pool it was given: nothing is written."""
    from repro.models import layers
    monkeypatch.setattr(layers, "paged_cache_update",
                        lambda pool, new, *a, **k: pool)


def _half_batch(monkeypatch):
    """Each decode step computes only the first half of the rows; the rest
    are served whatever the step leaves in them."""
    from repro.serving.executor import ModelExecutor
    step = ModelExecutor.decode_and_sample

    def half(self, n_valid, *args):
        n_valid = np.array(n_valid)
        n_valid[len(n_valid) // 2:] = 0
        return step(self, n_valid, *args)
    monkeypatch.setattr(ModelExecutor, "decode_and_sample", half)


@pytest.mark.parametrize("fault", [_token_altered, _state_unchanged,
                                   _half_batch])
def test_a_broken_step_is_not_correct(tiny_cell, monkeypatch, fault):
    fault(monkeypatch)
    res = harness.run_cell(tiny_cell("reason"), 2 ** 33 + 5, 3.0, False,
                           t_start=time.perf_counter())
    assert not res["correct"], res["compared"]
