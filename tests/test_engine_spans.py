"""The serving engine's profiler spans (`jax.profiler.TraceAnnotation`):
a tiny paged engine with the prefix cache on, traced into a temporary
directory and read back with `jax.profiler.ProfileData`, under the sync
and the overlapped loop. The spans nest as documented, their stats agree
with the engine's own counters, and tracing leaves the served tokens
unchanged."""
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving import Request, ServingEngine

GEN = 4
SHARED = 8
TAILS = [3, 7, 5, 2]


@pytest.fixture(scope="module")
def model():
    cfg = get_config("qwen2_5_14b").reduced()
    return cfg, M.init_params(cfg, jax.random.PRNGKey(0), dtype=jnp.float32)


def _requests(cfg):
    shared = jax.random.randint(jax.random.PRNGKey(2), (SHARED,), 0, cfg.vocab)
    out = []
    for i, n in enumerate(TAILS):
        tail = jax.random.randint(jax.random.fold_in(jax.random.PRNGKey(1), i),
                                  (n,), 0, cfg.vocab)
        out.append(Request(prompt=[int(t) for t in shared] +
                           [int(t) for t in tail],
                           max_new_tokens=GEN, id=i))
    return out


def _serve(model, overlap):
    cfg, params = model
    eng = ServingEngine(cfg, params, max_slots=2, max_len=24, prefill_chunk=4,
                        kv_block_size=4, prefix_cache=True, overlap=overlap)
    for r in _requests(cfg):
        eng.submit(r)
    tokens, steps = {}, 0
    while eng.has_work():
        steps += 1
        for out in eng.step():
            if out.finished:
                tokens[out.id] = out.tokens
    return tokens, steps, eng.stats()


def _engine_spans(trace_dir):
    """(name, start_ns, end_ns, stats) of the `engine.*` events on the
    host thread that ran the engine."""
    from jax.profiler import ProfileData
    path, = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    found = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            spans = [(ev.name, int(ev.start_ns), int(ev.end_ns),
                      dict(ev.stats))
                     for ev in line.events if ev.name.startswith("engine.")]
            if spans:
                found.append(spans)
    assert len(found) == 1, "engine spans on more than one thread"
    return found[0]


def _inside(span, outer):
    return outer[1] <= span[1] and span[2] <= outer[2]


@pytest.mark.parametrize("overlap", [False, True])
def test_engine_spans(model, overlap, tmp_path):
    untraced, _, _ = _serve(model, overlap)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        traced, steps, stats = _serve(model, overlap)
    assert traced == untraced
    assert sorted(traced) == list(range(len(TAILS)))

    spans = _engine_spans(tmp_path)
    by = {}
    for sp in spans:
        by.setdefault(sp[0], []).append(sp)
    assert set(by) >= {"engine.step", "engine.admit", "engine.plan",
                       "engine.flush", "engine.prefill", "engine.decode",
                       "engine.seed", "engine.drain", "engine.sync",
                       "engine.first_token"}
    assert len(by["engine.step"]) == steps
    assert [sp[3]["tick"] for sp in by["engine.step"]][:1] == [0]

    # nesting: every child inside a step; sync and first tokens in a drain
    for name, group in by.items():
        if name != "engine.step":
            for sp in group:
                assert any(_inside(sp, st) for st in by["engine.step"]), sp
    for name in ("engine.sync", "engine.first_token"):
        for sp in by[name]:
            assert any(_inside(sp, d) for d in by["engine.drain"]), sp

    # stats agree with the engine's counters
    assert (sum(sp[3]["take"] for sp in by["engine.prefill"])
            == stats["prefill_tokens_computed"])
    drained = sum(sp[3]["tokens"] for sp in by["engine.drain"])
    assert drained == sum(len(t) for t in traced.values()) \
        == len(TAILS) * GEN
    sampled = (sum(sp[3]["rows"] for sp in by["engine.decode"])
               + sum(sp[3]["rows"] for sp in by["engine.seed"]))
    assert sampled == drained + stats["wasted_decodes"]
    assert (sum(sp[3]["admitted"] for sp in by["engine.admit"])
            == len(TAILS))
    assert sum(sp[3]["uploads"] for sp in by["engine.flush"]) \
        == stats["h2d_updates"]

    # one first token per request, with its two phases
    firsts = by["engine.first_token"]
    assert sorted(sp[3]["request_id"] for sp in firsts) == sorted(traced)
    assert all(sp[3]["queued_ms"] >= 0 and sp[3]["prefill_ms"] > 0
               for sp in firsts)


def test_decode_span_counts_live_blocks(model, tmp_path):
    """`engine.decode`'s `live_blocks` is the table slots the walked rows'
    valid lengths reach, Σ ceil(kv_valid / bs) read here off the device's
    lengths after each decode, and `table_blocks` is slots x table width,
    on rows that span 1 to 3 blocks."""
    cfg, params = model
    bs, slots = 4, 3
    eng = ServingEngine(cfg, params, max_slots=slots, max_len=16,
                        prefill_chunk=4, kv_block_size=bs, overlap=True)
    walked, per_row = [], set()
    decode = eng.ex.decode_and_sample

    def counted(*args):
        toks = decode(*args)
        blocks = -(-np.asarray(eng.ex.cache["lengths"]) // bs)
        walked.append(int(blocks.sum()))
        per_row.update(int(n) for n in blocks)
        return toks

    eng.ex.decode_and_sample = counted
    for i, n in enumerate([2, 5, 9]):
        eng.submit(Request(prompt=list(range(1, n + 1)), max_new_tokens=3,
                           id=i))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    with jax.profiler.trace(str(tmp_path), profiler_options=opts):
        while eng.has_work():
            eng.step()
    spans = [sp for sp in _engine_spans(tmp_path)
             if sp[0] == "engine.decode"]
    assert [sp[3]["live_blocks"] for sp in spans] == walked
    width = eng.ex.cache["block_tables"].shape[1]
    assert all(sp[3]["table_blocks"] == slots * width for sp in spans)
    assert {1, 2, 3} <= per_row
