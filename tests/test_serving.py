"""Continuous-batching serving tests: ragged per-request cache semantics
(chunked prefill == token-by-token, batch-composition independence),
engine scheduling (EOS early release, late admission), per-request RNG,
and the streaming API (RequestOutput deltas, stream(), abort(), the
overlap-dispatch loop's bit-exactness vs the sync loop)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving import (FinishedRequest, Request, RequestOutput,
                           SamplingParams, ServingEngine)

KEY = jax.random.PRNGKey(0)


def _params(cfg):
    return M.init_params(cfg, KEY, dtype=jnp.float32)


def _prompt(i, plen, cfg):
    key = jax.random.fold_in(jax.random.PRNGKey(1), i)
    if cfg.input_mode == "tokens":
        return jax.random.randint(key, (plen,), 0, cfg.vocab)
    return jax.random.normal(key, (plen, cfg.d_model), jnp.bfloat16)


def _req(i, plen, cfg, gen=6, **kw):
    return Request(prompt=_prompt(i, plen, cfg), max_new_tokens=gen, id=i,
                   **kw)


# ---------------------------------------------------------------------------
# ragged decode_step semantics (no engine)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2_5_14b", "mamba2_370m",
                                  "zamba2_1p2b", "deepseek_moe_16b"])
def test_chunked_prefill_matches_token_by_token(arch):
    """One bulk decode_step call over the prompt == S token-by-token steps:
    same last logits, same per-request lengths, for all cache families."""
    cfg = get_config(arch).reduced()
    p = _params(cfg)
    seq = jax.random.randint(KEY, (2, 10), 0, cfg.vocab)
    cache_a = M.init_cache(cfg, 2, 16, dtype=jnp.float32)
    lg_a, cache_a = M.decode_step(cfg, p, cache_a, seq)
    cache_b = M.init_cache(cfg, 2, 16, dtype=jnp.float32)
    for t in range(10):
        lg_b, cache_b = M.decode_step(cfg, p, cache_b, seq[:, t:t + 1])
    np.testing.assert_allclose(np.asarray(lg_a[:, -1]), np.asarray(lg_b[:, 0]),
                               atol=2e-5)
    assert (cache_a["lengths"].tolist() == cache_b["lengths"].tolist()
            == [10, 10])


def test_ragged_rows_advance_independently():
    """n_valid=0 rows leave cache + lengths bit-untouched while other rows
    decode; per-row positions continue from each row's own length."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    seq = jax.random.randint(KEY, (2, 6), 0, cfg.vocab)
    cache = M.init_cache(cfg, 2, 12, dtype=jnp.float32)
    _, cache = M.decode_step(cfg, p, cache, seq,
                             n_valid=jnp.array([6, 3], jnp.int32))
    assert cache["lengths"].tolist() == [6, 3]
    row1_kv = np.asarray(cache["kv"]["k"][:, 1])
    # row 0 idles, row 1 decodes one token
    _, cache2 = M.decode_step(cfg, p, cache, seq[:, :1],
                              n_valid=jnp.array([0, 1], jnp.int32))
    assert cache2["lengths"].tolist() == [6, 4]
    np.testing.assert_array_equal(np.asarray(cache2["kv"]["k"][:, 0]),
                                  np.asarray(cache["kv"]["k"][:, 0]))
    # row 1's previously-valid prefix is untouched; position 3 was written
    np.testing.assert_array_equal(np.asarray(cache2["kv"]["k"][:, 1, :3]),
                                  row1_kv[:, :3])
    assert not np.array_equal(np.asarray(cache2["kv"]["k"][:, 1, 3]),
                              row1_kv[:, 3])


def test_last_only_gathers_per_row_valid_position():
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    seq = jax.random.randint(KEY, (2, 8), 0, cfg.vocab)
    nv = jnp.array([8, 5], jnp.int32)
    cache = M.init_cache(cfg, 2, 12, dtype=jnp.float32)
    full, _ = M.decode_step(cfg, p, cache, seq, n_valid=nv)
    cache = M.init_cache(cfg, 2, 12, dtype=jnp.float32)
    last, _ = M.decode_step(cfg, p, cache, seq, n_valid=nv, last_only=True)
    assert last.shape[1] == 1
    np.testing.assert_array_equal(np.asarray(last[0, 0]),
                                  np.asarray(full[0, 7]))
    np.testing.assert_array_equal(np.asarray(last[1, 0]),
                                  np.asarray(full[1, 4]))


# ---------------------------------------------------------------------------
# engine: batch-composition independence (the headline invariant)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ["qwen2_5_14b", "mamba2_370m",
                                  "zamba2_1p2b", "deepseek_moe_16b"])
def test_request_alone_matches_mixed_batch(arch):
    """A request decoded alone is bit-identical (greedy, reference backend)
    to the same request decoded inside a mixed-length batch with slot
    reuse and late admission."""
    cfg = get_config(arch).reduced()
    p = _params(cfg)
    lens = [(0, 5), (1, 11), (2, 8), (3, 3), (4, 9)]

    def run(ids):
        eng = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4)
        done = eng.run([_req(i, pl, cfg) for i, pl in lens if i in ids])
        return {f.id: f.tokens for f in done}

    mixed = run({0, 1, 2, 3, 4})
    for i, pl in lens:
        alone = run({i})
        assert alone[i] == mixed[i], (arch, i, alone[i], mixed[i])


def test_late_admitted_request_gets_correct_positions():
    """A request admitted mid-decode into a reused slot (stale cache from
    the previous occupant above its length) matches its solo run."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    # 1 slot: requests run strictly one after another through the same row
    eng = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    serial = {f.id: f.tokens for f in
              eng.run([_req(0, 12, cfg), _req(1, 4, cfg)])}
    solo = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    alone = solo.run([_req(1, 4, cfg)])[0].tokens
    assert serial[1] == alone


def test_eos_early_release_frees_slot():
    """EOS finishes a request early, frees its slot, and the next pending
    request is admitted into it."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    probe = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    first_tok = probe.run([_req(0, 6, cfg, gen=1)])[0].tokens[0]

    eng = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    reqs = [_req(0, 6, cfg, gen=8, eos_id=first_tok), _req(1, 4, cfg)]
    done = {f.id: f for f in eng.run(reqs)}
    assert done[0].finish_reason == "eos"
    assert done[0].tokens == [first_tok]        # stopped after 1 token
    assert done[1].finish_reason == "length"
    assert len(done[1].tokens) == 6
    # slot was actually reused: request 1 started after request 0 finished
    assert done[1].admitted_tick > done[0].finished_tick - 1
    # and its output is batch-composition independent
    solo = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    assert solo.run([_req(1, 4, cfg)])[0].tokens == done[1].tokens


def test_prefill_chunk_size_does_not_change_output():
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    outs = []
    for chunk in (2, 5, 16):
        eng = ServingEngine(cfg, p, max_slots=2, max_len=24,
                            prefill_chunk=chunk)
        outs.append({f.id: f.tokens
                     for f in eng.run([_req(0, 9, cfg), _req(1, 6, cfg)])})
    assert outs[0] == outs[1] == outs[2]


# ---------------------------------------------------------------------------
# sampling: per-request RNG + params
# ---------------------------------------------------------------------------

def test_sampled_output_independent_of_coscheduled_requests():
    """Per-request RNG: a temperature-sampled request produces the same
    tokens whether it runs alone or next to other requests."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    sp = SamplingParams(temperature=0.8, top_k=12)

    def run(ids):
        eng = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4)
        reqs = [_req(i, pl, cfg, sampling=sp, seed=100 + i)
                for i, pl in [(0, 6), (1, 9), (2, 4)] if i in ids]
        return {f.id: f.tokens for f in eng.run(reqs)}

    mixed = run({0, 1, 2})
    for i in (0, 1, 2):
        assert run({i})[i] == mixed[i], i


def test_per_request_sampling_params_apply():
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4)
    greedy = _req(0, 6, cfg)
    hot = _req(1, 6, cfg, sampling=SamplingParams(temperature=1.5), seed=7)
    done = {f.id: f.tokens for f in eng.run([greedy, hot])}
    # greedy row must equal a solo greedy run (unperturbed by the hot row)
    solo = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    assert done[0] == solo.run([_req(0, 6, cfg)])[0].tokens
    # hot sampling with a different seed gives a different trajectory
    eng2 = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    other = eng2.run([_req(1, 6, cfg,
                           sampling=SamplingParams(temperature=1.5),
                           seed=8)])[0].tokens
    assert other != done[1]


# ---------------------------------------------------------------------------
# engine hygiene
# ---------------------------------------------------------------------------

def test_submit_rejects_invalid_requests():
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=1, max_len=10, prefill_chunk=4)
    with pytest.raises(ValueError):               # oversized
        eng.submit(_req(0, 8, cfg, gen=8))
    with pytest.raises(ValueError):               # empty prompt wedges slot
        eng.submit(Request(prompt=[], max_new_tokens=4))
    with pytest.raises(ValueError):               # zero-token generation
        eng.submit(_req(1, 4, cfg, gen=0))
    assert not eng.has_work()


def test_submit_rejects_duplicate_live_ids():
    """Two live requests with one explicit id would share a fold_in RNG
    stream and interleave in run()'s sorted results: the second submit
    must raise while the first is pending or in flight. Once the first
    finishes, its id becomes reusable."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    eng.submit(_req(5, 4, cfg))
    with pytest.raises(ValueError):               # still pending
        eng.submit(_req(5, 4, cfg))
    eng.step()                                    # admitted, in flight
    with pytest.raises(ValueError):
        eng.submit(_req(5, 4, cfg))
    # auto-assigned ids keep clear of the explicit one
    auto = eng.submit(Request(prompt=_prompt(1, 4, cfg), max_new_tokens=2))
    assert auto != 5
    list(eng.events())
    assert eng.submit(_req(5, 4, cfg)) == 5       # finished: reusable
    list(eng.events())


# ---------------------------------------------------------------------------
# streaming API: RequestOutput events, stream(), abort()
# ---------------------------------------------------------------------------

def test_events_yield_per_token_deltas_then_finish():
    """events() emits one RequestOutput per sampled token; the deltas
    concatenate to exactly the finished token list, and the terminal
    event carries the completion metadata."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4)
    eng.submit(_req(0, 6, cfg, gen=4))
    eng.submit(_req(1, 9, cfg, gen=3))
    outs = list(eng.events())
    assert all(isinstance(o, RequestOutput) for o in outs)
    for rid, gen in [(0, 4), (1, 3)]:
        mine = [o for o in outs if o.id == rid]
        assert len(mine) == gen                   # one event per token
        deltas = [t for o in mine for t in o.new_tokens]
        assert mine[-1].finished and mine[-1].tokens == deltas
        assert mine[-1].finish_reason == "length"
        assert mine[-1].ttft_s >= 0.0
        assert not any(o.finished for o in mine[:-1])
        # cumulative view grows by exactly the delta each event
        for i, o in enumerate(mine):
            assert o.tokens == deltas[:i + 1]
    # the deprecated completion view is derivable from the stream
    fin = outs[-1].to_finished() if outs[-1].finished else None
    assert isinstance(fin, FinishedRequest)
    with pytest.raises(ValueError):
        next(o for o in outs if not o.finished).to_finished()


def test_stream_single_request_interleaved_with_events():
    """stream(request) yields only that request's events while other
    requests keep decoding; their events stay buffered for events()."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4)
    eng.submit(_req(0, 6, cfg, gen=6))
    streamed = list(eng.stream(_req(1, 4, cfg, gen=3)))
    assert [o.id for o in streamed] == [1, 1, 1]
    assert streamed[-1].finished
    # request 0's events were buffered, not dropped
    other = [o for o in eng.events() if o.id == 0]
    assert other and other[-1].finished and len(other[-1].tokens) == 6
    # streamed output matches the same request decoded via run()
    solo = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4)
    assert solo.run([_req(1, 4, cfg, gen=3)])[0].tokens == \
        streamed[-1].tokens


def test_abort_pending_and_inflight_release_cleanly():
    """abort() drains a queued request (no _submitted leak) and releases
    an in-flight one with refcounted block return; survivors decode
    exactly as if the aborted requests never existed."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4,
                        kv_block_size=4)
    a = eng.submit(_req(0, 8, cfg, gen=8))
    b = eng.submit(_req(1, 4, cfg, gen=4))
    c = eng.submit(_req(2, 5, cfg, gen=3))
    eng.step(); eng.step()                        # 0 in flight, 1/2 queued
    assert eng.abort(b) and eng.abort(a)          # queued + in-flight
    assert not eng.abort(b)                       # already gone
    eng.check_invariants()                        # incl. _submitted ledger
    outs = list(eng.events())
    reasons = {o.id: o.finish_reason for o in outs if o.finished}
    assert reasons[a] == reasons[b] == "aborted"
    assert reasons[c] == "length"
    survivor = [o for o in outs if o.id == c and o.finished][0]
    solo = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    assert solo.run([_req(2, 5, cfg, gen=3)])[0].tokens == survivor.tokens
    st = eng.stats()
    assert st["aborted_requests"] == 2 and st["pending_requests"] == 0
    assert st["free_blocks"] == st["kv_blocks"]   # every block returned
    eng.check_invariants()


def test_step_loop_drains_abort_events():
    """The documented `while has_work(): step()` loop must terminate
    after an abort — step() drains buffered terminal events (abort
    writes its event to the buffer, not a step return)."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    rid = eng.submit(_req(0, 6, cfg, gen=4))
    eng.submit(_req(1, 4, cfg, gen=2))
    eng.abort(rid)
    outs, spins = [], 0
    while eng.has_work():
        outs.extend(eng.step())
        spins += 1
        assert spins < 100, "step() loop live-locked on buffered events"
    reasons = {o.id: o.finish_reason for o in outs if o.finished}
    assert reasons == {0: "aborted", 1: "length"}
    # aborted-then-drained work still shows up in the throughput stats
    st = eng.stats()
    assert st["generated_tokens"] == 2 and st["prompt_tokens"] == 4


def test_abort_mid_overlap_discards_inflight_samples():
    """Aborting an in-flight request under the overlapped loop discards
    its already-dispatched decode (counted as wasted), keeps the ledger
    balanced, and never corrupts the surviving request."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4,
                        kv_block_size=4, overlap=True)
    a = eng.submit(_req(0, 4, cfg, gen=8))
    eng.submit(_req(1, 6, cfg, gen=4))
    eng.step(); eng.step(); eng.step()            # both decoding, 1 in flight
    assert eng.abort(a)
    eng.check_invariants()
    outs = list(eng.events())
    fin = [o for o in outs if o.id == 1 and o.finished][0]
    solo = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    assert solo.run([_req(1, 6, cfg, gen=4)])[0].tokens == fin.tokens
    assert eng.stats()["wasted_decodes"] >= 1
    assert eng.stats()["free_blocks"] == eng.stats()["kv_blocks"]


# ---------------------------------------------------------------------------
# overlap-dispatch loop: bit-exactness vs sync (the refactor's anchor)
# ---------------------------------------------------------------------------

def _mixed_workload(cfg, temp=0.0):
    sp = SamplingParams(temperature=temp, top_k=8 if temp > 0 else 0)
    lens = [(0, 5), (1, 11), (2, 8), (3, 3), (4, 9)]
    gens = [6, 3, 5, 4, 2]
    return [_req(i, pl, cfg, gen=g, sampling=sp, seed=50 + i)
            for (i, pl), g in zip(lens, gens)]


@pytest.mark.parametrize("arch", ["qwen2_5_14b", "mamba2_370m",
                                  "zamba2_1p2b", "deepseek_moe_16b"])
@pytest.mark.parametrize("paged", [False, True])
def test_overlap_bit_exact_vs_sync(arch, paged):
    """The overlapped loop (dispatch tick N+1 before syncing tick N's
    samples) decodes bit-identically to the sync loop for every cache
    family, contiguous and paged, greedy and sampled, with EOS release
    lagging one tick."""
    cfg = get_config(arch).reduced()
    p = _params(cfg)
    kw = dict(kv_block_size=4) if paged else {}

    def run(overlap, temp):
        eng = ServingEngine(cfg, p, max_slots=2, max_len=24,
                            prefill_chunk=4, overlap=overlap, **kw)
        done = eng.run(_mixed_workload(cfg, temp=temp))
        return {f.id: f.tokens for f in done}, eng

    for temp in (0.0, 0.9):
        sync, _ = run(False, temp)
        ovl, eng = run(True, temp)
        assert sync == ovl, (arch, paged, temp)
        st = eng.stats()
        # the overlap win is a counter, not wall clock: almost no token's
        # sample sync gated the next dispatch (only the final drain)
        assert st["sample_syncs_per_token"] < 1.0
        assert st["overlap"] is True


def test_overlap_bit_exact_with_prefix_cache_and_invariants():
    """Overlap composes with prefix caching: shared-prefix decode under
    the overlapped loop matches the cold sync paged run bit-exactly, and
    the allocator ledger balances after EVERY overlapped tick (drains in
    flight included)."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    shared = jax.random.randint(jax.random.PRNGKey(2), (8,), 0, cfg.vocab)
    reqs = lambda: [Request(  # noqa: E731
        prompt=jnp.concatenate([shared, _prompt(i, pl, cfg)]),
        max_new_tokens=4, id=i) for i, pl in [(0, 3), (1, 7), (2, 5), (3, 2)]]

    def run(**kw):
        eng = ServingEngine(cfg, p, max_slots=2, max_len=24,
                            prefill_chunk=4, **kw)
        for r in reqs():
            eng.submit(r)
        done = {}
        while eng.has_work():
            for out in eng.step():
                if out.finished:
                    done[out.id] = out.tokens
            eng.check_invariants()
        return done, eng

    cold, _ = run(kv_block_size=4)
    warm, eng = run(kv_block_size=4, prefix_cache=True, overlap=True)
    assert cold == warm
    assert eng.stats()["prefix_tokens_reused"] > 0
    assert eng.stats()["sample_syncs_per_token"] < 1.0


def test_overlap_eos_overrun_is_bounded_and_discarded():
    """EOS detection lags one tick under overlap: exactly the post-EOS
    decodes are dispatched-then-discarded (never emitted), and the
    emitted tokens match the sync run."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    probe = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4)
    first = probe.run([_req(0, 6, cfg, gen=1)])[0].tokens[0]

    def run(overlap):
        eng = ServingEngine(cfg, p, max_slots=1, max_len=24,
                            prefill_chunk=4, overlap=overlap)
        done = eng.run([_req(0, 6, cfg, gen=8, eos_id=first)])
        return done[0], eng

    fin_s, eng_s = run(False)
    fin_o, eng_o = run(True)
    assert fin_s.tokens == fin_o.tokens == [first]
    assert fin_s.finish_reason == fin_o.finish_reason == "eos"
    assert eng_s.stats()["wasted_decodes"] == 0
    assert eng_o.stats()["wasted_decodes"] == 1   # the one-tick overrun
    # length finishes are host-predicted: no overrun at all
    eng = ServingEngine(cfg, p, max_slots=1, max_len=24, prefill_chunk=4,
                        overlap=True)
    eng.run([_req(1, 6, cfg, gen=4)])
    assert eng.stats()["wasted_decodes"] == 0


def test_sample_sync_counter_sync_mode_is_one():
    """In sync mode every emitted token's device->host sample transfer
    gates the next dispatch: the counter reads exactly 1.0."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4)
    eng.run([_req(0, 6, cfg, gen=4), _req(1, 9, cfg, gen=4)])
    assert eng.stats()["sample_syncs_per_token"] == 1.0


def test_scheduler_flag_reaches_engine():
    """scheduler='spf' reorders admission (shortest prompt first) without
    perturbing any request's own tokens."""
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)

    def run(policy):
        eng = ServingEngine(cfg, p, max_slots=1, max_len=24,
                            prefill_chunk=4, scheduler=policy)
        for i, pl in [(0, 12), (1, 3), (2, 7)]:
            eng.submit(_req(i, pl, cfg, gen=2))
        outs = [o for o in eng.events() if o.finished]
        return [o.id for o in outs], {o.id: o.tokens for o in outs}

    fifo_order, fifo_toks = run("fifo")
    spf_order, spf_toks = run("spf")
    assert fifo_order == [0, 1, 2]
    assert spf_order == [1, 2, 0]
    assert fifo_toks == spf_toks          # batch-composition independence


def test_stats_and_finished_metadata():
    cfg = get_config("qwen2_5_14b").reduced()
    p = _params(cfg)
    eng = ServingEngine(cfg, p, max_slots=2, max_len=24, prefill_chunk=4)
    done = eng.run([_req(0, 6, cfg, gen=4), _req(1, 9, cfg, gen=4)])
    assert all(isinstance(f, FinishedRequest) for f in done)
    st = eng.stats()
    assert st["prompt_tokens"] == 15
    assert st["generated_tokens"] == 8
    assert st["prefill_tokens_computed"] == 15    # no prefix cache: all cold
    assert 0.0 < st["slot_utilization"] <= 1.0
    # queue-health satellite fields
    assert st["pending_requests"] == 0
    assert st["queue_wait_ticks_max"] >= 0
    assert st["queue_wait_ticks_mean"] >= 0.0
    assert st["wasted_decodes"] == 0              # sync mode never overruns
    assert all(f.ttft_s >= 0.0 for f in done)
    assert not eng.has_work()


@pytest.mark.parametrize("kv_block_size", [None, 4])
def test_peek_logits_matches_next_greedy_step(kv_block_size):
    """`ModelExecutor.peek_logits` reads the next decode step without
    advancing the cache or token buffer: peeking twice gives the same
    logits, their argmax is the token the engine then samples greedily,
    and a reference-policy peek on the same state agrees with the pallas
    one (quantize-once weights share the exact-integer contract), as
    does a reference-policy engine's own prefill fed the same first
    tokens through `tokens=`."""
    from repro.core.precision import PrecisionPolicy
    from repro.core.qtensor import quantize_params
    cfg = get_config("qwen2_5_14b").reduced()
    pol = PrecisionPolicy.flexpe(8, backend="pallas-interpret")
    params = quantize_params(_params(cfg), "fxp8")
    eng = ServingEngine(cfg, params, policy=pol, max_slots=2, max_len=24,
                        prefill_chunk=8, kv_block_size=kv_block_size)
    for i in range(2):
        eng.submit(_req(i, 8, cfg, gen=3))
    first = {o.id: o.tokens[0] for o in eng.step()}
    assert sorted(first) == [0, 1]
    nv = np.ones((2,), np.int32)
    lg = np.asarray(eng.ex.peek_logits(nv))
    np.testing.assert_array_equal(lg, np.asarray(eng.ex.peek_logits(nv)))
    ref = np.asarray(eng.ex.peek_logits(nv, policy=pol.with_backend(
        "reference")))
    np.testing.assert_allclose(lg, ref, rtol=1e-5, atol=1e-5)
    ref_eng = ServingEngine(cfg, params, policy=pol.with_backend(
        "reference"), max_slots=2, max_len=24, prefill_chunk=8,
        kv_block_size=kv_block_size)
    for i in range(2):
        ref_eng.submit(_req(i, 8, cfg, gen=3))
    ref_eng.step()
    feed = [first[s.request.id] for s in ref_eng.sched.slots]
    ref_all = np.asarray(ref_eng.ex.peek_logits(nv, tokens=feed))
    np.testing.assert_allclose(lg, ref_all, rtol=1e-5, atol=1e-5)
    second = {o.id: o.tokens[1] for o in eng.step()}
    rows = {s.request.id: b for b, s in enumerate(eng.sched.slots)
            if s is not None}
    for rid, tok in second.items():
        assert int(np.argmax(lg[rows[rid], :cfg.vocab])) == tok
