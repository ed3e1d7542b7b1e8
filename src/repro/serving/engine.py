"""Continuous-batching serving engine — scheduler/executor split with an
async, overlap-dispatch loop.

The engine is the software analogue of Flex-PE's time-multiplexed PE
array, and this module is deliberately thin: all host policy (admission,
slot assignment, block reservation, prefix matching) lives in
`scheduler.Scheduler`, all device state (compiled steps, cache, control-
array mirrors, the sampled-token feedback buffer) lives in
`executor.ModelExecutor`, and the engine just runs the tick loop between
them and turns drained samples into `RequestOutput` events.

One engine tick runs two kinds of jitted step, both jit-stable shapes:

  * per-slot chunked prefill — tokens [1, prefill_chunk] against ONE
    slot's cache row (sliced out of the pool by a traced slot index).
  * fused pool decode + sample — tokens [B, 1] read from the executor's
    device-resident token buffer, per-row `n_valid` (0 rows stay
    bit-untouched), sampled tokens written straight back into the
    buffer on device.

Because the feedback buffer closes the decode loop on device, the host
never needs a sampled token's *value* to build the next dispatch — only
to emit events and detect EOS. That enables two loop modes, bit-exact
with each other (both run the identical dispatch sequence; per-request
outputs are additionally batch-composition independent, the long-standing
engine invariant):

  * `overlap=False` (default): each tick's samples are synced to the
    host immediately after dispatch — the pre-split behaviour, with
    exact legacy tick timing.
  * `overlap=True`: the host enqueues tick N+1's dispatches *before*
    syncing tick N's samples, draining one tick behind, so the
    device→host sample sync overlaps the next tick's device compute
    instead of idling the array. Length finishes are predicted from the
    host-side scheduled count and release their slot at DISPATCH time,
    so admission timing stays identical to the sync loop; only EOS —
    unknowable until the sampled value syncs — is detected one tick
    late, bounded and accounted (at most one discarded decode per EOS'd
    request, counted in `wasted_decodes`, with its slot release lagging
    that one tick). `sample_syncs_per_token` in `stats()` exposes the
    win as a counter: the fraction of emitted tokens whose device→host
    sync gated the next dispatch (1.0 sync, ~0 overlapped).

The public output surface is the `RequestOutput` event stream —
`events()` yields per-token deltas plus finish events, `stream(request)`
narrows that to one request, `abort(id)` releases queued or in-flight
requests with refcounted block return — while `run()` keeps returning
the deprecated `FinishedRequest` completion view.

Paged KV, copy-on-write prefix caching, per-request sampling/RNG, and
the coalesced per-tick control-array writes are unchanged in semantics
from the pre-split engine; see `scheduler.py` / `executor.py` for where
each now lives.

Each tick is annotated with `jax.profiler.TraceAnnotation` spans, which a
profiler trace records on the host thread that calls `step()`, on the
same clock as the device's programs; while no trace is being taken each
costs about a microsecond. `engine.step` (stat `tick`) holds
`engine.admit` (`admitted`), `engine.plan` (`prefill_rows`,
`decode_rows`), `engine.flush` (`uploads`: control arrays written to
the device), one `engine.prefill` per chunk (`request_id`, `start`,
`take`), `engine.decode` (`rows`: key folding and the fused decode +
sample; on a paged cache also `live_blocks`, the table slots the rows'
valid lengths reach, and `table_blocks`, slots times table width),
`engine.seed` (`rows`) and one `engine.drain` per tick drained
(`tick`, `tokens`). A drain holds `engine.sync`, where the host waits
for the sampled tokens, and one `engine.first_token` per request whose
first token it emits (`request_id`; `queued_ms` from submit to
admission and `prefill_ms` from admission to that token, on the
scheduler's monotonic clock). Stats are host values only: no span adds
a device sync.
"""
from __future__ import annotations

import dataclasses
import time
from collections import deque
from typing import Any, List, Optional, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

from ..core.precision import policy_tier
from ..launch.mesh import make_tp_mesh
from .api import FinishedRequest, Request, RequestOutput, SamplingParams
from .executor import ModelExecutor
from .prefix_cache import PrefixCache
from .scheduler import Scheduler, SchedulingPolicy, SlotState

__all__ = ["ServingEngine", "Request", "RequestOutput", "FinishedRequest",
           "SamplingParams"]


@dataclasses.dataclass
class _InFlight:
    """One dispatched tick whose sampled tokens are not yet host-synced."""
    tick: int
    dec: List                    # [(row, SlotState, token_index)]
    dec_toks: Any                # device [max_slots] or None
    pf: List                     # [(row, SlotState, token_index)]
    pf_toks: Any                 # device [len(pf)] or None


class ServingEngine:
    """Slot-based continuous-batching engine over the scheduler/executor
    split.

    Usage:
        eng = ServingEngine(cfg, params, policy=pol, max_slots=4,
                            max_len=256, overlap=True)
        eng.submit(Request(prompt=[1, 2, 3], max_new_tokens=8))
        for out in eng.events():        # RequestOutput per-token stream
            print(out.id, out.new_tokens, out.finished)

        for out in eng.stream(Request(prompt=[1, 2, 3])):   # one request
            ...

        done = eng.run(reqs)            # deprecated completion-only view
    """

    def __init__(self, cfg, params, policy=None, max_slots: int = 4,
                 max_len: int = 256, prefill_chunk: int = 32, seed: int = 0,
                 mesh=None, kv_block_size: Optional[int] = None,
                 kv_blocks: Optional[int] = None, prefix_cache: bool = False,
                 scheduler: Union[str, SchedulingPolicy] = "fifo",
                 overlap: bool = False, tp: Optional[int] = None):
        self.cfg = cfg
        self.params = params
        self.policy = policy
        # ladder tier this engine serves at ('bf16' for native-precision
        # policies, the matmul format for flexpe tiers, None off-ladder):
        # stamped on every RequestOutput and what tier-pinned requests
        # validate against
        self.tier = policy_tier(policy)
        self.max_slots = max_slots
        self.max_len = max_len
        self.prefill_chunk = prefill_chunk
        self.seed = seed
        self.overlap = overlap
        if tp is not None and mesh is not None:
            raise ValueError("pass either tp or mesh, not both")
        # a (1, tp) mesh over exactly tp devices; no tp and no mesh is
        # single-device serving, however many devices the host exposes
        self.mesh = mesh if mesh is not None else make_tp_mesh(tp or 1)
        if kv_blocks is not None and kv_block_size is None:
            raise ValueError("kv_blocks requires kv_block_size (a pool size "
                             "only makes sense for the paged layout)")
        if prefix_cache and kv_block_size is None:
            raise ValueError("prefix_cache requires kv_block_size (prefix "
                             "sharing is a property of the paged layout)")
        self.kv_block_size = kv_block_size

        self.ex = ModelExecutor(cfg, params, policy=policy, mesh=self.mesh,
                                max_slots=max_slots, max_len=max_len,
                                prefill_chunk=prefill_chunk,
                                kv_block_size=kv_block_size,
                                kv_blocks=kv_blocks)
        # prefix caching shares KV across requests at block granularity;
        # SSM/hybrid carry a recurrence that cannot be entered mid-stream,
        # so for those families the flag degrades to a no-op
        prefix = (PrefixCache(kv_block_size)
                  if prefix_cache and self.ex.paged and not self.ex.has_ssm
                  else None)
        self.sched = Scheduler(
            max_slots, max_len, policy=scheduler,
            kv_block_size=kv_block_size if self.ex.paged else None,
            num_blocks=self.ex.num_blocks, paged=self.ex.paged,
            has_ssm=self.ex.has_ssm, prefix_cache=prefix,
            block_shards=self.ex.pool_shards, tier=self.tier)

        # block-table slots per row, all of which a paged decode walks
        self._table_width = (self.ex.cache["block_tables"].shape[1]
                             if self.ex.paged else 0)
        self.tick = 0
        self._inflight: deque = deque()      # dispatched, not yet drained
        self._out_buffer: deque = deque()    # events awaiting a consumer
        # cumulative stats
        self.prompt_tokens = 0
        self.generated_tokens = 0
        self.emitted_tokens = 0              # incl. tokens of live requests
        self.busy_slot_ticks = 0
        self.total_slot_ticks = 0
        self.prefill_tokens_computed = 0
        self.sample_sync_tokens = 0          # tokens whose sync gated dispatch
        self.wasted_decodes = 0              # overlap: post-EOS/abort drains
        self.aborted_requests = 0

    # -- compatibility views -------------------------------------------------

    @property
    def slots(self) -> List[Optional[SlotState]]:
        return self.sched.slots

    @property
    def pending(self) -> List[Request]:
        return self.sched.pending

    @property
    def paged(self) -> bool:
        return self.ex.paged

    @property
    def load(self) -> int:
        """Live requests on this engine: occupied slots + its own pending
        queue. What the router's least-loaded/affinity policies balance."""
        return (sum(s is not None for s in self.sched.slots)
                + len(self.sched.pending))

    def prefix_peek(self, keys) -> int:
        """How many leading chain-keyed prompt blocks this engine's prefix
        cache already holds (0 without a cache). Read-only — the router's
        affinity probe must not perturb LRU order or hit stats."""
        prefix = self.sched._prefix
        return prefix.peek(keys) if prefix is not None else 0

    @property
    def cache(self):
        return self.ex.cache

    # -- request lifecycle ---------------------------------------------------

    def submit(self, request: Request) -> int:
        return self.sched.submit(request, self.tick)

    def abort(self, rid: int) -> bool:
        """Release a queued or in-flight request. Queued requests leave
        the pending queue (their submit bookkeeping dropped); in-flight
        requests free their slot with refcounted block return — any
        still-dispatched device work targeting the slot is discarded at
        drain time. Emits a terminal `finish_reason='aborted'` event
        carrying the tokens drained so far. Returns False when `rid` is
        unknown or already finished."""
        req = self.sched.abort_pending(rid)
        if req is not None:
            self.aborted_requests += 1
            self._out_buffer.append(RequestOutput(
                id=rid, new_tokens=[], tokens=[],
                prompt_len=len(req.prompt), tick=self.tick, finished=True,
                finish_reason="aborted", prompt=req.prompt, tier=self.tier))
            return True
        found = self.sched.find_slot(rid)
        if found is None:
            return False
        b, slot = found
        slot.done = True                 # in-flight drains become discards
        self.sched.release(b, self.ex)
        self.aborted_requests += 1
        # work done before the abort still counts toward throughput:
        # prompt tokens actually prefilled + tokens actually drained (so
        # tok/s and sample_syncs_per_token keep describing one stream)
        self.prompt_tokens += slot.prefill_pos
        self.generated_tokens += len(slot.generated)
        self._out_buffer.append(RequestOutput(
            id=rid, new_tokens=[], tokens=list(slot.generated),
            prompt_len=slot.prompt_len, tick=self.tick, finished=True,
            finish_reason="aborted", prompt=slot.request.prompt,
            admitted_tick=slot.admitted_tick,
            prefix_hit_tokens=slot.prefix_hit, tier=self.tier))
        return True

    def has_work(self) -> bool:
        return (self.sched.has_work() or bool(self._inflight)
                or bool(self._out_buffer))

    def _request_key(self, req: Request):
        if req.seed is not None:
            return jax.random.PRNGKey(req.seed)
        return jax.random.fold_in(jax.random.PRNGKey(self.seed), req.id)

    # -- one engine tick -----------------------------------------------------

    def _prefill_block(self, slot: SlotState):
        """[1, chunk] block holding this slot's next prompt chunk."""
        cfg = self.cfg
        chunk = self.prefill_chunk
        take = min(chunk, slot.prompt_len - slot.prefill_pos)
        part = np.asarray(slot.request.prompt[slot.prefill_pos:
                                              slot.prefill_pos + take])
        if cfg.input_mode == "tokens":
            block = np.zeros((1, chunk), np.int64)
            block[0, :take] = part
            return jnp.asarray(block, jnp.int32), take
        block = np.zeros((1, chunk, cfg.d_model), np.float32)
        block[0, :take] = part
        return jnp.asarray(block, jnp.bfloat16), take

    def _dispatch_tick(self) -> bool:
        """Admit, then enqueue this tick's device work (prefill chunks,
        fused decode+sample, prefill-seed sampling) WITHOUT syncing any
        sampled value. Returns False when there was nothing to dispatch."""
        sched, ex = self.sched, self.ex
        with TraceAnnotation("engine.admit") as span:
            admitted = sched.admit(self.tick, ex)
            for _, slot in admitted:
                slot.key = self._request_key(slot.request)
            span.set_metadata(admitted=len(admitted))

        # plan the whole tick first — prefill chunks and decode rows are
        # known before any dispatch, so block allocation and control-array
        # updates coalesce into one flush
        with TraceAnnotation("engine.plan") as span:
            occupied = [(b, s) for b, s in enumerate(sched.slots)
                        if s is not None and not s.done]
            prefill_plan = []                    # (row, slot, tokens, take)
            for b, slot in occupied:
                if slot.prefilling:
                    tokens, take = self._prefill_block(slot)
                    sched.ensure_blocks(b, slot.cache_len + take, ex)
                    prefill_plan.append((b, slot, tokens, take))
            # decode rows hold a device-seeded token and have host
            # headroom: length finishes are predicted from the SCHEDULED
            # count, so a request never gets more than max_new_tokens
            # samples dispatched even before its latest values drain
            dec = [(b, s) for b, s in occupied
                   if not s.prefilling
                   and 0 < s.scheduled < s.request.max_new_tokens]
            for b, s in dec:
                sched.ensure_blocks(b, s.cache_len + 1, ex)
            span.set_metadata(prefill_rows=len(prefill_plan),
                              decode_rows=len(dec))
        if not prefill_plan and not dec:
            return False
        with TraceAnnotation("engine.flush") as span:
            uploads = ex.h2d_updates
            ex.flush()
            span.set_metadata(uploads=ex.h2d_updates - uploads)

        # 1) chunked prefill, one chunk per prefilling slot (B=1 calls);
        #    the final chunk's last-valid logits seed the first sample
        pf_items, pf_rows, pf_logits = [], [], []
        pf_keys, pf_temps, pf_topks = [], [], []
        for b, slot, tokens, take in prefill_plan:
            with TraceAnnotation("engine.prefill", request_id=slot.request.id,
                                 start=slot.prefill_pos, take=take):
                lg = ex.prefill(b, tokens, take)
                slot.prefill_pos += take
                slot.cache_len += take
                self.prefill_tokens_computed += take
                if not slot.prefilling:
                    pf_items.append((b, slot, slot.scheduled))
                    pf_rows.append(b)
                    pf_logits.append(lg)
                    pf_keys.append(jax.random.fold_in(slot.key,
                                                      slot.scheduled))
                    pf_temps.append(slot.request.sampling.temperature)
                    pf_topks.append(slot.request.sampling.top_k)
                    slot.scheduled += 1
                sched.register_prefix_blocks(b)

        # 2) fused pool decode + sample for device-seeded frontier rows
        dec_items, dec_toks = [], None
        if dec:
            with TraceAnnotation("engine.decode", rows=len(dec)) as span:
                n_valid = np.zeros((self.max_slots,), np.int32)
                keys = [_zero_key()] * self.max_slots
                temps = np.zeros((self.max_slots,), np.float32)
                topks = np.zeros((self.max_slots,), np.int32)
                for b, s in dec:
                    n_valid[b] = 1
                    keys[b] = jax.random.fold_in(s.key, s.scheduled)
                    temps[b] = s.request.sampling.temperature
                    topks[b] = s.request.sampling.top_k
                    dec_items.append((b, s, s.scheduled))
                    s.scheduled += 1
                    s.cache_len += 1
                dec_toks = ex.decode_and_sample(
                    n_valid, jnp.stack(keys), jnp.asarray(temps),
                    jnp.asarray(topks))
                if ex.paged:
                    # the paged decode kernel walks every row's table up
                    # to its valid length (cache_len after this step)
                    bs = self.kv_block_size
                    span.set_metadata(
                        live_blocks=sum(-(-s.cache_len // bs)
                                        for s in sched.slots
                                        if s is not None),
                        table_blocks=self.max_slots * self._table_width)

        # 3) sample + device-seed rows that finished prefill this tick
        pf_toks = None
        if pf_items:
            with TraceAnnotation("engine.seed", rows=len(pf_items)):
                pf_toks = ex.seed_tokens(
                    pf_rows, pf_logits, jnp.stack(pf_keys),
                    jnp.asarray(np.asarray(pf_temps, np.float32)),
                    jnp.asarray(np.asarray(pf_topks, np.int32)))

        # length finishes are host-predictable: a slot whose LAST sample
        # was just scheduled releases now (blocks returned, row free for
        # next tick's admission) so overlapped admission timing matches
        # the sync loop exactly; the drain still owns emitting its
        # events. Only EOS — unknowable until the value syncs — lags.
        for b, s, _ in dec_items + pf_items:
            if s.scheduled >= s.request.max_new_tokens and not s.released:
                sched.release(b, self.ex)

        self._inflight.append(_InFlight(self.tick, dec_items, dec_toks,
                                        pf_items, pf_toks))
        self.busy_slot_ticks += len(occupied)
        self.total_slot_ticks += self.max_slots
        self.tick += 1
        return True

    def _drain_one(self, events: List[RequestOutput]):
        """Sync the oldest in-flight tick's sampled tokens and turn them
        into events: per-token deltas, EOS/length finishes (releasing the
        slot), and discards for slots that finished/aborted after the
        dispatch (the overlap loop's bounded overrun)."""
        ent = self._inflight.popleft()
        # the sync "gates" the pipeline when no younger tick is already
        # dispatched — true on every sync-mode tick, false in the
        # overlapped steady state (this is what sample_syncs_per_token
        # measures; wall clock would hide it on fast hosts)
        gating = not self._inflight
        with TraceAnnotation("engine.sync"):
            dec = (np.asarray(ent.dec_toks) if ent.dec_toks is not None
                   else None)
            pf = np.asarray(ent.pf_toks) if ent.pf_toks is not None else None
        items = [(b, slot, idx, dec[b]) for b, slot, idx in ent.dec]
        items += [(b, slot, idx, pf[i])
                  for i, (b, slot, idx) in enumerate(ent.pf)]
        now = time.monotonic()
        emitted = 0
        for b, slot, idx, val in sorted(items, key=lambda it: it[0]):
            if slot.done:
                # dispatched before the host saw this slot finish/abort
                self.wasted_decodes += 1
                continue
            assert idx == len(slot.generated), "drain out of order"
            assert slot.released or self.sched.slots[b] is slot, (
                "slot recycled mid-flight")
            t = int(val)
            slot.generated.append(t)
            emitted += 1
            self.emitted_tokens += 1
            req = slot.request
            if slot.first_token_time is None:
                slot.first_token_time = now
                # a marker span: its stats are the request's two phases
                with TraceAnnotation(
                        "engine.first_token", request_id=req.id,
                        queued_ms=1e3 * (slot.admit_time - slot.submit_time),
                        prefill_ms=1e3 * (now - slot.admit_time)):
                    pass
            out = RequestOutput(
                id=req.id, new_tokens=[t], tokens=list(slot.generated),
                prompt_len=slot.prompt_len, tick=ent.tick, prompt=req.prompt,
                admitted_tick=slot.admitted_tick,
                prefix_hit_tokens=slot.prefix_hit, tier=self.tier)
            hit_eos = req.eos_id is not None and t == req.eos_id
            if hit_eos or len(slot.generated) >= req.max_new_tokens:
                slot.done = True
                out.finished = True
                out.finish_reason = "eos" if hit_eos else "length"
                out.ttft_s = slot.first_token_time - slot.submit_time
                self.prompt_tokens += slot.prompt_len
                self.generated_tokens += len(slot.generated)
                if not slot.released:       # EOS before the predicted end
                    self.sched.release(b, self.ex)  # refcounted block return
            events.append(out)
        if gating:
            self.sample_sync_tokens += emitted

    def step(self) -> List[RequestOutput]:
        """One engine step: dispatch the next tick's device work, then
        drain sampled tokens — immediately in sync mode, one tick behind
        with `overlap=True`. Returns every event now due: anything a
        consumer left buffered (e.g. an abort's terminal event) plus
        whatever drained this step (with overlap the drains describe the
        PREVIOUS tick). Draining the buffer here keeps the documented
        `while eng.has_work(): eng.step()` loop live-lock-free."""
        with TraceAnnotation("engine.step", tick=self.tick):
            events: List[RequestOutput] = list(self._out_buffer)
            self._out_buffer.clear()
            dispatched = self._dispatch_tick()
            depth = 1 if (self.overlap and dispatched) else 0
            while len(self._inflight) > depth:
                with TraceAnnotation("engine.drain",
                                     tick=self._inflight[0].tick) as span:
                    n = len(events)
                    self._drain_one(events)
                    span.set_metadata(tokens=len(events) - n)
        return events

    # -- output streams ------------------------------------------------------

    def events(self):
        """Generator: run ticks until idle, yielding `RequestOutput`
        events as they drain — one per sampled token plus a terminal
        event per request (streaming consumption)."""
        while self.has_work():
            yield from self.step()

    def stream(self, request: Request):
        """Submit `request` and yield ITS `RequestOutput` events as they
        arrive, ending after its terminal event. Events belonging to
        other in-flight requests are re-buffered for `events()`
        consumers (one partition pass per step, not per event), so
        streams and the global event loop compose."""
        rid = self.submit(request)
        while self.has_work():
            outs = self.step()
            mine = [o for o in outs if o.id == rid]
            self._out_buffer.extend(o for o in outs if o.id != rid)
            for out in mine:
                yield out
                if out.finished:
                    return
            if not mine and not (self.sched.has_work() or self._inflight):
                return      # terminal event consumed elsewhere (e.g. a
                            # concurrent events() drain): nothing left to wait on

    def run(self, requests: Optional[List[Request]] = None
            ) -> List[FinishedRequest]:
        """Deprecated completion-only view: submit `requests` (if given),
        drive to completion, return `FinishedRequest`s sorted by id."""
        for r in requests or ():
            self.submit(r)
        done = [out.to_finished() for out in self.events() if out.finished]
        return sorted(done, key=lambda f: f.id)

    # -- introspection -------------------------------------------------------

    def check_invariants(self):
        """Allocator/accounting consistency (see Scheduler
        .check_invariants) — valid after every tick, including overlapped
        ticks with sample drains still in flight."""
        self.sched.check_invariants()

    def stats(self) -> dict:
        util = self.busy_slot_ticks / max(self.total_slot_ticks, 1)
        st = {"ticks": self.tick,
              "prompt_tokens": self.prompt_tokens,
              "generated_tokens": self.generated_tokens,
              "prefill_tokens_computed": self.prefill_tokens_computed,
              "slot_utilization": util,
              "h2d_updates": self.ex.h2d_updates,
              "overlap": self.overlap,
              "sample_syncs_per_token": (self.sample_sync_tokens
                                         / max(self.emitted_tokens, 1)),
              "wasted_decodes": self.wasted_decodes,
              "aborted_requests": self.aborted_requests,
              # spec counters are part of the uniform stats schema so
              # fleet aggregation reads one shape whether a member is a
              # plain engine or a SpecDecodeCoordinator (which overrides
              # them with real values)
              "spec_proposed": 0,
              "spec_accepted": 0,
              "spec_acceptance_rate": 0.0,
              "spec_verify_steps": 0,
              "spec_rolled_back": 0}
        st.update(self.sched.stats())
        if self.ex.paged:
            st["cow_copies"] = self.ex.cow_copies
        return st


_ZERO_KEY = None


def _zero_key():
    """Placeholder PRNG key for non-decoding rows (lazily built so module
    import stays device-free)."""
    global _ZERO_KEY
    if _ZERO_KEY is None:
        _ZERO_KEY = jax.random.PRNGKey(0)
    return _ZERO_KEY
