"""Data-parallel multi-engine router: one admission front over N engines.

`EngineRouter` owns a single admission queue and fans requests out across
N independent `ServingEngine` replicas — each with its own slot pool,
paged block pool, and prefix cache, and each optionally tensor-parallel
sharded (`tp`). It is the data-parallel layer of the serving stack: where
`--tp` splits one model instance across devices, `--engines` multiplies
whole instances and routes traffic between them, which is the scale-out
story the ROADMAP's millions-of-users north star needs and the placement
half of POLARON's precision/placement-as-runtime-knobs framing.

Routing policy is pluggable:

  * `round-robin` — classic data-parallel dispatch, next replica in the
    candidate class per request. Always dispatches immediately; the
    fleet load-balances statistically.
  * `least-loaded` — fewest live requests (occupied slots + replica
    queue), ties to the lowest index. Holds requests at the router while
    every candidate replica is saturated, so the first freed slot
    anywhere takes the head of the queue.
  * `prefix-affinity` — requests whose prompt shares a chain-hashed
    block prefix (the SAME chain hash `serving/prefix_cache.py` keys
    physical blocks by) steer to the replica that already holds those
    blocks: first by asking each replica's prefix cache (a read-only
    `peek`), then by a router-side sticky map for prefixes routed but
    not yet cached. A stickiness bound keeps one hot prefix from
    starving the fleet: when the affinity replica's load runs more than
    `stickiness` requests ahead of the least-loaded one, the request
    spills to least-loaded instead (and re-sticks the prefix there).
  * `tiered` — least-loaded placement within the precision-tier class
    picked per request (requires `tiers`; see below).

**Precision tiers** (`tiers=['fxp4', 'fxp8']`): the fleet turns
heterogeneous — replica i runs the `PrecisionPolicy` of ladder tier
`tiers[i]` (`core.tiers.TIERS`), all serving from one shared
`TieredWeights` bank (quantize-once codes per tier + one float source).
A router-side `TierPolicy` picks each request's tier BEFORE the routing
policy picks a replica inside that tier class: an explicit
`Request.tier` pin is honored unconditionally, `priority > 0` takes the
fleet's best (most accurate) tier, `priority < 0` the cheapest, and
`priority == 0` walks best -> cheapest taking the first tier whose
queue pressure — (class live load + 1) / class slot capacity, live load
counting replica queues — clears `tier_threshold` (default 1.0: degrade
exactly when the better tier would have to queue the request). Every
routing policy composes: affinity probes and sticky entries are scoped
to the candidate tier class, so a prefix sticks per tier, never across
numerics boundaries.

Placement within a tier is a pure performance transform: per-request
outputs are batch-composition independent under composition-independent
numerics (bf16 — see PR 8's caveat on flexpe's per-tensor dynamic
activation scales) and all replicas share one `seed`, so a request's
tokens are bit-identical to running it alone on a single engine at the
same tier no matter which replica serves it. Placement across tiers is
deliberately NOT numerics-preserving — that is the whole accuracy /
throughput trade — which is why a tier pin is a hard contract: the tier
a request lands on is stamped on every `RequestOutput`, and a pinned
request is never degraded. `tests/test_tiered_routing.py` and
`benchmarks/ci_smoke.py --tiers` gate exactly that.

**Speculative decoding** (`spec_decode='fxp4:fxp8'`): replicas serving
the verify tier are constructed as `SpecDecodeCoordinator`s — a hidden
cheap-tier draft engine proposes k tokens per round and the verify-tier
engine scores them in one chunked dispatch, emitting streams
token-identical to the verify tier alone (see `serving/speculative.py`).
Composition with `tiers` is by class: only the verify-tier class turns
speculative (its draft codes ride the same `TieredWeights` bank); every
other tier class keeps plain replicas. On an untiered fleet every
replica becomes a coordinator. Acceptance is defined against the
verifier's argmax, so a speculative fleet is greedy-only: `submit`
rejects sampled requests up front.

The router exposes the same streaming surface as a single engine —
`submit() / events() / stream() / abort()` — with one merged event loop
driving every replica's tick, and `stats()` aggregates fleet totals plus
`per_engine` and per-tier breakdowns.
"""
from __future__ import annotations

import hashlib
from collections import deque
from typing import Dict, List, Optional, Sequence, Union

import numpy as np

from ..core.precision import tier_policy as make_tier_policy
from ..core.qtensor import TieredWeights
from ..core.tiers import tier_index
from ..launch.mesh import make_tp_mesh
from .api import FinishedRequest, Request, RequestOutput
from .engine import ServingEngine
from .prefix_cache import PrefixCache
from .speculative import SpecDecodeCoordinator

__all__ = ["EngineRouter", "RoutingPolicy", "ROUTING_POLICIES",
           "TierPolicy"]


class RoutingPolicy:
    """Pluggable placement policy: picks the replica index for the next
    request from `candidates` (the replica indices of the request's tier
    class; the whole fleet when untiered). `holds_when_saturated` lets a
    policy keep the head of the router queue un-dispatched while every
    candidate is at capacity (occupied slots + replica queue >=
    max_slots), so the first freed slot in the class serves it."""

    name = "round-robin"
    holds_when_saturated = False

    def pick(self, router: "EngineRouter", request: Request,
             loads: List[int], candidates: Sequence[int]) -> int:
        raise NotImplementedError


class RoundRobin(RoutingPolicy):
    """Next candidate replica per request — the classic data-parallel
    front, rotating independently per candidate class. Dispatches
    unconditionally; replicas queue internally."""

    name = "round-robin"

    def __init__(self):
        self._next: Dict[tuple, int] = {}

    def pick(self, router, request, loads, candidates):
        key = tuple(candidates)
        i = self._next.get(key, 0)
        self._next[key] = (i + 1) % len(candidates)
        return candidates[i]


class LeastLoaded(RoutingPolicy):
    """Fewest live requests wins, ties to the lowest replica index.
    Holds at the router when the whole candidate class is saturated."""

    name = "least-loaded"
    holds_when_saturated = True

    def pick(self, router, request, loads, candidates):
        return min(candidates, key=lambda i: (loads[i], i))


class Tiered(LeastLoaded):
    """The canonical heterogeneous-fleet policy: `TierPolicy` picks the
    tier class, then least-loaded picks the replica inside it. Requires
    the router to be constructed with `tiers`."""

    name = "tiered"


class PrefixAffinity(RoutingPolicy):
    """Steer shared-prefix requests to the candidate replica already
    holding their chain-hashed prompt blocks; fall back to least-loaded,
    bounded by `stickiness` (max load lead the affinity replica may have
    before the request spills — and re-sticks its prefix — elsewhere).
    Probes and sticky entries are scoped to the candidate class, so
    affinity only ever sticks within a tier."""

    name = "prefix-affinity"
    holds_when_saturated = True

    def __init__(self, stickiness: int = 4):
        if stickiness < 0:
            raise ValueError("stickiness must be >= 0")
        self.stickiness = stickiness
        self.affinity_hits = 0       # dispatches that followed affinity
        self.affinity_spills = 0     # affinity overridden by the bound

    def pick(self, router, request, loads, candidates):
        lo = min(candidates, key=lambda i: (loads[i], i))
        keys = router._chain_keys(request.prompt)
        # sticky entries key on (prefix, candidate class): one prefix may
        # legitimately be hot on a replica of EVERY tier it is pinned to
        skey = (keys[0], tuple(candidates)) if keys else None
        # deepest cached match wins (ties to the lowest index); the probe
        # is PrefixCache.peek — read-only, no LRU/stat perturbation
        aff, depth = None, 0
        for i in candidates:
            d = router.engines[i].prefix_peek(keys)
            if d > depth:
                aff, depth = i, d
        if aff is None and skey is not None:
            # routed-but-not-yet-cached prefixes (prefill still running,
            # or contiguous replicas with no prefix cache at all)
            aff = router._sticky.get(skey)
        if aff is not None:
            if loads[aff] - loads[lo] <= self.stickiness:
                self.affinity_hits += 1
                target = aff
            else:
                self.affinity_spills += 1
                target = lo
        else:
            target = lo
        if skey is not None:
            router._sticky[skey] = target
        return target


ROUTING_POLICIES = {
    "round-robin": RoundRobin,
    "least-loaded": LeastLoaded,
    "prefix-affinity": PrefixAffinity,
    "tiered": Tiered,
}


def make_routing_policy(policy: Union[str, RoutingPolicy],
                        stickiness: Optional[int] = None) -> RoutingPolicy:
    if isinstance(policy, RoutingPolicy):
        return policy
    if policy not in ROUTING_POLICIES:
        raise ValueError(f"unknown routing policy {policy!r}; choose from "
                         f"{sorted(ROUTING_POLICIES)}")
    if policy == "prefix-affinity" and stickiness is not None:
        return PrefixAffinity(stickiness=stickiness)
    return ROUTING_POLICIES[policy]()


class TierPolicy:
    """Per-request precision-tier selection for a heterogeneous fleet.

    `pick()` is pure (safe to re-evaluate while the head of the queue is
    held); the router calls `note()` once per ACTUAL placement so the
    pinned/degraded counters never double-count a hold-retry.

      * explicit `Request.tier` — honored unconditionally (the router
        validated fleet support at submit).
      * `priority > 0` — the best (most accurate) served tier, always.
      * `priority < 0` — the cheapest tier, always.
      * `priority == 0` — best -> cheapest walk, first tier whose
        pressure clears `threshold`; cheapest if nothing does. Pressure
        is (class live load + 1) / class slot capacity — "+1" counts the
        request being placed, live load counts replica queues — so
        threshold 1.0 degrades exactly when the tier would queue it.
    """

    def __init__(self, ladder: Sequence[str], threshold: float = 1.0):
        if not ladder:
            raise ValueError("TierPolicy needs at least one served tier")
        if threshold <= 0:
            raise ValueError("tier_threshold must be > 0")
        # cheap -> best, the global ladder order
        self.ladder = sorted(dict.fromkeys(ladder), key=tier_index)
        self.threshold = threshold
        self.pinned = 0          # placements that honored an explicit pin
        self.degraded = 0        # priority-0 placements pushed off best
        self.placed = {t: 0 for t in self.ladder}

    @property
    def best(self) -> str:
        return self.ladder[-1]

    @property
    def cheapest(self) -> str:
        return self.ladder[0]

    def pick(self, request: Request, pressures: Dict[str, float]) -> str:
        if request.tier is not None:
            return request.tier
        if request.priority > 0:
            return self.best
        if request.priority < 0:
            return self.cheapest
        for t in reversed(self.ladder):
            if pressures[t] <= self.threshold:
                return t
        return self.cheapest

    def note(self, request: Request, tier: str):
        """Record an actual placement (called once per dispatch)."""
        self.placed[tier] += 1
        if request.tier is not None:
            self.pinned += 1
        elif request.priority == 0 and tier != self.best:
            self.degraded += 1


class EngineRouter:
    """Single admission queue fanning out over N `ServingEngine` replicas.

    Usage mirrors a single engine:

        router = EngineRouter(cfg, params, engines=2,
                              routing="prefix-affinity", max_slots=4,
                              max_len=256, kv_block_size=8,
                              prefix_cache=True)
        router.submit(Request(prompt=[1, 2, 3], max_new_tokens=8))
        for out in router.events():
            ...

    Heterogeneous precision fleet: pass `tiers` (one ladder name per
    replica — it overrides `engines`) and the router derives each
    replica's `PrecisionPolicy` via `core.precision.tier_policy` and its
    weights from a shared `TieredWeights` (built from `params` when a
    plain float tree is passed; `backend` picks the kernel backend).
    `routing="tiered"` is the canonical pairing; any policy composes.

    Speculative fleet: pass `spec_decode="draft:verify"` (+ `spec_k`)
    and verify-tier replicas become `SpecDecodeCoordinator`s sharing the
    same bank (untiered fleets turn every replica speculative). Greedy
    requests only; streams stay token-identical to the verify tier.

    Engine-construction keywords (`max_slots`, `max_len`,
    `prefill_chunk`, `kv_block_size`, `kv_blocks`, `prefix_cache`,
    `scheduler`, `overlap`, `tp`, ...) apply to EVERY replica (`policy`
    too, unless `tiers` derives per-replica policies); `seed` is shared
    deliberately — per-request RNG derives from (seed, request id), so
    placement can never change a request's tokens. Untiered replicas
    share one `params` tree (and, through the executor's compiled-step
    cache, one set of jitted steps — same-TIER replicas still share
    compilations in a heterogeneous fleet); each replica owns its cache
    pool.
    """

    def __init__(self, cfg, params, *, engines: int = 2,
                 routing: Union[str, RoutingPolicy] = "least-loaded",
                 stickiness: Optional[int] = None, max_slots: int = 4,
                 kv_block_size: Optional[int] = None,
                 tiers: Optional[Sequence[str]] = None,
                 tier_threshold: float = 1.0, backend: str = "reference",
                 spec_decode: Optional[str] = None, spec_k: int = 4,
                 **engine_kw):
        self.routing = make_routing_policy(routing, stickiness=stickiness)
        self.spec_decode: Optional[tuple] = None
        self.spec_k = spec_k
        if spec_decode is not None:
            draft, _, verify = spec_decode.partition(":")
            if not draft or not verify:
                raise ValueError(
                    f"spec_decode must be 'draft:verify' (ladder tier "
                    f"names), got {spec_decode!r}")
            if tier_index(draft) >= tier_index(verify):
                raise ValueError(
                    f"spec_decode draft tier {draft!r} must sit below the "
                    f"verify tier {verify!r} on the ladder — a draft at "
                    "or above the verifier's precision has nothing to "
                    "accelerate")
            self.spec_decode = (draft, verify)

        # replica i gets its own group of tp devices (make_tp_mesh's
        # replica placement); without it every replica would build its
        # mesh over the host's first devices
        tp = engine_kw.pop("tp", None) or 1

        def placed(i):
            return dict(engine_kw, mesh=make_tp_mesh(tp, replica=i))

        def spec_coordinator(weights, verify_t, i):
            d, _ = self.spec_decode
            return SpecDecodeCoordinator(
                cfg, weights.for_tier(d), weights.for_tier(verify_t),
                draft_policy=make_tier_policy(d, backend=backend),
                verify_policy=make_tier_policy(verify_t, backend=backend),
                k=spec_k, max_slots=max_slots,
                kv_block_size=kv_block_size, **placed(i))

        if tiers is not None:
            if "policy" in engine_kw:
                raise ValueError(
                    "pass either tiers (per-replica policies derive from "
                    "the ladder) or policy, not both")
            if not tiers:
                raise ValueError("tiers must name at least one replica")
            for t in tiers:
                tier_index(t)                # unknown tier -> ValueError
            engines = len(tiers)
            bank_tiers = list(tiers) + (list(self.spec_decode)
                                        if self.spec_decode else [])
            weights = (params if isinstance(params, TieredWeights)
                       else TieredWeights(params, bank_tiers))
            for t in bank_tiers:
                if t not in weights:
                    raise ValueError(
                        f"tier {t!r} has no bank in the supplied "
                        f"TieredWeights (has {list(weights.tier_names)})")
            if self.spec_decode and self.spec_decode[1] not in tiers:
                raise ValueError(
                    f"spec_decode verify tier {self.spec_decode[1]!r} has "
                    f"no replica in this fleet (tiers={list(tiers)}); the "
                    "speculative pair accelerates the verify-tier class")
            self.tiered_weights: Optional[TieredWeights] = weights
            self.engines = [
                spec_coordinator(weights, t, i)
                if self.spec_decode and t == self.spec_decode[1]
                else ServingEngine(
                    cfg, weights.for_tier(t),
                    policy=make_tier_policy(t, backend=backend),
                    max_slots=max_slots,
                    kv_block_size=kv_block_size, **placed(i))
                for i, t in enumerate(tiers)]
        else:
            if isinstance(self.routing, Tiered):
                raise ValueError(
                    "routing='tiered' requires a heterogeneous fleet: "
                    "pass tiers=['fxp4', 'fxp8', ...]")
            if engines < 1:
                raise ValueError("engines must be >= 1")
            if self.spec_decode is not None:
                if "policy" in engine_kw:
                    raise ValueError(
                        "pass either spec_decode (per-side policies "
                        "derive from the tier pair) or policy, not both")
                weights = (params if isinstance(params, TieredWeights)
                           else TieredWeights(params, self.spec_decode))
                for t in self.spec_decode:
                    if t not in weights:
                        raise ValueError(
                            f"tier {t!r} has no bank in the supplied "
                            f"TieredWeights (has "
                            f"{list(weights.tier_names)})")
                self.tiered_weights = weights
                self.engines = [
                    spec_coordinator(weights, self.spec_decode[1], i)
                    for i in range(engines)]
            else:
                self.tiered_weights = None
                self.engines = [
                    ServingEngine(cfg, params, max_slots=max_slots,
                                  kv_block_size=kv_block_size, **placed(i))
                    for i in range(engines)]
        self.max_slots = max_slots
        # tier class map: ladder tier -> replica indices serving it (all
        # replicas of an untiered homogeneous fleet still land here via
        # their policy-derived engine.tier, so explicit pins route even
        # without the tiers= ctor path)
        self._tier_members: Dict[str, List[int]] = {}
        for i, eng in enumerate(self.engines):
            if eng.tier is not None:
                self._tier_members.setdefault(eng.tier, []).append(i)
        self.tier_policy = (TierPolicy(list(self._tier_members),
                                       threshold=tier_threshold)
                            if tiers is not None else None)
        # affinity keys reuse the replicas' chain hash exactly when the
        # pool is paged (so peek hits real cache entries); contiguous
        # replicas have no block size, so the sticky map keys on a fixed
        # granularity instead
        self._keyer = PrefixCache(kv_block_size or 16)
        self._sticky: Dict[tuple, int] = {}
        self.pending: deque = deque()        # the single admission queue
        self._placement: Dict[int, int] = {}  # live rid -> replica index
        self._active_ids: set = set()        # router queue + placed
        self._next_id = 0
        self._out_buffer: deque = deque()
        self.tick = 0
        self.dispatched = [0] * len(self.engines)  # per-replica placements
        self.aborted_requests = 0

    # -- affinity keying -----------------------------------------------------

    def _chain_keys(self, prompt) -> List[str]:
        """Chain keys of the prompt's full blocks (the prefix-cache hash);
        a prompt shorter than one block keys on its whole content so
        identical short prompts still stick together."""
        keys = self._keyer.block_keys(prompt)
        if keys:
            return keys
        arr = np.asarray(prompt)
        if arr.dtype.kind in "iu":
            arr = arr.astype(np.int64, copy=False)
        return [hashlib.sha1(arr.tobytes()).hexdigest()]

    # -- tier accounting -----------------------------------------------------

    @property
    def served_tiers(self) -> List[str]:
        """Ladder tiers this fleet serves, cheap -> best."""
        return sorted(self._tier_members, key=tier_index)

    def tier_loads(self) -> Dict[str, dict]:
        """Per-tier-class live load, slot capacity, and admission
        pressure — what `TierPolicy` degrades on."""
        out = {}
        for t, members in self._tier_members.items():
            load = sum(self.engines[i].load for i in members)
            cap = self.max_slots * len(members)
            out[t] = {"load": load, "capacity": cap,
                      "pressure": (load + 1) / cap}
        return out

    def _candidates(self, request: Request):
        """(tier, replica indices) the routing policy may place `request`
        on. Tier selection re-evaluates queue pressure on every call, so
        a held head-of-queue request re-picks as the fleet drains."""
        if self.tier_policy is not None:
            pressures = {t: v["pressure"] for t, v in self.tier_loads().items()}
            tier = self.tier_policy.pick(request, pressures)
            return tier, self._tier_members[tier]
        if request.tier is not None:
            # homogeneous fleet: the pin was validated at submit, so the
            # class exists — it is just every replica
            return request.tier, self._tier_members[request.tier]
        return None, list(range(len(self.engines)))

    # -- request lifecycle ---------------------------------------------------

    def submit(self, request: Request) -> int:
        """Validate against the replica geometry (identical across the
        fleet) and the fleet's served tiers, assign a router-unique id,
        and queue. EVERY check runs before any state mutates — a
        rejected request leaks nothing into the queue, the id set, or
        any replica. Duplicate ids are rejected across the WHOLE fleet —
        two live requests with one id would collide in the merged event
        stream (and share an RNG stream) regardless of which replicas
        they landed on."""
        if self.spec_decode is not None:
            s = request.sampling
            if s.temperature > 0.0 or s.top_k > 0:
                raise ValueError(
                    "a spec_decode fleet serves greedy requests only "
                    "(temperature<=0, top_k==0): speculative acceptance "
                    "is defined against the verifier's argmax, and tier "
                    "selection must never decide whether a request may "
                    "sample")
        self.engines[0].sched.validate(request, check_tier=False)
        if request.tier is not None:
            tier_index(request.tier)         # unknown name -> ValueError
            if request.tier not in self._tier_members:
                raise ValueError(
                    f"request pinned to tier {request.tier!r} but this "
                    f"fleet serves {self.served_tiers}; add a replica at "
                    "that tier or drop the pin")
        if request.id is not None and request.id in self._active_ids:
            raise ValueError(
                f"request id {request.id} is already pending or in flight "
                "on this router; ids must be unique among live requests")
        if request.id is None:
            request.id = self._next_id
        self._next_id = max(self._next_id, request.id) + 1
        self._active_ids.add(request.id)
        self.pending.append(request)
        return request.id

    def abort(self, rid: int) -> bool:
        """Abort wherever the request lives: still queued at the router
        (emits the terminal event directly) or dispatched to a replica
        (delegates — the replica's terminal event surfaces through the
        merged loop). Returns False for unknown/finished ids."""
        for i, req in enumerate(self.pending):
            if req.id == rid:
                del self.pending[i]
                self._active_ids.discard(rid)
                self.aborted_requests += 1
                self._out_buffer.append(RequestOutput(
                    id=rid, new_tokens=[], tokens=[],
                    prompt_len=len(req.prompt), tick=self.tick,
                    finished=True, finish_reason="aborted",
                    prompt=req.prompt, tier=req.tier))
                return True
        eng_i = self._placement.get(rid)
        if eng_i is None:
            return False
        if self.engines[eng_i].abort(rid):
            # the replica counts this abort in its own stats (summed by
            # `stats()`), so the router-level counter must not also
            self._placement.pop(rid, None)
            self._active_ids.discard(rid)
            return True
        return False

    def has_work(self) -> bool:
        return (bool(self.pending) or bool(self._out_buffer)
                or any(e.has_work() for e in self.engines))

    # -- the merged tick loop ------------------------------------------------

    def _dispatch(self):
        """Drain the admission queue through tier selection + the routing
        policy. FIFO and no-skip — the queue's head is placed (or held)
        before anything behind it, so router-level ordering matches a
        single engine's even when a later request's tier class has idle
        slots (head-of-line tier fairness is the same trade the paged
        pool's no-skip admission already makes)."""
        while self.pending:
            req = self.pending[0]
            tier, candidates = self._candidates(req)
            loads = [e.load for e in self.engines]
            if (self.routing.holds_when_saturated
                    and min(loads[i] for i in candidates) >= self.max_slots):
                break        # candidate class saturated: hold at the router
            self.pending.popleft()
            target = self.routing.pick(self, req, loads, candidates)
            assert target in candidates, (
                f"routing policy {self.routing.name} left the tier class: "
                f"{target} not in {candidates}")
            if self.tier_policy is not None:
                self.tier_policy.note(req, tier)
            self.engines[target].submit(req)
            self._placement[req.id] = target
            self.dispatched[target] += 1

    def step(self) -> List[RequestOutput]:
        """One router tick: route queued requests, then drive every
        replica's engine tick, returning the merged event stream (plus
        anything buffered, e.g. a router-level abort's terminal event)."""
        events: List[RequestOutput] = list(self._out_buffer)
        self._out_buffer.clear()
        self._dispatch()
        for eng in self.engines:
            if eng.has_work():
                events.extend(eng.step())
        for out in events:
            if out.finished:
                self._placement.pop(out.id, None)
                self._active_ids.discard(out.id)
        self.tick += 1
        return events

    # -- output streams (same shape as ServingEngine's) ----------------------

    def events(self):
        """Merged generator over the fleet: run router ticks until idle,
        yielding every replica's `RequestOutput` events as they drain."""
        while self.has_work():
            yield from self.step()

    def stream(self, request: Request):
        """Submit `request` and yield ITS events; other requests' events
        re-buffer for `events()` consumers, exactly like the
        single-engine `stream()`."""
        rid = self.submit(request)
        while self.has_work():
            outs = self.step()
            mine = [o for o in outs if o.id == rid]
            self._out_buffer.extend(o for o in outs if o.id != rid)
            for out in mine:
                yield out
                if out.finished:
                    return
            if not mine and not (self.pending
                                 or any(e.has_work() for e in self.engines)):
                return

    def run(self, requests: Optional[List[Request]] = None
            ) -> List[FinishedRequest]:
        """Completion-only view, mirroring `ServingEngine.run()`."""
        for r in requests or ():
            self.submit(r)
        done = [out.to_finished() for out in self.events() if out.finished]
        return sorted(done, key=lambda f: f.id)

    # -- introspection -------------------------------------------------------

    def check_invariants(self):
        """Fleet-wide consistency: every replica's block ledger audits
        clean, the router's id bookkeeping matches what it actually
        holds (queued ids + placed ids == active ids, no placement entry
        without a live id), and tier placement never broke a pin — every
        live tier-pinned request sits on (or is queued for) a replica of
        exactly its tier."""
        for eng in self.engines:
            eng.check_invariants()
        queued = {r.id for r in self.pending}
        assert queued | set(self._placement) == self._active_ids, (
            f"router id drift: queued {sorted(queued)} + placed "
            f"{sorted(self._placement)} != active "
            f"{sorted(self._active_ids)}")
        assert not (queued & set(self._placement)), (
            "a request is both queued at the router and placed on a "
            f"replica: {sorted(queued & set(self._placement))}")
        for rid, i in self._placement.items():
            assert 0 <= i < len(self.engines), (rid, i)
        # a pin is a hard contract: the serving replica's tier must match
        for eng in self.engines:
            for holder in list(eng.sched.pending) + [
                    s.request for s in eng.sched.slots if s is not None]:
                assert holder.tier is None or holder.tier == eng.tier, (
                    f"tier pin broken: request {holder.id} pinned to "
                    f"{holder.tier!r} is live on a {eng.tier!r} replica")
        if self.tier_policy is not None:
            assert sum(self.tier_policy.placed.values()) == sum(
                self.dispatched), "tier placement counter drift"

    def stats(self) -> dict:
        """Fleet totals plus `per_engine` and per-tier breakdowns.
        Aggregates sum the token/tick counters; `slot_utilization` is
        the fleet mean weighted by each replica's slot-ticks;
        `prefix_hit_rate` is prompt tokens served from a replica's
        prefix cache over prompt tokens it processed."""
        per = [e.stats() for e in self.engines]
        busy = sum(e.busy_slot_ticks for e in self.engines)
        total = sum(e.total_slot_ticks for e in self.engines)
        st = {
            "engines": len(self.engines),
            "routing_policy": self.routing.name,
            "ticks": self.tick,
            "pending_requests": len(self.pending),
            "dispatched": list(self.dispatched),
            "aborted_requests": (self.aborted_requests
                                 + sum(s["aborted_requests"] for s in per)),
            "prompt_tokens": sum(s["prompt_tokens"] for s in per),
            "generated_tokens": sum(s["generated_tokens"] for s in per),
            "prefill_tokens_computed": sum(s["prefill_tokens_computed"]
                                           for s in per),
            "prefix_tokens_reused": sum(s["prefix_tokens_reused"]
                                        for s in per),
            "slot_utilization": busy / max(total, 1),
        }
        if isinstance(self.routing, PrefixAffinity):
            routed = self.routing.affinity_hits + self.routing.affinity_spills
            st["affinity_hits"] = self.routing.affinity_hits
            st["affinity_spills"] = self.routing.affinity_spills
            st["affinity_hit_rate"] = (self.routing.affinity_hits
                                       / max(sum(self.dispatched), 1))
            st["affinity_spill_rate"] = (self.routing.affinity_spills
                                         / max(routed, 1))
        st["tiers"] = [e.tier for e in self.engines]
        if self.spec_decode is not None:
            proposed = sum(s.get("spec_proposed", 0) for s in per)
            accepted = sum(s.get("spec_accepted", 0) for s in per)
            st["spec_decode"] = ":".join(self.spec_decode)
            st["spec_k"] = self.spec_k
            st["spec_proposed"] = proposed
            st["spec_accepted"] = accepted
            st["spec_acceptance_rate"] = accepted / max(proposed, 1)
            st["spec_verify_steps"] = sum(s.get("spec_verify_steps", 0)
                                          for s in per)
            st["spec_rolled_back"] = sum(s.get("spec_rolled_back", 0)
                                         for s in per)
        if self.tier_policy is not None:
            st["tier_threshold"] = self.tier_policy.threshold
            st["tier_pinned"] = self.tier_policy.pinned
            st["tier_degraded"] = self.tier_policy.degraded
            st["tier_placed"] = dict(self.tier_policy.placed)
            st["tier_loads"] = self.tier_loads()
        st["per_engine"] = [{
            "tier": self.engines[i].tier,
            "queue_depth": s["pending_requests"],
            "slot_utilization": s["slot_utilization"],
            "prompt_tokens": s["prompt_tokens"],
            "generated_tokens": s["generated_tokens"],
            "prefill_tokens_computed": s["prefill_tokens_computed"],
            "prefix_hit_rate": (s["prefix_tokens_reused"]
                                / max(s["prompt_tokens"], 1)),
            "spec_acceptance_rate": s.get("spec_acceptance_rate", 0.0),
            "dispatched": self.dispatched[i],
        } for i, s in enumerate(per)]
        return st
