"""Data-parallel EngineRouter tests.

The headline invariant: routing is placement, never numerics — a request
served through the router (any policy, any replica, any co-tenants) is
bit-identical to the same request on a single engine, for every cache
family and KV layout including prefix-cache/CoW. These tests run float
params with no quantization policy, where per-request outputs are
batch-composition independent (the engine invariant `test_serving.py`
pins per family); flexpe's per-tensor dynamic activation scales are the
documented exception and are gated separately under identical placement.

Also covered: the failure paths (abort queued-at-router vs in-flight on
a replica, duplicate-submit rejection across replicas, validation) and a
per-tick `check_invariants()` sweep over every replica's block ledger.
"""
import jax
import jax.numpy as jnp
import pytest

from repro.configs import get_config
from repro.models import model as M
from repro.serving import (EngineRouter, Request, SamplingParams,
                           ServingEngine)
from repro.serving.router import PrefixAffinity, make_routing_policy

KEY = jax.random.PRNGKey(0)
ARCHS = ["qwen2_5_14b", "mamba2_370m", "zamba2_1p2b", "deepseek_moe_16b"]

_PARAMS = {}


def _setup(arch):
    if arch not in _PARAMS:
        cfg = get_config(arch).reduced()
        _PARAMS[arch] = (cfg, M.init_params(cfg, KEY, dtype=jnp.float32))
    return _PARAMS[arch]


def _prompt(i, plen, cfg, shared=0):
    """Unique tail per request, optionally behind a shared system prefix
    (the prefix-cache / affinity workload)."""
    key = jax.random.fold_in(jax.random.PRNGKey(1), i)
    if cfg.input_mode == "tokens":
        tail = jax.random.randint(key, (plen,), 0, cfg.vocab)
        if not shared:
            return tail
        sys_p = jax.random.randint(jax.random.PRNGKey(9), (shared,), 0,
                                   cfg.vocab)
        return jnp.concatenate([sys_p, tail])
    tail = jax.random.normal(key, (plen, cfg.d_model), jnp.bfloat16)
    if not shared:
        return tail
    sys_p = jax.random.normal(jax.random.PRNGKey(9), (shared, cfg.d_model),
                              jnp.bfloat16)
    return jnp.concatenate([sys_p, tail])


def _reqs(cfg, n=5, gen=4, shared=0):
    return [Request(prompt=_prompt(i, 4 + (i % 3) * 3, cfg, shared=shared),
                    max_new_tokens=gen, id=i) for i in range(n)]


_ENGINE_KW = dict(max_slots=2, max_len=32, prefill_chunk=4)


def _layout_kw(layout):
    return ({} if layout == "contiguous"
            else dict(kv_block_size=4, prefix_cache=True))


def _drive(target, reqs, audit=False):
    for r in reqs:
        target.submit(r)
    done = {}
    while target.has_work():
        done.update({o.id: o.tokens for o in target.step() if o.finished})
        if audit:
            target.check_invariants()
    return done


# ---------------------------------------------------------------------------
# token identity: every family x layout x routing policy
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("arch", ARCHS)
@pytest.mark.parametrize("layout", ["contiguous", "paged"])
def test_router_token_identical(arch, layout):
    """Router (2 replicas) == single engine, token for token, under both
    the round-robin and prefix-affinity policies, on a shared-prefix
    workload (paged runs add prefix-cache/CoW sharing per replica)."""
    cfg, params = _setup(arch)
    kw = {**_ENGINE_KW, **_layout_kw(layout)}
    single = _drive(ServingEngine(cfg, params, **kw), _reqs(cfg, shared=8))
    for routing in ("round-robin", "prefix-affinity"):
        router = EngineRouter(cfg, params, engines=2, routing=routing, **kw)
        routed = _drive(router, _reqs(cfg, shared=8), audit=True)
        assert routed == single, (arch, layout, routing)


def test_router_least_loaded_token_identical():
    cfg, params = _setup("qwen2_5_14b")
    kw = {**_ENGINE_KW, **_layout_kw("paged")}
    single = _drive(ServingEngine(cfg, params, **kw), _reqs(cfg, n=6))
    router = EngineRouter(cfg, params, engines=2, routing="least-loaded",
                          **kw)
    assert _drive(router, _reqs(cfg, n=6), audit=True) == single


def test_router_overlap_loop_token_identical():
    """The overlap-dispatch loop composes with routing: replicas running
    overlap=True emit the same tokens as a sync single engine."""
    cfg, params = _setup("qwen2_5_14b")
    kw = {**_ENGINE_KW, **_layout_kw("paged")}
    single = _drive(ServingEngine(cfg, params, **kw), _reqs(cfg))
    router = EngineRouter(cfg, params, engines=2, routing="round-robin",
                          overlap=True, **kw)
    assert _drive(router, _reqs(cfg), audit=True) == single


def test_router_sampled_token_identical():
    """Temperature/top-k sampling: per-request RNG derives from the
    shared seed + request id, so placement can't change sampled draws."""
    cfg, params = _setup("qwen2_5_14b")
    sampling = SamplingParams(temperature=0.8, top_k=5)

    def reqs():
        return [Request(prompt=_prompt(i, 6, cfg), max_new_tokens=4, id=i,
                        sampling=sampling) for i in range(4)]

    single = _drive(ServingEngine(cfg, params, **_ENGINE_KW), reqs())
    router = EngineRouter(cfg, params, engines=2, routing="least-loaded",
                          **_ENGINE_KW)
    assert _drive(router, reqs()) == single


# ---------------------------------------------------------------------------
# routing policy behaviour
# ---------------------------------------------------------------------------

def test_round_robin_uses_every_replica():
    cfg, params = _setup("qwen2_5_14b")
    router = EngineRouter(cfg, params, engines=2, routing="round-robin",
                          **_ENGINE_KW)
    _drive(router, _reqs(cfg, n=6))
    st = router.stats()
    assert st["dispatched"] == [3, 3]
    assert len(st["per_engine"]) == 2
    assert sum(pe["generated_tokens"] for pe in st["per_engine"]) \
        == st["generated_tokens"]


def test_prefix_affinity_concentrates_shared_prefix():
    """With a generous stickiness bound, every request of one shared
    prefix lands on one replica, whose cache serves the repeats —
    round-robin would split the group and cold-prefill the prefix on
    both replicas."""
    cfg, params = _setup("qwen2_5_14b")
    kw = {**_ENGINE_KW, **_layout_kw("paged")}
    router = EngineRouter(cfg, params, engines=2, routing="prefix-affinity",
                          stickiness=8, **kw)
    _drive(router, _reqs(cfg, n=6, shared=8))
    st = router.stats()
    assert sorted(st["dispatched"]) == [0, 6], st["dispatched"]
    assert st["affinity_hits"] >= 5        # first request seeds the sticky map
    assert st["affinity_spills"] == 0
    assert st["prefix_tokens_reused"] > 0
    hot = max(range(2), key=lambda i: st["dispatched"][i])
    assert st["per_engine"][hot]["prefix_hit_rate"] > 0


def test_prefix_affinity_stickiness_bound_spills():
    """stickiness=0: the affinity replica may never run ahead of the
    least-loaded one, so a hot prefix spreads across the fleet instead
    of starving it — and tokens still match the single engine."""
    cfg, params = _setup("qwen2_5_14b")
    kw = {**_ENGINE_KW, **_layout_kw("paged")}
    single = _drive(ServingEngine(cfg, params, **kw), _reqs(cfg, n=6,
                                                           shared=8))
    router = EngineRouter(cfg, params, engines=2, routing="prefix-affinity",
                          stickiness=0, **kw)
    routed = _drive(router, _reqs(cfg, n=6, shared=8))
    st = router.stats()
    assert routed == single
    assert st["affinity_spills"] > 0
    assert all(d > 0 for d in st["dispatched"]), st["dispatched"]


def test_least_loaded_holds_queue_when_saturated():
    """With the whole fleet saturated, least-loaded keeps the overflow in
    the ROUTER's queue (visible in stats) rather than piling it onto one
    replica's internal queue."""
    cfg, params = _setup("qwen2_5_14b")
    router = EngineRouter(cfg, params, engines=2, routing="least-loaded",
                          max_slots=1, max_len=32, prefill_chunk=4)
    for r in _reqs(cfg, n=5):
        router.submit(r)
    router.step()
    st = router.stats()
    assert st["pending_requests"] == 3          # 2 placed, 3 held
    assert all(pe["queue_depth"] == 0 for pe in st["per_engine"])
    done = {}
    while router.has_work():
        done.update({o.id: o.tokens for o in router.step() if o.finished})
        router.check_invariants()
    assert len(done) == 5
    assert router.stats()["pending_requests"] == 0


def test_routing_policy_parse_errors():
    with pytest.raises(ValueError, match="unknown routing policy"):
        make_routing_policy("fastest-first")
    with pytest.raises(ValueError, match="stickiness"):
        PrefixAffinity(stickiness=-1)


# ---------------------------------------------------------------------------
# failure paths: abort, duplicate submit, validation
# ---------------------------------------------------------------------------

def test_abort_queued_at_router():
    """Aborting a request the router still holds emits its terminal
    'aborted' event straight from the router (no replica ever saw it)
    and the rest of the workload completes identically."""
    cfg, params = _setup("qwen2_5_14b")
    kw = dict(max_slots=1, max_len=32, prefill_chunk=4)
    baseline = _drive(ServingEngine(cfg, params, **kw),
                      [r for r in _reqs(cfg, n=4) if r.id != 3])
    router = EngineRouter(cfg, params, engines=2, routing="least-loaded",
                          **kw)
    for r in _reqs(cfg, n=4):
        router.submit(r)
    assert router.stats()["pending_requests"] == 4   # nothing dispatched yet
    assert router.abort(3)
    events = []
    while router.has_work():
        events.extend(router.step())
        router.check_invariants()
    aborted = [o for o in events if o.id == 3]
    assert len(aborted) == 1 and aborted[0].finish_reason == "aborted"
    assert aborted[0].tokens == []
    done = {o.id: o.tokens for o in events if o.finished and o.id != 3}
    assert done == baseline
    assert not router.abort(3)                       # already gone


def test_abort_in_flight_on_replica():
    """Aborting a request mid-decode on whichever replica holds it:
    terminal event carries the tokens drained so far, the replica's
    blocks come back (ledger audits clean), and co-tenants finish with
    unchanged tokens (composition independence)."""
    cfg, params = _setup("qwen2_5_14b")
    kw = {**_ENGINE_KW, **_layout_kw("paged")}
    baseline = _drive(ServingEngine(cfg, params, **kw),
                      [r for r in _reqs(cfg, n=4, gen=8) if r.id != 1])
    router = EngineRouter(cfg, params, engines=2, routing="round-robin",
                          **kw)
    for r in _reqs(cfg, n=4, gen=8):
        router.submit(r)
    events = []
    for _ in range(3):
        events.extend(router.step())
        router.check_invariants()
    assert router.abort(1)
    router.check_invariants()
    while router.has_work():
        events.extend(router.step())
        router.check_invariants()
    term = [o for o in events if o.id == 1 and o.finished]
    assert len(term) == 1 and term[0].finish_reason == "aborted"
    assert len(term[0].tokens) < 8                   # cut short mid-decode
    done = {o.id: o.tokens for o in events if o.finished and o.id != 1}
    assert done == baseline
    assert not router.abort(1)


def test_duplicate_submit_rejected_across_replicas():
    """One id may not be live twice anywhere in the fleet: rejected while
    queued at the router, rejected after dispatch to a replica, and free
    again once the request finishes."""
    cfg, params = _setup("qwen2_5_14b")
    router = EngineRouter(cfg, params, engines=2, routing="round-robin",
                          **_ENGINE_KW)
    router.submit(Request(prompt=_prompt(0, 5, cfg), max_new_tokens=2, id=7))
    with pytest.raises(ValueError, match="already pending or in flight"):
        router.submit(Request(prompt=_prompt(1, 5, cfg), max_new_tokens=2,
                              id=7))
    router.step()                                    # now placed on a replica
    with pytest.raises(ValueError, match="already pending or in flight"):
        router.submit(Request(prompt=_prompt(1, 5, cfg), max_new_tokens=2,
                              id=7))
    while router.has_work():
        router.step()
    assert router.submit(Request(prompt=_prompt(1, 5, cfg),
                                 max_new_tokens=2, id=7)) == 7
    while router.has_work():
        router.step()


def test_router_validation_mirrors_engine():
    cfg, params = _setup("qwen2_5_14b")
    router = EngineRouter(cfg, params, engines=2, **_ENGINE_KW)
    with pytest.raises(ValueError, match="empty prompt"):
        router.submit(Request(prompt=jnp.zeros((0,), jnp.int32)))
    with pytest.raises(ValueError, match="exceeds engine max_len"):
        router.submit(Request(prompt=_prompt(0, 5, cfg),
                              max_new_tokens=1000))


# ---------------------------------------------------------------------------
# streaming surface
# ---------------------------------------------------------------------------

def test_router_stream_single_request():
    """stream() narrows the merged loop to one request's events while
    other traffic keeps flowing; its tokens match the single engine."""
    cfg, params = _setup("qwen2_5_14b")
    single = _drive(ServingEngine(cfg, params, **_ENGINE_KW),
                    _reqs(cfg, n=3))
    router = EngineRouter(cfg, params, engines=2, routing="least-loaded",
                          **_ENGINE_KW)
    background = _reqs(cfg, n=3)[:2]
    for r in background:
        router.submit(r)
    mine = _reqs(cfg, n=3)[2]
    seen = []
    for out in router.stream(mine):
        assert out.id == 2
        seen.extend(out.new_tokens)
        if out.finished:
            assert out.tokens == single[2]
    assert seen == single[2]
    rest = {o.id: o.tokens for o in router.events() if o.finished}
    assert rest == {0: single[0], 1: single[1]}


def test_replica_meshes_take_their_own_devices():
    """`make_tp_mesh(tp, replica=i)` — how the router places replica i —
    hands out devices [i*tp, (i+1)*tp) while the host has them, and (CPU
    devices only) wraps round onto groups already in use past the last
    whole group."""
    from repro.launch.mesh import make_tp_mesh
    devices = jax.devices()
    for tp in (1, 2):
        groups = len(devices) // tp
        if groups == 0:
            continue
        for i in range(groups + 1):
            got = list(make_tp_mesh(tp, replica=i).devices.flat)
            start = (i % groups) * tp
            assert got == devices[start:start + tp]


def test_replicas_never_share_a_chip(monkeypatch):
    """On a host of chips (not CPU threads) a fleet larger than the host
    is refused instead of stacking replicas on chips already in use."""
    import types

    from repro.launch import mesh as mesh_mod
    chips = [types.SimpleNamespace(id=i, platform="tpu") for i in range(4)]
    monkeypatch.setattr(mesh_mod.jax, "devices", lambda: chips)
    monkeypatch.setattr(mesh_mod.jax, "make_mesh",
                        lambda shape, axes, axis_types, devices: devices)
    for tp, replica in ((1, 3), (2, 1), (4, 0)):
        got = mesh_mod.make_tp_mesh(tp, replica=replica)
        assert [d.id for d in got] == list(
            range(replica * tp, (replica + 1) * tp))
    for tp, replica in ((1, 4), (2, 2), (3, 1), (4, 1)):
        with pytest.raises(RuntimeError, match="never share a chip"):
            mesh_mod.make_tp_mesh(tp, replica=replica)
