"""Serving launcher — thin CLI over the continuous-batching engine.

    PYTHONPATH=src python -m repro.launch.serve --arch qwen2_5_14b --reduced \
        --requests 8 --slots 4 --prompt-len 32 --mixed --gen 16 \
        --policy flexpe-fxp8 --backend auto --stream

Builds a `serving.ServingEngine` (scheduler/executor split over the slot
pool + ragged per-request KV cache), submits `--requests` generation
requests — with heterogeneous prompt lengths under `--mixed` — and
consumes the `RequestOutput` event stream: per-token deltas printed live
under `--stream`, completion summaries otherwise. The Flex-PE policy
applies end-to-end: quantized matmuls, CORDIC attention softmax, FxP8
quantized KV cache storage.

`--overlap` (default; `--no-overlap` for the sync loop) runs the
overlap-dispatch engine loop: the executor feeds each slot's sampled
token back on-device, so the host enqueues tick N+1's decode before
syncing tick N's samples — the device→host sample sync overlaps the next
tick's compute instead of idling the array, which `stats()` exposes as
`sample_syncs_per_token` (~0 overlapped vs 1.0 sync). The two loops are
bit-exact. `--scheduler fifo|spf` picks the admission policy
(shortest-prompt-first minimizes mean TTFT on mixed workloads).

`--backend` selects the kernel backend (see core/backend.py):
reference (fake-quant float path), pallas (real packed-int fxp_gemm +
CORDIC kernels), pallas-interpret, or auto. Any non-reference backend
first runs `quantize_params` model surgery, so decode moves packed integer
weight codes HBM→VMEM instead of re-fake-quantizing bf16 weights every
step — the paper's SIMD storage win at serving time.

`--prefix-cache` (requires `--kv-block-size`) turns on cross-request
prefix caching over the paged block pool: full blocks of prompt tokens
are chain-hashed and shared copy-on-write, so requests with a common
system prompt (`--shared-prefix N` prepends one to every generated
request) skip prefill for the matched blocks and share their physical KV.
Decode stays bit-exact vs the unshared paged and contiguous layouts —
`benchmarks/ci_smoke.py` gates that on every CI run, overlapped and sync.

`--engines N` serves the workload data-parallel: an `EngineRouter` fans
one admission queue out over N independent engine replicas (each with its
own slot pool, paged pool, and prefix cache; each tp-sharded when `--tp`
is also given). `--routing` picks the placement policy — round-robin,
least-loaded, or prefix-affinity (chain-hash steering of shared-prefix
requests to the replica already holding their cached blocks, bounded by
`--stickiness`). Placement never changes tokens: every replica shares the
seed and per-request outputs are batch-composition independent, so
`--engines N` is token-identical to `--engines 1` — gated by
`benchmarks/ci_smoke.py --engines 2` on both backends.

`--tiers fxp4,fxp8 --routing tiered` serves a heterogeneous precision
fleet instead: one replica per listed ladder tier (`core.tiers.TIERS`),
all sharing a single `TieredWeights` bank (quantize-once codes per tier
plus one float source-of-truth). The router's `TierPolicy` places each
request — an explicit pin (`--pin-tier`, or `Request.tier`) is honored
unconditionally, `--priority` > 0 takes the best tier / < 0 the
cheapest, and priority-0 requests degrade to a cheaper tier when the
better tier's queue pressure exceeds `--tier-threshold`. Within a tier
placement never changes tokens; across tiers it deliberately does —
that is the accuracy/throughput trade the paper's runtime-reconfigurable
PE exists for.

`--spec-decode fxp4:fxp8` turns on cross-tier speculative decoding: a
cheap-tier draft engine proposes `--spec-k` tokens per round, the verify
tier scores all of them in one chunked dispatch, and greedy acceptance
keeps the stream token-identical to serving the verify tier alone —
rejected suffixes roll back out of the paged KV pool. Composes with
`--tiers` (only the verify-tier replicas turn speculative) and serves
greedy requests only.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp

from ..configs.base import ARCH_IDS, get_config
from ..core.backend import BACKENDS
from ..core.qtensor import TieredWeights, packed_bytes, quantize_params
from ..models import model as M
from ..serving import (EngineRouter, Request, SamplingParams, ServingEngine)
from ..serving.router import ROUTING_POLICIES
from ..serving.scheduler import POLICIES
from .compile_cache import enable_compile_cache
from .mesh import make_tp_mesh
from .train import policy_from_name


def prepare_serving_params(params, policy, packed=None):
    """Quantize-once model surgery for a non-reference backend: replace
    matmul weights with QuantizedTensor (FxP4 nibble-packed) per
    policy.matmul. No-op for native-precision policies."""
    if policy.matmul is None:
        return params
    return quantize_params(params, policy.matmul, packed=packed)


def make_requests(cfg, n, prompt_len, gen, mixed=False, temp=0.0, top_k=0,
                  seed=0, shared_prefix=0, tier=None, priority=0):
    """n requests; `mixed` varies prompt lengths across [plen/2, plen];
    `shared_prefix` prepends a common system prompt of that many tokens to
    every request (the prefix-cache workload); `tier`/`priority` stamp
    every request's precision-tier pin / SLO class (tiered fleets)."""
    skey = jax.random.PRNGKey(seed + 1000)
    if cfg.input_mode == "tokens":
        system = jax.random.randint(skey, (shared_prefix,), 0, cfg.vocab)
    else:
        system = jax.random.normal(skey, (shared_prefix, cfg.d_model),
                                   jnp.bfloat16)
    reqs = []
    for i in range(n):
        plen = (max(1, prompt_len - (i % 4) * (prompt_len // 8))
                if mixed else prompt_len)
        key = jax.random.fold_in(jax.random.PRNGKey(seed + 1), i)
        if cfg.input_mode == "tokens":
            prompt = jax.random.randint(key, (plen,), 0, cfg.vocab)
        else:
            prompt = jax.random.normal(key, (plen, cfg.d_model), jnp.bfloat16)
        if shared_prefix:
            prompt = jnp.concatenate([system, prompt])
        reqs.append(Request(prompt=prompt, max_new_tokens=gen,
                            sampling=SamplingParams(temperature=temp,
                                                    top_k=top_k),
                            tier=tier, priority=priority))
    return reqs


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4,
                    help="decode slot pool size (max concurrent requests)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--mixed", action="store_true",
                    help="heterogeneous prompt lengths across requests")
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--overlap", default=True,
                    action=argparse.BooleanOptionalAction,
                    help="overlap-dispatch loop: enqueue the next tick's "
                         "decode before syncing this tick's samples "
                         "(bit-exact vs --no-overlap)")
    ap.add_argument("--scheduler", default="fifo", choices=list(POLICIES),
                    help="admission policy: fifo, or spf (shortest prompt "
                         "first — lower mean TTFT on mixed workloads)")
    ap.add_argument("--stream", action="store_true",
                    help="print tokens as they arrive (per-token "
                         "RequestOutput deltas) instead of completion "
                         "summaries")
    ap.add_argument("--kv-block-size", type=int, default=0,
                    help="paged KV cache: tokens per pool block (0 = "
                         "contiguous per-slot max_len windows)")
    ap.add_argument("--kv-blocks", type=int, default=0,
                    help="paged KV cache: pool size in blocks (0 = byte "
                         "parity with the contiguous layout)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="cross-request prefix caching over the paged "
                         "pool (copy-on-write block sharing; requires "
                         "--kv-block-size)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend a common system prompt of N tokens to "
                         "every request (prefix-cache workload)")
    ap.add_argument("--policy", default="flexpe-fxp8")
    ap.add_argument("--backend", default="reference", choices=list(BACKENDS),
                    help="kernel backend for qmatmul/act/softmax; any "
                         "non-reference choice serves quantize-once packed "
                         "weights through the Pallas kernels")
    ap.add_argument("--temp", type=float, default=0.0)
    ap.add_argument("--top-k", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard quantized weights "
                         "and the paged KV block pool over a (1, tp) mesh "
                         "(token-identical to --tp 1; on CPU set XLA_FLAGS="
                         "--xla_force_host_platform_device_count=N first)")
    ap.add_argument("--engines", type=int, default=1,
                    help="data-parallel replica count: an EngineRouter "
                         "fans one admission queue out over N independent "
                         "engines, each optionally tp-sharded (composable "
                         "with --tp; token-identical to --engines 1)")
    ap.add_argument("--routing", default="least-loaded",
                    choices=list(ROUTING_POLICIES),
                    help="router placement policy (--engines > 1): "
                         "round-robin, least-loaded, or prefix-affinity "
                         "(chain-hash steering of shared-prefix requests "
                         "to the replica holding their cached blocks)")
    ap.add_argument("--stickiness", type=int, default=None,
                    help="prefix-affinity only: max load lead the affinity "
                         "replica may have before a request spills to "
                         "least-loaded (default 4)")
    ap.add_argument("--tiers", default="",
                    help="comma-separated precision-tier ladder names "
                         "(fxp4,fxp8,fxp16,bf16): build a heterogeneous "
                         "fleet with one replica per entry, serving from "
                         "a shared TieredWeights bank (overrides --engines "
                         "and --policy; pair with --routing tiered)")
    ap.add_argument("--tier-threshold", type=float, default=1.0,
                    help="tiered fleets: queue-pressure admission "
                         "threshold above which a priority-0 request "
                         "degrades to a cheaper tier (pressure = (class "
                         "load + 1) / class slot capacity)")
    ap.add_argument("--pin-tier", default=None,
                    help="pin EVERY generated request to this tier "
                         "(hard SLO: never degraded, rejected if the "
                         "fleet lacks the tier)")
    ap.add_argument("--priority", type=int, default=0,
                    help="SLO class stamped on every request: > 0 always "
                         "best tier, < 0 always cheapest, 0 degrades "
                         "under pressure")
    ap.add_argument("--spec-decode", default=None, metavar="DRAFT:VERIFY",
                    help="cross-tier speculative decoding, e.g. fxp4:fxp8: "
                         "a cheap-tier draft engine proposes --spec-k "
                         "tokens per round and the verify-tier engine "
                         "scores them in one chunked dispatch — streams "
                         "stay token-identical to the verify tier alone "
                         "(greedy requests only). With --tiers, the "
                         "verify-tier replicas turn speculative; without, "
                         "every replica does (--policy does not apply)")
    ap.add_argument("--spec-k", type=int, default=4,
                    help="speculative draft depth per round "
                         "(k <= --prefill-chunk + 1)")
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    tiers = [t for t in args.tiers.split(",") if t]
    policy = policy_from_name(args.policy).with_backend(args.backend)
    mesh = make_tp_mesh(args.tp)
    with mesh:
        params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
        common = dict(
            max_slots=args.slots,
            max_len=args.prompt_len + args.shared_prefix + args.gen,
            prefill_chunk=args.prefill_chunk, seed=args.seed,
            kv_block_size=args.kv_block_size or None,
            kv_blocks=args.kv_blocks or None,
            prefix_cache=args.prefix_cache,
            scheduler=args.scheduler, overlap=args.overlap)
        spec_pair = (args.spec_decode.split(":")
                     if args.spec_decode else [])
        if tiers:
            # heterogeneous precision fleet: the router wraps the FLOAT
            # source tree in a shared TieredWeights bank (quantize-once
            # codes per tier) and derives each replica's policy from the
            # ladder, so --policy does not apply here; a --spec-decode
            # draft tier rides the same bank
            bank = TieredWeights(params, tiers + spec_pair)
            per_tier = bank.bytes_by_tier()
            print("tiered weight banks: " + ", ".join(
                f"{t} {per_tier[t] / 2**20:.1f} MiB"
                for t in bank.tier_names))
            engine = EngineRouter(cfg, bank, tiers=tiers,
                                  tier_threshold=args.tier_threshold,
                                  backend=args.backend,
                                  routing=args.routing,
                                  stickiness=args.stickiness,
                                  spec_decode=args.spec_decode,
                                  spec_k=args.spec_k,
                                  tp=args.tp, **common)
        elif args.spec_decode:
            # speculative fleet without --tiers: every replica is a
            # draft/verify coordinator pair off one TieredWeights bank;
            # per-side policies derive from the tier pair, so --policy
            # does not apply
            engine = EngineRouter(cfg, params, engines=args.engines,
                                  backend=args.backend,
                                  routing=args.routing,
                                  stickiness=args.stickiness,
                                  spec_decode=args.spec_decode,
                                  spec_k=args.spec_k,
                                  tp=args.tp, **common)
        else:
            # quantize-once surgery for EVERY backend when the policy is
            # FxP: the backend then selects only the compute path
            # (reference dequantizes the same codes; pallas moves them
            # packed), so reference-vs-pallas compares kernels, not
            # quantization grids
            params = prepare_serving_params(params, policy)
            qb, fb = packed_bytes(params)
            if fb:
                print(f"quantized weights: {qb / 2**20:.1f} MiB moved per "
                      f"full pass vs {fb / 2**20:.1f} MiB fp32 "
                      f"({fb / max(qb, 1):.1f}x reduction)")
            if args.engines > 1:
                # data-parallel fleet: every replica is built tp-sharded
                # over the same mesh geometry, so --engines and --tp
                # compose
                engine = EngineRouter(cfg, params, engines=args.engines,
                                      policy=policy,
                                      routing=args.routing,
                                      stickiness=args.stickiness,
                                      tp=args.tp, **common)
            else:
                engine = ServingEngine(cfg, params, mesh=mesh,
                                       policy=policy, **common)
        reqs = make_requests(cfg, args.requests, args.prompt_len, args.gen,
                             mixed=args.mixed, temp=args.temp,
                             top_k=args.top_k, seed=args.seed,
                             shared_prefix=args.shared_prefix,
                             tier=args.pin_tier, priority=args.priority)
        t0 = time.time()
        for r in reqs:
            engine.submit(r)
        finished = []
        for out in engine.events():   # RequestOutput per-token stream
            if args.stream and out.new_tokens:
                print(f"  req {out.id} +{out.new_tokens} "
                      f"(tick {out.tick}, {len(out.tokens)} total)")
            if out.finished:
                if not args.stream:
                    print(f"  req {out.id} done ({out.finish_reason}) "
                          f"prompt={out.prompt_len} toks={out.tokens[:8]}"
                          f"{'...' if len(out.tokens) > 8 else ''} "
                          f"[ticks {out.admitted_tick}-{out.tick}]")
                finished.append(out.to_finished())
        dt = time.time() - t0
    st = engine.stats()
    total = st["prompt_tokens"] + st["generated_tokens"]
    print(f"{len(finished)} requests, {total} tokens in {dt:.2f}s = "
          f"{total / dt:.1f} tok/s, slot utilization "
          f"{st['slot_utilization']:.0%} "
          f"(policy {'tiers ' + args.tiers if tiers else args.policy}, "
          f"backend {args.backend}, arch {cfg.name})")
    if tiers or args.engines > 1 or args.spec_decode:
        if "spec_decode" in st:
            print(f"speculative: {st['spec_decode']} k={st['spec_k']}, "
                  f"{st['spec_accepted']}/{st['spec_proposed']} draft "
                  f"tokens accepted ({st['spec_acceptance_rate']:.0%}), "
                  f"{st['spec_verify_steps']} verify steps, "
                  f"{st['spec_rolled_back']} tokens rolled back from KV")
        print(f"router: {st['engines']} engines, routing "
              f"{st['routing_policy']}, dispatched {st['dispatched']}, "
              f"{st['prefix_tokens_reused']} prompt tokens served from "
              f"replica prefix caches "
              f"({st['prefill_tokens_computed']} computed)"
              + (f", affinity hit rate {st['affinity_hit_rate']:.0%} "
                 f"({st['affinity_spills']} spills)"
                 if "affinity_hit_rate" in st else ""))
        if "tier_placed" in st:
            placed = ", ".join(f"{t}: {n}"
                               for t, n in st["tier_placed"].items())
            print(f"tiers: placed {{{placed}}}, {st['tier_pinned']} pinned, "
                  f"{st['tier_degraded']} degraded under pressure "
                  f"(threshold {st['tier_threshold']:.2f})")
        for i, pe in enumerate(st["per_engine"]):
            tier_tag = f" [{pe['tier']}]" if pe["tier"] else ""
            print(f"  engine {i}{tier_tag}: {pe['dispatched']} requests, "
                  f"queue depth {pe['queue_depth']}, slot utilization "
                  f"{pe['slot_utilization']:.0%}, prefix hit rate "
                  f"{pe['prefix_hit_rate']:.0%}")
        return finished
    print(f"loop: {'overlap' if args.overlap else 'sync'}, scheduler "
          f"{st['scheduler_policy']}, sample syncs/token "
          f"{st['sample_syncs_per_token']:.2f}, queue wait "
          f"mean {st['queue_wait_ticks_mean']:.1f} / "
          f"max {st['queue_wait_ticks_max']} ticks")
    if engine.paged:
        print(f"paged KV: {st['kv_blocks']} blocks x {st['kv_block_size']} "
              f"tokens, peak in use {st['peak_blocks_used']}")
    if args.tp > 1:
        db = engine.ex.device_bytes()
        print(f"tp={args.tp}: {db['weight_bytes'] / 2**20:.2f} MiB weights "
              f"and {db['kv_bytes'] / 2**20:.2f} MiB KV resident per device "
              f"({engine.ex.pool_shards} pool shards)")
    if "prefix_cache" in st:
        pc = st["prefix_cache"]
        print(f"prefix cache: {st['prefix_tokens_reused']} prompt tokens "
              f"reused ({st['prefill_tokens_computed']} computed), "
              f"{pc['hits']} block hits, {pc['evictions']} evictions, "
              f"{st['cow_copies']} CoW forks")
    return finished


if __name__ == "__main__":
    enable_compile_cache()
    main()
