"""Chip smoke test: MiniCPM-2B served at full width on a TPU through the
compiled Pallas path.

    python chip_smoke.py             # one chip: the serving path
    python chip_smoke.py --chips 4   # four chips: --tp 4 against --tp 1,
                                     # and a 4-replica router against one

One chip. MiniCPM-2B at its published widths and full depth (40 layers,
d_model 2304, 36 heads, d_ff 5760, vocab 122753), random bf16 weights from
`--seed` quantized once to FxP8 codes, served by `ServingEngine` under
policy flexpe-fxp8 on backend `pallas` (fxp_gemm with the fused CORDIC
AF epilogue, CORDIC softmax, fused paged decode attention) over a paged
KV pool: 4 requests of 128 prompt tokens and 32 new tokens on 4 slots,
drained through `events()`. The first decode step's logits are compared
with the `reference` backend's twice: on the same weights, cache and
tokens (the decode step alone), and from a reference engine that ran
its own prefill on the same prompts and was fed the same first tokens
(prefill and decode together).

Four chips. Only what exists across chips: the same model at `--tp 4`
against `--tp 1`, and a 4-replica `EngineRouter` whose replicas must sit
on distinct chips, against a single engine. Both compare the first
decode step's logits per request.

The script drives JAX in this one process and starts no other. It exits
non-zero without printing a result when JAX finds no TPU, when the
repository's `src/` is missing, or when any phase fails. A passing run
prints as its last line `{"ok": true, "device": {...}}`.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import jax
import numpy as np

SRC = Path(__file__).resolve().parent / "src"

ARCH = "minicpm_2b"
POLICY = "flexpe-fxp8"
SLOTS, PROMPT_LEN, GEN = 4, 128, 32
PREFILL_CHUNK = 128
KV_BLOCK_SIZE = 16

#: Bound on the relative RMS gap between two logit sets of the same step,
#: ||a - b|| / ||b|| over every live row. The backends (and tp=1 vs tp=4)
#: compute the same integer GEMMs exactly, the attention dots run at one
#: pinned precision (`core.precision.ATTN_PRECISION`), and the serving
#: steps keep their bf16 casts (`serving.executor`). What still differs:
#: the tied LM head (pallas quantizes it on the fly into an exact integer
#: GEMM, reference multiplies bf16 fake-quant values, about 2^-9 per
#: product) and float work that two differently fused programs round or
#: order differently, a few ulps that an int8 re-quantization or a step
#: of the 4-stage CORDIC exp can turn into a whole code step; 40 layers
#: compound those flips. On a v5e the gap is 0.048 with every argmax
#: equal. A wrong kernel (a permuted column, a misread block, K/V off
#: their bf16 grid) moved it to 0.11 and more, so 2^-4 separates the two.
LOGIT_RTOL = 2.0 ** -4


def log(msg: str):
    print(msg, flush=True)


def check_logits(name: str, got, want, vocab: int):
    """Finite logits of the expected shape, within LOGIT_RTOL of `want`."""
    got = np.asarray(got, np.float32)[..., :vocab]
    want = np.asarray(want, np.float32)[..., :vocab]
    if got.shape != want.shape:
        raise AssertionError(f"{name}: shape {got.shape} != {want.shape}")
    for label, arr in (("got", got), ("want", want)):
        if not np.isfinite(arr).all():
            raise AssertionError(f"{name}: non-finite logits ({label})")
    diff = got.astype(np.float64) - want
    gap = float(np.linalg.norm(diff) / np.linalg.norm(want.astype(np.float64)))
    agree = float(np.mean(got.argmax(-1) == want.argmax(-1)))
    log(f"{name}: logits {got.shape}, relative RMS gap {gap!r} "
        f"(bound {LOGIT_RTOL!r}), max abs gap "
        f"{float(np.abs(diff).max())!r}, argmax agreement {agree!r}")
    if not gap <= LOGIT_RTOL:
        raise AssertionError(f"{name}: relative RMS gap {gap} exceeds "
                             f"{LOGIT_RTOL}")


def build_weights(cfg, policy, seed: int):
    """Random bf16 weights from `seed`, quantized once for serving, made in
    one program on the device (the float tree never exists whole)."""
    from repro.launch.serve import prepare_serving_params
    from repro.models import model as M

    t0 = time.perf_counter()
    params = jax.jit(lambda key: prepare_serving_params(
        M.init_params(cfg, key), policy))(jax.random.PRNGKey(seed))
    jax.block_until_ready(params)
    nbytes = sum(a.nbytes for a in jax.tree.leaves(params))
    log(f"weights: {nbytes} bytes on the device after quantize-once "
        f"({nbytes / 2**30:.3f} GiB), built in "
        f"{time.perf_counter() - t0:.1f} s")
    return params


def engine_kw(seed: int, slots: int, gen: int):
    return dict(max_slots=slots, max_len=PROMPT_LEN + gen,
                prefill_chunk=PREFILL_CHUNK, seed=seed,
                kv_block_size=KV_BLOCK_SIZE)


def first_step_logits(fleet, requests):
    """Submit `requests` to an engine or a router and step it until every
    request holds its first (prefill-sampled) token; at that moment peek
    the logits of its first decode step. Then abort the requests, so no
    decode step is compiled that the check does not need. Returns
    {request id: logits row}."""
    engines = getattr(fleet, "engines", [fleet])
    for r in requests:
        fleet.submit(r)
    got = {}
    while len(got) < len(requests):
        fleet.step()
        for eng in engines:
            rows = {b: s for b, s in enumerate(eng.sched.slots)
                    if s is not None and len(s.generated) == 1
                    and s.request.id not in got}
            if rows:
                nv = np.zeros((eng.max_slots,), np.int32)
                nv[list(rows)] = 1
                lg = np.asarray(eng.ex.peek_logits(nv))
                for b, s in rows.items():
                    got[s.request.id] = lg[b]
    for rid in got:
        fleet.abort(rid)
    for _ in fleet.events():
        pass
    return got


def prefill_and_decode_vs_reference(cfg, params, policy, seed, eng, lg):
    """The whole path against `reference`: a reference-policy engine on
    the same weights prefills the same prompts into its own cache, and
    its first decode step, fed the tokens `eng` sampled from its own
    prefill, must give `eng`'s first decode logits `lg`. This covers the
    chunked prefill (fxp_gemm at 128-row tiles, CORDIC softmax in
    prefill attention, paged KV writes), which the check on `eng`'s
    cache alone cannot see."""
    from repro.launch.serve import make_requests
    from repro.serving import ServingEngine

    t0 = time.perf_counter()
    ref = ServingEngine(cfg, params, policy=policy.with_backend("reference"),
                        **engine_kw(seed, SLOTS, GEN))
    for r in make_requests(cfg, SLOTS, PROMPT_LEN, GEN, seed=seed):
        ref.submit(r)
    ref.step()
    ids = [s.request.id for s in eng.sched.slots]
    if [s.request.id for s in ref.sched.slots] != ids:
        raise AssertionError("the two engines admitted the requests to "
                             "different slots")
    feed = [s.generated[0] for s in eng.sched.slots]
    ref_lg = np.asarray(ref.ex.peek_logits(np.ones((SLOTS,), np.int32),
                                           tokens=feed))
    log(f"reference engine: prefill and first decode step compiled and "
        f"run in {time.perf_counter() - t0:.1f} s")
    check_logits(f"{eng.policy.backend} vs reference, prefill and first "
                 f"decode step", lg, ref_lg, cfg.vocab)
    for r in ids:
        ref.abort(r)
    for _ in ref.events():
        pass


def one_chip(cfg, backend: str, seed: int) -> None:
    """The serving path on one device, checked against `reference`."""
    from repro.launch.serve import make_requests
    from repro.launch.train import policy_from_name
    from repro.serving import ServingEngine

    policy = policy_from_name(POLICY).with_backend(backend)
    params = build_weights(cfg, policy, seed)
    t0 = time.perf_counter()
    eng = ServingEngine(cfg, params, policy=policy,
                        **engine_kw(seed, SLOTS, GEN))
    log(f"engine: {SLOTS} slots, paged KV {eng.ex.num_blocks} blocks x "
        f"{KV_BLOCK_SIZE} tokens, built in {time.perf_counter() - t0:.1f} s")
    for r in make_requests(cfg, SLOTS, PROMPT_LEN, GEN, seed=seed):
        eng.submit(r)

    t0 = time.perf_counter()
    events = eng.step()
    log(f"tick 0 (compiles chunked prefill + sampling, prefills "
        f"{SLOTS}x{PROMPT_LEN} tokens): {time.perf_counter() - t0:.1f} s")
    nv = np.ones((SLOTS,), np.int32)
    t0 = time.perf_counter()
    lg = np.asarray(eng.ex.peek_logits(nv))
    log(f"first decode step logits, {backend}: compiled and run in "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    ref = np.asarray(eng.ex.peek_logits(
        nv, policy=policy.with_backend("reference")))
    log(f"first decode step logits, reference: compiled and run in "
        f"{time.perf_counter() - t0:.1f} s")
    check_logits(f"{backend} vs reference, first decode step on the "
                 f"{backend} prefill's cache", lg, ref, cfg.vocab)
    prefill_and_decode_vs_reference(cfg, params, policy, seed, eng, lg)

    t0 = time.perf_counter()
    events += eng.step()
    log(f"tick 1 (compiles fused decode + sampling): "
        f"{time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    events += list(eng.events())
    dt = time.perf_counter() - t0
    done = [e for e in events if e.finished]
    toks = sum(len(e.new_tokens) for e in events)
    log(f"served: {len(done)} requests finished, {toks} tokens out, "
        f"remaining {eng.tick - 2} ticks drained in {dt:.1f} s")
    if len(done) != SLOTS or any(len(e.tokens) != GEN for e in done):
        raise AssertionError(f"expected {SLOTS} requests of {GEN} tokens, "
                             f"got {[len(e.tokens) for e in done]}")
    if any(e.finish_reason != "length" for e in done):
        raise AssertionError("a request finished early: "
                             f"{[e.finish_reason for e in done]}")
    if not all(0 <= t < cfg.vocab for e in done for t in e.tokens):
        raise AssertionError("sampled a token outside the vocabulary")
    eng.check_invariants()


def four_chips(cfg, backend: str, seed: int) -> None:
    """--tp 4 against --tp 1, and 4 replicas on 4 chips against one."""
    from repro.launch.serve import make_requests
    from repro.launch.train import policy_from_name
    from repro.serving import EngineRouter, ServingEngine

    policy = policy_from_name(POLICY).with_backend(backend)
    params = build_weights(cfg, policy, seed)

    def reqs(gen):
        return make_requests(cfg, SLOTS, PROMPT_LEN, gen, seed=seed)

    t0 = time.perf_counter()
    one = first_step_logits(
        ServingEngine(cfg, params, policy=policy, tp=1,
                      **engine_kw(seed, SLOTS, 2)), reqs(2))
    log(f"tp=1: first-step logits in {time.perf_counter() - t0:.1f} s")
    t0 = time.perf_counter()
    eng4 = ServingEngine(cfg, params, policy=policy, tp=4,
                         **engine_kw(seed, SLOTS, 2))
    db = eng4.ex.device_bytes()
    log(f"tp=4: {db['weight_bytes']} weight bytes and {db['kv_bytes']} KV "
        f"bytes per device, {eng4.ex.pool_shards} pool shards")
    four = first_step_logits(eng4, reqs(2))
    log(f"tp=4: first-step logits in {time.perf_counter() - t0:.1f} s")
    ids = sorted(one)
    check_logits("tp=4 vs tp=1", [four[i] for i in ids],
                 [one[i] for i in ids], cfg.vocab)

    # one slot per replica: the flexpe activation scales are per tensor,
    # so a row's numerics depend on the rows batched with it; single-row
    # batches give the router and the anchor the same composition
    kw = dict(engine_kw(seed, 1, 2), policy=policy, routing="round-robin")
    router = EngineRouter(cfg, params, engines=4, **kw)
    placed = [sorted(d.id for d in jax.tree.leaves(e.ex.params)[0].devices())
              for e in router.engines]
    log(f"router: replica devices {placed}")
    if len({tuple(p) for p in placed}) != 4:
        raise AssertionError(f"replicas share devices: {placed}")
    t0 = time.perf_counter()
    fleet = first_step_logits(router, reqs(2))
    log(f"router, 4 engines: first-step logits in "
        f"{time.perf_counter() - t0:.1f} s")
    anchor = first_step_logits(EngineRouter(cfg, params, engines=1, **kw),
                               reqs(2))
    ids = sorted(anchor)
    check_logits("engines=4 vs engines=1", [fleet[i] for i in ids],
                 [anchor[i] for i in ids], cfg.vocab)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devices = jax.devices()
    device = devices[0]
    if device.platform != "tpu":
        log(f"chip_smoke: JAX found no TPU (platform {device.platform!r})")
        return 1
    if len(devices) < args.chips:
        log(f"chip_smoke: --chips {args.chips} but JAX sees "
            f"{len(devices)} device(s)")
        return 1
    if not SRC.is_dir():
        log(f"chip_smoke: no {SRC} next to this script")
        return 1
    sys.path.insert(0, str(SRC))
    from repro.configs import get_config
    from repro.launch.compile_cache import enable_compile_cache

    cache_dir = Path(enable_compile_cache())
    cached = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    log(f"device: {device.platform} {device.device_kind}, {len(devices)} "
        f"visible; jax {jax.__version__}; compile cache {cache_dir} "
        f"({cached} entries)")
    cfg = get_config(ARCH)
    log(f"arch {cfg.name}: {cfg.n_layers} layers, d_model {cfg.d_model}, "
        f"{cfg.n_heads} heads ({cfg.n_kv_heads} kv, head_dim "
        f"{cfg.head_dim}), d_ff {cfg.d_ff}, vocab {cfg.vocab}; policy "
        f"{POLICY}, backend pallas, paged KV block {KV_BLOCK_SIZE}")
    t0 = time.perf_counter()
    (four_chips if args.chips == 4 else one_chip)(cfg, "pallas", args.seed)
    for d in devices[:args.chips]:
        stats = d.memory_stats() or {}
        log(f"device {d.id} memory: peak_bytes_in_use "
            f"{stats.get('peak_bytes_in_use')} of bytes_limit "
            f"{stats.get('bytes_limit')}")
    after = len(list(cache_dir.glob("*"))) if cache_dir.is_dir() else 0
    log(f"all phases passed in {time.perf_counter() - t0:.1f} s; compile "
        f"cache now {after} entries")
    print(json.dumps({"ok": True, "device": {
        "platform": device.platform, "kind": device.device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
