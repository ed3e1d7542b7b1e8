"""Streaming serving example: submit requests with different prompt
lengths and sampling params, stream per-token `RequestOutput` deltas as
they decode under the overlap-dispatch loop, and follow one request with
`engine.stream()` (Flex-PE FxP8 policy: quantized matmuls, CORDIC
attention softmax, FxP8-quantized KV cache).

    PYTHONPATH=src python examples/serve_lm.py
    PYTHONPATH=src python examples/serve_lm.py --arch mamba2_370m --gen 32
    PYTHONPATH=src python examples/serve_lm.py --backend auto --no-overlap
"""
import argparse

import jax

from repro.configs import get_config
from repro.launch.serve import prepare_serving_params
from repro.launch.train import policy_from_name
from repro.models import model as M
from repro.serving import Request, SamplingParams, ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_5_14b")
    ap.add_argument("--gen", type=int, default=12)
    ap.add_argument("--backend", default="reference")
    ap.add_argument("--overlap", default=True,
                    action=argparse.BooleanOptionalAction)
    args = ap.parse_args()

    cfg = get_config(args.arch).reduced()
    policy = policy_from_name("flexpe-fxp8").with_backend(args.backend)
    params = prepare_serving_params(
        M.init_params(cfg, jax.random.PRNGKey(0)), policy)

    engine = ServingEngine(cfg, params, policy=policy, max_slots=3,
                           max_len=64, prefill_chunk=8,
                           overlap=args.overlap)

    # six requests with heterogeneous prompt lengths and per-request
    # sampling — only three slots, so admission happens mid-decode
    for i, plen in enumerate((17, 5, 11, 3, 23, 8)):
        prompt = jax.random.randint(
            jax.random.fold_in(jax.random.PRNGKey(1), i), (plen,), 0,
            cfg.vocab)
        sampling = (SamplingParams()
                    if i % 2 == 0 else
                    SamplingParams(temperature=0.7, top_k=20))
        engine.submit(Request(prompt=prompt, max_new_tokens=args.gen,
                              sampling=sampling, seed=i))

    # events() streams RequestOutput objects: one per sampled token, plus
    # a terminal event per request (under overlap, samples drain one tick
    # behind the dispatch that produced them)
    for out in engine.events():
        if out.finished:
            mode = "greedy" if out.id % 2 == 0 else "top-k sampled"
            print(f"req {out.id:2d} [{mode:13s}] prompt={out.prompt_len:2d} "
                  f"ticks {out.admitted_tick:3d}-{out.tick:3d} "
                  f"-> {out.tokens}")
        else:
            print(f"req {out.id:2d} +{out.new_tokens[0]:5d}  "
                  f"({len(out.tokens):2d}/{args.gen} @ tick {out.tick})")

    # stream() narrows the event loop to a single request
    prompt = jax.random.randint(jax.random.PRNGKey(99), (9,), 0, cfg.vocab)
    print("streaming one more request:", end=" ", flush=True)
    for out in engine.stream(Request(prompt=prompt, max_new_tokens=8)):
        print(out.new_tokens[0] if out.new_tokens else "", end=" ",
              flush=True)
    print()

    st = engine.stats()
    print(f"done: {st['prompt_tokens']} prompt + {st['generated_tokens']} "
          f"generated tokens over {st['ticks']} ticks, "
          f"slot utilization {st['slot_utilization']:.0%}, "
          f"sample syncs/token {st['sample_syncs_per_token']:.2f} "
          f"({'overlap' if args.overlap else 'sync'} loop)")


if __name__ == "__main__":
    main()
