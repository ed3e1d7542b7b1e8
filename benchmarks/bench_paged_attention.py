"""Paged decode attention alone on the chip: time per call against the
share of each row's block table that is live.

    PYTHONPATH=src python benchmarks/bench_paged_attention.py \
        [--shapes minicpm_2b,mistral_nemo_12b] [--live 0,1,15,100] \
        [--iters 50] [--out results/paged_attention.jsonl]

Runs the fused kernel (`dispatch.paged_attention`, backend `pallas`) at
the served shapes: the `flexpe-fxp8` policy (int8 KV dequantized at
staging, CORDIC softmax), 136 table slots of 16 positions a row. A
`--live` value is the share of each row's slots inside its valid length
(0: no valid position; 1: one slot). Tables hold blocks up to the valid
length and the sentinel after it, as the engine allocates them. A
second kernel, `dead_walk`, walks the same grid staging only what a dead
slot stages, with no fetch and no epilogue: its time over B x MB grid
steps is the cost of one dead step.

Prints one JSON line per measurement: ms per call (median of 5 rounds of
`--iters` back-to-back calls) and a digest of the output, so two
versions of the kernel can be compared bit for bit on the chip.
"""
from __future__ import annotations

import argparse
import functools
import hashlib
import json
import statistics
import time
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.fxp import FORMATS
from repro.kernels import dispatch
from repro.launch.train import policy_from_name

BS, MB = 16, 136
#: slots, (query heads, KV heads, head dim), pool blocks of the cells
SHAPES = {"minicpm_2b": (16, (36, 36, 64), 1280),
          "mistral_nemo_12b": (32, (32, 8, 128), 3584)}


def _inputs(shape, live, seed=0):
    b, (h, kvh, hd), nb = SHAPES[shape]
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.normal(size=(b, 1, h, hd)), jnp.bfloat16)
    kc = jnp.asarray(rng.integers(-127, 128, (nb, BS, kvh, hd)), jnp.int8)
    vc = jnp.asarray(rng.integers(-127, 128, (nb, BS, kvh, hd)), jnp.int8)
    ks = jnp.asarray(rng.uniform(1e-3, 2e-2, (nb, BS, kvh, 1)), jnp.float32)
    vs = jnp.asarray(rng.uniform(1e-3, 2e-2, (nb, BS, kvh, 1)), jnp.float32)
    blocks = round(MB * live / 100) if live > 1 else live
    kv_valid = np.full((b,), max(blocks * BS - 5, 0) if blocks else 0,
                       np.int32)
    if live == 100:
        kv_valid[:] = MB * BS
    tables = np.full((b, MB), nb, np.int32)
    for r in range(b):
        tables[r, :blocks] = rng.choice(nb, blocks, replace=blocks > nb)
    lengths = jnp.asarray(np.maximum(kv_valid - 1, 0))
    return (q, kc, vc, ks, vs, jnp.asarray(tables), lengths,
            jnp.asarray(kv_valid))


def _attention_fn(backend):
    """The served kernel call, jitted once for every table layout."""
    pol = policy_from_name("flexpe-fxp8").with_backend(backend)
    fmt = FORMATS[pol.kv_cache]

    def fn(q, kc, vc, ks, vs, tables, lengths, kv_valid):
        return dispatch.paged_attention(
            q, kc, vc, ks, vs, tables, pol, backend, lengths=lengths,
            kv_valid=kv_valid, positions=lengths[:, None], fmt=fmt)
    return jax.jit(fn)


def _dead_walk(shape, backend):
    """The grid of the fused kernel, every step dead: no new block index
    (so no fetch), masked scores and zero values staged, no epilogue."""
    b, (h, kvh, hd), nb = SHAPES[shape]
    g = h // kvh

    def kernel(tbl_ref, meta_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
               o_ref, s_scr, v_scr):
        j = pl.program_id(1)
        s_scr[j] = jnp.full(s_scr.shape[1:], -1e30, jnp.float32)
        v_scr[pl.ds(j * BS, BS)] = jnp.zeros((BS, kvh, hd), jnp.float32)

        @pl.when(j == MB - 1)
        def _():
            o_ref[...] = jnp.zeros(o_ref.shape, o_ref.dtype)

    def index(bb, j, tbl, meta):
        return (jnp.minimum(tbl[bb, 0], nb - 1), 0, 0, 0)

    pool = [(1, BS, kvh, hd)] * 2 + [(1, BS, kvh, 1)] * 2
    spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(b, MB),
        in_specs=[pl.BlockSpec((1, kvh, g, hd),
                               lambda bb, j, tbl, meta: (bb, 0, 0, 0))]
        + [pl.BlockSpec(p, index) for p in pool],
        out_specs=pl.BlockSpec((1, h, hd),
                               lambda bb, j, tbl, meta: (bb, 0, 0)),
        scratch_shapes=[pltpu.VMEM((MB, kvh, g, BS), jnp.float32),
                        pltpu.VMEM((MB * BS, kvh, hd), jnp.float32)])
    call = pl.pallas_call(
        kernel, grid_spec=spec,
        out_shape=jax.ShapeDtypeStruct((b, h, hd), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(vmem_limit_bytes=100 * 2**20),
        interpret=backend == "pallas-interpret")
    q, kc, vc, ks, vs, tables, lengths, _ = _inputs(shape, 0)
    meta = jnp.stack([lengths, lengths, lengths], axis=1)
    fn = jax.jit(functools.partial(call, tables, meta))
    args = (q[:, 0].reshape(b, kvh, g, hd), kc, vc, ks, vs)
    return lambda: fn(*args)


def _ms_per_call(run, iters):
    jax.block_until_ready(run())                  # compile and warm up
    rounds = []
    for _ in range(5):
        t0 = time.perf_counter()
        for _ in range(iters):
            out = run()
        jax.block_until_ready(out)
        rounds.append((time.perf_counter() - t0) / iters * 1e3)
    return statistics.median(rounds), out


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--shapes", default=",".join(SHAPES))
    ap.add_argument("--live", default="0,1,15,100")
    ap.add_argument("--iters", type=int, default=50)
    ap.add_argument("--backend", default="pallas",
                    help="pallas-interpret runs the kernels on the CPU")
    ap.add_argument("--label", default="")
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    lines = []
    for shape in args.shapes.split(","):
        b = SHAPES[shape][0]
        attention = _attention_fn(args.backend)
        runs = [(f"live{x}", functools.partial(
                    attention, *_inputs(shape, int(x))))
                for x in args.live.split(",")]
        runs.append(("dead_walk", _dead_walk(shape, args.backend)))
        for name, run in runs:
            ms, out = _ms_per_call(run, args.iters)
            digest = hashlib.sha256(
                np.asarray(out, np.float32).tobytes()).hexdigest()[:16]
            line = {"label": args.label, "shape": shape, "run": name,
                    "ms": ms, "us_per_step": ms * 1e3 / (b * MB),
                    "digest": digest, "device": jax.devices()[0].device_kind}
            print(json.dumps(line), flush=True)
            lines.append(line)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        with open(args.out, "a") as f:
            f.writelines(json.dumps(x) + "\n" for x in lines)


if __name__ == "__main__":
    main()
