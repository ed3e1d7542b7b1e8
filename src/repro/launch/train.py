"""Training launcher — the end-to-end driver.

    PYTHONPATH=src python -m repro.launch.train --arch minicpm_2b \
        --reduced --steps 200 --policy flexpe-fxp8 --ckpt-dir /tmp/ckpt

Runs the full production stack on whatever devices exist (a host mesh on
CPU, the production mesh on a real fleet): sharded params/opt, policy-aware
model, stateless data pipeline, fault-tolerant loop (checkpoint/restart,
straggler monitor, preemption handler). `--reduced` selects the smoke-scale
config for CPU runs; on a pod slice, drop it and pass --mesh production.
"""
from __future__ import annotations

import argparse
import logging

import jax
import jax.numpy as jnp

from ..checkpoint.manager import CheckpointManager
from ..configs.base import ARCH_IDS, get_config
from ..core.precision import PrecisionPolicy
from ..data.pipeline import DataConfig, global_batch
from ..models import model as M
from ..optim import adamw
from ..runtime.trainer import TrainLoopConfig, train_loop
from . import steps as S
from .compile_cache import enable_compile_cache
from .mesh import make_host_mesh, make_production_mesh


def policy_from_name(name: str) -> PrecisionPolicy:
    if name == "bf16":
        return PrecisionPolicy.bf16()
    if name.startswith("flexpe-fxp"):
        return PrecisionPolicy.flexpe(int(name.replace("flexpe-fxp", "")))
    if name == "edge4":
        return PrecisionPolicy.edge4()
    raise ValueError(name)


def main(argv=None):
    logging.basicConfig(level=logging.INFO,
                        format="%(asctime)s %(name)s %(message)s")
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--policy", default="flexpe-fxp8")
    ap.add_argument("--mesh", choices=["host", "production", "multipod"],
                    default="host")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--schedule", default=None,
                    help="cosine|wsd|constant (minicpm defaults to wsd)")
    ap.add_argument("--micro-batches", type=int, default=1)
    ap.add_argument("--quantize-opt", action="store_true")
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    policy = policy_from_name(args.policy)
    mesh = {"host": make_host_mesh,
            "production": make_production_mesh,
            "multipod": lambda: make_production_mesh(multi_pod=True)}[
        args.mesh]()

    schedule = args.schedule or ("wsd" if args.arch == "minicpm_2b"
                                 else "cosine")
    opt_cfg = adamw.OptConfig(lr=args.lr, schedule=schedule,
                              warmup_steps=max(args.steps // 20, 5),
                              total_steps=args.steps)
    dcfg = DataConfig(vocab=cfg.vocab, seq_len=args.seq,
                      global_batch=args.batch, seed=args.seed,
                      input_mode=cfg.input_mode, d_model=cfg.d_model,
                      n_codebooks=cfg.n_codebooks)

    with mesh:
        step_fn_raw, state_sh, _, in_sh, out_sh = S.build_train_step(
            cfg, mesh, policy, opt_cfg=opt_cfg,
            shape_name="train_4k",  # sharding rules only; shapes come live
            micro_batches=args.micro_batches, quantize_opt=args.quantize_opt)
        params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
        params = jax.device_put(params, state_sh["params"])
        opt = adamw.init_opt_state(params, quantized=args.quantize_opt)
        opt = jax.device_put(opt, state_sh["opt"])
        state = {"params": params, "opt": opt}

        jit_step = jax.jit(step_fn_raw, in_shardings=in_sh,
                           out_shardings=out_sh, donate_argnums=(0,))

        def step_fn(state, batch, step):
            state, metrics = jit_step(state, batch, jnp.int32(step))
            return state, metrics

        ckpt = CheckpointManager(args.ckpt_dir, keep_n=3)
        start = ckpt.latest_step() or 0
        if start:
            state = ckpt.restore(start, state, state_sh)
            logging.info("restored from step %d", start)

        summary = train_loop(
            state, step_fn, lambda s: global_batch(dcfg, s), ckpt,
            TrainLoopConfig(total_steps=args.steps,
                            ckpt_every=args.ckpt_every),
            start_step=start, shardings=state_sh)
    print({k: v for k, v in summary.items() if k != "history"})
    if summary["history"]:
        first, last = summary["history"][0], summary["history"][-1]
        print(f"loss: {first['loss']:.4f} (step {first['step']}) -> "
              f"{last['loss']:.4f} (step {last['step']})")
    return summary


if __name__ == "__main__":
    enable_compile_cache()
    main()
