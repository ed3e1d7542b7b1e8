import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede any jax import: jax locks the device count on first init.

"""Multi-pod dry-run: AOT lower + compile every (arch x shape x mesh) cell.

For each cell:
    with mesh:  jit(step).lower(specs).compile()
then record memory_analysis() (proves the partitioned program fits),
cost_analysis() (FLOPs/bytes for the roofline) and the collective schedule
parsed from the compiled HLO (collective bytes for the roofline's third
term). Output: one JSON per cell under results/dryrun/.

Usage:
    python -m repro.launch.dryrun --arch mistral_nemo_12b --shape train_4k
    python -m repro.launch.dryrun --all [--multi-pod] [--policy flexpe-fxp8]
"""
import argparse
import json
import re
import time
import traceback

import jax

from ..configs.base import ARCH_IDS, SHAPES, arch_shapes, get_config
from ..core.precision import PrecisionPolicy
from . import steps as S
from .mesh import make_production_mesh
from .roofline import DRYRUN_DEVICE_KIND

_DTYPE_BYTES = {"f64": 8, "f32": 4, "bf16": 2, "f16": 2, "s64": 8, "u64": 8,
                "s32": 4, "u32": 4, "s16": 2, "u16": 2, "s8": 1, "u8": 1,
                "pred": 1, "f8e4m3fn": 1, "f8e5m2": 1, "s4": 1, "u4": 1}

_COLL_RE = re.compile(
    r"=\s+([\w(][\w\d\[\],{}() ]*?)\s+"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(?:-start)?\(")
_SHAPE_RE = re.compile(r"(\w+)\[([\d,]*)\]")


def _type_bytes(type_str: str) -> int:
    total = 0
    for dt, dims in _SHAPE_RE.findall(type_str):
        if dt not in _DTYPE_BYTES:
            continue
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dt]
    return total


def parse_collectives(hlo_text: str) -> dict:
    """Per-device collective bytes by op kind, from compiled (SPMD) HLO.
    Printed shapes are per-device partitioned shapes; all-reduce is charged
    2x (ring reduce-scatter + all-gather)."""
    out = {}
    for m in _COLL_RE.finditer(hlo_text):
        type_str, kind = m.group(1), m.group(2)
        nbytes = _type_bytes(type_str)
        factor = 2.0 if kind == "all-reduce" else 1.0
        rec = out.setdefault(kind, {"count": 0, "bytes": 0.0})
        rec["count"] += 1
        rec["bytes"] += nbytes * factor
    return out


def _policy(name: str):
    if name == "bf16":
        return PrecisionPolicy.bf16()
    if name.startswith("flexpe-fxp"):
        return PrecisionPolicy.flexpe(int(name.replace("flexpe-fxp", "")))
    raise ValueError(name)


def dryrun_cell(arch: str, shape: str, *, multi_pod: bool = False,
                policy_name: str = "flexpe-fxp8", fsdp=None,
                remat: bool = True, micro_batches: int | None = None,
                remat_policy: str = "full", policy=None) -> dict:
    cfg = get_config(arch)
    spec = arch_shapes(arch)[shape]
    cell = dict(arch=arch, shape=shape,
                mesh="2x16x16" if multi_pod else "16x16",
                policy=policy_name)
    if "skip" in spec:
        return dict(cell, status="skipped", reason=spec["skip"])

    if policy is None:
        policy = _policy(policy_name)
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    t0 = time.time()
    with mesh:
        if spec["kind"] == "train":
            fsdp_eff = True if fsdp is None else fsdp
            big = cfg.name == "grok-1-314b"
            mb_auto = {"grok-1-314b": 8 if multi_pod else 16,
                       "deepseek-moe-16b": 2}.get(cfg.name, 1)
            mb = micro_batches if micro_batches is not None else mb_auto
            fn, st_sh, specs, in_sh, out_sh = S.build_train_step(
                cfg, mesh, policy, fsdp=fsdp_eff, shape_name=shape,
                remat=remat, micro_batches=mb, quantize_opt=big,
                remat_policy=remat_policy,
                accum_dtype=__import__("jax.numpy", fromlist=["bfloat16"]
                                       ).bfloat16 if big else None)
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh, donate_argnums=(0,)
                              ).lower(specs["state"], specs["batch"],
                                      specs["step"])
        elif spec["kind"] == "prefill":
            _big_serve = cfg.name in ("grok-1-314b", "deepseek-coder-33b")
            fsdp_eff = _big_serve if fsdp is None else fsdp
            fn, p_sh, specs, in_sh, out_sh = S.build_prefill_step(
                cfg, mesh, policy, fsdp=fsdp_eff, shape_name=shape)
            lowered = jax.jit(fn, in_shardings=in_sh,
                              out_shardings=out_sh).lower(specs["params"],
                                                          specs["batch"])
        else:  # decode
            _big_serve = cfg.name in ("grok-1-314b", "deepseek-coder-33b")
            fsdp_eff = _big_serve if fsdp is None else fsdp
            fn, p_sh, specs, in_sh, out_sh = S.build_serve_step(
                cfg, mesh, policy, fsdp=fsdp_eff, shape_name=shape)
            lowered = jax.jit(fn, in_shardings=in_sh, out_shardings=out_sh,
                              donate_argnums=(1,)).lower(
                specs["params"], specs["cache"], specs["tokens"],
                specs["n_valid"])
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    mem = compiled.memory_analysis()
    cost = compiled.cost_analysis()
    hlo_txt = compiled.as_text()
    colls = parse_collectives(hlo_txt)
    # XLA:CPU lowers bf16 dots as convert->f32 sgemm and hoists the convert
    # of scan residual stacks into the forward loop, keeping an extra f32
    # copy of each stacked bf16 residual. TPU's MXU consumes bf16 directly,
    # so these f32 stacks do not exist on the target. Quantify them:
    cpu_artifact = 0
    seen = set()
    for mm in re.finditer(r"f32\[(" + str(cfg.n_layers)
                          + r"),([\d,]+)\]", hlo_txt):
        dims = (mm.group(1) + "," + mm.group(2))
        if dims in seen:
            continue
        seen.add(dims)
        n = 1
        for d_ in dims.split(","):
            n *= int(d_)
        if n * 4 > (64 << 20):  # only count stacks > 64 MiB
            cpu_artifact += n * 2  # f32 copy costs 2 bytes/elem over bf16

    # --- cost calibration ---------------------------------------------
    # XLA cost_analysis counts while-loop bodies ONCE (verified: an
    # 8-iteration scan reports 1/8 the flops of its unrolled equivalent),
    # so the scanned-layer numbers undercount by ~n_layers. Lower two small
    # UNROLLED variants and extrapolate linearly in depth:
    #   total(L) = f(l1) + (f(l2)-f(l1))/(l2-l1) * (L-l1)
    # Memory analysis stays from the full scanned compile (loop buffers are
    # correctly sized there).
    import dataclasses as _dc

    from ..models import model as M

    def _small_cost(lx):
        cfg_x = _dc.replace(cfg, n_layers=lx)
        M.SCAN_UNROLL = True
        try:
            with mesh:
                if spec["kind"] == "train":
                    fn2, _, sp2, ish2, osh2 = S.build_train_step(
                        cfg_x, mesh, policy, fsdp=fsdp_eff, shape_name=shape,
                        remat=remat, micro_batches=1,
                        remat_policy=remat_policy)
                    c2 = jax.jit(fn2, in_shardings=ish2, out_shardings=osh2,
                                 donate_argnums=(0,)).lower(
                        sp2["state"], sp2["batch"], sp2["step"]).compile()
                elif spec["kind"] == "prefill":
                    fn2, _, sp2, ish2, osh2 = S.build_prefill_step(
                        cfg_x, mesh, policy, fsdp=fsdp_eff, shape_name=shape)
                    c2 = jax.jit(fn2, in_shardings=ish2,
                                 out_shardings=osh2).lower(
                        sp2["params"], sp2["batch"]).compile()
                else:
                    fn2, _, sp2, ish2, osh2 = S.build_serve_step(
                        cfg_x, mesh, policy, fsdp=fsdp_eff, shape_name=shape)
                    c2 = jax.jit(fn2, in_shardings=ish2, out_shardings=osh2,
                                 donate_argnums=(1,)).lower(
                        sp2["params"], sp2["cache"], sp2["tokens"],
                        sp2["n_valid"]).compile()
        finally:
            M.SCAN_UNROLL = False
        ca2 = c2.cost_analysis()
        cl2 = parse_collectives(c2.as_text())
        return (ca2.get("flops", 0.0), ca2.get("bytes accessed", 0.0),
                sum(v["bytes"] for v in cl2.values()))

    if cfg.family == "hybrid":
        l1, l2 = cfg.attn_every, 2 * cfg.attn_every
    else:
        l1, l2 = 2, 4
    f1 = _small_cost(l1)
    f2 = _small_cost(l2)
    flops_cal, bytes_cal, coll_cal = (
        a + (b - a) / (l2 - l1) * (cfg.n_layers - l1)
        for a, b in zip(f1, f2))

    return dict(
        cell, status="ok", n_chips=n_chips,
        device_kind=DRYRUN_DEVICE_KIND,
        lower_s=round(t_lower, 2), compile_s=round(t_compile, 2),
        flops_per_device=flops_cal,
        bytes_per_device=bytes_cal,
        collective_bytes_per_device=coll_cal,
        raw_scanned=dict(
            flops=cost.get("flops", 0.0),
            bytes=cost.get("bytes accessed", 0.0),
            collective_bytes=sum(v["bytes"] for v in colls.values()),
            note="while bodies counted once; see calibrated fields"),
        collectives=colls,
        memory=dict(
            argument_bytes=mem.argument_size_in_bytes,
            output_bytes=mem.output_size_in_bytes,
            temp_bytes=mem.temp_size_in_bytes,
            alias_bytes=mem.alias_size_in_bytes,
            peak_estimate=(mem.argument_size_in_bytes
                           + mem.output_size_in_bytes
                           + mem.temp_size_in_bytes
                           - mem.alias_size_in_bytes),
            cpu_backend_f32_artifact=cpu_artifact,
            tpu_peak_estimate=(mem.argument_size_in_bytes
                               + mem.output_size_in_bytes
                               + mem.temp_size_in_bytes
                               - mem.alias_size_in_bytes - cpu_artifact),
        ),
    )


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--policy", default="flexpe-fxp8")
    ap.add_argument("--fsdp", type=int, default=-1,
                    help="-1 auto, 0 off, 1 on")
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    args = ap.parse_args()

    cells = []
    if args.all:
        for arch in ARCH_IDS:
            for shape in SHAPES:
                cells.append((arch, shape))
    else:
        assert args.arch and args.shape
        cells.append((args.arch, args.shape))

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    os.makedirs(args.out, exist_ok=True)
    failures = 0
    for arch, shape in cells:
        for mp in meshes:
            tag = f"{arch}.{shape}.{'2x16x16' if mp else '16x16'}.{args.policy}"
            fsdp = None if args.fsdp < 0 else bool(args.fsdp)
            try:
                rec = dryrun_cell(arch, shape, multi_pod=mp,
                                  policy_name=args.policy, fsdp=fsdp,
                                  remat=not args.no_remat)
            except Exception as e:
                rec = dict(arch=arch, shape=shape,
                           mesh="2x16x16" if mp else "16x16",
                           status="error", error=f"{type(e).__name__}: {e}",
                           traceback=traceback.format_exc()[-2000:])
                failures += 1
            with open(os.path.join(args.out, tag + ".json"), "w") as f:
                json.dump(rec, f, indent=1)
            print(json.dumps({k: rec[k] for k in
                              ("arch", "shape", "mesh", "status")}
                             | ({"compile_s": rec.get("compile_s")}
                                if rec.get("status") == "ok" else
                                {"why": rec.get("reason",
                                                rec.get("error"))})),
                  flush=True)
    raise SystemExit(1 if failures else 0)


if __name__ == "__main__":
    main()
