"""Readings for a cell's correctness limit, many seeds in one process.

    python3 benchmarks/onchip/calibrate.py --workload minicpm_2b.reason \
        --seconds 15 --seeds 101 102 103 [--policy flexpe-fxp4]

Runs the cell as `run.py` does (short window, the cell's own load), with
no limit, once per seed, and prints each seed's widest logit gap: the
program's readings, or with `--policy` the control's, the program's own
lower-precision path in place of the configuration's. The benchmark's
runs never run this; the limits in `limits/` were set from its output.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--policy", default=None)
    ap.add_argument("--rate", type=float, default=None,
                    help="open loop: arrivals per second instead of the "
                         "mix's (the knee sweep)")
    args = ap.parse_args(argv)
    from onchip import harness, sut
    cell = harness.load_cell(args.workload)
    sut.import_program()
    devices = harness.check_devices(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    cell.per_layer = []
    if args.rate is not None:
        cell.mix = dict(cell.mix, rate_per_s=args.rate)
    limits = dict(cell.limits, logit_gap_max=math.inf)
    rows = []
    t = T_START
    for seed in args.seeds:
        res = harness.run_cell(cell, seed, args.seconds, False, t_start=t,
                               devices=devices, policy_name=args.policy,
                               limit_override=limits)
        t = time.perf_counter()
        row = {"seed": seed, "policy": args.policy or "configured",
               "gap": res["compared"]["logit_gap_max"]["value"],
               "failed": res["failed"], "attempted": res["attempted"],
               "metrics": {k: v["value"] for k, v in res["metrics"].items()},
               "memory_peak_bytes": res["device"]["memory_peak_bytes"]}
        rows.append(row)
        print("READING " + json.dumps(row), flush=True)
    print(json.dumps(rows), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
