"""Run one cell of BENCHMARK.json once and print its result line.

    python3 benchmarks/onchip/run.py --workload minicpm_2b.reason \
        --seed 7 --seconds 30 --trace 0

Exits non-zero, printing no result, where JAX finds no TPU or fewer
chips than the cell asks for, or where the program under test (`src/`)
is not in the checkout. The last line of standard output is one JSON
object: correct, attempted, failed, metrics, device (and with --trace 1
a breakdown), then the numbers compared with their limits.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be >= 0")

    from onchip import harness, sut
    cell = harness.load_cell(args.workload)
    try:
        sut.import_program()
    except FileNotFoundError as e:
        harness.log(f"run: {e}")
        return 2
    devices = harness.check_devices(cell.chips)
    from repro.launch.compile_cache import enable_compile_cache
    import jax
    cache = enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    dev = devices[0]
    harness.log(f"device: {dev.platform} {dev.device_kind} x{len(devices)};"
                f" jax {jax.__version__}; compile cache {cache}")
    result = harness.run_cell(cell, args.seed, args.seconds,
                              bool(args.trace), t_start=T_START,
                              devices=devices)
    result["compared"] = result.pop("compared")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
