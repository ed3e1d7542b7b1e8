"""On-chip serving benchmark: one cell of `BENCHMARK.json` per run.

Run from the root of a checkout:

    python3 benchmarks/onchip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

A configuration is `configs/<name>.json`, a traffic mix
`traffic/<name>.json`, a per-layer metric `metrics/<name>.py`; the
harness finds each by the name `BENCHMARK.json` gives it.
"""
