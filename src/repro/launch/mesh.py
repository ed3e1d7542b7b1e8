"""Production mesh construction.

Mesh is built by a FUNCTION (never at import) so importing this module
never touches jax device state. Single-pod: (data=16, model=16) = 256 chips
(one TPU v5e pod-slice); multi-pod: (pod=2, data=16, model=16) = 512 chips.
The `pod` axis carries only data parallelism (gradient all-reduce crosses
the inter-pod DCI once per step); `model` stays inside a pod where ICI
bandwidth lives — the standard >=2-pod layout.
"""
from __future__ import annotations

import math

import jax
from jax.sharding import Mesh


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = math.prod(shape)
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, have {len(devices)} — the "
            "dry-run entrypoint must set XLA_FLAGS="
            "--xla_force_host_platform_device_count=512 before importing jax")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes),
                         devices=devices[:n])


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    mp = math.gcd(model_parallel, n)
    return jax.make_mesh((n // mp, mp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)


def make_tp_mesh(tp: int = 1, replica: int = 0) -> Mesh:
    """Serving tensor-parallel mesh: (data=1, model=tp) over replica
    `replica`'s own `tp` devices, devices [replica*tp, (replica+1)*tp).
    Unlike `make_host_mesh` this never degrades the degree — asking for
    more model parallelism than there are devices is a configuration
    error, not a preference. So is a fleet larger than the host's chips:
    a replica past the last whole group of devices is refused rather
    than stacked on chips another replica already holds. Only CPU
    devices, which are host threads rather than chips, may be shared:
    there the index wraps round onto groups already in use, so tests
    can build fleets larger than the host."""
    devices = jax.devices()
    if tp < 1:
        raise ValueError(f"tp must be >= 1, got {tp}")
    if len(devices) < tp:
        raise RuntimeError(
            f"tensor-parallel serving with tp={tp} needs {tp} devices, have "
            f"{len(devices)} — on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={tp} before importing "
            "jax")
    groups = len(devices) // tp
    if replica >= groups and devices[0].platform != "cpu":
        raise RuntimeError(
            f"replica {replica} at tp={tp} needs devices "
            f"[{replica * tp}, {(replica + 1) * tp}), but the host has "
            f"{len(devices)} {devices[0].platform} devices ({groups} "
            f"replica(s) of tp={tp}); replicas never share a chip")
    start = (replica % groups) * tp
    return jax.make_mesh((1, tp), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2,
                         devices=devices[start:start + tp])
