"""Production mesh construction.

A FUNCTION, not a module constant — importing this module never touches
jax device state (the dry-run sets XLA_FLAGS before any jax init).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def auto_mesh(shape, axes, devices=None):
    """``jax.make_mesh`` with every axis ``Auto``: the sharding rules
    (``distributed/sharding.py``) place arrays with ``NamedSharding``s
    and ``with_sharding_constraint`` and leave propagation to GSPMD,
    which ``Explicit`` axes (the ``make_mesh`` default) refuse."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False):
    """v5e-256 single pod (16x16) or 2 pods = 512 chips (2x16x16).

    Axes: ``data`` carries DP/FSDP + long-context KV sharding, ``model``
    carries TP/EP; ``pod`` (multi-pod) carries pure DP over DCN.
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()[:n]
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {shape} needs {n} devices, found {len(devices)} — "
            "launch via repro.launch.dryrun (it sets "
            "--xla_force_host_platform_device_count=512 before jax init)"
        )
    return auto_mesh(shape, axes, devices)


def make_host_mesh(shape=(2, 2), axes=("data", "model")):
    """Small mesh over host CPU devices (tests)."""
    return auto_mesh(shape, axes)


def make_serving_mesh(n: int, axis: str = "model"):
    """1-D tensor-parallel mesh for the paged serving path
    (``PagedServer(mesh=...)``, ``launch/serve.py --mesh model=N``).

    Uses the first ``n`` visible devices; on a CPU host, emulate with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=8`` *before* jax
    initializes.
    """
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"mesh {axis}={n} needs {n} devices, found {len(devices)} — "
            "on CPU set XLA_FLAGS=--xla_force_host_platform_device_count"
            f"={n} before starting the process"
        )
    return auto_mesh((n,), (axis,), devices[:n])
