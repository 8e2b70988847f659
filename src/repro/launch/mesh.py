"""Production mesh definitions.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run sets
``--xla_force_host_platform_device_count=512`` before first jax init, and
tests/benches must keep seeing 1 device.
"""

from __future__ import annotations

import numpy as np
import jax

__all__ = [
    "make_production_mesh",
    "make_host_mesh",
    "make_shard_mesh",
]


def _auto_mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the sharding annotations on the arrays drive placement
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    """Single pod: (16, 16) = ("data", "model") — 256 chips (TPU v5e pod).
    Multi-pod: (2, 16, 16) = ("pod", "data", "model") — 512 chips; the "pod"
    axis composes with "data" for cross-pod data parallelism (gradient
    all-reduce crosses pods once per step over DCN)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _auto_mesh(shape, axes)


def make_shard_mesh(num_shards: int, axis: str = "data") -> jax.sharding.Mesh:
    """1-D mesh over the first ``num_shards`` devices (distributed operators).

    Unlike :func:`make_host_mesh` this deliberately takes a device-count
    *subset*, so a partition over fewer parts than devices (e.g. 2 shards on
    an 8-device host platform) still maps one part per device.
    """
    devs = jax.devices()
    if num_shards > len(devs):
        raise ValueError(
            f"partition has {num_shards} parts but only {len(devs)} devices "
            "are available (set XLA_FLAGS=--xla_force_host_platform_device_"
            "count=N for host-platform testing)"
        )
    return jax.sharding.Mesh(np.asarray(devs[:num_shards]), (axis,))


def make_host_mesh(data: int = 1, model: int = 1) -> jax.sharding.Mesh:
    """Small mesh over whatever devices exist (tests/examples)."""
    n = len(jax.devices())
    if data * model > n:
        raise ValueError(f"mesh {data}x{model} needs {data*model} devices, have {n}")
    return _auto_mesh((data, model), ("data", "model"))
