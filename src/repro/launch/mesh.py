"""Production mesh definitions.

Functions, not module constants: importing this module must never touch
jax device state (the dry-run sets XLA_FLAGS before first jax init).

Every mesh here uses ``AxisType.Auto`` axes: the model code places
activations by sharding constraints and lets GSPMD propagate the rest,
which ``jax.make_mesh``'s default explicit axes reject (an embedding
gather on a vocab-sharded table raises ``ShardingTypeError``).
"""

from __future__ import annotations

import jax
import numpy as np
from jax.sharding import AxisType, Mesh

__all__ = [
    "make_mesh",
    "make_production_mesh",
    "make_local_mesh",
    "make_serving_mesh",
    "replica_devices",
]


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with Auto (GSPMD-propagated) axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips/pod; 2 pods = 512 chips when ``multi_pod``."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(data: int | None = None, model: int = 1):
    """Small mesh over whatever devices exist (tests / CPU smoke)."""
    n = len(jax.devices())
    data = data if data is not None else max(1, n // model)
    return make_mesh((data, model), ("data", "model"))


def replica_devices(replica: int, tp: int) -> list:
    """The devices of serving replica ``replica`` at tensor-parallel degree
    ``tp``: ``[replica·tp, (replica+1)·tp)``, so replicas of one host sit
    on disjoint chips.  With more replicas than whole slices (a one-device
    CPU host) the slices are reused round-robin."""
    devs = jax.devices()
    n_slices = len(devs) // tp
    if tp < 1 or n_slices < 1:
        raise ValueError(
            f"tp={tp} needs 1..{len(devs)} devices; {len(devs)} visible "
            "(set XLA_FLAGS=--xla_force_host_platform_device_count=N for "
            "host-local meshes)"
        )
    s = replica % n_slices
    return devs[s * tp:(s + 1) * tp]


def make_serving_mesh(tp: int, devices=None):
    """A (1, tp) ("data", "model") mesh over ``devices`` (default: the
    first ``tp`` devices).

    Unlike :func:`make_local_mesh` this takes a device subset, so a tp=2
    engine on an 8-device host (``--xla_force_host_platform_device_count``)
    uses exactly 2 devices — the shape tested by the sharded-serving bit-
    identity suite.  Data parallelism is replica-level (``serving.replica``
    runs one engine per replica on its own device slice,
    :func:`replica_devices`), so the "data" axis stays 1 here.
    """
    devs = replica_devices(0, tp) if devices is None else list(devices)
    if len(devs) != tp:
        raise ValueError(f"tp={tp} needs {tp} devices, got {len(devs)}")
    return Mesh(np.array(devs).reshape(1, tp), ("data", "model"),
                axis_types=(AxisType.Auto,) * 2)
