"""Device mesh construction and sub-mesh leasing.

TPU-native replacement for the reference's GPU-count resource model
(SURVEY.md §1-L0/§2B): instead of "num_gpus=1" workers coordinated by NCCL,
compute runs as SPMD programs over a `jax.sharding.Mesh`, and the scheduler
hands out *chip leases* (runtime.py) that this module turns into sub-meshes.

Axis convention (logical → physical):

* ``data``  — batch / DP axis; gradient psum rides ICI (replaces DDP
  all-reduce, Model_finetuning…ipynb:cc-29,35).
* ``model`` — tensor-parallel axis (optional; reference has none, SURVEY.md
  §2C — kept a config change away, per §7).

A process holding a chip lease (``TPU_AIR_CHIP_IDS``) sees only its leased
devices, so concurrent Tune trials / predictor actors build disjoint
sub-meshes of the same slice (§7 hard-part 1).
"""

from __future__ import annotations

import math
from typing import Optional, Sequence

import numpy as np

from tpu_air.core.chips import ChipLeaseError, confined, leased_chip_ids


def _jax():
    import jax

    return jax


def visible_devices():
    """Devices this process may use: its lease, else all devices.

    A worker on a chip host was confined to its lease before its backend
    started (``core/chips.py``), so every device it sees is its own.
    Anywhere else — the virtual CPU mesh, or a driver-level lease over the
    global device list of a multi-host run — the lease indexes
    ``jax.devices()``.  A lease this process cannot honour is an error,
    never another device."""
    devs = _jax().devices()
    lease = leased_chip_ids()
    if lease is None:
        return list(devs)
    if confined():
        if len(devs) != len(lease):
            raise ChipLeaseError(
                f"confined to chips {lease} but the backend shows "
                f"{len(devs)} device(s)")
        return list(devs)
    beyond = [i for i in lease if not 0 <= i < len(devs)]
    if beyond:
        raise ChipLeaseError(
            f"lease {lease} names device(s) {beyond}; this process sees "
            f"{len(devs)}")
    return [devs[i] for i in lease]


def topology() -> dict:
    """Discover the local slice topology (the ``ray.init()`` analog's first
    job on TPU — SURVEY.md §3.6)."""
    jax = _jax()
    devs = jax.devices()
    info = {
        "platform": devs[0].platform,
        "num_devices": len(devs),
        "num_visible": len(visible_devices()),
        "device_kind": getattr(devs[0], "device_kind", "unknown"),
        "process_count": jax.process_count(),
    }
    coords = getattr(devs[0], "coords", None)
    if coords is not None:
        info["coords"] = [tuple(getattr(d, "coords", ())) for d in devs]
    return info


def make_mesh(
    axis_names: Sequence[str] = ("data",),
    shape: Optional[Sequence[int]] = None,
    devices=None,
):
    """Build a Mesh over the visible (leased) devices.

    ``shape`` may contain one ``-1`` (inferred).  Default: all devices on the
    first axis (pure DP, the reference's only training parallelism,
    SURVEY.md §2C).
    """
    jax = _jax()
    devs = list(devices) if devices is not None else visible_devices()
    n = len(devs)
    if shape is None:
        shape = [n] + [1] * (len(axis_names) - 1)
    shape = list(shape)
    if -1 in shape:
        i = shape.index(-1)
        known = math.prod(s for s in shape if s != -1)
        if n % known != 0:
            raise ValueError(f"cannot infer axis: {n} devices, shape {shape}")
        shape[i] = n // known
    if math.prod(shape) != n:
        raise ValueError(
            f"mesh shape {tuple(shape)} needs {math.prod(shape)} devices, "
            f"have {n} visible"
        )
    arr = np.array(devs).reshape(shape)
    return jax.sharding.Mesh(arr, tuple(axis_names))


def data_parallel_mesh(num_devices: Optional[int] = None):
    devs = visible_devices()
    if num_devices is not None:
        if num_devices > len(devs):
            raise ValueError(
                f"requested {num_devices} devices, only {len(devs)} visible"
            )
        devs = devs[:num_devices]
    return make_mesh(("data",), (len(devs),), devices=devs)


def batch_sharding(mesh, axis: str = "data"):
    """NamedSharding for [batch, ...] arrays: leading dim over the data axis."""
    jax = _jax()
    P = jax.sharding.PartitionSpec
    return jax.sharding.NamedSharding(mesh, P(axis))


def replicated_sharding(mesh):
    jax = _jax()
    P = jax.sharding.PartitionSpec
    return jax.sharding.NamedSharding(mesh, P())
