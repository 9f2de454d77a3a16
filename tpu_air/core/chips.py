"""Which TPU chips this host has, and which of them a process may open.

An attached chip belongs to one process at a time, and a JAX backend opens
every chip it can see.  So the runtime counts chips without starting a
backend (:func:`local_chip_count`), confines each leased worker to its lease
before that worker's backend starts, and keeps workers without a lease on the
CPU (:func:`confine`).  Inside a confined worker "all devices" means "my
lease", which is what the predictor and engine paths — which compute on the
default device — need.

A host without chips is the virtual CPU mesh the tests run on.  Nothing is
confined there: every process sees all virtual devices and a lease indexes
them (``parallel/mesh.py``).  Which of the two applies is read from what the
process can see — ``JAX_PLATFORMS`` and the device nodes — not from an option.

Stdlib only at import; JAX is touched only where a function says so.
"""

from __future__ import annotations

import glob
import os
import sys
import threading
from typing import Dict, List, Optional, Sequence

_GOOGLE_PCI_VENDOR = "0x1ae0"

#: the lease a worker holds (set by the worker loop, read by parallel/mesh.py)
LEASE_ENV = "TPU_AIR_CHIP_IDS"

# TPU_CHIPS_PER_PROCESS_BOUNDS for each lease size the runtime confines to.
# Every entry ran on a v5e 2x2 host (four one-chip, two two-chip and one
# four-chip process side by side); a size that is not here is refused.
_PROCESS_BOUNDS = {1: "1,1,1", 2: "1,2,1", 4: "2,2,1"}


class ChipLeaseError(RuntimeError):
    """The process cannot be given exactly the chips its lease names."""


def local_chip_count(root: str = "/") -> int:
    """TPU chips a process on this host could open, found without JAX: the
    ``/dev/accel*`` nodes of the older generations plus the numbered
    ``/dev/vfio`` groups that hold a Google PCI device (v5e and later).  PCI
    enumeration alone over-counts: a machine can show four chips on the bus
    and hand this container one.  ``root`` is injectable for tests."""
    n = len(glob.glob(os.path.join(root, "dev/accel[0-9]*")))
    for node in glob.glob(os.path.join(root, "dev/vfio/[0-9]*")):
        vendors = glob.glob(os.path.join(
            root, "sys/kernel/iommu_groups", os.path.basename(node),
            "devices/*/vendor"))
        for path in vendors:
            try:
                with open(path) as f:
                    if f.read().strip() == _GOOGLE_PCI_VENDOR:
                        n += 1
                        break
            except OSError:
                continue
    return n


def accelerator_expected() -> bool:
    """True when a JAX backend started in this environment would open the
    host's chips: ``JAX_PLATFORMS`` is unset or names ``tpu``, and there are
    chips to open."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.lower().split(","):
        return False
    return local_chip_count() > 0


def backend_live() -> bool:
    """Whether this process has started a JAX backend (never starts one)."""
    xb = sys.modules.get("jax._src.xla_bridge")
    return xb is not None and xb.backends_are_initialized()


def sync_jax_config_from_env() -> None:
    """A forked worker inherits an already-imported jax whose platform and
    cache-directory settings were read from the environment at import; bring
    them up to the environment as it is now.  No-op when jax is not imported
    (its import will read the environment itself)."""
    jax = sys.modules.get("jax")
    if jax is None:
        return
    for env, option in (("JAX_PLATFORMS", "jax_platforms"),
                        ("JAX_COMPILATION_CACHE_DIR",
                         "jax_compilation_cache_dir")):
        if os.environ.get(env):
            jax.config.update(option, os.environ[env])


def confine(chip_ids: Sequence[int]) -> None:
    """Worker-side, before the worker's backend starts: restrict this
    process to ``chip_ids`` — or, with no lease, to the CPU.  On a host
    without chips this only records the lease.  Raises
    :class:`ChipLeaseError` for a lease this process cannot honour."""
    ids = sorted(int(c) for c in chip_ids)
    if ids:
        os.environ[LEASE_ENV] = ",".join(str(c) for c in ids)
    else:
        # a chip-less worker must not inherit a lease from the parent env
        os.environ.pop(LEASE_ENV, None)
    if not accelerator_expected():
        return
    if backend_live():
        raise ChipLeaseError(
            "this process started a JAX backend before its chip lease was "
            "applied; it already holds whatever chips it could see")
    if not ids:
        os.environ["JAX_PLATFORMS"] = "cpu"
        sync_jax_config_from_env()
        return
    n_local = local_chip_count()
    bounds = _PROCESS_BOUNDS.get(len(ids))
    aligned = ids == list(range(ids[0], ids[0] + len(ids))) \
        and ids[0] % len(ids) == 0
    if bounds is None or not aligned or ids[-1] >= n_local or ids[0] < 0:
        raise ChipLeaseError(
            f"cannot confine a process to chips {ids} on a host with "
            f"{n_local}: a lease is an aligned block of "
            f"{sorted(_PROCESS_BOUNDS)} chips")
    os.environ["TPU_VISIBLE_CHIPS"] = os.environ[LEASE_ENV]
    os.environ["TPU_CHIPS_PER_PROCESS_BOUNDS"] = bounds
    os.environ["TPU_PROCESS_BOUNDS"] = "1,1,1"


def leased_chip_ids() -> Optional[List[int]]:
    """Chip ids granted to this process by the scheduler, or None (all)."""
    raw = os.environ.get(LEASE_ENV)
    if not raw:
        return None
    return [int(x) for x in raw.split(",") if x != ""]


def confined() -> bool:
    """True when libtpu was told to show this process exactly its lease."""
    lease = os.environ.get(LEASE_ENV)
    return bool(lease) and os.environ.get("TPU_VISIBLE_CHIPS") == lease


# -- what a worker's backend saw and compiled ---------------------------------

_compile_lock = threading.Lock()
_compile = {"compile_s": 0.0, "cache_hits": 0, "cold_compiles": 0}
_thread = threading.local()
_watching = False


def watch_compiles() -> None:
    """Count this process's backend-compile seconds (XLA compiling a program
    or loading it from the persistent cache; tracing and lowering nest, so
    they are left out) and its persistent-cache traffic through
    ``jax.monitoring`` (imports jax, starts no backend).  A *cold compile* is
    a cache miss that then compiled for at least the cache's own minimum — a
    program the cache stores, so a warm cache has none."""
    global _watching
    if _watching:
        return
    _watching = True
    import jax
    from jax import monitoring

    def on_event(event, **_):
        if event == "/jax/compilation_cache/cache_hits":
            with _compile_lock:
                _compile["cache_hits"] += 1
        elif event == "/jax/compilation_cache/cache_misses":
            _thread.missed = True

    def on_duration(event, seconds, **_):
        if event != "/jax/core/compile/backend_compile_duration":
            return
        with _compile_lock:
            _compile["compile_s"] += seconds
            if getattr(_thread, "missed", False):
                _thread.missed = False
                floor = jax.config.jax_persistent_cache_min_compile_time_secs
                if seconds >= floor:
                    _compile["cold_compiles"] += 1

    monitoring.register_event_listener(on_event)
    monitoring.register_event_duration_secs_listener(on_duration)


def device_report() -> Optional[Dict]:
    """What JAX sees from inside this process — platform, device kind, device
    count — beside the lease it was given and what it has compiled so far.
    None until the process has started a backend; never starts one."""
    if not backend_live():
        return None
    import jax

    devs = jax.devices()
    with _compile_lock:
        compiled = dict(_compile)
    return {
        "pid": os.getpid(),
        "chip_ids": leased_chip_ids() or [],
        "platform": devs[0].platform,
        "device_kind": devs[0].device_kind,
        "num_devices": len(devs),
        **compiled,
    }
