"""The environment of a virtual CPU mesh, for the repo-root harness scripts
(``__graft_entry__.py``) that start CPU-mesh subprocesses.
(tests/conftest.py keeps its own self-contained copy because it must run
before anything else is importable.)
"""

from __future__ import annotations

import os
import re


def cpu_env(n_devices: int | None = None) -> dict:
    """A copy of os.environ with XLA:CPU forced; with ``n_devices`` an
    n-device virtual host-platform mesh is requested."""
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    xla = re.sub(
        r"--xla_force_host_platform_device_count=\d+", "", env.get("XLA_FLAGS", "")
    ).strip()
    if n_devices is not None:
        xla = f"{xla} --xla_force_host_platform_device_count={n_devices}".strip()
    env["XLA_FLAGS"] = xla
    return env
