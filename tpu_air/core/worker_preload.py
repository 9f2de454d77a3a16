"""Forkserver preload set.

A driver that computes with JAX on the CPU cannot fork its workers (the
child would inherit dead XLA threadpools), so ``Runtime._pick_ctx`` forks them
from a forkserver that has already imported the heavy module graph below
(jax's import alone is ~2s; pandas ~0.7s): each worker starts in ~10ms
instead of paying the imports again.  A driver on a chip host starts no
backend while chips are out on lease, and forks directly.

IMPORTANT: modules only — nothing here may initialize a jax backend or touch
devices; a worker starts its own backend on first use, after the worker loop
has confined it to its chip lease.
"""
# airlint: disable-file=RT003 — every preload import is optional: a failure
# here only means the worker pays that import lazily on first use

try:  # noqa: SIM105
    import numpy  # noqa: F401
except Exception:
    pass
try:
    import pandas  # noqa: F401
except Exception:
    pass
try:
    import jax  # noqa: F401
except Exception:
    pass
try:
    import sklearn.ensemble  # noqa: F401  (GBDT workloads, W8/W9)
except Exception:
    pass
try:
    import tpu_air.core.runtime  # noqa: F401
except Exception:
    pass
