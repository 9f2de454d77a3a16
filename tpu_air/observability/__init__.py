"""tpu_air.observability — dashboard, cluster state, profiling hooks.

The reference stack promotes the Ray Dashboard at 127.0.0.1:8265 as "a vital
observability tool" (Model_finetuning…ipynb:cc-9; Install_locally.md:64-67).
The TPU-native equivalent is a JSON status service + prometheus text
endpoint over the driver runtime's live state (SURVEY.md §2B dashboard row,
§5 tracing notes).
"""

from .dashboard import start_dashboard, stop_dashboard, snapshot
from .profiler import phase, profile_trace
from . import perf
from . import postmortem
from . import slo
from . import timeseries
from . import tracing
from . import trace_export
from . import watch

__all__ = [
    "perf",
    "phase",
    "postmortem",
    "profile_trace",
    "slo",
    "snapshot",
    "start_dashboard",
    "stop_dashboard",
    "timeseries",
    "trace_export",
    "tracing",
    "watch",
]
