"""tpu_air.engine — continuous-batching online inference.

A fixed pool of sequence slots over per-layer KV storage — block-table
PAGED pools with prefix sharing and chunked prefill (``kvpool/``) — one
persistent compiled decode step, admission/retirement between steps, and
per-token streaming back to callers.  The T5 family runs through
:class:`T5Engine`: a slot a request over one resident T5 decode state (a
ring of self-attention positions, a cross-attention row a slot).  See docs/SERVING.md for the architecture and the
token-parity contract with offline ``generate``.
"""

from .dist import DisaggRouter, MeshEngine, PrefillWorker, ShardedPagedPool
from .engine import InferenceEngine
from .kvpool import (
    AdmitPlan,
    BlockAllocator,
    KVPoolOOMError,
    PagedKVPool,
    PrefixCache,
    PrefixMatch,
)
from .metrics import EngineMetrics, snapshot_all
from .scheduler import Scheduler
from .slots import Slot, SlotManager
from .t5_engine import T5Engine, T5EngineConfig
from .types import (
    EngineClosedError,
    EngineConfig,
    EngineOverloadedError,
    Request,
    ExpertExchangeUnsupported,
    RecurrentStateUnsupported,
    RequestValidationError,
    ResponseStream,
)

__all__ = [
    "AdmitPlan",
    "BlockAllocator",
    "DisaggRouter",
    "EngineClosedError",
    "EngineConfig",
    "EngineMetrics",
    "EngineOverloadedError",
    "InferenceEngine",
    "KVPoolOOMError",
    "MeshEngine",
    "PagedKVPool",
    "PrefillWorker",
    "PrefixCache",
    "PrefixMatch",
    "Request",
    "ExpertExchangeUnsupported",
    "RecurrentStateUnsupported",
    "RequestValidationError",
    "ShardedPagedPool",
    "ResponseStream",
    "Scheduler",
    "Slot",
    "SlotManager",
    "T5Engine",
    "T5EngineConfig",
    "snapshot_all",
]
