"""tpu_air.engine.kvpool — block-table-paged KV cache for the engine.

Where a slot's K/V live is decided here and nowhere else: a pool of
fixed-size KV *pages* `[P, page_len, h*d]` per layer plus a host-side block
table mapping each slot's logical positions onto physical pages.  Three
pieces:

* :class:`BlockAllocator` — refcounted page ids over the device pool, with
  free-list reuse (host bookkeeping; the device arrays live in the engine's
  donated cache and never move).
* :class:`PrefixCache` — a radix-over-page-chunks index so prompts sharing
  a prefix (system prompts, few-shot templates) map their leading block
  table entries to the SAME physical pages; copy-on-write on the first
  divergent append into a shared page.
* :class:`PagedKVPool` — the per-engine orchestration: block tables,
  admission planning (which chunks still need prefill after prefix hits),
  CoW resolution and retirement refcounting.

Device-side companions (paged cache init, the paged decode step, the
chunked-prefill unit, the CoW page copy) live in
``tpu_air/models/lm/generate.py``; the page layout keeps the flat
``[*, page_len, h*d]`` last-two-dims contract of ``ops/decode_attention.py``
— ``page_len`` is a multiple of 8 so every page is whole (8, 128) tiles.
"""

from .allocator import BlockAllocator, KVPoolOOMError
from .pool import AdmitPlan, PagedKVPool
from .prefix import PrefixCache, PrefixMatch

__all__ = [
    "AdmitPlan",
    "BlockAllocator",
    "KVPoolOOMError",
    "PagedKVPool",
    "PrefixCache",
    "PrefixMatch",
]
