"""KV-page extraction/insertion — the payload of a prefill→decode handoff.

A PrefillWorker replica runs chunked prefill into its own private paged
cache, then pulls the prompt's pages out as host numpy arrays keyed by
layer path; the payload travels through the shm object store
(core/object_store.py — zero-copy for the numpy leaves via the arena) and
the decode engine writes the pages into freshly-allocated unshared slots
of ITS pool.  Page-granular device-to-device DMA is the on-TPU follow-up
(ROADMAP item 2); this host round-trip is the correctness path and the
CPU-rig test surface.
"""

from __future__ import annotations

from typing import Dict

import numpy as np

from tpu_air.faults import plan as _faults
from tpu_air.models.lm.paged_cache import map_pools, page_pools


class KVTransferError(ValueError):
    """A shipped KV payload does not fit the destination cache — wrong
    page count, page shape, or a dtype the destination cannot hold
    losslessly.  Raised *before* any page is written: a migrated stream
    that cannot be inserted cleanly falls back to journal replay instead
    of decoding from silently-corrupted pages."""


def extract_kv_pages(cache, page_ids) -> Dict[str, Dict[str, np.ndarray]]:
    """Pull pages ``page_ids`` (in prompt order) out of a paged cache as
    host arrays, every pool under its short name (models/lm/paged_cache.py):
    ``{layer_path: {"k": [n, page_len, h*d], "v": ...}}`` (a latent-attention
    layer: ``{"c": [n, page_len, latent_width]}``)."""
    if _faults.enabled():
        _faults.perturb("kv.transfer", key=str(len(page_ids)))
    ids = np.asarray(page_ids, np.int32)
    return {path: {short: np.asarray(pool[ids])
                   for short, pool in pools.items()}
            for path, pools in page_pools(cache).items()}


def _lossless_cast(src: np.dtype, dst: np.dtype) -> bool:
    """Can every value of ``src`` be represented in ``dst``?  ``safe``
    casting is exactly that rule; exotic dtypes numpy cannot reason about
    (possible with custom cache dtypes) count as lossy."""
    try:
        return bool(np.can_cast(src, dst, casting="safe"))
    except TypeError:
        return False


def validate_kv_payload(cache, page_ids, payload) -> None:
    """Check a shipped payload against the destination cache, raising
    :class:`KVTransferError` on any mismatch — truncated page counts,
    wrong page geometry, missing layers, or lossy dtype narrowing.  Runs
    before any write so a bad payload corrupts nothing."""
    n = len(page_ids)
    for path, pools in page_pools(cache).items():
        pages = payload.get(path)
        if pages is None:
            raise KVTransferError(
                f"kv payload missing layer {path!r} "
                f"(shipped layers: {sorted(payload)})")
        for name, dst in pools.items():
            if name not in pages:
                raise KVTransferError(
                    f"kv payload at {path!r} missing {name!r} pages")
            arr = np.asarray(pages[name])
            if arr.ndim != dst.ndim or arr.shape[0] != n:
                raise KVTransferError(
                    f"truncated kv payload at {path}/{name}: shipped "
                    f"shape {arr.shape} for {n} destination page ids")
            if tuple(arr.shape[1:]) != tuple(dst.shape[1:]):
                raise KVTransferError(
                    f"kv page shape mismatch at {path}/{name}: payload "
                    f"pages are {tuple(arr.shape[1:])}, destination pool "
                    f"holds {tuple(dst.shape[1:])}")
            src_dt, dst_dt = arr.dtype, np.dtype(dst.dtype)
            if src_dt != dst_dt and not _lossless_cast(src_dt, dst_dt):
                raise KVTransferError(
                    f"kv dtype mismatch at {path}/{name}: payload "
                    f"{src_dt} does not fit destination {dst_dt} "
                    "losslessly")


def insert_kv_pages(cache, page_ids, payload: Dict[str, Dict[str, np.ndarray]]):
    """Write shipped pages into ``page_ids`` of this cache (functional —
    returns the rebuilt cache; the caller rebinds its donated cache).
    ``page_ids[i]`` receives the payload's i-th page: id lists on both
    sides are in prompt order, so source and destination ids need not
    match — each engine allocates in its own pool.  Raises
    :class:`KVTransferError` (before writing anything) when the payload
    does not fit the destination cache."""
    import jax.numpy as jnp

    validate_kv_payload(cache, page_ids, payload)

    ids = jnp.asarray(np.asarray(page_ids, np.int32))

    return map_pools(cache, lambda path, short, pool: pool.at[ids].set(
        jnp.asarray(payload[path][short]).astype(pool.dtype)))


def payload_nbytes(payload: Dict[str, Dict[str, np.ndarray]]) -> int:
    """Total page bytes (K+V, or latent) in a handoff payload (the kv_transfer span attr)."""
    return sum(arr.nbytes for layer in payload.values()
               for arr in layer.values())


def payload_pages(payload: Dict[str, Dict[str, np.ndarray]]) -> int:
    first = next(iter(payload.values()), None)
    return int(next(iter(first.values())).shape[0]) if first else 0
