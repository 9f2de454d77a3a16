"""PrefillWorker — chunked prefill as a standalone actor family.

Disaggregation splits the two phases of a request across replicas:
prefill is compute-bound (one big attention pass over the prompt),
decode is memory-bound (one token per step against a growing KV cache).
A PrefillWorker runs ONLY the first phase: it drives the same
page-granular chunk program the engine uses
(``make_lm_prefill_chunk_fn``) against a private single-slot paged
cache, keeps a per-worker prefix cache so shared-prompt arrivals skip
recompute, and ships the finished pages + first token out through the
shm object store for a decode engine to land via
``InferenceEngine.submit_prefilled``.

The class is deliberately actor-shaped but not actor-bound: the
constructor keeps only a picklable recipe (checkpoint + shape config —
same discipline as serve's ``_EngineServer``) and builds jax state
lazily on first use, so ``tpu_air.remote(PrefillWorker).remote(...)``
round-trips the instance through the pickled object store; plain local
construction works too (the unit-test path).
"""

from __future__ import annotations

import os
import time
from typing import Any, Dict, Optional

from tpu_air.faults import plan as _faults

from .kv_transfer import extract_kv_pages, payload_nbytes, payload_pages


class PrefillWorker:
    """One prefill replica: prompt ids in, ``{"kv": ObjectRef,
    "first_token", "prompt_len"}`` out."""

    def __init__(self, checkpoint, *, page_len: int = 16,
                 slot_len: int = 256, num_pages: Optional[int] = None,
                 dtype: Optional[str] = None, name: str = "prefill"):
        if slot_len % page_len != 0:
            raise ValueError("slot_len must be a multiple of page_len")
        self._checkpoint = checkpoint
        self.page_len = page_len
        self.slot_len = slot_len
        self.pages_per_slot = slot_len // page_len
        # headroom beyond one slot keeps evicted-prefix pages resident
        # across requests (the worker-side prefix cache's working set)
        self.num_pages = (num_pages if num_pages is not None
                          else 4 * self.pages_per_slot + 1)
        self._dtype = dtype
        self.name = name
        self._built = False
        self._prefills = 0
        self._pages_shipped = 0
        self._bytes_shipped = 0

    # -- lazy jax state (unpicklable) ----------------------------------------
    def _ensure_built(self) -> None:
        if self._built:
            return
        from tpu_air.engine.kvpool import PagedKVPool
        from tpu_air.engine.types import refuse_pages_only
        from tpu_air.models.lm.generate import (
            init_paged_cache,
            make_lm_prefill_chunk_fn,
        )

        self.model, self.params = self._checkpoint.get_model(
            dtype=self._dtype)
        refuse_pages_only(
            self.model, "a PrefillWorker ships K/V pages only; this model "
            "keeps per-slot recurrent state beside them")
        self.pool = PagedKVPool(self.num_pages, self.page_len, 1,
                                self.pages_per_slot)
        self.cache = init_paged_cache(
            self.model, 1, self.num_pages, self.page_len,
            self.pages_per_slot)
        self._chunk_fn = make_lm_prefill_chunk_fn(
            self.model, self.page_len, self.slot_len)
        self._built = True

    # -- the one rpc ----------------------------------------------------------
    def prefill(self, prompt, carrier: Optional[Dict[str, str]] = None
                ) -> Dict[str, Any]:
        """Run the prompt's chunked prefill, ship the pages, return the
        handoff descriptor.  ``carrier`` continues the submitter's trace:
        this records as the ``engine.prefill`` span of the request's
        single trace, on THIS process."""
        import numpy as np

        import jax.numpy as jnp
        import tpu_air
        from tpu_air.observability.tracing import task_span

        if _faults.enabled():
            # "slow" sleeps past the router's prefill timeout (gray failure:
            # alive but useless); "kill" dies the involuntary way — no
            # cleanup, the router sees the actor-death sentinel
            spec = _faults.perturb("prefill.worker", key=self.name)
            if spec is not None and spec.action == "kill":
                os._exit(1)
        self._ensure_built()
        prompt = [int(t) for t in prompt]
        n = len(prompt)
        if n == 0:
            raise ValueError("empty prompt")
        if n > self.slot_len:
            raise ValueError(
                f"prompt length {n} exceeds worker slot_len {self.slot_len}")
        with task_span("engine.prefill", carrier) as sp:
            t0 = time.monotonic()
            # budget=1: the worker never decodes — it needs the prompt's
            # pages plus the greedy first token, nothing more
            plan = self.pool.admit(0, prompt, 1)
            C = self.page_len
            pad = self.model.config.pad_token_id
            tok = None
            while not plan.done:
                p0 = plan.next_start
                ids = np.full((1, C), pad, np.int32)
                chunk = prompt[p0:p0 + C]
                ids[0, :len(chunk)] = chunk
                is_last = plan.chunks_done == len(plan.chunk_starts) - 1
                last_local = (n - 1 - p0) if is_last else (C - 1)
                row = self.pool.chunk_row(0, p0, plan.null_target)
                self.cache, tok = self._chunk_fn(
                    self.params, self.cache, jnp.asarray(ids),
                    jnp.int32(p0), jnp.int32(last_local), jnp.asarray(row),
                )
                plan.chunks_done += 1
            first = int(np.asarray(tok))
            self.pool.register(0, prompt)
            page_ids = self.pool.prompt_page_ids(0, n)
            payload = extract_kv_pages(self.cache, page_ids)
            # release AFTER extraction: prefix-registered pages stay
            # resident (refcounted) for the next shared-prefix arrival
            self.pool.release(0)
            ref = tpu_air.put(payload)
            nbytes = payload_nbytes(payload)
            self._prefills += 1
            self._pages_shipped += payload_pages(payload)
            self._bytes_shipped += nbytes
            if sp is not None and hasattr(sp, "attrs"):
                sp.attrs.update({
                    "prompt_len": n,
                    "pages": payload_pages(payload),
                    "kv_bytes": nbytes,
                    "chunks": len(plan.chunk_starts),
                    "worker": self.name,
                    "prefill_s": round(time.monotonic() - t0, 6),
                })
        return {"kv": ref, "first_token": first, "prompt_len": n}

    # -- observability ---------------------------------------------------------
    def stats(self) -> Dict[str, Any]:
        out = {
            "name": self.name,
            "role": "prefill",
            "prefills": self._prefills,
            "pages_shipped": self._pages_shipped,
            "bytes_shipped": self._bytes_shipped,
            "page_len": self.page_len,
            "slot_len": self.slot_len,
        }
        if self._built:
            out["kvpool"] = self.pool.stats()
        return out

    def ping(self) -> str:
        return "ok"
