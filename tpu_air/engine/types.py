"""Request/response types and config for the online inference engine.

The engine's unit of work is a :class:`Request` (one prompt + decode
budget); its unit of delivery is a :class:`ResponseStream` — emitted token
ids land on the stream the same engine step they are decoded, so callers
see time-to-first-token, not time-to-last-token.
"""

from __future__ import annotations

import queue
import threading
import time
from dataclasses import dataclass, field
from typing import Iterator, List, Optional, Sequence, Tuple, Union

from tpu_air.core.runtime import TpuAirError
from tpu_air.observability.profiler import phase


#: SLO priority classes, highest first.  Admission pops classes in this
#: order every engine step (iteration-granularity priority — the Orca
#: observation applied to admission, not just batching), and the serve
#: plane's admission controller sheds/queues the tail classes first under
#: overload (serve/admission.py).
PRIORITIES = ("interactive", "batch", "best_effort")


class EngineOverloadedError(TpuAirError):
    """Admission queue is full — backpressure, not failure.  The serve
    proxy maps this to HTTP 503 (the NoLiveReplicasError semantics): the
    client should retry, nothing is broken."""


class EngineDrainingError(EngineOverloadedError):
    """The engine is draining (zero-downtime rollout / scale-down): new
    submissions are refused while already-admitted work retires.  Same
    retry contract as overload — the proxy maps it to 503 and the router
    has already stopped sending new traffic here."""


class EngineClosedError(TpuAirError):
    """The engine was shut down with this request still queued/in flight."""


class RecurrentStateUnsupported(NotImplementedError, TpuAirError):
    """The model keeps rows a slot beside its pages (Mamba layers' recurrent
    state, window layers' rings of K and V) and the operation moves or shares
    K/V PAGES only: pages without the rows at that boundary are a wrong
    answer, so it is refused by name (preemption migration, disaggregated
    prefill, the mesh engine; ROADMAP.md M6, M4)."""


def keeps_slot_state(model) -> bool:
    """The model keeps, beside its pages, rows a slot (the one place the
    engines ask: models/lm/paged_cache.py has what it keeps)."""
    return bool(model.config.keeps_slot_rows)


def refuse_pages_only(model, why: str) -> None:
    """Refuse, by name, what ships or shards PAGES alone for a model that
    keeps per-slot state beside them; ``why`` says what was asked and why it
    cannot be given."""
    if keeps_slot_state(model):
        raise RecurrentStateUnsupported(f"{why} (ROADMAP.md M6, M4)")


class ExpertExchangeUnsupported(NotImplementedError, TpuAirError):
    """The model holds a SHARE of the experts its router scores (one
    expert-parallel rank: ``LMConfig.experts_held < num_experts``) and the
    operation would have to send a token's other assignments to the ranks
    that hold them.  That exchange is not built (ROADMAP.md M1): the mesh
    engine refuses by name instead of serving partial sums as whole ones."""


class RequestValidationError(ValueError, TpuAirError):
    """The request itself is malformed (unknown ``adapter_id``): the
    client's fault, not the server's.  A ValueError subclass so local
    callers can keep catching ValueError, but distinct across the actor
    boundary — the proxy maps THIS name to HTTP 400 while an application
    ValueError raised inside a replica stays a 500 (it signals a server
    bug, and must not be retried as if resubmitting could fix it)."""


@dataclass
class EngineConfig:
    """Dials for the KV pool and admission policy.

    * ``num_slots`` — S, the fixed decode batch width.  One persistent
      compiled step serves the whole engine lifetime; a slot is one
      in-flight sequence.
    * ``slot_len`` — L, max positions per sequence.  Admission requires
      ``len(prompt) + max_new_tokens <= slot_len``.
    * ``max_new_tokens`` — default per-request decode budget.
    * ``max_queue`` — queued (not yet admitted) request cap; beyond it
      ``submit`` raises :class:`EngineOverloadedError`.
    * ``page_len`` — positions per KV page of the block-table-paged pool
      (``tpu_air/engine/kvpool/``).  Multiples of 8 keep every page whole
      (8, 128) TPU tiles in the flat ``h*d`` layout.
    * ``num_pages`` — physical pages in the pool (page 0 is the pinned
      null page).  ``None`` → every slot can fill its ``slot_len``,
      ``num_slots * ceil(slot_len / page_len) + 1``; prefix sharing turns
      the saved pages into headroom.
    * ``prefix_cache`` — keep retired prompts' pages resident (radix over
      page chunks) so later prompts sharing a prefix skip that prefill and
      share the physical pages.
    * ``prefill_chunks_per_step`` — prefill chunks run per
      engine step, interleaved between pool decode steps.  1 (default)
      bounds how long any prefill work can delay in-flight decodes, so a
      long prompt streams in page-sized pieces while short requests keep
      decoding (flat TTFT under long-prompt arrival).
    * ``reorder_window`` — admission may look this many queue entries past
      a request that does not currently fit (no free KV pages) and admit
      later ones that do.  0 restores strict FIFO.
    * ``reserved_interactive_slots`` — keep this many FREE slots that only
      ``interactive``-class requests may take: a burst of batch/best-effort
      decodes can then never occupy the whole pool, so an arriving
      interactive request admits (and reaches its first token) without
      waiting for a lower-class slot to retire.  0 (default) disables the
      reserve — all classes compete for all slots.
    * ``queue_shares`` — fraction of ``max_queue`` each priority class may
      see the TOTAL queue grow to before its submits are rejected
      (engine-side shed).  Defaults: interactive 1.0, batch 0.85,
      best_effort 0.5 — as the queue fills, best-effort sheds first,
      then batch, and interactive keeps the full ``max_queue``.
    * ``eos_token_id`` — ``"model"`` (default): use the model config's
      ``eos_token_id``; ``None``: never early-stop (budget-only
      retirement); an int: that id.
    * ``adapter_slots`` — multi-tenant LoRA: rows in the resident adapter
      bank (0 disables adapters; single-chip engines only).  Row 0
      is the pinned zero adapter, so the bank holds ``adapter_slots``
      loadable tenants on top of it.  Per-request selection rides
      ``Request.adapter_id``; the decode step gathers each slot's delta
      the way it gathers the block table.
    * ``adapter_rank`` — LoRA rank r of the bank rows ``[d, r] x [r, V]``.
      Lower-rank adapters zero-pad into the bank; higher ranks are
      rejected at load.
    """

    num_slots: int = 8
    slot_len: int = 256
    max_new_tokens: int = 64
    max_queue: int = 256
    page_len: int = 16
    num_pages: Optional[int] = None
    prefix_cache: bool = True
    prefill_chunks_per_step: int = 1
    reorder_window: int = 4
    reserved_interactive_slots: int = 0
    queue_shares: Optional[dict] = None
    eos_token_id: Union[int, None, str] = "model"
    adapter_slots: int = 0
    adapter_rank: int = 4

    _DEFAULT_QUEUE_SHARES = {
        "interactive": 1.0, "batch": 0.85, "best_effort": 0.5,
    }

    def queue_cap(self, priority: str) -> int:
        """Total queue depth at which ``priority``-class submits shed."""
        shares = self.queue_shares or self._DEFAULT_QUEUE_SHARES
        return int(self.max_queue * float(shares.get(priority, 1.0)))

    def pages_per_slot(self) -> int:
        return -(-self.slot_len // self.page_len)

    def pool_pages(self) -> int:
        if self.num_pages is not None:
            return self.num_pages
        return self.num_slots * self.pages_per_slot() + 1


_DONE = object()


class ResponseStream:
    """Per-request token stream.

    The engine appends ids as they are decoded; callers either iterate
    (``for tok in stream: ...`` — blocks until each token arrives, ends at
    retirement) or join (``stream.result()`` — the full id list, raising if
    the request failed).  Emitted tokens INCLUDE the EOS id when early-stop
    triggered, matching offline ``generate`` (which emits EOS then pads).
    """

    def __init__(self, request_id: int):
        self.request_id = request_id
        self._q: "queue.Queue" = queue.Queue()
        self._tokens: List[int] = []
        self._done = threading.Event()
        self._error: Optional[BaseException] = None

    # -- engine side ---------------------------------------------------------
    def _emit(self, token: int) -> None:
        self._tokens.append(token)
        self._q.put(token)

    def _finish(self, error: Optional[BaseException] = None) -> None:
        self._error = error
        self._done.set()
        self._q.put(_DONE)

    # -- caller side ---------------------------------------------------------
    def __iter__(self) -> Iterator[int]:
        while True:
            item = self._q.get()
            if item is _DONE:
                if self._error is not None:
                    raise self._error
                return
            yield item

    def result(self, timeout: Optional[float] = None) -> List[int]:
        if not self._done.wait(timeout):
            raise TimeoutError(
                f"request {self.request_id} not finished after {timeout}s"
            )
        if self._error is not None:
            raise self._error
        return list(self._tokens)

    def tokens_so_far(self) -> List[int]:
        return list(self._tokens)

    @property
    def done(self) -> bool:
        return self._done.is_set()


@dataclass
class Request:
    """One admitted unit of work (internal; callers hold the stream)."""

    request_id: int
    prompt: Sequence[int]
    max_new_tokens: int
    stream: ResponseStream
    # SLO class (one of PRIORITIES): admission pops interactive first each
    # step, and the scheduler sheds the tail classes at lower queue depths
    priority: str = "interactive"
    # the request's one set of stamps (``time.monotonic()``): made here,
    # handed out by the scheduler (``pop_admissible`` stamps every request
    # of a round with one reading), first token emitted.  ``stats()``'
    # ``queue_wait_s`` / ``prefill_s`` / ``ttft_s``, the ``engine.first_token``
    # phase and the airtrace span tree are all read from these three.
    submitted_at: float = field(default_factory=time.monotonic)
    admitted_at: Optional[float] = None
    first_token_at: Optional[float] = None
    # airtrace: the carrier captured at submit while tracing is enabled ({}
    # where no span was active).  Not None says the request is traced: its
    # span tree is built at retirement (engine.py _emit_request_spans).
    trace_ctx: Optional[dict] = None
    # disaggregated serving (engine/dist/): a request whose prefill ran on
    # a PrefillWorker replica arrives with its first token and the prompt's
    # KV pages ({"first_token": int, "pages": {layer_path: {"k", "v"}}});
    # admission inserts the pages and goes straight to decode.  None for
    # the normal (engine-prefills) path.
    prefilled: Optional[dict] = None
    # preemption migration (engine ``migrate_out`` → ``submit_migrated``):
    # a stream that was already DECODING on a preempted replica arrives
    # with every client-visible token it emitted there plus the KV pages
    # covering its context ({"streamed": [int], "pages": {...},
    # "client_prompt_len": int}); admission inserts the pages, force-emits
    # the streamed tokens, and resumes decode at the exact cursor — zero
    # prefill chunks.  None for every other path.
    migrated: Optional[dict] = None
    # end-to-end deadline as ABSOLUTE unix-epoch milliseconds (a relative
    # budget would silently re-extend at every hop).  The proxy converts
    # the client's relative budget at admission; the scheduler expires
    # still-queued requests past it (DeadlineExceededError → HTTP 504)
    # rather than letting them occupy a slot they can no longer use.
    deadline_ms: Optional[float] = None
    # multi-tenant LoRA: the tenant adapter this request decodes under
    # (None = base model).  Validated against the loaded-adapter table at
    # submit (fail fast) AND re-resolved at admission (the adapter may
    # have been evicted while the request sat queued); ``adapter_row`` is
    # the resolved bank row the slot gathers each step (0 = zero adapter).
    adapter_id: Optional[str] = None
    adapter_row: int = 0
    # cost-attribution label (airwatch CostLedger): who to BILL this
    # request's tokens/chip-seconds to when that differs from the LoRA
    # tenant — the batch lane stamps ``batch:<job_id>`` here so offline
    # work never folds into the interactive "default" tenant.  Unlike
    # ``adapter_id`` it is never validated (a pure label, not a bank row);
    # billing uses ``tenant or adapter_id``.
    tenant: Optional[str] = None

    def first_token(self, at: float, chunks: int) -> Tuple[float, float]:
        """The request's first token was emitted at ``at``, after ``chunks``
        prefill chunks on this engine (0: its prefill ran elsewhere and
        ``at`` is its ``admitted_at``).  Stamps it, puts one
        ``engine.first_token`` event on a live profiler capture
        (docs/OBSERVABILITY.md) and returns the two parts of the request's
        TTFT in seconds, ``(queue_wait, prefill)``: submit to admission and
        admission to first token, what ``EngineMetrics.record_ttft`` takes."""
        self.first_token_at = at
        queue_s = self.admitted_at - self.submitted_at
        prefill_s = at - self.admitted_at
        with phase("engine.first_token", queue_us=int(queue_s * 1e6),
                   prefill_us=int(prefill_s * 1e6), prompt=len(self.prompt),
                   chunks=chunks):
            pass
        return queue_s, prefill_s
