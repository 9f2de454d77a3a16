"""The continuous-batching inference engine.

One :class:`InferenceEngine` owns a fixed pool of ``S`` sequence slots and
keeps a single persistent jit-compiled decode step alive over that pool for
its whole lifetime (the cache is donated — device KV updates in place,
never copied).  The slots' K/V live in per-layer page pools
``[P, page_len, h*d]`` plus a host block table mapping slot positions onto
refcounted pages (engine/kvpool/).  Prompts prefill in page-sized CHUNKS
interleaved between decode steps (one compiled chunk program covers every
prompt length); prompts sharing a cached prefix skip the covered chunks and
share the physical pages, copy-on-write on the first divergent append.

Requests flow through three host-side phases, all of them run UNDER the
decode step the device is computing (see "one step in flight" below):

1. **admission** — FIFO from the scheduler queue, gated on KV-page
   capacity with a bounded reorder window so a big blocked head can't
   starve small requests behind it.
2. **prefill** — up to ``prefill_chunks_per_step`` chunks per engine
   step, shortest-remaining-prompt first, so short-request TTFT stays flat
   while long prompts stream in.
3. **decode + retirement** — one fixed-shape step over all ``S`` rows;
   a row that emits EOS (inclusive) or exhausts its budget is released on
   the next host visit (its private pages return to the free list; its
   prompt's pages stay resident in the prefix cache for future hits).

THREE PROGRAMS, chosen by what the iteration holds and by nothing else (no
option, no model's name).  A chunk alone and a decode step alone are each a
whole pass over the model and stream every weight from HBM; a decode step at
``S`` rows is far under the chip's ridge, so the chunk's ``page_len`` rows
cost its weight products next to nothing when they ride the same pass:

* a chunk to run AND rows to decode: ONE program, the MIXED STEP
  (``lm_paged_mixed_step``: models/lm/generate.py): every weight matrix is
  applied once to the ``S`` decoding rows and the chunk's ``page_len`` rows
  together, and only the sequence mixers take the two parts apart (paged
  single-token attention / state update for the step's rows, dense causal
  attention over the slot's pages / the scan from its carried state for the
  chunk's).  It carries the chunk the prefill quantum picks first; with
  ``prefill_chunks_per_step`` > 1 the others (of other prompts: a prompt's
  next chunk waits for the one that rides) run alone, ahead of it;
* a chunk and no row to decode (a cold start, a drain's last prompts): the
  chunk program (``lm_prefill_chunk``), alone;
* rows and no chunk: the decode step (``lm_paged_decode_step``).

The mixed step is compiled when the engine is built (``_compile_mixed_step``:
no single request reaches it).  ``stats()`` counts ``chunks_fused``,
``chunks_alone`` (their sum is every chunk run) and ``mixed_steps``.

ONE STEP IN FLIGHT.  The loop runs one decode step ahead of its host: step
N+1 is issued from step N's tokens as they lie on the device, and only then
is step N read back, emitted and retired from.  The step's ``tok`` and
``pos`` live on the device (``advance_rows_body`` moves them on behind every
step, ``set_row_body`` sets the row that joins or leaves), the masked block
table is uploaded again only when a row joined or left, and a prompt's last
chunk hands its first token to a step on the device and is read afterwards:
issued alone it joins the step of the same iteration; riding step N+1 it
joins step N+2 (its token does not exist before N+1 ends) and is read with
N+1.  So an iteration is: admit; make the first chunk ready and issue the
others alone, behind the step in flight; issue step N+1 (mixed, if a chunk
rides); read step N and emit it; read the first tokens of the prompts that
just finished (last chunk issued alone this iteration, or riding step N).
What follows from reading one step late:

* budgets are host state, so a row whose budget ends with step N is not in
  step N+1, and a step is issued only if some row has a token left after
  the one in flight;
* a row that ends on EOS in step N is learnt of after N+1 went out: it rides
  N+1 once.  That write lands past its prompt, in a page reserved for it at
  admission and private to it (``register`` publishes whole prompt pages
  only), its pages are released after N+1 was issued, and whatever is given
  them next runs behind N+1 on the device (the cache is donated through
  every program).  Its output in N+1 is discarded, never emitted or counted;
* a step whose rows all ended before it was read is dropped unread
  (``steps_dropped``), as is the step in flight at ``close``; the first
  token of a prompt whose last chunk rode a dropped step is still read;
* whatever reads or changes slot state from outside the loop (migration,
  weight swap and rollback, adapter load and unload) first settles the step
  in flight: reads it and emits it (``_settle``).

RECURRENT LAYERS.  A model with Mamba layers keeps, beside the pages of its
attention layers, a row of state a slot that no block table reaches (the
cache's format: models/lm/paged_cache.py).  A slot owns its pages by table
and its state row by index, and three things pages gave for free are done by
hand: the decode step holds the state of every row it does not decode (a row
mid-prefill rides every step issued between its chunks; the program takes
``pos > 0`` as the row being live, which is exactly the rows ``_issue``
leaves in the table); a prompt's first chunk starts the row from zeros; a
last chunk's padding never enters it.  In a mixed step the chunk's slot is
such a held row of the step's half AND the row the chunk's half advances:
the chunk starts from the state the slot holds as the program begins, and
its write of the row is the one that lands (as its page write lands beside
the step's scatter, which for that row goes to the null page).  The row that ends on EOS and rides
step N+1 advances a state nobody reads again: the next tenant's first chunk
zeroes it.  Prefix sharing is off for such a model (a page hit without the
state at that boundary is wrong), seen in the model and not set by an option,
and whatever ships pages alone is refused (``RecurrentStateUnsupported``).

WINDOW LAYERS.  A model whose window layers sit beside full ones
(``LMConfig.layer_mixers``) keeps the window layers' K and V as a RING of
rows a slot (models/lm/paged_cache.py: ``window_key`` / ``window_value [S, R,
g*d]``, no pool, no table) and the full layers' as pages.  The ring is rows a
slot like a Mamba layer's state, and the engine treats it so: the chunk
program is told its ``slot=``, prefix sharing is off by the model, what ships
pages alone is refused.  Nothing is zeroed for a new tenant: an entry's
position follows from where the row IS, so what a former tenant left reads as
older than any window.  A row that rides a step (free, mid-prefill) has no
null page to scatter to: the step drops its write (``valid_len``).  The pool's
bytes are the full layers' alone; ``stats()`` counts the rings beside them
(``window_ring_bytes``, ``kv_page_bytes``, ``window_ring_bytes_as_pages``) and,
as each step is read back, the positions x layers its cached reads had live by
kind (``window_positions_live``, ``kv_page_positions_live``; ``_alone``: over
the steps that carried no chunk).

LATENT ATTENTION, HELD EXPERTS.  A latent-attention layer keeps ONE page
pool (the normalised latent and the shared roped key a position:
models/lm/modeling.LatentAttention) where an attention layer keeps K and V
pools; table, null page, prefix sharing, copy-on-write and migration work on
it by page as they do on those (models/lm/paged_cache.py has each kind's
pools).  ``stats()``
counts the positions the decode steps had live (``latent_positions_live``:
the sum of the decoding rows' lengths as each step is read) and the pages
those lengths span (``latent_pages_read``: what the absorbed read visits a
layer where it reads the pool in place, ops/decode_attention.py) beside what
the slots could hold (``latent_positions_pool``: ``num_slots x slot_len``,
what a step's gather writes out where the pool is gathered).  A model that holds a
share of the experts its router scores (``LMConfig.experts_held``) returns,
behind the assignments to its held experts, those it sent elsewhere; both
are counted, only the first are computed (``moe_assignments_elsewhere``).

Depth is one and fixed; no option selects the behaviour.

Correctness anchor: with greedy decoding the engine's emitted tokens are
token-identical to offline ``generate()`` on the same prompts —
tests/test_engine.py pins this on CPU for burst, staggered and trickle
arrival schedules.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from tpu_air.models.lm.generate import (
    init_paged_cache,
    make_lm_paged_decode_step_fn,
    make_lm_paged_mixed_step_fn,
    make_lm_prefill_chunk_fn,
    make_lm_step_feed_fns,
    make_page_copy_fn,
)
from tpu_air.models.lm.paged_cache import (page_pool_bytes,
                                            recurrent_state_bytes,
                                            state_rows_move_in_place,
                                            window_ring_bytes)

from tpu_air.faults import plan as _faults
from tpu_air.observability import tracing as _tracing
from tpu_air.observability import perf as _perf
from tpu_air.observability.profiler import phase

from .kvpool import PagedKVPool
from .metrics import EngineMetrics, unregister
from .scheduler import Scheduler
from .slots import Slot, SlotManager
from .types import (
    PRIORITIES,
    EngineClosedError,
    EngineConfig,
    EngineDrainingError,
    EngineOverloadedError,
    Request,
    RequestValidationError,
    ResponseStream,
    keeps_slot_state,
    refuse_pages_only,
)


class _Chunk(NamedTuple):
    """One prefill chunk made ready on the host: the slot, where the chunk
    starts, and the chunk program's arguments behind the cache (``ids``,
    ``p0``, ``last_local``, ``table_row``) as they go to the device;
    ``tokens``: how many of its positions hold a token of the prompt."""

    slot: Slot
    start: int
    args: Tuple[Any, ...]
    adapter_row: int = 0
    tokens: int = 0


class _IssuedStep(NamedTuple):
    """A decode step handed to the device and not read yet: its device
    output, the rows it decodes with the request each held then, where the
    chunk that rode it starts (None: a plain decode step), and, where that
    chunk was its prompt's last, the prompt's slot and first token (on the
    device until the step is read)."""

    out: Any
    rows: List[Tuple[Slot, Request]]
    chunk_start: Optional[int] = None
    first: Optional[Tuple[Slot, Any]] = None

    def alive(self) -> List[Slot]:
        """The rows that still hold the request the step decoded for them
        (a row that ended since, or was given to another request, is not)."""
        return [s for s, req in self.rows if s.request is req]


class InferenceEngine:
    """Slot-pool online inference over a causal LM.

    ``submit`` is thread-safe and non-blocking (raises
    :class:`EngineOverloadedError` under backpressure); tokens stream back
    on the returned :class:`ResponseStream` as they are decoded.  With
    ``auto_start=True`` (the default) a daemon thread drives the step loop;
    ``auto_start=False`` hands the loop to the caller via :meth:`step` —
    the deterministic mode the parity tests drive.
    """

    def __init__(self, model, params, config: Optional[EngineConfig] = None,
                 *, auto_start: bool = True, name: str = "engine"):
        self.model = model
        self.params = params
        self.config = config or EngineConfig()
        self.name = name
        cfg = self.config
        if cfg.eos_token_id == "model":
            self.eos_token_id = model.config.eos_token_id
        else:
            self.eos_token_id = cfg.eos_token_id
        if cfg.slot_len > model.config.max_seq_len:
            raise ValueError(
                f"slot_len {cfg.slot_len} exceeds the model's max_seq_len "
                f"{model.config.max_seq_len}"
            )
        self.adapters_enabled = cfg.adapter_slots > 0
        # per-slot state that is not pages (module doc, RECURRENT LAYERS)
        self._recurrent = keeps_slot_state(model)
        # latent attention: one pool of latent pages (models/lm/modeling.py)
        self._latent = bool(getattr(model.config, "kv_lora_rank", 0))

        # device side: the persistent donated KV pool + compiled phases
        # (MeshEngine overrides the builder: a sharded pool/cache and
        # pjit-wrapped step fns, same host loop; it builds no mixed step,
        # and an engine without one issues every chunk alone)
        self._mixed_step = None
        # bytes of recurrent state and of window rings the cache holds
        # beside its pages (the builder below counts them)
        self._state_bytes = self._ring_bytes = 0
        self._build_paged_state()

        # the step's inputs as they lie on the device between steps (see
        # the module doc): tokens and positions, moved on behind every step;
        # the masked block table (and adapter rows), uploaded again by the
        # issue that finds a row joined or left (the first one does)
        self._tok_dev = jnp.zeros((cfg.num_slots,), jnp.int32)
        self._pos_dev = jnp.zeros((cfg.num_slots,), jnp.int32)
        self._table_dev = None
        self._adapter_ids_dev = None
        self._riding: set = set()   # rows at a position > 0 on the device
        # rows that join the next issued step: row -> (first token, on the
        # device or an int; prompt length)
        self._joins: Dict[int, Any] = {}
        # first tokens issued and not read yet: (slot, device scalar)
        self._firsts: List[Any] = []
        # the one issued step the host has not read; and when the stream's
        # current token step began (the last read-back, or its own issue)
        self._inflight: Optional[_IssuedStep] = None
        self._mark = 0.0
        self._round_reserved = 0   # pages promised during one admission round
        self._chunks_run = 0       # prefill chunks issued, engine lifetime
        # the chunk this iteration's decode step carries (set by the prefill
        # quantum, taken by the issue that follows it in the same iteration)
        self._chunk_riding: Optional[_Chunk] = None

        self.scheduler = Scheduler(cfg)
        self.slots = SlotManager(cfg.num_slots)
        self.metrics = EngineMetrics(name=name, num_slots=cfg.num_slots,
                                     programs=("decode", "mixed"))
        if self._latent:
            # the positions a decode step's gather writes out, live or not
            self.metrics.set_latent_pool(cfg.num_slots * cfg.slot_len)
        self._streams = int(getattr(self.model.config, "hc_mult", 1))
        if self._streams > 1:
            self.metrics.set_residual_streams(self._streams)
        if self._state_bytes:
            self.metrics.set_recurrent_state(
                self._state_bytes,
                prefix_cache_disabled=bool(cfg.prefix_cache))
        if self._ring_bytes:
            # what the window layers hold as rings, beside what the same
            # layers would hold as pages at slot_len (every layer's K and V
            # are equally wide: the full layers' pools say what a page costs)
            kinds = model.config.layer_kinds()
            self._window_layers = kinds.count("window")
            self._full_layers = len(kinds) - self._window_layers
            pages = page_pool_bytes(self.cache)
            self.metrics.set_window_rings(
                self._ring_bytes, pages,
                as_pages=pages * self._window_layers // max(
                    self._full_layers, 1),
                window=int(model.config.sliding_window),
                prefix_cache_disabled=bool(cfg.prefix_cache))
        # airscope: analytic flops/bytes per compiled program, fed into the
        # metrics ledger with each program's measured wall time.  The
        # decode-step cost is a CONSTANT — the fixed-shape step attends the
        # full compiled context for every slot regardless of occupancy, so
        # it is priced once at the compiled shape (S rows × slot_len).
        # Geometry-gated: the decoder-only formulas only apply to configs
        # exposing the LM geometry (T5's window engine skips the ledger).
        mc = self.model.config
        if all(hasattr(mc, a) for a in ("d_model", "n_layers", "n_heads",
                                        "head_dim", "d_ff", "vocab_size")):
            self._cost_model: Optional[Any] = _perf.LMCostModel(mc)
            self._decode_cost = self._cost_model.decode_step_cost(
                cfg.num_slots, cfg.slot_len)
        else:
            self._cost_model = None
            self._decode_cost = None

        # live-weight swap state (serve/weights.py): the version currently
        # serving plus the PRIOR device tree — rollback never touches the
        # store, so it survives a corrupted/GC'd publish.  Doubles weight
        # memory while a prior version is retained — the price of instant
        # rollback, documented in docs/SERVING.md.
        self._weights_version: Optional[int] = None
        self._prev_params: Any = None
        self._prev_version: Optional[int] = None

        # multi-tenant LoRA: name -> bank row map (row 0 = zero adapter)
        # and the host per-slot row table the decode step gathers from.
        # Lock order: _step_lock OUTER, _adapter_lock inner.
        self._adapter_rows: Dict[str, int] = {}
        self._adapter_lock = threading.Lock()
        self._adapter_ids_host = np.zeros((cfg.num_slots,), np.int32)

        self._next_request_id = 0
        self._id_lock = threading.Lock()
        self._step_lock = threading.Lock()
        self._closed = False
        self._draining = False
        self._preempting = False
        self._round_admits = 0  # slots taken during one admission round
        self._thread: Optional[threading.Thread] = None
        if self._mixed_step is not None:
            self._compile_mixed_step()
        if auto_start:
            self.start()

    # -- device-state builder (overridden by engine/dist MeshEngine) ---------
    def _build_paged_state(self) -> None:
        cfg = self.config
        self.pool = PagedKVPool(
            cfg.pool_pages(), cfg.page_len, cfg.num_slots,
            cfg.pages_per_slot(),
            prefix_cache=cfg.prefix_cache and not self._recurrent,
        )
        self.cache = init_paged_cache(
            self.model, cfg.num_slots, cfg.pool_pages(), cfg.page_len,
            cfg.pages_per_slot(),
        )
        self._state_bytes = recurrent_state_bytes(self.cache)
        self._ring_bytes = window_ring_bytes(self.cache)
        # does a step's pass move the state of the rows it advances alone
        # (asked once: the rule reads what does not change under an engine)
        self._state_in_place = state_rows_move_in_place(self.cache)
        self._decode_step = make_lm_paged_decode_step_fn(
            self.model, cfg.slot_len, adapters=self.adapters_enabled)
        self._chunk_fn = make_lm_prefill_chunk_fn(
            self.model, cfg.page_len, cfg.slot_len,
            adapters=self.adapters_enabled)
        self._mixed_step = make_lm_paged_mixed_step_fn(
            self.model, cfg.page_len, cfg.slot_len,
            adapters=self.adapters_enabled)
        self._copy_fn = make_page_copy_fn()
        self._advance, self._set_row = make_lm_step_feed_fns()
        if self.adapters_enabled:
            mc = self.model.config
            A, r = cfg.adapter_slots, cfg.adapter_rank
            # resident LoRA bank: row 0 is the pinned zero adapter, so
            # base-model slots gather an exact-zero delta (greedy parity)
            self._adapter_a = jnp.zeros((A + 1, mc.d_model, r), jnp.float32)
            self._adapter_b = jnp.zeros((A + 1, r, mc.vocab_size),
                                        jnp.float32)

    def _compile_mixed_step(self) -> None:
        """Run the mixed step once on the empty pool, so that it is compiled
        when the engine is built.  The other two programs compile when the
        first request meets them; this one takes a prompt that arrives while
        another request decodes, which a warm-up of one request never is,
        and the first such iteration under load would stall every stream for
        the compile.  Every row rides idle at the null page (the table as an
        empty engine's is); the chunk is slot 0's at position 0 with no real
        position (``last_local`` -1), so its K/V goes to the null page and
        row 0's state is set to the zeros it holds."""
        cfg = self.config
        ids = np.full((1, cfg.page_len), self.model.config.pad_token_id,
                      np.int32)
        null = np.array([[self._null_entry(i)] * cfg.pages_per_slot()
                         for i in range(cfg.num_slots)], np.int32)
        self._table_dev = jnp.asarray(null)
        if self.adapters_enabled:
            self._adapter_ids_dev = jnp.asarray(self._adapter_ids_host.copy())
        self._launch_step(_Chunk(self.slots.slots[0], 0, (
            jnp.asarray(ids), jnp.int32(0), jnp.int32(-1),
            jnp.asarray(null[0]))))

    # -- submission (any thread) ---------------------------------------------
    def _make_request(self, prompt, max_new_tokens, stream,
                      priority: str = "interactive", *,
                      admit_while_draining: bool = False,
                      deadline_ms: Optional[float] = None,
                      adapter_id: Optional[str] = None,
                      tenant: Optional[str] = None) -> Request:
        """Shared validation + Request construction for both submit paths.

        ``admit_while_draining`` is the disaggregated-handoff escape hatch:
        a ``submit_prefilled`` payload was ADMITTED at the router before the
        drain began — refusing it here would drop work the caller already
        streamed a first token for."""
        # airlint: disable=CC001 — _closed/_draining are GIL-atomic
        # monotonic bools (False→True once); a submit racing the flip is
        # indistinguishable from one that arrived a moment earlier
        if self._closed:
            raise EngineClosedError("engine is shut down")
        # airlint: disable=CC001 — same monotonic-flag discipline as _closed
        if self._draining and not admit_while_draining:
            raise EngineDrainingError(
                f"engine {self.name!r} is draining; submit elsewhere")
        if priority not in PRIORITIES:
            raise ValueError(
                f"unknown priority {priority!r} (expected one of {PRIORITIES})"
            )
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        budget = (self.config.max_new_tokens if max_new_tokens is None
                  else int(max_new_tokens))
        if budget < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {budget}")
        if len(prompt) + budget > self.config.slot_len:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({budget}) "
                f"exceeds slot_len ({self.config.slot_len})"
            )
        if adapter_id is not None:
            # fail fast at submit (the proxy maps RequestValidationError to
            # HTTP 400, unlike a plain replica-side ValueError which stays
            # 500); admission re-resolves — the adapter may be evicted
            # meanwhile
            if not self.adapters_enabled:
                raise RequestValidationError(
                    "adapter_id requires EngineConfig.adapter_slots > 0")
            with self._adapter_lock:
                if adapter_id not in self._adapter_rows:
                    raise RequestValidationError(
                        f"unknown adapter {adapter_id!r}")
        with self._id_lock:
            rid = self._next_request_id
            self._next_request_id += 1
        return Request(request_id=rid, prompt=prompt, max_new_tokens=budget,
                       stream=stream if stream is not None
                       else ResponseStream(rid),
                       priority=priority,
                       deadline_ms=(None if deadline_ms is None
                                    else float(deadline_ms)),
                       adapter_id=adapter_id,
                       tenant=(str(tenant) if tenant else None))

    def _enqueue(self, req: Request) -> ResponseStream:
        try:
            self.scheduler.submit(req)
        except EngineOverloadedError:  # backpressure: count the 503, surface it
            self.metrics.record_reject(req.priority)
            raise
        self.metrics.record_submit(req.priority)
        return req.stream

    def submit(self, prompt: Sequence[int],
               max_new_tokens: Optional[int] = None, *,
               priority: str = "interactive",
               stream: Optional[ResponseStream] = None,
               deadline_ms: Optional[float] = None,
               adapter_id: Optional[str] = None,
               tenant: Optional[str] = None) -> ResponseStream:
        """Queue one prompt; returns its token stream immediately.

        ``priority`` is the request's SLO class (``types.PRIORITIES``):
        admission pops interactive first each step, and under backpressure
        best-effort sheds at half the queue depth interactive does.
        ``stream`` lets a front-end that already handed a stream to its
        caller (the disagg router's prefill-fallback path) have the engine
        emit onto it instead of minting a fresh one.  ``deadline_ms`` is
        the request's ABSOLUTE end-to-end deadline (unix-epoch ms): still
        queued past it, the request expires with
        :class:`~tpu_air.faults.retry.DeadlineExceededError` instead of
        occupying a slot it can no longer use.  ``adapter_id`` selects the
        tenant LoRA adapter the request decodes under (None = base model;
        unknown/unloaded names raise ValueError here).  ``tenant`` is the
        pure cost-attribution label (never validated): airwatch bills
        ``tenant or adapter_id`` — the batch lane stamps
        ``batch:<job_id>`` so offline rows never fold into "default"."""
        return self._enqueue(self._make_request(prompt, max_new_tokens,
                                                stream, priority,
                                                deadline_ms=deadline_ms,
                                                adapter_id=adapter_id,
                                                tenant=tenant))

    def submit_prefilled(self, prompt: Sequence[int], first_token: int,
                         kv_pages: Dict[str, Any],
                         max_new_tokens: Optional[int] = None, *,
                         priority: str = "interactive",
                         stream: Optional[ResponseStream] = None,
                         deadline_ms: Optional[float] = None
                         ) -> ResponseStream:
        """Queue a request whose prefill ALREADY RAN elsewhere (a
        PrefillWorker replica — engine/dist/): ``kv_pages`` is the
        extract_kv_pages payload covering the whole prompt and
        ``first_token`` the prefill's greedy first token.  Admission
        allocates unshared pages, inserts the shipped K/V, emits
        ``first_token`` and goes straight to decode — same capacity gate
        and deferral as a normal submit, so pool exhaustion queues the
        handoff instead of dropping it."""
        # a handoff rides through a drain: the router admitted it before the
        # drain started and its prefill already ran on another replica
        self._refuse_pages_only("submit_prefilled")
        req = self._make_request(prompt, max_new_tokens, stream, priority,
                                 admit_while_draining=True,
                                 deadline_ms=deadline_ms)
        req.prefilled = {"first_token": int(first_token), "pages": kv_pages}
        return self._enqueue(req)

    def generate(self, prompts: Sequence[Sequence[int]],
                 max_new_tokens: Optional[int] = None,
                 timeout: Optional[float] = 120.0) -> List[List[int]]:
        """Blocking convenience: submit every prompt, join every stream.
        In manual mode (no background thread) it drives :meth:`step`."""
        streams = [self.submit(p, max_new_tokens) for p in prompts]
        if self._thread is None:
            while not self.idle():
                self.step()
        return [s.result(timeout) for s in streams]

    # -- the engine loop -----------------------------------------------------
    def step(self) -> bool:
        """One deterministic engine iteration: admit into free slots, issue
        the prefill quantum, then one token step: issue the next pool decode
        step if a row has budget for it, then read back and emit the one
        before.  Returns True if any work happened (callers loop
        ``while engine.step(): ...`` to drain)."""
        with self._step_lock:
            worked = False
            self._begin_admission_round()
            self._round_admits = 0
            # airlint: disable=CC001 — _preempting is a GIL-atomic
            # monotonic bool (False→True once); an admission round racing
            # the flip just admits one last batch before the freeze
            if not self._preempting:
                for req in self.scheduler.pop_admissible(
                    self.slots.free_count(), self._admit_gate()
                ):
                    self._admit(req)
                    worked = True
            if self._prefill_quantum():
                worked = True
            if self._token_step():
                worked = True
            self.metrics.observe_gauges(
                self.scheduler.depth(), self.slots.occupancy(),
                queue_by_class=self.scheduler.depth_by_class(),
                draining=self._draining,
                deadline_expired=self.scheduler.deadline_expired,
                kvpool=self.pool.stats(),
                reordered_admits=self.scheduler.reordered_admits,
                prefill_chunks=self._chunks_run,
            )
            return worked

    def idle(self) -> bool:
        # airlint: disable=CC001 — GIL-atomic pointer read; an unread step
        # holds at least one occupied slot (one whose rows all ended is
        # dropped in the iteration that ended them), so this only spells
        # out what occupancy already says
        unread = self._inflight is not None
        return (self.scheduler.depth() == 0 and self.slots.occupancy() == 0
                and not unread)

    # -- draining (zero-downtime rollout / scale-down) ------------------------
    def drain(self) -> None:
        """Stop admitting NEW submissions; everything already queued or in a
        slot retires normally (streaming untouched).  The deployment calls
        this before swapping/killing a replica; :meth:`drained` answers when
        the swap may proceed.  Idempotent; :meth:`close` is still required
        to stop the loop."""
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def drained(self) -> bool:
        """True once draining AND no admitted work remains."""
        return self._draining and self.idle()

    # -- preemption (lease revoked with notice) -------------------------------
    def preempt(self) -> None:
        """A lease-revocation notice arrived: stop admitting ANYTHING.
        New submits shed (:class:`EngineDrainingError` — the proxy routes
        elsewhere) and the already-queued backlog STAYS queued — unlike a
        rollout drain, prefilling it here would burn the notice window on
        work this replica cannot finish; the journal replays it on a
        survivor once the replica goes away.  Idempotent."""
        self._draining = True
        # airlint: disable=CC001 — GIL-atomic monotonic flip, never unset
        self._preempting = True

    @property
    def preempting(self) -> bool:
        # airlint: disable=CC001 — GIL-atomic monotonic bool read
        return self._preempting

    def _refuse_pages_only(self, what: str) -> None:
        refuse_pages_only(
            self.model, f"{what} ships K/V pages only and this model keeps "
            f"{self._state_bytes} bytes of recurrent state and "
            f"{self._ring_bytes} bytes of window rings a pool beside them")

    def migrate_out(self) -> List[Dict[str, Any]]:
        """Preemption drain: freeze the loop, settle the step in flight
        (read and emit it, so every cursor below is the device's) and pull
        every DECODING slot's live state into portable payloads for
        :meth:`submit_migrated` on a survivor.

        Each payload carries everything the destination needs to continue
        the stream exactly: the original prompt, every client-visible
        token emitted so far, the decode cursor, the remaining budget, the
        SLO class/deadline/tenant, and the KV pages covering positions
        ``0..pos-1`` (:func:`extract_kv_pages`).  Mid-prefill slots and
        the queued backlog are NOT shipped — their cheapest recovery is
        the journal-replay fallback, since little or none of their compute
        exists yet.  Migrated slots are released here (the destination
        owns the stream's future); their source streams are abandoned
        unfinished, and the proxy re-pins pollers at the destination.
        """
        self._refuse_pages_only("migrate_out")
        self.preempt()
        from .dist.kv_transfer import extract_kv_pages  # lazy: avoids cycle

        payloads: List[Dict[str, Any]] = []
        with self._step_lock:
            self._settle()
            for slot in list(self.slots.active_slots()):
                if slot.prefilling:
                    continue
                req = slot.request
                p = int(slot.pos)
                page_ids = self.pool.prompt_page_ids(slot.index, p)
                # airlint: disable=CC003 — the only sleep reachable here is
                # a test-only fault-injection delay; the loop is frozen by
                # design while live state is pulled
                pages = extract_kv_pages(self.cache, page_ids)
                payloads.append({
                    "request_id": req.request_id,
                    "prompt": [int(t) for t in req.prompt],
                    "streamed": req.stream.tokens_so_far(),
                    "pos": p,
                    "budget_left": int(slot.budget_left),
                    "priority": req.priority,
                    "deadline_ms": req.deadline_ms,
                    "adapter_id": req.adapter_id,
                    "tenant": req.tenant,
                    "pages": pages,
                })
                self.metrics.record_migration("out", len(page_ids))
                # every token this stream emitted stays useful — the
                # destination continues it, so nothing here is waste and
                # the slot is released without finishing the stream
                self.pool.release(slot.index)
                self.slots.release(slot)
                self._adapter_ids_host[slot.index] = 0
        return payloads

    def submit_migrated(self, payload: Dict[str, Any], *,
                        stream: Optional[ResponseStream] = None
                        ) -> ResponseStream:
        """Land one :meth:`migrate_out` payload on this engine.

        Validates the shipped pages against this cache's geometry BEFORE
        queueing (:class:`~tpu_air.engine.dist.kv_transfer.KVTransferError`
        surfaces synchronously so the supervisor can fall back to replay),
        then admission allocates unshared pages, inserts the K/V, replays
        the already-delivered tokens onto the fresh stream, and decode
        continues from the exact cursor — zero prefill chunks run, and
        greedy continuations are token-identical to the stream never
        having moved."""
        from .dist.kv_transfer import validate_kv_payload  # lazy: no cycle

        self._refuse_pages_only("submit_migrated")
        prompt = [int(t) for t in payload["prompt"]]
        streamed = [int(t) for t in payload["streamed"]]
        pos = int(payload["pos"])
        budget_left = int(payload["budget_left"])
        if not streamed or budget_left < 1 \
                or pos != len(prompt) + len(streamed) - 1:
            raise RequestValidationError(
                f"inconsistent migration payload: prompt={len(prompt)} "
                f"streamed={len(streamed)} pos={pos} "
                f"budget_left={budget_left}")
        n_pages = -(-pos // self.config.page_len)
        # airlint: disable=CC001 — geometry-only read; the cache is rebound
        # under _step_lock but every rebinding preserves layout, so a stale
        # reference validates identically
        validate_kv_payload(self.cache, range(n_pages), payload["pages"])
        # the cache-resident context is positions 0..pos-1: the prompt plus
        # every emitted token but the last (the cursor token is computed,
        # not yet written) — that context is the "prompt" the pool admits
        context = (prompt + streamed)[:pos]
        req = self._make_request(context, budget_left + 1, stream,
                                 payload.get("priority", "interactive"),
                                 admit_while_draining=True,
                                 deadline_ms=payload.get("deadline_ms"),
                                 adapter_id=payload.get("adapter_id"),
                                 tenant=payload.get("tenant"))
        req.migrated = {"streamed": streamed, "pages": payload["pages"],
                        "client_prompt_len": len(prompt)}
        return self._enqueue(req)

    def _admit_gate(self):
        """Per-round admission predicate handed to the scheduler.  Combines
        the page-capacity gate with the interactive slot reserve
        (``EngineConfig.reserved_interactive_slots``): a non-interactive
        request may only take a slot while MORE than ``reserved`` slots
        would stay free after this round's takes — so a lower-class burst
        can never occupy the whole pool and an arriving interactive request
        admits immediately."""
        reserved = self.config.reserved_interactive_slots
        if reserved <= 0:
            return self._can_admit

        def gate(req: Request) -> bool:
            if req.priority != "interactive" and (
                self.slots.free_count() - self._round_admits <= reserved
            ):
                return False
            if not self._can_admit(req):
                return False
            self._round_admits += 1
            return True

        return gate

    # -- admission -----------------------------------------------------------
    def _begin_admission_round(self) -> None:
        """Reset per-round reservation state before ``pop_admissible``
        probes the queue (the MeshEngine override tracks reservations PER
        dp REPLICA, simulating which replica each admit will land in)."""
        self._round_reserved = 0

    def _can_admit(self, req: Request) -> bool:
        """Page-capacity gate for the scheduler: answers whether the pool
        can cover the request's WORST CASE (no prefix sharing — a prior
        admit's eviction may invalidate a probe-time match, and shared
        pages stop being evictable, so the conservative bound is exactly
        what one round can consume).  A True answer RESERVES the pages for
        the rest of the round."""
        need = self.pool.worst_case_pages(len(req.prompt), req.max_new_tokens)
        if self.slots.free_count() == 0:
            return False
        if self._round_reserved + need > self.pool.capacity():
            return False
        self._round_reserved += need
        return True

    def _admit(self, req: Request) -> None:
        """Reserve pages + block-table row; actual compute happens in the
        chunked prefill quantum (no first token yet — TTFT lands when the
        final chunk runs).  A request carrying shipped KV pages skips the
        chunk phase entirely (prefill already ran on another replica)."""
        if not self._resolve_adapter(req):
            return
        slot = self.slots.acquire()
        slot.request = req
        self._adapter_ids_host[slot.index] = req.adapter_row
        if req.prefilled is not None:
            self._admit_prefilled(slot, req)
            return
        if req.migrated is not None:
            self._admit_migrated(slot, req)
            return
        slot.prefilling = True
        slot.plan = self.pool.admit(slot.index, req.prompt, req.max_new_tokens)
        # chunks about to be recomputed whose content the prefix cache held
        # before eviction: work the machine already did once (goodput waste)
        reprefill = getattr(slot.plan, "reprefill_tokens", 0)
        if reprefill:
            self.metrics.record_goodput("reprefill_cache_miss", reprefill)

    def _admit_prefilled(self, slot: Slot, req: Request) -> None:
        """Disaggregated handoff landing (engine/dist/): allocate UNSHARED
        pages (the shipped K/V is written into them — a write must never
        touch a prefix-shared page), insert the pages, emit the worker's
        first token, and hand the slot straight to decode.  ``register``
        then publishes the now-populated prompt pages to this engine's
        prefix cache, so later LOCAL submits share them normally.

        A step in flight is left in flight: the row is not in it (a row that
        rode it for a request that ended is skipped at the read, by
        identity), the insert below runs behind it on the device, and the
        row joins the next issued step with ``first`` as its token.  A
        settle here would retire rows in the middle of an admission round,
        under the MeshEngine's per-replica reservations."""
        n = len(req.prompt)
        slot.plan = self.pool.admit(
            slot.index, req.prompt, req.max_new_tokens, share=False)
        slot.plan.chunks_done = len(slot.plan.chunk_starts)  # nothing to run
        page_ids = self.pool.prompt_page_ids(slot.index, n)
        try:
            self.cache = self._insert_shipped_pages(
                self.cache, page_ids, req.prefilled["pages"])
        except ValueError as e:  # KVTransferError: payload does not fit
            self._fail_admission(slot, req, e)
            return
        first = int(req.prefilled["first_token"])
        # first token at the admission: the prefill ran elsewhere and reads
        # 0 here (and the > guard in _emit_request_spans keeps the remote
        # prefill from double-reporting as a local span)
        self.metrics.record_ttft(
            *req.first_token(req.admitted_at, chunks=0), req.priority,
            trace_id=(req.trace_ctx or {}).get("trace_id"))
        req.stream._emit(first)
        self.metrics.record_tokens(1)
        self.pool.register(slot.index, req.prompt)
        slot.prefilling = False
        slot.pos = n
        slot.budget_left = req.max_new_tokens - 1
        if slot.budget_left == 0 or (
            self.eos_token_id is not None and first == self.eos_token_id
        ):
            self._retire(slot)
        else:
            self._joins[slot.index] = (first, n)

    def _fail_admission(self, slot: Slot, req: Request,
                        error: BaseException) -> None:
        """Admission found the request unservable (bad shipped payload):
        give the slot back and fail the stream LOUDLY — the poller sees
        the typed error and the journal falls back to replay, instead of
        this engine decoding from corrupt pages."""
        self.pool.release(slot.index)
        self.slots.release(slot)
        self._adapter_ids_host[slot.index] = 0
        req.stream._finish(error)

    def _admit_migrated(self, slot: Slot, req: Request) -> None:
        """Migration landing (:meth:`submit_migrated`): like
        :meth:`_admit_prefilled` but for a stream that was already
        DECODING elsewhere.  Allocates unshared pages sized for the whole
        remaining run, inserts the shipped K/V, replays the client-visible
        tokens onto the stream, and parks the cursor exactly where the
        source stopped — ``chunks_done`` covers the whole chunk list, so
        ZERO prefill chunks run (``migrations.in_reprefill_chunks`` stays
        0; the acceptance test pins it).  The pages are NOT registered
        with the prefix cache: the tail page is mid-append and the
        admitted "prompt" includes generated tokens — publishing it would
        let a future prompt share a page decode is still writing into.
        A step in flight stays in flight, as in :meth:`_admit_prefilled`."""
        m = req.migrated
        p = len(req.prompt)          # cache-resident positions 0..p-1
        slot.plan = self.pool.admit(
            slot.index, req.prompt, req.max_new_tokens, share=False)
        slot.plan.chunks_done = len(slot.plan.chunk_starts)  # nothing to run
        page_ids = self.pool.prompt_page_ids(slot.index, p)
        try:
            self.cache = self._insert_shipped_pages(
                self.cache, page_ids, m["pages"])
        except ValueError as e:  # KVTransferError: payload does not fit
            self._fail_admission(slot, req, e)
            return
        # first token at the admission, as in _admit_prefilled (the source
        # replica's prefill does not re-report here); TTFT was counted there
        req.first_token(req.admitted_at, chunks=0)
        streamed = m["streamed"]
        for tok in streamed:
            # already counted and TTFT-stamped on the source — replayed
            # onto the fresh stream so it carries the FULL client-visible
            # list (the proxy re-pins pollers with offset 0)
            req.stream._emit(tok)
        slot.prefilling = False
        slot.pos = p
        slot.budget_left = req.max_new_tokens - 1
        self.metrics.record_migration(
            "in", len(page_ids), reprefill_chunks=slot.plan.chunks_left)
        self.metrics.record_tenant_migrated(req.tenant or req.adapter_id,
                                            len(page_ids))
        if slot.budget_left == 0 or (
            self.eos_token_id is not None
            and streamed[-1] == self.eos_token_id
        ):
            self._retire(slot)
        else:
            self._joins[slot.index] = (streamed[-1], p)

    def _insert_shipped_pages(self, cache, page_ids, payload):
        """Write a disaggregated handoff's KV pages into ``page_ids`` of the
        donated cache (MeshEngine re-places the rebuilt leaves onto its
        shardings afterwards)."""
        from .dist.kv_transfer import insert_kv_pages  # lazy: avoids cycle

        return insert_kv_pages(cache, page_ids, payload)

    def _prefill_quantum(self) -> bool:
        """Take up to ``prefill_chunks_per_step`` prefill chunks,
        SHORTEST-REMAINING-PROMPT first (ties: request id = arrival order).
        Where this iteration will issue a decode step, the first chunk rides
        it (``_chunk_riding``: one program for both, the weights streamed
        once); it is made ready here and goes out with the step.  Every other
        chunk is issued here, alone: behind the decode step in flight and
        ahead of the next one, so the device starts it the moment it is free.
        Bounding the per-step quantum keeps any single long prompt from
        stalling in-flight decodes; preferring short remainders keeps
        short-request TTFT flat while a long prompt streams in.  True if a
        chunk was issued alone."""
        ride = None   # will this iteration issue a step?  asked on first need
        ran = False
        for _ in range(max(1, self.config.prefill_chunks_per_step)):
            # the riding chunk goes out after the ones issued here: its
            # prompt's next chunk waits for the next iteration
            pending = [s for s in self.slots.active_slots() if s.prefilling
                       and (self._chunk_riding is None
                            or s is not self._chunk_riding.slot)]
            if not pending:
                break
            slot = min(
                pending,
                key=lambda s: (s.plan.chunks_left, s.request.request_id),
            )
            if ride is None:
                # the rows _token_step will find: a chunk issued here can
                # only add to them (a prompt that finishes joins the step)
                ride = self._mixed_step is not None and bool(self._step_rows(
                    self._inflight.alive() if self._inflight else []))
            fused = ride and self._chunk_riding is None
            with phase("engine.prefill", tokens=self.config.page_len,
                       start=slot.plan.next_start,
                       queued=self.scheduler.depth(), chunks=1,
                       fused=int(fused),
                       **self._chunk_pages(slot.plan.next_start)):
                chunk = self._ready_chunk(slot)
                if fused:
                    self._chunk_riding = chunk
                else:
                    self._run_chunk(chunk)
                    ran = True
        return ran

    def _chunk_pages(self, start: int) -> Dict[str, int]:
        """What a chunk at ``start`` counts: the ``pages`` of its slot its
        prompt has reached with it (what its attention has to visit) and
        the ``slot_pages`` a slot holds (what a gather of the slot visits)."""
        return {"pages": start // self.config.page_len + 1,
                "slot_pages": self.config.pages_per_slot()}

    def _ready_chunk(self, slot: Slot) -> _Chunk:
        """The slot's next chunk, its inputs on their way to the device."""
        plan = slot.plan
        req = slot.request
        C = self.config.page_len
        p0 = plan.next_start
        ids = np.full((1, C), self.model.config.pad_token_id, np.int32)
        chunk_toks = req.prompt[p0:p0 + C]
        ids[0, :len(chunk_toks)] = chunk_toks
        is_last = plan.chunks_done == len(plan.chunk_starts) - 1
        last_local = (plan.prompt_len - 1 - p0) if is_last else (C - 1)
        row = self.pool.chunk_row(slot.index, p0, plan.null_target)
        return _Chunk(slot, p0, (jnp.asarray(ids), jnp.int32(p0),
                                 jnp.int32(last_local), jnp.asarray(row)),
                      req.adapter_row, len(chunk_toks))

    def _state_row(self, slot: Slot) -> Dict[str, Any]:
        # the slot's state row goes with its table row; the chunk at position
        # 0 starts it from zeros (the last tenant left its state behind) and
        # positions past last_local are masked out of it
        return {"slot": jnp.int32(slot.index)} if self._recurrent else {}

    def _run_chunk(self, chunk: _Chunk) -> None:
        """Issue one chunk alone, with the chunk program."""
        t0 = time.monotonic()
        args = (self.params, self.cache) + chunk.args
        if self.adapters_enabled:
            args += (self._adapter_a, self._adapter_b,
                     jnp.int32(chunk.adapter_row))
        self.cache, tok = self._chunk_fn(*args, **self._state_row(chunk.slot))
        if self._cost_model is not None:
            # dispatch-time measurement: no chunk is host-synced here (the
            # final chunk's token is read after the next step went out), so
            # chunk seconds are the dispatch cost on an async backend —
            # exact on CPU, a lower bound on TPU (on-chip rerun is ROADMAP
            # item 5's lane)
            self.metrics.record_program(
                "prefill_chunk",
                self._cost_model.prefill_chunk_cost(self.config.page_len,
                                                    chunk.start),
                time.monotonic() - t0)
        if self._chunk_issued(chunk, tok, fused=False):
            self._firsts.append((chunk.slot, tok))

    def _chunk_issued(self, chunk: _Chunk, tok, fused: bool) -> bool:
        """Book a chunk that went to the device, alone or in a mixed step;
        ``tok`` is the program's token for it, on the device.  True when it
        was its prompt's last: the slot is handed over to decode, joins the
        next issued step with ``tok``, and the caller sees to the read of
        ``tok`` as the prompt's first token."""
        slot = chunk.slot
        plan, req = slot.plan, slot.request
        if self._state_bytes and chunk.start == 0:
            self.metrics.record_state_reset()
        plan.chunks_done += 1
        self._chunks_run += 1
        self.metrics.record_chunk(fused, **self._chunk_pages(chunk.start))
        if not plan.done:
            return False
        # final chunk: publication, CoW, hand over to decode.  The first
        # token stays on the device: the row joins the next issued step with
        # it, and the host reads it after that step went out (_read_firsts)
        self.pool.register(slot.index, req.prompt)
        cow = self.pool.resolve_cow(slot.index)
        if cow is not None:
            dst, src = cow
            self.cache = self._copy_fn(
                self.cache, jnp.int32(dst), jnp.int32(src))
        slot.prefilling = False
        slot.pos = plan.prompt_len
        slot.budget_left = req.max_new_tokens - 1
        if slot.budget_left:
            self._joins[slot.index] = (tok, plan.prompt_len)
        return True

    def _read_firsts(self) -> None:
        """Read and emit the first tokens of the prompts whose last chunk
        was issued alone this iteration, or rode the step just read (TTFT is
        stamped here, at the read).  A row whose budget was one token, or
        whose first token is EOS, retires here; the latter rode the step
        that went out before this read."""
        firsts, self._firsts = self._firsts, []
        with phase("engine.readback", first=len(firsts)):
            # airlint: disable=JX004 — one read a finished prompt, after the
            # step it joins went out: the device is busy behind this wait
            tokens = [int(np.asarray(tok)) for _, tok in firsts]
        with phase("engine.emit", emitted=len(firsts)):
            for (slot, _), first in zip(firsts, tokens):
                req = slot.request
                self.metrics.record_ttft(
                    *req.first_token(time.monotonic(),
                                     chunks=len(slot.plan.chunk_starts)),
                    req.priority,
                    trace_id=(req.trace_ctx or {}).get("trace_id"))
                req.stream._emit(first)
                if slot.budget_left == 0 or (
                    self.eos_token_id is not None
                    and first == self.eos_token_id
                ):
                    self._retire(slot)
            self.metrics.record_tokens(len(firsts))  # prefill's first tokens

    # -- live weight swap (serve/weights.py) ---------------------------------
    def swap_params(self, new_params, *, version: Optional[int] = None
                    ) -> float:
        """Replace the serving weights BETWEEN decode steps: taken under
        ``_step_lock`` and after the step in flight was settled (read and
        emitted: that step keeps the old weights, the next issued one takes
        the new) — slots, the device's token/position vectors and the paged
        pool are untouched, and in-flight streams continue on the new
        weights at their exact positions.  The new
        tree is resharded leaf-by-leaf onto the OLD leaves' shardings
        (``device_put`` per leaf — a tp/dp-partitioned checkpoint restores
        onto whatever mesh this engine serves on) after a structure/shape
        check that rejects mismatched trees before touching ``params``.

        Keeps the prior device tree for :meth:`rollback_params` and
        returns the swap's stall in milliseconds (request-to-done wall
        time: lock wait + reshard + transfer — the bound on the decode
        step gap the swap introduced)."""
        import jax

        t_req = time.monotonic()
        if _faults.enabled():
            _faults.perturb("weights.swap", key=self.name)
        with self._step_lock:
            self._settle()
            old_leaves, old_tree = jax.tree_util.tree_flatten(self.params)
            new_leaves, new_tree = jax.tree_util.tree_flatten(new_params)
            if old_tree != new_tree:
                raise ValueError(
                    "weight swap rejected: parameter tree structure differs "
                    "from the serving model")
            placed = []
            for o, n in zip(old_leaves, new_leaves):
                arr = np.asarray(n)
                if tuple(arr.shape) != tuple(o.shape):
                    raise ValueError(
                        f"weight swap rejected: leaf shape {arr.shape} != "
                        f"serving shape {tuple(o.shape)}")
                placed.append(jax.device_put(arr.astype(o.dtype), o.sharding))
            for p in placed:
                p.block_until_ready()
            self._prev_params = self.params
            self._prev_version = self._weights_version
            self.params = jax.tree_util.tree_unflatten(new_tree, placed)
            self._weights_version = version
            stall_ms = (time.monotonic() - t_req) * 1000.0
        self.metrics.record_weights_swap(version, stall_ms)
        return stall_ms

    def rollback_params(self) -> float:
        """Restore the weights :meth:`swap_params` replaced — a pure
        device-tree pointer swap under ``_step_lock`` (the step in flight is
        settled first and keeps the weights it was issued with), no store
        reads, so rollback works even when the bad publish's store objects
        are corrupt or already GC'd.  Raises RuntimeError with no prior
        version retained."""
        t_req = time.monotonic()
        with self._step_lock:
            self._settle()
            if self._prev_params is None:
                raise RuntimeError("no prior weights retained to roll back to")
            # one-shot: clearing the slot frees the bad tree's device memory
            # and makes a second rollback (nothing to restore) an error
            self.params, self._prev_params = self._prev_params, None
            self._weights_version, self._prev_version = (
                self._prev_version, None)
            version = self._weights_version
            stall_ms = (time.monotonic() - t_req) * 1000.0
        self.metrics.record_weights_swap(version, stall_ms, rollback=True)
        return stall_ms

    def weights_version(self) -> Optional[int]:
        # airlint: disable=CC001 — GIL-atomic pointer read for stats; a
        # reader racing a swap sees the old or new version, both valid,
        # and taking _step_lock here would stall stats behind a decode
        return self._weights_version

    # -- multi-tenant LoRA adapters ------------------------------------------
    def _resolve_adapter(self, req: Request) -> bool:
        """Admission-time resolution of ``req.adapter_id`` to a bank row.
        Submit already validated the name, but the adapter may have been
        evicted while the request sat queued — then the stream fails
        loudly (the proxy surfaces the error) instead of silently serving
        base-model tokens under the tenant's name."""
        if req.adapter_id is None:
            req.adapter_row = 0
            return True
        with self._adapter_lock:
            row = self._adapter_rows.get(req.adapter_id)
        if row is None:
            req.stream._finish(RequestValidationError(
                f"adapter {req.adapter_id!r} was evicted while request "
                f"{req.request_id} was queued"))
            return False
        req.adapter_row = row
        return True

    def load_adapter(self, name: str, a, b) -> int:
        """Load (or reload in place) tenant ``name``'s LoRA head delta
        ``logits += (h @ a) @ b`` into a free bank row.  ``a``: [d_model,
        r], ``b``: [r, vocab]; rank r <= ``adapter_rank`` zero-pads into
        the bank (zero padding is exact — padded lanes contribute 0).
        A cheap sub-swap: two ``.at[row].set`` writes under ``_step_lock``
        between decode steps (the one in flight is settled first); the
        jitted step never retraces."""
        if not self.adapters_enabled:
            raise ValueError(
                "adapters not enabled (EngineConfig.adapter_slots=0)")
        mc = self.model.config
        cfg = self.config
        a = np.asarray(a, np.float32)
        b = np.asarray(b, np.float32)
        if a.ndim != 2 or b.ndim != 2 or a.shape[1] != b.shape[0]:
            raise ValueError(
                f"adapter shapes must be [d,r] x [r,V], got {a.shape} "
                f"x {b.shape}")
        if a.shape[0] != mc.d_model or b.shape[1] != mc.vocab_size:
            raise ValueError(
                f"adapter {a.shape} x {b.shape} does not fit model "
                f"[d={mc.d_model}, V={mc.vocab_size}]")
        r = a.shape[1]
        if r > cfg.adapter_rank:
            raise ValueError(
                f"adapter rank {r} exceeds bank rank {cfg.adapter_rank}")
        pa = np.zeros((mc.d_model, cfg.adapter_rank), np.float32)
        pb = np.zeros((cfg.adapter_rank, mc.vocab_size), np.float32)
        pa[:, :r] = a
        pb[:r, :] = b
        with self._step_lock:
            self._settle()
            with self._adapter_lock:
                row = self._adapter_rows.get(name)
                if row is None:
                    used = set(self._adapter_rows.values())
                    free = [i for i in range(1, cfg.adapter_slots + 1)
                            if i not in used]
                    if not free:
                        raise ValueError(
                            f"adapter bank full ({cfg.adapter_slots} rows); "
                            f"unload a tenant first")
                    row = free[0]
                    self._adapter_rows[name] = row
                n_loaded = len(self._adapter_rows)
            self._adapter_a = self._adapter_a.at[row].set(jnp.asarray(pa))
            self._adapter_b = self._adapter_b.at[row].set(jnp.asarray(pb))
        self.metrics.set_adapters_loaded(n_loaded)
        return row

    def unload_adapter(self, name: str) -> bool:
        """Evict tenant ``name``: zero its bank row and free it.  Refuses
        (RuntimeError) while any active slot decodes under the row —
        eviction must not change tokens mid-stream."""
        if not self.adapters_enabled:
            return False
        with self._step_lock:
            self._settle()
            with self._adapter_lock:
                row = self._adapter_rows.get(name)
                if row is None:
                    return False
                if any(self._adapter_ids_host[s.index] == row
                       for s in self.slots.active_slots()):
                    raise RuntimeError(
                        f"adapter {name!r} is serving active slots; drain "
                        f"them before unloading")
                del self._adapter_rows[name]
                n_loaded = len(self._adapter_rows)
            self._adapter_a = self._adapter_a.at[row].set(0.0)
            self._adapter_b = self._adapter_b.at[row].set(0.0)
        self.metrics.set_adapters_loaded(n_loaded)
        return True

    def adapters(self) -> Dict[str, int]:
        """Loaded tenant adapters: name -> bank row."""
        with self._adapter_lock:
            return dict(self._adapter_rows)

    # -- decode --------------------------------------------------------------
    def _null_entry(self, slot_index: int) -> int:
        """The page id a non-decoding slot's table row is masked with.  The
        single-chip pool has one null page (id 0); the MeshEngine override
        returns the slot's OWN replica's null page so the ride-along
        scatter never crosses a data shard."""
        return 0

    def _token_step(self, issue: bool = True) -> bool:
        """One token step: issue the next decode step (``issue``, and a row
        has budget for it), carrying the chunk the prefill quantum left to
        ride it; then read back and emit the step before it and the first
        tokens of the prompts that just finished.  False when there was
        nothing to issue and nothing to read."""
        unread, self._inflight = self._inflight, None
        chunk, self._chunk_riding = self._chunk_riding, None
        reading = unread.alive() if unread else []
        rows = self._step_rows(reading) if issue else []
        assert chunk is None or rows, "a chunk rides only a step that goes out"
        if unread is None and not rows and not self._firsts:
            return False
        ahead = bool(rows) and unread is not None
        # the rows whose recurrent state the issued program's pass moves
        passed = ({"state_rows": self._state_rows_passed(len(rows))}
                  if self._state_bytes and rows else {})
        with phase("engine.step", live=len(reading if unread else rows),
                   batch=self.config.num_slots, ahead=int(ahead),
                   steps=int(bool(rows)), chunk=int(chunk is not None),
                   **passed):
            if rows:
                # out before step N is read: the device runs it while the
                # host reads, emits, retires and admits
                with phase("engine.dispatch"):
                    self._issue(rows, ahead, chunk)
            if unread is not None:
                self._read(unread, reading)
            if self._firsts:
                self._read_firsts()
            if self._inflight is not None and not self._inflight.alive():
                # every row of the step that is out ended (on EOS) in the
                # step just read: nobody will read it
                self._drop_step()
        return True

    def _settle(self) -> None:
        """Read and emit the step in flight, and issue none: afterwards the
        host's slot state is the device's, as between two steps of a loop
        that reads each step before the next goes out.  What reads or
        changes slot state from outside the loop calls this first (under
        ``_step_lock``)."""
        self._token_step(issue=False)

    def _state_rows_passed(self, issued: int) -> int:
        """The rows whose per-slot state a step that advances ``issued`` rows
        reads and writes: those rows where the pass moves the live rows
        alone, every slot's where it passes over the pool."""
        return issued if self._state_in_place else self.config.num_slots

    def _drop_step(self) -> None:
        if self._inflight is not None:
            if self._inflight.first is not None:
                # the prompt whose last chunk rode it still wants its token
                self._firsts.append(self._inflight.first)
            self._inflight = None
            self.metrics.record_dropped_step()

    def _step_rows(self, reading: List[Slot]) -> List[Slot]:
        """The rows the next step decodes: past their prompt, with budget
        for a token after the one the unread step holds for them
        (``reading``: its rows).  Budgets are host state, so a row that ends
        on its budget never rides; one that ends on EOS in the unread step
        is still here (see the module doc)."""
        held = {s.index for s in reading}
        return [s for s in self.slots.active_slots() if not s.prefilling
                and s.budget_left - (s.index in held) >= 1]

    def _issue(self, rows: List[Slot], ahead: bool,
               chunk: Optional[_Chunk] = None) -> None:
        """Issue one pool decode step over ``rows`` from the inputs on the
        device, and move those inputs on behind it.  With ``chunk`` the step
        is the mixed program and the chunk goes out in it."""
        wanted = {s.index for s in rows}
        leaving = self._riding - wanted
        for i in leaving:
            self._set_dev_row(i, 0, 0)
        for i, (tok, p) in self._joins.items():
            self._set_dev_row(i, tok, p)
        if leaving or self._joins:
            # every other row (free, mid-prefill, or out of budget) rides
            # along pointed at the null page: its scatter can't touch a live
            # or prefix-shared page.  The authoritative table stays
            # host-side; between these events a riding row's pages, reserved
            # at admission for its whole life, do not change.
            table = self.pool.block_table.copy()
            for s in self.slots.slots:
                if s.index not in wanted:
                    table[s.index] = self._null_entry(s.index)
            self._table_dev = jnp.asarray(table)
            if self.adapters_enabled:
                # per-slot LoRA rows gathered the way the table is: one
                # host array in, no retrace, row 0 = exact-zero delta
                self._adapter_ids_dev = jnp.asarray(
                    self._adapter_ids_host.copy())
        self._riding = wanted
        self._joins = {}
        out, tok = self._launch_step(chunk)
        self._tok_dev, self._pos_dev = self._advance(
            self._tok_dev, self._pos_dev, out)
        start = first = None
        if chunk is not None:
            start = chunk.start
            # after the joins were taken: a prompt that ends here joins the
            # NEXT step, with a token this program has yet to compute
            if self._chunk_issued(chunk, tok, fused=True):
                first = (chunk.slot, tok)
        self._inflight = _IssuedStep(
            out, [(s, s.request) for s in rows], start, first)
        self.metrics.record_issue(ahead, mixed=chunk is not None)
        if self._streams > 1:
            self.metrics.record_stream_rows(
                len(rows) + (chunk.tokens if chunk is not None else 0),
                chunk=chunk is not None)
        if self._state_bytes:
            # every row not in the step rode it with its state held; the
            # others' state the step advances, over the positions they hold
            self.metrics.record_rows_held(
                self.config.num_slots - len(rows), live=len(rows),
                positions=sum(s.pos + 1 for s in rows),
                passed=self._state_rows_passed(len(rows)))
        if not ahead:
            self._mark = time.monotonic()

    def _launch_step(self, chunk: Optional[_Chunk] = None):
        """Hand the device the decode step over its own inputs, or, with
        ``chunk``, the mixed step that carries it.  Returns the step's
        output and the chunk's token (None without one), both on the
        device."""
        args = (self.params, self.cache, self._tok_dev, self._pos_dev,
                self._table_dev)
        lora = ((self._adapter_a, self._adapter_b, self._adapter_ids_dev)
                if self.adapters_enabled else ())
        if chunk is None:
            self.cache, out = self._decode_step(*args, *lora)
            return out, None
        if lora:
            lora += (jnp.int32(chunk.adapter_row),)
        self.cache, out, tok = self._mixed_step(
            *args, *chunk.args, *lora, **self._state_row(chunk.slot))
        return out, tok

    def _set_dev_row(self, row: int, tok, p: int) -> None:
        self._tok_dev, self._pos_dev = self._set_row(
            self._tok_dev, self._pos_dev, np.int32(row),
            tok if hasattr(tok, "dtype") else np.int32(tok), np.int32(p))

    def _read(self, step: _IssuedStep, reading: List[Slot]) -> None:
        """Read one issued step back and emit it to ``reading``, the rows
        it decoded that are still the requests it decoded them for.  The
        first token of a prompt whose last chunk rode the step is ready with
        it: queued for ``_read_firsts``."""
        # the program of the step being READ (issued an iteration earlier)
        mixed = step.chunk_start is not None
        with phase("engine.readback", mixed=int(mixed)):
            nxt = np.asarray(step.out)
        if step.first is not None:
            self._firsts.append(step.first)
        # what this token step cost the stream: read-back to read-back, a
        # chunk that ran between the two steps (or in this one) included
        now = time.monotonic()
        dt, self._mark = now - self._mark, now
        if self._cost_model is not None:
            kind, cost = "decode_step", self._decode_cost
            if mixed:
                cfg = self.config
                kind, cost = "mixed_step", self._cost_model.mixed_step_cost(
                    cfg.num_slots, cfg.slot_len, cfg.page_len,
                    step.chunk_start)
            self.metrics.record_program(kind, cost, dt)
        if len(nxt) > self.config.num_slots:
            # sparse experts: the step's routing counters ride behind
            # the tokens (make_paged_decode_body); a model that holds a
            # share of its experts counts the assignments sent elsewhere
            # behind those to the held ones
            counts, elsewhere = nxt[self.config.num_slots:-1], None
            if len(counts) > self.model.config.experts_held:
                counts, elsewhere = counts[:-1], int(counts[-1])
            self.metrics.record_routing(counts, int(nxt[-1]), elsewhere,
                                        chunk=mixed)
        if self._latent:
            # what the step's absorbed read had live: each decoding row's
            # positions up to the one this token was computed at, and the
            # pages they span
            page = self.config.page_len
            self.metrics.record_latent_live(
                sum(slot.pos + 1 for slot in reading),
                sum(slot.pos // page + 1 for slot in reading))
        if self._ring_bytes:
            # what the step's cached reads had live, by kind of layer: a row
            # at position p read p + 1 positions of each full layer's pages
            # and the window's share of them of each ring
            window = self.model.config.sliding_window
            self.metrics.record_kv_live(
                ring=self._window_layers * sum(
                    min(slot.pos + 1, window) for slot in reading),
                pages=self._full_layers * sum(
                    slot.pos + 1 for slot in reading),
                chunk=mixed)
        # one phase around the walk over the rows, none per row
        with phase("engine.emit", emitted=len(reading)):
            for slot in reading:
                # airlint: disable=JX004 — nxt is the np.asarray'd step
                # result; the single device sync already happened above
                token = int(nxt[slot.index])
                slot.request.stream._emit(token)
                slot.pos += 1
                slot.budget_left -= 1
                if slot.budget_left == 0 or (
                    self.eos_token_id is not None
                    and token == self.eos_token_id
                ):
                    self._retire(slot)
            self.metrics.record_step(dt, len(reading), mixed=mixed)

    # -- retirement ----------------------------------------------------------
    def _retire(self, slot: Slot) -> None:
        if slot.request.trace_ctx is not None:  # submitted under airtrace
            self._emit_request_spans(slot)
        slot.request.stream._finish()
        self.metrics.record_complete()
        # goodput: every token this stream emitted reached a consumer that
        # saw the stream complete — useful work
        self.metrics.record_goodput(
            "useful", slot.pos - len(slot.request.prompt) + 1)
        # per-tenant cost attribution (airwatch ledger feed): bill the
        # stream's tokens and KV-page residency to its billing tenant —
        # the explicit ``tenant`` label when one rides the request (batch
        # lane), else its adapter_id tenant.  Residency runs from first
        # token (pages are fully resident once prefill lands) to
        # retirement; page count mirrors the pool's own ceil-division.
        req = slot.request
        n_pages = -(-slot.pos // self.config.page_len)
        resident_s = max(
            0.0, time.monotonic() - (req.first_token_at or req.submitted_at))
        self.metrics.record_tenant_retire(
            req.tenant or req.adapter_id,
            prefilled=len(req.prompt),
            decoded=slot.pos - len(req.prompt) + 1,
            kv_page_seconds=n_pages * resident_s)
        # private pages return to the free list; prompt pages the prefix
        # cache registered stay resident for future hits
        self.pool.release(slot.index)
        self.slots.release(slot)
        self._adapter_ids_host[slot.index] = 0

    def _emit_request_spans(self, slot: Slot) -> None:
        """Retirement-time airtrace emission: the request's whole span tree
        (queue-wait → prefill → decode residency) is reconstructed here from
        the request's one set of monotonic stamps, moved onto the spans'
        wall clock by one offset taken now, so the decode hot loop does zero
        tracing work (and stays JX004-clean)."""
        req = slot.request
        end = _tracing.now_ns()
        wall = end - int(time.monotonic() * 1e9)

        def ns(stamp: float) -> int:
            return wall + int(stamp * 1e9)

        ctx = req.trace_ctx or {}
        root = _tracing.record_span(
            "engine.request",
            trace_id=ctx.get("trace_id"),
            parent_id=ctx.get("span_id"),
            start_ns=ns(req.submitted_at),
            end_ns=end,
            attrs={"engine": self.name, "request_id": req.request_id},
        )
        _tracing.record_span(
            "engine.queue_wait",
            trace_id=root.trace_id, parent_id=root.span_id,
            start_ns=ns(req.submitted_at), end_ns=ns(req.admitted_at),
        )
        if req.first_token_at > req.admitted_at:
            # strictly-after: a disaggregated request lands with its first
            # token AT its admission (its prefill span was recorded on the
            # worker replica)
            attrs = {"slot": slot.index, "prompt_len": len(req.prompt)}
            if slot.plan is not None:
                attrs["chunks"] = len(slot.plan.chunk_starts)
                attrs["prefix_hit"] = slot.plan.prefix_tokens > 0
                attrs["prefix_tokens"] = slot.plan.prefix_tokens
            _tracing.record_span(
                "engine.prefill",
                trace_id=root.trace_id, parent_id=root.span_id,
                start_ns=ns(req.admitted_at), end_ns=ns(req.first_token_at),
                attrs=attrs,
            )
        _tracing.record_span(
            "engine.decode",
            trace_id=root.trace_id, parent_id=root.span_id,
            start_ns=ns(req.first_token_at), end_ns=end,
            attrs={
                "slot": slot.index,
                "tokens": slot.pos - len(req.prompt) + 1,
                "occupancy": self.slots.occupancy(),
            },
        )

    # -- background loop / lifecycle -----------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, name=f"tpu-air-{self.name}", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        while not self._closed:
            if not self.step():
                self.scheduler.wait_for_work(0.01)

    def close(self) -> None:
        """Stop the loop; fail queued and in-flight requests loudly."""
        if self._closed:
            return
        self._closed = True
        if self._thread is not None:
            self._thread.join(timeout=5.0)
            self._thread = None
        with self._step_lock:
            err = EngineClosedError("engine shut down")
            for req in self.scheduler.drain():
                req.stream._finish(err)
            # goodput: compute already spent on in-flight requests is lost —
            # a drained close sheds work it had prefilled (the stream moved
            # to another replica), a hard close kills live streams outright
            waste_cat = ("shed_after_prefill" if self._draining
                         else "dead_stream")
            for slot in self.slots.active_slots():
                req = slot.request
                if slot.prefilling:
                    plan = slot.plan
                    done_tokens = 0
                    if plan is not None and plan.chunks_done:
                        done_tokens = min(
                            plan.chunks_done * self.config.page_len,
                            len(req.prompt))
                    wasted = done_tokens
                else:
                    wasted = slot.pos - len(req.prompt) + 1
                self.metrics.record_goodput(waste_cat, wasted)
                req.stream._finish(err)
                self.pool.release(slot.index)
                self.slots.release(slot)
            # a step still out is never read: the device finishes it
            self._drop_step()
        unregister(self.name)

    def __enter__(self) -> "InferenceEngine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
