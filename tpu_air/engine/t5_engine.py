"""Continuous decoding for the T5 family: a slot a request.

A T5 decode step needs, for every row, the self-attention K/V of the tokens
the row has decoded and the cross-attention K/V of the row's own prompt.
``generate`` builds both for one batch that starts and ends together, under
one scalar cache position.  :class:`T5Engine` keeps ONE decode state for its
life, ``max_batch`` SLOTS of it, and a request is admitted to a free slot at
the next step boundary, whatever the other slots are doing:

* requests queue through the same :class:`~tpu_air.engine.scheduler.
  Scheduler` (backpressure, FIFO) and stream back per-token on the same
  :class:`~tpu_air.engine.types.ResponseStream`;
* SELF-ATTENTION IS A RING under the one scalar position.  The self slabs
  stay position-major ``[ring, slots, h*d]`` a layer and a step appends every
  row's K/V at the scalar ``cur`` in one block, as before; ``cur`` moves on
  one every issued step, wraps at ``ring = max_new_tokens + 1`` and never
  resets.  Each slot has ``born``, the ``cur`` its row was admitted at, and
  a key at ring position ``k`` is the row's own iff its age ``(cur - k) mod
  ring`` is at most the row's ``(cur - born) mod ring``: a per-row key mask.
  T5's relative-position bias is a function of that age alone, so it stays
  one ``[h, ring]`` row for the batch.  A row lives at most
  ``max_new_tokens`` steps, so it never laps itself
  (``models/t5/modeling.Decoder``, ``ring_born``);
* CROSS-ATTENTION IS A ROW A SLOT.  The cross slabs ``[slots, h, d, Lp]``,
  the encoder key mask, ``born`` and the token each slot feeds next live on
  the device for the engine's life, donated through every program.  An
  ADMIT program encodes ONE prompt at a fixed length (the full input length
  or, where that is whole 128-lane tiles, a quarter of it), projects its
  cross K/V and writes them into its slot's row in place, with its mask,
  ``born = cur`` and ``tok = decoder_start``.  It is issued between two
  steps on the device's queue; the row's first token comes out of the next
  ordinary step.  Admission takes the LOWEST free slot, FIFO; the requests
  an iteration finds waiting are one ROUND (one ``admitted_at``), an admit
  program each.  (One row a program: at a few rows the encoder is bound by
  its weights and costs the same for one row as for four, but every
  program more is 1.3 s of a replica's start, traced and lowered even when
  the compile cache holds it, and two arrivals in one 3 ms step are one
  round in twenty-five at ``t5large-serve``'s rate.);
* A STEP RUNS OVER A PREFIX OF THE SLOTS: the smallest of ``_MIN_STEP_ROWS``
  doubled up to ``max_batch`` that holds every row it decodes for, chosen
  by the host as it issues.  Rows of finished requests inside the prefix ride
  along as dead weight (their tokens are dropped on the host); slots past it
  cost nothing.  Lowest-free admission keeps the taken slots low;
* every program the engine can issue is compiled, and run, when the engine
  is built: none compiles later.

ONE STEP IN FLIGHT.  The decode loop runs one step ahead of its host: step
N+1 is issued from step N's tokens as they lie on the device and only then is step N read back, emitted and retired
from, so the host's whole visit runs under a device step instead of between
two.  What follows from reading one step late:

* budgets are host state, so a row is in a step only if it has budget for the
  token: a row that ends on its budget is in exactly the steps it reads;
* a row that ends on EOS is learnt of one step late.  It rides along for
  that step and its extra token is discarded, never emitted.  Its slot is
  free from the read-back that learnt of it: a request admitted to it goes to
  the device BEHIND the step that still carried the old row;
* an issued step whose rows have all ended by the time it would be read is
  dropped unread (``steps_dropped``); what comes next queues behind it.

Depth is one and fixed.  The step path uploads nothing; an admission uploads
one small array.

Greedy by construction: a request's token stream is identical to offline T5
``generate`` of its prompt alone, whenever it was admitted.

ACCOUNTING.  ``engine.window_close`` (docs/OBSERVABILITY.md) is what is left
of the window this engine used to be: one event every ``max_new_tokens``
token steps read, with what those steps did.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import Dict, List, NamedTuple, Optional, Sequence, Tuple

import numpy as np

import jax.numpy as jnp

from tpu_air.models.t5.generate import (
    init_slot_state,
    make_t5_admit_fn,
    make_t5_slot_step_fn,
)
from tpu_air.observability.profiler import phase

from .metrics import EngineMetrics, unregister
from .scheduler import Scheduler
from .types import (
    PRIORITIES,
    EngineClosedError,
    EngineDrainingError,
    EngineOverloadedError,
    Request,
    ResponseStream,
)

# A step over fewer rows than this reads the same weights and a cache that is
# small beside them: 16 rows are one bf16 tile of the slabs' row dimension.
_MIN_STEP_ROWS = 16
_LANES = 128


@dataclass
class T5EngineConfig:
    """Dials for the T5 slot engine.

    * ``max_batch`` — the slots: rows that can decode at once.
    * ``max_input_len`` — encoder-side prompt cap; the cross slabs are sized
      to it.
    * ``max_new_tokens`` — decode budget cap per request (the self-attention
      ring is sized to it).
    * ``max_queue`` — queued request cap; beyond it ``submit`` raises
      :class:`EngineOverloadedError`.
    * ``queue_shares`` — per-priority-class fraction of ``max_queue`` at
      which submits shed, same contract as
      :class:`~tpu_air.engine.types.EngineConfig.queue_shares`.
    """

    max_batch: int = 4
    max_input_len: int = 64
    max_new_tokens: int = 32
    max_queue: int = 256
    reorder_window: int = 0  # slot admission is FIFO; kept for Scheduler
    queue_shares: Optional[dict] = None

    def queue_cap(self, priority: str) -> int:
        """Total queue depth at which ``priority``-class submits shed
        (shares mirror EngineConfig's defaults)."""
        shares = self.queue_shares or {
            "interactive": 1.0, "batch": 0.85, "best_effort": 0.5,
        }
        return int(self.max_queue * float(shares.get(priority, 1.0)))


class _Issued(NamedTuple):
    """One issued step the host has not read back: its ``int32[slots]``
    device tokens, the ``(slot, request)`` it decodes for (a request that has
    ended since is skipped at the read) and the prefix it ran over."""

    tokens: object
    rows: List[Tuple[int, Request]]
    batch: int


def _buckets(smallest: int, largest: int) -> List[int]:
    """``smallest`` doubled while under ``largest``, then ``largest``."""
    out, n = [], min(smallest, largest)
    while n < largest:
        out.append(n)
        n *= 2
    return out + [largest]


class T5Engine:
    """Slot-level continuous decoding over a T5 model (see module doc)."""

    def __init__(self, model, params, config: Optional[T5EngineConfig] = None,
                 *, auto_start: bool = True, name: str = "t5-engine"):
        self.model = model
        self.params = params
        self.config = config or T5EngineConfig()
        self.name = name
        self.eos_token_id = model.config.eos_token_id
        self.pad_token_id = model.config.pad_token_id

        cfg = self.config
        slots, li = cfg.max_batch, cfg.max_input_len
        self._steps = {rows: make_t5_slot_step_fn(model, rows)
                       for rows in _buckets(_MIN_STEP_ROWS, slots)}
        short = li // 4 if li % (4 * _LANES) == 0 else li
        self._admits = {length: make_t5_admit_fn(model, length)
                        for length in sorted({short, li})}
        # the decode state, donated through every program, and the token
        # each slot feeds next, which is not (see ``init_slot_state``)
        self._state, self._tok = init_slot_state(
            model, params, slots, cfg.max_new_tokens + 1, li)
        # slot -> the request decoding in it, and the tokens it may still emit
        self._rows: List[Optional[Request]] = [None] * slots
        self._budget_left = np.zeros((slots,), np.int64)
        self._unread: Optional[_Issued] = None
        # when the stream's current token step began: the end of the last
        # read-back, or the issue of a step with none before it
        self._mark = 0.0
        self._span = self._new_span()
        self._warm()

        self.scheduler = Scheduler(cfg)
        self.metrics = EngineMetrics(name=name, num_slots=slots)

        self._next_request_id = 0
        self._id_lock = threading.Lock()
        self._step_lock = threading.Lock()
        self._closed = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        if auto_start:
            self.start()

    def _warm(self) -> None:
        """Run every program once over the empty state, then once more over
        a state that is a program's output, as every later call's is:
        nothing is traced, compiled or loaded after this.  The slots stay
        free; what the runs left in slot 0 and where the ring stands mean
        nothing to a row admitted later."""
        for _ in range(2):
            for length, admit in self._admits.items():
                prompts = np.zeros((1, length + 2), np.int32)
                prompts[:, length] = 1
                self._state, self._tok = admit(
                    self.params, self._state, self._tok, jnp.asarray(prompts))
            for step in self._steps.values():
                self._state, self._tok = step(
                    self.params, self._state, self._tok)

    # -- submission (any thread) ---------------------------------------------
    def submit(self, input_ids: Sequence[int],
               max_new_tokens: Optional[int] = None, *,
               priority: str = "interactive") -> ResponseStream:
        """Queue one encoder prompt; returns its token stream immediately.
        ``priority`` follows the same SLO-class contract as the causal-LM
        engine (admission is FIFO through the classes here; shed thresholds
        and per-class gauges apply)."""
        if self._closed:
            raise EngineClosedError("engine is shut down")
        if self._draining:
            raise EngineDrainingError(
                f"engine {self.name!r} is draining; submit elsewhere")
        if priority not in PRIORITIES:
            raise ValueError(
                f"unknown priority {priority!r} (expected one of {PRIORITIES})"
            )
        prompt = [int(t) for t in input_ids]
        if not prompt:
            raise ValueError("empty prompt")
        if len(prompt) > self.config.max_input_len:
            raise ValueError(
                f"prompt ({len(prompt)}) exceeds max_input_len "
                f"({self.config.max_input_len})"
            )
        budget = (self.config.max_new_tokens if max_new_tokens is None
                  else int(max_new_tokens))
        if not 1 <= budget <= self.config.max_new_tokens:
            raise ValueError(
                f"max_new_tokens must be in [1, "
                f"{self.config.max_new_tokens}], got {budget}"
            )
        with self._id_lock:
            rid = self._next_request_id
            self._next_request_id += 1
        stream = ResponseStream(rid)
        req = Request(request_id=rid, prompt=prompt, max_new_tokens=budget,
                      stream=stream, priority=priority)
        try:
            self.scheduler.submit(req)
        except EngineOverloadedError:
            self.metrics.record_reject(priority)
            raise
        self.metrics.record_submit(priority)
        return stream

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
        """One engine iteration: admit queued requests to free slots (an
        admit program each, queued on the device behind the step in
        flight), then one token step: issue the next decode step, then read
        back and emit the one before.  Returns True if any work happened."""
        with self._step_lock:
            worked = self._admit()
            worked = self._token_step() or worked
            self.metrics.observe_gauges(
                self.scheduler.depth(),
                sum(r is not None for r in self._rows),
                queue_by_class=self.scheduler.depth_by_class(),
                draining=self._draining,
            )
            return worked

    def idle(self) -> bool:
        # a step is unread only while a row it decodes for is live
        with self._step_lock:  # _rows is step-loop state (see step())
            return (self.scheduler.depth() == 0
                    and all(r is None for r in self._rows))

    # -- draining (same contract as InferenceEngine.drain) -------------------
    def drain(self) -> None:
        """Refuse new submits; queued + decoding work retires normally."""
        # airlint: disable=CC001 — monotonic GIL-atomic bool, flips
        # False→True once; a racing step() reads either value correctly
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def drained(self) -> bool:
        return self._draining and self.idle()

    def _admit(self) -> bool:
        """One round of admission: the queued requests the free slots can
        take, lowest slot first, an admit program each."""
        if not self.scheduler.depth():
            return False
        free = [s for s, r in enumerate(self._rows) if r is None]
        reqs = self.scheduler.pop_admissible(len(free)) if free else []
        if not reqs:
            return False
        in_flight = len(free) < len(self._rows)
        with phase("engine.prefill", rows=len(reqs),
                   queued=self.scheduler.depth(), in_flight=int(in_flight)):
            for req, slot in zip(reqs, free):
                length = min(n for n in self._admits if n >= len(req.prompt))
                prompt = np.full((1, length + 2), self.pad_token_id, np.int32)
                prompt[0, :len(req.prompt)] = req.prompt
                prompt[0, length:] = len(req.prompt), slot
                self._state, self._tok = self._admits[length](
                    self.params, self._state, self._tok, jnp.asarray(prompt))
                self._rows[slot] = req
                self._budget_left[slot] = req.max_new_tokens
        self.metrics.record_admission(len(reqs), in_flight)
        self._span["rows"] += len(reqs)
        return True

    def _token_step(self) -> bool:
        unread, self._unread = self._unread, None
        owed = {id(req) for _, req in unread.rows} if unread else ()
        # budgets are host state: a row is in the step to issue only if it
        # has a token left after the one the unread step holds for it
        want = [(slot, req) for slot, req in enumerate(self._rows)
                if req is not None
                and self._budget_left[slot] - (id(req) in owed) >= 1]
        # a row of the unread step that has ended since (on EOS, learnt at
        # the last read-back) is not read for
        live = [(slot, req) for slot, req in (unread.rows if unread else ())
                if self._rows[slot] is req]
        if not want and not live:
            return False
        with phase("engine.step", live=len(live),
                   batch=unread.batch if unread else 0, ahead=int(bool(want))):
            if want:
                # out before the step before is read: the device runs it
                # while the host reads, emits and retires below
                with phase("engine.dispatch"):
                    self._issue(want, ahead=unread is not None)
            if live:
                self._read(unread, live)
            if self._unread is not None and not any(
                    self._rows[slot] is req for slot, req in self._unread.rows):
                # every row it decodes for has ended: nobody will read it
                self._drop_unread()
        return True

    def _issue(self, want: List[Tuple[int, Request]], ahead: bool) -> None:
        """Issue one decode step over the smallest prefix of the slots that
        holds every row of ``want``.  The state is donated; the tokens are
        not, so the host can still read them after the next issue."""
        rows = min(r for r in self._steps if r > want[-1][0])
        self._state, self._tok = self._steps[rows](
            self.params, self._state, self._tok)
        self._unread = _Issued(self._tok, want, rows)
        self.metrics.record_issue(ahead, rows=rows)
        if not ahead:
            self._mark = time.monotonic()

    def _read(self, unread: _Issued, live: List[Tuple[int, Request]]) -> None:
        with phase("engine.readback"):
            nxt = np.asarray(unread.tokens)
        # what this token step cost the stream: read-back to read-back
        now = time.monotonic()
        span = self._span
        if not span["steps"]:
            span["opened_at"] = self._mark      # where its first step began
        dt, self._mark = now - self._mark, now
        # one phase around the walk over the live rows, none per row
        with phase("engine.emit", emitted=len(live)):
            for slot, req in live:
                # airlint: disable=JX004 — nxt is the np.asarray'd step
                # result; the single device sync already happened above
                token = int(nxt[slot])
                if req.first_token_at is None:
                    self.metrics.record_ttft(*req.first_token(now, chunks=1),
                                             req.priority)
                req.stream._emit(token)
                self._budget_left[slot] -= 1
                if self._budget_left[slot] == 0 or token == self.eos_token_id:
                    self._retire(slot)
            self.metrics.record_step(dt, len(live))
            span["steps"] += 1
            span["live_row_steps"] += len(live)
            span["row_steps"] += unread.batch
            if span["steps"] == self.config.max_new_tokens:
                self._close_span()

    def _drop_unread(self) -> None:
        """Forget the issued step: the device finishes it and whatever comes
        next queues behind."""
        self._unread = None
        self.metrics.record_dropped_step()

    def _new_span(self) -> Dict[str, float]:
        return {"steps": 0, "rows": 0, "live_row_steps": 0, "row_steps": 0,
                "opened_at": 0.0}

    def _close_span(self) -> None:
        """One ``engine.window_close`` says what the last ``max_new_tokens``
        token steps read did: the ``rows`` admitted meanwhile, how many of the
        ``row_steps`` they ran over (the prefix of each, summed) had a live
        row, how long they took (the first one's start to the last one's
        read-back) and the depth left waiting."""
        span, self._span = self._span, self._new_span()
        with phase("engine.window_close", steps=span["steps"],
                   rows=span["rows"], batch=self.config.max_batch,
                   live_row_steps=span["live_row_steps"],
                   row_steps=span["row_steps"],
                   us=int((self._mark - span["opened_at"]) * 1e6),
                   queued=self.scheduler.depth()):
            pass

    def _retire(self, slot: int) -> None:
        self._rows[slot].stream._finish()
        self._rows[slot] = None
        self.metrics.record_complete()

    # -- background loop / lifecycle -----------------------------------------
    def start(self) -> None:
        if self._thread is not None:
            return
        self._thread = threading.Thread(
            target=self._loop, name=f"tpu-air-{self.name}", daemon=True
        )
        self._thread.start()

    def _loop(self) -> None:
        # airlint: disable=CC001 — GIL-atomic stop flag; close() sets it
        # then joins this thread, so a stale read costs one extra iteration
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
            for slot, req in enumerate(self._rows):
                if req is not None:
                    req.stream._finish(err)
                    self._rows[slot] = None
            if self._unread is not None:
                self._drop_unread()
        unregister(self.name)

    def __enter__(self) -> "T5Engine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
