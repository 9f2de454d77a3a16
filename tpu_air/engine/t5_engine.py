"""Windowed continuous decoding for the T5 family.

The PR 1 engine entry points for T5 (models/t5/generate.py:
``make_t5_prefill_fn`` / ``make_t5_decode_step_fn``) are BATCH-
SYNCHRONIZED: the decode cache carries one scalar cache index and the
whole batch's cross-attention K/V, so rows cannot sit at different decode
positions the way the causal-LM slot pool allows.  :class:`T5Engine` is
therefore a WINDOW engine, honest about that boundary:

* requests queue through the same :class:`~tpu_air.engine.scheduler.
  Scheduler` (backpressure, FIFO) and stream back per-token on the same
  :class:`~tpu_air.engine.types.ResponseStream`;
* a *window* is one prefill (encode + cache build + first token) over up
  to ``max_batch`` queued requests padded to a fixed shape, followed by
  per-token decode steps driven between host visits — tokens stream out
  as they are decoded, rows retire individually on EOS (inclusive) or
  budget;
* ADMISSION happens only at window boundaries: a window must fully drain
  before the next batch starts (the cross-attn K/V of a retired row
  cannot be swapped out under the scalar index).  Early-retired rows ride
  along as dead weight until the window closes — exactly the cost the
  causal-LM slot engine exists to avoid; per-slot cross-attn slabs remain
  the open item before T5 can join the slot pool (ROADMAP).

ONE STEP IN FLIGHT.  Within a window the decode loop runs one step ahead
of its host: step N+1 is issued from step N's tokens as they lie on the
device (the step's ``int32[b]`` output is the next call's ``tok``; the
encoder mask is uploaded once, when the window opens), and only then is step
N read back, emitted and retired from — so the host's whole visit (issue,
read-back, the walk over the rows) runs under a device step instead of
between two.  What follows from reading one step late:

* budgets are host state, so a step is issued only if some row that was live
  after the last processed read-back still has budget for it: a window whose
  rows end on their budgets issues exactly the steps it reads;
* a row that ends on EOS is learnt of one step late.  It rides along for
  that step the way retired rows ride until the window closes, and its extra
  token is discarded, never emitted;
* if the last live rows all end on EOS, the one issued step is dropped with
  the window's cache (``steps_dropped``); the next window's prefill queues
  behind it on the device.

Depth is one and fixed.  The step path uploads nothing.

Greedy by construction: token streams are identical to offline T5
``generate`` with ``early_stop=True`` on the same window batch.
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass
from typing import List, Optional, Sequence

import numpy as np

import jax.numpy as jnp

from tpu_air.models.t5.generate import (
    make_t5_decode_step_fn,
    make_t5_prefill_fn,
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


@dataclass
class T5EngineConfig:
    """Dials for the T5 window engine.

    * ``max_batch`` — rows per window (the fixed prefill/decode batch
      shape; short windows pad with dead all-pad rows).
    * ``max_input_len`` — encoder-side prompt cap; prompts right-pad to
      this fixed length so one compiled prefill serves every window.
    * ``max_new_tokens`` — decode budget cap per request (the cache is
      sized to it).
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
    reorder_window: int = 0  # window admission is FIFO; kept for Scheduler
    queue_shares: Optional[dict] = None

    def queue_cap(self, priority: str) -> int:
        """Total queue depth at which ``priority``-class submits shed
        (shares mirror EngineConfig's defaults)."""
        shares = self.queue_shares or {
            "interactive": 1.0, "batch": 0.85, "best_effort": 0.5,
        }
        return int(self.max_queue * float(shares.get(priority, 1.0)))


class _Window:
    """One in-flight batch: device state + per-row host bookkeeping.

    ``cache``, ``enc`` and ``enc_mask`` live on the device for the window's
    life.  ``unread`` is the ``int32[b]`` device output of the one issued
    step the host has not read back yet: the next step's ``tok``, and what
    the next read-back copies.  ``mark`` is when the stream's current token
    step began: the end of the last read-back, or for a window's first step
    its issue.  What the window did, said once as it closes
    (``engine.window_close``): ``opened_at``, the ``rows`` it admitted, the
    token ``steps`` it emitted from (its prefill and every step read back)
    and ``live_row_steps``, the rows live in each of them summed (the
    tokens it emitted)."""

    def __init__(self, requests: List[Optional[Request]], cache, enc,
                 enc_mask, opened_at: float):
        self.requests = requests
        self.cache = cache
        self.enc = enc
        self.enc_mask = enc_mask
        self.unread = None
        self.mark = 0.0
        self.budget_left = np.zeros((len(requests),), np.int64)
        self.opened_at = opened_at
        self.rows = self.live_row_steps = len(self.live_rows())
        self.steps = 1

    def live_rows(self):
        return [i for i, r in enumerate(self.requests) if r is not None]


class T5Engine:
    """Window-level continuous decoding over a T5 model (see module doc)."""

    def __init__(self, model, params, config: Optional[T5EngineConfig] = None,
                 *, auto_start: bool = True, name: str = "t5-engine"):
        self.model = model
        self.params = params
        self.config = config or T5EngineConfig()
        self.name = name
        self.eos_token_id = model.config.eos_token_id
        self.pad_token_id = model.config.pad_token_id

        cfg = self.config
        self._prefill = make_t5_prefill_fn(model, cfg.max_new_tokens + 1)
        self._decode_step = make_t5_decode_step_fn(model)
        self._window: Optional[_Window] = None

        self.scheduler = Scheduler(cfg)
        self.metrics = EngineMetrics(name=name, num_slots=cfg.max_batch)

        self._next_request_id = 0
        self._id_lock = threading.Lock()
        self._step_lock = threading.Lock()
        self._closed = False
        self._draining = False
        self._thread: Optional[threading.Thread] = None
        if auto_start:
            self.start()

    # -- submission (any thread) ---------------------------------------------
    def submit(self, input_ids: Sequence[int],
               max_new_tokens: Optional[int] = None, *,
               priority: str = "interactive") -> ResponseStream:
        """Queue one encoder prompt; returns its token stream immediately.
        ``priority`` follows the same SLO-class contract as the causal-LM
        engine (admission is window-FIFO here, but shed thresholds and
        per-class gauges still apply)."""
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
        """One engine iteration: open a window if none is in flight (one
        prefill over the queued batch, and the window's first decode step
        issued behind it), else one token step: issue the next decode step,
        then read back and emit the one before.  Returns True if any work
        happened."""
        with self._step_lock:
            worked = False
            if self._window is None:
                worked = self._open_window()
            elif self._window is not None:
                self._decode_window()
                worked = True
            occ = len(self._window.live_rows()) if self._window else 0
            self.metrics.observe_gauges(
                self.scheduler.depth(), occ,
                queue_by_class=self.scheduler.depth_by_class(),
                draining=self._draining,
            )
            return worked

    def idle(self) -> bool:
        # an issued step lives in its window (``_Window.unread``) and a
        # window closes when nothing is left to read: no window, no step
        with self._step_lock:  # _window is step-loop state (see step())
            return self.scheduler.depth() == 0 and self._window is None

    # -- draining (same contract as InferenceEngine.drain) -------------------
    def drain(self) -> None:
        """Refuse new submits; queued + in-window work retires normally."""
        # airlint: disable=CC001 — monotonic GIL-atomic bool, flips
        # False→True once; a racing step() reads either value correctly
        self._draining = True

    @property
    def draining(self) -> bool:
        return self._draining

    def drained(self) -> bool:
        return self._draining and self.idle()

    def _open_window(self) -> bool:
        reqs = self.scheduler.pop_admissible(self.config.max_batch)
        if not reqs:
            return False
        with phase("engine.prefill", rows=len(reqs),
                   batch=self.config.max_batch,
                   queued=self.scheduler.depth()):
            self._prefill_window(reqs)
        return True

    def _prefill_window(self, reqs: List[Request]) -> None:
        cfg = self.config
        b, li = cfg.max_batch, cfg.max_input_len
        ids = np.full((b, li), self.pad_token_id, np.int32)
        mask = np.zeros((b, li), np.int32)
        for row, req in enumerate(reqs):
            ids[row, :len(req.prompt)] = req.prompt
            mask[row, :len(req.prompt)] = 1
        # rows past len(reqs) are dead filler: all-pad, zero mask — their
        # decode outputs are discarded host-side
        mask_dev = jnp.asarray(mask)
        tok_dev, cache, enc = self._prefill(
            self.params, jnp.asarray(ids), mask_dev)
        tok = np.asarray(tok_dev)
        rows: List[Optional[Request]] = list(reqs) + [None] * (b - len(reqs))
        # every row was admitted by one round: one reading opened the window
        win = _Window(rows, cache, enc, mask_dev, reqs[0].admitted_at)
        now = time.monotonic()
        emitted = 0
        for row, req in enumerate(reqs):
            first = int(tok[row])
            self.metrics.record_ttft(*req.first_token(now, chunks=1),
                                     req.priority)
            req.stream._emit(first)
            emitted += 1
            win.budget_left[row] = req.max_new_tokens - 1
            if win.budget_left[row] == 0 or first == self.eos_token_id:
                self._retire(win, row)
        self.metrics.record_tokens(emitted)
        self._window = win
        if win.live_rows():
            # the window's first step, from the prefill's tokens as they
            # lie on the device; nothing is in flight, so it is not ahead
            self._issue(win, tok_dev, ahead=False)
            win.mark = time.monotonic()
        else:
            self._drop_window()  # every row ended on its first token

    def _issue(self, win: _Window, tok, ahead: bool) -> None:
        """Issue one decode step from the device tokens ``tok``.  The cache
        is donated; ``tok`` is not, so the host can still read it back."""
        win.cache, win.unread = self._decode_step(
            self.params, win.cache, tok, win.enc, win.enc_mask)
        self.metrics.record_issue(ahead)

    def _decode_window(self) -> None:
        win = self._window
        live = win.live_rows()
        # budgets are host state: step N+1 is worth issuing only if a live
        # row has a token left after the one step N holds for it
        ahead = any(win.budget_left[row] >= 2 for row in live)
        with phase("engine.step", live=len(live),
                   batch=self.config.max_batch, ahead=int(ahead)):
            unread, win.unread = win.unread, None
            if ahead:
                # out before step N is read: the device runs it while the
                # host reads, emits and retires below
                with phase("engine.dispatch"):
                    self._issue(win, unread, ahead=True)
            with phase("engine.readback"):
                nxt = np.asarray(unread)
            # what this token step cost the stream: read-back to read-back
            now = time.monotonic()
            dt, win.mark = now - win.mark, now
            # one phase around the walk over the live rows, none per row
            with phase("engine.emit", emitted=len(live)):
                for row in live:
                    # airlint: disable=JX004 — nxt is the np.asarray'd step
                    # result; the single device sync already happened above
                    token = int(nxt[row])
                    req = win.requests[row]
                    req.stream._emit(token)
                    win.budget_left[row] -= 1
                    if win.budget_left[row] == 0 or token == self.eos_token_id:
                        self._retire(win, row)
                self.metrics.record_step(dt, len(live))
                win.steps += 1
                win.live_row_steps += len(live)
                if not win.live_rows():
                    # window drained: drop its cache, admit the next batch
                    # on the following step
                    self._drop_window()

    def _drop_window(self) -> None:
        """Close the window.  A step still unread (its last live rows ended
        on EOS, or the engine is closing) is dropped with the cache: the
        device finishes it and whatever comes next queues behind.  One
        ``engine.window_close`` says what the whole window did: how many of
        its ``steps`` x ``batch`` row-steps had a live row, how long it was
        open and the depth it left waiting."""
        win, self._window = self._window, None
        if win.unread is not None:
            self.metrics.record_dropped_step()
        batch = self.config.max_batch
        with phase("engine.window_close", steps=win.steps, rows=win.rows,
                   batch=batch, live_row_steps=win.live_row_steps,
                   row_steps=win.steps * batch,
                   us=int((time.monotonic() - win.opened_at) * 1e6),
                   queued=self.scheduler.depth()):
            pass

    def _retire(self, win: _Window, row: int) -> None:
        win.requests[row].stream._finish()
        win.requests[row] = None
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
            if self._window is not None:
                for row in self._window.live_rows():
                    self._window.requests[row].stream._finish(err)
                self._drop_window()
        unregister(self.name)

    def __enter__(self) -> "T5Engine":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
