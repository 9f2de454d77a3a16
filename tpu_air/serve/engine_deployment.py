"""EngineDeployment — serve the continuous-batching engine over HTTP.

Each replica actor owns one engine built from a Checkpoint — a
:class:`tpu_air.engine.InferenceEngine` (slot/page pool + persistent decode
step + background loop), or a :class:`tpu_air.engine.T5Engine` when the
``engine_config`` is a :class:`~tpu_air.engine.T5EngineConfig` (the config
type selects the engine family).  Two client surfaces:

* blocking HTTP: ``POST {"prompts": [[ids...], ...], "max_new_tokens": n,
  "priority": "interactive"}`` → ``{"results": [{"request_id": ...,
  "tokens": [...]}, ...]}`` — every prompt is submitted up front so they
  share slot-pool steps, then joined.
* streaming over HTTP (action payloads): ``POST {"action": "submit",
  "prompt": [ids...], "priority": ...}`` → ``{"request_id": rid}``
  immediately (no blocking — the actor's message loop stays free), then
  ``POST {"action": "poll", "request_id": rid, "cursor": c}`` →
  ``{"tokens": <new since cursor>, "done": bool}``.  Polls must land on
  the replica that took the submit — the proxy round-trips the replica
  tag in the ``x-tpu-air-replica`` header and pins polls to it.  The same
  submit/poll pair is also callable over actor RPC
  (``handle.method("submit")(...)``).

Backpressure: a full admission queue raises
:class:`~tpu_air.engine.EngineOverloadedError` inside the replica (class-
aware — best-effort sheds at a lower queue depth than interactive); a
DRAINING replica (zero-downtime rollout) raises ``EngineDrainingError``
for new submits while admitted streams keep polling.  Both cross the
actor boundary as ``RemoteError`` and the proxy maps them to HTTP 503
(same retry semantics as ``NoLiveReplicasError``).
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

from .deployment import Deployment


class _EngineServer:
    """The engine itself is built LAZILY on the first request, not in
    ``__init__``: the core runtime round-trips a replica instance through
    the (pickle-based) object store at actor creation, and a live engine
    holds threads, locks and device buffers — unpicklable by design.  The
    constructor keeps only the picklable recipe (checkpoint + config)."""

    def __init__(
        self,
        checkpoint,
        engine_config=None,
        *,
        dtype: Optional[str] = None,
        engine_name: str = "engine",
        join_timeout: float = 300.0,
        mesh: Optional[tuple] = None,
        disagg: Optional[Dict[str, Any]] = None,
    ):
        self._checkpoint = checkpoint
        self._engine_config = engine_config
        self._dtype = dtype
        self._engine_name = engine_name
        self._join_timeout = join_timeout
        # distributed serving (tpu_air.engine.dist): ``mesh=(dp, tp)``
        # builds a MeshEngine over a leased device mesh; ``disagg=`` (a
        # kwargs dict for DisaggRouter, e.g. {"prefill_replicas": 2})
        # routes prefill through separate worker actors.  Both compose.
        self._mesh = tuple(mesh) if mesh is not None else None
        self._disagg = dict(disagg) if disagg is not None else None
        self._engine = None
        self._router = None
        self._streams: Dict[int, Any] = {}
        # recently retired streams' full token lists: a poll AFTER the one
        # that delivered `done` still answers (insertion-ordered, bounded)
        self._finished: Dict[int, list] = {}
        self._draining = False
        # preemption: the chip lease this replica sits on (attached when
        # the engine builds) and the revocation notice, if one arrived
        self._lease = None
        self._preempt_notice_s: Optional[float] = None
        self._preempt_at: Optional[float] = None

    def _ensure_engine(self):
        if self._engine is None:
            # lazy import: the serve package must stay importable without jax
            from tpu_air.engine import (
                EngineConfig,
                InferenceEngine,
                T5Engine,
                T5EngineConfig,
            )

            model, params = self._checkpoint.get_model(dtype=self._dtype)
            if self._dtype:
                import jax
                import jax.numpy as jnp

                # the model says which of its leaves keep another dtype
                # (``CausalLM.cast_params``); one that says nothing has none
                cast = getattr(model, "cast_params", None)
                if cast is None:
                    def cast(tree, dtype):
                        return jax.tree_util.tree_map(
                            lambda x: (x.astype(jnp.dtype(dtype))
                                       if hasattr(x, "astype") else x), tree)
                params = cast(params, self._dtype)
            # the config type picks the engine family: a T5EngineConfig
            # gets the T5 slot engine (engine/t5_engine.py), any
            # EngineConfig (or None) the causal-LM slot/page engine
            if isinstance(self._engine_config, T5EngineConfig):
                if self._mesh or self._disagg:
                    raise ValueError(
                        "mesh/disagg serving supports the causal-LM paged "
                        "engine only")
                self._engine = T5Engine(
                    model, params, self._engine_config,
                    name=self._engine_name,
                )
            elif self._mesh is not None:
                from tpu_air.engine import MeshEngine

                dp, tp = self._mesh
                self._engine = MeshEngine(
                    model, params, self._engine_config or EngineConfig(),
                    dp=dp, tp=tp, name=self._engine_name,
                )
            else:
                self._engine = InferenceEngine(
                    model, params, self._engine_config or EngineConfig(),
                    name=self._engine_name,
                )
            if self._disagg is not None:
                from tpu_air.engine import DisaggRouter

                self._router = DisaggRouter(
                    self._checkpoint,
                    self._engine_config or EngineConfig(),
                    engine=self._engine, dtype=self._dtype,
                    name=self._engine_name, **self._disagg,
                )
            # attach the chip lease this actor was placed on: a revocation
            # notice (runtime.lease fault site, or a real preemption in
            # prod) freezes admission immediately, and the supervisor's
            # watcher sees it via preempt_status and orchestrates
            # migrate-or-replay from the driver side
            from tpu_air.core.runtime import attach_chip_lease

            self._lease = attach_chip_lease()
            self._lease.on_revoke(self._on_preempt)
        return self._engine

    def _on_preempt(self, notice_s: float) -> None:
        """Lease-revocation callback (the revoker's thread): stamp the
        notice and freeze engine admission.  The queued backlog stays
        queued — the notice window belongs to LIVE slots."""
        self._preempt_notice_s = float(notice_s)
        self._preempt_at = time.monotonic()
        engine = self._engine
        if engine is not None and hasattr(engine, "preempt"):
            engine.preempt()

    def _front(self):
        """The submit surface: the disagg router when configured (prefill
        on worker actors), else the engine itself."""
        self._ensure_engine()
        return self._router if self._router is not None else self._engine

    # -- HTTP path (blocking generate + streaming actions) --------------------
    def __call__(self, payload) -> Dict[str, Any]:
        if not isinstance(payload, dict):
            raise ValueError(
                'expected JSON object {"prompts": [[ids...], ...]} '
                '(or {"prompt": [ids...]}, or {"action": "submit"/"poll"})'
            )
        # streaming actions: fast, non-blocking RPCs — the actor's serial
        # message loop turns around immediately, so MANY clients can hold
        # concurrent streams against one replica (continuous batching is
        # only observable end-to-end through this path)
        action = payload.get("action")
        if action == "submit":
            return {"request_id": self.submit(
                payload.get("prompt") or [],
                payload.get("max_new_tokens"),
                priority=payload.get("priority", "interactive"),
                deadline_ms=payload.get("deadline_ms"),
                adapter_id=payload.get("adapter_id"),
                tenant=payload.get("tenant"),
            )}
        if action == "poll":
            return self.poll(int(payload.get("request_id", -1)),
                             int(payload.get("cursor", 0)))
        if action is not None:
            raise ValueError(f"unknown action {action!r}")
        if "prompt" in payload:
            prompts = [payload["prompt"]]
        else:
            prompts = payload.get("prompts")
        if not prompts:
            raise ValueError('payload needs "prompt" or a non-empty "prompts"')
        max_new = payload.get("max_new_tokens")
        priority = payload.get("priority", "interactive")
        deadline_ms = payload.get("deadline_ms")
        front = self._front()
        kw = {} if deadline_ms is None else {"deadline_ms": float(deadline_ms)}
        if payload.get("adapter_id") is not None:
            kw["adapter_id"] = str(payload["adapter_id"])
        # submit ALL before joining ANY — concurrent prompts share pool steps
        streams = [front.submit(p, max_new, priority=priority, **kw)
                   for p in prompts]
        return {
            "results": [
                {"request_id": s.request_id,
                 "tokens": s.result(self._join_timeout)}
                for s in streams
            ]
        }

    # -- streaming path (HTTP actions above, or direct actor RPC) -------------
    def submit(self, prompt, max_new_tokens: Optional[int] = None, *,
               priority: str = "interactive",
               deadline_ms: Optional[float] = None,
               adapter_id: Optional[str] = None,
               tenant: Optional[str] = None) -> int:
        # deadline_ms is absolute unix-epoch ms (the proxy converts the
        # client's relative budget at admission).  Passed through only when
        # set: the T5 engine doesn't take it, and None means "no
        # deadline" everywhere.  Same for adapter_id (multi-tenant LoRA —
        # paged causal-LM engines only) and tenant (pure cost-attribution
        # label, e.g. the batch lane's ``batch:<job_id>``).
        kw = {} if deadline_ms is None else {"deadline_ms": float(deadline_ms)}
        front = self._front()
        if adapter_id is not None:
            if self._router is not None:
                from ..engine.types import RequestValidationError
                raise RequestValidationError(
                    "adapter_id is not supported with disaggregated "
                    "serving (prefill workers hold no adapter bank)")
            kw["adapter_id"] = str(adapter_id)
        if tenant is not None and self._router is None \
                and hasattr(front, "submit_migrated"):
            # pure billing label, causal-LM engines only — the T5
            # engine (and the disagg router) take no per-request tenant;
            # dropping the label there degrades attribution, never submits
            kw["tenant"] = str(tenant)
        stream = front.submit(prompt, max_new_tokens,
                              priority=priority, **kw)
        self._streams[stream.request_id] = stream
        return stream.request_id

    def poll(self, request_id: int, cursor: int = 0) -> Dict[str, Any]:
        stream = self._streams.get(request_id)
        if stream is None:
            toks = self._finished.get(request_id)
            if toks is None:
                raise KeyError(f"unknown request_id {request_id}")
            if isinstance(toks, BaseException):
                raise toks  # failed-stream tombstone: every re-poll re-raises
            return {"tokens": toks[cursor:], "done": True}
        # read `done` BEFORE the tokens: done observed first guarantees the
        # token list is complete, so a client may stop at its first done
        # response without losing a tail emitted between the two reads
        done = stream.done
        toks = stream.tokens_so_far()
        if done:
            # delivery completes with this response; move the stream to the
            # bounded tombstone map so drain_status stops counting it but a
            # trailing confirmation poll still answers.  A FAILED stream
            # surfaces its error instead of masquerading as a short success —
            # DeadlineExceededError crosses the actor boundary as RemoteError
            # and the proxy maps it to HTTP 504 with Retry-After.
            self._streams.pop(request_id, None)
            err = getattr(stream, "_error", None)
            self._finished[request_id] = err if err is not None else toks
            while len(self._finished) > 512:
                self._finished.pop(next(iter(self._finished)))
            if err is not None:
                raise err
        return {"tokens": toks[cursor:], "done": done}

    # -- live weights (serve/weights.py WeightsController RPCs) ---------------
    def weights_swap(self, store_root: str,
                     version: Optional[int] = None) -> float:
        """Load ``version`` (default: latest) from the weight store —
        checksum-validated — and hot-swap it into the serving engine
        between decode steps.  Returns the swap's stall in ms."""
        from .weights import WeightStore

        engine = self._ensure_engine()
        store = WeightStore(store_root)
        if version is None:
            version = store.latest_version()
        params = store.load(version)
        return engine.swap_params(params, version=version)

    def weights_rollback(self) -> float:
        """Restore the pre-swap weights (engine-held device tree — no
        store reads, survives a corrupt/GC'd publish)."""
        return self._ensure_engine().rollback_params()

    def weights_version(self) -> Optional[int]:
        if self._engine is None:
            return None
        return self._engine.weights_version()

    def weights_probe(self, prompts, max_new: int = 8, *,
                      adapter_id: Optional[str] = None,
                      timeout_s: float = 60.0) -> list:
        """Run the canary probe prompts through THIS replica's engine
        (the full admit/prefill/decode path, not an offline forward) and
        return their greedy token lists."""
        engine = self._ensure_engine()
        kw = {} if adapter_id is None else {"adapter_id": str(adapter_id)}
        streams = [engine.submit([int(t) for t in p], int(max_new), **kw)
                   for p in prompts]
        return [s.result(float(timeout_s)) for s in streams]

    def weights_probe_logits(self, prompts) -> list:
        """Last-prompt-position logits under the SERVING params (the
        logit-tolerance gate surface for quantized bases)."""
        from .weights import probe_logits

        engine = self._ensure_engine()
        return probe_logits(engine.model, engine.params, prompts)

    def weights_load_adapter(self, name: str, a, b) -> int:
        return self._ensure_engine().load_adapter(name, a, b)

    def weights_unload_adapter(self, name: str) -> bool:
        return self._ensure_engine().unload_adapter(name)

    def weights_adapters(self) -> Dict[str, int]:
        if self._engine is None:
            return {}
        return self._engine.adapters()

    # -- draining (zero-downtime rollout / scale-down) ------------------------
    def drain(self) -> None:
        """Stop admitting new work; admitted streams retire and stay
        pollable.  Never forces the lazy engine build — a replica that
        served nothing drains instantly."""
        self._draining = True
        front = self._router if self._router is not None else self._engine
        if front is not None:
            front.drain()

    def drain_status(self) -> Dict[str, Any]:
        """``drained`` means: drain was requested, the engine retired all
        admitted work, and every finished stream was polled to its end
        (the deployment kills the replica only then — no client loses a
        tail it hasn't read)."""
        # drop fully-delivered streams a client finished mid-drain but
        # never polled past the end of
        pending = len(self._streams)
        engine_done = (self._engine is None
                       or (self._engine.drained() if self._draining
                           else False))
        return {
            "draining": self._draining,
            "pending_streams": pending,
            "drained": bool(self._draining and engine_done and pending == 0),
        }

    # -- preemption (serve/supervisor.py PreemptionWatcher RPCs) --------------
    def preempt_status(self) -> Dict[str, Any]:
        """Cheap poll surface for the driver-side watcher.  Never forces
        the lazy engine build; ``notice_left_s`` is how much of the
        revocation window remains (the watcher's migrate-vs-replay
        input)."""
        if self._preempt_notice_s is None:
            return {"preempting": False}
        left = self._preempt_notice_s - (time.monotonic() - self._preempt_at)
        return {
            "preempting": True,
            "notice_s": self._preempt_notice_s,
            "notice_left_s": max(0.0, left),
        }

    def borrow_return(self, notice_s: float = 5.0) -> bool:
        """Elastic chip borrowing (tpu_air/batch): hand this replica's
        chips back to the pool THROUGH the preemption path — deliver a
        revocation notice to our own lease, which freezes admission and
        lets the driver-side watcher drain/migrate live slots exactly as
        a real preemption would.  The batch broker calls this on replicas
        it borrowed during a trough when interactive load returns; reusing
        the lease-notice machinery means borrow-return is chaos-tested by
        construction.  Returns False when there is no lease to revoke
        (engine never built — nothing to return)."""
        if self._lease is None:
            return False
        self._lease.deliver_notice(float(notice_s))
        return True

    def migrate_out(self) -> list:
        """Freeze this replica's engine and pull every live decoding
        slot's state into portable payloads (prompt + streamed tokens +
        KV pages).  Also flips the engine into preemption drain if the
        notice callback hasn't already."""
        engine = self._ensure_engine()
        if not hasattr(engine, "migrate_out"):
            raise ValueError(
                "migrate_out needs the paged causal-LM engine "
                f"(this replica serves {type(engine).__name__})")
        # the abandoned source streams stay in ``_streams`` on purpose: a
        # client poll racing the migration window must keep getting 200s
        # (a stale-but-correct prefix) until the supervisor re-pins the
        # journal entry to the destination — this replica is going away,
        # so its drain accounting no longer matters
        return engine.migrate_out()

    def submit_migrated(self, payload: Dict[str, Any]) -> int:
        """Land one migrated stream on THIS replica (the survivor side of
        a preemption migration).  Raises synchronously — KVTransferError /
        RequestValidationError cross the actor boundary as RemoteError —
        when the payload cannot be admitted cleanly, so the supervisor
        falls back to journal replay."""
        engine = self._ensure_engine()
        if not hasattr(engine, "submit_migrated"):
            raise ValueError(
                "submit_migrated needs the paged causal-LM engine "
                f"(this replica serves {type(engine).__name__})")
        stream = engine.submit_migrated(payload)
        self._streams[stream.request_id] = stream
        return stream.request_id

    def stats(self) -> Dict[str, Any]:
        # a dashboard scrape must NEVER force the lazy engine build (model
        # load + compile) — no engine yet means nothing to report
        if self._engine is None:
            return {}
        snap = self._engine.metrics.snapshot()
        if self._router is not None:
            rst = self._router.stats()
            snap.setdefault("topology", {}).update(
                disagg="on",
                prefill_replicas=rst["prefill_replicas"],
                live_prefill_replicas=rst["live_prefill_replicas"],
            )
            snap["disagg"] = {k: rst[k] for k in
                              ("handoffs", "reroutes", "fallbacks",
                               "retries", "breakers")}
        return snap


EngineDeployment = Deployment(
    func_or_class=_EngineServer,
    name="EngineDeployment",
    num_replicas=1,
)
