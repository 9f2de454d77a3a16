"""Training session context — the worker↔driver reporting channel.

SURVEY.md §5 metrics notes: "a ``report(metrics, checkpoint)`` primitive from
workers → driver, pluggable sinks."  The training loop calls
``session.report`` per epoch; the session records history, applies
score-based checkpoint retention (CheckpointConfig, cc-40), forwards metrics
to sinks (tensorboard/prometheus when available), and raises ``StopTrial``
when a Tune scheduler has pruned the trial (ASHA, cc-51).
"""

from __future__ import annotations

import json
import os
import shutil
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

from tpu_air.faults import plan as _faults
from tpu_air.observability import tracing as _tracing

from .checkpoint import Checkpoint
from .config import CheckpointConfig


class StopTrial(Exception):
    """Raised inside the training loop when the scheduler stops this trial."""


class Session:
    def __init__(
        self,
        run_dir: str,
        checkpoint_config: Optional[CheckpointConfig] = None,
        datasets: Optional[Dict[str, Any]] = None,
        config: Optional[Dict[str, Any]] = None,
        world_size: int = 1,
        decision_cb: Optional[Callable[[Dict[str, Any]], bool]] = None,
        sinks: Optional[List] = None,
    ):
        self.run_dir = run_dir
        self.checkpoint_config = checkpoint_config or CheckpointConfig()
        self.datasets = datasets or {}
        self.config = config or {}
        self.world_size = world_size
        self.decision_cb = decision_cb
        self.sinks = sinks if sinks is not None else _default_sinks(run_dir)
        self.history: List[Dict[str, Any]] = []
        self.checkpoints: List[Tuple[str, Dict[str, Any]]] = []  # (dir, metrics)
        self._iter = 0
        # airtrace: ambient context at session construction (the trainer's
        # task span on the worker) so every train.iteration span lands on
        # the same trial timeline; report-to-report window stamps
        self._trace_ctx = _tracing.current_propagation()
        self._last_report_ns = _tracing.now_ns() if _tracing.enabled() else 0
        os.makedirs(run_dir, exist_ok=True)

    # -- dataset access (train_loop_per_worker surface) --------------------
    def get_dataset_shard(self, name: str):
        return self.datasets.get(name)

    # -- reporting ---------------------------------------------------------
    def report(self, metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None):
        self._iter += 1
        if _faults.enabled():
            # deterministic chaos (docs/RESILIENCE.md): a "kill" here takes
            # the whole trial actor down BEFORE this report's checkpoint is
            # retained — exactly the crash FailureConfig recovery must
            # survive by resuming from the previous retained checkpoint
            spec = _faults.perturb("train.report", key=str(self._iter))
            if spec is not None and spec.action == "kill":
                os._exit(1)
        rec = dict(metrics)
        rec.setdefault("training_iteration", self._iter)
        rec.setdefault("_timestamp", time.time())
        self.history.append(rec)
        if _tracing.enabled():
            self._emit_iteration_span()
        with open(os.path.join(self.run_dir, "progress.jsonl"), "a") as f:
            f.write(json.dumps(rec, default=float) + "\n")
        for sink in self.sinks:
            try:
                sink.log(rec, self._iter)
            except Exception:  # noqa: BLE001 — a broken sink must not kill the training loop
                pass
        if checkpoint is not None:
            self._retain(checkpoint, rec)
            if self.checkpoint_config.publish_weights_to:
                self._publish_weights(checkpoint, rec)
        # pass the internal monotone counter separately: user metrics may
        # override training_iteration, but report streaming must stay
        # contiguous (the Tune driver drains report-1, report-2, …)
        if self.decision_cb is not None and not self.decision_cb(rec, self._iter):
            raise StopTrial(f"trial stopped by scheduler at iteration {self._iter}")

    def _emit_iteration_span(self) -> None:
        """One ``train.iteration`` span per report, covering the window
        since the previous report, so the trial's cadence is visible on the
        same timeline as everything else."""
        now = _tracing.now_ns()
        if self._trace_ctx is None:
            # no ambient context at construction (tracing enabled later, or
            # a bare local session): root one trace for the whole session
            self._trace_ctx = {"trace_id": _tracing.new_trace_id()}
        _tracing.record_span(
            "train.iteration",
            trace_id=self._trace_ctx.get("trace_id"),
            parent_id=self._trace_ctx.get("span_id"),
            start_ns=self._last_report_ns or now,
            end_ns=now,
            attrs={"iteration": self._iter, "run_dir": self.run_dir},
        )
        self._last_report_ns = now

    # -- weight publishing (live-serving handoff) ----------------------------
    def _publish_weights(self, checkpoint: Checkpoint,
                         metrics: Dict[str, Any]) -> None:
        """Publish the retained checkpoint's params to the configured
        WeightStore (CheckpointConfig.publish_weights_to).  The publish is
        torn-proof (manifest written last) and checksummed; a failure —
        including an injected ``weights.publish`` fault — must not kill the
        training loop: serving simply keeps the previous version."""
        from tpu_air.serve.weights import WeightStore

        try:
            params = checkpoint.get_params()
        except Exception:  # noqa: BLE001 — dict/dir checkpoint without params
            params = None
        if params is None:
            return
        cfg = self.checkpoint_config
        try:
            store = WeightStore(cfg.publish_weights_to)
            store.publish(params, metadata={
                "iteration": self._iter,
                "run_dir": self.run_dir,
                "metrics": {k: v for k, v in metrics.items()
                            if isinstance(v, (int, float, str))},
            })
            store.gc(keep=cfg.num_to_keep or 2)
        except Exception:  # noqa: BLE001 — torn publish / store error: the
            pass           # trial continues; the store still ends in a sealed
            # state (no manifest for the torn version) so serving never sees it

    # -- retention (CheckpointConfig semantics, cc-40) ----------------------
    def _retain(self, checkpoint: Checkpoint, metrics: Dict[str, Any]):
        import tempfile

        ckpt_dir = os.path.join(self.run_dir, f"checkpoint_{self._iter:06d}")
        src = checkpoint.path
        checkpoint.to_directory(ckpt_dir)
        # from_model() stages into a tempdir; once copied under run_dir the
        # staging copy would leak one param tree per epoch — remove it and
        # repoint the handle at the retained copy.
        if (
            src
            and os.path.abspath(src) != os.path.abspath(ckpt_dir)
            and os.path.abspath(src).startswith(tempfile.gettempdir() + os.sep)
        ):
            shutil.rmtree(src, ignore_errors=True)
            checkpoint._path = ckpt_dir
        self.checkpoints.append((ckpt_dir, metrics))
        cfg = self.checkpoint_config
        if cfg.num_to_keep is None or len(self.checkpoints) <= cfg.num_to_keep:
            return
        attr = cfg.checkpoint_score_attribute
        if attr:
            sign = 1 if cfg.checkpoint_score_order == "min" else -1
            ranked = sorted(
                self.checkpoints,
                key=lambda cm: sign * float(cm[1].get(attr, float("inf") * sign)),
            )
        else:
            ranked = list(self.checkpoints)  # keep most recent
            ranked.reverse()
        keep = ranked[: cfg.num_to_keep]
        for path, _ in self.checkpoints:
            if all(path != k[0] for k in keep):
                shutil.rmtree(path, ignore_errors=True)
        self.checkpoints = [cm for cm in self.checkpoints if any(cm[0] == k[0] for k in keep)]

    # -- results ------------------------------------------------------------
    def best_checkpoint(self) -> Optional[Tuple[str, Dict[str, Any]]]:
        if not self.checkpoints:
            return None
        cfg = self.checkpoint_config
        attr = cfg.checkpoint_score_attribute
        if not attr:
            return self.checkpoints[-1]
        sign = 1 if cfg.checkpoint_score_order == "min" else -1
        return min(
            self.checkpoints,
            key=lambda cm: sign * float(cm[1].get(attr, float("inf") * sign)),
        )

    def latest_checkpoint(self) -> Optional[Tuple[str, Dict[str, Any]]]:
        return self.checkpoints[-1] if self.checkpoints else None


def _default_sinks(run_dir: str) -> List:
    """Tensorboard logging is opt-in (TPU_AIR_TENSORBOARD=1): the reference
    pins tensorboardX but never configures it (SURVEY.md §5 "Sinks pinned but
    not configured"), and the writer's protobuf import chain costs ~2.5s per
    worker process — too heavy to pay silently in every trial."""
    if os.environ.get("TPU_AIR_TENSORBOARD", "0") != "1":
        return []
    try:
        from tpu_air.utils.metrics import TensorboardSink

        return [TensorboardSink(run_dir)]
    except Exception:  # noqa: BLE001 — tensorboard missing or broken: run without the sink
        return []


# -- module-level session (what user train loops import) ---------------------

_active: Optional[Session] = None


def _set_active(s: Optional[Session]):
    global _active
    _active = s


def get_session() -> Session:
    if _active is None:
        raise RuntimeError("no active training session (call inside a trainer loop)")
    return _active


def report(metrics: Dict[str, Any], checkpoint: Optional[Checkpoint] = None):
    get_session().report(metrics, checkpoint)


def get_dataset_shard(name: str):
    return get_session().get_dataset_shard(name)


def get_config() -> Dict[str, Any]:
    return get_session().config


def get_world_size() -> int:
    return get_session().world_size
