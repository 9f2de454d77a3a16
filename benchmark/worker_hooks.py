"""What the benchmark runs INSIDE the leased workers — the only processes
that hold a chip, so the only ones that can take a device trace, read the
device's memory peak or clock a call without the data plane in the way.

Each class is the program's own class with observation added around it,
nothing changed inside it: the trainer still runs ``t5_train_loop``, the
predictor still runs ``T5GenerativePredictor.predict``, the replica is still
``_EngineServer``.  The hooks the program lacks (PERF.md lists them) are why
these exist; a ``tracing`` PR that adds them makes most of this file
unnecessary.
"""

from __future__ import annotations

import json
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from tpu_air.predict import T5GenerativePredictor
from tpu_air.serve.deployment import Deployment
from tpu_air.serve.engine_deployment import _EngineServer
from tpu_air.train import T5Trainer


def device_facts() -> Dict[str, Any]:
    """What this worker's backend has seen so far: the program's own
    ``chips.device_report()`` (platform, kind, count, compile seconds, cache
    hits, cold compiles) plus the memory peak of the fullest device.

    The TPU backend counts arrays (``peak_bytes_in_use``) and what running
    programs reserve for their temporaries (``peak_bytes_reserved``) apart;
    the second equals the compiler's own ``temp_size_in_bytes`` (1.41 GiB
    for the engine's prefill, measured both ways).  The largest program of
    every cell runs while the cell's arrays are all live, so the peak on
    the chip is their sum."""
    import jax

    from tpu_air.core import chips

    facts = dict(chips.device_report() or {})
    peaks = []
    for d in jax.local_devices():
        stats = d.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"])
                         + int(stats.get("peak_bytes_reserved", 0)))
    facts["memory_peak_bytes"] = max(peaks) if peaks else None
    facts["at"] = time.time()
    return facts


def _trace_for(trace_dir: str, seconds: float) -> Dict[str, float]:
    import jax

    t0 = time.time()
    jax.profiler.start_trace(trace_dir)
    t1 = time.time()
    time.sleep(seconds)
    jax.profiler.stop_trace()
    return {"start_call_s": t1 - t0, "traced_from": t1,
            "traced_to": time.time()}


class ReadWatch:
    """What the decode steps READ while it is installed had live, by the
    program that ran them: the steps, their decoding rows and the cached
    positions those rows held.  ``InferenceEngine`` reads an issued step back
    in ``_read(step, reading)``: ``reading`` the rows it decoded that still
    hold their request, each at ``pos`` (a row at position ``p`` read ``p +
    1`` positions of every attention layer: the sum the engine itself takes
    for ``record_latent_live`` / ``record_kv_live`` where it keeps such a
    count, a line above the walk that moves ``pos`` on), ``step.chunk_start``
    None where the step carried no prefill chunk.  The watch shadows that
    one bound method with a wrapper that adds the sums up and calls it, and
    takes the wrapper off again: exact, and nothing of the engine changed.
    Every counted capture (``_trace_with_counts``) keeps one, in a TRACED
    run only (an untraced run never builds one): its steps by program say
    when the capture holds both (:meth:`wants`), and its positions are the
    K/V count of a model the engine's ``stats()`` give none for (neither
    rings nor a latent pool).

    An engine without ``_read`` (``T5Engine``, a later tree) is left alone
    and :attr:`counts` stays empty: the readers then find nothing to read."""

    KEYS = ("steps_read", "steps_read_alone", "rows_read", "rows_read_alone",
            "kv_positions_read", "kv_positions_read_alone")

    def __init__(self, engine):
        self.engine = engine
        self.counts: Dict[str, int] = {}

    def _watched(self, step, reading):
        rows = len(reading)
        positions = sum(slot.pos + 1 for slot in reading)
        c = self.counts
        c["steps_read"] += 1
        c["rows_read"] += rows
        c["kv_positions_read"] += positions
        if step.chunk_start is None:
            c["steps_read_alone"] += 1
            c["rows_read_alone"] += rows
            c["kv_positions_read_alone"] += positions
        return self._read(step, reading)

    def wants(self, steps: int) -> bool:
        """Whether the watch is on an engine it fits and has read fewer than
        ``steps`` steps of either program so far."""
        if not self.counts:
            return False
        alone = self.counts["steps_read_alone"]
        return min(alone, self.counts["steps_read"] - alone) < steps

    def __enter__(self):
        self._read = getattr(self.engine, "_read", None)
        if self._read is not None:
            self.counts = dict.fromkeys(self.KEYS, 0)
            self.engine._read = self._watched
        return self

    def __exit__(self, *exc):
        if self._read is not None:
            del self.engine._read
        return False


# -- fine-tune -------------------------------------------------------------------


def observed_train_loop(config: Dict[str, Any]) -> None:
    """``t5_train_loop`` with a watcher thread beside it: device facts at the
    first report (end of the warm-up epoch) and at the end, and, if asked, a
    profiler window of ``trace_s`` seconds starting ``trace_delay_s`` after
    the first report.  Writes them as JSON to ``config["_bench"]["out"]``."""
    from tpu_air.train import session
    from tpu_air.train.t5_trainer import t5_train_loop

    bench = config["_bench"]
    sess = session.get_session()
    stop = threading.Event()
    seen: Dict[str, Any] = {}

    def watch() -> None:
        while not sess.history:
            if stop.wait(0.02):
                return
        seen["after_warmup"] = device_facts()
        if bench.get("trace_dir"):
            if stop.wait(float(bench["trace_delay_s"])):
                return
            seen["trace"] = _trace_for(bench["trace_dir"],
                                       float(bench["trace_s"]))

    watcher = threading.Thread(target=watch, daemon=True)
    watcher.start()
    try:
        t5_train_loop(config)
    finally:
        stop.set()
        watcher.join()
        seen["at_end"] = device_facts()
        with open(bench["out"], "w") as f:
            json.dump(seen, f)


class ObservedT5Trainer(T5Trainer):
    def _training_fn(self):
        return observed_train_loop


# -- batch generation --------------------------------------------------------------


class ObservedT5Predictor(T5GenerativePredictor):
    """Clocks every ``predict`` call on the worker's clock (the returned ids
    are numpy, so the device has finished), traces one call if asked, and
    sends the readings back as columns of the block it returns."""

    @classmethod
    def from_checkpoint(cls, checkpoint, *, bench: Optional[Dict] = None,
                        **kwargs):
        self = super().from_checkpoint(checkpoint, **kwargs)
        self._bench = dict(bench or {})
        self._calls = 0
        return self

    def predict(self, data, **kwargs):
        import jax

        self._calls += 1
        traced = (self._bench.get("trace_dir")
                  and self._calls == int(self._bench.get("trace_call", 3)))
        if traced:
            jax.profiler.start_trace(self._bench["trace_dir"])
        t0 = time.time()
        out = super().predict(data, **kwargs)
        t1 = time.time()
        if traced:
            jax.profiler.stop_trace()
        facts = device_facts()
        out["bench_call"] = self._calls
        out["bench_start"] = t0
        out["bench_end"] = t1
        out["bench_traced"] = bool(traced)
        out["bench_facts"] = json.dumps(facts)
        return out


# -- serving -----------------------------------------------------------------------


class ObservedEngineServer(_EngineServer):
    """The replica, with methods the driver calls OUTSIDE the measured
    window (facts, the correctness reference) and one that opens a profiler
    window from a thread of its own, so the replica's message loop stays
    free while it is traced."""

    #: ``stats()`` counters whose change over the profiler's window says what
    #: the CAPTURED steps did (a kind's own; none: a bare capture)
    TRACED_COUNTERS: Tuple[str, ...] = ()

    def bench_facts(self) -> Dict[str, Any]:
        self._ensure_engine()
        return device_facts()

    def bench_trace(self, trace_dir: str, seconds: float) -> bool:
        self._ensure_engine()
        threading.Thread(
            target=self._trace_with_counts if self.TRACED_COUNTERS
            else _trace_for, args=(trace_dir, seconds), daemon=True).start()
        return True

    #: a capture ends after ``seconds`` once its :class:`ReadWatch` has read
    #: this many steps of EACH program (the decode step alone, the mixed
    #: step), and after ``CAPTURE_MOST`` times ``seconds`` whatever it read
    CAPTURE_STEPS = 8
    CAPTURE_MOST = 3.0

    def _trace_with_counts(self, trace_dir: str, seconds: float) -> None:
        """``_trace_for`` with the engine's ``TRACED_COUNTERS`` read, and a
        :class:`ReadWatch` kept, once the capture has started and before it
        is stopped: what the CAPTURED steps did (the profiler's own window,
        not the run's average), which the roofline readers divide the
        captured programs' time by.  Kept as soon as it is read.

        The capture lasts ``seconds``, and longer, in tenths of a second up
        to ``CAPTURE_MOST`` times that, while the watch has read fewer than
        ``CAPTURE_STEPS`` steps of either program: a cell's per-layer
        metrics read BOTH programs, and where the window's prefill backlog
        keeps a chunk on every step for two seconds (``laguna-serve-
        mixedlen``'s seconds 10 to 12 offer 101 chunks where 92 steps fit;
        a capture asked for at 8 s that the host starts a second late is
        there) a capture of fixed length holds no decode step and half the
        metrics have nothing to read (PERF.md, PR 61)."""
        import jax

        engine = self._ensure_engine()
        jax.profiler.start_trace(trace_dir)
        with ReadWatch(engine) as watch:
            before = engine.metrics.snapshot()
            until = time.monotonic() + self.CAPTURE_MOST * seconds
            time.sleep(seconds)
            while watch.wants(self.CAPTURE_STEPS) and time.monotonic() < until:
                time.sleep(0.1)
            after = engine.metrics.snapshot()
        self._traced = {k: after.get(k, 0) - before.get(k, 0)
                        for k in self.TRACED_COUNTERS}
        self._traced.update(watch.counts)
        jax.profiler.stop_trace()

    def bench_traced_counts(self) -> Dict[str, int]:
        """What ``_trace_with_counts`` left (a kind with ``TRACED_COUNTERS``);
        empty before a capture and in an untraced run."""
        return dict(getattr(self, "_traced", {}))

    def bench_teacher_forced(self, prompts: List[List[int]],
                             answers: List[List[int]]) -> List[Dict[str, Any]]:
        """For each prompt and the tokens the engine streamed for it: run
        the model's FULL (uncached) forward with those tokens teacher-forced
        and say, per position, how far the streamed token's logit lies
        under the row's largest.  The cached window decode and this pass
        are different programs over the same parameters; with random
        weights the largest logit changes on rounding, so logits are
        compared and not token ids."""
        import jax
        import jax.numpy as jnp
        import numpy as np

        from tpu_air.models.t5 import shift_right

        engine = self._ensure_engine()
        model, cfg = engine.model, engine.model.config
        n = len(prompts)
        li, lo = engine.config.max_input_len, engine.config.max_new_tokens
        ids = np.full((n, li), cfg.pad_token_id, np.int32)
        mask = np.zeros((n, li), np.int32)
        toks = np.zeros((n, lo), np.int32)
        dec_mask = np.zeros((n, lo), np.int32)
        for i, (p, a) in enumerate(zip(prompts, answers)):
            ids[i, :len(p)], mask[i, :len(p)] = p, 1
            toks[i, :len(a)], dec_mask[i, :len(a)] = a, 1

        @jax.jit
        def margins(params, ids, mask, toks, dec_mask):
            dec_in = shift_right(toks, cfg.decoder_start_token_id,
                                 cfg.pad_token_id)
            logits = model.apply(
                {"params": params}, ids, mask, dec_in,
                decoder_attention_mask=dec_mask, deterministic=True,
            ).astype(jnp.float32)
            top = logits.max(-1)
            chosen = jnp.take_along_axis(logits, toks[..., None], -1)[..., 0]
            scale = top - jnp.median(logits, -1)
            return (top - chosen) / scale, logits.argmax(-1) == toks

        rel, exact = margins(engine.params, ids, mask, toks, dec_mask)
        rel, exact = np.asarray(rel), np.asarray(exact)
        out = []
        for i, a in enumerate(answers):
            k = len(a)
            out.append({"tokens": k,
                        "worst_margin": float(rel[i, :k].max()) if k else 0.0,
                        "exact": int(exact[i, :k].sum())})
        return out


ObservedEngineDeployment = Deployment(
    func_or_class=ObservedEngineServer,
    name="EngineDeployment",
    num_replicas=1,
)
