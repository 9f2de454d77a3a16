"""Kind ``finetune``: ``T5Trainer.fit`` on synthetic rows from the seed.

One leased worker process holds the cell's chips (``ScalingConfig(num_workers
=chips, num_chips_per_worker=1)``: a ``data=chips`` mesh in one process).
Evaluation and saving are off, so one program compiles.  The job runs
``1 + E`` epochs of ``steps_per_epoch`` steps over the same rows; epoch 1
compiles and warms up and is set-up, epochs 2.. are the window, clocked by
the ``_timestamp`` each ``session.report`` takes after the epoch's loss has
been read back from the device.  ``E`` is fixed by ``--seconds`` and the
traffic file's ``nominal_step_ms``: a fixed amount of work, not a deadline.
"""

from __future__ import annotations

import json
import math
import os

import numpy as np

from benchmark import traffic as gen
from benchmark import weights


def run(ctx) -> None:
    import tpu_air.data
    from tpu_air.train import RunConfig, ScalingConfig, TrainingArguments

    from benchmark.worker_hooks import ObservedT5Trainer

    t = ctx.traffic
    chips = ctx.chips
    per_device = int(t["per_device_train_batch_size"])
    enc, dec = int(t["encoder_len"]), int(t["decoder_len"])
    steps = int(t["steps_per_epoch"])
    step_ms = float(t["nominal_step_ms"])
    epochs = 1 + max(2, int(round(ctx.seconds * 1000.0 / (steps * step_ms))))
    global_batch = per_device * chips

    rng = np.random.default_rng([ctx.seed, 1])
    cols = gen.token_rows(rng, global_batch * steps, ctx.cfg["vocab_size"],
                          enc, dec)
    train = tpu_air.data.from_items(gen.as_items(cols))
    facts_path = os.path.join(ctx.scratch, "train_facts.json")
    result = ObservedT5Trainer(
        model_config=weights.t5_config(ctx.cfg, t["compute_dtype"]),
        training_args=TrainingArguments(
            per_device_train_batch_size=per_device,
            num_train_epochs=epochs, seed=ctx.seed,
            evaluation_strategy=t["evaluation_strategy"],
            save_strategy=t["save_strategy"]),
        scaling_config=ScalingConfig(num_workers=chips,
                                     num_chips_per_worker=1),
        datasets={"train": train},
        run_config=RunConfig(name=ctx.cell["name"], storage_path=ctx.scratch),
        trainer_init_config={"_bench": {
            "out": facts_path,
            "trace_dir": ctx.trace_dir if ctx.trace else None,
            "trace_delay_s": float(t["trace_delay_steps"]) * step_ms / 1000.0,
            "trace_s": float(t["trace_steps"]) * step_ms / 1000.0}},
    ).fit()
    if result.error is not None:
        raise result.error

    history = result.metrics_history
    stamps = [h["_timestamp"] for h in history]
    ctx.attempted = epochs * steps
    done = sum(int(h["steps"]) for h in history)
    ctx.failed = ctx.attempted - done
    ctx.check(len(history) == epochs and all(
        int(h["steps"]) == steps for h in history),
        f"wanted {epochs} epochs of {steps} steps, got "
        f"{[h['steps'] for h in history]}")
    losses = [float(h["loss"]) for h in history]
    lo, hi = t["loss_range"]
    ctx.check(all(math.isfinite(x) and lo <= x <= hi for x in losses),
              f"losses {losses} outside [{lo}, {hi}]")
    recorded = t.get("recorded_loss") or {}
    if not ctx.rehearse and recorded.get("seed") == ctx.seed:
        want = float(recorded["epoch1_loss"])
        ctx.check(abs(losses[0] - want) <= 1e-3 * abs(want),
                  f"epoch-1 loss {losses[0]} is not the recorded {want} "
                  f"for seed {ctx.seed}")
    last = history[-1]
    for key in ("mesh_data", "param_devices", "batch_devices"):
        ctx.check(last[key] == chips, f"{key}={last[key]}, wanted {chips}")

    with open(facts_path) as f:
        seen = json.load(f)
    warm, end = seen.get("after_warmup", {}), seen["at_end"]
    ctx.check(end["cold_compiles"] == warm.get("cold_compiles"),
              f"cold compiles after warm-up: {warm.get('cold_compiles')} -> "
              f"{end['cold_compiles']}")

    window_steps = done - int(history[0]["steps"])
    ctx.window_s = stamps[-1] - stamps[0]
    ctx.window_start = stamps[0]
    ctx.facts.update({
        "window_s": ctx.window_s,
        "train_steps": window_steps,
        "train_tokens": window_steps * global_batch * (enc + dec),
        "train_step_ms": 1000.0 * ctx.window_s / max(window_steps, 1),
        "encoder_len": enc, "decoder_len": dec,
        "epoch_s": [b - a for a, b in zip(stamps, stamps[1:])],
        "memory_peak_bytes": end.get("memory_peak_bytes"),
        "worker_compile_s": end["compile_s"],
        "worker_cold_compiles": end["cold_compiles"],
        "worker_cache_hits": end["cache_hits"],
        "compile_s_in_window": end["compile_s"] - warm.get("compile_s", 0.0),
    })
    ctx.notes.update(epochs=epochs, global_batch=global_batch,
                     epoch1_loss=losses[0], last_loss=losses[-1],
                     trace=seen.get("trace"))
