#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that tpu_air still starts on the chip.

Drives the repo's main path once on an attached TPU v5e, through the entry
points users call, at FLAN-T5-base's published widths with random weights and
synthetic token ids from ``--seed``:

* train:    ``tpu_air.init()`` → ``T5Trainer.fit()`` — 4 optimiser steps at the
            W1 shape (per-device batch 32, encoder 512, decoder 128, bf16),
            one eval, one checkpoint; loss finite, checkpoint loads.
* generate: ``BatchPredictor.from_checkpoint(...).predict()`` — one W3 batch
            (256 rows, encoder 512, 128 new tokens), given twice to one
            scoring worker on one chip; token ids for every row, and the
            first 8 rows of both passes equal.
* serve:    ``serve.run(EngineDeployment.bind(checkpoint, T5EngineConfig))``
            — 8 HTTP POSTs answer 200 with tokens; the engine counts 8
            completed, none shed, none expired.

This script is the driver and never starts a JAX backend (checked after every
phase): each phase runs where users' work runs, in a worker process that holds
a chip lease, and the platform on each phase line is the one seen from inside
that worker.  A phase that raises, or that ran anywhere but on the expected
platform, ends the run non-zero at once.  The last line of stdout is

    {"ok": true, "device": {"platform": "tpu", "kind": "...", "count": 1}}

``--chips 4`` runs the cross-chip paths and what each is compared with, and
nothing else: a dp-4 fit against the same fit on one chip (global batch 32),
and four one-chip scoring workers against one — at the same widths, with depth
cut to 4+4 layers.

The phases are functions of a model config, an expected platform and sizes, so
tests/test_chip_smoke.py can rehearse them on the CPU at ``T5Config.tiny()``;
``main()`` fixes FLAN-T5-base, ``tpu`` and the sizes below and offers no option
to change them.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import shutil
import socket
import sys
import tempfile
import threading
import time
import urllib.request
from dataclasses import dataclass
from typing import Any, Callable, Dict, List, Optional

_HERE = os.path.dirname(os.path.abspath(__file__))


@dataclass(frozen=True)
class Sizes:
    """The shapes the phases run at: W1 fine-tune and W3 batch generation,
    and a serve window of the same encoder length."""

    train_batch: int = 32      # per device
    enc_len: int = 512
    dec_len: int = 128
    train_steps: int = 4
    gen_rows: int = 256
    new_tokens: int = 128
    serve_batch: int = 8
    serve_new_tokens: int = 64
    requests: int = 8


class SmokeFailure(RuntimeError):
    """A phase ran but what came out is wrong."""


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


# -- what the driver may and may not do ---------------------------------------


def driver_holds_no_backend() -> None:
    from tpu_air.core import chips

    check(not chips.backend_live(),
          "the driver started a JAX backend; on a chip host it now holds "
          "the chips its workers need")


def child_pids() -> List[int]:
    """Live (non-zombie) children of this process, from /proc."""
    me, out = os.getpid(), []
    for pid in filter(str.isdigit, os.listdir("/proc")):
        try:
            with open(f"/proc/{pid}/stat") as f:
                fields = f.read().rsplit(")", 1)[1].split()
        except OSError:
            continue
        if int(fields[1]) == me and fields[0] != "Z":
            out.append(int(pid))
    return out


def rebuild_native() -> None:
    """Start from sources: remove the git-ignored build outputs (the chip
    tool copies the tree as it stands on disk, stale binaries included) and
    let the loaders rebuild them.  The regenerated protobuf bindings must
    equal the committed file."""
    for tool in ("g++", "protoc"):
        check(shutil.which(tool) is not None,
              f"{tool} not found: the native store and the control-plane "
              "daemon cannot be built from the committed sources")
    native = os.path.join(_HERE, "tpu_air", "_native")
    for name in os.listdir(native):
        if name.endswith(".so") or name.startswith("gcs.pb.") \
                or name == "tpu_air_gcs":
            os.remove(os.path.join(native, name))
    pb2 = os.path.join(_HERE, "tpu_air", "control", "gcs_pb2.py")
    with open(pb2, "rb") as f:
        committed = f.read()
    from tpu_air._native import load_store_lib
    from tpu_air.control.client import ensure_gcs_binary

    load_store_lib()
    ensure_gcs_binary()
    with open(pb2, "rb") as f:
        check(f.read() == committed,
              "protoc regenerated tpu_air/control/gcs_pb2.py differently "
              "from the committed file")


# -- phase bookkeeping ---------------------------------------------------------


class Phases:
    """Runs phases, prints one JSON line for each, and keeps the device the
    workers saw for the last line."""

    def __init__(self, platform: str):
        self.platform = platform
        self.device: Optional[Dict[str, Any]] = None
        self._seen_workers: set = set()

    def _new_reports(self) -> List[Dict[str, Any]]:
        from tpu_air.core.runtime import get_runtime

        fresh = [r for r in get_runtime().device_reports()
                 if r["worker_id"] not in self._seen_workers]
        self._seen_workers.update(r["worker_id"] for r in fresh)
        return fresh

    def run(self, name: str, fn: Callable[[], Dict[str, Any]],
            workers: int = 1) -> Dict[str, Any]:
        """``fn`` does the phase's work and returns what to add to its
        line; ``workers`` is how many leased workers must have reported."""
        t0 = time.time()
        extra = fn()
        seconds = time.time() - t0
        driver_holds_no_backend()
        reports = self._new_reports()
        check(len(reports) == workers,
              f"{name}: expected a device report from {workers} leased "
              f"worker(s), got {len(reports)}")
        for r in reports:
            check(r["platform"] == self.platform,
                  f"{name}: worker {r['worker_id']} computed on "
                  f"{r['platform']!r}, not {self.platform!r}")
        first = reports[0]
        self.device = {"platform": first["platform"],
                       "kind": first["device_kind"],
                       "count": max(r["num_devices"] for r in reports)}
        line = {
            "phase": name,
            "seconds": round(seconds, 2),
            "compile_seconds": round(
                max(r["compile_s"] for r in reports), 2),
            "platform": first["platform"],
            "device_kind": first["device_kind"],
            "device_count": self.device["count"],
            "worker_chips": [r["chip_ids"] for r in reports],
            # warm: every worker loaded programs from the persistent cache
            # and compiled none that the cache stores
            "cache_warm": all(r["cache_hits"] > 0 and r["cold_compiles"] == 0
                              for r in reports),
            "cache_hits": sum(r["cache_hits"] for r in reports),
            "cold_compiles": sum(r["cold_compiles"] for r in reports),
            **extra,
        }
        print(json.dumps(line), flush=True)
        return line


# -- the work -------------------------------------------------------------------


def _rows(rng, n: int, vocab: int, enc_len: int,
          dec_len: Optional[int]) -> List[Dict[str, Any]]:
    """Synthetic token-id rows, drawn in bulk (no pad, no EOS: ids in
    [2, vocab))."""
    import numpy as np

    columns = {"input_ids": rng.integers(2, vocab, (n, enc_len), np.int32),
               "attention_mask": np.ones((n, enc_len), np.int32)}
    if dec_len is not None:
        columns["labels"] = rng.integers(2, vocab, (n, dec_len), np.int32)
    return [{k: v[i] for k, v in columns.items()} for i in range(n)]


def fit(model_config, sizes: Sizes, seed: int, storage: str, name: str,
        *, num_workers: int, per_device_batch: int):
    """``T5Trainer.fit`` for ``sizes.train_steps`` steps, one eval, one
    checkpoint, on ``num_workers`` chips in one process."""
    import numpy as np

    import tpu_air.data
    from tpu_air.train import (
        CheckpointConfig, RunConfig, ScalingConfig, T5Trainer)
    from tpu_air.train.t5_trainer import TrainingArguments

    global_batch = per_device_batch * num_workers
    rng = np.random.default_rng(seed)
    train = _rows(rng, global_batch * sizes.train_steps,
                  model_config.vocab_size, sizes.enc_len, sizes.dec_len)
    evaluation = _rows(rng, global_batch, model_config.vocab_size,
                       sizes.enc_len, sizes.dec_len)
    result = T5Trainer(
        model_config=model_config,
        training_args=TrainingArguments(
            per_device_train_batch_size=per_device_batch,
            num_train_epochs=1, seed=seed),
        scaling_config=ScalingConfig(
            num_workers=num_workers, num_chips_per_worker=1),
        datasets={"train": tpu_air.data.from_items(train),
                  "evaluation": tpu_air.data.from_items(evaluation)},
        run_config=RunConfig(
            name=name, storage_path=storage,
            checkpoint_config=CheckpointConfig(num_to_keep=1)),
    ).fit()
    if result.error is not None:
        raise result.error
    m = result.metrics
    check(m["steps"] == sizes.train_steps,
          f"{name}: took {m['steps']} steps, wanted {sizes.train_steps}")
    check(math.isfinite(m["loss"]) and math.isfinite(m["eval_loss"]),
          f"{name}: loss {m['loss']}, eval_loss {m['eval_loss']}")
    check(m["mesh_data"] == num_workers
          and m["param_devices"] == num_workers
          and m["batch_devices"] == num_workers,
          f"{name}: wanted parameters and batch on {num_workers} devices, "
          f"the worker reports mesh_data={m['mesh_data']} "
          f"param_devices={m['param_devices']} "
          f"batch_devices={m['batch_devices']}")
    return result


def checkpoint_loads(checkpoint) -> int:
    """Read the checkpoint's parameters back on the host (with numpy, not
    ``Checkpoint.get_params``, which puts them on a device — the workers of
    the next phases load it that way); returns the parameter count."""
    import numpy as np
    from flax import serialization

    with open(os.path.join(checkpoint.path, "params.msgpack"), "rb") as f:
        params = serialization.msgpack_restore(f.read())
    count = 0
    stack = [params]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            stack.extend(node.values())
        else:
            leaf = np.asarray(node)
            check(bool(np.isfinite(leaf).all()),
                  "checkpoint holds non-finite values")
            count += leaf.size
    return count


def generate(checkpoint, model_config, sizes: Sizes, seed: int, *,
             passes: int, workers: int) -> List[List[List[int]]]:
    """``BatchPredictor.predict`` over ``passes`` blocks of
    ``sizes.gen_rows`` rows on ``workers`` one-chip scoring workers.
    Returns the token ids, one list of rows for each block."""
    import numpy as np

    import tpu_air.data
    from tpu_air.predict import BatchPredictor, T5GenerativePredictor

    rng = np.random.default_rng(seed + 1)
    rows = _rows(rng, sizes.gen_rows, model_config.vocab_size,
                 sizes.enc_len, None)
    # the same block ``passes`` times: one predictor call for each block
    ds = tpu_air.data.from_items(rows * passes, parallelism=passes)
    check(ds.num_blocks() == passes, "generate: one block for each pass")
    preds = BatchPredictor.from_checkpoint(
        checkpoint, T5GenerativePredictor, dtype="bfloat16",
    ).predict(
        ds,
        feature_columns=["input_ids", "attention_mask"],
        batch_size=sizes.gen_rows,
        min_scoring_workers=workers, max_scoring_workers=workers,
        num_chips_per_worker=1,
        max_new_tokens=sizes.new_tokens,
    )
    # no tokenizer in the checkpoint: the predictor returns the ids as text
    ids = [[int(t) for t in text.split()]
           for text in preds.to_pandas()["generated_output"]]
    check(len(ids) == sizes.gen_rows * passes
          and all(len(r) == sizes.new_tokens for r in ids),
          f"generate: wanted {sizes.gen_rows * passes} rows of "
          f"{sizes.new_tokens} token ids")
    check(all(0 <= t < model_config.vocab_size for r in ids for t in r),
          "generate: token id outside the vocabulary")
    return [ids[i * sizes.gen_rows:(i + 1) * sizes.gen_rows]
            for i in range(passes)]


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def serve_requests(checkpoint, model_config, sizes: Sizes,
                   seed: int) -> Dict[str, Any]:
    """``serve.run`` one engine replica on one chip, POST
    ``sizes.requests`` prompts at it at once, read the engine's counters."""
    import numpy as np

    import tpu_air
    from tpu_air import serve
    from tpu_air.engine import T5EngineConfig

    port = _free_port()
    handle = serve.run(
        serve.EngineDeployment.options(num_replicas=1, num_chips=1).bind(
            checkpoint,
            T5EngineConfig(max_batch=sizes.serve_batch,
                           max_input_len=sizes.enc_len,
                           max_new_tokens=sizes.serve_new_tokens),
            dtype="bfloat16"),
        port=port)
    rng = np.random.default_rng(seed + 2)
    prompts = rng.integers(2, model_config.vocab_size,
                           (sizes.requests, sizes.enc_len)).tolist()
    answers: List[Any] = [None] * len(prompts)

    def post(i: int) -> None:
        req = urllib.request.Request(
            f"http://127.0.0.1:{port}/",
            data=json.dumps({"prompt": prompts[i],
                             "max_new_tokens": sizes.serve_new_tokens}).encode(),
            headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=600) as resp:
                answers[i] = (resp.status, json.loads(resp.read()))
        except Exception as e:  # noqa: BLE001 — reported per request below
            answers[i] = (None, repr(e))

    threads = [threading.Thread(target=post, args=(i,))
               for i in range(len(prompts))]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    tokens = 0
    for i, (status, body) in enumerate(answers):
        check(status == 200, f"serve: request {i} answered {status}: {body}")
        toks = body["results"][0]["tokens"]
        check(1 <= len(toks) <= sizes.serve_new_tokens
              and all(0 <= t < model_config.vocab_size for t in toks),
              f"serve: request {i} returned tokens {toks!r}")
        tokens += len(toks)
    stats = tpu_air.get(handle.method("stats")())
    shed = sum(c["shed"] + c["quota_shed"]
               for c in stats["priority"].values())
    check(stats["requests_completed"] == sizes.requests
          and stats["requests_rejected"] == 0 and shed == 0
          and stats["deadline_expired"] == 0,
          f"serve: engine counted completed={stats['requests_completed']} "
          f"rejected={stats['requests_rejected']} shed={shed} "
          f"expired={stats['deadline_expired']}")
    serve.shutdown()
    return {"requests": sizes.requests, "http_200": sizes.requests,
            "completed": stats["requests_completed"], "shed": shed,
            "tokens": tokens}


# -- the two runs ---------------------------------------------------------------


def one_chip(model_config, platform: str, sizes: Sizes, seed: int,
             storage: str) -> Dict[str, Any]:
    """train → generate → serve, one leased worker each, in turn on the
    same chip.  Returns the device the workers saw."""
    phases = Phases(platform)
    state: Dict[str, Any] = {}

    def train() -> Dict[str, Any]:
        result = fit(model_config, sizes, seed, storage, "train",
                     num_workers=1, per_device_batch=sizes.train_batch)
        state["checkpoint"] = result.checkpoint
        return {"steps": result.metrics["steps"],
                "loss": round(result.metrics["loss"], 4),
                "eval_loss": round(result.metrics["eval_loss"], 4),
                "checkpoint_params": checkpoint_loads(result.checkpoint)}

    def gen() -> Dict[str, Any]:
        first, second = generate(state["checkpoint"], model_config, sizes,
                                 seed, passes=2, workers=1)
        check(first[:8] == second[:8],
              "generate: the same inputs gave different token ids")
        return {"rows": len(first), "new_tokens": sizes.new_tokens,
                "repeatable_rows": 8}

    phases.run("train", train)
    phases.run("generate", gen)
    phases.run("serve", lambda: serve_requests(
        state["checkpoint"], model_config, sizes, seed))
    return phases.device


def four_chips(model_config, platform: str, sizes: Sizes, seed: int,
               storage: str) -> Dict[str, Any]:
    """The paths that exist only across chips, each beside what it is
    compared with: one process on a four-chip lease (a ``data=4`` mesh)
    against one chip at the same global batch and seed, and four one-chip
    scoring processes against one.  The global batch is the W1 batch of one
    chip: four times that does not fit one chip (the v5e compiler wants
    23.59 GiB of 15.75 for the train step at batch 128), and the comparison
    needs both sides."""
    phases = Phases(platform)
    state: Dict[str, Any] = {}
    global_batch = sizes.train_batch

    def fit_on(num_workers: int) -> Callable[[], Dict[str, Any]]:
        def run() -> Dict[str, Any]:
            result = fit(model_config, sizes, seed, storage,
                         f"train_dp{num_workers}", num_workers=num_workers,
                         per_device_batch=global_batch // num_workers)
            state[num_workers] = result
            return {"global_batch": global_batch,
                    "mesh_data": result.metrics["mesh_data"],
                    "param_devices": result.metrics["param_devices"],
                    "batch_devices": result.metrics["batch_devices"],
                    "loss": round(result.metrics["loss"], 4),
                    "eval_loss": round(result.metrics["eval_loss"], 4)}
        return run

    def gen_on(workers: int) -> Callable[[], Dict[str, Any]]:
        def run() -> Dict[str, Any]:
            state["gen", workers] = generate(
                state[4].checkpoint, model_config, sizes, seed,
                passes=4, workers=workers)
            return {"rows": 4 * sizes.gen_rows, "scoring_workers": workers}
        return run

    phases.run("train_dp4", fit_on(4))
    dp4_device = phases.device
    check(dp4_device["count"] == 4,
          f"the dp-4 worker saw {dp4_device['count']} devices, wanted 4")
    phases.run("train_dp1", fit_on(1))
    for key in ("loss", "eval_loss"):
        a, b = state[4].metrics[key], state[1].metrics[key]
        check(abs(a - b) <= 1e-2 * abs(b),
              f"dp-4 {key} {a} and one-chip {key} {b} differ beyond bf16 "
              "tolerance")
    line = phases.run("generate_4_workers", gen_on(4), workers=4)
    check(len({tuple(c) for c in line["worker_chips"]}) == 4,
          f"four scoring workers, chips {line['worker_chips']}: wanted "
          "four different ones")
    phases.run("generate_1_worker", gen_on(1))
    check(state["gen", 4] == state["gen", 1],
          "four scoring workers and one gave different token ids")
    return dp4_device


def run(paths: Callable[..., Dict[str, Any]], model_config, platform: str,
        sizes: Sizes = Sizes(), seed: int = 0) -> Dict[str, Any]:
    """``tpu_air.init()``, the phases, shutdown; checks that nothing this
    process started is left.  Returns the device for the last line."""
    import tpu_air
    from tpu_air import serve

    model_config.dtype = "bfloat16"
    storage = tempfile.mkdtemp(prefix="tpu_air-chip-smoke-")
    try:
        tpu_air.init()
        device = paths(model_config, platform, sizes, seed, storage)
    finally:
        serve.shutdown()
        tpu_air.shutdown()
        shutil.rmtree(storage, ignore_errors=True)
    driver_holds_no_backend()
    left = child_pids()
    check(not left, f"processes left after shutdown: {left}")
    return device


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="weights and rows are made from it (default 0)")
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: only the cross-chip paths and what each is "
                         "compared with")
    args = ap.parse_args(argv)

    from tpu_air.core import chips
    from tpu_air.models.t5 import T5Config

    found = chips.local_chip_count() if chips.accelerator_expected() else 0
    if found != args.chips:
        print(f"chip_smoke: needs {args.chips} attached TPU chip(s), found "
              f"{found} (JAX_PLATFORMS={os.environ.get('JAX_PLATFORMS')!r})",
              file=sys.stderr)
        return 2
    rebuild_native()
    config = T5Config.flan_t5_base()
    if args.chips == 4:
        # Published widths, depth cut to 4+4 layers: what exists only across
        # chips (the mesh, the gradient all-reduce, four processes on four
        # chips) does not depend on depth, each side of the comparison
        # compiles its own train step, and at 12+12 layers one such compile
        # is about five minutes of a four-chip machine.
        config.num_layers = config.num_decoder_layers = 4
    device = run(one_chip if args.chips == 1 else four_chips,
                 config, "tpu", seed=args.seed)
    check(device["count"] == args.chips,
          f"workers saw {device['count']} device(s), wanted {args.chips}")
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
