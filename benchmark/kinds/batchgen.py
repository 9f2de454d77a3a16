"""Kind ``batchgen``: ``BatchPredictor.predict`` over blocks of synthetic
prompts, one scoring worker on one chip, greedy, fixed number of new tokens.

``1 + N`` blocks: the first compiles (or loads) the generate program and is
set-up; its rows are given again as block 2, which must return the same ids.
The window runs from the end of block 1 to the end of block ``1 + N`` on the
worker's clock, so it holds N predictor calls and the data plane between
them.  ``N`` is fixed by ``--seconds`` and ``nominal_block_ms``.
"""

from __future__ import annotations

import json
import os
import time

import numpy as np

from benchmark import traffic as gen
from benchmark import weights


def run(ctx) -> None:
    import tpu_air.data
    from tpu_air.predict import BatchPredictor

    from benchmark.worker_hooks import ObservedT5Predictor

    t = ctx.traffic
    rows, enc = int(t["rows_per_block"]), int(t["encoder_len"])
    new = int(t["max_new_tokens"])
    blocks = 1 + max(2, int(round(
        ctx.seconds * 1000.0 / float(t["nominal_block_ms"]))))
    vocab = ctx.cfg["vocab_size"]

    rng = np.random.default_rng([ctx.seed, 2])
    cols = gen.token_rows(rng, rows * (blocks - 1), vocab, enc, None)
    # block 2 repeats block 1
    cols = {k: np.concatenate([v[:rows], v]) for k, v in cols.items()}
    ds = tpu_air.data.from_items(gen.as_items(cols), parallelism=blocks)
    ctx.check(ds.num_blocks() == blocks, f"wanted {blocks} blocks")
    ckpt = weights.write_checkpoint(ctx.cfg, ctx.seed, t["dtype"],
                                    os.path.join(ctx.scratch, "checkpoint"))
    t_predict = time.time()
    preds = BatchPredictor.from_checkpoint(
        ckpt, ObservedT5Predictor, dtype=t["dtype"],
        bench={"trace_dir": ctx.trace_dir if ctx.trace else None,
               "trace_call": int(t["trace_call"])},
    ).predict(
        ds, feature_columns=["input_ids", "attention_mask"], batch_size=rows,
        min_scoring_workers=1, max_scoring_workers=1, num_chips_per_worker=1,
        max_new_tokens=new,
    ).to_pandas()

    calls = preds.groupby("bench_call", sort=True)
    start = calls["bench_start"].first().to_numpy()
    end = calls["bench_end"].first().to_numpy()
    sizes = calls.size().to_numpy()
    ctx.attempted = rows * blocks
    ctx.failed = ctx.attempted - len(preds)
    ctx.check(len(start) == blocks and (sizes == rows).all(),
              f"wanted {blocks} predictor calls of {rows} rows, got "
              f"{sizes.tolist()}")
    # no tokenizer in the checkpoint: the predictor returns the ids as text
    ids = np.array([[int(x) for x in s.split()]
                    for s in preds["generated_output"]])
    ctx.check(ids.shape == (rows * blocks, new),
              f"wanted {rows * blocks} x {new} ids, got {ids.shape}")
    ctx.check(bool(((ids >= 0) & (ids < vocab)).all()),
              "token id outside the vocabulary")
    first = preds["bench_call"].to_numpy() == 1
    second = preds["bench_call"].to_numpy() == 2
    ctx.check(np.array_equal(ids[first], ids[second]),
              "the same block scored twice gave different ids")

    facts = [json.loads(s) for s in calls["bench_facts"].first()]
    ctx.check(facts[-1]["cold_compiles"] == facts[0]["cold_compiles"],
              "cold compiles after the first block")
    traced = calls["bench_traced"].first().to_numpy().astype(bool)
    ctx.window_s = float(end[-1] - end[0])
    ctx.window_start = float(end[0])
    ctx.facts.update({
        "window_s": ctx.window_s,
        "gen_rows": int(sizes[1:].sum()),
        # the traced call runs under the profiler: left out of the per-call
        # readings, as is the first (it compiles)
        "gen_call_ms": [1000.0 * (e - s) for s, e, tr in
                        zip(start[1:], end[1:], traced[1:]) if not tr],
        "gen_block_gap_ms": (1000.0 * (start[1:] - end[:-1])).tolist(),
        "gen_first_call_s": float(end[0] - start[0]),
        "rows_per_block": rows, "encoder_len": enc, "max_new_tokens": new,
        "memory_peak_bytes": facts[-1].get("memory_peak_bytes"),
        "worker_compile_s": facts[-1]["compile_s"],
        "worker_cold_compiles": facts[-1]["cold_compiles"],
        "worker_cache_hits": facts[-1]["cache_hits"],
    })
    ctx.notes.update(blocks=blocks, first_call_s=float(end[0] - start[0]),
                     worker_ready_s=float(start[0]) - t_predict)
