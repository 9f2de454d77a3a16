"""W5: headless FLAN-T5 fine-tune + distributed batch inference job.

The reference's Anyscale job entrypoint distilled onto tpu_air
(NLP_workloads/Anyscale_job/flan-t5-batch-inference.py:1-138, submitted via
flan-t5-batch-inference-job-setup.yml:1-7): ingest Alpaca → tokenize with a
fitted BatchMapper preprocessor → SPMD data-parallel fine-tune → best
checkpoint → BatchPredictor over the eval split → join generated outputs back
onto the inputs, all seeded (transformers.set_seed(42) analog:
flan-t5-batch-inference.py:18).

Scale dials (the reference's SMALL_DATA pattern,
Model_finetuning_and_batch_inference.ipynb:cc-21):
  --smoke      tiny model + synthetic rows, CPU-friendly (CI / laptop)
  default      flan-t5-small on real Alpaca (needs HF cache) on the chip pool

Run directly, or as a managed job:
  python -m tpu_air.job submit examples/flan_t5_job.yml --wait
"""

from __future__ import annotations

import argparse
import sys

import numpy as np
import pandas as pd

import tpu_air
import tpu_air.data as tad
from tpu_air.data.preprocessors import BatchMapper
from tpu_air.models.t5 import T5Config
from tpu_air.models.tokenizer import ByteTokenizer, auto_tokenizer
from tpu_air.predict import BatchPredictor, T5GenerativePredictor
from tpu_air.train import (
    CheckpointConfig,
    RunConfig,
    ScalingConfig,
    T5Trainer,
    TrainingArguments,
)

SEED = 42


def load_alpaca(smoke: bool, limit: int, strict: bool = False):
    """Alpaca instruction rows (Model_finetuning…ipynb:cc-13,18: HF load →
    framework dataset → limit).  Smoke mode synthesizes instruction/output
    pairs offline so the job runs with zero network; ``strict`` forbids the
    synthetic fallback — a broken real-asset path must fail loudly,
    not produce a plausible-looking synthetic run."""
    if not smoke:
        try:
            from datasets import load_dataset

            hf = load_dataset("tatsu-lab/alpaca", split="train")
            ds = tad.from_huggingface(hf)
            return ds.limit(limit) if limit else ds
        except Exception as e:  # no cache / no network → fall through to smoke
            if strict:
                raise
            print(f"falling back to synthetic alpaca ({type(e).__name__}: {e})")
    rng = np.random.default_rng(SEED)
    verbs = ["list", "name", "describe", "repeat", "count"]
    things = ["planets", "colors", "rivers", "tools", "birds"]
    rows = [
        {
            "instruction": f"{verbs[rng.integers(5)]} three {things[rng.integers(5)]}",
            "input": "",
            "output": f"{things[rng.integers(5)]} a, b, c",
        }
        for _ in range(limit or 96)
    ]
    return tad.from_items(rows)


def build_tokenizer(smoke: bool, seq: int, strict: bool = False):
    if smoke:
        return ByteTokenizer(model_max_length=seq)
    return auto_tokenizer("google/flan-t5-small", strict=strict)


def make_preprocessor(tokenizer_factory, seq: int) -> BatchMapper:
    """Tokenizing BatchMapper — constructed inside the fn so it runs on data
    workers (the reference's pattern, NLP_workloads/Anyscale_job/utils.py:6-33),
    and persisted into the checkpoint so predict-time tokenization is
    automatic (predictor.py:93)."""

    def preprocess_function(df: pd.DataFrame) -> pd.DataFrame:
        tok = tokenizer_factory()
        prompts = [
            f"{inst} {inp}".strip()
            for inst, inp in zip(df["instruction"], df.get("input", [""] * len(df)))
        ]
        enc = tok(prompts, max_length=seq, padding="max_length",
                  truncation=True, return_tensors="np")
        out = {"input_ids": list(enc["input_ids"]),
               "attention_mask": list(enc["attention_mask"])}
        if "output" in df.columns:
            lab = tok(list(df["output"]), max_length=seq, padding="max_length",
                      truncation=True, return_tensors="np")
            out["labels"] = list(lab["input_ids"])
        return pd.DataFrame(out)

    return BatchMapper(preprocess_function, batch_format="pandas", batch_size=4096)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="tiny model + synthetic data (CPU smoke dials)")
    ap.add_argument("--strict", action="store_true",
                    help="require the REAL assets (Alpaca + flan-t5 vocab); "
                         "exit nonzero with the real error instead of "
                         "silently falling back to synthetic data")
    ap.add_argument("--limit", type=int, default=None,
                    help="row cap (SMALL_DATA dial)")
    ap.add_argument("--num-workers", type=int, default=None,
                    help="chips to train and score on (default: 2, or 1 "
                         "on a host with one chip)")
    ap.add_argument("--epochs", type=int, default=None)
    ap.add_argument("--max-new-tokens", type=int, default=None)
    args = ap.parse_args(argv)

    if args.strict and args.smoke:
        ap.error("--strict and --smoke are mutually exclusive")
    smoke = args.smoke
    seq = 32 if smoke else 512
    limit = args.limit if args.limit is not None else (96 if smoke else 100)
    epochs = args.epochs or (1 if smoke else 4)
    max_new = args.max_new_tokens or (4 if smoke else 128)

    runtime = tpu_air.init()
    if args.num_workers is None:
        # two chips where the host has them, one on a one-chip host (with
        # none at all, ask for two and let the runtime say what is missing)
        args.num_workers = min(2, runtime.num_chips) or 2

    ds = load_alpaca(smoke, limit, strict=args.strict)
    train_ds, eval_ds = ds.train_test_split(0.2, shuffle=True, seed=57)
    print(f"train rows: {train_ds.count()}  eval rows: {eval_ds.count()}")

    if smoke:
        tok = ByteTokenizer(model_max_length=seq)
        tok_factory = lambda: ByteTokenizer(model_max_length=seq)  # noqa: E731
        model_config = T5Config.tiny(vocab_size=384)
    else:
        strict = args.strict
        tok = build_tokenizer(smoke, seq, strict=strict)
        tok_factory = lambda: build_tokenizer(False, seq, strict=strict)  # noqa: E731
        model_config = T5Config.flan_t5_small()

    preprocessor = make_preprocessor(tok_factory, seq)

    # -- fine-tune (W1 config shape: Model_finetuning…ipynb:cc-34,38,40) -----
    trainer = T5Trainer(
        model_config=model_config,
        training_args=TrainingArguments(
            learning_rate=2e-5 if not smoke else 3e-3,
            per_device_train_batch_size=2,
            num_train_epochs=epochs,
            weight_decay=0.01,
            seed=SEED,
        ),
        tokenizer=tok,
        scaling_config=ScalingConfig(
            num_workers=args.num_workers, num_chips_per_worker=1
        ),
        datasets={"train": train_ds, "evaluation": eval_ds},
        run_config=RunConfig(
            checkpoint_config=CheckpointConfig(
                num_to_keep=1,
                checkpoint_score_attribute="eval_loss",
                checkpoint_score_order="min",
            )
        ),
        preprocessor=preprocessor,
    )
    result = trainer.fit()
    if result.error is not None:
        print(f"training failed: {result.error}")
        return 1
    print(f"metrics: {result.metrics}")

    # -- batch generation (W3 config shape: cc-64,67) ------------------------
    bp = BatchPredictor.from_checkpoint(
        result.checkpoint,
        T5GenerativePredictor,
        tokenizer=ByteTokenizer if smoke else None,
        dtype="bfloat16",
    )
    preds = bp.predict(
        eval_ds,
        feature_columns=["input_ids", "attention_mask"],
        batch_size=8 if smoke else 256,
        min_scoring_workers=1,
        max_scoring_workers=args.num_workers,
        num_chips_per_worker=1,
        max_new_tokens=max_new,
    )

    # join inputs ↔ outputs (flan-t5-batch-inference.py:136-138)
    inputs = eval_ds.to_pandas()
    outputs = preds.to_pandas()
    joined = pd.concat(
        [inputs.reset_index(drop=True), outputs.reset_index(drop=True)], axis=1
    )
    pd.set_option("display.max_colwidth", 60)
    print(joined[["instruction", "generated_output"]].head(10).to_string())
    print(f"generated {len(outputs)} outputs")
    tpu_air.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
