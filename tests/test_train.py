"""Train layer tests — the minimum end-to-end slice (SURVEY.md §7 stage 5):
W1 (fine-tune) + W4 (generate from checkpoint) at test dials, on the virtual
8-device CPU mesh."""

import numpy as np
import pandas as pd
import pytest

import tpu_air
from tpu_air import data as tad
from tpu_air.data import BatchMapper
from tpu_air.models import ByteTokenizer
from tpu_air.models.t5 import T5Config
from tpu_air.train import (
    Checkpoint,
    CheckpointConfig,
    FailureConfig,
    JaxTrainer,
    RunConfig,
    ScalingConfig,
    T5Trainer,
    TrainingArguments,
    XGBoostTrainer,
)

SEQ = 24


def make_alpaca_like(n=64):
    rows = [
        {"instruction": f"repeat the word w{i % 7}", "output": f"w{i % 7}"}
        for i in range(n)
    ]
    return tad.from_items(rows)


def tokenize_preprocessor():
    tok = ByteTokenizer(model_max_length=SEQ)

    def preprocess_function(df: pd.DataFrame) -> pd.DataFrame:
        # mirrors the reference preprocessor shape (utils.py:6-33): tokenizer
        # constructed inside the fn (runs on data workers), inputs padded to
        # max_length, labels from the target text
        t = ByteTokenizer(model_max_length=SEQ)
        enc = t(list(df["instruction"]), max_length=SEQ, padding="max_length",
                truncation=True, return_tensors="np")
        lab = t(list(df["output"]), max_length=SEQ, padding="max_length",
                truncation=True, return_tensors="np")
        return pd.DataFrame(
            {
                "input_ids": list(enc["input_ids"]),
                "attention_mask": list(enc["attention_mask"]),
                "labels": list(lab["input_ids"]),
            }
        )

    return tok, BatchMapper(preprocess_function, batch_format="pandas", batch_size=4096)


@pytest.fixture(scope="module")
def trained_result(air):
    ds = make_alpaca_like(64)
    train_ds, eval_ds = ds.train_test_split(0.25)
    tok, pp = tokenize_preprocessor()
    trainer = T5Trainer(
        model_config=T5Config.tiny(vocab_size=384),
        training_args=TrainingArguments(
            learning_rate=3e-3,
            per_device_train_batch_size=2,
            num_train_epochs=2,
            weight_decay=0.0,
        ),
        tokenizer=tok,
        scaling_config=ScalingConfig(num_workers=4, num_chips_per_worker=1),
        datasets={"train": train_ds, "evaluation": eval_ds},
        run_config=RunConfig(
            checkpoint_config=CheckpointConfig(
                num_to_keep=1,
                checkpoint_score_attribute="eval_loss",
                checkpoint_score_order="min",
            )
        ),
        preprocessor=pp,
    )
    return trainer.fit()


def test_fit_returns_metrics_and_checkpoint(trained_result):
    r = trained_result
    assert r.error is None
    assert r.checkpoint is not None
    assert "loss" in r.metrics and "eval_loss" in r.metrics
    assert len(r.metrics_history) == 2  # one report per epoch
    assert r.metrics["epoch"] == 2


def test_loss_decreases(trained_result):
    h = trained_result.metrics_history
    assert h[-1]["loss"] < h[0]["loss"]


def test_checkpoint_bundles_everything(trained_result):
    """SURVEY.md §5: checkpoint = model + tokenizer + fitted preprocessor."""
    ckpt = trained_result.checkpoint
    model, params = ckpt.get_model()
    assert model.config.d_model == 64
    tok = ckpt.get_tokenizer(ByteTokenizer)
    assert tok.model_max_length == SEQ
    pp = ckpt.get_preprocessor()
    assert pp is not None
    out = pp.transform_batch(pd.DataFrame({"instruction": ["hi"], "output": ["yo"]}))
    assert "input_ids" in out.columns


def test_generate_from_checkpoint(trained_result):
    """W4: single-example interactive generate from the fit checkpoint
    (Model_finetuning…ipynb:cc-49)."""
    from tpu_air.models.t5 import generate

    ckpt = trained_result.checkpoint
    model, params = ckpt.get_model()
    tok = ckpt.get_tokenizer(ByteTokenizer)
    enc = tok(["repeat the word w3"], max_length=SEQ, padding="max_length",
              truncation=True, return_tensors="np")
    out = generate(model, params, enc["input_ids"], enc["attention_mask"],
                   max_new_tokens=8)
    text = tok.batch_decode(out)[0]
    assert isinstance(text, str)


def test_checkpoint_dtype_morphing(trained_result):
    """bf16-at-load (the fp16/device_map analog, cc-64)."""
    import jax.numpy as jnp

    params = trained_result.checkpoint.get_params(dtype="bfloat16")
    leaf = params["shared"]["embedding"]
    assert leaf.dtype == jnp.bfloat16


def test_jax_function_trainer(air):
    """Generic train_loop_per_worker surface (session API)."""

    def loop(config):
        from tpu_air.train import session

        ds = session.get_dataset_shard("train")
        total = ds.count()
        for i in range(3):
            session.report({"seen": total, "metric": float(10 - i)})

    trainer = JaxTrainer(
        loop,
        train_loop_config={"x": 1},
        scaling_config=ScalingConfig(num_workers=1),
        datasets={"train": tad.range(10)},
    )
    r = trainer.fit()
    assert r.error is None
    assert r.metrics["seen"] == 10
    assert len(r.metrics_history) == 3


def test_trainer_error_surfaces(air):
    def loop(config):
        raise RuntimeError("explode")

    r = JaxTrainer(loop, scaling_config=ScalingConfig(num_workers=1)).fit()
    assert r.error is not None
    assert "explode" in str(r.error)


def test_failure_retry_resumes_from_checkpoint(air):
    """SURVEY.md §5 failure detection: restart from latest checkpoint."""

    def loop(config):
        from tpu_air.train import session

        start = 0
        if config.get("resume_from_checkpoint"):
            ck = Checkpoint.from_directory(config["resume_from_checkpoint"])
            start = ck.get_metrics()["i"]
        for i in range(start, 4):
            ck = Checkpoint.from_model(metrics={"i": i + 1})
            session.report({"i": i + 1}, checkpoint=ck)
            if i == 1 and start == 0:
                raise RuntimeError("simulated crash")

    r = JaxTrainer(
        loop,
        scaling_config=ScalingConfig(num_workers=1),
        run_config=RunConfig(failure_config=FailureConfig(max_failures=1)),
    ).fit()
    assert r.error is None
    assert r.metrics["i"] == 4


def test_gbdt_trainer_w8(air):
    """W8 tabular capability: XGBoostTrainer-equivalent with the reference's
    param surface and metric names (Introduction…ipynb:cc-32,40)."""
    rng = np.random.default_rng(0)
    X = rng.normal(size=(300, 4))
    y = (X[:, 0] + X[:, 1] > 0).astype(int)
    df = pd.DataFrame(X, columns=list("abcd"))
    df["is_big_tip"] = y
    train_df, valid_df = df.iloc[:240], df.iloc[240:]
    trainer = XGBoostTrainer(
        label_column="is_big_tip",
        num_boost_round=8,
        params={"objective": "binary:logistic", "eta": 0.3, "max_depth": 3},
        scaling_config=ScalingConfig(num_workers=2, use_tpu=False),
        datasets={
            "train": tad.from_pandas(train_df),
            "valid": tad.from_pandas(valid_df),
        },
    )
    r = trainer.fit()
    assert r.error is None
    assert "train-logloss" in r.metrics and "valid-error" in r.metrics
    assert r.metrics["train-error"] < 0.2
    assert r.checkpoint is not None
    est = r.checkpoint.get_model()
    assert hasattr(est, "predict_proba")


def test_tensor_parallel_trainer(air):
    """ScalingConfig(model_parallel=2) shards params over the model axis in
    the user-facing Trainer: per-device param bytes
    shrink, loss stays finite, and a dp=2 x tp=2 mesh is actually built."""
    ds = make_alpaca_like(32)
    tok, pp = tokenize_preprocessor()
    trainer = T5Trainer(
        model_config=T5Config.tiny(vocab_size=384),
        training_args=TrainingArguments(
            learning_rate=3e-3, per_device_train_batch_size=2,
            num_train_epochs=1, weight_decay=0.0,
        ),
        tokenizer=tok,
        scaling_config=ScalingConfig(num_workers=2, model_parallel=2),
        datasets={"train": ds},
        preprocessor=pp,
    )
    r = trainer.fit()
    assert r.error is None
    m = r.metrics
    assert m["mesh_data"] == 2 and m["mesh_model"] == 2
    # model-sharded leaves (attention/MLP kernels) occupy 1/2 their bytes per
    # device; embeddings/norms stay replicated, so the shrink is partial but
    # must be real
    assert m["params_bytes_per_device"] < m["params_bytes_total"]
    assert np.isfinite(m["loss"])


@pytest.mark.slow  # numerics-parity / superseded-coverage: slow tier (budget, r3 weak #5)
def test_tensor_parallel_matches_dp_loss(air):
    """One tp=2 epoch and one pure-DP epoch from the same init produce the
    same loss trajectory (TP is a layout change, not a math change)."""
    ds = make_alpaca_like(32)
    tok, pp = tokenize_preprocessor()

    def fit(sc):
        trainer = T5Trainer(
            model_config=T5Config.tiny(vocab_size=384),
            training_args=TrainingArguments(
                learning_rate=3e-3, per_device_train_batch_size=2,
                num_train_epochs=1, weight_decay=0.0, seed=7,
            ),
            tokenizer=tok,
            scaling_config=sc,
            datasets={"train": ds},
            preprocessor=pp,
        )
        r = trainer.fit()
        assert r.error is None
        return r.metrics["loss"]

    # same global batch (2 workers x 2) so the trajectories are comparable
    loss_dp = fit(ScalingConfig(num_workers=2))
    loss_tp = fit(ScalingConfig(num_workers=2, model_parallel=2))
    assert loss_tp == pytest.approx(loss_dp, rel=2e-3)


def test_distributed_gbdt_matches_single_process(air):
    """ScalingConfig(num_workers=4): 4 worker actors each fit ONLY their row
    shard, growing IDENTICAL trees from allreduce-merged histograms (rabit
    semantics; reference: 5-worker XGBoostTrainer,
    Introduction_to_Ray_AI_Runtime.ipynb:cc-32)."""
    rng = np.random.default_rng(3)
    n = 480
    X = rng.normal(size=(n, 6))
    y = ((X[:, 0] + 0.5 * X[:, 1] - X[:, 2] + 0.3 * rng.normal(size=n)) > 0).astype(int)
    rows = [dict({f"f{j}": float(X[i, j]) for j in range(6)}, label=int(y[i])) for i in range(n)]
    ds = tad.from_items(rows)
    train_ds, valid_ds = ds.train_test_split(0.25)

    def fit(num_workers):
        trainer = XGBoostTrainer(
            label_column="label",
            params={"objective": "binary:logistic", "eta": 0.3, "max_depth": 3},
            num_boost_round=8,
            scaling_config=ScalingConfig(num_workers=num_workers),
            datasets={"train": train_ds, "valid": valid_ds},
        )
        r = trainer.fit()
        assert r.error is None, r.error
        return r

    r1 = fit(1)
    r4 = fit(4)
    # metric-name parity survives the distributed path
    for k in ("train-logloss", "train-error", "valid-error", "valid-logloss"):
        assert k in r4.metrics, k
    # rank identity asserted inside the trial (hard error on divergence)
    assert r4.metrics["ranks_identical"] is True
    # true boosting on merged histograms: only the quantile-sketch merge
    # differs from single-process training, so metrics agree closely —
    # the bagging implementation this replaced drifted with num_workers
    assert abs(r4.metrics["valid-error"] - r1.metrics["valid-error"]) <= 0.04
    assert abs(r4.metrics["train-logloss"] - r1.metrics["train-logloss"]) <= 0.05

    # the checkpoint carries ONE merged-histogram booster (every rank's is
    # bit-identical) and predicts
    from tpu_air.train.hist_gbdt import HistGBDT

    model = r4.checkpoint.get_model()
    assert isinstance(model, HistGBDT) and len(model.trees) == 8
    from tpu_air.predict.predictors import GBDTPredictor

    pred = GBDTPredictor.from_checkpoint(r4.checkpoint)
    out = pred.predict(valid_ds.limit(8).to_pandas().drop(columns=["label"]))
    assert len(out) == 8


def test_scaling_config_rejects_zero_parallel_degrees():
    """An explicit 0 must raise, not silently coerce to 1 and train
    replicated (round-3 advisor finding, config.py)."""
    with pytest.raises(ValueError):
        ScalingConfig(num_workers=2, model_parallel=0)
    with pytest.raises(ValueError):
        ScalingConfig(num_workers=2, sequence_parallel=0)
    # None still defaults to 1
    assert ScalingConfig(num_workers=2).model_parallel == 1


def test_spill_dir_owner_marker_protects_custom_roots(tmp_path):
    """The stale-session sweeper must check the .owner marker path for
    liveness — a live session rooted in a CUSTOM base dir must not have its
    spill dir reaped (round-3 advisor finding, runtime.py)."""
    import os
    import time

    from tpu_air.core.object_store import ObjectStore
    from tpu_air.core.runtime import _sweep_stale_sessions

    custom_base = tmp_path / "custombase"
    custom_base.mkdir()
    root = custom_base / "tpu_air-livecustom"
    store = ObjectStore(str(root), create=True)
    store._spill_dir = str(tmp_path / "var_tmp" / "tpu_air-spill-tpu_air-livecustom")
    store._ensure_spill_dir()
    spilled = os.path.join(store._spill_dir, "someobject")
    with open(spilled, "w") as f:
        f.write("x")
    # age everything past the stale threshold
    old = time.time() - 3 * 3600
    os.utime(store._spill_dir, (old, old))
    os.utime(spilled, (old, old))

    real_var_tmp = str(tmp_path / "var_tmp")
    _sweep_stale_sessions(str(tmp_path / "shm"), spill_base=real_var_tmp)
    # live owner root exists → spill dir must survive
    assert os.path.exists(spilled), "sweeper reaped a live custom-root session"

    # now kill the owner: dir becomes reapable
    store.destroy()
    os.makedirs(store._spill_dir, exist_ok=True)
    with open(os.path.join(store._spill_dir, ".owner"), "w") as f:
        f.write(str(root))
    with open(spilled, "w") as f:
        f.write("x")
    os.utime(store._spill_dir, (old, old))
    os.utime(spilled, (old, old))
    _sweep_stale_sessions(str(tmp_path / "shm"), spill_base=real_var_tmp)
    assert not os.path.exists(store._spill_dir), "dead session spill dir not reaped"


def test_hist_gbdt_learns_and_is_deterministic():
    """The in-repo histogram booster: learns a separable problem in both
    objectives, and two fits on identical data produce bit-identical trees
    (the determinism the distributed rank-identity rests on)."""
    from tpu_air.train.hist_gbdt import HistGBDT

    rng = np.random.default_rng(7)
    X = rng.normal(size=(400, 5))
    y = ((X[:, 0] - 0.5 * X[:, 3]) > 0).astype(float)

    def fit():
        m = HistGBDT(max_depth=4, eta=0.3, max_bins=64)
        m.setup(X, y)
        for _ in range(10):
            m.fit_one_round()
        return m

    m1, m2 = fit(), fit()
    assert m1.signature() == m2.signature()
    p = m1.predict_proba(X)[:, 1]
    assert np.mean((p > 0.5) == y) > 0.95
    # scoring copy drops training state but scores identically
    sc = m1.scoring_copy()
    np.testing.assert_array_equal(sc.predict_proba(X), m1.predict_proba(X))
    assert sc._margin is None

    yr = X[:, 0] * 2.0 + X[:, 1] + 0.01 * rng.normal(size=400)
    mr = HistGBDT(objective="reg:squarederror", max_depth=4, max_bins=64)
    mr.setup(X, yr)
    for _ in range(20):
        mr.fit_one_round()
    rmse = float(np.sqrt(np.mean((mr.predict(X) - yr) ** 2)))
    assert rmse < 0.5, rmse
    # regression boosters must not expose predict_proba (GBDTPredictor
    # branches on hasattr)
    assert not hasattr(mr, "predict_proba")


def test_hist_gbdt_accuracy_comparable_to_sklearn():
    """Quality guard for the from-scratch histogram booster: held-out error
    within a small margin of sklearn's GradientBoostingClassifier at the
    same depth/rounds/learning rate on a nonlinear problem."""
    from sklearn.ensemble import GradientBoostingClassifier

    from tpu_air.train.hist_gbdt import HistGBDT

    rng = np.random.default_rng(11)
    X = rng.normal(size=(1200, 6))
    y = ((X[:, 0] * X[:, 1] + 0.8 * np.sin(2 * X[:, 2]) + 0.3 * X[:, 3]) > 0
         ).astype(float)
    Xtr, ytr, Xva, yva = X[:900], y[:900], X[900:], y[900:]

    ours = HistGBDT(eta=0.2, max_depth=4, max_bins=128)
    ours.setup(Xtr, ytr)
    for _ in range(30):
        ours.fit_one_round()
    err_ours = float(np.mean(ours.predict(Xva) != yva))

    sk = GradientBoostingClassifier(
        n_estimators=30, learning_rate=0.2, max_depth=4, random_state=0
    ).fit(Xtr, ytr)
    err_sk = float(np.mean(sk.predict(Xva) != yva))
    assert err_ours <= err_sk + 0.05, (err_ours, err_sk)
