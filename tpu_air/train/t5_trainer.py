"""T5Trainer — the flagship fine-tune engine (W1/W5, Model_finetuning…ipynb).

Replaces the reference's per-worker HF ``Trainer`` factory + NCCL DDP
(trainer_init_per_worker, cc-34; "PyTorch DDP synchronizes their weights",
cc-29) with one jit-compiled SPMD train step over a ``(data, model)`` mesh:

* batch sharded on ``data`` — per-device shards replace per-worker dataset
  shards; the gradient all-reduce is the psum XLA emits for replicated
  params (ICI, not NCCL);
* optional tensor parallelism via the ``model`` axis (param rules in
  tpu_air/parallel/sharding.py) — a config change, per SURVEY.md §2C;
* params donated through the step (no copies), activations in
  ``model_config.dtype`` (bf16 on TPU — the fp16-on-GPU analog);
* per-epoch eval / checkpoint / report matching the HF epoch strategies the
  reference configures (evaluation_strategy/save_strategy/logging_strategy
  ="epoch", cc-34), metric names ``loss``/``eval_loss`` (cc-40).
"""

from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import Any, Dict, Optional

import numpy as np

from tpu_air.observability.profiler import phase

from .checkpoint import Checkpoint
from .trainer import BaseTrainer


@dataclass
class TrainingArguments:
    """The subset of HF TrainingArguments the reference exercises (cc-34),
    plus TPU-native knobs."""

    learning_rate: float = 2e-5
    per_device_train_batch_size: int = 2
    per_device_eval_batch_size: Optional[int] = None
    num_train_epochs: int = 4
    weight_decay: float = 0.01
    warmup_steps: int = 0
    max_grad_norm: float = 1.0
    optimizer: str = "adamw"  # or "adafactor"
    lr_scheduler_type: str = "constant"  # or "linear" / "cosine" decay to 0
    # model.init takes PRNGKey(seed); the dropout masks come from their own
    # stream, t5_trainer.dropout_key(seed)
    seed: int = 42
    evaluation_strategy: str = "epoch"
    save_strategy: str = "epoch"
    logging_strategy: str = "epoch"
    max_steps_per_epoch: Optional[int] = None  # test dial
    tensor_parallelism: int = 1
    remat: bool = False  # jax.checkpoint the decoder layers (HBM for FLOPs)
    disable_tqdm: bool = True  # accepted for parity; no tqdm either way

    def __post_init__(self):
        if self.per_device_eval_batch_size is None:
            self.per_device_eval_batch_size = self.per_device_train_batch_size


def collate(batch_df, keys,
            seq_lens: Optional[Dict[str, int]] = None) -> Dict[str, np.ndarray]:
    """DataFrame of per-row token lists → stacked int32 arrays.  With
    ``seq_lens`` every column must have the length recorded for it (one
    compiled step serves the run; the decoder side may be shorter than the
    encoder side, as in the W1 shape: encoder 512, decoder 128)."""
    out = {}
    for k in keys:
        col = [np.asarray(v, dtype=np.int32) for v in batch_df[k]]
        out[k] = np.stack(col)
        if seq_lens is not None and out[k].shape[1] != seq_lens[k]:
            raise ValueError(
                f"column {k} has seq len {out[k].shape[1]}, expected "
                f"{seq_lens[k]}"
            )
    return out


def _make_optimizer(args: TrainingArguments, total_steps: int):
    import optax

    decay_steps = max(1, total_steps - args.warmup_steps)
    if args.lr_scheduler_type == "linear":
        decay = optax.linear_schedule(args.learning_rate, 0.0, decay_steps)
    elif args.lr_scheduler_type == "cosine":
        decay = optax.cosine_decay_schedule(args.learning_rate, decay_steps)
    else:
        decay = optax.constant_schedule(args.learning_rate)
    if args.warmup_steps > 0:
        lr = optax.join_schedules(
            [optax.linear_schedule(0.0, args.learning_rate, args.warmup_steps), decay],
            [args.warmup_steps],
        )
    else:
        lr = decay
    if args.optimizer == "adafactor":
        tx = optax.adafactor(learning_rate=lr)
    else:
        tx = optax.adamw(
            learning_rate=lr, weight_decay=args.weight_decay, b1=0.9, b2=0.999
        )
    if args.max_grad_norm:
        tx = optax.chain(optax.clip_by_global_norm(args.max_grad_norm), tx)
    return tx


def dropout_key(seed: int):
    """The key of the train step's dropout masks: a typed ``rbg`` key seeded
    with ``seed + 1``, so a mask is drawn by the chip's own bit generator
    (``rng-bit-generator``) and not by a software Threefry block an element,
    which took 46 % of the fine-tune step and was run again in the backward
    fusions (PERF.md, PR 37).  Only the dropout stream uses it: ``model.init``
    keeps ``PRNGKey(seed)``, as every other random stream keeps its key.  Masks
    therefore differ from those of versions that drew them from the default
    key at the same seed, and, as ``rbg`` bits are not invariant under
    sharding, from one mesh shape to another."""
    import jax

    return jax.random.key(seed + 1, impl="rbg")


def _loss_from_batch(model, p, batch, dropout_rng):
    import jax
    import jax.numpy as jnp

    from tpu_air.models.t5 import cross_entropy_loss, shift_right

    cfg = model.config
    labels = batch["labels"]
    # the model's own parts are named by its modules; what is outside them
    # gets a scope (docs/OBSERVABILITY.md, "Model parts on the device rows")
    with jax.named_scope("loss"):
        dec_in = shift_right(labels, cfg.decoder_start_token_id,
                             cfg.pad_token_id)
        dec_mask = (dec_in != cfg.pad_token_id).astype(jnp.int32).at[:, 0].set(1)
    logits = model.apply(
        {"params": p},
        batch["input_ids"],
        batch["attention_mask"],
        dec_in,
        decoder_attention_mask=dec_mask,
        deterministic=dropout_rng is None,
        rngs=None if dropout_rng is None else {"dropout": dropout_rng},
    )
    with jax.named_scope("loss"):
        return cross_entropy_loss(logits, labels, cfg.pad_token_id)


def make_train_step(model, tx, mesh=None):
    """``(params, opt_state, batch, key) -> (params, opt_state, loss, key)``,
    jitted with the first two donated: the loss with live dropout under a
    split of ``key`` (a ``dropout_key``), its gradient, one ``tx`` update.

    ``mesh``: the ``(data, model)`` mesh the arguments are sharded over.  XLA
    partitions the step but cannot partition a Pallas kernel, so the fused
    attention (``models/t5/modeling.Attention``, a training pass) is mapped
    over it: each chip runs the kernels on its own rows and heads.
    ``step.attention_sites`` holds, once the step has been traced,
    ``{"fused": n, "dense": n}``: the attention call sites by the path taken.

    For a TPU the step is compiled with
    ``xla_tpu_spmd_rng_bit_generator_unsafe``: each shard of a mask, over
    ``data`` and ``model`` alike, is then generated on the chip that holds
    it, from the key offset by the chip's place in the mesh.  Without it every
    chip generates every shard's bits and keeps its slice (four times the
    work on ``data=4``).  Other compilers do not know the option, and the
    platform is all that is looked at."""
    import jax
    import optax

    from tpu_air.models.t5.modeling import count_attention_sites
    from tpu_air.ops.flash_attention import kernel_mesh

    options = ({"xla_tpu_spmd_rng_bit_generator_unsafe": True}
               if jax.default_backend() == "tpu" else None)
    sites = {}

    def train_step(p, o, batch, rng):
        rng, sub = jax.random.split(rng)

        def lf(pp):
            loss, _ = _loss_from_batch(model, pp, batch, sub)
            return loss

        with kernel_mesh(mesh), count_attention_sites() as counted:
            loss, grads = jax.value_and_grad(lf)(p)
        sites.update(fused=counted["fused"], dense=counted["dense"])
        with jax.named_scope("optimizer"):    # the clip is part of ``tx``
            updates, o = tx.update(grads, o, p)
            p = optax.apply_updates(p, updates)
        return p, o, loss, rng

    step = jax.jit(train_step, donate_argnums=(0, 1), compiler_options=options)
    step.attention_sites = sites
    return step


def t5_train_loop(config: Dict[str, Any]) -> None:
    """The SPMD training function (runs inside the trial actor, on its chip
    lease). Uses the session API for data/report."""
    import jax
    import jax.numpy as jnp

    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.parallel import make_mesh, visible_devices
    from tpu_air.parallel.sharding import shard_params
    from tpu_air.train import session
    from jax.sharding import NamedSharding, PartitionSpec as P

    args: TrainingArguments = config.get("training_args") or TrainingArguments(
        **{
            k: v
            for k, v in config.items()
            if k in TrainingArguments.__dataclass_fields__
        }
    )
    # Tune-style overrides arrive as plain dict keys (cc-34 lines 75-79:
    # config.get("learning_rate", ...) pattern)
    for k in ("learning_rate", "num_train_epochs", "weight_decay"):
        if k in config:
            setattr(args, k, config[k])
    if "epochs" in config:
        args.num_train_epochs = config["epochs"]

    model_config: T5Config = config["model_config"]
    tokenizer = config.get("tokenizer")
    preprocessor = config.get("_preprocessor")

    # -- mesh ---------------------------------------------------------------
    # TP degree: ScalingConfig.model_parallel is the user-facing knob
    # (SURVEY.md §7 "TP is a config change"); TrainingArguments.tensor_
    # parallelism remains as the loop-level override for raw JaxTrainer use.
    devs = visible_devices()
    sc = config.get("_scaling_config")
    sc_mp = getattr(sc, "model_parallel", None) or 1
    # ScalingConfig wins when it requests real TP; otherwise the loop-level
    # TrainingArguments.tensor_parallelism override (raw JaxTrainer-style
    # usage) still applies — ScalingConfig's default of 1 must not mask it.
    tp = sc_mp if sc_mp > 1 else max(1, args.tensor_parallelism)
    if tp > len(devs):
        raise ValueError(
            f"model_parallel={tp} exceeds the {len(devs)} visible devices of "
            f"this run's chip lease"
        )
    dp = max(1, len(devs) // tp)
    mesh = make_mesh(("data", "model"), (dp, tp), devices=devs[: dp * tp])

    model = T5ForConditionalGeneration(model_config)
    pad_id = model_config.pad_token_id

    # -- data ---------------------------------------------------------------
    train_ds = session.get_dataset_shard("train")
    eval_ds = session.get_dataset_shard("evaluation")
    if eval_ds is None:
        eval_ds = session.get_dataset_shard("eval")
    if train_ds is None:
        raise ValueError("T5Trainer requires a 'train' dataset")
    global_bs = args.per_device_train_batch_size * dp
    keys = ["input_ids", "attention_mask", "labels"]

    # -- params -------------------------------------------------------------
    sample = next(train_ds.iter_batches(batch_size=2, batch_format="pandas"))
    sample_batch = collate(sample, keys)
    seq_lens = {k: v.shape[1] for k, v in sample_batch.items()}

    resume_dir = config.get("resume_from_checkpoint")
    pretrained = config.get("pretrained_params")
    if resume_dir:
        params = Checkpoint.from_directory(resume_dir).get_params()
    elif pretrained is not None:
        params = pretrained
    else:
        init_rng = jax.random.PRNGKey(args.seed)
        dummy = jnp.ones((1, 8), jnp.int32)
        params = model.init(init_rng, dummy, dummy, dummy[:, :4])["params"]

    n_train = train_ds.count()
    steps_per_epoch = max(1, n_train // global_bs)
    if args.max_steps_per_epoch:
        steps_per_epoch = min(steps_per_epoch, args.max_steps_per_epoch)
    tx = _make_optimizer(args, steps_per_epoch * args.num_train_epochs)

    params = shard_params(params, mesh)
    batch_sharding = NamedSharding(mesh, P("data"))
    rep = NamedSharding(mesh, P())
    multihost = jax.process_count() > 1

    def replicated(x):
        """``x`` (host-local, the same on every host) on every device of
        the mesh.  device_put rejects shardings with non-addressable
        devices, so across hosts each one hands its devices their copy."""
        if not multihost:
            return jax.device_put(x, rep)
        xa = np.asarray(x)
        return jax.make_array_from_callback(
            xa.shape, rep, lambda idx: xa[idx])

    # the moments take their parameters' shardings; the step counters come
    # out of ``tx.init`` on one device, uncommitted, and would come back from
    # the first step on the mesh: another signature, so the step was traced
    # and compiled twice (PERF.md, PR 39).  On the mesh from the start.
    opt_state = jax.tree_util.tree_map(
        lambda x: replicated(x) if x.ndim == 0 else x, tx.init(params))

    # Per-device param residency: with tp>1 the model-sharded leaves occupy
    # 1/tp of their bytes on each chip — the property that lets T5-XL fit
    # where replication cannot.  Reported so tests and
    # users can verify the shrink actually happened.
    leaves = jax.tree_util.tree_leaves(params)
    params_bytes_total = int(sum(x.nbytes for x in leaves))
    params_bytes_per_device = int(
        sum(
            x.addressable_shards[0].data.nbytes
            if getattr(x, "addressable_shards", None)
            else x.nbytes
            for x in leaves
        )
    )

    # distinct devices that hold the parameters — with the batch's below,
    # the proof that a dp mesh is really spread over the lease
    param_devices = len({d for x in leaves for d in x.sharding.device_set})

    # -- steps --------------------------------------------------------------
    train_step = make_train_step(model, tx, mesh)

    @jax.jit
    def eval_step(p, batch):
        return _loss_from_batch(model, p, batch, None)

    def put_batch(b):
        if multihost:
            # every host iterates the same global batch (broadcast dataset);
            # the callback hands each host's devices their slice — device_put
            # rejects shardings with non-addressable devices
            out = {}
            for k, v in b.items():
                xa = np.asarray(v)
                out[k] = jax.make_array_from_callback(
                    xa.shape, batch_sharding, lambda idx, _v=xa: _v[idx]
                )
            return out
        return {k: jax.device_put(jnp.asarray(v), batch_sharding) for k, v in b.items()}

    # a host-local key is committed to a local device and may not mix with
    # global-mesh arrays in one jit (identical bits on every host: same seed)
    local = dropout_key(args.seed)
    rng = jax.random.wrap_key_data(
        replicated(jax.random.key_data(local)),
        impl=jax.random.key_impl(local))

    # -- epochs -------------------------------------------------------------
    for epoch in range(int(args.num_train_epochs)):
        losses = []
        nsteps = 0
        batches = train_ds.iter_batches(
            batch_size=global_bs, batch_format="pandas", drop_last=True
        )
        while True:
            # host time to fetch, collate and place one global batch (the
            # last one of an epoch holds only the next() that found no more)
            with phase("train.input", step=nsteps):
                with phase("train.next_batch"):
                    batch_df = next(batches, None)
                if batch_df is None:
                    break
                if len(batch_df) < global_bs:
                    continue
                with phase("train.collate"):
                    host_batch = collate(batch_df, keys, seq_lens)
                with phase("train.put_batch"):
                    batch = put_batch(host_batch)
            with phase("train.dispatch", step=nsteps):
                params, opt_state, loss, rng = train_step(params, opt_state, batch, rng)
            losses.append(loss)
            nsteps += 1
            if args.max_steps_per_epoch and nsteps >= args.max_steps_per_epoch:
                break
        with phase("train.epoch_sync", epoch=epoch + 1):
            train_loss = float(jnp.mean(jnp.stack(losses))) if losses else float("nan")
        metrics: Dict[str, Any] = {
            "epoch": epoch + 1,
            "loss": train_loss,
            "steps": nsteps,
            "mesh_data": dp,
            "mesh_model": tp,
            # the train step's attention call sites by the path they took
            # (modeling.Attention): the Pallas kernels, or the dense einsum
            "fused_attention_sites": train_step.attention_sites.get("fused", 0),
            "dense_attention_sites": train_step.attention_sites.get("dense", 0),
            # how many PROCESSES the mesh spans — the cross-host proof for
            # the SPMD-multihost path (1 on a single host)
            "mesh_num_hosts": len(
                {getattr(d, "process_index", 0) for d in mesh.devices.flat}
            ),
            "params_bytes_total": params_bytes_total,
            "params_bytes_per_device": params_bytes_per_device,
            "param_devices": param_devices,
            "batch_devices": len(batch_sharding.device_set),
        }

        if eval_ds is not None and args.evaluation_strategy == "epoch":
            parts = []  # device scalars; host sync deferred past the loop
            ebs = args.per_device_eval_batch_size * dp
            for batch_df in eval_ds.iter_batches(
                batch_size=ebs, batch_format="pandas", drop_last=False
            ):
                if len(batch_df) < ebs:  # pad partial batch with pad rows
                    reps = ebs - len(batch_df)
                    import pandas as pd

                    pad_rows = pd.concat([batch_df.iloc[-1:]] * reps, ignore_index=True)
                    for k in keys:
                        pad_rows[k] = pad_rows[k].map(
                            lambda v: np.full_like(np.asarray(v), pad_id)
                        )
                    batch_df = pd.concat([batch_df, pad_rows], ignore_index=True)
                parts.append(
                    eval_step(params, put_batch(collate(batch_df, keys, seq_lens)))
                )
            # one post-loop sync keeps eval dispatch pipelined (airlint JX004)
            tot = sum(float(loss) * int(ntok) for loss, ntok in parts)  # airlint: disable=JX004 — epoch cadence, not the step path
            cnt = sum(int(ntok) for _, ntok in parts)  # airlint: disable=JX004 — epoch cadence, not the step path
            metrics["eval_loss"] = tot / max(cnt, 1)

        ckpt = None
        if args.save_strategy == "epoch":
            ckpt = Checkpoint.from_model(
                model_config=model_config,
                params=params,
                tokenizer=tokenizer,
                preprocessor=preprocessor,
                metrics=metrics,
            )
        session.report(metrics, checkpoint=ckpt)


class T5Trainer(BaseTrainer):
    """Drop-in for the reference's HuggingFaceTrainer-on-T5 configuration
    (Model_finetuning…ipynb:cc-40; flan-t5-batch-inference.py:96-111).

    Randomness: ``TrainingArguments.seed`` seeds the parameters
    (``PRNGKey(seed)``, unless pretrained or resumed) and, apart from them,
    the dropout masks (``dropout_key``: the chip's bit generator, 16 bits an
    element).  Equal seeds on an equal mesh give equal runs; the masks are not
    those of versions before PR 37 at the same seed, nor the same from one
    mesh shape to another."""

    _name_prefix = "T5Trainer"

    def __init__(
        self,
        *,
        model_config=None,
        model_name: Optional[str] = None,
        training_args: Optional[TrainingArguments] = None,
        tokenizer=None,
        pretrained_params=None,
        trainer_init_config: Optional[Dict[str, Any]] = None,
        **kwargs,
    ):
        super().__init__(**kwargs)
        if model_config is None:
            from tpu_air.models.t5 import T5Config

            model_config = T5Config.from_name(model_name or "flan-t5-base")
        self.model_config = model_config
        self.training_args = training_args or TrainingArguments()
        self.tokenizer = tokenizer
        self.pretrained_params = pretrained_params
        self.trainer_init_config = trainer_init_config or {}

    def _training_fn(self):
        return t5_train_loop

    def _train_loop_config(self) -> Dict[str, Any]:
        cfg = dict(self.trainer_init_config)
        cfg["model_config"] = self.model_config
        cfg["training_args"] = self.training_args
        cfg["tokenizer"] = self.tokenizer
        if self.pretrained_params is not None:
            cfg["pretrained_params"] = self.pretrained_params
        return cfg
