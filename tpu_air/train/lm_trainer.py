"""LMTrainer — long-context causal-LM training through the Trainer API.

Sequence parallelism as a CONFIG CHANGE, not a bespoke script:
``ScalingConfig(num_workers=dp, sequence_parallel=sp)`` builds a
``(data, sequence)`` mesh and runs the shard_map SP step
(parallel/sequence_parallel.py — ring attention over the sequence axis,
chunked lm-head CE, replicated params with a single psum).  The reference
caps every sequence at 512 tokens (utils.py:23-28); this trainer's context
scales with the ``sequence`` axis, wrapped in the same fit() → Result →
Checkpoint contract as T5Trainer so Tune / BatchPredictor / resume compose
unchanged.

Datasets: rows with an ``input_ids`` column (fixed-length token lists).
Targets are the global next-token shift, computed BEFORE sequence sharding.
"""

from __future__ import annotations

import time
from typing import Any, Dict, Optional

import numpy as np

from .checkpoint import Checkpoint
from .t5_trainer import TrainingArguments, _make_optimizer, collate
from .trainer import BaseTrainer


def lm_train_loop(config: Dict[str, Any]) -> None:
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_air.models.lm import LMConfig
    from tpu_air.parallel.sequence_parallel import (
        make_sp_mesh,
        make_sp_train_step,
        shard_batch,
        sp_local_loss,
    )
    from tpu_air.parallel.shardmap_compat import shard_map_unchecked
    from tpu_air.train import session

    args: TrainingArguments = config.get("training_args") or TrainingArguments()
    for k in ("learning_rate", "num_train_epochs", "weight_decay"):
        if k in config:
            setattr(args, k, config[k])

    model_config: LMConfig = config["model_config"]
    preprocessor = config.get("_preprocessor")

    sc = config.get("_scaling_config")
    sp = getattr(sc, "sequence_parallel", None) or 1
    mp = getattr(sc, "model_parallel", None) or 1
    if mp > 1 and sp > 1:
        raise ValueError(
            "LMTrainer: model_parallel and sequence_parallel cannot be "
            "combined yet — pick one axis per run (the SP step runs inside "
            "shard_map; TP rides pjit shardings)"
        )
    if mp > 1:
        _lm_tp_loop(config, args, model_config, preprocessor, mp)
        return
    mesh = make_sp_mesh(sp=sp)
    dp = mesh.shape["data"]
    ndev = dp * sp
    pad = model_config.pad_token_id

    train_ds = session.get_dataset_shard("train")
    if train_ds is None:
        raise ValueError("LMTrainer requires a 'train' dataset")
    eval_ds = session.get_dataset_shard("evaluation") or session.get_dataset_shard("eval")

    tx_total = train_ds.count()
    global_bs = args.per_device_train_batch_size * dp
    steps_per_epoch = max(1, tx_total // global_bs)
    if args.max_steps_per_epoch:
        steps_per_epoch = min(steps_per_epoch, args.max_steps_per_epoch)
    tx = _make_optimizer(args, steps_per_epoch * args.num_train_epochs)

    step, model = make_sp_train_step(model_config, mesh, tx)

    # eval: the SAME local-loss recipe the train step differentiates
    # (sp_local_loss — single source of truth), no update, psum'd sums
    def eval_local(params, input_ids, targets):
        s, c = sp_local_loss(model, params, input_ids, targets)
        return (jax.lax.psum(s, ("data", "sequence")),
                jax.lax.psum(c, ("data", "sequence")))

    repl, dsh = P(), P("data", "sequence")
    eval_step = jax.jit(shard_map_unchecked(
        eval_local, mesh=mesh, in_specs=(repl, dsh, dsh), out_specs=(repl, repl)
    ))

    resume_dir = config.get("resume_from_checkpoint")
    if resume_dir:
        params = Checkpoint.from_directory(resume_dir).get_params()
        params = jax.device_put(params, NamedSharding(mesh, P()))
    else:
        from tpu_air.parallel.sequence_parallel import init_sp_params

        params = init_sp_params(model_config, mesh, seed=args.seed)
    opt_state = jax.device_put(tx.init(params), NamedSharding(mesh, P()))

    def batches(ds, bs, drop_last=True):
        for df in ds.iter_batches(batch_size=bs, batch_format="pandas",
                                  drop_last=drop_last):
            ids = collate(df, ["input_ids"])["input_ids"]
            # global next-token shift BEFORE sequence sharding, on host
            # (shift_targets semantics, without a device round-trip)
            tgt = np.concatenate(
                [ids[:, 1:], np.full((ids.shape[0], 1), pad, ids.dtype)], axis=1
            )
            if len(ids) % bs:
                # partial eval batch: pad with all-pad rows — their targets
                # are fully masked, so they contribute (0, 0) to the sums
                need = bs - len(ids) % bs
                ids = np.concatenate(
                    [ids, np.full((need, ids.shape[1]), pad, ids.dtype)]
                )
                tgt = np.concatenate(
                    [tgt, np.full((need, tgt.shape[1]), pad, tgt.dtype)]
                )
            yield shard_batch(mesh, jnp.asarray(ids), jnp.asarray(tgt))

    for epoch in range(int(args.num_train_epochs)):
        t0 = time.time()
        losses, tokens, nsteps = [], 0, 0
        for ids, tgt in batches(train_ds, global_bs):
            params, opt_state, loss = step(params, opt_state, ids, tgt)
            losses.append(loss)  # device scalar; host sync deferred to epoch end
            tokens += ids.shape[0] * ids.shape[1]
            nsteps += 1
            if args.max_steps_per_epoch and nsteps >= args.max_steps_per_epoch:
                break
        dt = time.time() - t0
        metrics: Dict[str, Any] = {
            "epoch": epoch + 1,
            "loss": float(np.mean(losses)) if losses else float("nan"),
            "steps": nsteps,
            "train_tokens_per_sec": tokens / dt if dt > 0 else 0.0,
            "train_tokens_per_sec_per_chip": tokens / dt / ndev if dt > 0 else 0.0,
            "mesh_data": dp,
            "mesh_sequence": sp,
        }
        if eval_ds is not None and args.evaluation_strategy == "epoch":
            ebs = args.per_device_eval_batch_size * dp
            # keep eval results on device across the loop; one host sync
            # after it preserves async dispatch pipelining (airlint JX004)
            parts = [eval_step(params, ids, tgt)
                     for ids, tgt in batches(eval_ds, ebs, drop_last=False)]
            tot = sum(float(s) for s, _ in parts)  # airlint: disable=JX004 — epoch cadence, not the step path
            cnt = sum(int(c) for _, c in parts)  # airlint: disable=JX004 — epoch cadence, not the step path
            if cnt:
                metrics["eval_loss"] = tot / cnt
        ckpt = None
        if args.save_strategy == "epoch":
            ckpt = Checkpoint.from_model(
                model_config=model_config,
                params=params,
                preprocessor=preprocessor,
                metrics=metrics,
            )
        session.report(metrics, checkpoint=ckpt)


def _lm_tp_loop(config, args, model_config, preprocessor, mp) -> None:
    """Tensor-parallel LM training (``ScalingConfig(model_parallel=N)``):
    a (data, model) mesh with the LM sharding rules
    (parallel/sharding.lm_param_spec) — params and optimizer state live
    1/N-per-device on the ``model`` axis, XLA inserts the TP collectives.
    The param-sharding story for the LM family beyond replication: the
    long-context SP axis scales CONTEXT, this axis scales the MODEL."""
    import jax
    import jax.numpy as jnp
    from functools import partial
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_air.models.lm import (
        CausalLM,
        head_weight,
        lm_chunked_loss_with_targets,
    )
    from tpu_air.parallel import make_mesh, visible_devices
    from tpu_air.parallel.sharding import lm_param_spec, shard_params
    from tpu_air.train import session

    devs = visible_devices()
    if mp > len(devs):
        raise ValueError(
            f"model_parallel={mp} exceeds the {len(devs)} visible devices"
        )
    dp = max(1, len(devs) // mp)
    mesh = make_mesh(("data", "model"), (dp, mp), devices=devs[: dp * mp])
    ndev = dp * mp
    pad = model_config.pad_token_id

    train_ds = session.get_dataset_shard("train")
    if train_ds is None:
        raise ValueError("LMTrainer requires a 'train' dataset")
    eval_ds = session.get_dataset_shard("evaluation") or session.get_dataset_shard("eval")

    global_bs = args.per_device_train_batch_size * dp
    steps_per_epoch = max(1, train_ds.count() // global_bs)
    if args.max_steps_per_epoch:
        steps_per_epoch = min(steps_per_epoch, args.max_steps_per_epoch)
    tx = _make_optimizer(args, steps_per_epoch * args.num_train_epochs)

    model = CausalLM(model_config)
    resume_dir = config.get("resume_from_checkpoint")
    if resume_dir:
        params = Checkpoint.from_directory(resume_dir).get_params()
    else:
        import jax.random as jrandom

        params = model.init(jrandom.PRNGKey(args.seed),
                            jnp.ones((1, 8), jnp.int32))["params"]
    params = shard_params(params, mesh, spec_fn=lm_param_spec)
    opt_state = tx.init(params)
    batch_sh = NamedSharding(mesh, P("data"))

    leaves = jax.tree_util.tree_leaves(params)
    params_bytes_total = int(sum(x.nbytes for x in leaves))
    params_bytes_per_device = int(sum(
        x.addressable_shards[0].data.nbytes
        if getattr(x, "addressable_shards", None) else x.nbytes
        for x in leaves
    ))

    def loss_fn(p, ids, tgt):
        hidden = model.apply({"params": p}, ids, return_hidden=True)
        s, c = lm_chunked_loss_with_targets(
            hidden, head_weight(p, model_config), tgt, pad
        )
        return s / jnp.maximum(c, 1.0), c

    @partial(jax.jit, donate_argnums=(0, 1))
    def train_step(p, o, ids, tgt):
        import optax as _optax

        (loss, _), grads = jax.value_and_grad(loss_fn, has_aux=True)(p, ids, tgt)
        updates, o = tx.update(grads, o, p)
        return _optax.apply_updates(p, updates), o, loss

    @jax.jit
    def eval_step(p, ids, tgt):
        loss, c = loss_fn(p, ids, tgt)
        return loss, c

    def batches(ds, bs, drop_last=True):
        for df in ds.iter_batches(batch_size=bs, batch_format="pandas",
                                  drop_last=drop_last):
            ids = collate(df, ["input_ids"])["input_ids"]
            tgt = np.concatenate(
                [ids[:, 1:], np.full((ids.shape[0], 1), pad, ids.dtype)], axis=1
            )
            if len(ids) % bs:
                need = bs - len(ids) % bs
                ids = np.concatenate(
                    [ids, np.full((need, ids.shape[1]), pad, ids.dtype)]
                )
                tgt = np.concatenate(
                    [tgt, np.full((need, tgt.shape[1]), pad, tgt.dtype)]
                )
            yield (jax.device_put(jnp.asarray(ids), batch_sh),
                   jax.device_put(jnp.asarray(tgt), batch_sh))

    for epoch in range(int(args.num_train_epochs)):
        t0 = time.time()
        losses, tokens, nsteps = [], 0, 0
        for ids, tgt in batches(train_ds, global_bs):
            params, opt_state, loss = train_step(params, opt_state, ids, tgt)
            losses.append(loss)  # device scalar; host sync deferred to epoch end
            tokens += ids.shape[0] * ids.shape[1]
            nsteps += 1
            if args.max_steps_per_epoch and nsteps >= args.max_steps_per_epoch:
                break
        dt = time.time() - t0
        metrics: Dict[str, Any] = {
            "epoch": epoch + 1,
            "loss": float(np.mean(losses)) if losses else float("nan"),
            "steps": nsteps,
            "train_tokens_per_sec": tokens / dt if dt > 0 else 0.0,
            "train_tokens_per_sec_per_chip": tokens / dt / ndev if dt > 0 else 0.0,
            "mesh_data": dp,
            "mesh_model": mp,
            "mesh_sequence": 1,
            "params_bytes_total": params_bytes_total,
            "params_bytes_per_device": params_bytes_per_device,
        }
        if eval_ds is not None and args.evaluation_strategy == "epoch":
            tot, cnt = 0.0, 0
            ebs = args.per_device_eval_batch_size * dp
            for ids, tgt in batches(eval_ds, ebs, drop_last=False):
                loss, c = eval_step(params, ids, tgt)
                tot += float(loss) * int(c)
                cnt += int(c)
            if cnt:
                metrics["eval_loss"] = tot / cnt
        ckpt = None
        if args.save_strategy == "epoch":
            ckpt = Checkpoint.from_model(
                model_config=model_config,
                params=params,
                preprocessor=preprocessor,
                metrics=metrics,
            )
        session.report(metrics, checkpoint=ckpt)


class LMTrainer(BaseTrainer):
    """Long-context causal-LM trainer: SP (long context) and TP (big
    models) are ScalingConfig fields."""

    _name_prefix = "LMTrainer"

    def __init__(
        self,
        *,
        model_config,
        training_args: Optional[TrainingArguments] = None,
        trainer_init_config: Optional[Dict[str, Any]] = None,
        **kwargs,
    ):
        super().__init__(**kwargs)
        self.model_config = model_config
        self.training_args = training_args or TrainingArguments()
        self.trainer_init_config = trainer_init_config or {}

    def _training_fn(self):
        return lm_train_loop

    def _train_loop_config(self) -> Dict[str, Any]:
        return {
            "model_config": self.model_config,
            "training_args": self.training_args,
            **self.trainer_init_config,
        }
