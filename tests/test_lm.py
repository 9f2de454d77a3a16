"""Long-context LM + sequence parallelism tests (first-class long-context:
ring attention over a ``sequence`` mesh axis; cf. ops/ring_attention.py).

Run on the 8-device virtual CPU mesh (tests/conftest.py)."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from tpu_air.models.lm import CausalLM, LMConfig, lm_loss
from tpu_air.parallel.sequence_parallel import (
    init_sp_params,
    make_sp_mesh,
    make_sp_train_step,
    shard_batch,
    shift_targets,
)

B, L, V = 2, 64, 128


def tiny_cfg(**kw):
    base = dict(vocab_size=V, d_model=32, n_layers=2, n_heads=2, head_dim=16,
                d_ff=64, max_seq_len=L)
    base.update(kw)
    return LMConfig(**base)


@pytest.fixture(scope="module")
def batch():
    rng = np.random.default_rng(0)
    ids = rng.integers(1, V, size=(B, L)).astype(np.int32)
    return jnp.asarray(ids)


def test_forward_shapes(batch):
    cfg = tiny_cfg()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    logits = model.apply({"params": params}, batch)
    assert logits.shape == (B, L, V)
    s, c = lm_loss(logits, batch, cfg.pad_token_id)
    assert np.isfinite(float(s)) and float(c) > 0


def test_a_config_written_with_the_old_attention_options_still_loads():
    """``LMConfig`` lost ``attention``, ``flash_min_seq_len``, ``block_q`` and
    ``block_k`` (no caller set them): a checkpoint's ``model_config.json``
    written while it had them loads, the keys dropped."""
    import json

    gone = {"attention": "flash", "flash_min_seq_len": 1024, "block_q": 512,
            "block_k": None}
    written = json.dumps({**tiny_cfg().to_dict(), **gone,
                          "model_type": "causal_lm"})
    cfg = LMConfig.from_dict(json.loads(written))
    assert cfg == tiny_cfg()
    assert not set(gone) & set(cfg.to_dict())


def test_causality(batch):
    """Future tokens must not influence past logits."""
    cfg = tiny_cfg()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    base = model.apply({"params": params}, batch)
    mutated = batch.at[:, L // 2:].set(7)
    out = model.apply({"params": params}, mutated)
    np.testing.assert_allclose(
        np.asarray(base[:, : L // 2 - 1]), np.asarray(out[:, : L // 2 - 1]),
        rtol=2e-5, atol=2e-5,
    )


def test_ring_forward_matches_dense(batch):
    """shard_map ring attention over sequence == single-device dense."""
    from tpu_air.parallel.sequence_parallel import _shard_map
    from jax.sharding import PartitionSpec as P

    cfg = tiny_cfg()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    dense = model.apply({"params": params}, batch)

    mesh = make_sp_mesh(8, dp=2, sp=4)
    ring_cfg = tiny_cfg(sequence_axis="sequence")
    ring_model = CausalLM(ring_cfg)

    def local_fwd(p, ids):
        li = ids.shape[1]
        off = jax.lax.axis_index("sequence") * li
        pos = jnp.broadcast_to(off + jnp.arange(li, dtype=jnp.int32), ids.shape)
        return ring_model.apply({"params": p}, ids, pos)

    fwd = _shard_map(local_fwd, mesh=mesh,
                     in_specs=(P(), P("data", "sequence")),
                     out_specs=P("data", "sequence"))
    ring = jax.jit(fwd)(params, batch)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(ring),
                               rtol=2e-4, atol=2e-4)


def test_sp_train_step_runs_and_learns(batch):
    """One dp=2 x sp=4 train step: finite decreasing loss, replicated params."""
    cfg = tiny_cfg()
    mesh = make_sp_mesh(8, dp=2, sp=4)
    tx = optax.adam(1e-2)
    step, _ = make_sp_train_step(cfg, mesh, tx)
    params = init_sp_params(cfg, mesh, seed=0)
    opt_state = jax.device_put(
        tx.init(params), jax.sharding.NamedSharding(mesh, jax.sharding.PartitionSpec())
    )
    targets = shift_targets(batch, cfg.pad_token_id)
    ids, tgt = shard_batch(mesh, batch, targets)
    losses = []
    for _ in range(4):
        params, opt_state, loss = step(params, opt_state, ids, tgt)
        losses.append(float(loss))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


@pytest.mark.slow  # numerics-parity / superseded-coverage: slow tier (budget, r3 weak #5)
def test_sp_grads_match_single_device(batch):
    """The sequence-parallel psum'd gradient equals the single-device one."""
    cfg = tiny_cfg()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0), batch)["params"]
    targets = shift_targets(batch, cfg.pad_token_id)

    from tpu_air.models.lm import lm_loss_with_targets

    def dense_loss(p):
        logits = model.apply({"params": p}, batch)
        s, c = lm_loss_with_targets(logits, targets, cfg.pad_token_id)
        return s / jnp.maximum(c, 1.0)

    gd = jax.grad(dense_loss)(params)

    mesh = make_sp_mesh(8, dp=2, sp=4)
    # recover the psum'd grads from one sp step with SGD(lr=1): delta = -grad
    tx = optax.sgd(1.0)
    step, _ = make_sp_train_step(cfg, mesh, tx)
    p0 = init_sp_params(cfg, mesh, seed=0)
    import jax.tree_util as jtu

    p0_copy = jtu.tree_map(jnp.copy, p0)
    opt_state = tx.init(p0)
    ids, tgt = shard_batch(mesh, batch, targets)
    p1, _, _ = step(p0, opt_state, ids, tgt)
    gs = jtu.tree_map(lambda a, b: np.asarray(a - b), p0_copy, p1)
    flat_d, _ = jax.flatten_util.ravel_pytree(gd)
    flat_s, _ = jax.flatten_util.ravel_pytree(gs)
    np.testing.assert_allclose(np.asarray(flat_d), np.asarray(flat_s),
                               rtol=5e-4, atol=5e-4)


@pytest.mark.slow  # numerics-parity / superseded-coverage: slow tier (budget, r3 weak #5)
def test_chunked_head_loss_matches_dense():
    """lm_chunked_loss_with_targets (no (B,L,V) logits materialization) is
    numerically the dense head + CE, in value AND gradients."""
    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm import (
        CausalLM,
        LMConfig,
        head_weight,
        lm_chunked_loss_with_targets,
        lm_loss_with_targets,
    )

    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    rng = jax.random.PRNGKey(0)
    B, L = 2, 64
    ids = jax.random.randint(rng, (B, L), 2, cfg.vocab_size, jnp.int32)
    targets = jnp.concatenate(
        [ids[:, 1:], jnp.full((B, 1), cfg.pad_token_id, ids.dtype)], axis=1
    )
    params = model.init(rng, ids)["params"]

    def dense(p):
        logits = model.apply({"params": p}, ids)
        s, c = lm_loss_with_targets(logits, targets, cfg.pad_token_id)
        return s / c

    def chunked(p):
        hidden = model.apply({"params": p}, ids, return_hidden=True)
        s, c = lm_chunked_loss_with_targets(
            hidden, head_weight(p, cfg), targets, cfg.pad_token_id, chunk_size=16
        )
        return s / c

    ld, gd = jax.value_and_grad(dense)(params)
    lc, gc = jax.value_and_grad(chunked)(params)
    assert abs(float(ld) - float(lc)) < 1e-5, (ld, lc)
    flat_d = jax.tree_util.tree_leaves(gd)
    flat_c = jax.tree_util.tree_leaves(gc)
    for a, b in zip(flat_d, flat_c):
        import numpy as np

        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   atol=1e-4, rtol=1e-4)


def test_chunked_head_loss_pads_non_divisible_lengths():
    """A non-chunk-multiple length must keep the chunked (padded) path and
    still match the dense loss exactly — not silently fall back to dense."""
    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm import (
        CausalLM,
        LMConfig,
        head_weight,
        lm_chunked_loss_with_targets,
        lm_loss_with_targets,
    )

    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    rng = jax.random.PRNGKey(3)
    B, L = 2, 50  # 50 % 16 != 0
    ids = jax.random.randint(rng, (B, L), 2, cfg.vocab_size, jnp.int32)
    targets = jnp.concatenate(
        [ids[:, 1:], jnp.full((B, 1), cfg.pad_token_id, ids.dtype)], axis=1
    )
    params = model.init(rng, ids)["params"]
    hidden = model.apply({"params": params}, ids, return_hidden=True)
    s1, c1 = lm_chunked_loss_with_targets(
        hidden, head_weight(params, cfg), targets, cfg.pad_token_id, chunk_size=16
    )
    logits = model.apply({"params": params}, ids)
    s2, c2 = lm_loss_with_targets(logits, targets, cfg.pad_token_id)
    assert abs(float(s1) - float(s2)) < 1e-3 and float(c1) == float(c2)


def test_lm_trainer_sequence_parallel_fit(air):
    """Trainer coherence for SP: long-context training is a
    ScalingConfig field (sequence_parallel=N) through the standard
    fit() -> Result -> Checkpoint contract, not a bespoke script."""
    import numpy as np

    import tpu_air.data as tad
    from tpu_air.models.lm import LMConfig
    from tpu_air.train import (
        CheckpointConfig,
        LMTrainer,
        RunConfig,
        ScalingConfig,
        TrainingArguments,
    )

    rng = np.random.default_rng(0)
    period, L = 17, 64
    rows = [
        {"input_ids": (2 + (np.arange(L) + int(rng.integers(period))) % period)
                      .astype(np.int32).tolist()}
        for _ in range(32)
    ]
    ds = tad.from_items(rows)
    trainer = LMTrainer(
        model_config=LMConfig.tiny(),
        training_args=TrainingArguments(
            learning_rate=1e-3, per_device_train_batch_size=2,
            num_train_epochs=2, max_steps_per_epoch=4,
        ),
        scaling_config=ScalingConfig(num_workers=2, sequence_parallel=2),
        datasets={"train": ds, "evaluation": ds.limit(8)},
        run_config=RunConfig(
            checkpoint_config=CheckpointConfig(
                num_to_keep=1, checkpoint_score_attribute="eval_loss",
                checkpoint_score_order="min",
            )
        ),
    )
    result = trainer.fit()
    assert result.error is None, result.error
    m = result.metrics
    assert m["mesh_sequence"] == 2 and m["mesh_data"] >= 1
    assert np.isfinite(m["loss"]) and np.isfinite(m["eval_loss"])
    assert result.checkpoint is not None
    # the checkpoint round-trips params + config
    cfg = result.checkpoint._load_model_config()
    assert cfg.vocab_size == LMConfig.tiny().vocab_size


@pytest.mark.slow  # numerics-parity / superseded-coverage: slow tier (budget, r3 weak #5)
def test_lm_generate_kv_cache_matches_uncached():
    """Cached greedy decode must pick the same tokens as argmax over the
    full uncached forward at every step (KV-cache correctness)."""
    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm import CausalLM, LMConfig, generate

    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    rng = jax.random.PRNGKey(0)
    B, LP, NEW = 2, 8, 6
    prompt = jax.random.randint(rng, (B, LP), 2, cfg.vocab_size, jnp.int32)
    params = model.init(rng, prompt)["params"]

    toks = generate(model, params, prompt, max_new_tokens=NEW)
    assert toks.shape == (B, NEW)

    # uncached reference: grow the sequence, full forward each step
    seq = prompt
    ref = []
    for _ in range(NEW):
        logits = model.apply({"params": params}, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        ref.append(nxt)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    ref = jnp.stack(ref, axis=1)
    assert (toks == ref).all(), (toks, ref)


def test_lm_generate_eos_pads_after():
    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm import CausalLM, LMConfig, make_lm_generate_fn

    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    rng = jax.random.PRNGKey(1)
    prompt = jax.random.randint(rng, (1, 4), 2, cfg.vocab_size, jnp.int32)
    params = model.init(rng, prompt)["params"]
    # pick whatever greedy emits first as the "eos" and regenerate: the rest
    # of that row must be pad
    first = int(jax.device_get(
        make_lm_generate_fn(model, 1)(params, prompt, rng))[0, 0])
    toks = make_lm_generate_fn(model, 5, eos_token_id=first)(params, prompt, rng)
    toks = jax.device_get(toks)[0]
    assert toks[0] == first and all(t == cfg.pad_token_id for t in toks[1:])


def test_lm_checkpoint_to_batch_predictor(air):
    """LMTrainer checkpoint -> BatchPredictor(LMGenerativePredictor): the
    full train -> checkpoint -> distributed generate lifecycle for the LM
    family (the W3 arc on the long-context flagship)."""
    import numpy as np

    import tpu_air.data as tad
    from tpu_air.models.lm import LMConfig
    from tpu_air.predict import BatchPredictor, LMGenerativePredictor
    from tpu_air.train import LMTrainer, RunConfig, ScalingConfig, TrainingArguments

    rng = np.random.default_rng(0)
    L = 32
    rows = [{"input_ids": (2 + (np.arange(L) + int(rng.integers(11))) % 11)
             .astype(np.int32).tolist()} for _ in range(16)]
    trainer = LMTrainer(
        model_config=LMConfig.tiny(),
        training_args=TrainingArguments(
            learning_rate=1e-3, per_device_train_batch_size=2,
            num_train_epochs=1, max_steps_per_epoch=2,
        ),
        scaling_config=ScalingConfig(num_workers=2, sequence_parallel=1),
        datasets={"train": tad.from_items(rows)},
        run_config=RunConfig(),
    )
    result = trainer.fit()
    assert result.error is None, result.error

    prompts = tad.from_items(
        [{"input_ids": r["input_ids"][:8]} for r in rows[:6]]
    )
    bp = BatchPredictor.from_checkpoint(result.checkpoint, LMGenerativePredictor)
    out = bp.predict(prompts, batch_size=3, min_scoring_workers=1,
                     max_scoring_workers=2, max_new_tokens=4)
    df = out.to_pandas()
    assert len(df) == 6 and "generated_output" in df.columns
    assert all(isinstance(t, str) and t for t in df["generated_output"])


def test_lm_trainer_tensor_parallel_fit(air):
    """ScalingConfig(model_parallel=2) for the LM family: params/opt state
    shard over the ``model`` axis (per-device bytes shrink — the
    param-sharding story beyond replication), loss finite, checkpoint
    round-trips.  TP+SP combined raises (one axis per run for now)."""
    import tpu_air.data as tad
    from tpu_air.models.lm import LMConfig
    from tpu_air.train import LMTrainer, ScalingConfig, TrainingArguments

    rng = np.random.default_rng(0)
    rows = [{"input_ids": rng.integers(1, 250, size=32).astype(int).tolist()}
            for _ in range(16)]
    trainer = LMTrainer(
        model_config=LMConfig.tiny(),
        training_args=TrainingArguments(
            learning_rate=1e-3, per_device_train_batch_size=2,
            num_train_epochs=1, max_steps_per_epoch=2,
        ),
        scaling_config=ScalingConfig(num_workers=2, model_parallel=2),
        datasets={"train": tad.from_items(rows)},
    )
    r = trainer.fit()
    assert r.error is None, r.error
    m = r.metrics
    assert m["mesh_model"] == 2 and m["mesh_data"] == 2, m
    assert np.isfinite(m["loss"]), m
    assert m["params_bytes_per_device"] < m["params_bytes_total"], m
    assert r.checkpoint is not None and r.checkpoint.get_params()

    bad = LMTrainer(
        model_config=LMConfig.tiny(),
        training_args=TrainingArguments(num_train_epochs=1),
        scaling_config=ScalingConfig(num_workers=1, model_parallel=2,
                                     sequence_parallel=2,
                                     num_chips_per_worker=4),
        datasets={"train": tad.from_items(rows)},
    )
    r2 = bad.fit()
    assert r2.error is not None and "cannot be combined" in str(r2.error)


def _all_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for v in eqn.params.values():
            for x in v if isinstance(v, (list, tuple)) else (v,):
                inner = getattr(x, "jaxpr", x)
                if hasattr(inner, "eqns"):
                    yield from _all_eqns(inner)


@pytest.mark.parametrize("kind", ["paged_step", "generate_step"])
def test_lm_cached_step_never_views_a_slab_in_4d(kind):
    """PR 25's guard (tests/test_t5.py) on the LM's two single-token steps:
    no equation of the engine's paged decode step, nor of offline
    ``generate``'s loop body, makes a ``[b, L, h, d]`` (or ``[b, h, L, d]``)
    array for a cache length ``L``: both attend over the flat
    ``[b, L, h*d]`` slab as stored.  The prefill's one-time view is outside
    the loop body and is found, so the check can see one."""
    from tpu_air.models.lm.generate import (
        init_paged_cache, make_lm_generate_fn, make_lm_paged_decode_step_fn)

    cfg = tiny_cfg()
    model = CausalLM(cfg)
    h, d = cfg.n_heads, cfg.head_dim
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0),
                           jnp.ones((1, 8), jnp.int32)))["params"]

    def views(jaxpr, b, L):
        shapes = {(b, L, h, d), (b, h, L, d)}
        return [e for e in _all_eqns(jaxpr)
                if any(tuple(v.aval.shape) in shapes for v in e.outvars)]

    if kind == "paged_step":
        S, slot_len, C = 3, 32, 8
        npg = slot_len // C
        cache = jax.eval_shape(
            lambda: init_paged_cache(model, S, 1 + S * npg, C, npg))
        jaxpr = jax.make_jaxpr(make_lm_paged_decode_step_fn(model, slot_len))(
            params, cache, jnp.zeros((S,), jnp.int32),
            jnp.zeros((S,), jnp.int32), jnp.zeros((S, npg), jnp.int32))
        assert not views(jaxpr.jaxpr, S, slot_len)
        return
    b, lp, new = 3, 10, 6
    fn = make_lm_generate_fn(model, new, eos_token_id=1, early_stop=True)
    jaxpr = jax.make_jaxpr(fn)(
        params, jnp.ones((b, lp), jnp.int32), jax.random.PRNGKey(0))
    bodies = [e.params["body_jaxpr"].jaxpr for e in _all_eqns(jaxpr.jaxpr)
              if e.primitive.name == "while"]
    assert bodies, "no while-loop in generate"
    for body in bodies:
        assert not views(body, b, lp + new)
    assert views(jaxpr.jaxpr, b, lp + new), "the prefill's view was not seen"
