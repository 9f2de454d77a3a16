"""Live dropout in the T5 train step, through ``T5Trainer``'s own step function
(``t5_trainer.make_train_step``) on a ``data=4`` CPU mesh.

Every other T5 test runs at rate 0.0; this file holds what the masks are: the
rate, the scaling, that they differ wherever two masks could wrongly be the
same one (elements, heads, layers, sites, steps, data shards, seeds), that equal
seeds give equal masks, and that a step without live dropout holds no generator.
The masks are seen by recording what ``modeling._dropout`` was given and what it
returned, from inside the jitted step (``jax.debug.callback``).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration, modeling
from tpu_air.train.t5_trainer import (
    _loss_from_batch, dropout_key, make_train_step)

ROWS, ENC, DEC, SHARDS = 8, 192, 64, 4
RATE = 0.1


def _model(rate):
    return T5ForConditionalGeneration(T5Config(
        vocab_size=384, d_model=64, d_kv=16, d_ff=1024, num_layers=2,
        num_heads=4, dropout_rate=rate))


def _placed(model):
    """Parameters, AdamW state and a batch on a ``data=4`` mesh."""
    mesh = Mesh(np.array(jax.devices()[:SHARDS]), ("data",))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))
    one = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), one, one, one[:, :4])["params"]
    tx = optax.adamw(1e-3)
    rng = np.random.default_rng(0)
    batch = {"input_ids": rng.integers(2, 384, (ROWS, ENC)),
             "attention_mask": np.ones((ROWS, ENC)),
             "labels": rng.integers(2, 384, (ROWS, DEC))}
    batch = {k: jax.device_put(v.astype(np.int32), rows)
             for k, v in batch.items()}
    return tx, jax.device_put(params, rep), jax.device_put(tx.init(params), rep), batch, rep


def _two_steps(monkeypatch, seed):
    """[{site: (given, returned)} for each of two steps]: the trainer's step
    run twice from ``seed`` with every dropout call recorded.  A site is the
    call's index in the model's trace: encoder layers (self, feed-forward),
    then decoder layers (self, cross, feed-forward)."""
    real, calls, seen = modeling._dropout, [], {}

    def recorded(x, rate, key, **how):
        out = real(x, rate, key, **how)
        site = len(calls)
        calls.append(rate)
        jax.debug.callback(
            lambda a, b: seen.setdefault(site, []).append(
                (np.asarray(a, np.float32), np.asarray(b, np.float32))),
            x, out)
        return out

    monkeypatch.setattr(modeling, "_dropout", recorded)
    model = _model(RATE)
    tx, params, opt, batch, rep = _placed(model)
    step = make_train_step(model, tx)
    key = jax.device_put(dropout_key(seed), rep)
    losses = []
    for _ in range(2):
        params, opt, loss, key = step(params, opt, batch, key)
        losses.append(float(loss))
    jax.effects_barrier()
    assert calls == [RATE] * 10 and all(np.isfinite(losses))
    return [{site: pairs[i] for site, pairs in seen.items()} for i in range(2)]


@pytest.fixture(scope="module")
def runs():
    """Two steps from seed 7, the same again, and two steps from seed 8."""
    mp = pytest.MonkeyPatch()
    try:
        return [_two_steps(mp, seed) for seed in (7, 7, 8)]
    finally:
        mp.undo()


def _dropped(pair):
    """(dropped, live): which elements the mask zeroed, among those that were
    not zero before (a causal mask leaves exact zeros in the probabilities)."""
    given, returned = pair
    live = given != 0
    return (returned == 0) & live, live


# sites of one step by kind: 4-D attention probabilities, 3-D feed-forward hidden
KINDS = {"attention_probabilities": (0, 2, 4, 5, 7, 8),
         "feed_forward_hidden": (1, 3, 6, 9)}


@pytest.mark.parametrize("kind", sorted(KINDS))
def test_rate_and_scaling_at_each_site(runs, kind):
    """Over both steps and every layer of a kind, more than 10**6 elements:
    a share of 0.1 +- 0.002 is zeroed, what is kept is the input over 0.9, and
    the sum is preserved to within five standard deviations of what an
    independent mask a element does to it."""
    dropped = live = 0
    kept_sum = given_sum = given_squares = 0.0
    for step in runs[0]:
        for site in KINDS[kind]:
            given, returned = step[site]
            d, lv = _dropped(step[site])
            dropped, live = dropped + d.sum(), live + lv.sum()
            np.testing.assert_allclose(
                returned[~d], given[~d] / np.float32(1.0 - RATE), rtol=1e-6)
            kept_sum += float(returned.sum(dtype=np.float64))
            given_sum += float(given.sum(dtype=np.float64))
            given_squares += float(np.square(given, dtype=np.float64).sum())
    assert live >= 10**6, live
    assert abs(dropped / live - RATE) <= 0.002, dropped / live
    sigma = (RATE / (1.0 - RATE) * given_squares) ** 0.5
    assert abs(kept_sum - given_sum) <= 5 * sigma, (kept_sum, given_sum, sigma)


def _pick(runs, run, step, site, where=np.s_[:]):
    return _dropped(runs[run][step][site])[0][where]


# name -> two masks of one shape that must not be one mask
PAIRS = {
    "two_encoder_layers": lambda r: (_pick(r, 0, 0, 0), _pick(r, 0, 0, 2)),
    "two_feed_forwards": lambda r: (_pick(r, 0, 0, 1), _pick(r, 0, 0, 3)),
    "self_and_cross_of_two_decoder_layers":
        lambda r: (_pick(r, 0, 0, 5), _pick(r, 0, 0, 8)),
    "two_steps": lambda r: (_pick(r, 0, 0, 0), _pick(r, 0, 1, 0)),
    "two_heads": lambda r: (_pick(r, 0, 0, 0, np.s_[:, 0]),
                            _pick(r, 0, 0, 0, np.s_[:, 1])),
    "two_rows_of_one_shard": lambda r: (_pick(r, 0, 0, 0, np.s_[0]),
                                        _pick(r, 0, 0, 0, np.s_[1])),
    "two_data_shards": lambda r: (
        _pick(r, 0, 0, 0, np.s_[:ROWS // SHARDS]),
        _pick(r, 0, 0, 0, np.s_[ROWS // SHARDS:2 * (ROWS // SHARDS)])),
    "two_data_shards_feed_forward": lambda r: (
        _pick(r, 0, 0, 1, np.s_[:ROWS // SHARDS]),
        _pick(r, 0, 0, 1, np.s_[-(ROWS // SHARDS):])),
    "two_seeds": lambda r: (_pick(r, 0, 0, 0), _pick(r, 2, 0, 0)),
}


@pytest.mark.parametrize("pair", sorted(PAIRS))
def test_masks_are_independent(runs, pair):
    """Two masks that could wrongly be one: each has its own tenth zeroed and
    they agree no more than independent draws do (correlation 0 +- 0.02 over
    at least 10**5 elements; one mask used twice reads 1)."""
    a, b = (m.ravel().astype(np.float64) for m in PAIRS[pair](runs))
    assert a.size >= 10**5 and a.shape == b.shape
    assert abs(np.corrcoef(a, b)[0, 1]) < 0.02


def test_equal_seeds_give_equal_masks(runs):
    for first, again in zip(runs[0], runs[1]):
        for site in first:
            np.testing.assert_array_equal(first[site][1], again[site][1])


def _lowered_step(rate):
    model = _model(rate)
    tx, params, opt, batch, rep = _placed(model)
    return make_train_step(model, tx).lower(
        params, opt, batch, jax.device_put(dropout_key(0), rep)).as_text()


def test_the_live_step_draws_its_masks_from_the_bit_generator():
    """Ten sites, ten draws, none from Threefry (whose only use is to split the
    step's key: scalars)."""
    text = _lowered_step(RATE)
    assert text.count("stablehlo.rng_bit_generator") == 10


@pytest.mark.parametrize("how", ["rate_0", "deterministic"])
def test_no_generator_without_live_dropout(how):
    if how == "rate_0":
        text = _lowered_step(0.0)
    else:
        model = _model(RATE)
        _, params, _, batch, _ = _placed(model)
        text = jax.jit(lambda p, b: _loss_from_batch(model, p, b, None)).lower(
            params, batch).as_text()
        assert "threefry" not in text
    assert "rng_bit_generator" not in text
