"""Autoregressive generation under jit.

The reference calls torch ``model.generate(**inputs, max_new_tokens=128)``
(predictor.py:102; Model_finetuning…ipynb:cc-67).  TPU-native version: a
fixed-shape `lax.scan` decode loop over a pre-allocated KV cache — no Python
control flow, no recompiles across batches of the same shape (SURVEY.md §7
hard-part 2).  Cache tensors are built with `jax.eval_shape`, so cache
construction costs nothing.

Greedy decoding is the default (matching the reference's ``generate`` call,
which passes no sampling flags); temperature/top-k sampling is available.
"""

from __future__ import annotations

from functools import partial
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from .config import T5Config
from .modeling import T5ForConditionalGeneration


def init_cache(model, params, batch_size: int, max_decode_len: int,
               enc_hidden, enc_mask):
    """Build the decode cache.

    Self-attention slabs and bookkeeping come from ``eval_shape`` (free);
    the cross-attention K/V — an invariant of the encoder output — come
    from ONE real qlen-1 decoder pass (``init_decode_cache``) whose only
    meaningful compute is the per-layer K/V projections of ``enc_hidden``.
    The two trees are grafted: everything under a ``cross_attn`` module is
    taken from the real pass, the rest from the zeroed full-size tree."""

    def _init():
        return model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((batch_size, max_decode_len), jnp.int32),
            enc_hidden,
            enc_mask,
            decode=True,
            method=model.decode,
        )

    shapes = jax.eval_shape(_init)["cache"]
    cache = jax.tree_util.tree_map(
        lambda s: jnp.zeros(s.shape, s.dtype), shapes
    )
    _, vars1 = model.apply(
        {"params": params},
        jnp.zeros((batch_size, 1), jnp.int32),
        enc_hidden,
        enc_mask,
        mutable=["cache"],
        method=model.init_decode_cache,
    )

    def graft(dst, src, under_cross=False):
        for k, v in src.items():
            if isinstance(v, dict):
                graft(dst[k], v, under_cross or k == "cross_attn")
            elif under_cross:
                dst[k] = v

    from flax.core import unfreeze

    cache = unfreeze(cache)
    graft(cache, unfreeze(vars1["cache"]))
    return cache


def per_layer_slabs(cache):
    """The decode cache with each kind of self-attention slab, one
    ``[layers, L, b, ...]`` array as ``init_cache`` builds it, split into a
    tuple of the layers' own arrays; the model takes either and returns what
    it was given.  For a program that is ONE step and no loop: there the
    cache arrives as parameters, and the v5e's compiler copies each layer's
    slice of a parameter out before the step reads it (0.8 GB a step at the
    ``t5large-serve`` shape, 7.8 -> 9.4 ms; PERF.md, PR 34).  Inside a
    loop one array is what keeps the slabs out of the compiler's fast-memory
    round trip (``Decoder``)."""
    decoder = dict(cache["decoder"])
    for name, slab in decoder.items():
        if name.startswith("self_"):
            decoder[name] = tuple(slab)
    return {**cache, "decoder": decoder}


from tpu_air.models.sampling import sample_token as _sample_token  # noqa: E402


def make_generate_fn(
    model: T5ForConditionalGeneration,
    max_new_tokens: int = 128,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: int = 0,
    early_stop: bool = True,
):
    """Build a jit-compiled ``(params, input_ids, attention_mask, rng) ->
    (sequences, steps_taken)`` function with a fixed decode budget.

    ``early_stop=True`` (the default, matching the reference's torch
    ``model.generate`` stopping criterion — predictor.py:102) runs the
    decode as a ``lax.while_loop`` that exits once EVERY sequence has
    emitted EOS; outputs are identical to the full-budget scan (finished
    rows emit pad either way), the remaining steps are just not executed.
    ``early_stop=False`` keeps the fixed-trip ``lax.scan`` — what the
    bench measures, so throughput numbers always reflect the full budget.
    """
    cfg: T5Config = model.config
    start_id = cfg.decoder_start_token_id
    eos_id = cfg.eos_token_id
    pad_id = cfg.pad_token_id

    @jax.jit
    def generate_fn(params, input_ids, attention_mask, rng):
        batch = input_ids.shape[0]
        enc = model.apply(
            {"params": params}, input_ids, attention_mask, method=model.encode
        )
        cache = init_cache(model, params, batch, max_new_tokens + 1, enc,
                           attention_mask)
        tok0 = jnp.full((batch,), start_id, dtype=jnp.int32)
        # an all-pad input row is vacuous (bucket padding, empty inputs):
        # born finished, it emits pure pad and never blocks early-stop
        finished0 = jnp.sum(attention_mask, axis=-1) == 0

        def decode_one(tok, cache, finished, rng):
            logits, vars_out = model.apply(
                {"params": params, "cache": cache},
                tok[:, None],
                enc,
                attention_mask,
                decode=True,
                mutable=["cache"],
                method=model.decode,
            )
            rng, sub = jax.random.split(rng)
            nxt = _sample_token(
                logits[:, -1, :], sub, do_sample, temperature, top_k
            )
            nxt = jnp.where(finished, pad_id, nxt)
            finished = finished | (nxt == eos_id)
            return nxt, vars_out["cache"], finished, rng

        if early_stop:
            toks0 = jnp.full((batch, max_new_tokens), pad_id, jnp.int32)

            def cond(carry):
                step, _, _, finished, _, _ = carry
                return (step < max_new_tokens) & ~jnp.all(finished)

            def body(carry):
                step, tok, cache, finished, rng, toks = carry
                nxt, cache, finished, rng = decode_one(tok, cache, finished, rng)
                toks = jax.lax.dynamic_update_slice(
                    toks, nxt[:, None], (0, step)
                )
                return (step + 1, nxt, cache, finished, rng, toks)

            step, _, _, _, _, toks = jax.lax.while_loop(
                cond, body, (jnp.asarray(0), tok0, cache, finished0, rng, toks0)
            )
            return toks, step

        def step(carry, _):
            tok, cache, finished, rng = carry
            nxt, cache, finished, rng = decode_one(tok, cache, finished, rng)
            return (nxt, cache, finished, rng), nxt

        (_, _, _, _), toks = jax.lax.scan(
            step, (tok0, cache, finished0, rng), None, length=max_new_tokens
        )
        return jnp.transpose(toks), jnp.asarray(max_new_tokens)

    return generate_fn


# ---------------------------------------------------------------------------
# Continuous-batching entry points (tpu_air.engine)
#
# make_generate_fn keeps the encode+cache-build prefill and the per-token
# decode private inside one jitted program.  These expose the two phases as
# standalone compiled units so an online engine can admit/retire between
# steps.  Encoder-decoder caveat: the decode cache carries the CROSS-
# attention K/V of the whole batch's encoder output, so these entry points
# are batch-synchronized (one scalar cache index — every row at the same
# decode position); per-slot cross-attn slabs are the remaining work before
# the slot engine (engine/engine.py) can drive the T5 family.
# ---------------------------------------------------------------------------


def make_t5_prefill_fn(model: T5ForConditionalGeneration,
                       max_decode_len: int):
    """Build a jitted ``fn(params, input_ids, attention_mask) ->
    (first_tok, cache, enc_hidden)``: encode the prompts, build the decode
    cache (self-attn slabs zeroed, cross-attn K/V computed from the encoder
    output — the prefill-into-segment), and run the first decode step from
    ``decoder_start_token_id``, returning the first greedy token."""
    cfg: T5Config = model.config

    @jax.jit
    def prefill(params, input_ids, attention_mask):
        batch = input_ids.shape[0]
        enc = model.apply(
            {"params": params}, input_ids, attention_mask, method=model.encode
        )
        cache = per_layer_slabs(init_cache(
            model, params, batch, max_decode_len, enc, attention_mask))
        tok0 = jnp.full((batch, 1), cfg.decoder_start_token_id, jnp.int32)
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tok0, enc, attention_mask,
            decode=True, mutable=["cache"], method=model.decode,
        )
        tok = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return tok, vars_["cache"], enc

    return prefill


def make_t5_decode_step_fn(model: T5ForConditionalGeneration):
    """Build a jitted single-token decode step ``fn(params, cache, tok,
    enc_hidden, enc_mask) -> (cache', next_tok)`` with the cache donated —
    the per-step unit an online loop re-invokes, greedy (the engine parity
    anchor)."""
    from functools import partial

    @partial(jax.jit, donate_argnums=(1,))
    def step(params, cache, tok, enc_hidden, enc_mask):
        logits, vars_ = model.apply(
            {"params": params, "cache": cache}, tok[:, None], enc_hidden,
            enc_mask, decode=True, mutable=["cache"], method=model.decode,
        )
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return vars_["cache"], nxt

    return step


_GEN_CACHE: Dict[Tuple, Any] = {}
_GEN_CACHE_MAX = 16


def generate(
    model: T5ForConditionalGeneration,
    params,
    input_ids,
    attention_mask=None,
    max_new_tokens: int = 128,
    do_sample: bool = False,
    temperature: float = 1.0,
    top_k: int = 0,
    rng: Optional[jax.Array] = None,
    early_stop: bool = True,
):
    """Convenience wrapper caching compiled generate fns per config."""
    input_ids = jnp.asarray(input_ids, dtype=jnp.int32)
    if attention_mask is None:
        attention_mask = (input_ids != model.config.pad_token_id).astype(jnp.int32)
    else:
        attention_mask = jnp.asarray(attention_mask, dtype=jnp.int32)
    # key by config content, not id(model): model objects are rebuilt per
    # Checkpoint.get_model() call and ids can be reused after GC
    cfg_key = tuple(sorted(model.config.to_dict().items()))
    key = (cfg_key, max_new_tokens, do_sample, temperature, top_k, early_stop)
    if key not in _GEN_CACHE:
        if len(_GEN_CACHE) >= _GEN_CACHE_MAX:
            _GEN_CACHE.pop(next(iter(_GEN_CACHE)))
        _GEN_CACHE[key] = make_generate_fn(
            model, max_new_tokens, do_sample, temperature, top_k, early_stop
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    # batch-size BUCKETING (SURVEY.md §7 hard-part 2): pad the batch up to
    # the next power of two with all-pad rows (born finished, emit pad, cost
    # ~0 under early_stop) so a stream of blocks with a ragged tail reuses
    # one compiled program instead of retracing per batch size.  GREEDY
    # outputs are bit-identical to the unpadded batch; SAMPLED outputs are
    # distributionally equivalent but not bitwise reproducible across
    # bucket sizes (the per-position sampling noise is keyed by the padded
    # batch shape).
    n = input_ids.shape[0]
    bucket = 1 << max(0, int(n - 1).bit_length())
    if bucket != n:
        pad_id = model.config.pad_token_id
        L = input_ids.shape[1]
        input_ids = jnp.concatenate(
            [input_ids, jnp.full((bucket - n, L), pad_id, jnp.int32)]
        )
        attention_mask = jnp.concatenate(
            [attention_mask, jnp.zeros((bucket - n, L), jnp.int32)]
        )
    seqs, _steps = _GEN_CACHE[key](params, input_ids, attention_mask, rng)
    return seqs[:n]
