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

    from flax.core import unfreeze

    return _graft_cross(unfreeze(cache), unfreeze(vars1["cache"]),
                        lambda dst, src: src)


def _graft_cross(dst, src, leaf, under_cross=False):
    """The cache tree ``dst`` with ``leaf(dst[k], src[k])`` in place of
    every array that ``src`` holds under a ``cross_attn`` module."""
    out = dict(dst)
    for k, v in src.items():
        if isinstance(v, dict):
            out[k] = _graft_cross(dst[k], v, leaf,
                                  under_cross or k == "cross_attn")
        elif under_cross:
            out[k] = leaf(dst[k], v)
    return out


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
# Continuous-batching entry points (tpu_air.engine.T5Engine)
#
# make_generate_fn keeps encode, cache build and the per-token decode private
# inside one jitted program, over one batch that starts and ends together.
# An online engine's rows come and go, so it keeps ONE decode state for its
# life, a SLOT a row, and runs two kinds of program over it, each with the
# state donated: an ADMIT program that encodes a few prompts and writes each
# into its slot, and a STEP program that decodes one token for the first
# ``rows`` slots.  What lets rows of different ages share the decoder's one
# scalar position is in ``modeling.Decoder`` (``ring_born``).
# ---------------------------------------------------------------------------


def init_slot_state(model: T5ForConditionalGeneration, params, slots: int,
                    ring_len: int, input_len: int):
    """``(state, tok)`` for ``slots`` rows, zeroed.  ``state`` is what the
    programs below take donated: ``cache`` as ``init_cache`` shapes it for a
    batch of ``slots`` (self slabs a tuple a layer, ``[ring_len, slots,
    h*d]``: a ring, so a row may live ``ring_len - 1`` steps; cross slabs
    ``[slots, h, d, Lp]``; ``decoder_pos`` the ring's position), ``enc_mask``
    int32 ``[slots, input_len]`` and ``born`` int32 ``[slots]`` (the ring
    position a slot's row was admitted at).  ``tok`` int32 ``[slots]`` is the
    token each slot's row feeds its next step.  It goes through the programs
    beside the state and is never donated: a step's ``tok`` is what the
    caller reads its tokens from, after the state has gone on to the next
    program.  (As a second result cut from a donated ``tok``, the v5e's
    compiler holds 47 of FLAN-T5-large's 48 self slabs in fast memory through
    a 64-row step and writes each back whole; tests/test_chip_compile.py.)"""
    cfg: T5Config = model.config
    enc = jax.ShapeDtypeStruct((slots, input_len, cfg.d_model),
                               jnp.dtype(cfg.dtype))
    mask = jax.ShapeDtypeStruct((slots, input_len), jnp.int32)
    shapes = jax.eval_shape(
        lambda p, e, m: per_layer_slabs(
            init_cache(model, p, slots, ring_len, e, m)), params, enc, mask)

    def zeros(s):
        return jnp.zeros(s.shape, s.dtype)

    row = jax.ShapeDtypeStruct((slots,), jnp.int32)
    return {"cache": jax.tree_util.tree_map(zeros, shapes),
            "enc_mask": zeros(mask), "born": zeros(row)}, zeros(row)


def make_t5_admit_fn(model: T5ForConditionalGeneration, length: int):
    """Build a jitted ``fn(params, state, tok, prompt) -> (state, tok)``
    with the state donated: encode ONE prompt padded to ``length`` and write
    it into its slot in place: its cross-attention K/V (and their int8
    scales) into the slot's row of every cross slab, its key mask, ``born``
    = the ring's position and ``tok`` = ``decoder_start_token_id``, so that
    the next step over the slot decodes the row's first token.  ``prompt``
    is ONE int32 ``[1, length + 2]`` upload: the prompt's ids, then its
    length, then its slot.  What a slot's longer earlier prompt left past
    ``length`` lanes stays there, behind the new mask's zeros."""
    cfg: T5Config = model.config

    def into_slot(dst, src, slot):
        return jax.lax.dynamic_update_slice(
            dst, src, (slot[0],) + (0,) * (src.ndim - 1))

    @partial(jax.jit, donate_argnums=(1,))
    def admit(params, state, tok, prompt):
        ids, n, slot = (prompt[:, :length], prompt[:, length],
                        prompt[:, length + 1])
        mask = (jnp.arange(length)[None, :] < n[:, None]).astype(jnp.int32)
        enc = model.apply({"params": params}, ids, mask, method=model.encode)
        _, made = model.apply(
            {"params": params}, jnp.zeros((1, 1), jnp.int32), enc, mask,
            mutable=["cache"], method=model.init_decode_cache)
        cache = _graft_cross(state["cache"], made["cache"],
                             lambda dst, src: into_slot(dst, src, slot))
        spare = state["enc_mask"].shape[1] - length
        return {
            "cache": cache,
            "enc_mask": into_slot(state["enc_mask"],
                                  jnp.pad(mask, ((0, 0), (0, spare))), slot),
            "born": state["born"].at[slot].set(
                cache["decoder"]["decoder_pos"]),
        }, tok.at[slot].set(cfg.decoder_start_token_id)

    return admit


def make_t5_slot_step_fn(model: T5ForConditionalGeneration, rows: int):
    """Build a jitted greedy single-token step ``fn(params, state, tok) ->
    (state, tok)`` over the first ``rows`` slots, the state donated: each
    feeds its ``tok``, attends to the ring positions written since its
    ``born`` and to its own cross row, the step's K/V are appended at the
    ring's position for those rows, the position moves on one (for every
    slot: the others' rows are not touched and their ``born`` still tells
    their age), and the new tokens take their slots' places in ``tok``."""

    @partial(jax.jit, donate_argnums=(1,))
    def step(params, state, tok):
        mask = state["enc_mask"]
        # the cached cross-attention asks the encoder output its length alone
        enc = jnp.zeros((rows, mask.shape[1], 1), jnp.dtype(model.config.dtype))
        logits, vars_ = model.apply(
            {"params": params, "cache": state["cache"]},
            tok[:rows, None], enc, mask[:rows], decode=True,
            ring_born=state["born"][:rows], mutable=["cache"],
            method=model.decode,
        )
        nxt = jnp.argmax(logits[:, -1, :], axis=-1).astype(jnp.int32)
        return ({**state, "cache": vars_["cache"]},
                jax.lax.dynamic_update_slice(tok, nxt, (0,)))

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
