"""Autoregressive generation for the causal LM family, under jit.

Same contract as the T5 generate (models/t5/generate.py): a fixed-shape
``lax.scan`` decode loop over a pre-allocated KV cache — prefill processes
the whole prompt in one cached call, then one cached call per new token.
Greedy by default; temperature/top-k via the shared sampler
(models/sampling.py).  TPU-minded details:

* the cache is RIGHT-SIZED to ``L_prompt + max_new_tokens`` (a decode-time
  config override — cache length is static per compiled shape), not to the
  model's ``max_seq_len``, so per-token attention cost is O(L_prompt + t);
* prefill computes only the LAST position's logits via ``return_hidden`` +
  ``head_weight`` — the (B, L, V) prompt logits tensor (the long-context
  memory cliff lm_chunked_loss_with_targets exists for) never materializes;
* the scan emits the token it computes (no discarded final forward).
"""

from __future__ import annotations

from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_air.models.sampling import sample_token

from .config import LMConfig
from .modeling import (CausalLM, ChunkRows, expert_assignments,
                       head_weight)
# init_paged_cache is exported from here too: the engines, the benchmark's
# hooks and the tests import it beside the make_* factories
from .paged_cache import (copy_page, init_paged_cache,  # noqa: F401
                          push_chunk, push_step)


def init_cache(model: CausalLM, batch_size: int):
    """Zero cache with the right structure, via eval_shape (free).  Cache
    length comes from ``model.config.max_seq_len`` — generate passes a
    decode model whose config is right-sized to prompt + budget."""

    def _init():
        return model.init(
            jax.random.PRNGKey(0),
            jnp.zeros((batch_size, 1), jnp.int32),
            decode=True,
        )

    shapes = jax.eval_shape(_init)["cache"]
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def make_lm_generate_fn(model: CausalLM, max_new_tokens: int,
                        do_sample: bool = False, temperature: float = 1.0,
                        top_k: int = 0, eos_token_id: Optional[int] = None,
                        early_stop: bool = True):
    """Build a jitted ``fn(params, input_ids, rng, live_mask=None) ->
    (B, max_new_tokens)``.

    ``input_ids``: (B, L_prompt) un-padded prompts (fixed shape per compile).
    After ``eos_token_id`` is emitted a row keeps emitting pad.

    ``live_mask``: optional (B,) bool — True marks a REAL row, False marks
    bucket filler the batching wrapper appended (born finished: emits pure
    pad, never holds early-stop open).  Filler is declared by the caller —
    the host side knows which rows it appended — never inferred from
    content, so an all-pad USER prompt generates normally (ADVICE r5).
    ``None`` means every row is real.

    ``early_stop=True`` (requires ``eos_token_id``; the t5/generate.py
    pattern) runs the decode as a ``lax.while_loop`` that exits once EVERY
    row has emitted EOS — outputs identical to the full-budget scan, the
    remaining steps just don't execute.  With ``eos_token_id=None`` there
    is no stopping criterion and the fixed-trip scan runs regardless."""
    cfg = model.config
    pad = cfg.pad_token_id

    def pick(logits, rng):
        return sample_token(logits, rng, do_sample, temperature, top_k)

    @jax.jit
    def generate(params, input_ids, rng, live_mask=None):
        b, lp = input_ids.shape
        total = lp + max_new_tokens
        if total > cfg.max_seq_len:
            raise ValueError(
                f"prompt {lp} + max_new_tokens {max_new_tokens} exceeds "
                f"max_seq_len {cfg.max_seq_len}"
            )
        # decode model with a right-sized cache (lp/max_new are static at
        # trace time; params are unaffected by max_seq_len)
        dmodel = CausalLM(LMConfig.from_dict(
            {**cfg.to_dict(), "max_seq_len": total}
        ))
        cache = init_cache(dmodel, b)
        positions = jnp.broadcast_to(jnp.arange(lp, dtype=jnp.int32), (b, lp))
        # prefill: hidden states only — head applied to the LAST position,
        # never to the (B, L, V) prompt logits
        hidden, vars_ = dmodel.apply(
            {"params": params, "cache": cache}, input_ids, positions,
            decode=True, return_hidden=True, mutable=["cache"],
        )
        head_w = head_weight(params, cfg).astype(jnp.float32)

        def head(h):    # outside every module: scoped (docs/OBSERVABILITY.md)
            with jax.named_scope("lm_head"):
                return h @ head_w

        rng, sub = jax.random.split(rng)
        tok = pick(head(hidden[:, -1].astype(jnp.float32)), sub)
        if eos_token_id is not None:
            # filler rows (declared by the caller's live_mask) are born
            # finished: they emit pure pad and never hold the while_loop
            # open for the full budget
            filler = (jnp.zeros((b,), bool) if live_mask is None
                      else ~live_mask)
            tok = jnp.where(filler, pad, tok)
            done = filler | (tok == eos_token_id)
        else:
            done = None

        def decode_one(cache, tok, pos, rng, done):
            hidden, vars_ = dmodel.apply(
                {"params": params, "cache": cache}, tok[:, None],
                jnp.full((b, 1), pos, jnp.int32), decode=True,
                return_hidden=True, mutable=["cache"],
            )
            rng, sub = jax.random.split(rng)
            nxt = pick(head(hidden[:, -1].astype(jnp.float32)), sub)
            if done is not None:
                nxt = jnp.where(done, pad, nxt)
                done = done | (nxt == eos_token_id)
            return vars_["cache"], nxt, pos + 1, rng, done

        if early_stop and done is not None:
            toks0 = jnp.full((b, max_new_tokens), pad, jnp.int32)
            toks0 = toks0.at[:, 0].set(tok)

            def cond(carry):
                step, _, _, _, _, done, _ = carry
                return (step < max_new_tokens) & ~jnp.all(done)

            def body(carry):
                step, cache, tok, pos, rng, done, toks = carry
                cache, nxt, pos, rng, done = decode_one(
                    cache, tok, pos, rng, done
                )
                toks = jax.lax.dynamic_update_slice(
                    toks, nxt[:, None], (0, step)
                )
                return (step + 1, cache, nxt, pos, rng, done, toks)

            (_, _, _, _, _, _, toks) = jax.lax.while_loop(
                cond, body,
                (jnp.asarray(1), vars_["cache"], tok, jnp.int32(lp), rng,
                 done, toks0),
            )
            return toks

        def step(carry, _):
            cache, tok, pos, rng, done = carry
            cache, nxt, pos, rng, done = decode_one(cache, tok, pos, rng, done)
            return (cache, nxt, pos, rng, done), nxt

        # the prefill already produced token 0; the scan computes (and
        # emits) the remaining max_new_tokens - 1 — no discarded forward
        (_, _, _, _, _), toks = jax.lax.scan(
            step, (vars_["cache"], tok, jnp.int32(lp), rng, done), None,
            length=max_new_tokens - 1,
        )
        return jnp.concatenate([tok[:, None], toks.T], axis=1)

    return generate


# ---------------------------------------------------------------------------
# Engine entry points (tpu_air.engine)
#
# make_lm_generate_fn keeps prefill and the per-token step private inside one
# jitted program — right for offline batches, useless for an engine that must
# admit/retire requests BETWEEN steps.  These expose the same two phases as
# standalone compiled units over the engine's paged cache: what each layer
# keeps there (page pools, a row of state a slot) and the host state pushed
# into it at every call is models/lm/paged_cache.py's to say, and nothing
# here names a leaf.  Prefill is page-sized CHUNKS: one compiled program for
# every prompt length.  A model without recurrent layers has no per-slot
# state leaves, and its programs are what they were.
# ---------------------------------------------------------------------------


def _apply_paged(model: CausalLM, slot_len: int):
    """``fn(params, cache, ids, positions, chunk=None) -> (cache', hidden,
    rows)``: the model applied once over a paged cache, the way every engine
    body does.  ``rows`` is ``[layers, tokens, E]`` expert assignments for a
    sparse-expert model (``modeling.expert_assignments``), else None."""
    cfg = model.config
    dmodel = CausalLM(LMConfig.from_dict(
        {**cfg.to_dict(), "max_seq_len": slot_len}))
    mutable = ["cache", "intermediates"] if cfg.num_experts else ["cache"]

    def apply(params, cache, ids, positions, chunk=None):
        hidden, vars_ = dmodel.apply(
            {"params": params, "cache": cache}, ids, positions,
            decode=True, return_hidden=True, mutable=mutable, chunk=chunk,
        )
        rows = (expert_assignments(vars_["intermediates"])
                if cfg.num_experts else None)
        return vars_["cache"], hidden, rows

    return apply


def _with_routing(nxt, rows, live, held):
    """``nxt`` with a sparse-expert step's routing counters behind it
    (:func:`make_paged_decode_body`): assignments to each of the ``held``
    experts over the rows ``live [tokens]`` marks (and, where only a share of
    the experts is held, those rows' assignments sent elsewhere: ``rows`` has
    that column), then the held experts the step streamed."""
    if rows is None:
        return nxt
    return jnp.concatenate([
        nxt, (rows * live.astype(jnp.int32)[None, :, None]).sum((0, 1)),
        (rows[..., :held].sum(1) > 0).sum(dtype=jnp.int32)[None]])


def _lora_head_delta(h, bank_a, bank_b, ids):
    """Each row's LoRA head delta ``(h @ bank_a[id]) @ bank_b[id]``: ``h
    [rows, d]``, ``ids [rows]`` into the banks, gathered the way the block
    table gathers pages (row 0 of a bank is the exact-zero adapter)."""
    a = bank_a[ids]                                  # [rows, d, r]
    b = bank_b[ids]                                  # [rows, r, V]
    return jnp.einsum("sr,srv->sv", jnp.einsum("sd,sdr->sr", h, a), b)


def make_paged_decode_logits_body(model: CausalLM, slot_len: int):
    """``fn(params, cache, tok, pos, block_table) -> (cache', h, logits,
    rows)``: one paged decode step up to the head — ``h [S, D]`` float32
    last hidden states, ``logits [S, V]`` float32, ``rows`` as
    :func:`_apply_paged` gives them.  :func:`make_paged_decode_body` samples
    from it; a checker that wants the step's logits (the benchmark's
    comparison with the reference) calls this, so both run one program
    text."""
    cfg = model.config
    apply = _apply_paged(model, slot_len)

    def logits_step(params, cache, tok, pos, block_table):
        pos = pos.astype(jnp.int32)
        cache = push_step(cache, pos, block_table)
        cache, hidden, rows = apply(params, cache, tok[:, None], pos[:, None])
        h = hidden[:, -1].astype(jnp.float32)
        with jax.named_scope("lm_head"):
            logits = h @ head_weight(params, cfg).astype(jnp.float32)
        return cache, h, logits, rows

    return logits_step


def make_paged_decode_body(model: CausalLM, slot_len: int,
                           adapters: bool = False):
    """The UNJITTED paged decode step body: ``fn(params, cache, tok, pos,
    block_table) -> (cache', next_tok)``.  Both the single-chip factory
    below and the sharded factory (engine/dist/sharded.py, which adds
    pjit in/out shardings over a ``(data, model)`` mesh) wrap this same
    body — parity between the two engines is parity of jit options, not
    of two step implementations.

    With ``adapters=True`` the signature grows three trailing args —
    ``bank_a [A+1, d, r]``, ``bank_b [A+1, r, V]``, ``adapter_ids [S]``
    — and each slot's head logits get a per-slot LoRA delta
    ``(h @ bank_a[id]) @ bank_b[id]`` gathered exactly the way the block
    table gathers pages: one dynamic-gather per step, no per-tenant
    retrace.  Bank row 0 is the zero adapter, so slots with id 0 compute
    an exact-zero delta and stay bit-identical to the base model.

    For a sparse-expert model (``config.num_experts``) ``next_tok`` is
    ``[S + E + 1]``, ``E`` the experts the tree holds (``[S + E + 2]`` where
    that is a share of those routed over: the decoding rows' assignments to
    experts held elsewhere follow the ``E``): the ``S`` tokens; then the
    step's assignments to each
    of the ``E`` experts summed over layers and over the DECODING rows
    (``pos > 0``: the engine keeps a free or prefilling row at position 0,
    and a decoding row is past its prompt); then the number of experts the
    step streamed, summed over layers (an expert any row was routed to, idle
    rows too: they are computed like the rest).  One array, so the routing
    counters reach the host in the read-back that fetches the tokens."""
    logits_step = make_paged_decode_logits_body(model, slot_len)

    def step(params, cache, tok, pos, block_table,
             bank_a=None, bank_b=None, adapter_ids=None):
        cache, h, logits, rows = logits_step(params, cache, tok, pos,
                                             block_table)
        if adapters:
            logits = logits + _lora_head_delta(h, bank_a, bank_b, adapter_ids)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return cache, _with_routing(nxt, rows, pos > 0,
                                    model.config.experts_held)

    if not adapters:
        def base_step(params, cache, tok, pos, block_table):
            return step(params, cache, tok, pos, block_table)
        return base_step
    return step


def make_lm_paged_decode_step_fn(model: CausalLM, slot_len: int,
                                 adapters: bool = False):
    """The persistent paged engine step: jitted ``fn(params, cache, tok,
    pos, block_table) -> (cache', next_tok)``, cache donated so the pools
    update in place across the engine's lifetime.  ``tok``/``pos``: (S,)
    current token and cache position per slot.  Every slot steps every call
    (fixed shape — the continuous-batching discipline); free slots ride
    along at pos 0 and their outputs are discarded host-side.  Greedy by
    construction: the engine's correctness anchor is token-equality with
    offline greedy ``generate``.  ``block_table`` ``[S, pages_per_slot]``
    int32 is the host pool's authoritative table — rows of non-decoding
    slots pointed at the null page so their ride-along scatter can't touch
    a live or prefix-shared page.  ``adapters=True``
    appends the LoRA bank args (see :func:`make_paged_decode_body`); the
    banks are NOT donated — they persist across steps like params."""
    body = make_paged_decode_body(model, slot_len, adapters)
    # the program's name in a device trace ("XLA Modules": jit_<name>)
    body.__name__ = "lm_paged_decode_step"
    return jax.jit(body, donate_argnums=(1,))


def advance_rows_body(tok, pos, out):
    """``fn(tok, pos, out) -> (tok', pos')``: the engine's device-resident
    step inputs moved on by the step that just ran on them.  ``tok``/``pos``
    ``[S]`` int32 are what that step took, ``out`` what it returned (its
    first ``S`` entries are the tokens; a sparse-expert model's routing
    counters ride behind).  A decoding row (``pos > 0``) takes its new token
    and the next position; a row that rode along at position 0 stays at
    token 0, position 0."""
    live = pos > 0
    return (jnp.where(live, out[:tok.shape[0]], tok),
            pos + live.astype(pos.dtype))


def set_row_body(tok, pos, row, t, p):
    """``fn(tok, pos, row, t, p) -> (tok', pos')``: one row of the step
    inputs set — a row joining the step (``t`` its first token, as the
    chunk program left it on the device, ``p`` its prompt length) or
    leaving it (0, 0)."""
    return tok.at[row].set(t), pos.at[row].set(p)


def make_lm_step_feed_fns():
    """The two tiny programs that keep the paged step's ``tok`` and ``pos``
    on the device between steps: jitted :func:`advance_rows_body` (once a
    step) and :func:`set_row_body` (once a row that joins or leaves)."""
    return jax.jit(advance_rows_body), jax.jit(set_row_body)


def make_prefill_chunk_logits_body(model: CausalLM, page_len: int,
                                   slot_len: int):
    """``fn(params, cache, ids, p0, last_local, table_row, slot=None) ->
    (cache', h_last, logits)``: one prefill chunk up to the head — ``h_last
    [D]`` the float32 hidden state at ``last_local``, ``logits [V]`` float32
    there.  :func:`make_prefill_chunk_body` samples from it (see
    :func:`make_paged_decode_logits_body`).  ``slot``: the row of the
    per-slot state the chunk works for; a model with recurrent layers needs
    it, any other ignores it."""
    cfg = model.config
    apply = _apply_paged(model, slot_len)

    def logits_chunk(params, cache, ids, p0, last_local, table_row,
                     slot=None):
        p0 = p0.astype(jnp.int32)
        cache = push_chunk(cache, p0, last_local, table_row, slot)
        positions = (p0 + jnp.arange(page_len, dtype=jnp.int32))[None]
        cache, hidden, _ = apply(params, cache, ids, positions)
        h_last = hidden[0, last_local.astype(jnp.int32)].astype(jnp.float32)
        with jax.named_scope("lm_head"):
            logits = h_last @ head_weight(params, cfg).astype(jnp.float32)
        return cache, h_last, logits

    return logits_chunk


def make_prefill_chunk_body(model: CausalLM, page_len: int, slot_len: int,
                            adapters: bool = False):
    """The UNJITTED chunked-prefill body: ``fn(params, cache, ids, p0,
    last_local, table_row) -> (cache', tok)`` — shared by the single-chip
    jit wrapper below and the sharded pjit wrapper (engine/dist/sharded.py,
    where ids/p0/last_local/table_row replicate: a chunk is b=1 work, only
    its page writes land in a data shard).

    With ``adapters=True`` three trailing args appear — ``bank_a``,
    ``bank_b`` and a SCALAR ``adapter_id`` (a chunk is one slot's work) —
    and the final chunk's first greedy token gets the same LoRA head
    delta as the decode body, so a tenant's stream is adapter-consistent
    from token 0."""
    logits_chunk = make_prefill_chunk_logits_body(model, page_len, slot_len)

    def prefill_chunk(params, cache, ids, p0, last_local, table_row,
                      bank_a=None, bank_b=None, adapter_id=None, slot=None):
        cache, h_last, logits = logits_chunk(params, cache, ids, p0,
                                             last_local, table_row, slot)
        if adapters:
            logits = logits + (h_last @ bank_a[adapter_id]) @ bank_b[adapter_id]
        tok = jnp.argmax(logits).astype(jnp.int32)
        return cache, tok

    if not adapters:
        def base_chunk(params, cache, ids, p0, last_local, table_row,
                       slot=None):
            return prefill_chunk(params, cache, ids, p0, last_local,
                                 table_row, slot=slot)
        return base_chunk
    return prefill_chunk


def make_lm_prefill_chunk_fn(model: CausalLM, page_len: int, slot_len: int,
                             adapters: bool = False):
    """Build THE chunked-prefill unit: a jitted ``fn(params, cache, ids,
    p0, last_local, table_row) -> (cache', tok)``, cache donated.

    One call processes ONE page-sized chunk of ONE slot's prompt:

    * ``ids`` ``[1, page_len]`` — the chunk's tokens, right-padded on the
      final (partial) chunk.  Pad positions write don't-care K/V into the
      page tail; the per-slot validity mask hides them until decode
      appends overwrite them.
    * ``p0`` — the chunk's first global position (page-aligned).
    * ``last_local`` — index of the prompt's last real token WITHIN this
      chunk, valid only on the final chunk; the returned greedy first
      token is read there (intermediate chunks' tok is discarded).
    * ``table_row`` ``[pages_per_slot]`` — the slot's block-table row (the
      pool may substitute the null page for a fully-prefix-covered
      prompt's re-run tail chunk: PagedKVPool.chunk_row).

    Fixed shapes -> ONE compiled program covers every prompt length; the
    engine interleaves these calls between decode steps so long prompts
    stream in without stalling in-flight decodes."""
    body = make_prefill_chunk_body(model, page_len, slot_len, adapters)
    body.__name__ = "lm_prefill_chunk"
    return jax.jit(body, donate_argnums=(1,))


def make_paged_mixed_logits_body(model: CausalLM, page_len: int,
                                 slot_len: int):
    """``fn(params, cache, tok, pos, block_table, ids, p0, last_local,
    table_row, slot=None) -> (cache', h, logits, rows)``: a paged decode
    step (:func:`make_paged_decode_logits_body`'s first five arguments) and
    one prefill chunk (:func:`make_prefill_chunk_logits_body`'s others) in
    ONE pass over the model.  ``h [S + 1, D]`` / ``logits [S + 1, V]``
    float32: the ``S`` decoding rows, then the chunk's position
    ``last_local``; ``rows [layers, S + page_len, E]``, the step's rows
    first.

    The model sees ``S + page_len`` rows of one token each, so every weight
    matrix (projections, feed-forward, the grouped expert product, the head)
    is applied once to all of them; the sequence mixers alone take the step's
    rows and the chunk's apart (``modeling.ChunkRows``), each as its own
    program does.  The chunk's slot is not a decoding row: the engine keeps
    it at position 0 with the null table row until its prompt is in, so the
    step's half scatters its K/V to the null page and holds its state, and
    the chunk's half writes its page and its state row after it."""
    cfg = model.config
    apply = _apply_paged(model, slot_len)

    def logits_mixed(params, cache, tok, pos, block_table, ids, p0,
                     last_local, table_row, slot=None):
        if cfg.keeps_slot_rows and slot is None:
            raise ValueError(
                "a model with recurrent or window layers keeps rows a slot: "
                "the chunk needs slot=")
        s = tok.shape[0]
        pos, p0 = pos.astype(jnp.int32), p0.astype(jnp.int32)
        last = last_local.astype(jnp.int32)
        cache = push_step(cache, pos, block_table)
        chunk = ChunkRows(
            start=p0, valid=last + 1, table_row=table_row.astype(jnp.int32),
            slot=jnp.asarray(0 if slot is None else slot).astype(jnp.int32))
        positions = jnp.concatenate(
            [pos, p0 + jnp.arange(page_len, dtype=jnp.int32)])
        cache, hidden, rows = apply(
            params, cache, jnp.concatenate([tok, ids[0]])[:, None],
            positions[:, None], chunk)
        h = jnp.concatenate([hidden[:s, 0], hidden[s + last]]).astype(
            jnp.float32)
        with jax.named_scope("lm_head"):
            logits = h @ head_weight(params, cfg).astype(jnp.float32)
        return cache, h, logits, rows

    return logits_mixed


def make_paged_mixed_body(model: CausalLM, page_len: int, slot_len: int,
                          adapters: bool = False):
    """The UNJITTED mixed step: ``fn(params, cache, tok, pos, block_table,
    ids, p0, last_local, table_row, slot=None) -> (cache', next_tok,
    chunk_tok)``: ``next_tok`` as :func:`make_paged_decode_body` returns it
    (a sparse-expert model's routing counters behind the tokens: the load
    over the DECODING rows as there, the experts streamed over all the
    pass's rows) and ``chunk_tok`` the greedy token at ``last_local`` as
    :func:`make_prefill_chunk_body` returns it.

    With ``adapters=True`` the two programs' LoRA arguments follow
    ``table_row`` (``bank_a``, ``bank_b``, ``adapter_ids [S]``, then the
    chunk's scalar ``adapter_id``), and the head delta is gathered for the
    ``S + 1`` rows the way the decode body gathers it for ``S``."""
    logits_mixed = make_paged_mixed_logits_body(model, page_len, slot_len)

    def mixed(params, cache, tok, pos, block_table, ids, p0, last_local,
              table_row, bank_a=None, bank_b=None, adapter_ids=None,
              adapter_id=None, slot=None):
        cache, h, logits, rows = logits_mixed(
            params, cache, tok, pos, block_table, ids, p0, last_local,
            table_row, slot)
        if adapters:
            logits = logits + _lora_head_delta(
                h, bank_a, bank_b,
                jnp.concatenate([adapter_ids, adapter_id[None]]))
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        s = tok.shape[0]
        live = jnp.concatenate([pos > 0, jnp.zeros((page_len,), bool)])
        return cache, _with_routing(nxt[:s], rows, live,
                                    model.config.experts_held), nxt[s]

    if not adapters:
        def base_mixed(params, cache, tok, pos, block_table, ids, p0,
                       last_local, table_row, slot=None):
            return mixed(params, cache, tok, pos, block_table, ids, p0,
                         last_local, table_row, slot=slot)
        return base_mixed
    return mixed


def make_lm_paged_mixed_step_fn(model: CausalLM, page_len: int,
                                slot_len: int, adapters: bool = False):
    """The engine's third unit, jitted, cache donated: one decode step and
    one prefill chunk in one program (:func:`make_paged_mixed_body`), for
    the iteration that holds both.  Arguments and shapes are the two
    programs' own."""
    body = make_paged_mixed_body(model, page_len, slot_len, adapters)
    body.__name__ = "lm_paged_mixed_step"
    return jax.jit(body, donate_argnums=(1,))


def make_page_copy_fn():
    """Build the copy-on-write primitive: a jitted ``fn(cache, dst, src) ->
    cache'`` (cache donated) copying page ``src`` onto page ``dst`` in every
    page pool.  Run once when a slot's first decode append would
    land in a prefix-shared tail page (PagedKVPool.resolve_cow)."""
    return jax.jit(copy_page, donate_argnums=(0,))


_GEN_CACHE: Dict[Tuple, Any] = {}
_GEN_CACHE_MAX = 16


def generate(model: CausalLM, params, input_ids, max_new_tokens: int = 64,
             do_sample: bool = False, temperature: float = 1.0, top_k: int = 0,
             eos_token_id: Optional[int] = None, rng=None,
             early_stop: bool = True):
    """Convenience wrapper caching compiled generate fns per config (the
    t5/generate.py pattern — repeated same-shape calls never retrace)."""
    cfg_key = tuple(sorted(model.config.to_dict().items()))
    key = (cfg_key, max_new_tokens, do_sample, temperature, top_k,
           eos_token_id, early_stop)
    if key not in _GEN_CACHE:
        if len(_GEN_CACHE) >= _GEN_CACHE_MAX:
            _GEN_CACHE.pop(next(iter(_GEN_CACHE)))
        _GEN_CACHE[key] = make_lm_generate_fn(
            model, max_new_tokens, do_sample, temperature, top_k, eos_token_id,
            early_stop,
        )
    if rng is None:
        rng = jax.random.PRNGKey(0)
    ids = jnp.asarray(input_ids, jnp.int32)
    # batch-size bucketing (t5/generate.py pattern): a ragged tail batch
    # reuses the compiled program; the filler rows' outputs are discarded.
    # Same semantics caveat as the T5 path: GREEDY outputs are bit-identical
    # to the unpadded batch; SAMPLED outputs are distributionally equivalent
    # but not bitwise reproducible across bucket sizes (sampling noise is
    # keyed by the padded batch shape).  With ``eos_token_id`` set, filler
    # rows are born finished and cost ~0 under early_stop; with no EOS the
    # fixed-trip scan runs filler rows for the full decode budget — the
    # bucketing win is then compile-cache reuse only.
    n = ids.shape[0]
    bucket = 1 << max(0, int(n - 1).bit_length())
    live_mask = None
    if bucket != n:
        ids = jnp.concatenate(
            [ids, jnp.full((bucket - n, ids.shape[1]),
                           model.config.pad_token_id, jnp.int32)]
        )
        # declare the appended rows as filler EXPLICITLY (this wrapper knows
        # which rows it added) instead of inferring filler from all-pad
        # content — a legitimate all-pad user prompt stays live (ADVICE r5)
        live_mask = jnp.concatenate(
            [jnp.ones((n,), bool), jnp.zeros((bucket - n,), bool)]
        )
    return _GEN_CACHE[key](params, ids, rng, live_mask)[:n]
