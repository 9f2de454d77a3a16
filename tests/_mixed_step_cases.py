"""The engine's mixed step (one program for an iteration's prefill chunk and
its decode step), as cases over any ``CausalLM``: ``tests/test_engine.py``
(dense), ``tests/test_olmoe.py`` (sparse experts) and ``tests/test_jamba.py``
(hybrid, per-slot state) each run every case on their own tiny model.

A case is ``fn(model, params, check)``; ``check(prompt, tokens)`` asserts
that ``tokens`` are what the file's own reference streams for ``prompt``."""

import collections

import jax
import jax.numpy as jnp
import numpy as np

from tpu_air.engine import EngineConfig, InferenceEngine
from tpu_air.models.lm.generate import (
    init_paged_cache, make_paged_decode_logits_body,
    make_paged_mixed_logits_body, make_prefill_chunk_logits_body)

S, C, SLOT_LEN = 4, 8, 64


def _engine(model, params, **kw):
    cfg = dict(num_slots=S, slot_len=SLOT_LEN, page_len=C, max_new_tokens=8,
               eos_token_id=None, prefix_cache=False)
    cfg.update(kw)
    eng = InferenceEngine(model, params, EngineConfig(**cfg),
                          auto_start=False)
    # which program each call goes to from here on (the build has run the
    # mixed step once, to compile it)
    eng.calls = collections.Counter()
    for attr in ("_chunk_fn", "_decode_step", "_mixed_step"):
        def counted(*a, _fn=getattr(eng, attr), _name=attr, **k):
            eng.calls[_name] += 1
            return _fn(*a, **k)
        setattr(eng, attr, counted)
    return eng


def _prompts(model, seed, lengths):
    rng = np.random.default_rng(seed)
    return [rng.integers(2, model.config.vocab_size, n).tolist()
            for n in lengths]


def _drain(eng):
    while not eng.idle():
        eng.step()


# -- the body against the two bodies ------------------------------------------

def _two_programs_and_one(model, params):
    """A pool of four slots: rows 0 and 1 past their prompts (11 and 5
    tokens), row 2 with the first chunk of a 13-token prompt in, row 3 free.
    Returns what the chunk program then the decode step leave (cache, chunk
    logits, step logits), what the mixed step leaves from the same cache
    (cache, logits), and the cache both started from."""
    cfg = model.config
    npg = SLOT_LEN // C
    cache = init_paged_cache(model, S, S * npg + 1, C, npg)
    chunk = jax.jit(make_prefill_chunk_logits_body(model, C, SLOT_LEN))
    step = jax.jit(make_paged_decode_logits_body(model, SLOT_LEN))
    mixed = jax.jit(make_paged_mixed_logits_body(model, C, SLOT_LEN))
    table = 1 + np.arange(S * npg, dtype=np.int32).reshape(S, npg)
    kw = (lambda s: {"slot": jnp.int32(s)}) if cfg.keeps_slot_rows \
        else (lambda s: {})

    def chunk_args(s, toks, p0):
        ids = np.full((1, C), cfg.pad_token_id, np.int32)
        ids[0, :len(toks)] = toks
        return (jnp.asarray(ids), jnp.int32(p0), jnp.int32(len(toks) - 1),
                jnp.asarray(table[s]))

    prompts = dict(enumerate(_prompts(model, 0, (11, 5, 13))))
    for s, p in prompts.items():
        for p0 in range(0, C if s == 2 else len(p), C):
            cache, _, _ = chunk(params, cache, *chunk_args(s, p[p0:p0 + C], p0),
                                **kw(s))
    tok = jnp.asarray([7, 9, 0, 0], jnp.int32)
    pos = jnp.asarray([11, 5, 0, 0], jnp.int32)
    masked = table.copy()
    masked[2:] = 0                      # rows 2 and 3 ride at the null page
    masked = jnp.asarray(masked)
    rest = chunk_args(2, prompts[2][C:], C)
    two, _, chunk_logits = chunk(params, cache, *rest, **kw(2))
    two, _, step_logits, _ = step(params, two, tok, pos, masked)
    one, _, logits, rows = mixed(params, cache, tok, pos, masked, *rest,
                                 **kw(2))
    return (two, chunk_logits, step_logits), (one, logits, rows), cache


def _per_sequence_leaves(cache):
    """(path, leaf) of the K/V pools without the null page, and of the
    per-slot rows (recurrent state, a window layer's ring)."""
    for path, leaf in jax.tree_util.tree_flatten_with_path(cache)[0]:
        key = path[-1].key
        if key in ("cached_key", "cached_value"):
            yield jax.tree_util.keystr(path), np.asarray(leaf[1:])
        elif key in ("conv_state", "ssm_state", "window_key",
                     "window_value"):
            yield jax.tree_util.keystr(path), np.asarray(leaf)


def the_mixed_body_computes_what_the_two_bodies_compute(model, params, check):
    """Decode logits of the live rows, the chunk's logits at its last real
    position, every page but the null one and every row of state: the mixed
    body's are those of the chunk body then the decode body on the same
    cache (float32: to rounding in the products' order)."""
    (two, chunk_logits, step_logits), (one, logits, rows), _ = \
        _two_programs_and_one(model, params)
    assert logits.shape == (S + 1, model.config.vocab_size)
    np.testing.assert_allclose(logits[:2], step_logits[:2], atol=2e-5)
    np.testing.assert_allclose(logits[S], chunk_logits, atol=2e-5)
    for (name, a), (_, b) in zip(_per_sequence_leaves(two),
                                 _per_sequence_leaves(one)):
        np.testing.assert_allclose(a, b, atol=2e-6, err_msg=name)
    if model.config.num_experts:
        # the experts the tree holds; one more column (assignments sent
        # elsewhere) where that is a share of those routed over
        cfg = model.config
        assert rows.shape[1:] == (
            S + C, cfg.experts_held + (not cfg.holds_all_experts))


def the_chunks_slot_keeps_the_chunks_state_and_pages(model, params, check):
    """Row 2 rides the decode half held, at the null page, while the chunk
    half works for it: what is in its second page and in its state row after
    the mixed step is what the chunk wrote (not the held copy, not the
    step's scatter), and the rows that decoded and the free row are as the
    decode step alone leaves them."""
    (two, _, _), (one, _, _), before = _two_programs_and_one(model, params)
    npg = SLOT_LEN // C
    own = 2 * npg + 1                   # row 2's second page, less the null
    moved = False
    for (name, a), (_, b), (_, was) in zip(
            _per_sequence_leaves(two), _per_sequence_leaves(one),
            _per_sequence_leaves(before)):
        if "cached" in name:
            assert np.abs(b[own]).max() > 0 and np.abs(was[own]).max() == 0
            np.testing.assert_allclose(a[own], b[own], atol=2e-6)
        else:
            moved = True
            assert np.abs(b[2] - was[2]).max() > 0      # not the held copy
            np.testing.assert_allclose(a[2], b[2], atol=2e-6)
            np.testing.assert_array_equal(b[3], was[3])  # the free row, held
    assert moved == bool(model.config.keeps_slot_rows)


# -- the engine ---------------------------------------------------------------

def the_engine_streams_the_references_tokens_through_mixed_steps(
        model, params, check):
    """Prompts that cross chunk boundaries and end in padded chunks, one
    that fills its last chunk and one shorter than a chunk, arriving while
    others decode: most chunks ride a decode step, and every stream is the
    reference's token for token."""
    lengths = (5, 19, 9, 27, 16, 12, 21)
    prompts = _prompts(model, 11, lengths)
    eng = _engine(model, params)
    streams = []
    for i, p in enumerate(prompts):
        streams.append(eng.submit(p, 8))
        for _ in range(0 if i < 1 else 2):
            eng.step()
    _drain(eng)
    snap = eng.metrics.snapshot()
    eng.close()
    for p, s in zip(prompts, streams):
        check(p, s.result(5))
    chunks = sum(-(-n // C) for n in lengths)
    assert snap["chunks_fused"] + snap["chunks_alone"] == chunks \
        == snap["prefill_chunks"]
    assert snap["chunks_fused"] == snap["mixed_steps"] \
        == eng.calls["_mixed_step"]
    assert snap["chunks_alone"] == eng.calls["_chunk_fn"]
    assert snap["chunks_fused"] > snap["chunks_alone"]
    assert snap["steps_issued"] == (eng.calls["_decode_step"]
                                    + snap["mixed_steps"])


def a_reused_slots_first_fused_chunk_starts_from_zeros(model, params, check):
    """Two slots: one streams on while the other is used, freed and given to
    a third request, whose every chunk rides a step of the first: it streams
    what it streams alone (its pages overwritten as it goes, its state, where
    the model keeps one, from zeros)."""
    long_, short, third = _prompts(model, 12, (7, 11, 13))
    eng = _engine(model, params, num_slots=2, max_new_tokens=24)
    a = eng.submit(long_, 24)
    b = eng.submit(short, 2)
    while not b.done:
        eng.step()
    fused = eng.metrics.snapshot()["chunks_fused"]
    c = eng.submit(third, 6)
    _drain(eng)
    snap = eng.metrics.snapshot()
    eng.close()
    assert snap["chunks_fused"] - fused == 2          # both of the third's
    check(third, c.result(5))
    check(long_, a.result(5))
    if model.config.has_recurrent_layers:
        assert snap["ssm_state_resets"] == 3


def _deltas(eng, keys=("chunks_fused", "chunks_alone", "mixed_steps",
                       "steps_issued")):
    before = eng.metrics.snapshot()
    eng.step()
    after = eng.metrics.snapshot()
    return tuple(after[k] - before[k] for k in keys)


def a_chunk_with_no_row_to_decode_runs_alone(model, params, check):
    """A cold start: the lone prompt's chunks go to the chunk program, its
    decode steps to the decode program, and the mixed program is not used."""
    prompt = _prompts(model, 13, (19,))[0]
    eng = _engine(model, params)
    s = eng.submit(prompt, 5)
    _drain(eng)
    snap = eng.metrics.snapshot()
    eng.close()
    check(prompt, s.result(5))
    assert (snap["chunks_fused"], snap["chunks_alone"],
            snap["mixed_steps"]) == (0, 3, 0)
    assert eng.calls["_chunk_fn"] == 3 and eng.calls["_mixed_step"] == 0
    assert eng.calls["_decode_step"] == snap["steps_issued"] == 4


def rows_with_no_chunk_take_the_decode_step(model, params, check):
    """Once every prompt is in, an iteration is the decode program alone."""
    prompts = _prompts(model, 14, (6, 4))
    eng = _engine(model, params)
    streams = [eng.submit(p, 6) for p in prompts]
    while any(s.prefilling for s in eng.slots.active_slots()) \
            or eng.scheduler.depth():
        eng.step()
    mixed = eng.calls["_mixed_step"]
    assert _deltas(eng) == (0, 0, 0, 1)
    _drain(eng)
    eng.close()
    assert eng.calls["_mixed_step"] == mixed
    for p, s in zip(prompts, streams):
        check(p, s.result(5))


def of_two_chunks_an_iteration_the_first_rides_and_the_other_runs_alone(
        model, params, check):
    """``prefill_chunks_per_step`` 2 with a row decoding: of two prompts
    waiting, the one the quantum picks first rides the step and the other's
    chunk runs alone in the same iteration; a lone prompt of three chunks
    rides one chunk an iteration (its next chunk waits for the one that
    rides)."""
    first, two, three, long_ = _prompts(model, 15, (6, 5, 7, 21))
    eng = _engine(model, params, prefill_chunks_per_step=2,
                  max_new_tokens=24)
    streams = [eng.submit(first, 24)]
    eng.step()
    eng.step()
    streams += [eng.submit(two, 4), eng.submit(three, 4)]
    assert _deltas(eng) == (1, 1, 1, 1)
    streams.append(eng.submit(long_, 4))
    for _ in range(3):
        assert _deltas(eng) == (1, 0, 1, 1)
    _drain(eng)
    snap = eng.metrics.snapshot()
    eng.close()
    for p, s in zip((first, two, three, long_), streams):
        check(p, s.result(5))
    assert snap["chunks_fused"] + snap["chunks_alone"] \
        == snap["prefill_chunks"] == 1 + 1 + 1 + 3


def stats_count_the_pages_each_chunk_has_reached(model, params, check):
    """``chunk_pages_read`` is the sum over the chunks run of ``start //
    page_len + 1`` (what a chunk's attention has to visit) and
    ``chunk_pages_slot`` the chunks times the pages a slot holds (what it
    visits where it gathers the slot), whichever program carried the chunk."""
    lengths = (19, 5, 27, 40)
    prompts = _prompts(model, 16, lengths)
    eng = _engine(model, params)
    streams = [eng.submit(prompts[0], 8)]
    _drain(eng)                           # a cold start: its chunks run alone
    alone = eng.metrics.snapshot()
    assert (alone["chunks_alone"], alone["chunk_pages_read"],
            alone["chunk_pages_slot"]) == (3, 1 + 2 + 3, 3 * SLOT_LEN // C)
    streams.append(eng.submit(prompts[1], 24))
    eng.step()
    eng.step()
    streams += [eng.submit(p, 4) for p in prompts[2:]]   # these ride
    _drain(eng)
    snap = eng.metrics.snapshot()
    eng.close()
    for p, s in zip(prompts, streams):
        check(p, s.result(5))
    assert snap["chunks_fused"] >= 4 + 5
    chunks = [-(-n // C) for n in lengths]
    assert snap["prefill_chunks"] == sum(chunks)
    assert snap["chunk_pages_read"] == sum(n * (n + 1) // 2 for n in chunks)
    assert snap["chunk_pages_slot"] == sum(chunks) * (SLOT_LEN // C)


CASES = {fn.__name__: fn for fn in (
    the_mixed_body_computes_what_the_two_bodies_compute,
    the_chunks_slot_keeps_the_chunks_state_and_pages,
    the_engine_streams_the_references_tokens_through_mixed_steps,
    a_reused_slots_first_fused_chunk_starts_from_zeros,
    a_chunk_with_no_row_to_decode_runs_alone,
    rows_with_no_chunk_take_the_decode_step,
    of_two_chunks_an_iteration_the_first_rides_and_the_other_runs_alone,
    stats_count_the_pages_each_chunk_has_reached,
)}
