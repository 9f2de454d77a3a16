"""tpu_air.engine.kvpool — the block-table-paged KV pool.

Layers under test:
  * BlockAllocator: lowest-first alloc, refcounts, free-list reuse, OOM;
  * PrefixCache: full-chunk + partial-tail matching, insert dedup, LRU
    leaf eviction;
  * PagedKVPool: admission plans (chunk work lists, prefix sharing,
    null-target full cover), copy-on-write resolution, release accounting;
  * scheduler head-of-line relief: bounded reorder window + counter;
  * the paged ENGINE: token parity with offline generate AND with the
    slab engine, prefix hits / CoW end to end, chunked-prefill TTFT
    flatness under a long-prompt arrival, OOM deferral, kvpool gauges in
    the metrics snapshot and prometheus text;
  * the T5 slot engine: parity with offline T5 generate, whenever a
    request is admitted.
"""

import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_air.engine import (
    BlockAllocator,
    EngineClosedError,
    EngineConfig,
    InferenceEngine,
    KVPoolOOMError,
    PagedKVPool,
    PrefixCache,
    Request,
    ResponseStream,
    Scheduler,
    T5Engine,
    T5EngineConfig,
)
from tpu_air.engine.kvpool.allocator import NULL_PAGE
from tpu_air.models.lm import CausalLM, LMConfig
from tpu_air.models.lm.generate import generate as lm_generate


@pytest.fixture(scope="module")
def lm():
    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return cfg, model, params


def _prompts(seed, n, lo=3, hi=12, vocab=384):
    rng = np.random.RandomState(seed)
    return [list(map(int, rng.randint(1, vocab, size=rng.randint(lo, hi))))
            for _ in range(n)]


def _offline(model, params, prompt, max_new, eos=None):
    out = np.asarray(
        lm_generate(model, params, [prompt], max_new_tokens=max_new,
                    eos_token_id=eos)
    )[0].tolist()
    if eos is not None and eos in out:
        out = out[: out.index(eos) + 1]
    return out


def _drain(engine, limit=500):
    steps = 0
    while not engine.idle():
        engine.step()
        steps += 1
        assert steps < limit, "engine failed to drain"
    return steps


# ---------------------------------------------------------------------------
# BlockAllocator
# ---------------------------------------------------------------------------


def test_allocator_lowest_first_refcounts_and_oom():
    a = BlockAllocator(num_pages=5, page_len=8)
    assert a.free_count() == 4 and a.used_count() == 0
    assert a.refcount(NULL_PAGE) == 1  # pinned forever
    pages = [a.alloc() for _ in range(4)]
    assert pages == [1, 2, 3, 4]  # deterministic lowest-first
    with pytest.raises(KVPoolOOMError):
        a.alloc()
    # refcounting: a shared page survives one holder's release
    a.incref(2)
    assert a.refcount(2) == 2
    assert a.decref(2) is False and a.free_count() == 0
    assert a.decref(2) is True and a.free_count() == 1
    # freed page is handed out again, lowest-first
    a.decref(1)
    assert a.alloc() == 1
    assert a.alloc() == 2


def test_allocator_misuse_is_loud():
    a = BlockAllocator(num_pages=4, page_len=8)
    with pytest.raises(ValueError):
        a.incref(NULL_PAGE)  # null page is not a refcountable target
    with pytest.raises(ValueError):
        a.incref(99)
    with pytest.raises(ValueError):
        a.incref(1)  # still free
    with pytest.raises(ValueError):
        a.decref(1)
    with pytest.raises(ValueError):
        BlockAllocator(num_pages=1, page_len=8)
    with pytest.raises(ValueError):
        BlockAllocator(num_pages=4, page_len=0)


# ---------------------------------------------------------------------------
# PrefixCache
# ---------------------------------------------------------------------------


def _cached(allocator, cache, tokens):
    """Simulate a retired request: insert ``tokens``'s full chunks on fresh
    pages, then drop the slot's own refs so only the cache holds them."""
    full = len(tokens) // cache.page_len
    pages = [allocator.alloc() for _ in range(full)]
    cache.insert(tokens, pages)
    for p in pages:
        allocator.decref(p)
    return pages


def test_prefix_match_full_partial_and_miss():
    a = BlockAllocator(num_pages=16, page_len=4)
    c = PrefixCache(a, page_len=4)
    donor = list(range(100, 112))  # 3 full chunks
    pages = _cached(a, c, donor)
    assert c.resident_pages() == 3

    m = c.match(donor)
    assert m.pages == pages and m.matched_tokens == 12 and m.tail_page is None
    # longer prompt sharing the prefix: full chunks only
    m = c.match(donor + [7, 7, 7, 7, 7])
    assert m.pages == pages and m.matched_tokens == 12
    # partial tail: prompt ends inside a cached chunk -> that page shared
    m = c.match(donor[:10])
    assert m.pages == pages[:2]
    assert m.tail_page == pages[2] and m.matched_tokens == 10
    # diverging inside a chunk breaks the walk at the chunk boundary
    m = c.match(donor[:4] + [999] * 8)
    assert m.pages == pages[:1] and m.matched_tokens == 4
    m = c.match([999] * 8)
    assert m.pages == [] and m.matched_tokens == 0
    assert c.hits == 4 and c.misses == 1 and c.partial_hits == 1
    # capacity probes (touch=False) must not move stats
    c.match(donor, touch=False)
    assert c.hits == 4 and c.misses == 1


def test_prefix_insert_dedup_keeps_first_writer():
    a = BlockAllocator(num_pages=16, page_len=4)
    c = PrefixCache(a, page_len=4)
    donor = list(range(50, 58))
    pages = _cached(a, c, donor)
    # a second slot computed the same chunks on its own pages: existing
    # edges win, nothing new inserted, no extra refs taken
    dup = [a.alloc(), a.alloc()]
    assert c.insert(donor, dup) == 0
    assert c.match(donor).pages == pages
    assert a.refcount(dup[0]) == 1  # still only the slot's own ref


def test_prefix_evict_lru_leaves_cascade():
    a = BlockAllocator(num_pages=16, page_len=4)
    c = PrefixCache(a, page_len=4)
    old = _cached(a, c, list(range(0, 8)))      # 2 chunks
    new = _cached(a, c, list(range(20, 28)))    # 2 chunks
    c.match(list(range(20, 28)))                # bump 'new' to MRU
    assert c.evictable_count() == 2             # only the two leaves
    free0 = a.free_count()
    assert c.evict(1) == 1                      # LRU leaf: old's chunk 2
    assert a.free_count() == free0 + 1
    assert c.match(list(range(0, 8))).pages == old[:1]
    # cascading: evicting the leaf exposed old's chunk 1
    assert c.evict(3) == 3                      # old chunk1 + both of new
    assert c.resident_pages() == 0 and c.evictions == 4
    # a page a live slot still references is pinned: nothing to evict
    pinned = _cached(a, c, list(range(40, 44)))
    a.incref(pinned[0])  # a slot's block-table entry
    assert c.evictable_count() == 0 and c.evict(1) == 0


# ---------------------------------------------------------------------------
# PagedKVPool: admission plans, CoW, release
# ---------------------------------------------------------------------------


def test_pool_admit_miss_then_full_chunk_share():
    pool = PagedKVPool(num_pages=12, page_len=4, num_slots=2,
                       pages_per_slot=8)
    prompt = list(range(200, 210))  # 10 tokens = 2 full chunks + 2
    plan = pool.admit(0, prompt, budget=3)  # last write at pos 10+3-2 -> 3 pages
    assert plan.chunk_starts == [0, 4, 8] and not plan.null_target
    assert plan.prefix_tokens == 0 and not plan.shared_tail
    row0 = list(pool.block_table[0][:3])
    assert row0 == [1, 2, 3]
    pool.register(0, prompt)   # 2 full chunks become resident
    pool.release(0)
    assert (pool.block_table[0] == NULL_PAGE).all()
    assert pool.allocator.refcount(1) == 1  # cache residency survives
    assert pool.allocator.refcount(3) == 0  # decode page freed

    # same prompt again: leading chunks shared, only the tail prefilled
    plan = pool.admit(1, prompt, budget=3)
    assert plan.prefix_tokens == 8 and plan.chunk_starts == [8]
    assert list(pool.block_table[1][:2]) == row0[:2]
    assert pool.allocator.refcount(1) == 2  # cache + slot 1


def test_pool_partial_tail_cow_and_null_target():
    pool = PagedKVPool(num_pages=16, page_len=4, num_slots=2,
                       pages_per_slot=8)
    donor = list(range(300, 312))  # 3 full chunks
    pool.admit(0, donor, budget=2)
    pool.register(0, donor)
    pool.release(0)

    # prompt ends INSIDE donor's 3rd chunk: tail page shared, fully
    # covered -> single null-target chunk just for the first token's logits
    prompt = donor[:10]
    plan = pool.admit(1, prompt, budget=4)
    assert plan.shared_tail and plan.null_target
    assert plan.prefix_tokens == 10 and plan.chunk_starts == [8]
    tail_idx = len(prompt) // 4
    shared_tail = int(pool.block_table[1][tail_idx])
    assert pool.allocator.refcount(shared_tail) >= 2
    # the chunk's prefill view is redirected to the null page; the
    # authoritative table is untouched
    view = pool.chunk_row(1, plan.chunk_starts[0], plan.null_target)
    assert view[tail_idx] == NULL_PAGE
    assert int(pool.block_table[1][tail_idx]) == shared_tail

    # first decode append diverges from the cached content: CoW repoints
    # the tail at the reserved private page, donor's page keeps its holders
    cow = pool.resolve_cow(1)
    assert cow is not None
    dst, src = cow
    assert src == shared_tail and int(pool.block_table[1][tail_idx]) == dst
    assert pool.allocator.refcount(src) == 1  # cache residency only
    assert pool.cow_copies == 1
    assert pool.resolve_cow(1) is None  # idempotent
    pool.release(1)
    assert pool.allocator.refcount(dst) == 0


def test_pool_page_math_and_capacity():
    pool = PagedKVPool(num_pages=8, page_len=4, num_slots=1,
                       pages_per_slot=7, prefix_cache=False)
    # budget=1: the single emitted token is computed, never written
    assert pool.worst_case_pages(4, 1) == 1
    assert pool.worst_case_pages(5, 1) == 2
    # budget>1: budget-1 decode scatters land after the prompt
    assert pool.worst_case_pages(3, 2) == 1
    assert pool.worst_case_pages(4, 2) == 2
    assert pool.worst_case_pages(8, 5) == 3
    assert pool.capacity() == 7  # no prefix cache: free pages only
    pool.admit(0, list(range(10)), budget=3)
    assert pool.capacity() == 4


# ---------------------------------------------------------------------------
# scheduler: bounded reorder window
# ---------------------------------------------------------------------------


def _req(rid, n):
    return Request(request_id=rid, prompt=[1] * n, max_new_tokens=4,
                   stream=ResponseStream(rid))


def test_scheduler_reorder_window_relieves_blocked_head():
    s = Scheduler(EngineConfig(max_queue=16, reorder_window=2))
    for rid, n in enumerate([8, 2, 3, 9, 2]):  # big head, smalls behind
        s.submit(_req(rid, n))
    fits = lambda r: len(r.prompt) < 5
    out = s.pop_admissible(3, can_admit=fits)
    # head (r0) blocked each round; window=2 look-ahead admits in queue
    # order: r1, r2, then r4 (r3 also blocked)
    assert [r.request_id for r in out] == [1, 2, 4]
    assert s.reordered_admits == 3
    assert s.depth() == 2  # r0, r3 still queued, order preserved
    out = s.pop_admissible(2, can_admit=lambda r: True)
    assert [r.request_id for r in out] == [0, 3]


def test_scheduler_reorder_window_zero_is_strict_fifo():
    s = Scheduler(EngineConfig(max_queue=16, reorder_window=0))
    for rid, n in enumerate([8, 2, 2]):
        s.submit(_req(rid, n))
    assert s.pop_admissible(3, can_admit=lambda r: len(r.prompt) < 5) == []
    assert s.reordered_admits == 0 and s.depth() == 3


@pytest.mark.parametrize("page_len", [8, 16])
def test_gather_pages_places_every_position(page_len):
    """``gather_pages``: position ``p`` of slot ``s`` is
    ``pool[table[s, p // C], p % C]``, for unreached entries on the null
    page and for a page two rows share (a prefix hit) alike."""
    from tpu_air.ops.decode_attention import gather_pages

    C, npg, hd = page_len, 3, 4
    P = 6
    # every pool element names its own (page, offset, channel)
    pool = jnp.asarray(np.arange(P * C * hd).reshape(P, C, hd), jnp.float32)
    table = np.array([[4, 2, NULL_PAGE],      # two pages reached
                      [4, 5, 1],              # shares page 4 with row 0
                      [NULL_PAGE] * 3],       # a free slot
                     np.int32)
    got = np.asarray(gather_pages(pool, jnp.asarray(table)))
    assert got.shape == (3, npg * C, hd)
    want = np.asarray(pool)
    for s in range(3):
        for p in range(npg * C):
            np.testing.assert_array_equal(
                got[s, p], want[table[s, p // C], p % C])


# ---------------------------------------------------------------------------
# the paged engine, end to end
# ---------------------------------------------------------------------------


def test_paged_engine_matches_offline_and_mesh(lm):
    """The ISSUE acceptance anchor: the paged engine is token-identical to
    offline greedy generate — and so is the sharded MeshEngine (dp=2, tp=2
    over the forced-8-device CPU host) — on the same burst."""
    from tpu_air.engine import MeshEngine

    cfg, model, params = lm
    prompts = _prompts(seed=21, n=6)
    max_new = 8
    outs = {}
    for mode in ("paged", "mesh"):
        if mode == "mesh":
            if len(jax.devices()) < 4:
                continue  # rig needs the conftest's forced device count
            engine = MeshEngine(
                model, params,
                EngineConfig(num_slots=4, slot_len=64,
                             max_new_tokens=max_new, page_len=8),
                dp=2, tp=2, auto_start=False, name="kvpool-parity-mesh",
            )
        else:
            engine = InferenceEngine(
                model, params,
                EngineConfig(num_slots=3, slot_len=64, max_new_tokens=max_new,
                             page_len=8),
                auto_start=False, name=f"kvpool-parity-{mode}",
            )
        streams = [engine.submit(p) for p in prompts]
        _drain(engine)
        outs[mode] = [s.result(5.0) for s in streams]
        engine.close()
    want = [_offline(model, params, p, max_new) for p in prompts]
    for mode, got in outs.items():
        assert got == want, f"{mode} diverged from offline"


def test_paged_engine_prefix_hits_and_cow(lm):
    """Shared system prompt: the second request skips the covered chunks
    (prefix hit), a mid-chunk cut triggers exactly one copy-on-write, and
    every stream stays token-identical to offline generate."""
    cfg, model, params = lm
    rng = np.random.RandomState(31)
    sys_prompt = list(map(int, rng.randint(1, 384, size=16)))  # 2 full pages
    a = sys_prompt + list(map(int, rng.randint(1, 384, size=8)))  # 3 pages
    b = sys_prompt + list(map(int, rng.randint(1, 384, size=5)))
    tail = a[:20]  # ends inside a's 3rd page -> partial-tail share + CoW
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=6, page_len=8),
        auto_start=False, name="kvpool-prefix",
    )
    results = []
    for p in (a, b, tail):  # sequential: each later prompt sees the cache
        s = engine.submit(p)
        _drain(engine)
        results.append(s.result(5.0))
    stats = engine.pool.stats()
    engine.close()
    for p, got in zip((a, b, tail), results):
        assert got == _offline(model, params, p, 6)
    assert stats["prefix_hits"] == 2           # b and tail both hit
    assert stats["prefix_partial_hits"] == 1   # tail shared a's 3rd page
    assert stats["cow_copies"] == 1
    assert stats["prefix_tokens_reused"] == 16 + 20  # b's chunks + all of tail


def test_chunked_prefill_keeps_short_ttft_flat(lm):
    """A 40-token prompt prefills in page-sized chunks; a short prompt
    arriving alongside it reaches its first token in the SAME number of
    engine steps as it does on an idle engine (flat TTFT), while the long
    prompt's chunks interleave behind it."""
    cfg, model, params = lm

    def steps_to_first(engine, stream):
        steps = 0
        while not stream.tokens_so_far():
            assert engine.step(), "engine idle before first token"
            steps += 1
        return steps

    def fresh():
        return InferenceEngine(
            model, params,
            EngineConfig(num_slots=2, slot_len=64, max_new_tokens=6,
                         page_len=8, prefill_chunks_per_step=1),
            auto_start=False, name="kvpool-ttft",
        )

    rng = np.random.RandomState(41)
    long_p = list(map(int, rng.randint(1, 384, size=40)))  # 5 chunks
    short_p = list(map(int, rng.randint(1, 384, size=5)))  # 1 chunk

    engine = fresh()
    baseline = steps_to_first(engine, engine.submit(short_p))
    _drain(engine)
    engine.close()

    engine = fresh()
    s_long = engine.submit(long_p)
    s_short = engine.submit(short_p)
    loaded = steps_to_first(engine, s_short)
    # the short prompt's single chunk runs first (shortest-remaining-first)
    assert loaded == baseline
    # the long prompt is still mid-prefill: its 5 chunks run one per step
    assert not s_long.tokens_so_far()
    long_first = loaded + steps_to_first(engine, s_long)
    assert long_first >= 5
    # and the short request kept decoding underneath the long prefill
    assert len(s_short.tokens_so_far()) > 1
    _drain(engine)
    assert s_short.result(5.0) == _offline(model, params, short_p, 6)
    assert s_long.result(5.0) == _offline(model, params, long_p, 6)
    assert engine.metrics.snapshot()["prefill_chunks"] == 6
    engine.close()


def test_paged_engine_defers_on_pool_exhaustion(lm):
    """A request whose worst case exceeds the free pages waits; a small one
    behind it jumps the line (reorder window); the big one admits after
    pages free up.  Streams stay token-identical throughout."""
    cfg, model, params = lm
    rng = np.random.RandomState(51)
    big_a = list(map(int, rng.randint(1, 384, size=20)))  # wc 4 pages @ b=6
    big_b = list(map(int, rng.randint(1, 384, size=21)))  # wc 4 pages
    small = list(map(int, rng.randint(1, 384, size=4)))   # wc 1 page
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=32, max_new_tokens=6, page_len=8,
                     num_pages=6, reorder_window=2),  # 5 usable pages
        auto_start=False, name="kvpool-oom",
    )
    s_a = engine.submit(big_a)
    s_b = engine.submit(big_b)
    s_small = engine.submit(small, max_new_tokens=4)
    engine.step()
    # round 1: A reserved 4 of 5 pages, B (4 more) deferred, small (1) jumped
    assert engine.scheduler.depth() == 1
    assert engine.scheduler.reordered_admits == 1
    _drain(engine)
    assert s_a.result(5.0) == _offline(model, params, big_a, 6)
    assert s_b.result(5.0) == _offline(model, params, big_b, 6)
    assert s_small.result(5.0) == _offline(model, params, small, 4)
    assert engine.metrics.snapshot()["requests_completed"] == 3
    engine.close()


def test_kvpool_gauges_reach_snapshot_and_prometheus(lm):
    cfg, model, params = lm
    from tpu_air.engine.metrics import prometheus_lines

    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=4, page_len=8),
        auto_start=False, name="kvpool-gauges",
    )
    engine.generate(_prompts(seed=61, n=3))
    snap = engine.metrics.snapshot()
    assert snap["kvpool"]["pages_total"] == 2 * 8  # slab-equivalent pool
    # drained: the only allocated pages are prefix-cache residency
    assert snap["kvpool"]["pages_used"] == snap["kvpool"][
        "prefix_resident_pages"]
    assert snap["kvpool"]["pages_free"] + snap["kvpool"][
        "pages_used"] == snap["kvpool"]["pages_total"]
    assert 0.0 <= snap["kvpool"]["prefix_hit_rate"] <= 1.0
    assert snap["prefill_chunks"] >= 3
    assert snap["reordered_admits"] == 0
    text = "\n".join(prometheus_lines({snap["name"]: snap}))
    assert 'tpu_air_engine_kvpool_pages_free{engine="kvpool-gauges"}' in text
    assert 'tpu_air_engine_kvpool_prefix_hit_rate{engine="kvpool-gauges"}' in text
    assert 'tpu_air_engine_prefill_chunks{engine="kvpool-gauges"}' in text
    assert 'tpu_air_engine_ttft_s_p95{engine="kvpool-gauges"}' in text
    engine.close()


# ---------------------------------------------------------------------------
# T5 slot engine
# ---------------------------------------------------------------------------


def test_t5_slot_engine_matches_offline_generate():
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.models.t5.generate import generate as t5_generate

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    enc = jnp.ones((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), enc, jnp.ones_like(enc),
                        jnp.ones((2, 6), jnp.int32))["params"]
    rng = np.random.RandomState(71)
    prompts = [list(map(int, rng.randint(2, 384, size=rng.randint(3, 8))))
               for _ in range(5)]
    max_new = 6

    # offline reference: one padded batch; T5 rows are batch-independent,
    # so which rows the engine decodes together can't change a row's tokens
    li = max(len(p) for p in prompts)
    ids = np.full((len(prompts), li), cfg.pad_token_id, np.int32)
    for r, p in enumerate(prompts):
        ids[r, :len(p)] = p
    mask = (ids != cfg.pad_token_id).astype(np.int32)
    ref = np.asarray(t5_generate(model, params, jnp.asarray(ids),
                                 attention_mask=jnp.asarray(mask),
                                 max_new_tokens=max_new, early_stop=False))
    want = []
    for row in ref.tolist():  # engine emits EOS inclusive, then retires
        if cfg.eos_token_id in row:
            row = row[: row.index(cfg.eos_token_id) + 1]
        want.append(row)

    # 5 prompts through 2 slots: a slot is taken again as its row retires
    engine = T5Engine(
        model, params,
        T5EngineConfig(max_batch=2, max_input_len=8, max_new_tokens=max_new),
        auto_start=False, name="t5-slot-test",
    )
    streams = [engine.submit(p) for p in prompts]
    steps = 0
    while not engine.idle():
        engine.step()
        steps += 1
        assert steps < 200, "t5 engine failed to drain"
    for s, w in zip(streams, want):
        assert s.result(5.0) == w
    assert engine.metrics.snapshot()["requests_completed"] == 5
    engine.close()


# -- one step in flight: EOS learnt a step late, nothing uploaded a step ------


@pytest.fixture(scope="module")
def t5_tiny():
    """Tiny T5 weights, three prompts and their first eight greedy tokens
    (no early stop): what the engine must stream, up to the EOS a test
    picks."""
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.models.t5.generate import generate as t5_generate

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    enc = jnp.ones((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), enc, jnp.ones_like(enc),
                        jnp.ones((2, 6), jnp.int32))["params"]
    rng = np.random.RandomState(71)  # the parity test's prompts, three of them
    prompts = [list(map(int, rng.randint(2, 384, size=rng.randint(3, 8))))
               for _ in range(5)][1:4]
    ids = np.full((len(prompts), 8), cfg.pad_token_id, np.int32)
    for r, p in enumerate(prompts):
        ids[r, :len(p)] = p
    mask = (ids != cfg.pad_token_id).astype(np.int32)
    ref = np.asarray(t5_generate(model, params, jnp.asarray(ids),
                                 attention_mask=jnp.asarray(mask),
                                 max_new_tokens=8, early_stop=False)).tolist()
    # row 0 brings a token at its fifth place that it has not had before and
    # that row 1 never has: the EOS of the tests below
    eos = ref[0][4]
    assert eos not in ref[0][:4] and eos not in ref[1], ref
    return cfg, params, prompts, ref, eos


def _t5_engine(t5_tiny, name, eos=None, max_new=8, slots=2, **kw):
    """A slot engine over the fixture's weights whose model ends a row on
    ``eos`` (the weights do not know which token that is)."""
    import dataclasses

    from tpu_air.models.t5 import T5ForConditionalGeneration

    cfg, params = t5_tiny[:2]
    if eos is not None:
        cfg = dataclasses.replace(cfg, eos_token_id=eos)
    return T5Engine(
        T5ForConditionalGeneration(cfg), params,
        T5EngineConfig(max_batch=slots, max_input_len=8,
                       max_new_tokens=max_new),
        name=name, **kw)


def _run_dry(engine):
    steps = 0
    while not engine.idle():
        engine.step()
        steps += 1
        assert steps < 200, "t5 engine failed to drain"


@pytest.mark.parametrize("other_budget, issued, ahead, dropped", [
    # the other row runs on to its budget of 8: 8 steps, each one read (a
    # row's first token is its first step's)
    (8, 8, 7, 0),
    # the other row ends on its budget of 3, so the row that ends on EOS at
    # its fifth token is the last live one: the sixth token's step is out
    # when the host learns of it, and nobody reads it
    (3, 6, 5, 1),
], ids=["another-row-runs-on", "the-last-live-row"])
def test_t5_engine_learns_of_eos_one_step_late_and_emits_nothing_past_it(
        t5_tiny, other_budget, issued, ahead, dropped):
    _, _, prompts, ref, eos = t5_tiny
    engine = _t5_engine(t5_tiny, f"t5-eos-{other_budget}", eos=eos,
                        auto_start=False)
    ending = engine.submit(prompts[0], 8)
    other = engine.submit(prompts[1], other_budget)
    _run_dry(engine)
    assert ending.result(5.0) == ref[0][:5] and ending.result()[-1] == eos
    assert other.result(5.0) == ref[1][:other_budget]
    snap = engine.metrics.snapshot()
    assert (snap["steps_issued"], snap["steps_ahead"],
            snap["steps_dropped"]) == (issued, ahead, dropped)
    assert snap["tokens_emitted"] == 5 + other_budget
    assert engine._unread is None
    # the next request is issued behind the dropped step and streams as ever
    late = engine.submit(prompts[2], 4)
    assert not engine.idle()
    engine.drain()
    assert not engine.drained()
    _run_dry(engine)
    assert late.result(5.0) == ref[2][:4]
    assert engine.idle() and engine.drained()
    snap = engine.metrics.snapshot()
    assert (snap["steps_issued"], snap["steps_dropped"]) == (issued + 4,
                                                             dropped)
    assert snap["requests_completed"] == 3
    engine.close()
    assert engine.metrics.snapshot()["steps_dropped"] == dropped


@pytest.mark.parametrize("other_budget, issued, ahead, dropped", [
    # the other row's one chunk rides step 2 (the mixed program), so it joins
    # step 3 and runs on to its budget of 8: steps 1..9, each one read, all
    # but the first issued ahead
    (8, 9, 8, 0),
    # the other row ends on its budget of 3 (host state: it is in no step
    # past its last), so the row that ends on EOS at its fifth token is the
    # last: the sixth token's step is out when the host learns of it
    (3, 5, 4, 1),
], ids=["another-row-runs-on", "the-last-live-row"])
def test_paged_engine_learns_of_eos_one_step_late_and_emits_nothing_past_it(
        lm_live, other_budget, issued, ahead, dropped):
    """``InferenceEngine``'s counters for the same two endings as
    ``T5Engine``'s case above."""
    cfg, model, params = lm_live
    ending, other, late = _prompts(seed=71, n=3)
    ref = _offline(model, params, ending, 8)
    eos = ref[4]
    assert eos not in ref[:4] and eos not in _offline(model, params, other, 8)
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=8,
                     eos_token_id=eos),
        auto_start=False, name=f"paged-eos-{other_budget}")
    a = engine.submit(ending, 8)
    b = engine.submit(other, other_budget)
    _drain(engine)
    assert a.result(5.0) == ref[:5]
    assert b.result(5.0) == _offline(model, params, other, other_budget)
    snap = engine.metrics.snapshot()
    assert (snap["steps_issued"], snap["steps_ahead"],
            snap["steps_dropped"]) == (issued, ahead, dropped)
    # a row's first token is its last chunk's; the ride-along is not counted
    assert snap["tokens_emitted"] == 5 + other_budget
    assert engine._inflight is None and engine.slots.free_count() == 2
    # the next request is issued behind the dropped step and streams as ever
    c = engine.submit(late, 4)
    assert not engine.idle()
    engine.drain()
    assert not engine.drained()
    _drain(engine)
    assert c.result(5.0) == _offline(model, params, late, 4, eos)
    assert engine.idle() and engine.drained()
    snap = engine.metrics.snapshot()
    assert (snap["steps_issued"], snap["steps_dropped"]) == (
        issued + len(c.result()) - 1, dropped)
    assert snap["requests_completed"] == 3
    engine.close()
    assert engine.metrics.snapshot()["steps_dropped"] == dropped


def test_paged_decode_steps_upload_nothing_between_joins_and_leaves(lm_live):
    """With its rows settled the paged step takes tokens, positions, block
    table and cache from the device: with host-to-device transfers
    disallowed every step runs, until a row ends and the table goes up again
    (the parent uploaded tokens, positions and the table each step)."""
    cfg, model, params = lm_live
    prompts = _prompts(seed=73, n=2)
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=9,
                     eos_token_id=None),
        auto_start=False, name="paged-upload-test")
    streams = [engine.submit(p) for p in prompts]
    engine.step()
    engine.step()  # both prompts are in: two chunks, two joins
    engine.step()
    issued = engine.metrics.snapshot()["steps_issued"]
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        with pytest.raises(Exception, match="host-to-device"):
            jnp.asarray(np.zeros(2, np.int32))  # the guard is enforced here
        for _ in range(4):
            assert engine.step()
    assert engine.metrics.snapshot()["steps_issued"] == issued + 4
    _drain(engine)
    assert [s.result(5.0) for s in streams] == [
        _offline(model, params, p, 9) for p in prompts]
    engine.close()


def test_t5_step_counters_reach_metrics_only_from_an_engine_that_issues():
    from tpu_air.engine.metrics import (
        EngineMetrics,
        merge_snapshots,
        prometheus_lines,
        unregister,
    )

    ahead, plain = EngineMetrics("ahead-test"), EngineMetrics("plain-test")
    try:
        for is_ahead, rows in ((False, 16), (True, 16), (True, 32)):
            ahead.record_issue(is_ahead, rows=rows)
        ahead.record_dropped_step()
        ahead.record_admission(1, in_flight=False)
        ahead.record_admission(3, in_flight=True)
        snaps = {"a": ahead.snapshot(), "p": plain.snapshot()}
    finally:
        unregister("ahead-test")
        unregister("plain-test")
    assert "steps_issued" not in snaps["p"]
    assert "admissions" not in snaps["p"] and "steps_by_rows" not in snaps["p"]
    assert snaps["a"]["steps_by_rows"] == {16: 2, 32: 1}
    merged = merge_snapshots(snaps)
    assert (merged["steps_issued"], merged["steps_ahead"],
            merged["steps_dropped"]) == (3, 2, 1)
    assert (merged["admissions"], merged["rows_admitted"],
            merged["rows_admitted_in_flight"]) == (2, 4, 3)
    assert "steps_issued" not in merge_snapshots({"p": snaps["p"]})
    lines = prometheus_lines(snaps)
    for key, n in (("steps_issued", 3), ("steps_ahead", 2),
                   ("steps_dropped", 1), ("rows_admitted", 4),
                   ("rows_admitted_in_flight", 3)):
        assert f'tpu_air_engine_{key}{{engine="a"}} {n}' in lines
    assert not any('steps_issued{engine="p"}' in ln for ln in lines)
    assert not any('rows_admitted{engine="p"}' in ln for ln in lines)


@pytest.mark.parametrize("how", ["a-step-in-flight",
                                 "a-step-and-an-admission-in-flight"])
def test_t5_close_with_work_in_flight_fails_live_streams_and_joins(
        t5_tiny, how):
    """``close()`` while the device still holds an issued step (and, in the
    second case, an admit program and the step that would have decoded the
    admitted row's first token): every live stream fails, nothing hangs, the
    step nobody will read counts as dropped and the loop's thread is gone."""
    import threading

    _, _, prompts, ref, _ = t5_tiny
    threaded = how == "a-step-in-flight"
    engine = _t5_engine(t5_tiny, f"t5-close-{threaded}", max_new=512,
                        auto_start=threaded)
    stream = engine.submit(prompts[0], 512)
    streams = [stream]
    if threaded:
        deadline = time.monotonic() + 60.0
        while not stream.tokens_so_far():
            assert time.monotonic() < deadline, "no first token"
            time.sleep(0.001)
    else:
        for _ in range(3):
            engine.step()
        # admitted and issued inside this iteration, read by none
        streams.append(engine.submit(prompts[1], 512))
        engine.step()
        assert engine.metrics.snapshot()["rows_admitted_in_flight"] == 1
        assert [req is not None for req in engine._rows] == [True, True]
        assert len(engine._unread.rows) == 2
    engine.close()
    for s in streams:
        with pytest.raises(EngineClosedError):
            s.result(5.0)
    got = stream.tokens_so_far()
    assert 1 <= len(got) < 512 and got[:8] == ref[0][:len(got)]
    assert streams[-1] is stream or streams[-1].tokens_so_far() == []
    snap = engine.metrics.snapshot()
    # every issued step was read and emitted, but the one the close dropped
    assert snap["steps_dropped"] == 1
    assert snap["steps_issued"] == len(got) + 1
    assert engine._unread is None and engine._thread is None
    assert engine._rows == [None, None]
    assert not any(t.name == f"tpu-air-t5-close-{threaded}"
                   for t in threading.enumerate())
    with pytest.raises(EngineClosedError):
        engine.submit(prompts[1])


def test_t5_decode_steps_upload_nothing(t5_tiny):
    """The step path takes its tokens, masks, ring position and cache from
    the device: with host-to-device transfers disallowed, explicit ones too,
    every step between two admissions runs (the admission itself uploads one
    array: the round's prompts, their lengths and slots)."""
    _, _, prompts, ref, _ = t5_tiny
    engine = _t5_engine(t5_tiny, "t5-upload-test", auto_start=False)
    streams = [engine.submit(p, 8) for p in prompts[:2]]
    engine.step()  # both are admitted: the prompts go up, once
    assert not engine.idle()
    with jax.transfer_guard_host_to_device("disallow_explicit"):
        with pytest.raises(Exception, match="host-to-device"):
            jnp.asarray(np.zeros(2, np.int32))  # the guard is enforced here
        steps = 0
        while not engine.idle():
            engine.step()
            steps += 1
    assert steps == 8
    assert [s.result(5.0) for s in streams] == ref[:2]
    engine.close()


# -- a slot a request: whenever it is admitted, whatever the others do ---------


@pytest.fixture(scope="module")
def t5_many():
    """Tiny T5 weights (plain and with an int8 decode cache: the parameters
    are the same), 24 prompts and each one's first eight greedy tokens from
    offline ``generate`` under either config."""
    import dataclasses

    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.models.t5.generate import generate as t5_generate

    cfg = T5Config.tiny()
    enc = jnp.ones((2, 8), jnp.int32)
    params = T5ForConditionalGeneration(cfg).init(
        jax.random.PRNGKey(0), enc, jnp.ones_like(enc),
        jnp.ones((2, 6), jnp.int32))["params"]
    rng = np.random.RandomState(73)
    prompts = [list(map(int, rng.randint(2, 384, size=rng.randint(3, 9))))
               for _ in range(24)]
    ids = np.full((len(prompts), 8), cfg.pad_token_id, np.int32)
    for r, p in enumerate(prompts):
        ids[r, :len(p)] = p
    mask = (ids != cfg.pad_token_id).astype(np.int32)
    refs = {}
    for int8 in (False, True):
        model = T5ForConditionalGeneration(
            dataclasses.replace(cfg, decode_cache_int8=int8))
        refs[int8] = np.asarray(t5_generate(
            model, params, jnp.asarray(ids), attention_mask=jnp.asarray(mask),
            max_new_tokens=8, early_stop=False)).tolist()
    return cfg, params, prompts, refs


# scenario -> (slots, max_new_tokens, int8, [(iteration, prompt, budget)]):
# request ``prompt`` is submitted before engine iteration ``iteration``
_SLOT_SCENARIOS = {
    # one request every other iteration into four slots: every row joins
    # rows of other ages
    "admitted-at-different-steps": (
        4, 8, False, [(2 * i, i, 3 + i % 6) for i in range(10)]),
    # a ring of 5 positions turned four times over by 20 requests
    "after-the-ring-has-wrapped": (
        2, 4, False, [(i, i, 1 + i % 4) for i in range(20)]),
    # seven at once into two slots: the queue waits for a free slot, FIFO
    "more-requests-than-slots": (
        2, 8, False, [(0, i, 2 + i) for i in range(7)]),
    # 20 at once into 32 slots, the high slots' budgets short: the step's
    # prefix is 32 rows, then 16 again
    "prefix-grows-and-shrinks": (
        32, 8, False, [(0, i, 8) for i in range(3)]
        + [(4, i, 8 if i < 8 else 2) for i in range(3, 23)]),
    # the int8 decode cache goes through the ring and the slots' rows
    "int8-cache": (
        4, 8, True, [(2 * i, i, 3 + i % 6) for i in range(8)]),
}


@pytest.mark.parametrize("scenario", sorted(_SLOT_SCENARIOS))
def test_t5_slot_engine_streams_what_generate_alone_would(t5_many, scenario):
    import dataclasses

    from tpu_air.models.t5 import T5ForConditionalGeneration

    cfg, params, prompts, refs = t5_many
    slots, max_new, int8, plan = _SLOT_SCENARIOS[scenario]
    model = T5ForConditionalGeneration(
        dataclasses.replace(cfg, decode_cache_int8=int8, eos_token_id=-1))
    engine = T5Engine(
        model, params,
        T5EngineConfig(max_batch=slots, max_input_len=8,
                       max_new_tokens=max_new),
        auto_start=False, name=f"t5-slots-{scenario}")
    admitted, pop = [], engine.scheduler.pop_admissible

    def spy_pop(*a, **kw):
        out = pop(*a, **kw)
        admitted.extend(r.request_id for r in out)
        return out

    engine.scheduler.pop_admissible = spy_pop
    streams, batches, taken, it = {}, [], [], 0
    while len(streams) < len(plan) or not engine.idle():
        for at, k, budget in plan:
            if at == it:
                streams[k] = (engine.submit(prompts[k], budget), budget)
        engine.step()
        if engine._unread is not None:
            batches.append(engine._unread.batch)
        taken.append(sum(r is not None for r in engine._rows))
        it += 1
        assert it < 400, "the engine failed to drain"
    for k, (stream, budget) in streams.items():
        assert stream.result(5.0) == refs[int8][k][:budget], k
    snap = engine.metrics.snapshot()
    pos = int(engine._state["cache"]["decoder"]["decoder_pos"])
    engine.close()
    n = len(plan)
    assert snap["requests_completed"] == snap["rows_admitted"] == n
    assert snap["tokens_emitted"] == sum(b for _, b in streams.values())
    assert sum(snap["steps_by_rows"].values()) == snap["steps_issued"]
    assert admitted == sorted(admitted)            # FIFO, in every scenario
    assert max(taken) <= slots
    # the ring's position: the warm-up's steps and every step issued since
    ring, warm = max_new + 1, 2 * len(engine._steps)
    assert pos == (warm + snap["steps_issued"]) % ring
    if scenario == "admitted-at-different-steps":
        assert snap["rows_admitted_in_flight"] == n - 1
        assert snap["admissions"] == n
    elif scenario == "after-the-ring-has-wrapped":
        assert snap["steps_issued"] > 4 * ring
    elif scenario == "more-requests-than-slots":
        assert max(taken) == slots and snap["admissions"] > 2
        # all but the first round joined rows that were decoding
        assert snap["rows_admitted_in_flight"] == n - 2
    elif scenario == "prefix-grows-and-shrinks":
        assert set(snap["steps_by_rows"]) == {16, 32}
        grew = batches.index(32)
        assert set(batches[:grew]) == {16}
        shrank = grew + batches[grew:].index(16)
        assert set(batches[shrank:]) == {16} and len(batches) > shrank + 2
    # nothing was traced or compiled after the engine was built
    assert all(f._cache_size() == 1 for f in (*engine._steps.values(),
                                              *engine._admits.values()))


def test_t5_slot_is_taken_again_right_after_an_eos_learnt_one_step_late(
        t5_tiny):
    """Two slots, three requests.  The row in slot 0 ends on EOS at its fifth
    token, which the host learns with the sixth token's step already out and
    the slot's old row in it.  The waiting request is admitted to slot 0 at
    the next iteration, behind that step on the device, and none of its
    tokens is the old row's sixth."""
    _, _, prompts, ref, eos = t5_tiny
    engine = _t5_engine(t5_tiny, "t5-slot-reuse", eos=eos, auto_start=False)
    ending = engine.submit(prompts[0], 8)
    other = engine.submit(prompts[1], 8)
    waiting = engine.submit(prompts[2], 8)
    for _ in range(6):      # admit two; issue steps 1..6, read steps 1..5
        engine.step()
    assert ending.done and not other.done and not waiting.tokens_so_far()
    assert engine._rows[0] is None and engine.scheduler.depth() == 1
    assert [slot for slot, _ in engine._unread.rows] == [0, 1]
    engine.step()           # admits to slot 0 behind step 6, which it reads
    assert engine._rows[0] is not None and engine.scheduler.depth() == 0
    assert len(other.tokens_so_far()) == 6 and not waiting.tokens_so_far()
    _run_dry(engine)
    assert ending.result(5.0) == ref[0][:5]
    assert other.result(5.0) == ref[1]
    assert waiting.result(5.0) == ref[2]
    snap = engine.metrics.snapshot()
    assert (snap["rows_admitted"], snap["rows_admitted_in_flight"]) == (3, 1)
    assert snap["steps_dropped"] == 0
    assert snap["tokens_emitted"] == 5 + 8 + 8
    engine.close()
