"""tpu_air.engine — continuous-batching online inference.

Layers under test:
  * scheduler / slot-manager host logic (no device work);
  * the CPU token-parity gate: engine emitted tokens must be TOKEN-IDENTICAL
    to offline greedy ``generate()`` on the same prompts, for burst,
    staggered and trickle arrival schedules (ISSUE acceptance anchor);
  * EOS + budget retirement and slot reuse;
  * streaming + backpressure semantics;
  * metrics / dashboard export;
  * the T5 prefill/decode-step entry points;
  * EngineDeployment over HTTP (503 on overload).
"""

import json
import time
import urllib.error
import urllib.request

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_air.engine import (
    EngineConfig,
    EngineOverloadedError,
    InferenceEngine,
    Request,
    ResponseStream,
    Scheduler,
    SlotManager,
)
from tpu_air.models.lm import CausalLM, LMConfig
from tpu_air.models.lm.generate import generate as lm_generate

import _mixed_step_cases

PORT = 8127


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


def _offline(model, params, prompt, max_new, eos):
    """Reference: offline greedy generate, truncated after the first EOS
    (inclusive — the engine emits the EOS id then retires)."""
    out = np.asarray(
        lm_generate(model, params, [prompt], max_new_tokens=max_new,
                    eos_token_id=eos)
    )[0].tolist()
    if eos is not None and eos in out:
        out = out[: out.index(eos) + 1]
    return out


def _run_schedule(engine, arrivals):
    """Drive a manual-step engine through a deterministic arrival schedule:
    ``arrivals`` is a list of (engine_step, prompt); returns streams in
    submission order."""
    order = sorted(range(len(arrivals)), key=lambda i: arrivals[i][0])
    streams = {}
    t, i = 0, 0
    while i < len(order) or not engine.idle():
        while i < len(order) and arrivals[order[i]][0] <= t:
            streams[order[i]] = engine.submit(arrivals[order[i]][1])
            i += 1
        engine.step()
        t += 1
    return [streams[j] for j in range(len(arrivals))]


# ---------------------------------------------------------------------------
# host-side units: scheduler, slots, config
# ---------------------------------------------------------------------------


def _req(rid, prompt=(1, 2, 3)):
    return Request(request_id=rid, prompt=list(prompt), max_new_tokens=4,
                   stream=ResponseStream(rid))


def test_scheduler_fifo_order():
    s = Scheduler(EngineConfig(max_queue=16))
    for rid in range(5):
        s.submit(_req(rid))
    assert [r.request_id for r in s.pop_admissible(3)] == [0, 1, 2]
    assert [r.request_id for r in s.pop_admissible(8)] == [3, 4]
    assert s.depth() == 0


def test_scheduler_backpressure():
    s = Scheduler(EngineConfig(max_queue=2))
    s.submit(_req(0))
    s.submit(_req(1))
    with pytest.raises(EngineOverloadedError):
        s.submit(_req(2))
    # draining reopens admission
    assert len(s.pop_admissible(2)) == 2
    s.submit(_req(3))
    assert s.depth() == 1


def test_slot_manager_lowest_row_and_reuse():
    m = SlotManager(3)
    a, b, c = m.acquire(), m.acquire(), m.acquire()
    assert (a.index, b.index, c.index) == (0, 1, 2)
    assert m.free_count() == 0 and m.occupancy() == 3
    m.release(b)
    assert m.free_count() == 1
    assert m.acquire().index == 1  # freed row is handed out again
    m.release(a)
    m.release(c)
    assert m.acquire().index == 0  # lowest free row first


def test_engine_config_rejects_removed_options():
    """One KV layout: a caller that still names the removed layout options
    is told so at construction (a ``TypeError``), not silently ignored."""
    with pytest.raises(TypeError, match="kv_mode"):
        EngineConfig(kv_mode="slab")
    with pytest.raises(TypeError, match="prefill_buckets"):
        EngineConfig(prefill_buckets=(8, 16))
    cfg = EngineConfig(slot_len=48, page_len=16)
    assert (cfg.pages_per_slot(), cfg.pool_pages()) == (3, 8 * 3 + 1)


# ---------------------------------------------------------------------------
# the parity gate: engine tokens == offline greedy generate tokens
# ---------------------------------------------------------------------------


@pytest.mark.parametrize(
    "name,arrival_of",
    [
        ("burst", lambda i: 0),         # all at once, > num_slots deep
        ("staggered", lambda i: i),     # one new request per engine step
        ("trickle", lambda i: 4 * i),   # arrivals slower than completions
    ],
)
def test_token_parity_with_offline_generate(lm, name, arrival_of):
    """ISSUE acceptance: token-identical to offline greedy generate under
    deterministic scheduling, for three arrival shapes."""
    cfg, model, params = lm
    prompts = _prompts(seed=7, n=7)
    max_new = 10
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=3, slot_len=64, max_new_tokens=max_new),
        auto_start=False,
    )
    arrivals = [(arrival_of(i), p) for i, p in enumerate(prompts)]
    streams = _run_schedule(engine, arrivals)
    for p, s in zip(prompts, streams):
        assert s.result(5.0) == _offline(model, params, p, max_new, None)
    engine.close()


@pytest.mark.parametrize("arrival_of", [lambda i: 0, lambda i: i,
                                        lambda i: 4 * i],
                         ids=["burst", "staggered", "trickle"])
def test_token_parity_where_every_token_depends_on_the_context(
        lm_live, arrival_of):
    """The same three arrival shapes on weights whose every token depends on
    the whole context, with an EOS some rows reach mid-stream: rows join a
    step from the device, end one step before the host learns of it, and
    hand their pages on, and each stream is still offline ``generate``'s."""
    cfg, model, params = lm_live
    prompts = _prompts(seed=7, n=7)
    max_new = 10
    refs = [_offline(model, params, p, max_new, None) for p in prompts]
    eos = refs[1][4]
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=3, slot_len=64, max_new_tokens=max_new,
                     page_len=8, eos_token_id=eos),
        auto_start=False,
    )
    streams = _run_schedule(
        engine, [(arrival_of(i), p) for i, p in enumerate(prompts)])
    want = [_offline(model, params, p, max_new, eos) for p in prompts]
    assert [s.result(5.0) for s in streams] == want
    assert 0 < sum(len(w) < max_new for w in want) < len(want)
    snap = engine.metrics.snapshot()
    assert snap["tokens_emitted"] == sum(map(len, want))
    assert snap["steps_ahead"] >= snap["steps_issued"] - 3
    engine.close()


def test_token_parity_with_eos_retirement(lm):
    """Early-stop path: rows retire the step they emit EOS (id included),
    matching offline generate truncated after the first EOS."""
    cfg, model, params = lm
    prompts = _prompts(seed=11, n=6)
    max_new = 12
    # a realistic EOS: a token the greedy chain actually emits mid-stream
    ref = _offline(model, params, prompts[0], max_new, None)
    eos = ref[2]
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=max_new,
                     eos_token_id=eos),
        auto_start=False,
    )
    streams = _run_schedule(engine, [(i, p) for i, p in enumerate(prompts)])
    retired_early = 0
    for p, s in zip(prompts, streams):
        want = _offline(model, params, p, max_new, eos)
        assert s.result(5.0) == want
        if len(want) < max_new:
            retired_early += 1
    assert retired_early > 0, "EOS never triggered — test exercises nothing"
    engine.close()


@pytest.mark.parametrize("case", sorted(_mixed_step_cases.CASES))
def test_mixed_step(lm, case):
    """One program for an iteration's prefill chunk and its decode step
    (tests/_mixed_step_cases.py), on the dense model against offline
    ``generate``."""
    cfg, model, params = lm

    def check(prompt, tokens):
        assert tokens == _offline(model, params, prompt, len(tokens), None)

    _mixed_step_cases.CASES[case](model, params, check)


def test_slot_reuse_burst_deeper_than_pool(lm):
    """7 requests through a 2-slot pool: every slot is reused, and the
    engine drains completely (no stuck slots, no lost requests)."""
    cfg, model, params = lm
    prompts = _prompts(seed=3, n=7)
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=6),
        auto_start=False,
    )
    streams = [engine.submit(p) for p in prompts]
    steps = 0
    while not engine.idle():
        engine.step()
        steps += 1
        assert steps < 500, "engine failed to drain"
    assert engine.slots.free_count() == 2
    for p, s in zip(prompts, streams):
        assert s.result(5.0) == _offline(model, params, p, 6, None)
    assert engine.metrics.snapshot()["requests_completed"] == 7
    engine.close()


# -- one step in flight: the paged loop reads a step after the next went out --


def _slot_pages(engine, prompt, n_pages):
    """The physical pages of the slot that holds ``prompt``, or None."""
    for slot in engine.slots.active_slots():
        if slot.request.prompt == prompt:
            return set(map(int, engine.pool.block_table[slot.index][:n_pages]))
    return None


@pytest.mark.parametrize("another_row", [False, True],
                         ids=["the-only-row", "another-row-runs-on"])
def test_eos_with_a_step_in_flight_hands_its_pages_to_the_next_request(
        lm_live, another_row):
    """A row that ends on EOS in step N rides step N+1, which went out before
    N was read.  Its pages are released after that and given to the request
    that waited for them (the pool is one request deep): the ride-along write
    and every later step must leave the newcomer's K/V alone, so it streams
    what a fresh engine streams."""
    cfg, model, params = lm_live
    max_new = 10
    ending, other = [5, 9, 2, 7], [11, 3, 8, 1, 6]
    late = _prompts(seed=13, n=1, lo=11, hi=12)[0]        # 11 tokens
    ref = _offline(model, params, ending, max_new, None)
    eos = ref[2]
    assert eos not in ref[:2]
    assert eos not in _offline(model, params, other, max_new, None)
    assert len(_offline(model, params, late, max_new, eos)) > 3
    # pages of 8: `ending` and `other` take 2 each, `late` 3 (worst case)
    pages = 1 + (4 if another_row else 2) + 1
    ecfg = EngineConfig(num_slots=3, slot_len=32, max_new_tokens=max_new,
                        page_len=8, num_pages=pages, eos_token_id=eos)
    engine = InferenceEngine(model, params, ecfg, auto_start=False)
    first = [engine.submit(p) for p in ([ending, other] if another_row
                                        else [ending])]
    waiting = engine.submit(late)
    engine.step()
    held = _slot_pages(engine, ending, 2)
    assert held and _slot_pages(engine, late, 3) is None  # no room yet
    taken, steps = None, 0
    while not engine.idle():
        engine.step()
        taken = taken or _slot_pages(engine, late, 3)
        steps += 1
        assert steps < 200, "engine failed to drain"
    assert first[0].result(5.0) == ref[:3]
    assert taken & held, "the newcomer was not given the freed pages"
    snap = engine.metrics.snapshot()
    # alone, the step that went out before the EOS was read has no reader
    assert snap["steps_dropped"] == (0 if another_row else 1)
    engine.close()
    fresh = InferenceEngine(model, params, ecfg, auto_start=False)
    want = fresh.generate([late])[0]
    fresh.close()
    assert waiting.result(5.0) == want == _offline(model, params, late,
                                                   max_new, eos)
    if another_row:
        assert first[1].result(5.0) == _offline(model, params, other,
                                                max_new, eos)


@pytest.mark.parametrize("case", ["budget-of-one", "first-token-is-eos"])
def test_a_row_that_ends_on_its_first_token_never_streams_a_second(lm_live, case):
    """The first token is read after the step behind it went out.  A budget
    of one is host state: the row joins no step.  An EOS is learnt at the
    read: the row rode the one step, whose output is dropped."""
    cfg, model, params = lm_live
    prompt, nxt = _prompts(seed=17, n=2)
    first = _offline(model, params, prompt, 1, None)
    eos = first[0] if case == "first-token-is-eos" else None
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=8,
                     eos_token_id=eos),
        auto_start=False,
    )
    stream = engine.submit(prompt, 1 if eos is None else 8)
    while not engine.idle():
        engine.step()
    assert stream.result(5.0) == first
    snap = engine.metrics.snapshot()
    rode = int(eos is not None)
    assert (snap.get("steps_issued", 0),
            snap.get("steps_dropped", 0)) == (rode, rode)
    assert snap["tokens_emitted"] == 1 and snap["requests_completed"] == 1
    # the slot and its pages are free again and the loop goes on
    assert engine.generate([nxt], 6) == [_offline(model, params, nxt, 6, eos)]
    engine.close()


def _with_a_step_unread(lm, prompt, name):
    cfg, model, params = lm
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=10, page_len=8,
                     eos_token_id=None),
        auto_start=False, name=name,
    )
    stream = engine.submit(prompt)
    engine.step()
    engine.step()
    assert engine._inflight is not None and not engine.idle()
    return engine, stream


def test_migrate_out_settles_the_step_in_flight_first(lm_live):
    cfg, model, params = lm_live
    prompt = _prompts(seed=19, n=1)[0]
    src, stream = _with_a_step_unread(lm_live, prompt, "settle-migrate-src")
    seen = len(stream.tokens_so_far())
    (payload,) = src.migrate_out()
    # the unread step was read and emitted, not lost and not dropped: the
    # cursor shipped is the device's
    assert src._inflight is None
    assert len(payload["streamed"]) == seen + 1
    assert payload["pos"] == len(prompt) + len(payload["streamed"]) - 1
    assert src.metrics.snapshot()["steps_dropped"] == 0
    dst = InferenceEngine(model, params, src.config, auto_start=False,
                          name="settle-migrate-dst")
    landed = dst.submit_migrated(payload)
    while not dst.idle():
        dst.step()
    assert landed.result(5.0) == _offline(model, params, prompt, 10, None)
    src.close()
    dst.close()


def test_swap_params_settles_the_step_in_flight_first(lm_live):
    cfg, model, params = lm_live
    prompt = _prompts(seed=23, n=1)[0]
    engine, stream = _with_a_step_unread(lm_live, prompt, "settle-swap")
    seen = len(stream.tokens_so_far())
    engine.swap_params(jax.tree_util.tree_map(np.asarray, params), version=2)
    # the step in flight kept the weights it was issued with and was read
    # under the lock; the next one is issued with the new tree
    assert engine._inflight is None
    assert len(stream.tokens_so_far()) == seen + 1
    while not engine.idle():
        engine.step()
    assert stream.result(5.0) == _offline(model, params, prompt, 10, None)
    snap = engine.metrics.snapshot()
    assert snap["steps_dropped"] == 0
    # every step but the first and the one after the settle went out ahead
    assert snap["steps_issued"] - snap["steps_ahead"] == 2
    engine.close()


def test_an_unread_step_keeps_the_engine_from_idle_and_drained(lm_live):
    cfg, model, params = lm_live
    prompt = _prompts(seed=29, n=1)[0]
    engine, stream = _with_a_step_unread(lm_live, prompt, "settle-drain")
    engine.drain()
    assert engine.draining and not engine.drained() and not engine.idle()
    steps = 0
    while engine.step():
        steps += 1
        assert steps < 50, "engine failed to drain"
    assert engine._inflight is None and engine.idle() and engine.drained()
    assert stream.result(5.0) == _offline(model, params, prompt, 10, None)
    engine.close()


def test_submit_validation_and_backpressure(lm):
    cfg, model, params = lm
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=1, slot_len=32, max_new_tokens=8, max_queue=2),
        auto_start=False,
    )
    with pytest.raises(ValueError):
        engine.submit([])
    with pytest.raises(ValueError):
        engine.submit(list(range(1, 30)), max_new_tokens=8)  # 29 + 8 > 32
    engine.submit([1, 2, 3])
    engine.submit([4, 5, 6])
    with pytest.raises(EngineOverloadedError):
        engine.submit([7, 8, 9])
    assert engine.metrics.snapshot()["requests_rejected"] == 1
    while not engine.idle():
        engine.step()
    engine.close()


def test_streaming_background_thread(lm):
    """Tokens arrive on the stream while the request is still decoding —
    the per-token streaming contract, driven by the background loop."""
    cfg, model, params = lm
    with InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=8),
    ) as engine:
        prompt = _prompts(seed=5, n=1)[0]
        got = list(engine.submit(prompt))  # iterates until retirement
        assert got == _offline(model, params, prompt, 8, None)
        # convenience batch API on the same live engine
        outs = engine.generate(_prompts(seed=6, n=3), max_new_tokens=5)
        assert [len(o) for o in outs] == [5, 5, 5]


def test_metrics_and_dashboard_export(lm):
    cfg, model, params = lm
    from tpu_air.observability.dashboard import _prometheus_text, engine_stats

    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=4),
        auto_start=False, name="engine-test-metrics",
    )
    engine.generate(_prompts(seed=9, n=3))
    snap = engine.metrics.snapshot()
    assert snap["requests_submitted"] == 3
    assert snap["requests_completed"] == 3
    assert snap["tokens_emitted"] == 12
    assert snap["slot_occupancy"] == 0 and snap["queue_depth"] == 0
    assert snap["ttft_s"]["count"] == 3
    assert snap["step_latency_s"]["count"] >= 1
    # dashboard surfaces: /api/engines payload + prometheus text
    assert "engine-test-metrics" in engine_stats()
    text = _prometheus_text()
    assert 'tpu_air_engine_tokens_emitted{engine="engine-test-metrics"} 12' in text
    engine.close()
    assert "engine-test-metrics" not in engine_stats()  # unregistered


def _tiny_engine(kind, lm, name):
    """A manual-step engine over two rows: ``InferenceEngine`` on the tiny
    LM, or ``T5Engine`` on the tiny T5."""
    if kind == "paged":
        _, model, params = lm
        return InferenceEngine(
            model, params,
            EngineConfig(num_slots=2, slot_len=64, max_new_tokens=4,
                         eos_token_id=None),
            auto_start=False, name=name)
    from tpu_air.engine import T5Engine, T5EngineConfig
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration

    model = T5ForConditionalGeneration(T5Config.tiny())
    ones = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ones, ones,
                        ones[:, :4])["params"]
    return T5Engine(model, params,
                    T5EngineConfig(max_batch=2, max_input_len=12,
                                   max_new_tokens=4),
                    auto_start=False, name=name)


def _watch(engine):
    """The requests the engine admits, in admission order, and the parts
    its ``record_ttft`` is given, as they happen."""
    admitted, parts = [], []
    pop, record = engine.scheduler.pop_admissible, engine.metrics.record_ttft

    def spy_pop(*a, **kw):
        out = pop(*a, **kw)
        admitted.extend(out)
        return out

    def spy_record(queue_wait_s, prefill_s, *a, **kw):
        parts.append((queue_wait_s, prefill_s))
        return record(queue_wait_s, prefill_s, *a, **kw)

    engine.scheduler.pop_admissible = spy_pop
    engine.metrics.record_ttft = spy_record
    return admitted, parts


@pytest.mark.parametrize("kind", ["paged", "t5"])
def test_one_set_of_stamps_splits_ttft_into_queue_wait_and_prefill(lm, kind):
    """Both engines admit through ``Scheduler.pop_admissible``, which stamps
    ``admitted_at`` on every request it hands out, with or without a
    profiler session or airtrace: ``stats()`` holds one ``queue_wait_s`` and
    one ``prefill_s`` sample a request, and the two are the request's TTFT."""
    engine = _tiny_engine(kind, lm, f"engine-test-stamps-{kind}")
    admitted, parts = _watch(engine)
    engine.generate(_prompts(seed=31, n=5), max_new_tokens=3)
    snap = engine.metrics.snapshot()
    engine.close()
    assert len(admitted) == len(parts) == 5
    for key in ("ttft_s", "queue_wait_s", "prefill_s"):
        assert snap[key]["count"] == 5
    assert snap["priority"]["interactive"]["queue_wait_s"]["count"] == 5
    assert snap["priority"]["batch"]["queue_wait_s"]["count"] == 0
    for req in admitted:
        assert req.submitted_at <= req.admitted_at < req.first_token_at
        assert not hasattr(req, "t_submit_ns")
        assert req.trace_ctx is None          # airtrace is off
    # request by request: the parts recorded are the stamps' differences and
    # sum to the request's TTFT
    want = sorted((r.admitted_at - r.submitted_at,
                   r.first_token_at - r.admitted_at) for r in admitted)
    assert sorted(parts) == want
    for (queue_wait, prefill), r in zip(sorted(parts), sorted(
            admitted, key=lambda r: r.admitted_at - r.submitted_at)):
        assert queue_wait + prefill == pytest.approx(
            r.first_token_at - r.submitted_at, abs=1e-9)
    assert snap["queue_wait_s"]["sum"] + snap["prefill_s"]["sum"] == (
        pytest.approx(snap["ttft_s"]["sum"], abs=1e-9))
    # five requests over two rows: the later ones waited for a row
    assert snap["queue_wait_s"]["max"] > snap["queue_wait_s"]["min"] >= 0
    # one reading a round: requests admitted together share the stamp
    rounds = {r.admitted_at for r in admitted}
    assert len(rounds) < 5


@pytest.mark.parametrize("kind", ["paged", "t5"])
def test_step_latency_is_split_by_the_program_read(lm, kind):
    """``step_latency_by_program_s`` holds every ``step_latency_s`` sample
    once, under the program the step READ was: ``T5Engine`` has one program,
    ``InferenceEngine``'s mixed steps are as many as it issued and read."""
    engine = _tiny_engine(kind, lm, f"engine-test-programs-{kind}")
    engine.generate(_prompts(seed=32, n=5), max_new_tokens=4)
    snap = engine.metrics.snapshot()
    engine.close()
    by_program = snap["step_latency_by_program_s"]
    assert sorted(by_program) == (["decode", "mixed"] if kind == "paged"
                                  else ["decode"])
    assert sum(h["count"] for h in by_program.values()) == (
        snap["step_latency_s"]["count"])
    assert sum(h.get("sum", 0.0) for h in by_program.values()) == (
        pytest.approx(snap["step_latency_s"]["sum"], abs=1e-9))
    if kind == "paged":
        # budgets end these streams: every issued step was read
        assert snap["steps_dropped"] == 0
        assert by_program["mixed"]["count"] == snap["mixed_steps"] >= 1
        assert snap["step_latency_s"]["count"] == snap["steps_issued"]


def test_reset_window_clears_the_new_histograms_too(lm):
    engine = _tiny_engine("paged", lm, "engine-test-reset")
    engine.generate(_prompts(seed=33, n=3), max_new_tokens=3)
    engine.metrics.reset_window()
    snap = engine.metrics.snapshot()
    engine.close()
    assert snap["queue_wait_s"]["count"] == snap["prefill_s"]["count"] == 0
    assert all(h["count"] == 0
               for h in snap["step_latency_by_program_s"].values())
    assert snap["priority"]["interactive"]["queue_wait_s"]["count"] == 0
    assert snap["requests_completed"] == 3       # counters stay


def test_prefilled_admission_reads_a_prefill_of_zero(lm):
    """A request whose prefill ran elsewhere (``submit_prefilled``) is
    stamped like any other; its first token is emitted at its admission, so
    its ``prefill_s`` sample is 0 and its TTFT here is its queue wait."""
    from tpu_air.engine.dist.kv_transfer import extract_kv_pages

    cfg, model, params = lm
    ecfg = EngineConfig(num_slots=2, slot_len=64, max_new_tokens=4,
                        page_len=8, eos_token_id=None)
    src = InferenceEngine(model, params, ecfg, auto_start=False,
                          name="engine-test-prefill-src")
    dst = InferenceEngine(model, params, ecfg, auto_start=False,
                          name="engine-test-prefill-dst")
    prompt = _prompts(seed=34, n=1, lo=9, hi=12)[0]
    stream = src.submit(prompt)
    while not stream.tokens_so_far():
        src.step()
    slot = src.slots.active_slots()[0]
    pages = extract_kv_pages(
        src.cache, src.pool.prompt_page_ids(slot.index, len(prompt)))
    first = stream.tokens_so_far()[0]
    admitted, parts = _watch(dst)
    got = dst.submit_prefilled(prompt, first, pages, 4)
    while not dst.idle():
        dst.step()
    assert got.result(5.0) == _offline(model, params, prompt, 4, None)
    snap = dst.metrics.snapshot()
    src.close()
    dst.close()
    (req,) = admitted
    assert req.first_token_at == req.admitted_at >= req.submitted_at
    assert parts == [(req.admitted_at - req.submitted_at, 0.0)]
    assert snap["prefill_s"]["count"] == snap["queue_wait_s"]["count"] == 1
    assert snap["prefill_s"]["max"] == 0.0
    assert snap["ttft_s"]["sum"] == pytest.approx(
        snap["queue_wait_s"]["sum"], abs=1e-12)


def test_engine_emits_connected_trace(lm):
    """A traced request through the engine yields a connected span tree at
    retirement: engine.request → queue_wait / prefill / decode, parented
    under the submitter's span, annotated with slot + occupancy."""
    cfg, model, params = lm
    from tpu_air.observability import tracing

    tracing.enable()
    tracing.recorder().clear()
    try:
        engine = InferenceEngine(
            model, params,
            EngineConfig(num_slots=2, slot_len=64, max_new_tokens=4),
            auto_start=False, name="engine-test-trace",
        )
        with tracing.span("client.generate") as root:
            engine.generate(_prompts(seed=13, n=2))
        engine.close()
        spans = tracing.recorder().for_trace(root.trace_id)
        by_name = {}
        for s in spans:
            by_name.setdefault(s.name, []).append(s)
        assert len(by_name.get("engine.request", [])) == 2
        assert len(by_name.get("engine.queue_wait", [])) == 2
        assert len(by_name.get("engine.prefill", [])) == 2
        assert len(by_name.get("engine.decode", [])) == 2
        req_span = by_name["engine.request"][0]
        assert req_span.parent_id == root.span_id
        req_ids = {s.span_id for s in by_name["engine.request"]}
        for child_name in ("engine.queue_wait", "engine.prefill", "engine.decode"):
            for child in by_name[child_name]:
                assert child.parent_id in req_ids
        for pf in by_name["engine.prefill"]:
            # paged default: prefill spans carry the chunk count and
            # prefix-cache outcome instead of the slab-era bucket
            assert "slot" in pf.attrs and "chunks" in pf.attrs
            assert "prefix_hit" in pf.attrs
            assert pf.attrs["chunks"] >= 1
            assert pf.attrs["prompt_len"] > 0
        for dc in by_name["engine.decode"]:
            assert dc.attrs["tokens"] == 4  # max_new_tokens
            assert 0 <= dc.attrs["slot"] < 2
            assert dc.attrs["occupancy"] >= 1
        # timeline ordering within one request
        assert req_span.start_ns <= by_name["engine.prefill"][0].start_ns
        assert by_name["engine.decode"][0].end_ns <= req_span.end_ns
    finally:
        tracing.disable()
        tracing.recorder().clear()


def test_engine_untraced_requests_cost_nothing(lm):
    """With tracing off, requests carry no carrier and the recorder stays
    empty (the zero-cost-when-off contract)."""
    cfg, model, params = lm
    from tpu_air.observability import tracing

    assert not tracing.enabled()
    tracing.recorder().clear()
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=3),
        auto_start=False, name="engine-test-notrace",
    )
    engine.generate(_prompts(seed=14, n=2))
    engine.close()
    assert len(tracing.recorder()) == 0


# ---------------------------------------------------------------------------
# T5 continuous-decode entry points
# ---------------------------------------------------------------------------


def test_t5_admit_and_slot_step_match_offline_generate():
    """The engine's two programs by hand: two prompts admitted into slots 2
    and 0 of four, one step apart, then steps over a prefix of the slots."""
    from tpu_air.models.t5 import (
        T5Config,
        T5ForConditionalGeneration,
        init_slot_state,
        make_t5_admit_fn,
        make_t5_slot_step_fn,
    )
    from tpu_air.models.t5.generate import generate as t5_generate

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    enc = jnp.ones((2, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), enc, jnp.ones_like(enc),
                        jnp.ones((2, 6), jnp.int32))["params"]
    ids = np.array([[4, 5, 6, 1, 0, 0], [9, 8, 7, 6, 5, 1]], np.int32)
    max_new = 6

    want = np.asarray(t5_generate(model, params, jnp.asarray(ids),
                                  max_new_tokens=max_new, early_stop=False))

    state, tok = init_slot_state(model, params, 4, max_new + 1, 6)
    admit = make_t5_admit_fn(model, 6)
    step = make_t5_slot_step_fn(model, 4)
    got = {2: [], 0: []}
    for t in range(max_new + 1):
        if t < 2:       # row t goes to slot (2, 0)[t] before step t
            slot = (2, 0)[t]
            n = int((ids[t] != cfg.pad_token_id).sum())
            state, tok = admit(params, state, tok, jnp.asarray(
                [[*ids[t], n, slot]], jnp.int32))
        state, tok = step(params, state, tok)
        for slot, row in got.items():
            if t >= (slot == 0) and len(row) < max_new:
                row.append(int(tok[slot]))
    np.testing.assert_array_equal([got[2], got[0]], want)


# ---------------------------------------------------------------------------
# serve integration: EngineDeployment + 503 backpressure
# ---------------------------------------------------------------------------


def _post(path, payload, port=PORT):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"},
        method="POST",
    )
    with urllib.request.urlopen(req, timeout=120) as resp:
        return resp.status, json.loads(resp.read())


def test_engine_deployment_http_and_overload_503(lm, air):
    from tpu_air import serve
    from tpu_air.serve import EngineDeployment
    from tpu_air.train import Checkpoint

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    try:
        serve.run(
            EngineDeployment.options(
                name="lm-engine", route_prefix="/engine"
            ).bind(ckpt, EngineConfig(num_slots=2, slot_len=64,
                                      max_new_tokens=6)),
            port=PORT,
        )
        prompts = _prompts(seed=13, n=3)
        status, out = _post("/engine", {"prompts": prompts,
                                        "max_new_tokens": 6})
        assert status == 200, out
        assert len(out["results"]) == 3
        for p, r in zip(prompts, out["results"]):
            assert r["tokens"] == _offline(model, params, p, 6, None)

        # backpressure: a zero-capacity admission queue rejects EVERY
        # submit — the replica-side EngineOverloadedError must cross the
        # actor boundary and surface as HTTP 503 (retry semantics), not 500
        serve.run(
            EngineDeployment.options(
                name="lm-engine-full", route_prefix="/engine-full"
            ).bind(ckpt, EngineConfig(num_slots=1, slot_len=64,
                                      max_new_tokens=4, max_queue=0)),
            port=PORT,
        )
        try:
            status, out = _post("/engine-full", {"prompts": [[1, 2, 3]]})
        except urllib.error.HTTPError as e:
            status, out = e.code, json.loads(e.read())
        assert status == 503, out
        assert "EngineOverloadedError" in out["error"]
    finally:
        serve.shutdown()


def test_engine_deployment_streaming_rpc(lm, air):
    """The submit/poll actor-RPC surface: cursor polling sees the token
    stream grow and terminate."""
    import tpu_air
    from tpu_air import serve
    from tpu_air.serve import EngineDeployment
    from tpu_air.train import Checkpoint

    cfg, model, params = lm
    ckpt = Checkpoint.from_model(model_config=cfg, params=params)
    try:
        h = serve.run(
            EngineDeployment.options(
                name="lm-engine-stream", route_prefix="/engine-stream"
            ).bind(ckpt, EngineConfig(num_slots=2, slot_len=64,
                                      max_new_tokens=8)),
            port=PORT,
        )
        prompt = _prompts(seed=17, n=1)[0]
        rid = tpu_air.get(h.method("submit")(prompt))
        toks, cursor = [], 0
        deadline = time.time() + 120  # replica-side jit compiles on first use
        while time.time() < deadline:
            out = tpu_air.get(h.method("poll")(rid, cursor))
            toks += out["tokens"]
            cursor = len(toks)
            if out["done"] and not out["tokens"]:
                break
            time.sleep(0.05)
        assert toks == _offline(model, params, prompt, 8, None)
        stats = tpu_air.get(h.method("stats")())
        assert stats["requests_completed"] >= 1
    finally:
        serve.shutdown()
