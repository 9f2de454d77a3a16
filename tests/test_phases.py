"""Host phases on the profiler's clock (``observability.profiler.phase``):
the primitive, and the phases the engine step, the train loop and the
worker's actor call open — names, counts and nesting as docs/OBSERVABILITY.md
lists them.  A ``jax.profiler`` session on the CPU holds the ``/host:CPU``
plane, so all of it is checked here."""

import glob
import os
import subprocess
import sys
import time

import numpy as np
import pytest

import tpu_air
from tpu_air.observability.profiler import phase

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _phases(trace_dir):
    """Every ``layer.part`` event of the newest capture under ``trace_dir``,
    ordered by start: ``(name, start_ns, end_ns, {count: value})``.  Selected
    by name: the line is called ``python3`` whatever the thread is."""
    from jax.profiler import ProfileData

    path = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.split(".")[0] in ("engine", "train", "worker",
                                             "test"):
                    out.append((ev.name, ev.start_ns,
                                ev.start_ns + ev.duration_ns, dict(ev.stats)))
    return sorted(out, key=lambda p: p[1])


def _inside(phases, parent):
    return [p for p in phases
            if p is not parent and parent[1] <= p[1] and p[2] <= parent[2]]


def _histograms(before, after):
    """What ``stats()``' latency histograms gained between two snapshots:
    ``(count, sum in seconds)`` by name, the steps' by program too."""
    def gained(a, b):
        return (a["count"] - b.get("count", 0),
                a.get("sum", 0.0) - b.get("sum", 0.0))

    out = {k: gained(after[k], before[k]) for k in (
        "ttft_s", "queue_wait_s", "prefill_s", "step_latency_s")}
    out["by_program"] = {
        program: gained(h, before["step_latency_by_program_s"][program])
        for program, h in after["step_latency_by_program_s"].items()}
    return out


def test_phase_in_a_session_lands_on_the_host_plane_with_its_counts(tmp_path):
    import jax

    with phase("test.before", n=1):  # no session: nothing, and no error
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with phase("test.outer", live=37, batch=64, method="poll"):
            with phase("test.inner"):
                time.sleep(0.002)
    finally:
        jax.profiler.stop_trace()
    with phase("test.after", n=2):
        pass
    got = _phases(str(tmp_path))
    assert [p[0] for p in got] == ["test.outer", "test.inner"]
    outer, inner = got
    assert outer[3] == {"live": 37, "batch": 64, "method": "poll"}
    assert inner[3] == {}
    assert outer[1] <= inner[1] and inner[2] <= outer[2]
    assert inner[2] - inner[1] >= 2_000_000


def test_phase_without_jax_is_the_shared_noop_and_imports_nothing():
    code = (
        "import sys\n"
        "from tpu_air.observability.profiler import phase\n"
        "a, b = phase('x.y', n=1), phase('x.z')\n"
        "with a as got:\n"
        "    with b:\n"
        "        pass\n"
        "assert a is b and got is None, (a, b, got)\n"
        "assert 'jax' not in sys.modules\n"
        "import tpu_air.core.runtime\n"
        "assert 'jax' not in sys.modules\n"
        "print('inert')\n"
    )
    out = subprocess.run([sys.executable, "-c", code], cwd=_REPO,
                         capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert out.stdout.strip() == "inert"


@pytest.fixture(scope="module")
def engine_phases(tmp_path_factory):
    """A tiny ``T5Engine`` stepped by hand inside one session: five prompts
    with budgets 2..6 over two slots, so three of them wait for a slot and
    join a row that is decoding."""
    import jax
    import jax.numpy as jnp

    from tpu_air.engine import T5Engine, T5EngineConfig
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration

    cfg = T5Config.tiny()
    model = T5ForConditionalGeneration(cfg)
    ones = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ones, ones, ones[:, :4])["params"]
    engine = T5Engine(model, params,
                      T5EngineConfig(max_batch=2, max_input_len=8,
                                     max_new_tokens=6),
                      auto_start=False, name="t5-phase-test")
    rng = np.random.RandomState(0)
    prompts = [list(map(int, rng.randint(2, cfg.vocab_size, size=n)))
               for n in (3, 8, 5, 4, 6)]
    trace_dir = str(tmp_path_factory.mktemp("engine-trace"))
    # six token steps outside the trace: one whole accounting span, so the
    # spans inside the trace begin with it
    engine.generate(prompts[:2], max_new_tokens=6)
    before = engine.metrics.snapshot()
    jax.profiler.start_trace(trace_dir)
    try:
        streams = [engine.submit(p, max_new_tokens=2 + i)
                   for i, p in enumerate(prompts)]
        steps = 0
        while not engine.idle():
            engine.step()
            steps += 1
            assert steps < 100, "the engine failed to drain"
    finally:
        jax.profiler.stop_trace()
    tokens = sum(len(s.result(5.0)) for s in streams)
    after = engine.metrics.snapshot()
    engine.close()
    counted = {k: after[k] - before[k] for k in (
        "tokens_emitted", "steps_issued", "steps_ahead", "steps_dropped",
        "admissions", "rows_admitted", "rows_admitted_in_flight")}
    counted.update(_histograms(before, after), trace_dir=trace_dir)
    return _phases(trace_dir), tokens, counted


_EVENTS = ("engine.first_token", "engine.window_close")


def test_engine_step_holds_dispatch_readback_emit_in_order(engine_phases):
    phases, _, _ = engine_phases
    steps = [p for p in phases if p[0] == "engine.step"]
    assert len(steps) >= 5
    for st in steps:
        kids = [k for k in _inside(phases, st) if k[0] not in _EVENTS]
        # the next step goes out (at most once, and first), then the step
        # before it, if rows of it are live, is read back, then emitted
        read = ["engine.readback", "engine.emit"] if st[3]["live"] else []
        assert [k[0] for k in kids] == (
            ["engine.dispatch"] * st[3]["ahead"] + read)
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        # ``live`` and ``batch`` are the step READ: its live rows, the
        # prefix of the slots it ran over (both 0 where none was unread)
        assert 0 <= st[3]["live"] <= st[3]["batch"] <= 2
        assert (st[3]["batch"] == 0) == (st[3]["live"] == 0)
        if read:
            assert kids[-1][3] == {"emitted": st[3]["live"]}
        # first tokens and a span's close are said where rows are emitted
        events = [k for k in _inside(phases, st) if k[0] in _EVENTS]
        assert events == [k for k in events if _inside([k], kids[-1])]
        assert read or not events
    # nothing is unread when the first step goes out; budgets end these
    # streams, so only the last token step has nothing to issue ahead of it
    assert [st[3]["live"] for st in steps][0] == 0
    assert all(st[3]["live"] for st in steps[1:])
    assert [st[3]["ahead"] for st in steps] == [1] * (len(steps) - 1) + [0]
    # no phase per row or per token: a dispatch where a step was issued, a
    # read-back and an emit where one was read, one prefill an admission
    # round, one first token a request, one close a span, nothing else
    assert {p[0] for p in phases} == {
        "engine.prefill", "engine.step", "engine.dispatch",
        "engine.readback", "engine.emit", "engine.first_token",
        "engine.window_close"}
    by_name = {n: sum(p[0] == n for p in phases) for n in {p[0] for p in phases}}
    assert by_name["engine.dispatch"] == len(steps) - 1
    assert by_name["engine.readback"] == by_name["engine.emit"] == (
        len(steps) - 1)
    assert by_name["engine.first_token"] == 5
    assert by_name["engine.window_close"] == (len(steps) - 1) // 6


def test_engine_prefill_is_an_admission_round_and_emits_nothing(
        engine_phases):
    phases, tokens, counted = engine_phases
    prefills = [p for p in phases if p[0] == "engine.prefill"]
    # five requests, two slots: the first round takes two, then one a slot
    # as it comes free (an admit program a request); ``in_flight`` whether
    # other rows were decoding
    assert [p[3] for p in prefills] == [
        {"rows": 2, "queued": 3, "in_flight": 0},
        {"rows": 1, "queued": 2, "in_flight": 1},
        {"rows": 1, "queued": 1, "in_flight": 1},
        {"rows": 1, "queued": 0, "in_flight": 1}]
    assert (counted["admissions"], counted["rows_admitted"],
            counted["rows_admitted_in_flight"]) == (4, 5, 3)
    # nothing is read or emitted inside it, and it lies in no engine.step:
    # the admit program goes out and the row's first token is its first
    # step's, like any other token
    assert all(not _inside(phases, p) for p in prefills)
    steps = [p for p in phases if p[0] == "engine.step"]
    assert not any(_inside(prefills, st) for st in steps)
    emitted = sum(p[3]["emitted"] for p in phases if p[0] == "engine.emit")
    assert emitted == counted["tokens_emitted"] == tokens
    assert emitted == sum(st[3]["live"] for st in steps)


def test_engine_step_ahead_counts_are_the_engines_step_counters(
        engine_phases):
    phases, _, counted = engine_phases
    steps = [p for p in phases if p[0] == "engine.step"]
    dispatches = [p for p in phases if p[0] == "engine.dispatch"]
    # a dispatch phase is a step issued; it is ahead where a step was unread
    assert counted["steps_issued"] == len(dispatches) == sum(
        st[3]["ahead"] for st in steps)
    assert counted["steps_ahead"] == sum(
        st[3]["ahead"] for st in steps if st[3]["live"])
    # budgets end these streams: every issued step is read by one engine.step
    assert counted["steps_dropped"] == 0
    assert counted["step_latency_s"][0] == counted["steps_issued"]


def _first_token_counts_hold(phases, counted, chunks):
    """One ``engine.first_token`` a request submitted inside the session (the
    warm-up's two, outside it, left none), each with its four counts; their
    waits are the samples ``stats()`` took, to the microsecond a request the
    counts are cut to."""
    firsts = [p[3] for p in phases if p[0] == "engine.first_token"]
    assert len(firsts) == 5
    assert all(set(f) == {"queue_us", "prefill_us", "prompt", "chunks"}
               for f in firsts)
    assert sorted(f["prompt"] for f in firsts) == [3, 4, 5, 6, 8]
    assert all(f["chunks"] == chunks and f["queue_us"] >= 0
               and f["prefill_us"] > 0 for f in firsts)
    for part, hist in (("queue_us", "queue_wait_s"),
                       ("prefill_us", "prefill_s")):
        n, seconds = counted[hist]
        assert n == 5
        assert 0 <= 1e6 * seconds - sum(f[part] for f in firsts) < 5 + 1e-3
    # the two parts are the whole of it, request by request: one set of stamps
    assert counted["ttft_s"][0] == 5
    assert counted["ttft_s"][1] == pytest.approx(
        counted["queue_wait_s"][1] + counted["prefill_s"][1], abs=1e-9)


def test_engine_first_token_is_one_event_a_request_with_its_waits(
        engine_phases):
    phases, _, counted = engine_phases
    _first_token_counts_hold(phases, counted, chunks=1)
    # the first round's two rows were admitted by one reading of the clock
    # and emitted at one read-back; the other three waited for a slot
    firsts = [p[3] for p in phases if p[0] == "engine.first_token"]
    assert firsts[0]["prefill_us"] == firsts[1]["prefill_us"]
    assert min(f["queue_us"] for f in firsts[2:]) > max(
        f["queue_us"] for f in firsts[:2])
    # the slot engine has one kind of token-step program
    assert counted["by_program"] == {"decode": counted["step_latency_s"]}


def test_engine_window_close_counts_a_span_of_token_steps(engine_phases):
    """One ``engine.window_close`` every ``max_new_tokens`` (6) token steps
    read: ``live_row_steps`` is Σ ``live`` and ``row_steps`` Σ ``batch`` of
    those ``engine.step`` phases, ``rows`` the requests admitted meanwhile,
    ``us`` the first one's start to the last one's read-back."""
    phases, tokens, _ = engine_phases
    prefills = [p for p in phases if p[0] == "engine.prefill"]
    closes = [p for p in phases if p[0] == "engine.window_close"]
    reads = [p for p in phases if p[0] == "engine.step" and p[3]["live"]]
    readbacks = [p for p in phases if p[0] == "engine.readback"]
    dispatches = [p for p in phases if p[0] == "engine.dispatch"]
    assert len(reads) == 14 and len(closes) == 2
    began = dispatches[0][2]        # the first step's issue: nothing before it
    for k, close in enumerate(closes):
        c = close[3]
        assert set(c) == {"steps", "rows", "batch", "live_row_steps",
                          "row_steps", "us", "queued"}
        mine = reads[6 * k:6 * k + 6]
        assert _inside([close], mine[-1])
        assert (c["steps"], c["batch"]) == (6, 2)
        assert c["live_row_steps"] == sum(st[3]["live"] for st in mine)
        assert c["row_steps"] == sum(st[3]["batch"] for st in mine) == 12
        since = closes[k - 1][1] if k else 0
        assert c["rows"] == sum(p[3]["rows"] for p in prefills
                                if since < p[1] < close[1])
        # open to close on the engine's clock is the phases' own span
        ended = readbacks[6 * k + 5][2]
        assert abs(c["us"] * 1000 - (ended - began)) < 1_000_000
        began = ended
    # budgets 2..6 over two slots: 20 tokens in 14 steps, 2 of them after
    # the second span closed
    assert [c[3]["live_row_steps"] for c in closes] == [10, 8]
    assert [c[3]["rows"] for c in closes] == [4, 1]
    assert [c[3]["queued"] for c in closes] == [1, 0]
    assert tokens == 20 == 18 + sum(st[3]["live"] for st in reads[12:])


@pytest.fixture(scope="module")
def paged_phases(tmp_path_factory):
    """A tiny ``InferenceEngine`` stepped by hand through five prompts over
    two slots inside one session."""
    import jax
    import jax.numpy as jnp

    from tpu_air.engine import EngineConfig, InferenceEngine
    from tpu_air.models.lm import CausalLM, LMConfig

    cfg = LMConfig.tiny()
    model = CausalLM(cfg)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=32, max_new_tokens=6,
                     eos_token_id=None),
        auto_start=False, name="paged-phase-test")
    rng = np.random.RandomState(0)
    prompts = [list(map(int, rng.randint(2, cfg.vocab_size, size=n)))
               for n in (3, 8, 5, 4, 6)]
    trace_dir = str(tmp_path_factory.mktemp("paged-trace"))
    engine.generate(prompts[:2], max_new_tokens=2)  # compile outside the trace
    before = engine.metrics.snapshot()
    jax.profiler.start_trace(trace_dir)
    try:
        streams = [engine.submit(p, max_new_tokens=2 + i)
                   for i, p in enumerate(prompts)]
        steps = 0
        while not engine.idle():
            engine.step()
            steps += 1
            assert steps < 100, "the engine failed to drain"
    finally:
        jax.profiler.stop_trace()
    tokens = sum(len(s.result(5.0)) for s in streams)
    after = engine.metrics.snapshot()
    engine.close()
    counted = {k: after[k] - before[k] for k in (
        "tokens_emitted", "steps_issued", "steps_ahead", "steps_dropped",
        "chunks_fused", "chunks_alone", "mixed_steps", "chunk_pages_read",
        "chunk_pages_slot")}
    counted.update(_histograms(before, after), trace_dir=trace_dir)
    return _phases(trace_dir), tokens, counted


def test_paged_prefill_phases_count_the_pages_a_chunk_has_reached(
        paged_phases):
    """``engine.prefill`` counts ``pages`` = ``start // page_len + 1`` and
    ``slot_pages``, the pages a slot holds: the sums are the engine's
    ``chunk_pages_read`` / ``chunk_pages_slot`` (``chunk_page_visit_share``
    is their ratio over a traced run)."""
    phases, _, counted = paged_phases
    prefills = [p[3] for p in phases if p[0] == "engine.prefill"]
    assert prefills
    assert all(p["pages"] == p["start"] // p["tokens"] + 1 for p in prefills)
    assert len({p["slot_pages"] for p in prefills}) == 1
    assert all(1 <= p["pages"] <= p["slot_pages"] for p in prefills)
    assert sum(p["pages"] for p in prefills) == counted["chunk_pages_read"]
    assert (sum(p["slot_pages"] for p in prefills)
            == counted["chunk_pages_slot"])


def test_paged_phases_say_which_chunks_rode_a_step(paged_phases):
    """``engine.prefill`` counts ``chunks`` 1 and ``fused`` 0/1, the
    ``engine.step`` that carries the chunk ``chunk`` 1: the sums are the
    engine's ``chunks_fused`` / ``chunks_alone`` / ``mixed_steps``, and a
    fused chunk is made ready right before the step it goes out in."""
    phases, _, counted = paged_phases
    prefills = [p for p in phases if p[0] == "engine.prefill"]
    steps = [p for p in phases if p[0] == "engine.step"]
    assert all(p[3]["chunks"] == 1 for p in prefills)
    fused = [p for p in prefills if p[3]["fused"]]
    # the first prompt finds no step to ride, nor does one admitted when the
    # other slot's budget has just ended
    assert len(fused) == counted["chunks_fused"] == 3
    assert len(prefills) - len(fused) == counted["chunks_alone"] == 2
    carrying = [st for st in steps if st[3]["chunk"]]
    assert len(carrying) == counted["mixed_steps"] == len(fused)
    for p, st in zip(fused, carrying):
        between = [q for q in prefills + steps if p[2] <= q[1] < st[1]]
        assert p[2] <= st[1] and not between


def test_paged_step_holds_dispatch_readback_emit_in_order(paged_phases):
    phases, _, _ = paged_phases
    steps = [p for p in phases if p[0] == "engine.step"]
    assert len(steps) >= 8
    tokens = [p for p in phases if p[0] == "engine.first_token"]
    for st in steps:
        kids = [k for k in _inside(phases, st) if k not in tokens]
        names = [k[0] for k in kids]
        issued = names[:1] == ["engine.dispatch"]
        reads = [k for k in kids if k[0] == "engine.readback"]
        # the next step goes out (at most once, and first); then the step
        # before it is read back and emitted; then the first tokens of the
        # prompts whose last chunk went out this iteration, the same way
        assert names == (["engine.dispatch"] * issued
                         + ["engine.readback", "engine.emit"] * len(reads))
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        firsts = [r for r in reads if "first" in r[3]]
        assert len(firsts) <= 1 and reads[len(reads) - len(firsts):] == firsts
        # ahead: a step went out while the one before it was still unread
        assert st[3]["ahead"] == int(issued and len(reads) > len(firsts))
        assert 1 <= st[3]["live"] <= st[3]["batch"] == 2
        if len(reads) > len(firsts):
            assert kids[issued + 1][3] == {"emitted": st[3]["live"]}
        if firsts:
            assert kids[-1][3] == {"emitted": firsts[0][3]["first"]}
        # the first tokens are said where they are emitted, and nowhere else
        assert len(_inside(tokens, st)) == sum(r[3]["first"] for r in firsts)
        assert _inside(tokens, st) == (_inside(tokens, kids[-1])
                                       if firsts else [])
    # budgets 2..6 over two slots: only the very first step finds nothing
    # in flight, and the last read has nothing left to issue
    ahead = [st[3]["ahead"] for st in steps]
    assert ahead == [0] + [1] * (len(steps) - 2) + [0]
    assert {p[0] for p in phases} == {
        "engine.prefill", "engine.step", "engine.dispatch",
        "engine.readback", "engine.emit", "engine.first_token"}


def test_paged_prefill_is_one_phase_a_chunk_and_holds_no_read(paged_phases):
    phases, tokens, counted = paged_phases
    prefills = [p for p in phases if p[0] == "engine.prefill"]
    # five prompts of one chunk each; the chunk is issued, not waited for:
    # its first token is read inside the engine.step that follows
    assert [(p[3]["tokens"], p[3]["start"]) for p in prefills] == [(16, 0)] * 5
    assert not any(_inside(phases, p) for p in prefills)
    steps = [p for p in phases if p[0] == "engine.step"]
    assert not any(_inside(phases, st) and st[1] <= p[1] <= st[2]
                   for st in steps for p in prefills)
    emitted = sum(p[3]["emitted"] for p in phases if p[0] == "engine.emit")
    first = sum(p[3]["first"] for p in phases
                if p[0] == "engine.readback" and "first" in p[3])
    assert first == 5 and emitted == counted["tokens_emitted"] == tokens


def test_paged_step_ahead_counts_are_the_engines_step_counters(paged_phases):
    phases, _, counted = paged_phases
    steps = [p for p in phases if p[0] == "engine.step"]
    dispatches = [p for p in phases if p[0] == "engine.dispatch"]
    # every issued step is one engine.dispatch; all but the first went out
    # ahead; budgets end these streams, so every issued step is read
    assert counted["steps_issued"] == len(dispatches)
    assert counted["steps_ahead"] == sum(st[3]["ahead"] for st in steps) == (
        len(dispatches) - 1)
    assert counted["steps_dropped"] == 0


def test_paged_first_token_is_one_event_a_request_with_its_waits(
        paged_phases):
    phases, _, counted = paged_phases
    _first_token_counts_hold(phases, counted, chunks=1)


def test_paged_steps_say_which_program_was_issued_and_which_read(
        paged_phases):
    """``engine.step`` counts ``steps`` 1 where the iteration issued a step
    (0 where it only read): sum(chunk) / sum(steps) is the engine's
    ``mixed_steps`` / ``steps_issued``.  Every token-step ``engine.readback``
    counts ``mixed``, the program of the step it READS (issued an iteration
    earlier), and ``stats()`` splits the step samples the same way."""
    phases, _, counted = paged_phases
    steps = [p for p in phases if p[0] == "engine.step"]
    assert all(st[3]["steps"] in (0, 1) and st[3]["chunk"] <= st[3]["steps"]
               for st in steps)
    assert sum(st[3]["steps"] for st in steps) == counted["steps_issued"]
    assert sum(st[3]["chunk"] for st in steps) == counted["mixed_steps"] == 3
    for st in steps:
        issued = [k for k in _inside(phases, st) if k[0] == "engine.dispatch"]
        assert st[3]["steps"] == len(issued)
    reads = [p for p in phases if p[0] == "engine.readback"]
    token_steps = [r for r in reads if "first" not in r[3]]
    assert all(set(r[3]) == {"mixed"} and r[3]["mixed"] in (0, 1)
               for r in token_steps)
    assert all("mixed" not in r[3] for r in reads if "first" in r[3])
    # a step is read in the iteration after the one that issued it
    issued_mixed = [st[3]["chunk"] for st in steps if st[3]["steps"]]
    assert [r[3]["mixed"] for r in token_steps] == issued_mixed
    by_program = counted["by_program"]
    assert by_program["mixed"][0] == sum(r[3]["mixed"] for r in token_steps)
    assert by_program["decode"][0] == len(token_steps) - by_program["mixed"][0]
    assert (by_program["decode"][0] + by_program["mixed"][0]
            == counted["step_latency_s"][0])
    assert by_program["decode"][1] + by_program["mixed"][1] == pytest.approx(
        counted["step_latency_s"][1], abs=1e-9)


def _read_metric(monkeypatch, tmp_path, trace_dir, name):
    """A per-layer metric of ``BENCHMARK.json`` read from a real capture,
    laid where the benchmark's traced run leaves its own."""
    import shutil
    from types import SimpleNamespace

    from jax.profiler import ProfileData

    from benchmark import harness, manifest, xplane

    bench = manifest.Benchmark()
    monkeypatch.setattr(manifest, "REPO", str(tmp_path))
    shutil.copytree(trace_dir, os.path.join(
        str(tmp_path), harness.TRACE_DIR, "cell"), dirs_exist_ok=True)
    how = bench.load_json("layer_metrics", name + ".json")
    # a CPU capture has no device plane, so the harness's own reduction
    # gives nothing: the readers get the host events it would have kept (most
    # ask only whether the run was traced)
    host = [(ev.name, ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
            for plane in ProfileData.from_file(
                xplane.find_xplane(trace_dir)).planes
            if plane.name.startswith("/host:")
            for line in plane.lines for ev in line.events
            if ev.duration_ns > 0]
    trace = xplane.TraceSummary({}, host, (0.0, 0.0))
    return bench.module("readers", how["reader"]).read(
        SimpleNamespace(trace=trace, facts={}), **how["args"])


def test_the_benchmarks_readers_read_the_slot_engines_events(
        engine_phases, monkeypatch, tmp_path):
    """Every ``program_span`` metric of ``t5large-serve`` that a CPU capture
    can carry, read through its own metric file from a real capture of the
    engine: none returns None.  (``engine_idle_host_ms`` and
    ``engine_idle_readback_ms`` lay device gaps against these phases and
    need a device plane: the cell's traced run on the chip holds them.)"""
    phases, _, counted = engine_phases
    firsts = [p[3] for p in phases if p[0] == "engine.first_token"]
    steps = [p for p in phases if p[0] == "engine.step"]
    def read(name):
        return _read_metric(monkeypatch, tmp_path, counted["trace_dir"], name)

    assert read("engine_window_live_row_share") == pytest.approx(
        100.0 * 18 / 24)
    assert read("engine_live_row_share") == pytest.approx(100.0 * 20 / 28)
    readbacks = [p for p in phases if p[0] == "engine.readback"]
    host = sorted(
        (b[1] - a[1] - sum(r[2] - r[1] for r in _inside(readbacks, a))) / 1e6
        for a, b in zip(steps, steps[1:])
        if not any(a[1] <= p[1] < b[1] for p in phases
                   if p[0] == "engine.prefill"))
    assert host[0] <= read("engine_host_ms_p50") <= host[-1]
    waits = sorted(f["queue_us"] for f in firsts)
    assert read("engine_queue_wait_ms_p50") == pytest.approx(waits[2] / 1000.0)
    assert waits[3] / 1000.0 <= read("engine_queue_wait_ms_p95") <= (
        waits[4] / 1000.0)
    assert read("engine_first_prefill_ms_p50") == pytest.approx(
        sorted(f["prefill_us"] for f in firsts)[2] / 1000.0)
    # it issues no mixed step and marks no program: those read nothing here
    assert read("engine_mixed_step_share") is None
    assert read("engine_mixed_step_ms_p50") is None


def test_the_benchmarks_readers_read_the_paged_engines_events(
        paged_phases, monkeypatch, tmp_path):
    phases, _, counted = paged_phases
    def read(name):
        return _read_metric(monkeypatch, tmp_path, counted["trace_dir"], name)

    assert read("engine_mixed_step_share") == pytest.approx(
        100.0 * counted["mixed_steps"] / counted["steps_issued"])
    ends = [(p[2], p[3]["mixed"]) for p in phases
            if p[0] == "engine.readback" and "mixed" in p[3]]
    for mixed, name in ((0, "engine_decode_step_ms_p50"),
                        (1, "engine_mixed_step_ms_p50")):
        gaps = sorted(b[0] - a[0] for a, b in zip(ends, ends[1:])
                      if b[1] == mixed)
        assert gaps[0] / 1e6 <= read(name) <= gaps[-1] / 1e6
    assert read("engine_window_live_row_share") is None


def test_train_loop_phases_per_step_and_epoch(air, tmp_path):
    """``t5_train_loop`` in this process, one epoch of three steps."""
    import jax

    from tpu_air import data as tad
    from tpu_air.models.t5 import T5Config
    from tpu_air.train import TrainingArguments, session
    from tpu_air.train.t5_trainer import t5_train_loop

    ndev = len(jax.devices())
    rng = np.random.RandomState(0)
    rows = [{"input_ids": rng.randint(2, 300, size=12),
             "attention_mask": np.ones(12, np.int64),
             "labels": rng.randint(2, 300, size=6)}
            for _ in range(3 * ndev)]
    sess = session.Session(str(tmp_path / "run"),
                           datasets={"train": tad.from_items(rows)}, sinks=[])
    session._set_active(sess)
    config = {"model_config": T5Config.tiny(),
              "training_args": TrainingArguments(
                  per_device_train_batch_size=1, num_train_epochs=1,
                  evaluation_strategy="no", save_strategy="no")}
    jax.profiler.start_trace(str(tmp_path / "trace"))
    try:
        t5_train_loop(config)
    finally:
        jax.profiler.stop_trace()
        session._set_active(None)
    assert sess.history[-1]["steps"] == 3
    phases = _phases(str(tmp_path / "trace"))
    inputs = [p for p in phases if p[0] == "train.input"]
    kids = [[k[0] for k in _inside(phases, p)] for p in inputs]
    # three steps, then the next() that found the data exhausted
    assert kids == [["train.next_batch", "train.collate",
                     "train.put_batch"]] * 3 + [["train.next_batch"]]
    assert [p[3]["step"] for p in inputs] == [0, 1, 2, 3]
    assert [p[3] for p in phases if p[0] == "train.dispatch"] == [
        {"step": 0}, {"step": 1}, {"step": 2}]
    syncs = [p for p in phases if p[0] == "train.epoch_sync"]
    assert [p[3] for p in syncs] == [{"epoch": 1}]
    assert syncs[0][1] >= max(p[2] for p in phases if p[0] != "train.epoch_sync")


class _Traced:
    """An actor that opens and closes a profiler session in its own worker."""

    def start(self, trace_dir):
        import jax

        jax.profiler.start_trace(trace_dir)
        return True

    def poll(self, i):
        return i

    def stop(self):
        import jax

        jax.profiler.stop_trace()
        return True


def test_worker_actor_task_one_phase_a_call_with_its_method(air, tmp_path):
    actor = tpu_air.remote(_Traced).remote()
    assert tpu_air.get(actor.start.remote(str(tmp_path)))
    assert tpu_air.get([actor.poll.remote(i) for i in range(7)]) == list(
        range(7))
    assert tpu_air.get(actor.stop.remote())
    calls = [p for p in _phases(str(tmp_path)) if p[0] == "worker.actor_task"]
    # start's own phase began before the session and stop's ended after it:
    # the session holds the calls in between, whole
    assert [p[3] for p in calls] == [{"method": "poll"}] * 7
    assert all(a[2] <= b[1] for a, b in zip(calls, calls[1:]))


class _SlowCapture:
    """An actor whose worker writes a capture out slowly on a thread of its
    own, the way the benchmark's replica does: a real session, and a
    ``stop_trace`` made to take ``seconds`` (on a v5e host it takes a minute
    for two seconds of a 3 ms decode step; here the lock is held as long),
    and then to go on for ``after`` seconds more with the ``.xplane.pb``
    written (there: minutes, on the trace viewer's ``trace.json.gz``)."""

    def capture(self, trace_dir, seconds, linger=False, after=0.0):
        import threading
        import time

        import jax

        if linger:
            # a worker that holds a TPU rarely exits by itself: a thread
            # that is not a daemon keeps this one from it the same way
            threading.Thread(target=time.sleep, args=(90.0,)).start()

        def run():
            jax.profiler.start_trace(trace_dir)
            state = jax._src.profiler._profile_state
            stop, held = state.profile_session.stop_and_export, state.lock

            class Slow:
                def stop_and_export(self, log_dir):
                    time.sleep(seconds)
                    stop(log_dir)
                    time.sleep(after)

            state.profile_session = Slow()
            started.set()
            jax.profiler.stop_trace()
            assert not held.locked()

        started = threading.Event()
        threading.Thread(target=run, daemon=True).start()
        return started.wait(30.0)


@pytest.mark.parametrize("case", ["writing", "writing-and-lingering",
                                  "written-and-converting", "idle"])
def test_a_worker_told_to_exit_lets_its_capture_finish(air, tmp_path, case):
    """``kill`` of an actor whose worker is inside ``jax.profiler.stop_trace``
    on another thread (PR 57: the traced run of ``t5large-serve`` lost its
    capture to the replica's exit, twice): the worker stays until the
    ``.xplane.pb`` is written and the driver waits for that long and no
    longer, whatever ``stop_trace`` goes on to, also where the worker
    would not exit by itself afterwards (it is stopped as ever then); with no
    capture being written it is gone within the grace it always had."""
    import glob

    from tpu_air.core.remote import kill

    actor = tpu_air.remote(_SlowCapture).remote()
    if case == "idle":
        assert tpu_air.get(actor.capture.remote(str(tmp_path), 0.0))
        time.sleep(1.0)
    else:
        assert tpu_air.get(actor.capture.remote(
            str(tmp_path), 5.0, linger=case.endswith("lingering"),
            after=60.0 if case.endswith("converting") else 0.0))
    t0 = time.monotonic()
    kill(actor)
    took = time.monotonic() - t0
    (written,) = glob.glob(os.path.join(
        str(tmp_path), "plugins", "profile", "*", "*.xplane.pb"))
    assert took < 3.0 if case == "idle" else 4.0 < took < 30.0
    # what the wait ends on: the file's top-level fields end where it does
    from tpu_air.observability.profiler import _whole_proto

    assert _whole_proto(written)
    cut = tmp_path / "cut.xplane.pb"
    cut.write_bytes(open(written, "rb").read()[:-7])
    assert not _whole_proto(str(cut))
