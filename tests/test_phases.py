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
    """A tiny ``T5Engine`` stepped by hand through three windows (five
    prompts, two rows a window) inside one session."""
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
        "tokens_emitted", "steps_issued", "steps_ahead", "steps_dropped")}
    return _phases(trace_dir), tokens, counted


def test_engine_step_holds_dispatch_readback_emit_in_order(engine_phases):
    phases, _, _ = engine_phases
    steps = [p for p in phases if p[0] == "engine.step"]
    assert len(steps) >= 5
    for st in steps:
        kids = _inside(phases, st)
        # the next step goes out (at most once, and first), then the step
        # before it is read back, then emitted
        assert [k[0] for k in kids] == (
            ["engine.dispatch"] * st[3]["ahead"]
            + ["engine.readback", "engine.emit"])
        assert all(a[2] <= b[1] for a, b in zip(kids, kids[1:]))
        assert 1 <= st[3]["live"] <= st[3]["batch"] == 2
        assert kids[-1][3] == {"emitted": st[3]["live"]}
    # budgets 2, 3 | 4, 5 | 6: each window's last token step has nothing to
    # issue ahead of it (the window's first went out at its opening)
    ahead = [st[3]["ahead"] for st in steps]
    assert ahead == [1, 0] + [1, 1, 1, 0] + [1, 1, 1, 1, 0]
    # no phase per row or per token: a read-back and an emit a step, a
    # dispatch where one was issued, one prefill a window, nothing else
    assert {p[0] for p in phases} == {
        "engine.prefill", "engine.step", "engine.dispatch",
        "engine.readback", "engine.emit"}
    assert len(phases) == 3 * len(steps) + sum(ahead) + 3


def test_engine_prefill_counts_and_emitted_sum_to_the_engines_tokens(
        engine_phases):
    phases, tokens, counted = engine_phases
    emitted = counted["tokens_emitted"]
    prefills = [p for p in phases if p[0] == "engine.prefill"]
    # five requests, two rows a window; what each window left in the queue
    assert [(p[3]["rows"], p[3]["batch"], p[3]["queued"])
            for p in prefills] == [(2, 2, 3), (2, 2, 1), (1, 2, 0)]
    assert not any(_inside(phases, p) for p in prefills)
    first = sum(p[3]["rows"] for p in prefills)
    later = sum(p[3]["emitted"] for p in phases if p[0] == "engine.emit")
    assert first + later == emitted == tokens


def test_engine_step_ahead_counts_are_the_engines_step_counters(
        engine_phases):
    phases, _, counted = engine_phases
    steps = [p for p in phases if p[0] == "engine.step"]
    dispatches = [p for p in phases if p[0] == "engine.dispatch"]
    # a dispatch phase is a step issued ahead; each window's first step goes
    # out inside its engine.prefill; budgets end these windows, so every
    # issued step is read by one engine.step
    assert counted["steps_ahead"] == len(dispatches) == sum(
        st[3]["ahead"] for st in steps)
    assert counted["steps_issued"] == len(steps) == len(dispatches) + 3
    assert counted["steps_dropped"] == 0


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
    for st in steps:
        kids = _inside(phases, st)
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
    # budgets 2..6 over two slots: only the very first step finds nothing
    # in flight, and the last read has nothing left to issue
    ahead = [st[3]["ahead"] for st in steps]
    assert ahead == [0] + [1] * (len(steps) - 2) + [0]
    assert {p[0] for p in phases} == {
        "engine.prefill", "engine.step", "engine.dispatch",
        "engine.readback", "engine.emit"}


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
