"""PR 61: a whole-step floor counts the bytes a step MUST move for what the
run had live, and is read as a mean count over the mean time of the same
executions.  The cost functions by hand, the three readers on hand-made
captures, and the replica's counter against a schedule known by heart."""

import os
from types import SimpleNamespace as NS

import pytest

from benchmark import (costs, costs_moe, costs_ssm, manifest, scopes, spans,
                       xplane)
from benchmark.harness import ReadContext
from benchmark.kinds.serve import read_per_step
from benchmark.readers import decode_hbm_share, module_hbm_share, ssm_roofline

PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
DECODE, MIXED = "lm_paged_decode_step", "lm_paged_mixed_step"
#: what a decode step of ``olmoe-serve-decode`` had live in the traced runs
#: of PR 61, 8 to 10 s into a window (PERF.md 6: 4,169 / 6,007 / 6,765
#: positions over 19.2 / 26.4 / 25.5 rows, three seeds), rounded
OLMOE_LIVE = 6000.0
#: ``jamba2-serve-reason`` likewise: the rows whose state a step advanced
#: and the positions they held (66.0 / 83.0 / 87.1 rows, 21,079 / 33,014 /
#: 35,708 positions)
JAMBA_ROWS, JAMBA_POSITIONS = 83.0, 33000.0


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


def _plane(runs, ops=()):
    """A device plane whose ``XLA Modules`` line holds ``runs``: (program
    name, program id, seconds) back to back, and ``ops``: (run index, scope
    path, seconds), one after the other from the start of that run."""
    md, modules, events, at = {}, [], [], 0.0
    starts = []
    for name, pid, seconds in runs:
        md[100 + pid] = {"name": f"jit_{name}({pid})"}
        modules.append((100 + pid, at, at + seconds))
        starts.append((at, pid, name))
        at += seconds + 1e-6
    for k, (run, path, seconds) in enumerate(ops):
        start, pid, name = starts[run]
        md[k + 1] = {"name": f"%fusion.{k} = f32[8] fusion(...)",
                     "program_id": pid,
                     "tf_op": f"jit({name})/CausalLM/layer_0/{path}/mul:"}
        events.append((k + 1, start, start + seconds))
        starts[run] = (start + seconds, pid, name)
    plane = scopes.DevicePlane(metadata=md)
    plane.modules, plane.ops = modules, events
    return plane


def _rc(cfg, facts, trace=True, traffic=None):
    summary = xplane.TraceSummary({0: xplane.DeviceOps(ops=[])}, [],
                                  (0.0, 1.0)) if trace else None
    return ReadContext(facts, summary, cfg, traffic or {"dtype": "bfloat16"},
                       1, PEAK)


@pytest.fixture
def capture(monkeypatch):
    """``capture(plane)``: what the readers find as the newest capture."""
    held = {}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(scopes, "read", lambda path: {0: held["plane"]})
    return lambda plane: held.__setitem__("plane", plane)


# -- costs_moe: OLMoE's decode step -------------------------------------------

def test_olmoe_floor_counts_live_positions_not_the_pool(bench):
    cfg = bench.config("olmoe-1b-7b")
    need = costs_moe.live_step_bytes(cfg, OLMOE_LIVE)
    assert need["expert_bytes"] == 8 * 64 * 3 * 2048 * 1024 * 2   # 6.44 GB
    assert need["attention_router_bytes"] == 8 * (4 * 2048 * 2048
                                                  + 2048 * 64) * 2
    assert need["head_bytes"] == 2048 * 50304 * 2
    # K and V of eight layers at h * d = 2048, bf16: 65,536 bytes a position
    assert need["kv_bytes"] == OLMOE_LIVE * 8 * 2 * 2048 * 2      # 0.39 GB
    assert need["total_bytes"] == sum(
        v for k, v in need.items() if k != "total_bytes")
    assert need["total_bytes"] == pytest.approx(7.31e9, rel=5e-3)
    # the same at slots x slot_len is what the pool could hold: the
    # difference is K/V alone, by hand
    pool = costs_moe.decode_step_bytes(cfg, 64, 1024)
    assert pool == costs_moe.live_step_bytes(cfg, 64 * 1024)
    assert pool["kv_bytes"] == 64 * 1024 * 8 * 2 * 2048 * 2      # 4.29 GB
    assert pool["total_bytes"] - need["total_bytes"] == (
        (64 * 1024 - OLMOE_LIVE) * 65536)
    assert pool["total_bytes"] == pytest.approx(11.21e9, rel=5e-3)
    # nothing live: the weights alone
    assert costs_moe.live_step_bytes(cfg, 0)["kv_bytes"] == 0


def test_lm_decode_roofline_is_a_mean_count_over_a_mean_time(bench, capture):
    cfg = bench.config("olmoe-1b-7b")
    facts = {"lm_kv_positions_per_step": {DECODE: OLMOE_LIVE, MIXED: 9000.0},
             "num_slots": 64, "slot_len": 1024}
    floor_s = costs_moe.live_step_bytes(cfg, OLMOE_LIVE)[
        "total_bytes"] / 819e9
    # a program that takes exactly floor / peak reads 100 %; the first and
    # the last execution (cut by the capture's edges) and the other
    # programs between the steps decide nothing
    runs = ([(DECODE, 5, 0.001)] + [(DECODE, 5, floor_s)] * 4
            + [("lm_prefill_chunk", 6, 0.009), (MIXED, 7, 0.020)]
            + [(DECODE, 5, floor_s)] * 3 + [(DECODE, 5, 0.5)])
    capture(_plane(runs))
    got = module_hbm_share.read(_rc(cfg, facts), module=DECODE)
    assert got == pytest.approx(100.0, rel=1e-9)
    # the step ISSUE 61 is for: 12 ms once it reads its live pages alone is
    # under 100 % of the live floor, where the pool's floor read 114 %
    capture(_plane([(DECODE, 5, 0.012)] * 9))
    got = module_hbm_share.read(_rc(cfg, facts), module=DECODE)
    assert got == pytest.approx(100 * floor_s / 0.012, rel=1e-9)
    assert 70 < got < 80
    old = 100 * costs_moe.decode_step_bytes(cfg, 64, 1024)[
        "total_bytes"] / 819e9 / 0.012
    assert old > 105 and old == pytest.approx(114.1, abs=0.2)
    # the mixed step has its own count over its own time
    capture(_plane([(DECODE, 5, 0.012)] * 3 + [(MIXED, 7, 0.020)] * 5))
    got = module_hbm_share.read(_rc(cfg, facts), module=MIXED)
    assert got == pytest.approx(100 * costs_moe.live_step_bytes(
        cfg, 9000.0)["total_bytes"] / 819e9 / 0.020, rel=1e-9)


def test_steps_of_unequal_length_stay_under_the_peak(bench, capture):
    """A decode step with one row live, then fourteen: each runs AT the peak
    for its own bytes.  The mean count over the mean time reads 100 %; the
    same count over the MEDIAN time (the form this PR retires) passes it."""
    cfg = bench.config("olmoe-1b-7b")
    one, fourteen = 300.0, 14 * 300.0
    at_peak = lambda live: costs_moe.live_step_bytes(  # noqa: E731
        cfg, live)["total_bytes"] / 819e9
    # most steps short, a few long: the median is a short step's time
    runs = ([(DECODE, 5, at_peak(one))] * 7
            + [(DECODE, 5, at_peak(fourteen))] * 4)
    whole = runs[1:-1]
    mean_live = (6 * one + 3 * fourteen) / 9
    capture(_plane(runs))
    facts = {"lm_kv_positions_per_step": {DECODE: mean_live}}
    got = module_hbm_share.read(_rc(cfg, facts), module=DECODE)
    assert got == pytest.approx(100.0, rel=1e-9) and got <= 100.0 + 1e-9
    median = sorted(s for _, _, s in whole)[len(whole) // 2]
    assert 100 * at_peak(mean_live) / median > 100.5
    # slower steps read under it
    capture(_plane([(n, p, 1.25 * s) for n, p, s in runs]))
    assert module_hbm_share.read(_rc(cfg, facts), module=DECODE) == (
        pytest.approx(80.0, rel=1e-9))


def test_lm_decode_roofline_reads_nothing_without_a_count(bench, capture):
    cfg = bench.config("olmoe-1b-7b")
    capture(_plane([(DECODE, 5, 0.012)] * 5))
    facts = {"lm_kv_positions_per_step": {DECODE: OLMOE_LIVE}}
    assert module_hbm_share.read(_rc(cfg, facts), module=DECODE) is not None
    # no fact (an untraced run's empty dict, a tree the watch does not fit),
    # no count for that program, the pool's shape alone: nothing, never the
    # pool's bytes
    for none in ({}, {"lm_kv_positions_per_step": {}},
                 {"lm_kv_positions_per_step": {MIXED: 9000.0}},
                 {"num_slots": 64, "slot_len": 1024}):
        assert module_hbm_share.read(_rc(cfg, none), module=DECODE) is None
    assert module_hbm_share.read(_rc(cfg, facts), module="no_such") is None
    assert module_hbm_share.read(_rc(cfg, facts, trace=False),
                                 module=DECODE) is None
    assert module_hbm_share.read(_rc(bench.config("jamba2-3b"), facts),
                                 module=DECODE) is not None  # num_experts 1
    assert module_hbm_share.read(_rc({"d_model": 8}, facts),
                                 module=DECODE) is None


# -- costs_ssm: Jamba's decode step and its state update ----------------------

def test_jamba_floor_counts_live_rows_and_positions(bench):
    cfg = bench.config("jamba2-3b")
    need = costs_ssm.live_step_bytes(cfg, JAMBA_ROWS, JAMBA_POSITIONS)
    pool = costs_ssm.decode_step_bytes(cfg, 128, 2048)
    assert pool == costs_ssm.live_step_bytes(cfg, 128, 128 * 2048)
    assert pool["total_bytes"] == pytest.approx(8.71e9, rel=2e-3)
    for weights in ("mamba_weight_bytes", "attention_weight_bytes",
                    "mlp_bytes", "head_bytes"):
        assert need[weights] == pool[weights]
    # a row's state over 26 Mamba layers: [5120, 16] float32 and three
    # positions of bf16 tail, both ways
    row = 26 * (5120 * 16 * 4 + 3 * 5120 * 2)
    assert need["state_bytes"] == 2 * JAMBA_ROWS * row
    assert pool["state_bytes"] - need["state_bytes"] == 2 * 45 * row
    # K and V of the two attention layers at the K/V heads: by hand
    heads, hd = cfg["num_key_value_heads"], (
        cfg["hidden_size"] // cfg["num_attention_heads"])
    assert need["kv_bytes"] == 2 * 2 * JAMBA_POSITIONS * heads * hd * 2
    assert pool["kv_bytes"] == 2 * 2 * 128 * 2048 * heads * hd * 2
    assert need["total_bytes"] == sum(
        v for k, v in need.items() if k != "total_bytes")
    assert need["total_bytes"] == pytest.approx(7.64e9, rel=5e-3)


def test_ssm_roofline_step_and_state_update(bench, capture):
    cfg = bench.config("jamba2-3b")
    facts = {"ssm_rows_live_per_step": JAMBA_ROWS,
             "ssm_positions_live_per_step": JAMBA_POSITIONS,
             "num_slots": 128, "slot_len": 2048}
    step = dict(part="step", module=DECODE)
    state = dict(part="state_update", scope="^ssm_state_update$",
                 module=DECODE)
    floor_s = costs_ssm.live_step_bytes(
        cfg, JAMBA_ROWS, JAMBA_POSITIONS)["total_bytes"] / 819e9
    moved_s = 2 * 26 * JAMBA_ROWS * 5120 * 16 * 4 / 819e9
    assert 2 * costs_ssm.state_bytes(cfg, JAMBA_ROWS, tail_el=0) == (
        pytest.approx(moved_s * 819e9, rel=1e-12))
    # five executions, each with the update in two operations that take the
    # floor's time between them, and a projection: the edges' two left out
    runs = [(DECODE, 5, 0.004)] + [(DECODE, 5, floor_s)] * 3 + [
        (DECODE, 5, 0.3), (MIXED, 7, 0.02)]
    ops = []
    for k in range(5):
        ops += [(k, "mamba/ssm_state_update", moved_s / 2),
                (k, "mamba/in_proj", 1e-4),
                (k, "mamba/ssm_state_update", moved_s / 2)]
    plane = _plane(runs, ops)
    capture(plane)
    assert ssm_roofline.read(_rc(cfg, facts), **step) == pytest.approx(
        100.0, rel=1e-9)
    got = ssm_roofline.read(_rc(cfg, facts), **state)
    assert got == pytest.approx(100.0, rel=1e-6)
    # at every slot's state the same time would have read 128 / 83 of it:
    # idle slots' rows are time with no bytes to their name
    every = 2 * costs_ssm.state_bytes(cfg, 128, tail_el=0) / 819e9
    assert every / moved_s == pytest.approx(128 / JAMBA_ROWS)
    # no counts, another family, another program or scope, no trace: nothing
    assert ssm_roofline.read(_rc(cfg, {"num_slots": 128, "slot_len": 2048}),
                             **step) is None
    assert ssm_roofline.read(_rc(cfg, {"ssm_rows_live_per_step": 83.0}),
                             **step) is None
    assert ssm_roofline.read(_rc(bench.config("olmoe-1b-7b"), facts),
                             **step) is None
    assert ssm_roofline.read(_rc(cfg, facts), part="step",
                             module="no_such") is None
    assert ssm_roofline.read(_rc(cfg, facts), **{
        **state, "scope": "^moe_experts$"}) is None
    assert ssm_roofline.read(_rc(cfg, facts, trace=False), **step) is None
    with pytest.raises(ValueError):
        ssm_roofline.read(_rc(cfg, facts), part="tail", module=DECODE)


def test_ssm_roofline_steps_of_unequal_rows_stay_under_the_peak(bench,
                                                                capture):
    cfg = bench.config("jamba2-3b")
    at_peak = lambda rows: costs_ssm.live_step_bytes(  # noqa: E731
        cfg, rows, 500.0 * rows)["total_bytes"] / 819e9
    runs = ([(DECODE, 5, at_peak(1))] * 7 + [(DECODE, 5, at_peak(100))] * 4)
    capture(_plane(runs))
    rows = (6 * 1 + 3 * 100) / 9
    facts = {"ssm_rows_live_per_step": rows,
             "ssm_positions_live_per_step": 500.0 * rows}
    got = ssm_roofline.read(_rc(cfg, facts), part="step", module=DECODE)
    assert got == pytest.approx(100.0, rel=1e-9)


# -- costs: the T5 decode loop's mean step ------------------------------------

def test_gen_decode_roofline_prices_the_mean_written_length():
    with open(os.path.join(manifest.REPO, "benchmark", "configs",
                           "flan-t5-base.json")) as f:
        import json
        cfg = json.load(f)
    facts = {"max_new_tokens": 128, "rows_per_block": 256,
             "encoder_len": 512}
    whole = costs.decode_step_bytes(cfg, 256, 512, 129)
    mean = costs.decode_step_bytes(cfg, 256, 512, 64.5)
    # iteration i reads i + 1 positions: 1 .. 128, 64.5 on average
    assert sum(range(1, 129)) / 128 == 64.5
    assert mean["self_kv_bytes"] == 2 * 256 * 64.5 * 768 * 2 * 12
    assert whole["self_kv_bytes"] - mean["self_kv_bytes"] == (
        2 * 256 * 64.5 * 768 * 2 * 12)
    assert mean["cross_kv_bytes"] == whole["cross_kv_bytes"]
    assert mean["param_bytes"] == whole["param_bytes"]
    assert mean["total_bytes"] / 819e9 * 1e3 == pytest.approx(6.95, abs=0.01)
    assert whole["total_bytes"] / 819e9 * 1e3 == pytest.approx(7.69, abs=0.01)
    # a loop of 128 iterations that runs AT the peak for what each reads
    # takes 128 mean floors: 100 %, where the slab's capacity read 110.7 %
    loop_s = sum(costs.decode_step_bytes(cfg, 256, 512, i + 1)["total_bytes"]
                 for i in range(128)) / 819e9
    trace = NS(longest_container=lambda name: loop_s)
    rc = ReadContext(facts, trace, cfg, {}, 1, PEAK)
    assert decode_hbm_share.read(rc) == pytest.approx(100.0, rel=1e-9)
    assert 100 * whole["total_bytes"] / 819e9 / (loop_s / 128) > 110
    # the iteration of PR 59's ledger lines, 8.14 ms
    rc = ReadContext(facts, NS(longest_container=lambda name: 128 * 8.14e-3),
                     cfg, {}, 1, PEAK)
    assert decode_hbm_share.read(rc) == pytest.approx(85.4, abs=0.2)
    assert decode_hbm_share.read(
        ReadContext(facts, None, cfg, {}, 1, PEAK)) is None


# -- the counter: worker_hooks.ReadWatch --------------------------------------

class _Engine:
    """What ``ReadWatch`` touches of ``InferenceEngine``: ``_read(step,
    reading)`` moves every row it reads on by one position."""

    def __init__(self):
        self.read = []

    def _read(self, step, reading):
        self.read.append((step.chunk_start, [s.pos for s in reading]))
        for slot in reading:
            slot.pos += 1


def test_read_watch_counts_pos_plus_one_by_program():
    from benchmark.worker_hooks import ReadWatch

    engine = _Engine()
    # three requests: prompts of 5, 40 and 200 tokens; a row at position p
    # holds p + 1 cached positions as the step that computed its token ran
    a, b, c = NS(pos=5), NS(pos=40), NS(pos=200)
    plain = NS(chunk_start=None)
    engine._read(plain, [a])            # before the watch: not counted
    with ReadWatch(engine) as watch:
        engine._read(plain, [a, b])                      # 7 + 41
        engine._read(NS(chunk_start=128), [a, b, c])     # 8 + 42 + 201
        engine._read(plain, [b, c])                      # 43 + 202
        engine._read(NS(chunk_start=0), [])              # a chunk, no row
        engine._read(plain, [c])                         # 203
    engine._read(plain, [c])            # after it: not counted
    assert "_read" not in vars(engine)  # the engine's own method again
    assert [p for _, p in engine.read] == [
        [5], [6, 40], [7, 41, 200], [42, 201], [], [202], [203]]
    alone = (7 + 41) + (43 + 202) + 203
    mixed = 8 + 42 + 201
    assert watch.counts == {
        "steps_read": 5, "steps_read_alone": 3,
        "rows_read": 8, "rows_read_alone": 5,
        "kv_positions_read": alone + mixed, "kv_positions_read_alone": alone}
    per_step = read_per_step(watch.counts, "kv_positions_read")
    assert per_step == {DECODE: alone / 3, MIXED: mixed / 2}
    assert read_per_step(watch.counts, "rows_read") == {
        DECODE: 5 / 3, MIXED: 3 / 2}
    # a schedule by heart: one request, prompt 16, first token from its
    # chunk, then decode steps at positions 16, 17, 18: sum of pos + 1
    row = NS(pos=16)
    with ReadWatch(engine) as watch:
        for _ in range(3):
            engine._read(plain, [row])
    assert watch.counts["kv_positions_read_alone"] == 17 + 18 + 19
    # no watch's counts (an untraced run): no program, no fact
    assert read_per_step({}, "kv_positions_read") == {}
    assert read_per_step({"moe_steps": 9}, "kv_positions_read") == {}


def test_read_watch_leaves_an_engine_it_does_not_fit_alone():
    from benchmark.worker_hooks import ReadWatch

    other = NS(step=lambda: None)       # T5Engine: no _read
    with ReadWatch(other) as watch:
        pass
    assert watch.counts == {} and "_read" not in vars(other)
    # an error inside the watched span still takes the wrapper off
    engine = _Engine()
    with pytest.raises(RuntimeError):
        with ReadWatch(engine):
            raise RuntimeError("stop_trace failed")
    assert "_read" not in vars(engine)


@pytest.mark.parametrize("mixed_for_s, want_s, want_alone", [
    (0.0, 2.0, 10),     # both programs from the start: the capture asked for
    (2.5, 4.1, 8),      # a chunk on every step for 2.5 s: until 8 decode steps
    (99.0, 6.0, 0),     # never a decode step: three times the length, no more
])
def test_capture_lasts_until_it_holds_both_programs(
        monkeypatch, mixed_for_s, want_s, want_alone):
    """``_trace_with_counts`` on a simulated engine that reads a step every
    0.1 s of a simulated clock, a mixed step while ``mixed_for_s`` lasts and
    then decode and mixed steps in turn."""
    import jax

    from benchmark import worker_hooks

    engine = _Engine()
    engine.metrics = NS(snapshot=lambda: {"steps_issued": len(engine.read)})
    clock = {"now": 100.0}

    def sleep(seconds):
        for _ in range(round(seconds / 0.1)):
            clock["now"] += 0.1
            at = clock["now"] - 100.0
            mixed = at <= mixed_for_s + 1e-9 or len(engine.read) % 2
            engine._read(NS(chunk_start=0 if mixed else None), [NS(pos=3)])

    monkeypatch.setattr(worker_hooks, "time", NS(
        sleep=sleep, monotonic=lambda: round(clock["now"], 6)))
    traced = []
    monkeypatch.setattr(jax.profiler, "start_trace", traced.append)
    monkeypatch.setattr(jax.profiler, "stop_trace",
                        lambda: traced.append(clock["now"] - 100.0))
    server = object.__new__(worker_hooks.ObservedEngineServer)
    server._ensure_engine = lambda: engine
    server.TRACED_COUNTERS = ("steps_issued",)
    server._trace_with_counts("dir", 2.0)
    assert traced[0] == "dir" and traced[1] == pytest.approx(want_s)
    counts = server.bench_traced_counts()
    assert counts["steps_issued"] == counts["steps_read"] == round(want_s / 0.1)
    assert counts["steps_read_alone"] == want_alone
    assert "_read" not in vars(engine)
    # an engine the watch does not fit: the capture asked for and no longer
    clock["now"], other = 100.0, NS(metrics=engine.metrics)
    server._ensure_engine = lambda: other
    monkeypatch.setattr(worker_hooks.time, "sleep",
                        lambda s: clock.update(now=clock["now"] + s))
    server._trace_with_counts("dir", 2.0)
    assert traced[-1] == pytest.approx(2.0)


@pytest.mark.parametrize("name", [
    "lm_decode_roofline", "ssm_decode_roofline", "ssm_state_update_roofline",
    "gen_decode_roofline"])
def test_no_floor_doc_prices_a_pool(name):
    import json

    with open(os.path.join(manifest.REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        doc = json.load(f)["doc"]
    for phrase in ("slot_len", "every slot", "all slots", "median"):
        assert phrase not in doc, phrase
    assert "MEAN" in doc or "mean" in doc
