"""The per-layer metrics that read the engines' own events (PR 56):
``engine.first_token``, ``engine.window_close`` and the counts ``steps`` of
``engine.step`` and ``mixed`` of ``engine.readback``.  Two readers are new
(``span_count_quantile``, ``readback_interval``); each metric is read here
through its ``benchmark/layer_metrics`` file from a hand-made capture with
known answers, and from one as the PARENT's program writes it (the phases
without the new events and counts), where every one must read None and raise
nothing: the driver runs the parent with these files."""

import os
from types import SimpleNamespace

import pytest

from benchmark import harness, manifest

NEW = ["engine_queue_wait_ms_p50", "engine_queue_wait_ms_p95",
       "engine_first_prefill_ms_p50", "engine_window_live_row_share",
       "engine_mixed_step_share", "engine_decode_step_ms_p50",
       "engine_mixed_step_ms_p50"]
T5, PAGED = ["t5large-serve"], ["olmoe-serve-decode", "jamba2-serve-reason",
                                "gigachat-serve-docchat",
                                "nemotron3-serve-agent"]
# PR 58 appended its cell to the three of the engine's programs
XING = "xing4-serve-longdoc"
LAGUNA = "laguna-serve-mixedlen"       # PR 60 appended its own behind it

# (name, start us, duration us, counts).  Five requests: queue waits 100,
# 200, 300, 400, 1000 us (median 300; p95 = 400 + 0.8 x 600 = 880), prefill
# 50 each but one of 70.  Two windows: 5 + 9 of 6 + 10 row-steps live = 87.5 %.
# Six iterations issue five steps, two of them mixed: 40 %.  Token-step
# read-backs end at 10, 18, 34, 42, 50, 67 ms reading programs 0 0 1 0 0 1:
# the intervals by the later one's program are 8, 8, 8 (decode) and 16, 17
# (mixed: median 16.5); the read of first tokens between them is no token step.
CHANGE = (
    [("engine.first_token", 100 * k, 1,
      {"queue_us": q, "prefill_us": p, "prompt": 7, "chunks": 1})
     for k, (q, p) in enumerate([(300, 50), (100, 50), (1000, 70), (200, 50),
                                 (400, 50)])]
    + [("engine.window_close", 900, 1,
        {"steps": 3, "rows": 2, "batch": 2, "live_row_steps": 5,
         "row_steps": 6, "us": 800, "queued": 3}),
       ("engine.window_close", 1900, 1,
        {"steps": 5, "rows": 2, "batch": 2, "live_row_steps": 9,
         "row_steps": 10, "us": 900, "queued": 1})]
    + [("engine.step", 2000 + 10 * k, 5,
        {"live": 2, "batch": 4, "ahead": 1, "steps": s, "chunk": c})
       for k, (s, c) in enumerate([(1, 0), (1, 1), (1, 0), (1, 0), (1, 1),
                                   (0, 0)])]
    + [("engine.readback", end - 2000, 2000, {"mixed": m})
       for end, m in [(10000, 0), (18000, 0), (34000, 1), (42000, 0),
                      (50000, 0), (67000, 1)]]
    + [("engine.readback", 34500, 500, {"first": 1})])
WANT = {"engine_queue_wait_ms_p50": 0.3, "engine_queue_wait_ms_p95": 0.88,
        "engine_first_prefill_ms_p50": 0.05,
        "engine_window_live_row_share": 87.5, "engine_mixed_step_share": 40.0,
        "engine_decode_step_ms_p50": 8.0, "engine_mixed_step_ms_p50": 16.5}
# the same loop as the parent's program marks it: no first token, no window
# close, a step without ``steps``, a read-back without ``mixed``
PARENT = (
    [("engine.prefill", 0, 50, {"rows": 2, "batch": 2, "queued": 3})]
    + [("engine.step", 2000 + 10 * k, 5,
        {"live": 2, "batch": 4, "ahead": 1, "chunk": c})
       for k, c in enumerate([0, 1, 0])]
    + [("engine.readback", 8000, 2000, {}),
       ("engine.readback", 16000, 2000, {}),
       ("engine.readback", 34500, 500, {"first": 1})])


def _xspace(events):
    """A capture's text form: one host thread with ``events``, one device
    operation so that it reduces to a trace."""
    names = sorted({n for n, _, _, _ in events})
    stats = sorted({k for _, _, _, c in events for k in c})
    lines = []
    for name, start, dur, counts in events:
        got = " ".join(
            f"stats {{ metadata_id: {stats.index(k) + 1} int64_value: {v} }}"
            for k, v in counts.items())
        lines.append(f"    events {{ metadata_id: {names.index(name) + 1} "
                     f"offset_ps: {start * 10 ** 6} "
                     f"duration_ps: {dur * 10 ** 6} {got} }}")
    meta = [f'  event_metadata {{ key: {i + 1} value {{ id: {i + 1} '
            f'name: "{n}" }} }}' for i, n in enumerate(names)]
    meta += [f'  stat_metadata {{ key: {i + 1} value {{ id: {i + 1} '
             f'name: "{k}" }} }}' for i, k in enumerate(stats)]
    return "\n".join(
        ['planes {', '  id: 1', '  name: "/device:TPU:0"', '  lines {',
         '    id: 1', '    name: "XLA Ops"', '    timestamp_ns: 5000000',
         '    events { metadata_id: 1 offset_ps: 0 duration_ps: 100000000 }',
         '  }',
         '  event_metadata { key: 1 value { id: 1 name: "fusion.1" } }', '}',
         'planes {', '  id: 2', '  name: "/host:CPU"', '  lines {',
         '    id: 1', '    name: "python3"', '    timestamp_ns: 5000000']
        + lines + ['  }'] + meta + ['}'])


def _traced(repo, monkeypatch, events):
    """The read context of a traced run whose capture holds ``events``, laid
    where ``harness.run_cell`` has the workers write it."""
    from jax.profiler import ProfileData

    from benchmark import xplane

    monkeypatch.setattr(manifest, "REPO", str(repo))
    d = os.path.join(str(repo), harness.TRACE_DIR, "t5large-serve", "plugins",
                     "profile", "2026_01_01_00_00_00")
    os.makedirs(d)
    path = os.path.join(d, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(_xspace(events)))
    trace = xplane.reduce_file(path)
    assert trace is not None
    return SimpleNamespace(trace=trace, facts={})


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


def _read(bench, rc, name):
    how = bench.load_json("layer_metrics", name + ".json")
    return bench.module("readers", how["reader"]).read(rc, **how["args"])


@pytest.mark.parametrize("name", NEW)
def test_known_answers_from_a_hand_made_capture(bench, tmp_path, monkeypatch,
                                                name):
    rc = _traced(tmp_path, monkeypatch, CHANGE)
    assert _read(bench, rc, name) == pytest.approx(WANT[name], rel=1e-9)


@pytest.mark.parametrize("name", NEW)
def test_the_parents_capture_reads_none_and_raises_nothing(
        bench, tmp_path, monkeypatch, name):
    rc = _traced(tmp_path, monkeypatch, PARENT)
    assert _read(bench, rc, name) is None
    # nor without a capture at all (an untraced run)
    assert _read(bench, SimpleNamespace(trace=None, facts={}), name) is None


def test_count_quantile_skips_events_without_the_count(tmp_path, monkeypatch):
    from benchmark.readers import span_count_quantile

    rc = _traced(tmp_path, monkeypatch, CHANGE + [
        ("engine.first_token", 600, 1, {"prompt": 3})])
    args = ("engine.first_token", "queue_us", 0.5)
    assert span_count_quantile.read(rc, *args) == pytest.approx(300.0)
    assert span_count_quantile.read(rc, *args, scale=2.0) == pytest.approx(
        600.0)
    assert span_count_quantile.read(rc, "engine.first_token", "no_such",
                                    0.5) is None
    assert span_count_quantile.read(rc, "engine.no_such", "queue_us",
                                    0.5) is None


def test_readback_interval_wants_a_read_before_and_the_asked_program(
        tmp_path, monkeypatch):
    from benchmark.readers import readback_interval

    # one read-back alone is no interval; two decode reads hold no mixed step
    rc = _traced(tmp_path, monkeypatch, [
        ("engine.readback", 0, 10, {"mixed": 1})])
    assert readback_interval.read(rc, 1) is None
    rc = _traced(tmp_path / "two", monkeypatch, [
        ("engine.readback", 0, 1000, {"mixed": 0}),
        ("engine.readback", 4000, 1000, {"mixed": 0})])
    assert readback_interval.read(rc, 0) == pytest.approx(4.0)
    assert readback_interval.read(rc, 1) is None


def test_the_manifest_appends_the_seven_and_still_validates(bench):
    names = [m["name"] for m in bench.doc["per_layer"]]
    at = names.index(NEW[0])          # found by name: later PRs append behind
    assert names[at:at + 7] == NEW and at == 56
    cells = {w["name"] for w in bench.doc["workloads"]}
    e2e = {m["name"]: m for m in bench.doc["end_to_end"]}
    for m in bench.doc["per_layer"][at:at + 7]:
        assert (m["source"], m["layer"]) == ("program_span", "engine")
        assert m["workloads"] == (
            T5 if m["moves"] == "serve_ttft_p95_ms"
            else PAGED + [XING, LAGUNA])
        assert set(m["workloads"]) <= cells
        # a cell that lists the metric reports the metric it moves
        assert set(m["workloads"]) <= set(e2e[m["moves"]]["workloads"])
        how = bench.load_json("layer_metrics", m["name"] + ".json")
        assert how["name"] == m["name"] and len(how["doc"]) > 40
        assert callable(bench.module("readers", how["reader"]).read)
    manifest.validate(bench.doc)
    # each cell's list resolves, the new ones behind what it had
    assert [m["name"] for m in bench.metrics("per_layer", T5[0])][-4:] == NEW[:4]
    for cell in PAGED:
        assert [m["name"] for m in bench.metrics("per_layer", cell)][-3:] == NEW[4:]
    # PR 58's cell: the same three, its own six behind them
    assert [m["name"] for m in bench.metrics("per_layer", XING)][-9:-6] == NEW[4:]
