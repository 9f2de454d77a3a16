"""The readers of the program's host phases, on a hand-made trace with known
answers (data/synthetic_spans.xplane.txt; microseconds from the lines' start,
the device window is 0-1000):

device 0 runs 0-100 (a prefill), 150-250, 300-400, 460-560 (three steps),
600-700 (a prefill), 760-900, 940-1000 (two steps): idle 300 of 1000, in the
gaps 100-150, 250-300, 400-460, 560-600, 700-760, 900-940.

engine thread: prefill 0-120 (rows 2 of batch 4, 3 queued); step S1 130-290
(live 2; dispatch 130-150, readback 150-270, emit 270-290), S2 295-450 (live
2; readback 305-430), S3 455-590 (live 1; readback 465-570), prefill 595-740
(rows 3), S4 750-930 (live 3; readback 765-910), S5 935-1050 (live 3;
readback 945-1040; it ends after the window, so it is no whole step).

* live rows: 2+2+1+3+3 = 11 of 5 x 4 = 20 attempted: 55 %.
* host part of a step: S1->S2 295-130-120 = 45, S2->S3 455-295-125 = 35,
  S3->S4 has the prefill between (it would be 190) and is left out, S4->S5
  935-750-145 = 40: median 40.
* the gap 250-300 straddles the end of S1's readback (270): 20 inside, 30
  outside; 400-460: 30 and 30; 560-600: 10 inside S3's readback, 25 host, 5
  under the prefill; 900-940: 10 and 30; 100-150: 20 under the first
  prefill, 30 host; 700-760: 40 prefill, 20 host.  Inside readbacks 70,
  host 165, prefill 65 (sum 300); over 4 whole steps 17.5 and 41.25.

worker thread: actor calls 50-150, 200-260 (poll), 300-500 (submit),
900-1100 (poll): median 150; inside the window 100+60+200+100 = 460: 46 %.

train thread: train.input 10-40 (with its three children), 300-340, 600-660:
median 40.

runtime thread: a launch (``DoEnqueueProgram``) 5 before each of the seven
starts of device work, so the device leads the host by nothing.  With the
device's line stamped 20 EARLIER (the variant ``early``) every launch comes
15 after its program's start: the lead is 15, which puts the gaps at 95-145,
245-295, 395-455, 555-595, 695-755, 895-935 on the host's clock (5 early,
the launch latency no capture can tell from an offset): inside readbacks
25+35+15+15 = 90, host 25+25+25+25+15+25 = 140, prefill 70; over 4 whole
steps 22.5 and 35.  Read without the lead they would be 145 and 70.
"""

import json
import os
import shutil
from types import SimpleNamespace

import pytest

from benchmark import harness, manifest, spans, xplane

DATA = os.path.join(os.path.dirname(__file__), "data")
US = 1e-6
NEW = ["engine_live_row_share", "engine_host_ms_p50", "engine_idle_host_ms",
       "engine_idle_readback_ms", "replica_call_ms_p50",
       "replica_loop_busy_share", "train_input_ms_p50"]
WANT = {"engine_live_row_share": 55.0, "engine_host_ms_p50": 0.040,
        "engine_idle_host_ms": 0.04125, "engine_idle_readback_ms": 0.0175,
        "replica_call_ms_p50": 0.150, "replica_loop_busy_share": 46.0,
        "train_input_ms_p50": 0.040}


def _text(early=False):
    with open(os.path.join(DATA, "synthetic_spans.xplane.txt")) as f:
        text = f.read()
    if early:
        at = 'name: "XLA Ops"\n    timestamp_ns: 5000000'
        assert at in text
        text = text.replace(at, at.replace("5000000", "4980000"))
    return text


def _as_traced_run(repo, cell, serialized):
    """Lay a capture where ``harness.run_cell`` has the workers write it."""
    d = os.path.join(repo, harness.TRACE_DIR, cell, "plugins", "profile",
                     "2026_01_01_00_00_00")
    os.makedirs(d)
    path = os.path.join(d, "host.xplane.pb")
    with open(path, "wb") as f:
        f.write(serialized)
    return path


def _context(repo, early=False):
    from jax.profiler import ProfileData

    path = _as_traced_run(
        repo, "t5large-serve",
        ProfileData.text_proto_to_serialized_xspace(_text(early)))
    return SimpleNamespace(trace=xplane.reduce_file(path), facts={})


@pytest.fixture()
def traced(tmp_path, monkeypatch):
    """The read context of a run whose capture is the hand-made trace."""
    monkeypatch.setattr(manifest, "REPO", str(tmp_path))
    return _context(str(tmp_path))


def _read(bench, rc, name):
    with open(os.path.join(manifest.HERE, "layer_metrics", name + ".json")) as f:
        how = json.load(f)
    return bench.module("readers", how["reader"]).read(rc, **how["args"])


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


@pytest.mark.parametrize("name", NEW)
def test_known_answers(bench, traced, name):
    assert _read(bench, traced, name) == pytest.approx(WANT[name], rel=1e-6)


def test_the_idle_split_and_prefill_make_up_the_idle_time(bench, traced):
    t = traced.trace
    assert xplane.total(t.gaps()) == pytest.approx(300 * US)
    per_step = (_read(bench, traced, "engine_idle_host_ms")
                + _read(bench, traced, "engine_idle_readback_ms")) / 1000.0
    under_prefill = spans.covered(t.gaps(),
                                  spans.intervals(t, "engine.prefill"))
    assert under_prefill == pytest.approx(65 * US)
    assert 4 * per_step + under_prefill == pytest.approx(300 * US)


def test_device_stamps_that_lead_the_host_are_moved_back(bench, tmp_path,
                                                          monkeypatch):
    monkeypatch.setattr(manifest, "REPO", str(tmp_path))
    rc = _context(str(tmp_path), early=True)
    assert spans.device_lead(rc.trace) == pytest.approx(15 * US)
    assert _read(bench, rc, "engine_idle_readback_ms") == pytest.approx(0.0225)
    assert _read(bench, rc, "engine_idle_host_ms") == pytest.approx(0.035)
    # the phases' own clock is the host's: nothing else moves, but that the
    # window, which is the device's, now ends 20 earlier in the last call
    for name in NEW:
        if "idle" not in name and name != "replica_loop_busy_share":
            assert _read(bench, rc, name) == pytest.approx(WANT[name])
    assert _read(bench, rc, "replica_loop_busy_share") == pytest.approx(44.0)
    # a device that starts after its launch is latency, not an offset
    rc.trace.host = [(n, s - 30 * US if n == spans.LAUNCH else s,
                      e - 30 * US if n == spans.LAUNCH else e)
                     for n, s, e in rc.trace.host]
    assert spans.device_lead(rc.trace) == 0.0
    # and with no launch in the capture the idle split reads nothing
    rc.trace.host = [h for h in rc.trace.host if h[0] != spans.LAUNCH]
    assert spans.device_lead(rc.trace) is None
    assert _read(bench, rc, "engine_idle_host_ms") is None
    assert _read(bench, rc, "engine_host_ms_p50") == pytest.approx(0.040)


def test_a_step_followed_by_a_prefill_is_left_out_of_the_host_part(traced):
    t = traced.trace
    steps = spans.intervals(t, "engine.step")
    assert len(steps) == 5
    assert steps[3][0] - steps[2][0] == pytest.approx(295 * US)  # S3 -> S4
    # with it the median of (35, 40, 45, 190) would be 42.5, not 40
    from benchmark.readers import engine_step_split

    assert engine_step_split.read(traced, "host") == pytest.approx(0.040)
    with pytest.raises(ValueError):
        engine_step_split.read(traced, "else")


def test_children_by_containment_and_counts_by_name(traced):
    t = traced.trace
    first = spans.intervals(t, "train.input")[0]
    kids = [n for n, s, e in t.host
            if n != "train.input" and spans.inside([(s, e)], first)]
    assert sorted(kids) == ["train.collate", "train.next_batch",
                            "train.put_batch"]
    calls = spans.phase_stats(["worker.actor_task"])
    assert [c[3]["method"] for c in calls] == ["poll", "poll", "submit",
                                               "poll"]
    assert spans.phase_stats(["engine.prefill"])[1][3] == {
        "rows": 3, "batch": 4, "queued": 0}
    # the profiler's own Python events name no phase
    assert not [n for n, _, _ in t.host if n.startswith("$")]
    assert spans.clip([(0, 2), (3, 5), (6, 7)], (1, 4)) == [(1, 2), (3, 4)]


@pytest.mark.parametrize("name", NEW)
def test_a_trace_with_no_phase_reads_none(bench, tmp_path, monkeypatch, name):
    """data/v5e_small.xplane.pb is a trace of a program without phases, as the
    parent commit's are: every reader gives None, none raises."""
    monkeypatch.setattr(manifest, "REPO", str(tmp_path))
    with open(os.path.join(DATA, "v5e_small.xplane.pb"), "rb") as f:
        path = _as_traced_run(str(tmp_path), "t5large-serve", f.read())
    rc = SimpleNamespace(trace=xplane.reduce_file(path), facts={})
    assert rc.trace is not None and rc.trace.host
    assert _read(bench, rc, name) is None
    # nor without a trace at all (an untraced run, a run with --root)
    shutil.rmtree(os.path.join(str(tmp_path), harness.TRACE_DIR))
    assert spans.newest_xplane() is None
    assert _read(bench, SimpleNamespace(trace=None, facts={}), name) is None


def test_the_newest_capture_is_read_whichever_cell_wrote_it(tmp_path,
                                                            monkeypatch):
    from jax.profiler import ProfileData

    monkeypatch.setattr(manifest, "REPO", str(tmp_path))
    with open(os.path.join(DATA, "v5e_small.xplane.pb"), "rb") as f:
        old = _as_traced_run(str(tmp_path), "t5base-batchgen", f.read())
    os.utime(old, (1, 1))
    new = _as_traced_run(str(tmp_path), "t5large-serve",
                         ProfileData.text_proto_to_serialized_xspace(_text()))
    assert spans.newest_xplane() == new
    assert len(spans.phase_stats(["engine.step"])) == 5


def test_the_manifest_holds_the_seven_as_program_spans(bench):
    by_name = {m["name"]: m for m in bench.doc["per_layer"]}
    assert [m["name"] for m in bench.doc["per_layer"][-7:]] == NEW
    for name in NEW:
        assert by_name[name]["source"] == "program_span"
    serve = {m["name"] for m in bench.metrics("per_layer", "t5large-serve")}
    assert set(NEW[:6]) <= serve and "train_input_ms_p50" not in serve
    # on one chip the host issues an epoch ahead of the device and the
    # cell's capture holds no train.* phase (PERF.md, PR 24): dp4 only
    assert "train_input_ms_p50" in {
        m["name"] for m in bench.metrics("per_layer", "t5base-finetune-dp4")}
    for cell in ("t5base-finetune", "t5base-batchgen"):
        assert not set(NEW) & {
            m["name"] for m in bench.metrics("per_layer", cell)}
