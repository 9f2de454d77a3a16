"""airtrace tests — span recording, W3C propagation, chrome-trace export,
cross-boundary context (tasks, actors, worker death, HTTP proxy).

The first block is jax-free and fast (<2s): it exercises the tracing module
and exporter directly — the tier-1 smoke the tracing layer is gated on.
The second block uses the shared ``air`` runtime fixture to prove context
survives real process boundaries.
"""

import json
import urllib.request

import pytest

from tpu_air.observability import trace_export, tracing


@pytest.fixture(autouse=True)
def _clean_tracing():
    """Every test starts disabled with an empty recorder and leaves the
    module the same way (tracing is global state)."""
    tracing.disable()
    tracing.recorder().clear()
    yield
    tracing.disable()
    tracing.recorder().clear()


# ---------------------------------------------------------------------------
# unit: ids, traceparent, enable flag
# ---------------------------------------------------------------------------


def test_id_widths():
    assert len(tracing.new_trace_id()) == 32
    assert len(tracing.new_span_id()) == 16
    int(tracing.new_trace_id(), 16)  # hex


def test_traceparent_round_trip():
    ctx = tracing.SpanContext(tracing.new_trace_id(), tracing.new_span_id())
    header = tracing.format_traceparent(ctx)
    assert header == f"00-{ctx.trace_id}-{ctx.span_id}-01"
    back = tracing.extract_traceparent(header)
    assert back == ctx


def test_traceparent_rejects_malformed():
    assert tracing.extract_traceparent(None) is None
    assert tracing.extract_traceparent("") is None
    assert tracing.extract_traceparent("garbage") is None
    assert tracing.extract_traceparent("00-zz-zz-01") is None
    # ff version and all-zero ids are invalid per the W3C spec
    assert tracing.extract_traceparent(f"ff-{'a' * 32}-{'b' * 16}-01") is None
    assert tracing.extract_traceparent(f"00-{'0' * 32}-{'b' * 16}-01") is None
    assert tracing.extract_traceparent(f"00-{'a' * 32}-{'0' * 16}-01") is None


def test_disabled_path_is_allocation_free():
    assert not tracing.enabled()
    s1 = tracing.span("a")
    s2 = tracing.span("b")
    assert s1 is s2 is tracing._NOOP  # singleton, no per-call allocation
    with s1 as sp:
        sp.set_attr("k", "v")  # all no-ops
        assert sp.trace_id is None
    assert len(tracing.recorder()) == 0
    assert tracing.current_propagation() is None


def test_span_nesting_and_parenting():
    tracing.enable()
    with tracing.span("parent") as p:
        assert tracing.current_trace_id() == p.trace_id
        with tracing.span("child") as c:
            assert c.trace_id == p.trace_id
            assert c.parent_id == p.span_id
    assert tracing.current_trace_id() is None
    spans = tracing.recorder().for_trace(p.trace_id)
    assert {s.name for s in spans} == {"parent", "child"}
    assert all(s.end_ns >= s.start_ns for s in spans)


def test_span_error_status():
    tracing.enable()
    with pytest.raises(ValueError):
        with tracing.span("boom") as sp:
            raise ValueError("x")
    assert sp.status == "error:ValueError"


def test_task_span_force_records_when_carrier_present():
    # sender had tracing on; receiver's flag is off — must still record
    assert not tracing.enabled()
    carrier = {"trace_id": "a" * 32, "span_id": "b" * 16}
    with tracing.task_span("task.f", carrier) as sp:
        pass
    assert sp.trace_id == "a" * 32 and sp.parent_id == "b" * 16
    assert len(tracing.recorder()) == 1
    # no carrier + disabled → noop
    assert tracing.task_span("task.g", None) is tracing._NOOP


def test_ring_buffer_caps_and_counts_drops():
    rec = tracing.SpanRecorder(capacity=4)
    for i in range(7):
        rec.record(tracing.Span(f"s{i}", "t" * 32, f"{i:016d}"))
    assert len(rec) == 4
    st = rec.stats()
    assert st["recorded_total"] == 7 and st["dropped"] == 3
    assert [s.name for s in rec.recent(2)] == ["s5", "s6"]


def test_recorder_drain():
    tracing.enable()
    with tracing.span("x"):
        pass
    assert tracing.drain_if_any() is not None
    assert tracing.drain_if_any() is None  # empty → None, no allocation
    assert len(tracing.recorder()) == 0


# ---------------------------------------------------------------------------
# unit: chrome-trace export (the tier-1 no-jax smoke: record + export <2s)
# ---------------------------------------------------------------------------


def test_chrome_trace_export_schema():
    tracing.enable()
    with tracing.span("root", attrs={"k": 1}):
        with tracing.span("inner"):
            pass
    doc = trace_export.to_chrome_trace()
    assert doc["displayTimeUnit"] == "ms"
    events = doc["traceEvents"]
    meta = [e for e in events if e["ph"] == "M"]
    complete = [e for e in events if e["ph"] == "X"]
    assert meta and meta[0]["name"] == "process_name"
    assert len(complete) == 2
    for ev in complete:
        # the event-schema fields chrome://tracing requires
        for field in ("name", "ph", "ts", "dur", "pid", "tid", "args"):
            assert field in ev, f"missing {field}"
        assert isinstance(ev["ts"], float) and isinstance(ev["dur"], float)
        assert ev["dur"] >= 0
        assert len(ev["args"]["trace_id"]) == 32
    # the whole doc must be JSON-serializable as-is
    json.loads(trace_export.export_json())


def test_export_single_trace_filter(tmp_path):
    tracing.enable()
    with tracing.span("keep") as kept:
        pass
    with tracing.span("other"):
        pass
    doc = trace_export.to_chrome_trace(trace_id=kept.trace_id)
    names = [e["name"] for e in doc["traceEvents"] if e["ph"] == "X"]
    assert names == ["keep"]
    out = tmp_path / "trace.json"
    n = trace_export.export_file(str(out), trace_id=kept.trace_id)
    assert n == 1 and json.loads(out.read_text())["otherData"]["spans"] == 1


def test_trace_summaries_group_by_trace():
    tracing.enable()
    with tracing.span("req"):
        with tracing.span("sub"):
            pass
    with tracing.span("lone"):
        pass
    summaries = tracing.trace_summaries()
    assert len(summaries) == 2
    by_root = {t["root"]: t for t in summaries}
    assert by_root["req"]["spans"] == 2
    assert by_root["lone"]["spans"] == 1
    # newest first
    assert summaries[0]["start_ns"] >= summaries[1]["start_ns"]


# ---------------------------------------------------------------------------
# unit: prometheus metric-name sanitization (satellite)
# ---------------------------------------------------------------------------


def test_sanitize_metric_name():
    from tpu_air.utils.metrics import sanitize_metric_name

    assert sanitize_metric_name("loss") == "loss"
    assert sanitize_metric_name("val.loss") == "val_loss"
    assert sanitize_metric_name("grad-norm/layer.0") == "grad_norm_layer_0"
    assert sanitize_metric_name("9lives") == "_9lives"
    assert sanitize_metric_name("") == "_"
    # result is always a valid prometheus identifier
    import re

    for raw in ("a.b-c/d", "Ω", "x y", "ns:ok"):
        assert re.match(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$", sanitize_metric_name(raw))


# ---------------------------------------------------------------------------
# integration: context survives the runtime's process boundaries
# ---------------------------------------------------------------------------


def test_trace_context_survives_task_submission(air):
    import tpu_air

    tracing.enable()

    @tpu_air.remote
    def traced_work(x):
        return x * 2

    with tracing.span("driver.op") as root:
        ref = traced_work.remote(21)
        assert tpu_air.get(ref, timeout=60) == 42
    # the worker-side task span ships back on the done message and parents
    # under the driver span
    deadline_spans = _wait_for_trace(root.trace_id, want_names={"task.traced_work"})
    task_spans = [s for s in deadline_spans if s.name == "task.traced_work"]
    assert task_spans, f"no task span in {[s.name for s in deadline_spans]}"
    assert task_spans[0].parent_id == root.span_id
    assert task_spans[0].pid != root.pid  # recorded in the worker process


def test_trace_context_survives_actor_method_call(air):
    import tpu_air

    tracing.enable()

    @tpu_air.remote
    class Counter:
        def __init__(self):
            self.n = 0

        def incr(self):
            self.n += 1
            return self.n

    with tracing.span("driver.actor_op") as root:
        c = Counter.remote()
        assert tpu_air.get(c.incr.remote(), timeout=60) == 1
    spans = _wait_for_trace(root.trace_id, want_names={"actor.Counter.incr"})
    call_spans = [s for s in spans if s.name == "actor.Counter.incr"]
    assert call_spans and call_spans[0].trace_id == root.trace_id


def test_worker_death_remote_error_carries_trace_id(air):
    import os

    import tpu_air
    from tpu_air.core.runtime import RemoteError

    tracing.enable()

    @tpu_air.remote
    def die():
        os._exit(1)

    with tracing.span("driver.doomed") as root:
        ref = die.remote()
        with pytest.raises(RemoteError) as exc_info:
            tpu_air.get(ref, timeout=60)
    assert exc_info.value.cause_repr.startswith("WorkerCrashed")
    assert exc_info.value.trace_id == root.trace_id


def test_application_error_carries_trace_id(air):
    import tpu_air
    from tpu_air.core.runtime import RemoteError

    tracing.enable()

    @tpu_air.remote
    def raise_value_error():
        raise ValueError("bad")

    with tracing.span("driver.failing") as root:
        with pytest.raises(RemoteError) as exc_info:
            tpu_air.get(raise_value_error.remote(), timeout=60)
    assert exc_info.value.trace_id == root.trace_id


def _wait_for_trace(trace_id, want_names, timeout=30.0):
    """Worker spans arrive asynchronously on the done control message;
    poll the driver recorder until the wanted span names show up."""
    import time

    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        spans = tracing.recorder().for_trace(trace_id)
        if want_names <= {s.name for s in spans}:
            return spans
        time.sleep(0.05)
    return tracing.recorder().for_trace(trace_id)


# ---------------------------------------------------------------------------
# integration: proxy traceparent round trip + connected trace
# ---------------------------------------------------------------------------

TRACE_PORT = 8129


def test_proxy_traceparent_round_trip(air):
    from tpu_air import serve

    tracing.enable()

    @serve.deployment
    class Echo:
        def __call__(self, payload):
            return {"echo": payload}

    try:
        serve.run(Echo.options(name="echo", route_prefix="/echo").bind(),
                  port=TRACE_PORT)
        inbound_trace = "c" * 32
        req = urllib.request.Request(
            f"http://127.0.0.1:{TRACE_PORT}/echo",
            data=json.dumps({"hi": 1}).encode(),
            headers={
                "Content-Type": "application/json",
                "traceparent": f"00-{inbound_trace}-{'d' * 16}-01",
            },
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            # the proxy continues the inbound trace and surfaces it back
            assert resp.headers["x-tpu-air-trace-id"] == inbound_trace
            returned = tracing.extract_traceparent(resp.headers["traceparent"])
            assert returned is not None and returned.trace_id == inbound_trace
        spans = _wait_for_trace(inbound_trace, want_names={"http.request"})
        roots = [s for s in spans if s.name == "http.request"]
        assert roots and roots[0].parent_id == "d" * 16
        # the replica-side deployment call parents under the proxy span
        actor_spans = [s for s in spans if s.name.startswith("actor.")]
        assert actor_spans, f"no replica span in {[s.name for s in spans]}"
        assert actor_spans[0].trace_id == inbound_trace
    finally:
        serve.shutdown()


def test_proxy_opens_root_span_without_inbound_header(air):
    from tpu_air import serve

    tracing.enable()

    @serve.deployment
    class Pong:
        def __call__(self, payload):
            return "pong"

    try:
        serve.run(Pong.options(name="pong", route_prefix="/pong").bind(),
                  port=TRACE_PORT + 1)
        req = urllib.request.Request(
            f"http://127.0.0.1:{TRACE_PORT + 1}/pong",
            data=b"{}", headers={"Content-Type": "application/json"},
            method="POST",
        )
        with urllib.request.urlopen(req, timeout=60) as resp:
            assert resp.status == 200
            trace_id = resp.headers["x-tpu-air-trace-id"]
        assert trace_id and len(trace_id) == 32
        spans = _wait_for_trace(trace_id, want_names={"http.request"})
        roots = [s for s in spans if s.name == "http.request"]
        assert roots and roots[0].parent_id is None  # fresh root
    finally:
        serve.shutdown()


# ---------------------------------------------------------------------------
# the engine's span tree: built at retirement from the request's one set of
# monotonic stamps (submitted_at, admitted_at, first_token_at)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def tiny_lm():
    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm import CausalLM, LMConfig

    model = CausalLM(LMConfig.tiny())
    params = model.init(jax.random.PRNGKey(0),
                        jnp.ones((1, 8), jnp.int32))["params"]
    return model, params


def _traced_engine_run(tiny_lm, name, under_span):
    """Three requests over two slots of a manual-step engine with airtrace
    enabled; returns the admitted requests and the recorder's spans."""
    from tpu_air.engine import EngineConfig, InferenceEngine

    model, params = tiny_lm
    tracing.enable()
    engine = InferenceEngine(
        model, params,
        EngineConfig(num_slots=2, slot_len=64, max_new_tokens=4,
                     eos_token_id=None),
        auto_start=False, name=name)
    admitted = []
    pop = engine.scheduler.pop_admissible

    def spy(*a, **kw):
        out = pop(*a, **kw)
        admitted.extend(out)
        return out

    engine.scheduler.pop_admissible = spy
    prompts = [[5, 6, 7], [8, 9, 10, 11], [12, 13]]
    root = None
    if under_span:
        with tracing.span("client.generate") as root:
            engine.generate(prompts)
    else:
        engine.generate(prompts)
    engine.close()
    return admitted, tracing.recorder().recent(limit=0), root


def _tree(spans, request_id):
    """The span tree of one request: its root and the children by name."""
    root = next(s for s in spans if s.name == "engine.request"
                and s.attrs["request_id"] == request_id)
    kids = {s.name: s for s in spans if s.parent_id == root.span_id}
    return root, kids


def test_engine_span_tree_names_parents_and_order(tiny_lm):
    admitted, spans, client = _traced_engine_run(
        tiny_lm, "trace-tree", under_span=True)
    assert len(admitted) == 3
    assert sorted(s.name for s in spans if s.name.startswith("engine.")) == (
        sorted(["engine.request", "engine.queue_wait", "engine.prefill",
                "engine.decode"] * 3))
    for req in admitted:
        assert req.trace_ctx["trace_id"] == client.trace_id
        root, kids = _tree(spans, req.request_id)
        assert root.trace_id == client.trace_id
        assert root.parent_id == client.span_id
        assert root.attrs == {"engine": "trace-tree",
                              "request_id": req.request_id}
        assert sorted(kids) == ["engine.decode", "engine.prefill",
                                "engine.queue_wait"]
        wait, prefill, decode = (kids["engine.queue_wait"],
                                 kids["engine.prefill"], kids["engine.decode"])
        assert all(k.trace_id == client.trace_id for k in kids.values())
        # one timeline: the wait opens the request, prefill follows it to the
        # first token, decode runs from there to retirement
        assert root.start_ns == wait.start_ns <= wait.end_ns
        assert wait.end_ns == prefill.start_ns < prefill.end_ns
        assert prefill.end_ns == decode.start_ns <= decode.end_ns
        assert decode.end_ns == root.end_ns
        assert wait.attrs == {}
        assert prefill.attrs == {
            "slot": prefill.attrs["slot"], "prompt_len": len(req.prompt),
            "chunks": 1, "prefix_hit": False, "prefix_tokens": 0}
        assert decode.attrs["tokens"] == 4
        assert decode.attrs["slot"] == prefill.attrs["slot"] in (0, 1)
        assert decode.attrs["occupancy"] >= 1


def test_engine_span_tree_is_the_requests_one_set_of_stamps(tiny_lm):
    """The spans' durations are the differences of the request's monotonic
    stamps (what ``stats()`` and ``engine.first_token`` read), moved onto the
    wall clock by one offset: to the nanosecond a float of seconds keeps."""
    import time

    admitted, spans, _ = _traced_engine_run(
        tiny_lm, "trace-stamps", under_span=True)
    now_wall, now_mono = time.time_ns(), time.monotonic()
    for req in admitted:
        root, kids = _tree(spans, req.request_id)
        wait, prefill = kids["engine.queue_wait"], kids["engine.prefill"]
        assert wait.end_ns - wait.start_ns == pytest.approx(
            1e9 * (req.admitted_at - req.submitted_at), abs=2)
        assert prefill.end_ns - prefill.start_ns == pytest.approx(
            1e9 * (req.first_token_at - req.admitted_at), abs=2)
        # and on the wall clock they lie where the stamps were taken (the
        # two clocks drift by far less than this over a test)
        assert root.start_ns == pytest.approx(
            now_wall - 1e9 * (now_mono - req.submitted_at), abs=5e7)


def test_enabled_airtrace_with_no_carrier_still_emits_the_tree(tiny_lm):
    """No active span at submit: the carrier is empty, not None, and the
    request is traced all the same, as a fresh root trace of its own."""
    admitted, spans, _ = _traced_engine_run(
        tiny_lm, "trace-no-carrier", under_span=False)
    assert [req.trace_ctx for req in admitted] == [{}] * 3
    roots = [s for s in spans if s.name == "engine.request"]
    assert len(roots) == 3 and all(r.parent_id is None for r in roots)
    assert len({r.trace_id for r in roots}) == 3
    for req in admitted:
        root, kids = _tree(spans, req.request_id)
        assert sorted(kids) == ["engine.decode", "engine.prefill",
                                "engine.queue_wait"]
        assert all(k.trace_id == root.trace_id for k in kids.values())


def test_disabled_airtrace_leaves_stamps_and_no_carrier(tiny_lm):
    from tpu_air.engine import Request, ResponseStream, Scheduler
    from tpu_air.engine import EngineConfig

    assert not tracing.enabled()
    s = Scheduler(EngineConfig(num_slots=2))
    req = Request(request_id=0, prompt=[1, 2], max_new_tokens=2,
                  stream=ResponseStream(0))
    s.submit(req)
    assert req.trace_ctx is None and req.admitted_at is None
    (out,) = s.pop_admissible(1)
    assert out is req and req.admitted_at >= req.submitted_at
    assert not any(hasattr(req, k)
                   for k in ("t_submit_ns", "t_admit_ns", "t_first_ns"))
    assert len(tracing.recorder()) == 0
