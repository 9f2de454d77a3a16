"""airscope tests — histograms, cost model, perf ledger, SLO burn rates,
exposition format, exemplar→trace join, postmortems.

Everything here is CPU/tier-1: the cost-model numbers are hand-computed
from the closed-form geometry formulas, burn-rate windows run on an
injected clock, and the exposition test parses /metrics line by line
against the prometheus text-format grammar.
"""

import json
import re
import types
import urllib.request

import pytest

from tpu_air.observability import perf, slo
from tpu_air.observability.perf import (
    Histogram,
    LMCostModel,
    PeakSpec,
    PerfLedger,
    ProgramCost,
    bucket_index,
    bucket_upper,
)


@pytest.fixture(autouse=True)
def _clean_slo_registry():
    """The SLO monitor registry is process-global state; leave it empty."""
    slo.install(None)
    yield
    slo.install(None)


# ---------------------------------------------------------------------------
# histogram units
# ---------------------------------------------------------------------------


def test_bucket_bounds_partition_the_line():
    # every value lands in exactly one bucket, and bucket i's range is
    # (upper(i-1), upper(i)]
    for v in (1e-9, 1e-6, 0.001, 0.5, 1.0, 1.5, 2.0, 123.456, 9e5):
        i = bucket_index(v)
        assert v <= bucket_upper(i) * (1 + 1e-12)
        assert v > bucket_upper(i - 1) * (1 - 1e-12)
    # exact powers of the base stay in their own bucket
    assert bucket_index(1.0) == 0
    assert bucket_index(2.0) == 4  # base = 2**(1/4)
    assert bucket_upper(4) == pytest.approx(2.0)


def test_quantile_relative_error_bounded():
    h = Histogram()
    vals = [0.001 * i for i in range(1, 1001)]  # 1ms .. 1s uniform
    for v in vals:
        h.observe(v)
    # log-bucketing with base 2**(1/4) bounds relative quantile error ~9%
    for q in (0.5, 0.9, 0.95, 0.99):
        true = vals[int(q * len(vals)) - 1]
        assert h.quantile(q) == pytest.approx(true, rel=0.09)
    s = h.summary()
    assert s["count"] == 1000
    assert s["min"] == pytest.approx(0.001)
    assert s["max"] == pytest.approx(1.0)
    assert s["sum"] == pytest.approx(sum(vals))


def test_quantile_clamps_to_observed_extremes():
    h = Histogram()
    h.observe(0.5)
    assert h.quantile(0.0) == 0.5
    assert h.quantile(1.0) == 0.5
    assert h.quantile(0.99) == 0.5


def test_empty_and_reset():
    h = Histogram()
    assert h.summary() == {"count": 0}
    assert h.quantile(0.5) == 0.0
    h.observe(1.0)
    h.reset()
    assert h.summary() == {"count": 0}


def test_merge_equals_union():
    a, b, u = Histogram(), Histogram(), Histogram()
    for i in range(1, 500):
        a.observe(i * 0.003)
        u.observe(i * 0.003)
    for i in range(1, 500):
        b.observe(i * 0.010)
        u.observe(i * 0.010)
    a.merge(b)
    sa, su = a.summary(), u.summary()
    assert sa["count"] == su["count"]
    assert sa["buckets"] == su["buckets"]
    assert sa["p99"] == pytest.approx(su["p99"])
    assert sa["min"] == pytest.approx(su["min"])
    assert sa["max"] == pytest.approx(su["max"])


def test_dict_round_trip_through_json():
    h = Histogram()
    for i in range(100):
        h.observe(0.01 + i * 0.001, trace_id="t" * 32)
    state = json.loads(json.dumps(h.to_dict()))
    back = Histogram.from_dict(state)
    assert back.summary()["buckets"] == h.summary()["buckets"]
    assert back.count == h.count


def test_exemplar_tracks_worst_sample_per_bucket():
    h = Histogram()
    h.observe(1.0, trace_id="a" * 32)
    h.observe(1.05, trace_id="b" * 32)  # same bucket, larger → replaces
    h.observe(1.01, trace_id="c" * 32)  # same bucket, smaller → kept out
    h.observe(64.0, trace_id="d" * 32)  # far bucket: the p99 exemplar
    s = h.summary()
    exs = s["exemplars"]
    idx = bucket_index(1.05)
    assert exs[str(idx)]["trace_id"] == "b" * 32
    assert perf.exemplar_trace_id(s) == "d" * 32
    # exemplar-less summaries answer None
    assert perf.exemplar_trace_id({"count": 3, "buckets": {"0": 3}}) is None


def test_merge_summaries_handles_legacy_dicts():
    h = Histogram()
    for _ in range(10):
        h.observe(0.01)
    legacy = {"count": 5, "p99": 3.0, "max": 4.0}  # no buckets (pre-airscope)
    merged = perf.merge_summaries([h.summary(), legacy, {}, {"count": 0}])
    assert merged["count"] == 15
    assert merged["p99"] >= 3.0
    assert merged["max"] >= 4.0


# ---------------------------------------------------------------------------
# cost model — hand-computed spot checks
# ---------------------------------------------------------------------------

# tiny geometry, small enough to hand-verify every formula:
# D=8, L=2, H=2, Dh=4, F=16, V=32, f32 (4B), tied embeddings
_GEOM = types.SimpleNamespace(d_model=8, n_layers=2, n_heads=2, head_dim=4,
                              d_ff=16, vocab_size=32)


def test_cost_model_geometry():
    m = LMCostModel(_GEOM)
    # per layer: qkvo 4*8*8=256, swiglu 3*8*16=384 → 640; ×2 layers
    assert m.matmul_params == 1280
    assert m.param_count == 32 * 8 + 1280  # + tied embedding
    assert m.param_bytes == 1536 * 4
    # 2 flops/MAC over matmuls + lm head 8*32
    assert m.linear_flops_per_token == 2 * (1280 + 256)
    # K and V, all layers: L(2) * KV(2) * H(2) * Dh(4) * 4B
    assert m.kv_bytes_per_position == 128
    # QK^T + AV = 4 flops per (head_dim, position) pair per layer
    assert m.attention_flops(10) == 2 * 4 * 2 * 4 * 10


def test_decode_step_cost_hand_computed():
    m = LMCostModel(_GEOM)
    c = m.decode_step_cost(rows=3, attended=10)
    assert c.flops == 3 * (3072 + 640)            # 11136
    assert c.hbm_bytes == 6144 + 3 * 10 * 128 + 3 * 128  # 10368
    assert c.tokens == 3


def test_prefill_chunk_cost_hand_computed():
    m = LMCostModel(_GEOM)
    c = m.prefill_chunk_cost(chunk_len=4, start_pos=8)
    # attended positions: token t attends 8+t+1 → 9+10+11+12 = 42
    assert c.flops == 4 * 3072 + 64 * 42          # 14976
    assert c.hbm_bytes == 6144 + 12 * 128 + 4 * 128  # 8192
    assert c.tokens == 4


def test_mixed_step_cost_streams_the_weights_once():
    """A decode step and a prefill chunk in one program: the operations and
    the per-sequence bytes of both, the parameters once."""
    m = LMCostModel(_GEOM)
    c = m.mixed_step_cost(rows=3, attended=10, chunk_len=4, start_pos=8)
    assert c.flops == 11136 + 14976
    assert c.hbm_bytes == 10368 + 8192 - 6144
    assert c.tokens == 7


def test_train_step_cost_hand_computed():
    m = LMCostModel(_GEOM)
    c = m.train_step_cost(batch=2, seq_len=3)
    # fwd: 6 tokens linear + causal attention sum 2*(1+2+3); bwd = 2×fwd
    assert c.flops == 3 * (6 * 3072 + 64 * 12)    # 57600
    assert c.hbm_bytes == 3 * 6144 + 2 * 6 * 128  # 19968
    assert c.tokens == 6


def test_ledger_roofline_and_goodput():
    led = PerfLedger(peak=PeakSpec(1e9, 1e9, "test"))
    # compute-bound program: ideal = max(5e8/1e9, 1e8/1e9) = 0.5s over 1.0s
    led.record_program("decode_step", ProgramCost(5e8, 1e8, tokens=100), 1.0)
    led.record_tokens("useful", 90)
    led.record_tokens("shed_after_prefill", 10)
    snap = led.snapshot()
    assert snap["totals"]["roofline_fraction"] == pytest.approx(0.5)
    assert snap["totals"]["flops_per_s"] == pytest.approx(5e8)
    assert snap["programs"]["decode_step"]["calls"] == 1
    assert snap["goodput"]["goodput_ratio"] == pytest.approx(0.9)
    assert snap["goodput"]["wasted"] == 10
    # empty ledger: ratio defaults to 1.0 (nothing wasted), fraction 0
    empty = PerfLedger(peak=PeakSpec(1e9, 1e9, "test")).snapshot()
    assert empty["goodput"]["goodput_ratio"] == 1.0
    assert empty["totals"]["roofline_fraction"] == 0.0


def test_merge_ledger_snapshots():
    a = PerfLedger(peak=PeakSpec(1e9, 1e9, "test"))
    b = PerfLedger(peak=PeakSpec(1e9, 1e9, "test"))
    a.record_program("decode_step", ProgramCost(4e8, 1e8, tokens=10), 1.0)
    b.record_program("decode_step", ProgramCost(6e8, 1e8, tokens=10), 1.0)
    a.record_tokens("useful", 50)
    b.record_tokens("dead_stream", 50)
    merged = perf.merge_ledger_snapshots([a.snapshot(), b.snapshot()])
    p = merged["programs"]["decode_step"]
    assert p["calls"] == 2
    assert p["flops"] == pytest.approx(1e9)
    assert p["seconds"] == pytest.approx(2.0)
    assert merged["totals"]["flops_per_s"] == pytest.approx(5e8)
    assert merged["goodput"]["goodput_ratio"] == pytest.approx(0.5)
    assert perf.merge_ledger_snapshots([]) == {}


def test_detect_peak_env_override(monkeypatch):
    monkeypatch.setenv("TPU_AIR_PEAK_FLOPS", "1e15")
    monkeypatch.setenv("TPU_AIR_PEAK_BYTES", "2e12")
    p = perf.detect_peak()
    assert p.flops_per_s == 1e15
    assert p.bytes_per_s == 2e12
    assert p.source == "env"
    # half an override would leave the other half to be invented
    monkeypatch.delenv("TPU_AIR_PEAK_BYTES")
    with pytest.raises(ValueError, match="together"):
        perf.detect_peak()


class _FakeDevice:
    def __init__(self, platform, device_kind):
        self.platform, self.device_kind = platform, device_kind


@pytest.mark.parametrize("device, want", [
    (None, None),  # this host's own CPU backend: no peak, no fraction
    (_FakeDevice("tpu", "TPU v5 lite"), (197e12, 819e9, "TPU v5 lite")),
    (_FakeDevice("tpu", "TPU v99"), ValueError),
    (_FakeDevice("gpu", "H100"), ValueError),
])
def test_detect_peak_by_device(monkeypatch, device, want):
    """A known TPU kind has both peaks from one table; an unknown device
    raises; the CPU has none — and then the ledger publishes rates but no
    roofline share."""
    import jax

    monkeypatch.delenv("TPU_AIR_PEAK_FLOPS", raising=False)
    monkeypatch.delenv("TPU_AIR_PEAK_BYTES", raising=False)
    if device is not None:
        monkeypatch.setattr(jax, "devices", lambda *a: [device])
    if want is ValueError:
        with pytest.raises(ValueError, match="no peak"):
            perf.detect_peak()
        return
    peak = perf.detect_peak()
    if want is None:
        assert peak is None
        led = PerfLedger(peak)
        led.record_program("step", ProgramCost(1e9, 1e9), 1.0)
        snap = led.snapshot()
        assert snap["peak"] is None
        assert snap["totals"]["roofline_fraction"] is None
        assert snap["programs"]["step"]["roofline_fraction"] is None
        assert snap["totals"]["flops_per_s"] == pytest.approx(1e9)
        merged = perf.merge_ledger_snapshots([snap, snap])
        assert merged["totals"]["roofline_fraction"] is None
    else:
        assert (peak.flops_per_s, peak.bytes_per_s, peak.source) == want


# ---------------------------------------------------------------------------
# SLO burn-rate math (injected clock)
# ---------------------------------------------------------------------------


def _snap(good, bad):
    """One engine snapshot whose ttft_s histogram has ``good`` samples at
    ~0.5s (≤1s threshold) and ``bad`` at ~2s (>1s)."""
    buckets = {}
    if good:
        buckets[str(bucket_index(0.5))] = good
    if bad:
        buckets[str(bucket_index(2.0))] = bad
    return {"e": {"ttft_s": {"count": good + bad, "buckets": buckets}}}


def _mk_monitor(clock):
    s = slo.SLO(name="ttft", metric="ttft_s", threshold_s=1.0,
                objective=0.99, windows=((60.0, 2.0), (300.0, 1.0)))
    return slo.SLOMonitor([s], now=lambda: clock[0])


def test_count_le_interpolates_in_straddling_bucket():
    # one bucket covering (upper(i-1), upper(i)]; a threshold mid-bucket
    # credits the linear fraction of its samples
    i = bucket_index(2.0)
    lo, hi = bucket_upper(i - 1), bucket_upper(i)
    mid = (lo + hi) / 2
    assert slo.count_le({str(i): 100}, mid) == pytest.approx(50.0)
    assert slo.count_le({str(i): 100}, hi) == 100.0
    assert slo.count_le({str(i): 100}, lo) == 0.0


def test_burn_rate_windows():
    clock = [0.0]
    mon = _mk_monitor(clock)
    # healthy start: 1000 good, 0 bad
    mon.observe(_snap(1000, 0))
    st = mon.state()[0]
    assert not st["burning"]
    assert all(w["burn_rate"] == 0.0 for w in st["windows"])
    # 30s later every new event is an error: 100 new, all bad
    clock[0] = 30.0
    mon.observe(_snap(1000, 100))
    st = mon.state()[0]
    # windowed error rate = 100/100 = 1.0 → burn = 1.0/0.01 = 100x
    for w in st["windows"]:
        assert w["error_rate"] == pytest.approx(1.0)
        assert w["burn_rate"] == pytest.approx(100.0)
        assert w["exceeded"]
    assert st["burning"]
    assert mon.burning() == ["ttft"]


def test_burn_requires_every_window():
    clock = [0.0]
    mon = _mk_monitor(clock)
    mon.observe(_snap(0, 0))
    # a burst of errors, then a healthy stretch: the short window recovers
    # (no recent errors) while the long window still remembers the burst —
    # NOT burning, because burning needs ALL windows
    clock[0] = 10.0
    mon.observe(_snap(0, 50))
    clock[0] = 250.0
    mon.observe(_snap(50, 50))
    st = mon.state()[0]
    short, long_ = st["windows"]
    assert not short["exceeded"]   # last 60s: only good events arrived
    assert long_["exceeded"]       # since t=0: half of all events erred
    assert not st["burning"]
    assert mon.burning() == []


def test_counter_reset_clears_history():
    clock = [0.0]
    mon = _mk_monitor(clock)
    mon.observe(_snap(1000, 100))
    clock[0] = 10.0
    mon.observe(_snap(5, 0))  # totals dropped: engine restarted
    st = mon.state()[0]
    assert st["total"] == 5.0
    # one post-reset point → no deltas → nothing burning
    assert all(w["burn_rate"] == 0.0 for w in st["windows"])


def test_monitor_sums_across_snapshots():
    clock = [0.0]
    mon = _mk_monitor(clock)
    a = _snap(100, 0)["e"]
    b = _snap(0, 100)["e"]
    mon.observe({"a": a, "b": b})
    st = mon.state()[0]
    assert st["total"] == pytest.approx(200.0)
    assert st["good"] == pytest.approx(100.0)


def test_slo_validation():
    with pytest.raises(ValueError):
        slo.SLO(name="x", metric="m", threshold_s=1.0, objective=1.5)
    with pytest.raises(ValueError):
        slo.SLO(name="x", metric="m", threshold_s=-1.0)
    with pytest.raises(ValueError):
        slo.SLO(name="x", metric="m", threshold_s=1.0, windows=())
    with pytest.raises(ValueError):
        slo.SLOMonitor([slo.SLO(name="x", metric="m", threshold_s=1.0),
                        slo.SLO(name="x", metric="m2", threshold_s=1.0)])


def test_slo_prometheus_lines_have_headers():
    clock = [0.0]
    mon = _mk_monitor(clock)
    mon.observe(_snap(10, 0))
    lines = mon.prometheus_lines()
    families = {ln.split()[2] for ln in lines if ln.startswith("# HELP")}
    for fam in ("tpu_air_slo_burn_rate", "tpu_air_slo_burning",
                "tpu_air_slo_good_total", "tpu_air_slo_events_total"):
        assert fam in families
        assert any(ln.startswith(fam + "{") for ln in lines)


# ---------------------------------------------------------------------------
# autoscaler on burn
# ---------------------------------------------------------------------------


class _Handle:
    deployment_name = "d"

    def __init__(self, replicas=1):
        self.replicas = replicas
        self.ups = 0

    def num_replicas(self):
        return self.replicas

    def engine_stats(self):
        return {}

    def scale_up(self):
        self.ups += 1
        self.replicas += 1
        return True

    def scale_down(self):
        self.replicas -= 1
        return True


def test_autoscaler_scales_up_on_burning_slo():
    from tpu_air.serve.autoscaler import Autoscaler, AutoscalerConfig

    h = _Handle()
    a = Autoscaler(h, AutoscalerConfig(min_replicas=1, max_replicas=4),
                   slo_source=lambda: ("interactive-ttft",))
    # idle gauges alone would hold; the burning SLO forces the scale-up
    assert a.decide({}, 1) == "hold"
    assert a.tick() == "up"
    assert h.replicas == 2
    assert a.stats()["burning_slos"] == ["interactive-ttft"]
    # at max replicas the burn signal cannot add capacity
    h.replicas = 4
    assert a.decide({}, 4, burning=("interactive-ttft",)) == "down"


def test_autoscaler_survives_broken_slo_source():
    from tpu_air.serve.autoscaler import Autoscaler, AutoscalerConfig

    def boom():
        raise RuntimeError("slo source down")

    a = Autoscaler(_Handle(), AutoscalerConfig(), slo_source=boom)
    assert a.tick() == "hold"
    assert a.stats()["burning_slos"] == []


def test_autoscaler_default_source_reads_installed_monitor():
    from tpu_air.serve.autoscaler import _installed_monitor_burning

    assert _installed_monitor_burning() == ()  # none installed
    clock = [0.0]
    mon = _mk_monitor(clock)
    mon.observe(_snap(0, 0))
    clock[0] = 30.0
    mon.observe(_snap(0, 100))
    slo.install(mon)
    assert _installed_monitor_burning() == ("ttft",)


# ---------------------------------------------------------------------------
# exposition format — line-by-line parse of /metrics
# ---------------------------------------------------------------------------

_SAMPLE_RE = re.compile(
    r"^(?P<name>[a-zA-Z_:][a-zA-Z0-9_:]*)"
    r"(?P<labels>\{[^}]*\})?"
    r" (?P<value>[^ #]+)"
    r"(?P<exemplar> # \{trace_id=\"[^\"]+\"\} \S+ \S+)?$")
_HELP_RE = re.compile(r"^# HELP (?P<name>\S+) \S.*$")
_TYPE_RE = re.compile(
    r"^# TYPE (?P<name>\S+) (?P<type>gauge|counter|histogram)$")


def _parse_exposition(text):
    """Parse prometheus text format strictly; returns (families, samples)
    where families is {name: type} and samples is [(family, labels, value,
    exemplar)].  Raises AssertionError on any malformed or orphaned line."""
    families, helped, samples = {}, set(), []
    for ln in text.splitlines():
        if not ln:
            continue
        if ln.startswith("# HELP "):
            m = _HELP_RE.match(ln)
            assert m, f"malformed HELP line: {ln!r}"
            helped.add(m.group("name"))
            continue
        if ln.startswith("# TYPE "):
            m = _TYPE_RE.match(ln)
            assert m, f"malformed TYPE line: {ln!r}"
            families[m.group("name")] = m.group("type")
            continue
        assert not ln.startswith("#"), f"unknown comment line: {ln!r}"
        m = _SAMPLE_RE.match(ln)
        assert m, f"malformed sample line: {ln!r}"
        name = m.group("name")
        # resolve the family: histogram series use _bucket/_sum/_count
        fam = name
        for suffix in ("_bucket", "_sum", "_count"):
            if name.endswith(suffix) and name[: -len(suffix)] in families:
                fam = name[: -len(suffix)]
                break
        assert fam in families, f"sample without TYPE header: {ln!r}"
        assert fam in helped, f"sample without HELP header: {ln!r}"
        if m.group("exemplar"):
            assert families[fam] == "histogram", \
                f"exemplar on non-histogram family: {ln!r}"
            assert name.endswith("_bucket"), \
                f"exemplar outside _bucket series: {ln!r}"
        float(m.group("value"))  # parses as a number
        samples.append((fam, m.group("labels") or "", m.group("value"),
                        m.group("exemplar")))
    return families, samples


def _labels_of(sample):
    return dict(re.findall(r'(\w+)="([^"]*)"', sample[1]))


def test_metrics_exposition_parses_line_by_line(monkeypatch):
    from tpu_air.engine.metrics import EngineMetrics, unregister
    from tpu_air.observability import dashboard

    # a configured peak: off-chip the roofline families are absent
    monkeypatch.setenv("TPU_AIR_PEAK_FLOPS", "1e12")
    monkeypatch.setenv("TPU_AIR_PEAK_BYTES", "1e11")
    m = EngineMetrics(name="airscope-expo", num_slots=4)
    try:
        m.observe_gauges(queue_depth=2, slot_occupancy=3,
                         kvpool={"pages_free": 10, "pages_used": 6},
                         reordered_admits=1, prefill_chunks=7)
        m.record_submit("interactive")
        for i in range(50):
            m.record_ttft(0.0, 0.01 + i * 0.002, priority="interactive",
                          trace_id="ab" * 16)
        m.record_step(0.004, tokens=8)
        m.record_program("decode_step", ProgramCost(1e6, 1e5, tokens=8),
                         0.004)
        m.record_goodput("useful", 90)
        m.record_goodput("dead_stream", 10)
        m.set_topology(lease="L1", replicas=2)
        text = dashboard._prometheus_text()
    finally:
        unregister("airscope-expo")
    families, samples = _parse_exposition(text)

    mine = [s for s in samples
            if _labels_of(s).get("engine") == "airscope-expo"]
    fams = {s[0] for s in mine}
    # the headline families all surfaced for this engine
    for fam in ("tpu_air_engine_queue_depth", "tpu_air_engine_ttft_s",
                "tpu_air_engine_ttft_s_p99", "tpu_air_engine_step_latency_s",
                "tpu_air_engine_priority_ttft_s",
                "tpu_air_engine_kvpool_pages_free",
                "tpu_air_engine_roofline_fraction",
                "tpu_air_engine_goodput_ratio",
                "tpu_air_engine_tokens_wasted",
                "tpu_air_engine_topology_info"):
        assert fam in fams, f"{fam} missing from exposition"
    # histogram series are complete: +Inf bucket == _count == 50
    tt = [s for s in mine if s[0] == "tpu_air_engine_ttft_s"]
    inf = [s for s in tt if _labels_of(s).get("le") == "+Inf"]
    assert len(inf) == 1 and float(inf[0][2]) == 50.0
    # bucket series is cumulative (non-decreasing)
    cums = [float(s[2]) for s in tt if "le=" in s[1]]
    assert cums == sorted(cums)
    # at least one bucket carries the exemplar we recorded
    assert any(s[3] and "ab" * 16 in s[3] for s in tt)
    # slo families present too (the scrape installs the default monitor)
    assert "tpu_air_slo_burn_rate" in families


# ---------------------------------------------------------------------------
# exemplar → /api/traces join over live HTTP (the tier-1 acceptance path)
# ---------------------------------------------------------------------------


def test_exemplar_resolves_to_trace_over_http():
    from tpu_air.engine.metrics import EngineMetrics, unregister
    from tpu_air.observability import tracing
    from tpu_air.observability.dashboard import (start_dashboard,
                                                 stop_dashboard)

    tracing.enable()
    m = EngineMetrics(name="airscope-join", num_slots=1)
    url = start_dashboard(port=0)
    try:
        # a real recorded span whose trace_id becomes the TTFT exemplar —
        # exactly what engine.py does for traced requests
        with tracing.span("engine.request") as sp:
            with tracing.span("engine.prefill"):
                pass
            trace_id = sp.trace_id
        m.record_ttft(0.0, 2.5, priority="interactive", trace_id=trace_id)

        with urllib.request.urlopen(f"{url}/metrics", timeout=10) as r:
            text = r.read().decode()
        _, samples = _parse_exposition(text)
        exemplars = [s[3] for s in samples
                     if s[0] == "tpu_air_engine_ttft_s" and s[3]
                     and _labels_of(s).get("engine") == "airscope-join"]
        assert exemplars, "no exemplar surfaced on /metrics"
        got = re.search(r'trace_id="([0-9a-f]+)"', exemplars[0]).group(1)
        assert got == trace_id

        # the join: the exemplar's trace id resolves to its span tree
        with urllib.request.urlopen(
                f"{url}/api/traces?trace_id={got}", timeout=10) as r:
            payload = json.loads(r.read())
        names = {s["name"] for s in payload["spans"]}
        assert names == {"engine.request", "engine.prefill"}
    finally:
        stop_dashboard()
        unregister("airscope-join")
        tracing.disable()
        tracing.recorder().clear()


def test_api_slo_endpoint():
    from tpu_air.observability.dashboard import (start_dashboard,
                                                 stop_dashboard)

    url = start_dashboard(port=0)
    try:
        with urllib.request.urlopen(f"{url}/api/slo", timeout=10) as r:
            payload = json.loads(r.read())
        names = {s["name"] for s in payload["slos"]}
        assert {"interactive-ttft", "ttft"} <= names
        assert payload["burning"] == []
        for s in payload["slos"]:
            assert len(s["windows"]) == 2
    finally:
        stop_dashboard()


# ---------------------------------------------------------------------------
# postmortem round trip
# ---------------------------------------------------------------------------


def test_postmortem_round_trip(tmp_path):
    from tpu_air.observability import postmortem

    ctx = {"worker_id": 7, "pid": 4242, "actor_id": "a1",
           "busy_task": "t9", "outstanding_tasks": ["t9", "t10"],
           "trace_ids": []}
    path = postmortem.dump("WorkerCrashed(worker=7)", ctx,
                           directory=str(tmp_path))
    assert path is not None
    data = postmortem.load(path)
    assert data["schema"] == postmortem.SCHEMA
    assert data["reason"] == "WorkerCrashed(worker=7)"
    assert data["context"] == ctx
    assert "engines" in data and "traces" in data
    # the renderer consumes it without raising
    import io

    from tools.trace_dump import render_postmortem

    buf = io.StringIO()
    render_postmortem(data, out=buf)
    assert "WorkerCrashed(worker=7)" in buf.getvalue()
    assert "t10" in buf.getvalue()


def test_postmortem_disabled_and_never_raises(tmp_path, monkeypatch):
    from tpu_air.observability import postmortem

    monkeypatch.delenv(postmortem.ENV_DIR, raising=False)
    assert not postmortem.enabled()
    assert postmortem.dump("x") is None
    # unwritable target: swallowed, not raised
    assert postmortem.dump("x", directory="/proc/nope/nope") is None
    # env-gated path
    monkeypatch.setenv(postmortem.ENV_DIR, str(tmp_path))
    assert postmortem.enabled()
    path = postmortem.dump("env-gated")
    assert path and path.startswith(str(tmp_path))
    # load rejects non-postmortem JSON
    other = tmp_path / "other.json"
    other.write_text('{"schema": "something-else"}')
    with pytest.raises(ValueError):
        postmortem.load(str(other))


def test_postmortem_captures_live_engine_and_trace(tmp_path):
    from tpu_air.engine.metrics import EngineMetrics, unregister
    from tpu_air.observability import postmortem, tracing

    tracing.enable()
    m = EngineMetrics(name="airscope-pm", num_slots=1)
    try:
        with tracing.span("doomed.task") as sp:
            trace_id = sp.trace_id
        m.record_ttft(0.0, 0.1)
        path = postmortem.dump("crash", {"trace_ids": [trace_id]},
                               directory=str(tmp_path))
        data = postmortem.load(path)
        assert "airscope-pm" in data["engines"]
        spans = data["traces"]["spans"][trace_id]
        assert [s["name"] for s in spans] == ["doomed.task"]
    finally:
        unregister("airscope-pm")
        tracing.disable()
        tracing.recorder().clear()
