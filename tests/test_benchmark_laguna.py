"""What PR 60 added to the benchmark, checked on the CPU: the manifest with
the new cell, its configuration and traffic files, the seeded
published-layout weights, the cost functions, the new reader on a synthetic
capture, the verdicts against planted faults, the reference's copy, and a
rehearsal of the cell (control flow only: a CPU run prints no result
line)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import costs_swa, manifest, scopes, weights_swa, xplane
from benchmark.harness import ReadContext

RUN = os.path.join(manifest.REPO, "benchmark", "run.py")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL, CONFIG = "laguna-serve-mixedlen", "laguna-xs.2"
NEW = ["swa_step_roofline.decode", "swa_step_roofline.mixed",
       "swa_experts_roofline.decode", "swa_experts_roofline.mixed",
       "swa_window_read_roofline", "swa_full_read_roofline",
       "swa_window_share", "swa_full_share", "swa_window_held_share"]
# the accepted metrics the cell reports beside its own
LISTED = ["moe_shared_share", "moe_combine_share", "moe_load_max_over_mean",
          "engine_step_ms_p50", "engine_prefill_share",
          "engine_chunk_fused_share", "engine_mixed_step_share",
          "engine_decode_step_ms_p50", "engine_mixed_step_ms_p50",
          "engine_unscoped_share"]
STEP = ["lm_paged_decode_step", "lm_paged_mixed_step"]


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


def test_manifest_finds_the_cell_and_lists_it_where_it_reports(bench):
    manifest.validate(bench.doc)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert bench.doc["workloads"][10] is cell       # appended, not inserted
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    assert bench.doc["configs"][7]["name"] == CONFIG
    assert bench.doc["configs"][7]["reduced"] == [
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer"]
    assert bench.traffic(cell)["kind"] == "swaserve"
    assert hasattr(bench.module("kinds", "swaserve"), "deploy")
    e2e = {m["name"] for m in bench.metrics("end_to_end", CELL)}
    assert e2e == {"serve_tpot_p50_ms", "setup_s"}
    layer = {m["name"]: m for m in bench.metrics("per_layer", CELL)}
    assert set(layer) == set(NEW) | set(LISTED) | {
        "worker_compile_s", "worker_cold_compiles"}
    assert all(m["moves"] in e2e for m in layer.values())
    # a reader gated on another family's key would list and never report:
    # the experts' share of their roofline is this PR's own reading
    assert not {"moe_experts_roofline.decode", "moe_held_expert_roofline",
                "lm_kv_gather_share"} & set(layer)
    first = [m["name"] for m in bench.doc["per_layer"]].index(NEW[0])
    mine = bench.doc["per_layer"][first:first + len(NEW)]
    assert [m["name"] for m in mine] == NEW
    for m in mine:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["layer"] in ("engine", "kernels")
    assert [m["source"] for m in mine] == ["device_trace"] * 8 + [
        "program_counter"]
    # every reader of the new metrics is a file of the tree
    for m in mine:
        how = bench.load_json("layer_metrics", m["name"] + ".json")
        assert bench.find("readers", how["reader"] + ".py"), m["name"]
        assert len(how["doc"]) > 100
    # no other cell meets a reader or a hook of this PR
    for other in (w["name"] for w in bench.doc["workloads"][:10]):
        names = {m["name"] for m in bench.metrics("per_layer", other)}
        assert not names & set(NEW)
        assert bench.traffic(bench.cell(other))["kind"] != "swaserve"


def test_traffic_file_is_the_cell_the_issue_wrote(bench):
    t = bench.traffic(bench.cell(CELL))
    assert (t["num_slots"], t["slot_len"], t["page_len"],
            t["max_new_tokens"], t["max_batch"]) == (64, 4096, 256, 512, 64)
    assert t["prompt_len"] == {"median": 768, "sigma": 1.0, "min": 32,
                               "max": 3584}
    assert t["output_len"] == {"median": 160, "sigma": 0.7, "min": 32,
                               "max": 512}
    assert (t["priority"], t["poll_ms"], t["submit_threads"],
            t["poll_threads"], t["dtype"]) == ("batch", 50, 8, 12, "bfloat16")
    assert "schedule_seed" in t and "weights_seed" in t
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"], rel=0.02)
    # the requests the check is made on have wrapped the ring
    assert t["check_prompt_over"] == 768 and t["check_lowprec_bits"] == 3
    assert 0 < t["check_tie_eps"] < 0.1
    assert t["check_logit_tol"] < t["check_tie_margin"] < 1
    assert t["check_tie_margin"] < t["check_tie_tol"] <= 1
    assert 0.5 < t["check_kept_share"] < 1
    assert len(t["check_why"]) > 200
    # the longest prompt and answer fit a slot, and the check's fixed length
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= t["slot_len"]
    from tpu_air.serve.admission import AdmissionPolicy

    assert AdmissionPolicy().clamp_budget("batch", 512) == 512
    from benchmark import traffic

    lens = traffic.lognormal_lengths(np.random.default_rng(0), 1000,
                                     t["prompt_len"])
    # a tenth under 215, a tenth over 2,700: short and long in one queue
    assert 190 < np.quantile(lens, 0.1) < 240
    assert 2500 < np.quantile(lens, 0.9) < 2900


def test_published_weights_are_the_shapes_the_names_say(bench):
    cfg = bench.config(CONFIG)
    pub = weights_swa.Published(cfg, 60, "bfloat16")
    at = lambda key, **kw: weights_swa.names(cfg)[key].format(**kw)  # noqa: E731
    assert pub.shape(at("q", i=0)) == (48 * 128, 2048)
    assert pub.shape(at("q", i=2)) == (64 * 128, 2048)
    assert pub.shape(at("o", i=2)) == (2048, 64 * 128)
    assert pub.shape(at("g", i=4)) == (48, 2048)
    assert pub.shape(at("k", i=1)) == pub.shape(at("v", i=4)) == (1024, 2048)
    assert pub.shape(at("dense", i=0, m="down")) == (2048, 8192)
    assert pub.shape(at("expert", i=3, e=255, m="up")) == (512, 2048)
    assert pub.shape(at("shared", i=3, m="down")) == (2048, 512)
    assert pub.shape(at("router", i=1)) == (256, 2048)
    assert pub.shape(at("embed")) == pub.shape(at("head")) == (100352, 2048)
    with pytest.raises(KeyError):
        pub.shape("model.layers.1.self_attn.q_norm.weight")
    up = pub.tensor(at("expert", i=3, e=7, m="up"))
    assert up.shape == (512, 2048) and not up.flags.c_contiguous
    assert up.T.flags.c_contiguous              # column-major: kept transposed
    again = weights_swa.Published(cfg, 60, "bfloat16").tensor(
        at("expert", i=3, e=7, m="up"))
    np.testing.assert_array_equal(up.view(np.uint16), again.view(np.uint16))
    other = weights_swa.Published(cfg, 61, "bfloat16").tensor(
        at("expert", i=3, e=7, m="up"))
    assert (up.view(np.uint16) != other.view(np.uint16)).mean() > 0.9
    f32 = lambda a: np.asarray(a).astype(np.float32)  # noqa: E731
    assert f32(up).std() == pytest.approx(0.02, rel=0.05)
    # the gate is drawn wider, so that a token's gates run over (0, 1)
    assert f32(pub.tensor(at("g", i=1))).std() == pytest.approx(0.06, rel=0.1)
    # a router that is uneven and a bias that is not zero, dealt anew a layer
    rows = f32(pub.tensor(at("router", i=1))).std(-1)
    assert rows.max() / rows.min() > 2.5
    b1, b2 = (f32(pub.tensor(at("router_bias", i=i))) for i in (1, 2))
    assert b1.std() > 0 and not np.array_equal(b1, b2)
    assert sorted(b1.tolist()) == pytest.approx(sorted(b2.tolist()))
    assert (f32(pub.tensor(at("attn_norm", i=0))) == 1).all()


def test_the_checkpoint_is_the_importers_tree(tmp_path):
    """The tiny configuration through ``write_checkpoint``: the tree the
    replica loads is the importer's over the same tensors, leaf for leaf."""
    import jax

    from benchmark.kinds.swaserve import TINY
    from tpu_air.models.lm import hf_import

    ckpt = weights_swa.write_checkpoint(TINY, 3, "float32", str(tmp_path), 64)
    model, params = ckpt.get_model()
    assert model.config.layer_kinds() == ["attention", "window", "window",
                                          "window", "attention"]
    pub = weights_swa.Published(TINY, 3, "float32")
    want = hf_import.convert_laguna_state_dict(
        pub.tensor, model.config, names=weights_swa.names(TINY))
    got = jax.tree_util.tree_map(np.asarray, params)
    assert (jax.tree_util.tree_structure(got)
            == jax.tree_util.tree_structure(want))
    jax.tree_util.tree_map(np.testing.assert_array_equal, got, want)


def test_cost_functions_from_the_published_shapes(bench):
    cfg = bench.config(CONFIG)
    assert costs_swa.layer_counts(cfg) == {"full": 2, "window": 3,
                                           "dense": 1, "sparse": 4}
    assert costs_swa.attention_params(cfg, 0) == (
        2 * 2048 * 48 * 128 + 2 * 2048 * 1024 + 2048 * 48)
    assert costs_swa.attention_params(cfg, 1) == pytest.approx(37.88e6,
                                                               rel=1e-3)
    assert costs_swa.expert_params(cfg) == 3 * 2048 * 512
    assert costs_swa.param_count(cfg) == pytest.approx(3869.8e6, rel=1e-4)
    assert costs_swa.kv_position_bytes(cfg) == 4096
    assert costs_swa.cached_read_bytes(cfg, 1000) == 4096000
    assert costs_swa.expert_bytes(cfg, 2.0) == 2 * 3 * 2048 * 512 * 2
    assert costs_swa.ring_held_share(768, 4096) == 18.75
    # a decode step of 64 rows at 1,500 positions each: 220 experts a sparse
    # layer, the window of three rings, all of two page layers
    need = costs_swa.step_bytes(cfg, 4 * 220, 3 * 64 * 512, 2 * 64 * 1500)
    assert need["expert_bytes"] == pytest.approx(5.54e9, rel=0.01)
    assert need["ring_bytes"] == 3 * 64 * 512 * 4096
    assert need["page_bytes"] == 2 * 64 * 1500 * 4096
    assert need["head_bytes"] == 2048 * 100352 * 2
    assert need["total_bytes"] == sum(
        v for k, v in need.items() if k != "total_bytes")
    # weights that are read whole: everything but the embedding and the
    # routed experts
    fixed = (need["total_bytes"] - need["expert_bytes"] - need["ring_bytes"]
             - need["page_bytes"])
    assert fixed == 2 * (costs_swa.param_count(cfg) - 100352 * 2048
                         - 4 * 256 * costs_swa.expert_params(cfg))
    assert costs_swa.step_bytes(cfg, 880, 0, 0, 4)["total_bytes"] > fixed * 2


def _plane(mixed_runs=6):
    """Program step (id 5) runs four times of 100 us, program mixed (id 7)
    ``mixed_runs`` times of 200 us; each holds a window layer's write (5 us)
    and read (10 us), a full layer's gather and read (15 + 20 us) and write
    (5 us), the expert products (30 us) and an operation with no path (in
    the mixed step twice as long each)."""
    us = 1e-6
    md = {20: {"name": "jit_lm_paged_decode_step(5)"},
          22: {"name": "jit_lm_paged_mixed_step(7)"}}
    for base, program, name in ((0, 5, STEP[0]), (100, 7, STEP[1])):
        pre = f"jit({name})/CausalLM/"
        for i, (op, path) in enumerate((
                ("%scatter.1", "layer_1/attn/window_append/scatter:"),
                ("%fusion.2", "layer_1/attn/window_attention/"
                              "decode_attention/dot_general:"),
                ("%gather.3", "layer_4/attn/full_attention/kv_gather/gather:"),
                ("%fusion.4", "layer_4/attn/full_attention/decode_attention/"
                              "dot_general:"),
                ("%scatter.5", "layer_4/attn/kv_append/scatter:"),
                ("%gmm.6", "layer_1/moe/moe_experts/pallas_call:")), 1):
            md[base + i] = {"name": op, "program_id": program,
                            "tf_op": pre + path}
        md[base + 7] = {"name": "%copy.7", "program_id": program}
    plane = scopes.DevicePlane(metadata=md)
    spans_us = [(1, 0, 5), (2, 5, 15), (3, 15, 30), (4, 30, 50), (5, 50, 55),
                (6, 55, 85), (7, 85, 100)]
    for r in range(4):
        t0 = r * 200 * us
        plane.modules.append((20, t0, t0 + 100 * us))
        plane.ops += [(i, t0 + a * us, t0 + b * us) for i, a, b in spans_us]
    for r in range(mixed_runs):
        t0 = (1000 + r * 300) * us
        plane.modules.append((22, t0, t0 + 200 * us))
        plane.ops += [(100 + i, t0 + 2 * a * us, t0 + 2 * b * us)
                      for i, a, b in spans_us]
    return plane


FACTS = {
    "swa_experts_streamed_per_step": {STEP[0]: 2.0, STEP[1]: 8.0},
    "swa_ring_positions_per_step": {STEP[0]: 600.0, STEP[1]: 500.0},
    "swa_page_positions_per_step": {STEP[0]: 1500.0, STEP[1]: 1200.0},
}


def _rc(bench, cfg=None, facts=FACTS, trace=True, dtype="bfloat16"):
    trace = xplane.TraceSummary({0: xplane.DeviceOps(ops=[])}, [],
                                (0.0, 1.0)) if trace else None
    return ReadContext(facts, trace, cfg or bench.config(CONFIG),
                       {"dtype": dtype}, 1, PEAK)


def _args(name, reader="swa_roofline"):
    with open(os.path.join(manifest.REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        doc = json.load(f)
    assert doc["reader"] == reader
    return doc["args"]


def test_the_reader_divides_a_programs_own_count_by_its_own_time(
        bench, monkeypatch):
    from benchmark import spans
    from benchmark.readers import scope_share, swa_roofline

    cfg = bench.config(CONFIG)
    plane = {"is": _plane()}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(scopes, "read", lambda path: {0: plane["is"]})
    read = swa_roofline.read
    share = lambda need, seconds: 100.0 * need / 819e9 / seconds  # noqa: E731
    for runs in (6, 1):     # whichever the capture holds more of
        plane["is"] = _plane(mixed_runs=runs)
        assert read(_rc(bench), **_args("swa_step_roofline.decode")) == \
            pytest.approx(share(costs_swa.step_bytes(
                cfg, 2.0, 600.0, 1500.0)["total_bytes"], 100e-6))
        assert read(_rc(bench), **_args("swa_step_roofline.mixed")) == \
            pytest.approx(share(costs_swa.step_bytes(
                cfg, 8.0, 500.0, 1200.0)["total_bytes"], 200e-6))
        assert read(_rc(bench), **_args("swa_experts_roofline.decode")) == \
            pytest.approx(share(costs_swa.expert_bytes(cfg, 2.0), 30e-6))
        assert read(_rc(bench), **_args("swa_experts_roofline.mixed")) == \
            pytest.approx(share(costs_swa.expert_bytes(cfg, 8.0), 60e-6))
        # each kind's read against its LIVE bytes, in the decode program:
        # the ring's read alone (10 us), the gather and the read (35 us)
        assert read(_rc(bench), **_args("swa_window_read_roofline")) == \
            pytest.approx(share(600.0 * 4096, 10e-6))
        assert read(_rc(bench), **_args("swa_full_read_roofline")) == \
            pytest.approx(share(1500.0 * 4096, 35e-6))
    # float32 moves twice the bytes
    assert read(_rc(bench, dtype="float32"),
                **_args("swa_window_read_roofline")) == pytest.approx(
        share(600.0 * 8192, 10e-6))
    # a MEAN time: executions that differ weigh by what they took
    uneven = _plane()
    uneven.ops = [(i, s, e + (30e-6 if i == 6 and s > 3e-4 else 0))
                  for i, s, e in uneven.ops]
    plane["is"] = uneven
    assert read(_rc(bench), **_args("swa_experts_roofline.decode")) == \
        pytest.approx(share(costs_swa.expert_bytes(cfg, 2.0), 45e-6))
    # no execution of the program, another family's configuration, a tree
    # without the counters or the scopes, no capture: nothing, and no error
    plane["is"] = _plane(mixed_runs=0)
    decode, mixed = (_args("swa_step_roofline.decode"),
                     _args("swa_step_roofline.mixed"))
    assert read(_rc(bench), **mixed) is None
    assert read(_rc(bench), **decode) is not None
    for other in ("olmoe-1b-7b", "xing4.0-29b-a4b"):
        assert read(_rc(bench, cfg=bench.config(other)), **decode) is None
    assert read(_rc(bench, facts={}), **decode) is None
    assert read(_rc(bench), **{**_args("swa_full_read_roofline"),
                               "scope": "^no_such_scope$"}) is None
    assert read(_rc(bench, trace=False), **decode) is None
    monkeypatch.setattr(scopes, "read", lambda path: {})
    assert read(_rc(bench), **decode) is None
    with pytest.raises(ValueError):
        swa_roofline.need_bytes("attention", cfg, 1, 1, 1, 2)
    # the two shares of a decode step, by the reader that was there
    window = scope_share.share(_plane(), **_args("swa_window_share",
                                                 "scope_share"))
    full = scope_share.share(_plane(), **_args("swa_full_share",
                                               "scope_share"))
    assert window == pytest.approx(15.0) and full == pytest.approx(40.0)
    assert _args("swa_window_held_share", "fact") == {
        "key": "swa_window_held_share"}


class _Ctx:
    def __init__(self, traffic):
        self.traffic, self.failed_checks = traffic, []

    def check(self, ok, what):
        if not ok:
            self.failed_checks.append(what)


def _verdicts(n=40, tied_err=0.5, tied_margin=0.3, exact=38, low_kept=20,
              planted=(1.0, 1.4), weak=None):
    """Two requests of ``n`` positions, the first quarter of each near a
    tie; ``weak``: a control that reads under the limit."""
    from benchmark.kinds import swaserve

    k = n // 4
    gap = [0.0001] * k + [0.05] * (n - k)
    v = {"tokens": n, "exact": exact, "reference_on": "cpu", "gap": gap,
         "err": [tied_err] * k + [0.01] * (n - k),
         "margin": [tied_margin] * k + [0.0] * (n - k),
         "planted_margin": [planted[0]] * n, "planted_err": [planted[1]] * n}
    controls = {key: [0.001 if key == weak else 0.2] * n
                for key in swaserve.CONTROLS}
    return [dict(v), dict(v, control_kept={k: low_kept for k in controls},
                          **controls)]


@pytest.mark.parametrize("fault,fails", [
    ({}, None),
    ({"tied_margin": 0.95}, "near a tie 0.95"),
    ({"tied_err": 1.2}, "differ by up to 1.2"),
    ({"planted": (0.4, 1.4)}, "another request's token"),
    ({"planted": (1.0, 0.6)}, "another request's logits"),
    ({"exact": 25}, "are the reference's own choice"),
    ({"low_kept": 36}, "are the reference's own choice"),
    # each control must read over the limit, or the limit passes that fault
    ({"weak": "nowindow_err"}, "sees every earlier position"),
    ({"weak": "nogate_err"}, "without the output gate"),
    ({"weak": "wholerope_err"}, "rope on the whole head"),
    ({"weak": "noyarn_err"}, "without yarn's factor"),
    ({"weak": "otherring_err"}, "another slot's ring"),
    ({"weak": "lowprec_err"}, "mantissa bits"),
])
def test_the_verdicts_are_held_and_every_control_must_read_over_the_limit(
        bench, fault, fails):
    from benchmark.kinds import swaserve

    ctx = _Ctx(bench.traffic(bench.cell(CELL)))
    got = swaserve.hold_reference(ctx, bench.config(CONFIG),
                                  _verdicts(**fault))
    if fails is None:
        assert not ctx.failed_checks
        assert got["check_near_tied"] == 20 and got["check_positions"] == 80
        assert got["check_planted"]["margin"]["p50"] == 1.0
        assert set(got["check_control_medians"]) == set(swaserve.CONTROLS)
    else:
        assert len(ctx.failed_checks) == 1 and fails in ctx.failed_checks[0]


def test_step_facts_are_by_program_over_the_steps_that_were_read():
    from benchmark.kinds import swaserve

    counts = {"moe_steps": 10, "moe_steps_alone": 6,
              "moe_experts_streamed": 10 * 800 + 4 * 200,
              "moe_experts_streamed_alone": 6 * 800,
              "window_positions_live": 10 * 3000,
              "window_positions_live_alone": 6 * 3000,
              "kv_page_positions_live": 10 * 9000 + 4 * 1000,
              "kv_page_positions_live_alone": 6 * 9000,
              "steps_issued": 11, "mixed_steps": 4}
    ctx = NS(trace=False)
    got = swaserve.step_facts(ctx, None, counts.get)
    assert got["counts_over"] == "the window"
    assert got["swa_experts_streamed_per_step"] == {STEP[0]: 800.0,
                                                    STEP[1]: 1000.0}
    assert got["swa_ring_positions_per_step"] == {STEP[0]: 3000.0,
                                                  STEP[1]: 3000.0}
    assert got["swa_page_positions_per_step"] == {STEP[0]: 9000.0,
                                                  STEP[1]: 10000.0}
    traced = NS(trace=True)
    seen = {**counts, "moe_steps": 6, "moe_steps_alone": 6,
            "moe_experts_streamed": 6 * 800,
            "window_positions_live": 6 * 3000,
            "kv_page_positions_live": 6 * 9000}
    got = swaserve.step_facts(traced, lambda name: dict(seen), counts.get)
    assert got["counts_over"] == "the profiler's window"
    assert set(got["swa_experts_streamed_per_step"]) == {STEP[0]}


def test_the_reference_copy_is_the_programs():
    """benchmark/reference/laguna.py is
    tpu_air/models/lm/reference_laguna.py under a heading of its own, and
    imports nothing of the program."""
    with open(os.path.join(manifest.REPO, "tpu_air", "models", "lm",
                           "reference_laguna.py")) as f:
        ours = f.read()
    with open(os.path.join(manifest.REPO, "benchmark", "reference",
                           "laguna.py")) as f:
        theirs = f.read()
    assert theirs.split("\n\n", 1)[1] == ours.split('"""', 1)[1]
    assert "tpu_air" not in theirs.split('"""', 2)[2]


def test_rehearsal_of_the_new_cell():
    out = subprocess.run(
        [sys.executable, RUN, "--workload", CELL, "--rehearse",
         "--seconds", "3", "--trace", "1", "--seed", "2500000011"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert not [ln for ln in out.stdout.splitlines()
                if ln.startswith("{") and '"metrics"' in ln
                and '"info"' not in ln]
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith(f"rehearsal of {CELL}: ok"), last
    for name in ("serve_tpot_p50_ms", "engine_step_ms_p50",
                 "swa_window_held_share", "moe_load_max_over_mean"):
        assert name in last, name


def test_the_parent_tree_is_refused_in_one_line(bench, monkeypatch):
    """A tree without the importer's ``laguna``: the kind says so and the
    run exits 2 (``RunFailure``), before any checkpoint is written."""
    from benchmark.harness import RunFailure
    from tpu_air.models.lm import hf_import

    kind = bench.module("kinds", "swaserve")
    monkeypatch.delattr(hf_import, "LAGUNA_NAMES")
    ctx = NS(rehearse=True, cfg={}, traffic={}, scratch="/nonexistent")
    with pytest.raises(RunFailure, match="laguna"):
        kind.deploy(ctx)
