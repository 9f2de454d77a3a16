"""What PR 47 added to the benchmark, checked on the CPU: the manifest with
the new cell, its configuration and traffic files, the seeded
published-layout weights of one expert-parallel rank, the cost functions,
the new reader on a synthetic capture, and a rehearsal of the cell (control
flow only: a CPU run prints no result line)."""

import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import costs_ssd, manifest, scopes, weights_nemotron, xplane
from benchmark.harness import ReadContext
from benchmark.weights_mla import held, published_view

RUN = os.path.join(manifest.REPO, "benchmark", "run.py")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL, CONFIG = "nemotron3-serve-agent", "nemotron3-super-120b-a12b"
NEW = ["ssd_decode_roofline", "ssd_state_update_roofline",
       "latent_expert_roofline", "ssd_state_share", "ssd_scan_share",
       "latent_proj_share"]


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


def test_manifest_finds_the_cell_and_lists_it_where_it_reports(bench):
    manifest.validate(bench.doc)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert bench.doc["workloads"][8] is cell        # appended, not inserted
    assert bench.doc["configs"][5]["name"] == CONFIG
    assert bench.doc["configs"][5]["reduced"] == [
        "num_hidden_layers", "hybrid_override_pattern", "n_routed_experts",
        "vocab_size"]
    assert len(cell["why"]) <= 200
    assert bench.traffic(cell)["kind"] == "ssdserve"
    assert hasattr(bench.module("kinds", "ssdserve"), "deploy")
    e2e = {m["name"] for m in bench.metrics("end_to_end", CELL)}
    assert e2e == {"serve_tpot_p50_ms", "setup_s"}
    layer = {m["name"]: m for m in bench.metrics("per_layer", CELL)}
    for name in NEW + ["engine_step_ms_p50", "engine_prefill_share",
                       "engine_chunk_fused_share", "engine_unscoped_share",
                       "moe_load_max_over_mean", "moe_shared_share",
                       "worker_compile_s"]:
        assert name in layer, name
        assert layer[name]["moves"] in e2e, name
    # the other families' cost functions and the readers of the decode
    # program alone are not this cell's
    for name in ("lm_decode_roofline", "moe_expert_roofline",
                 "ssm_decode_roofline", "ssm_state_update_roofline",
                 "mla_decode_roofline", "moe_held_expert_roofline",
                 "engine_host_ms_p50", "ssm_state_share"):
        assert name not in layer, name
    # the new metrics are this cell's alone, appended in one piece (found by
    # name: later PRs append behind them)
    names = [m["name"] for m in bench.doc["per_layer"]]
    at = names.index(NEW[0])
    assert names[at:at + 6] == NEW
    assert all(m["workloads"] == [CELL] and m["unit"] == "%"
               and m["source"] == "device_trace"
               for m in bench.doc["per_layer"][at:at + 6])
    # PR 48: how much of the state the pass moves is the live rows'
    passed = layer["ssd_state_pass_live_share"]
    assert names[at + 6] == passed["name"]
    assert (passed["workloads"], passed["source"], passed["layer"]) == (
        [CELL], "program_span", "kernels")
    # PR 49: the sum over a token's choices, in the three sparse cells, by
    # the reader and over the programs ``moe_shared_share`` reads
    combine = layer["moe_combine_share"]
    assert names[at + 7] == combine["name"]
    assert combine["workloads"] == [
        "gigachat-serve-docchat", CELL, "olmoe-serve-decode",
        "xing4-serve-longdoc",                     # PR 58 appended its own
        "laguna-serve-mixedlen"]                   # and PR 60
    assert (combine["unit"], combine["better"], combine["source"],
            combine["layer"], combine["moves"]) == (
        "%", "lower", "device_trace", "kernels", "serve_tpot_p50_ms")
    assert (combine["reader"], combine["args"]) == (
        "scope_share", {"scope": "^moe_combine$"})
    assert combine["args"].keys() == layer["moe_shared_share"]["args"].keys()
    assert (passed["reader"], passed["args"]) == (
        "span_stat_ratio",
        {"name": "engine.step", "num": "live", "den": "state_rows"})
    assert [w["name"] for w in bench.doc["workloads"][:8]] == [
        "t5base-finetune", "t5base-finetune-dp4", "t5base-batchgen",
        "t5large-serve", "t5large-batchgen", "olmoe-serve-decode",
        "jamba2-serve-reason", "gigachat-serve-docchat"]


def test_traffic_file_is_the_cell_the_issue_wrote(bench):
    t = bench.traffic(bench.cell(CELL))
    assert (t["num_slots"], t["slot_len"], t["page_len"],
            t["max_new_tokens"]) == (128, 4096, 256, 1024)
    assert t["prompt_len"] == {"median": 384, "sigma": 0.8, "min": 32,
                               "max": 2048}
    assert t["output_len"] == {"median": 384, "sigma": 0.6, "min": 64,
                               "max": 1024}
    assert (t["priority"], t["poll_ms"], t["submit_threads"],
            t["poll_threads"], t["dtype"]) == ("batch", 50, 8, 12, "bfloat16")
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"], rel=0.02)
    assert t["check_requests"] == 4 and t["check_lowprec_bits"] == 3
    assert t["check_state_bits"] == 7            # a bfloat16's mantissa
    assert t["check_drop_state_at"] == t["page_len"]   # a chunk boundary
    assert 0 < t["check_tie_eps"] < 0.1 and t["check_tie_tol"] > t[
        "check_logit_tol"]
    assert len(t["check_why"]) > 200
    # a chunk is two blocks of the state-space form
    assert t["page_len"] == 2 * bench.config(CONFIG)["chunk_size"]
    # the longest prompt and answer fit a slot, and the check's fixed length
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= t["slot_len"]
    from tpu_air.serve.admission import AdmissionPolicy

    assert AdmissionPolicy().clamp_budget("batch", 1024) == 1024


def test_published_weights_are_the_ranks_share(bench):
    cfg = bench.config(CONFIG)
    a = weights_nemotron.Published(cfg, 2_500_000_001, "bfloat16")
    b = weights_nemotron.Published(cfg, 2_500_000_001, "bfloat16")
    c = weights_nemotron.Published(cfg, 7, "bfloat16")
    name = "backbone.layers.7.mixer.k_proj.weight"
    assert a.tensor(name).shape == (256, 4096)
    assert np.array_equal(a.raw(name), b.raw(name))
    assert not np.array_equal(a.raw(name), c.raw(name))
    assert abs(float(a.tensor(name).astype(np.float32).std()) - 0.02) < 1e-3
    assert held(cfg) == (0, 128)
    assert published_view(cfg)["n_routed_experts"] == 512
    m = "backbone.layers.0.mixer."
    assert a.shape(m + "in_proj.weight") == (8192 + 10240 + 128, 4096)
    assert a.shape(m + "conv1d.weight") == (10240, 1, 4)
    assert a.shape(m + "out_proj.weight") == (4096, 8192)
    e = "backbone.layers.1.mixer."
    assert a.shape(e + "experts.127.up_proj.weight") == (2688, 1024)
    assert a.shape(e + "experts.0.down_proj.weight") == (1024, 2688)
    assert a.shape(e + "fc1_latent_proj.weight") == (1024, 4096)
    assert a.shape(e + "shared_experts.down_proj.weight") == (4096, 5376)
    # Mamba-2's own initialisation: heads that remember a position and heads
    # that remember a thousand
    f32 = lambda n: a.tensor(n).astype(np.float32)  # noqa: E731
    rate = np.log1p(np.exp(f32(m + "dt_bias"))) * np.exp(f32(m + "A_log"))
    assert rate.shape == (128,) and rate.min() < 0.005 and rate.max() > 0.5
    assert 0.9 <= np.exp(f32(m + "A_log")).min() and np.exp(
        f32(m + "A_log")).max() <= 16.1
    assert np.all(f32(m + "D") == 1) and np.all(f32(m + "norm.weight") == 1)
    assert np.abs(f32(m + "conv1d.weight")).max() <= 0.5
    # the router scores all 512, unevenly; the bias is small and not zero
    gate = f32(e + "gate.weight")
    assert gate.shape == (512, 4096)
    rows = gate.std(-1)
    assert rows.max() / rows.min() > 2.5
    bias = f32(e + "gate.e_score_correction_bias")
    assert bias.shape == (512,) and 0.005 < bias.std() < 0.06
    # every rank's 128 experts take one gain and one bias from each stratum
    # of four neighbouring quantiles, dealt anew for each layer
    for what, got, want in ((0, rows / 0.02, lambda z: np.exp(0.25 * z)),
                            (1, bias, lambda z: z * a.bias_std)):
        dealt = a.dealt(1, what)
        order = np.argsort(np.argsort(dealt)).reshape(4, 128) // 4
        assert all(sorted(r) == list(range(128)) for r in order.tolist())
        np.testing.assert_allclose(got, want(dealt), rtol=0.05, atol=2e-4)
    assert not np.array_equal(a.dealt(1, 0), a.dealt(3, 0))
    assert np.array_equal(a.dealt(1, 0), b.dealt(1, 0))
    with pytest.raises(KeyError):
        a.shape(e + "experts.200.up_proj.weight")
    with pytest.raises(KeyError):
        a.shape(e + "experts.3.gate_proj.weight")
    # a matrix the importer transposes whole lies column-major (its transpose
    # costs no copy); what it gathers rows of, row-major
    for name, turned in ((e + "experts.3.down_proj.weight", True),
                         (m + "in_proj.weight", True),
                         ("lm_head.weight", True),
                         ("backbone.embeddings.weight", False),
                         (e + "gate.weight", False)):
        t = a.tensor(name)
        assert t.shape == a.shape(name)
        assert t.T.flags.c_contiguous == turned, name


def test_the_checkpoint_loads_the_normal_way(tmp_path, bench):
    import jax

    from tpu_air.models.lm import hf_import
    from tpu_air.train.checkpoint import Checkpoint

    cfg = bench.module("kinds", "ssdserve").TINY
    ckpt = weights_nemotron.write_checkpoint(
        cfg, 2_147_483_999, "float32", str(tmp_path / "c"), max_seq_len=64)
    config = weights_nemotron.lm_config(cfg, "float32", 64)
    assert (config.experts_first, config.experts_held,
            config.num_experts) == (8, 8, 16)
    pub = weights_nemotron.Published(cfg, 2_147_483_999, "float32")
    params = hf_import.convert_nemotron_h_state_dict(pub.tensor, config)
    loaded = Checkpoint.from_directory(ckpt.to_directory()).get_params()
    jax.tree_util.tree_map(np.testing.assert_array_equal, loaded, params)


def test_cost_functions_from_the_published_shapes(bench):
    cfg = bench.config(CONFIG)
    assert costs_ssd.layer_counts(cfg) == {"mamba": 5, "attention": 1,
                                           "experts": 5}
    assert costs_ssd.mamba_params(cfg) == pytest.approx(109.6e6, rel=2e-3)
    assert costs_ssd.attention_params(cfg) == pytest.approx(35.7e6, rel=2e-3)
    assert costs_ssd.expert_params(cfg) == 2 * 1024 * 2688
    assert costs_ssd.held_expert_bytes(cfg, 1) == pytest.approx(
        2 * 5.505e6, rel=1e-3)
    assert costs_ssd.state_bytes(cfg, 128, tail_el=0) == pytest.approx(
        2.68e9, rel=2e-3)
    assert costs_ssd.state_bytes(cfg, 1) == 5 * (128 * 64 * 128 * 4
                                                 + 3 * 10240 * 2)
    assert costs_ssd.kv_bytes(cfg, 128 * 4096) == pytest.approx(0.537e9,
                                                                rel=2e-3)
    b = costs_ssd.decode_step_bytes(cfg, 100, 60000.0, 5 * 127)
    assert b["total_bytes"] == sum(v for k, v in b.items()
                                   if k != "total_bytes")
    # 100 live rows: 4.3 GB of state both ways, 7.0 GB of experts, 2.0 GB of
    # other weights: the issue's 14.5 GB at full occupancy is 13.3 at 100
    assert b["state_bytes"] == pytest.approx(4.26e9, rel=3e-3)
    assert b["held_expert_bytes"] == pytest.approx(6.99e9, rel=2e-3)
    fixed = (b["mamba_weight_bytes"] + b["attention_weight_bytes"]
             + b["expert_layer_fixed_bytes"] + b["head_bytes"])
    assert fixed == pytest.approx(1.98e9, rel=3e-3)
    # by what is live, never by the pool: dead rows add nothing
    assert costs_ssd.decode_step_bytes(cfg, 0, 0, 0)["total_bytes"] == fixed


# -- the new reader on a hand-made capture ------------------------------------

def _plane(mixed_runs=0):
    """Program step (id 5) runs four times of 100 us; each holds the state
    update (two operations of 10 us under ssd_state_update), the expert
    products (two of 15 us under moe_experts), the latent pair (5 us each)
    and one operation with no path.  Program mixed (id 7) runs
    ``mixed_runs`` times: the same operations, each twice as long, and a
    chunk's block form (20 us under ssd_scan)."""
    us = 1e-6
    md = {20: {"name": "jit_lm_paged_decode_step(5)"},
          22: {"name": "jit_lm_paged_mixed_step(7)"}}
    paths = (("%fusion.1", "layer_0/mamba/ssd_state_update/mul:"),
             ("%fusion.2", "layer_2/mamba/ssd_state_update/reduce_sum:"),
             ("%gmm.1", "layer_1/moe/moe_experts/pallas_call:"),
             ("%anything", "layer_1/moe/moe_experts/mul:"),
             ("%fusion.5", "layer_1/moe/moe_latent_down/latent_down/dot:"),
             ("%fusion.6", "layer_1/moe/moe_latent_up/latent_up/dot:"),
             ("%fusion.7", "layer_0/mamba/ssd_scan/dot_general:"))
    for base, program, name in ((0, 5, "lm_paged_decode_step"),
                                (100, 7, "lm_paged_mixed_step")):
        for i, (op, path) in enumerate(paths, 1):
            md[base + i] = {"name": op, "program_id": program,
                            "tf_op": f"jit({name})/CausalLM/{path}"}
        md[base + 8] = {"name": "%copy.8", "program_id": program}
    plane = scopes.DevicePlane(metadata=md)
    spans_us = [(1, 0, 10), (2, 10, 20), (3, 20, 35), (4, 35, 50),
                (5, 50, 55), (6, 55, 60), (8, 60, 100)]
    for r in range(4):
        t0 = r * 200 * us
        plane.modules.append((20, t0, t0 + 100 * us))
        plane.ops += [(i, t0 + a * us, t0 + b * us) for i, a, b in spans_us]
    for r in range(mixed_runs):
        t0 = (1000 + r * 300) * us
        plane.modules.append((22, t0, t0 + 220 * us))
        plane.ops += [(100 + i, t0 + 2 * a * us, t0 + 2 * b * us)
                      for i, a, b in spans_us]
        plane.ops.append((107, t0 + 200 * us, t0 + 220 * us))
    return plane


STEP = ["lm_paged_decode_step", "lm_paged_mixed_step"]
FACTS = {"ssd_rows_live_per_step": 90.0,
         "ssd_positions_live_per_step": 60000.0,
         "moe_held_experts_streamed_per_step": {
             "lm_paged_decode_step": 500.0, "lm_paged_mixed_step": 620.0}}


def _rc(bench, cfg=None, facts=FACTS, trace=True):
    trace = xplane.TraceSummary({0: xplane.DeviceOps(ops=[])}, [],
                                (0.0, 1.0)) if trace else None
    return ReadContext(facts, trace, cfg or bench.config(CONFIG), {}, 1, PEAK)


def test_scope_rooflines_read_the_scope_not_a_kernel_name(bench, monkeypatch):
    from benchmark import spans
    from benchmark.readers import scope_share, ssd_roofline

    cfg = bench.config(CONFIG)
    plane = {"is": _plane()}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(scopes, "read", lambda path: {0: plane["is"]})
    update = dict(part="state_update", scope="^ssd_state_update$",
                  modules=STEP)
    experts = dict(part="experts", scope="^moe_experts$", modules=STEP)
    state_s = 2 * 90 * 5 * 128 * 64 * 128 * 4 / 819e9
    expert_s = 2 * 1024 * 2688 * 2 / 819e9
    # the LIVE rows' state, not the pool's, over the scope's 20 us
    got = ssd_roofline.read(_rc(bench), **update)
    assert got == pytest.approx(100.0 * state_s / 20e-6, rel=1e-6)
    assert state_s == pytest.approx(
        2 * costs_ssd.state_bytes(cfg, 90, tail_el=0) / 819e9)
    got = ssd_roofline.read(_rc(bench), **experts)
    assert got == pytest.approx(100.0 * 500 * expert_s / 30e-6, rel=1e-6)
    # another program, no operation in the scope, no counts, another family,
    # no trace: nothing
    assert ssd_roofline.read(
        _rc(bench), **{**experts, "modules": ["no_such"]}) is None
    assert ssd_roofline.read(
        _rc(bench), **{**experts, "scope": "^ssm_scan$"}) is None
    assert ssd_roofline.read(_rc(bench, facts={}), **experts) is None
    assert ssd_roofline.read(_rc(bench, facts={}), **update) is None
    for other in ("jamba2-3b", "gigachat3.1-702b-a36b", "olmoe-1b-7b"):
        assert ssd_roofline.read(_rc(bench, cfg=bench.config(other)),
                                 **experts) is None
    assert ssd_roofline.read(_rc(bench, trace=False), **update) is None
    # most of the capture's steps carried a chunk: the mixed step is read,
    # with the experts ITS steps streamed over ITS time in the scope
    plane["is"] = _plane(mixed_runs=6)
    got = ssd_roofline.read(_rc(bench), **experts)
    assert got == pytest.approx(100.0 * 620 * expert_s / 60e-6, rel=1e-6)
    got = ssd_roofline.read(_rc(bench), **update)
    assert got == pytest.approx(100.0 * state_s / 40e-6, rel=1e-6)
    only_alone = {**FACTS, "moe_held_experts_streamed_per_step": {
        "lm_paged_decode_step": 500.0}}
    assert ssd_roofline.read(_rc(bench, facts=only_alone),
                             **experts) is None
    # the three data-only shares, over every program of the capture
    every = 4 * 100 + 6 * 220
    for scope, want in (("^ssd_state_update$", 4 * 20 + 6 * 40),
                        ("^ssd_scan$", 6 * 20),
                        ("^(moe_latent_down|moe_latent_up)$",
                         4 * 10 + 6 * 20)):
        assert scope_share.share(plane["is"], scope=scope) == pytest.approx(
            100 * want / every)


def test_the_whole_steps_share_counts_what_is_live(bench, monkeypatch):
    import jax.profiler

    from benchmark import spans
    from benchmark.readers import ssd_roofline

    cfg = bench.config(CONFIG)
    ev = lambda name, ms: NS(name=name, duration_ns=int(ms * 1e6))  # noqa: E731
    line = NS(name="XLA Modules", events=[
        ev("jit_lm_paged_decode_step(5)", 20.0) for _ in range(7)] + [
        ev("jit_lm_paged_mixed_step(6)", 30.0)])
    data = NS(planes=[NS(name="/device:TPU:0", lines=[line])])
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    got = ssd_roofline.read(_rc(bench), part="step", modules=STEP)
    need = costs_ssd.decode_step_bytes(cfg, 90.0, 60000.0, 500.0)
    assert got == pytest.approx(
        100 * (need["total_bytes"] / 819e9) / 20e-3, rel=1e-6)
    assert 60 < got < 75
    # the live rows, not the pool's: every slot would add 1.6 GB
    whole = costs_ssd.decode_step_bytes(cfg, 128, 60000.0, 500.0)
    assert whole["total_bytes"] - need["total_bytes"] > 1.6e9
    # most steps carried a chunk: the mixed step's time and its own count
    line.events += [ev("jit_lm_paged_mixed_step(6)", 30.0)] * 9
    got = ssd_roofline.read(_rc(bench), part="step", modules=STEP)
    need = costs_ssd.decode_step_bytes(cfg, 90.0, 60000.0, 620.0)
    assert got == pytest.approx(
        100 * (need["total_bytes"] / 819e9) / 30e-3, rel=1e-6)
    assert ssd_roofline.read(_rc(bench, facts={}), part="step",
                             modules=STEP) is None
    assert ssd_roofline.read(_rc(bench), part="step",
                             modules=["no_such"]) is None


def test_the_reference_copy_is_the_programs(bench):
    """benchmark/reference/nemotron_h.py is
    tpu_air/models/lm/reference_nemotron_h.py under a heading of its own."""
    with open(os.path.join(manifest.REPO, "tpu_air", "models", "lm",
                           "reference_nemotron_h.py")) as f:
        ours = f.read()
    with open(os.path.join(manifest.REPO, "benchmark", "reference",
                           "nemotron_h.py")) as f:
        theirs = f.read()
    assert theirs.split("\n\n", 1)[1] == ours.split('"""', 1)[1]


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
                 "moe_load_max_over_mean"):
        assert name in last, name


def test_the_parent_tree_is_refused_in_one_line(bench, monkeypatch):
    """A tree without the importer: the kind says so and the run exits 2
    (``RunFailure``), before any checkpoint is written."""
    from benchmark.harness import RunFailure
    from tpu_air.models.lm import hf_import

    kind = bench.module("kinds", "ssdserve")
    monkeypatch.delattr(hf_import, "convert_nemotron_h_state_dict")
    ctx = NS(rehearse=True, cfg={}, traffic={}, scratch="/nonexistent")
    with pytest.raises(RunFailure, match="nemotron_h"):
        kind.deploy(ctx)
