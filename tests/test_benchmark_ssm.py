"""What PR 41 added to the benchmark, checked on the CPU: the manifest with
the new cell, its configuration and traffic files, the seeded
published-layout weights, the cost functions, each new reader on a synthetic
capture, and a rehearsal of the cell (control flow only: a CPU run prints no
result line)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import costs_ssm, manifest, scopes, weights_ssm, xplane
from benchmark.harness import ReadContext

RUN = os.path.join(manifest.REPO, "benchmark", "run.py")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL = "jamba2-serve-reason"


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


def test_manifest_finds_the_cell_and_lists_it_where_it_reports(bench):
    manifest.validate(bench.doc)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["chips"]) == ("jamba2-3b", 1)
    assert bench.doc["workloads"][6] is cell        # appended, not inserted
    assert bench.doc["configs"][3]["name"] == "jamba2-3b"
    assert bench.doc["configs"][3]["reduced"] == []
    t = bench.traffic(cell)
    assert t["kind"] == "ssmserve"
    assert hasattr(bench.module("kinds", "ssmserve"), "deploy")
    e2e = {m["name"] for m in bench.metrics("end_to_end", CELL)}
    assert e2e == {"serve_tpot_p50_ms", "setup_s"}
    layer = {m["name"]: m for m in bench.metrics("per_layer", CELL)}
    for name in ("ssm_decode_roofline", "ssm_state_update_roofline",
                 "ssm_mixer_share", "ssm_state_share", "engine_step_ms_p50",
                 "engine_host_ms_p50", "engine_prefill_share",
                 "engine_unscoped_share", "lm_attention_share",
                 "lm_kv_gather_share", "worker_compile_s"):
        assert name in layer, name
        assert layer[name]["moves"] in e2e, name
    # OLMoE's costs are keyed by num_experts, which this config has (1);
    # the two idle parts read nothing in a capture whose device never waits
    # near a launch (spans.device_lead), which is most of this cell's
    for name in ("lm_decode_roofline", "moe_expert_roofline",
                 "moe_load_max_over_mean", "lm_expert_share",
                 "engine_idle_host_ms", "engine_idle_readback_ms"):
        assert name not in layer, name
    # the cells that were there are where they were
    assert [w["name"] for w in bench.doc["workloads"][:6]] == [
        "t5base-finetune", "t5base-finetune-dp4", "t5base-batchgen",
        "t5large-serve", "t5large-batchgen", "olmoe-serve-decode"]


def test_traffic_file_is_the_cell_the_issue_wrote(bench):
    t = bench.traffic(bench.cell(CELL))
    assert (t["num_slots"], t["slot_len"], t["page_len"],
            t["max_new_tokens"]) == (128, 2048, 128, 1024)
    assert t["prompt_len"] == {"median": 96, "sigma": 0.8, "min": 16,
                               "max": 512}
    assert t["output_len"] == {"median": 512, "sigma": 0.5, "min": 128,
                               "max": 1024}
    assert (t["priority"], t["poll_ms"], t["submit_threads"],
            t["poll_threads"], t["drain_s"], t["lead_s"]) == (
        "batch", 50, 8, 12, 30, 1.5)
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"], rel=0.02)
    # the check is made on requests the window finished (kinds/ssmserve.py
    # picks them: every prompt crosses a chunk boundary and ends in a padded
    # chunk), their first 64 streamed tokens through the replay as well
    assert t["check_requests"] == 4 and t["check_new_tokens"] == 64
    assert "check_prompt_lens" not in t
    assert t["check_drop_state_at"] == t["page_len"]
    assert (t["check_lowprec_bits"], t["check_state_bits"]) == (3, 7)
    assert len(t["check_why"]) > 200
    from tpu_air.serve.admission import AdmissionPolicy

    assert AdmissionPolicy().clamp_budget("batch", 1024) == 1024


def test_published_weights_are_seeded_and_carry_mambas_init(bench):
    cfg = bench.config("jamba2-3b")
    a = weights_ssm.Published(cfg, 2_500_000_001, "bfloat16")
    b = weights_ssm.Published(cfg, 2_500_000_001, "bfloat16")
    c = weights_ssm.Published(cfg, 7, "bfloat16")
    name = "model.layers.3.mamba.x_proj.weight"
    assert a.tensor(name).shape == (192, 5120)
    assert np.array_equal(a.raw(name), b.raw(name))
    assert not np.array_equal(a.raw(name), c.raw(name))
    f32 = a.tensor(name).astype(np.float32)
    assert abs(float(f32.std()) - 0.02) < 1e-3
    assert a.tensor("model.layers.7.self_attn.k_proj.weight").shape == (
        128, 2560)
    assert a.tensor("model.layers.0.mamba.conv1d.weight").shape == (5120, 1, 4)
    a_log = a.tensor("model.layers.0.mamba.A_log").astype(np.float32)
    assert a_log.shape == (5120, 16)
    assert np.allclose(np.exp(a_log[17]), np.arange(1, 17), rtol=0.01)
    bias = a.tensor("model.layers.0.mamba.dt_proj.bias").astype(np.float32)
    dt0 = np.log1p(np.exp(bias))
    assert 0.9e-3 < dt0.min() < 2e-3 and 0.05 < dt0.max() < 0.11
    w = a.tensor("model.layers.0.mamba.dt_proj.weight").astype(np.float32)
    assert np.abs(w).max() <= 160 ** -0.5 * 1.01
    assert np.all(a.tensor("model.layers.0.mamba.D").astype(np.float32) == 1)
    with pytest.raises(KeyError):
        a.shape("model.layers.0.mlp.gate.weight")


def test_cost_functions_from_the_published_shapes(bench):
    cfg = bench.config("jamba2-3b")
    assert costs_ssm.layer_counts(cfg) == {"attention": 2, "mamba": 26}
    assert costs_ssm.mamba_mixer_params(cfg) == pytest.approx(41.2e6, rel=2e-3)
    assert costs_ssm.attention_mixer_params(cfg) == 13_762_560
    assert costs_ssm.state_bytes(cfg, 128) == 26 * 128 * (
        5120 * 16 * 4 + 3 * 5120 * 2)
    b = costs_ssm.decode_step_bytes(cfg, 128, 2048)
    assert b["total_bytes"] == sum(v for k, v in b.items()
                                   if k != "total_bytes")
    assert b["total_bytes"] == pytest.approx(8.71e9, rel=2e-3)
    mixers = b["mamba_weight_bytes"] + b["state_bytes"]
    assert 0.50 < mixers / b["total_bytes"] < 0.54    # over half the step


# -- the two new readers on a hand-made capture -----------------------------

def _plane():
    """Program step (id 5) runs four times of 100 us; each holds two
    operations under ssm_state_update (10 + 10 us), one under mamba/in_proj
    (30 us) and one with no path (20 us).  Program chunk (id 6) runs once."""
    us = 1e-6
    md = {
        1: {"name": "%fusion.1 = f32[8] fusion(...)", "program_id": 5,
            "tf_op": "jit(lm_paged_decode_step)/CausalLM/layer_0/mamba/"
                     "ssm_state_update/mul:"},
        2: {"name": "%fusion.2 = f32[8] fusion(...)", "program_id": 5,
            "tf_op": "jit(lm_paged_decode_step)/CausalLM/layer_1/mamba/"
                     "ssm_state_update/reduce_sum:"},
        3: {"name": "%fusion.3 = bf16[8] fusion(...)", "program_id": 5,
            "tf_op": "jit(lm_paged_decode_step)/CausalLM/layer_0/mamba/"
                     "in_proj/dot_general:"},
        4: {"name": "%copy.4 = bf16[8] copy(...)", "program_id": 5},
        5: {"name": "%fusion.5 = f32[8] fusion(...)", "program_id": 6,
            "tf_op": "jit(lm_prefill_chunk)/CausalLM/layer_0/mamba/"
                     "ssm_scan/while:"},
        20: {"name": "jit_lm_paged_decode_step(5)"},
        21: {"name": "jit_lm_prefill_chunk(6)"},
    }
    plane = scopes.DevicePlane(metadata=md)
    for r in range(4):
        t0 = r * 200 * us
        plane.modules.append((20, t0, t0 + 100 * us))
        plane.ops += [(1, t0, t0 + 10 * us), (3, t0 + 10 * us, t0 + 40 * us),
                      (2, t0 + 40 * us, t0 + 50 * us),
                      (4, t0 + 50 * us, t0 + 70 * us)]
    plane.modules.append((21, 900 * us, 1000 * us))
    plane.ops.append((5, 900 * us, 1000 * us))
    return plane


def test_ssm_state_share_reads_the_scope_not_a_kernel_name(bench, monkeypatch):
    from benchmark import spans
    from benchmark.readers import scope_share, ssm_state_share

    cfg = bench.config("jamba2-3b")
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(scopes, "read", lambda path: {0: _plane()})
    trace = xplane.TraceSummary({0: xplane.DeviceOps(ops=[])}, [], (0.0, 1.0))
    facts = {"num_slots": 128, "slot_len": 2048}
    rc = ReadContext(facts, trace, cfg, {}, 1, PEAK)
    got = ssm_state_share.read(rc, scope="^ssm_state_update$",
                               module="lm_paged_decode_step")
    # the float32 state alone: the convolution tail moves under ssm_conv
    moved = 2 * 26 * 128 * 5120 * 16 * 4
    assert 2 * costs_ssm.state_bytes(cfg, 128, tail_el=0) == moved
    assert got == pytest.approx(100.0 * (moved / 819e9) / 20e-6, rel=1e-6)
    # another program, another scope, another family, no trace: nothing
    assert ssm_state_share.read(rc, scope="^ssm_state_update$",
                                module="no_such") is None
    assert ssm_state_share.read(rc, scope="^moe_experts$",
                                module="lm_paged_decode_step") is None
    assert ssm_state_share.read(
        ReadContext(facts, trace, {"num_experts": 64}, {}, 1, PEAK),
        scope="^ssm_state_update$", module="lm_paged_decode_step") is None
    assert ssm_state_share.read(
        ReadContext(facts, None, cfg, {}, 1, PEAK),
        scope="^ssm_state_update$", module="lm_paged_decode_step") is None
    # the two data-only shares of the same program
    mixer = scope_share.share(_plane(), scope="^mamba$",
                              module="lm_paged_decode_step")
    state = scope_share.share(_plane(), scope="^ssm_state_update$",
                              module="lm_paged_decode_step")
    assert mixer == pytest.approx(100 * 50 / 70)
    assert state == pytest.approx(100 * 20 / 70)


def test_ssm_hbm_share_prices_the_whole_step(bench, monkeypatch):
    import jax.profiler

    from benchmark import spans
    from benchmark.readers import ssm_hbm_share

    cfg = bench.config("jamba2-3b")
    ev = lambda name, ms: NS(name=name, duration_ns=int(ms * 1e6))  # noqa: E731
    line = NS(name="XLA Modules", events=[
        ev("jit_lm_paged_decode_step(5)", 15.0) for _ in range(7)] + [
        ev("jit_lm_prefill_chunk(6)", 30.0)])
    data = NS(planes=[NS(name="/device:TPU:0", lines=[line])])
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    trace = xplane.TraceSummary({0: xplane.DeviceOps(ops=[])}, [], (0.0, 1.0))
    facts = {"num_slots": 128, "slot_len": 2048}
    got = ssm_hbm_share.read(ReadContext(facts, trace, cfg, {}, 1, PEAK),
                             module="lm_paged_decode_step")
    floor_ms = costs_ssm.decode_step_bytes(cfg, 128, 2048)[
        "total_bytes"] / 819e9 * 1e3
    assert got == pytest.approx(100 * floor_ms / 15.0, rel=1e-6)
    assert 70 < got < 72
    assert ssm_hbm_share.read(
        ReadContext(facts, trace, bench.config("olmoe-1b-7b"), {}, 1, PEAK),
        module="lm_paged_decode_step") is None
    assert ssm_hbm_share.read(ReadContext({}, trace, cfg, {}, 1, PEAK),
                              module="lm_paged_decode_step") is None


def test_the_reference_copy_is_the_programs(bench):
    """benchmark/reference/jamba.py is tpu_air/models/lm/reference_jamba.py
    under a heading of its own."""
    with open(os.path.join(manifest.REPO, "tpu_air", "models", "lm",
                           "reference_jamba.py")) as f:
        ours = f.read()
    with open(os.path.join(manifest.REPO, "benchmark", "reference",
                           "jamba.py")) as f:
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
    assert "serve_tpot_p50_ms" in last and "engine_step_ms_p50" in last
