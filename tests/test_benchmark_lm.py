"""What PR 27 added to the benchmark, checked on the CPU: the manifest with
its two new cells, the seeded published-layout weights, each new reader on a
synthetic trace, and a rehearsal of both cells (control flow only: a CPU run
prints no result line)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import manifest, weights_lm, xplane
from benchmark.harness import ReadContext

RUN = os.path.join(manifest.REPO, "benchmark", "run.py")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}


def test_manifest_is_valid_and_finds_both_new_cells():
    bench = manifest.Benchmark()
    manifest.validate(bench.doc)
    moe, gen = bench.cell("olmoe-serve-decode"), bench.cell("t5large-batchgen")
    assert (moe["config"], moe["chips"]) == ("olmoe-1b-7b", 1)
    assert (gen["config"], gen["chips"]) == ("flan-t5-large", 1)
    assert bench.traffic(moe)["kind"] == "lmserve"
    assert bench.traffic(gen)["kind"] == "batchgen"
    assert hasattr(bench.module("kinds", "lmserve"), "deploy")
    cfg = next(c for c in bench.doc["configs"] if c["name"] == "olmoe-1b-7b")
    assert cfg["reduced"] == ["num_hidden_layers"]
    e2e = {m["name"] for m in bench.metrics("end_to_end", moe["name"])}
    assert {"serve_tpot_p50_ms", "setup_s"} <= e2e
    layer = {m["name"]: m for m in bench.metrics("per_layer", moe["name"])}
    for name in ("lm_decode_roofline", "moe_expert_roofline",
                 "moe_load_max_over_mean", "engine_prefill_share",
                 "engine_step_ms_p50", "engine_host_ms_p50"):
        assert name in layer, name
        # a listed cell reports the end-to-end metric the metric moves
        assert layer[name]["moves"] in e2e, name
    assert {m["name"] for m in bench.metrics("per_layer", gen["name"])} >= {
        "gen_decode_roofline", "gen_call_ms_p50", "gen_block_gap_ms_p50"}
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1


def test_published_weights_are_a_function_of_the_seed_and_skew_the_router():
    cfg = manifest.Benchmark().config("olmoe-1b-7b")
    a = weights_lm.Published(cfg, 2_500_000_001, "bfloat16")
    b = weights_lm.Published(cfg, 2_500_000_001, "bfloat16")
    c = weights_lm.Published(cfg, 7, "bfloat16")
    name = "model.layers.3.mlp.experts.17.up_proj.weight"
    assert a.tensor(name).shape == (1024, 2048)
    assert np.array_equal(a.raw(name), b.raw(name))
    assert not np.array_equal(a.raw(name), c.raw(name))
    f32 = a.tensor_f32(name)
    assert f32.dtype == np.float32
    assert np.array_equal(f32, a.tensor(name).astype(np.float32))
    assert abs(float(f32.std()) - 0.02) < 1e-3
    router = a.tensor_f32("model.layers.0.mlp.gate.weight")
    assert router.shape == (64, 2048)
    gain = router.std(1) / 0.02
    assert np.allclose(np.sort(gain), np.sort(a.router_gain), rtol=0.1)
    # the same gains in every layer, so load summed over layers stays uneven
    again = a.tensor_f32("model.layers.5.mlp.gate.weight").std(1) / 0.02
    assert np.allclose(gain, again, rtol=0.1)
    assert 1.5 < gain.max() / gain.min() < 4.0
    assert a.tensor("model.norm.weight").shape == (2048,)


def _trace(ops):
    dev = xplane.DeviceOps(ops=ops)
    return xplane.TraceSummary({0: dev}, [], (0.0, 1.0))


def test_expert_kernel_share_on_a_synthetic_trace():
    from benchmark.readers import expert_kernel_share

    cfg = manifest.Benchmark().config("olmoe-1b-7b")
    # 24 calls of 0.4 ms, one slow straggler; other operations are not it
    ops = [(f"gmm.{i}" if i else "gmm", 0.01 * i, 0.01 * i + 4e-4)
           for i in range(24)]
    ops += [("gmm.99", 0.5, 0.52), ("fusion.7", 0.6, 0.7),
            ("gmmx", 0.8, 0.9)]
    facts = {"moe_experts_streamed_per_layer_step": 64.0}
    rc = ReadContext(facts, _trace(ops), cfg, {}, 1, PEAK)
    got = expert_kernel_share.read(rc, kernel=r"^gmm(\.\d+)?$")
    want = 100.0 * (64 * 2048 * 1024 * 2 / 819e9) / 4e-4
    assert got == pytest.approx(want, rel=1e-6) and 80 < got < 85
    # a program without the kernel, or without the counter: nothing, no error
    none = ReadContext(facts, _trace(ops[-2:]), cfg, {}, 1, PEAK)
    assert expert_kernel_share.read(none, kernel=r"^gmm(\.\d+)?$") is None
    assert expert_kernel_share.read(
        ReadContext({}, _trace(ops), cfg, {}, 1, PEAK),
        kernel=r"^gmm(\.\d+)?$") is None
    assert expert_kernel_share.read(
        ReadContext(facts, None, cfg, {}, 1, PEAK), kernel="x") is None


def test_module_durations_pick_the_decode_program_by_name():
    from benchmark.readers import module_hbm_share

    ev = lambda name, ms: NS(name=name, duration_ns=int(ms * 1e6))  # noqa: E731
    steps = [ev("jit_lm_paged_decode_step(123)", 15.0) for _ in range(9)]
    line = NS(name="XLA Modules", events=steps + [
        ev("jit_lm_prefill_chunk(456)", 9.0),
        ev("jit_lm_paged_decode_step_other(1)", 99.0)])
    data = NS(planes=[
        NS(name="/host:CPU", lines=[]),
        NS(name="/device:TPU:0", lines=[NS(name="XLA Ops", events=[]), line])])
    got = module_hbm_share.module_durations(data, "lm_paged_decode_step")
    assert got == pytest.approx([0.015] * 9)
    assert module_hbm_share.module_durations(data, "no_such") == []
    assert module_hbm_share.module_durations(NS(planes=[]), "x") == []
    # no trace, or facts of another kind: nothing, no error
    cfg = manifest.Benchmark().config("olmoe-1b-7b")
    assert module_hbm_share.read(
        ReadContext({}, None, cfg, {}, 1, PEAK), module="x") is None
    assert module_hbm_share.read(
        ReadContext({}, _trace([("a", 0, 1)]), {"d_model": 8}, {}, 1, PEAK),
        module="x") is None


def _result_lines(stdout):
    out = []
    for line in stdout.splitlines():
        try:
            doc = json.loads(line)
        except ValueError:
            continue
        if isinstance(doc, dict) and "metrics" in doc:
            out.append(doc)
    return out


@pytest.mark.parametrize("cell, seconds", [
    ("olmoe-serve-decode", "3"), ("t5large-batchgen", "1")])
def test_rehearsal_of_the_new_cells(cell, seconds):
    out = subprocess.run(
        [sys.executable, RUN, "--workload", cell, "--rehearse",
         "--seconds", seconds, "--trace", "1", "--seed", "2500000011"],
        capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stdout[-3000:] + out.stderr[-3000:]
    assert _result_lines(out.stdout) == []
    last = out.stdout.strip().splitlines()[-1]
    assert last.startswith(f"rehearsal of {cell}: ok"), last
    if cell == "olmoe-serve-decode":
        assert "moe_load_max_over_mean" in last
