"""What PR 43 added to the benchmark, checked on the CPU: the manifest with
the new cell, its configuration and traffic files, the seeded
published-layout weights of one expert-parallel rank, the cost functions,
each new reader on a synthetic capture, and a rehearsal of the cell (control
flow only: a CPU run prints no result line)."""

import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import costs_mla, manifest, scopes, weights_mla, xplane
from benchmark.harness import ReadContext

RUN = os.path.join(manifest.REPO, "benchmark", "run.py")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL, CONFIG = "gigachat-serve-docchat", "gigachat3.1-702b-a36b"


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


def test_manifest_finds_the_cell_and_lists_it_where_it_reports(bench):
    manifest.validate(bench.doc)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert bench.doc["workloads"][7] is cell        # appended, not inserted
    assert bench.doc["configs"][4]["name"] == CONFIG
    assert bench.doc["configs"][4]["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace", "n_routed_experts",
        "vocab_size"]
    assert bench.traffic(cell)["kind"] == "mlaserve"
    assert hasattr(bench.module("kinds", "mlaserve"), "deploy")
    e2e = {m["name"] for m in bench.metrics("end_to_end", CELL)}
    assert e2e == {"serve_tpot_p50_ms", "setup_s"}
    layer = {m["name"]: m for m in bench.metrics("per_layer", CELL)}
    for name in ("mla_decode_roofline", "mla_attention_roofline",
                 "moe_held_expert_roofline", "mla_latent_share",
                 "moe_shared_share", "latent_live_share",
                 "engine_step_ms_p50", "engine_prefill_share",
                 "engine_chunk_fused_share", "engine_unscoped_share",
                 "moe_load_max_over_mean", "worker_compile_s"):
        assert name in layer, name
        assert layer[name]["moves"] in e2e, name
    # costs_moe prices intermediate_size (here the DENSE width) and K/V at
    # h*d, and moe_expert_roofline reads kernels by name: not this cell's;
    # nor are the readers of the decode program ALONE and of pairs of steps
    # with no prefill between them: at this cell's rate nearly every
    # iteration carries a chunk and they find nothing (PERF.md, PR 43)
    for name in ("lm_decode_roofline", "moe_expert_roofline",
                 "ssm_decode_roofline", "engine_idle_host_ms",
                 "lm_kv_gather_share", "lm_attention_share",
                 "lm_expert_share", "engine_host_ms_p50"):
        assert name not in layer, name
    # the new metrics came in together, this cell the first to report them
    # (PR 47 appended its own behind them, and its cell to moe_shared_share)
    names = [m["name"] for m in bench.doc["per_layer"]]
    first = names.index("mla_decode_roofline")
    mine = bench.doc["per_layer"][first:first + 8]
    new = [m["name"] for m in mine]
    assert new == ["mla_decode_roofline", "mla_attention_roofline",
                   "moe_held_expert_roofline", "mla_latent_share",
                   "moe_shared_share", "latent_live_share",
                   # PR 45: the chunk's walk, and what the traffic leaves it
                   "mla_chunk_attention_share", "chunk_page_visit_share"]
    assert all(m["workloads"][0] == CELL for m in mine)
    assert [w["name"] for w in bench.doc["workloads"][:7]] == [
        "t5base-finetune", "t5base-finetune-dp4", "t5base-batchgen",
        "t5large-serve", "t5large-batchgen", "olmoe-serve-decode",
        "jamba2-serve-reason"]


def test_traffic_file_is_the_cell_the_issue_wrote(bench):
    t = bench.traffic(bench.cell(CELL))
    assert (t["num_slots"], t["slot_len"], t["page_len"],
            t["max_new_tokens"]) == (128, 4096, 256, 1024)
    assert t["prompt_len"] == {"median": 1024, "sigma": 0.7, "min": 128,
                               "max": 3072}
    assert t["output_len"] == {"median": 256, "sigma": 0.6, "min": 64,
                               "max": 1024}
    assert (t["priority"], t["poll_ms"], t["submit_threads"],
            t["poll_threads"], t["dtype"]) == ("batch", 50, 8, 12, "bfloat16")
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"], rel=0.02)
    assert t["check_requests"] == 4 and t["check_lowprec_bits"] == 3
    assert 0 < t["check_tie_eps"] < 0.1 and t["check_tie_tol"] > t[
        "check_logit_tol"]
    assert len(t["check_why"]) > 200
    # the longest prompt and answer fit a slot, and the check's fixed length
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= t["slot_len"]
    from tpu_air.serve.admission import AdmissionPolicy

    assert AdmissionPolicy().clamp_budget("batch", 1024) == 1024


def test_published_weights_are_the_ranks_share(bench):
    cfg = bench.config(CONFIG)
    a = weights_mla.Published(cfg, 2_500_000_001, "bfloat16")
    b = weights_mla.Published(cfg, 2_500_000_001, "bfloat16")
    c = weights_mla.Published(cfg, 7, "bfloat16")
    name = "model.layers.1.self_attn.kv_a_proj_with_mqa.weight"
    assert a.tensor(name).shape == (576, 7168)
    assert np.array_equal(a.raw(name), b.raw(name))
    assert not np.array_equal(a.raw(name), c.raw(name))
    assert abs(float(a.tensor(name).astype(np.float32).std()) - 0.02) < 1e-3
    assert weights_mla.held(cfg) == (0, 16)
    assert weights_mla.published_view(cfg)["n_routed_experts"] == 256
    assert a.shape("model.layers.3.self_attn.kv_b_proj.weight") == (
        64 * 320, 512)
    assert a.shape("model.layers.3.self_attn.o_proj.weight") == (7168, 12288)
    assert a.shape("model.layers.3.mlp.shared_experts.up_proj.weight") == (
        2048, 7168)
    # the router scores all 256, unevenly; the bias is small and not zero
    gate = a.tensor("model.layers.1.mlp.gate.weight").astype(np.float32)
    assert gate.shape == (256, 7168)
    rows = gate.std(-1)
    assert rows.max() / rows.min() > 2.5
    bias = a.tensor("model.layers.1.mlp.gate.e_score_correction_bias"
                    ).astype(np.float32)
    assert bias.shape == (256,) and 0.005 < bias.std() < 0.06
    # every rank's 16 experts take one gain and one bias from each sixteenth
    # of the 256 quantiles, dealt anew for each layer
    for what, got, want in ((0, rows / 0.02, lambda z: np.exp(0.25 * z)),
                            (1, bias, lambda z: z * a.bias_std)):
        dealt = a.dealt(1, what)
        order = np.argsort(np.argsort(dealt)).reshape(16, 16) // 16
        assert all(sorted(r) == list(range(16)) for r in order.tolist())
        np.testing.assert_allclose(got, want(dealt), rtol=0.05, atol=2e-4)
    assert not np.array_equal(a.dealt(1, 0), a.dealt(2, 0))
    assert np.array_equal(a.dealt(1, 0), b.dealt(1, 0))
    assert np.all(a.tensor("model.layers.1.self_attn.q_a_layernorm.weight"
                           ).astype(np.float32) == 1)
    with pytest.raises(KeyError):
        a.shape("model.layers.1.mlp.experts.200.up_proj.weight")
    with pytest.raises(KeyError):
        a.shape("model.layers.1.self_attn.q_proj.weight")
    # a matrix the importer transposes whole lies column-major (its
    # transpose costs no copy); what it gathers rows of or cuts by head,
    # row-major
    for name, turned in (
            ("model.layers.1.mlp.experts.3.down_proj.weight", True),
            ("model.layers.0.mlp.up_proj.weight", True),
            ("lm_head.weight", True), ("model.embed_tokens.weight", False),
            ("model.layers.1.self_attn.kv_b_proj.weight", False),
            ("model.layers.1.mlp.gate.weight", False)):
        t = a.tensor(name)
        assert t.shape == a.shape(name)
        assert t.T.flags.c_contiguous == turned, name
        assert t.flags.c_contiguous != turned, name


def test_the_checkpoint_is_streamed_in_flaxs_bytes(tmp_path):
    """``write_params`` writes leaf by leaf what ``Checkpoint.from_model``
    would (short leaves and long ones, two dtypes), and the checkpoint
    loads the normal way."""
    import io

    import jax
    from flax import serialization

    from tpu_air.models.lm import hf_import
    from tpu_air.train.checkpoint import Checkpoint

    kind = manifest.Benchmark().module("kinds", "mlaserve")
    cfg = {**kind.TINY, "hidden_size": 256, "vocab_size": 512}
    for dtype in ("bfloat16", "float32"):
        config = weights_mla.lm_config(cfg, dtype, 64)
        pub = weights_mla.Published(cfg, 2_147_483_999, dtype)
        params = jax.tree_util.tree_map(
            lambda a: a.view(pub.dtype),
            hf_import.convert_deepseek_v3_state_dict(pub.raw, config))
        sizes = [a.nbytes for a in jax.tree_util.tree_leaves(params)]
        assert min(sizes) < 1 << 16 <= max(sizes)
        f = io.BytesIO()
        weights_mla.write_params(params, f)
        assert f.getvalue() == serialization.msgpack_serialize(params)
    ckpt = weights_mla.write_checkpoint(cfg, 2_147_483_999, "float32",
                                        str(tmp_path / "c"), max_seq_len=64)
    loaded = Checkpoint.from_directory(ckpt.to_directory()).get_params()
    jax.tree_util.tree_map(np.testing.assert_array_equal, loaded, params)
    with pytest.raises(ValueError, match="chunks"):
        weights_mla.write_params(
            {"a": np.lib.stride_tricks.as_strided(
                np.zeros(1, np.uint8), (2 ** 30 + 1,), (0,))}, io.BytesIO())


def test_cost_functions_from_the_published_shapes(bench):
    cfg = bench.config(CONFIG)
    assert costs_mla.attention_params(cfg) == pytest.approx(132.6e6, rel=1e-3)
    assert costs_mla.expert_params(cfg) == 3 * 7168 * 2048
    assert costs_mla.layer_counts(cfg) == {"dense": 1, "sparse": 4}
    assert costs_mla.latent_width(cfg) == 576
    assert costs_mla.latent_bytes(cfg, 128 * 4096) == pytest.approx(
        3.02e9, rel=2e-3)
    live = 60000.0
    ops_per_byte = (costs_mla.absorbed_attention_flops(cfg, live)
                    / costs_mla.latent_bytes(cfg, live))
    assert ops_per_byte == pytest.approx(121, abs=0.5)      # under 240
    assert costs_mla.held_expert_bytes(cfg, 1) == pytest.approx(
        3 * 29.36e6, rel=1e-3)
    b = costs_mla.decode_step_bytes(cfg, live, 64)
    assert b["total_bytes"] == sum(v for k, v in b.items()
                                   if k != "total_bytes")
    # every held expert touched: 8.35 GB of weights, the held experts 5.64
    # of it, latent attention's matrices 1.33
    weights = b["total_bytes"] - b["latent_bytes"]
    assert weights == pytest.approx(8.35e9, rel=3e-3)
    assert b["held_expert_bytes"] == pytest.approx(5.64e9, rel=2e-3)
    assert b["attention_weight_bytes"] == pytest.approx(1.326e9, rel=2e-3)
    assert b["latent_bytes"] == 5 * live * 576 * 2


# -- the new readers on a hand-made capture ----------------------------------

def _plane(mixed_runs=0):
    """Program step (id 5) runs four times of 100 us; each holds a latent
    gather (10 us) and an absorbed read (20 us) under attn, the expert
    products (two of 15 us) under moe, latent attention's own matrices
    (mla_q 5 us, mla_out 5 us), the shared expert (10 us) and one operation
    with no path (10 us).  Program chunk (id 6) runs once.  Program mixed
    (id 7) runs ``mixed_runs`` times: the same operations, each twice as
    long."""
    us = 1e-6
    md = {20: {"name": "jit_lm_paged_decode_step(5)"},
          21: {"name": "jit_lm_prefill_chunk(6)"},
          22: {"name": "jit_lm_paged_mixed_step(7)"},
          9: {"name": "%fusion.9", "program_id": 6,
              "tf_op": "jit(lm_prefill_chunk)/CausalLM/layer_1/attn/"
                       "kv_gather/gather:"}}
    for base, program, name in ((0, 5, "lm_paged_decode_step"),
                                (100, 7, "lm_paged_mixed_step")):
        pre = f"jit({name})/CausalLM/layer_1/"
        for i, (op, path) in enumerate((
                ("%fusion.1", "attn/kv_gather/gather:"),
                ("%fusion.2", "attn/decode_attention/dot_general:"),
                ("%gmm.1", "moe/moe_experts/pallas_call:"),
                ("%some_other_kernel", "moe/moe_experts/mul:"),
                ("%fusion.5", "attn/mla_q/q_b/dot_general:"),
                ("%fusion.6", "attn/mla_out/o/dot_general:"),
                ("%fusion.7", "moe_shared/shared/up/dot_general:")), 1):
            md[base + i] = {"name": op, "program_id": program,
                            "tf_op": pre + path}
        md[base + 8] = {"name": "%copy.8", "program_id": program}
    plane = scopes.DevicePlane(metadata=md)
    spans_us = [(1, 0, 10), (2, 10, 30), (3, 30, 45), (4, 45, 60),
                (5, 60, 65), (6, 65, 70), (7, 70, 80), (8, 80, 90)]
    for r in range(4):
        t0 = r * 200 * us
        plane.modules.append((20, t0, t0 + 100 * us))
        plane.ops += [(i, t0 + a * us, t0 + b * us) for i, a, b in spans_us]
    plane.modules.append((21, 900 * us, 1000 * us))
    plane.ops.append((9, 900 * us, 1000 * us))
    for r in range(mixed_runs):
        t0 = (1000 + r * 300) * us
        plane.modules.append((22, t0, t0 + 200 * us))
        plane.ops += [(100 + i, t0 + 2 * a * us, t0 + 2 * b * us)
                      for i, a, b in spans_us]
    return plane


STEP = ["lm_paged_decode_step", "lm_paged_mixed_step"]
FACTS = {"latent_positions_live_per_step": 60000.0,
         "moe_held_experts_streamed_per_step": {
             "lm_paged_decode_step": 40.0, "lm_paged_mixed_step": 63.5}}


def _rc(bench, cfg=None, facts=FACTS, trace=True):
    trace = xplane.TraceSummary({0: xplane.DeviceOps(ops=[])}, [],
                                (0.0, 1.0)) if trace else None
    return ReadContext(facts, trace, cfg or bench.config(CONFIG), {}, 1, PEAK)


def test_scope_rooflines_read_the_scope_not_a_kernel_name(bench, monkeypatch):
    from benchmark import spans
    from benchmark.readers import mla_scope_roofline, scope_share

    cfg = bench.config(CONFIG)
    plane = {"is": _plane()}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(scopes, "read", lambda path: {0: plane["is"]})
    attention = dict(part="attention", under="^attn$",
                     scope="^(kv_gather|decode_attention)$", modules=STEP)
    got = mla_scope_roofline.read(_rc(bench), **attention)
    # the bytes are the larger bound: 121 operations a byte against 240
    by_bytes = costs_mla.latent_bytes(cfg, 60000.0) / 819e9
    by_ops = costs_mla.absorbed_attention_flops(cfg, 60000.0) / 197e12
    assert by_bytes > by_ops
    assert got == pytest.approx(100.0 * by_bytes / 30e-6, rel=1e-6)
    experts = dict(part="held_experts", scope="^moe_experts$", modules=STEP)
    expert_s = 3 * 7168 * 2048 * 2 / 819e9
    # only the decode program ran: its steps' count of experts
    got = mla_scope_roofline.read(_rc(bench), **experts)
    assert got == pytest.approx(100.0 * 40.0 * expert_s / 30e-6, rel=1e-6)
    # another program, no operation in the scope, no counts, another
    # family, no trace: nothing
    assert mla_scope_roofline.read(
        _rc(bench), **{**experts, "modules": ["no_such"]}) is None
    assert mla_scope_roofline.read(
        _rc(bench), **{**experts, "scope": "^ssm_scan$"}) is None
    assert mla_scope_roofline.read(_rc(bench, facts={}), **experts) is None
    assert mla_scope_roofline.read(
        _rc(bench, cfg=bench.config("olmoe-1b-7b")), **experts) is None
    assert mla_scope_roofline.read(_rc(bench, trace=False),
                                   **attention) is None
    # most of the capture's steps carried a chunk: the mixed step is read,
    # with the experts ITS steps streamed over ITS time in the scope
    plane["is"] = _plane(mixed_runs=6)
    got = mla_scope_roofline.read(_rc(bench), **experts)
    assert got == pytest.approx(100.0 * 63.5 * expert_s / 60e-6, rel=1e-6)
    got = mla_scope_roofline.read(_rc(bench), **attention)
    assert got == pytest.approx(100.0 * by_bytes / 60e-6, rel=1e-6)
    # ... and nothing where the engine counted no step of that program
    only_alone = {**FACTS, "moe_held_experts_streamed_per_step": {
        "lm_paged_decode_step": 40.0}}
    assert mla_scope_roofline.read(_rc(bench, facts=only_alone),
                                   **experts) is None
    # the two data-only shares, over every program of the capture
    own = scope_share.share(_plane(), scope="^(mla_q|mla_latent|mla_out)$")
    shared = scope_share.share(_plane(), scope="^moe_shared$")
    assert own == pytest.approx(100 * 4 * 10 / (4 * 90 + 100))
    assert shared == pytest.approx(100 * 4 * 10 / (4 * 90 + 100))


def test_mla_hbm_share_prices_the_whole_step_at_live_positions(
        bench, monkeypatch):
    import jax.profiler

    from benchmark import spans
    from benchmark.readers import mla_hbm_share

    cfg = bench.config(CONFIG)
    ev = lambda name, ms: NS(name=name, duration_ns=int(ms * 1e6))  # noqa: E731
    line = NS(name="XLA Modules", events=[
        ev("jit_lm_paged_decode_step(5)", 30.0) for _ in range(7)] + [
        ev("jit_lm_paged_mixed_step(6)", 45.0)])
    data = NS(planes=[NS(name="/device:TPU:0", lines=[line])])
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(jax.profiler.ProfileData, "from_file",
                        staticmethod(lambda path: data))
    got = mla_hbm_share.read(_rc(bench), modules=STEP)
    need = costs_mla.decode_step_bytes(cfg, 60000.0, 40.0)["total_bytes"]
    assert got == pytest.approx(100 * (need / 819e9) / 30e-3, rel=1e-6)
    assert 24 < got < 27
    # the live positions, not the pool's: the whole pool would add 2.7 GB
    whole = costs_mla.decode_step_bytes(cfg, 128 * 4096, 40.0)["total_bytes"]
    assert whole - need > 2.6e9
    # most steps carried a chunk: the mixed step's time and its own count
    line.events += [ev("jit_lm_paged_mixed_step(6)", 45.0)] * 9
    got = mla_hbm_share.read(_rc(bench), modules=STEP)
    need = costs_mla.decode_step_bytes(cfg, 60000.0, 63.5)["total_bytes"]
    assert got == pytest.approx(100 * (need / 819e9) / 45e-3, rel=1e-6)
    assert mla_hbm_share.read(_rc(bench, facts={}), modules=STEP) is None
    assert mla_hbm_share.read(_rc(bench, cfg=bench.config("jamba2-3b")),
                              modules=STEP) is None
    assert mla_hbm_share.read(_rc(bench), modules=["no_such"]) is None


def test_rounding_on_the_bit_pattern_is_the_accepted_rounding():
    """The check's low-precision reading rounds as ``worker_hooks_lm``'s
    does (ties to even, the exponent untouched), without frexp and ldexp."""
    import jax.numpy as jnp

    from benchmark import worker_hooks_lm, worker_hooks_mla

    rng = np.random.default_rng(0)
    x = (rng.standard_normal(100_000)
         * 10.0 ** rng.integers(-20, 20, 100_000)).astype(np.float32)
    x = np.concatenate([x, np.float32([0, -0.0, 1, 1.0625, 1.1875, -1.0625,
                                       0.96875, 255.5, 3e38])])
    for bits in (3, 7, 10):
        got = worker_hooks_mla.round_mantissa(bits)(jnp.asarray(x))
        want = worker_hooks_lm.round_mantissa(bits)(jnp.asarray(x))
        np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    three = np.asarray(worker_hooks_mla.round_mantissa(3)(jnp.asarray(x)))
    assert np.array_equal(three.astype(jnp.bfloat16).astype(np.float32), three)


def test_the_reference_copy_is_the_programs(bench):
    """benchmark/reference/deepseek.py is
    tpu_air/models/lm/reference_deepseek.py under a heading of its own."""
    with open(os.path.join(manifest.REPO, "tpu_air", "models", "lm",
                           "reference_deepseek.py")) as f:
        ours = f.read()
    with open(os.path.join(manifest.REPO, "benchmark", "reference",
                           "deepseek.py")) as f:
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
                 "latent_live_share", "moe_load_max_over_mean"):
        assert name in last, name


def test_the_parent_tree_is_refused_in_one_line(bench, monkeypatch):
    """A tree without the importer: the kind says so and the run exits 2
    (``RunFailure``), before any checkpoint is written."""
    from benchmark.harness import RunFailure
    from tpu_air.models.lm import hf_import

    kind = bench.module("kinds", "mlaserve")
    monkeypatch.delattr(hf_import, "convert_deepseek_v3_state_dict")
    ctx = NS(rehearse=True, cfg={}, traffic={}, scratch="/nonexistent")
    with pytest.raises(RunFailure, match="deepseek_v3"):
        kind.deploy(ctx)
