"""What PR 58 added to the benchmark, checked on the CPU: the manifest with
the new cell, its configuration and traffic files, the seeded
published-layout weights with their hyper-connections, the cost function,
the new reader on a synthetic capture, the reference's copy, and a rehearsal
of the cell (control flow only: a CPU run prints no result line)."""

import json
import os
import subprocess
import sys
from types import SimpleNamespace as NS

import numpy as np
import pytest

from benchmark import costs_mhc, manifest, scopes, weights_xing, xplane
from benchmark.harness import ReadContext

RUN = os.path.join(manifest.REPO, "benchmark", "run.py")
PEAK = {"hbm_bytes_per_s": 819e9, "bf16_flops_per_s": 197e12}
CELL, CONFIG = "xing4-serve-longdoc", "xing4.0-29b-a4b"
NEW = ["mhc_share", "mhc_sinkhorn_share", "mhc_stream_roofline",
       "mhc_stream_roofline.decode", "moe_experts_roofline.mixed",
       "moe_experts_roofline.decode"]
# the accepted metrics the cell reports beside its own
LISTED = ["mla_decode_roofline", "mla_attention_roofline", "mla_latent_share",
          "mla_chunk_attention_share", "chunk_page_visit_share",
          "latent_live_share", "moe_shared_share", "moe_combine_share",
          "moe_load_max_over_mean", "engine_step_ms_p50",
          "engine_prefill_share", "engine_chunk_fused_share",
          "engine_mixed_step_share", "engine_decode_step_ms_p50",
          "engine_mixed_step_ms_p50", "engine_unscoped_share"]


@pytest.fixture(scope="module")
def bench():
    return manifest.Benchmark()


def test_manifest_finds_the_cell_and_lists_it_where_it_reports(bench):
    manifest.validate(bench.doc)
    cell = bench.cell(CELL)
    assert (cell["config"], cell["chips"]) == (CONFIG, 1)
    assert bench.doc["workloads"][9] is cell        # appended, not inserted
    assert len(bench.doc["workloads"]) >= 10        # later PRs append
    assert sum(w["chips"] == 4 for w in bench.doc["workloads"]) == 1
    assert bench.doc["configs"][6]["name"] == CONFIG
    assert bench.doc["configs"][6]["reduced"] == [
        "num_hidden_layers", "first_k_dense_replace"]
    assert bench.traffic(cell)["kind"] == "mhcserve"
    assert hasattr(bench.module("kinds", "mhcserve"), "deploy")
    e2e = {m["name"] for m in bench.metrics("end_to_end", CELL)}
    assert e2e == {"serve_tpot_p50_ms", "setup_s"}
    layer = {m["name"]: m for m in bench.metrics("per_layer", CELL)}
    assert set(layer) == set(NEW) | set(LISTED) | {
        "worker_compile_s", "worker_cold_compiles"}
    assert all(m["moves"] in e2e for m in layer.values())
    # moe_held_expert_roofline pairs a mean count with a median time and
    # read 123 % on this cell's decode steps (PERF.md, PR 58): the cell is on
    # this PR's own readings of each program instead, and its capture is
    # where every other cell's is
    assert "moe_held_expert_roofline" not in layer
    t = bench.traffic(cell)
    assert (t["trace_delay_s"], t["trace_s"]) == (8, 2)
    # the new metrics came in together (at the end, until PR 59 appended
    # its two), this cell alone in them, and the cell is the last of every
    # list it was appended to
    first = [m["name"] for m in bench.doc["per_layer"]].index(NEW[0])
    mine = bench.doc["per_layer"][first:first + 6]
    assert [m["name"] for m in mine] == NEW
    for m in mine:
        assert m["workloads"] == [CELL] and m["unit"] == "%"
        assert m["source"] == "device_trace"
    # (PR 60 appended its own cell behind it where both report)
    for m in bench.doc["per_layer"] + bench.doc["end_to_end"]:
        if CELL in m.get("workloads", []):
            assert [w for w in m["workloads"]
                    if w != "laguna-serve-mixedlen"][-1] == CELL
    # no other cell meets a reader or a hook of this PR
    for other in (w["name"] for w in bench.doc["workloads"][:9]):
        names = {m["name"] for m in bench.metrics("per_layer", other)}
        assert not names & set(NEW)
        assert bench.traffic(bench.cell(other))["kind"] != "mhcserve"


def test_traffic_file_is_the_cell_the_issue_wrote(bench):
    t = bench.traffic(bench.cell(CELL))
    assert (t["num_slots"], t["slot_len"], t["page_len"],
            t["max_new_tokens"], t["max_batch"]) == (64, 8192, 256, 256, 64)
    assert t["prompt_len"] == {"median": 3072, "sigma": 0.6, "min": 512,
                               "max": 7680}
    assert t["output_len"] == {"median": 96, "sigma": 0.6, "min": 16,
                               "max": 256}
    assert (t["priority"], t["poll_ms"], t["submit_threads"],
            t["poll_threads"], t["dtype"]) == ("batch", 50, 8, 12, "bfloat16")
    assert "schedule_seed" in t
    assert t["rate_rps"] == pytest.approx(0.8 * t["knee_rps"], rel=0.02)
    assert t["check_prompt_over"] == 4096 and t["check_lowprec_bits"] == 3
    # near a tie a position is held under what a wrong token (a margin
    # near 1) and a wrong row's logits read
    assert 0 < t["check_tie_eps"] < 0.1
    assert t["check_logit_tol"] < t["check_tie_margin"] < 1
    assert t["check_tie_margin"] < t["check_tie_tol"] <= 1
    assert 0.5 < t["check_kept_share"] < 1
    assert len(t["check_why"]) > 200
    # the longest prompt and answer fit a slot, and the check's fixed length
    assert t["prompt_len"]["max"] + t["output_len"]["max"] <= t["slot_len"]
    from tpu_air.serve.admission import AdmissionPolicy

    assert AdmissionPolicy().clamp_budget("batch", 256) == 256


def test_published_weights_carry_their_hyper_connections(bench):
    """The ``deepseek_v3`` tensors are ``weights_mla``'s, value for value;
    the hyper-connections are float32 under the file's names, made as its
    ``mhc_init`` says; the checkpoint's tree is the one ``CausalLM`` builds."""
    import jax
    import jax.numpy as jnp

    from benchmark import weights_mla
    from benchmark.kinds import mhcserve
    from tpu_air.models.lm import CausalLM, hf_import

    cfg = bench.config(CONFIG)
    assert weights_xing.mhc_names(cfg) == hf_import.XING_MHC_NAMES
    assert weights_mla.held(cfg) == (0, 64)
    assert weights_mla.published_view(cfg)["n_routed_experts"] == 64
    pub = weights_xing.Published(cfg, 7, "bfloat16")
    base = weights_mla.Published(cfg, 7, "bfloat16")
    for name in ("model.layers.3.mlp.experts.63.down_proj.weight",
                 "model.layers.2.mlp.gate.weight",
                 "model.layers.0.self_attn.kv_b_proj.weight"):
        assert pub.shape(name) == base.shape(name)
        np.testing.assert_array_equal(pub.raw(name), base.raw(name))
    assert pub.shape("model.layers.3.mlp.experts.63.down_proj.weight") == (
        3584, 1024)
    phi = pub.tensor("model.layers.4.mlp_hc.phi.weight")
    assert phi.shape == (24, 4 * 3584) and phi.dtype == np.float32
    assert phi.T.flags.c_contiguous          # the importer's turn is free
    assert phi.std() == pytest.approx((4 * 3584) ** -0.5, rel=0.02)
    alpha = pub.tensor("model.layers.0.attn_hc.alpha")
    assert alpha.dtype == np.float32 and alpha.shape == (3,)
    assert np.all(np.abs(alpha / [1, 1, 0.5] - 1) <= 0.1)
    b = pub.tensor("model.layers.0.attn_hc.bias")
    assert b.shape == (24,) and b.dtype == np.float32
    res = b[8:].reshape(4, 4)
    assert np.diag(res).mean() - res.mean() == pytest.approx(1.5, abs=0.4)
    # another sublayer, another seed: other values; the same: the same
    assert not np.array_equal(
        phi, pub.tensor("model.layers.4.attn_hc.phi.weight"))
    np.testing.assert_array_equal(phi, weights_xing.Published(
        cfg, 7, "bfloat16").tensor("model.layers.4.mlp_hc.phi.weight"))
    with pytest.raises(KeyError):
        pub.shape("model.layers.0.attn_hc.other")
    # mhc_init's claim, on a tiny stream of unit-variance rows: the maps move
    # by token and H_res's diagonal averages between 0.3 and 0.9
    from tpu_air.ops import mhc

    x = jax.random.normal(jax.random.PRNGKey(0), (200, 4, 3584))
    for sub in ("attn", "mlp"):
        at = lambda k: jnp.asarray(pub.tensor(  # noqa: E731
            f"model.layers.2.{sub}_hc.{k}"))
        h, h_post, res = mhc.pre(x, at("phi.weight").T, at("bias"),
                                 at("alpha"), 1e-6)
        h_res = np.asarray(mhc.sinkhorn(res, 20, 1e-6, (-30.0, 30.0)))
        assert np.asarray(h_post).std(0).min() > 0.05
        diag = h_res[:, np.arange(4), np.arange(4)].mean()
        assert 0.3 < diag < 0.9
        np.testing.assert_allclose(h_res.sum(-1), 1.0, atol=1e-4)
    # the tiny preset's checkpoint is the tree CausalLM builds, the maps
    # float32 among weights of the model's dtype
    tiny = mhcserve.TINY
    config = weights_xing.lm_config(tiny, "bfloat16", 64)
    tpub = weights_xing.Published(tiny, 3, "bfloat16")
    params = hf_import.convert_deepseek_v3_state_dict(
        tpub.tensor, config, names=weights_xing.mhc_names(tiny))
    want = jax.eval_shape(lambda: CausalLM(config).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert (jax.tree_util.tree_map(lambda a: a.shape, params)
            == jax.tree_util.tree_map(lambda a: a.shape, want))
    kinds = {"/".join(p.key for p in path): a.dtype for path, a in
             jax.tree_util.tree_flatten_with_path(params)[0]}
    assert all((dt == np.float32) == ("_hc/" in k) for k, dt in kinds.items())


def test_the_checkpoint_keeps_the_maps_float32(tmp_path):
    """``write_checkpoint`` streams bfloat16 weights and float32
    hyper-connections, and the replica's cast to the model's dtype leaves
    the hyper-connections as they are."""
    import jax
    import jax.numpy as jnp

    from benchmark.kinds import mhcserve

    ckpt = weights_xing.write_checkpoint(
        mhcserve.TINY, 11, "bfloat16", str(tmp_path / "ckpt"), max_seq_len=64)
    model, params = ckpt.get_model(dtype="bfloat16")
    assert model.config.hc_mult == 4
    hc = params["layer_1"]["attn_hc"]
    assert {k: v.dtype for k, v in hc.items()} == {
        "phi": jnp.float32, "b": jnp.float32, "alpha": jnp.float32}
    assert params["layer_1"]["attn"]["o"]["kernel"].dtype == jnp.bfloat16
    pub = weights_xing.Published(mhcserve.TINY, 11, "bfloat16")
    np.testing.assert_array_equal(
        np.asarray(hc["phi"]),
        pub.tensor("model.layers.1.attn_hc.phi.weight").T)
    # the deployment's cast (serve/engine_deployment.py)
    from tpu_air.engine import EngineConfig
    from tpu_air.serve.engine_deployment import _EngineServer

    server = _EngineServer(ckpt, EngineConfig(
        num_slots=2, slot_len=32, page_len=8, max_new_tokens=4),
        dtype="bfloat16")
    engine = server._ensure_engine()
    try:
        got = engine.params["layer_2"]["mlp_hc"]
        assert all(v.dtype == jnp.float32 for v in got.values())
        assert engine.params["embedding"].dtype == jnp.bfloat16
        assert engine.metrics.snapshot()["mhc_streams"] == 4
    finally:
        engine.close()


def test_cost_function_from_the_published_shapes(bench):
    cfg = bench.config(CONFIG)
    assert costs_mhc.sublayers(cfg) == 10
    assert costs_mhc.row_bytes(cfg) == 10 * 3584 * 2 == 71680
    assert costs_mhc.phi_bytes(cfg) == 4 * 3584 * 24 * 4
    # a mixed step of 64 rows and a 256-token chunk: 243 MB, 0.30 ms at the
    # HBM peak, 2 % of a 13 ms step
    need = costs_mhc.stream_bytes(cfg, 320)
    assert need == 10 * (320 * 71680 + 1376256)
    assert need / 819e9 == pytest.approx(0.297e-3, rel=0.01)
    assert costs_mhc.stream_bytes(cfg, 320, 4) > need


def _plane(mixed_runs=6):
    """Program step (id 5) runs twice of 100 us, program mixed (id 7)
    ``mixed_runs`` times of 200 us; each holds the maps of one sublayer
    (mhc_pre 10 us, mhc_sinkhorn 20 us, mhc_post 10 us, in the mixed step
    twice that), the expert products (30 us) and an operation with no
    path."""
    us = 1e-6
    md = {20: {"name": "jit_lm_paged_decode_step(5)"},
          22: {"name": "jit_lm_paged_mixed_step(7)"}}
    for base, program, name in ((0, 5, "lm_paged_decode_step"),
                                (100, 7, "lm_paged_mixed_step")):
        pre = f"jit({name})/CausalLM/layer_1/"
        for i, (op, path) in enumerate((
                ("%fusion.1", "attn_hc/mhc_pre/dot_general:"),
                ("%fusion.2", "attn_hc/mhc_sinkhorn/div:"),
                ("%fusion.3", "mhc_post/add:"),
                ("%gmm.1", "moe/moe_experts/pallas_call:")), 1):
            md[base + i] = {"name": op, "program_id": program,
                            "tf_op": pre + path}
        md[base + 5] = {"name": "%copy.5", "program_id": program}
    plane = scopes.DevicePlane(metadata=md)
    spans_us = [(1, 0, 10), (2, 10, 30), (3, 30, 40), (4, 40, 70),
                (5, 70, 80)]
    for r in range(2):
        t0 = r * 200 * us
        plane.modules.append((20, t0, t0 + 100 * us))
        plane.ops += [(i, t0 + a * us, t0 + b * us) for i, a, b in spans_us]
    for r in range(mixed_runs):
        t0 = (1000 + r * 300) * us
        plane.modules.append((22, t0, t0 + 200 * us))
        plane.ops += [(100 + i, t0 + 2 * a * us, t0 + 2 * b * us)
                      for i, a, b in spans_us]
    return plane


FACTS = {"mhc_stream_rows_per_step": {"lm_paged_decode_step": 40.0,
                                      "lm_paged_mixed_step": 300.0}}
STEP = ["lm_paged_decode_step", "lm_paged_mixed_step"]


def _rc(bench, cfg=None, facts=FACTS, trace=True, dtype="bfloat16"):
    trace = xplane.TraceSummary({0: xplane.DeviceOps(ops=[])}, [],
                                (0.0, 1.0)) if trace else None
    return ReadContext(facts, trace, cfg or bench.config(CONFIG),
                       {"dtype": dtype}, 1, PEAK)


def _args(name):
    with open(os.path.join(manifest.REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        doc = json.load(f)
    assert doc["reader"] == "step_program_roofline"
    return doc["args"]


def test_program_roofline_divides_a_programs_own_count_by_its_own_time(
        bench, monkeypatch):
    from benchmark import costs_mla, spans
    from benchmark.readers import scope_share, step_program_roofline

    cfg = bench.config(CONFIG)
    plane = {"is": _plane()}
    monkeypatch.setattr(spans, "newest_xplane", lambda: "capture")
    monkeypatch.setattr(scopes, "read", lambda path: {0: plane["is"]})
    read = step_program_roofline.read
    mixed, decode = (_args("mhc_stream_roofline"),
                     _args("mhc_stream_roofline.decode"))
    assert (mixed["module"], decode["module"]) == tuple(reversed(STEP))
    # each program's rows over its own time under mhc_* (80 us, 40 us),
    # whichever the capture holds more of
    for runs in (6, 1):
        plane["is"] = _plane(mixed_runs=runs)
        assert read(_rc(bench), **mixed) == pytest.approx(
            100.0 * (costs_mhc.stream_bytes(cfg, 300.0) / 819e9) / 80e-6)
        assert read(_rc(bench), **decode) == pytest.approx(
            100.0 * (costs_mhc.stream_bytes(cfg, 40.0) / 819e9) / 40e-6)
    # float32 streams move twice the bytes a row
    assert read(_rc(bench, dtype="float32"), **mixed) == pytest.approx(
        100.0 * (costs_mhc.stream_bytes(cfg, 300.0, 4) / 819e9) / 80e-6)
    # the expert products: the experts THIS program's steps touched over
    # its own 60 us (30 us) under moe_experts
    for name, program, seconds in (
            ("moe_experts_roofline.mixed", STEP[1], 60e-6),
            ("moe_experts_roofline.decode", STEP[0], 30e-6)):
        facts = {"moe_held_experts_streamed_per_step": {program: 0.5}}
        assert read(_rc(bench, facts=facts), **_args(name)) == pytest.approx(
            100.0 * costs_mla.held_expert_bytes(cfg, 0.5) / 819e9 / seconds)
        assert read(_rc(bench), **_args(name)) is None    # no such count
    # a MEAN time: executions that differ weigh by what they took, so a
    # mean count over it is total bytes over total time
    uneven = _plane(mixed_runs=4)
    uneven.ops = [(i, s, e + (60e-6 if i == 103 and s > 1.5e-3 else 0))
                  for i, s, e in uneven.ops]
    plane["is"] = uneven
    assert read(_rc(bench), **mixed) == pytest.approx(
        100.0 * (costs_mhc.stream_bytes(cfg, 300.0) / 819e9) / 110e-6)
    # no execution of the program, a configuration of one stream, a tree
    # without the counter or the scopes, no capture: nothing, and no error
    plane["is"] = _plane(mixed_runs=0)
    assert read(_rc(bench), **mixed) is None
    assert read(_rc(bench), **decode) is not None
    assert read(_rc(bench, cfg=bench.config("gigachat3.1-702b-a36b")),
                **decode) is None
    assert read(_rc(bench, facts={}), **decode) is None
    assert read(_rc(bench), **{**decode, "scope": "^no_such_scope$"}) is None
    assert read(_rc(bench, trace=False), **decode) is None
    monkeypatch.setattr(scopes, "read", lambda path: {})
    assert read(_rc(bench), **decode) is None
    # the two data-only shares, over every program of the capture
    whole = scope_share.share(_plane(), scope=mixed["scope"])
    rounds = scope_share.share(_plane(), scope="^mhc_sinkhorn$")
    assert whole == pytest.approx(100 * (2 * 40 + 6 * 80) / (2 * 80 + 6 * 160))
    assert rounds == pytest.approx(100 * (2 * 20 + 6 * 40) / (2 * 80 + 6 * 160))


class _Ctx:
    def __init__(self, traffic):
        self.traffic, self.failed_checks = traffic, []

    def check(self, ok, what):
        if not ok:
            self.failed_checks.append(what)


def _verdicts(n=40, tied_err=0.5, tied_margin=0.3, exact=38, low_kept=20,
              planted=(1.0, 1.4)):
    """Two requests of ``n`` positions, the first quarter of each near a
    tie."""
    k = n // 4
    gap = [0.001] * k + [0.05] * (n - k)
    v = {"tokens": n, "exact": exact, "reference_on": "cpu", "gap": gap,
         "err": [tied_err] * k + [0.01] * (n - k),
         "margin": [tied_margin] * k + [0.0] * (n - k),
         "planted_margin": [planted[0]] * n, "planted_err": [planted[1]] * n}
    controls = {k: [0.2] * n for k in (
        "lowprec_err", "noyarn_err", "sinkhorn1_err", "identity_err")}
    return [dict(v), dict(v, control_kept={k: low_kept for k in controls},
                          **controls)]


@pytest.mark.parametrize("fault,fails", [
    ({}, None),
    # a single position near a tie that reads what a wrong token or a wrong
    # row reads is not passed: the tier lies UNDER the planted readings
    ({"tied_margin": 0.95}, "near a tie 0.95"),
    ({"tied_err": 1.2}, "differ by up to 1.2"),
    # the planted fault must read over the limit, or the limit catches nothing
    ({"planted": (0.6, 1.4)}, "another request's token"),
    ({"planted": (1.0, 0.9)}, "another request's logits"),
    # the window's tokens are the reference's own choice, and the low
    # precision keeps fewer of them
    ({"exact": 25}, "are the reference's own choice"),
    ({"low_kept": 36}, "are the reference's own choice"),
])
def test_the_check_near_a_tie_lies_under_what_a_planted_fault_reads(
        bench, fault, fails):
    from benchmark.kinds import mhcserve

    ctx = _Ctx(bench.traffic(bench.cell(CELL)))
    got = mhcserve.hold_reference(ctx, bench.config(CONFIG),
                                  _verdicts(**fault))
    if fails is None:
        assert not ctx.failed_checks
        assert got["check_near_tied"] == 20 and got["check_positions"] == 80
        assert got["check_planted"]["margin"]["p50"] == 1.0
    else:
        assert len(ctx.failed_checks) == 1 and fails in ctx.failed_checks[0]


def test_the_reference_copy_is_the_programs(bench):
    """benchmark/reference/xing.py is tpu_air/models/lm/reference_xing.py
    under a heading of its own, calling the benchmark's copy of the
    ``deepseek_v3`` parts."""
    with open(os.path.join(manifest.REPO, "tpu_air", "models", "lm",
                           "reference_xing.py")) as f:
        ours = f.read()
    with open(os.path.join(manifest.REPO, "benchmark", "reference",
                           "xing.py")) as f:
        theirs = f.read()
    ours = ours.replace(
        "from tpu_air.models.lm import reference_deepseek as deepseek",
        "from benchmark.reference import deepseek")
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
                 "latent_live_share", "moe_load_max_over_mean"):
        assert name in last, name


def test_the_parent_tree_is_refused_in_one_line(bench, monkeypatch):
    """A tree without the importer's ``xing4_0``: the kind says so and the
    run exits 2 (``RunFailure``), before any checkpoint is written."""
    from benchmark.harness import RunFailure
    from tpu_air.models.lm import hf_import

    kind = bench.module("kinds", "mhcserve")
    monkeypatch.delattr(hf_import, "XING_MHC_NAMES")
    ctx = NS(rehearse=True, cfg={}, traffic={}, scratch="/nonexistent")
    with pytest.raises(RunFailure, match="xing4_0"):
        kind.deploy(ctx)
