"""OLMoE (sparse experts, q/k RMSNorm, untied head) through ``CausalLM`` and
``InferenceEngine``, held to the plain reference (``models/lm/reference.py``)
at a tiny size on the CPU: d 64, 4 heads, 8 experts top-2, 2 layers, seeded
weights in the PUBLISHED layout imported through ``hf_import``.

Tolerance: everything here is float32 on the CPU, so system and reference
differ by accumulation order only; 2e-5 of a logit scale of about 3 holds
with an order of magnitude to spare and would fail a bf16 product (1e-2).
"""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_air.engine import EngineConfig, InferenceEngine
from tpu_air.models.lm import CausalLM, hf_import, reference
from tpu_air.models.lm.modeling import rope
from tpu_air.observability.perf import LMCostModel

import _combine_cases
import _mixed_step_cases

_REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TOL = 2e-5

HF = dict(model_type="olmoe", hidden_size=64, num_hidden_layers=2,
          num_attention_heads=4, num_key_value_heads=4, intermediate_size=32,
          num_experts=8, num_experts_per_tok=2, vocab_size=96,
          max_position_embeddings=128, rope_theta=10000.0, rms_norm_eps=1e-5,
          tie_word_embeddings=False, norm_topk_prob=False, clip_qkv=None,
          attention_bias=False, rope_scaling=None, hidden_act="silu")


def published(seed=0):
    """A seeded state dict in the published names and layout; non-trivial
    norm weights, so a wrong permutation of q_norm / k_norm shows."""
    rng = np.random.default_rng(seed)
    d, f, v, e = 64, 32, 96, 8
    mat = lambda o, i, std=None: (rng.standard_normal((o, i))  # noqa: E731
                                  * (std or i ** -0.5)).astype(np.float32)
    vec = lambda n, s: (1 + s * rng.standard_normal(n)).astype(  # noqa: E731
        np.float32)
    sd = {"model.embed_tokens.weight": mat(v, d, 1.0),
          "lm_head.weight": mat(v, d), "model.norm.weight": vec(d, 0.1)}
    for i in range(2):
        p = f"model.layers.{i}."
        for n in "qkvo":
            sd[p + f"self_attn.{n}_proj.weight"] = mat(d, d)
        sd[p + "self_attn.q_norm.weight"] = vec(d, 0.3)
        sd[p + "self_attn.k_norm.weight"] = vec(d, 0.3)
        sd[p + "input_layernorm.weight"] = vec(d, 0.1)
        sd[p + "post_attention_layernorm.weight"] = vec(d, 0.1)
        sd[p + "mlp.gate.weight"] = mat(e, d)
        for x in range(e):
            q = f"{p}mlp.experts.{x}."
            sd[q + "gate_proj.weight"] = mat(f, d)
            sd[q + "up_proj.weight"] = mat(f, d)
            sd[q + "down_proj.weight"] = mat(d, f)
    return sd


def build(sd):
    cfg = hf_import.lm_config_from_hf(HF)
    params = jax.tree_util.tree_map(
        jnp.asarray, hf_import.convert_olmoe_state_dict(sd.__getitem__, cfg))
    return CausalLM(cfg), params


def test_forward_logits_match_the_reference():
    sd = published()
    model, params = build(sd)
    ids = np.random.default_rng(1).integers(2, 96, 37)
    got = np.asarray(model.apply({"params": params}, jnp.asarray(ids[None])))
    want = reference.forward(sd.__getitem__, HF, ids)["logits"]
    assert want.shape == (37, 96) and np.abs(want).max() > 1.0
    assert np.abs(got[0] - want).max() < TOL


def test_chunked_prefill_then_paged_decode_match_the_reference():
    """The engine bodies' own programs up to the head (chunks of one page,
    then single-token steps over the paged cache), answers teacher-forced,
    against the reference's full forward over prompt + answer."""
    from benchmark.worker_hooks_lm import paged_logits

    sd = published()
    model, params = build(sd)
    rng = np.random.default_rng(2)
    prompts = [rng.integers(2, 96, n).tolist() for n in (5, 19, 8)]
    answers = [rng.integers(2, 96, n).tolist() for n in (6, 4, 6)]
    got = paged_logits(model, params, 8, prompts, answers)
    for p, a, g in zip(prompts, answers, got):
        ids = p + a[:-1]
        want = reference.forward(sd.__getitem__, HF, ids,
                                 rows=range(len(p) - 1, len(ids)))["logits"]
        assert g.shape == want.shape == (len(a), 96)
        assert np.abs(g - want).max() < TOL


def test_engine_streams_the_references_tokens_with_unequal_prompts():
    sd = published()
    model, params = build(sd)
    rng = np.random.default_rng(3)
    prompts = [rng.integers(2, 96, n).tolist() for n in (5, 17, 9, 22)]
    with InferenceEngine(
            model, params,
            EngineConfig(num_slots=3, slot_len=64, page_len=8,
                         max_new_tokens=6, eos_token_id=None),
            auto_start=False) as eng:
        outs = eng.generate(prompts, 6)
        snap = eng.metrics.snapshot()
    for p, o in zip(prompts, outs):
        ids = p + o[:-1]
        want = reference.forward(sd.__getitem__, HF, ids,
                                 rows=range(len(p) - 1, len(ids)))["logits"]
        # the streamed token is the reference's largest logit, or within
        # the float32 tolerance of it
        assert (want.max(-1) - want[np.arange(6), o]).max() < TOL
    # 4 requests x 5 tokens decoded in steps x 2 layers x top-2, none lost
    assert snap["moe_assignments"] == 4 * 5 * 2 * 2
    assert sum(snap["moe_expert_load"]) == snap["moe_assignments"]
    assert len(snap["moe_expert_load"]) == 8
    assert 0 < snap["moe_experts_streamed"] <= snap["moe_steps"] * 2 * 8


@pytest.mark.parametrize("case", sorted(_mixed_step_cases.CASES))
def test_mixed_step(case):
    """One program for an iteration's prefill chunk and its decode step
    (tests/_mixed_step_cases.py): the grouped expert product over the step's
    rows and the chunk's together, every streamed token held to the float32
    reference."""
    sd = published()
    model, params = build(sd)

    def check(prompt, tokens):
        ids = prompt + tokens[:-1]
        want = reference.forward(sd.__getitem__, HF, ids,
                                 rows=range(len(prompt) - 1, len(ids)))["logits"]
        assert (want.max(-1)
                - want[np.arange(len(tokens)), tokens]).max() < TOL

    _mixed_step_cases.CASES[case](model, params, check)


def test_a_skewed_router_overloads_one_expert_and_drops_nothing():
    sd = published(seed=4)
    # every token carries a common component (added to every embedding row)
    # and expert 0's router row points along it: nearly all tokens choose it
    sd["model.embed_tokens.weight"][:, :8] += 3.0
    for i in range(2):
        sd[f"model.layers.{i}.mlp.gate.weight"][0, :8] += 4.0
    model, params = build(sd)
    ids = np.random.default_rng(5).integers(2, 96, 64)
    got, state = model.apply({"params": params}, jnp.asarray(ids[None]),
                             mutable=["intermediates"])
    rows = np.asarray(jax.tree_util.tree_leaves(state["intermediates"])[0])
    load = rows.sum(0)
    assert rows.shape == (64, 8) and (rows.sum(1) == 2).all()
    assert load[0] >= 60 and load[0] >= 5 * np.delete(load, 0).mean()
    want = reference.forward(sd.__getitem__, HF, ids)["logits"]
    assert np.abs(np.asarray(got)[0] - want).max() < TOL


def test_expert_product_with_every_row_on_one_expert():
    """The grouped product itself under the most uneven load there is."""
    from tpu_air.ops.moe import expert_ffn

    rng = np.random.default_rng(6)
    t, d, f, e = 24, 16, 8, 4
    x = rng.standard_normal((t, d)).astype(np.float32)
    g, u = (rng.standard_normal((e, d, f)).astype(np.float32)
            for _ in range(2))
    dn = rng.standard_normal((e, f, d)).astype(np.float32)
    chosen = np.stack([np.full(t, 2), rng.choice([0, 1, 3], t)], 1)
    w = rng.uniform(0.1, 0.9, (t, 2)).astype(np.float32)
    got = np.asarray(expert_ffn(*map(jnp.asarray, (x, chosen, w, g, u, dn))))
    want = np.zeros((t, d))
    for i in range(t):
        for k in range(2):
            c = chosen[i, k]
            a, b = x[i] @ g[c], x[i] @ u[c]
            want[i] += w[i, k] * ((a / (1 + np.exp(-a)) * b) @ dn[c])
    assert np.abs(got - want).max() < 1e-4 * np.abs(want).max()


@pytest.mark.parametrize("routing", _combine_cases.WHOLE)
def test_gated_expert_ffn_and_its_gradient_against_a_loop_over_experts(
        routing):
    """Every expert held: the case in which every product row is held
    (tests/_combine_cases.py)."""
    _combine_cases.against_the_loop(routing, gated=True)


@pytest.mark.parametrize("form", sorted(_combine_cases.FORMS))
def test_the_sum_over_a_tokens_choices_with_every_row_held(form):
    _combine_cases.rows_of_no_group("none_elsewhere", form)


def test_the_sums_kernel_is_differentiated_as_the_gathered_form():
    _combine_cases.kernel_gradient("none_elsewhere")


def test_rope_columns_turn_rotate_half_into_the_programs_pairing():
    heads, d = 4, 16
    perm = hf_import.rope_columns(heads, d)
    assert sorted(perm) == list(range(heads * d))
    assert perm[:4].tolist() == [0, 8, 1, 9] and perm[d] == d
    rng = np.random.default_rng(7)
    x = rng.standard_normal((5, heads, d)).astype(np.float32)   # [T, H, d]
    pos = np.arange(5) + 3
    want = np.asarray(reference.rope_rotate_half(
        jnp.asarray(x), jnp.asarray(pos), 10000.0)).reshape(5, -1)[:, perm]
    mine = x.reshape(5, -1)[:, perm].reshape(1, 5, heads, d).transpose(
        0, 2, 1, 3)                                             # [B, H, L, d]
    got = np.asarray(rope(jnp.asarray(mine), jnp.asarray(pos[None]),
                          10000.0)).transpose(0, 2, 1, 3).reshape(5, -1)
    assert np.abs(got - want).max() < 1e-6


def test_the_importer_refuses_a_layer_it_does_not_compute():
    for key, value in (("norm_topk_prob", True), ("clip_qkv", 8.0),
                       ("num_key_value_heads", 2), ("model_type", "llama")):
        with pytest.raises(ValueError):
            hf_import.lm_config_from_hf({**HF, key: value})


def test_cost_model_prices_experts_stored_and_computed_apart():
    cfg = hf_import.lm_config_from_hf(HF, dtype="bfloat16")
    m = LMCostModel(cfg)
    d, f, e, k, layers, v, hd = 64, 32, 8, 2, 2, 96, 64
    attn, one = 4 * d * hd, 3 * d * f
    assert m.matmul_params == layers * (attn + e * one + d * e)
    assert m.active_matmul_params == layers * (attn + k * one + d * e)
    assert m.param_count == 2 * v * d + m.matmul_params
    assert m.linear_flops_per_token == 2.0 * (m.active_matmul_params + d * v)
    # one token touches k experts a layer; 64 rows x 2 nearly all 8
    assert m.experts_touched(1) == pytest.approx(8 * (1 - (7 / 8) ** 2))
    assert m.experts_touched(64) == pytest.approx(8.0, abs=1e-6)
    idle = e - m.experts_touched(1)
    assert m.streamed_param_bytes(1) == pytest.approx(
        (m.param_count - layers * idle * one) * 2)
    step = m.decode_step_cost(3, 64)
    kv = layers * 2 * hd * 2
    assert step.hbm_bytes == pytest.approx(
        m.streamed_param_bytes(3) + 3 * 64 * kv + 3 * kv)
    assert step.flops == pytest.approx(
        3 * (m.linear_flops_per_token + layers * 4.0 * hd * 64))
    # the mixed step streams the experts its 3 + 8 tokens touch, once
    mixed = m.mixed_step_cost(3, 64, 8, 0)
    chunk = m.prefill_chunk_cost(8, 0)
    assert mixed.flops == pytest.approx(step.flops + chunk.flops)
    assert mixed.hbm_bytes == pytest.approx(
        m.streamed_param_bytes(11) + 3 * 64 * kv + 3 * kv + 8 * kv + 8 * kv)
    # a dense model is priced as it always was
    from tpu_air.models.lm import LMConfig

    dense = LMCostModel(LMConfig.tiny())
    assert dense.active_matmul_params == dense.matmul_params
    assert dense.streamed_param_bytes(5) == dense.param_bytes


def _config_file():
    with open(os.path.join(_REPO, "benchmark", "configs",
                           "olmoe-1b-7b.json")) as fh:
        return json.load(fh)


def test_the_configuration_file_maps_onto_lmconfig_key_by_key():
    cfg = _config_file()
    lm = hf_import.lm_config_from_hf(cfg)
    for theirs, ours in hf_import.HF_KEYS.items():
        assert getattr(lm, ours) == cfg[theirs], theirs
    assert lm.head_dim == 128 and lm.qk_norm and not lm.tie_embeddings
    assert (lm.num_experts, lm.num_experts_per_tok, lm.d_ff) == (64, 8, 1024)
    # published widths, only the depth cut
    assert cfg["num_hidden_layers"] == 8
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 16
    assert list(cfg["reduced"]) == ["num_hidden_layers"]
    assert cfg["num_key_value_heads"] == cfg["num_attention_heads"] == 16
    assert set(hf_import.HF_FIXED) <= set(cfg)
    m = LMCostModel(hf_import.lm_config_from_hf(cfg, dtype="bfloat16"))
    assert m.param_bytes == pytest.approx(7.13e9, rel=2e-3)


def test_decode_step_bytes_against_hand_counts():
    from benchmark import costs_moe

    cfg = _config_file()
    need = costs_moe.decode_step_bytes(cfg, 64, 1024)
    assert need["expert_bytes"] == 8 * 64 * 3 * 2048 * 1024 * 2  # 6.44 GB
    assert need["attention_router_bytes"] == 8 * (4 * 2048 * 2048
                                                  + 2048 * 64) * 2
    assert need["head_bytes"] == 2048 * 50304 * 2
    assert need["kv_bytes"] == 64 * 1024 * 8 * 2 * 2048 * 2       # 4.29 GB
    assert need["total_bytes"] == sum(
        v for k, v in need.items() if k != "total_bytes")
    assert need["total_bytes"] == pytest.approx(11.2e9, rel=5e-3)
    assert costs_moe.expert_matrix_bytes(cfg) == 2048 * 1024 * 2
