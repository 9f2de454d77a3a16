"""GigaChat3.1 (``model_type: deepseek_v3``: latent attention, a leading
dense layer, sigmoid group-limited routing over experts of which the tree
holds a share, a shared expert) through ``CausalLM``, the importer and
``InferenceEngine``, against the plain float32 reference on seeded weights in
the published layout, at a small size on the CPU."""

import json
import math
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_air.models.lm import hf_import, reference_deepseek
from tpu_air.models.lm.config import LMConfig
from tpu_air.models.lm.modeling import (CausalLM, grouped_sigmoid_routing,
                                        yarn_inv_freq, yarn_mscale)

import _combine_cases
import _mixed_step_cases

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "model_type": "deepseek_v3", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "n_routed_experts": 16, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 4, "topk_group": 2, "topk_method": "noaux_tc",
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 2.5, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "rope_theta": 10000, "rope_scaling": {
        "rope_type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16},
    "rms_norm_eps": 1e-6, "vocab_size": 384, "attention_bias": False,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "max_position_embeddings": 512, "num_nextn_predict_layers": 1,
}
HELD = (4, 8)      # experts 4..11 of the 16: two whole groups of four


def published_shapes(cfg, experts=None):
    d, f, fm = (cfg["hidden_size"], cfg["intermediate_size"],
                cfg["moe_intermediate_size"])
    h, rq, r = (cfg["num_attention_heads"], cfg["q_lora_rank"],
                cfg["kv_lora_rank"])
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    e, v = cfg["n_routed_experts"], cfg["vocab_size"]
    out = {"model.embed_tokens.weight": (v, d), "lm_head.weight": (v, d),
           "model.norm.weight": (d,)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out.update({
            p + "input_layernorm.weight": (d,),
            p + "post_attention_layernorm.weight": (d,),
            p + "self_attn.q_a_proj.weight": (rq, d),
            p + "self_attn.q_a_layernorm.weight": (rq,),
            p + "self_attn.q_b_proj.weight": (h * (dn + dr), rq),
            p + "self_attn.kv_a_proj_with_mqa.weight": (r + dr, d),
            p + "self_attn.kv_a_layernorm.weight": (r,),
            p + "self_attn.kv_b_proj.weight": (h * (dn + dv), r),
            p + "self_attn.o_proj.weight": (d, h * dv)})
        if i < cfg["first_k_dense_replace"]:
            widths = {"mlp.": f}
        else:
            out[p + "mlp.gate.weight"] = (e, d)
            out[p + "mlp.gate.e_score_correction_bias"] = (e,)
            widths = {f"mlp.experts.{j}.": fm
                      for j in (range(e) if experts is None
                                else range(experts[0], sum(experts)))}
            widths["mlp.shared_experts."] = fm * cfg["n_shared_experts"]
        for pre, w in widths.items():
            out[p + pre + "gate_proj.weight"] = (w, d)
            out[p + pre + "up_proj.weight"] = (w, d)
            out[p + pre + "down_proj.weight"] = (d, w)
    return out


def published(cfg, seed=0, std=0.08):
    rng = np.random.default_rng(seed)
    sd = {}
    for name, shape in published_shapes(cfg).items():
        if name.endswith("norm.weight"):
            sd[name] = (1 + 0.1 * rng.standard_normal(shape)).astype(np.float32)
        elif name.endswith("e_score_correction_bias"):
            sd[name] = (0.05 * rng.standard_normal(shape)).astype(np.float32)
        elif name.endswith("mlp.gate.weight"):
            sd[name] = (0.3 * rng.standard_normal(shape)).astype(np.float32)
        else:
            sd[name] = (std * rng.standard_normal(shape)).astype(np.float32)
    return sd


def _build(sd, held=None, **kw):
    over = {} if held is None else {"experts_first": held[0],
                                    "experts_held": held[1]}
    config = hf_import.lm_config_from_hf(TINY, max_seq_len=256, **over, **kw)
    params = jax.tree_util.tree_map(
        jnp.asarray,
        hf_import.convert_deepseek_v3_state_dict(sd.__getitem__, config))
    return config, CausalLM(config), params


@pytest.fixture(scope="module")
def tiny():
    """One rank's share: experts 4..11 of 16."""
    sd = published(TINY)
    return (sd,) + _build(sd, HELD)


def _ref(sd, ids, rows=None, held=HELD, **how):
    return reference_deepseek.forward(sd.__getitem__, TINY, ids, rows,
                                      held=held, **how)


def _close(got, want, tol):
    scale = want.max(-1) - np.median(want, -1)
    assert (np.abs(got - want).max(-1) / scale).max() < tol


# -- the configuration -------------------------------------------------------

def test_config_maps_the_published_keys():
    cfg = hf_import.lm_config_from_hf(TINY, experts_first=4, experts_held=8)
    assert cfg.layer_kinds() == ["latent"] * 3
    assert cfg.ff_kinds() == ["dense", "sparse", "sparse"]
    assert (cfg.d_ff, cfg.dense_d_ff, cfg.num_experts, cfg.experts_held) == (
        32, 96, 16, 8)
    assert cfg.head_dim == 16 and cfg.latent_width == 24
    assert cfg.latent_row_width == 128      # whole lanes
    assert cfg.router == "sigmoid_groups" and not cfg.holds_all_experts
    assert (cfg.rope_factor, cfg.rope_original_len) == (4, 16)
    assert not cfg.has_recurrent_layers and not cfg.tie_embeddings
    # JSON round trip (a checkpoint's model_config.json)
    assert LMConfig.from_dict(json.loads(cfg.to_json())) == cfg
    for key, bad in (("scoring_func", "softmax"), ("topk_method", "greedy"),
                     ("norm_topk_prob", False), ("moe_layer_freq", 2)):
        with pytest.raises(ValueError, match=key):
            hf_import.lm_config_from_hf({**TINY, key: bad})
    with pytest.raises(ValueError, match="yarn"):
        hf_import.lm_config_from_hf(
            {**TINY, "rope_scaling": {"rope_type": "linear", "factor": 2}})
    with pytest.raises(ValueError, match="routed over"):
        hf_import.lm_config_from_hf(TINY, experts_first=12, experts_held=8)


OLMOE_TREE = {
    "embedding": (384, 64), "final_norm/weight": (64,),
    "layer_0/attn/k/kernel": (64, 64), "layer_0/attn/k_norm/weight": (64,),
    "layer_0/attn/o/kernel": (64, 64), "layer_0/attn/q/kernel": (64, 64),
    "layer_0/attn/q_norm/weight": (64,), "layer_0/attn/v/kernel": (64, 64),
    "layer_0/attn_norm/weight": (64,), "layer_0/mlp_norm/weight": (64,),
    "layer_0/moe/down": (8, 32, 64), "layer_0/moe/gate": (8, 64, 32),
    "layer_0/moe/router": (64, 8), "layer_0/moe/up": (8, 64, 32),
    "lm_head/kernel": (64, 384)}
JAMBA_TREE = {
    "embedding": (384, 64), "final_norm/weight": (64,),
    "layer_0/mamba/A_log": (128, 8), "layer_0/mamba/D": (128,),
    "layer_0/mamba/b_norm/weight": (8,), "layer_0/mamba/c_norm/weight": (8,),
    "layer_0/mamba/conv/bias": (128,), "layer_0/mamba/conv/kernel": (4, 128),
    "layer_0/mamba/dt_norm/weight": (6,),
    "layer_0/mamba/dt_proj/bias": (128,),
    "layer_0/mamba/dt_proj/kernel": (6, 128),
    "layer_0/mamba/in_proj/kernel": (64, 256),
    "layer_0/mamba/out_proj/kernel": (128, 64),
    "layer_0/mamba/x_proj/kernel": (128, 22),
    "layer_0/mamba_norm/weight": (64,),
    "layer_0/mlp/down/kernel": (96, 64), "layer_0/mlp/gate/kernel": (64, 96),
    "layer_0/mlp/up/kernel": (64, 96), "layer_0/mlp_norm/weight": (64,),
    "layer_2/attn/k/kernel": (64, 16), "layer_2/attn/o/kernel": (64, 64),
    "layer_2/attn/q/kernel": (64, 64), "layer_2/attn/v/kernel": (64, 16),
    "layer_2/attn_norm/weight": (64,), "layer_2/mlp/down/kernel": (96, 64),
    "layer_2/mlp/gate/kernel": (64, 96), "layer_2/mlp/up/kernel": (64, 96),
    "layer_2/mlp_norm/weight": (64,)}


@pytest.mark.parametrize("family", ["olmoe", "jamba"])
def test_the_other_families_keep_their_parameter_trees(family):
    """What the benchmark's two other LM configurations build is what they
    built before the feed-forward went by layer: the paths and shapes of
    their tiny configurations, as the parent commit made them (layers 0 and,
    for the hybrid, 2 stand for the rest)."""
    from benchmark.kinds import lmserve, ssmserve

    tiny_cfg, want, layers = {"olmoe": (lmserve.TINY, OLMOE_TREE, 2),
                              "jamba": (ssmserve.TINY, JAMBA_TREE, 4)}[family]
    cfg = hf_import.lm_config_from_hf(tiny_cfg, max_seq_len=64)
    assert cfg.holds_all_experts and cfg.router == "softmax"
    assert cfg.ff_kinds() == [
        "sparse" if family == "olmoe" else "dense"] * layers
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    got = {"/".join(p.key for p in path): tuple(v.shape) for path, v in
           jax.tree_util.tree_flatten_with_path(shapes)[0]}
    assert {k: v for k, v in got.items()
            if not k.startswith(("layer_1", "layer_3"))} == want
    assert len(got) == {"olmoe": 27, "jamba": 62}[family]


def test_importer_round_trip(tiny):
    """Every published tensor of the share lands in the tree exactly once,
    and the tree is the one ``CausalLM.init`` makes; a range of experts and
    of vocabulary rows is what the tree holds."""
    sd, config, model, params = tiny
    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    want = jax.tree_util.tree_map(lambda a: a.shape, init["params"])
    assert want == jax.tree_util.tree_map(lambda a: a.shape, params)
    assert (sum(int(np.prod(s)) for s in
                published_shapes(TINY, HELD).values())
            == sum(a.size for a in jax.tree_util.tree_leaves(params)))
    p = "model.layers.1."
    np.testing.assert_array_equal(
        params["layer_1"]["moe"]["gate"][2],
        sd[p + "mlp.experts.6.gate_proj.weight"].T)
    np.testing.assert_array_equal(params["layer_1"]["moe"]["router"],
                                  sd[p + "mlp.gate.weight"].T)
    np.testing.assert_array_equal(
        params["layer_1"]["moe"]["router_bias"],
        sd[p + "mlp.gate.e_score_correction_bias"])
    np.testing.assert_array_equal(
        params["layer_2"]["shared"]["down"]["kernel"],
        sd["model.layers.2.mlp.shared_experts.down_proj.weight"].T)
    np.testing.assert_array_equal(params["layer_0"]["mlp"]["up"]["kernel"],
                                  sd["model.layers.0.mlp.up_proj.weight"].T)
    kv_b = sd[p + "self_attn.kv_b_proj.weight"].reshape(4, 20, 16)
    np.testing.assert_array_equal(params["layer_1"]["attn"]["k_up"][:, 3],
                                  kv_b[3, :8].T)
    np.testing.assert_array_equal(params["layer_1"]["attn"]["v_up"][:, 1],
                                  kv_b[1, 8:].T)
    # the multi-token module and the experts held elsewhere: never asked for
    asked = []
    hf_import.convert_deepseek_v3_state_dict(
        lambda n: asked.append(n) or sd[n], config)
    assert not [n for n in asked if n.startswith("model.layers.3.")]
    assert {int(n.split(".")[5]) for n in asked if ".experts." in n} == set(
        range(4, 12))
    # a slice of the vocabulary: rows 128..255 of embedding and head
    cut = hf_import.lm_config_from_hf(TINY, vocab_size=128)
    part = hf_import.convert_deepseek_v3_state_dict(
        sd.__getitem__, cut, vocab_rows=range(128, 256))
    np.testing.assert_array_equal(part["embedding"],
                                  sd["model.embed_tokens.weight"][128:256])
    np.testing.assert_array_equal(part["lm_head"]["kernel"],
                                  sd["lm_head.weight"][128:256].T)
    with pytest.raises(ValueError, match="vocabulary rows"):
        hf_import.convert_deepseek_v3_state_dict(sd.__getitem__, cut)


# -- yarn and the softmax scale ----------------------------------------------

def test_yarn_frequencies_and_scale_by_hand():
    """The published configuration's numbers: 32 pairs at theta 1e5 over an
    original length of 4096, factor 64; correction dimensions 10 and 23."""
    dim, theta, orig = 64, 100000.0, 4096
    find = lambda rot: dim * math.log(orig / (rot * 2 * math.pi)) / (  # noqa: E731
        2 * math.log(theta))
    assert (math.floor(find(32)), math.ceil(find(1))) == (8, 19)
    got = np.asarray(yarn_inv_freq(dim, theta, 64.0, orig, 32.0, 1.0))
    plain = theta ** (-np.arange(32) / 32.0)
    np.testing.assert_allclose(got[:9], plain[:9], rtol=1e-6)
    np.testing.assert_allclose(got[19:], plain[19:] / 64, rtol=1e-6)
    j = 12      # inside the ramp: (12 - 8) / 11 of the way to the stretched
    keep = 1 - (j - 8) / 11
    np.testing.assert_allclose(
        got[j], plain[j] * keep + plain[j] / 64 * (1 - keep), rtol=1e-6)
    np.testing.assert_allclose(
        got, reference_deepseek.yarn_inv_freq(
            {"qk_rope_head_dim": 64, "rope_theta": 100000, "rope_scaling": {
                "factor": 64, "original_max_position_embeddings": 4096,
                "beta_fast": 32, "beta_slow": 1}}), rtol=1e-6)
    # sigma = 192^-1/2 (0.1 ln 64 + 1)^2
    assert yarn_mscale(64, 1) == pytest.approx(1.41589, rel=1e-5)
    assert 192 ** -0.5 * yarn_mscale(64, 1) ** 2 == pytest.approx(
        0.0722 * 2.0048, rel=1e-3)
    assert yarn_mscale(1, 1) == 1.0 and yarn_mscale(64, 0) == 1.0
    assert reference_deepseek.softmax_scale(
        {"qk_nope_head_dim": 128, "qk_rope_head_dim": 64, "rope_scaling": {
            "factor": 64, "mscale_all_dim": 1}}) == pytest.approx(
        192 ** -0.5 * yarn_mscale(64, 1) ** 2)


# -- routing ------------------------------------------------------------------

def _brute_force_routing(logits, bias, k, groups, keep, scale):
    t, e = logits.shape
    s = 1 / (1 + np.exp(-logits.astype(np.float64)))
    pick = s + bias
    w = np.zeros((t, e))
    chosen = []
    for i in range(t):
        by_group = pick[i].reshape(groups, -1)
        rank = np.sort(by_group, -1)[:, -2:].sum(-1)
        stay = sorted(range(groups), key=lambda g: (-rank[g], g))[:keep]
        among = [j for j in range(e) if j // (e // groups) in stay]
        top = sorted(among, key=lambda j: (-pick[i, j], j))[:k]
        chosen.append(top)
        w[i, top] = scale * s[i, top] / (s[i, top].sum() + 1e-20)
    return w, chosen


@pytest.mark.parametrize("ties", [False, True])
def test_group_limited_selection_against_brute_force(ties):
    rng = np.random.default_rng(4)
    t, e, k, groups, keep = 64, 16, 4, 4, 2
    logits = rng.standard_normal((t, e)).astype(np.float32)
    bias = (0.3 * rng.standard_normal(e)).astype(np.float32)
    if ties:
        # scores that repeat inside and across groups: the lower index wins
        logits = np.round(logits * 2) / 2
        bias = np.zeros(e, np.float32)
    w, chosen = grouped_sigmoid_routing(
        jnp.asarray(logits), jnp.asarray(bias), k, groups, keep, 2.5)
    want_w, want_chosen = _brute_force_routing(logits, bias, k, groups, keep,
                                               2.5)
    assert [sorted(r) for r in np.asarray(chosen).tolist()] == [
        sorted(r) for r in want_chosen]
    dense = np.zeros((t, e))
    np.put_along_axis(dense, np.asarray(chosen), np.asarray(w), -1)
    np.testing.assert_allclose(dense, want_w, rtol=1e-5, atol=1e-7)
    np.testing.assert_allclose(np.asarray(w).sum(-1), 2.5, rtol=1e-5)
    # the reference's router is the same choice
    cfg = {"n_group": groups, "topk_group": keep, "num_experts_per_tok": k,
           "routed_scaling_factor": 2.5}
    ref_w, gap = reference_deepseek.route(cfg, jnp.asarray(logits),
                                          jnp.asarray(bias))
    np.testing.assert_allclose(np.asarray(ref_w), want_w, rtol=1e-5,
                               atol=1e-7)
    assert (np.asarray(gap) >= 0).all()
    assert bool((np.asarray(gap) == 0).any()) == ties
    # as a rank that holds experts 4..7 sees it: the same weights; among
    # experts the gap is how far its lowest chosen one lies over the best
    # not chosen and its best not chosen under the k-th; the group boundary
    # counts always
    held_w, held_gap = reference_deepseek.route(
        cfg, jnp.asarray(logits), jnp.asarray(bias), held=(4, 4))
    np.testing.assert_array_equal(np.asarray(held_w), np.asarray(ref_w))
    pick = (1 / (1 + np.exp(-logits.astype(np.float64))) + bias).astype(
        np.float32)
    size = e // groups
    for i, top in enumerate(want_chosen):
        by_group = sorted((np.sort(pick[i, g * size:(g + 1) * size])[-2:].sum()
                           for g in range(groups)), reverse=True)
        kept = {j // size for j in top}
        rest = sorted((j for j in range(e) if j // size in kept
                       and j not in top), key=lambda j: (-pick[i, j], j))
        last = sorted(top, key=lambda j: (-pick[i, j], j))[-1]
        want = by_group[keep - 1] - by_group[keep]
        np.testing.assert_allclose(
            gap[i], min(want, pick[i, last] - pick[i, rest[0]]), atol=1e-6)
        own_in = [pick[i, j] for j in top if 4 <= j < 8]
        own_out = [pick[i, j] for j in rest if 4 <= j < 8]
        if own_in:
            want = min(want, min(own_in) - pick[i, rest[0]])
        if own_out:
            want = min(want, pick[i, last] - max(own_out))
        np.testing.assert_allclose(held_gap[i], want, atol=1e-6)
        assert held_gap[i] >= gap[i] - 1e-7


def test_the_bias_selects_and_does_not_weigh():
    logits = jnp.zeros((1, 8))                      # every s = 0.5
    bias = jnp.asarray([0, 0, 0, 0, 0.2, 0.1, 0, 0.05], jnp.float32)
    w, chosen = grouped_sigmoid_routing(logits, bias, 2, 2, 1, 1.0)
    assert sorted(np.asarray(chosen)[0].tolist()) == [4, 5]
    np.testing.assert_allclose(np.asarray(w), 0.5, rtol=1e-6)


def test_the_shares_add_up():
    """Four ranks of four of the sixteen experts: the routed parts of all
    ranks plus the shared expert once are the uncut layer (the reference's
    and the program's), and every token's eight assignments are counted
    once, at its own rank or elsewhere."""
    sd = published(TINY, seed=2)
    ids = np.random.default_rng(1).integers(2, 384, 40).tolist()
    whole = _ref(sd, ids, held=None, layer_outputs=True)
    x = jnp.asarray([ids], jnp.int32)
    _, model, params = _build(sd)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply({"params": params}, x)[0])
    _close(want, whole["logits"], 1e-4)

    # one layer alone: the same input to every rank's layer 1
    from tpu_air.models.lm.modeling import SparseExperts, SwiGLU

    h = jnp.asarray(np.random.default_rng(3).standard_normal((40, 64)),
                    jnp.float32)
    full_cfg, _, full = _build(sd)
    with jax.default_matmul_precision("highest"):
        uncut = SparseExperts(full_cfg).apply(
            {"params": full["layer_1"]["moe"]}, h)
        shared = SwiGLU(full_cfg, 32).apply(
            {"params": full["layer_1"]["shared"]}, h)
        parts, held_rows, elsewhere = [], 0, 0
        for rank in range(4):
            cfg, _, p = _build(sd, (4 * rank, 4))
            y, inter = SparseExperts(cfg).apply(
                {"params": p["layer_1"]["moe"]}, h,
                mutable=["intermediates"])
            rows = np.asarray(inter["intermediates"]["expert_rows"][0])
            assert rows.shape == (40, 5)
            held_rows += rows[:, :4].sum()
            elsewhere += rows[:, 4].sum()
            assert ((rows[:, :4].sum(-1) + rows[:, 4]) == 4).all()
            parts.append(np.asarray(y))
    np.testing.assert_allclose(sum(parts), np.asarray(uncut), rtol=1e-4,
                               atol=1e-6)
    assert held_rows == 40 * 4 and elsewhere == 3 * 40 * 4
    # and against the reference's routed and shared outputs of layer 1, fed
    # the reference's own input there: the sum over ranks of the references
    ranks = [_ref(sd, ids, held=(4 * r, 4), layer_outputs=True)
             for r in range(4)]
    np.testing.assert_allclose(
        sum(r["routed"][0] for r in ranks), whole["routed"][0], rtol=1e-4,
        atol=1e-6)
    for r in ranks:
        np.testing.assert_allclose(r["shared"][0], whole["shared"][0],
                                   rtol=1e-5, atol=1e-7)
    assert np.abs(np.asarray(shared)).max() > 0


def test_absent_assignments_cost_no_product():
    """``ops.moe.expert_ffn`` with ids held elsewhere: they sort behind the
    held groups, belong to none, and their rows come out as zeros."""
    from tpu_air.ops.moe import expert_ffn

    rng = np.random.default_rng(0)
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    x, gate, up, down = f(6, 8), f(3, 8, 4), f(3, 8, 4), f(3, 4, 8)
    chosen = jnp.asarray([[0, 3], [3, 3], [2, 1], [3, 0], [1, 3], [3, 2]])
    w = jnp.abs(f(6, 2))
    got = np.asarray(expert_ffn(x, chosen, w, gate, up, down))
    want = np.zeros((6, 8), np.float32)
    for t in range(6):
        for j in range(2):
            e = int(chosen[t, j])
            if e < 3:
                hid = jax.nn.silu(x[t] @ gate[e]) * (x[t] @ up[e])
                want[t] += np.asarray(w[t, j] * (hid @ down[e]))
    np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-6)
    assert np.all(got[1] == 0)


@pytest.mark.parametrize("routing", _combine_cases.PARTIAL)
def test_gated_expert_ffn_and_its_gradient_with_assignments_elsewhere(
        routing):
    """A share of the experts held (tests/_combine_cases.py): some of a
    token's choices elsewhere, all of every token's, and tokens that send
    none to all of theirs to this rank."""
    _combine_cases.against_the_loop(routing, gated=True)


@pytest.mark.parametrize("form", sorted(_combine_cases.FORMS))
@pytest.mark.parametrize("routing", _combine_cases.PARTIAL)
def test_rows_of_no_group_are_masked_never_multiplied(routing, form):
    """The sum over a token's choices, both forms, over products whose
    unvisited rows are NaN."""
    _combine_cases.rows_of_no_group(routing, form)


@pytest.mark.parametrize("routing", _combine_cases.PARTIAL)
def test_the_sums_kernel_is_differentiated_as_the_gathered_form(routing):
    _combine_cases.kernel_gradient(routing)


def test_the_rule_that_picks_the_sums_kernel(monkeypatch):
    """``expert_ffn`` sums through ``held_rows_sum`` on a TPU where the
    sorted rows and the width are whole tiles and the tokens fit one tile of
    the result; every CPU run gathers.  The cells' shapes (a decode step, a
    chunk, a mixed step of the three sparse configurations) all have a tile."""
    from tpu_air.ops import moe

    for t, k, d in [(128, 8, 7168), (256, 8, 7168), (384, 8, 7168),
                    (128, 22, 1024), (256, 22, 1024), (384, 22, 1024)]:
        assert moe.combine_tile(t, t * k, d) == 1024
    for t in (64, 128, 192):
        assert moe.combine_tile(t, t * 8, 2048) == 2048
    assert moe.combine_tile(1024, 8192, 2048) == 512
    assert moe.combine_tile(2048, 16384, 2048) is None      # too many tokens
    assert moe.combine_tile(19, 57, 16) is None             # no whole tiles
    assert moe.combine_tile(32, 128, 192) is None

    taken = []
    monkeypatch.setattr(moe, "held_rows_sum",
                        lambda *a: taken.append(a) or moe.gathered_sum(*a))
    f = lambda *s: jnp.ones(s, jnp.float32)  # noqa: E731
    args = (f(32, 128), jnp.zeros((32, 4), jnp.int32), f(32, 4),
            f(2, 128, 8), f(2, 128, 8), f(2, 8, 128))
    moe.expert_ffn(*args)
    assert not taken                                        # a CPU
    monkeypatch.setattr(moe, "_grouped", lambda lhs, rhs, sizes:
                        jax.lax.ragged_dot(lhs, rhs, sizes))
    monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")
    moe.expert_ffn(*args)
    assert len(taken) == 1


def test_the_kernel_is_taken_for_this_models_shapes():
    """A fallback cannot pass silently: at the published widths (7168 in,
    2048 a routed expert) every grouped product of the decode step (128 rows
    x 8), the chunk (256 x 8) and the mixed step has a tiling, and OLMoE's
    shapes keep the one they were measured with."""
    from tpu_air.ops import moe

    for rows in (128 * 8, 256 * 8, (128 + 256) * 8):
        assert moe.gmm_tiling(rows, 7168, 2048) == (128, 1024, 2048)
        assert moe.gmm_tiling(rows, 2048, 7168) == (128, 2048, 1024)
    assert moe.gmm_tiling(512, 2048, 1024) == (128, 2048, 1024)
    assert moe.gmm_tiling(512, 1024, 2048) == (128, 1024, 1024)
    assert moe.gmm_tiling(100, 7168, 2048) is None


# -- the model against the reference ------------------------------------------

def test_full_forward_matches_the_reference(tiny):
    sd, config, model, params = tiny
    ids = np.random.default_rng(3).integers(2, 384, 90).tolist()
    want = _ref(sd, ids)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params},
                                     jnp.asarray([ids], jnp.int32))[0])
    _close(got, want["logits"], 1e-4)
    # the check's two sensitivity readings move the reference
    scale = want["logits"].max(-1) - np.median(want["logits"], -1)
    plain = _ref(sd, ids, yarn_softmax_scale=False)["logits"]
    assert (np.abs(plain - want["logits"]).max(-1) / scale).max() > 1e-3
    # what the 8 absent experts would add is left out: the whole model differs
    whole = _ref(sd, ids, held=None)["logits"]
    assert (np.abs(whole - want["logits"]).max(-1) / scale).max() > 1e-2


def test_the_reference_of_several_is_the_reference_of_each(tiny):
    """``forward_each`` fetches a tensor once for all its sequences and
    computes each alone: lengths, rows and the two sensitivity switches are a
    sequence's own."""
    sd = tiny[0]
    rng = np.random.default_rng(5)
    jobs = [{"ids": rng.integers(2, 384, 40).tolist()},
            {"ids": rng.integers(2, 384, 23).tolist(), "rows": [3, 22]},
            {"ids": rng.integers(2, 384, 23).tolist(),
             "yarn_softmax_scale": False, "rounded_precision": "default",
             "round_inputs": lambda a: a.astype(jnp.bfloat16).astype(
                 jnp.float32)}]
    asked = []

    def get(name):
        asked.append(name)
        return sd[name]

    got = reference_deepseek.forward_each(get, TINY, jobs, held=HELD)
    dense = [n for n in asked if ".mlp." in n and "experts" not in n
             and ".gate." not in n]
    assert all(asked.count(n) == len(jobs) for n in dense) and dense
    assert all(asked.count(n) == 1 for n in set(asked) - set(dense)
               if n != "model.embed_tokens.weight")
    for job, one in zip(jobs, got):
        want = reference_deepseek.forward(sd.__getitem__, TINY, held=HELD,
                                          **job)
        np.testing.assert_array_equal(one["logits"], want["logits"])
        np.testing.assert_array_equal(one["router_gap"], want["router_gap"])
    assert got[1]["logits"].shape == (2, 384)


def test_absorbed_is_expanded(tiny):
    """The decode step's absorbed read over the plain cache gives the logits
    of the expanded form over the whole sequence."""
    from tpu_air.models.lm.generate import init_cache

    sd, config, model, params = tiny
    ids = np.random.default_rng(13).integers(2, 384, (2, 21))
    with jax.default_matmul_precision("highest"):
        want = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
        cache = init_cache(model, 2)
        assert cache["layer_0"]["attn"]["cached_latent"].shape == (2, 256, 128)
        _, v = model.apply({"params": params, "cache": cache},
                           jnp.asarray(ids[:, :13]), decode=True,
                           mutable=["cache"])
        for t in range(13, 21):
            got, v = model.apply(
                {"params": params, "cache": v["cache"]},
                jnp.asarray(ids[:, t:t + 1]),
                jnp.full((2, 1), t, jnp.int32), decode=True,
                mutable=["cache"])
            _close(np.asarray(got[:, 0]), want[:, t], 1e-4)


# -- the engine ----------------------------------------------------------------

def _engine(tiny, **kw):
    from tpu_air.engine import EngineConfig, InferenceEngine

    _, config, model, params = tiny
    cfg = dict(num_slots=4, slot_len=256, page_len=16, max_new_tokens=8,
               eos_token_id=None)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg),
                           auto_start=False)


@pytest.fixture(params=["gathered", "in_place"])
def read(request, monkeypatch):
    """How the step's rows read the latent pool.  ``gathered``: what the rule
    picks off a TPU.  ``in_place``: the rule's answer on a TPU at a geometry
    of whole tiles (the engine's here: float32 pages of 16 or 8 x 128), so
    the kernel runs, in interpret mode, inside the engine's own decode and
    mixed programs."""
    from tpu_air.ops import decode_attention as da

    traced = {"paged_latent_decode_attention": [],
              "paged_latent_chunk_attention": []}

    def counted(name):
        def kernel(*args, _fn=getattr(da, name), **kw):
            traced[name].append(args[0].shape)
            return _fn(*args, **kw)
        return kernel

    for name in traced:
        monkeypatch.setattr(da, name, counted(name))
    if request.param == "in_place":
        monkeypatch.setattr(da, "latent_pages_read_in_place",
                            da.pages_are_whole_tiles)
    yield request.param
    # a fallback cannot pass silently, nor a kernel slip into a CPU run:
    # the rows' read and the chunk's walk (PR 45) go by the one rule
    for name, shapes in traced.items():
        assert bool(shapes) == (request.param == "in_place"), name


_WALK_CASES = {
    # name: (page the chunk sits on, the slot's table row); pool pages 1..8
    # hold latent rows, page 0 is the null page
    "first_page": (0, [3, 5, 1, 7]),
    "middle_page": (1, [3, 5, 1, 7]),
    "last_page": (3, [3, 5, 1, 7]),
    # page 2 is another slot's first page too (a shared prefix), and its
    # second is the one this chunk writes
    "prefix_shared_first_page": (1, [2, 6, 4, 8]),
    # what the prompt has not reached is the null page
    "unreached_are_the_null_page": (2, [3, 5, 1, 0]),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("case", sorted(_WALK_CASES))
def test_a_chunks_walk_is_expanded_over_the_gathered_slot(tiny, monkeypatch,
                                                          case, dtype):
    """One latent layer's chunk call over a pool other chunks have filled:
    the pages its prompt has reached walked in place (the kernel, interpret
    mode) against the dense form over the slot's pages gathered at
    ``slot_len``; the layer's output to one step of the dtype, the pool
    written alike.  The null page holds large numbers: the gathered form
    masks them, the walk never reads them."""
    import dataclasses

    from tpu_air.models.lm.modeling import LatentAttention
    from tpu_air.ops import decode_attention as da

    at, row = _WALK_CASES[case]
    C, npg = 16, 4
    config = dataclasses.replace(tiny[1], dtype=dtype)
    layer = LatentAttention(config)
    params = jax.tree_util.tree_map(
        lambda a: a * 4,            # scores that spread: a softmax with a peak
        tiny[3]["layer_1"]["attn"])
    rng = np.random.default_rng(3)
    w = config.latent_row_width
    pool = rng.standard_normal((9, C, w)).astype(np.float32)
    pool[..., config.latent_width:] = 0
    pool[0] = 1e4
    cache = {
        "cached_latent": jnp.asarray(pool, dtype),
        "cache_index": jnp.full((2,), at * C, jnp.int32),
        "block_table": jnp.broadcast_to(jnp.asarray(row, jnp.int32), (2, npg)),
    }
    x = jnp.asarray(rng.standard_normal((1, C, config.d_model)), dtype)
    positions = (at * C + jnp.arange(C, dtype=jnp.int32))[None]
    walked = []

    def run(rule):
        monkeypatch.setattr(da, "latent_pages_read_in_place", rule)
        with jax.default_matmul_precision("highest"):
            y, v = layer.apply({"params": params, "cache": cache}, x,
                               positions, decode=True, mutable=["cache"])
        return np.asarray(y, np.float32), v["cache"]

    monkeypatch.setattr(
        da, "paged_latent_chunk_attention",
        lambda *a, _fn=da.paged_latent_chunk_attention, **k:
            walked.append(a[0].shape) or _fn(*a, **k))
    want, c_want = run(lambda pool: False)
    assert not walked
    got, c_got = run(da.pages_are_whole_tiles)
    assert walked == [(4, C, 8)]
    assert np.isfinite(got).all()
    step = 2.0 ** -7 if dtype == "bfloat16" else 1e-5
    assert np.abs(got - want).max() <= step * np.abs(want).max()
    assert np.abs(want).max() > 0.05
    for k in cache:
        np.testing.assert_array_equal(np.asarray(c_got[k], np.float32),
                                      np.asarray(c_want[k], np.float32))


def _reference_rows(sd, prompt, answer):
    ids = list(prompt) + list(answer[:-1])
    return _ref(sd, ids, range(len(prompt) - 1, len(ids)))["logits"]


def test_chunked_prefill_then_paged_decode_matches_the_reference(tiny, read):
    """Logits, not tokens: prompts that cross a chunk boundary and end in a
    padded chunk, one that fills its last chunk and one shorter than a chunk,
    through the engine's chunk (expanded) and decode (absorbed) programs over
    the engine's own latent pool; fewer slots than prompts, so one is reused
    and a row mid-prefill rides the steps of the rows before it."""
    from benchmark.worker_hooks_mla import replayed_logits

    sd = tiny[0]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 384, k).tolist() for k in (37, 32, 9, 50)]
    eng = _engine(tiny)
    layer = eng.cache["layer_1"]["attn"]
    assert set(layer) == {"cached_latent", "cache_index", "block_table"}
    assert layer["cached_latent"].shape == (4 * 16 + 1, 16, 128)
    answers = eng.generate(prompts, 6)
    assert all(len(a) == 6 for a in answers)
    with jax.default_matmul_precision("highest"):
        system = replayed_logits(eng, prompts, answers, [2, 0, 3])
    assert eng.generate(prompts, 6) == answers
    snap = eng.metrics.snapshot()
    eng.close()
    for p, a, got in zip(prompts, answers, system):
        _close(got, _reference_rows(sd, p, a), 1e-3)
        assert got.argmax(-1).tolist() == a
    # the counters: every decoded token's 4 assignments in 2 sparse layers,
    # at its 8 held experts or elsewhere; the latent positions read
    decoded = 2 * 4 * 5
    assert (snap["moe_assignments"] + snap["moe_assignments_elsewhere"]
            == decoded * 4 * 2)
    assert len(snap["moe_expert_load"]) == 8
    assert snap["moe_assignments_elsewhere"] > 0 < snap["moe_assignments"]
    assert snap["latent_positions_pool"] == 4 * 256
    want_live = 2 * sum(len(p) + j + 1 for p in prompts for j in range(5))
    assert snap["latent_positions_live"] == want_live
    # and the pages they span (page 16): a token computed at position q has
    # pages 0 .. q // 16 live
    assert snap["latent_pages_read"] == 2 * sum(
        (len(p) + j) // 16 + 1 for p in prompts for j in range(5))
    assert snap["latent_pages_read"] == 2 * (
        5 * 3 + 5 * 3 + 5 * 1 + 5 * 4)


def test_engine_streams_the_tokens_of_offline_generate(tiny, read):
    from tpu_air.models.lm.generate import generate

    _, config, model, params = tiny
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, 384, k).tolist() for k in (21, 40, 5)]
    eng = _engine(tiny)
    got = eng.generate(prompts, 8)
    eng.close()
    for p, g in zip(prompts, got):
        want = generate(model, params, np.asarray([p]), max_new_tokens=8)
        assert np.asarray(want)[0].tolist() == g


def test_a_row_mid_prefill_rides_the_mixed_step(tiny, read):
    """A long prompt's chunks go out one an iteration inside the decode step
    of the rows already streaming: the chunk's half (expanded, over the
    slot's pages) and the step's half (absorbed) in one program."""
    rng = np.random.default_rng(8)
    short = [rng.integers(2, 384, 6).tolist() for _ in range(2)]
    long_ = rng.integers(2, 384, 90).tolist()       # six chunks of 16
    eng = _engine(tiny, max_new_tokens=24, prefill_chunks_per_step=1)
    streams = [eng.submit(p, 24) for p in short]
    for _ in range(4):
        eng.step()
    late = eng.submit(long_, 6)
    while not eng.idle():
        eng.step()
    got = late.result(5)
    snap = eng.metrics.snapshot()
    eng.close()
    assert snap["chunks_fused"] >= 6 and snap["mixed_steps"] >= 6
    # routing is counted apart for the steps that carried no chunk
    assert 0 < snap["moe_steps_alone"] <= snap["moe_steps"] - 6
    assert (snap["moe_experts_streamed_alone"]
            < snap["moe_experts_streamed"])
    alone = _engine(tiny)
    assert got == alone.generate([long_], 6)[0]
    alone.close()
    assert all(len(s.result(5)) == 24 for s in streams)


@pytest.mark.parametrize("case", sorted(_mixed_step_cases.CASES))
def test_mixed_step(tiny, case, read):
    from tpu_air.models.lm.generate import generate

    _, config, model, params = tiny

    def check(prompt, tokens):
        want = generate(model, params, np.asarray([prompt]),
                        max_new_tokens=len(tokens))
        assert np.asarray(want)[0].tolist() == tokens

    _mixed_step_cases.CASES[case](model, params, check)


def test_prefix_sharing_and_copy_on_write_work_on_latent_pages(tiny, read):
    """A latent page is addressed by position like a K/V page: a second
    request with the same first pages hits the prefix cache, and its answer
    is the one a cold engine gives."""
    rng = np.random.default_rng(10)
    base = rng.integers(2, 384, 48).tolist()        # three whole pages
    a, b = base + [7, 8, 9], base + [11, 12]
    eng = _engine(tiny)
    assert eng.pool.prefix is not None
    first = eng.generate([a], 6)[0]
    second = eng.generate([b], 6)[0]
    again = eng.generate([a], 6)[0]
    hits = eng.metrics.snapshot()["kvpool"]
    eng.close()
    cold = _engine(tiny, prefix_cache=False)
    assert [first, second] == [cold.generate([p], 6)[0] for p in (a, b)]
    cold.close()
    assert again == first
    assert hits["prefix_hits"] >= 2 and hits["prefix_tokens_reused"] >= 96
    # a prompt that ends inside a page another prompt published: the tail
    # page is shared, and the first decode append copies it first
    eng = _engine(tiny)
    full = base + rng.integers(2, 384, 16).tolist()     # four whole pages
    tail = full[:56]                                    # ends inside the 4th
    eng.generate([full], 6)
    got = eng.generate([tail], 6)[0]
    stats = eng.pool.stats()
    eng.close()
    assert stats["cow_copies"] == 1 and stats["prefix_partial_hits"] == 1
    cold = _engine(tiny, prefix_cache=False)
    assert got == cold.generate([tail], 6)[0]
    cold.close()


def test_latent_pages_migrate(tiny, read):
    """``migrate_out`` / ``submit_migrated`` ship a latent layer's ONE pool
    under ``c``; the stream goes on at the destination token for token."""
    rng = np.random.default_rng(12)
    prompt = rng.integers(2, 384, 29).tolist()
    whole = _engine(tiny, max_new_tokens=12)
    want = whole.generate([prompt], 12)[0]
    whole.close()
    src = _engine(tiny, max_new_tokens=12)
    stream = src.submit(prompt, 12)
    while len(stream.tokens_so_far()) < 5:
        src.step()
    payloads = src.migrate_out()
    src.close()
    assert len(payloads) == 1
    pages = payloads[0]["pages"]
    assert set(pages) == {f"layer_{i}/attn" for i in range(3)}
    assert all(set(v) == {"c"} and v["c"].shape[1:] == (16, 128)
               for v in pages.values())
    dst = _engine(tiny, max_new_tokens=12)
    resumed = dst.submit_migrated(payloads[0])
    while not dst.idle():
        dst.step()
    got = resumed.result(5)
    migrated = dst.metrics.snapshot()["migrations"]
    dst.close()
    assert list(got) == want and len(payloads[0]["streamed"]) >= 5
    assert migrated["in"] == 1


def test_mesh_engine_refuses_held_experts_by_name(tiny):
    from tpu_air.engine import EngineConfig, ExpertExchangeUnsupported
    from tpu_air.engine.dist import MeshEngine

    _, config, model, params = tiny
    with pytest.raises(ExpertExchangeUnsupported, match="exchange"):
        MeshEngine(model, params,
                   EngineConfig(num_slots=4, slot_len=64, page_len=16,
                                max_new_tokens=4),
                   dp=2, tp=1, auto_start=False)


def test_the_mesh_engines_step_is_traced_for_its_mesh(monkeypatch):
    """What picks the paged kernel sees a program the partitioner will split
    (``engine/dist/sharded.py`` traces the decode step under
    ``kernel_mesh``) and keeps the gather there; the single-chip engine's
    programs are traced under none.  A latent-attention model with dense
    feed-forwards (``MeshEngine`` takes no routed experts) streams the single
    engine's tokens."""
    import dataclasses

    from tpu_air.engine import EngineConfig, InferenceEngine
    from tpu_air.engine.dist import MeshEngine
    from tpu_air.ops import decode_attention as da
    from tpu_air.ops.flash_attention import traced_for_mesh

    config = dataclasses.replace(
        hf_import.lm_config_from_hf(TINY, max_seq_len=256), num_experts=0,
        experts_held=0, num_experts_per_tok=0, num_shared_experts=0,
        router="softmax")
    assert config.layer_kinds() == ["latent"] * 3
    model = CausalLM(config)
    params = model.init(jax.random.PRNGKey(0),
                        jnp.zeros((1, 8), jnp.int32))["params"]
    prompts = [np.random.default_rng(14).integers(2, 384, k).tolist()
               for k in (19, 7)]
    cfg = EngineConfig(num_slots=4, slot_len=64, page_len=16,
                       max_new_tokens=4, eos_token_id=None)
    rule, asked = da.latent_pages_read_in_place, []
    monkeypatch.setattr(
        da, "latent_pages_read_in_place",
        lambda pool: asked.append(traced_for_mesh()) or rule(pool))
    mesh = MeshEngine(model, params, cfg, dp=2, tp=1, auto_start=False)
    on_mesh = mesh.generate(prompts, 4)
    mesh.close()
    assert asked and all(asked)
    del asked[:]
    single = InferenceEngine(model, params, cfg, auto_start=False)
    assert single.generate(prompts, 4) == on_mesh
    single.close()
    assert asked and not any(asked)


# -- costs and the configuration file -----------------------------------------

def test_cost_model_prices_latent_attention_and_held_experts():
    from tpu_air.observability.perf import LMCostModel

    with open(os.path.join(REPO, "benchmark", "configs",
                           "gigachat3.1-702b-a36b.json")) as f:
        hf = json.load(f)
    from benchmark import weights_mla

    m = LMCostModel(weights_mla.lm_config(hf, "bfloat16", 4096))
    assert m._attn_params == (7168 * 1536 + 1536 * 64 * 192 + 7168 * 576
                              + 512 * 64 * 320 + 64 * 192 * 7168)
    assert m._attn_params == pytest.approx(132.6e6, rel=1e-3)
    assert (m.n_sparse_layers, m.experts_held, m.shared_experts) == (4, 16, 1)
    # 1 dense + 4 sparse layers, embedding and head: 4,291 M parameters
    assert m.param_count == pytest.approx(4.291e9, rel=2e-3)
    assert m.kv_bytes_per_position == 5 * 576 * 2
    assert m.attention_flops(1) == 5 * 4 * 64 * 576
    # a token computes with half a held expert a layer, in expectation
    assert m.active_matmul_params == pytest.approx(
        5 * m._attn_params + 3 * 7168 * 18432
        + 4 * (1.5 * 3 * 7168 * 2048 + 7168 * 256))


def test_config_file_against_lmconfig_key_by_key():
    """benchmark/configs/gigachat3.1-702b-a36b.json: every published width
    reaches ``LMConfig`` unchanged; the four reduced keys are stated with
    their published values; what ``config.json`` does not give is under
    ``assumed``; the deployment is stated."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "gigachat3.1-702b-a36b.json")) as f:
        hf = json.load(f)
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "GigaChat3.1-702B-A36B")
    assert hf["source"] == row["source_url"]
    reduced = {"num_hidden_layers": (64, 5), "first_k_dense_replace": (3, 1),
               "n_routed_experts": (256, 16), "vocab_size": (128256, 16032)}
    assert set(hf["reduced"]) == set(reduced)
    for key, value in row["config"].items():
        if key in reduced:
            assert (hf["reduced"][key]["published"], hf[key]) == reduced[key]
            assert value == reduced[key][0]
        else:
            assert hf[key] == value, key
    dep = hf["deployment"]
    assert (dep["expert_parallel"], dep["expert_rank"], dep["router_width"],
            dep["vocab_parallel"]) == (16, 0, 256, 8)
    for key in ("eos_token_id", "pad_token_id", "initializer_range",
                "router_init", "multi_token_prediction"):
        assert key in hf["assumed"]
    from benchmark import weights_mla

    cfg = weights_mla.lm_config(hf, "bfloat16", 4096)
    view = weights_mla.published_view(hf)
    for theirs, ours in hf_import.DEEPSEEK_KEYS.items():
        assert getattr(cfg, ours) == view[theirs], theirs
    for theirs, ours in hf_import.YARN_KEYS.items():
        assert getattr(cfg, ours) == hf["rope_scaling"][theirs], theirs
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.latent_width,
            cfg.latent_row_width) == (7168, 64, 192, 576, 640)
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held) == (
        256, 0, 16)
    assert cfg.ff_kinds() == ["dense"] + ["sparse"] * 4
    assert (cfg.router_groups, cfg.router_topk_groups, cfg.router_scale,
            cfg.num_experts_per_tok) == (8, 4, 2.5, 8)
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 4.28e9 < n < 4.30e9           # 8.58 GB in bf16
    # the seeded tensors have the published names and the share's shapes
    pub = weights_mla.Published(hf, 1, "bfloat16")
    assert pub.shape("model.layers.2.mlp.gate.weight") == (256, 7168)
    assert pub.shape("model.layers.2.mlp.experts.15.down_proj.weight") == (
        7168, 2048)
    assert pub.shape("model.layers.0.mlp.up_proj.weight") == (18432, 7168)
    assert pub.shape("lm_head.weight") == (16032, 7168)
    with pytest.raises(KeyError):
        pub.shape("model.layers.2.mlp.experts.16.up_proj.weight")
