"""Nemotron-H (``model_type: nemotron_h``: layers that are a Mamba-2 mixer,
attention without positions or LatentMoE experts ALONE) through ``CausalLM``,
the importer and ``InferenceEngine``, against the plain float32 reference on
seeded weights in the published layout, at a small size on the CPU: all three
kinds of layer, two groups of three heads, 4 of 8 experts held."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_air.models.lm import hf_import, reference_nemotron_h as reference
from tpu_air.models.lm.config import LMConfig
from tpu_air.models.lm.modeling import (CausalLM, Mamba2Mixer,
                                        grouped_sigmoid_routing)
from tpu_air.ops import moe, ssm

import _combine_cases
import _mixed_step_cases

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "model_type": "nemotron_h", "hidden_size": 64, "num_hidden_layers": 6,
    "hybrid_override_pattern": "ME*MEME", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 16, "intermediate_size": 48,
    "moe_intermediate_size": 48, "moe_latent_size": 32,
    "moe_shared_expert_intermediate_size": 96, "n_routed_experts": 8,
    "n_shared_experts": 1, "num_experts_per_tok": 3, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 5,
    "mamba_num_heads": 6, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 16, "conv_kernel": 4, "chunk_size": 8,
    "layer_norm_epsilon": 1e-05, "vocab_size": 384,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "use_bias": False, "mlp_bias": False,
    "attention_bias": False, "mamba_proj_bias": False,
    "num_nextn_predict_layers": 1, "mtp_hybrid_override_pattern": "*E",
}
HELD = (2, 4)     # this tree's share: experts 2..5 of the 8 routed over


def published_shapes(cfg):
    """name -> shape of every tensor of a published nemotron_h state dict
    (the multi-token module's left out)."""
    d, v = cfg["hidden_size"], cfg["vocab_size"]
    H, P = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    G, N, K = cfg["n_groups"], cfg["ssm_state_size"], cfg["conv_kernel"]
    c, cd = H * P, H * P + 2 * G * N
    hd, lat = cfg["head_dim"], cfg["moe_latent_size"]
    q, kv = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    f, fs = (cfg["moe_intermediate_size"],
             cfg["moe_shared_expert_intermediate_size"])
    out = {"backbone.embeddings.weight": (v, d), "lm_head.weight": (v, d),
           "backbone.norm_f.weight": (d,)}
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    for i, kind in enumerate(pattern):
        out[f"backbone.layers.{i}.norm.weight"] = (d,)
        m = f"backbone.layers.{i}.mixer."
        if kind == "M":
            out.update({
                m + "in_proj.weight": (c + cd + H, d),
                m + "conv1d.weight": (cd, 1, K), m + "conv1d.bias": (cd,),
                m + "dt_bias": (H,), m + "A_log": (H,), m + "D": (H,),
                m + "norm.weight": (c,), m + "out_proj.weight": (d, c)})
        elif kind == "*":
            out.update({
                m + "q_proj.weight": (q, d), m + "k_proj.weight": (kv, d),
                m + "v_proj.weight": (kv, d), m + "o_proj.weight": (d, q)})
        else:
            e = cfg["n_routed_experts"]
            out.update({
                m + "gate.weight": (e, d),
                m + "gate.e_score_correction_bias": (e,),
                m + "fc1_latent_proj.weight": (lat, d),
                m + "fc2_latent_proj.weight": (d, lat),
                m + "shared_experts.up_proj.weight": (fs, d),
                m + "shared_experts.down_proj.weight": (d, fs)})
            for j in range(e):
                out[f"{m}experts.{j}.up_proj.weight"] = (f, lat)
                out[f"{m}experts.{j}.down_proj.weight"] = (lat, f)
    return out


def published(cfg, seed=0, std=0.08):
    """A seeded state dict in the published layout: matrices normal, norm
    weights near one, the Mamba-2 scalars by Mamba-2's own initialisation (so
    that some heads remember hundreds of positions), a router wide enough to
    choose."""
    rng = np.random.default_rng(seed)
    sd = {}
    for name, shape in published_shapes(cfg).items():
        if name.endswith("A_log"):
            w = np.log(rng.uniform(1.0, 16.0, shape))
        elif name.endswith("dt_bias"):
            dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            w = dt0 + np.log(-np.expm1(-dt0))
        elif "conv1d" in name:
            w = rng.uniform(-0.5, 0.5, shape)
        elif name.endswith("gate.weight"):
            w = rng.standard_normal(shape) * 0.5
        elif name.endswith("e_score_correction_bias"):
            w = rng.standard_normal(shape) * 0.05
        elif len(shape) == 1:
            w = 1.0 + 0.1 * rng.standard_normal(shape)
        else:
            w = rng.standard_normal(shape) * std * (
                3 if "latent" in name else 1.5 if "in_proj" in name else 1)
        sd[name] = w.astype(np.float32)
    return sd


def _tree(sd, held=None, **kw):
    over = {} if held is None else dict(experts_first=held[0],
                                        experts_held=held[1])
    config = hf_import.lm_config_from_hf(TINY, max_seq_len=256, **over, **kw)
    params = jax.tree_util.tree_map(
        jnp.asarray,
        hf_import.convert_nemotron_h_state_dict(sd.__getitem__, config))
    return config, CausalLM(config), params


@pytest.fixture(scope="module")
def tiny():
    sd = published(TINY)
    return (sd,) + _tree(sd, HELD)


def _rel(got, want):
    scale = want.max(-1) - np.median(want, -1)
    return (np.abs(got - want).max(-1) / scale).max()


def test_config_maps_the_published_keys():
    cfg = hf_import.lm_config_from_hf(TINY)
    assert cfg.layer_kinds() == ["mamba2", "none", "attention", "mamba2",
                                 "none", "mamba2"]
    assert cfg.ff_kinds() == ["none", "sparse", "none", "none", "sparse",
                              "none"]
    assert cfg.has_recurrent_layers and cfg.rope_theta is None
    assert (cfg.mamba_d_inner, cfg.mamba2_conv_dim) == (48, 48 + 2 * 2 * 16)
    assert (cfg.ff_act, cfg.moe_latent_size, cfg.shared_d_ff) == (
        "relu2", 32, 96)
    assert (cfg.router, cfg.router_groups, cfg.router_scale) == (
        "sigmoid_groups", 1, 5)
    assert not cfg.tie_embeddings and cfg.n_kv_heads == 2
    # the other families are what they were
    assert LMConfig.tiny().layer_kinds() == ["attention"] * 2
    assert LMConfig.tiny().ff_kinds() == ["dense"] * 2
    assert LMConfig.tiny().shared_d_ff == 0
    with pytest.raises(ValueError, match="relu2"):
        hf_import.lm_config_from_hf({**TINY, "mlp_hidden_act": "silu"})
    with pytest.raises(ValueError, match="layer_pattern"):
        hf_import.lm_config_from_hf({**TINY,
                                     "hybrid_override_pattern": "M-*E-M"})


def test_importer_round_trip(tiny):
    """Every published tensor of the share lands in the tree exactly once,
    transposed or renamed, and the tree is the one ``CausalLM.init`` makes;
    experts held elsewhere and the multi-token module are never asked for."""
    sd, config, model, params = tiny
    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    want = jax.tree_util.tree_map(lambda a: a.shape, init["params"])
    assert want == jax.tree_util.tree_map(lambda a: a.shape, params)
    asked = []

    def get(name):
        asked.append(name)
        return sd[name]

    hf_import.convert_nemotron_h_state_dict(get, config)
    assert len(asked) == len(set(asked))
    away = {n for n in sd if ".experts." in n and not HELD[0] <= int(
        n.split(".experts.")[1].split(".")[0]) < sum(HELD)}
    assert set(asked) == set(sd) - away
    m = "backbone.layers.0.mixer."
    mix = params["layer_0"]["mamba"]
    np.testing.assert_array_equal(mix["conv"]["kernel"],
                                  sd[m + "conv1d.weight"][:, 0, :].T)
    np.testing.assert_array_equal(mix["in_proj"]["kernel"],
                                  sd[m + "in_proj.weight"].T)
    np.testing.assert_array_equal(mix["norm"], sd[m + "norm.weight"])
    e = "backbone.layers.1.mixer."
    np.testing.assert_array_equal(
        params["layer_1"]["moe"]["up"][1],
        sd[e + "experts.3.up_proj.weight"].T)
    np.testing.assert_array_equal(
        params["layer_1"]["moe"]["latent_up"]["kernel"],
        sd[e + "fc2_latent_proj.weight"].T)
    np.testing.assert_array_equal(
        params["layer_2"]["attn"]["k"]["kernel"],
        sd["backbone.layers.2.mixer.k_proj.weight"].T)
    assert "gate" not in params["layer_1"]["moe"]
    # a slice of the vocabulary
    part = hf_import.convert_nemotron_h_state_dict(
        sd.__getitem__, LMConfig.from_dict({**config.to_dict(),
                                            "vocab_size": 96}),
        vocab_rows=range(96, 192))
    np.testing.assert_array_equal(part["lm_head"]["kernel"],
                                  sd["lm_head.weight"][96:192].T)


def test_init_is_mamba2s_own():
    cfg = hf_import.lm_config_from_hf(TINY)
    p = CausalLM(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 4), jnp.int32))["params"]
    mix = p["layer_0"]["mamba"]
    a = np.exp(np.asarray(mix["A_log"]))
    assert a.shape == (6,) and 1 <= a.min() and a.max() <= 16
    dt0 = np.asarray(jax.nn.softplus(mix["dt_bias"]))
    assert 1e-3 * 0.99 <= dt0.min() and dt0.max() <= 1e-1 * 1.01
    assert np.all(np.asarray(mix["D"]) == 1)
    assert np.all(np.asarray(mix["norm"]) == 1)


@pytest.mark.parametrize("held", [None, HELD])
def test_full_forward_matches_the_reference(held):
    sd = published(TINY)
    config, model, params = _tree(sd, held)
    ids = np.random.default_rng(3).integers(2, 384, 150).tolist()
    want = reference.forward(sd.__getitem__, TINY, ids, held=held)["logits"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params},
                                     jnp.asarray([ids], jnp.int32))[0])
    assert _rel(got, want) < 1e-4
    if held is None:
        return
    # the carried state matters: a reference that forgets at 128 differs
    lost = reference.forward(sd.__getitem__, TINY, ids, held=held,
                             drop_state_at=128)["logits"]
    scale = want.max(-1) - np.median(want, -1)
    err = np.abs(lost - want).max(-1) / scale
    assert err[:128].max() == 0 and err[128:].max() > 0.05


def test_the_ranks_layer_outputs_add_up_to_the_uncut_layer():
    """Two ranks of four experts each, the same hidden state into an ``E``
    layer: routed part over the held experts (taken back up by ``latent_up``,
    which is linear) plus the shared expert counted ONCE is the uncut
    reference's layer.  The system's layer, rank by rank, is the reference's
    for that rank."""
    from tpu_air.models.lm.modeling import Block

    sd = published(TINY)
    ids = np.random.default_rng(4).integers(2, 384, 40).tolist()
    whole = reference.forward(sd.__getitem__, TINY, ids, layer_outputs=True)
    h = np.asarray(sd["backbone.embeddings.weight"])[ids] + whole["layers"][0]
    total = 0
    for rank in ((0, 4), (4, 4)):
        config, _, params = _tree(sd, rank)
        with jax.default_matmul_precision("highest"):
            out = Block(config, "none", "sparse").apply(
                {"params": params["layer_1"]}, jnp.asarray(h)[None], None)
        routed = np.asarray(out[0]) - h - whole["shared"][0]
        cut = reference.forward(sd.__getitem__, TINY, ids, held=rank,
                                layer_outputs=True)
        np.testing.assert_allclose(routed, cut["layers"][1], atol=2e-5)
        np.testing.assert_allclose(cut["shared"][0], whole["shared"][0],
                                   atol=1e-6)
        total = total + routed
    np.testing.assert_allclose(total, whole["layers"][1], atol=4e-5)
    assert np.abs(whole["layers"][1]).max() > 0.01


def test_top_k_sigmoid_routing_with_the_bias_against_the_reference():
    """22 of 512 the published way, at a small size: one group, the bias
    selects and does not weigh, weights renormalised and times 5."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.standard_normal((33, 64)) * 2, jnp.float32)
    bias = jnp.asarray(rng.standard_normal(64) * 0.3, jnp.float32)
    cfg = {"num_experts_per_tok": 22, "routed_scaling_factor": 5}
    want, gap = reference.route(cfg, logits, bias)
    w, chosen = grouped_sigmoid_routing(logits, bias, 22, 1, 1, 5.0)
    got = np.zeros((33, 64), np.float32)
    got[np.arange(33)[:, None], np.asarray(chosen)] = np.asarray(w)
    np.testing.assert_allclose(got, np.asarray(want), atol=1e-6)
    np.testing.assert_allclose(got.sum(-1), 5.0, rtol=1e-5)
    assert (np.asarray(gap) > 0).all()
    # as one rank sees it: a tie among experts held elsewhere does not count
    _, mine = reference.route(cfg, logits, bias, held=(0, 16))
    assert (np.asarray(mine) >= np.asarray(gap) - 1e-7).all()


@pytest.mark.parametrize("partial", [False, True])
def test_two_matrix_relu2_expert_ffn_against_a_loop_over_experts(partial):
    rng = np.random.default_rng(6)
    t, k, e, d, f = 19, 3, 5, 16, 24
    x = jnp.asarray(rng.standard_normal((t, d)), jnp.float32)
    up = jnp.asarray(rng.standard_normal((e, d, f)) * 0.3, jnp.float32)
    down = jnp.asarray(rng.standard_normal((e, f, d)) * 0.3, jnp.float32)
    chosen = np.stack([rng.permutation(e + partial)[:k] for _ in range(t)])
    w = jnp.asarray(rng.uniform(0.1, 1, (t, k)), jnp.float32)
    with jax.default_matmul_precision("highest"):
        got = moe.expert_ffn(x, jnp.asarray(chosen), w, None, up, down)
        want = np.zeros((t, d), np.float32)
        for i in range(t):
            for j in range(k):
                if chosen[i, j] < e:
                    hid = np.maximum(np.asarray(x[i] @ up[chosen[i, j]]),
                                     0) ** 2
                    want[i] += float(w[i, j]) * np.asarray(
                        hid @ down[chosen[i, j]])
    np.testing.assert_allclose(np.asarray(got), want, rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("routing", sorted(_combine_cases.ROUTINGS))
def test_two_matrix_expert_ffn_and_its_gradient_against_the_loop(routing):
    """Two-matrix experts through the same sum over a token's choices
    (tests/_combine_cases.py), every routing of the other two files."""
    _combine_cases.against_the_loop(routing, gated=False)


def test_every_benchmark_configurations_products_keep_their_tile():
    """``ops/moe.gmm_tiling`` by configuration, the three products of each at
    the rows its cell runs (a decode step, a chunk, a mixed step): the third
    tile serves the sides of 2688 alone, and no shape that had a tile
    changed it."""
    want = {
        # olmoe-1b-7b: 64 experts of 2048 x 1024, top-8, 64 slots, page 128
        "olmoe": ((2048, 1024), (128, 2048, 1024), (128, 1024, 1024),
                  (512, 1024, 1536)),
        # gigachat3.1-702b-a36b: 7168 x 2048, top-8, 128 slots, page 256
        "gigachat": ((7168, 2048), (128, 1024, 2048), (128, 2048, 1024),
                     (1024, 2048, 3072)),
        # nemotron3-super-120b-a12b: 1024 x 2688, top-22, 128 slots, page 256
        "nemotron": ((1024, 2688), (128, 1024, 2688), (128, 2688, 1024),
                     (2816, 5632, 8448)),
    }
    for name, ((d, f), up, down, rows) in want.items():
        for m in rows:
            assert moe.gmm_tiling(m, d, f) == up, (name, m)      # gate, up
            assert moe.gmm_tiling(m, f, d) == down, (name, m)    # down
    assert moe.gmm_tiling(100, 1024, 2688) is None
    assert moe.GMM_TILING_WHOLE == (128, 2688, 2688)


# -- ops/ssm: the block form and the one-token update -------------------------

def _ssd_inputs(rng, b, l, H=6, P=8, G=2, N=16):
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    dt = jnp.asarray(np.exp(rng.uniform(np.log(1e-3), np.log(0.5),
                                        (b, l, H))), jnp.float32)
    A = -jnp.asarray(rng.uniform(1, 16, H), jnp.float32)
    return (f(b, l, H, P), dt, A, f(b, l, G, N), f(b, l, G, N), f(H),
            f(b, H, P, N))


def _plain_recurrence(u, dt, A, B, C, D, state, valid):
    """Position by position, numpy float64."""
    u, dt, A, B, C, D, s = (np.asarray(a, np.float64)
                            for a in (u, dt, A, B, C, D, state))
    b, l, H, P = u.shape
    k = H // B.shape[2]
    ys = np.zeros((b, l, H, P))
    for i in range(b):
        for t in range(int(valid[i])):
            Bh, Ch = np.repeat(B[i, t], k, 0), np.repeat(C[i, t], k, 0)
            s[i] = (np.exp(dt[i, t] * A)[:, None, None] * s[i]
                    + (dt[i, t][:, None] * u[i, t])[:, :, None]
                    * Bh[:, None, :])
            ys[i, t] = (s[i] * Ch[:, None, :]).sum(-1) + D[:, None] * u[i, t]
    return ys, s


@pytest.mark.parametrize("l,block,valid", [
    (16, 8, (16, 16)),     # two whole blocks
    (21, 8, (21, 13)),     # a padded last block; a row that ends mid-block
    (8, 8, (8, 3)),        # one block
    (24, 8, (24, 8)),      # a row whose last two blocks are padding alone
    (5, 128, (5, 2)),      # shorter than a block
])
def test_ssd_block_form_against_the_plain_recurrence(l, block, valid):
    rng = np.random.default_rng(l * 31 + block)
    u, dt, A, B, C, D, state = _ssd_inputs(rng, 2, l)
    valid = np.asarray(valid)
    y, new = ssm.ssd_chunk(u, dt, A, B, C, D, state, jnp.asarray(valid),
                           block)
    want_y, want = _plain_recurrence(u, dt, A, B, C, D, state, valid)
    np.testing.assert_allclose(np.asarray(new), want, rtol=2e-5, atol=2e-5)
    for i in range(2):
        np.testing.assert_allclose(np.asarray(y)[i, :valid[i]],
                                   want_y[i, :valid[i]], rtol=2e-4,
                                   atol=2e-4)
    # the carry between chunks is the carry between blocks: two calls of
    # half the positions leave what one call leaves
    if l == 16:
        h = 8
        _, mid = ssm.ssd_chunk(u[:, :h], dt[:, :h], A, B[:, :h], C[:, :h], D,
                               state, jnp.asarray([h, h]), block)
        _, end = ssm.ssd_chunk(u[:, h:], dt[:, h:], A, B[:, h:], C[:, h:], D,
                               mid, jnp.asarray([h, h]), block)
        np.testing.assert_allclose(np.asarray(end), np.asarray(new),
                                   rtol=1e-5, atol=1e-6)


def test_ssd_padding_and_held_rows_keep_the_state_bit_for_bit():
    rng = np.random.default_rng(7)
    u, dt, A, B, C, D, state = _ssd_inputs(rng, 3, 16)
    # a chunk of padding alone is the state that came in, bit for bit
    _, same = ssm.ssd_chunk(u, dt, A, B, C, D, state, jnp.asarray([0, 0, 0]),
                            8)
    np.testing.assert_array_equal(np.asarray(same), np.asarray(state))
    # blocks of padding behind the real positions change nothing
    _, s8 = ssm.ssd_chunk(u[:, :8], dt[:, :8], A, B[:, :8], C[:, :8], D,
                          state, jnp.asarray([8, 5, 8]), 8)
    _, s16 = ssm.ssd_chunk(u, dt, A, B, C, D, state, jnp.asarray([8, 5, 8]),
                           8)
    np.testing.assert_array_equal(np.asarray(s8), np.asarray(s16))
    # the one-token update: a row that is not live keeps its state
    live = jnp.asarray([True, False, True])
    y, new = ssm.ssd_state_update(u[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D,
                                  state, live)
    np.testing.assert_array_equal(np.asarray(new[1]), np.asarray(state[1]))
    want_y, want = _plain_recurrence(u[:, :1], dt[:, :1], A, B[:, :1],
                                     C[:, :1], D, state, [1, 0, 1])
    np.testing.assert_allclose(np.asarray(new), want, rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(np.asarray(y)[[0, 2]], want_y[[0, 2], 0],
                               rtol=1e-5, atol=1e-5)


_LIVE_PATTERNS = {
    "all": [1, 1, 1, 1, 1, 1],
    "none": [0, 0, 0, 0, 0, 0],
    "first": [1, 0, 0, 0, 0, 0],
    "last": [0, 0, 0, 0, 0, 1],
    "alternating": [1, 0, 1, 0, 1, 0],
    "prefix": [1, 1, 1, 0, 0, 0],
    "suffix": [0, 0, 0, 1, 1, 1],
}


@pytest.mark.parametrize("pattern", sorted(_LIVE_PATTERNS))
def test_ssd_rows_update_moves_the_live_rows_alone(pattern):
    """The in-place kernel (interpret mode) against the ``jnp`` form and the
    plain recurrence: two groups of four heads, a tile of two heads (so a
    row is four grid steps and a group two tiles).  A row that is not live is
    the input bit for bit and its ``y`` zeros; with none live the whole pool
    comes back bit for bit."""
    live = np.asarray(_LIVE_PATTERNS[pattern], bool)
    rng = np.random.default_rng(sum(map(ord, pattern)))
    u, dt, A, B, C, D, state = _ssd_inputs(rng, len(live), 1, H=8, P=8, G=2,
                                           N=128)
    args = (u[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], D, state)
    y, new = jax.jit(lambda *a: ssm.ssd_rows_update(*a, head_tile=2))(
        *args, jnp.asarray(live))
    y, new = np.asarray(y), np.asarray(new)
    np.testing.assert_array_equal(new[~live], np.asarray(state)[~live])
    np.testing.assert_array_equal(y[~live], 0.0)
    y_jnp, new_jnp = ssm.ssd_state_update(*args, jnp.asarray(live))
    want_y, want = _plain_recurrence(u, dt, A, B, C, D, state,
                                     live.astype(int))
    for got, ref in ((new, np.asarray(new_jnp)), (new, want)):
        np.testing.assert_allclose(got[live], ref[live], rtol=1e-5, atol=1e-6)
    for ref in (np.asarray(y_jnp), want_y[:, 0]):
        np.testing.assert_allclose(y[live], ref[live], rtol=1e-5, atol=1e-5)


def test_the_rule_that_moves_state_rows_in_place(monkeypatch):
    """``state_rows_move_in_place`` is decided from backend, mesh, dtype and
    tile shape: no on a CPU, under a ``kernel_mesh``, for a bf16 state, for a
    state that is not whole tiles (or Mamba-1's three dimensions); and where
    it says no ``Mamba2Mixer`` runs the ``jnp`` form."""
    from tpu_air.ops.flash_attention import kernel_mesh

    pool = lambda P=64, N=128, dtype=jnp.float32: jax.ShapeDtypeStruct(  # noqa: E731
        (4, 8, P, N), dtype)
    assert not ssm.state_rows_move_in_place(pool())              # a CPU
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert ssm.state_rows_move_in_place(pool())
    assert ssm.state_rows_move_in_place(pool(8, 256))
    assert not ssm.state_rows_move_in_place(pool(dtype=jnp.bfloat16))
    assert not ssm.state_rows_move_in_place(pool(N=64))
    assert not ssm.state_rows_move_in_place(pool(P=4))
    assert not ssm.state_rows_move_in_place(
        jax.ShapeDtypeStruct((4, 16, 5120), jnp.float32))
    with kernel_mesh(object()):
        assert not ssm.state_rows_move_in_place(pool())
    assert ssm.state_rows_move_in_place(pool())
    monkeypatch.undo()

    # the mixer asks the rule: on this CPU no kernel call is traced
    called = []
    monkeypatch.setattr(ssm, "ssd_rows_update",
                        lambda *a, **k: called.append(a))
    cfg = hf_import.lm_config_from_hf(TINY, max_seq_len=256)
    mixer = Mamba2Mixer(cfg)
    x = jnp.zeros((2, 1, cfg.d_model), jnp.dtype(cfg.dtype))
    variables = mixer.init(jax.random.PRNGKey(0), x, decode=True)
    text = jax.jit(lambda v, x: mixer.apply(
        v, x, decode=True, mutable=["cache"])).lower(variables, x).as_text()
    assert not called and "ssd_rows_update" not in text


# -- the engine: chunked prefill, then paged decode, the state a slot --------

def _engine(tiny, **kw):
    from tpu_air.engine import EngineConfig, InferenceEngine

    _, config, model, params = tiny
    cfg = dict(num_slots=4, slot_len=256, page_len=16, max_new_tokens=8,
               eos_token_id=None)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg),
                           auto_start=False)


def _reference_rows(sd, prompt, answer, **how):
    ids = list(prompt) + list(answer[:-1])
    return reference.forward(sd.__getitem__, TINY, ids,
                             range(len(prompt) - 1, len(ids)), held=HELD,
                             **how)


def test_chunked_prefill_then_paged_decode_matches_the_reference(tiny):
    """Logits, not tokens: prompts that cross a chunk boundary (which lies
    BETWEEN two state-space blocks of 8 in a chunk of 16) and end in a padded
    chunk, one that fills its last chunk, one shorter than a chunk and one
    shorter than a block, through the engine's chunk and decode programs,
    fewer slots than prompts (one is reused, a row mid-prefill rides the
    others' steps held at position 0): the function the benchmark's check
    runs inside the replica on the chip."""
    from benchmark.worker_hooks_ssd import replayed_logits

    sd = tiny[0]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 384, k).tolist() for k in (37, 32, 9, 50, 5)]
    eng = _engine(tiny)
    answers = eng.generate(prompts, 6)
    assert all(len(a) == 6 for a in answers)
    with jax.default_matmul_precision("highest"):
        system, carried = replayed_logits(eng, prompts, answers, [2, 0, 3])
    # the replay took the engine's own cache and handed it back
    assert eng.generate(prompts, 6) == answers
    snap = eng.metrics.snapshot()
    eng.close()
    assert snap["ssd_rows_live"] > 0 and snap["ssd_positions_live"] > 0
    assert snap["moe_assignments_elsewhere"] > 0
    for p, a, got, state in zip(prompts, answers, system, carried):
        ids = list(p) + list(a[:-1])
        want = _reference_rows(sd, p, a, state_after=len(ids))
        assert _rel(got, want["logits"]) < 1e-3
        # what the engine streamed is what those logits say
        assert got.argmax(-1).tolist() == a
        # the state the system carries in its first Mamba-2 layer, in a
        # reused slot too, is the reference's after the same positions
        assert state.shape == want["states"][0].shape == (6, 8, 16)
        np.testing.assert_allclose(state, want["states"][0], rtol=2e-4,
                                   atol=1e-6)


def test_reference_round_state_and_state_after(tiny):
    sd = tiny[0]
    ids = np.random.default_rng(8).integers(2, 384, 40).tolist()
    full = reference.forward(sd.__getitem__, TINY, ids, held=HELD,
                             state_after=20)
    short = reference.forward(sd.__getitem__, TINY, ids[:20], held=HELD,
                              state_after=20)
    np.testing.assert_allclose(full["states"], short["states"], rtol=1e-6)
    assert full["states"].shape == (3, 6, 8, 16)
    rounded = reference.forward(
        sd.__getitem__, TINY, ids, held=HELD, state_after=40,
        round_state=lambda s: s.astype(jnp.bfloat16).astype(jnp.float32))
    exact = reference.forward(sd.__getitem__, TINY, ids, held=HELD,
                              state_after=40)
    apart = (np.linalg.norm(rounded["states"][0] - exact["states"][0])
             / np.linalg.norm(exact["states"][0]))
    assert 1e-4 < apart < 0.05


def test_engine_streams_the_tokens_of_offline_generate(tiny):
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


def test_a_reused_slot_gives_the_logits_of_a_fresh_engine(tiny):
    rng = np.random.default_rng(7)
    first, second = (rng.integers(2, 384, k).tolist() for k in (45, 23))
    eng = _engine(tiny, num_slots=1)
    eng.generate([first], 8)
    reused = eng.generate([second], 8)
    stats = eng.metrics.snapshot()
    eng.close()
    fresh_eng = _engine(tiny, num_slots=1)
    fresh = fresh_eng.generate([second], 8)
    fresh_eng.close()
    assert reused == fresh
    assert stats["ssm_state_resets"] == 2


@pytest.mark.parametrize("case", sorted(_mixed_step_cases.CASES))
def test_mixed_step(tiny, case):
    """One program for an iteration's prefill chunk and its decode step
    (tests/_mixed_step_cases.py): the chunk's slot rides the step's half
    held and ends with the chunk's state; streams against offline
    ``generate``."""
    from tpu_air.models.lm.generate import generate

    _, config, model, params = tiny

    def check(prompt, tokens):
        want = generate(model, params, np.asarray([prompt]),
                        max_new_tokens=len(tokens))
        assert np.asarray(want)[0].tolist() == tokens

    _mixed_step_cases.CASES[case](model, params, check)


@pytest.mark.parametrize("in_place", [False, True])
def test_the_engine_counts_the_rows_whose_state_a_step_moves(
        tiny, monkeypatch, in_place):
    """``stats()["ssd_state_rows_passed"]`` and ``engine.step``'s
    ``state_rows``: every slot a step where the pass is the ``jnp`` form's
    (this CPU), the rows the step advances where the rule says the state
    moves in place (patched to yes: the kernel in interpret mode, through
    the engine's decode and mixed programs; the tokens are still offline
    ``generate``'s)."""
    import contextlib

    from tpu_air.engine import engine as engine_module
    from tpu_air.models.lm.generate import generate

    if in_place:
        monkeypatch.setattr(ssm, "state_rows_move_in_place",
                            lambda state: True)
    steps = []

    def phase(name, **counts):
        if name == "engine.step":
            steps.append(counts)
        return contextlib.nullcontext()

    monkeypatch.setattr(engine_module, "phase", phase)
    _, config, model, params = tiny
    rng = np.random.default_rng(16)
    prompts = [rng.integers(2, 384, k).tolist() for k in (21, 40, 5)]
    eng = _engine(tiny)
    got = eng.generate(prompts, 8)
    snap = eng.metrics.snapshot()
    eng.close()
    assert snap["ssd_rows_live"] > 0 and snap["mixed_steps"] > 0
    if in_place:
        assert snap["ssd_state_rows_passed"] == snap["ssd_rows_live"]
    else:
        assert (snap["ssd_state_rows_passed"]
                == eng.config.num_slots * snap["steps_issued"])
        assert snap["ssd_state_rows_passed"] > snap["ssd_rows_live"]
    issued = [s for s in steps if "state_rows" in s]
    assert len(issued) == snap["steps_issued"]
    assert sum(s["state_rows"] for s in issued) == snap["ssd_state_rows_passed"]
    for p, g in zip(prompts, got):
        want = generate(model, params, np.asarray([p]), max_new_tokens=8)
        assert np.asarray(want)[0].tolist() == g


def test_rows_beside_pages_prefix_sharing_off_and_moves_refused(tiny):
    from tpu_air.engine import (ExpertExchangeUnsupported, MeshEngine,
                                RecurrentStateUnsupported)
    from tpu_air.models.lm import paged_cache

    eng = _engine(tiny)
    assert eng.pool.prefix is None
    snap = eng.metrics.snapshot()
    assert snap["prefix_cache_disabled_by_model"] is True
    # three Mamba-2 layers: a float32 state [6, 8, 16] and a tail of 3 x 112
    assert snap["ssm_state_bytes"] == 3 * 4 * (6 * 8 * 16 * 4 + 3 * 112 * 4)
    kinds = {paged_cache.layer_kind(layer) for _, layer in
             paged_cache.layers(eng.cache)}
    assert kinds == {"mamba", "attention"}   # the rows' format is one
    assert paged_cache.FORMATS["mamba2"] == paged_cache.FORMATS["mamba"]
    assert len(paged_cache.layers(eng.cache)) == 4   # an E layer keeps none
    with pytest.raises(RecurrentStateUnsupported, match="migrate_out"):
        eng.migrate_out()
    with pytest.raises(RecurrentStateUnsupported):
        eng.submit_prefilled([1, 2, 3], 5, {})
    eng.close()
    _, config, model, params = tiny
    with pytest.raises((RecurrentStateUnsupported,
                        ExpertExchangeUnsupported)):
        MeshEngine(model, params, eng.config)


def test_config_file_against_lmconfig_key_by_key():
    """benchmark/configs/nemotron3-super-120b-a12b.json: every published
    width and count reaches ``LMConfig`` unchanged but the four cuts, and what
    the ``config.json`` does not give is under ``assumed``."""
    from benchmark import weights_nemotron

    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron3-super-120b-a12b.json")) as f:
        hf = json.load(f)
    assert sorted(hf["reduced"]) == ["hybrid_override_pattern",
                                     "n_routed_experts", "num_hidden_layers",
                                     "vocab_size"]
    assert hf["source"].endswith(
        "nvidia/NVIDIA-Nemotron-3-Super-120B-A12B-BF16/blob/main/config.json")
    cfg = weights_nemotron.lm_config(hf, "bfloat16", 4096)
    for theirs, ours in hf_import.NEMOTRON_H_KEYS.items():
        if theirs != "n_routed_experts":
            assert getattr(cfg, ours) == hf[theirs], theirs
    assert (cfg.num_experts, cfg.experts_first, cfg.experts_held) == (
        512, 0, 128)
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads,
            cfg.head_dim) == (4096, 11, 32, 2, 128)
    assert cfg.layer_pattern == "MEMEMEM*EME" == hf["reduced"][
        "hybrid_override_pattern"]["published"][:11]
    assert (cfg.mamba_n_heads, cfg.mamba_head_dim, cfg.mamba_n_groups,
            cfg.mamba_d_state, cfg.mamba_d_inner, cfg.mamba2_conv_dim) == (
        128, 64, 8, 128, 8192, 10240)
    assert (cfg.d_ff, cfg.moe_latent_size, cfg.shared_d_ff,
            cfg.num_experts_per_tok, cfg.router_scale, cfg.vocab_size) == (
        2688, 1024, 5376, 22, 5, 32768)
    assert cfg.rope_theta is None and not cfg.tie_embeddings
    assert hf["expand"] * hf["hidden_size"] == cfg.mamba_d_inner
    for key in ("position_encoding", "time_step_limit", "latent_pair",
                "multi_token_prediction", "dtype", "mamba_init",
                "router_init", "eos_token_id", "pad_token_id",
                "initializer_range"):
        assert key in hf["assumed"]
    assert hf["deployment"]["expert_parallel"] == 4
    assert hf["deployment"]["expert_rank"] == 0
    # parameters, reckoned from the shapes: 4.65 B, 9.30 GB in bf16
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 4.64e9 < n < 4.66e9
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        rows = [json.loads(line) for line in f if line.strip()]
    row = next(r for r in rows if r["name"].startswith(
        "NVIDIA-Nemotron-3-Super-120B"))
    for key, value in row["config"].items():
        if key not in hf["reduced"]:
            assert hf[key] == value, key


def test_cost_model_prices_a_layer_that_is_one_thing():
    """``LMCostModel`` on the cell's configuration: 4.65 B parameters held; a
    row keeps 5 x (4.19 MB + 61 KB); K/V of ONE layer on two heads; an expert
    is two matrices in the latent."""
    from benchmark import costs_ssd, weights_nemotron
    from tpu_air.observability.perf import LMCostModel

    with open(os.path.join(REPO, "benchmark", "configs",
                           "nemotron3-super-120b-a12b.json")) as f:
        hf = json.load(f)
    cfg = weights_nemotron.lm_config(hf, "bfloat16", 4096)
    m = LMCostModel(cfg)
    assert (m.n_attn_layers, m.n_mamba_layers, m.n_mamba2_layers,
            m.n_sparse_layers, m.n_dense_layers) == (1, 0, 5, 5, 0)
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    matrices = sum(int(np.prod(s.shape)) for s in
                   jax.tree_util.tree_leaves(shapes) if len(s.shape) > 1)
    convs = 5 * 4 * 10240
    assert m.param_count == matrices - convs
    assert m._expert_params == 2 * 1024 * 2688
    assert m.state_bytes_per_row == 5 * (128 * 64 * 128 * 4 + 3 * 10240 * 2)
    assert m.kv_bytes_per_position == 2 * 2 * 128 * 2
    assert 128 * m.state_bytes_per_row == costs_ssd.state_bytes(hf, 128)
    # the benchmark's own count agrees on the step: every weight outside the
    # experts but the embedding, the held experts touched, state both ways
    need = costs_ssd.decode_step_bytes(hf, 128, 128 * 700, 5 * 128)
    outside = (m.param_bytes - 32768 * 4096 * 2
               - 5 * 128 * 2 * 1024 * 2688 * 2)
    assert need["total_bytes"] - need["state_bytes"] - need["kv_bytes"] \
        - need["held_expert_bytes"] == outside
    assert need["state_bytes"] == 2 * 128 * m.state_bytes_per_row
    assert need["held_expert_bytes"] == 5 * 128 * 2 * 1024 * 2688 * 2
    # the families before it are priced as they were
    plain = LMCostModel(LMConfig.tiny())
    assert plain.n_dense_layers == 2 and plain._expert_params == 3 * 64 * 128
