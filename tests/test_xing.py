"""Xing4.0 (``model_type: xing4_0``: the ``deepseek_v3`` layer inside a
residual path of four streams, manifold-constrained hyper-connections)
through ``CausalLM``, the importer and ``InferenceEngine``, against the plain
float32 reference on seeded weights in the published layout, at a small size
on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_air.models.lm import hf_import, reference_xing
from tpu_air.models.lm.config import LMConfig
from tpu_air.models.lm.modeling import Block, CausalLM
from tpu_air.ops import mhc, moe

import _mixed_step_cases
from test_gigachat import _close
from test_gigachat import published as deepseek_published

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "model_type": "xing4_0", "hidden_size": 64, "intermediate_size": 96,
    "moe_intermediate_size": 32, "num_hidden_layers": 3,
    "first_k_dense_replace": 1, "moe_layer_freq": 1,
    "num_attention_heads": 4, "num_key_value_heads": 4,
    "n_routed_experts": 8, "n_shared_experts": 1, "num_experts_per_tok": 4,
    "n_group": 1, "topk_group": 1, "topk_method": "noaux_tc",
    "scoring_func": "sigmoid", "norm_topk_prob": True,
    "routed_scaling_factor": 2, "q_lora_rank": 24, "kv_lora_rank": 16,
    "qk_nope_head_dim": 8, "qk_rope_head_dim": 8, "v_head_dim": 12,
    "rope_theta": 10000, "rope_scaling": {
        "type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
        "mscale": 1, "mscale_all_dim": 1,
        "original_max_position_embeddings": 16},
    "rms_norm_eps": 1e-6, "vocab_size": 384, "attention_bias": False,
    "tie_word_embeddings": False, "hidden_act": "silu",
    "max_position_embeddings": 512, "num_nextn_predict_layers": 1,
    "hc_mult": 4, "hc_sinkhorn_iters": 20, "hc_eps": 1e-6,
    "mhc_h_res_clamp_min": -30, "mhc_h_res_clamp_max": 30,
}
N, MAPS = 4, 4 * 4 + 2 * 4


def published(cfg=TINY, seed=0):
    """The ``deepseek_v3`` tensors and, a sublayer, its hyper-connection's
    three under the assumed names: maps that move by token (``alpha`` near
    1, not the paper's 0.01 start) and mix the streams."""
    sd = deepseek_published(cfg, seed)
    rng = np.random.default_rng([seed, 7])
    n, c = cfg["hc_mult"], cfg["hidden_size"]
    for i in range(cfg["num_hidden_layers"]):
        for sub in ("attn", "mlp"):
            name = lambda k: hf_import.XING_MHC_NAMES[k].format(  # noqa: E731
                layer=i, sublayer=sub)
            sd[name("phi")] = ((n * c) ** -0.5 * rng.standard_normal(
                (n * n + 2 * n, n * c))).astype(np.float32)
            sd[name("b")] = np.concatenate([
                0.5 * rng.standard_normal(2 * n),
                (1.5 * np.eye(n) + 0.3 * rng.standard_normal((n, n))).ravel()
            ]).astype(np.float32)
            sd[name("alpha")] = rng.uniform(0.5, 1.5, 3).astype(np.float32)
    return sd


def _build(sd, **kw):
    config = hf_import.lm_config_from_hf(TINY, max_seq_len=256, **kw)
    params = jax.tree_util.tree_map(
        jnp.asarray,
        hf_import.convert_deepseek_v3_state_dict(sd.__getitem__, config))
    return config, CausalLM(config), params


@pytest.fixture(scope="module")
def tiny():
    sd = published()
    return (sd,) + _build(sd)


def _ref(sd, ids, rows=None, **how):
    return reference_xing.forward(sd.__getitem__, TINY, ids, rows, **how)


# -- the configuration and the importer ----------------------------------------

def test_config_maps_the_published_keys():
    cfg = hf_import.lm_config_from_hf(TINY)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert (cfg.router, cfg.router_groups, cfg.router_topk_groups,
            cfg.router_scale) == ("sigmoid_groups", 1, 1, 2)
    assert cfg.layer_kinds() == ["latent"] * 3
    assert cfg.ff_kinds() == ["dense", "sparse", "sparse"]
    assert (cfg.rope_factor, cfg.rope_original_len, cfg.rope_theta) == (
        4, 16, 10000)
    # the pair of the clamp comes back from a checkpoint's JSON as a list
    again = LMConfig.from_dict(json.loads(cfg.to_json()))
    assert again == cfg and isinstance(again.hc_res_clamp, tuple)
    with pytest.raises(ValueError, match="hc_mult"):
        LMConfig(hc_mult=0)
    # the published deepseek_v3 file has none of the four: one stream
    plain = {k: v for k, v in TINY.items() if "hc_" not in k}
    assert hf_import.lm_config_from_hf(
        {**plain, "model_type": "deepseek_v3"}).hc_mult == 1


def test_importer_takes_the_xing_names(tiny):
    """Every published tensor lands in the tree exactly once, the tree is the
    one ``CausalLM.init`` makes, and the hyper-connections come from the
    names the caller gives."""
    sd, config, model, params = tiny
    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    want = jax.tree_util.tree_map(lambda a: a.shape, init["params"])
    assert want == jax.tree_util.tree_map(lambda a: a.shape, params)
    assert (sum(a.size for a in sd.values())
            == sum(a.size for a in jax.tree_util.tree_leaves(params)))
    assert reference_xing.MHC_NAMES == hf_import.XING_MHC_NAMES
    hc = params["layer_1"]["mlp_hc"]
    assert set(hc) == {"phi", "b", "alpha"}
    assert hc["phi"].shape == (N * 64, MAPS) and hc["phi"].dtype == jnp.float32
    np.testing.assert_array_equal(hc["phi"],
                                  sd["model.layers.1.mlp_hc.phi.weight"].T)
    np.testing.assert_array_equal(params["layer_0"]["attn_hc"]["alpha"],
                                  sd["model.layers.0.attn_hc.alpha"])
    # other names, as a configuration file's ``assumed`` may list them
    names = {"phi": "blocks.{layer}.hc_{sublayer}.fn",
             "b": "blocks.{layer}.hc_{sublayer}.base",
             "alpha": "blocks.{layer}.hc_{sublayer}.scale"}
    moved = {k: v for k, v in sd.items() if "_hc." not in k}
    for i in range(3):
        for sub in ("attn", "mlp"):
            for k in names:
                moved[names[k].format(layer=i, sublayer=sub)] = sd[
                    hf_import.XING_MHC_NAMES[k].format(layer=i, sublayer=sub)]
    again = hf_import.convert_deepseek_v3_state_dict(
        moved.__getitem__, config, names=names)
    jax.tree_util.tree_map(np.testing.assert_array_equal, again,
                           jax.tree_util.tree_map(np.asarray, params))
    # the importer's tree against the reference from the same tensors
    ids = np.random.default_rng(2).integers(2, 384, 40).tolist()
    want = reference_xing.forward(moved.__getitem__, TINY, ids, names=names)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply(
            {"params": jax.tree_util.tree_map(jnp.asarray, again)},
            jnp.asarray([ids], jnp.int32))[0])
    _close(got, want["logits"], 1e-4)


# -- one stream is the tree and the programs that were there -------------------

def _tiny_presets():
    from benchmark.kinds import lmserve, mlaserve, ssdserve, ssmserve

    return {"olmoe": lmserve.TINY, "jamba": ssmserve.TINY,
            "gigachat": mlaserve.TINY, "nemotron": ssdserve.TINY}


#: leaves of each tiny preset's parameter tree, as the parent commit built it
LEAVES = {"olmoe": 27, "jamba": 62, "gigachat": 52,
          "nemotron": 44}


@pytest.mark.parametrize("family", ["olmoe", "jamba", "gigachat", "nemotron"])
def test_one_stream_is_the_path_that_was_there(family):
    """``hc_mult`` 1 (every configuration the benchmark had): no parameter of
    a hyper-connection in the tree (as many leaves as the parent built), no
    operation of one in the program, and logits that no other ``hc_*`` field
    moves by a bit.  (``tools/lowered_programs.py`` holds the engine's twelve
    programs to the parent's text.)"""
    hf = _tiny_presets()[family]
    over = {}
    dep = hf.get("deployment", {})
    if "router_width" in dep:
        held = hf["n_routed_experts"]
        hf = {**hf, "n_routed_experts": dep["router_width"]}
        over = {"experts_first": dep.get("expert_rank", 0) * held,
                "experts_held": held}
    cfg = hf_import.lm_config_from_hf(hf, max_seq_len=64, **over)
    assert cfg.hc_mult == 1
    model = CausalLM(cfg)
    ids = jnp.asarray(np.random.default_rng(1).integers(2, 384, (2, 24)),
                      jnp.int32)
    params = model.init(jax.random.PRNGKey(0), ids)["params"]
    paths = ["/".join(p.key for p in path) for path, _ in
             jax.tree_util.tree_flatten_with_path(params)[0]]
    assert not [p for p in paths if "_hc" in p]
    assert len(paths) == LEAVES[family]
    text = jax.jit(model.apply).lower({"params": params}, ids).as_text(
        debug_info=True)
    assert "mhc_" not in text
    got = model.apply({"params": params}, ids)
    other = CausalLM(LMConfig.from_dict(
        {**cfg.to_dict(), "hc_sinkhorn_iters": 3, "hc_eps": 0.5,
         "hc_res_clamp": (-1.0, 1.0)}))
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(other.apply({"params": params}, ids)))


def test_the_model_says_which_leaves_a_cast_leaves_float32():
    """``CausalLM.cast_params`` is the one rule a loader casts a tree by (the
    serving deployment calls it and tests no name): every leaf in the dtype
    but a hyper-connection's maps; with one stream, every leaf."""
    from tpu_air.models.lm import modeling

    ids = jnp.zeros((1, 4), jnp.int32)
    four = hf_import.lm_config_from_hf(TINY, max_seq_len=64)
    for cfg, kept in ((four, 3 * 2 * TINY["num_hidden_layers"]),
                      (LMConfig.from_dict({**four.to_dict(), "hc_mult": 1}),
                       0)):
        model = CausalLM(cfg)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), ids))["params"]
        params = jax.tree_util.tree_map(
            lambda a: np.zeros(a.shape, a.dtype), params)
        cast = model.cast_params(params, "bfloat16")
        assert model.cast_params is modeling.cast_params
        leaves = jax.tree_util.tree_flatten_with_path(cast)[0]
        f32 = ["/".join(k.key for k in path) for path, a in leaves
               if a.dtype == jnp.float32]
        assert len(f32) == kept and all("_hc/" in p for p in f32)
        assert all(a.dtype in (jnp.float32, jnp.bfloat16) for _, a in leaves)


# -- the maps ----------------------------------------------------------------

def _raw_maps(rows=50, seed=0, spread=0.5):
    """``H~res`` of the size the seeded maps have: a diagonal of 1.5 and
    ``spread`` around it (at a spread of 1 twenty rounds leave a column sum
    0.0014 off 1, at 0.5 four millionths)."""
    return (1.5 * np.eye(N) + spread * np.random.default_rng(
        seed).standard_normal((rows, N, N))).astype(np.float32)


def test_sinkhorn_rows_and_columns_sum_to_one():
    res = _raw_maps()
    m = np.asarray(mhc.sinkhorn(jnp.asarray(res), 20, 1e-6, (-30.0, 30.0)))
    assert (m >= 0).all()
    np.testing.assert_allclose(m.sum(-1), 1.0, atol=1e-4)
    np.testing.assert_allclose(m.sum(-2), 1.0, atol=1e-4)
    # against the reference's own rounds
    want = np.asarray(reference_xing.sinkhorn(jnp.asarray(res), 20, 1e-6,
                                              -30.0, 30.0))
    np.testing.assert_allclose(m, want, rtol=1e-5, atol=1e-7)


def test_one_sinkhorn_round_differs():
    res = jnp.asarray(_raw_maps())
    one = np.asarray(mhc.sinkhorn(res, 1, 1e-6, (-30.0, 30.0)))
    twenty = np.asarray(mhc.sinkhorn(res, 20, 1e-6, (-30.0, 30.0)))
    # one round leaves the rows at 1 and the columns off it
    np.testing.assert_allclose(one.sum(-1), 1.0, atol=1e-5)
    assert np.abs(one.sum(-2) - 1.0).max() > 0.02
    assert np.abs(one - twenty).max() > 0.01


def test_the_clamp_holds_at_thirty():
    """``exp`` of an entry beyond the clamp is ``exp`` of the clamp: 100 and
    30 give one matrix, -100 and -30 too; without the clamp float32 ``exp``
    overflows at 89."""
    res = _raw_maps(8, 1)
    big = res.copy()
    big[:, 0, 1], big[:, 2, 3] = 100.0, -100.0
    held = res.copy()
    held[:, 0, 1], held[:, 2, 3] = 30.0, -30.0
    got = np.asarray(mhc.sinkhorn(jnp.asarray(big), 20, 1e-6, (-30.0, 30.0)))
    want = np.asarray(mhc.sinkhorn(jnp.asarray(held), 20, 1e-6,
                                   (-30.0, 30.0)))
    assert np.isfinite(got).all()
    np.testing.assert_array_equal(got, want)
    wide = np.asarray(mhc.sinkhorn(jnp.asarray(big), 20, 1e-6,
                                   (-200.0, 200.0)))
    assert not np.isfinite(wide).all()


def test_a_block_against_the_references_layer(tiny):
    """One sparse ``Block`` on a state of four DIFFERENT streams (what a
    layer past the first meets) against the reference's layer: the streams
    behind the layer, and each map on the way."""
    sd, config, model, params = tiny
    ids = np.random.default_rng(4).integers(2, 384, 48).tolist()
    want = _ref(sd, ids, layer_outputs=True)["streams"]
    x = jnp.asarray(want[0])[None]                      # behind layer 0
    pos = jnp.arange(len(ids), dtype=jnp.int32)[None]
    with jax.default_matmul_precision("highest"):
        got = Block(config, "latent", "sparse").apply(
            {"params": params["layer_1"]}, x, pos)
    assert got.shape == (1, len(ids), N, 64)
    np.testing.assert_allclose(np.asarray(got[0]), want[1], rtol=2e-4,
                                atol=2e-5)
    # the maps by themselves, against the reference's
    hc = params["layer_1"]["attn_hc"]
    with jax.default_matmul_precision("highest"):
        h, h_post, res = mhc.pre(x[0], hc["phi"], hc["b"], hc["alpha"], 1e-6)
        h_res = mhc.sinkhorn(res, 20, 1e-6, (-30.0, 30.0))
        r_pre, r_post, r_res = reference_xing.maps(
            TINY, x[0], hc["phi"].T, hc["b"], hc["alpha"])
    np.testing.assert_allclose(h_post, r_post, rtol=1e-5)
    np.testing.assert_allclose(h_res, r_res, rtol=1e-4, atol=1e-6)
    np.testing.assert_allclose(
        h, jnp.einsum("ti,tic->tc", r_pre, x[0]), rtol=1e-4, atol=1e-5)
    # the maps move by token, and the streams mix
    assert np.std(np.asarray(r_post), 0).min() > 0.02
    diag = np.asarray(r_res)[:, np.arange(N), np.arange(N)]
    assert 0.2 < diag.mean() < 0.9


def test_full_forward_matches_the_reference(tiny):
    sd, config, model, params = tiny
    ids = np.random.default_rng(3).integers(2, 384, 90).tolist()
    want = _ref(sd, ids)
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params},
                                     jnp.asarray([ids], jnp.int32))[0])
    _close(got, want["logits"], 1e-4)
    # the check's controls move the reference: one Sinkhorn round, streams
    # that never mix, and (of the latent attention) yarn's softmax factor
    scale = want["logits"].max(-1) - np.median(want["logits"], -1)
    for how in ({"sinkhorn_iters": 1}, {"identity_res": True},
                {"yarn_softmax_scale": False}):
        other = _ref(sd, ids, **how)["logits"]
        assert (np.abs(other - want["logits"]).max(-1) / scale).max() > 1e-3, how


def test_the_reference_of_several_is_the_reference_of_each(tiny):
    sd = tiny[0]
    rng = np.random.default_rng(9)
    a, b = (rng.integers(2, 384, k).tolist() for k in (30, 17))
    jobs = [{"ids": a, "rows": [3, 29]}, {"ids": b, "sinkhorn_iters": 1},
            {"ids": b, "identity_res": True}]
    got = reference_xing.forward_each(sd.__getitem__, TINY, jobs)
    for job, g in zip(jobs, got):
        alone = reference_xing.forward(sd.__getitem__, TINY, **job)
        np.testing.assert_allclose(g["logits"], alone["logits"], rtol=1e-5,
                                   atol=1e-6)
    assert got[0]["logits"].shape == (2, 384)
    assert np.abs(got[1]["logits"] - got[2]["logits"]).max() > 1e-3


# -- through the engine --------------------------------------------------------

def _engine(tiny, **kw):
    from tpu_air.engine import EngineConfig, InferenceEngine

    _, config, model, params = tiny
    cfg = dict(num_slots=4, slot_len=256, page_len=16, max_new_tokens=8,
               eos_token_id=None)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg),
                           auto_start=False)


def _reference_rows(sd, prompt, answer):
    ids = list(prompt) + list(answer[:-1])
    return _ref(sd, ids, range(len(prompt) - 1, len(ids)))["logits"]


def test_chunked_prefill_then_paged_decode_matches_the_reference(tiny):
    """Logits, not tokens: prompts that cross a chunk boundary and end in a
    padded chunk, one that fills its last chunk and one shorter than a chunk,
    through the engine's chunk, mixed and decode bodies over the engine's own
    latent pool; fewer slots than prompts, so one is reused and a row
    mid-prefill rides the steps of the rows before it.  The maps keep no
    state a sequence: the pool is the 16 numbers a position that a
    ``deepseek_v3`` layer caches.  Tolerance 1e-3 of a row's top-to-median
    distance, ``test_gigachat``'s for the same bodies: float32 at the highest
    precision on both sides, the absorbed read and the expanded one adding in
    another order."""
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
    eng.close()
    for p, a, got in zip(prompts, answers, system):
        _close(got, _reference_rows(sd, p, a), 1e-3)
        assert got.argmax(-1).tolist() == a


def test_engine_streams_the_tokens_of_offline_generate(tiny):
    from tpu_air.models.lm.generate import generate

    _, config, model, params = tiny
    rng = np.random.default_rng(6)
    prompts = [rng.integers(2, 384, k).tolist() for k in (21, 40, 5)]
    eng = _engine(tiny)
    got = eng.generate(prompts, 8)
    snap = eng.metrics.snapshot()
    eng.close()
    for p, g in zip(prompts, got):
        want = generate(model, params, np.asarray([p]), max_new_tokens=8)
        assert np.asarray(want)[0].tolist() == g
    # the rows whose streams held a token, a step: every decoded token but a
    # request's first is one live row of one step, and a chunk that rode a
    # step brought its tokens
    assert snap["mhc_streams"] == 4
    decoded = sum(len(g) - 1 for g in got)
    assert snap["mhc_rows_live_alone"] <= snap["mhc_rows_live"]
    assert decoded <= snap["mhc_rows_live"] <= decoded + sum(map(len, prompts))


def test_a_chunk_that_rides_a_step_is_counted_with_its_tokens(tiny):
    rng = np.random.default_rng(8)
    short = [rng.integers(2, 384, 6).tolist() for _ in range(2)]
    long_ = rng.integers(2, 384, 90).tolist()       # six chunks of 16
    eng = _engine(tiny, max_new_tokens=24, prefill_chunks_per_step=1)
    streams = [eng.submit(p, 24) for p in short]
    for _ in range(4):
        eng.step()
    before = eng.metrics.snapshot()
    late = eng.submit(long_, 6)
    while not eng.idle():
        eng.step()
    got = late.result(5)
    snap = eng.metrics.snapshot()
    eng.close()
    fused = snap["chunks_fused"] - before["chunks_fused"]
    assert fused >= 5
    mixed_rows = ((snap["mhc_rows_live"] - snap["mhc_rows_live_alone"])
                  - (before["mhc_rows_live"] - before["mhc_rows_live_alone"]))
    # each fused chunk's tokens (16, the last 10) and two rows decoding
    assert mixed_rows >= (fused - 1) * (16 + 2)
    alone = _engine(tiny)
    assert got == alone.generate([long_], 6)[0]
    alone.close()
    assert all(len(s.result(5)) == 24 for s in streams)
    # an engine of one stream has none of the three counters
    assert "mhc_streams" not in before or before["mhc_streams"] == 4


@pytest.mark.parametrize("case", sorted(_mixed_step_cases.CASES))
def test_mixed_step(tiny, case):
    from tpu_air.models.lm.generate import generate

    _, config, model, params = tiny

    def check(prompt, tokens):
        want = generate(model, params, np.asarray([prompt]),
                        max_new_tokens=len(tokens))
        assert np.asarray(want)[0].tolist() == tokens

    _mixed_step_cases.CASES[case](model, params, check)


# -- the tile, the costs, the configuration file -------------------------------

@pytest.mark.parametrize("shape, want", [
    # olmoe-1b-7b: 2048 x 1024 and back
    ((512, 2048, 1024), (128, 2048, 1024)),
    ((512, 1024, 2048), (128, 1024, 1024)),
    # gigachat3.1-702b-a36b: 7168 x 2048 and back
    ((1024, 7168, 2048), (128, 1024, 2048)),
    ((1024, 2048, 7168), (128, 2048, 1024)),
    # nemotron3-super-120b-a12b: 1024 x 2688 and back
    ((2816, 1024, 2688), (128, 1024, 2688)),
    ((2816, 2688, 1024), (128, 2688, 1024)),
])
def test_the_other_configurations_keep_their_tiles(shape, want):
    assert moe.gmm_tiling(*shape) == want


@pytest.mark.parametrize("m", [256, 1280])
def test_a_side_of_3584_has_a_kernel_tile(m):
    """64 x 4 sorted rows of a decode step, 320 x 4 of a mixed step: both
    products of this model's experts go to the kernel, not ``ragged_dot``."""
    for kdim, n in ((3584, 1024), (1024, 3584)):
        tile = moe.gmm_tiling(m, kdim, n)
        assert tile is not None
        tm, tk, tn = tile
        assert m % tm == 0 and kdim % tk == 0 and n % tn == 0
        assert tk % 128 == 0 and tn % 128 == 0
        # two buffers of a weight tile beside the rows fit the default
        # scoped VMEM (16 MB)
        assert 2 * tk * tn * 2 <= 12 << 20


def _file():
    with open(os.path.join(REPO, "benchmark", "configs",
                           "xing4.0-29b-a4b.json")) as f:
        return json.load(f)


def test_cost_model_prices_the_maps_by_layer():
    from tpu_air.observability.perf import LMCostModel

    from benchmark import weights_xing

    m = LMCostModel(weights_xing.lm_config(_file(), "bfloat16", 8192))
    assert m._attn_params == (3584 * 768 + 768 * 32 * 192 + 3584 * 576
                              + 512 * 32 * 256 + 32 * 128 * 3584)
    assert m._attn_params == pytest.approx(28.41e6, rel=1e-3)
    assert (m.hc_mult, m.n_sublayers) == (4, 10)
    assert m._mhc_params == 10 * (4 * 3584 * 24 + 24 + 3)
    # (2n + 2) C elements of the dtype a row a sublayer
    assert m.mhc_stream_bytes_per_token == 10 * 10 * 3584 * 2
    assert m.mhc_flops_per_token == 10 * 2 * (4 * 3584 * 24 + 8 * 3584
                                              + 16 * 3584)
    # 8.10 GB: the maps are float32 among bfloat16 weights
    assert m.param_bytes == 2 * m.param_count + 2 * m._mhc_params
    assert m.param_bytes == pytest.approx(8.10e9, rel=3e-3)
    one = LMCostModel(LMConfig.from_dict(
        {**weights_xing.lm_config(_file(), "bfloat16", 8192).to_dict(),
         "hc_mult": 1}))
    assert one._mhc_params == 0 and one.mhc_stream_bytes_per_token == 0
    step, plain = m.decode_step_cost(64, 8192), one.decode_step_cost(64, 8192)
    assert step.hbm_bytes - plain.hbm_bytes == pytest.approx(
        64 * m.mhc_stream_bytes_per_token + 4 * m._mhc_params)


def test_config_file_against_lmconfig_key_by_key():
    """benchmark/configs/xing4.0-29b-a4b.json: every published width reaches
    ``LMConfig`` unchanged; the two reduced keys are stated with their
    published values; what ``config.json`` does not give is under
    ``assumed``; the deployment is stated; the cut's bytes follow from the
    file's own numbers."""
    from benchmark import weights_xing

    hf = _file()
    with open("/opt/skills/guides/model-configs/architectures.jsonl") as f:
        row = next(r for r in map(json.loads, f)
                   if r["name"] == "Xing4.0-29B-A4B")
    assert hf["source"] == row["source_url"]
    assert set(hf["reduced"]) == {"num_hidden_layers", "first_k_dense_replace"}
    for key, value in row["config"].items():
        if key in hf["reduced"]:
            assert hf["reduced"][key]["published"] == value
            assert hf["reduced"][key]["held"] == hf[key]
        else:
            assert hf[key] == value, key
    cfg = weights_xing.lm_config(hf, "bfloat16", 8192)
    assert (cfg.d_model, cfg.n_heads, cfg.head_dim, cfg.v_head_dim) == (
        3584, 32, 192, 128)
    assert (cfg.q_lora_rank, cfg.kv_lora_rank, cfg.qk_nope_head_dim,
            cfg.qk_rope_head_dim) == (768, 512, 128, 64)
    assert (cfg.dense_d_ff, cfg.d_ff, cfg.num_experts, cfg.experts_held,
            cfg.num_experts_per_tok, cfg.num_shared_experts) == (
        9216, 1024, 64, 64, 4, 1)
    assert (cfg.router_groups, cfg.router_topk_groups, cfg.router_scale) == (
        1, 1, 2)
    assert (cfg.vocab_size, cfg.n_layers, cfg.first_dense_layers) == (
        131072, 5, 1)
    assert (cfg.rope_factor, cfg.rope_original_len, cfg.rope_theta) == (
        64, 4096, 10000)
    assert (cfg.hc_mult, cfg.hc_sinkhorn_iters, cfg.hc_eps,
            cfg.hc_res_clamp) == (4, 20, 1e-6, (-30.0, 30.0))
    assert cfg.latent_row_width == 640 and cfg.holds_all_experts
    assert set(hf["assumed"]) >= {
        "eos_token_id", "pad_token_id", "initializer_range", "router_init",
        "multi_token_prediction", "rope_pairing", "dtype",
        "mhc_sinkhorn_order", "mhc_eps", "mhc_clamp", "mhc_rms",
        "mhc_entry_exit", "mhc_tensor_names", "mhc_init"}
    assert hf["assumed"]["mhc_tensor_names"] == hf_import.XING_MHC_NAMES
    dep = hf["deployment"]
    assert dep["expert_parallel"] == 1 and dep["vocab_parallel"] == 1
    # the cut's bytes from the file's own numbers
    d, v = hf["hidden_size"], hf["vocab_size"]
    h, dn, dr, dv = (hf["num_attention_heads"], hf["qk_nope_head_dim"],
                     hf["qk_rope_head_dim"], hf["v_head_dim"])
    attn = (d * hf["q_lora_rank"] + hf["q_lora_rank"] * h * (dn + dr)
            + d * (hf["kv_lora_rank"] + dr)
            + hf["kv_lora_rank"] * h * (dn + dv) + h * dv * d)
    expert = 3 * d * hf["moe_intermediate_size"]
    maps = 2 * (hf["hc_mult"] * d * 24 + 27)
    sparse = (attn + (hf["n_routed_experts"] + hf["n_shared_experts"])
              * expert + d * hf["n_routed_experts"])
    dense = attn + 3 * d * hf["intermediate_size"]
    layers = hf["num_hidden_layers"]
    weights = (2 * (dense + (layers - 1) * sparse + 2 * v * d)
               + 4 * layers * maps)
    assert weights == pytest.approx(8.10e9, rel=3e-3)
    assert 2 * sparse + 4 * maps == pytest.approx(1.490e9, rel=2e-3)
    pool = 64 * 8192 * 640 * 2 * layers
    assert pool == pytest.approx(3.36e9, rel=2e-3)
    params = jax.eval_shape(lambda: CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    assert sum(a.size * a.dtype.itemsize if a.dtype == jnp.float32 and
               "hc" in "/".join(p.key for p in path) else a.size * 2
               for path, a in
               jax.tree_util.tree_flatten_with_path(params)[0]) == (
        pytest.approx(weights, rel=1e-3))
