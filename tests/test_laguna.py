"""Laguna (``model_type: laguna``: window layers that keep a ring of
positions a slot beside full layers that keep pages, query heads counted by
layer kind, half a head turning under yarn on the full kind, a gate a head,
sigmoid routing over experts all held) through ``CausalLM``, the importer and
``InferenceEngine``, against the plain float32 reference on seeded weights in
the published layout, at a small size on the CPU: F-dense S S S F, a window
of 8, 6 / 8 query heads on 2 K/V heads, 16 experts top-4 and a shared one."""

import json
import os
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_air.engine import EngineConfig, InferenceEngine
from tpu_air.models.lm import hf_import, reference_laguna
from tpu_air.models.lm.config import LMConfig
from tpu_air.models.lm.generate import generate
from tpu_air.models.lm.modeling import CausalLM
from tpu_air.models.lm import paged_cache

import _mixed_step_cases

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
WINDOW = 8


def _tiny():
    from benchmark.kinds.swaserve import TINY

    return {k: v for k, v in TINY.items() if k != "assumed"}


TINY = _tiny()


def published(cfg=TINY, seed=0):
    """``get(name)``: seeded tensors in the published layout under the
    importer's names; norm weights and the selection bias are NOT ones and
    zeros, so a tensor that went to the wrong place moves the logits."""
    from benchmark import weights_swa

    shapes = weights_swa.Published(
        {**cfg, "assumed": {"tensor_names": hf_import.LAGUNA_NAMES}}, seed,
        "float32").shape

    def get(name):
        rng = np.random.default_rng([seed, zlib.crc32(name.encode())])
        shape = shapes(name)
        if name.endswith("norm.weight"):
            return (1 + 0.1 * rng.standard_normal(shape)).astype(np.float32)
        if name.endswith("bias"):
            return (0.05 * rng.standard_normal(shape)).astype(np.float32)
        scale = 0.3 if name.endswith("g_proj.weight") else 0.08
        return (scale * rng.standard_normal(shape)).astype(np.float32)

    return get


def _build(get, **kw):
    config = hf_import.lm_config_from_hf(TINY, max_seq_len=256, **kw)
    params = jax.tree_util.tree_map(
        jnp.asarray, hf_import.convert_laguna_state_dict(get, config))
    return config, CausalLM(config), params


@pytest.fixture(scope="module")
def tiny():
    get = published()
    return (get,) + _build(get)


def _ref(get, ids, rows=None, **how):
    return reference_laguna.forward(get, TINY, ids, rows, **how)


def _close(got, want, tol):
    """Every row within ``tol`` of its top-to-median distance."""
    scale = want.max(-1) - np.median(want, -1)
    err = np.abs(np.asarray(got) - want).max(-1) / scale
    assert err.max() <= tol, err.max()


def _engine(tiny, **kw):
    _, _, model, params = tiny
    cfg = dict(num_slots=4, slot_len=64, page_len=4, max_new_tokens=8,
               eos_token_id=None)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg),
                           auto_start=False)


# -- the configuration and the importer ----------------------------------------

def test_config_maps_the_published_keys():
    c = hf_import.lm_config_from_hf(TINY)
    assert c.layer_kinds() == ["attention", "window", "window", "window",
                               "attention"]
    assert c.ff_kinds() == ["dense"] + ["sparse"] * 4
    assert (c.n_heads, c.window_n_heads, c.n_kv_heads, c.head_dim) == (
        6, 8, 2, 16)
    assert (c.heads_of("attention"), c.heads_of("window")) == (6, 8)
    assert c.rope_of("attention") == (500000, 8)
    assert c.rope_of("window") == (10000, 16)
    assert (c.rope_factor, c.rope_original_len, c.rope_beta_fast) == (4, 16, 8)
    assert c.rope_mscale == pytest.approx(1.0)
    assert (c.sliding_window, c.attn_gate, c.router) == (8, "per_head",
                                                         "sigmoid_groups")
    assert (c.d_ff, c.dense_d_ff, c.shared_d_ff, c.num_shared_experts) == (
        32, 128, 32, 1)
    assert (c.router_scale, c.first_dense_layers, c.tie_embeddings) == (
        2.5, 1, False)
    assert c.keeps_slot_rows and not c.has_recurrent_layers
    # whole chunks, and a chunk written first leaves its first query the
    # window behind it
    assert [c.window_ring_len(n) for n in (1, 4, 8, 16)] == [8, 12, 16, 32]
    assert LMConfig(sliding_window=512).window_ring_len(256) == 768
    # a checkpoint's JSON hands the list back
    again = LMConfig.from_dict(json.loads(c.to_json()))
    assert again.layer_kinds() == c.layer_kinds()
    # the sibling's spelling of the gate
    assert hf_import.lm_config_from_hf(
        {**TINY, "gating": "per-head"}).attn_gate == "per_head"


@pytest.mark.parametrize("change, says", [
    ({"layer_mixers": ("attention", "window")}, "layer_mixers"),
    ({"layer_mixers": ("attention", "ring", "window", "window", "window")},
     "layer_mixers"),
    ({"rope_theta": None}, "a rope"),
    ({"sliding_window": 0}, "sliding_window"),
    ({"window_n_heads": 7}, "window_n_heads"),
    ({"rope_fraction": 0.45}, "rope_fraction"),
    ({"attn_gate": "per_token"}, "attn_gate"),
    ({"layer_pattern": "M*EEE"}, "layer_mixers"),
])
def test_lmconfig_refuses_what_it_cannot_be(change, says):
    fields = hf_import.lm_config_from_hf(TINY).to_dict()
    with pytest.raises(ValueError, match=says):
        LMConfig(**{**fields, **change})


@pytest.mark.parametrize("change, says", [
    ({"num_attention_heads_per_layer": [6, 8, 8]}, "entries"),
    ({"num_attention_heads_per_layer": [6, 8, 8, 6, 6]}, "one count a kind"),
    ({"num_attention_heads_per_layer": [8, 8, 8, 8, 8]}, "one count a kind"),
    ({"layer_types": ["full_attention", "chunked_attention"] * 2
      + ["full_attention"]}, "layer_types"),
    ({"mlp_layer_types": ["sparse", "dense", "sparse", "sparse", "sparse"]},
     "leading dense"),
    ({"gating": False}, "gating"),
    ({"attention_bias": True}, "attention_bias"),
    ({"norm_topk_prob": False}, "norm_topk_prob"),
    ({"moe_router_logit_softcapping": 30}, "softcapping"),
])
def test_the_importer_refuses_what_the_layer_does_not_compute(change, says):
    with pytest.raises(ValueError, match=says):
        hf_import.lm_config_from_hf({**TINY, **change})


def test_importer_takes_the_laguna_names(tiny):
    get, config, _, params = tiny
    d, hd = 64, 16
    for i, heads in enumerate(TINY["num_attention_heads_per_layer"]):
        attn = params[f"layer_{i}"]["attn"]
        assert attn["q"]["kernel"].shape == (d, heads * hd)
        assert attn["k"]["kernel"].shape == (d, 2 * hd)
        assert attn["o"]["kernel"].shape == (heads * hd, d)
        assert attn["gate"]["kernel"].shape == (d, heads)
        turned = 8 if heads == 6 else 16
        q = np.asarray(get(f"model.layers.{i}.self_attn.q_proj.weight")).T
        ours = np.asarray(attn["q"]["kernel"])
        # inside the numbers that turn, published j and j + turned/2 lie
        # side by side; the rest of the head stays
        np.testing.assert_array_equal(ours[:, 0], q[:, 0])
        np.testing.assert_array_equal(ours[:, 1], q[:, turned // 2])
        np.testing.assert_array_equal(ours[:, hd + 2], q[:, hd + 1])
        if turned < hd:
            np.testing.assert_array_equal(ours[:, turned:hd], q[:, turned:hd])
    moe = params["layer_2"]["moe"]
    assert moe["gate"].shape == (16, 64, 32) and moe["down"].shape == (
        16, 32, 64)
    assert moe["router"].shape == (64, 16) and moe["router_bias"].shape == (
        16,)
    assert set(params["layer_0"]) == {"attn", "attn_norm", "mlp", "mlp_norm"}
    assert set(params["layer_1"]) == {"attn", "attn_norm", "moe", "shared",
                                      "mlp_norm"}
    fresh = CausalLM(config).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))["params"]
    assert (jax.tree_util.tree_map(lambda a: a.shape, fresh)
            == jax.tree_util.tree_map(lambda a: a.shape, params))
    # other names, handed over
    renamed = {**hf_import.LAGUNA_NAMES,
               "g": "model.layers.{i}.self_attn.gate_proj.weight"}
    other = lambda name: get(name.replace(  # noqa: E731
        "self_attn.gate_proj", "self_attn.g_proj"))
    again = hf_import.convert_laguna_layer(other, 1, config, renamed)
    np.testing.assert_array_equal(again["attn"]["gate"]["kernel"],
                                  params["layer_1"]["attn"]["gate"]["kernel"])


def test_the_cost_model_counts_the_tree(tiny):
    from tpu_air.observability.perf import LMCostModel

    _, config, _, params = tiny
    cost = LMCostModel(config)
    norms = sum(v.size for path, v in
                jax.tree_util.tree_flatten_with_path(params)[0]
                if path[-1].key in ("weight", "router_bias"))
    assert cost.param_count == sum(
        v.size for v in jax.tree_util.tree_leaves(params)) - norms
    assert (cost.n_attn_layers, cost.n_window_layers) == (2, 3)
    # a token deep in its context reads the window of a ring and no more
    far, near = cost.decode_step_cost(1, 4096), cost.decode_step_cost(1, 8)
    per = 2 * 2 * 16 * 4
    assert far.hbm_bytes - near.hbm_bytes == pytest.approx(
        2 * per * (4096 - 8))


# -- the forward pass ------------------------------------------------------------

def test_full_forward_matches_the_reference(tiny):
    get, _, model, params = tiny
    ids = np.random.default_rng(1).integers(2, 384, (2, 41))
    with jax.default_matmul_precision("highest"):
        ours = np.asarray(model.apply({"params": params}, jnp.asarray(ids)))
    for row, got in zip(ids, ours):
        _close(got, _ref(get, row)["logits"], 1e-4)


CONTROLS = {
    "window_mask": dict(window_mask=False),
    "gate": dict(gate=False),
    "rope_whole_head": dict(rope_whole_head=True),
    "attention_factor": dict(attention_factor=False),
    "low_precision": None,
    "another_ring": None,
}


@pytest.mark.parametrize("control", sorted(CONTROLS))
def test_each_control_moves_the_logits_past_the_tolerance(tiny, control):
    """What the chip's check plants (``benchmark/worker_hooks_swa.py``): the
    reference computed as a system at fault would reads far over the 1e-4
    the system is held to here, at a position past the window."""
    from benchmark.worker_hooks_mla import round_mantissa

    get = tiny[0]
    rng = np.random.default_rng(2)
    ids, other = (rng.integers(2, 384, 40).tolist() for _ in range(2))
    want = _ref(get, ids)["logits"]
    if control == "low_precision":
        got = _ref(get, ids, round_inputs=round_mantissa(3),
                   rounded_precision="default")["logits"]
    elif control == "another_ring":
        got = reference_laguna.forward_each(
            get, TINY, [{"ids": other}, {"ids": ids, "ring_of": 0}]
        )[1]["logits"]
    else:
        got = _ref(get, ids, **CONTROLS[control])["logits"]
    scale = want.max(-1) - np.median(want, -1)
    err = np.abs(got - want).max(-1) / scale
    assert np.median(err[WINDOW:]) > 0.02, np.median(err)
    if control == "window_mask":
        # the first window of positions sees the same keys either way
        assert err[:WINDOW].max() < 1e-5


def test_the_reference_of_several_is_the_reference_of_each(tiny):
    get = tiny[0]
    rng = np.random.default_rng(3)
    seqs = [rng.integers(2, 384, n).tolist() for n in (30, 30, 17)]
    both = reference_laguna.forward_each(
        get, TINY, [{"ids": s, "rows": [len(s) - 1, 3]} for s in seqs])
    for s, got in zip(seqs, both):
        alone = _ref(get, s)
        np.testing.assert_allclose(got["logits"],
                                   alone["logits"][[len(s) - 1, 3]],
                                   atol=1e-6)
        np.testing.assert_allclose(got["router_gap"], alone["router_gap"],
                                   atol=1e-7)
    assert (both[0]["router_gap"] >= 0).all()


def test_plain_generate_gives_the_references_tokens(tiny):
    get, _, model, params = tiny
    rng = np.random.default_rng(4)
    for n in (5, WINDOW, 3 * WINDOW + 1):
        p = rng.integers(2, 384, n).tolist()
        with jax.default_matmul_precision("highest"):
            got = np.asarray(generate(model, params, np.asarray([p]),
                                      max_new_tokens=12))[0].tolist()
        rows = _ref(get, p + got[:-1])["logits"][n - 1:]
        assert rows.argmax(-1).tolist() == got


# -- the engine's cache: rings beside pages -----------------------------------------

def _reference_rows(get, prompt, answer):
    ids = list(prompt) + list(answer[:-1])
    return _ref(get, ids, range(len(prompt) - 1, len(ids)))["logits"]


@pytest.mark.parametrize("page_len", [4, 16])
def test_chunks_then_decode_through_the_engines_cache_match_the_reference(
        tiny, page_len):
    """Logits, not tokens: prompts under, at and several times over the
    window, across chunk boundaries, ending in a padded chunk or filling
    their last one, through the engine's chunk and decode bodies over the
    engine's own pages and rings; fewer slots than prompts, so a ring is
    reused with its former tenant's positions in it and a row mid-prefill
    rides the steps of the rows before it with its ring write dropped.
    Chunks of 4 (a ring of 12) and of 16 (a chunk longer than the window, a
    ring of 32)."""
    from benchmark.worker_hooks_swa import replayed_logits

    get = tiny[0]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 384, k).tolist()
               for k in (3, WINDOW, 13, 29, 32, 50, 6)]
    eng = _engine(tiny, page_len=page_len, slot_len=64)
    ring = tiny[1].window_ring_len(page_len)
    for i, kind in enumerate(tiny[1].layer_kinds()):
        layer = eng.cache[f"layer_{i}"]["attn"]
        if kind == "window":
            assert set(layer) == {"window_key", "window_value", "cache_index",
                                  "state_row", "valid_len"}
            assert layer["window_key"].shape == (4, ring, 32)
        else:
            assert set(layer) == {"cached_key", "cached_value", "cache_index",
                                  "block_table"}
    answers = eng.generate(prompts, 8)
    with jax.default_matmul_precision("highest"):
        system = replayed_logits(eng, prompts, answers, [2, 0, 3])
    assert eng.generate(prompts, 8) == answers
    eng.close()
    for p, a, got in zip(prompts, answers, system):
        _close(got, _reference_rows(get, p, a), 1e-3)
        assert got.argmax(-1).tolist() == a


@pytest.mark.parametrize("slot_len", [32, 64, 128])
def test_ring_leaves_do_not_grow_with_slot_len(tiny, slot_len):
    _, config, model, _ = tiny
    cache = paged_cache.init_paged_cache(
        model, 3, 3 * slot_len // 4 + 1, 4, slot_len // 4)
    rings = [leaf for _, layer in paged_cache.layers(cache)
             for name, leaf in layer.items() if name.startswith("window_")]
    assert len(rings) == 6 and {r.shape for r in rings} == {(3, 12, 32)}
    assert paged_cache.window_ring_bytes(cache) == 6 * 3 * 12 * 32 * 4
    assert paged_cache.recurrent_state_bytes(cache) == 0
    assert paged_cache.page_pool_bytes(cache) == (
        4 * (3 * slot_len // 4 + 1) * 4 * 32 * 4)
    assert paged_cache.layer_kind(cache["layer_1"]["attn"]) == "window"
    assert paged_cache.SHARD_AXES["window_key"] == ()


def test_engine_streams_the_tokens_of_offline_generate_and_counts_by_kind(
        tiny):
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
    assert snap["window_len"] == WINDOW
    assert snap["window_ring_bytes"] == 3 * 2 * 4 * 12 * 32 * 4
    assert snap["kv_page_bytes"] == 2 * 2 * (4 * 16 + 1) * 4 * 32 * 4
    assert snap["window_ring_bytes_as_pages"] == snap["kv_page_bytes"] * 3 // 2
    # a decoded token but a request's first is one live row of one step: it
    # reads all it holds of the two page layers, the window of three rings
    steps = [(len(p) + j) for p, g in zip(prompts, got)
             for j in range(1, len(g))]
    assert snap["kv_page_positions_live"] == 2 * sum(steps)
    assert snap["window_positions_live"] == 3 * sum(
        min(n, WINDOW) for n in steps)
    assert (snap["window_positions_live_alone"]
            <= snap["window_positions_live"])
    assert "ssm_state_bytes" not in snap


def test_a_reused_slot_never_sees_its_former_tenants_ring(tiny):
    """One slot: a long request fills the ring, and the short ones after it
    (shorter than the ring, shorter than a chunk) stream what a fresh engine
    streams."""
    rng = np.random.default_rng(7)
    long_, *short = [rng.integers(2, 384, k).tolist() for k in (45, 2, 9, 5)]
    eng = _engine(tiny, num_slots=1)
    eng.generate([long_], 8)
    got = [eng.generate([p], 8)[0] for p in short]
    eng.close()
    for p, g in zip(short, got):
        fresh = _engine(tiny, num_slots=1)
        assert fresh.generate([p], 8)[0] == g
        fresh.close()


def test_a_row_that_ended_on_eos_leaves_nothing_behind(tiny):
    """A row that ends on EOS in step N rides step N+1 once: its write lands
    in its own ring, past what any later tenant's window reaches."""
    _, _, model, params = tiny
    rng = np.random.default_rng(8)
    prompts = [rng.integers(2, 384, k).tolist() for k in (11, 19, 7, 23)]
    plain = _engine(tiny, num_slots=2)
    want = plain.generate(prompts, 8)
    plain.close()
    eos = want[0][3]
    eng = _engine(tiny, num_slots=2, eos_token_id=eos)
    got = eng.generate(prompts, 8)
    eng.close()
    for w, g in zip(want, got):
        cut = w.index(eos) + 1 if eos in w else len(w)
        assert g == w[:cut]


def test_preemption_and_resume_by_re_prefill_are_token_identical(tiny):
    """A model with rings cannot ship its pages (refused by name), so a
    preempted stream resumes by re-prefill: the prompt and what was streamed
    go in as a prompt on another engine, whose continuation is the
    uninterrupted stream's, ring wrapped or not."""
    rng = np.random.default_rng(9)
    prompts = [rng.integers(2, 384, k).tolist() for k in (30, 6)]
    whole = _engine(tiny, max_new_tokens=24)
    want = whole.generate(prompts, 24)
    whole.close()
    first = _engine(tiny, max_new_tokens=24)
    streams = [first.submit(p, 24) for p in prompts]
    for _ in range(14):
        first.step()
    first.preempt()
    first._settle()
    so_far = [s.tokens_so_far() for s in streams]
    assert all(0 < len(t) < 24 for t in so_far)
    first.close()
    second = _engine(tiny, max_new_tokens=24)
    resumed = [second.submit(p + t, 24 - len(t))
               for p, t in zip(prompts, so_far)]
    while not second.idle():
        second.step()
    rest = [s.result(5) for s in resumed]
    second.close()
    assert [t + r for t, r in zip(so_far, rest)] == want


@pytest.mark.parametrize("entry", ["submit_prefilled", "migrate_out",
                                   "submit_migrated", "PrefillWorker",
                                   "MeshEngine"])
def test_what_ships_pages_alone_is_refused_by_name(tiny, entry):
    from tpu_air.engine import RecurrentStateUnsupported

    _, _, model, params = tiny
    if entry in ("PrefillWorker", "MeshEngine"):
        import test_paged_cache

        build = {"PrefillWorker": test_paged_cache._worker_builds,
                 "MeshEngine": test_paged_cache._mesh_engine_builds}[entry]
        with pytest.raises(RecurrentStateUnsupported, match="M4"):
            build(model, params)
        return
    eng = _engine(tiny)
    call = {"submit_prefilled": lambda: eng.submit_prefilled(
                [3, 4, 5], 7, {}),
            "migrate_out": eng.migrate_out,
            "submit_migrated": lambda: eng.submit_migrated({})}[entry]
    with pytest.raises(RecurrentStateUnsupported, match="window rings"):
        call()
    eng.close()


def test_prefix_sharing_is_off_by_the_model(tiny):
    eng = _engine(tiny, prefix_cache=True)
    prompt = list(range(2, 30))
    a = eng.generate([prompt], 4)
    b = eng.generate([prompt], 4)
    snap = eng.metrics.snapshot()
    eng.close()
    assert a == b
    assert eng.pool.prefix is None
    assert snap["prefix_cache_disabled_by_model"] is True
    quiet = _engine(tiny, prefix_cache=False)
    assert quiet.metrics.snapshot()[
        "prefix_cache_disabled_by_model"] is False
    quiet.close()


@pytest.mark.parametrize("case", sorted(_mixed_step_cases.CASES))
def test_mixed_step(tiny, case):
    _, config, model, params = tiny

    def check(prompt, tokens):
        want = generate(model, params, np.asarray([prompt]),
                        max_new_tokens=len(tokens))
        assert np.asarray(want)[0].tolist() == tokens

    _mixed_step_cases.CASES[case](model, params, check)


def test_a_window_layer_is_dense_and_takes_no_sequence_axis(tiny):
    """Neither the ring over a mesh axis nor the flash kernel takes a
    window: the configuration refuses the first, and a window layer's
    full-sequence pass is the einsum whatever the length."""
    _, config, model, params = tiny
    with pytest.raises(ValueError, match="window layer is dense"):
        LMConfig.from_dict({**config.to_dict(), "sequence_axis": "sequence"})
    long_ = CausalLM(LMConfig.from_dict(
        {**config.to_dict(), "max_seq_len": 1024}))
    text = jax.jit(lambda p, x: long_.apply({"params": p}, x)).lower(
        params, jnp.zeros((1, 1024), jnp.int32)).as_text()
    assert "tpu_custom_call" not in text and "pallas" not in text


# -- the tile -------------------------------------------------------------------

@pytest.mark.parametrize("shape, want", [
    # laguna-xs.2: 256 experts of 2048 x 512 and back, a decode step's 512
    # sorted rows and a mixed step's 2,560: the first tile, cut to the matrix
    ((512, 2048, 512), (128, 2048, 512)),
    ((2560, 2048, 512), (128, 2048, 512)),
    ((512, 512, 2048), (128, 512, 1024)),
    ((2560, 512, 2048), (128, 512, 1024)),
])
def test_experts_of_2048_by_512_take_the_first_tile(shape, want):
    from tpu_air.ops import moe

    assert moe.gmm_tiling(*shape) == want
    assert want[1] <= moe.GMM_TILING[1] and want[2] <= moe.GMM_TILING[2]


# -- the configuration file ----------------------------------------------------

def test_config_file_against_lmconfig_key_by_key():
    """``benchmark/configs/laguna-xs.2.json``: every published key the
    importer maps lands on its ``LMConfig`` field, the lists are cut with the
    depth, and the tree it makes is the 7.74 GB the file states."""
    from benchmark import costs_swa, weights_swa

    with open(os.path.join(REPO, "benchmark", "configs",
                           "laguna-xs.2.json")) as f:
        cfg = json.load(f)
    c = weights_swa.lm_config(cfg, "bfloat16", 4096)
    for theirs, ours in hf_import.LAGUNA_KEYS.items():
        assert getattr(c, ours) == cfg[theirs], theirs
    assert c.layer_kinds() == ["attention", "window", "window", "window",
                               "attention"]
    assert (c.n_heads, c.window_n_heads, c.n_kv_heads, c.head_dim) == (
        48, 64, 8, 128)
    assert (c.rope_factor, c.rope_original_len, c.rope_beta_fast,
            c.rope_beta_slow) == (64, 4096, 64, 1)
    assert c.rope_mscale == pytest.approx(1.0)
    assert c.rope_of("attention") == (500000, 64)
    assert c.rope_of("window") == (10000, 128)
    assert c.window_ring_len(256) == 768
    assert set(cfg["reduced"]) == {
        "num_hidden_layers", "layer_types", "mlp_layer_types",
        "num_attention_heads_per_layer"}
    assert cfg["reduced"]["num_hidden_layers"]["published"] == 40
    assert cfg["assumed"]["tensor_names"] == hf_import.LAGUNA_NAMES
    shapes = jax.eval_shape(lambda: CausalLM(c).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32)))["params"]
    count = sum(x.size for x in jax.tree_util.tree_leaves(shapes))
    assert 2 * count == pytest.approx(7.74e9, rel=0.01)
    assert costs_swa.param_count(cfg) == pytest.approx(count, rel=1e-4)
