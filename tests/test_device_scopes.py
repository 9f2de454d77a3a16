"""Which part of the model a device operation belongs to (PR 38): the wire
reader of a capture's event metadata (``benchmark/scopes.py``), the scope
vocabulary of docs/OBSERVABILITY.md held against the programs compiled here,
and the one reader of the ten per-scope shares.  CPU only."""

import collections
import json
import os
import re

import jax
import jax.numpy as jnp
import pytest

from benchmark import manifest, scopes, xplane
from benchmark.readers import scope_share

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
DATA = os.path.join(REPO, "benchmark", "tests", "data")
US = 1e-6

# -- the wire reader on the checked-in captures -----------------------------


def by_name(plane, name):
    return [md for md in plane.metadata.values()
            if xplane.op_name(md["name"]) == name]


def test_wire_reader_gets_what_the_compiler_knew_of_fusion_3():
    planes = scopes.read(os.path.join(DATA, "v5e_small.xplane.pb"))
    assert sorted(planes) == [0]
    plane = planes[0]
    (md,) = by_name(plane, "fusion.3")
    assert md["tf_op"] == "jit(f)/dot_general:"
    assert md["hlo_category"] == "convolution fusion"
    assert md["flops"] == 17196646400
    assert md["bytes_accessed"] == 25165824
    assert md["program_id"] == 4414572259253011979 == plane.program_id("f")
    assert [plane.metadata[i]["name"] for i, _, _ in plane.modules] == [
        "jit_f(4414572259253011979)"] * 2
    assert plane.program_id("g") is None


def test_wire_reader_agrees_with_profile_data_on_the_events():
    from jax.profiler import ProfileData

    path = os.path.join(DATA, "v5e_small_4chip.xplane.pb")
    planes = scopes.read(path)
    assert sorted(planes) == [0, 1, 2, 3]
    summary = xplane.reduce_profile(ProfileData.from_file(path))
    for dev, plane in planes.items():
        mine = sorted((xplane.op_name(plane.metadata[i]["name"]), s, e)
                      for i, s, e in plane.ops)
        theirs = sorted(summary.devices[dev].ops)
        assert [n for n, _, _ in mine] == [n for n, _, _ in theirs]
        for (_, s, e), (_, s2, e2) in zip(mine, theirs):
            assert s == pytest.approx(s2, abs=2e-9)
            assert e == pytest.approx(e2, abs=2e-9)
    (reduce_,) = by_name(planes[0], "all-reduce")
    assert reduce_["tf_op"] == "jit(g)/reduce_sum:"
    assert reduce_["program_id"] == planes[0].program_id("g")
    assert scopes.read(path) is planes        # parsed once a process


# -- path normalisation --------------------------------------------------------

T5 = "T5ForConditionalGeneration"


@pytest.mark.parametrize("tf_op, want", [
    (f"jit(loss)/transpose(jvp({T5}))/{T5}.decode/decoder/layer_1/cross_attn"
     "/attn_softmax/reduce_max",
     [T5, f"{T5}.decode", "decoder", "layer_1", "cross_attn", "attn_softmax"]),
    ("jit(f)/dot_general:", []),
    ("", []),
    ("jit(train_step)/jvp(loss)/jit(log_softmax)/exp:",
     ["loss", "jit(log_softmax)"]),
    ("jit(train_step)/jit(main)/optimizer/mul:", ["optimizer"]),
    ("jit(train_step)/vmap()/while/body/closed_call/xor:",
     ["while", "body", "closed_call"]),
    (f"jit(generate_fn)/while/body/{T5}.decode/decoder/self_attn/kv_append"
     "/dynamic_update_slice:fusion",
     ["while", "body", f"{T5}.decode", "decoder", "self_attn", "kv_append"]),
    # two instructions merged into one: the first path
    ("jit(s)/M/attn/attn_scores/add;jit(s)/M/attn/add:", ["M", "attn",
                                                          "attn_scores"]),
])
def test_components(tf_op, want):
    assert scopes.components(tf_op) == want


@pytest.mark.parametrize("parts, covered, path", [
    ([T5, f"{T5}.encode", "encoder", "layer_11", "self_attn", "attn_softmax"],
     True, "encoder/*/self_attn/attn_softmax"),
    (["while", "body", "closed_call"], False, "(unscoped)"),
    (["cond", "branch_1_fun", "custom_vjp_call_jaxpr"], False, "(unscoped)"),
    ([T5, f"{T5}._head", "bqhd,bkhd->bhqk", "jit(_where)"], False,
     "(unscoped)"),
    (["loss", "jit(log_softmax)"], True, "loss"),
    ([], False, "(unscoped)"),
])
def test_parts_and_unscoped(parts, covered, path):
    assert scopes.covered(parts) is covered
    assert scopes.part_path(parts) == path


def test_part_path_depth():
    parts = ["M", "decoder", "layer_3", "cross_attn", "decode_attention"]
    assert scopes.part_path(parts, depth=2) == "decoder/*"


# -- the vocabulary against the programs compiled here -------------------------


def _words(compiled):
    """The vocabulary words found as path components of a compiled
    program's ``op_name`` metadata, each with the components around it."""
    found = collections.defaultdict(set)
    for name in set(re.findall(r'op_name="([^"]*)"', compiled.as_text())):
        parts = scopes.components(name)
        for word in WORDS:
            if word in parts:
                found[word].update(parts)
    return found


def _t5_train_step():
    import optax

    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.train.t5_trainer import dropout_key, make_train_step

    cfg = T5Config.tiny()
    cfg.dropout_rate = 0.1
    cfg.tie_word_embeddings = True      # the untied head is a module
    model = T5ForConditionalGeneration(cfg)
    one = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), one, one, one[:, :4])["params"]
    tx = optax.chain(optax.clip_by_global_norm(1.0), optax.adamw(1e-3))
    ids = jnp.ones((2, 16), jnp.int32)
    batch = {"input_ids": ids, "attention_mask": ids, "labels": ids[:, :8]}
    return make_train_step(model, tx).lower(
        params, tx.init(params), batch, dropout_key(0)).compile()


def _t5_slots(rows=None, admit=None):
    """One of ``T5Engine``'s programs over four slots: the step over the
    first ``rows`` of them, or the admit program of length ``admit``."""
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.models.t5.generate import (init_slot_state, make_t5_admit_fn,
                                            make_t5_slot_step_fn)

    model = T5ForConditionalGeneration(T5Config.tiny())
    one = jnp.ones((1, 8), jnp.int32)
    params = model.init(jax.random.PRNGKey(0), one, one, one[:, :4])["params"]
    state, tok = jax.eval_shape(
        lambda p: init_slot_state(model, p, 4, 9, 16), params)
    if admit is None:
        return make_t5_slot_step_fn(model, rows).lower(
            params, state, tok).compile()
    return make_t5_admit_fn(model, admit).lower(
        params, state, tok,
        jax.ShapeDtypeStruct((1, admit + 2), jnp.int32)).compile()


def _lm_paged_step():
    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.models.lm.generate import (init_paged_cache,
                                            make_lm_paged_decode_step_fn)

    cfg = LMConfig(vocab_size=96, d_model=32, n_layers=2, n_heads=2,
                   head_dim=16, d_ff=64, max_seq_len=32, num_experts=8,
                   num_experts_per_tok=2)
    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
    slots, slot_len, page = 3, 32, 8
    npg = slot_len // page
    cache = jax.eval_shape(
        lambda: init_paged_cache(model, slots, 1 + slots * npg, page, npg))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return make_lm_paged_decode_step_fn(model, slot_len).lower(
        params, cache, i32(slots), i32(slots), i32(slots, npg)).compile()


def _lm_prefill_chunk():
    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.models.lm.generate import (init_paged_cache,
                                            make_lm_prefill_chunk_fn)

    cfg = LMConfig(vocab_size=96, d_model=32, n_layers=2, n_heads=2,
                   head_dim=16, d_ff=64, max_seq_len=32, tie_embeddings=True)
    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
    slots, slot_len, page = 3, 32, 8
    npg = slot_len // page
    cache = jax.eval_shape(
        lambda: init_paged_cache(model, slots, 1 + slots * npg, page, npg))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return make_lm_prefill_chunk_fn(model, page, slot_len).lower(
        params, cache, i32(1, page), i32(), i32(), i32(npg)).compile()


def _jamba(build, *extra):
    """A tiny hybrid (Mamba, attention, Mamba) engine program."""
    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.models.lm.generate import init_paged_cache

    cfg = LMConfig(vocab_size=96, d_model=32, n_layers=3, n_heads=2,
                   n_kv_heads=1, head_dim=16, d_ff=64, max_seq_len=32,
                   rope_theta=None, attn_layer_period=3, attn_layer_offset=1,
                   mamba_d_state=4, mamba_dt_rank=4)
    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
    slots, slot_len, page = 3, 32, 8
    npg = slot_len // page
    cache = jax.eval_shape(
        lambda: init_paged_cache(model, slots, 1 + slots * npg, page, npg))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return build(model, params, cache, i32, slots, slot_len, page, npg)


def _jamba_paged_step():
    from tpu_air.models.lm.generate import make_lm_paged_decode_step_fn

    return _jamba(lambda model, params, cache, i32, slots, slot_len, page,
                  npg: make_lm_paged_decode_step_fn(model, slot_len).lower(
                      params, cache, i32(slots), i32(slots),
                      i32(slots, npg)).compile())


def _jamba_prefill_chunk():
    from tpu_air.models.lm.generate import make_lm_prefill_chunk_fn

    return _jamba(lambda model, params, cache, i32, slots, slot_len, page,
                  npg: make_lm_prefill_chunk_fn(model, page, slot_len).lower(
                      params, cache, i32(1, page), i32(), i32(), i32(npg),
                      slot=i32()).compile())


def _jamba_mixed_step():
    from tpu_air.models.lm.generate import make_lm_paged_mixed_step_fn

    return _jamba(lambda model, params, cache, i32, slots, slot_len, page,
                  npg: make_lm_paged_mixed_step_fn(
                      model, page, slot_len).lower(
                      params, cache, i32(slots), i32(slots), i32(slots, npg),
                      i32(1, page), i32(), i32(), i32(npg),
                      slot=i32()).compile())


def _nemotron(build):
    """A tiny engine program of layers that are ONE thing: Mamba-2, experts
    in a latent (half of the router's held, a shared one), attention."""
    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.models.lm.generate import init_paged_cache

    cfg = LMConfig(vocab_size=96, d_model=32, n_layers=3, n_heads=2,
                   n_kv_heads=1, head_dim=16, d_ff=24, max_seq_len=32,
                   rope_theta=None, tie_embeddings=False, num_experts=8,
                   num_experts_per_tok=3, num_shared_experts=1,
                   shared_d_ff=40, router="sigmoid_groups", router_scale=5.0,
                   experts_first=4, experts_held=4, layer_pattern="ME*",
                   mamba_n_heads=4, mamba_head_dim=8, mamba_n_groups=2,
                   mamba_d_state=8, mamba_chunk_size=4, ff_act="relu2",
                   moe_latent_size=16)
    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
    slots, slot_len, page = 3, 32, 8
    npg = slot_len // page
    cache = jax.eval_shape(
        lambda: init_paged_cache(model, slots, 1 + slots * npg, page, npg))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return build(model, params, cache, i32, slots, slot_len, page, npg)


def _nemotron_paged_step():
    from tpu_air.models.lm.generate import make_lm_paged_decode_step_fn

    return _nemotron(lambda model, params, cache, i32, slots, slot_len, page,
                     npg: make_lm_paged_decode_step_fn(model, slot_len).lower(
                         params, cache, i32(slots), i32(slots),
                         i32(slots, npg)).compile())


def _nemotron_prefill_chunk():
    from tpu_air.models.lm.generate import make_lm_prefill_chunk_fn

    return _nemotron(lambda model, params, cache, i32, slots, slot_len, page,
                     npg: make_lm_prefill_chunk_fn(
                         model, page, slot_len).lower(
                         params, cache, i32(1, page), i32(), i32(), i32(npg),
                         slot=i32()).compile())


def _nemotron_mixed_step():
    from tpu_air.models.lm.generate import make_lm_paged_mixed_step_fn

    return _nemotron(lambda model, params, cache, i32, slots, slot_len, page,
                     npg: make_lm_paged_mixed_step_fn(
                         model, page, slot_len).lower(
                         params, cache, i32(slots), i32(slots),
                         i32(slots, npg), i32(1, page), i32(), i32(),
                         i32(npg), slot=i32()).compile())


def _gigachat(build, **more):
    """A tiny latent-attention engine program: a dense layer, then a sparse
    one that holds half of its router's experts, with a shared expert."""
    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.models.lm.generate import init_paged_cache

    cfg = LMConfig(**more, vocab_size=96, d_model=32, n_layers=2, n_heads=2,
                   head_dim=12, d_ff=16, max_seq_len=32, tie_embeddings=False,
                   num_experts=8, num_experts_per_tok=2, q_lora_rank=16,
                   kv_lora_rank=8, qk_nope_head_dim=8, qk_rope_head_dim=4,
                   v_head_dim=8, rope_factor=4.0, rope_original_len=8,
                   rope_mscale_all_dim=1.0, first_dense_layers=1,
                   dense_d_ff=48, num_shared_experts=1,
                   router="sigmoid_groups", router_groups=4,
                   router_topk_groups=2, router_scale=2.5, experts_first=4,
                   experts_held=4)
    model = CausalLM(cfg)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
    slots, slot_len, page = 3, 32, 8
    npg = slot_len // page
    cache = jax.eval_shape(
        lambda: init_paged_cache(model, slots, 1 + slots * npg, page, npg))
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    return build(model, params, cache, i32, slots, slot_len, page, npg)


def _gigachat_paged_step():
    from tpu_air.models.lm.generate import make_lm_paged_decode_step_fn

    return _gigachat(lambda model, params, cache, i32, slots, slot_len, page,
                     npg: make_lm_paged_decode_step_fn(model, slot_len).lower(
                         params, cache, i32(slots), i32(slots),
                         i32(slots, npg)).compile())


def _xing(build):
    """The same layers inside four residual streams (PR 58)."""
    return lambda: _gigachat(build, hc_mult=4)


def _lower_step(model, params, cache, i32, slots, slot_len, page, npg):
    from tpu_air.models.lm.generate import make_lm_paged_decode_step_fn

    return make_lm_paged_decode_step_fn(model, slot_len).lower(
        params, cache, i32(slots), i32(slots), i32(slots, npg)).compile()


def _lower_chunk(model, params, cache, i32, slots, slot_len, page, npg):
    from tpu_air.models.lm.generate import make_lm_prefill_chunk_fn

    return make_lm_prefill_chunk_fn(model, page, slot_len).lower(
        params, cache, i32(1, page), i32(), i32(), i32(npg)).compile()


def _lower_mixed(model, params, cache, i32, slots, slot_len, page, npg):
    from tpu_air.models.lm.generate import make_lm_paged_mixed_step_fn

    return make_lm_paged_mixed_step_fn(model, page, slot_len).lower(
        params, cache, i32(slots), i32(slots), i32(slots, npg), i32(1, page),
        i32(), i32(), i32(npg)).compile()


def _laguna(build):
    """Window layers that keep a ring beside full layers that keep pages,
    heads by kind, a gate a head (PR 60): F-dense S S S F at the tiny size
    of ``tests/test_laguna.py``; its chunk programs are told their slot."""
    import test_laguna
    from tpu_air.models.lm import CausalLM, hf_import
    from tpu_air.models.lm.generate import init_paged_cache

    def run():
        cfg = hf_import.lm_config_from_hf(test_laguna.TINY, max_seq_len=32)
        model = CausalLM(cfg)
        params = jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32)))["params"]
        slots, slot_len, page = 3, 32, 8
        npg = slot_len // page
        cache = jax.eval_shape(lambda: init_paged_cache(
            model, slots, 1 + slots * npg, page, npg))
        i32 = lambda *shape: jax.ShapeDtypeStruct(  # noqa: E731
            shape, jnp.int32)
        return build(model, params, cache, i32, slots, slot_len, page, npg)

    return run


def _lower_chunk_of_a_slot(model, params, cache, i32, slots, slot_len, page,
                           npg):
    from tpu_air.models.lm.generate import make_lm_prefill_chunk_fn

    return make_lm_prefill_chunk_fn(model, page, slot_len).lower(
        params, cache, i32(1, page), i32(), i32(), i32(npg),
        slot=i32()).compile()


def _lower_mixed_of_a_slot(model, params, cache, i32, slots, slot_len, page,
                           npg):
    from tpu_air.models.lm.generate import make_lm_paged_mixed_step_fn

    return make_lm_paged_mixed_step_fn(model, page, slot_len).lower(
        params, cache, i32(slots), i32(slots), i32(slots, npg), i32(1, page),
        i32(), i32(), i32(npg), slot=i32()).compile()


# the five scopes of the residual streams, in each of the engine's three
# programs: the maps under their sublayer's module
_MHC = {"mhc_expand": None, "mhc_pre": "attn_hc", "mhc_sinkhorn": "mlp_hc",
        "mhc_post": None, "mhc_reduce": None}


def _gigachat_mixed_step():
    from tpu_air.models.lm.generate import make_lm_paged_mixed_step_fn

    return _gigachat(lambda model, params, cache, i32, slots, slot_len, page,
                     npg: make_lm_paged_mixed_step_fn(
                         model, page, slot_len).lower(
                         params, cache, i32(slots), i32(slots),
                         i32(slots, npg), i32(1, page), i32(), i32(),
                         i32(npg)).compile())


# the words that came after benchmark/scopes.py wrote its list down (PR 41,
# PR 43, PR 47, PR 58, PR 60: lower-case words, which its reader takes for parts of a model
# as they are)
LATER_WORDS = {"ssm_conv", "ssm_scan", "ssm_state_update",
               "mla_q", "mla_latent", "mla_out", "moe_shared",
               "ssd_conv", "ssd_scan", "ssd_state_update", "ssd_gate_norm",
               "moe_latent_down", "moe_latent_up",
               "mhc_expand", "mhc_pre", "mhc_sinkhorn", "mhc_post",
               "mhc_reduce",
               "window_attention", "window_append", "full_attention",
               "attn_gate"}
WORDS = set(scopes.VOCABULARY) | LATER_WORDS

# program -> the words it must carry, and for some the module around them
PROGRAMS = {
    "jamba_paged_step": (_jamba_paged_step, {
        "ssm_conv": "mamba", "ssm_state_update": "mamba",
        "kv_gather": "attn", "decode_attention": "attn", "lm_head": None}),
    "jamba_prefill_chunk": (_jamba_prefill_chunk, {
        "ssm_conv": "mamba", "ssm_scan": "mamba", "attn_scores": "attn",
        "kv_append": "attn", "lm_head": None}),
    # the mixed step holds the step's scopes and the chunk's, side by side
    "jamba_mixed_step": (_jamba_mixed_step, {
        "ssm_conv": "mamba", "ssm_state_update": "mamba", "ssm_scan": "mamba",
        "kv_gather": "attn", "decode_attention": "attn",
        "attn_scores": "attn", "kv_append": "attn", "lm_head": None}),
    # latent attention: the absorbed read of the step and, in the mixed
    # step, the chunk's expanded attention beside it, all under ``attn``
    "gigachat_paged_step": (_gigachat_paged_step, {
        "mla_q": "attn", "mla_latent": "attn", "mla_out": "attn",
        "kv_gather": "attn", "decode_attention": "attn", "kv_append": "attn",
        "moe_router": "moe", "moe_experts": "moe", "moe_shared": "shared",
        "lm_head": None}),
    "gigachat_mixed_step": (_gigachat_mixed_step, {
        "mla_q": "attn", "mla_latent": "attn", "mla_out": "attn",
        "kv_gather": "attn", "decode_attention": "attn",
        "attn_scores": "attn", "attn_context": "attn",
        "moe_shared": "shared", "lm_head": None}),
    "xing_paged_step": (_xing(_lower_step), {
        **_MHC, "decode_attention": "attn", "moe_experts": "moe"}),
    "xing_prefill_chunk": (_xing(_lower_chunk), {
        **_MHC, "attn_scores": "attn", "moe_experts": "moe"}),
    "xing_mixed_step": (_xing(_lower_mixed), {
        **_MHC, "decode_attention": "attn", "attn_scores": "attn"}),
    # two kinds of attention layer in one model (PR 60): the ring's write and
    # read, the full kind's gathered read under its own word, the gate
    "laguna_paged_step": (_laguna(_lower_step), {
        "window_attention": "attn", "window_append": "attn",
        "full_attention": "attn", "kv_gather": "attn", "kv_append": "attn",
        "decode_attention": "attn", "attn_gate": "attn",
        "moe_experts": "moe", "moe_shared": "shared"}),
    "laguna_prefill_chunk": (_laguna(_lower_chunk_of_a_slot), {
        "window_attention": "attn", "window_append": "attn",
        "full_attention": "attn", "attn_scores": "attn",
        "attn_gate": "attn"}),
    "laguna_mixed_step": (_laguna(_lower_mixed_of_a_slot), {
        "window_attention": "attn", "window_append": "attn",
        "full_attention": "attn", "decode_attention": "attn",
        "attn_scores": "attn", "attn_gate": "attn"}),
    # a layer that is one thing (PR 47): Mamba-2's scopes under ``mamba``,
    # the latent pair around the routed experts under ``moe``
    "nemotron_paged_step": (_nemotron_paged_step, {
        "ssd_conv": "mamba", "ssd_state_update": "mamba",
        "ssd_gate_norm": "mamba", "moe_latent_down": "moe",
        "moe_latent_up": "moe", "moe_router": "moe", "moe_experts": "moe",
        "moe_shared": "shared", "decode_attention": "attn",
        "lm_head": None}),
    "nemotron_prefill_chunk": (_nemotron_prefill_chunk, {
        "ssd_conv": "mamba", "ssd_scan": "mamba", "ssd_gate_norm": "mamba",
        "moe_latent_down": "moe", "moe_latent_up": "moe",
        "attn_scores": "attn", "lm_head": None}),
    "nemotron_mixed_step": (_nemotron_mixed_step, {
        "ssd_conv": "mamba", "ssd_state_update": "mamba",
        "ssd_scan": "mamba", "ssd_gate_norm": "mamba",
        "moe_latent_down": "moe", "moe_latent_up": "moe",
        "moe_experts": "moe", "moe_shared": "shared", "lm_head": None}),
    "t5_train_step": (_t5_train_step, {
        "attn_scores": "self_attn", "attn_softmax": "cross_attn",
        "attn_context": "self_attn", "dropout": "mlp", "loss": None,
        "optimizer": None, "lm_head": None}),
    "t5_decode_step": (lambda: _t5_slots(rows=2), {
        "decode_attention": "cross_attn", "kv_append": "self_attn",
        "kv_gather": "self_attn"}),
    "t5_admit": (lambda: _t5_slots(admit=16), {
        "attn_scores": "self_attn", "attn_softmax": "self_attn",
        "attn_context": "self_attn"}),
    "lm_paged_step": (_lm_paged_step, {
        "kv_gather": "attn", "decode_attention": "attn", "kv_append": "attn",
        "moe_router": "moe", "moe_sort": "moe", "moe_experts": "moe",
        "moe_combine": "moe", "lm_head": None}),
    "lm_prefill_chunk": (_lm_prefill_chunk, {
        "attn_scores": "attn", "attn_softmax": "attn", "attn_context": "attn",
        "kv_gather": "attn", "kv_append": "attn", "lm_head": None}),
}


@pytest.mark.parametrize("program", sorted(PROGRAMS))
def test_every_scope_is_in_a_compiled_program(program):
    """A refactor that drops a scope fails here, not in a metric."""
    build, want = PROGRAMS[program]
    found = _words(build())
    for word, module in want.items():
        assert word in found, f"{program}: no operation under {word!r}"
        if module is not None:
            assert module in found[word], (
                f"{program}: {word!r} is not inside a {module!r} module")


def _scope_sites():
    """Every ``named_scope("...")`` of the program's source."""
    names = set()
    for root, _, files in os.walk(os.path.join(REPO, "tpu_air")):
        for f in files:
            if f.endswith(".py"):
                with open(os.path.join(root, f)) as fh:
                    names.update(re.findall(
                        r'named_scope\(\s*"([^"]*)"', fh.read()))
    return names


def test_the_document_is_the_contract():
    """The program uses only documented names, the document lists none that
    no compiled program above carries, and each is in its table."""
    sites = _scope_sites()
    assert {s.split("/")[-1] for s in sites} == WORDS
    assert all(scopes.is_part(w) for w in LATER_WORDS)
    tested = set().union(*(want for _, want in PROGRAMS.values()))
    assert tested == WORDS
    with open(os.path.join(REPO, "docs", "OBSERVABILITY.md")) as f:
        doc = f.read()
    section = doc.split("## Model parts on the device rows", 1)[1]
    section = section.split("\n## ", 1)[0]
    for site in sites:
        assert f"`{site}`" in section, f"{site} is not in the document"
    documented = set(re.findall(r"^\| `([a-z_/]+)`", section, re.M))
    assert {d.split("/")[-1] for d in documented} == WORDS


# -- scope_share on a hand-made capture -------------------------------------------
# microseconds from the line's start.  Program step (id 77) runs twice, 0-100
# and 200-300, each: fusion.1 cross decode_attention 0-30, fusion.2 self
# decode_attention 30-50, fusion.3 self_attn/kv_append 50-60, copy.4 (no
# path) 60-70, fusion.5 (jax's own words only) 70-80, fusion.7 mlp 80-100,
# inside the container while.6 0-100.  Program other (id 88) runs once,
# 400-500: ANOTHER fusion.1, attn_softmax, 400-450, and fusion.10 (no path)
# 450-500.  Device 1 and the host plane are there to be left alone.

_M = "jit(step)/Model/Model.decode/decoder"
_OPS = {
    1: ("%fusion.1 = bf16[8]{0} fusion(...)", 77,
        f"{_M}/layer_0/cross_attn/decode_attention/bhd,bhdl->bhl/dot_general:"),
    2: ("%fusion.2 = bf16[8]{0} fusion(...)", 77,
        f"{_M}/layer_0/self_attn/decode_attention/dot_general:"),
    3: ("%fusion.3 = bf16[8]{0} fusion(...)", 77,
        f"{_M}/self_attn/kv_append/dynamic_update_slice:"),
    4: ("%copy.4 = bf16[8]{0} copy(...)", 77, None),
    5: ("%fusion.5 = s32[] fusion(...)", 77,
        "jit(step)/while/body/jit(_where)/select_n:"),
    6: ("%while.6 = (s32[]) while(...)", 77, "jit(step)/while:"),
    7: ("%fusion.7 = bf16[8]{0} fusion(...)", 77,
        f"{_M}/layer_1/mlp/wo/dot_general:"),
    9: ("%fusion.1 = f32[8]{0} fusion(...)", 88,
        "jit(other)/Model/encoder/layer_0/self_attn/attn_softmax/reduce_max:"),
    10: ("%fusion.10 = f32[8]{0} fusion(...)", 88, None),
    20: ("jit_step(77)", None, None),
    21: ("jit_other(88)", None, None),
}
_STEP = [(6, 0, 100), (1, 0, 30), (2, 30, 20), (3, 50, 10), (4, 60, 10),
         (5, 70, 10), (7, 80, 20)]


def _capture_text():
    def events(rows):
        return "\n".join(
            f"    events {{ metadata_id: {i} offset_ps: {at * 10**6} "
            f"duration_ps: {dur * 10**6} }}" for i, at, dur in rows)

    metadata = []
    for ident, (name, program, tf_op) in _OPS.items():
        stats = ""
        if program is not None:
            stats += f" stats {{ metadata_id: 2 uint64_value: {program} }}"
            stats += " stats { metadata_id: 3 uint64_value: 1000 }"
            stats += " stats { metadata_id: 4 uint64_value: 4000 }"
        if tf_op is not None:
            stats += f' stats {{ metadata_id: 1 str_value: "{tf_op}" }}'
        metadata.append(f"  event_metadata {{ key: {ident} value {{ id: "
                        f'{ident} name: "{name}"{stats} }} }}')
    ops = (_STEP + [(i, at + 200, dur) for i, at, dur in _STEP]
           + [(9, 400, 50), (10, 450, 50)])
    return f"""
planes {{
  id: 1
  name: "/device:TPU:0"
  lines {{
    id: 1
    name: "XLA Modules"
    timestamp_ns: 5000000
{events([(20, 0, 100), (20, 200, 100), (21, 400, 100)])}
  }}
  lines {{
    id: 2
    name: "XLA Ops"
    timestamp_ns: 5000000
{events(ops)}
  }}
  lines {{
    id: 3
    name: "Steps"
    timestamp_ns: 5000000
{events([(20, 0, 500)])}
  }}
{chr(10).join(metadata)}
  stat_metadata {{ key: 1 value {{ id: 1 name: "tf_op" }} }}
  stat_metadata {{ key: 2 value {{ id: 2 name: "program_id" }} }}
  stat_metadata {{ key: 3 value {{ id: 3 name: "bytes_accessed" }} }}
  stat_metadata {{ key: 4 value {{ id: 4 name: "flops" }} }}
}}
planes {{
  id: 2
  name: "/device:TPU:1"
  lines {{
    id: 1
    name: "XLA Ops"
    timestamp_ns: 5000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 500000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "%fusion.1 = f32[] fusion()" }} }}
}}
planes {{
  id: 3
  name: "/host:CPU"
  lines {{
    id: 1
    name: "python3"
    timestamp_ns: 5000000
    events {{ metadata_id: 1 offset_ps: 0 duration_ps: 1000000000 }}
  }}
  event_metadata {{ key: 1 value {{ id: 1 name: "engine.step" }} }}
}}
"""


@pytest.fixture(scope="module")
def capture(tmp_path_factory):
    from jax.profiler import ProfileData

    path = tmp_path_factory.mktemp("capture") / "hand.xplane.pb"
    path.write_bytes(
        ProfileData.text_proto_to_serialized_xspace(_capture_text()))
    return str(path)


def test_hand_made_capture_is_read_as_written(capture):
    planes = scopes.read(capture)
    assert sorted(planes) == [0, 1]
    plane = planes[0]
    assert plane.program_id("step") == 77
    assert plane.program_id("other") == 88
    assert len(plane.modules) == 3
    # the container is left out; two operations are both called fusion.1
    assert len(plane.ops) == 2 * 6 + 2
    assert {plane.metadata[i]["program_id"] for i, _, _ in plane.ops
            if xplane.op_name(plane.metadata[i]["name"]) == "fusion.1"} == {
                77, 88}
    first = min(plane.ops, key=lambda ev: ev[1])
    assert first[1] == pytest.approx(5000 * US)
    assert first[2] - first[1] == pytest.approx(30 * US)


@pytest.mark.parametrize("args, want", [
    (dict(scope="^decode_attention$", module="step"), 50.0),
    (dict(scope="^decode_attention$", under="^cross_attn$", module="step"),
     30.0),
    (dict(scope="^(decode_attention|kv_append)$", under="^self_attn$",
          module="step"), 30.0),
    # its two halves (PR 59: gen_self_read_share, gen_kv_append_share)
    (dict(scope="^decode_attention$", under="^self_attn$", module="step"),
     20.0),
    (dict(scope="^kv_append$", under="^self_attn$", module="step"), 10.0),
    # "under" is ANOTHER component: kv_append is not under itself
    (dict(scope="^kv_append$", under="^kv_append$", module="step"), None),
    (dict(unscoped=True, module="step"), 20.0),
    (dict(unscoped=True), 100.0 * 90 / 300),
    (dict(scope="^attn_softmax$"), 100.0 * 50 / 300),
    (dict(scope="^attn_softmax$", module="other"), 50.0),
    # no operation in the scope / no such program: nothing to read
    (dict(scope="^attn_softmax$", module="step"), None),
    (dict(scope="^moe_experts$"), None),
    (dict(unscoped=True, module="missing"), None),
])
def test_scope_share(capture, args, want):
    got = scope_share.share(scopes.read(capture)[0], **args)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)


def test_scope_share_reads_the_newest_capture_or_nothing(capture, monkeypatch):
    from benchmark import spans

    rc = collections.namedtuple("rc", "trace")
    monkeypatch.setattr(spans, "newest_xplane", lambda: capture)
    assert scope_share.read(rc(object()), scope="^kv_append$") == (
        pytest.approx(100.0 * 20 / 300))
    assert scope_share.read(rc(None), scope="^kv_append$") is None
    monkeypatch.setattr(spans, "newest_xplane", lambda: None)
    assert scope_share.read(rc(object()), unscoped=True) is None
    # a capture older than the scopes' reader can tell: no path anywhere
    monkeypatch.setattr(
        spans, "newest_xplane",
        lambda: os.path.join(DATA, "v5e_small.xplane.pb"))
    assert scope_share.read(rc(object()), scope="^kv_gather$") is None
    assert scope_share.read(rc(object()), unscoped=True) == pytest.approx(100)


def test_scope_table_rows(capture, capsys):
    from benchmark.tools import scope_table

    plane = scopes.read(capture)[0]
    found = scope_table.programs(plane)
    assert [(n, pid, runs) for n, pid, runs, _ in found] == [
        ("jit_step", 77, 2), ("jit_other", 88, 1)]
    rows, ms = scope_table.table(plane, 77, 2)
    assert ms == pytest.approx(0.1)
    by_path = {r[0]: r for r in rows}
    assert set(by_path) == {
        "decoder/*/cross_attn/decode_attention",
        "decoder/*/self_attn/decode_attention", "decoder/self_attn/kv_append",
        "decoder/*/mlp/wo", "(unscoped)"}
    path, t, share, count, gbs, tfs = by_path[
        "decoder/*/cross_attn/decode_attention"]
    assert (t, share, count) == (pytest.approx(0.03), pytest.approx(30.0), 1)
    assert gbs == pytest.approx(1000 / 30e-6 / 1e9)
    assert tfs == pytest.approx(4000 / 30e-6 / 1e12)
    assert by_path["(unscoped)"][3] == 2
    assert scope_table.report(capture, module="step", ops="^fusion") == 0
    out = capsys.readouterr().out
    assert "jit_step: 2 executions" in out and "jit_other" not in out
    assert "operations named ^fusion" in out


# -- the ten metrics -------------------------------------------------------------------

NEW = {
    "train_attention_share": ["t5base-finetune", "t5base-finetune-dp4"],
    "train_optimizer_share": ["t5base-finetune", "t5base-finetune-dp4"],
    "train_unscoped_share": ["t5base-finetune", "t5base-finetune-dp4"],
    "gen_cross_attn_share": ["t5base-batchgen", "t5large-batchgen"],
    "gen_self_attn_share": ["t5base-batchgen", "t5large-batchgen"],
    "gen_unscoped_share": ["t5base-batchgen", "t5large-batchgen"],
    "lm_kv_gather_share": ["olmoe-serve-decode", "jamba2-serve-reason"],
    "lm_attention_share": ["olmoe-serve-decode", "jamba2-serve-reason"],
    "lm_expert_share": ["olmoe-serve-decode"],
    "engine_unscoped_share": ["t5large-serve", "olmoe-serve-decode",
                              "jamba2-serve-reason",
                              "gigachat-serve-docchat",
                              "nemotron3-serve-agent", "xing4-serve-longdoc",
                              "laguna-serve-mixedlen"],
    # PR 41: the hybrid's decode step (a flax module's name and a scope word)
    "ssm_mixer_share": ["jamba2-serve-reason"],
    "ssm_state_share": ["jamba2-serve-reason"],
    # PR 43: latent attention's own matrices, and the shared expert (over
    # every program of the capture: nearly every iteration of the cell is the
    # mixed step, so the readers of the decode program alone do not list it)
    # (PR 58 appended its cell to the three it shares the scopes of)
    "mla_latent_share": ["gigachat-serve-docchat", "xing4-serve-longdoc"],
    "moe_shared_share": ["gigachat-serve-docchat", "nemotron3-serve-agent",
                         "xing4-serve-longdoc", "laguna-serve-mixedlen"],
    # PR 45: a chunk's attention over its slot's latent pages, of the mixed
    # step (the dense form's three words; the walk's kernel is under the last)
    "mla_chunk_attention_share": ["gigachat-serve-docchat", "xing4-serve-longdoc"],
    # PR 47: the Mamba-2 state's pass, a chunk's block form, the latent pair
    "ssd_state_share": ["nemotron3-serve-agent"],
    "ssd_scan_share": ["nemotron3-serve-agent"],
    "latent_proj_share": ["nemotron3-serve-agent"],
    # PR 58: the residual streams' five scopes, and the Sinkhorn rounds alone
    "mhc_share": ["xing4-serve-longdoc"],
    "mhc_sinkhorn_share": ["xing4-serve-longdoc"],
    # PR 59: the two halves of gen_self_attn_share, a layer's read and the
    # stacked append
    "gen_self_read_share": ["t5base-batchgen", "t5large-batchgen"],
    "gen_kv_append_share": ["t5base-batchgen", "t5large-batchgen"],
    # PR 60: each kind of attention layer's share of a decode step
    "swa_window_share": ["laguna-serve-mixedlen"],
    "swa_full_share": ["laguna-serve-mixedlen"],
}


@pytest.mark.parametrize("name", sorted(NEW))
def test_metric_loads_for_its_cells(name):
    bench = manifest.Benchmark(REPO)
    with open(os.path.join(REPO, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        how = json.load(f)
    assert how["reader"] == "scope_share" and how["doc"]
    for cell in (w["name"] for w in bench.doc["workloads"]):
        got = [m for m in bench.metrics("per_layer", cell)
               if m["name"] == name]
        assert bool(got) == (cell in NEW[name])
        for m in got:
            assert (m["unit"], m["source"]) == ("%", "device_trace")
            assert m["args"] == how["args"]
            # the arguments are the reader's own, and compile
            assert set(m["args"]) <= {"scope", "under", "module", "unscoped"}
            re.compile(m["args"].get("under", ""))
            words = re.findall(r"[a-z_]+", m["args"].get("scope", ""))
            assert set(words) <= WORDS | {"mamba"}
