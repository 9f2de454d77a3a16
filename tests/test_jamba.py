"""Jamba (``model_type: jamba``: Mamba-1 layers beside attention layers with
one K/V head and no position encoding) through ``CausalLM``, the importer and
``InferenceEngine``, against the plain float32 reference on seeded weights in
the published layout, at a small size on the CPU."""

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from tpu_air.models.lm import hf_import, reference_jamba
from tpu_air.models.lm.config import LMConfig
from tpu_air.models.lm.modeling import CausalLM

import _mixed_step_cases

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

TINY = {
    "model_type": "jamba", "hidden_size": 64, "intermediate_size": 96,
    "num_hidden_layers": 4, "num_attention_heads": 4,
    "num_key_value_heads": 1, "attn_layer_period": 4, "attn_layer_offset": 2,
    "num_experts": 1, "mamba_expand": 2, "mamba_d_state": 8,
    "mamba_d_conv": 4, "mamba_dt_rank": 6, "mamba_conv_bias": True,
    "mamba_proj_bias": False, "rms_norm_eps": 1e-6, "vocab_size": 384,
    "tie_word_embeddings": True, "hidden_act": "silu",
    "max_position_embeddings": 512,
}


def published_shapes(cfg):
    """name -> shape of every tensor of a published jamba state dict."""
    d, f, v = cfg["hidden_size"], cfg["intermediate_size"], cfg["vocab_size"]
    c = cfg["mamba_expand"] * d
    n, k, r = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    hd = d // cfg["num_attention_heads"]
    kv = cfg["num_key_value_heads"] * hd
    out = {"model.embed_tokens.weight": (v, d),
           "model.final_layernorm.weight": (d,)}
    for i in range(cfg["num_hidden_layers"]):
        p = f"model.layers.{i}."
        out[p + "input_layernorm.weight"] = (d,)
        out[p + "pre_ff_layernorm.weight"] = (d,)
        out[p + "feed_forward.gate_proj.weight"] = (f, d)
        out[p + "feed_forward.up_proj.weight"] = (f, d)
        out[p + "feed_forward.down_proj.weight"] = (d, f)
        if reference_jamba.layer_is_attention(cfg, i):
            out[p + "self_attn.q_proj.weight"] = (d, d)
            out[p + "self_attn.k_proj.weight"] = (kv, d)
            out[p + "self_attn.v_proj.weight"] = (kv, d)
            out[p + "self_attn.o_proj.weight"] = (d, d)
        else:
            m = p + "mamba."
            out[m + "in_proj.weight"] = (2 * c, d)
            out[m + "conv1d.weight"] = (c, 1, k)
            out[m + "conv1d.bias"] = (c,)
            out[m + "x_proj.weight"] = (r + 2 * n, c)
            out[m + "dt_proj.weight"] = (c, r)
            out[m + "dt_proj.bias"] = (c,)
            out[m + "A_log"] = (c, n)
            out[m + "D"] = (c,)
            out[m + "out_proj.weight"] = (d, c)
            out[m + "dt_layernorm.weight"] = (r,)
            out[m + "b_layernorm.weight"] = (n,)
            out[m + "c_layernorm.weight"] = (n,)
    return out


def published(cfg, seed=0, std=0.08):
    """A seeded state dict in the published layout; the Mamba scalars get
    Mamba's own init (slow channels remember hundreds of positions)."""
    rng = np.random.default_rng(seed)
    sd = {}
    for name, shape in published_shapes(cfg).items():
        if name.endswith("layernorm.weight"):
            sd[name] = (1 + 0.1 * rng.standard_normal(shape)).astype(np.float32)
        elif name.endswith("A_log"):
            sd[name] = np.log(np.broadcast_to(
                np.arange(1, shape[1] + 1, dtype=np.float32), shape)).copy()
        elif name.endswith("mamba.D"):
            sd[name] = np.ones(shape, np.float32)
        elif name.endswith("dt_proj.bias"):
            dt0 = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shape))
            sd[name] = (dt0 + np.log(-np.expm1(-dt0))).astype(np.float32)
        else:
            sd[name] = (std * rng.standard_normal(shape)).astype(np.float32)
    return sd


@pytest.fixture(scope="module")
def tiny():
    sd = published(TINY)
    config = hf_import.lm_config_from_hf(TINY, max_seq_len=256)
    params = jax.tree_util.tree_map(
        jnp.asarray, hf_import.convert_jamba_state_dict(sd.__getitem__, config))
    return sd, config, CausalLM(config), params


def test_config_maps_the_published_keys():
    cfg = hf_import.lm_config_from_hf(TINY)
    assert cfg.layer_kinds() == ["mamba", "mamba", "attention", "mamba"]
    assert cfg.has_recurrent_layers and cfg.n_kv_heads == 1
    assert cfg.rope_theta is None and cfg.num_experts == 0
    assert cfg.mamba_d_inner == 128 and cfg.tie_embeddings
    # the other family is what it was: every layer attention, rope on
    assert LMConfig.tiny().layer_kinds() == ["attention"] * 2
    assert not LMConfig.tiny().has_recurrent_layers
    with pytest.raises(ValueError, match="num_experts"):
        hf_import.lm_config_from_hf({**TINY, "num_experts": 4})


def test_importer_round_trip(tiny):
    """Every published tensor lands in the tree exactly once, transposed or
    renamed, and the tree is the one ``CausalLM.init`` makes."""
    sd, config, model, params = tiny
    init = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32))
    want = jax.tree_util.tree_map(lambda a: a.shape, init["params"])
    got = jax.tree_util.tree_map(lambda a: a.shape, params)
    assert want == got
    assert (sum(int(np.prod(s)) for s in published_shapes(TINY).values())
            == sum(a.size for a in jax.tree_util.tree_leaves(params)))
    m = "model.layers.0.mamba."
    np.testing.assert_array_equal(
        params["layer_0"]["mamba"]["conv"]["kernel"],
        sd[m + "conv1d.weight"][:, 0, :].T)
    np.testing.assert_array_equal(
        params["layer_0"]["mamba"]["in_proj"]["kernel"],
        sd[m + "in_proj.weight"].T)
    np.testing.assert_array_equal(
        params["layer_2"]["attn"]["k"]["kernel"],
        sd["model.layers.2.self_attn.k_proj.weight"].T)


def test_init_is_mambas_own():
    cfg = hf_import.lm_config_from_hf(TINY)
    p = CausalLM(cfg).init(jax.random.PRNGKey(1),
                           jnp.zeros((1, 4), jnp.int32))["params"]
    mix = p["layer_0"]["mamba"]
    np.testing.assert_allclose(np.exp(mix["A_log"][0]), np.arange(1, 9),
                               rtol=1e-6)
    dt0 = np.asarray(jax.nn.softplus(mix["dt_proj"]["bias"]))
    assert 1e-3 * 0.99 <= dt0.min() and dt0.max() <= 1e-1 * 1.01
    assert np.all(np.asarray(mix["D"]) == 1)


def test_full_forward_matches_the_reference(tiny):
    sd, config, model, params = tiny
    ids = np.random.default_rng(3).integers(2, 384, 150).tolist()
    want = reference_jamba.forward(sd.__getitem__, TINY, ids)["logits"]
    with jax.default_matmul_precision("highest"):
        got = np.asarray(model.apply({"params": params},
                                     jnp.asarray([ids], jnp.int32))[0])
    scale = want.max(-1) - np.median(want, -1)
    assert (np.abs(got - want).max(-1) / scale).max() < 1e-4
    # the carried state matters: a reference that forgets at 128 differs
    lost = reference_jamba.forward(sd.__getitem__, TINY, ids,
                                   drop_state_at=128)["logits"]
    err = np.abs(lost - want).max(-1) / scale
    assert err[:128].max() == 0 and err[128:].max() > 0.05


# -- the engine: chunked prefill, then paged decode, the state a slot --------

def _engine(tiny, **kw):
    from tpu_air.engine import EngineConfig, InferenceEngine

    _, config, model, params = tiny
    cfg = dict(num_slots=4, slot_len=256, page_len=16, max_new_tokens=8,
               eos_token_id=None)
    cfg.update(kw)
    return InferenceEngine(model, params, EngineConfig(**cfg),
                           auto_start=False)


def _system_logits(eng, prompts, answers, slots=(2, 0, 3)):
    """The system's logits for each streamed token through its own chunk and
    decode programs over the ENGINE'S pool, the answer teacher-forced: the
    function the benchmark's check runs inside the replica on the chip.
    Fewer slots than prompts: one is reused, and a row mid-prefill rides the
    decode steps of the rows before it.  With them, the state the first
    Mamba layer carries for each sequence at its end."""
    from benchmark.worker_hooks_ssm import replayed_logits

    return replayed_logits(eng, prompts, answers, list(slots))


def _reference_rows(sd, prompt, answer):
    ids = list(prompt) + list(answer[:-1])
    return reference_jamba.forward(
        sd.__getitem__, TINY, ids, range(len(prompt) - 1, len(ids)))["logits"]


def test_chunked_prefill_then_paged_decode_matches_the_reference(tiny):
    """Logits, not tokens: prompts that cross a chunk boundary and end in a
    padded chunk, one that fills its last chunk, and one shorter than a
    chunk, through the engine's chunk and decode programs."""
    sd = tiny[0]
    rng = np.random.default_rng(5)
    prompts = [rng.integers(2, 384, k).tolist() for k in (37, 32, 9, 50)]
    eng = _engine(tiny)
    answers = eng.generate(prompts, 6)
    assert all(len(a) == 6 for a in answers)
    with jax.default_matmul_precision("highest"):
        system, carried = _system_logits(eng, prompts, answers)
    # the replay took the engine's own cache and handed it back: the engine
    # serves on, from whatever the replay left in its pages and state rows
    assert eng.generate(prompts, 6) == answers
    eng.close()
    for p, a, got in zip(prompts, answers, system):
        want = _reference_rows(sd, p, a)
        scale = want.max(-1) - np.median(want, -1)
        assert (np.abs(got - want).max(-1) / scale).max() < 1e-3
        # what the engine streamed is what those logits say
        assert got.argmax(-1).tolist() == a
    # the state the system carries in its first Mamba layer, in a reused
    # slot too, is the reference's after the same positions
    for p, a, state in zip(prompts, answers, carried):
        ids = list(p) + list(a[:-1])
        want = reference_jamba.forward(
            sd.__getitem__, TINY, ids, [0], state_after=len(ids))["states"][0]
        assert state.shape == want.shape == (128, 8)
        np.testing.assert_allclose(state, want, rtol=2e-4, atol=1e-6)


@pytest.mark.parametrize("after", [1, 9, 20])
def test_reference_state_after_is_the_shorter_sequences_last_state(tiny,
                                                                   after):
    """``state_after``: what every Mamba layer carries after that many
    positions, whatever follows them (the benchmark pads its sequences)."""
    sd = tiny[0]
    ids = np.random.default_rng(11).integers(2, 384, 20).tolist()
    whole = reference_jamba.forward(sd.__getitem__, TINY, ids, [0],
                                    state_after=after)["states"]
    alone = reference_jamba.forward(sd.__getitem__, TINY, ids[:after], [0],
                                    state_after=after)["states"]
    assert whole.shape == (3, 128, 8)           # three Mamba layers of four
    np.testing.assert_allclose(whole, alone, rtol=1e-6, atol=1e-8)
    assert "states" not in reference_jamba.forward(
        sd.__getitem__, TINY, ids, [0])


def test_reference_round_state_rounds_what_is_carried(tiny):
    """The third sensitivity reading: the carried state rounded after every
    position moves the state and the logits; the identity moves nothing."""
    from benchmark.worker_hooks_lm import round_mantissa

    sd = tiny[0]
    ids = np.random.default_rng(12).integers(2, 384, 40).tolist()
    run = lambda **how: reference_jamba.forward(  # noqa: E731
        sd.__getitem__, TINY, ids, [39], state_after=40, **how)
    plain, same = run(), run(round_state=lambda s: s)
    rounded = run(round_state=round_mantissa(7))
    np.testing.assert_array_equal(plain["states"], same["states"])
    np.testing.assert_array_equal(plain["logits"], same["logits"])
    apart = (np.linalg.norm(rounded["states"] - plain["states"])
             / np.linalg.norm(plain["states"]))
    assert 1e-4 < apart < 2e-2          # bf16's eight bits, accumulated
    assert np.abs(rounded["logits"] - plain["logits"]).max() > 0


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
    """One slot, two requests in turn: the second starts from zeros, not from
    what the first left in the row."""
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


def test_a_row_mid_prefill_keeps_its_state_while_others_decode(tiny):
    """A long prompt's chunks go out one an iteration, each riding the decode
    step of the rows already streaming (the mixed program): the step's half
    must hold the prompt's state (the live mask) while the chunk's half
    advances it, and ``engine.step``'s live rows are the rows whose state the
    step's half advances."""
    rng = np.random.default_rng(8)
    short = [rng.integers(2, 384, 6).tolist() for _ in range(2)]
    long_ = rng.integers(2, 384, 90).tolist()       # six chunks of 16
    eng = _engine(tiny, max_new_tokens=24, prefill_chunks_per_step=1)
    streams = [eng.submit(p, 24) for p in short]
    for _ in range(4):
        eng.step()
    held0 = eng.metrics.snapshot()["ssm_rows_held"]
    issued0 = eng.metrics.snapshot()["steps_issued"]
    late = eng.submit(long_, 6)
    steps_with_prefilling_row = 0
    while not eng.idle():
        prefilling = any(s.prefilling for s in eng.slots.active_slots())
        before = eng.metrics.snapshot()
        eng.step()
        after = eng.metrics.snapshot()
        if after["steps_issued"] > before["steps_issued"]:
            steps_with_prefilling_row += prefilling
            # the rows the step advances are the rows it decodes
            live = len(eng._inflight.rows) if eng._inflight else None
            if live is not None:
                assert (after["ssm_rows_held"] - before["ssm_rows_held"]
                        == 4 - live)
    assert steps_with_prefilling_row >= 4
    got = late.result(5)
    snap = eng.metrics.snapshot()
    eng.close()
    # the first short prompt met no step; the second rode the first's, and
    # each of the long one's six chunks a step of both
    assert (snap["chunks_alone"], snap["chunks_fused"],
            snap["mixed_steps"]) == (1, 7, 7)
    alone = _engine(tiny)
    want = alone.generate([long_], 6)[0]
    alone.close()
    assert got == want
    assert [s.result(5) for s in streams][0][:3]  # the others streamed on
    assert held0 >= 0 and issued0 > 0


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


def test_live_mask_holds_a_state_bit_for_bit():
    from tpu_air.ops import ssm

    rng = np.random.default_rng(0)
    b, n, c = 3, 4, 8
    f = lambda *s: jnp.asarray(rng.standard_normal(s), jnp.float32)  # noqa: E731
    state = f(b, n, c)
    live = jnp.asarray([True, False, True])
    y, new = ssm.selective_state_update(
        f(b, c), jnp.abs(f(b, c)), -jnp.abs(f(n, c)), f(b, n), f(b, n),
        f(c), state, live)
    assert np.array_equal(np.asarray(new[1]), np.asarray(state[1]))
    assert not np.array_equal(np.asarray(new[0]), np.asarray(state[0]))
    # a chunk's padded positions leave state and tail untouched
    u, dt = f(1, 8, c), jnp.abs(f(1, 8, c))
    A, B, C, D = -jnp.abs(f(n, c)), f(1, 8, n), f(1, 8, n), f(c)
    _, s5 = ssm.selective_scan_chunk(u[:, :5], dt[:, :5], A, B[:, :5],
                                     C[:, :5], D, state[:1], jnp.array([5]))
    _, s8 = ssm.selective_scan_chunk(u, dt, A, B, C, D, state[:1],
                                     jnp.array([5]))
    np.testing.assert_array_equal(np.asarray(s5), np.asarray(s8))
    tail = f(1, 3, c)
    _, t5 = ssm.causal_conv_chunk(u[:, :5], tail, f(4, c), f(c),
                                  jnp.array([5]))
    _, t8 = ssm.causal_conv_chunk(u, tail, f(4, c), f(c), jnp.array([5]))
    np.testing.assert_array_equal(np.asarray(t5), np.asarray(t8))
    np.testing.assert_array_equal(np.asarray(t5), np.asarray(u[:, 2:5]))


def test_prefix_sharing_is_off_and_page_only_moves_are_refused(tiny):
    from tpu_air.engine import RecurrentStateUnsupported

    eng = _engine(tiny)
    assert eng.pool.prefix is None
    snap = eng.metrics.snapshot()
    assert snap["prefix_cache_disabled_by_model"] is True
    c, n, k = 128, 8, 4
    assert snap["ssm_state_bytes"] == 3 * 4 * (n * c * 4 + (k - 1) * c * 4)
    with pytest.raises(RecurrentStateUnsupported, match="migrate_out"):
        eng.migrate_out()
    with pytest.raises(RecurrentStateUnsupported):
        eng.submit_prefilled([1, 2, 3], 5, {})
    eng.close()


def test_a_row_that_ended_on_eos_leaves_nothing_behind(tiny):
    """The loop reads a step late: a row that ends on EOS in step N rides
    step N+1, which advances its state once more.  Nobody reads that state
    again: the slot's next tenant starts from zeros."""
    rng = np.random.default_rng(9)
    first, second = (rng.integers(2, 384, k).tolist() for k in (30, 19))
    probe = _engine(tiny, num_slots=1, max_new_tokens=12)
    plain = probe.generate([first], 12)[0]
    probe.close()
    eos = plain[4]
    eng = _engine(tiny, num_slots=1, max_new_tokens=12, eos_token_id=eos)
    ended = eng.generate([first], 12)[0]
    assert ended == plain[:plain.index(eos) + 1]
    assert eng.metrics.snapshot()["steps_issued"] > len(ended) - 1
    after = eng.generate([second], 12)[0]
    eng.close()
    fresh = _engine(tiny, num_slots=1, max_new_tokens=12, eos_token_id=eos)
    assert after == fresh.generate([second], 12)[0]
    fresh.close()


@pytest.mark.parametrize("kv_heads", [1, 2, 4])
def test_grouped_decode_attention_matches_the_per_head_reference(kv_heads):
    from tpu_air.ops.decode_attention import (decode_attention_reference,
                                              flat_decode_attention)

    rng = np.random.default_rng(kv_heads)
    b, L, h, d = 3, 24, 4, 8
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), jnp.float32)
    k = jnp.asarray(rng.standard_normal((b, L, kv_heads, d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, L, kv_heads, d)), jnp.float32)
    mask = jnp.asarray(np.arange(L)[None] < np.array([[5], [24], [13]]))
    got = flat_decode_attention(
        q, k.reshape(b, L, -1), v.reshape(b, L, -1), mask, h, jnp.float32,
        kv_heads)
    rep = h // kv_heads
    want = decode_attention_reference(
        q, jnp.repeat(k, rep, axis=2), jnp.repeat(v, rep, axis=2),
        kv_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=2e-5)


def test_config_file_against_lmconfig_key_by_key():
    """benchmark/configs/jamba2-3b.json: every published width and count
    reaches ``LMConfig`` unchanged, nothing is reduced, and what the
    ``config.json`` does not give is under ``assumed``."""
    with open(os.path.join(REPO, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        hf = json.load(f)
    assert hf["reduced"] == {}
    assert hf["source"].endswith("ai21labs/AI21-Jamba2-3B/blob/main/config.json")
    cfg = hf_import.lm_config_from_hf(hf, dtype="bfloat16", max_seq_len=2048)
    for theirs, ours in hf_import.JAMBA_KEYS.items():
        assert getattr(cfg, ours) == hf[theirs], theirs
    assert (cfg.d_model, cfg.n_layers, cfg.n_heads, cfg.n_kv_heads) == (
        2560, 28, 20, 1)
    assert cfg.head_dim == 128 == hf["assumed"]["head_dim"]
    assert (cfg.d_ff, cfg.vocab_size, cfg.mamba_d_inner) == (8192, 65536, 5120)
    assert (cfg.mamba_d_state, cfg.mamba_d_conv, cfg.mamba_dt_rank) == (
        16, 4, 160)
    kinds = cfg.layer_kinds()
    assert [i for i, k in enumerate(kinds) if k == "attention"] == [7, 21]
    assert kinds.count("mamba") == 26
    assert cfg.rope_theta is None and cfg.tie_embeddings
    assert cfg.num_experts == 0 and hf["num_experts"] == 1
    for key in ("layer_order", "inner_norms", "eos_token_id", "pad_token_id",
                "initializer_range", "state_dtype", "mamba_init"):
        assert key in hf["assumed"]
    # parameters, reckoned from the shapes: 3.03 B
    shapes = jax.eval_shape(lambda: CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((1, 4), jnp.int32)))["params"]
    n = sum(int(np.prod(s.shape)) for s in jax.tree_util.tree_leaves(shapes))
    assert 3.02e9 < n < 3.04e9


def test_cost_model_prices_layer_kinds():
    """``LMCostModel`` on the published widths: 3.03 B parameters, a decode
    step of 128 rows at 2048 positions streams 8.7 GB of which the state,
    read and written, is 2.39; a model of attention layers alone is priced as
    it was."""
    from tpu_air.observability.perf import LMCostModel

    with open(os.path.join(REPO, "benchmark", "configs",
                           "jamba2-3b.json")) as f:
        hf = json.load(f)
    m = LMCostModel(hf_import.lm_config_from_hf(hf, dtype="bfloat16",
                                                max_seq_len=2048))
    assert (m.n_attn_layers, m.n_mamba_layers, m.n_kv_heads) == (2, 26, 1)
    assert m.param_count == pytest.approx(3.03e9, rel=2e-3)
    assert 128 * m.state_bytes_per_row == 26 * 128 * (
        5120 * 16 * 4 + 3 * 5120 * 2)
    step = m.decode_step_cost(128, 2048)
    assert step.hbm_bytes == pytest.approx(8.71e9, rel=2e-3)
    assert m.kv_bytes_per_position == 2 * 2 * 128 * 2
    plain = LMCostModel(LMConfig.tiny())
    assert plain.state_bytes_per_row == 0 and plain.n_mamba_layers == 0
    assert plain._attn_params == 4 * 64 * 4 * 16
    assert plain.kv_bytes_per_position == 2 * 2 * 4 * 16 * 4
