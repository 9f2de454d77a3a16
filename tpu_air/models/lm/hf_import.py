"""Published (Hugging Face) OLMoE, Jamba, DeepSeek-V3, Xing4.0, Nemotron-H and
Laguna configurations and weights -> ``LMConfig`` and this framework's ``CausalLM``
parameter tree.

Beside T5's importer (models/t5/hf_import.py).  Pure numpy: the converter
only transposes, permutes and stacks, so it works on any element type (the
benchmark hands it 16-bit views of bf16 tensors).

The one boundary that is not a renaming is the rotary embedding.  The
published model pairs dimension ``i`` of a head with ``i + d/2`` ("rotate
half"); ``modeling.rope`` pairs ``2i`` with ``2i + 1``.  A dot product of a
rotated query and key is the same in any ordering of a head's dimensions as
long as both use it, so the importer reorders the COLUMNS of the q and k
projections inside each head (``rope_columns``): column ``2i`` of the
program is the published column ``i``, column ``2i + 1`` the published
``i + d/2``.  The q and k RMSNorms sit between projection and rope, act
elementwise over the whole ``h*d`` vector and divide by a mean no ordering
changes, so their weights are reordered the same way.  v, o and everything
else are untouched.

``model_type: deepseek_v3`` needs no such reordering: its published rotary
embedding takes a head's rope dimensions in PAIRS ``(2j, 2j + 1)`` (the
modeling code de-interleaves them before its rotate-half), which is
``modeling.rope``'s own pairing, so the rope columns of ``q_b_proj`` and
``kv_a_proj_with_mqa`` go in as published.  What is not a renaming there:
``kv_b_proj`` is cut into its two halves a head (``k_up``, ``v_up``:
``modeling.LatentAttention`` uses them apart), and the tree may hold a SHARE
of the model (an expert-parallel rank): a range of the routed experts and a
range of the vocabulary's rows.

``model_type: xing4_0`` is ``deepseek_v3`` inside a residual path of
``hc_mult`` streams: the same keys and tensors plus, a sublayer, the three
tensors of its hyper-connection (``modeling.HyperConnection``), whose
published NAMES the catalog does not give: :data:`XING_MHC_NAMES` is what is
assumed, and a caller that knows better hands ``names=``.

``model_type: nemotron_h`` is renaming and transposition alone: its
attention has no position encoding, the layer pattern goes in as published
(``hybrid_override_pattern``), and the Mamba-2 mixer's ``in_proj`` keeps its
published column order ``[z | x B C | dt]``.  The tree may hold a share, as
above.

``model_type: laguna`` (window layers beside full ones): ``layer_types``
goes in as ``LMConfig.layer_mixers``, the per-layer lists of heads and of
feed-forward kinds as the by-kind numbers they are a function of (refused
where they are not), ``rope_parameters`` a kind.  Its rotary embedding is
rotate-half over the LEADING share of a head that turns, so the q and k
columns are reordered as OLMoE's are, inside that share alone
(``rope_columns(.., turned)``).  The catalog gives no tensor names:
:data:`LAGUNA_NAMES` is what is assumed, and a caller that knows better hands
``names=``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import numpy as np

from .config import LMConfig

#: published keys this importer maps, and the ``LMConfig`` field of each
HF_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "intermediate_size": "d_ff",          # the width of ONE expert
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "vocab_size": "vocab_size",
    "max_position_embeddings": "max_seq_len",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rmsnorm_eps",
    "tie_word_embeddings": "tie_embeddings",
}

#: published keys that must hold exactly this value: what the layer as
#: written in modeling.py computes (no bias, no clipping, top-k weights not
#: renormalised, plain rope, SiLU gate, every head with its own K/V)
HF_FIXED = {
    "model_type": "olmoe",
    "attention_bias": False,
    "clip_qkv": None,
    "norm_topk_prob": False,
    "rope_scaling": None,
    "hidden_act": "silu",
}


#: ``model_type: jamba`` (AI21-Jamba2-3B): the keys mapped, and the values
#: that must hold.  No rope key exists in this family: attention layers take
#: no position encoding (``rope_theta`` None), the Mamba layers carry order.
JAMBA_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "rmsnorm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "attn_layer_period": "attn_layer_period",
    "attn_layer_offset": "attn_layer_offset",
    "mamba_expand": "mamba_expand",
    "mamba_d_state": "mamba_d_state",
    "mamba_d_conv": "mamba_d_conv",
    "mamba_dt_rank": "mamba_dt_rank",
}
JAMBA_FIXED = {
    "num_experts": 1,           # every feed-forward is the one SwiGLU
    "sliding_window": None,
    "mamba_conv_bias": True,
    "mamba_proj_bias": False,
    "hidden_act": "silu",
}


#: ``model_type: deepseek_v3`` (GigaChat3.1-702B-A36B): the keys mapped, and
#: the values that must hold.  ``rope_scaling`` is null or yarn.
DEEPSEEK_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",
    "moe_intermediate_size": "d_ff",       # one routed or shared expert
    "intermediate_size": "dense_d_ff",     # the leading dense layers
    "n_routed_experts": "num_experts",     # what the router scores
    "num_experts_per_tok": "num_experts_per_tok",
    "n_shared_experts": "num_shared_experts",
    "first_k_dense_replace": "first_dense_layers",
    "n_group": "router_groups",
    "topk_group": "router_topk_groups",
    "routed_scaling_factor": "router_scale",
    "q_lora_rank": "q_lora_rank",
    "kv_lora_rank": "kv_lora_rank",
    "qk_nope_head_dim": "qk_nope_head_dim",
    "qk_rope_head_dim": "qk_rope_head_dim",
    "v_head_dim": "v_head_dim",
    "vocab_size": "vocab_size",
    "rope_theta": "rope_theta",
    "rms_norm_eps": "rmsnorm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
DEEPSEEK_FIXED = {
    "moe_layer_freq": 1,
    "topk_method": "noaux_tc",
    "scoring_func": "sigmoid",
    "norm_topk_prob": True,
    "attention_bias": False,
    "hidden_act": "silu",
}
#: published ``rope_scaling`` keys (``rope_type: yarn``) -> ``LMConfig``
YARN_KEYS = {
    "factor": "rope_factor",
    "original_max_position_embeddings": "rope_original_len",
    "beta_fast": "rope_beta_fast",
    "beta_slow": "rope_beta_slow",
    "mscale": "rope_mscale",
    "mscale_all_dim": "rope_mscale_all_dim",
}


#: ``model_type: xing4_0`` (Xing4.0-29B-A4B): ``DEEPSEEK_KEYS`` and these
XING_KEYS = {
    "hc_mult": "hc_mult",
    "hc_sinkhorn_iters": "hc_sinkhorn_iters",
    "hc_eps": "hc_eps",
}
#: the published names of a sublayer's hyper-connection tensors, ASSUMED:
#: ``{layer}`` the layer's number, ``{sublayer}`` ``attn`` or ``mlp``;
#: ``phi`` as a linear layer stores its weight, ``[n*n + 2n, n*C]``
XING_MHC_NAMES = {
    "phi": "model.layers.{layer}.{sublayer}_hc.phi.weight",
    "b": "model.layers.{layer}.{sublayer}_hc.bias",
    "alpha": "model.layers.{layer}.{sublayer}_hc.alpha",
}


#: ``model_type: nemotron_h`` (NVIDIA-Nemotron-3-Super-120B-A12B): the keys
#: mapped, and the values that must hold.  ``rope_theta`` and
#: ``partial_rotary_factor`` are published and read by nothing: the family's
#: attention applies no position encoding.
NEMOTRON_H_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "hybrid_override_pattern": "layer_pattern",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "moe_intermediate_size": "d_ff",       # one routed expert
    "moe_shared_expert_intermediate_size": "shared_d_ff",
    "moe_latent_size": "moe_latent_size",
    "n_routed_experts": "num_experts",     # what the router scores
    "num_experts_per_tok": "num_experts_per_tok",
    "n_shared_experts": "num_shared_experts",
    "n_group": "router_groups",
    "topk_group": "router_topk_groups",
    "routed_scaling_factor": "router_scale",
    "mamba_num_heads": "mamba_n_heads",
    "mamba_head_dim": "mamba_head_dim",
    "n_groups": "mamba_n_groups",
    "ssm_state_size": "mamba_d_state",
    "conv_kernel": "mamba_d_conv",
    "chunk_size": "mamba_chunk_size",
    "vocab_size": "vocab_size",
    "layer_norm_epsilon": "rmsnorm_eps",
    "tie_word_embeddings": "tie_embeddings",
}
NEMOTRON_H_FIXED = {
    "attention_bias": False,
    "mlp_bias": False,
    "use_bias": False,
    "mamba_proj_bias": False,
    "use_conv_bias": True,
    "mamba_hidden_act": "silu",
    "mlp_hidden_act": "relu2",
    "norm_topk_prob": True,
    "sliding_window": None,
}


#: ``model_type: laguna`` (Laguna-XS.2): the keys mapped, and the values that
#: must hold (no bias, top-k weights renormalised, no soft cap on the router's
#: logits, every layer past the dense ones sparse, a gate a head)
LAGUNA_KEYS = {
    "hidden_size": "d_model",
    "num_hidden_layers": "n_layers",
    "num_attention_heads": "n_heads",        # a full layer's
    "num_key_value_heads": "n_kv_heads",
    "head_dim": "head_dim",
    "intermediate_size": "dense_d_ff",
    "moe_intermediate_size": "d_ff",         # the width of ONE expert
    "shared_expert_intermediate_size": "shared_d_ff",
    "num_experts": "num_experts",
    "num_experts_per_tok": "num_experts_per_tok",
    "vocab_size": "vocab_size",
    "rms_norm_eps": "rmsnorm_eps",
    "tie_word_embeddings": "tie_embeddings",
    "sliding_window": "sliding_window",
    "moe_routed_scaling_factor": "router_scale",
}
LAGUNA_FIXED = {
    "attention_bias": False,
    "norm_topk_prob": True,
    "moe_router_logit_softcapping": 0,
    "moe_apply_router_weight_on_input": False,
    "decoder_sparse_step": 1,
}
#: the kinds of layer as published -> ``LMConfig.layer_kinds()``'s
LAGUNA_LAYER_TYPES = {"full_attention": "attention",
                      "sliding_attention": "window"}
#: tensor names ASSUMED for ``laguna`` (``qwen2_moe``'s, whose key names the
#: config carries; the gate as ``g_proj``, the selection bias under
#: ``deepseek_v3``'s name)
LAGUNA_NAMES = {
    "embed": "model.embed_tokens.weight",
    "head": "lm_head.weight",
    "final_norm": "model.norm.weight",
    "attn_norm": "model.layers.{i}.input_layernorm.weight",
    "mlp_norm": "model.layers.{i}.post_attention_layernorm.weight",
    "q": "model.layers.{i}.self_attn.q_proj.weight",
    "k": "model.layers.{i}.self_attn.k_proj.weight",
    "v": "model.layers.{i}.self_attn.v_proj.weight",
    "o": "model.layers.{i}.self_attn.o_proj.weight",
    "g": "model.layers.{i}.self_attn.g_proj.weight",
    "dense": "model.layers.{i}.mlp.{m}_proj.weight",
    "router": "model.layers.{i}.mlp.gate.weight",
    "router_bias": "model.layers.{i}.mlp.gate.e_score_correction_bias",
    "expert": "model.layers.{i}.mlp.experts.{e}.{m}_proj.weight",
    "shared": "model.layers.{i}.mlp.shared_expert.{m}_proj.weight",
}


def _check_fixed(hf: Dict[str, Any], fixed: Dict[str, Any]) -> None:
    for key, want in fixed.items():
        if hf.get(key, want) != want:
            raise ValueError(
                f"published {key}={hf.get(key)!r}: only {want!r} is "
                "implemented (models/lm/modeling.py)")


def _jamba_config_from_hf(hf: Dict[str, Any], dtype: str,
                          **overrides: Any) -> LMConfig:
    _check_fixed(hf, JAMBA_FIXED)
    fields = {ours: hf[theirs] for theirs, ours in JAMBA_KEYS.items()}
    fields.update(
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        rope_theta=None,
        max_seq_len=hf.get("max_position_embeddings", 2048),
        pad_token_id=hf.get("pad_token_id") or 0,
        eos_token_id=hf.get("eos_token_id"),
        dtype=dtype)
    fields.update(overrides)
    return LMConfig(**fields)


def _deepseek_config_from_hf(hf: Dict[str, Any], dtype: str,
                             **overrides: Any) -> LMConfig:
    _check_fixed(hf, DEEPSEEK_FIXED)
    kv = hf.get("num_key_value_heads", hf["num_attention_heads"])
    if kv != hf["num_attention_heads"]:
        raise ValueError(
            f"num_key_value_heads {kv} != num_attention_heads: every head of "
            "latent attention expands its own K and V")
    fields = {ours: hf[theirs] for theirs, ours in DEEPSEEK_KEYS.items()}
    scaling = hf.get("rope_scaling")
    if scaling is not None:
        kind = scaling.get("rope_type", scaling.get("type"))
        if kind != "yarn":
            raise ValueError(f"rope_scaling {kind!r}: only yarn is "
                             "implemented (models/lm/modeling.py)")
        fields.update({ours: scaling[theirs]
                       for theirs, ours in YARN_KEYS.items()
                       if theirs in scaling})
    if hf.get("model_type") == "xing4_0":
        fields.update({ours: hf[theirs]
                       for theirs, ours in XING_KEYS.items()})
        fields["hc_res_clamp"] = (hf["mhc_h_res_clamp_min"],
                                  hf["mhc_h_res_clamp_max"])
    fields.update(
        head_dim=hf["qk_nope_head_dim"] + hf["qk_rope_head_dim"],
        router="sigmoid_groups",
        max_seq_len=hf.get("max_position_embeddings", 2048),
        pad_token_id=hf.get("pad_token_id") or 0,
        eos_token_id=hf.get("eos_token_id"),
        dtype=dtype)
    fields.update(overrides)
    return LMConfig(**fields)


def _nemotron_h_config_from_hf(hf: Dict[str, Any], dtype: str,
                               **overrides: Any) -> LMConfig:
    _check_fixed(hf, NEMOTRON_H_FIXED)
    fields = {ours: hf[theirs] for theirs, ours in NEMOTRON_H_KEYS.items()}
    # a tree of fewer layers than the pattern publishes holds its first ones
    fields["layer_pattern"] = fields["layer_pattern"][:fields["n_layers"]]
    fields.update(
        rope_theta=None, router="sigmoid_groups", ff_act="relu2",
        max_seq_len=hf.get("max_position_embeddings", 2048),
        pad_token_id=hf.get("pad_token_id") or 0,
        eos_token_id=hf.get("eos_token_id"),
        dtype=dtype)
    fields.update(overrides)
    return LMConfig(**fields)


def _laguna_config_from_hf(hf: Dict[str, Any], dtype: str,
                           **overrides: Any) -> LMConfig:
    _check_fixed(hf, LAGUNA_FIXED)
    if hf.get("gating") not in (True, "per-head"):
        raise ValueError(f"published gating={hf.get('gating')!r}: only a "
                         "gate a head is implemented (models/lm/modeling.py)")
    fields = {ours: hf[theirs] for theirs, ours in LAGUNA_KEYS.items()}
    n = fields["n_layers"]
    # a tree of fewer layers than the lists publish holds their first ones
    lists = {key: list(hf[key])[:n] for key in (
        "layer_types", "mlp_layer_types", "num_attention_heads_per_layer")}
    for key, got in lists.items():
        if len(got) != n:
            raise ValueError(f"published {key} has {len(got)} entries for "
                             f"{n} layers")
    mixers = [LAGUNA_LAYER_TYPES.get(t) for t in lists["layer_types"]]
    if None in mixers:
        raise ValueError(f"published layer_types {lists['layer_types']}: "
                         f"only {sorted(LAGUNA_LAYER_TYPES)} are implemented")
    heads = {m: {h for m2, h in zip(
        mixers, lists["num_attention_heads_per_layer"]) if m2 == m}
        for m in set(mixers)}
    if any(len(v) != 1 for v in heads.values()) or heads.get(
            "attention", {fields["n_heads"]}) != {fields["n_heads"]}:
        raise ValueError(
            "published num_attention_heads_per_layer "
            f"{lists['num_attention_heads_per_layer']} is not one count a "
            "kind of layer with num_attention_heads on the full kind")
    dense = sum(t == "dense" for t in lists["mlp_layer_types"])
    if lists["mlp_layer_types"] != ["dense"] * dense + ["sparse"] * (
            n - dense):
        raise ValueError(
            f"published mlp_layer_types {lists['mlp_layer_types']}: only "
            "leading dense layers are implemented")
    full = hf["rope_parameters"]["full_attention"]
    slide = hf["rope_parameters"].get("sliding_attention", full)
    if slide.get("rope_type", "default") != "default":
        raise ValueError("a sliding layer's rope is plain: rope_type "
                         f"{slide.get('rope_type')!r}")
    fields.update(
        layer_mixers=tuple(mixers),
        window_n_heads=next(iter(heads.get("window", {fields["n_heads"]}))),
        first_dense_layers=dense, num_shared_experts=1,
        router="sigmoid_groups", attn_gate="per_head",
        rope_theta=full["rope_theta"],
        rope_fraction=full.get("partial_rotary_factor", 1.0),
        window_rope_theta=slide["rope_theta"],
        window_rope_fraction=slide.get("partial_rotary_factor", 1.0),
        max_seq_len=hf.get("max_position_embeddings", 2048),
        pad_token_id=hf.get("pad_token_id") or 0,
        eos_token_id=hf.get("eos_token_id"),
        dtype=dtype)
    kind = full.get("rope_type", "default")
    if kind == "yarn":
        import math

        fields.update({ours: full[theirs]
                       for theirs, ours in YARN_KEYS.items()
                       if theirs in full})
        # cos and sin times attention_factor, which LMConfig holds as the
        # mscale yarn's own formula makes it of: 0.1 mscale ln(factor) + 1
        factor = full.get("attention_factor")
        if factor is not None:
            fields["rope_mscale"] = (factor - 1.0) / (
                0.1 * math.log(full["factor"]))
    elif kind != "default":
        raise ValueError(f"rope_type {kind!r}: only yarn is implemented "
                         "(models/lm/modeling.py)")
    fields.update(overrides)
    return LMConfig(**fields)


def lm_config_from_hf(hf: Dict[str, Any], dtype: str = "float32",
                      **overrides: Any) -> LMConfig:
    """``LMConfig`` of a published ``olmoe``, ``jamba``, ``deepseek_v3``,
    ``xing4_0`` or ``nemotron_h`` ``config.json`` (a dict).  Refuses a configuration whose layer this
    framework does not compute.  A tree that holds a share of a
    ``deepseek_v3`` or ``nemotron_h`` model says so in ``overrides``: ``experts_first`` /
    ``experts_held`` (of the ``n_routed_experts`` the router scores) and the
    ``vocab_size`` of its slice.  The multi-token module
    (``num_nextn_predict_layers``) is an extra block the next-token logits do
    not depend on; it is neither made nor run."""
    if hf.get("model_type") == "jamba":
        return _jamba_config_from_hf(hf, dtype, **overrides)
    if hf.get("model_type") in ("deepseek_v3", "xing4_0"):
        return _deepseek_config_from_hf(hf, dtype, **overrides)
    if hf.get("model_type") == "nemotron_h":
        return _nemotron_h_config_from_hf(hf, dtype, **overrides)
    if hf.get("model_type") == "laguna":
        return _laguna_config_from_hf(hf, dtype, **overrides)
    _check_fixed(hf, HF_FIXED)
    kv = hf.get("num_key_value_heads", hf["num_attention_heads"])
    if kv != hf["num_attention_heads"]:
        raise ValueError(
            f"num_key_value_heads {kv} != num_attention_heads: grouped K/V "
            "heads are not implemented")
    fields = {ours: hf[theirs] for theirs, ours in HF_KEYS.items()}
    fields.update(
        head_dim=hf["hidden_size"] // hf["num_attention_heads"],
        qk_norm=True,  # part of the published olmoe layer, not a key
        pad_token_id=hf.get("pad_token_id") or 0,
        eos_token_id=hf.get("eos_token_id"),
        dtype=dtype)
    fields.update(overrides)
    return LMConfig(**fields)


def rope_columns(n_heads: int, head_dim: int,
                 turned: int = None) -> np.ndarray:
    """``perm`` with ``program[..., j] = published[..., perm[j]]`` over the
    ``n_heads * head_dim`` outputs of a q or k projection.  ``turned``: how
    many leading numbers of a head the rope turns (default all): the
    reordering is inside those, the rest stay."""
    turned = head_dim if turned is None else turned
    half = turned // 2
    inside = np.arange(head_dim, dtype=np.int64)
    inside[0:turned:2] = np.arange(half)
    inside[1:turned:2] = np.arange(half) + half
    return (np.arange(n_heads)[:, None] * head_dim + inside[None]).reshape(-1)


def _t(w) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(w).T)


def convert_olmoe_layer(get: Callable[[str], Any], i: int,
                        config: LMConfig) -> Dict[str, Any]:
    """Layer ``i`` of the tree, from ``get(published tensor name)``."""
    pre = f"model.layers.{i}."
    perm = rope_columns(config.n_heads, config.head_dim)
    attn = {
        "q": {"kernel": _t(get(pre + "self_attn.q_proj.weight"))[:, perm]},
        "k": {"kernel": _t(get(pre + "self_attn.k_proj.weight"))[:, perm]},
        "v": {"kernel": _t(get(pre + "self_attn.v_proj.weight"))},
        "o": {"kernel": _t(get(pre + "self_attn.o_proj.weight"))},
        "q_norm": {"weight": np.asarray(
            get(pre + "self_attn.q_norm.weight"))[perm]},
        "k_norm": {"weight": np.asarray(
            get(pre + "self_attn.k_norm.weight"))[perm]},
    }
    experts = range(config.num_experts)
    stack = lambda which: np.stack([  # noqa: E731
        _t(get(f"{pre}mlp.experts.{e}.{which}_proj.weight")) for e in experts])
    return {
        "attn": attn,
        "attn_norm": {"weight": np.asarray(
            get(pre + "input_layernorm.weight"))},
        "mlp_norm": {"weight": np.asarray(
            get(pre + "post_attention_layernorm.weight"))},
        "moe": {"router": _t(get(pre + "mlp.gate.weight")),
                "gate": stack("gate"), "up": stack("up"),
                "down": stack("down")},
    }


def convert_olmoe_state_dict(get: Callable[[str], Any],
                             config: LMConfig) -> Dict[str, Any]:
    """The whole ``CausalLM`` parameter tree from a published ``olmoe``
    state dict, given as ``get(name)`` (``sd.__getitem__`` for a dict)."""
    params: Dict[str, Any] = {
        "embedding": np.asarray(get("model.embed_tokens.weight")),
        "final_norm": {"weight": np.asarray(get("model.norm.weight"))},
    }
    if not config.tie_embeddings:
        params["lm_head"] = {"kernel": _t(get("lm_head.weight"))}
    for i in range(config.n_layers):
        params[f"layer_{i}"] = convert_olmoe_layer(get, i, config)
    return params


def convert_jamba_layer(get: Callable[[str], Any], i: int,
                        config: LMConfig) -> Dict[str, Any]:
    """Layer ``i`` of the tree from the published ``jamba`` names: pure
    renaming and transposition (there is no rope, so no column order to
    keep); the depthwise convolution ``[channels, 1, width]`` becomes
    ``[width, channels]``."""
    pre = f"model.layers.{i}."
    kernel = lambda name: {"kernel": _t(get(pre + name + ".weight"))}  # noqa: E731
    weight = lambda name: {"weight": np.asarray(  # noqa: E731
        get(pre + name + ".weight"))}
    layer = {
        "mlp_norm": weight("pre_ff_layernorm"),
        "mlp": {w: kernel(f"feed_forward.{w}_proj")
                for w in ("gate", "up", "down")},
    }
    if config.layer_kinds()[i] == "attention":
        layer["attn_norm"] = weight("input_layernorm")
        layer["attn"] = {w: kernel(f"self_attn.{w}_proj") for w in "qkvo"}
        return layer
    layer["mamba_norm"] = weight("input_layernorm")
    layer["mamba"] = {
        "in_proj": kernel("mamba.in_proj"),
        "conv": {"kernel": _t(np.asarray(
            get(pre + "mamba.conv1d.weight"))[:, 0, :]),
            "bias": np.asarray(get(pre + "mamba.conv1d.bias"))},
        "x_proj": kernel("mamba.x_proj"),
        "dt_proj": {**kernel("mamba.dt_proj"),
                    "bias": np.asarray(get(pre + "mamba.dt_proj.bias"))},
        "A_log": np.asarray(get(pre + "mamba.A_log")),
        "D": np.asarray(get(pre + "mamba.D")),
        "out_proj": kernel("mamba.out_proj"),
        "dt_norm": weight("mamba.dt_layernorm"),
        "b_norm": weight("mamba.b_layernorm"),
        "c_norm": weight("mamba.c_layernorm"),
    }
    return layer


def convert_jamba_state_dict(get: Callable[[str], Any],
                             config: LMConfig) -> Dict[str, Any]:
    """The whole ``CausalLM`` parameter tree from a published ``jamba`` state
    dict (tied embeddings: no head tensor), given as ``get(name)``."""
    params: Dict[str, Any] = {
        "embedding": np.asarray(get("model.embed_tokens.weight")),
        "final_norm": {"weight": np.asarray(
            get("model.final_layernorm.weight"))},
    }
    if not config.tie_embeddings:
        params["lm_head"] = {"kernel": _t(get("lm_head.weight"))}
    for i in range(config.n_layers):
        params[f"layer_{i}"] = convert_jamba_layer(get, i, config)
    return params


def convert_deepseek_v3_layer(get: Callable[[str], Any], i: int,
                              config: LMConfig,
                              names: Dict[str, str] = XING_MHC_NAMES
                              ) -> Dict[str, Any]:
    """Layer ``i`` of the tree from the published ``deepseek_v3`` names.
    Only the experts the configuration holds are asked for.  With
    ``config.hc_mult > 1`` (``xing4_0``) the layer's two hyper-connections
    come with it, from ``names``."""
    pre = f"model.layers.{i}."
    kernel = lambda name: {"kernel": _t(get(pre + name + ".weight"))}  # noqa: E731
    weight = lambda name: {"weight": np.asarray(  # noqa: E731
        get(pre + name + ".weight"))}
    h, dn, r = config.n_heads, config.qk_nope_head_dim, config.kv_lora_rank
    # [h * (dn + dv), r]: a head's k rows, then its v rows
    kv_b = np.asarray(get(pre + "self_attn.kv_b_proj.weight")).reshape(
        h, dn + config.v_head_dim, r)
    layer = {
        "attn_norm": weight("input_layernorm"),
        "mlp_norm": weight("post_attention_layernorm"),
        "attn": {
            "q_a": kernel("self_attn.q_a_proj"),
            "q_a_norm": weight("self_attn.q_a_layernorm"),
            "q_b": kernel("self_attn.q_b_proj"),
            "kv_a": kernel("self_attn.kv_a_proj_with_mqa"),
            "kv_a_norm": weight("self_attn.kv_a_layernorm"),
            "k_up": np.ascontiguousarray(kv_b[:, :dn].transpose(2, 0, 1)),
            "v_up": np.ascontiguousarray(kv_b[:, dn:].transpose(2, 0, 1)),
            "o": kernel("self_attn.o_proj"),
        },
    }
    if config.hc_mult > 1:
        for sub in ("attn", "mlp"):
            at = lambda k: get(names[k].format(layer=i, sublayer=sub))  # noqa: E731
            layer[sub + "_hc"] = {"phi": _t(at("phi")),
                                  "b": np.asarray(at("b")),
                                  "alpha": np.asarray(at("alpha"))}
    if config.ff_kinds()[i] == "dense":
        layer["mlp"] = {w: kernel(f"mlp.{w}_proj")
                        for w in ("gate", "up", "down")}
        return layer
    experts = range(config.experts_first,
                    config.experts_first + config.experts_held)
    stack = lambda which: np.stack([  # noqa: E731
        _t(get(f"{pre}mlp.experts.{e}.{which}_proj.weight")) for e in experts])
    layer["moe"] = {
        "router": _t(get(pre + "mlp.gate.weight")),
        "router_bias": np.asarray(
            get(pre + "mlp.gate.e_score_correction_bias")),
        "gate": stack("gate"), "up": stack("up"), "down": stack("down")}
    if config.num_shared_experts:
        layer["shared"] = {w: kernel(f"mlp.shared_experts.{w}_proj")
                           for w in ("gate", "up", "down")}
    return layer


def convert_deepseek_v3_state_dict(get: Callable[[str], Any],
                                   config: LMConfig,
                                   vocab_rows: Any = None,
                                   names: Dict[str, str] = XING_MHC_NAMES
                                   ) -> Dict[str, Any]:
    """The ``CausalLM`` parameter tree from a published ``deepseek_v3`` (or,
    with its hyper-connections under ``names``, ``xing4_0``) state
    dict, given as ``get(name)``: the first ``config.n_layers`` layers, the
    routed experts ``config.experts_first .. + config.experts_held`` of each
    sparse layer, and the rows ``vocab_rows`` (a ``range`` or slice; default
    all) of the embedding and of the head (``config.vocab_size`` of them).
    The multi-token module's tensors (``model.layers.<num_hidden_layers>.*``)
    are never asked for."""
    rows = slice(None) if vocab_rows is None else vocab_rows
    if isinstance(rows, range):
        rows = slice(rows.start, rows.stop, rows.step)
    take = lambda name: np.asarray(get(name))[rows]  # noqa: E731
    params: Dict[str, Any] = {
        "embedding": take("model.embed_tokens.weight"),
        "final_norm": {"weight": np.asarray(get("model.norm.weight"))},
    }
    if not config.tie_embeddings:
        params["lm_head"] = {"kernel": _t(take("lm_head.weight"))}
    if params["embedding"].shape[0] != config.vocab_size:
        raise ValueError(
            f"{params['embedding'].shape[0]} vocabulary rows for a "
            f"configuration of {config.vocab_size}")
    for i in range(config.n_layers):
        params[f"layer_{i}"] = convert_deepseek_v3_layer(get, i, config,
                                                         names)
    return params


def convert_nemotron_h_layer(get: Callable[[str], Any], i: int,
                             config: LMConfig) -> Dict[str, Any]:
    """Layer ``i`` of the tree from the published ``nemotron_h`` names
    (``backbone.layers.i.norm`` and ``.mixer.*``, whatever the layer is):
    renaming and transposition; the depthwise convolution ``[channels, 1,
    width]`` becomes ``[width, channels]``.  Only the experts the
    configuration holds are asked for."""
    pre = f"backbone.layers.{i}."
    kernel = lambda name: {"kernel": _t(  # noqa: E731
        get(f"{pre}mixer.{name}.weight"))}
    vector = lambda name: np.asarray(get(f"{pre}mixer.{name}"))  # noqa: E731
    norm = {"weight": np.asarray(get(pre + "norm.weight"))}
    kind = config.layer_pattern[i]
    if kind == "M":
        return {"mamba_norm": norm, "mamba": {
            "in_proj": kernel("in_proj"),
            "conv": {"kernel": _t(vector("conv1d.weight")[:, 0, :]),
                     "bias": vector("conv1d.bias")},
            "dt_bias": vector("dt_bias"), "A_log": vector("A_log"),
            "D": vector("D"), "norm": vector("norm.weight"),
            "out_proj": kernel("out_proj")}}
    if kind == "*":
        return {"attn_norm": norm,
                "attn": {w: kernel(f"{w}_proj") for w in "qkvo"}}
    experts = range(config.experts_first,
                    config.experts_first + config.experts_held)
    stack = lambda which: np.stack([  # noqa: E731
        _t(get(f"{pre}mixer.experts.{e}.{which}_proj.weight"))
        for e in experts])
    return {"mlp_norm": norm,
            "moe": {"router": _t(get(pre + "mixer.gate.weight")),
                    "router_bias": vector("gate.e_score_correction_bias"),
                    "latent_down": kernel("fc1_latent_proj"),
                    "latent_up": kernel("fc2_latent_proj"),
                    "up": stack("up"), "down": stack("down")},
            "shared": {w: kernel(f"shared_experts.{w}_proj")
                       for w in ("up", "down")}}


def convert_nemotron_h_state_dict(get: Callable[[str], Any],
                                  config: LMConfig,
                                  vocab_rows: Any = None) -> Dict[str, Any]:
    """The ``CausalLM`` parameter tree from a published ``nemotron_h`` state
    dict, given as ``get(name)``: the first ``config.n_layers`` layers, the
    routed experts ``config.experts_first .. + config.experts_held`` of each
    ``E`` layer, and the rows ``vocab_rows`` (a ``range`` or slice; default
    all) of the embedding and of the head.  The multi-token module's tensors
    (``mtp.*``) are never asked for."""
    rows = slice(None) if vocab_rows is None else vocab_rows
    if isinstance(rows, range):
        rows = slice(rows.start, rows.stop, rows.step)
    take = lambda name: np.asarray(get(name))[rows]  # noqa: E731
    params: Dict[str, Any] = {
        "embedding": take("backbone.embeddings.weight"),
        "final_norm": {"weight": np.asarray(get("backbone.norm_f.weight"))},
    }
    if not config.tie_embeddings:
        params["lm_head"] = {"kernel": _t(take("lm_head.weight"))}
    if params["embedding"].shape[0] != config.vocab_size:
        raise ValueError(
            f"{params['embedding'].shape[0]} vocabulary rows for a "
            f"configuration of {config.vocab_size}")
    for i in range(config.n_layers):
        params[f"layer_{i}"] = convert_nemotron_h_layer(get, i, config)
    return params


def convert_laguna_layer(get: Callable[[str], Any], i: int, config: LMConfig,
                         names: Dict[str, str] = LAGUNA_NAMES
                         ) -> Dict[str, Any]:
    """Layer ``i`` of the tree from a ``laguna`` state dict under ``names``:
    the layer's kind says how many query heads ``q``, ``o`` and the gate
    have and how much of a head the rope turns."""
    at = lambda key, **kw: get(names[key].format(i=i, **kw))  # noqa: E731
    kind = config.layer_kinds()[i]
    turned = config.rope_of(kind)[1]
    d = config.head_dim
    layer = {
        "attn_norm": {"weight": np.asarray(at("attn_norm"))},
        "mlp_norm": {"weight": np.asarray(at("mlp_norm"))},
        "attn": {
            "q": {"kernel": _t(at("q"))[:, rope_columns(
                config.heads_of(kind), d, turned)]},
            "k": {"kernel": _t(at("k"))[:, rope_columns(
                config.n_kv_heads, d, turned)]},
            "v": {"kernel": _t(at("v"))},
            "o": {"kernel": _t(at("o"))},
            "gate": {"kernel": _t(at("g"))},
        },
    }
    three = lambda key, **kw: {  # noqa: E731
        m: {"kernel": _t(at(key, m=m, **kw))} for m in ("gate", "up", "down")}
    if config.ff_kinds()[i] == "dense":
        layer["mlp"] = three("dense")
        return layer
    stack = lambda m: np.stack([  # noqa: E731
        _t(at("expert", e=e, m=m)) for e in range(config.num_experts)])
    layer["moe"] = {
        "router": _t(at("router")),
        "router_bias": np.asarray(at("router_bias")),
        "gate": stack("gate"), "up": stack("up"), "down": stack("down")}
    layer["shared"] = three("shared")
    return layer


def convert_laguna_state_dict(get: Callable[[str], Any], config: LMConfig,
                              names: Dict[str, str] = LAGUNA_NAMES
                              ) -> Dict[str, Any]:
    """The ``CausalLM`` parameter tree from a ``laguna`` state dict, given as
    ``get(name)``: the first ``config.n_layers`` layers, every expert."""
    params: Dict[str, Any] = {
        "embedding": np.asarray(get(names["embed"])),
        "final_norm": {"weight": np.asarray(get(names["final_norm"]))},
    }
    if not config.tie_embeddings:
        params["lm_head"] = {"kernel": _t(get(names["head"]))}
    for i in range(config.n_layers):
        params[f"layer_{i}"] = convert_laguna_layer(get, i, config, names)
    return params
