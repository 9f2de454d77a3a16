"""What ONE expert-parallel rank of a Mamba-2 / latent sparse-expert decoder
(``model_type: nemotron_h``) needs, from the configuration file's dict alone
(``cfg``: its ``n_routed_experts`` the experts the chip HOLDS, its
``deployment.router_width`` what the router scores): the bytes one paged
decode step must move, the bytes of the Mamba-2 state, the bytes of the held
experts.  A change to the program cannot move them.  State, K/V and experts
are counted by what the engine reports LIVE or touched (rows whose state the
step advanced, positions those rows hold, experts any row was routed to),
never by ``num_slots`` or ``slot_len``: a later program that stops touching
dead rows must not read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    pattern = cfg["hybrid_override_pattern"][:cfg["num_hidden_layers"]]
    return {"mamba": pattern.count("M"), "attention": pattern.count("*"),
            "experts": pattern.count("E")}


def mamba_params(cfg: Dict[str, Any]) -> int:
    """One Mamba-2 layer's matrices: ``in_proj`` to ``[z | xBC | dt]`` and
    ``out_proj`` (76.0 M + 33.6 M at the published widths); the convolution,
    the scalars a head and the norms are left out."""
    d = cfg["hidden_size"]
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    return d * (inner + conv + cfg["mamba_num_heads"]) + inner * d


def attention_params(cfg: Dict[str, Any]) -> int:
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    return 2 * d * hd * (cfg["num_attention_heads"]
                         + cfg["num_key_value_heads"])


def expert_params(cfg: Dict[str, Any]) -> int:
    """The TWO matrices of one routed expert, in the latent (5.505 M)."""
    return 2 * cfg["moe_latent_size"] * cfg["moe_intermediate_size"]


def expert_layer_fixed_params(cfg: Dict[str, Any]) -> int:
    """What an expert layer streams whatever the routing: the router over
    the whole width, the latent pair, the shared expert's two matrices."""
    d = cfg["hidden_size"]
    width = cfg.get("deployment", {}).get("router_width",
                                          cfg["n_routed_experts"])
    return (d * width + 2 * d * cfg["moe_latent_size"]
            + 2 * d * cfg["moe_shared_expert_intermediate_size"])


def state_bytes(cfg: Dict[str, Any], rows: float, state_el: int = 4,
                tail_el: int = 2) -> float:
    """The per-slot state of ``rows`` sequences over all Mamba-2 layers: the
    float32 state ``[heads, head_dim, d_state]`` (4.19 MB) and the bf16
    convolution tail ``[conv_kernel - 1, conv_dim]`` of each."""
    inner = cfg["mamba_num_heads"] * cfg["mamba_head_dim"]
    conv = inner + 2 * cfg["n_groups"] * cfg["ssm_state_size"]
    per_row = (inner * cfg["ssm_state_size"] * state_el
               + (cfg["conv_kernel"] - 1) * conv * tail_el)
    return layer_counts(cfg)["mamba"] * rows * per_row


def held_expert_bytes(cfg: Dict[str, Any], experts_streamed: float,
                      bytes_el: int = 2) -> float:
    """Two matrices of each of ``experts_streamed`` held experts (a step's
    count over layers: the engine's)."""
    return experts_streamed * expert_params(cfg) * bytes_el


def kv_bytes(cfg: Dict[str, Any], positions: float,
             bytes_el: int = 2) -> float:
    """K and V of ``positions`` positions over the attention layers."""
    return (layer_counts(cfg)["attention"] * positions * 2
            * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_el)


def decode_step_bytes(cfg: Dict[str, Any], rows_live: float,
                      positions_live: float, experts_streamed: float,
                      bytes_el: int = 2) -> Dict[str, float]:
    """Bytes ONE decode step must move:

    * every held weight outside the routed experts once, but the embedding
      (its rows are gathered): the Mamba-2 and attention matrices, router,
      latent pair and shared expert of every expert layer, the head over the
      vocabulary slice; norms, convolutions and scalars are left out;
    * two matrices of each held expert the step touched
      (``experts_streamed``, summed over layers: the engine's count);
    * the float32 state and the bf16 tail of the ``rows_live`` rows whose
      state the step advanced, read and written;
    * K and V at ``positions_live`` (the sum of those rows' lengths).
    """
    kinds = layer_counts(cfg)
    mamba = kinds["mamba"] * mamba_params(cfg) * bytes_el
    attention = kinds["attention"] * attention_params(cfg) * bytes_el
    fixed = kinds["experts"] * expert_layer_fixed_params(cfg) * bytes_el
    head = cfg["hidden_size"] * cfg["vocab_size"] * bytes_el
    experts = held_expert_bytes(cfg, experts_streamed, bytes_el)
    state = 2 * state_bytes(cfg, rows_live)
    kv = kv_bytes(cfg, positions_live, bytes_el)
    return {"mamba_weight_bytes": mamba, "attention_weight_bytes": attention,
            "expert_layer_fixed_bytes": fixed, "head_bytes": head,
            "held_expert_bytes": experts, "state_bytes": state,
            "kv_bytes": kv,
            "total_bytes": (mamba + attention + fixed + head + experts
                            + state + kv)}
