"""What ONE expert-parallel rank of a latent-attention sparse-expert decoder
(``model_type: deepseek_v3``) needs, from the configuration file's dict
alone (``cfg``: its ``n_routed_experts`` the experts the chip HOLDS, its
``deployment.router_width`` what the router scores): the bytes one paged
decode step streams, the bytes and operations of the absorbed attention
read, the bytes of the held experts.  A change to the program cannot move
them.  The latent is counted at the positions the engine counted LIVE, never
at ``slot_len``: a program that reads pages in place instead of gathering
every slot at its full length must not read over 100 %.
"""

from __future__ import annotations

from typing import Any, Dict


def attention_params(cfg: Dict[str, Any]) -> int:
    """One layer's latent-attention matrices: ``q_a``, ``q_b``, ``kv_a``,
    ``kv_b``, ``o`` (132.6 M at the published widths)."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    rq, r = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    dn, dr, dv = (cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"],
                  cfg["v_head_dim"])
    return (d * rq + rq * h * (dn + dr) + d * (r + dr)
            + r * h * (dn + dv) + h * dv * d)


def expert_params(cfg: Dict[str, Any]) -> int:
    """The three matrices of one routed (or shared) expert (44.04 M)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def layer_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    dense = min(cfg["first_k_dense_replace"], cfg["num_hidden_layers"])
    return {"dense": dense, "sparse": cfg["num_hidden_layers"] - dense}


def latent_width(cfg: Dict[str, Any]) -> int:
    """Numbers cached a position a layer: the latent and the roped key."""
    return cfg["kv_lora_rank"] + cfg["qk_rope_head_dim"]


def latent_bytes(cfg: Dict[str, Any], positions: float,
                 bytes_el: int = 2) -> float:
    """The latent of ``positions`` positions over all layers."""
    return (cfg["num_hidden_layers"] * positions * latent_width(cfg)
            * bytes_el)


def absorbed_attention_flops(cfg: Dict[str, Any], positions: float) -> float:
    """Operations the absorbed read NEEDS for one query position a row over
    ``positions`` live positions in all, all layers: every head scores the
    latent row (``r + dr`` multiply-adds) and takes its context in latent
    space (``r``).  121 a byte of latent at the published widths."""
    h, r = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    return (cfg["num_hidden_layers"] * positions * h
            * 2.0 * (latent_width(cfg) + r))


def held_expert_bytes(cfg: Dict[str, Any], experts_streamed: float,
                      bytes_el: int = 2) -> float:
    """Three matrices of each of ``experts_streamed`` held experts (one
    layer's count, or a step's over layers: the caller's)."""
    return experts_streamed * expert_params(cfg) * bytes_el


def decode_step_bytes(cfg: Dict[str, Any], live_positions: float,
                      experts_streamed: float,
                      bytes_el: int = 2) -> Dict[str, float]:
    """Bytes ONE decode step streams from HBM:

    * every held weight once but the embedding (its rows are gathered):
      latent attention's matrices of every layer, the dense feed-forward of
      the leading layers, router and shared expert of every sparse layer,
      the head over the vocabulary slice; norms are left out;
    * the routed experts the step touched, ``experts_streamed`` summed over
      layers (the engine's count), three matrices each;
    * the latent at ``live_positions`` (the sum of the decoding rows'
      lengths, the engine's count), every layer.
    """
    d = cfg["hidden_size"]
    kinds = layer_counts(cfg)
    width = cfg.get("deployment", {}).get("router_width",
                                          cfg["n_routed_experts"])
    attention = cfg["num_hidden_layers"] * attention_params(cfg) * bytes_el
    dense = kinds["dense"] * 3 * d * cfg["intermediate_size"] * bytes_el
    router = kinds["sparse"] * d * width * bytes_el
    shared = (kinds["sparse"] * cfg.get("n_shared_experts", 0)
              * expert_params(cfg) * bytes_el)
    head = d * cfg["vocab_size"] * bytes_el
    experts = held_expert_bytes(cfg, experts_streamed, bytes_el)
    latent = latent_bytes(cfg, live_positions, bytes_el)
    return {"attention_weight_bytes": attention, "dense_ff_bytes": dense,
            "router_bytes": router, "shared_expert_bytes": shared,
            "head_bytes": head, "held_expert_bytes": experts,
            "latent_bytes": latent,
            "total_bytes": (attention + dense + router + shared + head
                            + experts + latent)}
