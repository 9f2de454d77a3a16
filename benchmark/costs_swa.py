"""What a decoder whose layers keep their K and V in two ways (``model_type:
laguna``: window layers beside full ones, query heads counted by layer kind,
a gate a head, sparse experts all held) needs, from the configuration file's
dict alone: parameters and bytes held, the bytes one step streams, the bytes
of each kind's cached read.  A change to the program cannot move them.

K and V are counted at the positions the engine counted LIVE, never at
``slot_len`` or at the ring's length: a row at position ``p`` reads ``p + 1``
positions of each full layer and ``min(p + 1, sliding_window)`` of each
window layer.  A program that gathers a slot's pages at their full length
reads under 100 % by how much it gathers in vain; one that reads the live
pages in place must not read over it.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    kinds = cfg["layer_types"][:cfg["num_hidden_layers"]]
    ff = cfg["mlp_layer_types"][:cfg["num_hidden_layers"]]
    return {"full": kinds.count("full_attention"),
            "window": kinds.count("sliding_attention"),
            "dense": ff.count("dense"), "sparse": ff.count("sparse")}


def attention_params(cfg: Dict[str, Any], layer: int) -> int:
    """Layer ``layer``'s attention matrices: q and o at the layer's own head
    count, k and v at the K/V heads, the gate (29.46 M a full layer and 37.88
    M a window layer at the published widths)."""
    d, hd = cfg["hidden_size"], cfg["head_dim"]
    h = cfg["num_attention_heads_per_layer"][layer]
    return (2 * d * h * hd + 2 * d * cfg["num_key_value_heads"] * hd
            + d * h)


def expert_params(cfg: Dict[str, Any]) -> int:
    """The three matrices of one routed expert (3.146 M)."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def param_count(cfg: Dict[str, Any]) -> int:
    """Every matrix the chip holds (norms left out): embedding and head, the
    attention of each layer, the dense feed-forward, every expert, the shared
    one and the router of each sparse layer (3,870 M as cut)."""
    d, kinds = cfg["hidden_size"], layer_counts(cfg)
    tied = bool(cfg.get("tie_word_embeddings"))
    return ((1 if tied else 2) * cfg["vocab_size"] * d
            + sum(attention_params(cfg, i)
                  for i in range(cfg["num_hidden_layers"]))
            + kinds["dense"] * 3 * d * cfg["intermediate_size"]
            + kinds["sparse"] * (
                cfg["num_experts"] * expert_params(cfg)
                + 3 * d * cfg["shared_expert_intermediate_size"]
                + d * cfg["num_experts"]))


def kv_position_bytes(cfg: Dict[str, Any], bytes_el: int = 2) -> int:
    """K and V of one position of one layer (4 KB in bfloat16): the same for
    both kinds, which share the K/V heads."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * bytes_el


def cached_read_bytes(cfg: Dict[str, Any], positions: float,
                      bytes_el: int = 2) -> float:
    """The K and V of ``positions`` live positions x layers (the engine's
    count of either kind: it sums over the kind's layers)."""
    return positions * kv_position_bytes(cfg, bytes_el)


def expert_bytes(cfg: Dict[str, Any], experts_streamed: float,
                 bytes_el: int = 2) -> float:
    """Three matrices of each of ``experts_streamed`` experts (a step's
    count over layers: the caller's)."""
    return experts_streamed * expert_params(cfg) * bytes_el


def ring_held_share(ring_len: int, slot_len: int) -> float:
    """What a window layer holds as a ring of ``ring_len`` positions a slot,
    in percent of what it would hold as pages at ``slot_len``."""
    return 100.0 * ring_len / slot_len


def step_bytes(cfg: Dict[str, Any], experts_streamed: float,
               ring_positions: float, page_positions: float,
               bytes_el: int = 2) -> Dict[str, float]:
    """Bytes ONE step streams from HBM:

    * every held weight once but the embedding (its rows are gathered) and
      the routed experts: attention of every layer, the dense feed-forward,
      router and shared expert of every sparse layer, the head; norms are
      left out;
    * the routed experts the step touched, ``experts_streamed`` summed over
      layers (the engine's count), three matrices each;
    * K and V at the live positions x layers of each kind (the engine's
      counts over the decoding rows; a mixed step's chunk reads its own
      slot's besides, which is left out: a floor).
    """
    d, kinds = cfg["hidden_size"], layer_counts(cfg)
    attention = sum(attention_params(cfg, i)
                    for i in range(cfg["num_hidden_layers"])) * bytes_el
    dense = kinds["dense"] * 3 * d * cfg["intermediate_size"] * bytes_el
    router = kinds["sparse"] * d * cfg["num_experts"] * bytes_el
    shared = (kinds["sparse"] * 3 * d
              * cfg["shared_expert_intermediate_size"] * bytes_el)
    head = d * cfg["vocab_size"] * bytes_el
    experts = expert_bytes(cfg, experts_streamed, bytes_el)
    ring = cached_read_bytes(cfg, ring_positions, bytes_el)
    pages = cached_read_bytes(cfg, page_positions, bytes_el)
    return {"attention_weight_bytes": attention, "dense_ff_bytes": dense,
            "router_bytes": router, "shared_expert_bytes": shared,
            "head_bytes": head, "expert_bytes": experts,
            "ring_bytes": ring, "page_bytes": pages,
            "total_bytes": (attention + dense + router + shared + head
                            + experts + ring + pages)}
