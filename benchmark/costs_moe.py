"""What a sparse-expert decoder needs, from shapes alone: the bytes one paged
decode step streams and the bytes of one grouped expert product.  Counted
from the published configuration (``cfg``: the configuration file's dict);
a change to the program cannot move them.
"""

from __future__ import annotations

from typing import Any, Dict


def expert_matrix_bytes(cfg: Dict[str, Any], bytes_el: int = 2) -> int:
    """One of an expert's three matrices."""
    return cfg["hidden_size"] * cfg["intermediate_size"] * bytes_el


def decode_step_bytes(cfg: Dict[str, Any], slots: int, slot_len: int,
                      bytes_el: int = 2) -> Dict[str, int]:
    """Bytes ONE decode step over ``slots`` rows streams from HBM:

    * every expert of every layer (three matrices each): at 64 rows x 8
      assignments every expert is touched, and an idle slot's row is
      computed like a live one;
    * the attention projections (q, k, v, o) and the router of every layer;
    * the untied head (the embedding is read by row: ``slots`` rows, left
      out, as are the norms);
    * K and V of every layer for every slot at the full ``slot_len``: what
      the paged step is compiled to read (ops/decode_attention.gather_pages
      over the whole block table), whatever the live lengths are.
    """
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = d  # heads x head size = hidden_size in this family
    experts = layers * cfg["num_experts"] * 3 * expert_matrix_bytes(
        cfg, bytes_el)
    attention = layers * (4 * d * hd + d * cfg["num_experts"]) * bytes_el
    head = d * cfg["vocab_size"] * bytes_el
    kv = layers * 2 * slots * slot_len * hd * bytes_el
    return {"expert_bytes": experts, "attention_router_bytes": attention,
            "head_bytes": head, "kv_bytes": kv,
            "total_bytes": experts + attention + head + kv}
