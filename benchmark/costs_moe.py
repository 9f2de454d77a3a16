"""What a sparse-expert decoder needs: the bytes one paged decode step must
move for the cached positions it has live, and the bytes of one grouped
expert product.  Counted from the published configuration (``cfg``: the
configuration file's dict) and the run's live count; a change to the program
cannot move them.
"""

from __future__ import annotations

from typing import Any, Dict


def expert_matrix_bytes(cfg: Dict[str, Any], bytes_el: int = 2) -> int:
    """One of an expert's three matrices."""
    return cfg["hidden_size"] * cfg["intermediate_size"] * bytes_el


def live_step_bytes(cfg: Dict[str, Any], live_positions: float,
                    bytes_el: int = 2) -> Dict[str, float]:
    """Bytes ONE decode step MUST move through HBM for what it has live:

    * every expert of every layer (three matrices each): at 64 rows x 8
      assignments every expert is touched, and an idle slot's row is
      computed like a live one;
    * the attention projections (q, k, v, o) and the router of every layer;
    * the untied head (the embedding is read by row, left out, as are the
      norms);
    * K and V of every layer at ``live_positions``: the cached positions the
      step's rows hold, summed over the rows (a row at position ``p`` reads
      ``p + 1``), whatever the pool could hold and whatever the program
      gathers: ``costs_mla.decode_step_bytes`` and
      ``costs_swa.cached_read_bytes`` count their kinds the same way, so a
      step that reads its pages in place stays under the peak.
    """
    d, layers = cfg["hidden_size"], cfg["num_hidden_layers"]
    hd = d  # heads x head size = hidden_size in this family
    experts = layers * cfg["num_experts"] * 3 * expert_matrix_bytes(
        cfg, bytes_el)
    attention = layers * (4 * d * hd + d * cfg["num_experts"]) * bytes_el
    head = d * cfg["vocab_size"] * bytes_el
    kv = layers * 2 * live_positions * hd * bytes_el
    return {"expert_bytes": experts, "attention_router_bytes": attention,
            "head_bytes": head, "kv_bytes": kv,
            "total_bytes": experts + attention + head + kv}


def decode_step_bytes(cfg: Dict[str, Any], slots: int, slot_len: int,
                      bytes_el: int = 2) -> Dict[str, float]:
    """:func:`live_step_bytes` with every slot full to ``slot_len``: what the
    pool could hold.  No reader prices a step at it since PR 61 (a step that
    read only its live pages would read over 100 % of it); it stays for
    ``tests/test_olmoe.py``, which holds its hand counts, until a PR that may
    edit that file moves the test to :func:`live_step_bytes` (PERF.md 7)."""
    return live_step_bytes(cfg, slots * slot_len, bytes_el)
