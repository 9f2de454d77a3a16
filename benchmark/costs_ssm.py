"""What a hybrid state-space / attention decoder (``model_type: jamba``)
needs: the bytes one paged decode step must move for the rows and cached
positions it has live, and the bytes of the recurrent state it reads and
writes.  Counted from the configuration file's dict (``cfg``) and the run's
live counts; a change to the program cannot move them.
"""

from __future__ import annotations

from typing import Any, Dict


def layer_counts(cfg: Dict[str, Any]) -> Dict[str, int]:
    layers = cfg["num_hidden_layers"]
    attention = sum(1 for i in range(layers)
                    if i % cfg["attn_layer_period"] == cfg["attn_layer_offset"])
    return {"attention": attention, "mamba": layers - attention}


def mamba_mixer_params(cfg: Dict[str, Any]) -> int:
    d = cfg["hidden_size"]
    c = cfg["mamba_expand"] * d
    n, k, r = cfg["mamba_d_state"], cfg["mamba_d_conv"], cfg["mamba_dt_rank"]
    return (d * 2 * c          # in_proj
            + c * k + c        # conv1d and its bias
            + c * (r + 2 * n)  # x_proj
            + r * c + c        # dt_proj and its bias
            + c * n + c        # A_log, D
            + c * d            # out_proj
            + r + 2 * n)       # the three inner norms


def attention_mixer_params(cfg: Dict[str, Any]) -> int:
    d, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // heads
    return 2 * d * d + 2 * d * cfg["num_key_value_heads"] * hd


def state_bytes(cfg: Dict[str, Any], slots: float, state_el: int = 4,
                tail_el: int = 2) -> float:
    """The recurrent state of ``slots`` sequences over all Mamba layers: the
    float32 state ``[d_inner, d_state]`` and the bf16 convolution tail
    ``[d_conv - 1, d_inner]`` of each."""
    c = cfg["mamba_expand"] * cfg["hidden_size"]
    per_row = (c * cfg["mamba_d_state"] * state_el
               + (cfg["mamba_d_conv"] - 1) * c * tail_el)
    return layer_counts(cfg)["mamba"] * slots * per_row


def live_step_bytes(cfg: Dict[str, Any], rows_live: float,
                    positions_live: float,
                    bytes_el: int = 2) -> Dict[str, float]:
    """Bytes ONE decode step MUST move through HBM for what it has live:

    * every weight once: the Mamba mixers, the attention mixers, one SwiGLU a
      layer (``num_experts`` 1), the tied head (the embedding read as the
      head's matrix; the rows gathered for the input, and the norms, are
      left out);
    * the recurrent state of the ``rows_live`` rows whose state the step
      ADVANCED, TWICE (read and written): an idle slot's state need not move,
      whatever the program passes over;
    * K and V of the attention layers at ``positions_live``: the cached
      positions those rows hold, summed over the rows, whatever the pool
      could hold and whatever the program gathers (``costs_ssd.
      decode_step_bytes`` counts a Mamba-2 rank the same way).
    """
    d, f = cfg["hidden_size"], cfg["intermediate_size"]
    kinds = layer_counts(cfg)
    hd = d // cfg["num_attention_heads"]
    mamba = kinds["mamba"] * mamba_mixer_params(cfg) * bytes_el
    attention = kinds["attention"] * attention_mixer_params(cfg) * bytes_el
    mlp = cfg["num_hidden_layers"] * 3 * d * f * bytes_el
    head = d * cfg["vocab_size"] * bytes_el
    state = 2 * state_bytes(cfg, rows_live)
    kv = (kinds["attention"] * 2 * positions_live
          * cfg["num_key_value_heads"] * hd * bytes_el)
    return {"mamba_weight_bytes": mamba, "attention_weight_bytes": attention,
            "mlp_bytes": mlp, "head_bytes": head, "state_bytes": state,
            "kv_bytes": kv,
            "total_bytes": mamba + attention + mlp + head + state + kv}


def decode_step_bytes(cfg: Dict[str, Any], slots: int, slot_len: int,
                      bytes_el: int = 2) -> Dict[str, float]:
    """:func:`live_step_bytes` with every slot live and full to ``slot_len``:
    what the pool and the state could hold.  No metric's reader prices a
    step at it since PR 61; it stays for ``tests/test_benchmark_ssm.py`` and
    the reader that file pins (``readers/ssm_hbm_share.py``), until a PR
    that may edit the test moves it to :func:`live_step_bytes` (PERF.md 7)."""
    return live_step_bytes(cfg, slots, slots * slot_len, bytes_el)
