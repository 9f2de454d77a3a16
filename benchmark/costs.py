"""What the algorithm needs, from shapes alone: operations a trained token
costs and bytes one cached decode step must stream.  These are the yardstick:
they count the mathematics, not what a compiler emitted (no recompute, no
padding waste), so a change to the program cannot move them.

``cfg`` is a configuration file's dict (the published key names).
"""

from __future__ import annotations

from typing import Any, Dict


def _ffn_mats(cfg: Dict[str, Any]) -> int:
    return 3 if "gated" in cfg["feed_forward_proj"] else 2


def forward_macs(cfg: Dict[str, Any], enc_len: int, dec_len: int) -> int:
    """Multiply-accumulates of one forward pass over one (encoder,
    decoder) pair of sequences.

    Encoder layer, per token: q/k/v/o projections (4 d h), scores and
    context against all ``enc_len`` keys (2 enc_len h), FFN (2 or 3 d ff).
    Decoder layer, per token: self q/k/v/o (4 d h), causal scores and
    context (on average (dec_len+1)/2 keys: 2 h (dec_len+1)/2), cross q/o
    (2 d h), cross scores and context (2 enc_len h), FFN; plus, per encoder
    token, the cross k/v projections (2 d h).  Head: d V per decoder token.
    Embedding look-ups, norms, softmax and the position bias are not matrix
    work and are left out."""
    d, ff, v = cfg["d_model"], cfg["d_ff"], cfg["vocab_size"]
    h = cfg["num_heads"] * cfg["d_kv"]
    ffn = _ffn_mats(cfg) * d * ff
    enc_tok = 4 * d * h + 2 * enc_len * h + ffn
    dec_tok = (4 * d * h + h * (dec_len + 1)      # 2 * h * (dec_len+1)/2
               + 2 * d * h + 2 * enc_len * h + ffn)
    cross_kv = 2 * d * h * enc_len
    return (cfg["num_layers"] * enc_tok * enc_len
            + cfg["num_decoder_layers"] * (dec_tok * dec_len + cross_kv)
            + d * v * dec_len)


def train_flops_per_token(cfg: Dict[str, Any], enc_len: int,
                          dec_len: int) -> float:
    """FLOPs one trained token needs: forward plus backward (twice the
    forward: gradients for activations and for weights), two FLOPs to a
    multiply-accumulate, over the ``enc_len + dec_len`` tokens of a pair."""
    return 3 * 2 * forward_macs(cfg, enc_len, dec_len) / (enc_len + dec_len)


def decode_step_bytes(cfg: Dict[str, Any], batch: int, enc_len: int,
                      self_len: float, bytes_el: int = 2) -> Dict[str, float]:
    """Bytes ONE cached decode step must stream from HBM (copied from
    bench.py ``_decode_step_bytes``, full-width caches): the cross-attention
    K/V cache, read in full; the self-attention slabs at ``self_len``, the
    positions the step reads (written so far, its own among them: for the
    MEAN step of a call the mean written length, not the slab's capacity;
    since PR 59 ``generate``'s loop reads that prefix alone); the
    decoder-side parameters and the head matrix.  Activations at query length
    1 are negligible."""
    h = cfg["num_heads"] * cfg["d_kv"]
    layers = cfg["num_decoder_layers"]
    d, ff = cfg["d_model"], cfg["d_ff"]
    cross_kv = 2 * batch * enc_len * h * bytes_el * layers
    self_kv = 2 * batch * self_len * h * bytes_el * layers
    # per layer: self q/k/v/o + cross q/o (cross k/v are cached) + FFN
    p_layer = 4 * d * h + 2 * d * h + _ffn_mats(cfg) * d * ff
    params = (layers * p_layer + d * cfg["vocab_size"]) * bytes_el
    return {"cross_kv_bytes": cross_kv, "self_kv_bytes": self_kv,
            "param_bytes": params,
            "total_bytes": cross_kv + self_kv + params}
