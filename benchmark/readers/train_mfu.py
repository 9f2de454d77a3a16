"""Model FLOP/s utilisation of training, in percent: tokens a second times
the FLOPs a token needs (``costs.train_flops_per_token``: forward and
backward from shapes, no recompute) over chips times the peak."""

from benchmark import costs


def read(rc):
    f = rc.facts
    if rc.peak is None or not f.get("train_tokens") or not f.get("window_s"):
        return None
    per_token = costs.train_flops_per_token(
        rc.cfg, f["encoder_len"], f["decoder_len"])
    achieved = f["train_tokens"] / f["window_s"] * per_token
    return 100.0 * achieved / (rc.chips * rc.peak["bf16_flops_per_s"])
