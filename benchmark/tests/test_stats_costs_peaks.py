import json
import os

import pytest

from benchmark import costs, peaks, stats

HERE = os.path.dirname(os.path.dirname(__file__))


def test_percentile_and_counts():
    xs = list(range(1, 101))  # 1..100
    assert stats.percentile(xs, 0.5) == pytest.approx(50.5)
    assert stats.percentile(xs, 0.95) == pytest.approx(95.05)
    assert stats.percentile([7.0], 0.95) == 7.0
    assert stats.percentile([], 0.5) is None
    # a p95 wants ten samples beyond it: 200 give exactly ten
    assert stats.samples_beyond(200, 0.95) == 10
    assert stats.samples_beyond(19, 0.95) == 0


def test_peaks_known_and_unknown():
    p = peaks.peak_for("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("TPU v9 imaginary")
    with pytest.raises(peaks.UnknownDevice):
        peaks.peak_for("cpu")


def _cfg(name):
    with open(os.path.join(HERE, "configs", name + ".json")) as f:
        return json.load(f)


def test_forward_macs_tiny_by_hand():
    # T5Config.tiny(): d 64, heads 4 x 16 = 64, ff 128 gated, 2+2 layers,
    # vocab 384; encoder 8 tokens, decoder 4
    from tpu_air.models.t5 import T5Config

    cfg = T5Config.tiny().to_dict()
    d, h, ff, v, le, ld = 64, 64, 128, 384, 8, 4
    enc_tok = 4 * d * h + 2 * le * h + 3 * d * ff        # 16384+1024+24576
    assert enc_tok == 41984
    dec_tok = 4 * d * h + h * (ld + 1) + 2 * d * h + 2 * le * h + 3 * d * ff
    assert dec_tok == 16384 + 320 + 8192 + 1024 + 24576 == 50496
    want = 2 * enc_tok * le + 2 * (dec_tok * ld + 2 * d * h * le) + d * v * ld
    assert want == 671744 + 2 * (201984 + 65536) + 98304 == 1305088
    assert costs.forward_macs(cfg, le, ld) == want
    assert costs.train_flops_per_token(cfg, le, ld) == 6 * want / 12


def test_train_flops_base_by_hand():
    cfg = _cfg("flan-t5-base")
    d, h, ff, v, le, ld, n = 768, 768, 2048, 32128, 512, 128, 12
    enc = n * le * (4 * d * h + 2 * le * h + 3 * d * ff)
    dec = n * (ld * (6 * d * h + h * (ld + 1) + 2 * le * h + 3 * d * ff)
               + 2 * d * h * le)
    head = d * v * ld
    assert costs.forward_macs(cfg, le, ld) == enc + dec + head
    per_token = costs.train_flops_per_token(cfg, le, ld)
    assert per_token == pytest.approx(6 * (enc + dec + head) / 640)
    # the familiar 6 N rule brackets it: 248 M parameters, of which the
    # decoder's act on a fifth of the tokens
    assert 0.5e9 < per_token < 6 * 248e6


def test_decode_step_bytes_by_hand():
    cfg = _cfg("flan-t5-base")
    got = costs.decode_step_bytes(cfg, 256, 512, 129)
    assert got["cross_kv_bytes"] == 2 * 256 * 512 * 768 * 2 * 12
    assert got["self_kv_bytes"] == 2 * 256 * 129 * 768 * 2 * 12
    assert got["param_bytes"] == 2 * (
        12 * (6 * 768 * 768 + 3 * 768 * 2048) + 768 * 32128)
    assert got["total_bytes"] == sum(
        got[k] for k in ("cross_kv_bytes", "self_kv_bytes", "param_bytes"))
    # and it is the program's own count (bench.py, which this copies)
    import bench
    from benchmark import weights

    theirs = bench._decode_step_bytes(
        weights.t5_config(cfg, "bfloat16"), 256, 512, 129)
    assert theirs["total_bytes"] == got["total_bytes"]
