"""Pure-Python sentencepiece unigram tokenizer tests.

Parity oracle: the Rust ``tokenizers`` Unigram model (same algorithm the HF
fast T5 tokenizer runs), configured with an identical toy vocabulary and
T5-style Metaspace handling.  This proves the Viterbi segmentation and the
ModelProto wire round-trip without needing the sentencepiece wheel or
network access.  When a real FLAN-T5 ``tokenizer.json``
is present locally the same parity check runs on the real 32k vocab.
"""

import json
import os
import tempfile

import numpy as np
import pytest

from tpu_air.models.sentencepiece_unigram import (
    SentencePieceUnigram,
    T5SentencePieceTokenizer,
    parse_model_proto,
    serialize_model_proto,
    _CONTROL,
    _NORMAL,
    _UNKNOWN,
)

# toy unigram vocab: T5 layout (pad/eos/unk first), ▁-escaped word pieces
TOY_PIECES = (
    [("<pad>", 0.0, _CONTROL), ("</s>", 0.0, _CONTROL), ("<unk>", 0.0, _UNKNOWN)]
    + [
        ("▁", -2.0, _NORMAL),
        ("▁the", -1.5, _NORMAL),
        ("▁quick", -3.0, _NORMAL),
        ("▁brown", -3.1, _NORMAL),
        ("▁fox", -3.2, _NORMAL),
        ("▁jump", -3.5, _NORMAL),
        ("s", -2.5, _NORMAL),
        ("ed", -2.6, _NORMAL),
        ("▁over", -3.3, _NORMAL),
        ("▁lazy", -3.6, _NORMAL),
        ("▁dog", -3.4, _NORMAL),
        ("qu", -4.0, _NORMAL),
        ("ick", -4.1, _NORMAL),
        ("b", -5.0, _NORMAL),
        ("r", -5.0, _NORMAL),
        ("o", -5.0, _NORMAL),
        ("w", -5.0, _NORMAL),
        ("n", -5.0, _NORMAL),
        ("e", -5.0, _NORMAL),
        ("d", -5.0, _NORMAL),
        ("t", -5.0, _NORMAL),
        ("h", -5.0, _NORMAL),
        ("▁a", -2.2, _NORMAL),
    ]
)

SENTENCES = [
    "the quick brown fox",
    "the quick brown fox jumps over the lazy dog",
    "a brown dog jumped",
    "the the the",
    "  extra   spaces   collapse  ",
    "brownfox",  # no leading space piece for 'brownfox' → char assembly
]


def _toy_tokenizer() -> T5SentencePieceTokenizer:
    return T5SentencePieceTokenizer(
        SentencePieceUnigram(list(TOY_PIECES)), model_max_length=64, extra_ids=4
    )


def test_model_proto_roundtrip(tmp_path):
    blob = serialize_model_proto(list(TOY_PIECES))
    assert parse_model_proto(blob) == [
        (p, pytest.approx(s), t) for p, s, t in TOY_PIECES
    ]
    tok = _toy_tokenizer()
    tok.save_pretrained(str(tmp_path))
    # no explicit extra_ids: from_pretrained must honor the persisted count
    # (a mismatch would shift every sentinel id and change vocab_size)
    tok2 = T5SentencePieceTokenizer.from_pretrained(str(tmp_path))
    assert tok2.vocab_size == tok.vocab_size
    for s in SENTENCES + ["the <extra_id_0> fox"]:
        assert tok.encode(s) == tok2.encode(s)


def test_encode_decode_roundtrip():
    tok = _toy_tokenizer()
    for s in ["the quick brown fox", "a lazy dog"]:
        ids = tok.encode(s)
        assert ids[-1] == tok.eos_token_id
        assert tok.decode(ids) == s


def test_call_surface_padding_truncation():
    tok = _toy_tokenizer()
    out = tok(SENTENCES[:3], max_length=16, padding="max_length",
              truncation=True, return_tensors="np")
    assert out["input_ids"].shape == (3, 16)
    assert out["attention_mask"].shape == (3, 16)
    assert out["input_ids"].dtype == np.int32
    # pad id fills the tail where mask is 0
    masked = out["input_ids"][out["attention_mask"] == 0]
    assert (masked == tok.pad_token_id).all()


def test_extra_id_sentinels():
    tok = _toy_tokenizer()
    ids = tok.encode("the <extra_id_0> fox", add_eos=False)
    assert tok.vocab_size - 1 in ids  # <extra_id_0> = last id (HF T5 layout)
    assert "<extra_id_0>" in tok.decode(ids)


def _rust_unigram():
    tokenizers = pytest.importorskip("tokenizers")
    from tokenizers import Tokenizer, models, pre_tokenizers

    vocab = [(p, s) for p, s, _ in TOY_PIECES]
    tok = Tokenizer(models.Unigram(vocab, unk_id=2, byte_fallback=False))
    # T5's metaspace convention: ' '→▁ with a prepended dummy prefix
    tok.pre_tokenizer = pre_tokenizers.Metaspace(
        replacement="▁", prepend_scheme="first", split=False
    )
    return tok


def test_viterbi_parity_with_rust_unigram():
    rust = _rust_unigram()
    mine = _toy_tokenizer()
    for s in SENTENCES:
        # rust Metaspace doesn't collapse whitespace; compare on the
        # normalized form (single spaces) which is what T5's nmt_nfkc feeds
        norm = " ".join(s.split())
        got = mine.encode(norm, add_eos=False)
        want = rust.encode(norm).ids
        assert got == want, f"{norm!r}: {got} != {want}"


def test_viterbi_prefers_higher_score_segmentation():
    sp = SentencePieceUnigram(list(TOY_PIECES))
    # '▁the' (-1.5) must beat '▁'+'t'+'h'+'e' (-2.0-5-5-5)
    assert sp.encode_pieces("the") == ["▁the"]
    # unknown chars fall back to per-char unk pieces
    pieces = sp.encode_pieces("théz")
    assert any(p not in sp.piece_to_id for p in pieces)


def _real_asset_dir():
    """Genuine FLAN-T5 tokenizer dir when present, else the vendored tiny
    real-format asset (trained by the in-repo EM trainer) — the parity test
    always runs."""
    d = os.environ.get("FLAN_T5_TOKENIZER_DIR")
    if d and os.path.exists(os.path.join(d, "tokenizer.json")):
        return d
    vendored = os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "assets", "flan_t5_tiny"
    )
    return vendored if os.path.exists(os.path.join(vendored, "tokenizer.json")) else None


@pytest.mark.skipif(_real_asset_dir() is None,
                    reason="no tokenizer.json asset present")
def test_real_flan_t5_parity():
    d = _real_asset_dir()
    from tokenizers import Tokenizer

    rust = Tokenizer.from_file(os.path.join(d, "tokenizer.json"))
    mine = T5SentencePieceTokenizer.from_pretrained(d)
    for s in SENTENCES + ["Translate to German: hello world."]:
        norm = " ".join(s.split())
        assert mine.encode(norm, add_eos=False) == rust.encode(norm).ids
