"""Real-asset test tier.

Two lanes over the SAME tests:

* **vendored** (always runs, zero network): ``tests/assets/flan_t5_tiny``
  holds a REAL-format unigram ``spiece.model`` trained by the in-repo EM
  trainer on this repo's docs, a Rust-``tokenizers`` export of the same
  vocab, and a tiny REAL HF T5 checkpoint written by transformers itself —
  so the from-scratch wire reader, the Viterbi segmentation, and the torch
  weight import run their true load paths in every CI run instead of
  skipping.
* **flan-t5-small** (skips without assets): the genuine 32k-piece asset via
  ``TPU_AIR_ASSETS_DIR``/HF cache, same tests at full scale.

Per-lane expectations (min vocab, params, probe text) come from
``asset_meta.json`` next to the assets.
"""

import glob
import json
import os

import pytest

pytestmark = pytest.mark.requires_assets

_HERE = os.path.dirname(os.path.abspath(__file__))
_VENDORED = os.path.join(_HERE, "assets", "flan_t5_tiny")


def _find_flan_t5_small():
    """Directory holding real flan-t5-small assets, or None."""
    for env in ("TPU_AIR_ASSETS_DIR", "FLAN_T5_SMALL_DIR", "FLAN_T5_TOKENIZER_DIR"):
        d = os.environ.get(env)
        if d and os.path.exists(os.path.join(d, "spiece.model")):
            return d
    hf_home = os.environ.get(
        "HF_HOME", os.path.expanduser("~/.cache/huggingface")
    )
    for snap in glob.glob(
        os.path.join(hf_home, "hub", "models--google--flan-t5-small",
                     "snapshots", "*")
    ):
        if os.path.exists(os.path.join(snap, "spiece.model")):
            return snap
    return None


_FLAN = _find_flan_t5_small()
_LANES = [pytest.param(_VENDORED, id="vendored")]
if _FLAN is not None:
    _LANES.append(pytest.param(_FLAN, id="flan-t5-small"))


def test_flan_t5_small_lane_present():
    """ONE visible marker for the optional full-scale lane: the vendored
    lane above always exercises the real load paths; this skip is the
    (single) signal that the genuine 32k-piece asset wasn't available."""
    if _FLAN is None:
        pytest.skip(
            "genuine flan-t5-small assets not present — set "
            "TPU_AIR_ASSETS_DIR or populate the HF cache to run the "
            "full-scale lane (the vendored lane covered the load paths)"
        )


def _meta(assets: str) -> dict:
    p = os.path.join(assets, "asset_meta.json")
    if os.path.exists(p):
        with open(p) as f:
            return json.load(f)
    # genuine flan-t5-small defaults
    return {
        "min_vocab": 32000,
        "min_params": 70_000_000,
        "probe_text": "Translate English to German: The house is wonderful.",
        "probe_words": ["house", "wonderful"],
    }


def _has_weights(d: str) -> bool:
    return any(
        os.path.exists(os.path.join(d, f))
        for f in ("model.safetensors", "pytorch_model.bin")
    )


@pytest.mark.parametrize("assets", _LANES)
def test_real_spiece_loads_and_tokenizes(assets):
    """The from-scratch unigram loader reads a REAL-format vocab and
    produces sane, reversible tokenizations."""
    from tpu_air.models.sentencepiece_unigram import T5SentencePieceTokenizer

    meta = _meta(assets)
    tok = T5SentencePieceTokenizer.from_pretrained(assets)
    assert tok.vocab_size >= meta["min_vocab"], tok.vocab_size
    ids = tok.encode(meta["probe_text"])
    assert len(ids) > 5 and ids[-1] == tok.eos_token_id
    # no unk pieces for in-domain text, and the decode round-trips
    text = tok.decode([i for i in ids if i != tok.eos_token_id])
    for w in meta["probe_words"]:
        assert w in text, (w, text)


@pytest.mark.parametrize("assets", _LANES)
def test_real_spiece_viterbi_parity(assets):
    """Viterbi parity against an independent implementation on the SAME
    asset: the Rust ``tokenizers`` Unigram (tokenizer.json) — and, when the
    sentencepiece wheel can load it, HF's slow T5Tokenizer too."""
    from tpu_air.models.sentencepiece_unigram import T5SentencePieceTokenizer

    meta = _meta(assets)
    mine = T5SentencePieceTokenizer.from_pretrained(assets)
    sentences = [
        meta["probe_text"],
        "the quick brown fox jumps over the lazy dog",
        "Give three tips for staying healthy.",
    ]
    checked = 0
    tok_json = os.path.join(assets, "tokenizer.json")
    if os.path.exists(tok_json):
        from tokenizers import Tokenizer

        rust = Tokenizer.from_file(tok_json)
        for s in sentences:
            norm = " ".join(s.split())
            assert mine.encode(norm, add_eos=False) == rust.encode(norm).ids, norm
        checked += 1
    try:
        from transformers import T5Tokenizer

        hf = T5Tokenizer.from_pretrained(assets, legacy=False)
    except Exception:
        hf = None  # no sentencepiece wheel / no slow files — rust lane stands
    if hf is not None:
        for s in sentences:
            norm = " ".join(s.split())
            assert mine(norm)["input_ids"][0].tolist() == hf(norm)["input_ids"], norm
        checked += 1
    assert checked, f"no parity oracle loadable for {assets}"


@pytest.mark.parametrize("assets", _LANES)
def test_real_weight_import_fingerprint(assets):
    """Import a real torch checkpoint into the Flax tree: structural
    completeness (imported leaf set == fresh-init leaf set), finite values,
    and a working jitted forward — the real W1 model path end-to-end."""
    if not _has_weights(assets):
        pytest.skip(f"no model weights next to spiece.model in {assets}")
    torch = pytest.importorskip("torch")  # noqa: F841
    import jax
    import jax.numpy as jnp

    from tpu_air.models.t5 import T5ForConditionalGeneration
    from tpu_air.models.t5.hf_import import load_t5_from_hf

    meta = _meta(assets)
    model, params = load_t5_from_hf(assets, dtype="float32")
    config = model.config

    # structural fingerprint: every fresh-init leaf must be present with the
    # same shape (a missed/renamed tensor in the converter shows up here)
    ref = T5ForConditionalGeneration(config)
    ref_params = ref.init(
        jax.random.PRNGKey(0),
        jnp.ones((1, 8), jnp.int32), jnp.ones((1, 8), jnp.int32),
        jnp.ones((1, 4), jnp.int32),
    )["params"]
    got = {jax.tree_util.keystr(k): v.shape
           for k, v in jax.tree_util.tree_leaves_with_path(params)}
    want = {jax.tree_util.keystr(k): v.shape
            for k, v in jax.tree_util.tree_leaves_with_path(ref_params)}
    assert got == want
    n_params = sum(v.size for v in jax.tree_util.tree_leaves(params))
    assert n_params >= meta["min_params"], n_params
    assert all(
        bool(jnp.isfinite(v).all()) for v in jax.tree_util.tree_leaves(params)
    )

    # behavioral fingerprint: the weights drive a coherent forward
    ids = jnp.ones((1, 7), jnp.int32)
    logits = jax.jit(
        lambda p, i, m, d: model.apply({"params": p}, i, m, d)
    )(params, ids, jnp.ones((1, 7), jnp.int32), jnp.zeros((1, 1), jnp.int32))
    assert logits.shape == (1, 1, config.vocab_size)
    assert bool(jnp.isfinite(logits).all())
