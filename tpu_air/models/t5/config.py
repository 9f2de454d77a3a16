"""T5 configuration.

Covers the FLAN-T5 family the reference fine-tunes and generates with
(`google/flan-t5-base`, Model_finetuning…ipynb:cc-25,35; sizes small→xl per
BASELINE.json configs).  FLAN-T5 is the T5 v1.1 architecture: gated-GELU MLP,
untied embedding/lm_head, RMSNorm, relative position bias, no attention
score scaling.
"""

from __future__ import annotations

import dataclasses
import json
from dataclasses import dataclass
from typing import Optional


@dataclass
class T5Config:
    vocab_size: int = 32128
    d_model: int = 512
    d_kv: int = 64
    d_ff: int = 1024
    num_layers: int = 8
    num_decoder_layers: Optional[int] = None
    num_heads: int = 6
    relative_attention_num_buckets: int = 32
    relative_attention_max_distance: int = 128
    dropout_rate: float = 0.1
    layer_norm_epsilon: float = 1e-6
    feed_forward_proj: str = "gated-gelu"  # v1.1 / FLAN; "relu" for t5 v1.0
    tie_word_embeddings: bool = False
    pad_token_id: int = 0
    eos_token_id: int = 1
    decoder_start_token_id: int = 0
    # dtype policy: bf16 activations on TPU (fp16-on-GPU analog of
    # Model_finetuning…ipynb:cc-64), fp32 params.
    dtype: str = "float32"
    # Attention dispatch.  ``attention_impl`` picks per-call at TRACE time:
    # * "auto"   — a deterministic pass: einsum below ``flash_min_seq_len``,
    #   Pallas flash at or above it (the measured v5e crossover for a forward
    #   without dropout: dense wins at 512, flash is 3.5-5x at >=2048 —
    #   docs/KERNELS.md).  A training pass with live attention dropout: the
    #   fused kernels (mask drawn in the kernel, no probabilities in HBM)
    #   from the shape alone, ``ops.flash_attention.train_dispatch_ok`` —
    #   512 x 512 and up, whatever ``flash_min_seq_len`` says.  No user flag
    #   needed.
    # * "einsum" — always the XLA dense path.
    # * "flash"  — always the Pallas kernel where eligible (a training pass:
    #   where the shape has real tiles).
    # Flash is only eligible off the cached-decode path with structured
    # masks (see modeling.Attention).
    attention_impl: str = "auto"
    flash_min_seq_len: int = 1024
    # Opt-in int8 cross-attention K/V cache for cached decode: the cross
    # K/V are the dominant HBM term of every decode step (B x enc_len x
    # n_heads x d_kv x 2 x layers, re-read per emitted token); storing them
    # int8 with per-(batch, head, channel) scales halves that traffic at
    # the cost of quantization error in the cross-attention scores.  Off by
    # default — the reference decodes fp16 (cc-64); numerics parity is
    # tested at tolerance in tests/test_t5.py.
    decode_cache_int8: bool = False

    def __post_init__(self):
        if self.num_decoder_layers is None:
            self.num_decoder_layers = self.num_layers

    @property
    def is_gated_act(self) -> bool:
        return "gated" in self.feed_forward_proj

    @property
    def act_fn(self) -> str:
        proj = self.feed_forward_proj
        return proj.split("-")[-1] if "-" in proj else proj

    # -- presets -----------------------------------------------------------
    @classmethod
    def tiny(cls, vocab_size: int = 384) -> "T5Config":
        """Test-dial config (SURVEY.md §4.2 smallest-variant strategy)."""
        return cls(
            vocab_size=vocab_size, d_model=64, d_kv=16, d_ff=128,
            num_layers=2, num_heads=4, dropout_rate=0.0,
        )

    @classmethod
    def flan_t5_small(cls) -> "T5Config":
        return cls(d_model=512, d_kv=64, d_ff=1024, num_layers=8, num_heads=6)

    @classmethod
    def flan_t5_base(cls) -> "T5Config":
        return cls(d_model=768, d_kv=64, d_ff=2048, num_layers=12, num_heads=12)

    @classmethod
    def flan_t5_large(cls) -> "T5Config":
        return cls(d_model=1024, d_kv=64, d_ff=2816, num_layers=24, num_heads=16)

    @classmethod
    def flan_t5_xl(cls) -> "T5Config":
        return cls(d_model=2048, d_kv=64, d_ff=5120, num_layers=24, num_heads=32)

    @classmethod
    def from_name(cls, name: str) -> "T5Config":
        key = name.split("/")[-1].replace("flan-t5-", "").replace("t5-", "")
        presets = {
            "tiny": cls.tiny,
            "small": cls.flan_t5_small,
            "base": cls.flan_t5_base,
            "large": cls.flan_t5_large,
            "xl": cls.flan_t5_xl,
        }
        if key not in presets:
            raise ValueError(f"unknown T5 preset {name!r}")
        return presets[key]()

    # -- (de)serialization — checkpoints store the config ------------------
    def to_dict(self) -> dict:
        return dataclasses.asdict(self)

    @classmethod
    def from_dict(cls, d: dict) -> "T5Config":
        return cls(**{k: v for k, v in d.items() if k in {f.name for f in dataclasses.fields(cls)}})

    def to_json(self) -> str:
        return json.dumps(self.to_dict(), indent=2)

    @classmethod
    def from_json(cls, s: str) -> "T5Config":
        return cls.from_dict(json.loads(s))
