"""Config for the long-context causal LM family."""

from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import List, Optional


@dataclass
class LMConfig:
    """Decoder-only transformer (RoPE + SwiGLU, pre-RMSNorm) — the
    framework's long-context flagship.  ``attention`` picks the kernel:

    * ``"dense"`` — XLA einsum softmax (baseline, any backend);
    * ``"flash"`` — the Pallas blockwise kernel (ops/flash_attention.py);
    * ``"ring"``  — ring attention over the ``sequence_axis`` mesh axis
      (ops/ring_attention.py): each device holds L/P of the sequence and
      K/V shards rotate over ICI, so context length scales with the mesh.

    ``num_experts > 0`` makes every block's feed-forward a routed expert
    layer (``modeling.SparseExperts``): ``num_experts`` SwiGLU experts of
    width ``d_ff``, each token summed over its ``num_experts_per_tok`` most
    probable ones.  ``qk_norm`` puts an RMSNorm over the whole q and k
    projections before the split into heads.  Both are what OLMoE publishes
    (``hf_import.lm_config_from_hf`` maps its key names onto these).

    LAYER KINDS.  Layer ``i`` mixes with attention where ``i %
    attn_layer_period == attn_layer_offset`` and with a Mamba-1 state-space
    mixer (``modeling.MambaMixer``) elsewhere (:meth:`layer_kinds`; the
    defaults, period 1 and offset 0, make every layer attention).  The Mamba
    widths are ``mamba_expand`` (``d_inner = expand * d_model``),
    ``mamba_d_state``, ``mamba_d_conv`` and ``mamba_dt_rank``.
    ``n_kv_heads`` K/V heads are shared by ``n_heads / n_kv_heads`` query
    heads each (default: one a query head).  ``rope_theta`` None: no position
    encoding at all (what Jamba publishes: its Mamba layers carry order).
    """

    vocab_size: int = 32000
    d_model: int = 512
    n_layers: int = 4
    n_heads: int = 8
    n_kv_heads: Optional[int] = None  # default n_heads
    head_dim: Optional[int] = None  # default d_model // n_heads
    d_ff: Optional[int] = None      # default 4 * d_model (SwiGLU uses 2/3)
    max_seq_len: int = 2048
    rope_theta: Optional[float] = 10000.0  # None: no position encoding
    rmsnorm_eps: float = 1e-6
    dropout_rate: float = 0.0
    dtype: str = "float32"
    tie_embeddings: bool = True
    # "auto" picks per-trace by sequence length: dense below
    # flash_min_seq_len, the Pallas flash kernel at/above it (measured v5e
    # crossover — docs/KERNELS.md).  "ring" stays explicit: it
    # needs a sequence mesh axis.
    attention: str = "auto"           # auto | dense | flash | ring
    flash_min_seq_len: int = 1024
    sequence_axis: Optional[str] = None  # mesh axis for ring attention
    # None -> kernel's measured-on-TPU auto tiling (512/1024 caps)
    block_q: Optional[int] = None
    block_k: Optional[int] = None
    pad_token_id: int = 0
    eos_token_id: Optional[int] = None  # None: generation never early-stops
    num_experts: int = 0          # 0: one dense SwiGLU a block
    num_experts_per_tok: int = 0
    qk_norm: bool = False
    attn_layer_period: int = 1
    attn_layer_offset: int = 0
    mamba_expand: int = 2
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_dt_rank: Optional[int] = None  # default ceil(d_model / 16)

    def __post_init__(self):
        if self.n_kv_heads is None:
            self.n_kv_heads = self.n_heads
        if self.n_heads % self.n_kv_heads:
            raise ValueError(
                f"n_heads {self.n_heads} is not a multiple of n_kv_heads "
                f"{self.n_kv_heads}")
        if self.mamba_dt_rank is None:
            self.mamba_dt_rank = -(-self.d_model // 16)
        if not 0 <= self.attn_layer_offset < self.attn_layer_period:
            raise ValueError(
                f"attn_layer_offset {self.attn_layer_offset} is not in "
                f"0..attn_layer_period ({self.attn_layer_period})")
        if self.head_dim is None:
            self.head_dim = self.d_model // self.n_heads
        if self.d_ff is None:
            self.d_ff = int(8 * self.d_model / 3 + 255) // 256 * 256
        if self.num_experts and not (
                1 <= self.num_experts_per_tok <= self.num_experts):
            raise ValueError(
                f"num_experts_per_tok {self.num_experts_per_tok} is not in "
                f"1..num_experts ({self.num_experts})")

    def layer_kinds(self) -> List[str]:
        """``"attention"`` or ``"mamba"`` for each layer, in order."""
        return ["attention" if i % self.attn_layer_period
                == self.attn_layer_offset else "mamba"
                for i in range(self.n_layers)]

    @property
    def has_recurrent_layers(self) -> bool:
        """Some layer keeps per-sequence state that is not K/V pages."""
        return "mamba" in self.layer_kinds()

    @property
    def mamba_d_inner(self) -> int:
        return self.mamba_expand * self.d_model

    def to_dict(self) -> dict:
        return asdict(self)

    def to_json(self) -> str:
        """Checkpoint serialization (train/checkpoint.py model_config.json);
        ``model_type`` tags the config class for reconstruction."""
        import json

        return json.dumps({**self.to_dict(), "model_type": "causal_lm"})

    @classmethod
    def from_dict(cls, d: dict) -> "LMConfig":
        return cls(**{k: v for k, v in d.items() if k in cls.__dataclass_fields__})

    @classmethod
    def tiny(cls, vocab_size: int = 384) -> "LMConfig":
        return cls(vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
                   head_dim=16, d_ff=128, max_seq_len=512)
