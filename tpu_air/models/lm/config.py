"""Config for the long-context causal LM family."""

from __future__ import annotations

from dataclasses import asdict, dataclass, field
from typing import List, Optional, Tuple


@dataclass
class LMConfig:
    """Decoder-only transformer (RoPE + SwiGLU, pre-RMSNorm) — the
    framework's long-context flagship.  No option picks the attention of a
    full-sequence pass: with ``sequence_axis`` naming a mesh axis it is ring
    attention over that axis (ops/ring_attention.py: each device holds L/P of
    the sequence and K/V shards rotate over ICI, so context length scales
    with the mesh); otherwise the trace's sequence length picks the Pallas
    blockwise kernel (ops/flash_attention.py, its own tiling) or the XLA
    einsum softmax (``modeling.CausalSelfAttention``).

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

    LATENT ATTENTION (``kv_lora_rank > 0``; what ``deepseek_v3`` publishes):
    an attention layer is ``modeling.LatentAttention`` (:meth:`layer_kinds`
    says ``"latent"``): queries through a low-rank ``q_lora_rank`` pair, K and
    V expanded from ONE joint latent of ``kv_lora_rank`` numbers a position
    plus one rotary key of ``qk_rope_head_dim`` shared by all heads, score
    heads of ``qk_nope_head_dim + qk_rope_head_dim`` (= ``head_dim``) beside
    value heads of ``v_head_dim``.  What is cached a position a layer is the
    latent and the roped key (:attr:`latent_width` numbers).  ``rope_factor >
    1`` stretches the rotary frequencies the yarn way
    (``modeling.yarn_inv_freq``) over ``rope_original_len`` with the ramp
    between ``rope_beta_fast`` and ``rope_beta_slow``; ``rope_mscale`` /
    ``rope_mscale_all_dim`` scale cos/sin and the softmax as published.

    FEED-FORWARD BY LAYER (:meth:`ff_kinds`): with ``num_experts > 0`` the
    first ``first_dense_layers`` layers keep one dense SwiGLU of width
    ``dense_d_ff`` and the others route; ``num_shared_experts`` adds a SwiGLU
    of ``num_shared_experts * d_ff`` every token takes beside its routed ones.
    ``router`` names the routing rule: ``"softmax"`` (OLMoE: top-k of the
    softmax, not renormalised) or ``"sigmoid_groups"`` (sigmoid scores, a
    selection bias, the best ``router_topk_groups`` of ``router_groups``
    groups of consecutive experts ranked by their two largest, top-k among
    those, renormalised and times ``router_scale``).  ``experts_held`` of the
    ``num_experts`` routed over, from id ``experts_first``, are the ones this
    parameter tree HOLDS and computes (an expert-parallel rank; default all):
    a token's assignments to the others are counted and left to their ranks.

    A LAYER THAT IS ONE THING (``layer_pattern``; what ``nemotron_h``
    publishes as ``hybrid_override_pattern``): a string of one character a
    layer, ``M`` a Mamba-2 mixer (``modeling.Mamba2Mixer``), ``*`` attention,
    ``E`` routed experts; layer ``i`` is ``x + f_i(RMSNorm(x))`` and has no
    second half, so :meth:`layer_kinds` says ``"none"`` for an ``E`` layer
    and :meth:`ff_kinds` ``"none"`` for the two others.  The period and the
    leading dense layers above do not apply then.  Mamba-2's widths:
    ``mamba_n_heads`` heads of ``mamba_head_dim`` channels (``d_inner`` their
    product), ``mamba_n_groups`` groups of heads that share B and C,
    ``mamba_d_state`` states a channel, ``mamba_chunk_size`` the block of the
    state-space-duality form (``ops/ssm.py``).  ``ff_act`` ``"relu2"`` makes
    every feed-forward (an expert, the shared one) TWO matrices around a
    squared ReLU instead of SwiGLU's three; ``moe_latent_size > 0`` puts the
    routed experts inside a down- and up-projection to that width (the router
    and the shared expert take the full hidden state); ``shared_d_ff`` is the
    shared expert's width where it is not ``num_shared_experts * d_ff``.

    A RESIDUAL PATH OF SEVERAL STREAMS (``hc_mult > 1``; what ``xing4_0``
    publishes: manifold-constrained hyper-connections, arXiv:2512.24880): a
    token's state between sublayers is ``hc_mult`` streams of ``d_model``
    numbers; each sublayer reads a token-dependent mix of them and writes
    back through a doubly stochastic ``hc_mult x hc_mult`` map, which
    ``hc_sinkhorn_iters`` rounds of column and row normalisation (``hc_eps``
    in both denominators) make of ``exp`` of a matrix clamped to
    ``hc_res_clamp`` (``ops/mhc.py`` has the equations,
    ``modeling.HyperConnection`` the parameters).  1: ``x + f(norm(x))`` and
    no parameter of it in the tree.
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
    sequence_axis: Optional[str] = None  # mesh axis: ring attention over it
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
    q_lora_rank: int = 0
    kv_lora_rank: int = 0           # > 0: latent attention
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    rope_factor: float = 1.0        # > 1: yarn
    rope_original_len: int = 0
    rope_beta_fast: float = 32.0
    rope_beta_slow: float = 1.0
    rope_mscale: float = 1.0
    rope_mscale_all_dim: float = 0.0
    first_dense_layers: int = 0
    dense_d_ff: Optional[int] = None    # default d_ff
    num_shared_experts: int = 0
    router: str = "softmax"         # softmax | sigmoid_groups
    router_groups: int = 1
    router_topk_groups: int = 1
    router_scale: float = 1.0
    experts_first: int = 0
    experts_held: Optional[int] = None  # default num_experts
    layer_pattern: Optional[str] = None  # e.g. "MEM*E": one thing a layer
    mamba_n_heads: int = 0          # > 0 with layer_pattern's M: Mamba-2
    mamba_head_dim: int = 0
    mamba_n_groups: int = 1
    mamba_chunk_size: int = 128
    ff_act: str = "swiglu"          # swiglu | relu2
    moe_latent_size: int = 0        # > 0: routed experts work in a latent
    shared_d_ff: Optional[int] = None   # default num_shared_experts * d_ff
    hc_mult: int = 1                # > 1: that many residual streams (mHC)
    hc_sinkhorn_iters: int = 20
    hc_eps: float = 1e-6
    hc_res_clamp: Tuple[float, float] = (-30.0, 30.0)
    layer_mixers: Optional[Tuple[str, ...]] = None  # attention | window a layer
    sliding_window: int = 0         # keys a window layer's position sees
    window_n_heads: Optional[int] = None    # default n_heads
    window_rope_theta: Optional[float] = None   # default rope_theta
    window_rope_fraction: float = 1.0
    rope_fraction: float = 1.0      # the share of a head that turns
    attn_gate: str = "none"         # none | per_head

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
        if self.dense_d_ff is None:
            self.dense_d_ff = self.d_ff
        if self.experts_held is None:
            self.experts_held = self.num_experts
        if not (0 <= self.experts_first
                and self.experts_first + self.experts_held
                <= self.num_experts):
            raise ValueError(
                f"experts {self.experts_first}..+{self.experts_held} are not "
                f"among the {self.num_experts} routed over")
        if self.router not in ("softmax", "sigmoid_groups"):
            raise ValueError(f"router {self.router!r}")
        if self.router == "sigmoid_groups" and (
                self.num_experts % self.router_groups
                or not 1 <= self.router_topk_groups <= self.router_groups
                or self.num_experts // self.router_groups < 2
                or self.num_experts_per_tok > self.router_topk_groups
                * (self.num_experts // self.router_groups)):
            raise ValueError(
                f"{self.num_experts} experts do not make "
                f"{self.router_groups} groups of two or more of which "
                f"{self.router_topk_groups} hold "
                f"{self.num_experts_per_tok} a token")
        if self.ff_act not in ("swiglu", "relu2"):
            raise ValueError(f"ff_act {self.ff_act!r}")
        if self.shared_d_ff is None:
            self.shared_d_ff = self.num_shared_experts * self.d_ff
        # a checkpoint's JSON hands the pair back as a list
        self.hc_res_clamp = tuple(float(v) for v in self.hc_res_clamp)
        if self.hc_mult < 1 or len(self.hc_res_clamp) != 2:
            raise ValueError(
                f"hc_mult {self.hc_mult} streams, hc_res_clamp "
                f"{self.hc_res_clamp}: one or more streams and a (min, max)")
        if self.window_n_heads is None:
            self.window_n_heads = self.n_heads
        if self.window_rope_theta is None:
            self.window_rope_theta = self.rope_theta
        if self.attn_gate not in ("none", "per_head"):
            raise ValueError(f"attn_gate {self.attn_gate!r}")
        for name in ("rope_fraction", "window_rope_fraction"):
            turns = getattr(self, name) * self.head_dim
            if not 0 < turns <= self.head_dim or turns % 2:
                raise ValueError(
                    f"{name} {getattr(self, name)} of a head of "
                    f"{self.head_dim} is not a whole number of pairs")
        if self.layer_mixers is not None:
            # a checkpoint's JSON hands the tuple back as a list
            self.layer_mixers = tuple(self.layer_mixers)
            if (len(self.layer_mixers) != self.n_layers
                    or set(self.layer_mixers) - {"attention", "window"}):
                raise ValueError(
                    f"layer_mixers {self.layer_mixers!r} is not "
                    f"{self.n_layers} entries of 'attention' and 'window'")
            if self.layer_pattern is not None or self.kv_lora_rank \
                    or self.attn_layer_period != 1:
                raise ValueError(
                    "layer_mixers lists attention layers of two kinds: no "
                    "layer_pattern, latent or attn_layer_period beside it")
            if "window" in self.layer_mixers \
                    and self.sequence_axis is not None:
                raise ValueError(
                    "a window layer is dense; sequence_axis="
                    f"{self.sequence_axis!r} asks for the ring")
            if "window" in self.layer_mixers and (
                    self.sliding_window < 1 or self.rope_theta is None
                    or self.window_n_heads % self.n_kv_heads):
                raise ValueError(
                    f"a window layer wants sliding_window "
                    f"({self.sliding_window}) keys, a rope (rope_theta "
                    f"{self.rope_theta}: a ring keeps no order of its own) "
                    f"and window_n_heads ({self.window_n_heads}) a multiple "
                    f"of n_kv_heads ({self.n_kv_heads})")
        if self.layer_pattern is not None:
            p = self.layer_pattern
            if len(p) != self.n_layers or set(p) - set("M*E"):
                raise ValueError(
                    f"layer_pattern {p!r} is not {self.n_layers} characters "
                    "of M (Mamba-2), * (attention) and E (experts)")
            if "E" in p and not self.num_experts:
                raise ValueError("layer_pattern has E layers and "
                                 "num_experts is 0")
            if "M" in p and (
                    self.mamba_n_heads < 1 or self.mamba_head_dim < 1
                    or self.mamba_n_heads % self.mamba_n_groups):
                raise ValueError(
                    f"Mamba-2 wants mamba_n_heads ({self.mamba_n_heads}) "
                    f"heads of mamba_head_dim ({self.mamba_head_dim}) in "
                    f"mamba_n_groups ({self.mamba_n_groups}) equal groups")
        if self.kv_lora_rank and (
                self.head_dim
                != self.qk_nope_head_dim + self.qk_rope_head_dim
                or not self.v_head_dim or not self.q_lora_rank
                or self.rope_theta is None):
            raise ValueError(
                "latent attention wants q_lora_rank, v_head_dim, rope_theta "
                "and head_dim = qk_nope_head_dim + qk_rope_head_dim")

    def layer_kinds(self) -> List[str]:
        """The sequence mixer of each layer, in order: ``"attention"``
        (``"latent"`` where the configuration has a latent) or ``"mamba"``;
        under a ``layer_pattern`` ``"mamba2"``, ``"attention"`` or, for a
        layer that is experts alone, ``"none"``; under ``layer_mixers`` that
        list (``"attention"`` or ``"window"`` a layer)."""
        if self.layer_mixers is not None:
            return list(self.layer_mixers)
        attention = "latent" if self.kv_lora_rank else "attention"
        if self.layer_pattern is not None:
            return [{"M": "mamba2", "*": attention, "E": "none"}[c]
                    for c in self.layer_pattern]
        return [attention if i % self.attn_layer_period
                == self.attn_layer_offset else "mamba"
                for i in range(self.n_layers)]

    def ff_kinds(self) -> List[str]:
        """The feed-forward of each layer, in order: ``"dense"`` (one SwiGLU)
        or ``"sparse"`` (routed experts, and the shared one); under a
        ``layer_pattern`` ``"sparse"`` for an ``E`` layer and ``"none"`` for
        a layer that is a mixer alone."""
        if self.layer_pattern is not None:
            return ["sparse" if c == "E" else "none"
                    for c in self.layer_pattern]
        return ["sparse" if self.num_experts and i >= self.first_dense_layers
                else "dense" for i in range(self.n_layers)]

    @property
    def latent_width(self) -> int:
        """Numbers a latent-attention layer caches a position."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    @property
    def latent_row_width(self) -> int:
        """The width of a cached row: :attr:`latent_width` in whole lanes of
        128, zeros behind the numbers.  A TPU tile pads a row to whole lanes
        in memory whatever its declared width; declared so, the chip's
        compiler appends to the page pool in place and gathers pages as they
        lie, where at 576 of 640 it copies the pool into another layout and
        back every step (8.97 against 5.06 ms a layer at 128 slots x 4096:
        PERF.md, PR 43)."""
        return -(-self.latent_width // 128) * 128

    def heads_of(self, kind: str) -> int:
        """Query heads of an attention layer of ``kind``."""
        return self.window_n_heads if kind == "window" else self.n_heads

    def rope_of(self, kind: str) -> Tuple[Optional[float], int]:
        """``(theta, turned)`` of an attention layer of ``kind``: the rope's
        base (None: no position encoding) and how many leading numbers of a
        head it turns."""
        if kind == "window":
            return (self.window_rope_theta,
                    int(self.window_rope_fraction * self.head_dim))
        return self.rope_theta, int(self.rope_fraction * self.head_dim)

    def window_ring_len(self, chunk_len: int) -> int:
        """Positions a window layer keeps a slot where prefill chunks are
        ``chunk_len`` long: whole chunks (a chunk lands in one piece), enough
        that a chunk written BEFORE it is read leaves its first query the
        ``sliding_window - 1`` positions behind it: the least multiple of
        ``chunk_len`` that holds ``sliding_window + chunk_len - 1`` (768 for
        a window of 512 under chunks of 256)."""
        return -(-(self.sliding_window + chunk_len - 1) // chunk_len
                 ) * chunk_len

    @property
    def holds_all_experts(self) -> bool:
        return self.experts_held == self.num_experts

    @property
    def has_recurrent_layers(self) -> bool:
        """Some layer keeps per-sequence state that is not K/V pages."""
        return bool({"mamba", "mamba2"} & set(self.layer_kinds()))

    @property
    def keeps_slot_rows(self) -> bool:
        """Some layer keeps ROWS a slot beside the pages (recurrent state, a
        window layer's ring): what no block table reaches, so whatever
        shares or ships pages alone does not carry it."""
        return self.has_recurrent_layers or "window" in self.layer_kinds()

    @property
    def mamba_d_inner(self) -> int:
        if self.mamba_n_heads:
            return self.mamba_n_heads * self.mamba_head_dim
        return self.mamba_expand * self.d_model

    @property
    def mamba2_conv_dim(self) -> int:
        """Channels of Mamba-2's one convolution: x, then B and C a group."""
        return (self.mamba_d_inner
                + 2 * self.mamba_n_groups * self.mamba_d_state)

    def to_dict(self) -> dict:
        return asdict(self)

    def to_json(self) -> str:
        """Checkpoint serialization (train/checkpoint.py model_config.json);
        ``model_type`` tags the config class for reconstruction."""
        import json

        return json.dumps({**self.to_dict(), "model_type": "causal_lm"})

    @classmethod
    def from_dict(cls, d: dict) -> "LMConfig":
        # keys the class does not have are dropped: a checkpoint written when
        # it had more options (``attention``, ``block_q`` ...) still loads
        return cls(**{k: v for k, v in d.items() if k in cls.__dataclass_fields__})

    @classmethod
    def tiny(cls, vocab_size: int = 384) -> "LMConfig":
        return cls(vocab_size=vocab_size, d_model=64, n_layers=2, n_heads=4,
                   head_dim=16, d_ff=128, max_seq_len=512)
