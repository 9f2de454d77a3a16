"""airscope — the perf pillar of tpu_air observability.

Three pieces, each usable alone:

* :class:`Histogram` — a thread-safe log-bucketed streaming histogram.
  Buckets grow by ``2**(1/4)`` (≤ ~9% relative error per bucket), counts
  are a sparse ``{bucket_index: count}`` dict so two histograms — or two
  serialized snapshots from different replicas — merge by adding counts.
  Each bucket optionally carries an OpenMetrics-style *exemplar*: the
  airtrace ``trace_id`` of the bucket's worst recent sample, so a p99 on
  the dashboard is one ``/api/traces?trace_id=`` click from its span tree.
  This replaces the seed's 256-sample deques + sorted-index quantiles:
  quantiles here are unwindowed and unbiased to bucket resolution.

* :class:`LMCostModel` — an analytic flops/bytes model for the engine's
  compiled programs (paged decode step, prefill chunk, train step),
  derived from model geometry the way the pjit/TPUv4 scaling work does it
  (PAPERS.md, arXiv:2204.06514): costs come from the shapes the machine
  actually executes (fixed S×slot_len decode, ``[1, page_len]`` chunks),
  not from per-request token counts.

* :class:`PerfLedger` — accumulates ``(cost, seconds)`` per program kind
  into achieved flops/s and bytes/s, a roofline fraction against the
  device's peak (:func:`detect_peak` — no peak on the CPU, so no fraction
  there: a roofline share is a device number), and a goodput split of
  emitted tokens into useful vs. wasted work (shed-after-prefill,
  re-prefilled-on-cache-miss, dead-stream; spec-decode rejections plug in
  as just another category).
"""

from __future__ import annotations

import math
import os
import threading
import time
from dataclasses import dataclass
from typing import Any, Dict, Iterable, List, Optional

# -- histogram ---------------------------------------------------------------

# bucket upper bounds are _BASE**i for integer i (i may be negative);
# bucket i covers (_BASE**(i-1), _BASE**i].  2**(1/4) keeps relative
# quantile error under ~9% while a seconds-scale latency range
# (1e-6 .. 1e3) still spans only ~120 live buckets.
_BASE = 2.0 ** 0.25
_LN_BASE = math.log(_BASE)
# values at or below this clamp into the bottom bucket (latencies are
# positive; 1ns is far below anything a host-side timer can resolve)
_MIN_VALUE = 1e-9
# an exemplar older than this loses its slot to ANY newer sample, even a
# smaller one — "worst recent", not "worst ever"
_EXEMPLAR_TTL_S = 300.0


def bucket_index(value: float) -> int:
    """The histogram bucket a value lands in: smallest integer ``i`` with
    ``value <= _BASE**i`` (epsilon keeps exact bounds in their own bucket)."""
    v = max(float(value), _MIN_VALUE)
    return math.ceil(math.log(v) / _LN_BASE - 1e-9)


def bucket_upper(index: int) -> float:
    """Inclusive upper bound of bucket ``index``."""
    return math.exp(index * _LN_BASE)


class Histogram:
    """Streaming log-bucketed histogram with mergeable buckets and
    per-bucket trace exemplars.  All methods are thread-safe."""

    def __init__(self):
        self._lock = threading.Lock()
        self._buckets: Dict[int, int] = {}
        self._exemplars: Dict[int, Dict[str, Any]] = {}
        self._count = 0
        self._sum = 0.0
        self._min = math.inf
        self._max = -math.inf

    # -- recording -----------------------------------------------------------
    def observe(self, value: float, trace_id: Optional[str] = None) -> None:
        v = float(value)
        idx = bucket_index(v)
        now = time.time()
        with self._lock:
            self._buckets[idx] = self._buckets.get(idx, 0) + 1
            self._count += 1
            self._sum += v
            if v < self._min:
                self._min = v
            if v > self._max:
                self._max = v
            if trace_id:
                ex = self._exemplars.get(idx)
                if (ex is None or v >= ex["value"]
                        or now - ex["ts"] > _EXEMPLAR_TTL_S):
                    self._exemplars[idx] = {
                        "value": v, "trace_id": trace_id, "ts": now}

    def merge_state(self, state: Dict[str, Any]) -> None:
        """Fold a serialized snapshot (:meth:`to_dict` of another instance,
        possibly from another process) into this histogram."""
        if not state or not state.get("count"):
            return
        with self._lock:
            for key, n in (state.get("buckets") or {}).items():
                idx = int(key)
                self._buckets[idx] = self._buckets.get(idx, 0) + int(n)
            for key, ex in (state.get("exemplars") or {}).items():
                idx = int(key)
                mine = self._exemplars.get(idx)
                if mine is None or ex["value"] >= mine["value"]:
                    self._exemplars[idx] = dict(ex)
            self._count += int(state["count"])
            self._sum += float(state.get("sum", 0.0))
            if "min" in state:
                self._min = min(self._min, float(state["min"]))
            if "max" in state:
                self._max = max(self._max, float(state["max"]))

    def merge(self, other: "Histogram") -> None:
        # sequential lock holds (other's, then ours) — never nested
        self.merge_state(other.to_dict())

    def reset(self) -> None:
        with self._lock:
            self._buckets.clear()
            self._exemplars.clear()
            self._count = 0
            self._sum = 0.0
            self._min = math.inf
            self._max = -math.inf

    # -- reading -------------------------------------------------------------
    @property
    def count(self) -> int:
        with self._lock:
            return self._count

    def quantile(self, q: float) -> float:
        with self._lock:
            return self._quantile_locked(q)

    def _quantile_locked(self, q: float) -> float:
        if self._count == 0:
            return 0.0
        rank = q * self._count
        if rank <= 0:
            return self._min
        cum = 0
        for idx in sorted(self._buckets):
            c = self._buckets[idx]
            cum += c
            if cum >= rank:
                hi = bucket_upper(idx)
                lo = bucket_upper(idx - 1)
                frac = (rank - (cum - c)) / c
                v = lo + frac * (hi - lo)
                # observed extremes are exact — clamp the interpolation
                return min(max(v, self._min), self._max)
        return self._max

    def to_dict(self) -> Dict[str, Any]:
        """Serializable state: str bucket keys (JSON round-trips), plus the
        summary scalars.  ``from_dict``/``merge_state`` accept it back."""
        with self._lock:
            out: Dict[str, Any] = {
                "count": self._count,
                "sum": self._sum,
                "buckets": {str(i): c for i, c in sorted(self._buckets.items())},
            }
            if self._count:
                out["min"] = self._min
                out["max"] = self._max
            if self._exemplars:
                out["exemplars"] = {
                    str(i): dict(ex)
                    for i, ex in sorted(self._exemplars.items())
                }
            return out

    @classmethod
    def from_dict(cls, state: Dict[str, Any]) -> "Histogram":
        h = cls()
        h.merge_state(state or {})
        return h

    def summary(self) -> Dict[str, Any]:
        """The engine-snapshot distribution dict.  Superset of the seed's
        ``_dist`` keys (count/mean/p50/p95/p99/max) so every existing
        consumer keeps working; ``buckets``/``sum``/``exemplars`` make it
        mergeable and exemplar-linked downstream."""
        with self._lock:
            if self._count == 0:
                return {"count": 0}
            out = {
                "count": self._count,
                "mean": self._sum / self._count,
                "p50": self._quantile_locked(0.50),
                "p95": self._quantile_locked(0.95),
                "p99": self._quantile_locked(0.99),
                "min": self._min,
                "max": self._max,
                "sum": self._sum,
                "buckets": {str(i): c for i, c in sorted(self._buckets.items())},
            }
            if self._exemplars:
                out["exemplars"] = {
                    str(i): dict(ex)
                    for i, ex in sorted(self._exemplars.items())
                }
            return out

    def cumulative_buckets(self) -> List[Any]:
        """``[(upper_bound, cumulative_count, exemplar_or_None), ...]`` over
        the non-empty buckets, ascending — the prometheus ``_bucket`` series
        (caller appends the ``+Inf`` bound = count)."""
        with self._lock:
            out = []
            cum = 0
            for idx in sorted(self._buckets):
                cum += self._buckets[idx]
                out.append((bucket_upper(idx), cum, self._exemplars.get(idx)))
            return out


def cumulative_from_summary(summary: Dict[str, Any]) -> List[Any]:
    """``[(upper_bound, cumulative_count, exemplar_or_None), ...]`` from a
    SERIALIZED distribution dict — the prometheus ``_bucket`` series for
    snapshots that already crossed a process boundary."""
    buckets = (summary or {}).get("buckets") or {}
    exemplars = (summary or {}).get("exemplars") or {}
    out = []
    cum = 0
    for idx in sorted(int(k) for k in buckets):
        cum += int(buckets[str(idx)])
        out.append((bucket_upper(idx), cum, exemplars.get(str(idx))))
    return out


def merge_summaries(summaries: Iterable[Dict[str, Any]]) -> Dict[str, Any]:
    """Merge distribution dicts (``Histogram.summary()`` outputs, possibly
    JSON-round-tripped from other replicas) into one summary.  Entries
    without ``buckets`` (a pre-airscope snapshot, or a synthetic test dict)
    degrade gracefully: their counts still add and the merged max/p99 are
    at least as large as theirs."""
    h = Histogram()
    legacy_count = 0
    legacy_floor: Dict[str, float] = {}
    for s in summaries:
        if not s or not s.get("count"):
            continue
        if s.get("buckets"):
            h.merge_state(s)
        else:
            legacy_count += int(s["count"])
            for k in ("p50", "p95", "p99", "max", "mean"):
                if k in s:
                    legacy_floor[k] = max(legacy_floor.get(k, 0.0),
                                          float(s[k]))
    out = h.summary()
    if legacy_count:
        out["count"] = out.get("count", 0) + legacy_count
        for k, v in legacy_floor.items():
            out[k] = max(out.get(k, 0.0), v)
    return out


def exemplar_trace_id(summary: Dict[str, Any],
                      q: float = 0.99) -> Optional[str]:
    """The trace id joined to the tail of a distribution: the exemplar of
    the highest bucket at or below the q-quantile's bucket (falling back to
    the worst exemplar present).  None when the summary carries none."""
    exemplars = (summary or {}).get("exemplars") or {}
    if not exemplars:
        return None
    best_idx = max(int(i) for i in exemplars)
    return exemplars[str(best_idx)]["trace_id"]


# -- peak detection ----------------------------------------------------------

# (bf16 peak FLOP/s, HBM bytes/s) of one chip by PJRT device_kind, from the
# public spec sheets (Google Cloud TPU documentation, system architecture
# pages).  One table, so a kind has both numbers or neither.
_PEAKS: Dict[str, tuple] = {
    "TPU v3": (123e12, 900e9),
    "TPU v4": (275e12, 1228e9),
    "TPU v5 lite": (197e12, 819e9),
    "TPU v5e": (197e12, 819e9),
    "TPU v5": (459e12, 2765e9),
    "TPU v5p": (459e12, 2765e9),
    "TPU v6 lite": (918e12, 1640e9),
    "TPU v6e": (918e12, 1640e9),
}


@dataclass(frozen=True)
class PeakSpec:
    """The roofline ceiling the ledger divides by."""

    flops_per_s: float
    bytes_per_s: float
    source: str  # "env" | the device_kind table key


def detect_peak() -> Optional[PeakSpec]:
    """The peak of the device this process computes on.  Both env overrides
    together (``TPU_AIR_PEAK_FLOPS`` and ``TPU_AIR_PEAK_BYTES``) win; a TPU
    is looked up by ``device_kind`` and a kind the tables do not hold is an
    error, not a default; the CPU has no peak here (None), so the ledger
    publishes no roofline fraction from a CPU run."""
    env_f = os.environ.get("TPU_AIR_PEAK_FLOPS")
    env_b = os.environ.get("TPU_AIR_PEAK_BYTES")
    if env_f or env_b:
        if not (env_f and env_b):
            raise ValueError(
                "set TPU_AIR_PEAK_FLOPS and TPU_AIR_PEAK_BYTES together")
        return PeakSpec(float(env_f), float(env_b), source="env")
    import jax

    dev = jax.devices()[0]
    if dev.platform == "cpu":
        return None
    for k in sorted(_PEAKS, key=len, reverse=True):
        if dev.device_kind.startswith(k):
            return PeakSpec(*_PEAKS[k], source=k)
    raise ValueError(
        f"no peak FLOP/s and bytes/s on record for device kind "
        f"{dev.device_kind!r} ({dev.platform}); add it to "
        "observability/perf.py with its source, or set TPU_AIR_PEAK_FLOPS "
        "and TPU_AIR_PEAK_BYTES")


# -- analytic cost model -----------------------------------------------------

_DTYPE_BYTES = {
    "float32": 4, "f32": 4, "float64": 8,
    "bfloat16": 2, "bf16": 2, "float16": 2, "f16": 2,
    "int8": 1, "uint8": 1,
}


@dataclass(frozen=True)
class ProgramCost:
    """What one execution of a compiled program costs the machine."""

    flops: float
    hbm_bytes: float
    tokens: int = 0

    def scaled(self, n: float) -> "ProgramCost":
        return ProgramCost(self.flops * n, self.hbm_bytes * n,
                           int(self.tokens * n))


class LMCostModel:
    """Flops/bytes for the decoder-only LM's compiled programs.

    Geometry (``D`` d_model, ``H`` heads, ``Dh`` head_dim, ``F`` d_ff,
    ``L`` layers, ``V`` vocab, ``b`` dtype bytes) gives the exact formulas
    the unit tests hand-compute:

    * matmul params/layer: ``4*D*H*Dh`` (q,k,v,o) + ``3*D*F`` (SwiGLU
      gate/up/down); lm head compute ``D*V`` per token (params stored only
      when untied; embedding lookup adds no matmul flops).
    * linear flops/token: ``2 * (L*(4*D*H*Dh + 3*D*F) + D*V)``.
    * attention flops: ``4*H*Dh*P`` per layer for a token attending ``P``
      positions (QK^T and AV, 2 flops/MAC each).
    * KV bytes/position: ``L * 2*H*Dh * b`` (K and V, all layers).

    Sparse experts (``config.num_experts = E > 0``, ``k`` a token): the
    feed-forward STORES ``E * 3*D*F`` parameters a layer plus the ``D*E``
    router, and a token COMPUTES with ``k * 3*D*F`` of them plus the router,
    so stored bytes and operations part ways.  A program streams every
    expert at least one of its tokens is routed to; with ``n`` assignments
    spread over ``E`` experts that is priced at its expectation under even
    routing, ``E * (1 - (1 - 1/E)**n)`` experts a layer (63.98 of 64 at a
    128-token chunk of top-8), an upper estimate when routing is skewed.

    Layer kinds (``config.layer_kinds()``): only the ``A`` attention layers
    have q, k, v, o (k and v at ``G = n_kv_heads`` heads: ``2*D*H*Dh +
    2*D*G*Dh``), K/V bytes and attention flops; each of the ``M`` Mamba
    layers has its four projections instead (``D*2c + c*(r + 2n) + r*c +
    c*D`` at ``c = d_inner``, ``n`` states, ``r`` the step size's rank) and
    keeps ``c*n*4 + (k-1)*c*b`` bytes of state a row, which a decode step
    reads and writes for EVERY row and a chunk for one.  The recurrence's own
    arithmetic (``~7*c*n`` a token a layer) is counted; it is vector work.

    Feed-forward by layer (``config.ff_kinds()``) and held experts: the
    ``S`` sparse layers store ``held * 3*D*F`` (``config.experts_held`` of the
    ``E`` routed over), the ``D*E`` router and ``shared * 3*D*F``; a token
    computes with ``k * held/E`` held experts in expectation, plus the
    shared one; the other layers keep one SwiGLU of ``dense_d_ff``.  Latent
    attention (``config.kv_lora_rank = r``): the layer's matrices are ``D*rq
    + rq*H*(dn+dr) + D*(r+dr) + r*H*(dn+dv) + H*dv*D``, a position keeps
    ``(r+dr) * b`` bytes a layer, and a token attending ``P`` positions in
    the absorbed form costs ``4*H*(r+dr)*P`` (both contractions run over the
    slab's whole width).

    A layer that is one thing (``config.layer_pattern``): the counts above
    are by kind, so a layer of experts alone has no attention and a mixer
    layer no feed-forward.  Each of the ``M2`` Mamba-2 layers has ``D*(2c +
    2*G*n + H) + c*D`` (``H`` heads, ``G`` groups) and keeps ``c*n*4 +
    (k-1)*(c + 2*G*n)*b`` bytes a row.  ``ff_act`` ``relu2``: a feed-forward
    is TWO matrices.  ``moe_latent_size = l``: a routed expert is ``2*l*F``
    and the layer has the pair ``2*D*l`` beside it; the shared expert stays
    at the model's width, ``shared_d_ff`` wide.

    A residual path of ``n = config.hc_mult > 1`` streams: each sublayer
    (a mixer, a feed-forward: ``n_sublayers``) stores ``n*D*(n*n + 2n) + n*n
    + 2n + 3`` float32 parameters of its hyper-connection whatever the
    model's dtype, computes ``2*(n*D*(n*n + 2n) + 2*n*D + n*n*D)`` operations
    a token with them (the product onto the maps, the read and the
    write-back; the Sinkhorn rounds are ``n*n`` numbers) and moves ``(2n +
    2)*D*b`` bytes of streams a token: the state read once and written
    once, the sublayer's input written and its output read.

    Window layers beside full ones (``layer_kinds()`` holds ``"window"``):
    each of the ``W`` window layers has q and o at ``window_n_heads`` heads
    and keeps K and V like a full layer, but a token reads at most
    ``sliding_window`` positions of it, whatever the context; ``attn_gate``
    adds ``D*H`` a layer of either kind.

    Norms, rotary embeddings and softmax are omitted (≪1% of the matmul
    budget at any real geometry); the model is deliberately closed-form so
    identical claims can be recomputed anywhere (arXiv:2204.06514 §4).
    """

    def __init__(self, config):
        self.d_model = int(config.d_model)
        self.n_layers = int(config.n_layers)
        self.n_heads = int(config.n_heads)
        self.head_dim = int(config.head_dim)
        self.d_ff = int(config.d_ff)
        self.vocab_size = int(config.vocab_size)
        self.tie_embeddings = bool(getattr(config, "tie_embeddings", True))
        self.num_experts = int(getattr(config, "num_experts", 0) or 0)
        self.experts_per_tok = int(
            getattr(config, "num_experts_per_tok", 0) or 0)
        self.dtype_bytes = _DTYPE_BYTES.get(
            str(getattr(config, "dtype", "float32")), 4)
        self.n_kv_heads = int(getattr(config, "n_kv_heads", None)
                              or self.n_heads)
        kinds = (config.layer_kinds() if hasattr(config, "layer_kinds")
                 else ["attention"] * self.n_layers)
        self.n_mamba_layers = kinds.count("mamba")
        self.n_mamba2_layers = kinds.count("mamba2")
        self.n_attn_layers = (kinds.count("attention")
                              + kinds.count("latent"))
        self.n_window_layers = kinds.count("window")
        self.window_n_heads = int(getattr(config, "window_n_heads", None)
                                  or self.n_heads)
        self.sliding_window = int(getattr(config, "sliding_window", 0) or 0)
        self.attn_gate = getattr(config, "attn_gate", "none") != "none"
        self.mamba_n_heads = int(getattr(config, "mamba_n_heads", 0) or 0)
        self.mamba_n_groups = int(getattr(config, "mamba_n_groups", 1) or 1)
        self.d_inner = int(getattr(config, "mamba_d_inner", 0) or 0)
        self.d_state = int(getattr(config, "mamba_d_state", 0) or 0)
        self.d_conv = int(getattr(config, "mamba_d_conv", 0) or 0)
        self.dt_rank = int(getattr(config, "mamba_dt_rank", 0) or 0)
        ff = (config.ff_kinds() if hasattr(config, "ff_kinds")
              else ["sparse" if self.num_experts else "dense"]
              * self.n_layers)
        self.n_sparse_layers = ff.count("sparse")
        self.n_dense_layers = ff.count("dense")
        self.ff_matrices = 2 if getattr(
            config, "ff_act", "swiglu") == "relu2" else 3
        self.moe_latent = int(getattr(config, "moe_latent_size", 0) or 0)
        self.dense_d_ff = int(getattr(config, "dense_d_ff", None)
                              or self.d_ff)
        self.experts_held = int(getattr(config, "experts_held", None)
                                or self.num_experts)
        self.shared_experts = int(
            getattr(config, "num_shared_experts", 0) or 0)
        self.shared_d_ff = int(getattr(config, "shared_d_ff", None)
                               or self.shared_experts * self.d_ff)
        self.kv_lora_rank = int(getattr(config, "kv_lora_rank", 0) or 0)
        self.q_lora_rank = int(getattr(config, "q_lora_rank", 0) or 0)
        self.qk_nope = int(getattr(config, "qk_nope_head_dim", 0) or 0)
        self.qk_rope = int(getattr(config, "qk_rope_head_dim", 0) or 0)
        self.v_head_dim = int(getattr(config, "v_head_dim", 0) or 0)
        self.hc_mult = int(getattr(config, "hc_mult", 1) or 1)
        self.n_sublayers = (self.n_attn_layers + self.n_window_layers
                            + self.n_mamba_layers
                            + self.n_mamba2_layers + self.n_sparse_layers
                            + self.n_dense_layers)

    # -- derived geometry ----------------------------------------------------
    @property
    def _attn_params(self) -> int:
        if self.kv_lora_rank:
            d, h, r = self.d_model, self.n_heads, self.kv_lora_rank
            return (d * self.q_lora_rank
                    + self.q_lora_rank * h * (self.qk_nope + self.qk_rope)
                    + d * (r + self.qk_rope)
                    + r * h * (self.qk_nope + self.v_head_dim)
                    + h * self.v_head_dim * d)
        return self._qkvo_params(self.n_heads)

    def _qkvo_params(self, heads: int) -> int:
        """q and o at ``heads`` heads, k and v at the K/V heads, the gate."""
        return (2 * self.d_model * self.head_dim * (heads + self.n_kv_heads)
                + self.d_model * heads * self.attn_gate)

    @property
    def _mamba_params(self) -> int:
        c, n, r = self.d_inner, self.d_state, self.dt_rank
        return (self.d_model * 2 * c + c * (r + 2 * n) + r * c
                + c * self.d_model)

    @property
    def _mamba2_conv_dim(self) -> int:
        return self.d_inner + 2 * self.mamba_n_groups * self.d_state

    @property
    def _mamba2_params(self) -> int:
        c = self.d_inner
        return (self.d_model * (c + self._mamba2_conv_dim
                                + self.mamba_n_heads) + c * self.d_model)

    @property
    def state_bytes_per_row(self) -> int:
        """Recurrent state one sequence keeps over the Mamba layers: the
        float32 state and the convolution tail in the model's dtype."""
        tail = (self.d_conv - 1) * self.dtype_bytes
        return (self.n_mamba_layers * self.d_inner * (self.d_state * 4 + tail)
                + self.n_mamba2_layers * (self.d_inner * self.d_state * 4
                                          + self._mamba2_conv_dim * tail))

    @property
    def _mhc_params(self) -> int:
        """Float32 parameters of the hyper-connections (0: one stream)."""
        n = self.hc_mult
        if n == 1:
            return 0
        maps = n * n + 2 * n
        return self.n_sublayers * (n * self.d_model * maps + maps + 3)

    @property
    def mhc_flops_per_token(self) -> float:
        n, d = self.hc_mult, self.d_model
        if n == 1:
            return 0.0
        return self.n_sublayers * 2.0 * (
            n * d * (n * n + 2 * n) + 2 * n * d + n * n * d)

    @property
    def mhc_stream_bytes_per_token(self) -> float:
        if self.hc_mult == 1:
            return 0.0
        return (self.n_sublayers * (2 * self.hc_mult + 2) * self.d_model
                * self.dtype_bytes)

    @property
    def _expert_params(self) -> int:
        """One routed expert of width ``d_ff``, in the width it works in."""
        return self.ff_matrices * (self.moe_latent or self.d_model) \
            * self.d_ff

    def _layer_params(self, experts: float) -> float:
        """Matrix parameters of the layers with ``experts`` routed experts
        counted in each sparse one (beside its router and shared expert; a
        dense layer has the one feed-forward and no router)."""
        sparse = (experts * self._expert_params
                  + self.ff_matrices * self.d_model * self.shared_d_ff
                  + 2 * self.d_model * self.moe_latent
                  + self.d_model * self.num_experts)
        dense = self.ff_matrices * self.d_model * self.dense_d_ff
        return (self.n_attn_layers * self._attn_params
                + self.n_window_layers * self._qkvo_params(
                    self.window_n_heads)
                + self.n_mamba_layers * self._mamba_params
                + self.n_mamba2_layers * self._mamba2_params
                + self.n_sparse_layers * sparse
                + self.n_dense_layers * dense)

    @property
    def matmul_params(self) -> int:
        """Matrix parameters STORED in the layers."""
        return self._layer_params(self.experts_held)

    @property
    def active_matmul_params(self) -> float:
        """Matrix parameters one token COMPUTES with in the layers (of its
        ``k`` experts the share this tree holds, in expectation)."""
        if not self.num_experts:
            return self._layer_params(0)
        return self._layer_params(
            self.experts_per_tok * self.experts_held / self.num_experts)

    @property
    def param_count(self) -> int:
        n = (self.vocab_size * self.d_model + self.matmul_params
             + self._mhc_params)
        if not self.tie_embeddings:
            n += self.d_model * self.vocab_size
        return n

    @property
    def param_bytes(self) -> int:
        # the hyper-connections are float32 in a model of any dtype
        return (self.param_count * self.dtype_bytes
                + self._mhc_params * (4 - self.dtype_bytes))

    def experts_touched(self, tokens: int) -> float:
        """Experts of one layer a program over ``tokens`` tokens streams
        (expectation under even routing); 0 for a dense model."""
        if not self.num_experts:
            return 0.0
        e = self.num_experts
        return self.experts_held * (
            1.0 - (1.0 - 1.0 / e) ** (tokens * self.experts_per_tok))

    def streamed_param_bytes(self, tokens: int) -> float:
        """Parameter bytes a program over ``tokens`` tokens reads: all of
        them for a dense model (the embedding table is read by row, but is
        priced whole as it always was); for sparse experts only the experts
        touched."""
        if not self.num_experts:
            return float(self.param_bytes)
        idle = self.experts_held - self.experts_touched(tokens)
        return (self.param_bytes - self.n_sparse_layers * idle
                * self._expert_params * self.dtype_bytes)

    @property
    def linear_flops_per_token(self) -> float:
        return (2.0 * (self.active_matmul_params
                       + self.d_model * self.vocab_size)
                + (self.n_mamba_layers + self.n_mamba2_layers) * 7.0
                * self.d_inner * self.d_state
                + self.mhc_flops_per_token)

    @property
    def kv_bytes_per_position(self) -> float:
        if self.kv_lora_rank:
            return self.n_attn_layers * (self.kv_lora_rank + self.qk_rope) \
                * self.dtype_bytes
        return self.n_attn_layers * 2 * self.n_kv_heads * self.head_dim \
            * self.dtype_bytes

    @property
    def window_kv_bytes_per_position(self) -> float:
        """K and V bytes a position keeps over the WINDOW layers (a token
        reads at most ``sliding_window`` positions of them)."""
        return self.n_window_layers * 2 * self.n_kv_heads * self.head_dim \
            * self.dtype_bytes

    def _window_attended(self, attended: float, tokens: float = 1) -> float:
        """Of ``attended`` positions summed over ``tokens`` tokens, those a
        window layer's reads reach."""
        return min(attended, tokens * self.sliding_window)

    def attention_flops(self, attended_positions: float,
                        tokens: float = 1) -> float:
        """``tokens`` tokens (default ONE) attending ``attended_positions``
        positions between them."""
        width = (self.kv_lora_rank + self.qk_rope if self.kv_lora_rank
                 else self.head_dim)
        return 4.0 * width * (
            self.n_attn_layers * self.n_heads * attended_positions
            + self.n_window_layers * self.window_n_heads
            * self._window_attended(attended_positions, tokens))

    # -- program costs -------------------------------------------------------
    def decode_step_cost(self, rows: int, attended: int) -> ProgramCost:
        """One fixed-shape pool decode step: ``rows`` slots each computing
        one token and attending the COMPILED context length (the paged
        gather reads ``attended = slot_len`` positions per row regardless
        of occupancy — that is what the machine executes)."""
        flops = rows * (self.linear_flops_per_token
                        + self.attention_flops(attended))
        hbm = (self.streamed_param_bytes(rows)
               + rows * attended * self.kv_bytes_per_position   # KV read
               + rows * self._window_attended(attended)
               * self.window_kv_bytes_per_position              # ring read
               + rows * (self.kv_bytes_per_position             # KV write
                         + self.window_kv_bytes_per_position)
               + 2 * rows * self.state_bytes_per_row            # state r+w
               + rows * self.mhc_stream_bytes_per_token)
        return ProgramCost(flops=flops, hbm_bytes=hbm, tokens=rows)

    def prefill_chunk_cost(self, chunk_len: int,
                           start_pos: int) -> ProgramCost:
        """One ``[1, chunk_len]`` prefill chunk starting at ``start_pos``:
        token ``t`` of the chunk attends ``start_pos + t + 1`` positions, so
        the chunk's attended-position total is
        ``chunk_len*start_pos + chunk_len*(chunk_len+1)/2``."""
        c = int(chunk_len)
        attended_sum = c * start_pos + c * (c + 1) / 2.0
        flops = (c * self.linear_flops_per_token
                 + self.attention_flops(attended_sum, c))
        hbm = (self.streamed_param_bytes(c)
               + (start_pos + c) * self.kv_bytes_per_position   # prefix read
               + min(start_pos + c, self.sliding_window + c)
               * self.window_kv_bytes_per_position              # ring read
               + c * (self.kv_bytes_per_position                # KV write
                      + self.window_kv_bytes_per_position)
               + 2 * self.state_bytes_per_row                   # one row
               + c * self.mhc_stream_bytes_per_token)
        return ProgramCost(flops=flops, hbm_bytes=hbm, tokens=c)

    def mixed_step_cost(self, rows: int, attended: int, chunk_len: int,
                        start_pos: int) -> ProgramCost:
        """One decode step and one prefill chunk in one pass over the model:
        each part's operations and per-sequence bytes as above, the
        parameters streamed ONCE for the ``rows + chunk_len`` tokens."""
        step = self.decode_step_cost(rows, attended)
        chunk = self.prefill_chunk_cost(chunk_len, start_pos)
        hbm = (step.hbm_bytes + chunk.hbm_bytes
               - self.streamed_param_bytes(rows)
               - self.streamed_param_bytes(chunk_len)
               + self.streamed_param_bytes(rows + chunk_len))
        return ProgramCost(flops=step.flops + chunk.flops, hbm_bytes=hbm,
                           tokens=step.tokens + chunk.tokens)

    def train_step_cost(self, batch: int, seq_len: int) -> ProgramCost:
        """One train step over ``[batch, seq_len]``: backward ≈ 2× forward
        (the standard 3× multiplier), bytes ≈ 3 weight-sized streams
        (params + grads + optimizer update) plus activation KV traffic."""
        tokens = batch * seq_len
        attended_sum = batch * seq_len * (seq_len + 1) / 2.0
        fwd = (tokens * self.linear_flops_per_token
               + self.attention_flops(attended_sum))
        hbm = 3.0 * self.param_bytes \
            + 2.0 * tokens * self.kv_bytes_per_position
        return ProgramCost(flops=3.0 * fwd, hbm_bytes=hbm, tokens=tokens)


# -- the ledger --------------------------------------------------------------

# wasted-token categories the engine reports today; the set is open —
# ledger.record_tokens accepts any string (spec-decode rejections land as
# "spec_rejected" without a ledger change)
WASTED_CATEGORIES = ("shed_after_prefill", "reprefill_cache_miss",
                     "dead_stream")


class PerfLedger:
    """Per-engine accumulator: program costs → achieved rates + roofline
    fraction; token categories → goodput ratio.  Thread-safe."""

    def __init__(self, peak: Optional[PeakSpec]):
        """``peak=None``: no device peak (a CPU run) — rates are still
        accumulated, every ``roofline_fraction`` is None."""
        self._lock = threading.Lock()
        self._peak = peak
        self._programs: Dict[str, Dict[str, float]] = {}
        self._tokens: Dict[str, int] = {}

    def record_program(self, kind: str, cost: ProgramCost,
                       seconds: float, calls: int = 1) -> None:
        with self._lock:
            p = self._programs.setdefault(
                kind, {"calls": 0, "flops": 0.0, "bytes": 0.0,
                       "seconds": 0.0, "tokens": 0})
            p["calls"] += int(calls)
            p["flops"] += cost.flops
            p["bytes"] += cost.hbm_bytes
            p["seconds"] += max(float(seconds), 0.0)
            p["tokens"] += cost.tokens

    def record_tokens(self, category: str, n: int) -> None:
        """Goodput accounting: ``category`` is ``"useful"`` or a wasted
        class (``WASTED_CATEGORIES`` or any future string)."""
        if n <= 0:
            return
        with self._lock:
            self._tokens[category] = self._tokens.get(category, 0) + int(n)

    def reset(self) -> None:
        """Clear accumulators (bench steady-state windows)."""
        with self._lock:
            self._programs.clear()
            self._tokens.clear()

    def _ideal_seconds(self, flops: float, nbytes: float) -> float:
        return max(flops / self._peak.flops_per_s,
                   nbytes / self._peak.bytes_per_s)

    def snapshot(self) -> Dict[str, Any]:
        with self._lock:
            programs: Dict[str, Any] = {}
            tot_flops = tot_bytes = tot_seconds = 0.0
            tot_ideal = 0.0

            def fraction(ideal: float, secs: float) -> Optional[float]:
                # no device peak (a CPU run): no roofline share
                if self._peak is None:
                    return None
                return ideal / secs if secs else 0.0

            for kind, p in sorted(self._programs.items()):
                secs = p["seconds"]
                ideal = (self._ideal_seconds(p["flops"], p["bytes"])
                         if self._peak else 0.0)
                programs[kind] = {
                    "calls": int(p["calls"]),
                    "flops": p["flops"],
                    "bytes": p["bytes"],
                    "seconds": secs,
                    "tokens": int(p["tokens"]),
                    "flops_per_s": p["flops"] / secs if secs else 0.0,
                    "bytes_per_s": p["bytes"] / secs if secs else 0.0,
                    "roofline_fraction": fraction(ideal, secs),
                }
                tot_flops += p["flops"]
                tot_bytes += p["bytes"]
                tot_seconds += secs
                tot_ideal += ideal
            useful = self._tokens.get("useful", 0)
            wasted = sum(n for cat, n in self._tokens.items()
                         if cat != "useful")
            total = useful + wasted
            return {
                "peak": None if self._peak is None else {
                    "flops_per_s": self._peak.flops_per_s,
                    "bytes_per_s": self._peak.bytes_per_s,
                    "source": self._peak.source,
                },
                "programs": programs,
                "totals": {
                    "flops": tot_flops,
                    "bytes": tot_bytes,
                    "seconds": tot_seconds,
                    "flops_per_s": tot_flops / tot_seconds
                    if tot_seconds else 0.0,
                    "bytes_per_s": tot_bytes / tot_seconds
                    if tot_seconds else 0.0,
                    "roofline_fraction": fraction(tot_ideal, tot_seconds),
                },
                "goodput": {
                    **{cat: int(n) for cat, n in sorted(self._tokens.items())},
                    "total": total,
                    "wasted": wasted,
                    "goodput_ratio": useful / total if total else 1.0,
                },
            }


def merge_ledger_snapshots(snaps: Iterable[Dict[str, Any]]
                           ) -> Dict[str, Any]:
    """Fleet view: sum program accumulators and token categories across
    ledger snapshots (rates/fractions recomputed from the sums; the peak
    of the FIRST snapshot wins — replicas share hardware)."""
    snaps = [s for s in snaps if s]
    if not snaps:
        return {}
    peak = snaps[0].get("peak")
    ledger = PerfLedger(peak and PeakSpec(
        peak["flops_per_s"], peak["bytes_per_s"],
        peak.get("source", "merged")))
    for s in snaps:
        for kind, p in (s.get("programs") or {}).items():
            ledger.record_program(
                kind,
                ProgramCost(p.get("flops", 0.0), p.get("bytes", 0.0),
                            int(p.get("tokens", 0))),
                p.get("seconds", 0.0), calls=int(p.get("calls", 1)))
        for cat, n in (s.get("goodput") or {}).items():
            if cat in ("total", "wasted", "goodput_ratio"):
                continue
            ledger.record_tokens(cat, int(n))
    return ledger.snapshot()
