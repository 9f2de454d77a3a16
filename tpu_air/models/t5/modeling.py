"""Flax T5 (encoder-decoder) implemented TPU-first.

Replaces the reference's torch `T5ForConditionalGeneration`
(Model_finetuning…ipynb:cc-25,46; predictor.py:68,102) with a from-scratch
flax.linen implementation designed for XLA:

* static shapes everywhere — one compiled program serves every batch;
* autoregressive `generate` as a `lax.scan` over a pre-allocated KV cache
  (SURVEY.md §7 hard-part 2), jit-compiled end to end, cache constructed
  via `jax.eval_shape` (no throwaway init compute);
* bf16-friendly: activations in `config.dtype`, params fp32;
* matmul-heavy blocks (DenseGeneral projections, gated-GELU MLP) shaped for
  the MXU; sharding is applied externally by the trainer's partitioner
  (tpu_air/parallel) so DP/TP are config choices, not model rewrites.

Architecture notes (T5 v1.1 == FLAN-T5): RMSNorm pre-norm, relative position
bias (bucketed; table hoisted to each stack and shared by its layers), NO
attention score scaling, gated-GELU feed-forward, untied lm_head.
"""

from __future__ import annotations

import collections
import contextlib
import importlib
import math
from typing import Any, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpu_air.ops.decode_attention import (
    flat_append_decode_attention,
    length_minor,
    length_minor_decode_attention,
    pad_keys,
    prefix_append_decode_attention,
    prefix_slabs_read_in_place,
)

from .config import T5Config

# the module: ``tpu_air.ops.flash_attention`` as an attribute is the function
# of that name, which ``ops/__init__`` re-exports over it
fa = importlib.import_module("tpu_air.ops.flash_attention")

Array = jax.Array

NEG_INF = -1e9


@jax.named_scope("dropout")
def _dropout(x: Array, rate: float, key, transposed: bool = False) -> Array:
    """Inverted dropout from 16 random bits an element: kept where the
    element's ``uint16`` draw is under ``round((1 - rate) * 2**16)`` (rate 0.1:
    58,982 of 65,536, keep probability 0.899994), scaled by ``1 / (1 - rate)``.

    Both live sites (attention probabilities, feed-forward hidden) draw here,
    and what a mask element costs in random bits is most of what live dropout
    costs the train step (PERF.md, PR 37).  Under the trainer's ``rbg`` key a
    draw is one pass of the chip's bit generator, written once and read by the
    forward and the backward fusions; 16 bits halve those bytes against the
    32-bit uniform of ``flax.linen.Dropout``, and 8 cannot state a tenth.
    ``transposed`` draws the bits with the last two axes exchanged, for a site
    whose array the compiler lays out that way: the mask is as good, and the
    bits need no copy into the layout."""
    keep = fa.keep_threshold(rate)
    if transposed:
        *lead, rows, cols = x.shape
        bits = jnp.swapaxes(
            jax.random.bits(key, (*lead, cols, rows), jnp.uint16), -1, -2)
    else:
        bits = jax.random.bits(key, x.shape, jnp.uint16)
    return jnp.where(bits < keep, x / (1.0 - rate), jnp.zeros_like(x))


def _flat_dot_general(x, kernel, dimension_numbers, precision=None,
                      preferred_element_type=None):
    """The product ``DenseGeneral`` asks for with the head and width axes it
    would split (q, k, v: the result then ``[b, L, h*d]``) or contract (o)
    kept as one axis — the same parameters, one plain matrix product."""
    contract = len(dimension_numbers[0][0])        # trailing axes of x
    x = x.reshape(*x.shape[:x.ndim - contract], -1)
    kernel = kernel.reshape(math.prod(kernel.shape[:contract]), -1)
    return jax.lax.dot_general(
        x, kernel, (((x.ndim - 1,), (0,)), ((), ())), precision=precision,
        preferred_element_type=preferred_element_type)


def _train_fused(cfg: T5Config, deterministic: bool, batch: int, qlen: int,
                 klen: int) -> bool:
    """Does a plain (no cache, structured mask) attention of these lengths
    take the fused training kernels?  Live dropout, and a shape the dispatch
    sends there — ``ops.flash_attention.train_dispatch_ok`` under ``"auto"``
    (chosen at trace time from the shape, no knob), any shape with tiles
    under ``"flash"`` — that the kernels' mesh can split."""
    if deterministic or cfg.dropout_rate <= 0 or qlen <= 1:
        return False
    if cfg.attention_impl == "auto":
        ok = fa.train_dispatch_ok(qlen, klen, cfg.d_kv)
    else:
        ok = cfg.attention_impl == "flash" and fa.has_tiles(qlen, klen)
    return ok and fa.mesh_divides(batch, cfg.num_heads)


def _seed_words(key) -> Array:
    """Two 32-bit words of a call site's dropout key, the fused attention's
    seed: the site's own key as ``make_rng`` folded it, so no generator runs
    for them."""
    if jnp.issubdtype(key.dtype, jax.dtypes.prng_key):
        key = jax.random.key_data(key)
    return jax.lax.bitcast_convert_type(key.reshape(-1)[:2], jnp.int32)


# While a program is traced under ``count_attention_sites`` every ``Attention``
# call outside the cached decode step counts itself here, by the path it took.
_site_counts: Optional[collections.Counter] = None


@contextlib.contextmanager
def count_attention_sites():
    """Counts, by ``"fused"`` (the Pallas kernels) and ``"dense"`` (einsum),
    the attention call sites of what is traced under it.
    ``T5Trainer`` reports its train step's (``fused_attention_sites``,
    ``dense_attention_sites``)."""
    global _site_counts
    was, _site_counts = _site_counts, collections.Counter()
    try:
        yield _site_counts
    finally:
        _site_counts = was


def _dtype(config: T5Config):
    return jnp.dtype(config.dtype)


class RMSNorm(nn.Module):
    """T5 LayerNorm: scale-only RMS normalization (no mean, no bias)."""

    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        weight = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        y = x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)
        return (weight * y).astype(self.dtype)


def relative_position_bucket(
    relative_position: Array,
    bidirectional: bool,
    num_buckets: int,
    max_distance: int,
) -> Array:
    """Bucketed relative positions (T5 paper §2.1). ``relative_position`` is
    ``key_position - query_position``."""
    ret = jnp.zeros_like(relative_position)
    n = relative_position
    if bidirectional:
        num_buckets //= 2
        ret += (n > 0).astype(jnp.int32) * num_buckets
        n = jnp.abs(n)
    else:
        n = -jnp.minimum(n, 0)
    max_exact = num_buckets // 2
    is_small = n < max_exact
    val_if_large = max_exact + (
        jnp.log(n.astype(jnp.float32) / max_exact + 1e-6)
        / jnp.log(max_distance / max_exact)
        * (num_buckets - max_exact)
    ).astype(jnp.int32)
    val_if_large = jnp.minimum(val_if_large, num_buckets - 1)
    return ret + jnp.where(is_small, n, val_if_large)


class RelativePositionBias(nn.Module):
    """Relative attention bias table → [1, heads, qlen, klen]."""

    config: T5Config
    bidirectional: bool

    @nn.compact
    def __call__(self, query_positions: Array, key_positions: Array) -> Array:
        cfg = self.config
        table = self.param(
            "embedding",
            nn.initializers.normal(stddev=1.0),
            (cfg.relative_attention_num_buckets, cfg.num_heads),
            jnp.float32,
        )
        rel = key_positions[None, :] - query_positions[:, None]  # [q, k]
        buckets = relative_position_bucket(
            rel,
            self.bidirectional,
            cfg.relative_attention_num_buckets,
            cfg.relative_attention_max_distance,
        )
        bias = table[buckets]  # [q, k, heads]
        return jnp.transpose(bias, (2, 0, 1))[None].astype(_dtype(cfg))


class Attention(nn.Module):
    """Multi-head attention with optional pre-allocated decode cache.

    T5 detail: scores are NOT scaled by sqrt(d_kv).
    """

    config: T5Config

    @nn.compact
    def __call__(
        self,
        hidden: Array,
        kv_hidden: Array,
        mask: Optional[Array],           # [*, 1|heads, qlen, klen] additive (dense)
        position_bias: Optional[Array],  # [1, heads, qlen, klen]
        kv_mask: Optional[Array] = None,  # [batch, klen] 1=attend (structured)
        causal: bool = False,             # structured causal flag
        decode: bool = False,             # no flash: a decoding pass
        appending: Optional[tuple] = None,  # cached self-attention step
        cross_decode: bool = False,
        deterministic: bool = True,
    ):
        """``appending`` ``(k_slab, v_slab, k_scales, v_scales, index,
        layer)``: this layer's self-attention slabs ``[L, b, h*d]`` as they
        were before the step (scales ``[L, b, h]`` or None) and the position
        the step's rows go to; ``layer`` None, or this layer's index where
        the slabs are the STACKED ``[layers, L, b, h*d]`` of a decode loop
        whose positions from ``index`` on no row has written (read where
        they lie, ``prefix_append_decode_attention``).  The result is then
        ``(out, rows)``, ``rows`` the step's ``(k, v, k_scales, v_scales)``
        as stored, ``[new, b, ...]``."""
        cfg = self.config
        dtype = _dtype(cfg)
        init = nn.initializers.normal(stddev=cfg.d_model**-0.5)

        # A training pass with live attention dropout, no cache in play and
        # the structured mask form: the attention between the projections is
        # the fused Pallas kernels wherever the shape is worth a kernel
        # (``_train_fused``).  The dense path there writes, keeps and reads
        # the [b, h, q, k] probabilities and their mask words in HBM, a third
        # of the fine-tune step (PERF.md, PR 39); the kernels draw the mask
        # where they use it, forward and backward, and keep neither.  Known
        # before the projections, because these then keep heads and widths
        # one axis, [b, L, h*d]: the kernels read a head's lanes where they
        # lie, and no array is copied into another layout on the way.
        live_dropout = not deterministic and cfg.dropout_rate > 0
        train_fused = (
            not decode and not cross_decode and appending is None
            and mask is None
            and _train_fused(cfg, deterministic, hidden.shape[0],
                             hidden.shape[1], kv_hidden.shape[1]))
        flat = _flat_dot_general if train_fused else None

        def dense(name):
            return nn.DenseGeneral(
                features=(cfg.num_heads, cfg.d_kv),
                axis=-1, use_bias=False, dtype=dtype, kernel_init=init, name=name,
                dot_general=flat,
            )

        q = dense("q")(hidden)           # [b, q, h, d]; train_fused: [b, q, h*d]
        cache_int8 = getattr(cfg, "decode_cache_int8", False)

        def _quant(x):
            # per-(batch, head, channel) scale over the length dim: the
            # length axis is what streams from HBM every step.  Pad
            # positions are zeroed FIRST — they are masked out of the
            # scores anyway, and a pad-position activation outlier would
            # otherwise inflate the scale and coarsen the grid for every
            # valid token in its channel.
            xf = x.astype(jnp.float32)
            if kv_mask is not None:
                xf = xf * kv_mask[:, :, None, None].astype(jnp.float32)
            amax = jnp.max(jnp.abs(xf), axis=1, keepdims=True)
            scale = jnp.maximum(amax, 1e-8) / 127.0
            q8 = jnp.clip(jnp.round(xf / scale), -127, 127)
            return q8.astype(jnp.int8), scale

        def _dequant(q8, scale):
            return (q8.astype(jnp.float32) * scale).astype(dtype)

        # Decode caches have two layouts, by how they are written
        # (ops/decode_attention.py).  SELF slabs grow a position a step and
        # are FLAT and position-major, [L, b, h*d], scales [L, b, h] (per
        # position); the ``Decoder`` holds them and appends to them, this
        # module reads them.  CROSS slabs are written once, at cache init,
        # here, and are LENGTH-MINOR, [b, h, d, Lp], scales [b, h, d, 1]
        # (per channel): (d_kv, Lp) are whole tiles, Lp the encoder length
        # padded up to 128 lanes behind a zero key mask.  What neither is,
        # ever, is a row-major [b, L, h, d]: TPU tiles its last two dims
        # (12, 64) up to (16, 128), 2.67x the physical HBM bytes.  The
        # cached single-token step attends over each slab as stored and
        # makes no copy of one in another order
        # (tests/test_t5.py::test_cached_step_never_views_a_slab_in_4d).
        dk_scales = (None, None)
        cached_step = False    # k/v hold cache slabs, not [b, k, h, d]
        cross_cached = False   # ... and they are the length-minor ones

        if cross_decode and self.has_variable("cache", "cached_key"):
            # Cross-attention during cached decode: K/V are an invariant of
            # the encoder output, computed ONCE at cache init.  Recomputing
            # the two 512-token projections per decode step was the dominant
            # cost of W3 generation (~12 layers x 2 projections x the full
            # encoder length, per emitted token).
            k = self.get_variable("cache", "cached_key")     # [b, h, d, Lp]
            v = self.get_variable("cache", "cached_value")
            cached_step = cross_cached = True
            if cache_int8:
                dk_scales = (
                    self.get_variable("cache", "cached_key_scale"),
                    self.get_variable("cache", "cached_value_scale"),
                )
            if k.shape[0] > q.shape[0]:
                # a cache of more rows than the step has: the step runs over
                # the first rows of it (``Decoder``, a step over a prefix)
                k, v = k[:q.shape[0]], v[:q.shape[0]]
                dk_scales = tuple(
                    s if s is None else s[:q.shape[0]] for s in dk_scales)
            # the lanes ``length_minor`` added hold no key: masked for
            # every row, whatever the caller's mask says of the real ones
            klp = k.shape[-1]
            if klp != kv_hidden.shape[1]:
                if kv_mask is None:
                    kv_mask = jnp.ones(kv_hidden.shape[:2], jnp.float32)
                kv_mask = pad_keys(kv_mask, klp)
                if position_bias is not None:
                    position_bias = pad_keys(position_bias, klp)
                if mask is not None:
                    mask = pad_keys(mask, klp)
        else:
            k = dense("k")(kv_hidden)    # [b, k, h, d]
            v = dense("v")(kv_hidden)
            if cross_decode:
                # one transposed write a cache; XLA folds it into the
                # projection's output layout
                if cache_int8:
                    kq, ks = _quant(k)
                    vq, vs = _quant(v)
                    self.variable("cache", "cached_key",
                                  lambda: length_minor(kq))
                    self.variable("cache", "cached_key_scale",
                                  lambda: ks.transpose(0, 2, 3, 1))
                    self.variable("cache", "cached_value",
                                  lambda: length_minor(vq))
                    self.variable("cache", "cached_value_scale",
                                  lambda: vs.transpose(0, 2, 3, 1))
                    # the init pass itself attends with the dequantized
                    # values so its output matches later steps
                    k = _dequant(kq, ks)
                    v = _dequant(vq, vs)
                else:
                    self.variable("cache", "cached_key",
                                  lambda: length_minor(k))
                    self.variable("cache", "cached_value",
                                  lambda: length_minor(v))

        appended = None
        if appending is not None:
            # Cached self-attention: the decoder hands in this layer's
            # slices of its slabs as they were before the step, and takes
            # back the step's rows as they are to be stored (``Decoder``
            # appends them, for all layers at once).  With
            # decode_cache_int8 the rows are int8 with a per-(batch,
            # position, head) scale over the channel dim, quantized as each
            # step's K/V land — the self-attention half of the
            # decode-bandwidth story (cross is quantized whole at cache
            # init above).
            k_slab, v_slab, ks_slab, vs_slab, cur, layer = appending
            bsz, new = k.shape[0], k.shape[1]
            hd = cfg.num_heads * cfg.d_kv
            ks_rows = vs_rows = None

            def rows_first(x):        # [b, new, ...] -> [new, b, ...]
                return jnp.swapaxes(x, 0, 1)

            if cache_int8:
                def _quant_pos(x):
                    xf = x.astype(jnp.float32)
                    amax = jnp.max(jnp.abs(xf), axis=-1, keepdims=True)
                    s = jnp.maximum(amax, 1e-8) / 127.0
                    x8 = jnp.clip(jnp.round(xf / s), -127, 127)
                    return x8.astype(jnp.int8), rows_first(s[..., 0])

                k, ks_rows = _quant_pos(k)
                v, vs_rows = _quant_pos(v)
                # the scales are small: the step reads them as appended to
                dk_scales = (
                    jax.lax.dynamic_update_slice(ks_slab, ks_rows, (cur, 0, 0)),
                    jax.lax.dynamic_update_slice(vs_slab, vs_rows, (cur, 0, 0)),
                )
            appended = (rows_first(k.reshape(bsz, new, hd)),
                        rows_first(v.reshape(bsz, new, hd)), ks_rows, vs_rows)
            k, v = k_slab, v_slab
            cached_step = True

        # cached slabs: cross [b, h, d, Lp], self [L, b, h*d] (stacked: of
        # every layer)
        stacked = appending is not None and layer is not None
        qlen = q.shape[1]
        klen = k.shape[-1 if cross_cached else 1 if not cached_step or stacked
                       else 0]
        # Pallas blockwise path for a DETERMINISTIC pass: eligible when
        # callers passed the structured mask form (causal flag + key-padding
        # row — never a dense (q, k) tensor) and we're not in cached decode
        # (qlen == 1 per-token launches are a perf cliff; XLA's einsum path
        # wins there).  Dispatch among eligible paths is by SHAPE at trace
        # time (config.attention_impl="auto"): einsum below the measured
        # crossover, flash at/above it.  (Live dropout below the training
        # dispatch's own crossover stays dense: ``train_fused`` above.)
        eligible = (
            not decode
            and not cross_cached
            and qlen > 1
            and mask is None
            and not live_dropout
            and fa.mesh_divides(q.shape[0], cfg.num_heads)
        )
        if cfg.attention_impl == "auto":
            use_flash = eligible and (
                max(qlen, klen) >= cfg.flash_min_seq_len
                and fa.auto_dispatch_ok(qlen, klen)
            )
        else:
            use_flash = eligible and cfg.attention_impl == "flash"
        if _site_counts is not None and not cached_step:
            _site_counts["fused" if use_flash or train_fused else "dense"] += 1
        if cached_step:
            # Single-token step over cache slabs.  Structured-mask
            # contract: mask here is batch-shared (decode causal row) or
            # None.
            # The plain step attends over EVERY slab as stored, full-width
            # and int8: self through ``flat_append_decode_attention``, cross
            # through ``length_minor_decode_attention``.  The dense path in
            # the else branch stays as the only path for what is not a
            # plain single-token step (qlen > 1, live dropout, per-row
            # mask): over a self slab it needs a [b, L, h, d] view, whose
            # layout XLA picks from the program around the step (PERF.md,
            # PR 25); a cross slab it contracts as stored.
            fast_ok = (
                qlen == 1
                and (deterministic or cfg.dropout_rate == 0)
                and (mask is None or mask.shape[0] == 1)
            )
            if fast_ok:
                if mask is not None and not (
                    mask.ndim == 4 and mask.shape[2] == 1
                ):
                    # the comb[0, :, 0, :] slice below assumes the decode
                    # mask layout [1, h|1, 1, klen]; any other layout would
                    # be silently mis-sliced (ADVICE r5) — fail loudly
                    raise ValueError(
                        "decode fast path expects a [1, h|1, 1, klen] "
                        f"mask; got shape {mask.shape}"
                    )
                bias_arg = None
                if position_bias is not None or mask is not None:
                    comb = jnp.zeros((1, 1, 1, klen), jnp.float32)
                    if position_bias is not None:
                        comb = comb + position_bias.astype(jnp.float32)
                    if mask is not None:
                        comb = comb + mask.astype(jnp.float32)
                    # batch-shared [1, h|1, 1, klen] -> [h, klen]
                    bias_arg = jnp.broadcast_to(
                        comb[0, :, 0, :], (cfg.num_heads, klen)
                    )
                if cross_cached:
                    ctx = length_minor_decode_attention(
                        q, k, v, bias_arg, kv_mask,
                        dk_scales[0], dk_scales[1], dtype,
                    )
                elif stacked:
                    ctx = prefix_append_decode_attention(
                        q, k, v, layer, appended[0], appended[1], cur,
                        bias_arg, kv_mask, cfg.num_heads, dtype,
                    )
                else:
                    ctx = flat_append_decode_attention(
                        q, k, v, appended[0], appended[1], cur,
                        bias_arg, kv_mask,
                        dk_scales[0], dk_scales[1], cfg.num_heads, dtype,
                    )
            else:
                # fallback path: fall through to the dense einsum below,
                # over the (dequantized) cross slab as stored, over a 4-D
                # view of a self slab
                bsz = q.shape[0]
                hpd = (cfg.num_heads, cfg.d_kv)
                ks_, vs_ = dk_scales
                if cross_cached:
                    if ks_ is not None:             # per-channel
                        k = _dequant(k, ks_)
                        v = _dequant(v, vs_)
                else:
                    # a copy of this layer's slabs with the rows in place,
                    # batch-major again
                    if stacked:
                        k, v = k[layer], v[layer]

                    def view(slab, rows, scale):
                        x = jnp.swapaxes(jax.lax.dynamic_update_slice(
                            slab, rows, (cur, 0, 0)), 0, 1)
                        x = x.reshape(bsz, klen, *hpd)
                        if scale is None:
                            return x
                        # per-position
                        return _dequant(x, jnp.swapaxes(scale, 0, 1)[..., None])

                    k = view(k, appended[0], ks_)
                    v = view(v, appended[1], vs_)
                ctx = None
        else:
            ctx = None
        if ctx is not None:
            pass
        elif train_fused or use_flash:
            # position_bias stays (1, H, q, k) — the kernel's BlockSpec
            # replays the head tile per batch element; no HBM broadcast.
            # Block sizes: None → the kernel's measured-on-TPU auto tiling
            # (512/1024 caps; 128-capped tiles ran the MXU at ~1/8 rate).
            # A training pass hands q, k, v over as the projections wrote
            # them, [b, L, h*d], with its rate and the site's seed; a
            # deterministic one heads first, [b, h, L, d].  Under the
            # trainer's mesh each shard runs the kernels on its own rows and
            # heads.  One scope word for the call, forward and backward: the
            # scores, the softmax and the mask are inside it.
            if train_fused:
                operands = (q, k, v)
                training = dict(
                    num_heads=cfg.num_heads, dropout_rate=cfg.dropout_rate,
                    dropout_seed=_seed_words(self.make_rng("dropout")))
            else:
                operands = [x.transpose(0, 2, 1, 3) for x in (q, k, v)]
                training = {}
            with jax.named_scope("attn_context"):
                ctx = fa.flash_attention_on_mesh(
                    *operands,
                    None if position_bias is None
                    else position_bias.astype(jnp.float32),
                    kv_mask=kv_mask,
                    causal=causal,
                    scale=1.0,  # T5: unscaled scores
                    **training,
                )
            # [b, q, h, d], as ``o`` counts its axes (a training pass: its
            # product folds them again, no copy)
            ctx = (ctx.reshape(*ctx.shape[:2], cfg.num_heads, cfg.d_kv)
                   if train_fused else ctx.transpose(0, 2, 1, 3))
        else:
            if mask is None and (kv_mask is not None or causal):
                # densify the structured mask for the einsum path
                mask = jnp.zeros((1, 1, qlen, klen), jnp.float32)
                if kv_mask is not None:
                    mask = mask + (1.0 - kv_mask[:, None, None, :].astype(jnp.float32)) * NEG_INF
                if causal:
                    c = jnp.tril(jnp.ones((qlen, klen), jnp.float32))
                    mask = mask + ((1.0 - c) * NEG_INF)[None, None]
                mask = mask.astype(dtype)
            # a cached cross slab is [b, h, d, k]; anything else [b, k, h, d]
            kv_dims = "bhdk" if cross_cached else "bkhd"
            with jax.named_scope("attn_scores"):
                scores = jnp.einsum(f"bqhd,{kv_dims}->bhqk", q, k)
                if position_bias is not None:
                    scores = scores + position_bias
                if mask is not None:
                    scores = scores + mask
            with jax.named_scope("attn_softmax"):
                probs = jax.nn.softmax(
                    scores.astype(jnp.float32), axis=-1).astype(dtype)
            if not deterministic and cfg.dropout_rate > 0:
                # where queries and keys are as many (self-attention) the
                # chip's compiler lays the probabilities out query-minor, and
                # would copy bits drawn key-minor: 0.6 ms a layer at
                # [32, 12, 512, 512] (tests/test_chip_compile.py counts none)
                probs = _dropout(probs, cfg.dropout_rate,
                                 self.make_rng("dropout"),
                                 transposed=qlen == klen)
            with jax.named_scope("attn_context"):
                ctx = jnp.einsum(f"bhqk,{kv_dims}->bqhd", probs, v)
        out = nn.DenseGeneral(
            features=cfg.d_model, axis=(-2, -1), use_bias=False, dtype=dtype,
            kernel_init=nn.initializers.normal(stddev=(cfg.num_heads * cfg.d_kv) ** -0.5),
            name="o", dot_general=flat,
        )(ctx)
        return out if appended is None else (out, appended)


class FeedForward(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, x: Array, deterministic: bool = True) -> Array:
        cfg = self.config
        dtype = _dtype(cfg)
        init = nn.initializers.normal(stddev=cfg.d_model**-0.5)
        act = getattr(jax.nn, cfg.act_fn)
        if cfg.is_gated_act:
            wi0 = nn.Dense(cfg.d_ff, use_bias=False, dtype=dtype, kernel_init=init,
                           name="wi_0")(x)
            wi1 = nn.Dense(cfg.d_ff, use_bias=False, dtype=dtype, kernel_init=init,
                           name="wi_1")(x)
            h = act(wi0) * wi1
        else:
            h = act(nn.Dense(cfg.d_ff, use_bias=False, dtype=dtype, kernel_init=init,
                             name="wi")(x))
        if not deterministic and cfg.dropout_rate > 0:
            h = _dropout(h, cfg.dropout_rate, self.make_rng("dropout"))
        return nn.Dense(
            cfg.d_model, use_bias=False, dtype=dtype,
            kernel_init=nn.initializers.normal(stddev=cfg.d_ff**-0.5), name="wo",
        )(h)


class EncoderLayer(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, x, kv_mask, position_bias, deterministic=True):
        cfg = self.config
        h = RMSNorm(cfg.layer_norm_epsilon, _dtype(cfg), name="ln_self")(x)
        x = x + Attention(cfg, name="self_attn")(
            h, h, None, position_bias, kv_mask=kv_mask, deterministic=deterministic
        )
        h = RMSNorm(cfg.layer_norm_epsilon, _dtype(cfg), name="ln_mlp")(x)
        x = x + FeedForward(cfg, name="mlp")(h, deterministic=deterministic)
        return x


class DecoderLayer(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(
        self, x, enc, position_bias, self_mask=None, self_kv_mask=None,
        self_causal=False, cross_kv_mask=None,
        decode=False, appending=None, deterministic=True,
    ):
        """With ``appending`` (see ``Attention``) returns ``(x, rows)``."""
        cfg = self.config
        h = RMSNorm(cfg.layer_norm_epsilon, _dtype(cfg), name="ln_self")(x)
        attn = Attention(cfg, name="self_attn")(
            h, h, self_mask, position_bias, kv_mask=self_kv_mask,
            causal=self_causal, decode=decode, appending=appending,
            deterministic=deterministic,
        )
        rows = None
        if appending is not None:
            attn, rows = attn
        x = x + attn
        h = RMSNorm(cfg.layer_norm_epsilon, _dtype(cfg), name="ln_cross")(x)
        x = x + Attention(cfg, name="cross_attn")(
            h, enc, None, None, kv_mask=cross_kv_mask, cross_decode=decode,
            deterministic=deterministic,
        )
        h = RMSNorm(cfg.layer_norm_epsilon, _dtype(cfg), name="ln_mlp")(x)
        x = x + FeedForward(cfg, name="mlp")(h, deterministic=deterministic)
        return x if appending is None else (x, rows)


class Encoder(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(self, embeds, attention_mask, deterministic=True):
        cfg = self.config
        L = embeds.shape[1]
        positions = jnp.arange(L)
        bias = RelativePositionBias(cfg, bidirectional=True, name="rel_bias")(
            positions, positions
        )
        if _train_fused(cfg, deterministic, embeds.shape[0], L, L):
            # every layer's kernels take the bias: a copy a batch shard of
            # the trainer's mesh, made once for the stack, so its gradient is
            # reduced over the shards once and not once a layer
            bias = fa.bias_per_batch_shard(bias)
        x = embeds
        for i in range(cfg.num_layers):
            x = EncoderLayer(cfg, name=f"layer_{i}")(
                x, attention_mask, bias, deterministic
            )
        return RMSNorm(cfg.layer_norm_epsilon, _dtype(cfg), name="final_ln")(x)


class Decoder(nn.Module):
    config: T5Config

    @nn.compact
    def __call__(
        self, embeds, enc, enc_mask, dec_mask=None,
        decode=False, deterministic=True, ring_born=None,
    ):
        cfg = self.config
        dtype = _dtype(cfg)
        qlen = embeds.shape[1]

        if decode:
            # Single-step (or cache-init) decoding over a pre-allocated cache
            # of klen = cache max_len.  Track the absolute query position.
            pos = self.variable(
                "cache", "decoder_pos", lambda: jnp.array(0, dtype=jnp.int32)
            )
            # The self-attention slabs of ALL layers live here, one array a
            # kind and position-major: ``self_keys`` / ``self_values``
            # [layers, L, b, h*d] (int8 caches: and their per-position
            # scales [layers, L, b, h]).  A layer reads its slice as it was
            # before the step and hands back the step's rows; they are
            # appended below, in one update a kind.  One array, and not one
            # a layer: an array the chip's fast memory can hold is moved
            # there for the step, appended to there and written back to HBM
            # whole, every step (PERF.md, PR 34); these cannot be, so a step
            # reads each slab once and writes one row of it.  Created by
            # init_cache (eval_shape) at klen = the cache's length, which is
            # qlen there; afterwards the slabs carry it.
            # A caller may hand the same slabs in as a tuple of the layers'
            # own arrays (``generate.per_layer_slabs``), and gets them back
            # so.  A program that is one step and no loop takes its cache as
            # parameters, and the compiler copies every slice it reads of a
            # parameter out before use; a layer's own [L, b, h*d] parameter
            # it leaves in HBM, because a position is a whole block of it.
            #
            # Two things a caller whose rows come and go asks for
            # (engine/t5_engine.py), both through what is here already.
            # ``ring_born`` int32 [b]: the slabs are a RING of positions.
            # ``decoder_pos`` wraps at the slabs' length and never resets,
            # and a row sees the keys written since the position it was
            # born at: a key's age is ``(pos - k) mod L``, a row's
            # ``(pos - born) mod L``, and the key is the row's own iff it is
            # no older than the row (a per-row key mask in the causal row's
            # place).  The relative-position bias is a function of a key's
            # age alone, so it stays one row for the batch.  A row may live
            # ``L - 1`` steps.  And slabs of MORE ROWS than ``embeds`` has:
            # the step runs over the first rows of every slab and appends to
            # those (the cross slabs: ``Attention``).
            is_init = not self.has_variable("cache", "self_keys")
            cache_int8 = getattr(cfg, "decode_cache_int8", False)
            nl, bsz = cfg.num_decoder_layers, embeds.shape[0]
            klen = (qlen if is_init else
                    self.get_variable("cache", "self_keys")[0].shape[0])
            slab = (nl, klen, bsz, cfg.num_heads * cfg.d_kv)
            slabs = [
                self.variable("cache", name, jnp.zeros, slab,
                              jnp.int8 if cache_int8 else dtype)
                for name in ("self_keys", "self_values")
            ]
            if cache_int8:
                slabs += [
                    self.variable("cache", name, jnp.zeros,
                                  (nl, klen, bsz, cfg.num_heads), jnp.float32)
                    for name in ("self_key_scales", "self_value_scales")
                ]
            query_positions = pos.value + jnp.arange(qlen)
            key_positions = jnp.arange(klen)
            rel_bias = RelativePositionBias(cfg, bidirectional=False,
                                            name="rel_bias")
            if ring_born is None:
                bias = rel_bias(query_positions, key_positions)
                causal = (
                    key_positions[None, :] <= query_positions[:, None]
                ).astype(jnp.float32)
                self_masks = dict(
                    self_mask=((1.0 - causal[None, None]) * NEG_INF
                               ).astype(dtype))
            else:
                age = (pos.value - key_positions) % klen
                bias = rel_bias(jnp.zeros((1,), jnp.int32), -age)
                self_masks = dict(self_kv_mask=(
                    age[None, :] <= ((pos.value - ring_born) % klen)[:, None]
                ).astype(jnp.float32))
            x = embeds
            rows = []
            kwargs = dict(**self_masks, cross_kv_mask=enc_mask,
                          decode=True, deterministic=deterministic)
            # One causal prefix for every row (no ring), all layers in one
            # array a kind, the step over all its rows: a layer's read may
            # then walk the stacked slabs where they lie, the positions
            # written so far alone (ops/decode_attention.py has the rule
            # for the rest: a TPU, no mesh, bf16 or f32 in whole tiles)
            in_place = (
                not is_init and ring_born is None
                and not isinstance(slabs[0].value, tuple)
                and slabs[0].value.shape[2] == bsz
                and prefix_slabs_read_in_place(slabs[0].value, cfg.num_heads))
            for i in range(nl):
                layer = DecoderLayer(cfg, name=f"layer_{i}")
                if is_init:
                    x = layer(x, enc, bias, **kwargs)
                    continue
                if in_place:
                    own = [s.value for s in slabs]
                else:
                    own = [s.value[i] for s in slabs]
                    if own[0].shape[1] > bsz:        # a step over a prefix
                        with jax.named_scope("self_attn/kv_gather"):
                            own = [o[:, :bsz] for o in own]
                own += [None] * (4 - len(own))       # no scales
                x, new = layer(x, enc, bias, **kwargs, appending=(
                    *own, pos.value, i if in_place else None))
                rows.append(new)
            if not is_init:
                # the cache-init pass (a real apply now, so cross K/V get
                # computed) is not a decoding step — nothing is appended
                # and the position stays 0
                # no layer's module is around the append: the scope says
                # whose rows they are (docs/OBSERVABILITY.md)
                with jax.named_scope("self_attn/kv_append"):
                    for s, new in zip(slabs, zip(*rows)):
                        if isinstance(s.value, tuple):   # one array a layer
                            s.value = tuple(
                                jax.lax.dynamic_update_slice(
                                    own, row, (pos.value, 0, 0))
                                for own, row in zip(s.value, new))
                        else:
                            s.value = jax.lax.dynamic_update_slice(
                                s.value, jnp.stack(new), (0, pos.value, 0, 0))
                pos.value = pos.value + qlen
                if ring_born is not None:
                    pos.value = pos.value % klen
            return RMSNorm(cfg.layer_norm_epsilon, dtype, name="final_ln")(x)

        positions = jnp.arange(qlen)
        bias = RelativePositionBias(cfg, bidirectional=False, name="rel_bias")(
            positions, positions
        )
        if _train_fused(cfg, deterministic, embeds.shape[0], qlen, qlen):
            bias = fa.bias_per_batch_shard(bias)     # as in the encoder
        x = embeds
        for i in range(cfg.num_decoder_layers):
            x = DecoderLayer(cfg, name=f"layer_{i}")(
                x, enc, bias, self_kv_mask=dec_mask, self_causal=True,
                cross_kv_mask=enc_mask,
                decode=False, deterministic=deterministic,
            )
        return RMSNorm(cfg.layer_norm_epsilon, dtype, name="final_ln")(x)


class T5ForConditionalGeneration(nn.Module):
    """Encoder-decoder LM head model (reference: predictor.py:68 loads the
    torch equivalent from a checkpoint)."""

    config: T5Config

    def setup(self):
        cfg = self.config
        self.shared = nn.Embed(
            cfg.vocab_size, cfg.d_model,
            embedding_init=nn.initializers.normal(stddev=1.0),
            dtype=_dtype(cfg), name="shared",
        )
        self.encoder = Encoder(cfg, name="encoder")
        self.decoder = Decoder(cfg, name="decoder")
        if not cfg.tie_word_embeddings:
            self.lm_head = nn.Dense(
                cfg.vocab_size, use_bias=False, dtype=_dtype(cfg),
                kernel_init=nn.initializers.normal(stddev=cfg.d_model**-0.5),
                name="lm_head",
            )

    def encode(self, input_ids, attention_mask, deterministic: bool = True):
        return self.encoder(self.shared(input_ids), attention_mask, deterministic)

    def _head(self, hidden):
        cfg = self.config
        if cfg.tie_word_embeddings:
            # the untied head is a module of this name
            with jax.named_scope("lm_head"):
                hidden = hidden * (cfg.d_model**-0.5)
                return hidden @ self.shared.embedding.T.astype(hidden.dtype)
        return self.lm_head(hidden)

    def init_decode_cache(self, decoder_input_ids, encoder_hidden, encoder_mask):
        """One real decoder pass (no LM head) whose purpose is the
        CROSS-ATTENTION K/V: computed from the encoder output once and
        stored in the cache, turning every subsequent decode step from
        compute-bound (re-projecting the whole encoder sequence) into
        bandwidth-bound (streaming the cached K/V).  Callers pass a qlen-1
        dummy — the self-attention slabs this pass creates are wrong-sized
        throwaways; ``generate.init_cache`` grafts only the ``cross_attn``
        entries onto an eval_shape-zeroed full-size tree."""
        self.decoder(
            self.shared(decoder_input_ids), encoder_hidden, encoder_mask,
            decode=True,
        )

    def decode(
        self, decoder_input_ids, encoder_hidden, encoder_mask,
        decoder_attention_mask=None, decode: bool = False,
        deterministic: bool = True, ring_born=None,
    ):
        hidden = self.decoder(
            self.shared(decoder_input_ids), encoder_hidden, encoder_mask,
            dec_mask=decoder_attention_mask, decode=decode,
            deterministic=deterministic, ring_born=ring_born,
        )
        return self._head(hidden)

    def __call__(
        self, input_ids, attention_mask, decoder_input_ids,
        decoder_attention_mask=None, deterministic: bool = True,
    ):
        enc = self.encode(input_ids, attention_mask, deterministic)
        return self.decode(
            decoder_input_ids, enc, attention_mask,
            decoder_attention_mask=decoder_attention_mask,
            deterministic=deterministic,
        )


# -- training-loss helpers ---------------------------------------------------


def shift_right(labels: Array, decoder_start_token_id: int, pad_token_id: int) -> Array:
    """Teacher-forcing inputs: [start, y_0, ..., y_{n-2}]."""
    shifted = jnp.roll(labels, 1, axis=-1)
    shifted = shifted.at[:, 0].set(decoder_start_token_id)
    return jnp.where(shifted == -100, pad_token_id, shifted)


def cross_entropy_loss(
    logits: Array, labels: Array, pad_token_id: int
) -> tuple[Array, Array]:
    """Mean CE over non-pad label positions. Returns (loss, num_tokens)."""
    mask = (labels != pad_token_id) & (labels != -100)
    safe = jnp.where(mask, labels, 0)
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, safe[..., None], axis=-1)[..., 0]
    ntok = jnp.maximum(mask.sum(), 1)
    return (nll * mask).sum() / ntok, ntok
