"""Single-token decode attention over FLAT K/V cache slabs ``[b, L, h*d]``.

Every cached single-token step in the repo (T5 self and cross, the LM's
offline and paged steps) attends through :func:`flat_decode_attention`:
pure XLA, all heads in one batched MXU matmul per contraction via a
block-diagonal selector, the slab streamed once in its unpadded storage
layout and never viewed as ``[b, L, h, d]`` (whose last two dims TPU pads
to (16, 128), 2.67x the bytes).  On the v5e that is 72.5 % of the HBM
roofline under the while-loop ``predict`` runs, where the dense path over a
4-D view read 37.0 % (ledger, PR 25, ``t5base-batchgen``: ``gen_seq_s``
x 1.80).  PERF.md §6 has the candidates that lost.

Quantisation: int8 slabs carry scales that fold into the math, per channel
(cross, ``[b, 1, h*d]``) into q and the context, per position (self,
``[b, L, h]``) into the scores and probabilities; no dequantised slab is
ever materialised.  Masking: ``bias`` is additive f32 ``[h, L]`` and already
holds causal masking, ``kv_mask`` ``[b, L]`` is per-row key validity.  A
fully-masked row gives the plain mean of V (uniform softmax): finite, never
zero, and to be treated as undefined by a caller that can produce one.
Scores and softmax in f32, matmul operands in the model dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_MASK_FLOOR = -1e20
_NEG_INF_DENSE = -1e9


def decode_attention_reference(q, k, v, *, bias=None, kv_mask=None,
                               k_scale=None, v_scale=None):
    """jnp reference with identical semantics (tests; non-TPU fallbacks)."""
    squeeze = q.ndim == 4
    if squeeze:
        q = q[:, 0]
    kf = k.astype(jnp.float32)
    vf = v.astype(jnp.float32)
    if k_scale is not None:
        kf = kf * k_scale.astype(jnp.float32)
    if v_scale is not None:
        vf = vf * v_scale.astype(jnp.float32)
    s = jnp.einsum("bhd,blhd->bhl", q.astype(jnp.float32), kf)
    if bias is not None:
        if bias.ndim == 4:
            bias = bias[0, :, 0, :]
        s = s + bias.astype(jnp.float32)[None]
    if kv_mask is not None:
        s = s + jnp.where(kv_mask > 0, 0.0, _MASK_FLOOR)[:, None, :]
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhl,blhd->bhd", p, vf).astype(
        q.dtype if q.dtype != jnp.int8 else jnp.float32)
    return out[:, None] if squeeze else out


def gather_pages(pool: jax.Array, block_table: jax.Array) -> jax.Array:
    """Assemble per-slot flat K/V slabs from a paged pool.

    ``pool`` ``[P, page_len, h*d]`` — the engine's physical KV pages (page 0
    is the pinned null page); ``block_table`` ``[S, pages_per_slot]`` int32 —
    each slot's logical pages in position order.  Returns
    ``[S, pages_per_slot * page_len, h*d]``: position ``p`` of slot ``s``
    lives at ``(block_table[s, p // page_len], p % page_len)``, so the
    gathered result is exactly the flat slab :func:`flat_decode_attention`
    consumes — the paged pool changes WHERE pages live, not the layout
    attention streams.  Pages keep the ``[*, page_len, h*d]`` last-two-dims
    contract from the r5 roofline study: with ``page_len`` a multiple of 8
    and h*d a multiple of 128 every page is whole (8, 128) f32 tiles, so
    paging adds zero tile padding over the slab layout it replaces.
    Entries pointing at the null page gather don't-care bytes that the
    caller's validity mask (``position <= cache_index``) hides."""
    s, npg = block_table.shape
    _, page_len, hd = pool.shape
    return pool[block_table].reshape(s, npg * page_len, hd)


def flat_decode_attention(q, kf, vf, bias_hl, kv_mask, k_scale, v_scale,
                           num_heads, dtype):
    """Single-token attention over FLAT cache slabs ``[b, L, h*d]``.
    All heads ride ONE batched MXU matmul per contraction via block-
    diagonal expansion (selector ``E``), so the slab streams from HBM
    exactly once in its unpadded storage layout, under any loop or program
    boundary (the module docstring has the chip's numbers).  int8 scales
    fold into the math (cross per-channel -> q / context; self
    per-position -> scores / probs) — the dequantized slab is never
    materialized.

    q [b, 1, h, d]; bias_hl additive f32 [h, L] (carries causal masking);
    kv_mask [b, L]; k_scale/v_scale None or [b, 1, h*d] (per-channel) or
    [b, L, h] (per-position).  Returns [b, 1, h, d] in model dtype."""
    b, L, hd = kf.shape
    h, d = num_heads, hd // num_heads
    qv = q.reshape(b, hd).astype(jnp.float32)
    k_chan = k_scale is not None and k_scale.shape[1] == 1
    v_chan = v_scale is not None and v_scale.shape[1] == 1
    if k_chan:
        qv = qv * k_scale[:, 0, :]
    sel = jnp.arange(hd)[:, None] // d == jnp.arange(h)[None, :]  # [hd, h]
    qexp = jnp.where(sel[None], qv[:, :, None], 0.0).astype(dtype)
    s = jnp.einsum("blf,bfh->blh", kf.astype(dtype), qexp,
                   preferred_element_type=jnp.float32)
    if k_scale is not None and not k_chan:
        s = s * k_scale
    if bias_hl is not None:
        s = s + bias_hl.T[None]
    if kv_mask is not None:
        s = s + jnp.where(kv_mask > 0, 0.0, _NEG_INF_DENSE)[:, :, None]
    p = jax.nn.softmax(s, axis=1)
    if v_scale is not None and not v_chan:
        p = p * v_scale
    ctx2 = jnp.einsum("blh,blf->bhf", p.astype(dtype), vf.astype(dtype),
                      preferred_element_type=jnp.float32)
    ctx = jnp.sum(jnp.where(sel.T[None], ctx2, 0.0), axis=1)  # [b, hd]
    if v_chan:
        ctx = ctx * v_scale[:, 0, :]
    return ctx.reshape(b, 1, h, d).astype(dtype)
