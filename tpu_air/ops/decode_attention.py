"""Single-token decode attention over cached K/V slabs.  Which read a slab
gets follows from how the slab is WRITTEN, and from nothing else:

=====================  ==========================  ================================
slab                   stored                      read by
=====================  ==========================  ================================
T5 cross-attention:    LENGTH-MINOR                :func:`length_minor_decode_attention`
written once at cache  ``[b, h, d, Lp]``, scales
init, only read after  ``[b, h, d, 1]``
T5 self-attention:     FLAT, position-major        :func:`flat_append_decode_attention`
grows a position a     ``[L, b, h*d]``, scales
step                   ``[L, b, h]``
the LM's K/V: pages    FLAT ``[b, L, h*d]``,       :func:`flat_decode_attention`
gathered for the step  scales ``[b, L, h]`` or
                       ``[b, 1, h*d]``
the LM's latent:       ONE slab ``[b, L, w]``       :func:`latent_decode_attention`
pages gathered for     (latent ``r``, roped key
the step               ``dr``, zeros), no scales
=====================  ==========================  ================================

FLAT: all heads ride one batched MXU matmul per contraction via a
block-diagonal selector, the slab streamed in its unpadded storage layout and
never viewed as ``[b, L, h, d]`` (whose last two dims TPU pads to (16, 128),
2.67x the bytes; PERF.md, PR 25).  A slab the step appends to is read AS IT
WAS BEFORE the step, the step's own row apart
(:func:`flat_append_decode_attention`): while the read depends on the append,
the v5e's compiler keeps the slab in fast memory through the step and writes
it back to HBM whole, every step (PERF.md, PR 34: 23 of FLAN-T5-base's 24
slabs under ``generate``'s ``while``, a sixth of the step's traffic and none
of it counted by the roofline).  Whoever holds the slabs appends the rows
apart from the read (``models/t5/modeling.py``, ``Decoder``).

LENGTH-MINOR: ``(d, Lp)`` are whole tiles as stored, so each head's slab is
contracted directly, with no selector and none of its ``num_heads`` x
multiply-accumulates (bare, 739 GB/s on the v5e where the flat read gives 685:
ISSUE 34's record of PR 33's chip runs).  Only a slab that is never appended
to can be stored so: a new position would be one lane of every tile.
:func:`length_minor` makes it, padding ``L`` up to whole lanes; the caller
masks the padding (:func:`pad_keys`).

Quantisation: int8 slabs carry scales that fold into the math, per channel
(cross) into q and the context, per position (self, the LM's) into the scores
and probabilities; no dequantised slab is ever materialised.  Masking:
``bias`` is additive f32 ``[h, L]`` and already holds causal masking,
``kv_mask`` ``[b, L]`` is per-row key validity.  A fully-masked row gives the
plain mean of V (uniform softmax): finite, never zero, and to be treated as
undefined by a caller that can produce one.  Scores and softmax in f32,
matmul operands in the model dtype.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

_MASK_FLOOR = -1e20
_NEG_INF_DENSE = -1e9
_LANES = 128    # the minor dimension of a TPU tile


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


@jax.named_scope("kv_gather")
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


@jax.named_scope("decode_attention")
def flat_decode_attention(q, kf, vf, bias_hl, kv_mask, k_scale, v_scale,
                           num_heads, dtype, num_kv_heads=None):
    """Single-token attention over FLAT cache slabs ``[b, L, g*d]``, ``g =
    num_kv_heads`` K/V heads (default ``num_heads``) serving ``h / g`` query
    heads each.  All heads ride ONE batched MXU matmul per contraction via
    a selector that lets a query head see the features of ITS K/V head
    (block-diagonal at ``g == h``; dense with one K/V head: every query
    head reads the whole row and none of the products is wasted on zeros),
    so the slab streams from HBM exactly once in its unpadded storage
    layout, under any loop or program boundary (the module docstring has
    the chip's numbers).  int8 scales fold into the math (per-channel -> q /
    context; per-position -> scores / probs) — the dequantized slab is
    never materialized.

    q [b, 1, h, d]; bias_hl additive f32 [h, L] (carries causal masking);
    kv_mask [b, L]; k_scale/v_scale None or [b, 1, g*d] (per-channel) or
    [b, L, g] (per-position).  Returns [b, 1, h, d] in model dtype."""
    b, L, gd = kf.shape
    h = num_heads
    g = num_kv_heads or h
    d, r = gd // g, h // g
    k_chan = k_scale is not None and k_scale.shape[1] == 1
    v_chan = v_scale is not None and v_scale.shape[1] == 1
    # sel[f, hq]: feature f of the slab belongs to query head hq's K/V head
    sel = jnp.arange(gd)[:, None] // d == jnp.arange(h)[None, :] // r
    # qexp[b, f, hq] = q[b, hq, f % d] where sel: a K/V head's features, in
    # the slab's order, against the r query heads it serves
    qt = jnp.swapaxes(q.reshape(b, g, r, d).astype(jnp.float32), 2, 3)
    qt = qt.reshape(b, gd, 1, r)
    if k_chan:
        qt = qt * k_scale[:, 0, :, None, None]
    qexp = jnp.where(
        sel[None], jnp.broadcast_to(qt, (b, gd, g, r)).reshape(b, gd, h),
        0.0).astype(dtype)
    s = jnp.einsum("blf,bfh->blh", kf.astype(dtype), qexp,
                   preferred_element_type=jnp.float32)
    if k_scale is not None and not k_chan:
        s = s * jnp.repeat(k_scale, r, axis=2)
    if bias_hl is not None:
        s = s + bias_hl.T[None]
    if kv_mask is not None:
        s = s + jnp.where(kv_mask > 0, 0.0, _NEG_INF_DENSE)[:, :, None]
    p = jax.nn.softmax(s, axis=1)
    if v_scale is not None and not v_chan:
        p = p * jnp.repeat(v_scale, r, axis=2)
    ctx2 = jnp.einsum("blh,blf->bhf", p.astype(dtype), vf.astype(dtype),
                      preferred_element_type=jnp.float32)      # [b, h, g*d]
    # a query head keeps its own K/V head's features: summed over the K/V
    # heads' axis (the major one), what is left is [b, r, g*d]
    ctx = jnp.where(sel.T[None], ctx2, 0.0).reshape(b, g, r, gd).sum(1)
    if v_chan:
        ctx = ctx * v_scale[:, 0, None, :]
    ctx = jnp.swapaxes(ctx.reshape(b, r, g, d), 1, 2)          # [b, g, r, d]
    return ctx.reshape(b, 1, h, d).astype(dtype)


@jax.named_scope("decode_attention")
def latent_decode_attention(q, latent, kv_mask, rank, dtype):
    """Single-token ABSORBED attention over a latent slab ``[b, L, w]``
    (``r = rank`` numbers of joint K/V latent, then the ``dr`` of the one
    roped key every head shares, then zeros up to whole lanes; ``q`` is as
    wide): every head's query, already folded into latent space
    (``q [b, h, r + dr]``: ``q_nope W_UK`` then the roped part, the softmax
    scale folded in), scores the SAME row, so the ``h`` heads are the rows of
    one matmul a sequence and the slab streams once for all of them; the
    context is taken in latent space (``[b, h, r]``: the caller applies
    ``W_UV``).  The second contraction runs over the slab's whole width and
    the roped key's ``dr`` columns of the result are dropped: slicing them
    off the slab first would copy it.  ``kv_mask [b, L]`` is per-row key
    validity; scores and softmax in f32, operands in the model dtype."""
    s = jnp.einsum("bhc,blc->bhl", q.astype(dtype), latent.astype(dtype),
                   preferred_element_type=jnp.float32)
    s = s + jnp.where(kv_mask > 0, 0.0, _NEG_INF_DENSE)[:, None, :]
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhl,blc->bhc", p.astype(dtype), latent.astype(dtype),
                     preferred_element_type=jnp.float32)
    return ctx[..., :rank].astype(dtype)


@jax.named_scope("decode_attention")
def flat_append_decode_attention(q, kf, vf, k_row, v_row, cur, bias_hl,
                                 kv_mask, k_scale, v_scale, num_heads, dtype):
    """Single-token attention over POSITION-MAJOR flat slabs ``[L, b, h*d]``
    that the step is about to append to: ``kf``/``vf`` as they were BEFORE
    the step, and the step's own row ``k_row``/``v_row`` ``[1, b, h*d]``
    (what position ``cur`` will hold; same dtype as the slab) apart from
    them.  The formulation is :func:`flat_decode_attention`'s (block-diagonal
    selector, one MXU matmul a contraction, no per-head view) and so is the
    result over the appended slab; the difference is what the program around
    it has to keep.  Reading the appended slab makes the append a producer of
    the read: the v5e's compiler then holds the whole slab in fast memory
    through the step and writes all of it back to HBM for the next one
    (PERF.md, PR 34).  Read this way the slab is only read, and the append
    is one ``[1, b, h*d]`` block written in place, by whoever holds the
    slab: whole tiles where the program keeps this order (the engine's step
    does; a loop's carry the compiler lays out as it likes), where a row of
    a ``[b, L, h*d]`` slab is one sublane of ``b`` tiles.

    The row's scores are written into the small ``[b, L, h]`` score array at
    ``cur`` and its probability is taken out again for the row's own value,
    so position ``cur`` of ``kf``/``vf`` is never used.  bias_hl additive
    f32 [h, L] (carries causal masking); kv_mask [b, L]; ``k_scale``/
    ``v_scale`` None or per-position ``[L, b, h]`` AFTER the step (they are
    small).  q and the result [b, 1, h, d]."""
    L, b, hd = kf.shape
    h, d = num_heads, hd // num_heads
    qv = q.reshape(b, hd).astype(jnp.float32)
    sel = jnp.arange(hd)[:, None] // d == jnp.arange(h)[None, :]  # [hd, h]
    qexp = jnp.where(sel[None], qv[:, :, None], 0.0).astype(dtype)

    # the transposes are the contractions' dimension numbers, not copies
    # (XLA folds them into the matmuls; its CPU backend has no bf16 product
    # with the batch dimension second)
    def scores(slab):                                     # -> [b, L, h]
        return jnp.einsum("blf,bfh->blh", jnp.swapaxes(slab, 0, 1).astype(dtype),
                          qexp, preferred_element_type=jnp.float32)

    def context(p, slab):                                 # -> [b, hd]
        ctx2 = jnp.einsum("blh,blf->bhf", p.astype(dtype),
                          jnp.swapaxes(slab, 0, 1).astype(dtype),
                          preferred_element_type=jnp.float32)
        return jnp.sum(jnp.where(sel.T[None], ctx2, 0.0), axis=1)

    s = jax.lax.dynamic_update_slice(scores(kf), scores(k_row), (0, cur, 0))
    if k_scale is not None:
        s = s * jnp.swapaxes(k_scale, 0, 1)
    if bias_hl is not None:
        s = s + bias_hl.T[None]
    if kv_mask is not None:
        s = s + jnp.where(kv_mask > 0, 0.0, _NEG_INF_DENSE)[:, :, None]
    p = jax.nn.softmax(s, axis=1)
    if v_scale is not None:
        p = p * jnp.swapaxes(v_scale, 0, 1)
    p_row = jax.lax.dynamic_slice_in_dim(p, cur, 1, axis=1)
    p = jax.lax.dynamic_update_slice(p, jnp.zeros_like(p_row), (0, cur, 0))
    ctx = context(p, vf) + context(p_row, v_row)
    return ctx.reshape(b, 1, h, d).astype(dtype)


def length_minor(x: jax.Array) -> jax.Array:
    """``[b, L, h, d]`` -> the stored slab ``[b, h, d, Lp]``, ``Lp`` the next
    multiple of 128 lanes over ``L``, zeros past ``L`` (the tile padding,
    made visible)."""
    L = x.shape[1]
    return pad_keys(jnp.transpose(x, (0, 2, 3, 1)), L + -L % _LANES)


def pad_keys(x: jax.Array, length: int) -> jax.Array:
    """Zero-pad the last (key) dimension up to ``length``: a key mask so
    padded hides the positions :func:`length_minor` added, an additive bias
    so padded leaves them to the mask."""
    return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, length - x.shape[-1])])


@jax.named_scope("decode_attention")
def length_minor_decode_attention(q, k, v, bias_hl, kv_mask, k_scale, v_scale,
                                  dtype):
    """Single-token attention over LENGTH-MINOR cache slabs ``[b, h, d, L]``:
    the plain per-head contraction, scores ``bhd,bhdl->bhl`` and context
    ``bhl,bhdl->bhd``, each slab streamed once as stored.  int8 scales are
    per channel and fold into q and the context.

    q [b, 1, h, d]; bias_hl additive f32 [h, L]; kv_mask [b, L];
    k_scale/v_scale None or [b, h, d, 1].  Returns [b, 1, h, d] in model
    dtype."""
    b, h, d, _ = k.shape
    qv = q.reshape(b, h, d)
    if k_scale is not None:
        qv = qv.astype(jnp.float32) * k_scale[..., 0]
    s = jnp.einsum("bhd,bhdl->bhl", qv.astype(dtype), k.astype(dtype),
                   preferred_element_type=jnp.float32)
    if bias_hl is not None:
        s = s + bias_hl[None]
    if kv_mask is not None:
        s = s + jnp.where(kv_mask > 0, 0.0, _NEG_INF_DENSE)[:, None, :]
    p = jax.nn.softmax(s, axis=-1)
    ctx = jnp.einsum("bhl,bhdl->bhd", p.astype(dtype), v.astype(dtype),
                     preferred_element_type=jnp.float32)
    if v_scale is not None:
        ctx = ctx * v_scale[..., 0]
    return ctx.reshape(b, 1, h, d).astype(dtype)
