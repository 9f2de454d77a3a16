"""Single-token decode attention over FLAT K/V cache slabs.

The decode hot loop (reference hot path: predictor.py:102 — W3 batch
generation) is HBM-bandwidth-bound: every emitted token re-reads the whole
K/V cache.  Round 5 profiled the XLA einsum decode at 290 GB/s of the
v5e's 819 GB/s roofline and found the chip was NOT slow — the 4-D
``[b, L, h, d]`` slab layout was: TPU tiles the last two dims (12, 64) up
to (16, 128), a 2.67x physical-byte inflation, and XLA streamed those
padded bytes at ~92% of the roofline.  The fix is layout + formulation,
not a bespoke kernel:

* ``flat_decode_attention`` — what ``decode_attention_impl="auto"`` takes
  for every cache since PR 25 (pure XLA): caches stored flat
  ``[b, L, h*d]`` (768 = six clean (8, 128) tiles, zero padding), all
  heads riding ONE batched MXU matmul per contraction via block-diagonal
  expansion, and no ``[b, L, h, d]`` view of a slab anywhere in the step.
  On the v5e (PERF.md, PR 25; FLAN-T5-base, 256 x 512, bf16) the decode
  iteration is 10.6 ms under ``generate``'s ``early_stop`` while-loop and
  10.0 ms under its fixed-trip scan, 72.5 % and 76.9 % of the HBM
  roofline.  The dense path over a 4-D view of the same flat slab, which
  ``"auto"`` took for full-width caches up to PR 24, is whatever layout XLA
  assigns the view: 8.8 ms (87.6 %) under the scan, which is all r05
  measured, and 20.8 ms (37.0 %; ``t5base-batchgen`` in the ledger, PR 24:
  86.7 seq/s) under the while-loop that ``predict`` runs.
* ``decode_attention`` — the same computation as a fused Pallas kernel
  (online softmax over L-chunks, int8 dequant folded into operands so
  int8 slabs stay int8 into VMEM).  Slower than the flat XLA path: 12.1 ms
  an iteration (63.7 %) in the same while-loop (per-program overhead at
  b=256 x 1-chunk grids) — kept as the measured alternative and as the
  scaffold for shapes XLA fuses badly, selectable via
  ``T5Config.decode_attention_impl="pallas"``.

Quantization contract (both paths): int8 slabs carry scales that FOLD
into the math — per-channel (cross-attn, ``[b, 1, h*d]``) into q before
the score matmul / into the context after; per-position (self-attn,
``[b, L, h]``) into the scores / probabilities.  No dequantized slab is
ever materialized; the HBM traffic for an int8 cache IS the int8 bytes.

Masking contract: ``bias`` is an additive f32 ``[h, L]`` that already
includes any causal/validity masking (the T5 decode path's relative-
position bias + causal row collapse to exactly this); ``kv_mask`` is the
per-batch key-padding mask.  A fully-masked ROW (no valid key at all)
does NOT yield a zero context vector: every score sits at the same mask
floor, the softmax degenerates to UNIFORM, and the output is the plain
mean of V over all (masked) positions — finite, NaN-free, but carrying
no information.  Decode rows always have >=1 valid key (self: position
0; cross: a non-empty prompt), so this is a don't-care guarded against
NaN; callers that could produce an all-masked row must treat its output
as undefined rather than zero (ADVICE r5).

f32 score/softmax math, MXU-dtype (bf16 on chip) operands — the same
precision budget as the dense path it replaces.
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_MASK_FLOOR = -1e20
_NEG_INF_DENSE = -1e9


def _kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, ks_ref, vs_ref,
            out_ref, m_ref, l_ref, acc_ref, *, h, d, k_kind, v_kind,
            compute_dtype):
    ci = pl.program_id(1)
    nc = pl.num_programs(1)
    hd = h * d

    @pl.when(ci == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _MASK_FLOOR)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    qv = q_ref[0].astype(jnp.float32)            # [1, hd]
    if k_kind == "chan":
        qv = qv * ks_ref[0]                      # fold per-channel K scale
    # Qexp[r, c] = qv[r] iff head_of(r) == c: one [C,hd]x[hd,h] MXU matmul
    # computes every head's q.k row instead of h tiny matvecs.
    rows = jax.lax.broadcasted_iota(jnp.int32, (hd, h), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (hd, h), 1)
    head_sel = rows // d == cols                 # [hd, h] block diagonal
    # transposed selector built from its own iotas: Mosaic cannot
    # transpose an i1 vector (failed-to-legalize tpu.transpose)
    sel_t = (jax.lax.broadcasted_iota(jnp.int32, (h, hd), 1) // d
             == jax.lax.broadcasted_iota(jnp.int32, (h, hd), 0))
    qexp = jnp.where(head_sel, qv.reshape(hd, 1), 0.0).astype(compute_dtype)

    k = k_ref[0].astype(compute_dtype)           # [C, hd]
    s = jax.lax.dot_general(                     # [C, h] f32
        k, qexp, (((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    if k_kind == "pos":
        s = s * ks_ref[0]                        # [C, h] per-position scale
    if bias_ref is not None:
        s = s + bias_ref[...]                    # [C, h] additive (f32)
    if mask_ref is not None:
        s = s + mask_ref[0]                      # [C, 1] additive (f32)

    m_prev = m_ref[...]                          # [1, h]
    m_new = jnp.maximum(jnp.max(s, axis=0, keepdims=True), m_prev)
    m_new = jnp.maximum(m_new, _MASK_FLOOR)      # fully-masked chunk guard
    alpha = jnp.exp(m_prev - m_new)              # [1, h]
    p = jnp.exp(s - m_new)                       # [C, h]
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=0, keepdims=True)
    m_ref[...] = m_new

    if v_kind == "pos":
        p = p * vs_ref[0]                        # fold per-position V scale
    v = v_ref[0].astype(compute_dtype)           # [C, hd]
    ctx_h = jax.lax.dot_general(                 # [h, hd] f32
        p.astype(compute_dtype), v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32,
    )
    # the block diagonal of ctx_h is the per-head context; sel_t masks it
    # out and the h-row reduce flattens to [1, hd]
    contrib = jnp.sum(jnp.where(sel_t, ctx_h, 0.0), axis=0,
                      keepdims=True)
    # alpha/l are per-head; expand to per-column through the same selector
    alpha_exp = jnp.sum(jnp.where(sel_t, alpha.reshape(h, 1), 0.0),
                        axis=0, keepdims=True)   # [1, hd]
    acc_ref[...] = acc_ref[...] * alpha_exp + contrib

    @pl.when(ci == nc - 1)
    def _finish():
        l_exp = jnp.sum(
            jnp.where(sel_t, l_ref[...].reshape(h, 1), 0.0),
            axis=0, keepdims=True,
        )
        out = acc_ref[...] / jnp.maximum(l_exp, 1e-20)
        if v_kind == "chan":
            out = out * vs_ref[0]                # fold per-channel V scale
        out_ref[0] = out.astype(out_ref.dtype)


def _pick_block(L: int) -> int:
    if L <= 512:
        return L
    for c in (512, 256, 128):
        if L % c == 0:
            return c
    if L <= 2048:
        return L
    raise ValueError(f"decode_attention: unsupported cache length {L}")


def decode_attention(
    q: jax.Array,                   # [b, 1, h, d] (or [b, h, d])
    k: jax.Array,                   # [b, L, h, d] or flat [b, L, h*d]
    v: jax.Array,                   # same; bf16/f32 or int8
    *,
    bias: Optional[jax.Array] = None,     # [h, L] or [1, h, 1, L] additive
    kv_mask: Optional[jax.Array] = None,  # [b, L] 1=attend
    k_scale: Optional[jax.Array] = None,  # [b, L, h, 1] or [b, 1, h, d] f32
    v_scale: Optional[jax.Array] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> jax.Array:
    """Single-query-token attention over a cached K/V slab.  Returns the
    context in q's layout ``[b, 1, h, d]`` (model dtype).  See module
    docstring for the masking and quantization contracts."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    squeeze = q.ndim == 4
    if squeeze:
        if q.shape[1] != 1:
            raise ValueError(f"decode_attention wants qlen==1, got {q.shape}")
        q = q[:, 0]
    b, h, d = q.shape
    L = k.shape[1]
    hd = h * d
    C = block_k or _pick_block(L)
    if L % C != 0:
        raise ValueError(f"block_k {C} must divide cache length {L}")
    out_dtype = q.dtype if q.dtype != jnp.int8 else jnp.float32
    compute_dtype = q.dtype

    def _scale_kind(s, name):
        if s is None:
            return None
        if s.shape in ((b, L, h, 1), (b, L, h)):
            return "pos"
        if s.shape in ((b, 1, h, d), (b, 1, hd)):
            return "chan"
        raise ValueError(f"{name} shape {s.shape} is neither per-position "
                         f"[b,L,h,1] nor per-channel [b,1,h,d] (or their "
                         f"flat forms)")

    k_kind = _scale_kind(k_scale, "k_scale")
    v_kind = _scale_kind(v_scale, "v_scale")

    grid = (b, L // C)
    # the Mosaic block rule constrains the last TWO dims of every block:
    # per-batch vectors ride as [b, 1, hd] so their (1, hd) tail equals
    # the array dims exactly
    qf = q.reshape(b, 1, hd)
    kf = k.reshape(b, L, hd)
    vf = v.reshape(b, L, hd)

    in_specs = [
        pl.BlockSpec((1, 1, hd), lambda bi, ci: (bi, 0, 0)),
        pl.BlockSpec((1, C, hd), lambda bi, ci: (bi, ci, 0)),
        pl.BlockSpec((1, C, hd), lambda bi, ci: (bi, ci, 0)),
    ]
    args = [qf, kf, vf]

    if bias is not None:
        if bias.ndim == 4:                       # [1, h, 1, L]
            bias = bias[0, :, 0, :]
        bias_t = bias.astype(jnp.float32).T      # [L, h]
        in_specs.append(pl.BlockSpec((C, h), lambda bi, ci: (ci, 0)))
        args.append(bias_t)
    else:
        in_specs.append(None)
        args.append(None)

    if kv_mask is not None:
        madd = jnp.where(kv_mask.astype(jnp.float32) > 0, 0.0, _MASK_FLOOR)
        in_specs.append(pl.BlockSpec((1, C, 1), lambda bi, ci: (bi, ci, 0)))
        args.append(madd.reshape(b, L, 1))
    else:
        in_specs.append(None)
        args.append(None)

    for s, kind in ((k_scale, k_kind), (v_scale, v_kind)):
        if kind == "pos":
            in_specs.append(pl.BlockSpec((1, C, h), lambda bi, ci: (bi, ci, 0)))
            args.append(s.astype(jnp.float32).reshape(b, L, h))
        elif kind == "chan":
            in_specs.append(pl.BlockSpec((1, 1, hd), lambda bi, ci: (bi, 0, 0)))
            args.append(s.astype(jnp.float32).reshape(b, 1, hd))
        else:
            in_specs.append(None)
            args.append(None)

    live_specs = [sp for sp in in_specs if sp is not None]
    live_args = [a for a in args if a is not None]

    def wrapped(*refs):
        it = iter(refs[: len(live_specs)])
        full = [next(it) if sp is not None else None for sp in in_specs]
        out_ref = refs[len(live_specs)]
        scratch = refs[len(live_specs) + 1:]
        _kernel(*full, out_ref, *scratch, h=h, d=d, k_kind=k_kind,
                v_kind=v_kind, compute_dtype=compute_dtype)

    out = pl.pallas_call(
        wrapped,
        grid=grid,
        in_specs=live_specs,
        out_specs=pl.BlockSpec((1, 1, hd), lambda bi, ci: (bi, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((b, 1, hd), out_dtype),
        scratch_shapes=[
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, h), jnp.float32),
            pltpu.VMEM((1, hd), jnp.float32),
        ],
        interpret=interpret,
    )(*live_args)
    out = out.reshape(b, h, d)
    return out[:, None] if squeeze else out


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
