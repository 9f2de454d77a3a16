"""Flash attention — Pallas TPU kernel with blockwise online softmax.

The hot op of the model layer (SURVEY.md §2B ATen row → "Pallas for anything
custom").  Blockwise streaming over K/V keeps the (Lq, Lk) score matrix out
of HBM: VMEM holds one (BQ, BK) tile at a time and the MXU sees back-to-back
(BQ,D)x(D,BK) and (BQ,BK)x(BK,D) matmuls; running max/sum statistics ride in
VMEM scratch across the sequentially-iterated k grid dimension (TPU grid
order is row-major, so the innermost k axis revisits the same q tile's
scratch).

Broadcast-aware operands — the reason a stock kernel doesn't fit T5:
* ``bias``: additive scores of shape (1|H|B·H, Lq, Lk).  T5's relative-
  position bias is per-head but batch-shared (H, Lq, Lk); the BlockSpec
  index map replays the same head tile for every batch element instead of
  materializing a (B·H, Lq, Lk) array in HBM.
* ``kv_mask``: per-batch key-padding mask (B, Lk), 1 = attend.  Expanded to
  a (1, BK) additive tile inside VMEM, never an (Lq, Lk) matrix.
* ``causal``: masking from block-local iota, zero HBM.

f32 accumulation regardless of input dtype.  BACKWARD is blockwise Pallas
too (``_pallas_bwd``: one kernel where a (q, k) pair is one tile, else a dq
pass and a dk/dv pass, over saved (out, lse)) — O(L) memory end to end, which
is what makes long-context TRAINING feasible, not just the forward.  An
additive ``bias`` (T5's learned relative-position bias) has its gradient
from the same kernels: ``dbias`` is dense (H, Lq, Lk) whatever makes it, but
the (B·H, Lq, Lk) scores and probabilities behind it never leave the chip.
Both the attention output and the logsumexp are differentiable — the lse
cotangent folds into the backward's delta term — so ring attention
(ring_attention.py) trains through merged stats on the kernel path.

Dropout on the probabilities (a T5 training pass) happens inside the
kernels: a tile's keep mask is drawn from the chip's generator, seeded with
the call's seed and the tile's number, by the forward and again by the
backward; no mask word is stored (``_keep_tile``).

Operands come head-major (B·H, L, D) or token-major (B, L, H·D), as a
projection writes them; the second is read in place, a head its lanes
(``_head``).

Fully-masked rows (a query whose ``kv_mask`` hides EVERY key): the forward
emits mean(V) — matching the dense reference, whose softmax over an all
-masked row degenerates to uniform weights — but the custom VJP defines the
gradient of such a row as exactly ZERO dq/dk/dv, where autodiff of the
computed function would give a nonzero uniform dv.  This is deliberate:
a fully-masked row is padding, and padding must not train.  SP/ring users
who pad whole rows get zero gradients for them by contract (see
``_bwd_p``).
"""

from __future__ import annotations

import contextlib
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_NEG_INF = -1e30


# --------------------------------------------------------------------------
# grids, operands a call may lack, the dropout draw
# --------------------------------------------------------------------------
#
# Two grids, both counted in BLOCKS of ``heads`` consecutive row-and-heads
# (``_layout``; 1 where a tile is work enough for a step).  Without a
# bias a step is one block, ``(blocks, x, y)``, ``y`` the tile axis a kernel
# accumulates over.  With a bias whose leading dim is shared by several rows
# (T5: per head, every batch row; ``group`` blocks of it) the rows become the
# third axis, ``(group, x, rows, y)``, block ``r * group + g``: where a
# (q, k) pair is one tile the bias block's index does not change from one row
# to the next, so it is fetched once a head and not once a row-and-head, and
# a ``dbias`` block of the backward stays resident while the rows add to it.
# Index maps and kernels are written over ``(block, x, y)`` and these three
# adapt them.


def _grid(group, blocks, nx, ny):
    if group is None:
        return (blocks, nx, ny)
    return (group, nx, blocks // group, ny)


def _at(group, index_map, swap=False):
    """``index_map(block, i, j)`` as an index map of ``_grid(group, ...)``
    run over ``(block, i, j)`` or, with ``swap``, ``(block, j, i)`` (the
    dk/dv pass)."""
    if swap:
        inner = index_map
        index_map = lambda b, j, i: inner(b, i, j)  # noqa: E731
    if group is None:
        return index_map
    return lambda g, x, r, y: index_map(r * group + g, x, y)


def _grid_ids(group):
    """``(block, x, y, ny, r)`` of this step: the block of row-and-heads, the
    two tile indices in grid order, how many steps the inner one has, and
    which of the rows that share a bias block this is (0 where none do)."""
    if group is None:
        return (pl.program_id(0), pl.program_id(1), pl.program_id(2),
                pl.num_programs(2), 0)
    r = pl.program_id(2)
    return (r * group + pl.program_id(0), pl.program_id(1), pl.program_id(3),
            pl.num_programs(3), r)


def _tile_number(b, i, j, tiles):
    nq, nk = tiles
    return (b * nq + i) * nk + j


def _fill_absent(kernel, present, **kw):
    """``kernel`` with ``None`` handed in for each ref a call does not have
    (``present``: one flag a positional ref, in the kernel's order)."""
    def call(*refs):
        it = iter(refs)
        return kernel(*[next(it) if p else None for p in present], **kw)
    return call


def keep_threshold(rate: float) -> int:
    """A probability is kept where its 16-bit draw is under this (rate 0.1:
    58,982 of 65,536) — ``models/t5/modeling._dropout``'s rule (PERF.md,
    PR 37: 16 bits state a tenth, 8 cannot)."""
    return min(int(round((1.0 - rate) * 2**16)), 2**16 - 1)


def keep_from_seed(seed, shape, rate: float):
    """The keep mask ``(bh, lq, lk)`` of ``seed`` (two 32-bit words) as int8,
    drawn by ``jax.random``: what a call is handed where the kernels cannot
    draw for themselves (interpret mode has no generator).  Not the chip's
    bits — only the same law, and the same mask for the same seed."""
    key = jax.random.wrap_key_data(
        jnp.asarray(seed).astype(jnp.uint32).reshape(2), impl="threefry2x32")
    bits = jax.random.bits(key, shape, jnp.uint16)
    return (bits < keep_threshold(rate)).astype(jnp.int8)


def _keep_tile(seed_ref, keep, tile, shape, rate):
    """True where a tile keeps its probability; ``tile`` its number among the
    call's (``_tile_number``).  Drawn here, from the chip's generator seeded
    with the call's two words and the tile's number, so the backward meets
    the forward's mask without a bit of it stored; or ``keep``, the tile the
    caller supplied."""
    if keep is not None:
        return keep != 0
    # the generator takes two words: the tile's number goes into the first,
    # spread by an odd constant (distinct tiles, distinct seeds)
    pltpu.prng_seed(seed_ref[0] + tile * jnp.int32(-1640531535), seed_ref[1])
    bits = pltpu.prng_random_bits(shape)             # int32: all 2**32 words
    # the upper 16 bits against the threshold, as one signed comparison
    return bits < (keep_threshold(rate) << 16) - 2**31


# --------------------------------------------------------------------------
# forward kernel
# --------------------------------------------------------------------------


def _scores(q, k, bias, mask, i, j, scale, causal, block_q, block_k):
    """The (BQ, BK) f32 score tile, masked: the forward's and the backward's
    single source of truth.  Matmul operands stay in the INPUT dtype (bf16 on
    chip runs the MXU at ~4x its f32 rate); accumulation and every softmax
    statistic are f32, the dense einsum path's precision budget."""
    s = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)
    if scale != 1.0:                                  # T5 does not scale
        s = s * scale
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if mask is not None:
        # (1, BK) additive key-padding row, broadcast over queries
        s = s + mask.astype(jnp.float32)
    if causal:
        qi = i * block_q + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        kj = j * block_k + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(qi >= kj, s, _NEG_INF)
    return s


def _causal_live(i, j, block_q, block_k):
    """False iff the (i, j) tile is ENTIRELY above the causal diagonal
    (max query index < min key index) — its p is identically zero, so the
    matmuls and the exp can be skipped (~2x at large L)."""
    return (i + 1) * block_q - 1 >= j * block_k


def _tile_of(ref, h=0):
    return None if ref is None else ref[h]


# q, k, v and what is shaped like them come in one of two layouts.  Head-major
# (B·H, L, D): a step's block is (heads, L-block, D).  Token-major
# (B, L, H·D), the projections' own: a block is one batch row's (1, L-block,
# heads·D), head ``h`` its lanes [h·D, (h+1)·D) — no transposed copy of an
# operand or a result exists, and at D = 64 no row is padded out to 128 lanes
# in HBM.  ``lanes`` is D for the second, None for the first.


def _head(ref, h, lanes):
    if lanes is None:
        return ref[h]
    return ref[0, :, h * lanes:(h + 1) * lanes]


def _put_head(ref, h, lanes, x):
    if lanes is None:
        ref[h] = x
    else:
        ref[0, :, h * lanes:(h + 1) * lanes] = x


def _rows_spec(at, heads, d, per_row, block, of_k=False):
    """BlockSpec of a q-like (``of_k``: k-like) array over ``(block, i, j)``;
    ``per_row``: blocks a batch row of a token-major array, else None."""
    if per_row is None:
        return pl.BlockSpec(
            (heads, block, d), at(lambda b, i, j: (b, j if of_k else i, 0)))
    return pl.BlockSpec(
        (1, block, heads * d),
        at(lambda b, i, j: (b // per_row, j if of_k else i, b % per_row)))


def _fwd_kernel(q_ref, k_ref, v_ref, bias_ref, mask_ref, seed_ref, keep_ref,
                out_ref, lse_ref, acc_ref, m_ref, l_ref, *, scale, causal,
                block_q, block_k, group, heads, rate, tiles, lanes):
    blk, i, j, nk, _ = _grid_ids(group)

    @pl.when(j == 0)
    def _init():
        acc_ref[:] = jnp.zeros_like(acc_ref)
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)

    # Skip tiles entirely above the causal diagonal: p is identically zero
    # there, so both matmuls and the softmax update are dead work (~2x at
    # large L).
    live = _causal_live(i, j, block_q, block_k) if causal else True

    @pl.when(live)
    def _body():
        for h in range(heads):
            s = _scores(_head(q_ref, h, lanes), _head(k_ref, h, lanes),
                        _tile_of(bias_ref, h), _tile_of(mask_ref), i, j,
                        scale, causal, block_q, block_k)
            m_prev = m_ref[h, :, :1]  # (BQ, 1)
            l_prev = l_ref[h, :, :1]
            m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
            p = jnp.exp(s - m_new)  # (BQ, BK)
            alpha = jnp.exp(m_prev - m_new)
            # the running sum is of the UNDROPPED p (dropout acts on
            # normalised probabilities); the accumulator takes the dropped
            # one, and the kept are scaled by 1/(1-rate) once, at the end
            l_new = l_prev * alpha + jnp.sum(p, axis=-1, keepdims=True)
            if rate:
                keep = _keep_tile(
                    seed_ref, _tile_of(keep_ref, h),
                    _tile_number(blk * heads + h, i, j, tiles), p.shape, rate)
                p = jnp.where(keep, p, 0.0)
            acc_ref[h] = acc_ref[h] * alpha + jax.lax.dot_general(
                p.astype(v_ref.dtype), _head(v_ref, h, lanes),
                (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32,
            )
            m_ref[h] = jnp.broadcast_to(m_new, m_ref.shape[1:])
            l_ref[h] = jnp.broadcast_to(l_new, l_ref.shape[1:])

    @pl.when(j == nk - 1)
    def _finalize():
        for h in range(heads):
            # NB: masking uses finite -1e30, so a fully-masked row has
            # p=exp(0)=1 per entry and l == klen, never 0 — such rows yield
            # mean(V), matching the dense softmax reference path.  The guard
            # below only protects against division by zero for degenerate
            # zero-length tiles.
            l = l_ref[h]                                    # (BQ, 128)
            safe_l = jnp.where(l == 0.0, 1.0, l)
            denom = safe_l[:, :1] * (1.0 - rate) if rate else safe_l[:, :1]
            _put_head(out_ref, h, lanes,
                      (acc_ref[h] / denom).astype(out_ref.dtype))
            # lse leaves as a (1, BQ) row of a (bh, 1, lq) array, lane-dense:
            # a (bh, lq, 1) column is tiled out to 128 lanes an element in
            # HBM (100 MB at [384, 512] for 0.8 MB of statistics).  The
            # statistics live lane-broadcast in (BQ, 128) scratch;
            # transposed, any row of the result is the row wanted.
            lse_ref[h] = jnp.transpose(m_ref[h] + jnp.log(safe_l))[:1]


_BLOCK_CANDIDATES = (1024, 512, 256, 128, 64, 32, 16, 8, 4, 2, 1)
# Measured on TPU v5e (BH=48, D=64, bf16, slope-timed): (128, 128) runs at
# 6-8 TF/s while (512, 1024) reaches 48-80 TF/s — 3-5x FASTER than XLA's
# dense path at L >= 2048 and ~parity at L = 512.  Bigger k tiles amortize
# the per-block online-softmax rescale; bigger q tiles amortize k/v
# streams.  r5 re-sweep with bf16 matmul operands (halved VMEM tiles):
# (1024, 1024) beats (512, 1024) at every length — 52.9 vs 49.0 TF/s at
# L=1024, 62.7 vs 55.9 at 2048, 66.4 vs 57.7 at 4096 (4.26x dense);
# (1024, 4096) and (2048, 2048) exceed VMEM.  The r5 512-seq tile sweep
# (tools/tune_flash_tiles.py) also RE-confirmed the einsum crossover for a
# DETERMINISTIC FORWARD: best flash tiling at L=512 is 29 TF/s vs 80 for XLA
# dense, so flash_min_seq_len=1024 stands on data there.  A training pass
# with live dropout is another comparison (``train_dispatch_ok``).
_AUTO_BLOCK_Q_CAP = 1024
_AUTO_BLOCK_K_CAP = 1024
# a pass that returns ``dbias`` keeps a (block_q, Lk) f32 row of it resident:
# at most this many elements (2 MB; twice that with the write-back buffer)
_DBIAS_ROW_ELEMS = 512 * 1024
# one (q, k) pair in one tile of at most this many elements: the backward is
# one kernel (scores, p and dS made once for dq, dk, dv and dbias)
_ONE_PASS_ELEMS = 512 * 512


def _auto_block(length: int, cap: int) -> int:
    """Largest power-of-two-ish tile <= cap that divides ``length``."""
    for s in _BLOCK_CANDIDATES:
        if s <= cap and s <= length and length % s == 0:
            return s
    return 1


def _blocks(lq, lk, block_q, block_k, dbias=False):
    """The (block_q, block_k) of a call.  The forward and the backward of one
    differentiated call take the same pair — with dropout a tile's mask is a
    function of its shape — so ``dbias`` (a biased call under
    differentiation) caps the automatic block_q in both."""
    q_cap = _AUTO_BLOCK_Q_CAP
    if dbias and lq * lk > _ONE_PASS_ELEMS:
        q_cap = min(q_cap, max(8, _DBIAS_ROW_ELEMS // lk))
    block_q = _auto_block(lq, q_cap) if block_q is None else min(block_q, lq)
    block_k = _auto_block(lk, _AUTO_BLOCK_K_CAP) if block_k is None else min(block_k, lk)
    if lq % block_q or lk % block_k:
        raise ValueError(
            f"sequence lengths ({lq}, {lk}) must divide block sizes "
            f"({block_q}, {block_k}); pad inputs first"
        )
    return block_q, block_k


def has_tiles(qlen: int, klen: int) -> bool:
    """The auto tiling finds real tiles: an awkward length (no
    power-of-two-ish divisor) degrades to 1-wide tiles, the ~1/8-MXU-rate
    cliff, so einsum wins there."""
    return (_auto_block(qlen, _AUTO_BLOCK_Q_CAP) >= 128
            and _auto_block(klen, _AUTO_BLOCK_K_CAP) >= 128)


def auto_dispatch_ok(qlen: int, klen: int) -> bool:
    """Should attention_impl="auto" route this shape to the flash kernel?

    Two gates beyond the caller's seq-length crossover check:
    * backend must be TPU — off-TPU the kernel runs in Pallas INTERPRET
      mode, orders of magnitude slower than einsum regardless of length;
    * the auto tiling must find real tiles (``has_tiles``).
    """
    return jax.default_backend() == "tpu" and has_tiles(qlen, klen)


# a grid step costs a third of a microsecond before it computes anything: a
# step takes as many consecutive row-and-heads as keep its score tiles
# together under this many elements (512 x 512: one; 128 x 512: four;
# 128 x 128: T5-base's twelve heads.  Measured on a v5e, PERF.md PR 39)
_STEP_ELEMS = 512 * 512
# a step's blocks and tile temporaries pass the compiler's default 16 MB of
# fast memory where two 512 x 512 heads, their bias and their dbias meet
_COMPILER_PARAMS = pltpu.CompilerParams(vmem_limit_bytes=64 * 2**20)


def _layout(bias, kv_mask, bh, block_q, block_k, d, num_heads=None):
    """``(heads, blocks, group, per_row)``: row-and-heads a grid step, how
    many such blocks there are, how many of them share no bias tile (None
    without a bias; see the grids' note), and for token-major operands
    (``num_heads`` given) the blocks a batch row.  ``heads`` divides what
    consecutive row-and-heads must have in common — a bias's period, a mask
    row's rows, a batch row's heads — and token-major its lanes fill whole
    128-lane tiles (or are all of a row's)."""
    unit = bh
    if bias is not None:
        if bh % bias.shape[0]:
            raise ValueError(
                f"bias leading dim {bias.shape[0]} incompatible with "
                f"batch·heads {bh}")
        unit = bias.shape[0]
    if kv_mask is not None:
        unit = math.gcd(unit, bh // kv_mask.shape[0])
    if num_heads is not None:
        unit = math.gcd(unit, num_heads)
    valid = [g for g in range(1, unit + 1) if unit % g == 0 and (
        num_heads is None or g * d % 128 == 0 or g == num_heads)]
    fits = [g for g in valid
            if g <= 16 and g * block_q * block_k <= _STEP_ELEMS]
    heads = max(fits) if fits else min(valid)
    group = None if bias is None else bias.shape[0] // heads
    per_row = None if num_heads is None else num_heads // heads
    return heads, bh // heads, group, per_row


def _shared_operands(bias, kv_mask, seed, keep, bh, block_q, block_k, heads,
                     group, at):
    """(specs, args, present) of the operands a call may lack, in the
    kernels' order: bias, mask, seed, keep; ``at`` turns an index map over
    ``(block, i, j)`` into the grid's."""
    specs, args = [], []
    if bias is not None:
        # per-head, batch-shared: row-and-head = batch*H + head
        specs.append(pl.BlockSpec((heads, block_q, block_k),
                                  at(lambda b, i, j: (b % group, i, j))))
        args.append(bias)
    if kv_mask is not None:
        rows_per = bh // kv_mask.shape[0]            # 1 mask row: every b
        # carried as (B, 1, Lk): the singleton sublane dim must equal the
        # array dim for the TPU lowering (a (1, block_k) block over (B, Lk)
        # is rejected — sublane 1 neither divides 8 nor equals B)
        specs.append(pl.BlockSpec(
            (1, 1, block_k), at(lambda b, i, j: (b * heads // rows_per, 0, j))))
        args.append(kv_mask[:, None, :])
    if seed is not None:
        specs.append(pl.BlockSpec(memory_space=pltpu.SMEM))
        args.append(seed)
    if keep is not None:
        specs.append(pl.BlockSpec((heads, block_q, block_k),
                                  at(lambda b, i, j: (b, i, j))))
        args.append(keep)
    present = [x is not None for x in (bias, kv_mask, seed, keep)]
    return specs, args, present


def _dims(q, k, num_heads):
    """(bh, lq, lk, d) of head-major (B·H, L, D) or, with ``num_heads``,
    token-major (B, L, H·D) operands."""
    if num_heads is None:
        return q.shape[0], q.shape[1], k.shape[1], q.shape[2]
    return (q.shape[0] * num_heads, q.shape[1], k.shape[1],
            q.shape[2] // num_heads)


# The two wrappers below are jitted so that a model's layers, which call them
# with equal shapes, share one trace and one lowered function: a kernel is
# traced and lowered to Mosaic once a program, not once a layer (the fine-tune
# step has twelve of each; tracing them is host time before a cached program
# can even be looked up).
@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret", "rate", "num_heads",
    "dbias"))
def _pallas_fwd(q, k, v, bias, kv_mask, seed, keep, scale, causal, block_q,
                block_k, interpret, rate=0.0, num_heads=None, dbias=False):
    bh, lq, lk, d = _dims(q, k, num_heads)
    block_q, block_k = _blocks(lq, lk, block_q, block_k, dbias)
    heads, blocks, group, per_row = _layout(
        bias, kv_mask, bh, block_q, block_k, d, num_heads)
    at = functools.partial(_at, group)
    rows = functools.partial(_rows_spec, at, heads, d, per_row)
    more_specs, more_args, present = _shared_operands(
        bias, kv_mask, seed, keep, bh, block_q, block_k, heads, group, at)

    out, lse = pl.pallas_call(
        _fill_absent(
            _fwd_kernel, [True] * 3 + present + [True] * 5, scale=scale,
            causal=causal, block_q=block_q, block_k=block_k, group=group,
            heads=heads, rate=rate, tiles=(lq // block_q, lk // block_k),
            lanes=None if num_heads is None else d),
        grid=_grid(group, blocks, lq // block_q, lk // block_k),
        in_specs=[rows(block_q), rows(block_k, of_k=True),
                  rows(block_k, of_k=True), *more_specs],
        out_specs=[
            rows(block_q),
            # lse as (bh, 1, lq): a (1, 1, block_q) block satisfies the TPU
            # (sublane, lane) tiling rules where a (1, block_q) block over
            # (bh, lq) does not
            pl.BlockSpec((heads, 1, block_q), at(lambda b, i, j: (b, 0, i))),
        ],
        out_shape=[
            jax.ShapeDtypeStruct(q.shape, q.dtype),
            jax.ShapeDtypeStruct((bh, 1, lq), jnp.float32),
        ],
        scratch_shapes=[
            pltpu.VMEM((heads, block_q, d), jnp.float32),    # acc
            pltpu.VMEM((heads, block_q, 128), jnp.float32),  # running max (lane-bcast)
            pltpu.VMEM((heads, block_q, 128), jnp.float32),  # running sum (lane-bcast)
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_fwd",
    )(q, k, v, *more_args)
    return out, lse[:, 0]


# --------------------------------------------------------------------------
# reference (the dense computation: oracle for tests and bench.py)
# --------------------------------------------------------------------------


def _expand_bias(bias, bh, lq, lk):
    if bias is None:
        return None
    b0 = bias.shape[0]
    if b0 == bh:
        return bias
    if b0 == 1:
        return jnp.broadcast_to(bias, (bh, lq, lk))
    reps = bh // b0
    return jnp.broadcast_to(bias[None], (reps, b0, lq, lk)).reshape(bh, lq, lk)


def _reference_pair(q, k, v, bias, kv_mask, scale, causal, keep=None,
                    rate=0.0):
    """Dense attention in f32, ``(out, lse)``.  With ``keep`` (bh, lq, lk) and
    ``rate``: the normalised probabilities are dropped where ``keep`` is 0 and
    the kept scaled by ``1 / (1 - rate)``, as ``models/t5`` drops them."""
    bh, lq, d = q.shape
    lk = k.shape[1]
    s = jnp.einsum(
        "bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32)
    ) * scale
    bias = _expand_bias(bias, bh, lq, lk)
    if bias is not None:
        s = s + bias.astype(jnp.float32)
    if kv_mask is not None:
        h_per = bh // kv_mask.shape[0]
        m = jnp.repeat(kv_mask.astype(jnp.float32), h_per, axis=0)  # (bh, lk)
        s = s + m[:, None, :]
    if causal:
        qi = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        kj = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        s = jnp.where(qi >= kj, s, _NEG_INF)
    lse = jax.scipy.special.logsumexp(s, axis=-1)
    p = jnp.exp(s - lse[..., None])
    if keep is not None:
        p = jnp.where(keep != 0, p / (1.0 - rate), 0.0)
    out = jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)
    return out, lse


def _reference_attention(q, k, v, bias, scale, causal, kv_mask=None):
    return _reference_pair(q, k, v, bias, kv_mask, scale, causal)[0]


# --------------------------------------------------------------------------
# backward kernels (blockwise, O(L) memory — no (Lq, Lk) materialization)
# --------------------------------------------------------------------------
#
# Standard flash-attention backward from the saved (out, lse) statistics,
# with the dense path's dropout (kept probabilities scaled by c = 1/(1-rate);
# M the keep mask times c):
#   p_ij  = exp(s_ij - lse_i)           pd_ij = p_ij · M_ij
#   dv_j  = Σ_i pd_ij^T · do_i
#   dp_ij = (do_i · v_j^T) · M_ij
#   ds_ij = p_ij · (dp_ij - Δ_i)        Δ_i = rowsum(do_i ∘ o_i) - glse_i
#   dq_i  = Σ_j ds_ij · k_j · scale
#   dk_j  = Σ_i ds_ij^T · q_i · scale
#   dbias = Σ_rows ds                   (the rows that share a bias block)
# Δ needs no mask: rowsum(dp ∘ p) = rowsum((do·v^T) ∘ pd) = rowsum(do ∘ o);
# each tile makes it from the do and out rows it holds.
# The logsumexp cotangent folds into Δ (∂lse_i/∂s_ij = p_ij), which is what
# lets ring attention train through merged softmax stats with no extra pass.
# Where a (q, k) pair is one tile, one kernel makes all four gradients from
# one recompute.  Otherwise two, because the two accumulations run over
# different grid axes: dq accumulates across j (j innermost revisits the q
# tile's scratch), dk/dv across i; the dq pass also adds each tile's dS into
# its (block_q, Lk) row of dbias, resident while the rows that share it pass.


def _bwd_p(s, lse):
    """exp(s - lse), with MASKED entries hard-zeroed.  f32 can't represent
    -1e30 + log(klen), so a fully-masked row's lse rounds back to -1e30 and
    the naive exp gives 1 per entry — klen-times the forward's
    normalization.  Zeroing keeps such degenerate rows' gradients at 0."""
    return jnp.where(s <= 0.5 * _NEG_INF, 0.0, jnp.exp(s - lse))


def _column(row):
    """A lane-dense (1, BQ) row statistic as the (BQ, 1) column a score tile
    broadcasts against (the forward's transpose, undone)."""
    return jnp.transpose(jnp.broadcast_to(row, (128, row.shape[1])))[:, :1]


def _bwd_tile(refs, h, b, i, j, scale, causal, block_q, block_k, rate, tiles,
              lanes):
    """Shared per-tile backward computation for head ``h`` of the step's
    block, row-and-head ``b``: recompute scores with the SAME masking as the
    forward (``_scores``) and the same dropout draw (``_keep_tile``), then p
    and ds.  ``refs``: the eleven input refs in order.  Returns (q, k, do, pd,
    ds): operands q/k/do in their INPUT dtype (bf16 matmuls on chip), pd (the
    probabilities the context product saw) and ds f32."""
    (q_ref, k_ref, v_ref, do_ref, out_ref, lse_ref, glse_ref, bias_ref,
     mask_ref, seed_ref, keep_ref) = refs
    q = _head(q_ref, h, lanes)
    k = _head(k_ref, h, lanes)
    s = _scores(q, k, _tile_of(bias_ref, h), _tile_of(mask_ref), i, j, scale,
                causal, block_q, block_k)
    p = _bwd_p(s, _column(lse_ref[h]))               # (BQ, BK)
    do = _head(do_ref, h, lanes)                     # (BQ, D)
    dp = jax.lax.dot_general(
        do.astype(v_ref.dtype), _head(v_ref, h, lanes),
        (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
    )                                                # (BQ, BK)
    pd = p
    if rate:
        keep = _keep_tile(seed_ref, _tile_of(keep_ref, h),
                          _tile_number(b, i, j, tiles), p.shape, rate)
        pd = jnp.where(keep, p * (1.0 / (1.0 - rate)), 0.0)
        dp = jnp.where(keep, dp * (1.0 / (1.0 - rate)), 0.0)
    # Δ_i = rowsum(do_i ∘ o_i) - glse_i, made here from the rows at hand
    delta = jnp.sum(do.astype(jnp.float32)
                    * _head(out_ref, h, lanes).astype(jnp.float32),
                    axis=-1, keepdims=True)                  # (BQ, 1)
    if glse_ref is not None:
        delta = delta - _column(glse_ref[h])
    ds = p * (dp - delta)
    return q, k, do, pd, ds


def _t_dot(a, b):
    """a^T · b in f32: (BQ, BK)^T (BQ, D) -> (BK, D)."""
    return jax.lax.dot_general(
        a.astype(b.dtype), b, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)


def _bwd_one_pass_kernel(*refs, scale, causal, block_q, block_k, group, heads,
                         rate, tiles, lanes):
    *ins, dq_ref, dk_ref, dv_ref, dbias_ref = refs
    blk, _, _, _, r = _grid_ids(group)
    for h in range(heads):
        q, k, do, pd, ds = _bwd_tile(
            ins, h, blk * heads + h, 0, 0, scale, causal, block_q, block_k,
            rate, tiles, lanes)
        dq = jax.lax.dot_general(
            ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        dk = _t_dot(ds, q)
        if scale != 1.0:
            dq, dk = dq * scale, dk * scale
        _put_head(dq_ref, h, lanes, dq.astype(dq_ref.dtype))
        _put_head(dk_ref, h, lanes, dk.astype(dk_ref.dtype))
        _put_head(dv_ref, h, lanes, _t_dot(pd, do).astype(dv_ref.dtype))
        if dbias_ref is not None:
            @pl.when(r == 0)
            def _first():
                dbias_ref[h] = ds

            @pl.when(r != 0)
            def _add():
                dbias_ref[h] = dbias_ref[h] + ds


def _bwd_dq_kernel(*refs, scale, causal, block_q, block_k, group, heads, rate,
                   tiles, lanes):
    *ins, dq_ref, dbias_ref, dq_acc = refs
    blk, i, j, nk, r = _grid_ids(group)

    @pl.when(j == 0)
    def _init():
        dq_acc[:] = jnp.zeros_like(dq_acc)

    if dbias_ref is not None:
        @pl.when((j == 0) & (r == 0))
        def _init_dbias():
            dbias_ref[:] = jnp.zeros_like(dbias_ref)

    live = _causal_live(i, j, block_q, block_k) if causal else True

    @pl.when(live)
    def _body():
        for h in range(heads):
            _, k, _, _, ds = _bwd_tile(
                ins, h, blk * heads + h, i, j, scale, causal, block_q,
                block_k, rate, tiles, lanes)
            dq_acc[h] = dq_acc[h] + jax.lax.dot_general(
                ds.astype(k.dtype), k, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.float32,
            ) * scale
            if dbias_ref is not None:
                cols = pl.ds(pl.multiple_of(j * block_k, block_k), block_k)
                dbias_ref[h, :, cols] = dbias_ref[h, :, cols] + ds

    @pl.when(j == nk - 1)
    def _finalize():
        for h in range(heads):
            _put_head(dq_ref, h, lanes, dq_acc[h].astype(dq_ref.dtype))


def _bwd_dkv_kernel(*refs, scale, causal, block_q, block_k, group, heads,
                    rate, tiles, lanes):
    *ins, dk_ref, dv_ref, dk_acc, dv_acc = refs
    blk, j, i, nq, _ = _grid_ids(group)

    @pl.when(i == 0)
    def _init():
        dk_acc[:] = jnp.zeros_like(dk_acc)
        dv_acc[:] = jnp.zeros_like(dv_acc)

    live = _causal_live(i, j, block_q, block_k) if causal else True

    @pl.when(live)
    def _body():
        for h in range(heads):
            q, _, do, pd, ds = _bwd_tile(
                ins, h, blk * heads + h, i, j, scale, causal, block_q,
                block_k, rate, tiles, lanes)
            dv_acc[h] = dv_acc[h] + _t_dot(pd, do)              # (BK, D)
            dk_acc[h] = dk_acc[h] + _t_dot(ds, q) * scale       # (BK, D)

    @pl.when(i == nq - 1)
    def _finalize():
        for h in range(heads):
            _put_head(dk_ref, h, lanes, dk_acc[h].astype(dk_ref.dtype))
            _put_head(dv_ref, h, lanes, dv_acc[h].astype(dv_ref.dtype))


@functools.partial(jax.jit, static_argnames=(
    "scale", "causal", "block_q", "block_k", "interpret", "rate", "num_heads"))
def _pallas_bwd(q, k, v, bias, kv_mask, seed, keep, out, lse, do, glse, scale,
                causal, block_q, block_k, interpret, rate=0.0, num_heads=None):
    """(dq, dk, dv, dbias) via the blockwise backward.  ``kv_mask`` here is
    the ADDITIVE form (as in the forward); ``dbias`` is None without a bias,
    else f32 of the bias's shape."""
    bh, lq, lk, d = _dims(q, k, num_heads)
    block_q, block_k = _blocks(lq, lk, block_q, block_k, bias is not None)
    nq, nk = lq // block_q, lk // block_k
    heads, blocks, group, per_row = _layout(
        bias, kv_mask, bh, block_q, block_k, d, num_heads)

    # lse (and its cotangent, where it has one) as lane-dense (bh, 1, lq)
    # rows (see the forward's note)
    lse_row = lse.astype(jnp.float32)[:, None, :]
    glse_row = None if glse is None else glse.astype(jnp.float32)[:, None, :]

    kw = dict(scale=scale, causal=causal, block_q=block_q, block_k=block_k,
              group=group, heads=heads, rate=rate, tiles=(nq, nk),
              lanes=None if num_heads is None else d)
    like = lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype)  # noqa: E731

    def operands(swap):
        """in_specs, args and flags of a pass over ``(block, i, j)`` — or,
        with ``swap``, ``(block, j, i)``."""
        at = functools.partial(_at, group, swap=swap)
        more_specs, more_args, present = _shared_operands(
            bias, kv_mask, seed, keep, bh, block_q, block_k, heads, group, at)
        stat = pl.BlockSpec((heads, 1, block_q), at(lambda b, i, j: (b, 0, i)))
        rows = functools.partial(_rows_spec, at, heads, d, per_row)
        specs = [
            rows(block_q),                                          # q
            rows(block_k, of_k=True),                               # k
            rows(block_k, of_k=True),                               # v
            rows(block_q),                                          # do
            rows(block_q),                                          # out
            stat,                                                   # lse
            *([stat] if glse_row is not None else []),              # glse
            *more_specs,
        ]
        args = (q, k, v, do, out, lse_row,
                *([glse_row] if glse_row is not None else []), *more_args)
        return (specs, args, [True] * 6 + [glse_row is not None] + present,
                at, rows)

    has_dbias = bias is not None
    dbias_shape = ([jax.ShapeDtypeStruct(bias.shape, jnp.float32)]
                   if has_dbias else [])

    if nq == 1 and nk == 1 and lq * lk <= _ONE_PASS_ELEMS:
        specs, args, present, at, rows = operands(swap=False)
        dbias_spec = ([pl.BlockSpec((heads, lq, lk),
                                    at(lambda b, i, j: (b % group, 0, 0)))]
                      if has_dbias else [])
        dq, dk, dv, *dbias = pl.pallas_call(
            _fill_absent(_bwd_one_pass_kernel,
                         present + [True] * 3 + [has_dbias], **kw),
            grid=_grid(group, blocks, 1, 1),
            in_specs=specs,
            out_specs=[rows(lq), rows(lk, of_k=True), rows(lk, of_k=True),
                       *dbias_spec],
            out_shape=[like(q), like(k), like(v), *dbias_shape],
            compiler_params=_COMPILER_PARAMS,
            interpret=interpret,
            name="flash_bwd",
        )(*args)
        return dq, dk, dv, (dbias[0] if has_dbias else None)

    # pass 1: dq (and dbias) — grid (block, i, j), j innermost accumulates
    # into the dq scratch
    specs, args, present, at, rows = operands(swap=False)
    dbias_spec = ([pl.BlockSpec((heads, block_q, lk),
                                at(lambda b, i, j: (b % group, i, 0)))]
                  if has_dbias else [])
    dq, *dbias = pl.pallas_call(
        _fill_absent(_bwd_dq_kernel, present + [True, has_dbias, True], **kw),
        grid=_grid(group, blocks, nq, nk),
        in_specs=specs,
        out_specs=[rows(block_q), *dbias_spec],
        out_shape=[like(q), *dbias_shape],
        scratch_shapes=[pltpu.VMEM((heads, block_q, d), jnp.float32)],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dq",
    )(*args)

    # pass 2: dk/dv — grid (block, j, i), i innermost accumulates into scratch
    specs, args, present, at, rows = operands(swap=True)
    dk, dv = pl.pallas_call(
        _fill_absent(_bwd_dkv_kernel, present + [True] * 4, **kw),
        grid=_grid(group, blocks, nk, nq),
        in_specs=specs,
        out_specs=[rows(block_k, of_k=True), rows(block_k, of_k=True)],
        out_shape=[like(k), like(v)],
        scratch_shapes=[
            pltpu.VMEM((heads, block_k, d), jnp.float32),
            pltpu.VMEM((heads, block_k, d), jnp.float32),
        ],
        compiler_params=_COMPILER_PARAMS,
        interpret=interpret,
        name="flash_bwd_dkv",
    )(*args)
    return dq, dk, dv, (dbias[0] if has_dbias else None)


# --------------------------------------------------------------------------
# differentiable entry (custom VJP over both outputs)
# --------------------------------------------------------------------------


@functools.partial(jax.custom_vjp, nondiff_argnums=(7, 8, 9, 10, 11, 12, 13))
def _flash_pair(q, k, v, bias, kv_mask, seed, keep, scale, causal, block_q,
                block_k, interpret, rate, num_heads):
    return _pallas_fwd(q, k, v, bias, kv_mask, seed, keep, scale, causal,
                       block_q, block_k, interpret, rate, num_heads)


def _flash_pair_fwd(q, k, v, bias, kv_mask, seed, keep, scale, causal,
                    block_q, block_k, interpret, rate, num_heads):
    out, lse = _pallas_fwd(q, k, v, bias, kv_mask, seed, keep, scale, causal,
                           block_q, block_k, interpret, rate, num_heads,
                           dbias=bias is not None)
    # no score, no probability and no mask word among the residuals
    return (out, lse), (q, k, v, bias, kv_mask, seed, keep, out, lse)


def _flash_pair_bwd(scale, causal, block_q, block_k, interpret, rate,
                    num_heads, res, g):
    q, k, v, bias, kv_mask, seed, keep, out, lse = res
    do, glse = g
    # blockwise backward: O(L) memory, no (Lq, Lk) materialization — this is
    # what makes long-context training (ring attention / SP) memory-feasible,
    # not just the forward; the only dense result is the bias's own gradient
    dq, dk, dv, dbias = _pallas_bwd(
        q, k, v, bias, kv_mask, seed, keep, out, lse, do, glse, scale, causal,
        block_q, block_k, interpret, rate, num_heads)
    if dbias is not None:
        dbias = dbias.astype(bias.dtype)
    dmask = None if kv_mask is None else jnp.zeros_like(kv_mask)
    return dq, dk, dv, dbias, dmask, None, None


_flash_pair.defvjp(_flash_pair_fwd, _flash_pair_bwd)


# --------------------------------------------------------------------------
# public API
# --------------------------------------------------------------------------


def _normalize(q, k, v, bias, num_heads):
    """Fold the operands for the kernels: 4-D (B, H, L, D) to head-major
    (B·H, L, D) and their 4-D bias (1|B, H|1, Lq, Lk) to 3-D; 3-D operands as
    they are, head-major or with ``num_heads`` token-major (B, L, H·D), their
    bias 3-D or batch-shared 4-D.  Returns (q, k, v, bias, (b, h) or None)."""
    if q.ndim != 4:
        if bias is not None and bias.ndim == 4:
            if bias.shape[0] != 1:
                raise ValueError("a 4D bias of 3D operands is batch-shared")
            bias = bias[0]
        return q, k, v, bias, None
    if num_heads is not None:
        raise ValueError("token-major operands (num_heads) are 3D: (B, L, H·D)")
    b, h, lq, d = q.shape
    q, k, v = (x.reshape(b * h, x.shape[2], d) for x in (q, k, v))
    if bias is not None:
        if bias.ndim != 4:
            raise ValueError("bias must be 4D when q/k/v are 4D")
        bb, bh_, blq, blk = bias.shape
        if bb == 1:
            bias = bias.reshape(bh_, blq, blk)  # (H|1, Lq, Lk)
        else:
            bias = jnp.broadcast_to(bias, (b, h, blq, blk)).reshape(
                b * h, blq, blk
            )
    return q, k, v, bias, (b, h)


def _call(q, k, v, bias, kv_mask, scale, causal, block_q, block_k, interpret,
          dropout_rate, dropout_seed, dropout_keep, num_heads=None):
    shape4 = q.shape
    q, k, v, bias, fold = _normalize(q, k, v, bias, num_heads)
    bh, lq, lk, d = _dims(q, k, num_heads)
    if scale is None:
        scale = 1.0 / (d ** 0.5)
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    addmask = None
    if kv_mask is not None:
        addmask = (1.0 - kv_mask.astype(jnp.float32)) * _NEG_INF
    seed = keep = None
    rate = float(dropout_rate)
    if rate:
        if dropout_keep is not None:
            keep = jnp.asarray(dropout_keep).reshape(bh, lq, lk).astype(jnp.int8)
        elif dropout_seed is None:
            raise ValueError("dropout_rate > 0 needs a dropout_seed")
        elif interpret:
            keep = keep_from_seed(dropout_seed, (bh, lq, lk), rate)
        else:
            seed = jnp.asarray(dropout_seed).astype(jnp.int32).reshape(2)
    out, lse = _flash_pair(q, k, v, bias, addmask, seed, keep, float(scale),
                           bool(causal), block_q, block_k, bool(interpret),
                           rate, num_heads)
    if fold is not None:
        out = out.reshape(shape4)
        lse = lse.reshape(*fold, lq)
    return out, lse


def flash_attention(
    q,
    k,
    v,
    bias: Optional[jax.Array] = None,
    *,
    kv_mask: Optional[jax.Array] = None,
    scale: Optional[float] = None,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
    dropout_rate: float = 0.0,
    dropout_seed: Optional[jax.Array] = None,
    dropout_keep: Optional[jax.Array] = None,
    num_heads: Optional[int] = None,
):
    """Blockwise attention.

    q/k/v: head-major (B·H, L, D) or (B, H, L, D); or, with ``num_heads``,
    token-major (B, L, H·D) as a projection writes them — the kernels then
    read each head's lanes where they lie, the result comes back so, and no
    transposed copy is made.  bias: additive scores, leading dim
    1, H, or B·H (T5 passes its (1, H, Lq, Lk) relative-position bias
    directly — it is NOT expanded to batch size).  kv_mask: (B, Lk) with
    1 = attend, 0 = masked (key padding).  scale defaults to 1/sqrt(D);
    pass 1.0 for T5.  On non-TPU backends runs in Pallas interpret mode so
    the same code path tests on the CPU mesh (SURVEY.md §4.3).

    ``dropout_rate`` > 0 drops normalised probabilities and scales the kept
    by ``1 / (1 - rate)``, as the dense path does, inside the kernels, forward
    and backward.  The mask is a function of ``dropout_seed`` (two 32-bit
    words), the tile sizes and each tile's place; it is drawn on the chip
    where it is used and never stored.  ``dropout_keep`` (B·H, Lq, Lk),
    nonzero = keep, supplies the mask instead; in interpret mode, which has no
    generator, a seed alone is turned into one by ``keep_from_seed``.
    """
    return _call(q, k, v, bias, kv_mask, scale, causal, block_q, block_k,
                 interpret, dropout_rate, dropout_seed, dropout_keep,
                 num_heads)[0]


def flash_attention_with_lse(
    q, k, v, bias=None, *, kv_mask=None, scale=None, causal=False,
    block_q: Optional[int] = None, block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
) -> Tuple[jax.Array, jax.Array]:
    """(out, logsumexp) variant — ring attention merges partial softmaxes
    across devices with the lse.  Differentiable in both outputs."""
    return _call(q, k, v, bias, kv_mask, scale, causal, block_q, block_k,
                 interpret, 0.0, None, None)


# --------------------------------------------------------------------------
# a training pass: which shapes, and over which mesh
# --------------------------------------------------------------------------


# the smallest (Lq x Lk) a training pass hands the kernels: between the two
# nearest shapes measured (128 x 512: parity at best; 512 x 512: 1.5x)
_TRAIN_MIN_ELEMS = 256 * 512


def train_dispatch_ok(qlen: int, klen: int, head_dim: int) -> bool:
    """Does a training pass with live attention dropout take the kernels at
    this shape?  Chosen at trace time from what the call can observe, no
    knob: a TPU backend (interpret mode is for tests), real tiles, a head
    width the MXU takes whole, and scores enough a head.  The dense path
    writes, keeps and reads the (Lq, Lk) probabilities and their mask words
    in HBM, which at 512 x 512 is most of its time; measured on a v5e, forward
    and backward, 12 heads of 64, 32 rows (docs/KERNELS.md; PERF.md, PR 39):
    512 x 512 the kernels 2.1 ms against 4.1 dense; 128 x 512 0.63 against
    0.63; 128 x 128 0.36 against 0.12 (what a tile costs before it computes —
    its statistics' transposes, its dbias block — is no longer small beside
    16 registers of scores)."""
    return (auto_dispatch_ok(qlen, klen) and head_dim % 64 == 0
            and qlen * klen >= _TRAIN_MIN_ELEMS)


_kernel_mesh = None


@contextlib.contextmanager
def kernel_mesh(mesh):
    """While a program is traced under this, ``flash_attention_on_mesh`` maps
    its kernel calls over ``mesh`` (``train/t5_trainer.make_train_step``
    enters it).  XLA cannot partition a Mosaic call: left to the partitioner a
    kernel would run replicated on gathered operands."""
    global _kernel_mesh
    was, _kernel_mesh = _kernel_mesh, mesh
    try:
        yield
    finally:
        _kernel_mesh = was


def traced_for_mesh() -> bool:
    """Is the program being traced under a :func:`kernel_mesh`?"""
    return _kernel_mesh is not None


def mesh_divides(batch: int, heads: int, batch_axis: str = "data",
                 head_axis: str = "model") -> bool:
    """Can ``flash_attention_on_mesh`` split these rows and heads evenly over
    the ``kernel_mesh``?  (True without one.)"""
    mesh = _kernel_mesh
    return mesh is None or (batch % mesh.shape[batch_axis] == 0
                            and heads % mesh.shape[head_axis] == 0)


def bias_per_batch_shard(bias, batch_axis: str = "data"):
    """``bias`` (1, H, Lq, Lk) as one copy a batch shard of the
    ``kernel_mesh``, (shards, H, Lq, Lk), for ``flash_attention_on_mesh``:
    handed in once for all the layers that share the bias, their ``dbias``
    are added shard by shard and reduced over the shards once, by this
    broadcast's transpose — not once a layer.  Without a mesh, as it is."""
    mesh = _kernel_mesh
    if mesh is None or mesh.shape[batch_axis] == 1:
        return bias
    return jnp.broadcast_to(bias, (mesh.shape[batch_axis], *bias.shape[1:]))


def flash_attention_on_mesh(q, k, v, bias=None, *, kv_mask=None,
                            dropout_seed=None, num_heads=None,
                            batch_axis: str = "data",
                            head_axis: str = "model", **kw):
    """``flash_attention`` of (B, H, L, D) operands or, with ``num_heads``,
    token-major (B, L, H·D) ones, each shard of the ``kernel_mesh`` running
    the kernels on its own rows (``batch_axis``) and heads (``head_axis``),
    its dropout seed offset by its place in the mesh so no two shards draw one
    mask.  ``bias`` (1, H, Lq, Lk) is handed to each batch shard as its own
    copy (``bias_per_batch_shard``, made here unless the caller did) and its
    gradient summed over them afterwards, outside the mapped region.  No
    mesh, or one of a single device: the plain call."""
    mesh = _kernel_mesh
    if mesh is None or mesh.size == 1:
        return flash_attention(q, k, v, bias, kv_mask=kv_mask,
                               dropout_seed=dropout_seed, num_heads=num_heads,
                               **kw)
    from jax.sharding import PartitionSpec as P

    from tpu_air.parallel.shardmap_compat import shard_map_unchecked

    nb, nh = mesh.shape[batch_axis], mesh.shape[head_axis]
    rows = (P(batch_axis, head_axis, None, None) if num_heads is None
            else P(batch_axis, None, head_axis))
    operands, specs = [q, k, v], [rows, rows, rows]
    names = ["q", "k", "v"]
    if bias is not None:
        # a copy a batch shard, so the shards' dbias leave the mapped region
        # as they are and are summed by the broadcast's transpose
        if nb > 1 and bias.shape[0] == 1:
            bias = bias_per_batch_shard(bias, batch_axis)
        operands.append(bias)
        specs.append(P(batch_axis, head_axis, None, None))
        names.append("bias")
    if kv_mask is not None:
        operands.append(kv_mask)
        specs.append(P(batch_axis, None))
        names.append("kv_mask")
    if dropout_seed is not None:
        operands.append(jnp.asarray(dropout_seed).astype(jnp.int32).reshape(2))
        specs.append(P())
        names.append("dropout_seed")

    def shard(*xs):
        args = dict(zip(names, xs))
        if "dropout_seed" in args:
            place = (jax.lax.axis_index(batch_axis) * nh
                     + jax.lax.axis_index(head_axis))
            args["dropout_seed"] = args["dropout_seed"].at[1].add(place)
        q, k, v = args.pop("q"), args.pop("k"), args.pop("v")
        return flash_attention(
            q, k, v, args.pop("bias", None),
            num_heads=None if num_heads is None else num_heads // nh,
            **args, **kw)

    return shard_map_unchecked(
        shard, mesh=mesh, in_specs=tuple(specs), out_specs=rows)(*operands)
