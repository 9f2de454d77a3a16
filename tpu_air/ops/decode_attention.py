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
... in a decode LOOP   the same, all layers one    :func:`prefix_append_decode_attention`
on a TPU: one causal   array ``[layers, L, b,      (Pallas: the blocks that hold a
prefix for every row   h*d]``, read where it lies  written position, no other)
the LM's K/V: pages    FLAT ``[b, L, h*d]``,       :func:`flat_decode_attention`
gathered for the step  no scales
the LM's latent,       ONE slab ``[b, L, w]``       :func:`latent_decode_attention`
plain cache or pages   (latent ``r``, roped key
gathered for the step  ``dr``, zeros), no scales
the LM's latent page   the pool itself ``[P,        :func:`paged_latent_decode_attention`
pool on a TPU: a       page_len, w]``, rows as      (Pallas: a row's live pages
row's live pages read  above, whole tiles a page    walked through its block table)
in place
=====================  ==========================  ================================

One read is not a single token's: a prefill CHUNK's attention over the same
latent pool on a TPU, :func:`paged_latent_chunk_attention` (Pallas: the pages
the chunk's prompt has reached walked through the slot's table row, K and V
made for the page in hand, a running softmax).  It lives here because it
shares the pool, the rule and the walk with the rows' read.

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

PREFIX (PR 59): how a loop's carry LIES is the compiler's to choose, and for
the flat read's two einsums, whose batch dimension is ``b``, it chose
batch-major: the logical ``[layers, L, b, h*d]`` stored ``[layers, b, L,
h*d]``.  The step's row was then one sublane of every tile (FLAN-T5-base's 9.4
MB of rows a step took 0.64 ms, not 11 us), 129 positions were stored and read
as 144, and the positions not yet written, half of them over a 128-token call,
were a strided sliver of every tile that no read could skip.
:func:`prefix_append_decode_attention` is one kernel a layer over the stacked
array as it is stored logically: a ``pallas_call`` takes its operands
row-major, so the carry stays position-major, a position is ``b x h*d``
contiguous whole tiles, the append writes whole tiles, and the kernel copies
the blocks up to the step's position and no other.
:func:`prefix_slabs_read_in_place` is the rule: a TPU, no mesh, bf16 or f32
slabs of whole tiles; that every row shares one causal prefix is the caller's
to know (``Decoder``: no ring).  A ring (``T5Engine``: any position may be
live for some row), int8 slabs and every CPU run keep the flat read.

LENGTH-MINOR: ``(d, Lp)`` are whole tiles as stored, so each head's slab is
contracted directly, with no selector and none of its ``num_heads`` x
multiply-accumulates (bare, 739 GB/s on the v5e where the flat read gives 685:
ISSUE 34's record of PR 33's chip runs).  Only a slab that is never appended
to can be stored so: a new position would be one lane of every tile.
:func:`length_minor` makes it, padding ``L`` up to whole lanes; the caller
masks the padding (:func:`pad_keys`).

IN PLACE (PR 44): a gather writes every slot's pages out at ``slot_len`` and
the read passes over that slab twice, live or not; with a twelfth of the
positions live (``gigachat-serve-docchat``: 128 slots of 4096) that was 20.8 ms
of a 55.1 ms step.  :func:`paged_latent_decode_attention` copies only the
pages a row's length spans, HBM to fast memory, one while the one before is
computed on (1.0 ms of the same step; docs/KERNELS.md has its budget).
:func:`latent_pages_read_in_place` is the rule that picks it: a TPU, pages of
whole tiles, no mesh.  PR 45 took the chunk's half of the same disease (its
dense attention over the slot's 4096 gathered positions, three quarters of
them masked, with a ``[64, 256, 4096]`` f32 score array through HBM: 14.0 ms
of a 35 ms mixed step) the same way, by the same rule.
The K/V page kind (:func:`flat_decode_attention` over
:func:`gather_pages`) stays gathered: its roofline counts K/V at ``slot_len``
(PERF.md section 7, harness edit 7), and a read of live pages would read over
100 % of it.

Quantisation (the T5 reads alone: the LM's slabs and pages are never int8):
int8 slabs carry scales that fold into the math, per channel (cross,
:func:`length_minor_decode_attention`) into q and the context, per position
(self, :func:`flat_append_decode_attention`) into the scores and
probabilities; no dequantised slab is ever materialised.  Masking: ``bias``
(the T5 reads) is additive f32 ``[h, L]`` and already holds causal masking,
``kv_mask`` ``[b, L]`` is per-row key validity.  A fully-masked row gives the
plain mean of V (uniform softmax): finite, never zero, and to be treated as
undefined by a caller that can produce one.  Scores and softmax in f32,
matmul operands in the model dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import traced_for_mesh

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
def flat_decode_attention(q, kf, vf, kv_mask, num_heads, dtype,
                          num_kv_heads=None):
    """Single-token attention over FLAT cache slabs ``[b, L, g*d]``, ``g =
    num_kv_heads`` K/V heads (default ``num_heads``) serving ``h / g`` query
    heads each.  All heads ride ONE batched MXU matmul per contraction via
    a selector that lets a query head see the features of ITS K/V head
    (block-diagonal at ``g == h``; dense with one K/V head: every query
    head reads the whole row and none of the products is wasted on zeros),
    so the slab streams from HBM exactly once in its unpadded storage
    layout, under any loop or program boundary (the module docstring has
    the chip's numbers).

    q [b, 1, h, d]; kv_mask [b, L] per-row key validity (what hides the
    positions a row has not reached), or None.  Returns [b, 1, h, d] in
    model dtype."""
    b, L, gd = kf.shape
    h = num_heads
    g = num_kv_heads or h
    d, r = gd // g, h // g
    # sel[f, hq]: feature f of the slab belongs to query head hq's K/V head
    sel = jnp.arange(gd)[:, None] // d == jnp.arange(h)[None, :] // r
    # qexp[b, f, hq] = q[b, hq, f % d] where sel: a K/V head's features, in
    # the slab's order, against the r query heads it serves
    qt = jnp.swapaxes(q.reshape(b, g, r, d).astype(jnp.float32), 2, 3)
    qt = qt.reshape(b, gd, 1, r)
    qexp = jnp.where(
        sel[None], jnp.broadcast_to(qt, (b, gd, g, r)).reshape(b, gd, h),
        0.0).astype(dtype)
    s = jnp.einsum("blf,bfh->blh", kf.astype(dtype), qexp,
                   preferred_element_type=jnp.float32)
    if kv_mask is not None:
        s = s + jnp.where(kv_mask > 0, 0.0, _NEG_INF_DENSE)[:, :, None]
    p = jax.nn.softmax(s, axis=1)
    ctx2 = jnp.einsum("blh,blf->bhf", p.astype(dtype), vf.astype(dtype),
                      preferred_element_type=jnp.float32)      # [b, h, g*d]
    # a query head keeps its own K/V head's features: summed over the K/V
    # heads' axis (the major one), what is left is [b, r, g*d]
    ctx = jnp.where(sel.T[None], ctx2, 0.0).reshape(b, g, r, gd).sum(1)
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


def _sublanes(dtype) -> int:
    """Rows of a TPU tile of ``dtype``: 8 of 32 bits, 16 of 16, 32 of 8."""
    return 32 // jnp.dtype(dtype).itemsize


def pages_are_whole_tiles(pool: jax.Array) -> bool:
    """A page ``[page_len, w]`` of the pool is whole tiles of its dtype."""
    _, page_len, w = pool.shape
    return page_len % _sublanes(pool.dtype) == 0 and w % _LANES == 0


def latent_pages_read_in_place(pool: jax.Array) -> bool:
    """Does a decode step read this latent page pool ``[P, page_len, w]``
    where it lies (:func:`paged_latent_decode_attention`) or gather it
    first?  Decided at trace time from what the call can observe, no knob:
    the backend is a TPU (interpret mode is for tests), a page is whole
    tiles, and the program is not traced for a mesh
    (``flash_attention.kernel_mesh``: XLA cannot partition a Mosaic call,
    and left to the partitioner the kernel would run on the gathered
    pool)."""
    return (jax.default_backend() == "tpu" and not traced_for_mesh()
            and pages_are_whole_tiles(pool))


def _paged_latent_kernel(table_ref, pos_ref, q_ref, pool_ref, o_ref, buf,
                         sems, first_ref, m_ref, l_ref, acc_ref, *, rank, npg):
    """One grid step a row ``s``: a loop over its live pages, each copied
    from the pool in HBM into one of two buffers while the page before it is
    computed on.  The row's last page starts the copy of the NEXT row's
    first, so only row 0 waits for a copy it has just started; which buffer
    a row's first page is in rides ``first_ref`` from row to row (the grid
    is sequential)."""
    s, rows = pl.program_id(0), pl.num_programs(0)
    page_len = buf.shape[1]
    # a position past the slot would walk the table on into the next row's
    pos = jnp.minimum(pos_ref[s], npg * page_len - 1)
    n = pos // page_len + 1

    def fetch(row, j, slot):
        return pltpu.make_async_copy(
            pool_ref.at[table_ref[row * npg + j]], buf.at[slot],
            sems.at[slot])

    @pl.when(s == 0)
    def _():
        first_ref[0] = 0
        fetch(0, 0, 0).start()

    first = first_ref[0]
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    q = q_ref[0]                                             # [h, w]

    def page(j, carry):
        slot = (first + j) % 2

        @pl.when(j + 1 < n)
        def _():
            fetch(s, j + 1, 1 - slot).start()

        @pl.when(jnp.logical_and(j + 1 == n, s + 1 < rows))
        def _():
            fetch(s + 1, 0, 1 - slot).start()

        fetch(s, j, slot).wait()
        lat = buf[slot].astype(q.dtype)                      # [page_len, w]
        sc = jax.lax.dot_general(q, lat, (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        at = j * page_len + jax.lax.broadcasted_iota(jnp.int32, sc.shape, 1)
        sc = jnp.where(at <= pos, sc, _NEG_INF_DENSE)
        m = m_ref[...]
        m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(sc - m_new)
        l_ref[...] = alpha * l_ref[...] + p.sum(-1, keepdims=True)
        acc_ref[...] = alpha * acc_ref[...] + jnp.dot(
            p.astype(q.dtype), lat, preferred_element_type=jnp.float32)
        m_ref[...] = m_new
        return carry

    jax.lax.fori_loop(0, n, page, None)
    first_ref[0] = (first + n) % 2
    o_ref[0] = (acc_ref[:, :rank] / l_ref[...]).astype(o_ref.dtype)


@jax.named_scope("decode_attention")
def paged_latent_decode_attention(q, pool, block_table, pos, rank, dtype,
                                  interpret=None):
    """:func:`latent_decode_attention` over the pages of the pool, IN PLACE:
    ``q [S, h, w]`` as that one takes it; ``pool [P, page_len, w]`` the
    latent page pool, left in HBM; ``block_table [S, pages_per_slot]`` int32
    and ``pos [S]`` int32, row ``s`` attending positions ``0 .. pos[s]`` of
    its slot (both ride to the kernel as scalar-prefetch arguments).  Row
    ``s`` visits pages ``table[s, 0 .. pos[s] // page_len]`` and no other:
    on each the scores ``[h, page_len]`` in f32, the mask ``<= pos[s]`` (the
    last page's tail), an online softmax and the context accumulated in f32
    over the page's whole width; the first ``rank`` columns over the sum at
    the end -> ``[S, h, rank]`` in ``dtype``.  Operands in ``dtype``: the
    precision of the gathered read, the order of the sums apart.  A row at
    ``pos == 0`` (the engine's idle rows, their table at the null page)
    reads position 0 of its first page, as the gathered read has it.

    VMEM (the cell: 64 heads, page 256 x 640 bf16): two page buffers 0.66
    MB, q and the output block twice 0.29 MB, the f32 accumulator 0.16 MB.
    ``interpret`` None: interpret mode off a TPU (tests)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    rows, h, w = q.shape
    _, page_len, _ = pool.shape
    npg = block_table.shape[1]
    return pl.pallas_call(
        functools.partial(_paged_latent_kernel, rank=rank, npg=npg),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(rows,),
            in_specs=[
                pl.BlockSpec((1, h, w), lambda s, *_: (s, 0, 0)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, h, rank), lambda s, *_: (s, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((2, page_len, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, 1), jnp.float32),
                pltpu.VMEM((h, w), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((rows, h, rank), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=bool(interpret),
        name="paged_latent_decode_attention",
    )(block_table.reshape(-1).astype(jnp.int32), pos.astype(jnp.int32),
      q.astype(dtype), pool)


def _paged_latent_chunk_kernel(table_ref, start_ref, qn_ref, qr_ref, kup_ref,
                               vup_ref, pool_ref, o_ref, buf, sems, first_ref,
                               m_ref, l_ref, acc_ref, *, rank, scale, npg):
    """One grid step a tile of heads: a loop over the pages the chunk's
    prompt has reached, each copied from the pool in HBM into one of two
    buffers while the page before it is computed on (the tile's last page
    starts the copy of the NEXT tile's first, as the rows' kernel has it).
    On a page, a head at a time: K and V of the page's rows from ``W_UKV``,
    the scores, the causal mask (it bites on the last page alone), and the
    running maximum, sum and context in f32."""
    t, tiles = pl.program_id(0), pl.num_programs(0)
    heads, chunk, _ = qn_ref.shape
    rope = qr_ref.shape[2]
    page_len = buf.shape[1]
    dtype = qn_ref.dtype
    # a start past the slot would walk the table off its end
    start = jnp.minimum(start_ref[0], (npg - 1) * page_len)
    n = start // page_len + 1

    def fetch(j, slot):
        return pltpu.make_async_copy(
            pool_ref.at[table_ref[j]], buf.at[slot], sems.at[slot])

    @pl.when(t == 0)
    def _():
        first_ref[0] = 0
        fetch(0, 0).start()

    first = first_ref[0]
    m_ref[...] = jnp.full_like(m_ref, -jnp.inf)
    l_ref[...] = jnp.zeros_like(l_ref)
    acc_ref[...] = jnp.zeros_like(acc_ref)
    ends = (((1,), (1,)), ((), ()))             # contract the minor of both

    def page(j, carry):
        slot = (first + j) % 2

        @pl.when(j + 1 < n)
        def _():
            fetch(j + 1, 1 - slot).start()

        @pl.when(jnp.logical_and(j + 1 == n, t + 1 < tiles))
        def _():
            fetch(0, 1 - slot).start()

        fetch(j, slot).wait()
        c = buf[slot, :, :rank].astype(dtype)                # [page_len, r]
        k_r = buf[slot, :, rank:rank + rope].astype(dtype)   # [page_len, dr]
        at = (chunk, page_len)
        live = (start + jax.lax.broadcasted_iota(jnp.int32, at, 0)
                >= j * page_len + jax.lax.broadcasted_iota(jnp.int32, at, 1))
        for hd in range(heads):
            k_n = jnp.dot(c, kup_ref[hd],
                          preferred_element_type=jnp.float32).astype(dtype)
            v = jnp.dot(c, vup_ref[hd],
                        preferred_element_type=jnp.float32).astype(dtype)
            sc = (jax.lax.dot_general(qn_ref[hd], k_n, ends,
                                      preferred_element_type=jnp.float32)
                  + jax.lax.dot_general(qr_ref[hd], k_r, ends,
                                        preferred_element_type=jnp.float32)
                  ) * scale
            sc = jnp.where(live, sc, _NEG_INF_DENSE)
            m = m_ref[hd]
            m_new = jnp.maximum(m, sc.max(-1, keepdims=True))
            alpha = jnp.exp(m - m_new)
            p = jnp.exp(sc - m_new)
            l_ref[hd] = alpha * l_ref[hd] + p.sum(-1, keepdims=True)
            acc_ref[hd] = alpha * acc_ref[hd] + jnp.dot(
                p.astype(dtype), v, preferred_element_type=jnp.float32)
            m_ref[hd] = m_new
        return carry

    jax.lax.fori_loop(0, n, page, None)
    first_ref[0] = (first + n) % 2
    o_ref[...] = (acc_ref[...] / l_ref[...]).astype(o_ref.dtype)


_CHUNK_HEAD_TILE = 4    # heads a grid step: 7 MB of VMEM at the cell's widths


@jax.named_scope("attn_context")
def paged_latent_chunk_attention(q_n, q_r, k_up, v_up, pool, table_row,
                                 start, scale, dtype, interpret=None):
    """A prefill CHUNK's expanded attention over the pages its prompt has
    reached, IN PLACE: ``q_n [h, C, dn]`` and ``q_r [h, C, dr]`` the queries
    of positions ``start .. start + C - 1`` of one slot; ``k_up [h, r, dn]``
    and ``v_up [h, r, dv]`` the two halves of ``W_UKV`` a head; ``pool [P,
    page_len, w]`` the latent page pool, left in HBM; ``table_row
    [pages_per_slot]`` int32 the slot's pages and ``start`` int32, a chunk
    being one page: ``C == page_len`` and ``start`` a multiple of it (both
    ride to the kernel as scalar-prefetch arguments).  Visits pages
    ``table_row[0 .. start // page_len]`` and no other, the trip count taken
    from ``start`` at run time: on each, K and V of the page's rows (in
    ``dtype``), the scores ``[C, page_len]`` a head in f32, the mask ``start
    + q >= j * page_len + k``, an online softmax and the context in f32,
    divided at the end -> ``[h, C, dv]`` in ``dtype``.  The precision of the dense form
    over the gathered slot, the order of the sums apart; no score array over
    ``slot_len`` and no K or V beyond the page in hand ever exist.

    VMEM (the cell: tiles of 4 of 64 heads, chunk and page 256, r 512, dn
    128, dr 64, dv 192, bf16): the queries, ``W_UKV`` and the output block
    twice 4.1 MB, two page buffers 0.66 MB, the f32 context, maximum and sum
    1.8 MB.  ``interpret`` None: interpret mode off a TPU (tests)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    h, chunk, dn = q_n.shape
    dr, dv = q_r.shape[2], v_up.shape[2]
    _, page_len, w = pool.shape
    if chunk != page_len:
        raise ValueError(f"a chunk is one page: {chunk} rows, page {page_len}")
    rank = k_up.shape[1]
    heads = next(t for t in (_CHUNK_HEAD_TILE, 2, 1) if h % t == 0)

    def tile(minor):
        return pl.BlockSpec((heads,) + minor, lambda t, *_: (t, 0, 0))

    return pl.pallas_call(
        functools.partial(_paged_latent_chunk_kernel, rank=rank,
                          scale=float(scale), npg=table_row.shape[0]),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(h // heads,),
            in_specs=[
                tile((chunk, dn)), tile((chunk, dr)),
                tile((rank, dn)), tile((rank, dv)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=tile((chunk, dv)),
            scratch_shapes=[
                pltpu.VMEM((2, page_len, w), pool.dtype),
                pltpu.SemaphoreType.DMA((2,)),
                pltpu.SMEM((1,), jnp.int32),
                pltpu.VMEM((heads, chunk, 1), jnp.float32),
                pltpu.VMEM((heads, chunk, 1), jnp.float32),
                pltpu.VMEM((heads, chunk, dv), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((h, chunk, dv), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=bool(interpret),
        name="paged_latent_chunk_attention",
    )(table_row.astype(jnp.int32), jnp.reshape(start, (1,)).astype(jnp.int32),
      *(a.astype(dtype) for a in (q_n, q_r, k_up, v_up)), pool)


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
    slab: whole tiles where the program keeps this order, where a row of a
    ``[b, L, h*d]`` slab is one sublane of ``b`` tiles.  The engine's step
    keeps it (its slabs are parameters).  A loop's carry the compiler lays
    out as it likes, and for this function's two einsums it likes ``b``
    first: which is why a decode loop on a TPU reads through
    :func:`prefix_append_decode_attention` instead, whose operands fix the
    order.

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


_PREFIX_BLOCK = 16      # positions a copy
_PREFIX_ROWS = 64       # batch rows a copy
_PREFIX_VMEM = 64 << 20  # the kernel's blocks and their f32 products


def _prefix_row_tile(b: int, dtype) -> int:
    """Batch rows a block of :func:`prefix_append_decode_attention`: the
    largest divisor of ``b`` up to ``_PREFIX_ROWS`` that is whole sublane
    tiles of the slab's dtype, or 0 where there is none."""
    return next((t for t in range(min(b, _PREFIX_ROWS), 0, -1)
                 if b % t == 0 and t % _sublanes(dtype) == 0), 0)


def prefix_blocks_are_whole_tiles(slabs: jax.Array, num_heads: int) -> bool:
    """Stacked slabs ``[layers, L, b, h*d]`` bf16 or f32 (an int8 slab's
    scale a position is an operand the kernel does not take) whose position
    ``[b, h*d]`` is whole tiles that split into row tiles, with positions
    enough for a block and heads that fit a tile's lanes."""
    _, L, b, hd = slabs.shape
    return (slabs.dtype in (jnp.bfloat16, jnp.float32)
            and hd % _LANES == 0 and hd % num_heads == 0
            and num_heads <= _LANES and L >= _PREFIX_BLOCK
            and _prefix_row_tile(b, slabs.dtype) > 0)


def prefix_slabs_read_in_place(slabs: jax.Array, num_heads: int) -> bool:
    """Does a decode loop's step read these stacked self-attention slabs
    ``[layers, L, b, h*d]`` where they lie, the written prefix alone
    (:func:`prefix_append_decode_attention`), or a layer's slice whole
    (:func:`flat_append_decode_attention`)?  Decided at trace time from what
    the call can observe, no knob: the backend is a TPU (interpret mode is
    for tests), the program is not traced for a mesh, and the blocks are
    whole tiles.  That the positions from the step's own on are unwritten
    for EVERY row is the caller's to know (``Decoder``: no ring)."""
    return (jax.default_backend() == "tpu" and not traced_for_mesh()
            and prefix_blocks_are_whole_tiles(slabs, num_heads))


def _selected(x, sel, pieces):
    """``x [n, k]`` f32 times the 0/1 selector ``sel [k, m]`` through the
    MXU, exact as far as ``pieces`` bf16 pieces hold ``x``: 2 hold the
    product of two bf16 numbers whole (its 16 leading bits), 3 any f32.
    With ``sel [h*d, 128]`` a head's lanes are summed, in f32 over exact
    products; with its transpose a number a head is spread over the head's
    lanes."""
    out = None
    for _ in range(pieces):
        piece = x.astype(jnp.bfloat16)
        x = x - piece.astype(jnp.float32)
        part = jnp.dot(piece, sel, preferred_element_type=jnp.float32)
        out = part if out is None else out + part
    return out


def _key_mask(valid, first, n):
    """``valid [r, Lp]`` 0/1, a row's keys along the lanes -> the additive
    mask ``[n, r, 128]`` of keys ``first .. first + n - 1``, a key's number
    in every lane of its row: one 0/1 column selected a key, by the MXU
    (a lane that moves at run time is no slice the compiler takes)."""
    at = jax.lax.broadcasted_iota(jnp.int32, (valid.shape[1], _LANES), 0)
    keys = [jnp.dot(valid, (at == first + p).astype(valid.dtype),
                    preferred_element_type=jnp.float32) for p in range(n)]
    return jnp.where(jnp.stack(keys) > 0.5, 0.0, _NEG_INF_DENSE)


def _prefix_kernel(at_ref, q_ref, krow_ref, vrow_ref, bias_ref, sel_ref,
                   selt_ref, *refs, dtype, masked):
    """The whole read of one layer (``at_ref``: the step's position, the
    layer): the step's own row first (it is every
    row's running maximum to begin with, so no row is ever empty), then a
    loop over the LIVE blocks ``(positions, rows)``, positions outermost,
    each copied from the stacked slabs in HBM into one of two buffers while
    the block before it is computed on; the trip count is taken from ``cur``
    at run time, so a position not yet written is never copied.  A block
    that would run off the slab's end starts earlier instead and masks what
    the block before it has counted."""
    valid_ref = refs[0] if masked else None
    k_hbm, v_hbm, o_ref, kbuf, vbuf, sems, m_ref, l_ref, acc_ref = refs[masked:]
    block, rows, hd = kbuf.shape[1:]
    L, b = k_hbm.shape[1], k_hbm.shape[2]
    tiles = b // rows
    cur = jnp.minimum(at_ref[0], L - 1)     # the step's row has a position
    layer = at_ref[1]
    total = pl.cdiv(cur, block) * tiles
    f32 = jnp.float32

    def at(t):
        j, i = t // tiles, t % tiles
        return j, pl.multiple_of(i * rows, rows), jnp.minimum(j * block,
                                                              L - block)

    def fetch(t, slot):
        _, row0, start = at(t)
        src = (layer, pl.ds(start, block), pl.ds(row0, rows))
        return (pltpu.make_async_copy(k_hbm.at[src], kbuf.at[slot],
                                      sems.at[0, slot]),
                pltpu.make_async_copy(v_hbm.at[src], vbuf.at[slot],
                                      sems.at[1, slot]))

    @pl.when(total > 0)
    def _():
        for copy in fetch(0, 0):
            copy.start()

    sel, sel_t = sel_ref[...], selt_ref[...]
    pieces = 2 if kbuf.dtype == jnp.bfloat16 else 3

    own = _selected(krow_ref[...].astype(f32) * q_ref[...].astype(f32), sel,
                    pieces)
    own = own + bias_ref[cur]                              # [b, 128]
    if masked:
        own = own + _key_mask(valid_ref[...], cur, 1)[0]
    m_ref[...] = own
    l_ref[...] = jnp.ones_like(l_ref)
    acc_ref[...] = vrow_ref[...].astype(f32)

    def step(t, carry):
        slot = t % 2

        @pl.when(t + 1 < total)
        def _():
            for copy in fetch(t + 1, 1 - slot):
                copy.start()

        for copy in fetch(t, slot):
            copy.wait()
        j, row0, start = at(t)
        at_rows = pl.ds(row0, rows)
        q = q_ref[at_rows, :].astype(f32)                  # [rows, hd]
        s = _selected((kbuf[slot].astype(f32) * q[None]
                       ).reshape(block * rows, hd), sel, pieces)
        s = s.reshape(block, rows, _LANES) + bias_ref[pl.ds(start, block)]
        if masked:
            s = s + _key_mask(valid_ref[at_rows, :], start, block)
        pos = start + jax.lax.broadcasted_iota(jnp.int32, s.shape, 0)
        s = jnp.where(jnp.logical_and(pos >= j * block, pos < cur), s,
                      _MASK_FLOOR)
        m = m_ref[at_rows, :]
        m_new = jnp.maximum(m, s.max(0))
        alpha = jnp.exp(m - m_new)
        p = jnp.exp(s - m_new[None])
        l_ref[at_rows, :] = alpha * l_ref[at_rows, :] + p.sum(0)
        m_ref[at_rows, :] = m_new
        spread = jnp.dot(
            p.reshape(block * rows, _LANES).astype(dtype), sel_t,
            preferred_element_type=f32).reshape(block, rows, hd)
        acc_ref[at_rows, :] = (
            _selected(alpha, sel_t, 3) * acc_ref[at_rows, :]
            + (spread * vbuf[slot].astype(f32)).sum(0))
        return carry

    jax.lax.fori_loop(0, total, step, None)
    o_ref[...] = (acc_ref[...] / _selected(l_ref[...], sel_t, 3)
                  ).astype(o_ref.dtype)


@jax.named_scope("decode_attention")
def prefix_append_decode_attention(q, keys, values, layer, k_row, v_row, cur,
                                   bias_hl, kv_mask, num_heads, dtype,
                                   interpret=None):
    """:func:`flat_append_decode_attention` for a decode LOOP, over the
    stacked slabs where they lie: ``keys``/``values`` ``[layers, L, b,
    h*d]`` as they were before the step, of which this call reads layer
    ``layer`` and of that the positions ``< cur`` alone, the step's own row
    ``k_row``/``v_row`` ``[1, b, h*d]`` apart from them as there.  One
    Pallas kernel, K and V in one call; it takes the stacked arrays
    row-major as every ``pallas_call`` does, which is what keeps the loop's
    carry position-major: a position is ``b x h*d`` contiguous whole tiles,
    the append after the reads writes whole tiles, and ``L`` is no tiled
    dimension (the compiler left to itself lays the carry out batch-major
    for the flat read's two einsums: a row is then one sublane of every tile
    and 129 positions are stored as 144; docs/KERNELS.md).

    For a caller whose positions from ``cur`` on are unwritten for EVERY row
    (one causal prefix under one scalar position: ``generate``); a ring
    (``T5Engine``) has no such prefix and keeps the flat read.
    :func:`prefix_slabs_read_in_place` is the rule.

    Same mathematics as the flat read over the same keys (positions ``<
    cur`` and the row): operands in the slab's dtype, a head's products
    summed in f32 and exact before the sum (``_selected``), ``bias_hl``
    additive f32 ``[h, L]`` (its column ``cur`` is the row's), ``kv_mask``
    ``[b, L]`` or None, a running softmax in f32, the probabilities in
    ``dtype`` against V with the context accumulated in f32; the order of
    the sums apart.  q and the result ``[b, 1, h, d]``.

    ``layer`` rides to the kernel beside ``cur`` as a run-time scalar and the
    call is jitted, so the layers of a program share ONE trace and one
    lowering of the kernel (a trace a layer was 3 s of FLAN-T5-large's first
    call on the chip's host).

    VMEM (``t5base-batchgen``: blocks of 16 positions x 64 rows x 768
    bf16): two K and two V buffers 6.3 MB, the f32 products and
    probabilities of a block about 16 MB, q, the rows and the result 1.6
    MB, the running maximum, sum and context 1.0 MB.  ``interpret`` None:
    interpret mode off a TPU (tests)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    at = jnp.stack([jnp.asarray(cur, jnp.int32), jnp.asarray(layer, jnp.int32)])
    return _prefix_read(at, q, k_row, v_row, bias_hl, kv_mask, keys, values,
                        num_heads=num_heads, dtype=dtype,
                        interpret=bool(interpret))


@functools.partial(jax.jit, static_argnames=("num_heads", "dtype", "interpret"))
def _prefix_read(at, q, k_row, v_row, bias_hl, kv_mask, keys, values, *,
                 num_heads, dtype, interpret):
    """The kernel's call: ``at`` int32 ``[2]``, the step's position and the
    layer."""
    _, L, b, hd = keys.shape
    h, d = num_heads, hd // num_heads
    rows = _prefix_row_tile(b, keys.dtype)
    # sel[f, n]: lane f of a row belongs to head n (n < h; 128 wide for the MXU)
    sel = (jnp.arange(hd)[:, None] // d == jnp.arange(_LANES)[None, :])
    # a position a tile row, a head a lane
    bias = (jnp.zeros((L, 1, _LANES), jnp.float32) if bias_hl is None else
            pad_keys(bias_hl.T.astype(jnp.float32), _LANES)[:, None, :])

    masked = kv_mask is not None
    valid = []
    if masked:          # 0/1, a row's keys along whole lanes
        valid = [pad_keys((kv_mask > 0).astype(jnp.bfloat16), L + -L % _LANES)]

    def whole(shape):
        return pl.BlockSpec(shape, lambda i, *_: (0,) * len(shape))

    out = pl.pallas_call(
        functools.partial(_prefix_kernel, dtype=dtype, masked=masked),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(1,),
            in_specs=[
                whole((b, hd)), whole((b, hd)), whole((b, hd)),
                whole((L, 1, _LANES)), whole((hd, _LANES)),
                whole((_LANES, hd)), *(whole(v.shape) for v in valid),
                pl.BlockSpec(memory_space=pl.ANY),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=whole((b, hd)),
            scratch_shapes=[
                pltpu.VMEM((2, _PREFIX_BLOCK, rows, hd), keys.dtype),
                pltpu.VMEM((2, _PREFIX_BLOCK, rows, hd), values.dtype),
                pltpu.SemaphoreType.DMA((2, 2)),
                pltpu.VMEM((b, _LANES), jnp.float32),
                pltpu.VMEM((b, _LANES), jnp.float32),
                pltpu.VMEM((b, hd), jnp.float32),
            ]),
        out_shape=jax.ShapeDtypeStruct((b, hd), dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",),
            vmem_limit_bytes=_PREFIX_VMEM),
        interpret=interpret,
        name="prefix_append_decode_attention",
    )(at, q.reshape(b, hd).astype(dtype), k_row.reshape(b, hd),
      v_row.reshape(b, hd), bias, sel.astype(jnp.bfloat16),
      sel.T.astype(jnp.bfloat16), *valid, keys, values)
    return out.reshape(b, 1, h, d)


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
