"""The selective state-space recurrences (Mamba-1, and Mamba-2 at the end of
the file), each in the two shapes a serving engine needs it: a CHUNK of
positions from a carried state (prefill, and the whole-sequence forward) and
ONE token over every slot (decode).

Per channel ``c`` and state ``n``, with ``A = -exp(A_log)``::

    s_t[c, n] = exp(dt_t[c] * A[c, n]) * s_{t-1}[c, n] + dt_t[c] * B_t[n] * u_t[c]
    y_t[c]    = sum_n C_t[n] * s_t[c, n] + D[c] * u_t[c]

LAYOUT.  The state is held STATE-MAJOR, ``[rows, d_state, d_inner]``, not the
published ``[d_inner, d_state]``: the chip tiles an array's last two
dimensions to (8, 128), so 16 states in the minor dimension would be padded
to 128 lanes, eight times the bytes of the largest stream of a decode step.
``d_inner`` minor is whole lanes.  ``A`` is taken as ``[d_state, d_inner]``
likewise (the caller transposes the published ``A_log``: a small tensor).

MASKING.  A position that is not real (the padding of a prompt's last chunk;
in decode a row that is free or mid-prefill) must leave the state as it was:
pages are overwritten position by position, a state is accumulated.  Both
functions take that as data (``valid_len`` / ``live``), and a held state is
the input state BIT FOR BIT (a ``where``, not a multiplication by one).

Everything is float32 whatever the model computes in: the state integrates
hundreds of positions.  Plain ``jax.lax``, with one exception: on a TPU the
Mamba-2 one-token update moves the state of the LIVE rows alone, where it
lies (``ssd_rows_update``, a Pallas kernel; ``state_rows_move_in_place`` is
the rule, decided at trace time from what the call can observe).

MAMBA-2 (``ssd_chunk``, ``ssd_state_update``).  A head ``h`` of ``P``
channels has ONE scalar decay a position, and the heads of a group ``g``
share ``B`` and ``C``; with ``A[h] < 0``::

    S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] * u_t[h] (x) B_t[g(h)]     [P, N]
    y_t[h] = S_t[h] C_t[g(h)] + D[h] u_t[h]

The state is held as published, ``[rows, H, P, N]``: ``N`` (128) minor is
whole lanes and ``P`` (64) whole sublanes, so no state-major turn is needed.
A scalar decay a head is what allows the BLOCK form (state-space duality): in
a block of ``Q`` positions ``y = (C B^T (.) L) (dt u) + exp(cum a) C S_in``
with ``L[t, s] = exp(sum of a over s+1..t)`` for ``s <= t``, three matrix
products a head instead of ``Q`` dependent steps over a 4 MB state, and the
state leaves the block as ``exp(cum a_Q) S_in + sum_s exp(cum a_Q - cum a_s)
dt_s u_s (x) B_s``.  Padding is ``dt = 0`` there: decay 1 and no drive, so a
block of padding alone multiplies the state by 1.0 and adds 0.0, bit for bit
what came in.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import traced_for_mesh

_LANES = 128
# the tile of one live row a grid step moves, [1, heads, P, N] float32, and the
# heads one step of its loop takes: chosen on the chip (PERF.md, PR 48)
_STATE_TILE_BYTES = 2 << 20
_HEADS_A_LOOP_STEP = 4


def causal_conv_chunk(x, tail, weight, bias, valid_len):
    """Depthwise causal convolution of a chunk that continues a sequence.

    ``x [b, l, c]``: the chunk's inputs; ``tail [b, k-1, c]``: the last
    ``k-1`` inputs before it (zeros at a sequence's start); ``weight [k, c]``
    (tap ``k-1`` multiplies the current position), ``bias [c]``;
    ``valid_len [b]``: how many of the ``l`` positions are real.  Returns
    ``(y [b, l, c], tail')``: ``tail'`` holds the last ``k-1`` REAL inputs,
    so padded positions never enter it."""
    k = weight.shape[0]
    l = x.shape[1]
    ext = jnp.concatenate([tail.astype(x.dtype), x], axis=1)  # [b, k-1+l, c]
    w = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32) + sum(
        w[j] * ext[:, j:j + l].astype(jnp.float32) for j in range(k))
    new_tail = jax.vmap(
        lambda e, n: jax.lax.dynamic_slice_in_dim(e, n, k - 1, axis=0))(
            ext, valid_len.astype(jnp.int32))
    return y, new_tail.astype(tail.dtype)


def causal_conv_step(x, tail, weight, bias, live):
    """:func:`causal_conv_chunk` for ONE position of every row, on the tail as
    it is STORED: ``x [b, c]``, ``tail [b, (k-1) * c]`` flat (position-major:
    columns ``j*c .. (j+1)*c`` are input ``t - (k-1) + j``), ``live [b]``.
    Every slice is whole lanes; nothing is viewed ``[b, k-1, c]`` (three
    sublanes of a padded tile).  Returns ``(y [b, c], tail')``; a row that is
    not live keeps its tail."""
    k, c = weight.shape
    w = weight.astype(jnp.float32)
    y = bias.astype(jnp.float32) + w[k - 1] * x.astype(jnp.float32) + sum(
        w[j] * tail[:, j * c:(j + 1) * c].astype(jnp.float32)
        for j in range(k - 1))
    moved = jnp.concatenate([tail[:, c:], x.astype(tail.dtype)], axis=1)
    return y, jnp.where(live[:, None], moved, tail)


def selective_scan_chunk(u, dt, A, B, C, D, state, valid_len):
    """``l`` positions of the recurrence from a carried state.

    ``u``, ``dt`` ``[b, l, c]``; ``A [n, c]`` (negative); ``B``, ``C``
    ``[b, l, n]``; ``D [c]``; ``state [b, n, c]`` float32; ``valid_len [b]``.
    Returns ``(y [b, l, c] float32, state')``.  Positions at or past
    ``valid_len`` leave the state untouched (their ``y`` is don't-care).

    Sequential over positions (the recurrence is), with the decay and input
    terms of all positions made beforehand in one vector pass, so an
    iteration is two multiply-adds over ``[b, n, c]`` and a reduction."""
    f32 = jnp.float32
    u, dt = u.astype(f32), dt.astype(f32)
    l = u.shape[1]
    real = jnp.arange(l)[None, :] < valid_len.astype(jnp.int32)[:, None]
    decay = jnp.exp(dt[:, :, None, :] * A.astype(f32)[None, None])
    drive = (dt * u)[:, :, None, :] * B.astype(f32)[..., None]  # [b,l,n,c]

    def step(s, xs):
        a, x, c_t, keep = xs
        new = a * s + x
        y = jnp.einsum("bn,bnc->bc", c_t, new)
        return jnp.where(keep[:, None, None], new, s), y

    xs = (jnp.swapaxes(decay, 0, 1), jnp.swapaxes(drive, 0, 1),
          jnp.swapaxes(C.astype(f32), 0, 1), real.T)
    state, ys = jax.lax.scan(step, state.astype(f32), xs, unroll=8)
    return jnp.swapaxes(ys, 0, 1) + D.astype(f32) * u, state


def selective_state_update(u, dt, A, B, C, D, state, live):
    """One token of the recurrence over every row.

    ``u``, ``dt`` ``[b, c]``; ``A [n, c]``; ``B``, ``C`` ``[b, n]``;
    ``D [c]``; ``state [b, n, c]`` float32; ``live [b]`` bool.  Returns
    ``(y [b, c] float32, state')``; a row with ``live`` false keeps its state
    bit for bit (its ``y`` is don't-care).  One elementwise pass over the
    state: read once, written once."""
    f32 = jnp.float32
    u, dt = u.astype(f32), dt.astype(f32)
    new = (jnp.exp(dt[:, None, :] * A.astype(f32)[None]) * state
           + (dt * u)[:, None, :] * B.astype(f32)[:, :, None])
    y = jnp.einsum("bn,bnc->bc", C.astype(f32), new) + D.astype(f32) * u
    return y, jnp.where(live[:, None, None], new, state)


def ssd_chunk(u, dt, A, B, C, D, state, valid_len, block: int = 128):
    """``l`` positions of the Mamba-2 recurrence from a carried state, in the
    block form (module doc).

    ``u [b, l, H, P]``; ``dt [b, l, H]`` (after softplus); ``A [H]``
    (negative); ``B``, ``C`` ``[b, l, G, N]`` (head ``h`` reads group ``h //
    (H / G)``); ``D [H]``; ``state [b, H, P, N]`` float32; ``valid_len [b]``.
    Returns ``(y [b, l, H, P] float32, state')``.  Positions at or past
    ``valid_len`` leave the state untouched (their ``y`` is don't-care).
    ``l`` is padded to whole blocks of ``block`` positions; the blocks are a
    ``lax.scan`` that carries the state.  Every product is float32 at the
    highest precision: the state sums hundreds of positions."""
    f32 = jnp.float32
    hi = jax.lax.Precision.HIGHEST
    b, l, H, P = u.shape
    G, N = B.shape[2], B.shape[3]
    k = H // G
    q = min(block, l)
    nb = -(-l // q)
    pad = nb * q - l
    real = jnp.arange(l)[None, :] < valid_len.astype(jnp.int32)[:, None]
    dt = jnp.where(real[:, :, None], dt.astype(f32), 0.0)

    def blocks(x):
        x = jnp.pad(x.astype(f32), ((0, 0), (0, pad)) + ((0, 0),) *
                    (x.ndim - 2))
        return jnp.swapaxes(x.reshape((b, nb, q) + x.shape[2:]), 0, 1)

    tri = jnp.tril(jnp.ones((q, q), bool))

    def one(s, xs):
        u_, dt_, B_, C_ = xs                       # [b, q, ..]
        a = dt_ * A.astype(f32)                    # [b, q, H], <= 0
        cum = jnp.cumsum(a, axis=1)
        # L[t, s] = exp(cum_t - cum_s) where s <= t (<= 1: no overflow)
        diff = cum[:, :, None, :] - cum[:, None, :, :]       # [b, t, s, H]
        decay = jnp.exp(jnp.where(tri[None, :, :, None], diff, -jnp.inf))
        cb = jnp.einsum("btgn,bsgn->btsg", C_, B_, precision=hi)
        m = decay.reshape(b, q, q, G, k) * cb[..., None]     # [b,t,s,G,k]
        x = (dt_[..., None] * u_).reshape(b, q, G, k, P)     # dt u
        y = jnp.einsum("btsgk,bsgkp->btgkp", m, x, precision=hi)
        s5 = s.reshape(b, G, k, P, N)
        y = y + jnp.exp(cum).reshape(b, q, G, k)[..., None] * jnp.einsum(
            "btgn,bgkpn->btgkp", C_, s5, precision=hi)
        last = cum[:, -1]                                    # [b, H]
        w = jnp.exp(last[:, None] - cum).reshape(b, q, G, k)
        new = (jnp.exp(last).reshape(b, G, k)[..., None, None] * s5
               + jnp.einsum("bsgkp,bsgn->bgkpn", w[..., None] * x, B_,
                            precision=hi))
        return new.reshape(b, H, P, N), y.reshape(b, q, H, P)

    state, ys = jax.lax.scan(one, state.astype(f32),
                             (blocks(u), blocks(dt), blocks(B), blocks(C)))
    y = jnp.swapaxes(ys, 0, 1).reshape(b, nb * q, H, P)[:, :l]
    return y + D.astype(f32)[:, None] * u.astype(f32), state


def state_rows_move_in_place(state: jax.Array) -> bool:
    """Does :func:`ssd_state_update` move the state of the live rows alone,
    where it lies in the pool ``[rows, H, P, N]`` (:func:`ssd_rows_update`),
    or pass over every row?  Decided at trace time from what the call can
    observe, no knob (``decode_attention.latent_pages_read_in_place`` is the
    same rule for the latent pages): the backend is a TPU (interpret mode is
    for tests), the program is not traced for a mesh (XLA cannot partition a
    Mosaic call), and a head's state ``[P, N]`` is whole float32 tiles."""
    return (jax.default_backend() == "tpu" and not traced_for_mesh()
            and state.ndim == 4 and state.dtype == jnp.float32
            and state.shape[2] % 8 == 0 and state.shape[3] % _LANES == 0)


def _head_tile(H: int, P: int, N: int) -> int:
    """Heads a grid step moves: all of a row's where they fit
    ``_STATE_TILE_BYTES``, else the most that divide ``H`` into blocks
    ``[heads, P]`` of whole sublanes and fit (a row's all where none does)."""
    most = _STATE_TILE_BYTES // (P * N * 4)
    if most >= H:
        return H
    return max((h for h in range(8, most + 1, 8) if H % h == 0), default=H)


def _ssd_rows_kernel(rows_ref, count_ref, decay_ref, drive_ref, b_ref, c_ref,
                     s_ref, y_ref, o_ref, *, per_group):
    """Grid step ``(i, j)``: tile ``j`` (``hb`` heads) of the ``i``-th live
    row, a head at a time.  The blocks were chosen by the index maps from
    the prefetched row list; a step past the live count sits on the last
    live tile (nothing moves for it) with its body off."""
    i, j, n = pl.program_id(0), pl.program_id(1), count_ref[0]
    hb, P, _ = s_ref.shape[1:]
    H = decay_ref.shape[2]

    @pl.when(i < n)
    def _():
        # a [1, P] row as a [P, 1] column and back: through the diagonal, so
        # the head index stays a loop variable (sublanes) and nothing is
        # sliced along lanes
        eye = (jax.lax.broadcasted_iota(jnp.int32, (P, P), 0)
               == jax.lax.broadcasted_iota(jnp.int32, (P, P), 1))
        head_of_lane = jax.lax.broadcasted_iota(jnp.int32, (1, H), 1)
        decay = decay_ref[0]                                     # [1, H]

        def head(t):
            h = j * hb + t
            g = h // per_group
            a = jnp.sum(jnp.where(head_of_lane == h, decay, 0.0), axis=1,
                        keepdims=True)                           # [1, 1]
            x = jnp.sum(jnp.where(eye, drive_ref[0, pl.ds(t, 1), :], 0.0),
                        axis=1, keepdims=True)                   # [P, 1]
            new = a * s_ref[0, t] + x * b_ref[0, pl.ds(g, 1), :]
            o_ref[0, t] = new
            y = jnp.sum(new * c_ref[0, pl.ds(g, 1), :], axis=1,
                        keepdims=True)                           # [P, 1]
            y_ref[0, pl.ds(t, 1), :] = jnp.sum(
                jnp.where(eye, y, 0.0), axis=0, keepdims=True)   # [1, P]

        # a head is one chain of dependent operations, two reductions over
        # lanes in it, and only the heads of one loop step overlap: one at a
        # time the pass is bound by that chain at twice the copies' time
        step = math.gcd(hb, _HEADS_A_LOOP_STEP)

        def heads(q, carry):
            for r in range(step):
                head(q * step + r)
            return carry

        jax.lax.fori_loop(0, hb // step, heads, None)

    # no live row: the one tile the steps sit on goes back as it came (an
    # output block nobody wrote would be written back as it is)
    @pl.when((n == 0) & (i == 0) & (j == 0))
    def _():
        o_ref[...] = s_ref[...]


def ssd_rows_update(u, dt, A, B, C, D, state, live, head_tile=None,
                    interpret=None):
    """:func:`ssd_state_update` over the LIVE rows alone, IN PLACE: the pool
    ``state [b, H, P, N]`` float32 goes in and comes out as one buffer
    (``input_output_aliases``) and a grid step moves one tile ``[1, hb, P,
    N]`` of one live row through fast memory: read once, written once where
    it lay.  The live rows' indices (live ones first, in slot order) and
    their count are made here from ``live`` and ride as scalar-prefetch
    arguments; the block specs index by them.  A row that is not live is
    never touched; with no live row one tile is written back as it was read.
    The formula and its order are the ``jnp`` form's, in float32; ``y`` of a
    row that is not live is zeros.

    VMEM (the cell: ``hb`` = 64 heads of 64 x 128 float32): the state tile in
    and out, two buffers each, 8 MB.  ``head_tile`` None:
    :func:`_head_tile`; ``interpret`` None: interpret mode off a TPU
    (tests)."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    f32 = jnp.float32
    b, H, P = u.shape
    G, N = B.shape[1], B.shape[2]
    hb = head_tile or _head_tile(H, P, N)
    tiles = H // hb
    u, dt = u.astype(f32), dt.astype(f32)
    rows = jnp.argsort(jnp.logical_not(live), stable=True).astype(jnp.int32)
    count = jnp.sum(live, dtype=jnp.int32)[None]

    def at(i, j, rows_ref, count_ref):
        """(row, tile) of grid step ``(i, j)``; past the live count, the last
        live row's last tile (with none live, a tile of row ``rows[0]``)."""
        n = count_ref[0]
        return (rows_ref[jnp.minimum(i, jnp.maximum(n - 1, 0))],
                jnp.where(i < n, j, tiles - 1))

    a_row = lambda *s: (at(*s)[0], 0, 0)                         # noqa: E731
    a_tile = lambda *s: at(*s) + (0,)                            # noqa: E731
    a_state = lambda *s: at(*s) + (0, 0)                         # noqa: E731
    y, state = pl.pallas_call(
        functools.partial(_ssd_rows_kernel, per_group=H // G),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(b, tiles),
            in_specs=[
                pl.BlockSpec((1, 1, H), a_row),
                pl.BlockSpec((1, hb, P), a_tile),
                pl.BlockSpec((1, G, N), a_row),
                pl.BlockSpec((1, G, N), a_row),
                pl.BlockSpec((1, hb, P, N), a_state),
            ],
            out_specs=[
                pl.BlockSpec((1, hb, P), a_tile),
                pl.BlockSpec((1, hb, P, N), a_state),
            ]),
        out_shape=[jax.ShapeDtypeStruct((b, H, P), f32),
                   jax.ShapeDtypeStruct(state.shape, f32)],
        # operands count the two prefetched scalars
        input_output_aliases={6: 1},
        # no ``vmem_limit_bytes``: the tile is sized to the default scoped
        # limit, and a call that asks for more changes how the compiler tiles
        # OTHER fusions of the program (PERF.md, PR 48: an attention softmax
        # eleven times slower)
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=bool(interpret),
        name="ssd_rows_update",
    )(rows, count, jnp.exp(dt * A.astype(f32))[:, None, :], dt[..., None] * u,
      B.astype(f32), C.astype(f32), state)
    y = y + D.astype(f32)[:, None] * u
    return jnp.where(live[:, None, None], y, 0.0), state


def ssd_state_update(u, dt, A, B, C, D, state, live):
    """One token of the Mamba-2 recurrence over every row.

    ``u [b, H, P]``; ``dt [b, H]``; ``A [H]``; ``B``, ``C`` ``[b, G, N]``;
    ``D [H]``; ``state [b, H, P, N]`` float32; ``live [b]`` bool.  Returns
    ``(y [b, H, P] float32, state')``; a row with ``live`` false keeps its
    state bit for bit.  Where :func:`state_rows_move_in_place` says so (a
    TPU) the live rows' state alone is read and written, where it lies
    (:func:`ssd_rows_update`), and ``y`` of a row that is not live is ZEROS;
    elsewhere one elementwise pass over every row's state, read once and
    written once, ``y`` reduced over the lanes in the same pass, and ``y`` of
    a row that is not live is don't-care."""
    if state_rows_move_in_place(state):
        return ssd_rows_update(u, dt, A, B, C, D, state, live)
    f32 = jnp.float32
    b, H, P = u.shape
    G, N = B.shape[1], B.shape[2]
    k = H // G
    u, dt = u.astype(f32), dt.astype(f32)
    s5 = state.astype(f32).reshape(b, G, k, P, N)
    decay = jnp.exp(dt * A.astype(f32)).reshape(b, G, k, 1, 1)
    drive = (dt[..., None] * u).reshape(b, G, k, P, 1)
    new = decay * s5 + drive * B.astype(f32)[:, :, None, None, :]
    y = (new * C.astype(f32)[:, :, None, None, :]).sum(-1).reshape(b, H, P)
    new = new.reshape(b, H, P, N)
    return (y + D.astype(f32)[:, None] * u,
            jnp.where(live[:, None, None, None], new, state))
