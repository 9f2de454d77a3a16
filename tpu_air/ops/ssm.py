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
hundreds of positions.  Plain ``jax.lax``; there is one path.

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

import jax
import jax.numpy as jnp


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


def ssd_state_update(u, dt, A, B, C, D, state, live):
    """One token of the Mamba-2 recurrence over every row.

    ``u [b, H, P]``; ``dt [b, H]``; ``A [H]``; ``B``, ``C`` ``[b, G, N]``;
    ``D [H]``; ``state [b, H, P, N]`` float32; ``live [b]`` bool.  Returns
    ``(y [b, H, P] float32, state')``; a row with ``live`` false keeps its
    state bit for bit (its ``y`` is don't-care).  One elementwise pass over
    the state: read once, written once, ``y`` reduced over the lanes in the
    same pass."""
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
