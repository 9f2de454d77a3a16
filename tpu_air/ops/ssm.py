"""The Mamba-1 selective state-space recurrence, in the two shapes a serving
engine needs it: a CHUNK of positions from a carried state (prefill, and the
whole-sequence forward) and ONE token over every slot (decode).

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
