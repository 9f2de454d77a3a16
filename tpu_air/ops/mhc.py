"""Manifold-constrained hyper-connections (mHC; Xie et al., arXiv:2512.24880):
the residual path of ``n`` streams that ``xing4_0`` publishes, as the plain
arithmetic of one sublayer.

A token's state between sublayers is ``X [n, C]`` (``n = hc_mult`` streams of
the hidden size) in the model's dtype.  A sublayer ``f`` (attention behind its
norm, the feed-forward behind its) has ``phi [n*C, n*n + 2n]``, ``b [n*n +
2n]`` and three scalars ``alpha``, all float32, and computes in float32::

    v = vec(X)                                       n*C numbers
    m = (mean(v^2) + rms_eps)^(-1/2) * (v phi)       RMSNorm(v) phi, no learned scale
    H~pre = a_pre m[0:n] + b[0:n]    H~post = a_post m[n:2n] + b[n:2n]
    H~res = a_res mat(m[2n:]) + mat(b[2n:])          n x n, row-major
    H_pre = sigmoid(H~pre)           H_post = 2 sigmoid(H~post)
    M = exp(clip(H~res, clamp));  `iters` times: M /= column sums + eps;  M /= row sums + eps
    h = sum_i H_pre[i] X[i];   y = f(h);   X'[i] = sum_j M[i, j] X[j] + H_post[i] y

:func:`pre` is everything up to ``h`` and the three raw maps, :func:`sinkhorn`
the projection of ``H~res`` onto the doubly stochastic matrices, :func:`post`
``X'``.  On entry every stream is the token's embedding row (:func:`expand`),
on exit the streams are summed (:func:`reduce`): Zhu et al., "Hyper-
Connections", arXiv:2409.19606.  Every leading dimension is a row: a chunk's
positions and a step's slots take the maps alike.

Plain ``jax.lax`` throughout, the Sinkhorn rounds a Python loop (``iters`` is
static and small) so that the compiler may fuse them: no kernel.  Each
function is one device scope (docs/OBSERVABILITY.md): ``mhc_expand``,
``mhc_pre``, ``mhc_sinkhorn``, ``mhc_post``, ``mhc_reduce``.
"""

from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp

Array = jax.Array


def expand(x: Array, n: int) -> Array:
    """``[..., C] -> [..., n, C]``: every stream the token's row."""
    with jax.named_scope("mhc_expand"):
        return jnp.broadcast_to(x[..., None, :],
                                x.shape[:-1] + (n, x.shape[-1]))


def reduce(streams: Array) -> Array:
    """``[..., n, C] -> [..., C]``: the sum of the streams, added in
    float32."""
    with jax.named_scope("mhc_reduce"):
        return streams.astype(jnp.float32).sum(-2).astype(streams.dtype)


def pre(streams: Array, phi: Array, b: Array, alpha: Array,
        rms_eps: float) -> Tuple[Array, Array, Array]:
    """``streams [..., n, C]`` -> ``(h [..., C]`` in the streams' dtype,
    ``H_post [..., n]``, ``H~res [..., n, n])``, the two maps float32.  The
    product onto the ``n*n + 2n`` numbers is float32 at the highest
    precision: they go through ``exp`` and twenty normalisations."""
    n, c = streams.shape[-2:]
    with jax.named_scope("mhc_pre"):
        x = streams.astype(jnp.float32)
        v = x.reshape(x.shape[:-2] + (n * c,))
        scale = jax.lax.rsqrt(jnp.mean(jnp.square(v), -1, keepdims=True)
                              + rms_eps)
        m = scale * jnp.matmul(v, phi.astype(jnp.float32),
                               precision=jax.lax.Precision.HIGHEST)
        b = b.astype(jnp.float32)
        h_pre = jax.nn.sigmoid(alpha[0] * m[..., :n] + b[:n])
        h_post = 2.0 * jax.nn.sigmoid(alpha[1] * m[..., n:2 * n] + b[n:2 * n])
        res = (alpha[2] * m[..., 2 * n:] + b[2 * n:]).reshape(
            m.shape[:-1] + (n, n))
        h = (h_pre[..., None] * x).sum(-2).astype(streams.dtype)
    return h, h_post, res


def sinkhorn(res: Array, iters: int, eps: float,
             clamp: Tuple[float, float]) -> Array:
    """``H~res [..., n, n]`` -> ``H_res``: ``exp`` of the clamped matrix,
    then ``iters`` rounds of columns over their sums, rows over theirs
    (the paper's ``T_r(T_c(.))``), ``eps`` in both denominators."""
    with jax.named_scope("mhc_sinkhorn"):
        m = jnp.exp(jnp.clip(res.astype(jnp.float32), clamp[0], clamp[1]))
        for _ in range(iters):
            m = m / (m.sum(-2, keepdims=True) + eps)
            m = m / (m.sum(-1, keepdims=True) + eps)
    return m


def post(streams: Array, y: Array, h_res: Array, h_post: Array) -> Array:
    """``X'[i] = sum_j H_res[i, j] X[j] + H_post[i] y`` in float32, handed
    back in the streams' dtype.  ``y [..., C]`` is the sublayer's output."""
    n = streams.shape[-2]
    with jax.named_scope("mhc_post"):
        x = streams.astype(jnp.float32)
        out = h_post[..., None] * y.astype(jnp.float32)[..., None, :]
        # n multiply-adds over [.., n, C], not a product with an [n, n, C] middle
        for j in range(n):
            out = out + h_res[..., :, j, None] * x[..., j, None, :]
        return out.astype(streams.dtype)
