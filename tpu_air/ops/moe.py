"""The routed expert product of a sparse-expert feed-forward layer.

``expert_ffn`` computes, for every token ``t`` and each of its ``k`` chosen
experts ``e``, ``w[t, e] * down_e(silu(gate_e x[t]) * up_e x[t])`` (without a
gate matrix: ``w[t, e] * down_e(relu(up_e x[t])^2)``, the two-matrix expert
``nemotron_h`` publishes) and sums over the ``k``: exactly the published sum.  There is no capacity and no
fixed group size, so no assignment is dropped or re-routed however uneven
the load.  Inputs in the model's dtype, accumulation in float32.

The caller may hold only SOME of the experts the router scores (an
expert-parallel rank: ``partial``): an assignment to an expert held elsewhere
comes in with the id one past the last held expert, sorts behind every held
group, belongs to no group, and no product touches its row; the rows past the
last group come out as zeros.  Shapes stay static: such rows cost the sort
and nothing else.

One formulation, no switch: rows are sorted by expert and the three
products run as grouped matmuls over the uneven groups (the Pallas
``megablox`` kernel on a TPU, ``jax.lax.ragged_dot`` elsewhere — the same
mathematics; the kernel exists only for the TPU).  The other formulations
that were measured against it on the v5e are in PERF.md (PR 27).
"""

from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

Array = jax.Array

# (rows, contraction, columns) of one kernel tile: the fastest of eight
# measured on the v5e at 512 and 1024 sorted rows over 64 groups of
# 2048 x 1024 (PERF.md, PR 27).  A device trace names the kernel's calls
# ``gmm``, ``gmm.1``, ...
GMM_TILING = (128, 2048, 1024)
# where GMM_TILING does not divide the shapes (a contraction of 7168 = 7 x
# 1024): the fastest of twelve tried on the v5e (PERF.md, PR 43) at 16
# groups of 7168 x 2048, 64 to 1024 held rows among 1024 to 3072 sorted:
# 0.67 to 0.80 ms a product where the 470 MB of weights take 0.57 at the HBM
# peak; (128, 1024, 1024) reads 0.71 to 0.83, (128, 3584, 512) 0.85 to 0.97,
# and a contraction tile of 1792 or more beside 2048 columns does not fit
# VMEM.  The down product (2048 x 7168) keeps GMM_TILING, the fastest of ten
# there (0.64 to 0.71 ms).  PERF.md, PR 43.
GMM_TILING_K1024 = (128, 1024, 2048)
# where neither divides them (a side of 2688 = 21 x 128, which 1024 and 2048
# columns do not divide): the WHOLE matrix a tile, so 128 x 1024 x 2688 for
# nemotron_h's up product and 128 x 2688 x 1024 for its down product (5.5 MB
# a tile: two buffers of it fit VMEM beside the rows).  The fastest of six a
# product tried on the v5e (PERF.md, PR 47) at 128 groups of 1024 x 2688 and
# of 2688 x 1024, 704 to 2112 held rows among 2816 to 8448 sorted: 1.05 to
# 1.10 ms (up) and 1.04 to 1.07 ms (down) where the 705 MB of weights take
# 0.86 at the HBM peak; 896 of the 2688 a tile reads 1.07 to 1.13 (up) and
# 1.18 to 1.23 (down), 384 of them 1.16 to 1.27, 256 rows a tile 1.15 to
# 1.36, a tile of 1344 does not lower, and ``ragged_dot`` 2.9 to 5.4.
GMM_TILING_WHOLE = (128, 2688, 2688)


def gmm_tiling(m: int, kdim: int, n: int):
    """The kernel tile for ``[m, kdim] x [g, kdim, n]``: the first of the
    measured tilings whose every side divides the shapes and is whole lanes,
    or None (no kernel: the shapes go to ``ragged_dot``)."""
    for tiling in (GMM_TILING, GMM_TILING_K1024, GMM_TILING_WHOLE):
        tm, tk, tn = tiling[0], min(tiling[1], kdim), min(tiling[2], n)
        if (m % tm == 0 and kdim % tk == 0 and n % tn == 0
                and tk % 128 == 0 and tn % 128 == 0):
            return tm, tk, tn
    return None


def _grouped(lhs: Array, rhs: Array, sizes: Array) -> Array:
    """``lhs [m, k]`` (rows sorted by group) times ``rhs [g, k, n]`` with
    ``sizes [g]`` rows a group -> ``[m, n]`` float32.  Rows past the last
    group are undefined (the kernel never visits them)."""
    m, kdim = lhs.shape
    tiling = gmm_tiling(m, kdim, rhs.shape[-1])
    if jax.default_backend() == "tpu" and tiling is not None:
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
                   tiling=tiling)
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


def expert_ffn(x: Array, chosen: Array, weights: Array,
               gate: Optional[Array], up: Array, down: Array,
               partial: bool = False) -> Array:
    """``x [t, d]``; ``chosen [t, k]`` int expert ids; ``weights [t, k]``
    float32; ``gate``/``up`` ``[e, d, f]``, ``down [e, f, d]`` -> ``[t, d]``
    float32.  ``gate`` None: two-matrix experts around a squared ReLU.
    ``partial``: ``chosen`` may hold the id ``e``, an expert held elsewhere
    (module doc)."""
    t, k = chosen.shape
    e = up.shape[0]
    with jax.named_scope("moe_sort"):
        flat = chosen.reshape(-1)
        order = jnp.argsort(flat, stable=True)       # assignments by expert
        sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
        xs = x[order // k]                           # [t*k, d]
    with jax.named_scope("moe_experts"):
        if gate is None:
            h = jnp.square(jax.nn.relu(_grouped(xs, up, sizes))).astype(
                x.dtype)
        else:
            h = (jax.nn.silu(_grouped(xs, gate, sizes))
                 * _grouped(xs, up, sizes)).astype(x.dtype)
        y = _grouped(h, down, sizes)
    with jax.named_scope("moe_combine"):
        y = y * weights.reshape(-1)[order][:, None]
        if partial:
            # rows of no group hold whatever the kernel's buffer held
            y = jnp.where((flat[order] < e)[:, None], y, 0.0)
        back = jnp.zeros((t * k, y.shape[-1]), jnp.float32).at[order].set(y)
        return back.reshape(t, k, -1).sum(1)
