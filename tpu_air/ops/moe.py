"""The routed expert product of a sparse-expert feed-forward layer.

``expert_ffn`` computes, for every token ``t`` and each of its ``k`` chosen
experts ``e``, ``w[t, e] * down_e(silu(gate_e x[t]) * up_e x[t])`` (without a
gate matrix: ``w[t, e] * down_e(relu(up_e x[t])^2)``, the two-matrix expert
``nemotron_h`` publishes) and sums over the ``k``: exactly the published sum.  There is no capacity and no
fixed group size, so no assignment is dropped or re-routed however uneven
the load.  Inputs in the model's dtype, accumulation in float32.

The caller may hold only SOME of the experts the router scores (an
expert-parallel rank): an assignment to an expert held elsewhere comes in
with the id one past the last held expert, sorts behind every held group,
belongs to no group, no product touches its row and the sum takes exactly
0.0 for it.  Shapes stay static: such rows cost the sort and nothing else.

One formulation, no switch: rows are sorted by expert and the three
products run as grouped matmuls over the uneven groups (the Pallas
``megablox`` kernel on a TPU, ``jax.lax.ragged_dot`` elsewhere — the same
mathematics; the kernel exists only for the TPU).  The other formulations
that were measured against it on the v5e are in PERF.md (PR 27).

The sum over the ``k`` (the scope ``moe_combine``) reads each product row at
most once.  A caller that holds every expert is the case in which every row
is held: one form for both.  On a TPU it is :func:`held_rows_sum`, a Pallas
kernel over the held prefix of the sorted rows that writes the ``[t, d]``
result alone; elsewhere, under ``jax.grad`` and where the shapes are not
whole tiles, :func:`gathered_sum`, each token's rows gathered into the
reduction (PERF.md, PR 49, has both against the scatter that was there).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from .flash_attention import traced_for_mesh

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
# where none of the three divides them (a side of 3584 = 7 x 512: xing4_0's
# experts, 3584 x 1024 and back): HALF the long side a tile, 128 x 1792 x
# 1024 for the up and gate products and 128 x 1024 x 1792 for the down
# product (3.7 MB a tile; the whole matrix, 7.3 MB, does not fit VMEM twice:
# the chip's compiler refuses it).  Tried on the v5e (PERF.md, PR 58) at 64
# groups of 3584 x 1024 and of 1024 x 3584, 256 / 512 / 1024 / 1280 sorted
# rows, the wall time of one jitted call in ms (0.58-0.60 of it the call's
# dispatch and read-back, which an empty program reads; the 470 MB of weights
# take 0.57 at the HBM peak):
#   up    (128,1792,1024) 1.32-1.48   (128,512,1024) 1.34-1.46
#         (128,896,1024)  1.33-1.50   (128,3584,512) 1.25-1.54
#         (128,1792,512)  1.39-1.59   (128,896,512)  1.40-1.57
#         (128,512,512)   1.45-2.04   (256,1792,1024) 1.36-1.54
#         ragged_dot      1.72-2.25
#   down  (128,1024,1792) 1.27-1.41   (128,1024,896) 1.27-1.44
#         (128,1024,512)  1.30-1.46   (128,512,3584) 1.28-1.49
#         (128,512,1792)  1.29-1.53   (128,512,896)  1.32-1.52
#         (128,512,512)   1.61-2.03   (256,1024,1792) 1.29-1.41
#         ragged_dot      1.69-2.30
# The tiles that keep the short side whole lie within 3 % of one another at
# every row count; this one is the fastest or second fastest of the down
# product throughout and of the up product up to 512 rows, and ONE tuple
# names both (a contraction tile of 896 beside 1024 columns cannot: 1024 is
# no multiple of 896).  In the cell's own mixed step the twelve products
# read 80 % of their HBM roofline (moe_held_expert_roofline, PERF.md PR 58).
GMM_TILING_HALF = (128, 1792, 1792)


def gmm_tiling(m: int, kdim: int, n: int):
    """The kernel tile for ``[m, kdim] x [g, kdim, n]``: the first of the
    measured tilings whose every side divides the shapes and is whole lanes,
    or None (no kernel: the shapes go to ``ragged_dot``)."""
    for tiling in (GMM_TILING, GMM_TILING_K1024, GMM_TILING_WHOLE,
                   GMM_TILING_HALF):
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


# sorted rows a grid step of the combine's kernel takes: the grouped product's
# own row tile, so the same shapes are whole tiles for both
_COMBINE_ROWS = 128
# the most a tile ``[t, columns]`` float32 of the combine's result may take:
# two buffers of it, two of the rows' tile and the products' temporaries stay
# inside the default scoped VMEM (16 MB; no ``vmem_limit_bytes``: PERF.md,
# PR 48)
_COMBINE_TILE_BYTES = 2 << 20


def combine_tile(t: int, m: int, d: int) -> Optional[int]:
    """Columns a grid step of :func:`held_rows_sum` takes for ``m`` sorted
    rows of ``d`` numbers summed into ``t`` tokens: the widest of 2048, 1024,
    512 (or, of a narrower ``d``, all of it) that divides ``d`` into whole
    lanes and keeps the result's tile within ``_COMBINE_TILE_BYTES``, or None
    (no whole tiles, or more tokens than one tile holds: 1024 at 512
    columns): :func:`gathered_sum`."""
    if m % _COMBINE_ROWS or t % 8:
        return None
    return next((c for c in (2048, 1024, 512, d)
                 if d % c == 0 and c % 128 == 0
                 and t * c * 4 <= _COMBINE_TILE_BYTES), None)


def gathered_sum(y: Array, flat: Array, order: Array, weights: Array,
                 e: int) -> Array:
    """``y [t*k, d]`` float32, the products sorted by expert; ``flat [t*k]``
    the ids in token order, ``order`` the sort's permutation, ``weights
    [t, k]`` -> ``[t, d]``: token ``i``'s ``sum_j weights[i, j] * y[row of
    (i, j)]`` in choice order.  The inverse permutation is ``t*k`` integers;
    the rows travel from ``y`` to the sum through one gather.  A row of no
    group (``flat == e``) holds whatever the kernel's buffer held: it is
    masked before anything multiplies it, and its index points at row 0, so
    it costs no bytes of its own."""
    t, k = weights.shape
    held = flat < e
    at = jnp.zeros(t * k, jnp.int32).at[order].set(
        jnp.arange(t * k, dtype=jnp.int32))
    rows = jnp.where(held[:, None], y[jnp.where(held, at, 0)], 0.0)
    return (rows.reshape(t, k, -1) * weights[:, :, None]).sum(1)


def _held_rows_kernel(n_ref, y_ref, tok_ref, w_ref, out_ref):
    """Grid step ``(j, i)``: tile ``i`` of the sorted rows into column tile
    ``j`` of the result, which stays in fast memory over the ``i``.  A step
    past the held rows sits on the last held tile (nothing moves for it)
    with its body off."""
    i, n = pl.program_id(1), n_ref[0]
    t, rows = out_ref.shape[0], y_ref.shape[0]

    @pl.when(i == 0)
    def _():
        out_ref[...] = jnp.zeros_like(out_ref)

    @pl.when(i * rows < n)
    def _():
        row = i * rows + jax.lax.broadcasted_iota(jnp.int32, (rows, 1), 0)
        yw = jnp.where(row < n, y_ref[...] * w_ref[...], 0.0)
        # out[tok[r]] += yw[r] as a product with a 0/1 matrix on the MXU.
        # float32 through it EXACTLY: three bfloat16 pieces of 8 bits hold
        # its 24, each times 1.0 and added in float32
        hot = (jax.lax.broadcasted_iota(jnp.int32, (t, rows), 0)
               == tok_ref[0]).astype(jnp.bfloat16)
        for _ in range(3):
            piece = yw.astype(jnp.bfloat16)
            yw = yw - piece.astype(jnp.float32)
            out_ref[...] += jnp.dot(hot, piece,
                                    preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def held_rows_sum(y: Array, flat: Array, order: Array, weights: Array,
                  e: int, interpret: Optional[bool] = None) -> Array:
    """:func:`gathered_sum` (same arguments) as one pass over the HELD prefix
    of the sorted rows: they lie first in ``y``, ``n`` of them, and a grid
    step takes a tile of 128 with their tokens and weights, masks the rows
    from ``n`` on, and adds each row into its token's row of the ``[t,
    columns]`` result tile in fast memory.  ``n`` rides as a scalar-prefetch
    argument and the block specs index by it: the tiles past the held rows
    are never read.  Float32 throughout; a token's rows are added in sorted
    order, not choice order, so the result is :func:`gathered_sum`'s to
    float32 rounding, not to the bit.  ``interpret`` None: interpret mode
    off a TPU (tests).  Differentiated as :func:`gathered_sum`."""
    if interpret is None:
        interpret = jax.default_backend() != "tpu"
    t, k = weights.shape
    m, d = y.shape
    rows, cols = _COMBINE_ROWS, combine_tile(t, m, d)

    def tile(j, i, n_ref):
        return jnp.minimum(i, jnp.maximum(n_ref[0] - 1, 0) // rows)

    return pl.pallas_call(
        _held_rows_kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(d // cols, m // rows),
            in_specs=[
                pl.BlockSpec((rows, cols), lambda j, i, n: (tile(j, i, n), j)),
                pl.BlockSpec((1, 1, rows),
                             lambda j, i, n: (tile(j, i, n), 0, 0)),
                pl.BlockSpec((rows, 1), lambda j, i, n: (tile(j, i, n), 0)),
            ],
            out_specs=pl.BlockSpec((t, cols), lambda j, i, n: (0, j))),
        out_shape=jax.ShapeDtypeStruct((t, d), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=bool(interpret),
        name="held_rows_sum",
    )(jnp.sum(flat < e, dtype=jnp.int32)[None], y,
      (order // k).astype(jnp.int32).reshape(m // rows, 1, rows),
      weights.reshape(-1)[order][:, None])


def _held_rows_sum_fwd(y, flat, order, weights, e, interpret):
    return (held_rows_sum(y, flat, order, weights, e, interpret),
            (y, flat, order, weights))


def _held_rows_sum_bwd(e, interpret, saved, g):
    y, flat, order, weights = saved
    dy, dw = jax.vjp(lambda y, w: gathered_sum(y, flat, order, w, e),
                     y, weights)[1](g)
    return dy, None, None, dw


held_rows_sum.defvjp(_held_rows_sum_fwd, _held_rows_sum_bwd)


def expert_ffn(x: Array, chosen: Array, weights: Array,
               gate: Optional[Array], up: Array, down: Array) -> Array:
    """``x [t, d]``; ``chosen [t, k]`` int expert ids; ``weights [t, k]``
    float32; ``gate``/``up`` ``[e, d, f]``, ``down [e, f, d]`` -> ``[t, d]``
    float32.  ``gate`` None: two-matrix experts around a squared ReLU.
    ``chosen`` may hold the id ``e``, an expert held elsewhere (module
    doc)."""
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
        if (jax.default_backend() == "tpu" and not traced_for_mesh()
                and combine_tile(t, *y.shape) is not None):
            return held_rows_sum(y, flat, order, weights, e)
        return gathered_sum(y, flat, order, weights, e)
