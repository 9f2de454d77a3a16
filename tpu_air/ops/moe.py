"""The routed expert product of a sparse-expert feed-forward layer.

``expert_ffn`` computes, for every token ``t`` and each of its ``k`` chosen
experts ``e``, ``w[t, e] * down_e(silu(gate_e x[t]) * up_e x[t])`` and sums
over the ``k``: exactly the published sum.  There is no capacity and no
fixed group size, so no assignment is dropped or re-routed however uneven
the load.  Inputs in the model's dtype, accumulation in float32.

One formulation, no switch: rows are sorted by expert and the three
products run as grouped matmuls over the uneven groups (the Pallas
``megablox`` kernel on a TPU, ``jax.lax.ragged_dot`` elsewhere — the same
mathematics; the kernel exists only for the TPU).  tools/moe_candidates.py
holds the other formulations that were measured against it on the v5e
(PERF.md, PR 27).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

# (rows, contraction, columns) of one kernel tile: the fastest of eight
# measured on the v5e at 512 and 1024 sorted rows over 64 groups of
# 2048 x 1024 (PERF.md, PR 27).  A device trace names the kernel's calls
# ``gmm``, ``gmm.1``, ...
GMM_TILING = (128, 2048, 1024)


def _grouped(lhs: Array, rhs: Array, sizes: Array) -> Array:
    """``lhs [m, k]`` (rows sorted by group) times ``rhs [g, k, n]`` with
    ``sizes [g]`` rows a group -> ``[m, n]`` float32."""
    m, kdim = lhs.shape
    n = rhs.shape[-1]
    tm, tk, tn = GMM_TILING[0], min(GMM_TILING[1], kdim), min(GMM_TILING[2], n)
    if (jax.default_backend() == "tpu" and m % tm == 0
            and kdim % tk == 0 and n % tn == 0 and tk % 128 == 0
            and tn % 128 == 0):
        from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

        return gmm(lhs, rhs, sizes, preferred_element_type=jnp.float32,
                   tiling=(tm, tk, tn))
    return jax.lax.ragged_dot(lhs, rhs, sizes,
                              preferred_element_type=jnp.float32)


def expert_ffn(x: Array, chosen: Array, weights: Array, gate: Array,
               up: Array, down: Array) -> Array:
    """``x [t, d]``; ``chosen [t, k]`` int expert ids; ``weights [t, k]``
    float32; ``gate``/``up`` ``[e, d, f]``, ``down [e, f, d]`` -> ``[t, d]``
    float32."""
    t, k = chosen.shape
    e = gate.shape[0]
    with jax.named_scope("moe_sort"):
        flat = chosen.reshape(-1)
        order = jnp.argsort(flat, stable=True)       # assignments by expert
        sizes = jnp.bincount(flat, length=e).astype(jnp.int32)
        xs = x[order // k]                           # [t*k, d]
    with jax.named_scope("moe_experts"):
        h = (jax.nn.silu(_grouped(xs, gate, sizes))
             * _grouped(xs, up, sizes)).astype(x.dtype)
        y = _grouped(h, down, sizes)
    with jax.named_scope("moe_combine"):
        y = y * weights.reshape(-1)[order][:, None]
        back = jnp.zeros((t * k, y.shape[-1]), jnp.float32).at[order].set(y)
        return back.reshape(t, k, -1).sum(1)
