"""Ring attention — sequence/context parallelism over a mesh axis.

Long-context first-class support: the (Lq, Lk) attention problem is sharded
so each device owns an L/P slice of Q, K, V.  K/V blocks rotate around the
ring via ``jax.lax.ppermute`` (ICI neighbor exchange — the XLA-collective
equivalent of the published ring-attention schedule), and each device folds
the incoming block into its running blockwise softmax using the (out, lse)
pair from the local flash kernel.  P steps later every device holds its
exact attention output — no device ever materializes more than
O((L/P)² ) scores, and the rotation overlaps with compute under XLA's
async collective scheduling.

Causal masking works by HOP TYPE: shards are contiguous global slices, so
each ring step is either the diagonal (local causal mask inside the
kernel), fully visible (no mask), or fully masked (kernel skipped
entirely — and its ~P/2 of the hops' compute saved).  No additive bias is
ever built, which keeps the blockwise Pallas backward on the training path.

Composable with data/tensor parallelism: just name a ``sequence`` axis in
the mesh and shard L over it (see tests/test_ops.py for the shard_map
harness on the 8-device CPU mesh).
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from .flash_attention import flash_attention_with_lse


def _merge(out_a, lse_a, out_b, lse_b):
    """Combine two partial-softmax results (flash's streaming rule, applied
    across devices instead of across VMEM tiles)."""
    m = jnp.maximum(lse_a, lse_b)
    wa = jnp.exp(lse_a - m)[..., None]
    wb = jnp.exp(lse_b - m)[..., None]
    out = (out_a.astype(jnp.float32) * wa + out_b.astype(jnp.float32) * wb) / (wa + wb)
    lse = m + jnp.log(jnp.exp(lse_a - m) + jnp.exp(lse_b - m))
    return out, lse


def ring_attention(
    q,
    k,
    v,
    *,
    axis_name: str,
    scale: Optional[float] = None,
    causal: bool = False,
    block_q: Optional[int] = None,
    block_k: Optional[int] = None,
    interpret: Optional[bool] = None,
):
    """Attention over sequence-sharded q/k/v inside shard_map/pmap.

    ``q/k/v``: (batch·heads, L_local, head_dim) — the local sequence shard.
    Must run inside a mapped context where ``axis_name`` is a mesh axis of
    size P; returns the local (batch·heads, L_local, head_dim) output shard.
    ``interpret`` goes to the flash kernel as given (None: interpret mode
    off-TPU, like its siblings).
    """
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    p = jax.lax.psum(1, axis_name)  # ring size
    my = jax.lax.axis_index(axis_name)
    l_local = q.shape[1]

    def step(carry, _):
        out, lse, kv_k, kv_v, owner = carry
        # Causality by HOP TYPE, not by an additive bias: the shards are
        # contiguous global slices, so a hop is (a) the diagonal
        # (owner == my: plain local causal), (b) fully visible (owner < my),
        # or (c) fully masked (owner > my: skip the kernel entirely).
        # Keeping ``bias=None`` is load-bearing — the bias path falls back
        # to the dense-recompute VJP, while these branches keep the
        # blockwise Pallas BACKWARD (O(L) memory) on the training path.
        kw = dict(scale=scale, block_q=block_q, block_k=block_k,
                  interpret=interpret)

        def diagonal(q, kk, vv):
            return flash_attention_with_lse(q, kk, vv, causal=True, **kw)

        def visible(q, kk, vv):
            return flash_attention_with_lse(q, kk, vv, causal=False, **kw)

        def masked(q, kk, vv):
            return (jnp.zeros(q.shape, q.dtype),
                    jnp.full(q.shape[:2], -1e30, jnp.float32))

        if causal:
            branch = jnp.where(owner == my, 0, jnp.where(owner < my, 1, 2))
            o_i, lse_i = jax.lax.switch(branch, [diagonal, visible, masked],
                                        q, kv_k, kv_v)
        else:
            o_i, lse_i = visible(q, kv_k, kv_v)
        out, lse = _merge(out, lse, o_i, lse_i)
        # rotate K/V to the next device on the ring (neighbor ICI hop)
        perm = [(i, (i + 1) % p) for i in range(p)]
        kv_k = jax.lax.ppermute(kv_k, axis_name, perm)
        kv_v = jax.lax.ppermute(kv_v, axis_name, perm)
        owner = (owner - 1) % p
        return (out, lse, kv_k, kv_v, owner), None

    out0 = jnp.zeros(q.shape, jnp.float32)
    lse0 = jnp.full(q.shape[:2], -1e30, jnp.float32)
    (out, lse, _, _, _), _ = jax.lax.scan(
        step, (out0, lse0, k, v, my), None, length=p
    )
    return out.astype(q.dtype)


def ring_attention_sharded(q, k, v, mesh, *, axis_name: str = "sequence",
                           causal: bool = False, scale=None,
                           block_q: Optional[int] = None, block_k: Optional[int] = None,
                           interpret: Optional[bool] = None):
    """Convenience wrapper: shard (bh, L, d) arrays over ``axis_name`` of
    ``mesh`` and run ring attention via shard_map."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from tpu_air.parallel.shardmap_compat import shard_map_unchecked

    spec = P(None, axis_name, None)
    body = functools.partial(
        ring_attention, axis_name=axis_name, causal=causal, scale=scale,
        block_q=block_q, block_k=block_k, interpret=interpret,
    )
    fn = shard_map_unchecked(
        body, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
    )
    sharding = NamedSharding(mesh, spec)
    q, k, v = (jax.device_put(x, sharding) for x in (q, k, v))
    return fn(q, k, v)
