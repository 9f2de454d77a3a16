"""pjit wrappers for the paged engine's compiled phases over a
``(data, model)`` mesh.

The sharded engine runs the SAME step bodies as the single-chip engine
(models/lm/generate.py ``make_paged_decode_body`` /
``make_prefill_chunk_body``, models/lm/paged_cache.py ``copy_page``) — only
the jit options differ: explicit ``in_shardings``/``out_shardings`` place the KV page
pools, per-slot indices and block tables over the ``data`` axis and the
q/k/v/gate/up/o/down kernels over ``model`` (parallel/sharding.py
``lm_param_spec``), and XLA's SPMD partitioner inserts the tensor-parallel
all-reduces the unchanged model code needs.  ``gather_pages`` runs
untouched inside each dp shard: the ShardedPagedPool hands out page ids
laid out so every slot's pages live in that slot's own data shard
(engine/dist/pool.py), making the gather shard-local.

Layout over a ``(dp, tp)`` mesh:

* the cache's leaves as ``paged_cache.SHARD_AXES`` says: page pools split by
  page across dp replicas, the per-slot index and the block table by slot
  (slots follow pages), the rest replicated;
* decode ``tok``/``pos`` ``[S]`` → ``P("data")``; prefill chunk args
  (b=1 work) and CoW page ids → replicated.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import NamedSharding, PartitionSpec as P

from tpu_air.ops.flash_attention import kernel_mesh

from tpu_air.models.lm.generate import (
    advance_rows_body,
    make_paged_decode_body,
    make_prefill_chunk_body,
    set_row_body,
)
from tpu_air.models.lm.paged_cache import SHARD_AXES, copy_page, map_layers


def paged_cache_shardings(cache, mesh):
    """NamedSharding tree matching an ``init_paged_cache`` result, every
    leaf over the mesh axes ``paged_cache.SHARD_AXES`` names for it."""
    return map_layers(cache, lambda _, layer: {
        leaf: NamedSharding(mesh, P(*SHARD_AXES[leaf])) for leaf in layer})


def _traced_for(mesh, body):
    """``body``, traced under ``kernel_mesh(mesh)``: what picks a kernel at
    trace time (``ops.decode_attention.latent_pages_read_in_place``: the
    step's rows and, since PR 45, a chunk's walk) sees
    that the partitioner will split this program's arrays, and keeps the
    path the partitioner can split."""
    @functools.wraps(body)
    def traced(*args):
        with kernel_mesh(mesh):
            return body(*args)
    return traced


def make_sharded_paged_decode_step_fn(model, slot_len: int, mesh,
                                      param_shardings, cache_shardings):
    """The MeshEngine decode step: same body and donate contract as
    ``make_lm_paged_decode_step_fn``, with batch args over ``data``."""
    batch = NamedSharding(mesh, P("data"))
    table = NamedSharding(mesh, P("data", None))
    return jax.jit(
        _traced_for(mesh, make_paged_decode_body(model, slot_len)),
        donate_argnums=(1,),
        in_shardings=(param_shardings, cache_shardings, batch, batch, table),
        out_shardings=(cache_shardings, batch),
    )


def make_sharded_step_feed_fns(mesh):
    """``make_lm_step_feed_fns`` for the MeshEngine: the step's ``tok`` and
    ``pos`` stay over ``data`` between steps, as its ``in_shardings`` want
    them."""
    batch = NamedSharding(mesh, P("data"))
    return (jax.jit(advance_rows_body, out_shardings=(batch, batch)),
            jax.jit(set_row_body, out_shardings=(batch, batch)))


def make_sharded_prefill_chunk_fn(model, page_len: int, slot_len: int, mesh,
                                  param_shardings, cache_shardings):
    """The MeshEngine chunked-prefill unit: chunk args replicate (one b=1
    chunk is broadcast work; only its page writes land in a data shard)."""
    repl = NamedSharding(mesh, P())
    return jax.jit(
        _traced_for(mesh, make_prefill_chunk_body(model, page_len, slot_len)),
        donate_argnums=(1,),
        in_shardings=(param_shardings, cache_shardings, repl, repl, repl,
                      repl),
        out_shardings=(cache_shardings, repl),
    )


def make_sharded_page_copy_fn(mesh, cache_shardings):
    """Copy-on-write under pjit.  The ShardedPagedPool always resolves CoW
    within one replica's page range, so the copy never crosses shards."""
    repl = NamedSharding(mesh, P())
    return jax.jit(
        copy_page,
        donate_argnums=(0,),
        in_shardings=(cache_shardings, repl, repl),
        out_shardings=cache_shardings,
    )
