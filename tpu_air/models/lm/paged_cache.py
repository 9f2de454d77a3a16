"""The engine's paged cache: its format, in one place.

``InferenceEngine`` keeps ONE donated cache tree for all its slots, nested as
the flax modules nest it (``cache[layer][mixer][leaf]``).  What a layer keeps
there for a sequence follows from its kind (``LMConfig.layer_kinds()``), and
:data:`FORMATS` is the table of it:

* **page pools** ``[num_pages, page_len, width]`` shared by every slot (page 0
  the pinned null page), reached through a block table: an attention layer's
  K and V, a latent-attention layer's one latent pool.  Pages are what prefix
  sharing, copy-on-write and shipping (engine/dist/kv_transfer.py) move, each
  pool under its short name.
* **rows** a slot: a Mamba layer's convolution tail ``conv_state [S, (d_conv -
  1) * d_inner]`` and float32 ``ssm_state [S, d_state, d_inner]``, which no
  table reaches and the slot's index does; a Mamba-2 layer's are the same two
  leaves at its own shapes (``[S, (d_conv - 1) * conv_dim]`` over x, B and C
  together, ``[S, heads, head_dim, d_state]`` as published).  A WINDOW
  layer's K and V are rows too: ``window_key`` / ``window_value [S, R, g*d]``,
  a RING of ``R = LMConfig.window_ring_len(page_len)`` positions a slot
  (position ``p`` at ``p % R``, roped before the write, so the order within
  the ring does not matter to the read); no pool, no table, and nothing of it
  is ``slot_len`` long (``modeling.CausalSelfAttention`` has its writes and
  reads).
* **pushed leaves**, host state (engine/kvpool/) written into the tree before
  every program so the donated cache never round-trips: ``cache_index [S]``
  (where each row's call starts), ``block_table [S, pages_per_slot]``,
  ``valid_len [S]`` (how many of the call's positions are real for each row:
  in a decode step 1 for a decoding row and 0 for a row that rides along, so
  the step holds that row's state; in a chunk the real tokens, so padding
  never enters the state) and ``state_row [S]`` (the slot a chunk works for).

The modules that create and read the leaves are models/lm/modeling.py's
mixers; everything else (the engine's programs in models/lm/generate.py, the
mesh's shardings, page shipping) goes through the functions below and names
no leaf.  A new kind of per-sequence state is a row of :data:`FORMATS` (and of
:data:`SHARD_AXES`), its mixer, and whatever the host must count for it.
"""

from __future__ import annotations

from typing import Callable, Dict, List, NamedTuple, Optional, Tuple

import jax
import jax.numpy as jnp

from tpu_air.ops import ssm

from .config import LMConfig

CACHE_INDEX = "cache_index"
BLOCK_TABLE = "block_table"
VALID_LEN = "valid_len"
STATE_ROW = "state_row"


class LayerFormat(NamedTuple):
    """What one kind of layer keeps in the paged cache."""

    pools: Dict[str, str]     # page pool leaf -> the short name it ships under
    rows: Tuple[str, ...]     # leaves that hold a row a slot
    pushed: Tuple[str, ...]   # leaves the host pushes in before a program


#: by kind of layer, as ``LMConfig.layer_kinds()`` names it
FORMATS: Dict[str, LayerFormat] = {
    "attention": LayerFormat({"cached_key": "k", "cached_value": "v"}, (),
                             (CACHE_INDEX, BLOCK_TABLE)),
    "latent": LayerFormat({"cached_latent": "c"}, (),
                          (CACHE_INDEX, BLOCK_TABLE)),
    "mamba": LayerFormat({}, ("conv_state", "ssm_state"),
                         (CACHE_INDEX, STATE_ROW, VALID_LEN)),
    # the same leaves at Mamba-2's shapes (the mixer makes them): a layer's
    # dict reads as "mamba" in layer_kind, whose format is this one
    "mamba2": LayerFormat({}, ("conv_state", "ssm_state"),
                          (CACHE_INDEX, STATE_ROW, VALID_LEN)),
    # a window layer's ring: ``valid_len`` says which rows of a step write
    # (a row that rides along has no null page to scatter to)
    "window": LayerFormat({}, ("window_key", "window_value"),
                          (CACHE_INDEX, STATE_ROW, VALID_LEN)),
}

_POOLS = {leaf: short for fmt in FORMATS.values()
          for leaf, short in fmt.pools.items()}
_ROWS = {leaf for fmt in FORMATS.values() for leaf in fmt.rows}
_RING = set(FORMATS["window"].rows)

#: the mesh axes of every leaf over a ``(data, model)`` mesh, a name a
#: dimension (engine/dist/sharded.py makes the ``NamedSharding``s): pools by
#: page and the index and table by slot over ``data`` (slots follow pages:
#: engine/dist/pool.py), the rest replicated
SHARD_AXES: Dict[str, Tuple[Optional[str], ...]] = {
    **{leaf: ("data", None, None) for leaf in _POOLS},
    **{leaf: () for leaf in _ROWS},
    CACHE_INDEX: ("data",), BLOCK_TABLE: ("data", None),
    VALID_LEN: (), STATE_ROW: (),
}


def map_layers(cache, fn: Callable[[str, dict], dict], path=()):
    """THE walker: the cache tree rebuilt with ``fn(path, layer) -> layer``
    applied to every layer's dict of leaves (the innermost dicts), ``path``
    the ``/``-joined keys down to it (``layer_3/attn``)."""
    if not any(isinstance(v, dict) for v in cache.values()):
        return fn("/".join(path), cache)
    return {k: map_layers(v, fn, path + (k,)) if isinstance(v, dict) else v
            for k, v in cache.items()}


def layers(cache) -> List[Tuple[str, dict]]:
    """``(path, layer)`` of every layer's dict of leaves, in tree order."""
    found: List[Tuple[str, dict]] = []

    def note(path, layer):
        found.append((path, layer))
        return layer

    map_layers(cache, note)
    return found


def map_leaves(cache, fns: Dict[str, Callable]):
    """The cache with ``fns[leaf](value)`` in place of every leaf so named.
    One leaf name after the other, in ``fns``' order: what the functions
    trace lands in the program in that order."""
    for name, fn in fns.items():
        cache = map_layers(cache, lambda _, layer, name=name, fn=fn: {
            k: fn(v) if k == name else v for k, v in layer.items()})
    return cache


def map_pools(cache, fn: Callable[[str, str, jax.Array], jax.Array]):
    """The cache with ``fn(path, short, pool)`` in place of every page pool
    (``short``: the name the pool ships under); every other leaf as it was."""
    return map_layers(cache, lambda path, layer: {
        k: fn(path, _POOLS[k], v) if k in _POOLS else v
        for k, v in layer.items()})


def page_pools(cache) -> Dict[str, Dict[str, jax.Array]]:
    """``{layer path: {short name: pool}}`` of the layers that keep pages:
    what a shipment of pages holds (a layer that keeps none is not there)."""
    out = {path: {_POOLS[k]: v for k, v in layer.items() if k in _POOLS}
           for path, layer in layers(cache)}
    return {path: pools for path, pools in out.items() if pools}


def layer_kind(layer: dict) -> str:
    """The kind of layer whose pools and rows ``layer`` holds."""
    for kind, fmt in FORMATS.items():
        if all(leaf in layer for leaf in (*fmt.pools, *fmt.rows)):
            return kind
    raise ValueError(
        f"no kind of layer in paged_cache.FORMATS keeps {sorted(layer)}")


def init_paged_cache(model, num_slots: int, num_pages: int, page_len: int,
                     pages_per_slot: int):
    """Zero paged cache, the persistent donated cache of the engine: each
    layer's pools at ``[num_pages, page_len, width]``, its rows at
    ``num_slots`` rows, its pushed leaves int32 ``[num_slots]`` (the table
    ``[num_slots, pages_per_slot]``, 0 = unreached/null), by its row of
    :data:`FORMATS`; widths and dtypes are what the model's modules make for
    a plain cache; a window layer's ring is sized by the window and the
    chunk (``LMConfig.window_ring_len``), whatever a slot's length."""
    cfg = LMConfig.from_dict(
        {**model.config.to_dict(), "max_seq_len": page_len})
    ring = cfg.window_ring_len(page_len)
    plain = jax.eval_shape(lambda: model.clone(config=cfg).init(
        jax.random.PRNGKey(0), jnp.zeros((num_slots, 1), jnp.int32),
        decode=True))["cache"]

    def paged(_, made):
        fmt = FORMATS[layer_kind(made)]
        out = {leaf: jnp.zeros((num_pages, page_len, made[leaf].shape[-1]),
                               made[leaf].dtype) for leaf in fmt.pools}
        out.update({leaf: jnp.zeros(
            (num_slots, ring, made[leaf].shape[-1]) if leaf in _RING
            else made[leaf].shape, made[leaf].dtype) for leaf in fmt.rows})
        out.update({leaf: jnp.zeros(
            (num_slots, pages_per_slot) if leaf == BLOCK_TABLE
            else (num_slots,), jnp.int32) for leaf in fmt.pushed})
        return out

    return map_layers(plain, paged)


def _leaf_bytes(cache, names) -> int:
    return sum(v.size * v.dtype.itemsize for _, layer in layers(cache)
               for k, v in layer.items() if k in names)


def recurrent_state_bytes(cache) -> int:
    """Bytes of the per-slot recurrent state (Mamba layers' rows) the cache
    holds beside its pages."""
    return _leaf_bytes(cache, _ROWS - _RING)


def window_ring_bytes(cache) -> int:
    """Bytes of the window layers' rings."""
    return _leaf_bytes(cache, _RING)


def page_pool_bytes(cache) -> int:
    """Bytes of every page pool (what the block tables reach)."""
    return _leaf_bytes(cache, _POOLS)


def state_rows_move_in_place(cache) -> bool:
    """Does a decode step's pass over the per-slot state move the rows it
    advances alone (True), or every slot's (False)?  The mixers' own rule
    (``ops/ssm.state_rows_move_in_place``: a TPU, no mesh, float32 Mamba-2
    states of whole tiles), asked of every layer's state this cache holds;
    False for a cache that holds none."""
    states = [layer["ssm_state"] for _, layer in layers(cache)
              if "ssm_state" in layer]
    return bool(states) and all(map(ssm.state_rows_move_in_place, states))


def push_step(cache, pos, block_table):
    """The cache with a decode step's host-side facts pushed in: every
    row's position ``pos [S]`` int32, the (masked) block table, and which
    rows are live."""
    return map_leaves(cache, {
        CACHE_INDEX: lambda _: pos,
        BLOCK_TABLE: lambda _: block_table.astype(jnp.int32),
        # a row that decodes is past its prompt; every other row (free, or
        # mid-prefill with its chunks building its state) sits at position 0
        # and the step must hold whatever state it has
        VALID_LEN: lambda _: (pos > 0).astype(jnp.int32),
    })


def push_chunk(cache, p0, last_local, table_row, slot=None):
    """The cache with one prefill chunk's facts pushed in: its first
    position ``p0`` (int32), the index ``last_local`` of its last real token,
    its slot's table row, and, for the layers that keep a row a slot,
    ``slot``.  Leaf shapes stay ``[S]`` / ``[S, npg]`` across chunk and decode
    calls (shape-stable donation); only row 0 is consulted at b=1."""
    def state_row(v):
        if slot is None:
            raise ValueError(
                "a model with recurrent or window layers keeps rows a slot: "
                "the chunk program needs slot=")
        return jnp.full(v.shape, jnp.asarray(slot).astype(jnp.int32),
                        jnp.int32)

    return map_leaves(cache, {
        # the real positions of a chunk end at last_local (a full chunk's is
        # its last): padding past it must not enter the state
        VALID_LEN: lambda v: jnp.full(
            v.shape, last_local.astype(jnp.int32) + 1, jnp.int32),
        STATE_ROW: state_row,
        CACHE_INDEX: lambda v: jnp.full(v.shape, p0, jnp.int32),
        BLOCK_TABLE: lambda v: jnp.broadcast_to(
            table_row.astype(jnp.int32)[None], v.shape),
    })


def copy_page(cache, dst, src):
    """The UNJITTED copy-on-write body: page ``src`` copied onto page ``dst``
    in every page pool; every other leaf passes through.  Wrapped by
    ``generate.make_page_copy_fn`` (single chip) and the sharded factory
    (engine/dist/sharded.py)."""
    dst = dst.astype(jnp.int32) if hasattr(dst, "astype") else dst
    src = src.astype(jnp.int32) if hasattr(src, "astype") else src

    def copy(_, __, pool):
        page = jax.lax.dynamic_slice(pool, (src, 0, 0), (1,) + pool.shape[1:])
        return jax.lax.dynamic_update_slice(pool, page, (dst, 0, 0))

    return map_pools(cache, copy)
