"""Long-context causal LM — Flax decoder-only transformer, TPU-first.

First-class sequence parallelism: with ``config.sequence_axis`` naming a mesh
axis, the model runs INSIDE shard_map with activations sequence-sharded —
each device holds L/P tokens, RoPE uses global positions (shard offset from ``lax.axis_index``), and attention is
ring attention (ops/ring_attention.py): K/V shards rotate over ICI while the
blockwise-softmax state folds in each incoming block.  Context length then
scales linearly with the ``sequence`` mesh axis — the long-context design
the reference never had (its T5 path truncates at 512:
NLP_workloads/Anyscale_job/utils.py:23-28).

Everything is static-shape and scan/ppermute-based, so one compiled program
serves every step.  Architecture: pre-RMSNorm, RoPE attention, SwiGLU MLP,
tied embeddings (LLaMA-style — chosen for MXU-friendly dims, not copied
from any reference code).
"""

from __future__ import annotations

import contextlib
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from flax import linen as nn

from tpu_air.ops import decode_attention, mhc, ssm
from tpu_air.ops.decode_attention import (flat_decode_attention, gather_pages,
                                          latent_decode_attention)

from .config import LMConfig
from .paged_cache import BLOCK_TABLE, CACHE_INDEX, STATE_ROW, VALID_LEN

Array = jax.Array
NEG_INF = -1e30
# the full-sequence pass takes the Pallas flash kernel from this many tokens
# (the measured v5e crossover: docs/KERNELS.md), the einsum path below
FLASH_MIN_SEQ_LEN = 1024


class ChunkRows(NamedTuple):
    """The prefill chunk that rides a paged decode step (the engine's mixed
    step, models/lm/generate.py): the call's ``x`` is ``[S + C, 1, D]``, the
    pool's ``S`` decoding rows and then the ``C = page_len`` positions of ONE
    slot's chunk.  Everything that works on a token alone (norms,
    projections, feed-forward, experts) sees ``S + C`` rows in one product a
    weight matrix; only the sequence mixers tell the parts apart, and they
    take from here what the chunk program takes from the cache's leaves."""

    start: Array      # int32 scalar: the chunk's first position (page-aligned)
    valid: Array      # int32 scalar: how many of its positions are real
    table_row: Array  # int32 [pages_per_slot]: the slot's block-table row
    slot: Array       # int32 scalar: the slot (its row of recurrent state)


class RMSNorm(nn.Module):
    eps: float = 1e-6
    dtype: Any = jnp.float32

    @nn.compact
    def __call__(self, x: Array) -> Array:
        w = self.param("weight", nn.initializers.ones, (x.shape[-1],), jnp.float32)
        var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
        return (w * x.astype(jnp.float32) * jax.lax.rsqrt(var + self.eps)).astype(self.dtype)


def yarn_inv_freq(dim: int, theta: float, factor: float, original_len: int,
                  beta_fast: float, beta_slow: float) -> Array:
    """The ``dim / 2`` rotary frequencies stretched the yarn way (DeepSeek-V3's
    ``yarn_find_correction_range`` and linear ramp): pair ``j`` keeps
    ``theta**(-2j/dim)`` where it turns more than ``beta_fast`` times over
    ``original_len`` positions, takes that over ``factor`` where it turns
    fewer than ``beta_slow`` times, and a linear blend by pair index between
    the two correction dimensions."""
    import math

    def correction_dim(rotations):
        return (dim * math.log(original_len / (rotations * 2 * math.pi))
                / (2 * math.log(theta)))

    low = max(math.floor(correction_dim(beta_fast)), 0)
    high = min(math.ceil(correction_dim(beta_slow)), dim - 1)
    if low == high:
        high += 0.001     # as published: no division by zero
    j = jnp.arange(dim // 2, dtype=jnp.float32)
    plain = theta ** (-2.0 * j / dim)
    keep = 1.0 - jnp.clip((j - low) / (high - low), 0.0, 1.0)
    return plain / factor * (1.0 - keep) + plain * keep


def yarn_mscale(factor: float, mscale: float) -> float:
    """DeepSeek-V3's ``yarn_get_mscale``: ``0.1 * mscale * ln(factor) + 1``
    (1 without stretching)."""
    import math

    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def rope(x: Array, positions: Array, theta: float,
         inv_freq: Optional[Array] = None) -> Array:
    """Rotary embedding.  x: (B, H, L, D), positions: (B, L) global token
    positions (sequence-sharded models pass shard-offset positions).
    ``inv_freq`` ``[D/2]``: the frequencies, where they are not the plain
    ``theta**(-2j/D)`` (:func:`yarn_inv_freq`)."""
    d = x.shape[-1]
    if inv_freq is None:
        inv_freq = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = positions[:, None, :, None].astype(jnp.float32) * inv_freq  # (B,1,L,D/2)
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    y1 = x1 * cos - x2 * sin
    y2 = x2 * cos + x1 * sin
    return jnp.stack([y1, y2], axis=-1).reshape(x.shape).astype(x.dtype)


def _repeat_kv(x: Array, n_heads: int) -> Array:
    """(B,G,L,D) K or V with fewer heads than the queries, each repeated for
    the query heads it serves (a no-op where every head has its own)."""
    g = x.shape[1]
    return x if g == n_heads else jnp.repeat(x, n_heads // g, axis=1)


def _dense_causal_attention(q, k, v, scale, q_offset=0, window=0,
                            key_pos=None):
    """(B,H,L,D) einsum attention with causal mask; baseline path.  k, v may
    carry fewer heads than q (grouped K/V).  ``window`` > 0: a query sees the
    ``window`` positions up to its own, itself counted.  ``key_pos [Lk]``:
    the position each key holds where the keys do not lie in order (a window
    layer's ring; negative: nothing written there yet)."""
    k, v = _repeat_kv(k, q.shape[1]), _repeat_kv(v, q.shape[1])
    with jax.named_scope("attn_scores"):
        s = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                       k.astype(jnp.float32))
        s = s * scale
        lq, lk = q.shape[2], k.shape[2]
        qi = q_offset + jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 0)
        kj = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
        seen = qi >= kj
        if key_pos is not None:
            kj = jnp.broadcast_to(key_pos.astype(jnp.int32)[None], (lq, lk))
            seen = (qi >= kj) & (kj >= 0)
        if window:
            seen = seen & (qi - kj < window)
        s = jnp.where(seen, s, NEG_INF)
    with jax.named_scope("attn_softmax"):
        p = jax.nn.softmax(s, axis=-1)
    with jax.named_scope("attn_context"):
        return jnp.einsum("bhqk,bhkd->bhqd", p,
                          v.astype(jnp.float32)).astype(q.dtype)


class CacheKind(NamedTuple):
    """What an attention mixer hands :func:`_cached_attention`: its writes to
    and reads of what it keeps a position.  ``pools`` is a tuple of arrays
    (K and V, or the one latent): page pools ``[P, C, w]`` over the engine's
    paged cache, slabs ``[b, L, w]`` over the plain one; ``new`` the rows the
    call's positions leave behind, an array a pool; ``q`` a tuple of the
    call's query arrays ``[b, h, l, .]``; ``i`` the cache index.  Every read
    returns the attention's output rows as the call lays them, ``[b, l,
    h*dv]``, for the mixer's output projection."""

    # paged: one new position of every slot (a decode step's rows) ...
    append_rows: Callable    # (pools, table, i, new [S, 1, w]) -> pools
    attend_rows: Callable    # (q [S, h, 1, .], pools, table, i) -> [S, 1, .]
    # ... or one whole page of one slot (a prefill chunk).  ``where``: the
    # slot's table row, or a table whose first row it is; ``ahead``: None in
    # the chunk program (``q [1, h, C, .]``), in a mixed step the number of
    # step rows ``q`` holds ahead of the chunk's ``C`` rows of one token
    append_chunk: Callable   # (pools, page, new [1, C, w]) -> pools
    attend_chunk: Callable   # (q, pools, where, start, ahead) -> chunk rows
    # plain (``generate``): the call's positions land at ``i``; a one-token
    # call reads the slab up to ``i``, a longer one (the prompt) attends
    # causally with its first query at ``i``
    append_plain: Callable   # (pools, i, new [b, l, w]) -> pools
    attend_token: Callable   # (q, pools, i) -> [b, 1, .]
    attend_prompt: Callable  # (q, pools, i) -> [b, l, .]


def _slot_page(where, start, page_len):
    """The page id position ``start`` of a slot lies in: ``where`` is the
    slot's table row, or a table whose first row it is."""
    return where[(0,) * (where.ndim - 1) + (start // page_len,)]


def _cached_attention(mod: nn.Module, kind: CacheKind, q, new, pools, idx,
                      chunk):
    """The cached (``decode=True``) call of an attention mixer, whatever it
    keeps a position: ``pools`` and ``idx`` are ``mod``'s cache variables,
    ``kind`` its writes and reads (:class:`CacheKind`).  The SAME call serves
    five callers, told apart by what the cache holds and the call's shape:

    * the engine's PAGED cache (a ``block_table`` leaf: engine/kvpool/,
      models/lm/paged_cache.py): the pools are page pools shared by every
      slot and ``block_table [S, pages_per_slot]`` maps each slot's logical
      positions onto physical pages: position p of slot s lives at
      ``(table[s, p // C], p % C)``.  Prefix-shared pages appear in several
      rows at once; the null page (id 0) absorbs writes/reads of masked rows
      and unreached entries.  A DECODE STEP (``l == 1``) scatters each
      slot's new row to its current (page, offset), then reads.  A PREFILL
      CHUNK (``b == 1``, ``l == page_len``) is one page-aligned piece of one
      slot's prompt at positions p0 .. p0+l-1: the whole chunk writes its
      page at once and attends with the query offset at p0 (earlier chunks /
      prefix-shared pages supply 0 .. p0-1), so one compiled program serves
      EVERY prompt length.  A MIXED STEP (``chunk``: :class:`ChunkRows`)
      is both: rows [:S] are the pool's decode step, rows [S:] one slot's
      chunk.  Both writes, then both reads: a row that rides along scatters
      to the null page, which is also where a chunk whose real pages are all
      shared writes (PagedKVPool.chunk_row); the chunk's write is the later,
      so its own read finds what it wrote.
    * the PLAIN cache (``generate``): slabs ``[b, max_len, w]``, new rows
      land at the running index; the same call handles the multi-token
      prefill and the 1-token decode steps (positions are global: the caller
      derives them from the index)."""
    b, l = new[0].shape[:2]
    i = idx.value
    held = tuple(p.value for p in pools)

    def keep(held, step):
        for p, v in zip(pools, held):
            p.value = v
        idx.value = i + step

    if not mod.has_variable("cache", BLOCK_TABLE):
        held = kind.append_plain(held, i, new)
        keep(held, l)
        if l == 1:
            return kind.attend_token(q, held, i)
        return kind.attend_prompt(q, held, i)
    table = mod.variable("cache", BLOCK_TABLE,
                         lambda: jnp.zeros((b, 1), jnp.int32)).value
    C = held[0].shape[1]
    if chunk is not None:
        S = table.shape[0]
        if l != 1 or b != S + C:
            raise ValueError(
                f"mixed step wants {S} + {C} rows of one token; "
                f"got b={b}, l={l}")
        held = kind.append_rows(held, table, i, tuple(x[:S] for x in new))
        held = kind.append_chunk(
            held, _slot_page(chunk.table_row, chunk.start, C),
            tuple(x[S:, 0][None] for x in new))
        keep(held, 1)
        return jnp.concatenate([
            kind.attend_rows(tuple(x[:S] for x in q), held, table, i),
            kind.attend_chunk(q, held, chunk.table_row, chunk.start, S)])
    if l == 1:
        held = kind.append_rows(held, table, i, new)
        keep(held, 1)
        return kind.attend_rows(q, held, table, i)
    if b != 1 or l != C:
        raise ValueError(
            f"paged chunk prefill wants b=1, l=page_len ({C}); "
            f"got b={b}, l={l}")
    p0 = i[0]
    held = kind.append_chunk(held, _slot_page(table, p0, C), new)
    keep(held, l)
    return kind.attend_chunk(q, held, table, p0, None)


# CausalSelfAttention's paged writes and reads (its CacheKind).

def _paged_append_rows(pools, table, i, new):
    """Scatter row ``s``'s new ``k``/``v`` ``[S, 1, g*d]`` to its current
    ``(table[s, i // C], i % C)``; returns the pools."""
    C = pools[0].shape[1]
    rows = jnp.arange(table.shape[0])
    page = table[rows, i // C]
    off = i % C
    with jax.named_scope("kv_append"):
        return tuple(p.at[page, off].set(x[:, 0].astype(p.dtype))
                     for p, x in zip(pools, new))


def _paged_attend_rows(q, ck, cv, table, i, scale, h, g, dtype):
    """``q [S, h, 1, d]``, each row over its own gathered pages up to its
    position ``i [S]`` -> ``[S, 1, h*d]``: the flat r5 formulation over
    pool-resident pages."""
    kvm = jnp.arange(table.shape[1] * ck.shape[1])[None, :] <= i[:, None]
    o4 = flat_decode_attention(
        q.transpose(0, 2, 1, 3) * scale, gather_pages(ck, table),
        gather_pages(cv, table), kvm, h, dtype, g)
    return o4.reshape(q.shape[0], 1, -1)


def _paged_append_chunk(pools, page, new):
    """Write one slot's chunk ``k``/``v`` ``[1, C, g*d]`` over ``page``."""
    with jax.named_scope("kv_append"):
        return tuple(jax.lax.dynamic_update_slice(
            p, x.astype(p.dtype), (page, 0, 0)) for p, x in zip(pools, new))


def _paged_attend_chunk(q, ck, cv, table, p0, scale, g):
    """``q [1, h, C, d]`` at positions ``p0 ..`` dense and causal over the
    pages of ``table[:1]`` (earlier chunks and prefix-shared pages supply
    ``0 .. p0-1``) -> ``[1, C, h*d]``."""
    kg = gather_pages(ck, table[:1])
    vg = gather_pages(cv, table[:1])
    lg, d = kg.shape[1], q.shape[-1]
    k4 = kg.reshape(1, lg, g, d).transpose(0, 2, 1, 3)
    v4 = vg.reshape(1, lg, g, d).transpose(0, 2, 1, 3)
    o = _dense_causal_attention(q, k4, v4, scale, q_offset=p0)
    return o.transpose(0, 2, 1, 3).reshape(1, q.shape[2], -1)


# CausalSelfAttention's writes to and reads of a WINDOW layer's ring over the
# engine's cache: ``window_key`` / ``window_value [S, R, g*d]``, position p of
# slot s at ``[s, p % R]`` (models/lm/paged_cache.py; R is
# ``LMConfig.window_ring_len``: whole chunks, and a chunk written before it
# is read still finds the ``window - 1`` positions behind its first query).

def _ring_held(at, R):
    """The position each of a ring's ``R`` entries holds once position ``at``
    is written (``at`` of any shape; one more axis of ``R`` behind it): the
    largest ``p <= at`` with ``p % R`` the entry's index; negative where
    nothing was written yet (what a former tenant left there is older than
    any window)."""
    at = at[..., None]
    return at - (at - jnp.arange(R)) % R


def _ring_append_rows(rings, i, live, new):
    """Row ``s``'s new ``k``/``v`` ``[S, 1, g*d]`` to ``[s, i % R]``, for the
    rows ``live [S]`` marks alone: a row that rides along has no null page
    to scatter to, so its write is dropped."""
    S, R = rings[0].shape[:2]
    at = jnp.where(live > 0, i % R, R)          # R: out of range, dropped
    with jax.named_scope("window_append"):
        return tuple(r.at[jnp.arange(S), at].set(
            x[:, 0].astype(r.dtype), mode="drop") for r, x in zip(rings, new))


def _ring_attend_rows(q, rk, rv, i, window, scale, h, g, dtype):
    """``q [S, h, 1, d]``, each row over the ring of its slot AS IT LIES,
    under a mask of what is written and inside the window at its position
    ``i [S]`` -> ``[S, 1, h*d]``: the flat read, no gather."""
    held = _ring_held(i, rk.shape[1])
    live = (held >= 0) & (held > i[:, None] - window)
    with jax.named_scope("window_attention"):
        o4 = flat_decode_attention(q.transpose(0, 2, 1, 3) * scale, rk, rv,
                                   live, h, dtype, g)
    return o4.reshape(q.shape[0], 1, -1)


def _ring_append_chunk(rings, slot, p0, new):
    """One slot's chunk ``k``/``v`` ``[1, C, g*d]`` at positions ``p0 ..``
    (chunk-aligned, and ``R`` is whole chunks: one piece)."""
    R = rings[0].shape[1]
    with jax.named_scope("window_append"):
        return tuple(jax.lax.dynamic_update_slice(
            r, x.astype(r.dtype), (slot, p0 % R, 0))
            for r, x in zip(rings, new))


def _ring_attend_chunk(q, rk, rv, slot, p0, window, scale, g):
    """``q [1, h, C, d]`` at positions ``p0 ..`` over the slot's ring, the
    chunk written into it: dense under the window mask -> ``[1, C, h*d]``."""
    R, C, d = rk.shape[1], q.shape[2], q.shape[-1]
    k4, v4 = (jax.lax.dynamic_slice_in_dim(r, slot, 1, axis=0).reshape(
        1, R, g, d).transpose(0, 2, 1, 3) for r in (rk, rv))
    with jax.named_scope("window_attention"):
        o = _dense_causal_attention(
            q, k4, v4, scale, q_offset=p0, window=window,
            key_pos=_ring_held(p0 + C - 1, R))
    return o.transpose(0, 2, 1, 3).reshape(1, C, -1)


def _ring_attention(mod: nn.Module, q, new, rings, idx, chunk, window, scale,
                    h, g, dtype):
    """The cached call of a WINDOW layer over the engine's cache, for
    :func:`_cached_attention`'s three paged callers (a decode step, a
    prefill chunk of slot ``state_row[0]``, a mixed step): writes, then
    reads, as there.  ``valid_len [S]`` says which rows of a step decode."""
    b, l = new[0].shape[:2]
    i, live = idx.value, mod.get_variable("cache", VALID_LEN)
    held = tuple(r.value for r in rings)
    S, R = held[0].shape[:2]

    def keep(held):
        for r, v in zip(rings, held):
            r.value = v
        idx.value = i + l

    if chunk is not None:
        C = b - S
        if l != 1 or C < 1 or R % C:
            raise ValueError(
                f"mixed step wants {S} rows of one token and a chunk the "
                f"ring of {R} is whole numbers of; got b={b}, l={l}")
        held = _ring_append_rows(held, i, live, tuple(x[:S] for x in new))
        held = _ring_append_chunk(held, chunk.slot, chunk.start,
                                  tuple(x[S:, 0][None] for x in new))
        keep(held)
        return jnp.concatenate([
            _ring_attend_rows(q[:S], *held, i, window, scale, h, g, dtype),
            _ring_attend_chunk(q[S:].transpose(2, 1, 0, 3), *held,
                               chunk.slot, chunk.start, window, scale, g
                               ).reshape(C, 1, -1)])
    if l == 1:
        held = _ring_append_rows(held, i, live, new)
        keep(held)
        return _ring_attend_rows(q, *held, i, window, scale, h, g, dtype)
    if b != 1 or R % l:
        raise ValueError(
            f"a chunk over a window layer's ring of {R} wants b=1 and a "
            f"length the ring is whole numbers of; got b={b}, l={l}")
    slot, p0 = mod.get_variable("cache", STATE_ROW)[0], i[0]
    held = _ring_append_chunk(held, slot, p0, new)
    keep(held)
    return _ring_attend_chunk(q, *held, slot, p0, window, scale, g)


class CausalSelfAttention(nn.Module):
    """Attention over K and V as projected (no latent).  ``kind`` is the
    layer's (``LMConfig.layer_kinds()``): ``"attention"`` sees every
    position up to its own, ``"window"`` the last ``config.sliding_window``
    of them; heads and rope follow the kind (``LMConfig.heads_of``,
    ``rope_of``).  Over a cache the full kind keeps slabs or pages
    (:func:`_cached_attention`); the window kind keeps slabs under
    ``generate`` (the same reads under the window's mask) and a ring a slot
    under the engine (:func:`_ring_attention`).  Scopes
    (docs/OBSERVABILITY.md), in a model that has both kinds:
    ``full_attention`` around a full layer's cached read and
    ``window_attention`` around a window layer's, ``window_append`` the
    ring's write (a full layer's is ``kv_append``), ``attn_gate`` the output
    gate."""

    config: LMConfig
    kind: str = "attention"

    @nn.compact
    def __call__(self, x: Array, positions: Array, decode: bool = False,
                 chunk: Optional[ChunkRows] = None) -> Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        b, l, _ = x.shape
        h, g, d = cfg.heads_of(self.kind), cfg.n_kv_heads, cfg.head_dim
        window = cfg.sliding_window if self.kind == "window" else 0
        theta, turned = cfg.rope_of(self.kind)

        def proj(name, out):
            return nn.Dense(out, use_bias=False, dtype=dtype,
                            kernel_init=nn.initializers.normal(0.02), name=name)

        q, k = proj("q", h * d)(x), proj("k", g * d)(x)
        if cfg.qk_norm:
            # over the whole h*d projection, before the split into heads
            q = RMSNorm(cfg.rmsnorm_eps, dtype, name="q_norm")(q)
            k = RMSNorm(cfg.rmsnorm_eps, dtype, name="k_norm")(k)
        q = q.reshape(b, l, h, d).transpose(0, 2, 1, 3)
        k = k.reshape(b, l, g, d).transpose(0, 2, 1, 3)
        v = proj("v", g * d)(x).reshape(b, l, g, d).transpose(0, 2, 1, 3)
        if theta is not None:
            # None: the family has no position encoding (LMConfig)
            inv_freq, wave = None, 1.0
            if cfg.rope_factor > 1 and not window:
                # yarn, on the full kind alone: over the numbers that turn
                inv_freq = yarn_inv_freq(
                    turned, theta, cfg.rope_factor, cfg.rope_original_len,
                    cfg.rope_beta_fast, cfg.rope_beta_slow)
                wave = yarn_mscale(cfg.rope_factor, cfg.rope_mscale)

            def turn(v):
                if turned == d and wave == 1.0:
                    return rope(v, positions, theta, inv_freq)
                # the leading ``turned`` numbers of a head turn (cos and sin
                # times yarn's factor), the rest pass
                t = rope(v[..., :turned].astype(jnp.float32), positions,
                         theta, inv_freq) * wave
                return jnp.concatenate([t.astype(v.dtype), v[..., turned:]],
                                       -1)

            q, k = turn(q), turn(k)
        scale = 1.0 / (d ** 0.5)

        def out(o):
            """``o [b, l, h*d]`` through the gate, then ``W_o``."""
            if cfg.attn_gate == "per_head":
                with jax.named_scope("attn_gate"):
                    gate = jax.nn.sigmoid(
                        proj("gate", h)(x).astype(jnp.float32))
                    o = (o.reshape(o.shape[:2] + (h, d)).astype(jnp.float32)
                         * gate[..., None]).astype(dtype).reshape(o.shape)
            return proj("o", cfg.d_model)(o)

        if decode:
            # KV-cache path (autoregressive generate, SURVEY.md §7
            # hard-part 2; the engine's paged cache): _cached_attention has
            # the callers.  Cached k is already RoPE'd.  Slabs and pages are
            # stored FLAT [.., L, h*d]: the r5 T5 profile measured the
            # [.., L, d=64] layout at 2x physical HBM bytes from (8, 128)
            # tile padding; h*d is unpadded, and the 1-token step attends
            # via the flat block-diagonal formulation
            # (ops/decode_attention.py) that streams the slab once in
            # storage layout, over the plain slab or the gathered pages.
            max_len = cfg.max_seq_len
            names = (("window_key", "window_value") if window
                     else ("cached_key", "cached_value"))
            ck, cv = (self.variable(
                "cache", name, lambda: jnp.zeros((b, max_len, g * d), dtype))
                for name in names)
            idx = self.variable(
                "cache", CACHE_INDEX, lambda: jnp.array(0, jnp.int32))
            kflat = k.transpose(0, 2, 1, 3).reshape(b, l, g * d)
            vflat = v.transpose(0, 2, 1, 3).reshape(b, l, g * d)
            if window and self.has_variable("cache", STATE_ROW):
                # the engine's cache: a ring a slot (what the host pushes in
                # tells it from the plain one, as for a Mamba layer's rows)
                return out(_ring_attention(
                    self, q, (kflat, vflat), (ck, cv), idx, chunk, window,
                    scale, h, g, dtype))
            if chunk is not None:
                # the chunk's reads take the slot's row as a table of one
                chunk = chunk._replace(table_row=chunk.table_row[None])

            def append_plain(pools, i, new):
                with jax.named_scope("kv_append"):
                    return tuple(jax.lax.dynamic_update_slice(
                        p, x.astype(dtype), (0, i, 0))
                        for p, x in zip(pools, new))

            def attend_chunk(q, pools, table, start, ahead):
                (q,) = q
                if ahead is not None:   # C rows of one token: one row of C
                    q = q[ahead:].transpose(2, 1, 0, 3)
                with full():
                    o = _paged_attend_chunk(q, *pools, table, start, scale, g)
                return o if ahead is None else o.reshape(-1, 1, h * d)

            def attend_token(q, pools, i):
                # future cache slots are zeros; the kv_mask hides them
                at = jnp.arange(max_len)
                seen = (at <= i) & (at > i - window) if window else at <= i
                kvm = jnp.broadcast_to(seen[None], (b, max_len))
                o4 = flat_decode_attention(
                    q[0].transpose(0, 2, 1, 3) * scale, *pools, kvm, h,
                    dtype, g)
                return o4.reshape(b, 1, h * d)

            def attend_prompt(q, pools, i):
                # dense attention over the cache with the query offset at
                # the index: a one-time 4-D view per generate call.  Future
                # slots are zeros but kj > qi masks them out.
                ck4, cv4 = (p.reshape(b, max_len, g, d).transpose(0, 2, 1, 3)
                            for p in pools)
                o = _dense_causal_attention(q[0], ck4, cv4, scale, q_offset=i,
                                            window=window)
                return o.transpose(0, 2, 1, 3).reshape(b, l, h * d)

            def full():
                # a model of both kinds says which one a cached read is
                return (jax.named_scope("full_attention")
                        if cfg.layer_mixers is not None
                        else contextlib.nullcontext())

            def attend_rows(q, pools, table, i):
                with full():
                    return _paged_attend_rows(q[0], *pools, table, i, scale,
                                              h, g, dtype)

            o = _cached_attention(self, CacheKind(
                append_rows=_paged_append_rows, attend_rows=attend_rows,
                append_chunk=_paged_append_chunk,
                attend_chunk=attend_chunk, append_plain=append_plain,
                attend_token=attend_token, attend_prompt=attend_prompt,
            ), (q,), (kflat, vflat), (ck, cv), idx, chunk)
            return out(o)

        k, v = _repeat_kv(k, h), _repeat_kv(v, h)  # the kernels want h heads
        # imported here: a test replaces auto_dispatch_ok on its module
        from tpu_air.ops.flash_attention import (auto_dispatch_ok,
                                                 flash_attention)

        if window:
            # neither kernel below takes a window: a window layer's
            # full-sequence pass is the einsum under the window's mask (the
            # configuration refuses sequence_axis beside window layers)
            o = _dense_causal_attention(q, k, v, scale, window=window)
        elif cfg.sequence_axis is not None:
            from tpu_air.ops.ring_attention import ring_attention

            # fold heads into batch: ring expects (B·H, L_local, D)
            o = ring_attention(
                q.reshape(b * h, l, d), k.reshape(b * h, l, d),
                v.reshape(b * h, l, d), axis_name=cfg.sequence_axis,
                scale=scale, causal=True,
            ).reshape(b, h, l, d)
        elif l >= FLASH_MIN_SEQ_LEN and auto_dispatch_ok(l, l):
            # trace-time shape dispatch, no user flag: the einsum path wins
            # short sequences, the Pallas kernel (its own measured tiling)
            # wins at/above the crossover; off-TPU and tile-degenerate shapes
            # stay dense (interpret-mode flash and 1-wide tiles are both perf
            # cliffs)
            o = flash_attention(
                q.reshape(b * h, l, d), k.reshape(b * h, l, d),
                v.reshape(b * h, l, d), scale=scale, causal=True,
            ).reshape(b, h, l, d)
        else:
            o = _dense_causal_attention(q, k, v, scale)

        return out(o.transpose(0, 2, 1, 3).reshape(b, l, h * d))


class LatentAttention(nn.Module):
    """Multi-head latent attention (``deepseek_v3``), per position ``t``::

        c_q = RMSNorm(W_DQ h);  [q_n, q_r] = W_UQ c_q        h heads x (dn + dr)
        [c, k_r] = W_DKV h;  c = RMSNorm(c);  q_r, k_r = rope(q_r), rope(k_r)
        [k_n, v] = W_UKV c                                   h heads x (dn + dv)
        score = sigma (q_n . k_n + q_r . k_r);  o = softmax(score) v;  out = W_O o

    ``k_r`` is ONE vector every head shares, and what a position leaves
    behind is ``[c, k_r]`` (``config.latent_width`` numbers): the cache is one
    slab, or one page pool (``cached_latent``), whose rows are those numbers
    in whole lanes (``config.latent_row_width``, zeros behind them).  ``W_UKV`` is
    held as its two halves a head, ``k_up [r, h, dn]`` and ``v_up [r, h,
    dv]``, because the two forms below use them apart.

    Two forms of the same mathematics, picked from the call's shape at trace
    time, no option.  EXPANDED (the whole sequence, a prefill chunk, a
    prompt over the plain cache): K and V are made from the latent rows and
    attention is the dense causal one.  ABSORBED (one query position a row:
    a decode step): ``q~ = q_n W_UK^T`` scores the latent directly, the
    context is taken in latent space and ``W_UV`` applied after
    (``ops.decode_attention.latent_decode_attention``): the slab streams
    once for all heads and no K or V is ever made.  The callers over a cache
    (the paged step, chunk and mixed step, the plain cache) are
    :func:`_cached_attention`'s, as for :class:`CausalSelfAttention`, over
    the one pool; the two reads of it are this page kind's own, and one
    rule picks both (``ops.decode_attention.latent_pages_read_in_place``:
    what the trace can see, no option).  A step's rows (``attend_rows``: the
    decode step, the step's half of the mixed step): on a TPU each row's live
    pages where they lie in the pool
    (``ops.decode_attention.paged_latent_decode_attention``, a Pallas
    kernel), elsewhere every slot's pages gathered at ``slot_len`` first.  A
    chunk's rows (``attend_chunk``: the chunk program, the chunk's half of
    the mixed step): on a TPU the pages its prompt has reached,
    ``table_row[0 .. start // page_len]``, walked where they lie, K and V
    made for the page in hand and the softmax a running one
    (``ops.decode_attention.paged_latent_chunk_attention``), elsewhere the
    slot's pages gathered at ``slot_len`` and ``expanded`` over them.

    Scopes (docs/OBSERVABILITY.md): ``mla_q`` the query's path (in a decode
    step the fold of ``W_UK`` too), ``mla_latent`` the latent's (down, norm,
    rope of ``k_r``, the append; in the expanded form ``W_UKV``),
    ``kv_gather`` and ``decode_attention`` the latent gather (where the
    reads are in place: nothing) and the absorbed read, kernel or not,
    ``attn_scores`` ``attn_softmax`` ``attn_context`` the expanded form's
    attention (the chunk's walk is one kernel call under the last; the two
    transposes of ``W_UKV`` it wants under ``mla_latent``), ``mla_out``
    ``W_UV`` and ``W_O``."""

    config: LMConfig

    @nn.compact
    def __call__(self, x: Array, positions: Array, decode: bool = False,
                 chunk: Optional[ChunkRows] = None) -> Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        b, l, _ = x.shape
        h, r = cfg.n_heads, cfg.kv_lora_rank
        dn, dr, dv = cfg.qk_nope_head_dim, cfg.qk_rope_head_dim, cfg.v_head_dim
        if cfg.sequence_axis is not None:
            raise ValueError(
                "latent attention is dense; sequence_axis="
                f"{cfg.sequence_axis!r} asks for the ring")
        init = nn.initializers.normal(0.02)

        def proj(name, out):
            return nn.Dense(out, use_bias=False, dtype=dtype,
                            kernel_init=init, name=name)

        inv_freq = None
        if cfg.rope_factor > 1:
            inv_freq = yarn_inv_freq(
                dr, cfg.rope_theta, cfg.rope_factor, cfg.rope_original_len,
                cfg.rope_beta_fast, cfg.rope_beta_slow)
        # as published: cos and sin times mscale / mscale_all_dim (1 where
        # the two are equal), the softmax scale times mscale_all_dim's square
        all_dim = yarn_mscale(cfg.rope_factor, cfg.rope_mscale_all_dim)
        wave = yarn_mscale(cfg.rope_factor, cfg.rope_mscale) / all_dim
        scale = (dn + dr) ** -0.5 * all_dim ** 2

        def turned(v):
            v = rope(v, positions, cfg.rope_theta, inv_freq)
            return v if wave == 1.0 else (v * wave).astype(v.dtype)

        with jax.named_scope("mla_q"):
            cq = RMSNorm(cfg.rmsnorm_eps, dtype, name="q_a_norm")(
                proj("q_a", cfg.q_lora_rank)(x))
            q = proj("q_b", h * (dn + dr))(cq)
            q = q.reshape(b, l, h, dn + dr).transpose(0, 2, 1, 3)
            q_n, q_r = q[..., :dn], turned(q[..., dn:])
        with jax.named_scope("mla_latent"):
            ckr = proj("kv_a", r + dr)(x)
            c = RMSNorm(cfg.rmsnorm_eps, dtype, name="kv_a_norm")(ckr[..., :r])
            k_r = turned(ckr[..., r:][:, None])[:, 0]
            # a cached row: [c, k_r] and zeros up to whole lanes
            w = cfg.latent_row_width
            latent = jnp.concatenate(
                [c, k_r, jnp.zeros((b, l, w - r - dr), c.dtype)],
                -1).astype(dtype)                               # [b, l, w]
        k_up = self.param("k_up", init, (r, h, dn), jnp.float32).astype(dtype)
        v_up = self.param("v_up", init, (r, h, dv), jnp.float32).astype(dtype)
        f32 = dict(preferred_element_type=jnp.float32)

        def expanded(q_n, q_r, lat, q_offset):
            """``q_* [B, h, Lq, .]`` over the latent rows ``lat [B, Lk, w]``,
            causal with the first query at ``q_offset`` -> ``[B, Lq, h*dv]``."""
            with jax.named_scope("mla_latent"):
                k_n = jnp.einsum("bkr,rhn->bhkn", lat[..., :r], k_up)
                v = jnp.einsum("bkr,rhv->bhkv", lat[..., :r], v_up)
            with jax.named_scope("attn_scores"):
                s = (jnp.einsum("bhqn,bhkn->bhqk", q_n, k_n, **f32)
                     + jnp.einsum("bhqd,bkd->bhqk", q_r,
                                  lat[..., r:r + dr], **f32)) * scale
                lq, lk = q_n.shape[2], lat.shape[1]
                qi = q_offset + jax.lax.broadcasted_iota(
                    jnp.int32, (lq, lk), 0)
                kj = jax.lax.broadcasted_iota(jnp.int32, (lq, lk), 1)
                s = jnp.where(qi >= kj, s, NEG_INF)
            with jax.named_scope("attn_softmax"):
                p = jax.nn.softmax(s, axis=-1)
            with jax.named_scope("attn_context"):
                o = jnp.einsum("bhqk,bhkv->bhqv", p.astype(dtype), v, **f32)
            return o.astype(dtype).transpose(0, 2, 1, 3).reshape(
                q_n.shape[0], q_n.shape[2], h * dv)

        def fold(q_n, q_r):
            """The ABSORBED form's queries: ``q_* [S, h, 1, .]`` -> ``[S, h,
            w]`` in latent space (``q_n W_UK``, then the roped part, the
            softmax scale folded in, zeros against the row's trailing
            zeros)."""
            with jax.named_scope("mla_q"):
                qt = jnp.einsum("shn,rhn->shr", q_n[:, :, 0], k_up, **f32)
                qc = jnp.concatenate(
                    [qt, q_r[:, :, 0].astype(jnp.float32)], -1) * scale
                return jnp.pad(qc, ((0, 0), (0, 0), (0, w - r - dr)))

        def lift(o_lat):
            """The absorbed form's context, taken in latent space ``[S, h,
            r]`` -> ``[S, 1, h*dv]``."""
            with jax.named_scope("mla_out"):
                o = jnp.einsum("shr,rhv->shv", o_lat, v_up, **f32)
            return o.astype(dtype).reshape(o_lat.shape[0], 1, h * dv)

        def out(o):
            with jax.named_scope("mla_out"):
                return proj("o", cfg.d_model)(o)

        if not decode:
            return out(expanded(q_n, q_r, latent, 0))

        max_len = cfg.max_seq_len
        cl = self.variable("cache", "cached_latent", lambda: jnp.zeros(
            (b, max_len, w), dtype))
        idx = self.variable(
            "cache", CACHE_INDEX, lambda: jnp.array(0, jnp.int32))

        def append_rows(pools, table, i, new):
            """Row ``s``'s new latent ``[S, 1, w]`` to its current ``(table[s,
            i // C], i % C)``."""
            (pool,), (lat,) = pools, new
            C = pool.shape[1]
            page = table[jnp.arange(table.shape[0]), i // C]
            with jax.named_scope("mla_latent"), jax.named_scope("kv_append"):
                return (pool.at[page, i % C].set(lat[:, 0]),)

        def append_chunk(pools, page, new):
            """One slot's chunk ``[1, C, w]`` over ``page``."""
            with jax.named_scope("mla_latent"), jax.named_scope("kv_append"):
                return (jax.lax.dynamic_update_slice(
                    pools[0], new[0], (page, 0, 0)),)

        def append_plain(pools, i, new):
            with jax.named_scope("mla_latent"), jax.named_scope("kv_append"):
                return (jax.lax.dynamic_update_slice(
                    pools[0], new[0], (0, i, 0)),)

        def attend_chunk(q, pools, where, start, ahead):
            """A chunk's rows at positions ``start .. start + C - 1`` of the
            slot whose table row ``where`` is (or holds first): the pages the
            prompt has reached walked where they lie, or the slot's pages
            gathered at ``slot_len`` and attended densely (the rule is
            ``attend_rows``'s)."""
            (pool,) = pools
            if ahead is None:
                q_n, q_r, table_row = q[0][0], q[1][0], where[0]
            else:    # C rows of one token
                q_n, q_r = (x[ahead:, :, 0].transpose(1, 0, 2) for x in q)
                table_row = where
            # q_* [h, C, .] -> o [C, h*dv]
            if decode_attention.latent_pages_read_in_place(pool):
                with jax.named_scope("mla_latent"):
                    ku, vu = k_up.transpose(1, 0, 2), v_up.transpose(1, 0, 2)
                o = decode_attention.paged_latent_chunk_attention(
                    q_n, q_r, ku, vu, pool, table_row, start, scale, dtype)
                o = o.transpose(1, 0, 2).reshape(q_n.shape[1], h * dv)
            else:
                o = expanded(q_n[None], q_r[None],
                             gather_pages(pool, table_row[None]), start)[0]
            return o[None] if ahead is None else o[:, None]

        def attend_rows(q, pools, table, i):
            """The step's rows, each over positions ``0 .. i`` of its slot:
            its live pages read where they lie, or every slot's pages
            gathered at ``slot_len`` first (``ops.decode_attention`` has the
            rule)."""
            (pool,) = pools
            if decode_attention.latent_pages_read_in_place(pool):
                return lift(decode_attention.paged_latent_decode_attention(
                    fold(*q), pool, table, i, r, dtype))
            kvm = jnp.arange(table.shape[1] * pool.shape[1])[None, :] \
                <= i[:, None]
            lat = gather_pages(pool, table)
            return lift(latent_decode_attention(
                fold(*q), lat, kvm, r, dtype))

        def attend_token(q, pools, i):
            kvm = jnp.broadcast_to((jnp.arange(max_len) <= i)[None],
                                   (b, max_len))
            return lift(latent_decode_attention(
                fold(*q), pools[0], kvm, r, dtype))

        # the engine's paged cache: ``cached_latent`` is ONE page pool
        # [P, page_len, w]; table, null page and the callers are
        # _cached_attention's.  A prompt over the plain cache: future cache
        # rows are zeros and kj > qi masks them out
        return out(_cached_attention(self, CacheKind(
            append_rows=append_rows, attend_rows=attend_rows,
            append_chunk=append_chunk, attend_chunk=attend_chunk,
            append_plain=append_plain, attend_token=attend_token,
            attend_prompt=lambda q, pools, i: expanded(*q, pools[0], i),
        ), (q_n, q_r), (latent,), (cl,), idx, chunk))


class SwiGLU(nn.Module):
    config: LMConfig
    width: Optional[int] = None     # default config.d_ff

    @nn.compact
    def __call__(self, x: Array) -> Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        width = self.width or cfg.d_ff
        dense = lambda name, out: nn.Dense(  # noqa: E731
            out, use_bias=False, dtype=dtype,
            kernel_init=nn.initializers.normal(0.02), name=name)
        gate = nn.silu(dense("gate", width)(x))
        up = dense("up", width)(x)
        return dense("down", cfg.d_model)(gate * up)


class ReLU2(nn.Module):
    """``down(relu(up x)^2)``: the two-matrix feed-forward ``nemotron_h``
    publishes (``mlp_hidden_act: relu2``), no gate and no bias."""

    config: LMConfig
    width: int

    @nn.compact
    def __call__(self, x: Array) -> Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        dense = lambda name, out: nn.Dense(  # noqa: E731
            out, use_bias=False, dtype=dtype,
            kernel_init=nn.initializers.normal(0.02), name=name)
        return dense("down", cfg.d_model)(
            jnp.square(nn.relu(dense("up", self.width)(x))))


def grouped_sigmoid_routing(logits: Array, bias: Array, k: int, groups: int,
                            topk_groups: int, scale: float):
    """``deepseek_v3``'s ``noaux_tc`` routing over ``logits [t, E]`` float32:
    ``s = sigmoid(logits)``; the selection scores are ``s + bias``; the
    ``groups`` groups of ``E / groups`` consecutive experts are ranked by the
    sum of their two largest selection scores and the best ``topk_groups``
    stay; the token's experts are the ``k`` largest selection scores among
    those groups' experts; their weights are ``scale * s_e / (sum of the
    chosen s + 1e-20)``: the bias selects, it does not weigh.  Ties go to the
    lower index (``lax.top_k``).  Returns ``(weights [t, k] float32, chosen
    [t, k] int32)``."""
    t, e = logits.shape
    s = jax.nn.sigmoid(logits)
    pick = s + bias
    by_group = pick.reshape(t, groups, e // groups)
    rank = jax.lax.top_k(by_group, 2)[0].sum(-1)              # [t, groups]
    best = jax.lax.top_k(rank, topk_groups)[1]
    stays = jnp.zeros((t, groups), bool).at[
        jnp.arange(t)[:, None], best].set(True)
    among = jnp.where(stays[:, :, None], by_group, -jnp.inf).reshape(t, e)
    chosen = jax.lax.top_k(among, k)[1]
    w = jnp.take_along_axis(s, chosen, axis=-1)
    return scale * w / (w.sum(-1, keepdims=True) + 1e-20), chosen


class SparseExperts(nn.Module):
    """Routed feed-forward: ``y[t] = sum over the token's top-k experts e of
    w[t, e] * down_e(silu(gate_e x[t]) * up_e x[t])``.  The router is float32
    and follows ``config.router``: ``"softmax"``: ``w = softmax(x @ router)``
    NOT renormalised over the chosen k (OLMoE); ``"sigmoid_groups"``:
    :func:`grouped_sigmoid_routing` with the learned selection bias
    ``router_bias`` (``deepseek_v3``).  Every assignment to an expert this
    tree HOLDS is computed (``ops.moe.expert_ffn``: no capacity, nothing
    dropped or re-routed, whatever the load).  The tree holds
    ``config.experts_held`` experts from id ``config.experts_first``; the
    router scores all ``config.num_experts``, and an assignment to an expert
    held elsewhere is counted and contributes nothing here (its rank adds
    it; the sum of all ranks' outputs is the uncut layer's).

    ``config.ff_act`` ``"relu2"``: an expert is two matrices, ``down_e(relu(
    up_e x)^2)``, and the tree has no ``gate``.  ``config.moe_latent_size``
    (``nemotron_h``'s LatentMoE): the experts work on ``l = latent_down x``,
    that many numbers wide, and ``latent_up`` takes their weighted sum back
    to ``d_model``; the router scores the FULL hidden state.  Over a share
    the down-projection is computed here for every token and ``latent_up`` is
    applied to the held experts' partial sum: it is linear, so the ranks'
    parts add as before.

    Sows ``expert_rows`` int32 into ``intermediates`` for callers that make
    it mutable (the engine's routing counters): ``[tokens, E]``, 1 where the
    token went to the expert; where only a share is held ``[tokens, held +
    1]``, the held experts' columns and then HOW MANY of the token's
    assignments went elsewhere."""

    config: LMConfig

    @nn.compact
    def __call__(self, x: Array) -> Array:
        from tpu_air.ops.moe import expert_ffn

        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        e, k, d, f = (cfg.num_experts, cfg.num_experts_per_tok, cfg.d_model,
                      cfg.d_ff)
        held, whole = cfg.experts_held, cfg.holds_all_experts
        init = nn.initializers.normal(0.02)
        router = self.param("router", init, (d, e), jnp.float32)
        # the width the experts work in: the model's, or the latent's
        dl = cfg.moe_latent_size or d
        gate = None
        if cfg.ff_act != "relu2":
            gate = self.param("gate", init, (held, dl, f), jnp.float32)
        up = self.param("up", init, (held, dl, f), jnp.float32)
        down = self.param("down", init, (held, f, dl), jnp.float32)
        t = x.reshape(-1, d)
        with jax.named_scope("moe_router"):
            logits = jnp.dot(t.astype(jnp.float32), router.astype(jnp.float32),
                             precision=jax.lax.Precision.HIGHEST)
            if cfg.router == "softmax":
                probs, chosen = jax.lax.top_k(
                    jax.nn.softmax(logits, axis=-1), k)
            else:
                bias = self.param("router_bias", nn.initializers.zeros, (e,),
                                  jnp.float32)
                probs, chosen = grouped_sigmoid_routing(
                    logits, bias.astype(jnp.float32), k, cfg.router_groups,
                    cfg.router_topk_groups, cfg.router_scale)
            if not whole:
                # ids among the held ones; ``held`` itself: held elsewhere
                local = chosen - cfg.experts_first
                chosen = jnp.where((local >= 0) & (local < held), local, held)
            rows = jnp.zeros((t.shape[0], held), jnp.int32).at[
                jnp.arange(t.shape[0])[:, None], chosen].set(1, mode="drop")
            if not whole:
                rows = jnp.concatenate(
                    [rows, (chosen == held).sum(-1, dtype=jnp.int32)[:, None]],
                    axis=-1)
            self.sow("intermediates", "expert_rows", rows)
        t = t.astype(dtype)
        if cfg.moe_latent_size:
            with jax.named_scope("moe_latent_down"):
                t = nn.Dense(dl, use_bias=False, dtype=dtype,
                             kernel_init=init, name="latent_down")(t)
        y = expert_ffn(t, chosen, probs,
                       None if gate is None else gate.astype(dtype),
                       up.astype(dtype), down.astype(dtype)).astype(dtype)
        if cfg.moe_latent_size:
            with jax.named_scope("moe_latent_up"):
                y = nn.Dense(d, use_bias=False, dtype=dtype,
                             kernel_init=init, name="latent_up")(y)
        return y.reshape(x.shape)


def expert_assignments(intermediates) -> Array:
    """``[layers, tokens, E]`` int32 (``E``: the experts held, and one more
    column where a share is held): what every ``SparseExperts`` layer sowed
    as ``expert_rows`` (callers sum or count over the layers: their order
    here is the tree's, not the model's)."""
    return jnp.stack([
        v for path, v in jax.tree_util.tree_flatten_with_path(intermediates)[0]
        if any(getattr(p, "key", None) == "expert_rows" for p in path)])


class DepthwiseConv(nn.Module):
    """The Mamba mixer's causal depthwise convolution over positions:
    ``kernel [width, channels]`` (the last tap multiplies the current
    position) and ``bias``; the caller carries ``tail [b, (width-1) *
    channels]``, the ``width - 1`` inputs before the call, flat as stored
    (``ops/ssm.py``).  Returns the outputs and the tail after the call's
    ``valid_len [b]`` real positions (with ``ride``, a mixed step's two
    tails)."""

    width: int
    ssd: bool = False   # Mamba-2's: the device rows say ssd_conv

    @nn.compact
    def __call__(self, x: Array, tail: Array, valid_len: Array, ride=None):
        b, l, c = x.shape
        # fan-in of a channel is the width (the published Conv1d default)
        bound = self.width ** -0.5
        uniform = lambda key, shape, dt: jax.random.uniform(  # noqa: E731
            key, shape, dt, -bound, bound)
        kernel = self.param("kernel", uniform, (self.width, c), jnp.float32)
        bias = self.param("bias", uniform, (c,), jnp.float32)

        def chunk(x, tail, valid_len):
            y, tail = ssm.causal_conv_chunk(
                x, tail.reshape(-1, self.width - 1, c), kernel, bias,
                valid_len)
            return y, tail.reshape(tail.shape[0], -1)

        with (jax.named_scope("ssd_conv") if self.ssd
              else jax.named_scope("ssm_conv")):
            if ride is not None:
                # mixed step (ChunkRows): one position of the ``s`` rows
                # whose tails came in, then one row's chunk from ``ride``,
                # its own ``(tail [1, ..], valid_len [1])``
                s = tail.shape[0]
                y, tail = ssm.causal_conv_step(x[:s, 0], tail, kernel, bias,
                                               valid_len > 0)
                y_c, tail_c = chunk(x[s:, 0][None], *ride)
                return (jnp.concatenate([y, y_c[0]])[:, None],
                        (tail, tail_c))
            if l == 1:
                y, tail = ssm.causal_conv_step(x[:, 0], tail, kernel, bias,
                                               valid_len > 0)
                return y[:, None], tail
            return chunk(x, tail, valid_len)


def _init_dt_bias(key, shape, dtype=jnp.float32):
    """Mamba's own: ``softplus(bias)`` log-uniform in [1e-3, 1e-1]."""
    dt0 = jnp.exp(jax.random.uniform(key, shape, jnp.float32)
                  * (jnp.log(0.1) - jnp.log(1e-3)) + jnp.log(1e-3))
    return (dt0 + jnp.log(-jnp.expm1(-dt0))).astype(dtype)


class _SlotRows:
    """What a Mamba mixer keeps a sequence, and how a call reaches it: the
    convolution tail and the float32 state, a row a sequence, in the
    ``cache`` collection (``conv_state [S, tail_width]`` in the model's
    dtype, ``ssm_state [S, *state_shape]``), for :class:`MambaMixer` and
    :class:`Mamba2Mixer` alike.  Three callers, told apart by what the cache
    holds (:class:`MambaMixer` has them); ``tail``, ``state``, ``valid`` and
    ``ride`` are what the call starts from (``ride``: a mixed step's chunk,
    the ``(tail [1, ..], valid_len [1])`` :class:`DepthwiseConv` wants), and
    :meth:`run` runs the recurrence for the call's shape and writes back."""

    def __init__(self, mod: nn.Module, decode: bool,
                 chunk: Optional[ChunkRows], b: int, l: int, tail_width: int,
                 state_shape, dtype):
        self.decode, self.one_token = decode, l == 1
        self.tail = jnp.zeros((b, tail_width), dtype)
        self.state = jnp.zeros((b,) + state_shape, jnp.float32)
        self.valid = jnp.full((b,), l, jnp.int32)
        # what the host pushes in (models/lm/paged_cache.py) tells the engine's
        # cache from the plain one
        self.engine = decode and mod.has_variable("cache", STATE_ROW)
        if decode:
            self.cs = mod.variable(
                "cache", "conv_state",
                lambda: jnp.zeros((b, tail_width), dtype))
            self.ss = mod.variable(
                "cache", "ssm_state",
                lambda: jnp.zeros((b,) + state_shape, jnp.float32))
            self.tail, self.state = self.cs.value, self.ss.value
        self.ride = None
        if self.engine:
            index = mod.get_variable("cache", CACHE_INDEX)
            self.valid = mod.get_variable("cache", VALID_LEN)
            if chunk is not None:
                # mixed step: every slot's row takes one token (or is held)
                # and rows [S:] are the chunk of ``chunk.slot``, from what
                # that slot holds as the step begins
                self.row, self.fresh = chunk.slot, chunk.start == 0
                self.ride = (self._row_of(self.tail), chunk.valid[None])
            elif l > 1:
                # one chunk of one row's prompt (b == 1)
                self.row, self.fresh = mod.get_variable(
                    "cache", STATE_ROW)[0], index[0] == 0
                self.tail, self.state = (self._row_of(self.tail),
                                         self._row_of(self.state))
                self.valid = self.valid[:1]

    def _row_of(self, rows):
        """The call's slot's row of ``rows`` as a chunk starts from it: zeros
        where the chunk is its prompt's first."""
        return jnp.where(self.fresh, 0, jax.lax.dynamic_slice_in_dim(
            rows, self.row, 1, axis=0))

    def run(self, xs, new_tail, scan, update):
        """``xs``: the per-position inputs of the recurrence, ``[rows, l,
        ..]`` each; ``scan(*xs, state, valid)`` a call of several positions,
        ``update(*xs, state, valid)`` the one-token call over all rows, both
        ``-> (y, state')``; ``new_tail`` what the convolution left.  Returns
        ``y`` and keeps tail and state where the cache wants them."""
        if self.ride is not None:
            S = self.state.shape[0]
            y, new_state = update(*(a[:S] for a in xs), self.state,
                                  self.valid)
            # the chunk's row is read from what the step's half left: it
            # held that row, so this is the state the slot had, and the pool
            # of states has ONE reader at a time (read beside the update,
            # the chip's compiler copies every layer's 42 MB of state first
            # at full depth: PERF.md, PR 42).  The chunk's positions as one
            # row: [C, 1, ..] -> [1, C, ..]
            y_c, state_c = scan(*(jnp.swapaxes(a[S:], 0, 1) for a in xs),
                                self._row_of(new_state), self.ride[1])
            y = jnp.concatenate([y, jnp.swapaxes(y_c, 0, 1)])
            new_tail, tail_c = new_tail
        elif self.one_token and self.decode:
            y, new_state = update(*xs, self.state, self.valid)
        else:
            y, new_state = scan(*xs, self.state, self.valid)
        if self.decode:
            if self.ride is not None:
                # the step held the chunk's row (it rides at position 0); the
                # chunk's own state is written over it, and is what lands
                new_tail = jax.lax.dynamic_update_slice_in_dim(
                    new_tail, tail_c, self.row, axis=0)
                new_state = jax.lax.dynamic_update_slice_in_dim(
                    new_state, state_c, self.row, axis=0)
            elif self.engine and not self.one_token:
                new_tail = jax.lax.dynamic_update_slice_in_dim(
                    self.cs.value, new_tail, self.row, axis=0)
                new_state = jax.lax.dynamic_update_slice_in_dim(
                    self.ss.value, new_state, self.row, axis=0)
            self.cs.value, self.ss.value = new_tail, new_state
        return y


class MambaMixer(nn.Module):
    """Mamba-1 selective state-space mixer, with Jamba's three inner norms
    (over the step size's low-rank input and over B and C)::

        [u, z] = in_proj(h);  u = silu(conv(u))
        [dt, B, C] = x_proj(u);  dt, B, C = dt_norm(dt), b_norm(B), c_norm(C)
        dt = softplus(dt_proj(dt));  A = -exp(A_log)
        s_t = exp(dt_t A) s_{t-1} + dt_t B_t u_t;  y_t = C_t . s_t + D u_t
        out = out_proj(y * silu(z))

    What it keeps between calls is a convolution tail (the last ``d_conv -
    1`` inputs of the convolution) and the float32 state, a row a sequence:
    ``conv_state [S, (d_conv-1) * d_inner]`` (flat: whole tiles on the chip)
    and ``ssm_state [S, d_state, d_inner]`` (state-major, ``ops/ssm.py``) in
    the ``cache`` collection.  Three callers, told apart by what the cache
    holds:

    * no cache (``decode=False``): the whole sequence from a zero state;
    * a plain cache (``generate``): row ``b`` of the state is sequence ``b``;
      a multi-token call is the prompt, a one-token call a decode step;
    * the engine's cache (``state_row`` present; models/lm/paged_cache.py):
      ``valid_len [S]`` says how many of the call's positions are real for
      each row and ``cache_index [S]`` where the call starts.  A one-token
      call is the pool's decode step over ALL rows, and a row with
      ``valid_len`` 0 (free, or mid-prefill: its chunks are building its
      state) keeps its state bit for bit.  A longer call is one chunk of the
      prompt of row ``state_row[0]``: it starts from zeros when it is the
      prompt's first (``cache_index`` 0: a slot's last tenant left its state
      behind), and its padded positions neither advance the state nor enter
      the tail.  With ``chunk`` (:class:`ChunkRows`) the call is both at
      once, the engine's mixed step: the projections see the decode step's
      rows and the chunk's positions together, the recurrence takes each
      part as above, and the chunk's slot, which the step holds, ends with
      the chunk's state."""

    config: LMConfig

    @nn.compact
    def __call__(self, x: Array, decode: bool = False,
                 chunk: Optional[ChunkRows] = None) -> Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        b, l, _ = x.shape
        c, n, k, r = (cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv,
                      cfg.mamba_dt_rank)
        dense = lambda name, out, **kw: nn.Dense(  # noqa: E731
            out, use_bias=False, dtype=dtype,
            kernel_init=nn.initializers.normal(0.02), name=name, **kw)
        a_log = self.param(
            "A_log", lambda key, shape, dt: jnp.log(jnp.broadcast_to(
                jnp.arange(1, n + 1, dtype=dt), shape)), (c, n), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (c,), jnp.float32)
        A = -jnp.exp(a_log.astype(jnp.float32)).T                  # [n, c]

        rows = _SlotRows(self, decode, chunk, b, l, (k - 1) * c, (n, c), dtype)

        def scan(u, dt, B, C, state, valid):
            with jax.named_scope("ssm_scan"):
                # bounded pieces: the scan makes [b, piece, n, c] float32
                # terms for all of a piece's positions at once
                ys, piece = [], 256
                for p0 in range(0, u.shape[1], piece):
                    sl = slice(p0, p0 + piece)
                    y, state = ssm.selective_scan_chunk(
                        u[:, sl], dt[:, sl], A, B[:, sl], C[:, sl], skip,
                        state, jnp.clip(valid - p0, 0, piece))
                    ys.append(y)
                y = ys[0] if len(ys) == 1 else jnp.concatenate(ys, axis=1)
            return y, state

        def update(u, dt, B, C, state, valid):
            with jax.named_scope("ssm_state_update"):
                y, state = ssm.selective_state_update(
                    u[:, 0], dt[:, 0], A, B[:, 0], C[:, 0], skip, state,
                    valid > 0)
                return y[:, None], state

        uz = dense("in_proj", 2 * c)(x)
        u, z = uz[..., :c], uz[..., c:]
        u, new_tail = DepthwiseConv(k, name="conv")(
            u, rows.tail, rows.valid, rows.ride)
        u = nn.silu(u).astype(dtype)
        dbc = dense("x_proj", r + 2 * n)(u)
        dt = RMSNorm(cfg.rmsnorm_eps, dtype, name="dt_norm")(dbc[..., :r])
        B = RMSNorm(cfg.rmsnorm_eps, dtype, name="b_norm")(dbc[..., r:r + n])
        C = RMSNorm(cfg.rmsnorm_eps, dtype, name="c_norm")(dbc[..., r + n:])
        dt = nn.softplus(nn.Dense(
            c, use_bias=True, dtype=jnp.float32, name="dt_proj",
            kernel_init=lambda key, shape, dt_: jax.random.uniform(
                key, shape, dt_, -r ** -0.5, r ** -0.5),
            bias_init=_init_dt_bias)(dt.astype(jnp.float32)))
        y = rows.run((u, dt, B, C), new_tail, scan, update)
        return dense("out_proj", cfg.d_model)(
            (y * nn.silu(z.astype(jnp.float32))).astype(dtype))


class Mamba2Mixer(nn.Module):
    """Mamba-2 mixer as ``nemotron_h`` publishes it: ``H`` heads of ``P``
    channels, ``G`` groups of heads that share B and C, ONE convolution over
    x, B and C together, a scalar decay a head, the gate BEFORE a group
    RMSNorm::

        [z | xBC | dt] = in_proj(h);  xBC = silu(conv(xBC));  [u | B | C] = xBC
        dt = softplus(dt + dt_bias);  A = -exp(A_log)                 a head
        S_t[h] = exp(dt_t[h] A[h]) S_{t-1}[h] + dt_t[h] u_t[h] (x) B_t[g(h)]
        y_t[h] = S_t[h] C_t[g(h)] + D[h] u_t[h]
        out = out_proj(RMSNorm_groups(y * silu(z)) * w)

    (the mean square over each group's ``d_inner / G`` channels).  What it
    keeps between calls is :class:`MambaMixer`'s, a row a sequence:
    ``conv_state [S, (d_conv-1) * conv_dim]`` in the model's dtype and float32
    ``ssm_state [S, H, P, N]``, as published (``ops/ssm.py``: whole tiles).
    The callers are :class:`MambaMixer`'s three, told apart the same way; a
    chunk runs the block form (``ssm.ssd_chunk``, blocks of
    ``config.mamba_chunk_size``), a decode step the one-token update
    (``ssm.ssd_state_update``: over all rows, or on a TPU over the live rows'
    state alone, in place; the rule is ``ssm.state_rows_move_in_place``).

    Scopes (docs/OBSERVABILITY.md): ``ssd_conv`` the convolution,
    ``ssd_scan`` a chunk's block form, ``ssd_state_update`` the step's pass
    over the state, ``ssd_gate_norm`` the gate and the group norm."""

    config: LMConfig

    @nn.compact
    def __call__(self, x: Array, decode: bool = False,
                 chunk: Optional[ChunkRows] = None) -> Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        b, l, _ = x.shape
        H, P, G, n, k = (cfg.mamba_n_heads, cfg.mamba_head_dim,
                         cfg.mamba_n_groups, cfg.mamba_d_state,
                         cfg.mamba_d_conv)
        c, cd = cfg.mamba_d_inner, cfg.mamba2_conv_dim
        dense = lambda name, out: nn.Dense(  # noqa: E731
            out, use_bias=False, dtype=dtype,
            kernel_init=nn.initializers.normal(0.02), name=name)
        a_log = self.param(
            "A_log", lambda key, shape, dt: jnp.log(jax.random.uniform(
                key, shape, dt, 1.0, 16.0)), (H,), jnp.float32)
        skip = self.param("D", nn.initializers.ones, (H,), jnp.float32)
        dt_bias = self.param("dt_bias", _init_dt_bias, (H,), jnp.float32)
        A = -jnp.exp(a_log.astype(jnp.float32))                     # [H]

        rows = _SlotRows(self, decode, chunk, b, l, (k - 1) * cd, (H, P, n),
                         dtype)

        def heads(u, B, C):
            """``[r, l, ..]`` flat as projected -> by head and by group."""
            r, q = u.shape[:2]
            return (u.reshape(r, q, H, P), B.reshape(r, q, G, n),
                    C.reshape(r, q, G, n))

        def scan(u, dt, B, C, state, valid):
            with jax.named_scope("ssd_scan"):
                u4, B4, C4 = heads(u, B, C)
                y, state = ssm.ssd_chunk(u4, dt, A, B4, C4, skip, state,
                                         valid, cfg.mamba_chunk_size)
            return y.reshape(y.shape[:2] + (c,)), state

        def update(u, dt, B, C, state, valid):
            with jax.named_scope("ssd_state_update"):
                u4, B4, C4 = heads(u, B, C)
                y, state = ssm.ssd_state_update(
                    u4[:, 0], dt[:, 0], A, B4[:, 0], C4[:, 0], skip, state,
                    valid > 0)
                return y.reshape(y.shape[0], 1, c), state

        zxd = dense("in_proj", 2 * c + 2 * G * n + H)(x)
        z, xbc, dt = zxd[..., :c], zxd[..., c:c + cd], zxd[..., c + cd:]
        xbc, new_tail = DepthwiseConv(k, ssd=True, name="conv")(
            xbc, rows.tail, rows.valid, rows.ride)
        xbc = nn.silu(xbc).astype(dtype)
        u, B, C = xbc[..., :c], xbc[..., c:c + G * n], xbc[..., c + G * n:]
        dt = nn.softplus(dt.astype(jnp.float32) + dt_bias.astype(jnp.float32))
        y = rows.run((u, dt, B, C), new_tail, scan, update)
        with jax.named_scope("ssd_gate_norm"):
            w = self.param("norm", nn.initializers.ones, (c,), jnp.float32)
            y = (y * nn.silu(z.astype(jnp.float32))).reshape(
                y.shape[:2] + (G, c // G))
            y = y * jax.lax.rsqrt(
                jnp.mean(jnp.square(y), -1, keepdims=True) + cfg.rmsnorm_eps)
            y = (y.reshape(y.shape[:2] + (c,)) * w).astype(dtype)
        return dense("out_proj", cfg.d_model)(y)


class HyperConnection(nn.Module):
    """The maps of ONE sublayer's manifold-constrained hyper-connection
    (``ops/mhc.py`` has the equations): ``phi [n*C, n*n + 2n]``, ``b [n*n +
    2n]`` and ``alpha [3]`` (pre, post, res), float32 whatever the model
    computes in.  ``streams [..., n, C]`` -> ``(h, H_post, H_res)``: the
    sublayer's input, and what :func:`mhc.post` writes its output back
    through.  A fresh module starts where the paper does: ``alpha`` 0.01,
    ``b`` zeros but for 4 on ``H~res``'s diagonal (streams that mostly keep
    to themselves)."""

    config: LMConfig

    @nn.compact
    def __call__(self, streams: Array):
        cfg = self.config
        n, c = streams.shape[-2:]
        phi = self.param("phi", nn.initializers.normal((n * c) ** -0.5),
                         (n * c, n * n + 2 * n), jnp.float32)
        b = self.param(
            "b", lambda key, shape, dt: jnp.concatenate(
                [jnp.zeros(2 * n, dt), 4.0 * jnp.eye(n, dtype=dt).ravel()]),
            (n * n + 2 * n,), jnp.float32)
        alpha = self.param("alpha", nn.initializers.constant(0.01), (3,),
                           jnp.float32)
        h, h_post, res = mhc.pre(streams, phi, b, alpha, cfg.rmsnorm_eps)
        return h, h_post, mhc.sinkhorn(res, cfg.hc_sinkhorn_iters,
                                       cfg.hc_eps, cfg.hc_res_clamp)


def cast_params(params, dtype):
    """``params`` with every array leaf in ``dtype`` but those a model of any
    dtype keeps float32: the maps of a :class:`HyperConnection` (``Block``
    names the two of a layer ``attn_hc`` and ``mlp_hc``).  The one rule for
    whoever casts a ``CausalLM`` tree to the dtype it serves or trains in
    (``CausalLM.cast_params``)."""
    dtype = jnp.dtype(dtype)

    def cast(path, x):
        if not hasattr(x, "astype") or any(
                str(getattr(k, "key", "")).endswith("_hc") for k in path):
            return x
        return x.astype(dtype)

    return jax.tree_util.tree_map_with_path(cast, params)


class Block(nn.Module):
    """One layer: a sequence mixer (``kind``) and then a feed-forward
    (``ff``), each ``x + f(RMSNorm(x))``; either may be ``"none"`` (a
    ``layer_pattern``'s layer is ONE of the two).  With ``config.hc_mult >
    1`` ``x`` is ``[b, l, n, C]`` and each of the two goes through its own
    :class:`HyperConnection` (``attn_hc``, ``mlp_hc``) instead of the
    sum."""

    config: LMConfig
    kind: str = "attention"   # LMConfig.layer_kinds()
    ff: str = "dense"         # LMConfig.ff_kinds()

    @nn.compact
    def __call__(self, x: Array, positions: Array, deterministic: bool = True,
                 decode: bool = False,
                 chunk: Optional[ChunkRows] = None) -> Array:
        cfg = self.config
        dtype = jnp.dtype(cfg.dtype)
        drop = nn.Dropout(cfg.dropout_rate, deterministic=deterministic)

        def residual(x, name, f):
            if cfg.hc_mult == 1:
                return x + drop(f(x))
            h, h_post, h_res = HyperConnection(cfg, name=name + "_hc")(x)
            return mhc.post(x, drop(f(h)), h_res, h_post)

        # ``chunk``: only the sequence mixer tells a mixed step's two parts
        # apart; the norms, the maps of the residual streams and the
        # feed-forward below take its rows as rows
        if self.kind in ("mamba", "mamba2"):
            mixer = MambaMixer if self.kind == "mamba" else Mamba2Mixer
            x = residual(x, "mamba", lambda h: mixer(cfg, name="mamba")(
                RMSNorm(cfg.rmsnorm_eps, dtype, name="mamba_norm")(h),
                decode=decode, chunk=chunk))
        elif self.kind != "none":
            mixer = (LatentAttention if self.kind == "latent"
                     else functools.partial(CausalSelfAttention,
                                            kind=self.kind))
            x = residual(x, "attn", lambda h: mixer(cfg, name="attn")(
                RMSNorm(cfg.rmsnorm_eps, dtype, name="attn_norm")(h),
                positions, decode=decode, chunk=chunk,
            ))
        if self.ff == "none":
            return x
        # the feed-forward kind follows from the configuration's numbers
        ffn = ReLU2 if cfg.ff_act == "relu2" else SwiGLU

        def feed_forward(x):
            h = RMSNorm(cfg.rmsnorm_eps, dtype, name="mlp_norm")(x)
            if self.ff != "sparse":
                return ffn(cfg, cfg.dense_d_ff, name="mlp")(h)
            y = SparseExperts(cfg, name="moe")(h)
            if cfg.num_shared_experts:
                # every token's, beside its routed ones
                with jax.named_scope("moe_shared"):
                    y = y + ffn(cfg, cfg.shared_d_ff, name="shared")(h)
            return y

        return residual(x, "mlp", feed_forward)


class CausalLM(nn.Module):
    """``apply(params, input_ids, positions=None) -> logits``.

    ``positions``: (B, L) global positions; defaults to 0..L-1.  Sequence-
    parallel callers pass ``shard_offset + arange(L_local)`` so RoPE and the
    ring causal mask see global coordinates.  ``chunk``: :class:`ChunkRows`,
    over the engine's paged cache alone.
    """

    config: LMConfig

    #: a loader's cast of this model's tree (the module-level function)
    cast_params = staticmethod(cast_params)

    @nn.compact
    def __call__(self, input_ids: Array, positions: Optional[Array] = None,
                 deterministic: bool = True, return_hidden: bool = False,
                 decode: bool = False,
                 chunk: Optional[ChunkRows] = None) -> Array:
        cfg = self.config
        b, l = input_ids.shape
        if l > cfg.max_seq_len:
            raise ValueError(
                f"sequence length {l} exceeds max_seq_len {cfg.max_seq_len}"
            )
        dtype = jnp.dtype(cfg.dtype)
        if positions is None:
            positions = jnp.broadcast_to(jnp.arange(l, dtype=jnp.int32), (b, l))
        embed = self.param(
            "embedding", nn.initializers.normal(0.02), (cfg.vocab_size, cfg.d_model),
            jnp.float32,
        )
        x = embed[input_ids].astype(dtype)
        if cfg.hc_mult > 1:
            # [b, l, n, C] from here to the sum in front of the last norm
            x = mhc.expand(x, cfg.hc_mult)
        for i, (kind, ff) in enumerate(zip(cfg.layer_kinds(),
                                           cfg.ff_kinds())):
            x = Block(cfg, kind, ff, name=f"layer_{i}")(
                x, positions, deterministic, decode=decode, chunk=chunk)
        if cfg.hc_mult > 1:
            x = mhc.reduce(x)
        x = RMSNorm(cfg.rmsnorm_eps, dtype, name="final_norm")(x)
        if return_hidden:
            # pre-head hidden states: pair with head_weight() +
            # lm_chunked_loss_with_targets so the (B, L, V) logits are never
            # materialized (the other long-context memory cliff besides
            # attention; at L=8k, V=50k that tensor alone is GBs)
            return x
        if cfg.tie_embeddings:
            # the untied head is a module of this name
            with jax.named_scope("lm_head"):
                logits = x.astype(jnp.float32) @ embed.T
        else:
            logits = nn.Dense(cfg.vocab_size, use_bias=False, dtype=jnp.float32,
                              name="lm_head")(x.astype(jnp.float32))
        return logits


def head_weight(params, config: LMConfig) -> Array:
    """The (d_model, vocab) head matrix out of a CausalLM param tree."""
    if config.tie_embeddings:
        return params["embedding"].T
    return params["lm_head"]["kernel"]


def lm_loss(logits: Array, input_ids: Array, pad_token_id: int):
    """Next-token cross entropy over non-pad targets; returns (sum, count)
    so sequence-parallel callers can psum both before dividing."""
    return lm_loss_with_targets(logits[:, :-1], input_ids[:, 1:], pad_token_id)


def lm_chunked_loss_with_targets(hidden: Array, head_w: Array, targets: Array,
                                 pad_token_id: int, chunk_size: int = 512):
    """CE without materializing the (B, L, V) logits.

    Scans over sequence chunks; each chunk's logits exist only inside the
    (rematerialized) chunk body, so peak memory is O(B·chunk·V) in both the
    forward and the backward instead of O(B·L·V) — the lm-head analog of
    blockwise attention, and the second memory cliff of long-context
    training.  Returns (sum, count) like :func:`lm_loss_with_targets`."""
    b, l, d = hidden.shape
    chunk_size = min(chunk_size, l)
    if l % chunk_size:
        # pad to a chunk multiple — padded targets are pad_token_id, so they
        # are masked out and contribute (0, 0).  Never fall back to the
        # dense (B, L, V) head: odd lengths show up exactly in the
        # long-context regime this function exists for.
        padded = (l + chunk_size - 1) // chunk_size * chunk_size
        hidden = jnp.pad(hidden, ((0, 0), (0, padded - l), (0, 0)))
        targets = jnp.pad(targets, ((0, 0), (0, padded - l)),
                          constant_values=pad_token_id)
        l = padded
    n = l // chunk_size
    hs = hidden.reshape(b, n, chunk_size, d).transpose(1, 0, 2, 3)
    ts = targets.reshape(b, n, chunk_size).transpose(1, 0, 2)

    @jax.checkpoint
    def chunk(carry, xt):
        h, t = xt
        logits = h.astype(jnp.float32) @ head_w.astype(jnp.float32)
        s, c = lm_loss_with_targets(logits, t, pad_token_id)
        return (carry[0] + s, carry[1] + c), None

    (s, c), _ = jax.lax.scan(chunk, (jnp.float32(0), jnp.float32(0)), (hs, ts))
    return s, c


def lm_loss_with_targets(logits: Array, targets: Array, pad_token_id: int):
    """CE against precomputed targets — the sequence-parallel form: the
    next-token shift crosses shard boundaries, so callers shift GLOBALLY
    before sharding (use parallel.sequence_parallel.shift_targets, which
    pads-and-masks the final position — a plain roll would wrap token 0 into
    it and score it unmasked) so every local position keeps its true
    target."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    nll = -jnp.take_along_axis(logp, targets[..., None], axis=-1)[..., 0]
    mask = (targets != pad_token_id).astype(jnp.float32)
    return jnp.sum(nll * mask), jnp.sum(mask)
