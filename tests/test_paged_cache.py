"""``tpu_air/models/lm/paged_cache.py``: the one table of what each kind of
layer keeps in the engine's cache, held against the flax modules that create
the leaves and against every reader of the tree (the programs' pushes,
copy-on-write, page shipping, the mesh's shardings, the refusals), for the
four families ``tools/lowered_programs.py`` builds and the one whose window
layers keep a ring a slot."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

import test_gigachat
import test_jamba
import test_laguna
import test_olmoe
from tpu_air.engine import (EngineConfig, InferenceEngine,
                            RecurrentStateUnsupported)
from tpu_air.engine.dist import MeshEngine, PrefillWorker
from tpu_air.engine.dist.kv_transfer import (extract_kv_pages,
                                             insert_kv_pages,
                                             validate_kv_payload)
from tpu_air.engine.dist.sharded import paged_cache_shardings
from tpu_air.engine.types import refuse_pages_only
from tpu_air.models.lm import CausalLM, LMConfig, hf_import, paged_cache
from tpu_air.models.lm.generate import (init_cache, make_paged_decode_body,
                                        make_prefill_chunk_body)
from tpu_air.parallel.mesh import make_mesh

S, C, L = 4, 8, 64
NPG = L // C
PAGES = S * NPG + 1

FAMILIES = {
    "dense": lambda: LMConfig.tiny(),
    "olmoe": lambda: hf_import.lm_config_from_hf(test_olmoe.HF),
    "jamba": lambda: hf_import.lm_config_from_hf(test_jamba.TINY,
                                                 max_seq_len=256),
    "latent": lambda: hf_import.lm_config_from_hf(
        test_gigachat.TINY, max_seq_len=256, experts_first=4, experts_held=8),
    "laguna": lambda: hf_import.lm_config_from_hf(
        test_laguna.TINY, max_seq_len=256),
}


@pytest.fixture(params=list(FAMILIES))
def model(request):
    return CausalLM(FAMILIES[request.param]())


def _cache(model, fill=None):
    """A paged cache; with ``fill`` (a seed) every leaf holds its own junk."""
    cache = paged_cache.init_paged_cache(model, S, PAGES, C, NPG)
    if fill is None:
        return cache
    rng = np.random.default_rng(fill)
    return jax.tree_util.tree_map(
        lambda v: jnp.asarray(rng.integers(1, 100, v.shape), v.dtype), cache)


def _by_kind(model, cache):
    """``(kind, layer's dict of leaves)`` in layer order."""
    kinds = model.config.layer_kinds()
    found = dict(paged_cache.layers(cache))
    assert len(found) == len(kinds)
    return [(kind, next(v for p, v in found.items()
                        if p.startswith(f"layer_{i}/")))
            for i, kind in enumerate(kinds)]


def _table_leaves(kind):
    fmt = paged_cache.FORMATS[kind]
    return {*fmt.pools, *fmt.rows, *fmt.pushed}


def test_the_table_names_the_leaves_the_modules_create(model):
    """Each layer of the paged cache holds its kind's row of the table and
    nothing else; the modules, run over it (a decode step and a chunk), hand
    back that same tree: a module that makes a leaf the table lacks, or reads
    one under another name, fails here and not in a walker."""
    cfg = model.config
    cache = _cache(model)
    for kind, layer in _by_kind(model, cache):
        assert set(layer) == _table_leaves(kind), kind
        assert paged_cache.layer_kind(layer) == kind
    # the plain cache the same modules make holds no leaf the table lacks
    for (kind, _), (_, plain) in zip(
            _by_kind(model, cache),
            paged_cache.layers(init_cache(CausalLM(cfg), 2))):
        assert set(plain) <= _table_leaves(kind)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"])
    i32 = lambda *s: jax.ShapeDtypeStruct(s, jnp.int32)  # noqa: E731
    want = jax.tree_util.tree_map(
        lambda v: jax.ShapeDtypeStruct(v.shape, v.dtype), cache)
    after, _ = jax.eval_shape(make_paged_decode_body(model, L), params, cache,
                              i32(S), i32(S), i32(S, NPG))
    assert after == want
    slot = {"slot": i32()} if cfg.keeps_slot_rows else {}
    after, _ = jax.eval_shape(
        make_prefill_chunk_body(model, C, L), params, cache, i32(1, C), i32(),
        i32(), i32(NPG), **slot)
    assert after == want


def test_init_paged_cache_shapes_and_dtypes_by_kind(model):
    cfg = model.config
    dtype = jnp.dtype(cfg.dtype)
    pool_width = {"attention": cfg.n_kv_heads * cfg.head_dim,
                  "latent": cfg.latent_row_width}   # a window layer: no pool
    c, n, k = cfg.mamba_d_inner, cfg.mamba_d_state, cfg.mamba_d_conv
    ring = (S, cfg.window_ring_len(C), cfg.n_kv_heads * cfg.head_dim)
    rows = {"conv_state": ((S, (k - 1) * c), dtype),
            "ssm_state": ((S, n, c), jnp.float32),
            "window_key": (ring, dtype), "window_value": (ring, dtype)}
    state = rings = 0
    for kind, layer in _by_kind(model, _cache(model)):
        fmt = paged_cache.FORMATS[kind]
        for leaf in fmt.pools:
            assert layer[leaf].shape == (PAGES, C, pool_width[kind])
            assert layer[leaf].dtype == dtype
        for leaf in fmt.rows:
            assert (layer[leaf].shape, layer[leaf].dtype) == rows[leaf]
            size = layer[leaf].size * layer[leaf].dtype.itemsize
            if kind == "window":
                rings += size
            else:
                state += size
        for leaf in fmt.pushed:
            table = leaf == paged_cache.BLOCK_TABLE
            assert layer[leaf].shape == ((S, NPG) if table else (S,))
            assert layer[leaf].dtype == jnp.int32
        assert not any(np.asarray(v).any() for v in layer.values())
    assert paged_cache.recurrent_state_bytes(_cache(model)) == state
    assert paged_cache.window_ring_bytes(_cache(model)) == rings
    assert (state > 0) == cfg.has_recurrent_layers
    assert (state + rings > 0) == cfg.keeps_slot_rows


def test_pushes_touch_exactly_the_pushed_leaves(model):
    cache = _cache(model, fill=1)
    pos = jnp.array([0, 5, 0, 9], jnp.int32)
    table = jnp.arange(S * NPG, dtype=jnp.int32).reshape(S, NPG)
    stepped = paged_cache.push_step(cache, pos, table)
    row = jnp.arange(NPG, dtype=jnp.int32) + 3
    chunked = paged_cache.push_chunk(cache, jnp.int32(16), jnp.int32(4), row,
                                     slot=2)
    want_step = {"cache_index": pos, "block_table": table,
                 "valid_len": jnp.array([0, 1, 0, 1])}
    want_chunk = {"cache_index": jnp.full((S,), 16),
                  "block_table": jnp.broadcast_to(row, (S, NPG)),
                  "valid_len": jnp.full((S,), 5),
                  "state_row": jnp.full((S,), 2)}
    for pushed, want in ((stepped, want_step), (chunked, want_chunk)):
        for (kind, before), (_, after) in zip(_by_kind(model, cache),
                                              _by_kind(model, pushed)):
            assert set(after) == set(before)
            for leaf in before:
                if leaf in want:
                    assert leaf in paged_cache.FORMATS[kind].pushed
                    assert after[leaf].dtype == jnp.int32
                    np.testing.assert_array_equal(after[leaf], want[leaf])
                else:
                    assert after[leaf] is before[leaf], leaf
    # a step pushes no state_row: it is the chunk's
    assert set(want_chunk) - set(want_step) == {"state_row"}
    if model.config.keeps_slot_rows:
        with pytest.raises(ValueError, match="slot="):
            paged_cache.push_chunk(cache, jnp.int32(0), jnp.int32(4), row)
    else:
        paged_cache.push_chunk(cache, jnp.int32(0), jnp.int32(4), row)


def test_copy_page_and_shipping_move_every_pool_and_no_state(model):
    src_cache, dst_cache = _cache(model, fill=2), _cache(model, fill=3)
    copied = paged_cache.copy_page(src_cache, jnp.int32(5), jnp.int32(2))
    shipped = extract_kv_pages(src_cache, [2, 7, 1])
    validate_kv_payload(dst_cache, [4, 3, 9], shipped)
    landed = insert_kv_pages(dst_cache, [4, 3, 9], shipped)
    kinds = _by_kind(model, src_cache)
    assert len(shipped) == sum(
        bool(paged_cache.FORMATS[kind].pools) for kind, _ in kinds)
    paths = [p for p, _ in paged_cache.layers(src_cache)]
    for path, (kind, src), (_, cow), (_, dst), (_, got) in zip(
            paths, kinds, _by_kind(model, copied),
            _by_kind(model, dst_cache), _by_kind(model, landed)):
        fmt = paged_cache.FORMATS[kind]
        assert set(shipped.get(path, {})) == set(fmt.pools.values())
        for leaf, short in fmt.pools.items():
            want = np.asarray(src[leaf]).copy()
            want[5] = want[2]
            np.testing.assert_array_equal(cow[leaf], want)
            np.testing.assert_array_equal(shipped[path][short],
                                          np.asarray(src[leaf])[[2, 7, 1]])
            want = np.asarray(dst[leaf]).copy()
            want[[4, 3, 9]] = np.asarray(src[leaf])[[2, 7, 1]]
            np.testing.assert_array_equal(got[leaf], want)
        for leaf in (*fmt.rows, *fmt.pushed):
            assert cow[leaf] is src[leaf] and got[leaf] is dst[leaf], leaf


def test_the_sharding_rule_names_every_leaf(model):
    cache = _cache(model)
    mesh = make_mesh(("data", "model"), (2, 1), devices=jax.devices()[:2])
    shardings = paged_cache_shardings(cache, mesh)
    assert (jax.tree_util.tree_structure(shardings)
            == jax.tree_util.tree_structure(cache))
    for (kind, layer), (_, sh) in zip(_by_kind(model, cache),
                                      _by_kind(model, shardings)):
        fmt = paged_cache.FORMATS[kind]
        for leaf, value in layer.items():
            axes = paged_cache.SHARD_AXES[leaf]
            assert sh[leaf].spec == P(*axes) and len(axes) <= value.ndim
            # pages and the slots that follow them over data, the rest whole
            over_data = leaf in fmt.pools or leaf in ("cache_index",
                                                      "block_table")
            assert axes[:1] == (("data",) if over_data else ())
    with pytest.raises(KeyError):
        paged_cache_shardings({"layer_0": {"attn": {"new_leaf": 0}}}, mesh)


def test_an_unknown_kind_of_layer_is_named():
    with pytest.raises(ValueError, match="new_leaf"):
        paged_cache.layer_kind({"new_leaf": 0})


class _Checkpoint:
    def __init__(self, model, params):
        self.model, self.params = model, params

    def get_model(self, dtype=None):
        return self.model, self.params


def _params(model):
    return model.init(jax.random.PRNGKey(0),
                      jnp.zeros((1, 8), jnp.int32))["params"]


def _engine_migrates(model, params):
    cfg = EngineConfig(num_slots=2, slot_len=L, page_len=C, max_new_tokens=4)
    engine = InferenceEngine(model, params, cfg, auto_start=False)
    try:
        engine.migrate_out()
    finally:
        engine.close()


def _mesh_engine_builds(model, params):
    cfg = EngineConfig(num_slots=2, slot_len=L, page_len=C, max_new_tokens=4)
    MeshEngine(model, params, cfg, dp=2, tp=1, devices=jax.devices()[:2],
               auto_start=False).close()


def _worker_builds(model, params):
    PrefillWorker(_Checkpoint(model, params), page_len=C,
                  slot_len=L)._ensure_built()


ENTRY_POINTS = {"engine": _engine_migrates, "mesh_engine": _mesh_engine_builds,
                "prefill_worker": _worker_builds}


def test_what_ships_pages_alone_is_refused_for_a_model_with_slot_state(model):
    if model.config.keeps_slot_rows:
        with pytest.raises(RecurrentStateUnsupported, match="M6"):
            refuse_pages_only(model, "pages alone")
    else:
        refuse_pages_only(model, "pages alone")


@pytest.mark.parametrize("entry", list(ENTRY_POINTS))
@pytest.mark.parametrize("family", ["dense", "jamba"])
def test_the_three_entry_points_refuse_through_the_one_helper(family, entry):
    model = CausalLM(FAMILIES[family]())
    if family == "jamba":
        with pytest.raises(RecurrentStateUnsupported, match="M6"):
            ENTRY_POINTS[entry](model, _params(model))
    else:
        ENTRY_POINTS[entry](model, _params(model))
