"""The Pallas kernels of the main path compile for a TPU v5e — checked with
the chip's compiler and no chip, at the widths the models use.

Interpret mode (every other kernel test on this CPU host) cannot see what the
chip's compiler refuses: a slice off the tiling, too much fast memory, a
kernel that cannot be partitioned.  ``jax.experimental.topologies`` describes
a ``v5e:2x2`` host that is not attached and compiles for it; each case asserts
the Mosaic kernel is really in the program (``tpu_custom_call``), so a path
that quietly took interpret mode fails.  A compile that passes is not a chip
run: numbers and results come from ``chip_smoke.py`` and ``-m tpu``.

The last two tests compile the flat decode attention and ``generate`` itself
at the W3 shape and read what the compiler made of the decode cache's layout
(no kernel in them).
"""

import os

import numpy as np
import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")  # else the compiler logs under /tmp

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
from jax.sharding import (  # noqa: E402
    Mesh, NamedSharding, PartitionSpec as P, SingleDeviceSharding)

from tpu_air.ops.decode_attention import flat_decode_attention  # noqa: E402
from tpu_air.ops.flash_attention import flash_attention  # noqa: E402
from tpu_air.ops.ring_attention import ring_attention_sharded  # noqa: E402


@pytest.fixture(scope="module")
def v5e():
    """The described (not attached) devices of one v5e 2x2 host."""
    from jax.experimental import topologies

    # libtpu takes /tmp/libtpu_lockfile when it is loaded, to keep two
    # processes off one chip.  Nothing here opens a chip, and test
    # processes run side by side (pytest-xdist, a second checkout), so the
    # compiler may be loaded next to another one — for this load only.
    mp = pytest.MonkeyPatch()
    mp.setenv("ALLOW_MULTIPLE_LIBTPU_LOAD", "1")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — no TPU compiler in this install
        pytest.skip(f"cannot describe a v5e:2x2 topology here: {e!r}")
    finally:
        mp.undo()
    # a compile for a described chip is written to the persistent cache but
    # cannot be read back without the chip: keep the cache out of it
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield topo.devices
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# FLAN-T5-base attention: 12 heads of 64; W1 encoder 512, W3 batch 256
H, D = 12, 64


def _flash_t5(devs):
    """Forward with T5's relative-position bias and a key-padding mask at
    the W1 shape (B·H 48, L 512)."""
    b, L = 4, 512
    qkv = _struct((b * H, L, D), jnp.bfloat16, devs)
    bias = _struct((H, L, L), jnp.float32, devs)
    mask = _struct((b, L), jnp.int32, devs)
    fn = lambda q, k, v, bias, mask: flash_attention(  # noqa: E731
        q, k, v, bias=bias, kv_mask=mask, scale=1.0, interpret=False)
    return fn, (qkv, qkv, qkv, bias, mask)


def _flash_causal(devs, grad: bool):
    """Causal forward, or the blockwise backward, at L 2048 (the
    long-context LM path)."""
    qkv = _struct((8, 2048, D), jnp.float32 if grad else jnp.bfloat16, devs)
    fwd = lambda q, k, v: flash_attention(  # noqa: E731
        q, k, v, causal=True, interpret=False)
    if grad:
        return jax.grad(lambda q, k, v: fwd(q, k, v).sum(),
                        argnums=(0, 1, 2)), (qkv, qkv, qkv)
    return fwd, (qkv, qkv, qkv)


def _ring(devs):
    """Causal ring attention over the four chips of the host, L 4096 (1024 on
    each chip)."""
    mesh = Mesh(np.array(devs), ("sequence",))
    qkv = jax.ShapeDtypeStruct(
        (8, 4096, D), jnp.bfloat16,
        sharding=NamedSharding(mesh, P(None, "sequence", None)))
    fn = lambda q, k, v: ring_attention_sharded(  # noqa: E731
        q, k, v, mesh, causal=True, interpret=False)
    return fn, (qkv, qkv, qkv)


def _struct(shape, dtype, devs):
    return jax.ShapeDtypeStruct(
        shape, dtype, sharding=SingleDeviceSharding(devs[0]))


CASES = {
    "flash_fwd_t5_bias_mask": _flash_t5,
    "flash_fwd_causal_2048": lambda d: _flash_causal(d, grad=False),
    "flash_bwd_causal_2048": lambda d: _flash_causal(d, grad=True),
    "ring_causal_4_chips": _ring,
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_kernel_compiles_for_v5e(v5e, case):
    fn, args = CASES[case](v5e)
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{case}: compiled without the Pallas kernel in it"


def test_expert_product_compiles_with_its_kernel_for_v5e(v5e, monkeypatch):
    """The routed expert product (ops/moe.py) at OLMoE's widths and the
    serving cell's two shapes, 64 rows (a decode step) and 128 (a prefill
    chunk) of top-8 over 64 experts: three calls of the grouped-matmul
    kernel each.  ``expert_ffn`` takes the kernel where the backend is a TPU;
    the described chip is not the backend, so the test says so."""
    from tpu_air.ops import moe

    monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")
    for rows in (64, 128):
        args = (_struct((rows, 2048), jnp.bfloat16, v5e),
                _struct((rows, 8), jnp.int32, v5e),
                _struct((rows, 8), jnp.float32, v5e),
                _struct((64, 2048, 1024), jnp.bfloat16, v5e),
                _struct((64, 2048, 1024), jnp.bfloat16, v5e),
                _struct((64, 1024, 2048), jnp.bfloat16, v5e))
        text = jax.jit(moe.expert_ffn).lower(*args).compile().as_text()
        assert text.count("tpu_custom_call") == 3, rows


@pytest.mark.parametrize("cache", ["bf16", "int8_per_position",
                                   "int8_per_channel"])
def test_flat_decode_attention_compiles_for_v5e(v5e, cache):
    """One decode token against the W3 cross-attention cache (b 256, L 512):
    bf16, int8 with a scale per position, int8 with a scale per channel.  The
    chip's compiler takes the step every decode cell runs and makes no
    ``[256, 512, 12, 64]`` array of a slab, of any type."""
    b, L = 256, 512
    q = _struct((b, 1, H, D), jnp.bfloat16, v5e)
    kv = _struct((b, L, H * D),
                 jnp.bfloat16 if cache == "bf16" else jnp.int8, v5e)
    mask = _struct((b, L), jnp.int32, v5e)
    scale = {"bf16": None,
             "int8_per_position": _struct((b, L, H), jnp.float32, v5e),
             "int8_per_channel": _struct((b, 1, H * D), jnp.float32, v5e)}[cache]
    fn = lambda q, k, v, mask, ks, vs: flat_decode_attention(  # noqa: E731
        q, k, v, None, mask, ks, vs, H, jnp.bfloat16)
    compiled = jax.jit(fn).lower(q, kv, kv, mask, scale, scale).compile()
    assert "[256,512,12,64]" not in compiled.as_text()


@pytest.mark.parametrize("early_stop", [True, False], ids=["while", "scan"])
def test_generate_streams_the_cache_unpadded_on_v5e(v5e, early_stop):
    """``generate`` at the W3 shape (FLAN-T5-base, 256 x 512, bf16, 128 new
    tokens) under both loop forms: the chip's compiler keeps no row-major
    4-D copy of a cache slab (minor pair (12, 64) tiled to (16, 128), 2.67 x
    the bytes) and the program's temporaries stay near the flat cache's 6 GB.
    With the dense path under the while-loop they were 14.1 GB (PERF.md, PR
    25).  tests/test_t5.py holds the same at the jaxpr; this holds what XLA
    makes of it."""
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.models.t5.generate import make_generate_fn

    cfg = T5Config.flan_t5_base()
    cfg.dtype = "bfloat16"
    model = T5ForConditionalGeneration(cfg)
    one = jnp.ones((1, 8), jnp.int32)
    params = jax.tree_util.tree_map(
        lambda s: _struct(s.shape, jnp.bfloat16, v5e),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), one, one, one))["params"])
    ids = _struct((256, 512), jnp.int32, v5e)
    compiled = make_generate_fn(model, 128, early_stop=early_stop).lower(
        params, ids, ids, _struct((2,), jnp.uint32, v5e)).compile()
    assert "bf16[256,512,12,64]{3,2,1,0" not in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 7.5e9
