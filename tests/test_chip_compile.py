"""The Pallas kernels of the main path compile for a TPU v5e — checked with
the chip's compiler and no chip, at the widths the models use.

Interpret mode (every other kernel test on this CPU host) cannot see what the
chip's compiler refuses: a slice off the tiling, too much fast memory, a
kernel that cannot be partitioned.  ``jax.experimental.topologies`` describes
a ``v5e:2x2`` host that is not attached and compiles for it; each case asserts
the Mosaic kernel is really in the program (``tpu_custom_call``), so a path
that quietly took interpret mode fails.  A compile that passes is not a chip
run: numbers and results come from ``chip_smoke.py`` and ``-m tpu``.

The next two tests compile the flat decode attention, ``generate`` at the W3
shape and the engine's step and admit programs at the serving shape, and read
what the compiler made of the decode cache's layout (no kernel in them).  The last compile
``T5Trainer``'s train step at the fine-tune cells' shapes and count what a
dropout mask element costs in random bits, on one chip and on ``data=4``.
"""

import os
import re

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


def _flash_t5_train(devs, grad: bool):
    """The fine-tune step's encoder self-attention (PR 39): bias, key mask
    and live dropout drawn in the kernel, operands ``[b, L, h·d]`` as the
    projections write them; forward, or forward and the one-kernel backward
    with ``dbias``."""
    b, L = 4, 512
    qkv = _struct((b, L, H * D), jnp.bfloat16, devs)
    bias = _struct((1, H, L, L), jnp.float32, devs)
    mask = _struct((b, L), jnp.int32, devs)
    seed = _struct((2,), jnp.int32, devs)
    fwd = lambda q, k, v, bias, mask, seed: flash_attention(  # noqa: E731
        q, k, v, bias=bias, kv_mask=mask, scale=1.0, interpret=False,
        dropout_rate=0.1, dropout_seed=seed, num_heads=H)
    if grad:
        return jax.grad(
            lambda *a: fwd(*a).astype(jnp.float32).sum(),
            argnums=(0, 1, 2, 3)), (qkv, qkv, qkv, bias, mask, seed)
    return fwd, (qkv, qkv, qkv, bias, mask, seed)


CASES = {
    "flash_fwd_t5_bias_mask": _flash_t5,
    "flash_fwd_t5_bias_mask_dropout": lambda d: _flash_t5_train(d, grad=False),
    "flash_bwd_t5_bias_mask_dropout": lambda d: _flash_t5_train(d, grad=True),
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
    kernel each and one of the sum over a token's choices
    (``held_rows_sum``, PR 49).  ``expert_ffn`` takes the kernels where the
    backend is a TPU; the described chip is not the backend, so the test
    says so."""
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
        assert text.count("tpu_custom_call") == 4, rows
        assert sum("tpu_custom_call" in line and "held_rows_sum" in line
                   for line in text.splitlines()) == 1, rows


# -- the sum over a token's choices moves the held rows once (PR 49) -----------

def _rows_written_under_combine(text, m, d):
    """Float32 arrays ``[m, d]`` (a row a product, ``m = t * k``) the ENTRY
    computation writes under the scope ``moe_combine``: each is a pass over
    every assignment's row, held here or not."""
    entry = text[text.index("\nENTRY "):]
    return len([line for line in entry.splitlines()
                if re.match(rf"\s*(?:ROOT )?%\S+ = f32\[{m},{d}\]\S* "
                            r"(?!bitcast|parameter|get-tuple-element)[\w-]+\(",
                            line)
                and re.search(r'op_name="[^"]*/moe_combine/', line)])


def test_combine_counter_sees_what_the_parent_program_did():
    """Not vacuous: the three ``[3072, 7168]`` float32 arrays of the program
    as it was compiled before PR 49 (the weighted and masked rows, the
    zeros, the scatter into them), and none for a bitcast or another scope."""
    made = ('  %{0} = f32[3072,7168]{{1,0:T(8,128)}} fusion(%a, %b), '
            'kind=kLoop, calls=%c, metadata={{op_name="jit(f)/moe/{1}" '
            'stack_frame_id=4}}\n')
    parent = ("\nENTRY %main {\n"
              + made.format("multiply_select_fusion",
                            "moe_combine/jit(_where)/select_n")
              + made.format("fusion.40", "moe_combine/scatter")
              + made.format("fusion.19", "moe_combine/scatter")
              + made.format("fusion.7", "moe_experts/jit(gmm)/pallas_call")
              + '  %bitcast.6 = f32[3072,7168]{1,0:T(8,128)} bitcast(%x), '
                'metadata={op_name="jit(f)/moe/moe_combine/reshape"}\n'
              + '  %r = f32[384,7168]{1,0:T(8,128)} fusion(%fusion.19), '
                'kind=kLoop, calls=%d, metadata={op_name="jit(f)/moe/'
                'moe_combine/reduce_sum"}\n}')
    assert _rows_written_under_combine(parent, 3072, 7168) == 3


def test_the_sum_over_choices_writes_no_row_of_no_group_on_v5e(
        v5e, monkeypatch):
    """``expert_ffn`` at ``gigachat-serve-docchat``'s widths and its mixed
    step's rows (384 of top-8, 16 experts held, ids held elsewhere among
    them): under ``moe_combine`` the program writes a float32 ``[t*k, d]``
    at most once (before PR 49 three times: 88 MB each, fifteen sixteenths
    of it rows of no group), there is no scatter, the sum is the one kernel
    beside the three products, and no fusion got a window the compiler's
    cost model cannot price (PERF.md, PR 48).  Off a TPU the gathered form
    writes such an array once: the gather, read by the reduction."""
    from tpu_air.ops import moe

    t, k, d, f, e = 384, 8, 7168, 2048, 16
    args = (_struct((t, d), jnp.bfloat16, v5e),
            _struct((t, k), jnp.int32, v5e),
            _struct((t, k), jnp.float32, v5e),
            _struct((e, d, f), jnp.bfloat16, v5e),
            _struct((e, d, f), jnp.bfloat16, v5e),
            _struct((e, f, d), jnp.bfloat16, v5e))
    # (a function each: a trace is cached by the function, not the backend)
    gathered = jax.jit(lambda *a: moe.expert_ffn(*a)).lower(
        *args).compile().as_text()
    assert _rows_written_under_combine(gathered, t * k, d) == 1
    monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")
    text = jax.jit(lambda *a: moe.expert_ffn(*a)).lower(
        *args).compile().as_text()
    assert _rows_written_under_combine(text, t * k, d) == 0
    assert not re.search(r'scatter\([^\n]*op_name="[^"]*/moe_combine/', text)
    assert text.count("tpu_custom_call") == 4
    assert '"estimated_cycles":"9223372036854775807"' not in text


def test_flat_decode_attention_compiles_for_v5e(v5e):
    """One decode token against a bf16 ``[256, 512, 12 * 64]`` slab: the
    chip's compiler takes the LM's single-token step and makes no ``[256,
    512, 12, 64]`` array of a slab."""
    b, L = 256, 512
    q = _struct((b, 1, H, D), jnp.bfloat16, v5e)
    kv = _struct((b, L, H * D), jnp.bfloat16, v5e)
    mask = _struct((b, L), jnp.int32, v5e)
    fn = lambda q, k, v, mask: flat_decode_attention(  # noqa: E731
        q, k, v, mask, H, jnp.bfloat16)
    compiled = jax.jit(fn).lower(q, kv, kv, mask).compile()
    assert "[256,512,12,64]" not in compiled.as_text()


def _t5_on(devs, name):
    """A bf16 FLAN-T5 and the shapes of its parameters on the described chip."""
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration

    cfg = getattr(T5Config, name)()
    cfg.dtype = "bfloat16"
    model = T5ForConditionalGeneration(cfg)
    one = jnp.ones((1, 8), jnp.int32)
    params = jax.tree_util.tree_map(
        lambda s: _struct(s.shape, jnp.bfloat16, devs),
        jax.eval_shape(lambda: model.init(
            jax.random.PRNGKey(0), one, one, one))["params"])
    return model, params


def _generate_w3(devs, early_stop):
    """``generate`` at the W3 shape: FLAN-T5-base, 256 x 512, 128 new tokens."""
    from tpu_air.models.t5.generate import make_generate_fn

    model, params = _t5_on(devs, "flan_t5_base")
    ids = _struct((256, 512), jnp.int32, devs)
    return make_generate_fn(model, 128, early_stop=early_stop).lower(
        params, ids, ids, _struct((2,), jnp.uint32, devs)), (256, 12, 64, 512)


def _engine_program(devs, rows=None, admit=None):
    """One of ``T5Engine``'s donated-state programs at the ``t5large-serve``
    shape (FLAN-T5-large, 64 slots x 512, a ring for 128 new tokens): the
    step over the first ``rows`` slots, or the admit program of length
    ``admit``."""
    from tpu_air.models.t5.generate import (
        init_slot_state, make_t5_admit_fn, make_t5_slot_step_fn)

    model, params = _t5_on(devs, "flan_t5_large")
    state, tok = jax.tree_util.tree_map(
        lambda s: _struct(s.shape, s.dtype, devs),
        jax.eval_shape(lambda p: init_slot_state(model, p, 64, 129, 512),
                       params))
    if admit is None:
        return make_t5_slot_step_fn(model, rows).lower(
            params, state, tok), (64, 16, 64, 512)
    return make_t5_admit_fn(model, admit).lower(
        params, state, tok, _struct((1, admit + 2), jnp.int32, devs))


# program -> (builder, the most its temporaries may take)
DECODE_PROGRAMS = {
    "while": (lambda d: _generate_w3(d, True), 7.5e9),
    "scan": (lambda d: _generate_w3(d, False), 7.5e9),
    "engine_step16": (lambda d: _engine_program(d, rows=16), 0.15e9),
    "engine_step32": (lambda d: _engine_program(d, rows=32), 0.15e9),
    "engine_step64": (lambda d: _engine_program(d, rows=64), 0.15e9),
}

_HBM = r"\{[\d,]*:T\(8,128\)\(2,1\)\}"       # a tiled layout outside fast memory
_FAST = r"\{[\d,]*:T\(8,128\)\(2,1\)S\(1\)\}"  # ... and in it


def _self_slab(b, hd, dec_len=129):
    """A regex for one layer's self-attention slab, in either order of
    position and batch, alone or as a one-layer slice of the stacked array."""
    return rf"(?:bf16|s8)\[(?:1,)?(?:{dec_len},{b}|{b},{dec_len}),{hd}\]"


def _slabs_written_back(text, slab):
    """Self slabs the program holds in fast memory, appends to there and
    writes back to HBM whole: ``copy-start`` of a slab from ``S(1)`` to HBM."""
    return len(re.findall(rf"= \({slab}{_HBM}, {slab}{_FAST}, \S+\) copy-start\(",
                          text))


def _slabs_made_anew(text, slab):
    """Slab-sized arrays the ENTRY computation makes in HBM: what is not a
    parameter, a bitcast of one, or a ``dynamic-update-slice`` on one in
    place.  A copy of a slab out of a parameter is one, a write-back too."""
    entry = text[text.index("\nENTRY "):]
    made = re.findall(rf"^\s*(?:ROOT )?%(\S+) = {slab}{_HBM} ([\w-]+)\(", entry,
                      flags=re.M)
    return len([op for name, op in made
                if op not in ("parameter", "bitcast")
                and "dynamic-update-slice" not in name])


def _stacked_slabs(text, layers, b, hd, dec_len=129):
    """``(layouts, ops)`` of the stacked self slabs ``[layers, L, b, h*d]`` in
    the program: the minor-to-major order of every array of that shape (a
    loop's carry laid out position-major reads ``3,2,1,0``, batch-major
    ``3,1,2,0``) and the operations that make one."""
    shape = rf"bf16\[{layers},{dec_len},{b},{hd}\]\{{([\d,]*)[^}}]*\}}"
    made = re.findall(rf"%(\S+) = {shape} ([\w-]+)\(", text)
    return set(re.findall(shape, text)), {
        "append" if op == "dynamic-update-slice"
        or "dynamic-update-slice" in name else op for name, _, op in made}


def test_slab_counters_see_what_the_parent_programs_did():
    """The counters are not vacuous: lines of the programs as they were
    compiled before PR 34 (the ``while`` body's write-back of a layer's slab;
    the one-step program's copy of a layer out of a stacked parameter) and
    before PR 59 (the ``while``'s carry laid out batch-major, and a layer's
    slice of it as the flat read's einsums took it)."""
    slab = _self_slab(256, 768)
    carry = ("  %while.5 = (s32[]{:T(128)}, s32[256]{0:T(256)}, "
             "bf16[12,129,256,768]{3,1,2,0:T(8,128)(2,1)}, "
             "bf16[12,129,256,768]{3,1,2,0:T(8,128)(2,1)}) while(%tuple.7)\n"
             "  ROOT %bitcast.1786 = bf16[256,129,768]{2,1,0:T(8,128)(2,1)} "
             "bitcast(%slice.351)\n"
             "  %copy.3 = bf16[12,129,256,768]{3,2,1,0:T(8,128)(2,1)} "
             "copy(%get-tuple-element.9)")
    layouts, ops = _stacked_slabs(carry, 12, 256, 768)
    assert layouts == {"3,1,2,0", "3,2,1,0"}
    assert ops == {"copy"}
    assert re.search(slab, carry)
    line = ("  %copy-start.13 = (bf16[256,129,768]{2,1,0:T(8,128)(2,1)}, "
            "bf16[256,129,768]{2,1,0:T(8,128)(2,1)S(1)}, u32[]{:S(2)}) "
            "copy-start(%dynamic_update_slice.226)")
    assert _slabs_written_back(line, slab) == 1
    prefetch = line.replace("(2,1)}, bf16", "(2,1)S(1)}, bf16").replace(
        "(2,1)S(1)}, u32", "(2,1)}, u32")       # HBM -> fast memory: a read
    assert _slabs_written_back(prefetch, slab) == 0
    entry = ("\nENTRY %main {\n"
             "  %p = bf16[24,129,64,1024]{3,2,1,0:T(8,128)(2,1)} parameter(0)\n"
             "  %get-tuple-element.42 = bf16[1,129,64,1024]{3,2,1,0:T(8,128)(2,1)} "
             "get-tuple-element(%fusion.2009), index=4\n"
             "  %constant_dynamic-update-slice_fusion.9 = bf16[129,64,1024]"
             "{2,1,0:T(8,128)(2,1)} fusion(%q), kind=kLoop\n}")
    assert _slabs_made_anew(entry, _self_slab(64, 1024)) == 1


@pytest.mark.parametrize("program", sorted(DECODE_PROGRAMS))
def test_generate_streams_the_cache_unpadded_on_v5e(v5e, monkeypatch, program):
    """The programs that carry the T5 decode cache, as the chip's compiler
    makes them: ``generate`` at the W3 shape under both loop forms and the
    engine's donated step at the ``t5large-serve`` shape, over each prefix of
    its 64 slots it can be issued for.

    Cross slabs: ``bf16[b, h, 64, 512]`` laid out length-minor as stored
    (``{3,2,1,0:T(8,128)(2,1)}``: (64, 512) are whole tiles), nothing copies
    or transposes an array of a slab's dimensions in any order (the K/V
    projection writes the layout itself), and no row-major 4-D slab is left
    (minor pair (12, 64) tiled to (16, 128), 2.67 x the bytes).

    Self slabs, the count that says PR 34's mechanism engages: **no slab is
    held in fast memory through the step and written back to HBM whole**
    (before: 23 of base's 24 under the ``while``, 1.2 of the 7.5 GB a step
    moved; 10 of large's 48 in the engine's step, where a few still are), and
    the one-step program makes no slab-sized array anew, so it copies no
    slice of a cache parameter out before reading it (34 of 48 where one
    array held all layers' slabs, 0.58 GB of temporaries).  That count
    hangs on how the step's tokens leave it: as a second result cut from a
    donated token vector, 47 of the 64-row step's 48 slabs are held and
    written back (``init_slot_state``); beside the donated state, 2 or 3.

    A step over a PREFIX of the slots reads the cross slabs' first rows in
    place, inside the fusions that contract them.  The self slabs' first rows
    it copies out before reading them, one ``[129, rows, 1024]`` slice a slab:
    48 x 4.2 MB = 203 MB a 16-row step, about 0.4 ms of the chip's 819 GB/s
    read and written.  That is the next thing to take (PERF.md section 7),
    and it is counted here so that taking it shows.

    The temporaries stay near the cache's own 6 GB; with the dense path under
    the while-loop they were 14.1 GB (PERF.md, PR 25).  tests/test_t5.py
    holds the same at the jaxpr; this holds what XLA makes of it.

    Under a loop (PR 59) a layer reads the stacked slabs where they lie, one
    ``prefix_append_decode_attention`` kernel a layer, and the kernel's
    row-major operand is what lays the carry out: ``bf16[12,129,256,768]``
    position-major (``{3,2,1,0}``: a position is whole tiles, 129 stays 129)
    and nowhere batch-major (``{3,1,2,0}``, the flat read's einsums' choice:
    a row one sublane of every tile, 129 stored as 144), no layer's slice
    ``[129,256,768]`` / ``[256,129,768]`` is cut or copied out of it for the
    kernel, and nothing makes a stacked array but the cache's zeros and the
    append in place.  The engine's step is a ring with no prefix: it keeps
    the flat read and has no kernel."""
    import itertools

    from tpu_air.ops import decode_attention as da

    # what the program traces on the chip, not on this host
    monkeypatch.setattr(da.jax, "default_backend", lambda: "tpu")
    build, temp_limit = DECODE_PROGRAMS[program]
    lowered, cross = build(v5e)
    compiled = lowered.compile()
    text = compiled.as_text()
    b, h, d, L = cross
    name = f"bf16[{b},{h},{d},{L}]"
    layouts = [lay for ln in text.splitlines() if "cross_attn" in ln
               for lay in re.findall(re.escape(name) + r"\{([^}S]*)", ln)]
    engine = program.startswith("engine_step")
    rows = int(program[len("engine_step"):]) if engine else b
    layers = 24 if engine else 12
    assert len(layouts) >= 2 * layers, len(layouts)
    assert set(layouts) == {"3,2,1,0:T(8,128)(2,1)"}, set(layouts)
    dims = "|".join(",".join(map(str, p))
                    for whole in {cross, (rows,) + cross[1:]}
                    for p in set(itertools.permutations(whole)))
    moved = re.findall(
        rf"(?:bf16|f32)\[(?:{dims})\]\{{[^}}]*\}} (?:copy|transpose)\(.*", text)
    assert not moved, moved[:4]
    assert f"bf16[{b},{L},{h},{d}]{{3,2,1,0" not in text
    assert compiled.memory_analysis().temp_size_in_bytes < temp_limit

    slab = _self_slab(b, h * d)
    kernels = sum("tpu_custom_call" in ln and "prefix_append_decode_attention"
                  in ln for ln in text.splitlines())
    written_back = _slabs_written_back(text, slab)
    if engine:
        assert re.search(slab, text), "no self slab of the expected shape"
        assert kernels == 0
        assert written_back <= 4, written_back
        assert _slabs_made_anew(text, slab) == written_back
        prefix = rf"= bf16\[129,{rows},{h * d}\]\{{[^}}]*\}} slice-done\("
        assert len(re.findall(prefix, text)) == (48 if rows < b else 0)
    else:
        assert kernels == layers
        assert written_back == 0, written_back
        assert not re.search(slab, text), re.search(slab, text).group(0)
        layouts, ops = _stacked_slabs(text, layers, b, h * d)
        assert layouts == {"3,2,1,0"}, layouts
        assert ops <= {"parameter", "get-tuple-element", "broadcast",
                       "append"}, ops


@pytest.mark.parametrize("length", [128, 512])
def test_engine_admit_writes_its_slots_rows_in_place_on_v5e(v5e, length):
    """``T5Engine``'s admit program at the ``t5large-serve`` shape: the
    state is donated and the admitted prompt's cross K/V go into its slot's
    row of the 48 ``[64, 16, 64, 512]`` slabs by ``dynamic-update-slice`` on
    the parameter itself: no slab-sized array is made anew (a copy of one is
    67 MB, 3.2 GB for all), no self slab is touched, and the temporaries are
    the encoder's own."""
    compiled = _engine_program(v5e, admit=length).compile()
    text = compiled.as_text()
    entry = text[text.index("\nENTRY "):]
    made = re.findall(
        r"^\s*(?:ROOT )?%(\S+) = bf16\[64,16,64,512\]\S* ([\w-]+)\(", entry,
        flags=re.M)
    anew = [(name, op) for name, op in made
            if op not in ("parameter", "bitcast", "get-tuple-element")
            and "dynamic-update-slice" not in name]
    assert not anew, anew[:4]
    assert sum("dynamic-update-slice" in name for name, _ in made) == 48
    assert _slabs_made_anew(text, _self_slab(64, 1024)) == 0
    memory = compiled.memory_analysis()
    assert memory.temp_size_in_bytes < 0.2e9
    assert memory.alias_size_in_bytes > 4.0e9      # the state, in place


# -- the train step's dropout masks (PR 37) ----------------------------------

def _mask_draws(jaxpr):
    """(generator, bits an element, shape) of every array of random bits the
    program draws, sub-programs included.  A Threefry key spends one 20-round
    block on each element of a draw of up to 32 bits (the partitionable form
    JAX 0.9 defaults to); an ``rbg`` key asks the chip's own generator."""
    out = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "random_bits":
            out.append((eqn.invars[0].aval.dtype._impl.name,
                        eqn.params["bit_width"], tuple(eqn.params["shape"])))
        for sub in jax.core.jaxprs_in_params(eqn.params):
            out.extend(_mask_draws(sub))
    return out


def test_mask_draw_counter_sees_what_the_parent_step_drew():
    """The counter is not vacuous: the parent's draw (``flax.linen.Dropout``
    under the default key, at one encoder layer's probabilities) reads as one
    32-bit Threefry draw of every element."""
    from flax import linen as nn

    shape = (32, 12, 512, 512)
    jaxpr = jax.make_jaxpr(lambda key, x: nn.Dropout(0.1).apply(
        {}, x, deterministic=False, rngs={"dropout": key}))(
            jax.random.key(0), jax.ShapeDtypeStruct(shape, jnp.bfloat16))
    assert _mask_draws(jaxpr.jaxpr) == [("threefry2x32", 32, shape)]


def _train_step_w1(devs, chips, monkeypatch):
    """``T5Trainer``'s step as ``fit`` builds it (``value_and_grad`` + clipped
    AdamW, fp32 parameters, bf16 compute, dropout 0.1) at FLAN-T5-base widths,
    2 + 2 layers, 32 rows a chip, encoder 512, decoder 128, on a
    ``(data=chips, model=1)`` mesh of the described host."""
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.train import t5_trainer

    cfg = T5Config.flan_t5_base()
    cfg.dtype, cfg.num_layers, cfg.num_decoder_layers = "bfloat16", 2, 2
    model = T5ForConditionalGeneration(cfg)
    tx = t5_trainer._make_optimizer(t5_trainer.TrainingArguments(), 100)
    mesh = Mesh(np.array(devs[:chips]).reshape(chips, 1), ("data", "model"))
    rep, rows = NamedSharding(mesh, P()), NamedSharding(mesh, P("data"))

    def on(tree, sharding):
        return jax.tree_util.tree_map(lambda s: jax.ShapeDtypeStruct(
            s.shape, s.dtype, sharding=sharding), tree)

    one = jnp.ones((1, 8), jnp.int32)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), one, one, one[:, :4])["params"])
    b = 32 * chips
    batch = {k: jax.ShapeDtypeStruct((b, n), jnp.int32, sharding=rows)
             for k, n in (("input_ids", 512), ("attention_mask", 512),
                          ("labels", 128))}
    args = (on(params, rep), on(jax.eval_shape(tx.init, params), rep), batch,
            on(jax.eval_shape(lambda: t5_trainer.dropout_key(0)), rep))
    # the step asks the platform which compiler it will meet; the described
    # chip is not the backend, so the test says so
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    return t5_trainer.make_train_step(model, tx, mesh), args


def _xla_site_shapes(b):
    """The dropout sites of a 2 + 2-layer step at ``b`` rows whose mask XLA
    still draws: the feed-forward hidden, and the decoder's two small
    attentions, which stay dense (128 x 128 and 128 x 512 are under the
    training dispatch's crossover)."""
    enc = [(b, 512, 2048)]
    dec = [(b, H, 128, 128), (b, H, 128, 512), (b, 128, 2048)]
    return sorted(2 * (enc + dec))


@pytest.mark.parametrize("chips", [1, 4])
def test_train_step_draws_each_mask_once_from_the_chips_generator(
        v5e, monkeypatch, chips):
    """What the fine-tune step pays for its masks, and for its attention.  The
    encoder's self-attention sites (512 x 512) are the fused Pallas kernels,
    one forward and one backward each: they draw their masks where they use
    them, so no array of a site's probabilities ``[rows, 12, 512, 512]``, of
    any type, is in the compiled step, and none of the projections' results
    ``[rows, 512, 768]`` is copied into another layout for them.  What XLA
    still draws (the feed-forward hidden, the decoder's small attentions) it
    draws once a site, 16 bits an element, from the chip's generator, no
    Threefry draw (PR 37; the test above holds the parent's count), and uses
    as generated: no copy of bits.  Under ``data=4`` every chip runs the
    kernels on, and generates the bits of, its own 32 rows: no
    ``rng-bit-generator`` of the global 128, no all-gather in the step, and
    one all-reduce of the encoder's position-bias gradient."""
    step, args = _train_step_w1(v5e, chips, monkeypatch)
    draws = _mask_draws(step.trace(*args).jaxpr.jaxpr)
    assert sorted(shape for _, _, shape in draws) == _xla_site_shapes(32 * chips)
    assert {(g, bits) for g, bits, _ in draws} == {("rbg", 16)}
    assert step.attention_sites == {"fused": 2, "dense": 4}

    text = step.lower(*args).compile().as_text()
    assert text.count('custom_call_target="tpu_custom_call"') == 2 * 2
    made = re.findall(r"= u(\d+)\[([\d,]+)\]\S* rng-bit-generator\(", text)
    assert sorted(tuple(map(int, dims.split(","))) for _, dims in made) \
        == _xla_site_shapes(32), made
    assert {bits for bits, _ in made} == {"16"}
    assert not re.findall(r"= u16\[[\d,]+\]\S* copy\(", text)
    for rows in (32, 32 * chips):
        assert f"[{rows},{H},512,512]" not in text
        assert not re.findall(rf"= \w+\[{rows},512,768\]\S* copy\(", text)
    assert "all-gather" not in text
    # the position bias's gradient crosses the chips once a stack (every
    # layer's ``dbias`` added on its own chip first), not once a layer
    reduced = [dims for line in text.splitlines() if " all-reduce(" in line
               for dims in re.findall(r"\w\[([\d,]+)\]",
                                      line.split(" all-reduce(")[0])]
    assert sum(sorted(dims.split(",")) == ["12", "512", "512"]
               for dims in reduced) == (chips > 1), reduced


def test_hybrid_decode_step_keeps_its_state_in_place_on_v5e(v5e):
    """The paged decode step of a hybrid model at Jamba2-3B's widths (three
    layers: Mamba, attention with one K/V head, Mamba) over 128 slots: the
    chip's compiler takes it, updates the per-slot state where it lies (the
    donated cache comes back aliased, nothing the size of a state is held
    beside it) and pads neither the state-major ``[128, 16, 5120]`` state
    nor the flat ``[128, 15360]`` convolution tail."""
    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.models.lm.generate import (init_paged_cache,
                                            make_paged_decode_body)

    cfg = LMConfig(vocab_size=65536, d_model=2560, n_layers=3, n_heads=20,
                   n_kv_heads=1, head_dim=128, d_ff=8192, max_seq_len=2048,
                   rope_theta=None, attn_layer_period=3, attn_layer_offset=1,
                   mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=160,
                   dtype="bfloat16")
    model = CausalLM(cfg)
    slots, slot_len, page = 128, 2048, 128
    npg = slot_len // page
    on = lambda tree, dtype=None: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _struct(s.shape, dtype or s.dtype, v5e), tree)
    params = on(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        jnp.bfloat16)
    cache = on(jax.eval_shape(
        lambda: init_paged_cache(model, slots, slots * npg + 1, page, npg)))
    i32 = lambda *shape: _struct(shape, jnp.int32, v5e)  # noqa: E731
    compiled = jax.jit(make_paged_decode_body(model, slot_len),
                       donate_argnums=(1,)).lower(
        params, cache, i32(slots), i32(slots), i32(slots, npg)).compile()
    mem = compiled.memory_analysis()
    state = 2 * slots * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert mem.alias_size_in_bytes >= state          # updated in place
    assert mem.temp_size_in_bytes < state // 2       # no second copy of it
    text = compiled.as_text()
    assert "f32[128,5120,16]" not in text            # never channel-major
    assert not re.search(r"= \w+\[128,3,5120\]\S* copy\(", text)


# -- the engine's mixed step (PR 42) ------------------------------------------

def _weight_consumers(text):
    """For every weight matrix among the compiled program's parameters (a
    ``params`` leaf of two or more dimensions that a product streams: not the
    Mamba mixer's ``A_log`` and convolution taps, which are elementwise
    operands made once), the operations of the entry computation that read
    it, followed through what only moves it: the compiler's prefetches of a
    weight into fast memory in slices (``slice-start`` / ``slice-done`` /
    ``ConcatBitcast``) and copies."""
    entry = text[text.index("\nENTRY"):]
    entry = entry[:entry.index("\n}\n")]
    moving = {"copy", "copy-start", "copy-done", "bitcast", "slice-start",
              "slice-done", "get-tuple-element", "ConcatBitcast"}
    ops, users, weights = {}, {}, []
    for line in entry.splitlines()[1:]:
        m = re.match(r"\s*(?:ROOT )?%(\S+) = (.*)", line)
        call = m and re.search(r" ([a-z][\w\-]*)\((?=%|\d|\))", " " + m[2])
        if not call:
            continue
        name, rest = m[1], m[2]
        ops[name] = ("ConcatBitcast" if '"ConcatBitcast"' in rest
                     else call[1])
        if ops[name] == "parameter" and name.startswith("params__") \
                and re.match(r"\w+\[\d+,", rest) \
                and not re.search("A_log|conv____kernel", name):
            weights.append(name)
        args = re.split(r"\), \w+=", rest[call.end() - 1:])[0]
        for operand in re.findall(r"%([\w\.\-]+)", args):
            users.setdefault(operand, []).append(name)
    out = {}
    for w in weights:
        found, todo = set(), [w]
        while todo:
            for u in users.get(todo.pop(), []):
                (todo.append if ops[u] in moving else found.add)(u)
        out[w] = found
    return out


def _serving_pool(model, slots, slot_len, page, devs):
    """(params in bf16, paged cache, a struct maker) of a serving pool on
    the described chip, as shapes."""
    from tpu_air.models.lm.generate import init_paged_cache

    npg = slot_len // page
    on = lambda tree, dtype=None: jax.tree_util.tree_map(  # noqa: E731
        lambda s: _struct(s.shape, dtype or s.dtype, devs), tree)
    params = on(jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]),
        jnp.bfloat16)
    cache = on(jax.eval_shape(
        lambda: init_paged_cache(model, slots, slots * npg + 1, page, npg)))
    return params, cache, lambda *shape: _struct(shape, jnp.int32, devs)


def _hybrid(n_layers, period, offset):
    from tpu_air.models.lm import LMConfig

    return LMConfig(vocab_size=65536, d_model=2560, n_layers=n_layers,
                    n_heads=20, n_kv_heads=1, head_dim=128, d_ff=8192,
                    max_seq_len=2048, rope_theta=None,
                    attn_layer_period=period, attn_layer_offset=offset,
                    mamba_d_state=16, mamba_d_conv=4, mamba_dt_rank=160,
                    dtype="bfloat16")


@pytest.mark.parametrize("family", ["hybrid", "sparse_experts"])
def test_mixed_step_streams_each_weight_once_on_v5e(v5e, monkeypatch, family):
    """The engine's mixed step (a decode step over every slot and one slot's
    prefill chunk in one program) at the two serving cells' widths and
    geometry: the chip's compiler takes it; every weight matrix is read by
    ONE operation (the point of the program: XLA does not merge two products
    that share a weight, and the two bodies traced into one program read each
    weight twice, which the counter sees); the tied embedding is read by the
    token gather and the head product.

    The hybrid is Jamba2-3B WHOLE (28 layers, attention at 7 and 21 on one
    K/V head; 128 slots and 128 chunk positions), because what goes wrong
    shows only at depth: with the chunk's state row read from the pool of
    states beside the step's update of it, the compiler copied 22 of the 26
    layers' ``[128, 16, 5120]`` states whole, every step (2.7 ms of a 22 ms
    program on the chip: PERF.md, PR 42), and at 3 or 12 layers none.  Read
    from what the update leaves, the state is updated where it lies: the
    donated cache comes back aliased and no state is copied.  The
    sparse-expert model (OLMoE's widths, 2 layers, 64 slots) runs its three
    grouped products a layer as the kernel, once over the step's rows and
    the chunk's together, and the sum over a token's choices as its own."""
    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.models.lm.generate import (
        make_paged_decode_body, make_paged_mixed_body,
        make_prefill_chunk_body)
    from tpu_air.ops import moe

    if family == "hybrid":
        cfg = _hybrid(28, 14, 7)
        slots, slot_len, page = 128, 2048, 128
    else:
        cfg = LMConfig(vocab_size=50304, d_model=2048, n_layers=2, n_heads=16,
                       n_kv_heads=16, head_dim=128, d_ff=1024,
                       max_seq_len=1024, num_experts=64,
                       num_experts_per_tok=8, qk_norm=True,
                       tie_embeddings=False, dtype="bfloat16")
        slots, slot_len, page = 64, 1024, 128
        monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")

    def lowered(body, cfg):
        model = CausalLM(cfg)
        params, cache, i32 = _serving_pool(model, slots, slot_len, page, v5e)
        slot = {"slot": i32()} if cfg.has_recurrent_layers else {}
        return jax.jit(body(model), donate_argnums=(1,)).lower(
            params, cache, i32(slots), i32(slots),
            i32(slots, slot_len // page), i32(1, page), i32(), i32(),
            i32(slot_len // page), **slot)

    compiled = lowered(
        lambda m: make_paged_mixed_body(m, page, slot_len), cfg).compile()
    text = compiled.as_text()
    readers = _weight_consumers(text)
    tied = {w for w in readers if "embedding" in w and cfg.tie_embeddings}
    assert len(readers) >= 4 * cfg.n_layers
    assert {w: len(r) for w, r in readers.items()} == {
        w: 2 if w in tied else 1 for w in readers}
    if family == "sparse_experts":
        # three products and the sum over a token's choices
        assert text.count("tpu_custom_call") == 4 * cfg.n_layers
        return
    mem = compiled.memory_analysis()
    state = 26 * slots * (16 * 5120 * 4 + 3 * 5120 * 2)
    assert mem.alias_size_in_bytes >= state          # updated in place
    assert mem.temp_size_in_bytes < state // 4       # no second copy of it
    assert not re.search(r"= f32\[128,16,5120\]\S* copy\(", text)
    assert "f32[128,5120,16]" not in text            # never channel-major
    assert not re.search(r"= \w+\[128,3,5120\]\S* copy\(", text)

    # the counter is not vacuous: the two bodies, one after the other in one
    # program (three layers of it), read every weight matrix twice
    def both(model):
        chunk = make_prefill_chunk_body(model, page, slot_len)
        step = make_paged_decode_body(model, slot_len)

        def body(params, cache, tok, pos, table, *chunk_args, slot):
            cache, first = chunk(params, cache, *chunk_args, slot=slot)
            return step(params, cache, tok, pos, table) + (first,)
        return body

    twice = _weight_consumers(
        lowered(both, _hybrid(3, 3, 1)).compile().as_text())
    assert {len(r) for w, r in twice.items() if "embedding" not in w} == {2}


# -- the latent page pool read in place (PR 44, PR 45) --------------------------

_LATENT_SLAB = r"bf16\[128,4096,640\]"           # every slot's pages, gathered
_LATENT_POOL_MOVED = r"= bf16\[2049,256,640\]\S* (copy|transpose)\("
_CHUNK_SLOT = r"bf16\[1,4096,640\]"              # a chunk's one slot, gathered
_CHUNK_SCORES = r"\[(1,)?64,256,4096\]"           # its scores over slot_len


@pytest.mark.parametrize("program", ["decode", "mixed", "chunk"])
def test_latent_decode_rows_read_their_pages_in_place_on_v5e(
        v5e, monkeypatch, program):
    """The latent family's three engine programs, at
    ``gigachat-serve-docchat``'s widths, depth and geometry (128 slots x 4096,
    pages of 256 x 640): the chip's compiler takes them with the paged kernel
    of the rows' read in every layer of the two programs that decode rows,
    the kernel of the chunk's walk in every layer of the two that carry a
    chunk, beside the twelve grouped products and the four sums over a
    token's choices (``held_rows_sum``); nothing of the gathered
    slab's shape ``[128, 4096, 640]`` is made (the gathered read makes it:
    the counter sees it there), nothing of a chunk's gathered slot ``[1,
    4096, 640]`` nor of its scores over ``slot_len`` ``[64, 256, 4096]`` (the
    dense form over the gathered slot makes both: seen there too), no pool
    is copied around a kernel's call or laid out anew for it, and the
    donated pools come back aliased."""
    import json

    from benchmark import weights_mla
    from tpu_air.models.lm import CausalLM
    from tpu_air.models.lm.generate import (make_paged_decode_body,
                                            make_paged_mixed_body,
                                            make_prefill_chunk_body)
    from tpu_air.ops import decode_attention as da

    slots, slot_len, page = 128, 4096, 256
    npg = slot_len // page
    if program == "decode":
        # the counter is not vacuous: the gathered read alone holds the slab
        def gathered(q, pool, table, pos):
            kvm = jnp.arange(slot_len)[None, :] <= pos[:, None]
            return da.latent_decode_attention(
                q, da.gather_pages(pool, table), kvm, 512, jnp.bfloat16)

        text = jax.jit(gathered).lower(
            _struct((slots, 64, 640), jnp.bfloat16, v5e),
            _struct((slots * npg + 1, page, 640), jnp.bfloat16, v5e),
            _struct((slots, npg), jnp.int32, v5e),
            _struct((slots,), jnp.int32, v5e)).compile().as_text()
        assert re.search(_LATENT_SLAB, text)

    with open(os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "benchmark", "configs",
            "gigachat3.1-702b-a36b.json")) as f:
        cfg = weights_mla.lm_config(json.load(f), "bfloat16", slot_len)
    model = CausalLM(cfg)
    params, cache, i32 = _serving_pool(model, slots, slot_len, page, v5e)
    assert cache["layer_0"]["attn"]["cached_latent"].shape == (
        slots * npg + 1, page, 640)
    step = (params, cache, i32(slots), i32(slots), i32(slots, npg))
    a_chunk = (i32(1, page), i32(), i32(), i32(npg))

    def lowered():
        if program == "decode":
            return jax.jit(make_paged_decode_body(model, slot_len),
                           donate_argnums=(1,)).lower(*step)
        if program == "mixed":
            return jax.jit(make_paged_mixed_body(model, page, slot_len),
                           donate_argnums=(1,)).lower(*step, *a_chunk)
        return jax.jit(make_prefill_chunk_body(model, page, slot_len),
                       donate_argnums=(1,)).lower(params, cache, *a_chunk)

    if program == "chunk":
        # not vacuous either: off a TPU the chunk gathers its slot and
        # scores all of it (one layer is enough to see it)
        dense = lowered().compile().as_text()
        assert re.search(_CHUNK_SLOT, dense)
        assert re.search(_CHUNK_SCORES, dense)
    monkeypatch.setattr(da.jax, "default_backend", lambda: "tpu")
    compiled = lowered().compile()
    text = compiled.as_text()
    kernels = {name: sum("tpu_custom_call" in line and name in line
                         for line in text.splitlines())
               for name in ("paged_latent_decode_attention",
                            "paged_latent_chunk_attention")}
    assert kernels == {
        "paged_latent_decode_attention":
            cfg.n_layers if program != "chunk" else 0,
        "paged_latent_chunk_attention":
            cfg.n_layers if program != "decode" else 0}
    assert text.count("tpu_custom_call") == sum(kernels.values()) + 4 * 4
    assert not re.search(_LATENT_SLAB, text)
    assert not re.search(_LATENT_POOL_MOVED, text)
    assert not re.search(_CHUNK_SLOT, text)
    assert not re.search(_CHUNK_SCORES, text)
    # no fusion with a window the compiler's cost model cannot price
    assert '"estimated_cycles":"9223372036854775807"' not in text
    pools = cfg.n_layers * (slots * npg + 1) * page * 640 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools           # appended to in place
    assert mem.temp_size_in_bytes < pools // 4        # and no second pool


# -- a layer that is one thing: Mamba-2 rows beside held latent experts (PR 47)

@pytest.mark.parametrize("program", ["decode", "mixed", "chunk"])
def test_mamba2_state_pool_stays_in_place_at_full_depth_on_v5e(
        v5e, monkeypatch, program):
    """``nemotron3-serve-agent``'s three programs at its widths, depth (all
    11 layers: PR 42 met its copy only at full depth) and geometry (128 slots
    x 4096, pages of 256): the chip's compiler takes each, the donated cache
    comes back aliased, nothing the size of the ``[128, 128, 64, 128]``
    float32 state pool (537 MB a layer) is held or copied beside it, the ten
    expert products are the grouped-matmul kernel with the whole-matrix tile
    and the five sums over a token's choices one kernel each, a step's five
    passes over the state are the in-place kernel of the live
    rows (``ops/ssm.ssd_rows_update``, PR 48: the pool aliased through it, in
    the mixed step too, where the chunk's row is read from what it left),
    and the program fits the chip beside its 12.6 GB of arguments."""
    import json
    import os

    from benchmark import weights_nemotron
    from tpu_air.models.lm import CausalLM
    from tpu_air.models.lm.generate import (make_paged_decode_body,
                                            make_paged_mixed_body,
                                            make_prefill_chunk_body)
    from tpu_air.ops import moe

    monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "configs", "nemotron3-super-120b-a12b.json")) as f:
        cfg = weights_nemotron.lm_config(json.load(f), "bfloat16", 4096)
    model = CausalLM(cfg)
    slots, slot_len, page = 128, 4096, 256
    npg = slot_len // page
    params, cache, i32 = _serving_pool(model, slots, slot_len, page, v5e)
    step = (i32(slots), i32(slots), i32(slots, npg))
    chunk = (i32(1, page), i32(), i32(), i32(npg))
    body, args = {
        "decode": (make_paged_decode_body(model, slot_len), step),
        "chunk": (make_prefill_chunk_body(model, page, slot_len), chunk),
        "mixed": (make_paged_mixed_body(model, page, slot_len), step + chunk),
    }[program]
    kw = {} if program == "decode" else {"slot": i32()}
    compiled = jax.jit(body, donate_argnums=(1,)).lower(
        params, cache, *args, **kw).compile()
    mem = compiled.memory_analysis()
    pool = slots * 128 * 64 * 128 * 4                 # one layer's states
    assert mem.alias_size_in_bytes >= 5 * pool        # updated in place
    assert mem.temp_size_in_bytes < pool // 2 + (1 << 28)   # no second copy
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
    text = compiled.as_text()
    assert not re.search(r"= f32\[128,128,64,128\]\S* copy\(", text)
    # ten expert products and five sums over the choices; a step's five
    # passes (the chunk program has none)
    assert text.count("tpu_custom_call") == (15 if program == "chunk" else 20)
    assert ("ssd_rows_update" in text) == (program != "chunk")
    # a kernel call that asks for more than the default scoped fast memory
    # changes how the compiler tiles OTHER fusions: with 16 MB asked the
    # attention's softmax got a window its cost model could not price (this
    # number) and ran eleven times as long on the chip (PERF.md, PR 48)
    assert '"estimated_cycles":"9223372036854775807"' not in text


# -- four residual streams around latent attention and held experts (PR 58) ---

@pytest.mark.parametrize("program", ["decode", "mixed", "chunk"])
def test_residual_streams_programs_fit_the_chip_with_their_kernels_on_v5e(
        v5e, monkeypatch, program):
    """``xing4-serve-longdoc``'s three programs at its widths, depth and
    geometry (64 slots x 8192, pages of 256 x 640): the chip's compiler takes
    each; both expert products of the four sparse layers are the grouped
    kernel (a side of 3584: the fourth tile) beside the sum over a token's
    choices and the latent family's two paged kernels a layer; the donated
    pools come back aliased; and the program fits the chip beside its 11.5
    GB of arguments (bfloat16 weights, float32 hyper-connections)."""
    import json
    import os

    from benchmark import weights_xing
    from tpu_air.models.lm import CausalLM
    from tpu_air.models.lm.generate import (make_paged_decode_body,
                                            make_paged_mixed_body,
                                            make_prefill_chunk_body)
    from tpu_air.ops import decode_attention as da

    monkeypatch.setattr(da.jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "configs", "xing4.0-29b-a4b.json")) as f:
        cfg = weights_xing.lm_config(json.load(f), "bfloat16", 8192)
    model = CausalLM(cfg)
    slots, slot_len, page = 64, 8192, 256
    npg = slot_len // page
    params, cache, i32 = _serving_pool(model, slots, slot_len, page, v5e)
    params = jax.tree_util.tree_map_with_path(
        lambda path, s: _struct(
            s.shape, jnp.float32 if any(
                str(k.key).endswith("_hc") for k in path) else s.dtype, v5e),
        params)
    step = (i32(slots), i32(slots), i32(slots, npg))
    chunk = (i32(1, page), i32(), i32(), i32(npg))
    body, args = {
        "decode": (make_paged_decode_body(model, slot_len), step),
        "chunk": (make_prefill_chunk_body(model, page, slot_len), chunk),
        "mixed": (make_paged_mixed_body(model, page, slot_len), step + chunk),
    }[program]
    compiled = jax.jit(body, donate_argnums=(1,)).lower(
        params, cache, *args).compile()
    text = compiled.as_text()
    kernels = {name: sum("tpu_custom_call" in line and name in line
                         for line in text.splitlines())
               for name in ("paged_latent_decode_attention",
                            "paged_latent_chunk_attention", "held_rows_sum")}
    assert kernels == {
        "paged_latent_decode_attention": 0 if program == "chunk" else 5,
        "paged_latent_chunk_attention": 0 if program == "decode" else 5,
        "held_rows_sum": 4}
    # what is left: three grouped products a sparse layer, none ragged_dot
    assert text.count("tpu_custom_call") == sum(kernels.values()) + 4 * 3
    assert "ragged" not in text
    assert '"estimated_cycles":"9223372036854775807"' not in text
    pools = cfg.n_layers * (slots * npg + 1) * page * 640 * 2
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= pools           # appended to in place
    assert mem.temp_size_in_bytes < pools // 4        # and no second pool
    assert mem.argument_size_in_bytes == pytest.approx(11.46e9, rel=0.01)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)


# -- window layers that keep a ring beside full layers that keep pages (PR 60)

@pytest.mark.parametrize("program", ["decode", "mixed", "chunk"])
def test_window_rings_programs_fit_the_chip_and_hold_no_slot_len_array_on_v5e(
        v5e, monkeypatch, program):
    """``laguna-serve-mixedlen``'s three programs at its widths, depth and
    geometry (64 slots x 4096, pages of 256): the chip's compiler takes each;
    the three products of the four sparse layers are the grouped kernel (256
    experts of 2048 x 512: the first tile fits) beside the sum over a token's
    choices; NO array ``[64, 4096, .]`` (every slot at ``slot_len``) belongs
    to a window layer, whose rings ``[64, 768, 1024]`` come back aliased with
    the full layers' pools; and the program fits the chip beside its 10.5 GB
    of arguments."""
    import json
    import os

    from benchmark import weights_swa
    from tpu_air.models.lm import CausalLM
    from tpu_air.models.lm.generate import (make_paged_decode_body,
                                            make_paged_mixed_body,
                                            make_prefill_chunk_body)
    from tpu_air.ops import moe

    monkeypatch.setattr(moe.jax, "default_backend", lambda: "tpu")
    with open(os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "benchmark", "configs", "laguna-xs.2.json")) as f:
        cfg = weights_swa.lm_config(json.load(f), "bfloat16", 4096)
    model = CausalLM(cfg)
    slots, slot_len, page = 64, 4096, 256
    npg = slot_len // page
    assert cfg.window_ring_len(page) == 768
    params, cache, i32 = _serving_pool(model, slots, slot_len, page, v5e)
    step = (i32(slots), i32(slots), i32(slots, npg))
    chunk = (i32(1, page), i32(), i32(), i32(npg))
    slot = {"slot": i32()}
    body, args, kw = {
        "decode": (make_paged_decode_body(model, slot_len), step, {}),
        "chunk": (make_prefill_chunk_body(model, page, slot_len), chunk, slot),
        "mixed": (make_paged_mixed_body(model, page, slot_len), step + chunk,
                  slot),
    }[program]
    compiled = jax.jit(body, donate_argnums=(1,)).lower(
        params, cache, *args, **kw).compile()
    text = compiled.as_text()
    sums = sum("tpu_custom_call" in line and "held_rows_sum" in line
               for line in text.splitlines())
    assert sums == 4
    # what is left: three grouped products a sparse layer, none ragged_dot
    assert text.count("tpu_custom_call") == sums + 4 * 3
    assert "ragged" not in text
    assert '"estimated_cycles":"9223372036854775807"' not in text
    whole = re.compile(r"\[64,4096,\d+\]")
    of_window = [line for line in text.splitlines() if whole.search(
        line.split(" = ")[-1].split("(")[0] if " = " in line else "")
        and re.search(r"layer_[123]/", line)]
    assert not of_window, of_window[:3]
    if program != "chunk":      # the full layers' gathered read is there
        assert any(whole.search(line) and re.search(r"layer_[04]/", line)
                   for line in text.splitlines())
    held = (2 * 2 * (slots * npg + 1) * page * 1024 * 2
            + 3 * 2 * slots * 768 * 1024 * 2)
    mem = compiled.memory_analysis()
    assert mem.alias_size_in_bytes >= held            # written to in place
    assert mem.argument_size_in_bytes == pytest.approx(
        7.74e9 + held, rel=0.01)
    assert (mem.argument_size_in_bytes + mem.temp_size_in_bytes
            < 15.75 * 2 ** 30)
