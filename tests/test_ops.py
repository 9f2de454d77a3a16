"""Kernel tests: Pallas flash attention (interpret mode on the CPU mesh —
SURVEY.md §4.3: distributed/kernel tests must run without TPU hardware) and
ring attention across the virtual 8-device mesh."""

import functools

import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from tpu_air.ops import (  # noqa: E402
    flash_attention,
    flash_attention_with_lse,
    ring_attention_sharded,
)
from tpu_air.ops.flash_attention import (  # noqa: E402
    _reference_attention,
    _reference_pair,
)

BH, L, D = 4, 256, 64


@pytest.fixture(scope="module")
def qkv():
    rng = np.random.default_rng(0)
    mk = lambda: jnp.asarray(rng.normal(size=(BH, L, D)), jnp.float32)  # noqa: E731
    return mk(), mk(), mk()


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("with_bias", [False, True])
def test_flash_matches_reference(qkv, causal, with_bias):
    q, k, v = qkv
    bias = (
        jnp.asarray(np.random.default_rng(1).normal(size=(BH, L, L)), jnp.float32)
        if with_bias
        else None
    )
    out = flash_attention(q, k, v, bias, causal=causal)
    ref = _reference_attention(q, k, v, bias, 1.0 / D**0.5, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_flash_t5_mode_no_scale(qkv):
    """T5 does not scale attention scores (scale=1.0) and always passes a
    position bias — the exact configuration the framework's T5 uses."""
    q, k, v = qkv
    bias = jnp.asarray(np.random.default_rng(2).normal(size=(1, L, L)), jnp.float32)
    bias = jnp.broadcast_to(bias, (BH, L, L))
    out = flash_attention(q, k, v, bias, scale=1.0)
    ref = _reference_attention(q, k, v, bias, 1.0, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=3e-5, rtol=1e-4)


def test_flash_gradients_match(qkv):
    q, k, v = qkv

    def f_flash(q, k, v):
        return flash_attention(q, k, v, causal=True).sum()

    def f_ref(q, k, v):
        return _reference_attention(q, k, v, None, 1.0 / D**0.5, True).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)


def test_flash_bf16(qkv):
    q, k, v = (x.astype(jnp.bfloat16) for x in qkv)
    out = flash_attention(q, k, v)
    assert out.dtype == jnp.bfloat16
    ref = _reference_attention(q, k, v, None, 1.0 / D**0.5, False)
    np.testing.assert_allclose(
        np.asarray(out, np.float32), np.asarray(ref, np.float32), atol=3e-2
    )


def test_flash_rejects_indivisible_lengths():
    q = jnp.zeros((1, 100, 64))
    with pytest.raises(ValueError, match="divide"):
        flash_attention(q, q, q, block_q=64, block_k=64)


def test_lse_is_logsumexp(qkv):
    q, k, v = qkv
    _, lse = flash_attention_with_lse(q, k, v, scale=1.0)
    s = jnp.einsum("bqd,bkd->bqk", q, k)
    ref_lse = jax.scipy.special.logsumexp(s, axis=-1)
    np.testing.assert_allclose(np.asarray(lse), np.asarray(ref_lse), atol=1e-4, rtol=1e-4)


# -- ring attention over the virtual mesh ------------------------------------


@pytest.mark.parametrize("causal", [False, True])
def test_ring_attention_matches_full(qkv, causal):
    from jax.sharding import Mesh

    q, k, v = qkv
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("sequence",))
    out = ring_attention_sharded(
        q, k, v, mesh, causal=causal, block_q=32, block_k=32
    )
    ref = _reference_attention(q, k, v, None, 1.0 / D**0.5, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_ring_attention_is_actually_sharded(qkv):
    """The local shard view must be L/P long — guard against silent
    full-replication (which would defeat sequence parallelism)."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    q, k, v = qkv
    devs = np.array(jax.devices()[:8])
    mesh = Mesh(devs, ("sequence",))
    out = ring_attention_sharded(q, k, v, mesh, block_q=32, block_k=32)
    # output sharding preserves the sequence partitioning
    assert out.sharding.is_equivalent_to(
        NamedSharding(mesh, P(None, "sequence", None)), out.ndim
    )


@pytest.mark.slow  # numerics-parity / superseded-coverage: slow tier (budget, r3 weak #5)
def test_t5_flash_config_path_matches_einsum():
    """attention_impl="flash" swaps the attention impl without changing
    the math — parity through the full T5 stack."""
    import dataclasses

    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration

    cfg = T5Config.tiny()
    cfg.dropout_rate = 0.0
    m1 = T5ForConditionalGeneration(cfg)
    m2 = T5ForConditionalGeneration(dataclasses.replace(cfg, attention_impl="flash"))
    rng = jax.random.PRNGKey(0)
    b, le, ld = 2, 64, 32
    ii = jax.random.randint(rng, (b, le), 2, cfg.vocab_size, jnp.int32)
    am = jnp.ones((b, le), jnp.int32).at[:, 50:].set(0)
    di = jax.random.randint(rng, (b, ld), 2, cfg.vocab_size, jnp.int32)
    params = m1.init(rng, ii[:1, :8], am[:1, :8], di[:1, :4])["params"]
    o1 = m1.apply({"params": params}, ii, am, di, deterministic=True)
    o2 = m2.apply({"params": params}, ii, am, di, deterministic=True)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2), atol=2e-4, rtol=1e-3)


def test_flash_bias_gradient_matches(qkv):
    """dbias flows back to T5's relative-position table — must match the
    reference VJP, including the reduction over the batch broadcast."""
    q, k, v = qkv
    bias = jnp.asarray(
        np.random.default_rng(3).normal(size=(1, L, L)), jnp.float32
    )  # batch-shared, like T5's (1|H, Lq, Lk) table output

    def f_flash(bias):
        return flash_attention(q, k, v, bias, scale=1.0).sum()

    def f_ref(bias):
        return _reference_attention(q, k, v, bias, 1.0, False).sum()

    gf = jax.grad(f_flash)(bias)
    gr = jax.grad(f_ref)(bias)
    assert gf.shape == bias.shape
    np.testing.assert_allclose(np.asarray(gf), np.asarray(gr), atol=1e-3, rtol=1e-3)


def test_flash_kv_mask_matches_dense_mask(qkv):
    q, k, v = qkv
    kv_mask = jnp.ones((BH, L), jnp.int32).at[:, L // 2 :].set(0)
    out = flash_attention(q, k, v, kv_mask=kv_mask)
    dense = jnp.where(kv_mask[:, None, :] == 1, 0.0, -1e30)
    ref = _reference_attention(q, k, v, dense, 1.0 / D**0.5, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5, rtol=1e-4)


def test_ring_attention_gradients(qkv):
    """Ring attention must train: grads through the ppermute/merge schedule
    match full-attention grads."""
    from jax.sharding import Mesh

    q, k, v = qkv
    mesh = Mesh(np.array(jax.devices()[:8]), ("sequence",))

    def f_ring(q, k, v):
        return ring_attention_sharded(q, k, v, mesh, block_q=32, block_k=32).sum()

    def f_ref(q, k, v):
        return _reference_attention(q, k, v, None, 1.0 / D**0.5, False).sum()

    gf = jax.grad(f_ring, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-3, rtol=1e-3)


def test_t5_flash_decode_uses_einsum_path(monkeypatch):
    """Cached decode must never launch the Pallas kernel (per-token qlen=1
    launches are the perf cliff the config docstring promises to avoid)."""
    import importlib

    # NB: `import tpu_air.ops.flash_attention as fa` would bind the *function*
    # (the `from .flash_attention import flash_attention` re-export in
    # ops/__init__.py shadows the submodule attribute of the same name), so
    # resolve the module explicitly.
    fa = importlib.import_module("tpu_air.ops.flash_attention")
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration
    from tpu_air.models.t5.generate import generate

    qlens = []
    orig = fa._pallas_fwd

    def counting(q, *a, **kw):
        qlens.append(q.shape[1])
        return orig(q, *a, **kw)

    monkeypatch.setattr(fa, "_pallas_fwd", counting)
    cfg = T5Config.tiny()
    cfg.dropout_rate = 0.0
    cfg.attention_impl = "flash"
    model = T5ForConditionalGeneration(cfg)
    rng = jax.random.PRNGKey(0)
    ii = jax.random.randint(rng, (1, 16), 2, cfg.vocab_size, jnp.int32)
    am = jnp.ones((1, 16), jnp.int32)
    params = model.init(rng, ii, am, ii[:, :4])["params"]
    qlens.clear()
    seqs = generate(model, params, np.asarray(ii), attention_mask=np.asarray(am),
                    max_new_tokens=4)
    assert seqs.shape[0] == 1
    # The encoder traces flash once per layer (qlen=16); init_cache's
    # eval_shape additionally traces decoder cross-attention at the full
    # decode budget (qlen=5, costless — abstract trace only).  The contract:
    # no per-token qlen=1 launch may ever reach the kernel — that is the perf
    # cliff the config docstring promises to avoid, and it is exactly what
    # the lax.scan decode body would produce if the gating regressed.
    assert qlens, "flash never ran (encoder path should trace it)"
    assert all(q > 1 for q in qlens), f"flash ran with per-token qlen=1: {qlens}"


def test_flash_grad_through_lse_and_kv_mask(qkv):
    """The blockwise backward folds the logsumexp cotangent into the delta
    term (ring attention trains through merged stats) and respects the
    key-padding mask; both must match autodiff of the dense reference."""
    q, k, v = qkv
    B = q.shape[0]
    L = q.shape[1]
    key = jax.random.PRNGKey(7)
    kv_mask = (jax.random.uniform(key, (B, L)) > 0.3).astype(jnp.int32)
    w = jax.random.normal(key, (B, L))  # lse weighting: nonzero lse cotangent

    def f_flash(q, k, v):
        o, lse = flash_attention_with_lse(q, k, v, kv_mask=kv_mask, scale=1.0)
        return (o * 0.3).sum() + (lse * w).sum()

    addmask = (1.0 - kv_mask.astype(jnp.float32)) * -1e30

    def f_ref(q, k, v):
        o, lse = _reference_pair(q, k, v, None, addmask, 1.0, False)
        return (o * 0.3).sum() + (lse * w).sum()

    gf = jax.grad(f_flash, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(f_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gf, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-3, rtol=2e-3)


def test_fully_masked_row_grads_are_finite_and_small(qkv):
    """A zero-length (fully key-padded) row must not blow up the backward:
    f32 can't represent -1e30 + log(klen), so the naive exp(s - lse) gives
    klen-inflated gradients; the kernel hard-zeroes masked entries."""
    q, k, v = qkv
    B, L = q.shape[0], q.shape[1]
    kv_mask = jnp.ones((B, L), jnp.int32).at[0].set(0)  # batch 0: all masked

    def f(q, k, v):
        return flash_attention(q, k, v, kv_mask=kv_mask, scale=1.0).sum()

    dq, dk, dv = jax.grad(f, argnums=(0, 1, 2))(q, k, v)
    for g in (dq, dk, dv):
        assert bool(jnp.isfinite(g).all())
    # the masked batch element's k/q grads are exactly zero (p == 0 there);
    # an inflation bug makes them ~L times a normal gradient instead
    assert float(jnp.abs(dq[0]).max()) == 0.0
    assert float(jnp.abs(dk[0]).max()) == 0.0


def test_attention_auto_dispatch_by_seq_len(monkeypatch):
    """attention_impl="auto" (the default) picks the path at TRACE time by
    sequence length: einsum below flash_min_seq_len, flash at/above it —
    no user flag."""
    import importlib

    fa = importlib.import_module("tpu_air.ops.flash_attention")
    from tpu_air.models.t5 import T5Config, T5ForConditionalGeneration

    calls = []
    orig = fa._pallas_fwd

    def counting(q, *a, **kw):
        calls.append(q.shape[1])
        return orig(q, *a, **kw)

    monkeypatch.setattr(fa, "_pallas_fwd", counting)
    # the backend/tile gate is measured-on-TPU policy; neutralize it here so
    # the SHAPE dispatch is testable on the CPU mesh (interpret-mode flash)
    monkeypatch.setattr(fa, "auto_dispatch_ok", lambda q, k: True)
    cfg = T5Config.tiny()
    cfg.dropout_rate = 0.0
    cfg.flash_min_seq_len = 32  # tiny-dial stand-in for the 1024 crossover
    assert cfg.attention_impl == "auto"
    model = T5ForConditionalGeneration(cfg)
    rng = jax.random.PRNGKey(0)

    def run(seq):
        ii = jax.random.randint(rng, (1, seq), 2, cfg.vocab_size, jnp.int32)
        am = jnp.ones((1, seq), jnp.int32)
        params = model.init(rng, ii[:, :8], am[:, :8], ii[:, :4])["params"]
        model.apply({"params": params}, ii, am, ii[:, :8], deterministic=True)

    calls.clear()
    run(16)  # below threshold → einsum everywhere
    assert not calls, f"flash traced below the crossover: {calls}"
    run(64)  # at/above threshold → encoder + cross attention use flash
    # encoder self-attn traces at qlen=64; decoder CROSS attention traces at
    # qlen=8 but klen=64 — dispatch is max(qlen, klen), so both are flash
    assert calls and max(calls) == 64, calls

    # LM family: the same rule, its crossover a module constant
    from tpu_air.models.lm import CausalLM, LMConfig, modeling

    lcfg = LMConfig.tiny()
    monkeypatch.setattr(modeling, "FLASH_MIN_SEQ_LEN", 32)
    lm = CausalLM(lcfg)
    ids16 = jax.random.randint(rng, (1, 16), 2, lcfg.vocab_size, jnp.int32)
    ids64 = jax.random.randint(rng, (1, 64), 2, lcfg.vocab_size, jnp.int32)
    lp = lm.init(rng, ids16)["params"]
    calls.clear()
    lm.apply({"params": lp}, ids16)
    assert not calls, f"LM flash traced below the crossover: {calls}"
    lm.apply({"params": lp}, ids64)
    assert calls, "LM flash not traced at/above the crossover"


# -- decode attention over cached slabs (ops/decode_attention.py) ------------


def _dk_inputs(b=3, L=96, h=4, d=16, seed=0, dtype=jnp.float32):
    """q ``[b, 1, h, d]``, FLAT slabs ``[b, L, h*d]``, additive ``[h, L]``
    bias, ``[b, L]`` key mask with at least two valid keys in every row."""
    rng = np.random.default_rng(seed)
    q = jnp.asarray(rng.standard_normal((b, 1, h, d)), dtype)
    k = jnp.asarray(rng.standard_normal((b, L, h * d)), dtype)
    v = jnp.asarray(rng.standard_normal((b, L, h * d)), dtype)
    bias = jnp.asarray(rng.standard_normal((h, L)), jnp.float32)
    mask = jnp.asarray(rng.integers(0, 2, (b, L)) | (np.arange(L) < 2),
                       jnp.float32)
    return q, k, v, bias, mask


def _heads(x, h):
    """The ``[b, L, h, d]`` view ``decode_attention_reference`` reads."""
    return x.reshape(*x.shape[:2], h, -1)


# float32 operands: exact to rounding.  bfloat16 operands are what every
# cell runs: the flat path rounds the softmax probabilities to bfloat16
# before the second matmul (the reference keeps them float32), which is
# 2^-9 relative on each; 2e-2 absolute on contexts of order 1 holds that.
_DK_TOL = {"float32": 2e-5, "bfloat16": 2e-2}


@pytest.mark.parametrize("kv_heads", [4, 2], ids=["own_kv", "shared_kv"])
@pytest.mark.parametrize("with_mask", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("L", [96, 129])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_flat_decode_attention_matches_reference(kv_heads, with_mask, L,
                                                 dtype):
    """The LM's single-token step == the dense reference over the same slab
    (per-row key mask or none; a K/V head a query head or one for two), at a
    cache length that is a multiple of 8 and one that is not."""
    from tpu_air.ops.decode_attention import (
        decode_attention_reference, flat_decode_attention,
    )

    h, g = 4, kv_heads
    q, k, v, _, mask = _dk_inputs(L=L, h=h, dtype=jnp.dtype(dtype))
    k, v = k[..., :g * 16], v[..., :g * 16]
    mask = mask if with_mask else None
    got = flat_decode_attention(q, k, v, mask, h, jnp.dtype(dtype), g)
    want = decode_attention_reference(
        q, jnp.repeat(_heads(k, g), h // g, axis=2),
        jnp.repeat(_heads(v, g), h // g, axis=2), kv_mask=mask)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_DK_TOL[dtype], rtol=_DK_TOL[dtype])


@pytest.mark.parametrize("cur", [0, 41, 95])
@pytest.mark.parametrize("cache", ["float32", "bfloat16", "int8"])
def test_flat_append_decode_attention_equals_flat_over_the_appended_slab(
        cache, cur):
    """The read of a slab a step is about to append to (position-major
    ``[L, b, h*d]`` as it was, the step's row apart) == the dense reference
    over the ``[b, L, h, d]`` slab with the row in place, at the first, a
    middle and the last position, with the causal bias a decode step carries
    and a key mask; whatever the slab held at ``cur`` before is never used.  int8: per-position scales, read as after the
    step."""
    from tpu_air.ops.decode_attention import (
        decode_attention_reference, flat_append_decode_attention,
    )

    b, L, h, d = 3, 96, 4, 16
    dtype = jnp.float32 if cache == "int8" else jnp.dtype(cache)
    rng = np.random.default_rng(7)
    q, k, v, bias, mask = _dk_inputs(dtype=dtype)
    bias = bias + jnp.where(jnp.arange(L) <= cur, 0.0, -1e9)[None]
    ks = vs = None
    if cache == "int8":
        k = jnp.asarray(rng.integers(-127, 128, (b, L, h * d)), jnp.int8)
        v = jnp.asarray(rng.integers(-127, 128, (b, L, h * d)), jnp.int8)
        ks = jnp.asarray(rng.uniform(0.001, 0.02, (b, L, h)), jnp.float32)
        vs = jnp.asarray(rng.uniform(0.001, 0.02, (b, L, h)), jnp.float32)
    per_pos = lambda x: None if x is None else x[..., None]  # noqa: E731
    want = decode_attention_reference(
        q, _heads(k, h), _heads(v, h), bias=bias, kv_mask=mask,
        k_scale=per_pos(ks), v_scale=per_pos(vs))
    # position-major, and junk where the step's row will go
    pm = lambda x: None if x is None else jnp.swapaxes(x, 0, 1)  # noqa: E731
    junk = jnp.full((1, b, h * d), 99, k.dtype)
    got = flat_append_decode_attention(
        q, jax.lax.dynamic_update_slice(pm(k), junk, (cur, 0, 0)),
        jax.lax.dynamic_update_slice(pm(v), junk, (cur, 0, 0)),
        pm(k)[cur:cur + 1], pm(v)[cur:cur + 1], jnp.asarray(cur), bias, mask,
        pm(ks), pm(vs), h, dtype)
    assert got.shape == q.shape and got.dtype == dtype
    tol = _DK_TOL["bfloat16" if cache == "bfloat16" else "float32"]
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("heads", [12, 16], ids=["base", "large"])
@pytest.mark.parametrize("with_mask", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("cur", [0, 21, 32, 40])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_prefix_append_decode_attention_matches_reference_and_flat_form(
        dtype, cur, with_mask, heads):
    """A decode loop's read of the stacked slabs where they lie (the Pallas
    kernel, interpret mode; FLAN-T5-base's and -large's heads of 64, 16 rows)
    == the dense reference over the slab with the row in place == the flat
    read of the layer's slice, with no position written, ``cur`` inside a
    block, on a block's edge and at the slab's last position (the last
    block then starts early and masks what the one before it counted), with
    the causal bias and with or without a key mask.  Only the blocks that
    hold a written position are copied: every block past them is NaN here,
    and so is the other layer."""
    from tpu_air.ops import decode_attention as da

    b, L, d, block = 16, 41, 64, da._PREFIX_BLOCK
    dt = jnp.dtype(dtype)
    q, k, v, bias, mask = _dk_inputs(b, L, heads, d, seed=cur, dtype=dt)
    bias = bias + jnp.where(jnp.arange(L) <= cur, 0.0, -1e9)[None]
    mask = mask.at[:, cur].set(1.0) if with_mask else None
    want = da.decode_attention_reference(
        q, _heads(k, heads), _heads(v, heads), bias=bias, kv_mask=mask)
    pm = lambda x: jnp.swapaxes(x, 0, 1)  # noqa: E731
    row = slice(cur, cur + 1)
    flat = da.flat_append_decode_attention(
        q, pm(k), pm(v), pm(k)[row], pm(v)[row], jnp.asarray(cur), bias,
        mask, None, None, heads, dt)

    def stacked(x):
        # junk where the step's row will go, NaN from the first block with
        # no written position on, and in the layer this call does not read
        x = jax.lax.dynamic_update_slice(
            pm(x), jnp.full((1, b, heads * d), 99, dt), (cur, 0, 0))
        x = x.at[-(-cur // block) * block:].set(jnp.nan)
        return jnp.stack([jnp.full_like(x, jnp.nan), x])

    assert da.prefix_blocks_are_whole_tiles(stacked(k), heads)
    got = da.prefix_append_decode_attention(
        q, stacked(k), stacked(v), 1, pm(k)[row], pm(v)[row],
        jnp.asarray(cur), bias, mask, heads, dt)
    assert got.shape == q.shape and got.dtype == dt
    for other in (want, flat):
        np.testing.assert_allclose(
            np.asarray(got, np.float32), np.asarray(other, np.float32),
            atol=_DK_TOL[dtype], rtol=_DK_TOL[dtype])


def test_prefix_read_is_taken_on_a_tpu_for_whole_tiles_alone(monkeypatch):
    """The trace-time rule: a TPU, no mesh, bf16 or f32 slabs whose position
    is whole tiles that split into row tiles, and positions enough for a
    block; everything else keeps the flat read."""
    from tpu_air.ops import decode_attention as da
    from tpu_air.ops.flash_attention import kernel_mesh

    def slabs(L=129, b=256, hd=768, dtype=jnp.bfloat16):
        return jax.ShapeDtypeStruct((12, L, b, hd), dtype)

    assert not da.prefix_slabs_read_in_place(slabs(), 12)      # a CPU
    monkeypatch.setattr(da.jax, "default_backend", lambda: "tpu")
    assert da.prefix_slabs_read_in_place(slabs(), 12)
    assert da.prefix_slabs_read_in_place(slabs(b=128, hd=1024), 16)
    assert da.prefix_slabs_read_in_place(slabs(b=8, dtype=jnp.float32), 12)
    assert not da.prefix_slabs_read_in_place(slabs(b=8), 12)   # half a tile
    assert not da.prefix_slabs_read_in_place(slabs(b=3), 12)
    assert not da.prefix_slabs_read_in_place(slabs(hd=64), 4)
    assert not da.prefix_slabs_read_in_place(slabs(dtype=jnp.int8), 12)
    assert not da.prefix_slabs_read_in_place(slabs(L=8), 12)
    with kernel_mesh(object()):
        assert not da.prefix_slabs_read_in_place(slabs(), 12)


def _minor(x, h):
    """A flat ``[b, L, h*d]`` slab as the length-minor ``[b, h, d, L]`` one
    (no lane padding: the op takes any ``L``)."""
    return jnp.transpose(_heads(x, h), (0, 2, 3, 1))


@pytest.mark.parametrize("with_bias", [False, True], ids=["nobias", "bias"])
@pytest.mark.parametrize("with_mask", [False, True], ids=["nomask", "mask"])
@pytest.mark.parametrize("L", [128, 129])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_length_minor_decode_attention_matches_reference(with_bias, with_mask,
                                                         L, dtype):
    """The read of the T5 cross cache == the dense reference over the same
    values, same operand contract as the flat read, at a length that is whole
    lanes and one that is not."""
    from tpu_air.ops.decode_attention import (
        decode_attention_reference, length_minor_decode_attention,
    )

    h = 4
    q, k, v, bias, mask = _dk_inputs(L=L, h=h, dtype=jnp.dtype(dtype))
    bias = bias if with_bias else None
    mask = mask if with_mask else None
    got = length_minor_decode_attention(q, _minor(k, h), _minor(v, h), bias,
                                        mask, None, None, jnp.dtype(dtype))
    want = decode_attention_reference(q, _heads(k, h), _heads(v, h),
                                      bias=bias, kv_mask=mask)
    assert got.shape == q.shape and got.dtype == q.dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32),
                               atol=_DK_TOL[dtype], rtol=_DK_TOL[dtype])


@pytest.mark.parametrize("L", [96, 128, 300])
def test_length_minor_pads_to_whole_lanes_behind_the_mask(L):
    """``length_minor`` stores ``[b, L, h, d]`` as ``[b, h, d, Lp]``, ``Lp``
    the next multiple of 128, zeros past ``L``; with the key mask padded by
    ``pad_keys`` the read over the padded slab is the read over the real
    positions."""
    from tpu_air.ops.decode_attention import (
        length_minor, length_minor_decode_attention, pad_keys,
    )

    h = 4
    q, k, v, bias, mask = _dk_inputs(L=L, h=h)
    ks, vs = length_minor(_heads(k, h)), length_minor(_heads(v, h))
    Lp = -(-L // 128) * 128
    assert ks.shape == (3, h, 16, Lp)
    np.testing.assert_array_equal(np.asarray(ks[..., :L]), np.asarray(_minor(k, h)))
    assert not np.asarray(ks[..., L:]).any()
    got = length_minor_decode_attention(
        q, ks, vs, pad_keys(bias, Lp), pad_keys(mask, Lp), None, None,
        jnp.float32)
    want = length_minor_decode_attention(
        q, _minor(k, h), _minor(v, h), bias, mask, None, None, jnp.float32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6, rtol=2e-6)


@pytest.mark.parametrize("kv_heads", [1, 2])
def test_flat_decode_attention_grouped_kv_heads(kv_heads):
    """One formulation for every number of K/V heads: ``g`` K/V heads serving
    ``h / g`` query heads each, against the explicit reference over K/V
    repeated a query head."""
    from tpu_air.ops.decode_attention import (
        decode_attention_reference, flat_decode_attention,
    )

    b, L, h, d = 3, 96, 4, 16
    g, rep = kv_heads, 4 // kv_heads
    rng = np.random.default_rng(3 + kv_heads)
    q, _, _, _, mask = _dk_inputs()
    k = jnp.asarray(rng.standard_normal((b, L, g * d)), jnp.float32)
    v = jnp.asarray(rng.standard_normal((b, L, g * d)), jnp.float32)
    got = flat_decode_attention(q, k, v, mask, h, jnp.float32, g)
    want = decode_attention_reference(
        q, jnp.repeat(_heads(k, g), rep, axis=2),
        jnp.repeat(_heads(v, g), rep, axis=2), kv_mask=mask)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


def test_length_minor_decode_attention_int8_scale_folding():
    """int8 cross slabs, per-channel scales ``[b, h, d, 1]``: folded into q
    and the context, equal to the reference over the dequantised values."""
    from tpu_air.ops.decode_attention import (
        decode_attention_reference, length_minor_decode_attention,
    )

    b, L, h, d = 3, 96, 4, 16
    rng = np.random.default_rng(1)
    q, _, _, bias, mask = _dk_inputs()
    k8 = jnp.asarray(rng.integers(-127, 128, (b, L, h * d)), jnp.int8)
    v8 = jnp.asarray(rng.integers(-127, 128, (b, L, h * d)), jnp.int8)
    ks = jnp.asarray(rng.uniform(0.001, 0.02, (b, h, d, 1)), jnp.float32)
    vs = jnp.asarray(rng.uniform(0.001, 0.02, (b, h, d, 1)), jnp.float32)
    got = length_minor_decode_attention(q, _minor(k8, h), _minor(v8, h), bias,
                                        mask, ks, vs, jnp.float32)
    want = decode_attention_reference(
        q, _heads(k8, h), _heads(v8, h), bias=bias, kv_mask=mask,
        k_scale=ks.reshape(b, 1, h, d), v_scale=vs.reshape(b, 1, h, d))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


# the paged latent read: rows x (table, position); page 16, 4 pages a slot.
# A table names the slot's pages in position order; 0 is the null page.
_PAGED_LATENT = {
    "length_1": ([[5, 0, 0, 0]], [0]),
    "length_page_less_one": ([[5, 0, 0, 0]], [14]),
    "length_page": ([[5, 0, 0, 0]], [15]),
    "length_page_plus_one": ([[5, 9, 0, 0]], [16]),
    "length_full_slot": ([[5, 9, 2, 7]], [63]),
    # rows at position 0 (free, or mid-prefill: table at the null page)
    # before, between and behind live rows
    "idle_rows_between_live_rows": (
        [[0, 0, 0, 0], [3, 4, 0, 0], [0, 0, 0, 0], [0, 0, 0, 0],
         [6, 1, 8, 0], [0, 0, 0, 0]], [0, 20, 0, 0, 40, 0]),
    "shuffled_physical_pages": (
        [[12, 3, 10, 1], [2, 11, 4, 9], [8, 5, 7, 6]], [63, 50, 33]),
    "rows_sharing_prefix_pages": (
        [[4, 5, 6, 0], [4, 5, 7, 0], [4, 8, 0, 0]], [37, 45, 16]),
    "null_page_in_the_tables_tail": (
        [[3, 0, 0, 0], [4, 5, 0, 0], [6, 7, 8, 0]], [9, 31, 32]),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_PAGED_LATENT))
def test_paged_latent_read_is_the_gathered_read(case, dtype):
    """``paged_latent_decode_attention`` (the kernel, interpret mode) reads
    each row's live pages where they lie and gives what
    ``latent_decode_attention`` gives over ``gather_pages``, the order of the
    sums apart.  Every page no row has live (a table's tail, a page of no
    table) holds NaN for the kernel and numbers for the gathered read: a
    visit to one would show, in that row and, through the copy a row's last
    page starts for the next row, in the one behind it."""
    from tpu_air.ops.decode_attention import (
        gather_pages, latent_decode_attention, paged_latent_decode_attention,
    )

    table, pos = (np.asarray(a, np.int32) for a in _PAGED_LATENT[case])
    h, w, rank, C = 4, 128, 16, 16
    rng = np.random.default_rng(7)
    pool = rng.standard_normal((14, C, w)).astype(np.float32)
    pool[..., 24:] = 0.0                 # a row's zeros behind [c, k_r]
    q = jnp.asarray(rng.standard_normal((len(pos), h, w)), dtype)
    live = {0} | {int(p) for row, at in zip(table, pos)
                  for p in row[:at // C + 1]}
    poisoned = pool.copy()
    poisoned[[p for p in range(len(pool)) if p not in live]] = np.nan
    kvm = jnp.arange(table.shape[1] * C)[None, :] <= pos[:, None]
    want = latent_decode_attention(
        q, gather_pages(jnp.asarray(pool, dtype), jnp.asarray(table)), kvm,
        rank, dtype)
    got = paged_latent_decode_attention(
        q, jnp.asarray(poisoned, dtype), jnp.asarray(table), jnp.asarray(pos),
        rank, dtype, interpret=True)
    assert got.shape == (len(pos), h, rank) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    tol = 2e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


# the chunk's walk: (start, the slot's table row); page 16, 4 pages a slot,
# h = 5 heads (a tile of one) or 4 (one tile), so a tile's last page starts
# the copy of the next tile's first
_CHUNK_WALK = {
    "first_page": (0, [5, 0, 0, 0], 5),
    "second_page": (16, [5, 9, 0, 0], 5),
    "last_page_of_the_slot": (48, [12, 3, 10, 1], 4),
    "third_page_behind_two_others": (32, [7, 2, 11, 0], 4),
    "shared_first_pages": (32, [4, 5, 6, 0], 6),
}


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("case", sorted(_CHUNK_WALK))
def test_paged_latent_chunk_walk_is_the_dense_form(case, dtype):
    """``paged_latent_chunk_attention`` (the kernel, interpret mode) visits
    pages ``table_row[0 .. start // page_len]`` and gives what the dense
    causal form gives over the slot's gathered pages (K and V from ``W_UKV``
    over all of them, a softmax over ``slot_len``), the order of the sums
    apart.  Every other page of the pool, the null page too, holds NaN for
    the kernel: a visit to one would show."""
    from tpu_air.ops.decode_attention import (gather_pages,
                                              paged_latent_chunk_attention)

    start, row, h = _CHUNK_WALK[case]
    C, w, r, dn, dr, dv = 16, 128, 16, 8, 8, 12
    rng = np.random.default_rng(9)
    pool = rng.standard_normal((14, C, w)).astype(np.float32)
    pool[..., r + dr:] = 0.0
    q_n = jnp.asarray(rng.standard_normal((h, C, dn)), dtype)
    q_r = jnp.asarray(rng.standard_normal((h, C, dr)), dtype)
    k_up = jnp.asarray(rng.standard_normal((h, r, dn)) * 0.5, dtype)
    v_up = jnp.asarray(rng.standard_normal((h, r, dv)) * 0.5, dtype)
    scale = (dn + dr) ** -0.5
    poisoned = pool.copy()
    poisoned[[p for p in range(len(pool))
              if p not in row[:start // C + 1]]] = np.nan
    f32 = dict(preferred_element_type=jnp.float32)
    lat = gather_pages(jnp.asarray(pool, dtype), jnp.asarray([row]))[0]
    k_n = jnp.einsum("kr,hrn->hkn", lat[:, :r], k_up)
    v = jnp.einsum("kr,hrv->hkv", lat[:, :r], v_up)
    sc = (jnp.einsum("hqn,hkn->hqk", q_n, k_n, **f32)
          + jnp.einsum("hqd,kd->hqk", q_r, lat[:, r:r + dr], **f32)) * scale
    keep = (start + jnp.arange(C))[:, None] >= jnp.arange(lat.shape[0])[None]
    prob = jax.nn.softmax(jnp.where(keep, sc, -1e30), axis=-1)
    want = jnp.einsum("hqk,hkv->hqv", prob.astype(dtype), v, **f32)
    got = paged_latent_chunk_attention(
        q_n, q_r, k_up, v_up, jnp.asarray(poisoned, dtype),
        jnp.asarray(row, jnp.int32), jnp.int32(start), scale, dtype,
        interpret=True)
    assert got.shape == (h, C, dv) and got.dtype == dtype
    got = np.asarray(got, np.float32)
    assert np.isfinite(got).all()
    tol = 2e-5 if dtype == jnp.float32 else 3e-2
    np.testing.assert_allclose(got, np.asarray(want, np.float32), atol=tol,
                               rtol=tol)


def test_latent_pages_are_read_in_place_on_a_tpu_in_whole_tiles_off_a_mesh(
        monkeypatch):
    """The rule that picks the kernel sees the backend, the page's tiles
    and whether the program is traced for a mesh, and nothing else."""
    from tpu_air.ops import decode_attention as da
    from tpu_air.ops.flash_attention import kernel_mesh

    pool = lambda page, w, dt: jax.ShapeDtypeStruct(  # noqa: E731
        (9, page, w), jnp.dtype(dt))
    assert not da.latent_pages_read_in_place(pool(256, 640, "bfloat16"))
    monkeypatch.setattr(da.jax, "default_backend", lambda: "tpu")
    assert da.latent_pages_read_in_place(pool(256, 640, "bfloat16"))
    assert da.latent_pages_read_in_place(pool(8, 128, "float32"))
    assert not da.latent_pages_read_in_place(pool(8, 128, "bfloat16"))
    assert not da.latent_pages_read_in_place(pool(256, 576, "bfloat16"))
    with kernel_mesh(object()):
        assert not da.latent_pages_read_in_place(pool(256, 640, "bfloat16"))
    assert da.latent_pages_read_in_place(pool(256, 640, "bfloat16"))


def _read(layout, q, k, v, mask, h):
    from tpu_air.ops.decode_attention import (
        flat_decode_attention, length_minor_decode_attention,
    )

    if layout == "flat":
        return flat_decode_attention(q, k, v, mask, h, jnp.float32)
    return length_minor_decode_attention(q, _minor(k, h), _minor(v, h), None,
                                         mask, None, None, jnp.float32)


@pytest.mark.parametrize("layout", ["flat", "length_minor"])
def test_fully_masked_row_is_finite_mean_of_v(layout):
    """The masking contract of the module docstring, for both reads: a row
    with no valid key gets a uniform softmax, so its context is the plain
    mean of V over all positions: finite, not zero.  Rows with valid keys are
    untouched by it."""
    from tpu_air.ops.decode_attention import decode_attention_reference

    h = 4
    q, k, v, _, mask = _dk_inputs()
    mask = mask.at[1].set(0.0)
    got = np.asarray(_read(layout, q, k, v, mask, h))
    assert np.isfinite(got).all()
    np.testing.assert_allclose(
        got[1, 0].reshape(-1), np.asarray(v[1]).mean(axis=0),
        atol=2e-5, rtol=2e-5)
    want = np.asarray(decode_attention_reference(
        q, _heads(k, h), _heads(v, h), kv_mask=mask))
    np.testing.assert_allclose(got[[0, 2]], want[[0, 2]], atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("lens", [(32, 32, 32), (1, 13, 32)],
                         ids=["full", "ragged"])
def test_paged_decode_equals_flat_over_gathered_slab(lens):
    """What the paged step computes, at op level: attention over
    ``gather_pages(pool, table)`` under the validity mask equals the
    reference over each row's own positions alone, wherever its pages lie in
    the pool (out of order, unreached entries on the null page)."""
    from tpu_air.ops.decode_attention import (
        decode_attention_reference, flat_decode_attention, gather_pages,
    )

    h, d, C, npg = 4, 16, 8, 4
    rng = np.random.default_rng(5)
    S, P = len(lens), 1 + len(lens) * npg
    kpool = jnp.asarray(rng.standard_normal((P, C, h * d)), jnp.float32)
    vpool = jnp.asarray(rng.standard_normal((P, C, h * d)), jnp.float32)
    table = np.zeros((S, npg), np.int32)
    pages = rng.permutation(np.arange(1, P))
    for s, n in enumerate(lens):
        live = -(-n // C)
        table[s, :live] = pages[s * npg:s * npg + live]
    q = jnp.asarray(rng.standard_normal((S, 1, h, d)), jnp.float32)
    valid = jnp.asarray(np.arange(npg * C)[None] < np.asarray(lens)[:, None])
    got = np.asarray(flat_decode_attention(
        q, gather_pages(kpool, jnp.asarray(table)),
        gather_pages(vpool, jnp.asarray(table)), valid, h, jnp.float32))
    for s, n in enumerate(lens):
        own = lambda pool: jnp.concatenate(  # noqa: E731
            [pool[table[s, p]] for p in range(npg)])[None, :n]
        want = decode_attention_reference(
            q[s:s + 1], _heads(own(kpool), h), _heads(own(vpool), h))
        np.testing.assert_allclose(got[s:s + 1], np.asarray(want),
                                   atol=2e-5, rtol=2e-5)


def _tiny_t5():
    from tpu_air.models.t5.config import T5Config
    from tpu_air.models.t5.modeling import T5ForConditionalGeneration

    cfg = T5Config.tiny()
    enc = jnp.ones((2, 8), jnp.int32)
    params = T5ForConditionalGeneration(cfg).init(
        jax.random.PRNGKey(0), enc, jnp.ones_like(enc),
        jnp.ones((2, 6), jnp.int32))["params"]
    ids = jnp.array([[4, 5, 6, 1, 0, 0], [7, 8, 9, 2, 1, 0]], jnp.int32)
    return cfg, params, ids, (ids != 0).astype(jnp.int32)


@pytest.mark.parametrize("early_stop", [True, False], ids=["while", "scan"])
def test_t5_cached_generate_matches_uncached_greedy(early_stop):
    """End to end, fp32: the tokens ``generate`` emits from its cache are the
    argmax of the uncached full decoder forward, teacher-forced on those same
    tokens, under both loop forms ``generate`` compiles."""
    from tpu_air.models.t5.generate import generate
    from tpu_air.models.t5.modeling import T5ForConditionalGeneration

    cfg, params, ids, mask = _tiny_t5()
    model = T5ForConditionalGeneration(cfg)
    toks = np.asarray(generate(model, params, ids, mask, max_new_tokens=6,
                               early_stop=early_stop))
    dec_in = np.concatenate(
        [np.full((2, 1), cfg.decoder_start_token_id, np.int32), toks[:, :-1]],
        axis=1)
    logits = model.apply({"params": params}, ids, mask, jnp.asarray(dec_in))
    want = np.asarray(jnp.argmax(logits, axis=-1))
    # a finished row emits pad whatever the model says
    done = np.cumsum(toks == cfg.eos_token_id, axis=1) - (toks == cfg.eos_token_id) > 0
    assert not done.all()
    np.testing.assert_array_equal(toks[~done], want[~done])
    assert (toks[done] == cfg.pad_token_id).all()


@pytest.mark.parametrize("int8", [False, True], ids=["full", "int8"])
def test_t5_cached_window_matches_single_steps(int8):
    """The dense fallback that stays: a cached window of several tokens
    (``qlen > 1``, which the flat path does not take) gives the logits of the
    same tokens fed one at a time through the flat path, over the same (for
    int8: quantised) cache, and leaves the same cache and index behind."""
    import dataclasses

    from tpu_air.models.t5.generate import init_cache
    from tpu_air.models.t5.modeling import T5ForConditionalGeneration

    cfg, params, ids, mask = _tiny_t5()
    model = T5ForConditionalGeneration(
        dataclasses.replace(cfg, decode_cache_int8=int8))
    enc = model.apply({"params": params}, ids, mask, method=model.encode)
    rng = np.random.default_rng(3)
    dec = jnp.asarray(rng.integers(2, cfg.vocab_size, (2, 5)), jnp.int32)
    dec = dec.at[:, 0].set(cfg.decoder_start_token_id)

    def run(cache, toks):
        logits, upd = model.apply(
            {"params": params, "cache": cache}, toks, enc, mask, decode=True,
            mutable=["cache"], method=model.decode)
        return np.asarray(logits), upd["cache"]

    cache = init_cache(model, params, 2, 6, enc, mask)
    first, cache = run(cache, dec[:, :1])      # both start from one flat step
    one, cache_one = [], cache
    for t in range(1, 5):
        lg, cache_one = run(cache_one, dec[:, t:t + 1])
        one.append(lg[:, 0])
    window, cache_win = run(cache, dec[:, 1:5])
    span = float(first.max() - first.min())
    np.testing.assert_allclose(window, np.stack(one, axis=1),
                               atol=2e-4 * span, rtol=0)
    # the caches agree to rounding (an int8 entry by at most one step): a
    # layer's K/V come from the layer below's attention output
    for a, b in zip(jax.tree_util.tree_leaves(cache_win),
                    jax.tree_util.tree_leaves(cache_one)):
        np.testing.assert_allclose(
            np.asarray(a, np.float32), np.asarray(b, np.float32),
            atol=1 if a.dtype == jnp.int8 else 1e-5, rtol=0)


# -- the fused training attention (PR 39): bias, key mask, dropout, dbias ------

FUSED_SHAPES = {          # (Lq, Lk, causal, bias): the fine-tune cell's three
    "self_256x256": (256, 256, False, True),       # attention kinds, smaller
    "cross_128x256": (128, 256, False, False),
    "causal_128x128": (128, 128, True, True),
}


def _fused_case(shape, rate, seed=0):
    """(q, k, v, bias, kv_mask, keep) at B·H = 2·3, head width 64: the bias
    per head and batch-shared, as T5's, the key mask with padded rows."""
    lq, lk, causal, with_bias = FUSED_SHAPES[shape]
    b, h, d = 2, 3, 64
    rng = np.random.default_rng(seed)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    q, k, v = mk(b * h, lq, d), mk(b * h, lk, d), mk(b * h, lk, d)
    bias = mk(h, lq, lk) if with_bias else None
    kv_mask = jnp.ones((b, lk), jnp.int32).at[1, lk - 40:].set(0)
    keep = None
    if rate:
        keep = jnp.asarray(rng.random((b * h, lq, lk)) < 1.0 - rate, jnp.int8)
    return q, k, v, bias, kv_mask, keep, causal


@pytest.mark.parametrize("layout", ["head_major", "token_major"])
@pytest.mark.parametrize("tiles", ["one_tile", "tiled"])
@pytest.mark.parametrize("rate", [0.1, 0.0])
@pytest.mark.parametrize("shape", sorted(FUSED_SHAPES))
def test_fused_attention_matches_dense_with_the_mask_supplied(
        shape, rate, tiles, layout):
    """``out``, ``dq``, ``dk``, ``dv`` and ``dbias`` of the kernels against the
    dense reference under the same keep mask: one tile a (q, k) pair (the
    one-kernel backward, the cell's case) and 64-wide tiles (the two-pass
    backward, whose dq pass carries the dbias rows); operands head-major, and
    token-major ``[b, L, h·d]`` as T5's projections hand them over (the
    kernels read a head's lanes in place)."""
    q, k, v, bias, kv_mask, keep, causal = _fused_case(shape, rate)
    block = None if tiles == "one_tile" else 64
    addmask = (1.0 - kv_mask.astype(jnp.float32)) * -1e30
    w = jnp.asarray(np.random.default_rng(9).normal(size=q.shape), jnp.float32)
    b, h = 2, 3

    def as_given(x):          # (b·h, L, d) -> (b, L, h·d) where token-major
        if layout == "head_major":
            return x
        x = x.reshape(b, h, *x.shape[1:]).transpose(0, 2, 1, 3)
        return x.reshape(b, x.shape[1], -1)

    def f_flash(q, k, v, bias):
        out = flash_attention(
            as_given(q), as_given(k), as_given(v), bias, kv_mask=kv_mask,
            scale=1.0, causal=causal, block_q=block, block_k=block,
            dropout_rate=rate, dropout_keep=keep,
            num_heads=None if layout == "head_major" else h)
        if layout == "token_major":
            out = out.reshape(b, -1, h, q.shape[-1]).transpose(0, 2, 1, 3)
        return (w * out.reshape(q.shape)).sum()

    def f_ref(q, k, v, bias):
        return (w * _reference_pair(q, k, v, bias, addmask, 1.0, causal,
                                    keep=keep, rate=rate)[0]).sum()

    argnums = (0, 1, 2, 3) if bias is not None else (0, 1, 2)
    got = jax.value_and_grad(f_flash, argnums)(q, k, v, bias)
    want = jax.value_and_grad(f_ref, argnums)(q, k, v, bias)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-4)
    for name, a, b in zip(("dq", "dk", "dv", "dbias"), got[1], want[1]):
        assert a.shape == b.shape and a.dtype == b.dtype, name
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-4,
                                   rtol=1e-3, err_msg=name)


@pytest.mark.parametrize("case", ["same_seed", "other_seed", "other_shard"])
def test_fused_attention_meets_its_forward_mask_in_the_backward(case):
    """A seed is all the backward has of the forward's mask.  In interpret
    mode (no generator) ``keep_from_seed`` stands in for the kernels' draw:
    the same seed gives the same ``out`` and the gradients of the dense
    reference under that very mask; another seed, or another shard's offset
    of the same seed (``flash_attention_on_mesh`` adds a shard's place to the
    second word), gives another mask."""
    from tpu_air.ops.flash_attention import keep_from_seed

    rate = 0.1
    q, k, v, bias, kv_mask, _, causal = _fused_case("self_256x256", 0.0)
    addmask = (1.0 - kv_mask.astype(jnp.float32)) * -1e30
    seed = jnp.asarray([1234, 77], jnp.int32)
    other = {"same_seed": seed, "other_seed": seed.at[0].add(1),
             "other_shard": seed.at[1].add(1)}[case]
    shape = (q.shape[0], q.shape[1], k.shape[1])
    keep = keep_from_seed(seed, shape, rate)
    same = bool(jnp.all(keep == keep_from_seed(other, shape, rate)))
    assert same == (case == "same_seed")

    def f_flash(q, k, v, bias, seed):
        return flash_attention(q, k, v, bias, kv_mask=kv_mask, scale=1.0,
                               dropout_rate=rate, dropout_seed=seed).sum()

    def f_ref(q, k, v, bias):
        return _reference_pair(q, k, v, bias, addmask, 1.0, causal,
                               keep=keep, rate=rate)[0].sum()

    got = jax.value_and_grad(f_flash, (0, 1, 2, 3))(q, k, v, bias, other)
    want = jax.value_and_grad(f_ref, (0, 1, 2, 3))(q, k, v, bias)
    close = all(
        np.allclose(np.asarray(a), np.asarray(b), atol=2e-4, rtol=1e-3)
        for a, b in zip((got[0], *got[1]), (want[0], *want[1])))
    assert close == (case == "same_seed")


@pytest.mark.parametrize("rate", [0.1, 0.5])
def test_fused_attention_keeps_its_share(rate):
    """Kept share within 3 sigma of ``1 - rate`` (the 16-bit threshold's own
    rounding is under a thousandth of a sigma at this size)."""
    from tpu_air.ops.flash_attention import keep_from_seed, keep_threshold

    n = 6 * 256 * 256
    keep = keep_from_seed(jnp.asarray([5, 6], jnp.int32), (6, 256, 256), rate)
    sigma = (rate * (1 - rate) / n) ** 0.5
    assert abs(float(keep.mean()) - (1 - rate)) < 3 * sigma
    assert abs(keep_threshold(rate) / 2**16 - (1 - rate)) < 2**-16


@pytest.mark.parametrize("layout", ["head_major", "token_major",
                                    "token_major_bias_copied_by_the_caller"])
def test_fused_attention_on_a_mesh_matches_one_device(layout):
    """``flash_attention_on_mesh`` over ``data=2 x model=2``: every shard runs
    the kernels on its own rows and heads — (B, H, L, D) operands, and the
    projections' (B, L, H·D) split along their last axis; the result and every
    gradient, the batch-shared bias's summed over the batch shards, are the
    one-device call's — also where the caller made the bias's copy a batch
    shard itself (``bias_per_batch_shard``: T5 does, once a stack)."""
    from jax.sharding import Mesh

    from tpu_air.ops.flash_attention import (
        bias_per_batch_shard, flash_attention_on_mesh, kernel_mesh)

    b, h, lq, lk, d = 4, 4, 128, 128, 64
    rng = np.random.default_rng(3)
    mk = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)  # noqa: E731
    if layout == "head_major":
        q, k, v = mk(b, h, lq, d), mk(b, h, lk, d), mk(b, h, lk, d)
        heads = {}
    else:
        q, k, v = mk(b, lq, h * d), mk(b, lk, h * d), mk(b, lk, h * d)
        heads = {"num_heads": h}
    bias = mk(1, h, lq, lk)
    kv_mask = jnp.ones((b, lk), jnp.int32).at[3, 100:].set(0)
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("data", "model"))

    def loss(q, k, v, bias, on_mesh):
        fn = flash_attention_on_mesh if on_mesh else flash_attention
        if on_mesh and layout.endswith("by_the_caller"):
            bias = bias_per_batch_shard(bias)
            assert bias.shape[0] == 2
        return (fn(q, k, v, bias, kv_mask=kv_mask, scale=1.0, **heads)
                ** 2).sum()

    want = jax.value_and_grad(loss, (0, 1, 2, 3))(q, k, v, bias, False)
    with kernel_mesh(mesh):
        got = jax.jit(jax.value_and_grad(loss, (0, 1, 2, 3)),
                      static_argnums=4)(q, k, v, bias, True)
    np.testing.assert_allclose(got[0], want[0], rtol=1e-5)
    for a, b_ in zip(got[1], want[1]):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b_), atol=1e-4,
                                   rtol=1e-4)
