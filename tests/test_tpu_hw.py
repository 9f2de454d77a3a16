"""TPU-hardware-gated tests: the Pallas kernels must be proven COMPILED AND
RUN on the chip, not just in interpret mode on the CPU (tests/test_ops.py) or
compiled for a described chip (tests/test_chip_compile.py).

The suite proper runs on XLA:CPU; a chip belongs to one process at a time, so
each test here starts a process of its own that takes the chip, and the
pytest process never starts a backend on it.  Whether a chip is attached is
read from the device nodes (``chips.local_chip_count``), without JAX.  They
are marked ``tpu`` and excluded from the default run — on the chip, through
the tool::

    python -m pytest tests/ -m tpu -q

Skips visibly on a host without a TPU.
"""

import os
import re
import subprocess
import sys

import pytest

from tpu_air.core.chips import local_chip_count

pytestmark = [
    pytest.mark.tpu,
    pytest.mark.skipif(local_chip_count() == 0,
                       reason="no TPU chip attached to this host"),
]


def _tpu_env() -> dict:
    """The suite's environment minus what pins it to the virtual CPU mesh."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("TPU_AIR_NUM_CHIPS", None)
    env["XLA_FLAGS"] = re.sub(
        r"--xla_force_host_platform_device_count=\d+"
        r"|--xla_backend_optimization_level=\d+", "",
        env.get("XLA_FLAGS", ""),
    ).strip()
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env["PYTHONPATH"] = repo + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else ""
    )
    return env


def _run_on_tpu(script: str, timeout: float = 900.0):
    proc = subprocess.run(
        [sys.executable, "-c", script], env=_tpu_env(),
        capture_output=True, text=True, timeout=timeout,
    )
    assert proc.returncode == 0, f"stdout={proc.stdout}\nstderr={proc.stderr[-3000:]}"
    return proc.stdout


_FLASH_SCRIPT = """
import jax, jax.numpy as jnp
assert jax.devices()[0].platform == "tpu", jax.devices()
from tpu_air.ops.flash_attention import flash_attention, _reference_attention

B, H, L, D = 4, 12, 512, 64  # W1 attention shapes (flan-t5-base, seq 512)
key = jax.random.PRNGKey(0)
kq, kk, kv, kb, km = jax.random.split(key, 5)
q = jax.random.normal(kq, (B * H, L, D), jnp.bfloat16)
k = jax.random.normal(kk, (B * H, L, D), jnp.bfloat16)
v = jax.random.normal(kv, (B * H, L, D), jnp.bfloat16)
bias = jax.random.normal(kb, (H, L, L), jnp.float32)  # T5 per-head, batch-shared
kv_mask = (jax.random.uniform(km, (B, L)) > 0.2).astype(jnp.int32)
# repeat to (B*H, ...) grouping: kernel maps mask batch b -> grid b // (BH//B)

for name, kwargs in [
    ("bias+mask", dict(bias=bias, kv_mask=kv_mask, scale=1.0)),
    ("plain", dict()),
    ("causal", dict(causal=True)),
]:
    out = jax.jit(
        lambda q, k, v: flash_attention(q, k, v, interpret=False, **kwargs)
    )(q, k, v)
    ref = _reference_attention(
        q, k, v, kwargs.get("bias"), kwargs.get("scale", 1.0 / D ** 0.5),
        kwargs.get("causal", False), kv_mask=(
            (1.0 - kwargs["kv_mask"].astype(jnp.float32)) * -1e30
            if "kv_mask" in kwargs else None
        ),
    )
    err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
    print(f"{name}: max_err={err:.5f}")
    assert err < 0.06, f"{name}: compiled flash diverges from reference ({err})"
print("FLASH_TPU_OK")
"""


def test_flash_attention_compiled_on_chip():
    """Flash forward COMPILED on TPU (not interpret) matches the dense
    reference at W1 shapes, for the T5 bias+mask, plain, and causal paths."""
    out = _run_on_tpu(_FLASH_SCRIPT)
    assert "FLASH_TPU_OK" in out


_FLASH_BWD_SCRIPT = """
import jax, jax.numpy as jnp
assert jax.devices()[0].platform == "tpu", jax.devices()
from tpu_air.ops.flash_attention import flash_attention, _reference_attention

BH, L, D = 8, 2048, 64
key = jax.random.PRNGKey(2)
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (BH, L, D), jnp.float32)
k = jax.random.normal(kk, (BH, L, D), jnp.float32)
v = jax.random.normal(kv, (BH, L, D), jnp.float32)

def f_flash(q, k, v):
    return flash_attention(q, k, v, causal=True, interpret=False).sum()

def f_ref(q, k, v):
    return _reference_attention(q, k, v, None, 1.0 / D ** 0.5, True).sum()

gf = jax.jit(jax.grad(f_flash, argnums=(0, 1, 2)))(q, k, v)
gr = jax.jit(jax.grad(f_ref, argnums=(0, 1, 2)))(q, k, v)
for name, a, b in zip("qkv", gf, gr):
    err = float(jnp.max(jnp.abs(a - b)))
    rel = err / (float(jnp.max(jnp.abs(b))) + 1e-9)
    print(f"d{name}: max_abs_err={err:.5f} rel={rel:.5f}")
    assert rel < 2e-2, (name, err, rel)
print("FLASH_BWD_TPU_OK")
"""


def test_flash_backward_compiled_on_chip():
    """The blockwise Pallas BACKWARD (dq + dk/dv kernels) compiled on TPU
    matches autodiff of the dense reference at long sequence."""
    out = _run_on_tpu(_FLASH_BWD_SCRIPT)
    assert "FLASH_BWD_TPU_OK" in out


_FUSED_TRAIN_SCRIPT = """
import jax, jax.numpy as jnp
assert jax.devices()[0].platform == "tpu", jax.devices()
from tpu_air.ops.flash_attention import (
    flash_attention, keep_threshold, _reference_pair)

# the fine-tune step's encoder self-attention, fewer rows: token-major
# operands, T5's bias, a key mask with padding, the mask drawn in the kernel
B, H, L, D, RATE = 2, 12, 512, 64, 0.1
f32 = jnp.float32


def fused(q, k, v, bias, kv_mask, seed):
    return flash_attention(
        q, k, v, bias, kv_mask=kv_mask, scale=1.0, interpret=False,
        dropout_rate=RATE, dropout_seed=seed, num_heads=H)


def mask_of(seed):
    # The keep mask the kernels draw for ``seed``, read off the chip: with
    # zero q and k every probability is 1 / L, and a v of unit rows hands 64
    # columns of the dropped probabilities out a call.
    z = jnp.zeros((B, L, H * D), jnp.bfloat16)
    cols = []
    for c in range(L // D):
        unit = jnp.zeros((L, D), f32).at[c * D + jnp.arange(D),
                                        jnp.arange(D)].set(1.0)
        v = jnp.tile(unit[None, :, None, :], (B, 1, H, 1))
        out = fused(z, z, v.reshape(B, L, H * D).astype(jnp.bfloat16), None,
                    None, seed)
        cols.append(out.reshape(B, L, H, D))
    pd = jnp.concatenate(cols, axis=-1)                  # [B, Lq, H, Lk]
    return (pd > 0).transpose(0, 2, 1, 3).reshape(B * H, L, L)


def head_major(x):                       # [B, L, H*D] -> (B*H, L, D)
    return x.reshape(B, L, H, D).transpose(0, 2, 1, 3).reshape(B * H, L, D)


seed = jnp.asarray([1234567, 89], jnp.int32)
keep = mask_of(seed)
assert bool(jnp.all(keep == mask_of(seed))), "one seed, two masks"
n = keep.size
share = float(keep.mean())
want = keep_threshold(RATE) / 2**16
z = (share - want) / (want * (1 - want) / n) ** 0.5
print(f"kept share {share:.6f} (law {want:.6f}), z = {z:.2f}")
assert abs(z) < 4, z
for name, other in (("other seed", seed.at[0].add(1)),
                    ("other shard", seed.at[1].add(1))):
    differ = float((keep != mask_of(other)).mean())
    print(f"{name}: {differ:.4f} of the mask differs")
    assert differ > 0.1, (name, differ)

ks = jax.random.split(jax.random.PRNGKey(3), 6)
q, k, v = (jax.random.normal(kk, (B, L, H * D), jnp.bfloat16) for kk in ks[:3])
bias = jax.random.normal(ks[3], (H, L, L), f32)
w = jax.random.normal(ks[4], (B, L, H * D), f32)
kv_mask = jnp.ones((B, L), jnp.int32).at[1, 400:].set(0)
addmask = (1.0 - kv_mask.astype(f32)) * -1e30


def ref_out(q, k, v, bias, keep):
    return _reference_pair(head_major(q), head_major(k), head_major(v), bias,
                           addmask, 1.0, False, keep=keep, rate=RATE)[0]


def fused_out(q, k, v, bias):
    return head_major(fused(q, k, v, bias, kv_mask, seed))


def grads(out_fn, *more):
    loss = lambda q, k, v, bias: (                                # noqa: E731
        head_major(w) * out_fn(q, k, v, bias, *more).astype(f32)).sum()
    return jax.jit(jax.grad(loss, (0, 1, 2, 3)))(q, k, v, bias)


other = mask_of(seed.at[0].add(1))
got = (fused_out(q, k, v, bias), *grads(fused_out))
want = (ref_out(q, k, v, bias, keep), *grads(ref_out, keep))
wrong = (ref_out(q, k, v, bias, other), *grads(ref_out, other))
for name, a, b, c in zip(("out", "dq", "dk", "dv", "dbias"), got, want, wrong):
    scale = float(jnp.max(jnp.abs(b.astype(f32)))) + 1e-9
    rel = float(jnp.max(jnp.abs(a.astype(f32) - b.astype(f32)))) / scale
    off = float(jnp.max(jnp.abs(a.astype(f32) - c.astype(f32)))) / scale
    print(f"{name}: rel_err={rel:.5f} (under another mask {off:.5f})")
    # the backward drew the forward's mask: under any other the gradients
    # are off by as much as they are large
    assert rel < 3e-2 and off > 5 * rel, (name, rel, off)
print("FUSED_TRAIN_TPU_OK")
"""


def test_fused_training_attention_on_chip():
    """The fused training attention (PR 39) COMPILED on the chip with the
    mask drawn by the chip's generator: the mask is read off the forward, is a
    function of the seed alone (the same twice; another for another seed or
    another shard's offset), keeps its share, and forward and backward —
    ``dq``, ``dk``, ``dv``, ``dbias`` — are the dense reference's under that
    very mask, so the backward met the forward's mask with none of it
    stored."""
    out = _run_on_tpu(_FUSED_TRAIN_SCRIPT)
    assert "FUSED_TRAIN_TPU_OK" in out


_RING_SCRIPT = """
import jax, jax.numpy as jnp
assert jax.devices()[0].platform == "tpu", jax.devices()
from jax.sharding import Mesh
from tpu_air.ops.ring_attention import ring_attention_sharded
from tpu_air.ops.flash_attention import _reference_attention

# single-chip mesh: the ring degenerates to one hop but the COMPILED
# shard_map + pallas path executes on hardware
mesh = Mesh(jax.devices()[:1], ("sequence",))
BH, L, D = 8, 1024, 64
key = jax.random.PRNGKey(1)
kq, kk, kv = jax.random.split(key, 3)
q = jax.random.normal(kq, (BH, L, D), jnp.bfloat16)
k = jax.random.normal(kk, (BH, L, D), jnp.bfloat16)
v = jax.random.normal(kv, (BH, L, D), jnp.bfloat16)
out = ring_attention_sharded(q, k, v, mesh, causal=True)
ref = _reference_attention(q, k, v, None, 1.0 / D ** 0.5, True)
err = float(jnp.max(jnp.abs(out.astype(jnp.float32) - ref.astype(jnp.float32))))
print(f"ring: max_err={err:.5f}")
assert err < 0.06, err
print("RING_TPU_OK")
"""


def test_ring_attention_step_on_chip():
    """One compiled ring-attention step executes on the chip."""
    out = _run_on_tpu(_RING_SCRIPT)
    assert "RING_TPU_OK" in out


# The rehearsal off the chip (``python -c`` of this text with JAX_PLATFORMS=cpu)
# patches the rule to yes and interprets the kernel at a smaller pool.
_SSD_ROWS_SCRIPT = r"""
import numpy as np
import jax, jax.numpy as jnp
from tpu_air.ops import ssm
ON_CHIP = jax.devices()[0].platform == "tpu"
if not ON_CHIP:                       # the rehearsal: the kernel interpreted
    ssm.state_rows_move_in_place = lambda state: True
from tpu_air.engine import EngineConfig, InferenceEngine
from tpu_air.models.lm import hf_import, paged_cache
from tpu_air.models.lm.modeling import CausalLM

# 1. the kernel alone, at the cell's head geometry: rows that are not live
# come back bit for bit (with none live the whole pool) and their y is
# zeros; live rows are the recurrence's, in float64 on the host
S, H, P, G, N = (16, 128, 64, 8, 128) if ON_CHIP else (6, 16, 8, 2, 128)
ks = jax.random.split(jax.random.PRNGKey(48), 7)
u = jax.random.normal(ks[0], (S, H, P), jnp.float32)
dt = jax.random.uniform(ks[1], (S, H), jnp.float32, 1e-3, 0.5)
A = -jax.random.uniform(ks[2], (H,), jnp.float32, 1.0, 16.0)
B, C = (jax.random.normal(k, (S, G, N), jnp.float32) for k in ks[3:5])
D = jax.random.normal(ks[5], (H,), jnp.float32)
state = jax.random.normal(ks[6], (S, H, P, N), jnp.float32)
assert ssm.state_rows_move_in_place(state)
u8, dt8, A8, B8, C8, D8, s8 = (np.asarray(a, np.float64)
                               for a in (u, dt, A, B, C, D, state))
Bh, Ch = np.repeat(B8, H // G, 1), np.repeat(C8, H // G, 1)
want = (np.exp(dt8 * A8)[..., None, None] * s8
        + (dt8[..., None] * u8)[..., None] * Bh[:, :, None, :])
want_y = (want * Ch[:, :, None, :]).sum(-1) + D8[:, None] * u8
step = jax.jit(ssm.ssd_state_update)
for name, live in (("none", np.zeros(S, bool)), ("one", np.arange(S) == S - 1),
                   ("some", np.arange(S) % 3 != 1), ("all", np.ones(S, bool))):
    y, new = (np.asarray(a) for a in step(u, dt, A, B, C, D, state,
                                          jnp.asarray(live)))
    np.testing.assert_array_equal(new[~live], np.asarray(state)[~live])
    np.testing.assert_array_equal(y[~live], 0.0)
    # the chip's float32 exp against the host's float64 one
    np.testing.assert_allclose(new[live], want[live], rtol=2e-5, atol=1e-5)
    np.testing.assert_allclose(y[live], want_y[live], rtol=1e-4, atol=1e-4)
    print("kernel", name, "ok", flush=True)

# 2. through the engine: a model whose Mamba-2 state is whole tiles, a slot
# whose tenant has left (its state stays behind) beside one that decodes; the
# pool read back before and after a step
cfg = hf_import.lm_config_from_hf({
    "model_type": "nemotron_h", "hidden_size": 128, "num_hidden_layers": 4,
    "hybrid_override_pattern": "M*ME", "num_attention_heads": 4,
    "num_key_value_heads": 2, "head_dim": 32, "intermediate_size": 128,
    "moe_intermediate_size": 128, "moe_latent_size": 128,
    "moe_shared_expert_intermediate_size": 128, "n_routed_experts": 4,
    "n_shared_experts": 1, "num_experts_per_tok": 2, "n_group": 1,
    "topk_group": 1, "norm_topk_prob": True, "routed_scaling_factor": 2.5,
    "mamba_num_heads": 16, "mamba_head_dim": 8, "n_groups": 2,
    "ssm_state_size": 128, "conv_kernel": 4, "chunk_size": 16,
    "layer_norm_epsilon": 1e-05, "vocab_size": 512,
    "max_position_embeddings": 512, "tie_word_embeddings": False,
    "mlp_hidden_act": "relu2", "mamba_hidden_act": "silu",
    "use_conv_bias": True, "use_bias": False, "mlp_bias": False,
    "attention_bias": False, "mamba_proj_bias": False}, max_seq_len=256)
model = CausalLM(cfg)
params = model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32))["params"]
eng = InferenceEngine(model, params, EngineConfig(
    num_slots=4, slot_len=256, page_len=16, max_new_tokens=24,
    eos_token_id=None), auto_start=False)
assert paged_cache.state_rows_move_in_place(eng.cache)


def states():
    return [np.asarray(layer["ssm_state"]) for _, layer in
            paged_cache.layers(eng.cache) if "ssm_state" in layer]


rng = np.random.default_rng(48)
short = eng.submit(rng.integers(2, 512, 20).tolist(), 3)
long = eng.submit(rng.integers(2, 512, 37).tolist(), 24)
while not short.done:
    eng.step()
held_and_advanced = 0
for _ in range(6):
    riding_before, before = set(eng._riding), states()
    eng.step()
    moved_by = riding_before | set(eng._riding)
    after = states()
    for a, b in zip(before, after):
        changed = {i for i in range(4) if not np.array_equal(a[i], b[i])}
        assert changed <= moved_by, (changed, moved_by)
        stale = [i for i in range(4) if i not in moved_by and a[i].any()]
        held_and_advanced += bool(stale) and bool(changed)
assert held_and_advanced, "no step held a slot's stale state beside a live row"
while not eng.idle():
    eng.step()
snap = eng.metrics.snapshot()
assert snap["ssd_state_rows_passed"] == snap["ssd_rows_live"] > 0, snap
eng.close()
print("engine ok: steps that held a stale row beside a live one:",
      held_and_advanced, "rows passed", snap["ssd_state_rows_passed"])
"""


def test_ssd_rows_update_holds_the_rows_it_does_not_advance_on_chip():
    """PR 48: ``ops/ssm.ssd_rows_update`` compiled and run: a pool read back
    before and after the pass (the kernel alone at the cell's head geometry,
    then an engine's step with a slot's stale state beside a decoding row):
    a row that is not live is bit for bit what it was."""
    out = _run_on_tpu(_SSD_ROWS_SCRIPT)
    assert "engine ok" in out and out.count("kernel") == 4, out


_HELD_ROWS_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
assert jax.devices()[0].platform == "tpu", jax.devices()
from tpu_air.ops import moe

# the three sparse cells' mixed-step shapes: tokens, top-k, experts routed
# over, experts held, width
for name, (t, k, routed, e, d) in {
        "gigachat": (384, 8, 256, 16, 7168), "nemotron": (384, 22, 512, 128, 1024),
        "olmoe": (192, 8, 64, 64, 2048), "none_held": (128, 8, 256, 0, 1024)}.items():
    rng = np.random.default_rng(49)
    chosen = np.stack([rng.permutation(routed)[:k] for _ in range(t)])
    if e == 0:
        e, chosen = 16, np.full((t, k), 16)
    flat = np.minimum(chosen, e).reshape(-1)
    order = np.argsort(flat, kind="stable")
    n = int((flat < e).sum())
    y = rng.standard_normal((t * k, d)).astype(np.float32)
    y[n:] = np.nan                       # what the product never wrote
    w = rng.uniform(0.1, 1, (t, k)).astype(np.float32)
    want = np.zeros((t, d))
    np.add.at(want, order[:n] // k,
              (w.reshape(-1)[order[:n], None] * y[:n]).astype(np.float64))
    args = (jnp.asarray(y), jnp.asarray(flat, jnp.int32),
            jnp.asarray(order, jnp.int32), jnp.asarray(w))
    assert moe.combine_tile(t, t * k, d)
    for form in (moe.held_rows_sum, moe.gathered_sum):
        got = np.asarray(jax.jit(lambda *a: form(*a, e))(*args))
        assert np.isfinite(got).all(), (name, form.__name__)
        np.testing.assert_allclose(got, want, rtol=2e-6, atol=2e-6)
        if n == 0:
            assert not got.any()
    print("held rows", name, n, "ok", flush=True)
"""


def test_held_rows_sum_is_the_float32_sum_on_chip():
    """PR 49: ``ops/moe.held_rows_sum`` compiled and run at the sparse cells'
    mixed-step shapes over products whose unwritten rows are NaN: finite,
    and the float64 sum of the float32 products to float32 rounding (nothing
    went through the MXU at bfloat16's 8 bits), as the gathered form is."""
    out = _run_on_tpu(_HELD_ROWS_SCRIPT)
    assert out.count("held rows") == 4, out


_PREFIX_SCRIPT = """
import jax, jax.numpy as jnp, numpy as np
assert jax.devices()[0].platform == "tpu", jax.devices()
from tpu_air.ops import decode_attention as da

bf = jnp.bfloat16
# the two batch-inference cells' slabs, two layers of them
for name, (L, b, h, d) in {"base": (129, 256, 12, 64),
                           "large": (129, 128, 16, 64)}.items():
    ks = jax.random.split(jax.random.PRNGKey(59), 7)
    shape = (2, L, b, h * d)
    keys, vals = (jax.random.normal(k, shape, jnp.float32).astype(bf)
                  for k in ks[:2])
    q = jax.random.normal(ks[2], (b, 1, h, d), jnp.float32).astype(bf)
    kr, vr = (jax.random.normal(k, (1, b, h * d), jnp.float32).astype(bf)
              for k in ks[3:5])
    assert da.prefix_slabs_read_in_place(keys, h)
    for cur in (0, 5, 64, 127, 128):
        bias = (jax.random.normal(ks[5], (h, L), jnp.float32)
                + jnp.where(jnp.arange(L) <= cur, 0.0, -1e9)[None])
        mask = (jax.random.uniform(ks[6], (b, L)) > 0.3).astype(
            jnp.float32).at[:, cur].set(1.0)
        live = -(-cur // da._PREFIX_BLOCK) * da._PREFIX_BLOCK
        # NaN wherever no block with a written position reaches
        nan = lambda x: x.at[0].set(jnp.nan).at[1, live:].set(jnp.nan)
        for m in (None, mask):
            got = jax.jit(lambda *a: da.prefix_append_decode_attention(
                *a, h, bf), static_argnums=(3,))(
                q, nan(keys), nan(vals), 1, kr, vr, jnp.int32(cur), bias, m)
            want = jax.jit(lambda *a: da.flat_append_decode_attention(
                *a, None, None, h, bf))(
                q, keys[1], vals[1], kr, vr, jnp.int32(cur), bias, m)
            got, want = (np.asarray(x, np.float32) for x in (got, want))
            assert np.isfinite(got).all(), (name, cur)
            np.testing.assert_allclose(got, want, atol=3e-2, rtol=3e-2)
    print("prefix read", name, "ok", flush=True)
"""


def test_prefix_append_decode_attention_reads_the_written_prefix_on_chip():
    """PR 59: ``ops/decode_attention.prefix_append_decode_attention``
    compiled and run at the two batch-inference cells' shapes, at no position
    written, inside a block, mid-slab, and at the last two positions (the
    last block starts early), with and without a key mask, over slabs that
    are NaN in the other layer and in every block with no written position:
    finite, and the flat read's result over the same keys to bf16 rounding."""
    out = _run_on_tpu(_PREFIX_SCRIPT)
    assert out.count("prefix read") == 2, out
