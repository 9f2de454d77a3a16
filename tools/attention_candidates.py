#!/usr/bin/env python3
"""Candidates for the fine-tune step's attention between the projections,
forward and backward with live dropout, timed bare on the chip at the three
shapes of the fine-tune cells (PERF.md, PR 39): FLAN-T5-base heads (12 of
64), 32 rows, bf16, rate 0.1, a key-padding mask, and where T5 has one the
batch-shared position bias — encoder self 512 x 512, decoder self 128 x 128
causal, cross 128 x 512 (no bias).

    python tools/attention_candidates.py            # on a TPU: ms a layer
    python tools/attention_candidates.py --aot      # here: compile for a described v5e

``dense`` is ``models/t5/modeling.Attention``'s einsum path (scores, bias,
mask, f32 softmax, 16-bit ``rbg`` dropout, context): what the step ran at all
three shapes before PR 39 and still runs at the two small ones.  The others
are ``ops.flash_attention``, one switch each: ``fused`` what the step runs at
512 x 512 (operands token-major, as the projections write them);
``fused_head_major`` the same kernels behind ``[b, h, L, d]`` transposes, with
one head a grid step or as many as fit; the backward in two passes.  A
candidate takes and returns ``[b, L, h, d]`` and is differentiated in q, k, v
and the bias.  ``ms`` is the host's clock around a call (under a millisecond it
is mostly the launch); ``device_ms`` the device time of the call's operations
from a profiler capture, longest first: the kernels are ``*flash_fwd*`` and
``*flash_bwd*``, the rest of a ``fused`` row are copies between layouts, which
the step's own projections do not make (``tests/test_chip_compile.py``).  A
word of random bits split over two elements was tried too: no faster at
512 x 512, refused by the compiler at 128 (PERF.md, PR 39).
"""

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

B, H, D, RATE = 32, 12, 64, 0.1
SHAPES = {                       # lq, lk, causal, bias
    "enc_self_512x512": (512, 512, False, True),
    "dec_self_128x128": (128, 128, True, True),
    "cross_128x512": (128, 512, False, False),
}


def dense(q, k, v, bias, kv_mask, key, causal):
    from tpu_air.models.t5.modeling import NEG_INF, _dropout

    lq, lk = q.shape[1], k.shape[1]
    mask = (1.0 - kv_mask[:, None, None, :].astype(jnp.float32)) * NEG_INF
    if causal:
        c = jnp.tril(jnp.ones((lq, lk), jnp.float32))
        mask = mask + ((1.0 - c) * NEG_INF)[None, None]
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    if bias is not None:
        scores = scores + bias.astype(q.dtype)
    scores = scores + mask.astype(q.dtype)
    probs = jax.nn.softmax(scores.astype(jnp.float32), axis=-1).astype(q.dtype)
    probs = _dropout(probs, RATE, key, transposed=lq == lk)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v)


def fused(q, k, v, bias, kv_mask, key, causal):
    """The kernels on the projections' own layout: a head's lanes read where
    they lie, no transposed copy."""
    from tpu_air.ops.flash_attention import flash_attention

    seed = jax.random.key_data(key).reshape(-1)[:2]
    flat = lambda x: x.reshape(*x.shape[:2], H * D)  # noqa: E731
    return flash_attention(
        flat(q), flat(k), flat(v), bias, kv_mask=kv_mask, causal=causal,
        scale=1.0, dropout_rate=RATE, dropout_seed=seed, interpret=False,
        num_heads=H).reshape(q.shape)


def fused_head_major(q, k, v, bias, kv_mask, key, causal):
    """The kernels on ``[b, h, L, d]`` operands: three transposes in, one out
    (and four more in the backward), each a copy in HBM with the 64-wide rows
    padded out to 128 lanes."""
    from tpu_air.ops.flash_attention import flash_attention

    seed = jax.random.key_data(key).reshape(-1)[:2]
    return flash_attention(
        q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3),
        v.transpose(0, 2, 1, 3), bias, kv_mask=kv_mask, causal=causal,
        scale=1.0, dropout_rate=RATE, dropout_seed=seed, interpret=False,
    ).transpose(0, 2, 1, 3)


# name -> (function, module globals of ``ops.flash_attention`` set while the
# candidate is traced, forward and backward)
CANDIDATES = {
    "dense": (dense, {}),
    "fused": (fused, {}),
    "fused_head_major": (fused_head_major, {}),
    "fused_head_major_one_head_a_step": (fused_head_major, {"_STEP_ELEMS": 0}),
    "fused_two_pass_backward": (fused, {"_ONE_PASS_ELEMS": 0}),
}


def step(fn, causal, with_bias):
    def loss(q, k, v, bias, kv_mask, key, w):
        out = fn(q, k, v, bias if with_bias else None, kv_mask, key, causal)
        return (out.astype(jnp.float32) * w).sum()

    return jax.jit(jax.grad(loss, argnums=(0, 1, 2, 3) if with_bias else (0, 1, 2)))


def operands(shape, struct=None):
    lq, lk, _, _ = SHAPES[shape]
    rng = np.random.default_rng(0)
    shapes = [((B, lq, H, D), jnp.bfloat16), ((B, lk, H, D), jnp.bfloat16),
              ((B, lk, H, D), jnp.bfloat16), ((1, H, lq, lk), jnp.float32)]
    if struct is not None:
        key = jax.eval_shape(lambda: jax.random.key(1, impl="rbg"))
        return ([struct(s, d) for s, d in shapes]
                + [struct((B, lk), jnp.int32), struct(key.shape, key.dtype),
                   struct((B, lq, H, D), jnp.float32)])
    arrays = [jnp.asarray(rng.normal(size=s), d) for s, d in shapes]
    lens = rng.integers(lk // 2, lk + 1, size=B)
    kv_mask = jnp.asarray(np.arange(lk)[None] < lens[:, None], jnp.int32)
    w = jnp.asarray(rng.normal(size=(B, lq, H, D)), jnp.float32)
    return arrays + [kv_mask, jax.random.key(1, impl="rbg"), w]


def device_ops(fn, ops, top, calls=5):
    """Device ms a call of the longest operations, from a profiler capture
    (the host's clock around a call under a millisecond is mostly the launch):
    ``{"all": total, name: ms, ...}``."""
    import collections
    import glob
    import tempfile

    with tempfile.TemporaryDirectory() as d:
        with jax.profiler.trace(d):
            for _ in range(calls):
                out = fn(*ops)
            jax.block_until_ready(out)
        path = glob.glob(os.path.join(d, "plugins/profile/*/*.xplane.pb"))[0]
        data = jax.profiler.ProfileData.from_file(path)
    ms = collections.Counter()
    for plane in data.planes:
        if plane.name != "/device:TPU:0":
            continue
        for line in plane.lines:
            if line.name != "XLA Ops":
                continue
            for ev in line.events:
                ms[ev.name.split(" = ", 1)[0].lstrip("%")] += ev.duration_ns / 1e6
    out = {"all": round(sum(ms.values()) / calls, 4)}
    out.update((k, round(v / calls, 4)) for k, v in ms.most_common(top))
    return out


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--only", default=None, help="comma-separated candidates")
    ap.add_argument("--iters", type=int, default=30)
    ap.add_argument("--top", type=int, default=8,
                    help="device operations listed a candidate")
    args = ap.parse_args()
    names = args.only.split(",") if args.only else list(CANDIDATES)

    struct = None
    if args.aot:
        os.environ.setdefault("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        from jax.sharding import SingleDeviceSharding

        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        one = SingleDeviceSharding(topo.devices[0])
        struct = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one)  # noqa: E731
    elif jax.default_backend() != "tpu":
        sys.exit("no TPU: times come from the chip (--aot compiles here)")

    import importlib

    fa = importlib.import_module("tpu_air.ops.flash_attention")
    for shape, (lq, lk, causal, with_bias) in SHAPES.items():
        ops = operands(shape, struct)
        want = None
        for name in names:
            candidate, switches = CANDIDATES[name]
            fn = step(candidate, causal, with_bias)
            row = {"shape": shape, "candidate": name}
            was = {g: getattr(fa, g) for g in switches}
            for g, value in switches.items():
                setattr(fa, g, value)
            if switches:                 # the jitted wrappers cache a trace
                jax.clear_caches()       # made under the globals as they were
            try:
                if args.aot:
                    t0 = time.perf_counter()
                    compiled = fn.lower(*ops).compile()
                    text = compiled.as_text()
                    mem = compiled.memory_analysis()
                    row.update(
                        compile_s=round(time.perf_counter() - t0, 1),
                        kernels=text.count("tpu_custom_call"),
                        temp_mb=round(mem.temp_size_in_bytes / 2**20, 1))
                else:
                    got = jax.block_until_ready(fn(*ops))
                    for _ in range(3):
                        jax.block_until_ready(fn(*ops))
                    t0 = time.perf_counter()
                    for _ in range(args.iters):
                        out = fn(*ops)
                    jax.block_until_ready(out)
                    row["ms"] = round(
                        (time.perf_counter() - t0) / args.iters * 1e3, 4)
                    row["device_ms"] = device_ops(fn, ops, args.top)
                    # the masks differ, so gradients agree only in size
                    norms = [float(jnp.linalg.norm(g.astype(jnp.float32)))
                             for g in got]
                    if want is None:
                        want = norms
                    row["grad_norm_over_first"] = [
                        round(a / b, 4) for a, b in zip(norms, want)]
            except Exception as e:  # noqa: BLE001 — a candidate the compiler refuses
                row["error"] = repr(e)[:400]
            finally:
                for g, value in was.items():
                    setattr(fa, g, value)
                if switches:
                    jax.clear_caches()
            print(json.dumps(row), flush=True)


if __name__ == "__main__":
    main()
