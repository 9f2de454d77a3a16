#!/usr/bin/env python3
"""Candidates for the routed expert product, timed on the chip at the two
shapes the serving cell runs (PERF.md, PR 27): 64 rows (a decode step) and
128 rows (a prefill chunk), 8 assignments a row, 64 experts of 2048 x 1024,
bf16, uneven groups.

    python tools/moe_candidates.py            # on a TPU: times, errors
    python tools/moe_candidates.py --aot      # here: compile for a described v5e

Each candidate computes exactly ``sum_e w[t,e] * down_e(silu(gate_e x_t) *
up_e x_t)`` over the row's top-k experts: no capacity, no dropped row.  The
one kept is ``tpu_air.ops.moe.expert_ffn``; the others live only here.
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

D, F, E, K = 2048, 1024, 64, 8


def _sorted(idx, vals):
    flat = idx.reshape(-1)
    order = jnp.argsort(flat, stable=True)
    sizes = jnp.bincount(flat, length=E).astype(jnp.int32)
    return order, order // idx.shape[1], sizes, vals.reshape(-1)[order]


def _unsort(y, order, w, t, k):
    y = y.astype(jnp.float32) * w[:, None]
    out = jnp.zeros((t * k, y.shape[-1]), jnp.float32).at[order].set(y)
    return out.reshape(t, k, -1).sum(1)


def ragged(x, idx, vals, wg, wu, wd):
    t, k = idx.shape
    order, tok, sizes, w = _sorted(idx, vals)
    xs = x[tok]
    rd = lambda a, b: jax.lax.ragged_dot(  # noqa: E731
        a, b, sizes, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(rd(xs, wg)) * rd(xs, wu)).astype(x.dtype)
    return _unsort(rd(h, wd), order, w, t, k)


def make_gmm(tiling):
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    def run(x, idx, vals, wg, wu, wd):
        t, k = idx.shape
        order, tok, sizes, w = _sorted(idx, vals)
        xs = x[tok]
        mm = lambda a, b, tl: gmm(  # noqa: E731
            a, b, sizes, preferred_element_type=jnp.float32, tiling=tl)
        tm, tk, tn = tiling
        h = (jax.nn.silu(mm(xs, wg, (tm, tk, tn)))
             * mm(xs, wu, (tm, tk, tn))).astype(x.dtype)
        return _unsort(mm(h, wd, (tm, min(tk, F), tn)), order, w, t, k)

    return run


def dense_weights(idx, vals):
    """[T, E] float32: the row's probability for its top-k experts, else 0."""
    t = idx.shape[0]
    return jnp.zeros((t, E), jnp.float32).at[
        jnp.arange(t)[:, None], idx].set(vals)


def masked(x, idx, vals, wg, wu, wd):
    w = dense_weights(idx, vals)
    g = jnp.einsum("td,edf->etf", x, wg, preferred_element_type=jnp.float32)
    u = jnp.einsum("td,edf->etf", x, wu, preferred_element_type=jnp.float32)
    h = (jax.nn.silu(g) * u * w.T[:, :, None]).astype(x.dtype)
    return jnp.einsum("etf,efd->td", h, wd,
                      preferred_element_type=jnp.float32)


def scan(x, idx, vals, wg, wu, wd):
    w = dense_weights(idx, vals)

    def one(acc, ew):
        g, u, d, we = ew
        h = (jax.nn.silu(jnp.dot(x, g, preferred_element_type=jnp.float32))
             * jnp.dot(x, u, preferred_element_type=jnp.float32)
             * we[:, None]).astype(x.dtype)
        return acc + jnp.dot(h, d, preferred_element_type=jnp.float32), None

    out, _ = jax.lax.scan(one, jnp.zeros((x.shape[0], D), jnp.float32),
                          (wg, wu, wd, w.T))
    return out


CANDIDATES = {
    "ragged_dot": ragged,
    "gmm_128_128_128": make_gmm((128, 128, 128)),
    "gmm_128_512_512": make_gmm((128, 512, 512)),
    "gmm_128_2048_512": make_gmm((128, 2048, 512)),
    "gmm_64_1024_1024": make_gmm((64, 1024, 1024)),
    "gmm_128_1024_1024": make_gmm((128, 1024, 1024)),
    "gmm_128_2048_1024": make_gmm((128, 2048, 1024)),
    "gmm_128_2048_256": make_gmm((128, 2048, 256)),
    "gmm_256_1024_512": make_gmm((256, 1024, 512)),
    "masked_dense": masked,
    "scan_experts": scan,
}


def inputs(t, seed=0):
    rng = np.random.default_rng(seed)
    x = rng.standard_normal((t, D), np.float32)
    gain = np.exp(0.6 * rng.standard_normal(E))  # uneven load
    logits = rng.standard_normal((t, E)) + np.log(gain)
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)
    idx = np.argsort(-p, -1)[:, :K].astype(np.int32)
    vals = np.take_along_axis(p, idx, -1).astype(np.float32)
    ws = [(rng.standard_normal(s, np.float32) * 0.02)
          for s in ((E, D, F), (E, D, F), (E, F, D))]
    return x, idx, vals, ws


def exact(x, idx, vals, ws):
    """float64 on the host, over bf16-rounded inputs."""
    import ml_dtypes

    r = lambda a: a.astype(ml_dtypes.bfloat16).astype(np.float64)  # noqa: E731
    x = r(x)
    out = np.zeros((x.shape[0], D))
    for t in range(x.shape[0]):
        for e, w in zip(idx[t], vals[t]):
            g, u = x[t] @ r(ws[0][e]), x[t] @ r(ws[1][e])
            h = r((g / (1 + np.exp(-g)) * u).astype(np.float32))
            out[t] += w * (h @ r(ws[2][e]))
    return out


def aot() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=one)  # noqa: E731
    for t in (64, 128):
        args = (s((t, D), jnp.bfloat16), s((t, K), jnp.int32),
                s((t, K), jnp.float32), s((E, D, F), jnp.bfloat16),
                s((E, D, F), jnp.bfloat16), s((E, F, D), jnp.bfloat16))
        for name, fn in CANDIDATES.items():
            try:
                c = jax.jit(fn).lower(*args).compile()
                m = c.memory_analysis()
                print(name, t, "ok temp", m.temp_size_in_bytes, flush=True)
            except Exception as e:  # the compiler's refusal is the finding
                print(name, t, "REFUSED", str(e)[:300], flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--aot", action="store_true")
    ap.add_argument("--reps", type=int, default=30)
    args = ap.parse_args()
    if args.aot:
        return aot()
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print("moe_candidates: no TPU", file=sys.stderr)
        return 2
    peak = 819e9
    out = []
    for t in (64, 128):
        x, idx, vals, ws = inputs(t)
        want = exact(x, idx, vals, ws)
        scale = np.abs(want).max()
        dx = jnp.asarray(x, jnp.bfloat16)
        dws = [jnp.asarray(w, jnp.bfloat16) for w in ws]
        di, dv = jnp.asarray(idx), jnp.asarray(vals)
        load = np.bincount(idx.reshape(-1), minlength=E)
        floor_ms = 1e3 * 3 * E * D * F * 2 / peak
        for name, fn in CANDIDATES.items():
            row = {"candidate": name, "rows": t,
                   "load_max_over_mean": float(load.max() / load.mean()),
                   "byte_floor_ms": floor_ms}
            try:
                f = jax.jit(fn)
                got = np.asarray(f(dx, di, dv, *dws), np.float64)
                row["max_err_rel"] = float(np.abs(got - want).max() / scale)
                f(dx, di, dv, *dws).block_until_ready()
                t0 = time.perf_counter()
                for _ in range(args.reps):
                    y = f(dx, di, dv, *dws)
                y.block_until_ready()
                row["ms"] = 1e3 * (time.perf_counter() - t0) / args.reps
                row["roofline_share"] = floor_ms / row["ms"]
            except Exception as e:
                row["error"] = str(e)[:300]
            print(json.dumps(row), flush=True)
            out.append(row)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/moe_candidates.json", "w") as fh:
        json.dump(out, fh, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
