#!/usr/bin/env python3
"""Tile shapes for the grouped expert product where ``ops.moe.GMM_TILING``
(128, 2048, 1024) does not divide the shapes, timed on the chip (PERF.md,
PR 43): 16 held experts of 7168 x 2048 (gate, up) and 2048 x 7168 (down),
bf16, uneven groups, at the sorted-row counts the serving cell runs: a decode
step (128 rows x 8 = 1024 sorted rows of which about a sixteenth belong to a
held expert), a 256-token chunk (2048) and the mixed step (3072).  Rows past
the last held group cost nothing: the kernel never visits them.

    python tools/gmm_tilings.py            # on a TPU: ms and GB/s a product
"""

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax
import jax.numpy as jnp
import numpy as np

D, F, E = 7168, 2048, 16
UP = [(128, 1024, 1024), (128, 1024, 2048), (128, 1792, 1024),
      (128, 1792, 2048), (128, 3584, 512), (128, 3584, 1024),
      (128, 1024, 512), (128, 512, 2048), (128, 7168, 256),
      (256, 1024, 1024), (256, 1792, 1024), (64, 1024, 2048)]
DOWN = [(128, 2048, 1024), (128, 1024, 1024), (128, 2048, 512),
        (128, 2048, 1792), (128, 1024, 1792), (128, 2048, 3584),
        (128, 1024, 3584), (256, 2048, 1024), (128, 512, 1792),
        (64, 2048, 1792)]
# (sorted rows, rows that belong to a held expert)
CASES = [(1024, 64), (1024, 256), (2048, 128), (3072, 192), (3072, 1024)]


def sizes(held, seed):
    p = np.random.default_rng(seed).dirichlet(np.full(E, 2.0))
    return np.random.default_rng(seed + 1).multinomial(held, p).astype(np.int32)


def timed(fn, *args, reps=20):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / reps, out


def main() -> int:
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm

    if jax.default_backend() != "tpu":
        print("gmm_tilings: needs a TPU", file=sys.stderr)
        return 2
    key = jax.random.PRNGKey(0)
    w_up = jax.random.normal(key, (E, D, F), jnp.bfloat16) * 0.02
    w_down = jax.random.normal(key, (E, F, D), jnp.bfloat16) * 0.02
    rows = []
    for m, held in CASES:
        gs = jnp.asarray(sizes(held, m + held))
        touched = int((np.asarray(gs) > 0).sum())
        for which, w, tilings, k in (("up", w_up, UP, D),
                                     ("down", w_down, DOWN, F)):
            x = jax.random.normal(key, (m, k), jnp.bfloat16)
            want = np.asarray(jax.lax.ragged_dot(
                x, w, gs, preferred_element_type=jnp.float32))[:held]
            for tiling in tilings:
                row = {"product": which, "sorted_rows": m, "held_rows": held,
                       "experts_touched": touched, "tiling": list(tiling)}
                try:
                    fn = jax.jit(lambda a, b, s, _t=tiling: gmm(
                        a, b, s, preferred_element_type=jnp.float32,
                        tiling=_t))
                    sec, out = timed(fn, x, w, gs)
                    got = np.asarray(out)[:held]
                    row.update(
                        ms=1000 * sec,
                        gb_s=touched * D * F * 2 / sec / 1e9,
                        max_err=float(np.abs(got - want).max()))
                except Exception as e:      # VMEM, tiling refused
                    row["error"] = f"{type(e).__name__}: {str(e)[:160]}"
                rows.append(row)
                print(json.dumps(row), flush=True)
    os.makedirs("chiprun_out", exist_ok=True)
    with open("chiprun_out/gmm_tilings.json", "w") as f:
        json.dump(rows, f, indent=1)
    return 0


if __name__ == "__main__":
    sys.exit(main())
