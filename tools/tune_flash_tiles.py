"""Short-sequence flash-attention tile sweep.

The Pallas kernel loses to XLA dense at seq 512 with the auto tiles (bare
W1 steps of July 2026, git history before PR 29: 92,077 tokens/s flash
against 142,848 einsum; no benchmark cell); this sweeps (block_q, block_k)
candidates at short sequence lengths on the chip and prints a table, so the
crossover either moves down or the 512-einsum default is confirmed with data.  Slope-timed (two scan
lengths; fixed dispatch and sync costs cancel — see bench.py's module
docstring).

Run ON the chip (single process — never concurrently with bench.py):
    python tools/tune_flash_tiles.py [--seq 512] [--bh 48] [--d 64]
"""

import argparse
import os
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def slope_time(fn, q, k, v, steps=512, reps=3):
    # steps must be large enough that 2*steps of attention dwarf the fixed
    # cost of a dispatch and its sync, or the 25%-slope validity gate NaNs
    # out
    import jax
    import jax.numpy as jnp

    def chain(n):
        def body(c, _):
            o = fn(c, k, v)
            return o, ()

        def run(q, k, v):
            out, _ = jax.lax.scan(body, q, None, length=n)
            return jnp.sum(out)

        return jax.jit(run)

    short, long_ = chain(steps), chain(3 * steps)
    float(short(q, k, v))
    float(long_(q, k, v))
    ts, tl = [], []
    for _ in range(reps):
        t0 = time.perf_counter(); float(short(q, k, v)); ts.append(time.perf_counter() - t0)
        t0 = time.perf_counter(); float(long_(q, k, v)); tl.append(time.perf_counter() - t0)
    ms, ml = sorted(ts)[reps // 2], sorted(tl)[reps // 2]
    per = (ml - ms) / (2 * steps)
    return per if ml - ms > 0.25 * ml else float("nan")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--seq", type=int, default=512)
    ap.add_argument("--bh", type=int, default=48)
    ap.add_argument("--d", type=int, default=64)
    ap.add_argument("--steps", type=int, default=512)
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from tpu_air.ops.flash_attention import flash_attention

    L, BH, D = args.seq, args.bh, args.d
    rng = jax.random.PRNGKey(0)
    q = jax.random.normal(rng, (BH, L, D), jnp.bfloat16)
    k = jax.random.normal(rng, (BH, L, D), jnp.bfloat16)
    v = jax.random.normal(rng, (BH, L, D), jnp.bfloat16)
    flops = 4.0 * BH * L * L * D  # qk + pv matmuls

    def dense(q, k, v):
        s = jnp.einsum("bqd,bkd->bqk", q.astype(jnp.float32), k.astype(jnp.float32))
        p = jax.nn.softmax(s * (1.0 / D**0.5), axis=-1)
        return jnp.einsum("bqk,bkd->bqd", p, v.astype(jnp.float32)).astype(q.dtype)

    per = slope_time(dense, q, k, v, steps=args.steps)
    print(f"dense: {per*1e3:8.3f} ms  {flops/per/1e12:6.1f} TF/s")

    candidates = [b for b in (64, 128, 256, 512) if L % b == 0]
    results = []
    for bq in candidates:
        for bk in candidates:
            def fn(q, k, v, bq=bq, bk=bk):
                return flash_attention(q, k, v, block_q=bq, block_k=bk)

            try:
                per = slope_time(fn, q, k, v, steps=args.steps)
            except Exception as e:  # noqa: BLE001
                print(f"flash bq={bq:4d} bk={bk:4d}: FAILED {type(e).__name__}")
                continue
            tf = flops / per / 1e12 if per == per and per > 0 else float("nan")
            results.append((tf, bq, bk, per))
            print(f"flash bq={bq:4d} bk={bk:4d}: {per*1e3:8.3f} ms  {tf:6.1f} TF/s")
    if results:
        best = max(results)
        print(f"\nbest flash: bq={best[1]} bk={best[2]} at {best[0]:.1f} TF/s "
              f"(seq {L}); update flash_min_seq_len / auto tiles if it beats "
              "dense")


if __name__ == "__main__":
    main()
