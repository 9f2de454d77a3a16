#!/usr/bin/env python3
"""Find a serving cell's knee once, on the chip: the highest rate the system
sustains.  One deployment, then open-loop phases of ``--seconds`` each at
rates that double from ``--start`` until one is not sustained and then bisect
the bracket to within 10 %.  A phase is sustained when at least 99 % of the
requests due in it completed and the queue is not growing: the mean queue
depth sampled over its last quarter is no deeper than over its second
quarter (times 1.5, plus an eighth of a window's rows as slack for the
window cycle), and the median time to first token of the requests due in the
last quarter is within 1.25 times the middle one of the first three.  Every phase is printed; the traffic file gets 0.8 of the
knee BY HAND, with the sweep recorded in PERF.md.

    python benchmark/tools/sweep.py --workload t5large-serve [--seconds 45]
"""

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def phase(ctx, serve_kind, handle, port, rate, seconds, seed):
    from benchmark import stats

    params = {**ctx.traffic, "rate_rps": rate}
    load = serve_kind.offer_load(ctx, handle, port, params, seed, seconds,
                                 tag=f"sweep_{rate:g}")
    s = serve_kind.summarize(load["rows"], seconds, float(params["drain_s"]))

    def mean_queue(lo, hi):
        xs = [q["queue_depth"] for q in load["queue"]
              if lo * seconds <= q["t"] < hi * seconds]
        return sum(xs) / len(xs) if xs else 0.0

    def ttft(lo, hi):
        xs = [1000.0 * r["ttft_s"] for r in load["rows"]
              if r["ttft_s"] is not None
              and lo * seconds <= r["due_s"] < hi * seconds]
        return stats.percentile(xs, 0.5)

    q2, q4 = mean_queue(0.25, 0.5), mean_queue(0.75, 1.0)
    share = s["completed"] / max(s["attempted"], 1)
    by_quarter = [ttft(i / 4, (i + 1) / 4) for i in range(4)]
    sustained = (share >= 0.99
                 and q4 <= 1.5 * q2 + ctx.traffic["max_batch"] / 8.0
                 and None not in by_quarter
                 and by_quarter[3] <= 1.25 * sorted(by_quarter[:3])[1])
    line = {
        "rate_rps": rate, "sustained": sustained, "requests": s["attempted"],
        "completed_share": share, "outcomes": s["outcomes"],
        "queue_q2": q2, "queue_q4": q4,
        "ttft_p50_by_quarter_ms": by_quarter,
        "ttft_p50_ms": stats.percentile(s["client_ttft_ms"], 0.5),
        "ttft_p95_ms": stats.percentile(s["client_ttft_ms"], 0.95),
        "tpot_p50_ms": stats.percentile(s["client_tpot_ms"], 0.5),
        "late_p95_ms": stats.percentile(s["client_late_ms"], 0.95),
        "poll_late_p95_ms": stats.percentile(load["poll_late_ms"], 0.95),
        "poll_interval_p50_ms": stats.percentile(s["poll_interval_ms"], 0.5),
        "tokens_per_s": s["tokens"] / seconds, "polls": s["polls"],
    }
    print(json.dumps({"sweep": line}), flush=True)
    return sustained


def main() -> int:
    sys.path.insert(0, ROOT)
    os.environ["PYTHONPATH"] = ROOT + os.pathsep + os.environ.get(
        "PYTHONPATH", "")
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=45.0)
    ap.add_argument("--start", type=float, default=4.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    import tpu_air
    from tpu_air import serve

    from benchmark import harness, manifest

    bench = manifest.Benchmark()
    cell = bench.cell(args.workload)
    cfg, traffic = bench.config(cell["config"]), bench.traffic(cell)
    if harness.chips_here() < cell["chips"]:
        print("sweep: no attached TPU chip", file=sys.stderr)
        return 2
    ctx = harness.Context(
        bench=bench, cell=cell, cfg=cfg, traffic=traffic, seed=args.seed,
        seconds=args.seconds, trace=False, rehearse=False,
        scratch=tempfile.mkdtemp(prefix="tpu_air-sweep-"), trace_dir="")
    serve_kind = bench.module("kinds", ctx.traffic["kind"])
    harness.place_compile_cache()
    tpu_air.init()
    try:
        handle, port = serve_kind.deploy(ctx)

        def run(rate):
            ok = phase(ctx, serve_kind, handle, port, rate, args.seconds,
                       args.seed)
            time.sleep(2.0)
            return ok

        lo, hi, rate = None, None, args.start
        while hi is None and rate <= 512:
            if run(rate):
                lo, rate = rate, rate * 2
            else:
                hi = rate
        if lo is None or hi is None:
            print(json.dumps({"knee": None, "bracket": [lo, hi]}))
            return 1
        while hi / lo > 1.10:
            mid = (lo * hi) ** 0.5
            if run(mid):
                lo = mid
            else:
                hi = mid
        print(json.dumps({"knee_rps": lo, "first_unsustained_rps": hi,
                          "rate_at_0.8": 0.8 * lo}), flush=True)
        return 0
    finally:
        serve.shutdown()
        tpu_air.shutdown()
        import shutil

        shutil.rmtree(ctx.scratch, ignore_errors=True)


if __name__ == "__main__":
    sys.exit(main())
