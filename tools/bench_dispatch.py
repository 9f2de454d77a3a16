"""Task-dispatch microbenchmark (docs/NATIVE_RUNTIME.md
deviation 1).

Measures what the Python control half actually costs per task, so the
"microseconds of bookkeeping" claim is data, not argument:

* **breakdown** — sequential no-op round-trips, split by wall timestamps
  into submit->exec (schedule + pipe + deserialize), exec (user fn), and
  exec->get (seal + notify + driver fetch);
* **throughput** — pipelined no-op tasks/sec (submit N, then gather), the
  dispatch-rate ceiling of the runtime;
* **actor round-trip** — the BatchPredictor-shaped path (method call on a
  live worker process);
* **overhead share** — dispatch cost as a fraction of a W9-shaped task
  (~100 ms of real work, Overview_of_Ray.ipynb:cc-41), the workload class
  with the MOST dispatches per unit compute in the reference.

Run: ``python tools/bench_dispatch.py [--tasks 200]``.  Prints one JSON
object.  CPU-only — never touches the chip.
"""

from __future__ import annotations

import argparse
import json
import os
import statistics
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _noop_timed():
    t = time.time()
    # no work: exec window is just the timestamp capture
    return t, time.time()


def _sleep_100ms():
    # sleep, not spin: on a small/shared host a spinning task contends with
    # the driver for cores and the excess measures CPU starvation, not
    # dispatch.  Sleeping isolates exactly the scheduler+pipe+seal cost.
    time.sleep(0.1)
    return 1


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--tasks", type=int, default=200)
    args = ap.parse_args()

    import tpu_air

    tpu_air.init(num_cpus=4)
    try:
        noop = tpu_air.remote(_noop_timed)
        busy = tpu_air.remote(_sleep_100ms)

        # warm the FULL worker pool (each first task on a fresh worker pays
        # process spawn): 4 concurrent sleepers force all 4 workers up
        for r in [busy.remote() for _ in range(8)]:
            tpu_air.get(r)
        for _ in range(4):
            tpu_air.get(noop.remote())

        # -- breakdown: sequential round-trips --------------------------------
        pre_us, exec_us, post_us, total_us = [], [], [], []
        for _ in range(args.tasks):
            t_submit = time.time()
            ref = noop.remote()
            t_exec_start, t_exec_end = tpu_air.get(ref)
            t_got = time.time()
            pre_us.append((t_exec_start - t_submit) * 1e6)
            exec_us.append((t_exec_end - t_exec_start) * 1e6)
            post_us.append((t_got - t_exec_end) * 1e6)
            total_us.append((t_got - t_submit) * 1e6)

        def stats(xs):
            xs = sorted(xs)
            return {
                "p50_us": round(statistics.median(xs), 1),
                "p90_us": round(xs[int(len(xs) * 0.9)], 1),
                "mean_us": round(statistics.fmean(xs), 1),
            }

        breakdown = {
            "submit_to_exec (schedule+pipe+deserialize)": stats(pre_us),
            "exec (user fn)": stats(exec_us),
            "exec_to_get (seal+notify+fetch)": stats(post_us),
            "round_trip": stats(total_us),
        }

        # -- throughput: pipelined no-ops -------------------------------------
        t0 = time.perf_counter()
        refs = [noop.remote() for _ in range(args.tasks)]
        for r in refs:
            tpu_air.get(r)
        pipelined_s = time.perf_counter() - t0
        tasks_per_sec = round(args.tasks / pipelined_s, 1)

        # -- actor method round-trip ------------------------------------------
        @tpu_air.remote
        class Echo:
            def hit(self):
                return time.time()

        a = Echo.remote()
        tpu_air.get(a.hit.remote())  # warm
        actor_us = []
        for _ in range(args.tasks):
            t_submit = time.time()
            tpu_air.get(a.hit.remote())
            actor_us.append((time.time() - t_submit) * 1e6)
        tpu_air.kill(a)

        # -- dispatch share of a W9-shaped workload ---------------------------
        # 20 tasks x 100 ms over 4 workers (Overview_of_Ray.ipynb:cc-41
        # shape). Ideal wall = 0.5 s; everything above it is scheduler +
        # pipe + seal + gather — the dispatch overhead share.
        t0 = time.perf_counter()
        refs = [busy.remote() for _ in range(20)]
        for r in refs:
            tpu_air.get(r)
        w9_wall = time.perf_counter() - t0
        w9_ideal = 20 * 0.1 / 4
        overhead_pct = round(100.0 * (w9_wall - w9_ideal) / w9_wall, 2)

        out = {
            "benchmark": "task_dispatch",
            "tasks": args.tasks,
            "breakdown": breakdown,
            "pipelined_tasks_per_sec": tasks_per_sec,
            "actor_round_trip": stats(actor_us),
            "w9_shaped": {
                "wall_s": round(w9_wall, 3),
                "ideal_s": w9_ideal,
                "dispatch_plus_skew_pct": overhead_pct,
            },
        }
        print(json.dumps(out))
    finally:
        tpu_air.shutdown()


if __name__ == "__main__":
    main()
