"""Kind ``serve``: one engine replica behind ``serve.run``, open-loop load
over HTTP at the fixed rate the traffic file holds.

The replica is ``EngineDeployment``'s server class (``worker_hooks.
ObservedEngineDeployment`` adds methods called outside the window).  Proxy,
admission and queue settings are ``serve.run``'s defaults.  The load comes
from ``benchmark/loadgen.py`` in a process of its own: streaming ``submit``,
then ``poll`` pinned to the replica, the next one due ``poll_ms`` after the
answer to the last, through a fixed pool of ``poll_threads`` connections (a
stream is polled less often than that when polls are answered more slowly
than the live streams ask; the run reports the interval it got).  Requests are due
inside the ``--seconds`` window; after it the client keeps polling for at most
``drain_s`` and what is unfinished then has failed.
"""

from __future__ import annotations

import json
import os
import socket
import subprocess
import sys
import time
import urllib.request
from typing import Any, Dict, List

import numpy as np

from benchmark import stats
from benchmark import traffic as gen
from benchmark import weights

_LOADGEN = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "loadgen.py")


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _post(port: int, payload: Dict[str, Any], timeout: float = 600.0):
    req = urllib.request.Request(
        f"http://127.0.0.1:{port}/", data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return json.loads(resp.read())


def deploy(ctx):
    """Checkpoint from the seed, ``serve.run``, both engine programs warm.
    Returns (handle, port)."""
    from tpu_air import serve
    from tpu_air.engine import T5EngineConfig

    from benchmark.worker_hooks import ObservedEngineDeployment

    t = ctx.traffic
    ckpt = weights.write_checkpoint(ctx.cfg, ctx.seed, t["dtype"],
                                    os.path.join(ctx.scratch, "checkpoint"))
    port = _free_port()
    handle = serve.run(
        ObservedEngineDeployment.options(num_replicas=1, num_chips=1).bind(
            ckpt,
            T5EngineConfig(max_batch=int(t["max_batch"]),
                           max_input_len=int(t["max_input_len"]),
                           max_new_tokens=int(t["max_new_tokens"])),
            dtype=t["dtype"]),
        port=port)
    # the engine builds on the first request; two tokens run prefill and step
    _post(port, {"prompt": [5, 6, 7], "max_new_tokens": 2})
    return handle, port


def offer_load(ctx, handle, port: int, params: Dict[str, Any], seed: int,
               seconds: float, tag: str = "load") -> Dict[str, Any]:
    """One open-loop phase against a deployed replica: writes the plan,
    runs the client process to its end, samples the engine's queue once a
    second meanwhile.  Returns the client's rows and the queue samples."""
    import tpu_air

    schedule = gen.open_loop_schedule(params, seed, seconds,
                                      ctx.cfg["vocab_size"])
    plan_path = os.path.join(ctx.scratch, f"{tag}_plan.json")
    out_path = os.path.join(ctx.scratch, f"{tag}_out.json")
    start_at = time.monotonic() + float(params.get("lead_s", 1.5))
    with open(plan_path, "w") as f:
        json.dump({"host": "127.0.0.1", "port": port, "path": "/",
                   "start_at": start_at, "seconds": seconds,
                   "poll_s": float(params["poll_ms"]) / 1000.0,
                   "submit_threads": int(params["submit_threads"]),
                   "poll_threads": int(params["poll_threads"]),
                   "drain_s": float(params["drain_s"]),
                   "requests": schedule}, f)
    child = subprocess.Popen([sys.executable, _LOADGEN, plan_path, out_path])
    queue: List[Dict[str, float]] = []
    traced: Dict[str, Any] = {}
    try:
        trace_at = (start_at + float(params["trace_delay_s"])
                    if ctx.trace and tag == "load" else None)
        while True:
            try:
                child.wait(timeout=1.0)
                break
            except subprocess.TimeoutExpired:
                pass
            now = time.monotonic()
            if trace_at is not None and now >= trace_at:
                tpu_air.get(handle.method("bench_trace")(
                    ctx.trace_dir, float(params["trace_s"])))
                trace_at, traced["asked_at_s"] = None, now - start_at
            if start_at <= now <= start_at + seconds:
                s = tpu_air.get(handle.method("stats")())
                queue.append({"t": now - start_at,
                              "queue_depth": s.get("queue_depth", 0),
                              "slot_occupancy": s.get("slot_occupancy", 0)})
        if child.returncode != 0:
            raise RuntimeError(f"loadgen exited {child.returncode}")
    finally:
        if child.poll() is None:
            child.kill()
        child.wait()
    with open(out_path) as f:
        out = json.load(f)
    return {"rows": out["requests"], "queue": queue, "schedule": schedule,
            "poll_late_ms": [1000.0 * x for x in out["poll_late_s"]],
            "traced": traced,
            "started_at": time.time() - (time.monotonic() - start_at)}


def read_per_step(seen: Dict[str, int], key: str) -> Dict[str, float]:
    """``key`` a step, by the program that ran the step, of what the
    replica's ``worker_hooks.ReadWatch`` counted over the profiler's window
    (``bench_traced_counts``): the decode program alone, and the mixed step
    that carried a chunk; a program none of whose steps was read is left
    out, and without a watch's counts (an untraced run) so are both."""
    alone = (seen.get("steps_read_alone", 0), seen.get(key + "_alone", 0))
    mixed = (seen.get("steps_read", 0) - alone[0],
             seen.get(key, 0) - alone[1])
    return {program: value / steps for program, (steps, value) in (
        ("lm_paged_decode_step", alone), ("lm_paged_mixed_step", mixed))
        if steps}


def summarize(rows: List[Dict[str, Any]], seconds: float,
              drain_s: float) -> Dict[str, Any]:
    """Client-side series.  A request that failed, was shed or did not
    finish has no first token: it counts at the give-up horizon, the worst
    a client can have seen."""
    ok = [r for r in rows if r["outcome"] == "ok"]
    missing_ms = 1000.0 * (seconds + drain_s)
    ttft = [1000.0 * r["ttft_s"] if r["outcome"] == "ok" else missing_ms
            for r in rows]
    tpot = [1000.0 * r["first_to_done_s"] / (len(r["tokens"]) - 1)
            for r in ok if len(r["tokens"]) > 1]
    return {
        "attempted": len(rows), "completed": len(ok),
        "failed": len(rows) - len(ok),
        "outcomes": {o: sum(1 for r in rows if r["outcome"] == o)
                     for o in sorted({r["outcome"] for r in rows})},
        "client_ttft_ms": ttft, "client_tpot_ms": tpot,
        "client_late_ms": [1000.0 * r["late_s"] for r in rows
                           if r["late_s"] is not None],
        "poll_interval_ms": [1000.0 * r["poll_interval_s"] for r in rows
                             if r["poll_interval_s"] is not None],
        "tokens": sum(len(r["tokens"]) for r in ok),
        "polls": sum(r["polls"] for r in rows),
    }


def run(ctx) -> None:
    import tpu_air

    t = ctx.traffic
    vocab = ctx.cfg["vocab_size"]
    handle, port = deploy(ctx)
    facts0 = tpu_air.get(handle.method("bench_facts")())
    stats0 = tpu_air.get(handle.method("stats")())

    load = offer_load(ctx, handle, port, t, ctx.seed, ctx.seconds)
    stats1 = tpu_air.get(handle.method("stats")())
    facts1 = tpu_air.get(handle.method("bench_facts")())
    rows, schedule = load["rows"], load["schedule"]
    summary = summarize(rows, ctx.seconds, float(t["drain_s"]))

    ctx.attempted, ctx.failed = summary["attempted"], summary["failed"]
    for r, s in zip(rows, schedule):
        if r["outcome"] == "ok":
            toks = r["tokens"]
            ctx.check(1 <= len(toks) <= s["max_new_tokens"]
                      and all(0 <= x < vocab for x in toks),
                      f"request due at {r['due_s']:.3f}s answered "
                      f"{len(toks)} tokens for a budget of "
                      f"{s['max_new_tokens']}")
    # the two counts are equal whenever nothing failed (every cell so far);
    # a request the client gave up on at its horizon is still the engine's
    # to finish, so with failures the engine's count may lie in between
    done_in_engine = (stats1["requests_completed"]
                      - stats0["requests_completed"])
    ctx.check(summary["completed"] <= done_in_engine
              <= summary["attempted"],
              f"engine completed {done_in_engine}, the client saw "
              f"{summary['completed']} of {summary['attempted']}")
    ctx.check(facts1["cold_compiles"] == facts0["cold_compiles"],
              "cold compiles inside the window")

    # outside the window: a few prompts sent alone, their streamed tokens
    # held against the model's full forward pass in the replica
    rng = np.random.default_rng([ctx.seed, 3])
    n, budget = int(t["check_prompts"]), int(t["check_new_tokens"])
    prompts = [rng.integers(2, vocab, int(k)).tolist() for k in
               rng.integers(t["prompt_len"]["min"],
                            t["prompt_len"]["max"] + 1, n)]
    answers = [r["tokens"] for r in _post(
        port, {"prompts": prompts, "max_new_tokens": budget})["results"]]
    verdicts = tpu_air.get(handle.method("bench_teacher_forced")(
        prompts, answers))
    tol = float(t["check_margin"])
    for i, v in enumerate(verdicts):
        ctx.check(1 <= v["tokens"] <= budget and v["worst_margin"] <= tol,
                  f"check prompt {i}: {v} (a streamed token's logit lies "
                  f"more than {tol} of the row's top-to-median distance "
                  "under the largest)")
    facts2 = tpu_air.get(handle.method("bench_facts")())

    late95 = stats.percentile(summary["client_late_ms"], 0.95)
    if late95 is not None and late95 > float(t["poll_ms"]):
        print(f"benchmark: WARNING the load generator ran late: p95 "
              f"{late95:.1f} ms against a poll interval of {t['poll_ms']} "
              "ms — not a fast server", file=sys.stderr)
    poll_late95 = stats.percentile(load["poll_late_ms"], 0.95)
    poll_every = stats.percentile(summary["poll_interval_ms"], 0.5)
    if poll_late95 is not None and poll_late95 > float(t["poll_ms"]):
        every = "?" if poll_every is None else f"{poll_every:.1f}"
        print(f"benchmark: WARNING polls left late (p95 {poll_late95:.1f} "
              f"ms) waiting for one of the client's {t['poll_threads']} poll "
              f"connections: a stream was polled every {every} ms (median), "
              f"not every {t['poll_ms']}", file=sys.stderr)
    half = [q for q in load["queue"] if q["t"] <= ctx.seconds / 2]
    ctx.window_s = ctx.seconds
    ctx.window_start = load["started_at"]
    ctx.facts.update({
        "window_s": ctx.window_s,
        "client_ttft_ms": summary["client_ttft_ms"],
        "client_tpot_ms": summary["client_tpot_ms"],
        "client_late_ms": summary["client_late_ms"],
        "client_poll_late_ms": load["poll_late_ms"],
        "serve_tokens": summary["tokens"],
        "serve_completed": summary["completed"],
        "engine_step_ms_p50": 1000.0 * stats1["step_latency_s"]["p50"],
        "engine_ttft_ms_p50": 1000.0 * stats1["ttft_s"]["p50"],
        "engine_steps": (stats1["step_latency_s"]["count"]
                         - stats0["step_latency_s"].get("count", 0)),
        "engine_tokens": stats1["tokens_emitted"] - stats0["tokens_emitted"],
        "queue_depth_half": half[-1]["queue_depth"] if half else None,
        "queue_depth_end": (load["queue"][-1]["queue_depth"]
                            if load["queue"] else None),
        "max_batch": int(t["max_batch"]),
        "max_input_len": int(t["max_input_len"]),
        "max_new_tokens": int(t["max_new_tokens"]),
        "memory_peak_bytes": facts2.get("memory_peak_bytes"),
        "worker_compile_s": facts2["compile_s"],
        "worker_cold_compiles": facts2["cold_compiles"],
        "worker_cache_hits": facts2["cache_hits"],
    })
    ctx.notes.update(
        requests=summary["attempted"], outcomes=summary["outcomes"],
        ttft_samples_beyond_p95=stats.samples_beyond(
            summary["attempted"], 0.95),
        polls=summary["polls"], loadgen_late_ms_p95=late95,
        loadgen_poll_late_ms_p95=poll_late95,
        poll_interval_ms_p50=poll_every,
        check_exact_tokens=[v["exact"] for v in verdicts],
        check_worst_margin=max(v["worst_margin"] for v in verdicts),
        traced=load["traced"])
