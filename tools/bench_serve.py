"""Open-loop serve-plane benchmark: SLO-aware admission under Poisson load.

Unlike ``bench_engine.py`` (closed-loop, driver-embedded engines), this
bench exercises the REAL serving path end to end: HTTP proxy → SLO
admission (priority class + token budget) → least-loaded replica actor →
streaming submit/poll with replica pinning — all under airtrace spans.

The workload is OPEN-LOOP: arrivals follow seeded Poisson processes whose
rates do not slow down when the system backs up (the honest way to measure
overload behaviour — a closed loop self-throttles and hides queueing
collapse).  Each arrival is a streaming client thread: one
``{"action": "submit"}`` POST (TTFT clock starts), then pinned
``{"action": "poll"}`` POSTs until ``done``.

Two phases run against the same deployment, and the INTERACTIVE arrival
rate is IDENTICAL in both — only the background (batch + best_effort)
rate changes.  That isolates the SLO claim: background pressure, not
interactive self-load, is what must not move interactive latency.

* **underload** — background arrivals well inside capacity; every class
  admits.  Interactive TTFT here is the baseline.
* **overload** — background arrivals far past capacity; the admission
  controller queues then sheds best_effort and batch (503 + Retry-After)
  while ``reserved_interactive_slots`` keeps decode slots available to
  interactive, whose p99 TTFT must hold ~flat vs the underload baseline
  (the ``interactive_p99_ratio`` headline; tests/test_serve_slo.py
  asserts ≤1.2x with a CPU-noise floor).

A third **swap** phase measures the live weight hot-swap path
(tpu_air/serve/weights.py): underload-rate traffic runs while a
WeightsController publishes + canary-promotes the SAME weights across
the fleet mid-phase.  Headlines: ``swap_stall_ms`` — the worst decode
gap any replica's swap introduced (fleet-merged
``tpu_air_weights_swap_stall_ms_max``) — and ``swap_errors_total``,
which must stay 0 (a swap drops no streams).

A fourth **preemption** phase measures lease-revocation recovery
(docs/RESILIENCE.md "Preemption & migration"): two single-chip replicas
serve underload-rate traffic while a seeded ``runtime.lease`` notice
revokes one replica's chip mid-phase; the PreemptionWatcher drains it
and live-migrates its KV pages to the survivor.  Headlines:
``preemption_recovery_ms`` — worst notice-to-out-of-rotation
orchestration wall time — and ``migrated_vs_replayed`` — the fraction
of rescued streams that moved with their KV state (zero re-prefill)
rather than falling back to journal replay; with a generous notice it
must be 1.0.

A fifth **batch** phase measures the elastic offline lane
(tpu_air/batch, docs/SERVING.md "Batch lane"): a ``BatchJob`` epoch
streams rows through the route at ``best_effort`` while the interactive
trace runs open-loop — first a trough (base interactive rate; the job
borrows the idle chip via ``scale_up`` and widens its window), then a
spike (6x interactive rate, longer streams; depth crosses
``borrow_depth_high`` and the loan is preempted back through the
lease-notice drain).  The phase gets a FRESH runtime and watch: the job
bills the cost ledger as tenant ``batch:<job_id>``, which would dilute
the main run's pinned ``cost.tenants.default.token_share = 1.0``.
Headline: ``rows_s_per_chip`` — epoch rows per ledger-accounted engine
chip-second (attributed + idle), so holding a borrowed chip without
converting it to rows costs the number.

Reported per phase and class: arrivals, completed, shed (proxy 503s and
engine-side overload look identical to the client), proxy-side
queued/shed counter deltas, TTFT p50/p99 both CLIENT-observed (includes
bench-harness noise — hundreds of client threads share this process's
GIL) and ENGINE-recorded (submit → first token inside the serving plane;
the headline ratio reads this one), plus phase tokens/s.

The whole run executes with airwatch installed (observability/watch.py):
the driver-side FleetScraper rides along exactly as it would in
production, and its per-tenant cost ledger yields the ``cost`` section —
``chip_seconds_per_1k_tokens`` (attributed busy chip-seconds per 1k
tokens, the $/token proxy) and the per-tenant token split.  Bench
traffic carries no ``adapter_id``, so every token must land on the
``default`` tenant (``cost.tenants.default.token_share`` pins 1.0).

Honest CPU caveat: on XLA:CPU a decode step costs ~2-3 ms dispatch, so
absolute TTFTs here are noise-dominated; what transfers to TPU is the
SHAPE — shed ordering (best_effort first, interactive never) and the
interactive TTFT ratio between the two phases.

Prints one JSON object; ``--out`` also writes it (the committed
``BENCH_serve.json``).  Run: ``JAX_PLATFORMS=cpu python tools/bench_serve.py``.
"""

from __future__ import annotations

import argparse
import json
import os
import random
import sys
import threading
import time
import urllib.error
import urllib.request

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

PORT = 8219
#: background arrivals split batch / best_effort
BACKGROUND_MIX = (("batch", 0.6), ("best_effort", 0.4))


def _pctl(xs, q):
    xs = sorted(xs)
    if not xs:
        return 0.0
    return xs[min(len(xs) - 1, int(len(xs) * q))]


def _post(path, payload, headers=None, timeout=60.0):
    """POST JSON; returns (status, body_dict, response_headers)."""
    req = urllib.request.Request(
        f"http://127.0.0.1:{PORT}{path}",
        data=json.dumps(payload).encode(),
        headers={"Content-Type": "application/json", **(headers or {})},
        method="POST",
    )
    try:
        with urllib.request.urlopen(req, timeout=timeout) as resp:
            return resp.status, json.loads(resp.read()), dict(resp.headers)
    except urllib.error.HTTPError as e:
        return e.code, json.loads(e.read()), dict(e.headers)


class _Client:
    """One open-loop arrival: streaming submit + pinned polls to done,
    over ONE persistent HTTP/1.1 connection (the proxy is thread-per-
    connection — keep-alive means one proxy thread per client for its
    whole stream instead of one per poll).

    Interactive clients poll tight (latency is their SLO); background
    clients poll lazily — which also keeps a backlog of batch streams from
    saturating the replica's serial message loop with poll RPCs and
    queueing interactive traffic behind them."""

    def __init__(self, prompt, priority, max_new):
        self.prompt = prompt
        self.priority = priority
        self.max_new = max_new
        self.poll_s = 0.005 if priority == "interactive" else 0.08
        self.outcome = None       # "ok" | "shed" | "error"
        self.ttft_s = None        # submit sent -> first token observed
        self.tokens = 0
        self.thread = threading.Thread(target=self._run, daemon=True)

    def _post(self, payload, headers=None):
        """POST on the persistent connection; reopens once on a stale
        keep-alive socket.  Returns (status, body_dict, resp_headers)."""
        import http.client

        body = json.dumps(payload).encode()
        hdrs = {"Content-Type": "application/json", **(headers or {})}
        for attempt in (0, 1):
            if self._conn is None:
                import socket

                self._conn = http.client.HTTPConnection(
                    "127.0.0.1", PORT, timeout=60.0)
                self._conn.connect()
                # Nagle off: tiny pipelined polls must not wait out the
                # server's delayed ACK on the reused socket
                self._conn.sock.setsockopt(
                    socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            try:
                self._conn.request("POST", "/engine", body=body,
                                   headers=hdrs)
                resp = self._conn.getresponse()
                data = json.loads(resp.read())
                return resp.status, data, dict(resp.headers)
            except Exception:  # noqa: BLE001 — stale keep-alive socket: reopen once
                try:
                    self._conn.close()
                finally:
                    self._conn = None
                if attempt:
                    raise
        raise RuntimeError("unreachable")

    def _run(self):
        self._conn = None
        t0 = time.monotonic()
        try:
            try:
                status, out, hdrs = self._post({
                    "action": "submit", "prompt": self.prompt,
                    "max_new_tokens": self.max_new,
                    "priority": self.priority,
                })
            except Exception:  # noqa: BLE001 — transport failure = client error
                self.outcome = "error"
                return
            if status == 503:
                self.outcome = "shed"
                return
            if status != 200:
                self.outcome = "error"
                return
            rid = out["request_id"]
            pin = {"x-tpu-air-replica": hdrs.get("x-tpu-air-replica", "")}
            cursor = 0
            deadline = time.monotonic() + 120.0
            while time.monotonic() < deadline:
                try:
                    status, out, _ = self._post({
                        "action": "poll", "request_id": rid,
                        "cursor": cursor,
                    }, headers=pin)
                except Exception:  # noqa: BLE001 — transient poll failure: retry
                    time.sleep(0.01)
                    continue
                if status != 200:
                    self.outcome = "error"
                    return
                got = out.get("tokens") or []
                if got and self.ttft_s is None:
                    self.ttft_s = time.monotonic() - t0
                cursor += len(got)
                if out.get("done"):
                    self.tokens = cursor
                    self.outcome = "ok"
                    return
                time.sleep(self.poll_s)
            self.outcome = "error"  # poll deadline
        finally:
            if self._conn is not None:
                try:
                    self._conn.close()
                except Exception:  # noqa: BLE001 — socket teardown is best-effort
                    pass


def _scrape_admission():
    """The proxy's cumulative per-class admission counters."""
    try:
        status, stats, _ = _post("/-/stats", {})
    except Exception:  # noqa: BLE001 — stats scrape is best-effort
        return {}
    if status != 200 or "/engine" not in stats:
        return {}
    adm = stats["/engine"]["admission"]
    return {k: dict(adm.get(k) or {}) for k in ("admitted", "queued", "shed")}


def _counter_delta(after, before):
    return {
        k: {p: after.get(k, {}).get(p, 0) - before.get(k, {}).get(p, 0)
            for p in after.get(k, {})}
        for k in after
    }


def _run_phase(interactive_rps, background_rps, duration_s, prompts,
               max_new, rng):
    """One open-loop phase: merged Poisson arrivals (interactive at a
    FIXED rate + background at the phase's rate) for ``duration_s``."""
    before = _scrape_admission()
    clients = []
    total_rate = interactive_rps + background_rps
    t_start = time.monotonic()
    t_end = t_start + duration_s
    i = 0
    while time.monotonic() < t_end:
        # merged process: this arrival is interactive with probability
        # rate_i / rate_total, else a background class from the fixed mix
        if rng.random() < interactive_rps / total_rate:
            priority = "interactive"
        else:
            r, acc = rng.random(), 0.0
            priority = BACKGROUND_MIX[-1][0]
            for klass, share in BACKGROUND_MIX:
                acc += share
                if r < acc:
                    priority = klass
                    break
        c = _Client(prompts[i % len(prompts)], priority, max_new)
        clients.append(c)
        c.thread.start()
        i += 1
        # open loop: the NEXT arrival time does not depend on service
        time.sleep(rng.expovariate(total_rate))
    for c in clients:
        c.thread.join(timeout=180.0)
    wall = time.monotonic() - t_start

    # engine-recorded per-class TTFT (submit -> first token INSIDE the
    # serving plane): free of bench-harness noise — a few hundred client
    # threads sharing this process's GIL put tens-of-ms outliers into the
    # client-observed tail that no server ever saw.  The deployment is
    # fresh per phase, so the gauge window holds only this phase's samples.
    engine_ttft = {}
    from tpu_air.engine.metrics import merge_snapshots
    from tpu_air.serve.proxy import replica_engine_stats

    replica_snaps = replica_engine_stats()
    # fleet-merged view: per-class TTFT quantiles from the MERGED histogram
    # buckets (mergeable across replicas — not a max-of-p99s), and the perf
    # ledger's roofline/goodput totals summed over replicas
    fleet = merge_snapshots(replica_snaps) if replica_snaps else {}
    for klass, pr in (fleet.get("priority") or {}).items():
        d = pr.get("ttft_s") or {}
        if d.get("count"):
            engine_ttft[klass] = {"p50": d["p50"], "p99": d["p99"],
                                  "count": d["count"]}
    perf = fleet.get("perf") or {}

    by_class = {}
    for klass in ("interactive", "batch", "best_effort"):
        mine = [c for c in clients if c.priority == klass]
        ttfts = [c.ttft_s for c in mine if c.ttft_s is not None]
        by_class[klass] = {
            "arrivals": len(mine),
            "completed": sum(1 for c in mine if c.outcome == "ok"),
            "shed": sum(1 for c in mine if c.outcome == "shed"),
            "errors": sum(1 for c in mine if c.outcome == "error"),
            "client_ttft_s_p50": round(_pctl(ttfts, 0.50), 4),
            "client_ttft_s_p99": round(_pctl(ttfts, 0.99), 4),
            "engine_ttft_s": engine_ttft.get(klass),
        }
    total_tokens = sum(c.tokens for c in clients)
    return {
        "interactive_rps": interactive_rps,
        "background_rps": background_rps,
        "arrivals": len(clients),
        "wall_s": round(wall, 3),
        "tokens_per_s": round(total_tokens / wall, 2) if wall else 0.0,
        # None off-chip: a roofline share is a device number
        "roofline_fraction": (perf.get("totals") or {}).get(
            "roofline_fraction"),
        "goodput_ratio": round(
            (perf.get("goodput") or {}).get("goodput_ratio", 0.0), 4),
        "classes": by_class,
        "proxy_counters_delta": _counter_delta(_scrape_admission(), before),
    }


def _tiny_lm_checkpoint():
    """Runs in a pooled worker: seeded LMConfig.tiny weights as a
    directory checkpoint, and the platform a worker without a chip lease
    (as the replicas of the rate phases are) computes on."""
    import jax
    import jax.numpy as jnp

    from tpu_air.models.lm import CausalLM, LMConfig
    from tpu_air.train import Checkpoint

    cfg = LMConfig.tiny()
    params = CausalLM(cfg).init(
        jax.random.PRNGKey(0), jnp.ones((1, 8), jnp.int32))["params"]
    return (Checkpoint.from_model(model_config=cfg, params=params),
            jax.default_backend())


def _publish_weights(ckpt, store_root, probe_prompts):
    """Runs in a pooled worker: publish the checkpoint's own weights to the
    weight store with a canary probe pinned under them."""
    from tpu_air.serve import WeightStore
    from tpu_air.serve.weights import compute_probe

    model, params = ckpt.get_model()
    return WeightStore(store_root).publish(
        params, metadata={"bench": True},
        probe=compute_probe(model, params, probe_prompts, max_new=4))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--duration", type=float, default=8.0,
                    help="seconds per rate phase")
    ap.add_argument("--interactive-rps", type=float, default=4.0,
                    help="interactive arrival rate, SAME in both phases")
    ap.add_argument("--underload-rps", type=float, default=2.5,
                    help="background (batch+best_effort) rate, underload")
    ap.add_argument("--overload-rps", type=float, default=70.0,
                    help="background rate, overload")
    ap.add_argument("--max-new", type=int, default=12)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--out", type=str, default=None)
    args = ap.parse_args()

    import numpy as np

    import tpu_air
    from tpu_air import serve
    from tpu_air.engine import EngineConfig
    from tpu_air.observability import tracing
    from tpu_air.observability import watch as watch_mod
    from tpu_air.serve import AdmissionPolicy, EngineDeployment

    # This process starts chip-leased replicas, so it computes nothing with
    # JAX itself (a driver that starts a backend holds the chips): the
    # checkpoint and the published weights are made in pooled workers.
    tpu_air.init(num_cpus=4, num_chips=8)
    ckpt, platform = tpu_air.get(
        tpu_air.remote(_tiny_lm_checkpoint).remote())

    rng = random.Random(args.seed)
    np_rng = np.random.RandomState(args.seed)
    prompts = [list(map(int, np_rng.randint(1, 384, size=np_rng.randint(4, 12))))
               for _ in range(16)]

    engine_cfg = EngineConfig(
        num_slots=4, slot_len=64, max_new_tokens=args.max_new, max_queue=16,
        reserved_interactive_slots=2,
    )
    # thresholds sized to the tiny engine: best_effort queues at 2 queued
    # per replica and sheds at 6; batch queues at 6, sheds at 12
    policy = AdmissionPolicy(queue_soft=2.0, queue_high=6.0, queue_hard=12.0)

    tracing.enable()
    # airwatch rides along for the whole run: serve.run starts the
    # FleetScraper against each phase's deployment, and the cost ledger
    # accumulates per-tenant attribution across phases (counter resets at
    # phase boundaries re-baseline without attributing negative deltas)
    fleet_watch = watch_mod.install(watch_mod.WatchConfig(
        interval_s=0.5, seed=args.seed))
    result = {
        "bench": "serve_slo_open_loop",
        "config": {
            "model": "LMConfig.tiny",
            "phase_duration_s": args.duration,
            "interactive_rps": args.interactive_rps,
            "background_mix": {k: v for k, v in BACKGROUND_MIX},
            "max_new_tokens": args.max_new,
            "num_slots": engine_cfg.num_slots,
            "reserved_interactive_slots":
                engine_cfg.reserved_interactive_slots,
            "max_queue": engine_cfg.max_queue,
            "admission": {"queue_soft": policy.queue_soft,
                          "queue_high": policy.queue_high,
                          "queue_hard": policy.queue_hard},
            "platform": platform,
        },
    }
    try:
        for name, bg_rate in (("underload", args.underload_rps),
                              ("overload", args.overload_rps)):
            # fresh deployment per phase: the engine's rolling TTFT gauge
            # window then holds exactly this phase's samples (serve.run on
            # the same route retires the previous replicas)
            serve.run(
                EngineDeployment.options(
                    name="bench-engine", route_prefix="/engine"
                ).bind(ckpt, engine_cfg),
                port=PORT,
                admission_policy=policy,
            )
            # warm-up: compile the prefill/decode programs OUTSIDE the
            # timed window (one full blocking generate through the proxy;
            # the XLA cache makes the second phase's warm-up instant).
            # Tagged batch so its compile-inclusive TTFT sample stays OUT
            # of the interactive gauge the headline ratio reads.
            _post("/engine", {"prompt": prompts[0], "priority": "batch",
                              "max_new_tokens": args.max_new}, timeout=300.0)
            result[name] = _run_phase(args.interactive_rps, bg_rate,
                                      args.duration, prompts, args.max_new,
                                      rng)

        # -- swap phase: live hot-swap under streaming load ---------------
        import tempfile

        from tpu_air.engine.metrics import merge_snapshots
        from tpu_air.serve import WeightsController, WeightStore
        from tpu_air.serve.proxy import replica_engine_stats

        h = serve.run(
            EngineDeployment.options(
                name="bench-engine", route_prefix="/engine"
            ).bind(ckpt, engine_cfg),
            port=PORT,
            admission_policy=policy,
        )
        _post("/engine", {"prompt": prompts[0], "priority": "batch",
                          "max_new_tokens": args.max_new}, timeout=300.0)
        store = WeightStore(tempfile.mkdtemp(prefix="bench-wstore-"))
        tpu_air.get(tpu_air.remote(_publish_weights).remote(
            ckpt, store.root, prompts[:2]))
        ctl = WeightsController(h, store.root, probe_prompts=prompts[:2],
                                probe_max_new=4, soak_s=0.3)
        promote_out = {}

        def _promote():
            # fire mid-phase so the swap lands under live decode traffic
            time.sleep(args.duration / 3.0)
            promote_out.update(ctl.promote())

        th = threading.Thread(target=_promote, daemon=True)
        th.start()
        result["swap"] = _run_phase(args.interactive_rps,
                                    args.underload_rps, args.duration,
                                    prompts, args.max_new, rng)
        th.join(timeout=120.0)
        merged_w = (merge_snapshots(replica_engine_stats())
                    if replica_engine_stats() else {}).get("weights") or {}
        result["swap"]["promote"] = promote_out
        result["swap_stall_ms"] = round(
            float(merged_w.get("max_stall_ms", 0.0)), 3)
        result["swap_errors_total"] = sum(
            c["errors"] for c in result["swap"]["classes"].values())

        # -- preemption phase: lease-notice revocation under live load ----
        from tpu_air import faults
        from tpu_air.faults import FaultPlan, FaultSpec
        from tpu_air.serve.proxy import serve_control_stats

        # fresh runtime: earlier phases rotated the chip pool, and the
        # fault spec targets the replica whose lease key is "chips=1" —
        # a clean pool makes the two replicas land on chips 0 and 1
        serve.shutdown()
        tpu_air.shutdown()
        tpu_air.init(num_cpus=4, num_chips=8)
        # delay_s counts from the replica's lease ATTACH (deploy time).
        # Warmup compiles BOTH replicas in parallel (below) and costs a
        # few seconds of fresh-process XLA compile, so a full duration of
        # delay lands the notice a few seconds INTO the arrival window —
        # while the doomed replica has streams decoding (live KV to
        # migrate).  delay_s = duration/2 used to race the compile: a slow
        # warmup let the notice fire before any traffic, and the phase
        # measured a drain of nothing (migrations=0, recovery ~1ms).
        plan = FaultPlan(seed=args.seed, specs=[
            FaultSpec("runtime.lease", "notice", at=1, match="chips=1",
                      delay_s=args.duration, notice_s=60.0)])
        # max_restarts=0: this phase measures the DRAIN + MIGRATE cost, not
        # replacement-spawn cost — and a respawn would re-lease the revoked
        # chip (lowest free id) in a fresh process whose per-process fault
        # counter re-fires the seeded notice, turning the phase into a
        # preemption loop.  Long streams (max_new 320, slot_len 336): on
        # CPU a decode step costs ~2-3 ms, so a 12-token stream lives
        # ~40 ms and even an 80-token one ~0.25 s — at these arrival
        # rates the notice instant would catch a live slot on the doomed
        # replica only by luck.  ~320-token streams live ~1 s, which
        # keeps expected occupancy ≥1 slot per replica so the drain has
        # live KV state to move.  Half background rate: the survivor must
        # stay shallow-queued after capacity halves — queued
        # (not-yet-decoding) streams can only be rescued by replay, and a
        # deep post-kill queue admission-sheds best_effort replays,
        # polluting the migrate-vs-replay signal.
        preempt_max_new = max(args.max_new, 320)
        preempt_cfg = EngineConfig(
            num_slots=engine_cfg.num_slots, slot_len=336,
            max_new_tokens=preempt_max_new, max_queue=engine_cfg.max_queue,
            reserved_interactive_slots=engine_cfg.reserved_interactive_slots,
        )
        serve.run(
            EngineDeployment.options(
                name="bench-engine", route_prefix="/engine",
                num_replicas=2, num_chips=1, max_restarts=0,
            ).bind(ckpt, preempt_cfg),
            port=PORT,
            admission_policy=policy,
            fault_plan=plan,
        )
        # warm up BOTH replicas in parallel (replicas are separate worker
        # processes — each compiles its own prefill/decode programs for
        # the preempt shapes).  The handle round-robins idle replicas and
        # counts its own in-flight calls, so two concurrent blocking
        # generates land on different replicas; serially they would
        # compile back-to-back and push the phase past the lease notice.
        warm_threads = [
            threading.Thread(
                target=_post,
                args=("/engine", {"prompt": prompts[0], "priority": "batch",
                                  "max_new_tokens": preempt_max_new}),
                kwargs={"timeout": 300.0}, daemon=True)
            for _ in range(2)]
        t_warm = time.monotonic()
        warm_threads[0].start()
        time.sleep(0.2)
        warm_threads[1].start()
        for th_w in warm_threads:
            th_w.join(timeout=300.0)
        warmup_s = round(time.monotonic() - t_warm, 3)
        result["preemption"] = _run_phase(args.interactive_rps,
                                          args.underload_rps / 2.0,
                                          args.duration,
                                          prompts, preempt_max_new, rng)
        rec = serve_control_stats().get("recovery") or {}
        # warmup wall vs the notice delay: the notice fires delay_s after
        # lease attach, so (delay_s - warmup_s) is how far INTO the
        # arrival window it landed — diagnostic for a run where the drain
        # caught nothing live
        result["preemption"]["warmup_s"] = warmup_s
        result["preemption"]["recovery"] = {
            k: rec.get(k) for k in (
                "preemptions", "migrations", "migrated_pages",
                "migration_fallbacks", "replays", "replay_failures",
                "preemption_recovery_ms")}
        result["preemption_recovery_ms"] = round(
            float(rec.get("preemption_recovery_ms") or 0.0), 3)
        rescued = int(rec.get("migrations") or 0) + int(rec.get("replays") or 0)
        result["migrated_vs_replayed"] = round(
            int(rec.get("migrations") or 0) / rescued, 3) if rescued else 0.0
        result["preemption_errors_total"] = sum(
            c["errors"] for c in result["preemption"]["classes"].values())

        under = result["underload"]["classes"]["interactive"]
        over = result["overload"]["classes"]["interactive"]
        # the headline: engine-recorded interactive p99 TTFT under
        # background overload vs the underload baseline (CPU noise floor
        # keeps a 3ms-vs-1ms blip from reading as 3x); the client-observed
        # ratio rides along for the harness-inclusive view
        floor = 0.05
        u99 = (under.get("engine_ttft_s") or {}).get(
            "p99", under["client_ttft_s_p99"])
        o99 = (over.get("engine_ttft_s") or {}).get(
            "p99", over["client_ttft_s_p99"])
        result["interactive_p99_ratio"] = round(
            max(o99, floor) / max(u99, floor), 3)
        result["interactive_client_p99_ratio"] = round(
            max(over["client_ttft_s_p99"], floor)
            / max(under["client_ttft_s_p99"], floor), 3)
        result["overload_shed_total"] = sum(
            c["shed"] for c in result["overload"]["classes"].values())
        result["interactive_shed_total"] = (
            result["underload"]["classes"]["interactive"]["shed"]
            + over["shed"])

        # -- airwatch cost attribution over the whole run -----------------
        # one final synchronous scrape closes the last attribution
        # interval, then the ledger's fleet headline becomes the bench's
        # $/token proxy: attributed busy chip-seconds per 1k tokens
        fleet_watch.scrape_once()
        led = fleet_watch.ledger.snapshot()
        head = led.get("headline") or {}
        result["cost"] = {
            "chip_seconds_per_1k_tokens": round(
                float(head.get("chip_seconds_per_1k_tokens", 0.0)), 4),
            "chip_seconds_attributed": round(
                float(head.get("chip_seconds_attributed", 0.0)), 3),
            "idle_chip_seconds": round(
                float(led.get("idle_chip_seconds", 0.0)), 3),
            "tokens_total": round(float(head.get("tokens_total", 0.0)), 1),
            "intervals": int(led.get("intervals", 0)),
            "watch_scrapes": int(fleet_watch.scrapes),
            "watch_anomalies": len(fleet_watch.events(kind="watch.anomaly")),
            "tenants": {
                name: {
                    "tokens_total": round(
                        float(t.get("tokens_total", 0.0)), 1),
                    "token_share": round(float(t.get("token_share", 0.0)), 4),
                    "chip_seconds": round(
                        float(t.get("chip_seconds", 0.0)), 3),
                    "chip_seconds_per_1k_tokens": round(
                        float(t.get("chip_seconds_per_1k_tokens", 0.0)), 4),
                }
                for name, t in (led.get("tenants") or {}).items()
            },
        }

        # -- batch phase: offline epoch with borrowing, trough + spike ----
        from tpu_air.batch import BatchJob, BatchJobConfig
        from tpu_air.data import from_items

        # fresh runtime AND a fresh watch: the job bills the ledger as
        # tenant batch:<job_id>, which would dilute the pinned
        # cost.tenants.default.token_share = 1.0 headline above — the
        # lane gets its own ledger and a clean chip pool
        serve.shutdown()
        tpu_air.shutdown()
        watch_mod.clear()
        tpu_air.init(num_cpus=4, num_chips=8)
        batch_watch = watch_mod.install(watch_mod.WatchConfig(
            interval_s=0.5, seed=args.seed))
        serve.run(
            EngineDeployment.options(
                name="bench-engine", route_prefix="/engine",
                num_replicas=1, num_chips=1,
            ).bind(ckpt, engine_cfg),
            port=PORT,
            admission_policy=policy,
        )
        # warm the replica's prefill buckets across the prompt-length
        # range — a fresh process recompiles per bucket, and a multi-
        # second compile stall under the spike reads as interactive shed
        for wp in (prompts[0], min(prompts, key=len), max(prompts, key=len)):
            _post("/engine", {"prompt": wp, "priority": "batch",
                              "max_new_tokens": args.max_new}, timeout=300.0)

        n_rows = max(48, int(round(args.duration * 25)))
        ds = from_items([{"prompt": prompts[i % len(prompts)]}
                         for i in range(n_rows)], parallelism=4)
        # thresholds sized to the tiny engine: the job's own queued rows
        # sit ~2 deep (window 4, two non-reserved slots), under the
        # borrow gate in the trough; the spike's longer interactive
        # streams queue past borrow_depth_high and preempt the loan back
        job = BatchJob(ds, job_id="bench-epoch", config=BatchJobConfig(
            route_prefix="/engine", max_new_tokens=args.max_new,
            priority="best_effort", num_shards=2, seed=args.seed,
            chunk_rows=8, window=4, borrow=True,
            borrow_depth_low=2.5, borrow_depth_high=3.0,
            borrow_notice_s=5.0))
        job_out = {}

        def _epoch():
            job_out.update(job.run())

        jth = threading.Thread(target=_epoch, daemon=True)
        t_batch = time.monotonic()
        jth.start()
        result["batch_trough"] = _run_phase(
            args.interactive_rps, 0.0, args.duration / 2.0,
            prompts, args.max_new, rng)
        result["batch_spike"] = _run_phase(
            args.interactive_rps * 6.0, 0.0, args.duration / 2.0,
            prompts, max(args.max_new, 32), rng)
        jth.join(timeout=600.0)
        batch_wall = round(time.monotonic() - t_batch, 3)

        # one synchronous scrape closes the last attribution interval;
        # the denominator is TOTAL engine chip-time the lane's ledger saw
        # (attributed + idle) — the borrowed replica counts only while
        # the loan is held
        batch_watch.scrape_once()
        bled = batch_watch.ledger.snapshot()
        bhead = bled.get("headline") or {}
        chip_s = (float(bhead.get("chip_seconds_attributed", 0.0))
                  + float(bled.get("idle_chip_seconds", 0.0)))
        if chip_s <= 0.0:
            chip_s = batch_wall  # ledger empty (scraper raced shutdown)
        rows_done = int(job_out.get("rows_done") or 0)
        result["batch"] = {
            "wall_s_epoch": batch_wall,
            "chip_seconds": round(chip_s, 3),
            "job": {k: job_out.get(k) for k in (
                "state", "rows_total", "rows_done", "rows_per_s",
                "chunks_done", "checkpoints", "borrows", "borrow_returns",
                "borrowed_replicas", "shed_retries", "submit_retries")},
            "cost": {
                "batch_chip_seconds": round(
                    float(bhead.get("batch_chip_seconds", 0.0)), 3),
                "interactive_chip_seconds": round(
                    float(bhead.get("interactive_chip_seconds", 0.0)), 3),
                "batch_chip_share": round(
                    float(bhead.get("batch_chip_share", 0.0)), 4),
            },
        }
        result["rows_s_per_chip"] = round(rows_done / chip_s, 3) \
            if chip_s else 0.0
        result["batch_errors_total"] = sum(
            c["errors"]
            for ph in ("batch_trough", "batch_spike")
            for c in result[ph]["classes"].values())
    finally:
        serve.shutdown()
        tpu_air.shutdown()
        watch_mod.clear()
        from tpu_air import faults as _faults

        _faults.clear()

    blob = json.dumps(result, indent=1)
    print(blob)
    if args.out:
        with open(args.out, "w") as f:
            f.write(blob + "\n")


if __name__ == "__main__":
    main()
